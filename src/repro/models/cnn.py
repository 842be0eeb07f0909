"""The paper's own model zoo: LEAF-style CNNs + ResNet9 + gaze MLP head.

These are the models CycleSL was benchmarked with (paper §4.1, App. H).
Each model is expressed as an ordered list of *stages*; the split-learning
cut index selects how many stages stay on the client — exactly the
paper's block-wise cut ablation (Table 4).

Conv layers use NHWC and ``lax.conv_general_dilated``; everything is
float32.  The same code runs on CPU (tests) and TPU (``chip_smoke.py``).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.models import module


# --------------------------------------------------------------- conv ops
def conv_init(key, kh: int, kw: int, cin: int, cout: int, dtype=jnp.float32):
    fan_in = kh * kw * cin
    w = jax.random.truncated_normal(key, -2, 2, (kh, kw, cin, cout), jnp.float32)
    return {"w": (w / jnp.sqrt(fan_in)).astype(dtype),
            "b": jnp.zeros((cout,), dtype)}


def conv2d(params, x, stride: int = 1, padding: str = "SAME"):
    y = jax.lax.conv_general_dilated(
        x, params["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + params["b"]


def maxpool(x, k: int = 2, s: int = 2):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, k, k, 1), (1, s, s, 1), "VALID")


def batchnorm_init(c: int):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def batchnorm(params, x, eps: float = 1e-5):
    # batch-stat norm (training mode); SL benchmarks always train
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]


# ------------------------------------------------------- stage-list models
class StageModel:
    """A model = ordered stages; stage i: (init_fn(key)->params, apply_fn).

    ``cut`` splits stages into client [0:cut] / server [cut:] — the
    paper's block-wise cut point.
    """

    def __init__(self, name: str, stages: Sequence[tuple[Callable, Callable]],
                 n_classes: int, head_is_linear: bool = False):
        self.name = name
        self.stages = list(stages)
        self.n_classes = n_classes
        self.n_stages = len(stages)
        # True iff the FINAL stage is a bias-free flatten-matmul
        # (``x.reshape(B, -1) @ w``): the contract that lets a last-cut
        # split expose the head to the fused gather+loss kernel
        # (SplitTask.server_head).  resnet9's head pools first, so it
        # does NOT qualify.
        self.head_is_linear = head_is_linear

    def init(self, key):
        keys = jax.random.split(key, self.n_stages)
        return [init(k) for (init, _), k in zip(self.stages, keys)]

    def apply_range(self, params, x, lo: int, hi: int):
        for i in range(lo, hi):
            x = self.stages[i][1](params[i], x)
        return x

    def apply(self, params, x):
        return self.apply_range(params, x, 0, self.n_stages)


# ------------------------------------------------------------ LEAF FEMNIST
def femnist_cnn(n_classes: int = 62, width: int = 32) -> StageModel:
    """LEAF FEMNIST CNN (paper Table 11).  Input [B, 28, 28, 1].
    Cut in the middle (stage 2 of 4) matches the paper's setup."""
    w = width

    def s0_init(k):
        return {"conv": conv_init(k, 5, 5, 1, w)}

    def s0(p, x):
        return maxpool(jax.nn.relu(conv2d(p["conv"], x)))

    def s1_init(k):
        return {"conv": conv_init(k, 5, 5, w, 2 * w)}

    def s1(p, x):
        return maxpool(jax.nn.relu(conv2d(p["conv"], x)))

    def s2_init(k):
        return {"lin": {"w": module.dense_init(k, 7 * 7 * 2 * w, 2048)}}

    def s2(p, x):
        x = x.reshape(x.shape[0], -1)
        return jax.nn.relu(x @ p["lin"]["w"])

    def s3_init(k):
        return {"lin": {"w": module.dense_init(k, 2048, n_classes)}}

    def s3(p, x):
        return x @ p["lin"]["w"]

    return StageModel("femnist_cnn", [(s0_init, s0), (s1_init, s1),
                                      (s2_init, s2), (s3_init, s3)], n_classes,
                      head_is_linear=True)


# ------------------------------------------------------------- LEAF CelebA
def celeba_cnn(n_classes: int = 2, width: int = 32, img: int = 84) -> StageModel:
    """LEAF CelebA CNN (paper Table 13): 4 conv-bn-pool stages + head.
    Input [B, img, img, 3]; cut after stage 1 (paper: middle)."""
    w = width

    def conv_stage_init(cin, cout):
        def init(k):
            return {"conv": conv_init(k, 3, 3, cin, cout),
                    "bn": batchnorm_init(cout)}
        return init

    def conv_stage(p, x):
        x = conv2d(p["conv"], x)
        x = batchnorm(p["bn"], x)
        return jax.nn.relu(maxpool(x))

    final_hw = img // 16

    def head_init(k):
        return {"lin": {"w": module.dense_init(k, final_hw * final_hw * w,
                                               n_classes)}}

    def head(p, x):
        return x.reshape(x.shape[0], -1) @ p["lin"]["w"]

    stages = [(conv_stage_init(3, w), conv_stage)]
    for _ in range(3):
        stages.append((conv_stage_init(w, w), conv_stage))
    stages.append((head_init, head))
    return StageModel("celeba_cnn", stages, n_classes,
                      head_is_linear=True)


# ----------------------------------------------------------------- ResNet9
def resnet9(n_classes: int = 100, width: int = 64, img: int = 32) -> StageModel:
    """ResNet9 (paper Table 4 ablation: 4 conv blocks, 2 residual blocks,
    1 head = 6 cut positions).  Input [B, img, img, 3]."""
    w = width

    def convblock_init(cin, cout):
        def init(k):
            return {"conv": conv_init(k, 3, 3, cin, cout),
                    "bn": batchnorm_init(cout)}
        return init

    def convblock(p, x, pool):
        x = jax.nn.relu(batchnorm(p["bn"], conv2d(p["conv"], x)))
        return maxpool(x) if pool else x

    def resblock_init(c):
        def init(k):
            k1, k2 = jax.random.split(k)
            return {"c1": conv_init(k1, 3, 3, c, c), "b1": batchnorm_init(c),
                    "c2": conv_init(k2, 3, 3, c, c), "b2": batchnorm_init(c)}
        return init

    def resblock(p, x):
        h = jax.nn.relu(batchnorm(p["b1"], conv2d(p["c1"], x)))
        h = jax.nn.relu(batchnorm(p["b2"], conv2d(p["c2"], h)))
        return x + h

    def head_init(k):
        return {"lin": {"w": module.dense_init(k, 8 * w, n_classes)}}

    def head(p, x):
        x = jnp.max(x, axis=(1, 2))         # global max pool
        return x @ p["lin"]["w"]

    stages = [
        (convblock_init(3, w), partial(_flip(convblock), False)),         # conv1
        (convblock_init(w, 2 * w), partial(_flip(convblock), True)),      # conv2
        (resblock_init(2 * w), resblock),                                 # res1
        (convblock_init(2 * w, 4 * w), partial(_flip(convblock), True)),  # conv3
        (convblock_init(4 * w, 8 * w), partial(_flip(convblock), True)),  # conv4
        (resblock_init(8 * w), resblock),                                 # res2
        (head_init, head),                                                # head
    ]
    return StageModel("resnet9", stages, n_classes)


def _flip(fn):
    """(p, x, flag) -> (flag, p, x) so partial can bind the static flag."""
    return lambda flag, p, x: fn(p, x, flag)


# -------------------------------------------------------------------- MLP
def mlp(d_in: int, hidden: Sequence[int], d_out: int) -> StageModel:
    """Generic MLP (gaze-estimator head analog / quick tasks)."""
    dims = [d_in] + list(hidden)

    def lin_init(a, b):
        def init(k):
            return {"w": module.dense_init(k, a, b)}
        return init

    def lin(act, p, x):
        y = x.reshape(x.shape[0], -1) @ p["w"]
        return jax.nn.relu(y) if act else y

    stages = [(lin_init(a, b), partial(lin, True))
              for a, b in zip(dims[:-1], dims[1:])]
    stages.append((lin_init(dims[-1], d_out), partial(lin, False)))
    return StageModel("mlp", stages, d_out, head_is_linear=True)
