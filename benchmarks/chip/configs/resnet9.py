"""Plain reference of ResNet9, for ``resnet9-*.json``.

Stages (the cut index counts them), widths w, 2w, 4w, 8w:

  0  conv 3x3, C -> w, bias, batch-norm, ReLU
  1  conv 3x3, w -> 2w, bias, batch-norm, ReLU, maxpool 2x2
  2  residual: x + ReLU(BN(conv(ReLU(BN(conv(x))))))  at 2w
  3  conv 3x3, 2w -> 4w, bias, batch-norm, ReLU, maxpool 2x2
  4  conv 3x3, 4w -> 8w, bias, batch-norm, ReLU, maxpool 2x2
  5  residual at 8w
  6  global max pool, dense 8w -> n_classes (no bias)

Batch-norm uses the statistics of the batch it is given (training
mode, eps 1e-5).  NHWC, SAME padding.  Parameters are a list of one
dict per stage, in the layout the program's ``StageModel`` uses.  The
rest of the configuration interface (split, loss, population, program
task) is ``stage_model.py``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from stage_model import (forwards, loss, population,  # noqa: F401
                         program_task, sample_spec, split, tiny)

N_STAGES = 7


def init(key, cfg: dict) -> list:
    w, c_in = cfg["width"], cfg["input_shape"][-1]
    keys = iter(jax.random.split(key, 9))

    def conv(cin, cout):
        k = next(keys)
        return {"w": jax.random.normal(k, (3, 3, cin, cout), jnp.float32)
                / jnp.sqrt(jnp.float32(9 * cin)),
                "b": jnp.zeros((cout,), jnp.float32)}

    def bn(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    def block(cin, cout):
        return {"conv": conv(cin, cout), "bn": bn(cout)}

    def res(c):
        return {"c1": conv(c, c), "b1": bn(c), "c2": conv(c, c), "b2": bn(c)}

    head = jax.random.normal(next(keys), (8 * w, cfg["n_classes"]),
                             jnp.float32) / jnp.sqrt(jnp.float32(8 * w))
    return [block(c_in, w), block(w, 2 * w), res(2 * w), block(2 * w, 4 * w),
            block(4 * w, 8 * w), res(8 * w), {"lin": {"w": head}}]


def _conv(p, x, precision):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    return y + p["b"].astype(x.dtype)


def _bn(p, x):
    mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply_range(params: list, x, lo: int, hi: int, precision) -> jax.Array:
    """Stages ``lo`` .. ``hi - 1`` on ``x``; ``params[i - lo]`` is stage i's."""
    for i in range(lo, hi):
        p = params[i - lo]
        if i in (2, 5):
            h = jax.nn.relu(_bn(p["b1"], _conv(p["c1"], x, precision)))
            x = x + jax.nn.relu(_bn(p["b2"], _conv(p["c2"], h, precision)))
        elif i == 6:
            x = jnp.dot(jnp.max(x, axis=(1, 2)), p["lin"]["w"].astype(x.dtype),
                        precision=precision)
        else:
            x = jax.nn.relu(_bn(p["bn"], _conv(p["conv"], x, precision)))
            if i != 0:
                x = _pool(x)
    return x


client_forward, server_forward = forwards(apply_range, N_STAGES)
