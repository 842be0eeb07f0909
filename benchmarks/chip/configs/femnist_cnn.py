"""Plain reference of the LEAF FEMNIST CNN, for ``femnist_cnn-*.json``.

Stages (the cut index counts them):

  0  conv 5x5, 1 -> w, bias, ReLU, maxpool 2x2
  1  conv 5x5, w -> 2w, bias, ReLU, maxpool 2x2
  2  flatten (7 * 7 * 2w), dense -> fc_dim, ReLU (no bias)
  3  dense fc_dim -> n_classes (no bias)

NHWC, SAME padding.  Parameters are a list of one dict per stage, in
the layout the program's ``StageModel`` uses, so that one set of
weights made by the benchmark feeds both.  Every contraction takes the
``precision`` and ``dtype`` it is given.  The rest of the configuration
interface (split, loss, population, program task) is ``stage_model.py``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from stage_model import (forwards, loss, population,  # noqa: F401
                         program_task, sample_spec, split, tiny)

N_STAGES = 4


def init(key, cfg: dict) -> list:
    w, k = cfg["width"], jax.random.split(key, 4)
    c_in = cfg["input_shape"][-1]

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
            jnp.float32(shape[0] if len(shape) == 2 else
                        shape[0] * shape[1] * shape[2]))

    return [
        {"conv": {"w": dense(k[0], (5, 5, c_in, w)),
                  "b": jnp.zeros((w,), jnp.float32)}},
        {"conv": {"w": dense(k[1], (5, 5, w, 2 * w)),
                  "b": jnp.zeros((2 * w,), jnp.float32)}},
        {"lin": {"w": dense(k[2], (7 * 7 * 2 * w, cfg["fc_dim"]))}},
        {"lin": {"w": dense(k[3], (cfg["fc_dim"], cfg["n_classes"]))}},
    ]


def _conv(p, x, precision):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    return y + p["b"].astype(x.dtype)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply_range(params: list, x, lo: int, hi: int, precision) -> jax.Array:
    """Stages ``lo`` .. ``hi - 1`` on ``x``; ``params[i - lo]`` is stage i's."""
    for i in range(lo, hi):
        p = params[i - lo]
        if i < 2:
            x = _pool(jax.nn.relu(_conv(p["conv"], x, precision)))
        elif i == 2:
            x = jax.nn.relu(jnp.dot(x.reshape(x.shape[0], -1),
                                    p["lin"]["w"].astype(x.dtype),
                                    precision=precision))
        else:
            x = jnp.dot(x, p["lin"]["w"].astype(x.dtype), precision=precision)
    return x


client_forward, server_forward = forwards(apply_range, N_STAGES)
