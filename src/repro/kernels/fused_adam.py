"""Fused Adam update — Pallas TPU kernel.

The optimizer update is memory-bound: a naive XLA lowering streams
param/grad/m/v through HBM several times across unfused elementwise
ops.  This kernel fuses the whole update (moment updates, bias
correction, parameter step) into one VMEM pass per tile: each operand
is read once and written once — the HBM-optimal schedule.

Operands are flattened and laid out as lane-dense ``[rows, 128]``
slabs tiled by ``(block // 128, 128)``.  The bias corrections
``1 - b1**t`` and ``1 - b2**t`` are computed outside the kernel and
enter as a small VMEM tile: Mosaic has no lowering for ``powf`` with a
traced exponent.  ``repro.optim.adam`` applies it leaf-wise over a pytree.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
TILE = 16 * LANES                 # one (16, 128) tile: whole 16-bit tiles


def _adam_kernel(bc_ref, p_ref, g_ref, m_ref, v_ref,
                 p_out, m_out, v_out, *,
                 lr: float, b1: float, b2: float, eps: float,
                 weight_decay: float):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mh = m / bc_ref[0:1, :]
    vh = v / bc_ref[1:2, :]
    upd = -lr * mh / (jnp.sqrt(vh) + eps)
    if weight_decay:
        upd = upd - lr * weight_decay * p
    p_out[...] = (p + upd).astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


def bias_corrections(step, b1: float, b2: float):
    """``1 - b1**t`` and ``1 - b2**t`` at ``t = step + 1``, as the two
    rows of a float32 ``[2, 128]`` tile (each broadcast over the lanes,
    so that a vmapped call batches it to a legal ``(2, 128)`` block)."""
    t = jnp.asarray(step, jnp.float32) + 1.0
    bc = jnp.stack([1.0 - b1 ** t, 1.0 - b2 ** t])
    return jnp.broadcast_to(bc[:, None], (2, LANES))


def fused_adam(p, g, m, v, step, *, lr: float, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8,
               weight_decay: float = 0.0, block: int = 65536,
               interpret: Optional[bool] = None):
    """One Adam step on flat arrays.  p/g any float dtype, m/v fp32,
    step scalar int32.  Returns (p', m', v').

    ``block`` is the number of elements per grid step, rounded up to a
    whole number of (16, 128) tiles and capped at the padded array size.
    ``interpret=None`` selects the mode from the backend (compiled on
    TPU, Pallas interpreter elsewhere) — the same gate
    ``repro.kernels.ops.default_interpret`` applies to every kernel.
    """
    if interpret is None:
        from repro.kernels.ops import default_interpret
        interpret = default_interpret()
    n = p.size
    rnd = lambda x, k: -(-x // k) * k
    block = min(rnd(block, TILE), rnd(n, TILE))
    total = rnd(n, block)

    def slab(x):
        x = x.reshape(-1)
        if total != n:
            x = jnp.pad(x, (0, total - n))
        return x.reshape(total // LANES, LANES)

    rows = block // LANES
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    kernel = functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
    p2, m2, v2 = pl.pallas_call(
        kernel,
        grid=(total // block,),
        in_specs=[pl.BlockSpec((2, LANES), lambda i: (0, 0)),
                  tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((total // LANES, LANES), p.dtype),
            jax.ShapeDtypeStruct((total // LANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((total // LANES, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="fused_adam",
    )(bias_corrections(step, b1, b2), slab(p), slab(g), slab(m), slab(v))
    unslab = lambda x, like: x.reshape(-1)[:n].reshape(like.shape)
    return unslab(p2, p), unslab(m2, m), unslab(v2, v)
