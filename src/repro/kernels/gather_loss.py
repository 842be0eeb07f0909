"""CycleSL fused resample-gather + server-head loss — Pallas TPU kernel.

The server inner loop's hot path is gather-then-loss: resample a
minibatch of pooled rows (Eq. 3), push it through the server head, take
the cross-entropy.  Dispatched separately, the gathered [sb, D] batch
round-trips HBM between the two (the gather kernel writes it, the loss
matmul reads it back).  This kernel fuses them: the same scalar-prefetch
row-block grid as ``feature_resample`` picks 8 source rows per grid
step into a VMEM tile and feeds it straight into the head matmul +
log-softmax, so the gathered batch never materializes in HBM.

Head model: a flattened linear head ``logits = f @ w (+ b)`` with
integer cross-entropy labels — the StageModel zoo's final stage (the
paper's CNN/MLP heads are all bias-free flatten-matmuls; an optional
bias is supported for generality).  The per-row losses leave the kernel
as lane-dense ``(8, 128)`` tiles (each row's loss broadcast over the
lanes), since a ``(1, 1)`` block violates the TPU's (8, 128) tiling.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.feature_resample import (R, pad_indices, pick_rows,
                                            row_block_specs)

LANES = 128


def gather_loss_microbatch(src, labels, idx, w, b: Optional[jax.Array] = None,
                           *, interpret: bool = True):
    """Per-row fused gather + linear-head cross-entropy.

    ``out[i] = xent(src[idx[i]] @ w (+ b), labels[idx[i]])`` — src
    [T, D], labels [T] int, idx [M] int32, w [D, K], b [K] or None.
    Returns the per-row losses [M] float32 (the caller owns the
    microbatch mean).  The math is float32 whatever the input dtypes.
    """
    M = idx.shape[0]
    src = src.astype(jnp.float32)
    T, D = src.shape
    K = w.shape[1]
    b = (jnp.zeros((1, K), jnp.float32) if b is None
         else b.astype(jnp.float32).reshape(1, K))
    idx, Mp = pad_indices(idx)
    yv = jnp.take(labels, idx, axis=0).astype(jnp.int32).reshape(Mp, 1)
    row_specs, B = row_block_specs(T, D)

    def kernel(idx_ref, *refs):
        *blocks, y_ref, w_ref, b_ref, out_ref, f_ref = refs
        # assemble the R picked rows into one VMEM tile, then one head
        # matmul + stable log-softmax + label pick for all of them
        for r, row in enumerate(pick_rows(idx_ref, blocks, B)):
            f_ref[pl.ds(r, 1), :] = row
        logits = jnp.dot(f_ref[...], w_ref[...].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32) + b_ref[...]
        m = jnp.max(logits, axis=-1, keepdims=True)
        z = logits - m
        lse = jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
        # one-hot label pick — a vector select, not a dynamic gather
        onehot = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) == y_ref[...]
        loss = lse - jnp.sum(jnp.where(onehot, z, 0.0), axis=-1,
                             keepdims=True)                     # [R, 1]
        out_ref[...] = jnp.broadcast_to(loss, out_ref.shape)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // R,),
            in_specs=row_specs + [
                pl.BlockSpec((R, 1), lambda i, idx_ref: (i, 0)),
                pl.BlockSpec((D, K), lambda i, idx_ref: (0, 0)),
                pl.BlockSpec((1, K), lambda i, idx_ref: (0, 0)),
            ],
            out_specs=pl.BlockSpec((R, LANES), lambda i, idx_ref: (i, 0)),
            scratch_shapes=[pltpu.VMEM((R, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, LANES), jnp.float32),
        interpret=interpret,
        name="gather_loss",
    )(idx, *([src] * R), yv, w, b)
    return out[:M, 0]
