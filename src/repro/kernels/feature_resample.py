"""CycleSL feature-resampling gather — Pallas TPU kernel.

The server's resampled mini-batches (paper Eq. 3) are a permutation
row-gather over the pooled smashed-data array.  XLA lowers ad-hoc
gathers with index broadcasting; on TPU the idiom here is a
*scalar-prefetch* grid: the permutation indices sit in SMEM and the
BlockSpec index maps read them to stream source blocks from HBM into
VMEM, with no index arithmetic on the VPU.

The TPU tiles the last two dims of every 32-bit block by (8, 128), so
a block cannot be a single row.  Each grid step therefore writes one
whole ``(8, D)`` output tile and takes 8 inputs that are all views of
the SAME source array: input r streams the aligned 8-row block holding
source row ``idx[8*i + r]``, and the kernel picks that row out of it
with a dynamic sublane slice.  The cost is 8 source rows read per row
gathered.  Mosaic slices 32-bit rows only, so narrower dtypes travel
as 32-bit words (:func:`as_words`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R = 8                                   # rows of one 32-bit TPU tile


def _round_up(n: int, k: int) -> int:
    return -(-n // k) * k


def row_block_specs(T: int, D: int):
    """R BlockSpecs over one ``[T, D]`` source: spec r maps grid step i
    to the row block holding source row ``idx[R*i + r]`` (``idx`` is the
    first scalar-prefetch operand).  Blocks have ``B = min(R, T)`` rows
    (a block may equal the whole dim).  Returns ``(specs, B)``."""
    B = min(R, T)

    def spec(r):
        def index_map(i, idx_ref, *_):
            return (idx_ref[i * R + r] // B, 0)
        return pl.BlockSpec((B, D), index_map)

    return [spec(r) for r in range(R)], B


def pick_rows(idx_ref, blocks, B: int):
    """The R picked source rows, each ``[1, D]``: row ``idx[R*i + r] %
    B`` of block r."""
    base = pl.program_id(0) * R
    return [blk[pl.ds(idx_ref[base + r] % B, 1), :]
            for r, blk in enumerate(blocks)]


def pad_indices(idx):
    """int32 indices padded with row 0 to a whole number of R-row grid
    steps.  Returns ``(idx, Mp)``."""
    M = idx.shape[0]
    idx = idx.astype(jnp.int32)
    Mp = _round_up(max(M, 1), R)
    return (jnp.pad(idx, (0, Mp - M)) if Mp != M else idx), Mp


def as_words(src):
    """View ``[T, D]`` rows of a sub-32-bit dtype as ``[T, ceil(D/k)]``
    uint32 words (k = 4 // itemsize, D padded to a multiple of k).
    Returns ``(words, unpack)``; ``unpack`` maps gathered ``[M, ...]``
    words back to ``[M, D]`` of the source dtype."""
    dtype = src.dtype
    k = 4 // dtype.itemsize
    if k <= 1:
        return src, lambda w: w
    T, D = src.shape
    Dp = _round_up(D, k)
    if Dp != D:
        src = jnp.pad(src, ((0, 0), (0, Dp - D)))
    words = jax.lax.bitcast_convert_type(src.reshape(T, Dp // k, k),
                                         jnp.uint32)

    def unpack(w):
        out = jax.lax.bitcast_convert_type(w, dtype)
        return out.reshape(w.shape[0], Dp)[:, :D]

    return words, unpack


def feature_resample(src, idx, *, interpret: bool = True):
    """out[i] = src[idx[i]].  src [T, D], idx [M] int32 -> [M, D]."""
    M = idx.shape[0]
    src, unpack = as_words(src)
    T, D = src.shape
    idx, Mp = pad_indices(idx)
    in_specs, B = row_block_specs(T, D)

    def kernel(idx_ref, *refs):
        *blocks, out_ref = refs
        for r, row in enumerate(pick_rows(idx_ref, blocks, B)):
            out_ref[pl.ds(r, 1), :] = row

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // R,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((R, D), lambda i, idx_ref: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, D), src.dtype),
        interpret=interpret,
        name="feature_resample",
    )(idx, *([src] * R))
    return unpack(out[:M])
