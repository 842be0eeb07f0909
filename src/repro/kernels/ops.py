"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend the kernels run compiled (Mosaic).  On any other
backend they run in the Pallas interpreter, which is how the CPU test
suite checks them against ``ref.py``; ``tests/test_tpu_compile.py``
compiles the main-path kernels for a described TPU v5e without one.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import feature_resample as _fr
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topk_gating as _tk


def default_interpret() -> bool:
    """One backend gate for every kernel: compiled on TPU, Pallas
    interpreter everywhere else (the interpreter is for CPU tests)."""
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, block_q=block_q,
                               block_k=block_k,
                               interpret=default_interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=default_interpret())


@partial(jax.jit, static_argnames=("k", "block_t"))
def topk_gating(logits, k: int, *, block_t: int = 1024):
    return _tk.topk_gating(logits, k, block_t=block_t,
                           interpret=default_interpret())


@jax.jit
def feature_resample(src, idx):
    return _fr.feature_resample(src, idx, interpret=default_interpret())


def resample_rows(src, idx):
    """Row gather ``out[i] = src[idx[i]]`` for ANY trailing shape via the
    ``feature_resample`` scalar-prefetch kernel (rows flattened to 2-D
    and restored).  This is the entry point ``FeatureStore``'s resample
    gather dispatches to on TPU (backend-gated like ``fused_adam``); it
    deliberately stays un-jitted so it inlines into the caller's trace
    and composes with GSPMD sharding of the pooled array."""
    flat = src.reshape((src.shape[0], -1))
    out = _fr.feature_resample(flat, idx, interpret=default_interpret())
    return out.reshape((idx.shape[0],) + src.shape[1:])


def gather_loss_microbatch(src, labels, idx, w, b=None):
    """Fused resample-gather + linear-head cross-entropy per-row losses
    via the ``gather_loss`` scalar-prefetch kernel (rows flattened to
    2-D like ``resample_rows``).  src [T, ...], labels [T] int, idx [M],
    w [prod(...), K] -> [M] float32.  Un-jitted for the same reason as
    ``resample_rows``: it inlines into the server inner loop's trace."""
    from repro.kernels import gather_loss as _gl
    flat = src.reshape((src.shape[0], -1))
    return _gl.gather_loss_microbatch(flat, labels, idx, w, b,
                                      interpret=default_interpret())


@jax.custom_vjp
def fused_gather_loss_mean(src, labels, idx, w):
    """Mean fused gather+loss over one microbatch, differentiable in the
    head weights ``w`` ONLY (the pooled features are stop_gradient'd by
    construction — paper Eq. 3 treats D_S^f as data).

    Forward streams the pool through the Pallas kernel (the gathered
    batch never materializes); backward is the analytic linear-head
    cross-entropy VJP — ``dw = fᵀ (softmax(logits) − onehot(y)) / M`` —
    recomputed in jnp (the re-gather is one [M, D] read, and M << T).
    """
    return jnp.mean(gather_loss_microbatch(src, labels, idx, w))


def _fglm_fwd(src, labels, idx, w):
    return fused_gather_loss_mean(src, labels, idx, w), (src, labels, idx, w)


def _fglm_bwd(res, g):
    import numpy as np
    src, labels, idx, w = res
    f = jnp.take(src.reshape((src.shape[0], -1)), idx,
                 axis=0).astype(jnp.float32)
    logits = f @ w.astype(jnp.float32)
    y = jnp.take(labels, idx, axis=0)
    p = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(y, w.shape[1], dtype=jnp.float32)
    dlogits = (p - onehot) * (g / idx.shape[0])
    dw = (f.T @ dlogits).astype(w.dtype)
    zero = lambda x: (np.zeros(x.shape, jax.dtypes.float0)
                      if jnp.issubdtype(x.dtype, jnp.integer)
                      else jnp.zeros_like(x))
    return zero(src), zero(labels), zero(idx), dw


fused_gather_loss_mean.defvjp(_fglm_fwd, _fglm_bwd)


@partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "weight_decay"))
def fused_adam(p, g, m, v, step, *, lr: float, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8,
               weight_decay: float = 0.0):
    from repro.kernels import fused_adam as _fa2
    return _fa2.fused_adam(p, g, m, v, step, lr=lr, b1=b1, b2=b2, eps=eps,
                           weight_decay=weight_decay,
                           interpret=default_interpret())
