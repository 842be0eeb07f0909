"""Decoder-only transformer assembly (dense / MoE / SSM / hybrid / VLM).

One module covers all decoder-only assigned archs; whisper's enc-dec
lives in ``encdec.py`` on top of the same block primitives.

Structure
---------
  embed -> [client blocks] -> CUT -> [server blocks] -> final_norm -> head

Blocks are stacked along a leading layer dim and executed with
``lax.scan`` over *groups* of ``period`` blocks (period=2 for gemma2's
local/global alternation, else 1), with ``jax.checkpoint`` on the group
body so backward memory is O(1) in depth.  The split-learning cut is a
leading-dim slice of the stacked block params, so client/server parts
reuse the exact same code path — this is what ``repro.core.split``
relies on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import ffn as ffn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import moe as moe_lib
from repro.models.attention import KVCache, kv_cache_init
from repro.models.layers import (embedding, embedding_init, rmsnorm,
                                 rmsnorm_init, softcap, unembed)
from repro.models.module import stacked_init
from repro.sharding.specs import constrain_batch
from repro.utils.tree import tree_slice

# numpy, not jnp: a jnp array made at import would initialize the JAX
# backend, and a process that has done that holds the accelerator
ZERO_METRICS = {"aux_loss": np.zeros((), np.float32),
                "z_loss": np.zeros((), np.float32)}


# ---------------------------------------------------------------- helpers
def block_kind(cfg: ArchConfig) -> str:
    if cfg.family == "ssm":
        return "mamba"
    if cfg.family == "hybrid":
        return "hybrid"
    if cfg.moe is not None:
        return "moe"
    return "dense"


def pattern_period(cfg: ArchConfig) -> int:
    return 2 if cfg.attn.pattern == "local_global" else 1


def _is_local(cfg: ArchConfig, slot: int) -> bool:
    """gemma2 convention: even layer indices are local (sliding window)."""
    return cfg.attn.pattern == "local_global" and slot % 2 == 0


# ------------------------------------------------------------- block init
def _dense_block_init(key, cfg: ArchConfig, dtype):
    ka, kf = jax.random.split(key)
    p = {
        "attn": attn_lib.attn_init(ka, cfg, dtype),
        "ffn": ffn_lib.swiglu_init(kf, cfg.d_model, cfg.d_ff, dtype),
        "norm_attn": rmsnorm_init(cfg.d_model, dtype),
        "norm_ffn": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.sandwich_norm:
        p["post_attn"] = rmsnorm_init(cfg.d_model, dtype)
        p["post_ffn"] = rmsnorm_init(cfg.d_model, dtype)
    return p


def _moe_block_init(key, cfg: ArchConfig, dtype):
    ka, km, ks = jax.random.split(key, 3)
    p = {
        "attn": attn_lib.attn_init(ka, cfg, dtype),
        "moe": moe_lib.moe_init(km, cfg.d_model, cfg.moe, dtype),
        "norm_attn": rmsnorm_init(cfg.d_model, dtype),
        "norm_ffn": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.moe.n_shared_experts:
        f = cfg.moe.n_shared_experts * cfg.moe.d_ff_expert
        p["shared_ffn"] = ffn_lib.swiglu_init(ks, cfg.d_model, f, dtype)
    return p


def _mamba_block_init(key, cfg: ArchConfig, dtype):
    km = jax.random.split(key, 2)[0]
    return {
        "mamba": mamba_lib.mamba_init(km, cfg, dtype),
        "norm": rmsnorm_init(cfg.d_model, dtype),
    }


def block_init(key, cfg: ArchConfig, dtype):
    kind = block_kind(cfg)
    if kind in ("mamba", "hybrid"):
        return _mamba_block_init(key, cfg, dtype)
    if kind == "moe":
        return _moe_block_init(key, cfg, dtype)
    return _dense_block_init(key, cfg, dtype)


# ---------------------------------------------------------- block forward
def dense_or_moe_block(params, cfg: ArchConfig, x, positions, window):
    """One attention block (full-seq).  Returns (x, metrics)."""
    h = rmsnorm(params["norm_attn"], x, cfg.norm_eps)
    a, _ = attn_lib.attend_full(params["attn"], cfg, h, positions, window)
    if cfg.sandwich_norm:
        a = rmsnorm(params["post_attn"], a, cfg.norm_eps)
    x = x + a
    h = rmsnorm(params["norm_ffn"], x, cfg.norm_eps)
    metrics = ZERO_METRICS
    if "moe" in params:
        f, m = moe_lib.moe_apply(params["moe"], cfg.moe, h,
                                 expert_spec=moe_lib.expert_partition_spec(cfg.moe))
        if "shared_ffn" in params:
            f = f + ffn_lib.swiglu(params["shared_ffn"], h)
        metrics = {"aux_loss": m["aux_loss"], "z_loss": m["z_loss"]}
    else:
        f = ffn_lib.swiglu(params["ffn"], h)
        if cfg.sandwich_norm:
            f = rmsnorm(params["post_ffn"], f, cfg.norm_eps)
    return x + f, metrics


def mamba_block(params, cfg: ArchConfig, x):
    h = rmsnorm(params["norm"], x, cfg.norm_eps)
    y, _ = mamba_lib.mamba_forward(params["mamba"], cfg, h)
    return x + y, ZERO_METRICS


# --------------------------------------------------------------- the model
class Transformer:
    """Namespace of pure functions for decoder-only models."""

    # ---------------- init ----------------
    @staticmethod
    def init(key, cfg: ArchConfig):
        dtype = cfg.jnp_dtype
        ke, kb, kh, ks = jax.random.split(key, 4)
        kind = block_kind(cfg)
        n = cfg.n_layers
        params = {
            "embed": embedding_init(ke, cfg.vocab_padded, cfg.d_model, dtype),
            "blocks": stacked_init(
                lambda k: block_init(k, cfg, dtype), kb, n),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {
                "w": jax.random.normal(kh, (cfg.d_model, cfg.vocab_padded),
                                       jnp.float32).astype(dtype) * 0.02}
        if kind == "hybrid":
            # one SHARED attention block (zamba2), reused at each position
            shared_cfg = cfg
            params["shared_attn"] = _dense_block_init(ks, shared_cfg, dtype)
        return params

    # -------------- stacks -----------------
    @staticmethod
    def _run_stack(blocks, cfg: ArchConfig, x, positions, *, layer_offset: int,
                   long_context: bool, shared_attn=None, n_blocks: int = None):
        """Scan over stacked block params.  Returns (x, metrics_sum)."""
        kind = block_kind(cfg)
        period = pattern_period(cfg)
        n = n_blocks if n_blocks is not None else \
            jax.tree.leaves(blocks)[0].shape[0]
        if n == 0:
            return x, ZERO_METRICS
        assert n % period == 0, f"stack of {n} not divisible by period {period}"
        grouped = jax.tree.map(
            lambda a: a.reshape((n // period, period) + a.shape[1:]), blocks)

        def group_body(carry, gparams):
            xs, acc = carry
            xs = constrain_batch(xs)    # keep batch on the data axes
            m_tot = acc
            for slot in range(period):
                bp = jax.tree.map(lambda a: a[slot], gparams)
                if kind in ("mamba", "hybrid"):
                    xs, m = mamba_block(bp, cfg, xs)
                else:
                    local = _is_local(cfg, (layer_offset + slot) % period
                                      if period > 1 else 0)
                    window = attn_lib.layer_window(cfg, local, long_context)
                    xs, m = dense_or_moe_block(bp, cfg, xs, positions, window)
                m_tot = {k: m_tot[k] + m[k] for k in m_tot}
            return (xs, m_tot), None

        body = jax.checkpoint(group_body)
        (x, metrics), _ = jax.lax.scan(body, (x, ZERO_METRICS), grouped)
        return x, metrics

    @staticmethod
    def _hybrid_stack(blocks, shared_attn, cfg: ArchConfig, x, positions, *,
                      first_block: int, n_blocks: int, long_context: bool):
        """Mamba blocks [first, first+n) with the shared attention block
        applied after every block index listed in cfg.ssm.shared_attn_positions."""
        pos_set = [p for p in cfg.ssm.shared_attn_positions
                   if first_block <= p < first_block + n_blocks]
        window = attn_lib.layer_window(cfg, False, long_context)
        metrics = ZERO_METRICS
        cursor = first_block
        segments = []
        for p in pos_set:
            segments.append((cursor, p + 1, True))
            cursor = p + 1
        if cursor < first_block + n_blocks:
            segments.append((cursor, first_block + n_blocks, False))
        for (a, b, with_attn) in segments:
            seg = tree_slice(blocks, a - first_block, b - first_block)
            x, m = Transformer._run_stack(seg, cfg, x, positions,
                                          layer_offset=a, long_context=long_context)
            metrics = {k: metrics[k] + m[k] for k in metrics}
            if with_attn:
                x, m = dense_or_moe_block(shared_attn, cfg, x, positions, window)
                metrics = {k: metrics[k] + m[k] for k in metrics}
        return x, metrics

    # -------------- forward -----------------
    @staticmethod
    def embed_inputs(params, cfg: ArchConfig, tokens, patch_embeds=None):
        x = embedding(params["embed"], tokens)
        if cfg.family == "vlm" and patch_embeds is not None:
            npt = patch_embeds.shape[1]
            x = jnp.concatenate([patch_embeds.astype(x.dtype), x[:, npt:]], axis=1)
        return constrain_batch(x * jnp.sqrt(float(cfg.d_model)).astype(x.dtype))

    @staticmethod
    def stack_forward(params, cfg: ArchConfig, x, positions, *,
                      first_block: int, n_blocks: int, long_context: bool = False):
        """Run blocks [first, first+n) of a (possibly sliced) stack."""
        if n_blocks == 0:
            return x, ZERO_METRICS
        if block_kind(cfg) == "hybrid":
            shared = params.get("shared_attn")
            if shared is None:
                # split-client stacks must not span a shared-attn position
                assert not any(first_block <= p < first_block + n_blocks
                               for p in cfg.ssm.shared_attn_positions), \
                    "client cut crosses a shared-attention position"
            return Transformer._hybrid_stack(
                params["blocks"], shared, cfg, x, positions,
                first_block=first_block, n_blocks=n_blocks,
                long_context=long_context)
        return Transformer._run_stack(
            params["blocks"], cfg, x, positions, layer_offset=first_block,
            long_context=long_context)

    @staticmethod
    def head(params, cfg: ArchConfig, x, keep_padded: bool = False):
        """Final norm + unembedding.  Returns fp32 logits [..., vocab]
        (padded columns sliced off unless ``keep_padded``)."""
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = unembed(params["embed"], x) if cfg.tie_embeddings \
            else x @ params["lm_head"]["w"]
        logits = softcap(logits.astype(jnp.float32), cfg.attn.final_softcap)
        if keep_padded or cfg.vocab_padded == cfg.vocab:
            return logits
        return logits[..., :cfg.vocab]

    @staticmethod
    def chunked_lm_loss(params, cfg: ArchConfig, hidden, labels,
                        chunk: int = 512):
        """Cross-entropy from final hidden states without materializing the
        [S, vocab] logits (perf iteration 4, EXPERIMENTS.md §Perf): the
        sequence is processed in checkpointed chunks, each computing a
        [chunk, vocab_padded] logits tile (vocab stays model-sharded).
        Padded vocab columns are masked to -inf.  Returns (mean nll,
        mean accuracy)."""
        B, S, d = hidden.shape
        chunk = min(chunk, S)
        if S % chunk:
            pad = chunk - S % chunk
            hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
            S += pad
        nc = S // chunk
        hs = jnp.moveaxis(hidden.reshape(B, nc, chunk, d), 1, 0)
        ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
        n_pad = cfg.vocab_padded - cfg.vocab

        @jax.checkpoint
        def one(args):
            h, l = args
            logits = Transformer.head(params, cfg, h, keep_padded=True)
            if n_pad:
                logits = logits.at[..., cfg.vocab:].set(-1e30)
            ll = jax.nn.log_softmax(logits, axis=-1)
            valid = (l >= 0).astype(jnp.float32)
            lc = jnp.clip(l, 0)
            nll = -jnp.take_along_axis(ll, lc[..., None], axis=-1)[..., 0]
            correct = (jnp.argmax(ll, -1) == lc).astype(jnp.float32)
            return (jnp.sum(nll * valid), jnp.sum(correct * valid),
                    jnp.sum(valid))

        nlls, corrects, counts = jax.lax.map(one, (hs, ls))
        n = jnp.maximum(jnp.sum(counts), 1.0)
        return jnp.sum(nlls) / n, jnp.sum(corrects) / n

    @staticmethod
    def forward(params, cfg: ArchConfig, tokens, patch_embeds=None,
                long_context: bool = False):
        """Full forward.  tokens [B,S] -> (logits fp32 [B,S,V], metrics)."""
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = Transformer.embed_inputs(params, cfg, tokens, patch_embeds)
        x, metrics = Transformer.stack_forward(
            params, cfg, x, positions, first_block=0, n_blocks=cfg.n_layers,
            long_context=long_context)
        return Transformer.head(params, cfg, x), metrics

    # -------------- loss -----------------
    @staticmethod
    def loss_fn(params, cfg: ArchConfig, tokens, labels, patch_embeds=None):
        logits, metrics = Transformer.forward(params, cfg, tokens, patch_embeds)
        ll = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(ll, labels[..., None], axis=-1)[..., 0]
        loss = jnp.mean(nll)
        if cfg.moe is not None:
            loss = (loss + cfg.moe.aux_weight * metrics["aux_loss"]
                    + cfg.moe.router_z_weight * metrics["z_loss"])
        return loss, metrics

    # -------------- serving -----------------
    @staticmethod
    def cache_capacity(cfg: ArchConfig, seq_len: int, long_context: bool):
        if long_context:
            w = cfg.long_context_window
            if cfg.attn.pattern in ("local", "local_global") and cfg.attn.window:
                w = max(w, cfg.attn.window)
            return min(seq_len, w)
        return seq_len

    @staticmethod
    def init_decode_state(cfg: ArchConfig, batch: int, seq_len: int,
                          long_context: bool = False):
        """Allocate KV caches / SSM state for decode at a given context."""
        dtype = cfg.jnp_dtype
        kind = block_kind(cfg)
        state = {}
        if kind == "mamba":
            state["mamba"] = mamba_lib.mamba_state_init(cfg, cfg.n_layers, batch, dtype)
        elif kind == "hybrid":
            state["mamba"] = mamba_lib.mamba_state_init(cfg, cfg.n_layers, batch, dtype)
            n_apps = len(cfg.ssm.shared_attn_positions)
            cap = Transformer.cache_capacity(cfg, seq_len, long_context)
            state["kv"] = kv_cache_init(cfg, n_apps, batch, cap, dtype)
        else:
            cap = Transformer.cache_capacity(cfg, seq_len, long_context)
            state["kv"] = kv_cache_init(cfg, cfg.n_layers, batch, cap, dtype)
        state["pos"] = jnp.zeros((), jnp.int32)
        return state

    @staticmethod
    def decode_step(params, cfg: ArchConfig, token, state,
                    long_context: bool = False):
        """One-token decode.  token [B,1] -> (logits [B,1,V], state')."""
        pos = state["pos"]
        x = Transformer.embed_inputs(params, cfg, token)
        kind = block_kind(cfg)

        if kind == "mamba":
            ms: mamba_lib.MambaState = state["mamba"]

            def body(carry, inp):
                xs = carry
                bp, h, cv = inp
                hnorm = rmsnorm(bp["norm"], xs[:, 0], cfg.norm_eps)[:, None]
                y, h2, cv2 = mamba_lib.mamba_decode(bp["mamba"], cfg, hnorm, h, cv)
                return xs + y, (h2, cv2)

            xs, (h_new, cv_new) = jax.lax.scan(
                body, x, (params["blocks"], ms.h, ms.conv))
            state = dict(state, mamba=mamba_lib.MambaState(h_new, cv_new),
                         pos=pos + 1)
            return Transformer.head(params, cfg, xs), state

        if kind == "hybrid":
            return Transformer._hybrid_decode(params, cfg, x, state, long_context)

        # dense / moe / vlm: scan over layers; the cache is CARRIED as one
        # buffer and updated in place per layer (ys-collection would
        # double-buffer the whole cache — §Perf decode iteration).
        kv: KVCache = state["kv"]
        period = pattern_period(cfg)

        def body(carry, inp):
            xs, k_all, v_all = carry
            bp, li = inp
            lk = jax.lax.dynamic_index_in_dim(k_all, li, 0, keepdims=False)
            lv = jax.lax.dynamic_index_in_dim(v_all, li, 0, keepdims=False)
            h = rmsnorm(bp["norm_attn"], xs, cfg.norm_eps)
            # per-layer local/global needs a traced switch when period>1
            if period > 1:
                w_local = cfg.attn.window
                w_global = attn_lib.layer_window(cfg, False, long_context)
                a_l, k_l, v_l = attn_lib.attend_decode(
                    bp["attn"], cfg, h, lk, lv, pos, w_local)
                a_g, k_g, v_g = attn_lib.attend_decode(
                    bp["attn"], cfg, h, lk, lv, pos, w_global)
                is_local = (li % 2 == 0)
                a = jnp.where(is_local, a_l, a_g)
                nk = jnp.where(is_local, k_l, k_g)
                nv = jnp.where(is_local, v_l, v_g)
            else:
                window = attn_lib.layer_window(
                    cfg, cfg.attn.pattern == "local", long_context)
                a, nk, nv = attn_lib.attend_decode(
                    bp["attn"], cfg, h, lk, lv, pos, window)
            if cfg.sandwich_norm:
                a = rmsnorm(bp["post_attn"], a, cfg.norm_eps)
            xs = xs + a
            h = rmsnorm(bp["norm_ffn"], xs, cfg.norm_eps)
            if "moe" in bp:
                f, _ = moe_lib.moe_apply(bp["moe"], cfg.moe, h)
                if "shared_ffn" in bp:
                    f = f + ffn_lib.swiglu(bp["shared_ffn"], h)
            else:
                f = ffn_lib.swiglu(bp["ffn"], h)
                if cfg.sandwich_norm:
                    f = rmsnorm(bp["post_ffn"], f, cfg.norm_eps)
            k_all = jax.lax.dynamic_update_index_in_dim(k_all, nk, li, 0)
            v_all = jax.lax.dynamic_update_index_in_dim(v_all, nv, li, 0)
            return (xs + f, k_all, v_all), None

        lidx = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        (xs, nk, nv), _ = jax.lax.scan(
            body, (x, kv.k, kv.v), (params["blocks"], lidx))
        state = dict(state, kv=KVCache(nk, nv, kv.idx + 1), pos=pos + 1)
        return Transformer.head(params, cfg, xs), state

    @staticmethod
    def _hybrid_decode(params, cfg: ArchConfig, x, state, long_context):
        pos = state["pos"]
        ms: mamba_lib.MambaState = state["mamba"]
        kv: KVCache = state["kv"]
        window = attn_lib.layer_window(cfg, False, long_context)
        attn_pos = cfg.ssm.shared_attn_positions
        h_all, cv_all = ms.h, ms.conv
        nk, nv = kv.k, kv.v
        xs = x
        cursor = 0
        for app_i, p in enumerate(list(attn_pos) + [cfg.n_layers - 1]):
            is_attn = app_i < len(attn_pos)
            hi = p + 1 if is_attn else cfg.n_layers
            if hi > cursor:
                seg_blocks = tree_slice(params["blocks"], cursor, hi)
                seg_h = h_all[cursor:hi]
                seg_cv = cv_all[cursor:hi]

                def body(carry, inp):
                    xc = carry
                    bp, h, cv = inp
                    hnorm = rmsnorm(bp["norm"], xc[:, 0], cfg.norm_eps)[:, None]
                    y, h2, cv2 = mamba_lib.mamba_decode(bp["mamba"], cfg, hnorm, h, cv)
                    return xc + y, (h2, cv2)

                xs, (h2, cv2) = jax.lax.scan(body, xs, (seg_blocks, seg_h, seg_cv))
                h_all = h_all.at[cursor:hi].set(h2)
                cv_all = cv_all.at[cursor:hi].set(cv2)
                cursor = hi
            if is_attn:
                bp = params["shared_attn"]
                h = rmsnorm(bp["norm_attn"], xs, cfg.norm_eps)
                a, k2, v2 = attn_lib.attend_decode(
                    bp["attn"], cfg, h, nk[app_i], nv[app_i], pos, window)
                nk = nk.at[app_i].set(k2)
                nv = nv.at[app_i].set(v2)
                xs = xs + a
                h = rmsnorm(bp["norm_ffn"], xs, cfg.norm_eps)
                xs = xs + ffn_lib.swiglu(bp["ffn"], h)
        state = dict(state,
                     mamba=mamba_lib.MambaState(h_all, cv_all),
                     kv=KVCache(nk, nv, kv.idx + 1), pos=pos + 1)
        return Transformer.head(params, cfg, xs), state
