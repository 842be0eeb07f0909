"""Split-serving driver: batched decode with the composed model.

Runs for real on CPU with a smoke-sized arch (``--smoke``, default) and
demonstrates the full serve path the decode dry-run shapes lower:
prefill a prompt batch, then step the KV/SSM cache token by token.

``--continuous`` switches decoder-only archs to the production path:
the fixed-slot continuous-batching runtime in :mod:`repro.serve`
(compile-once slot table, deadlines, retry/backoff) driven by the
closed-loop load generator.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --steps 16
  PYTHONPATH=src python -m repro.launch.serve --continuous --concurrency 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, list_archs, smoke_config
from repro.models.encdec import EncDec
from repro.models.transformer import Transformer


def serve_decoder_only(cfg, batch: int, prompt_len: int, steps: int,
                       seed: int = 0):
    if batch < 1:
        raise ValueError(f"batch={batch} must be >= 1")
    if prompt_len < 0 or steps < 0:
        raise ValueError(f"prompt_len={prompt_len} and steps={steps} must "
                         "be >= 0")
    key = jax.random.PRNGKey(seed)
    params = Transformer.init(key, cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, cfg.vocab)
    # capacity >= 1 keeps the zero-work edge (prompt_len=0, steps=0) a
    # well-defined no-op instead of a degenerate 0-length ring buffer
    state = Transformer.init_decode_state(cfg, batch,
                                          max(prompt_len + steps, 1))

    decode = jax.jit(lambda p, t, s: Transformer.decode_step(p, cfg, t, s))
    # prefill by stepping the prompt through the SAME jitted step the
    # decode loop uses (cache-exact, CPU-friendly): one trace total, so
    # prefill_s measures the model, not per-token retrace overhead
    logits = None
    tok = jnp.zeros((batch, 1), jnp.int32)
    t0 = time.time()
    for i in range(prompt_len):
        logits, state = decode(params, prompt[:, i:i+1], state)
    if prompt_len:
        jax.block_until_ready(logits)
        # greedy continuation: generation starts from the token the
        # prefilled prompt predicts, not a replay of the prompt's start
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t_prefill = time.time() - t0
    out_tokens = []
    t0 = time.time()
    for _ in range(steps):
        logits, state = decode(params, tok, state)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.time() - t0
    toks = (jnp.concatenate(out_tokens, axis=1) if out_tokens
            else jnp.zeros((batch, 0), jnp.int32))
    if logits is not None:
        assert bool(jnp.isfinite(logits).all()), \
            "non-finite logits in serve loop"
    return {"tokens": toks, "prefill_s": t_prefill,
            "decode_s_per_token": dt / steps if steps else 0.0,
            "batch": batch}


def serve_whisper(cfg, batch: int, steps: int, seed: int = 0):
    if batch < 1:
        raise ValueError(f"batch={batch} must be >= 1")
    if steps < 0:
        raise ValueError(f"steps={steps} must be >= 0")
    key = jax.random.PRNGKey(seed)
    params = EncDec.init(key, cfg)
    frames = jax.random.normal(jax.random.PRNGKey(1),
                               (batch, 60, cfg.enc_d_model), cfg.jnp_dtype) * 0.1
    state = EncDec.init_decode_state(params, cfg, frames, seq_len=steps + 1)
    decode = jax.jit(lambda p, t, s: EncDec.decode_step(p, cfg, t, s))
    logits = None
    tok = jnp.zeros((batch, 1), jnp.int32)
    outs = []
    t0 = time.time()
    for _ in range(steps):
        logits, state = decode(params, tok, state)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        outs.append(tok)
    dt = time.time() - t0
    if logits is not None:
        assert bool(jnp.isfinite(logits).all())
    return {"tokens": (jnp.concatenate(outs, axis=1) if outs
                       else jnp.zeros((batch, 0), jnp.int32)),
            "decode_s_per_token": dt / steps if steps else 0.0,
            "batch": batch}


def serve_continuous(cfg, serve_cfg, concurrency: int, n_requests: int,
                     seed: int = 0):
    """Drive the continuous-batching runtime with a closed loop."""
    from repro.serve import ServeRuntime, make_prompts, run_closed_loop
    rt = ServeRuntime(cfg, serve_cfg, seed=seed)
    prompts = make_prompts(n_requests, serve_cfg.max_prompt_len, cfg.vocab,
                           seed=seed + 1)
    row = run_closed_loop(rt, prompts, concurrency=concurrency)
    row["traces"] = dict(rt.traces)
    row["max_slot_reuse"] = rt.stats()["max_slot_reuse"]
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=list_archs())
    ap.add_argument("--full", action="store_true",
                    help="use the full config (needs real accelerators)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="serve via the fixed-slot continuous-batching "
                         "runtime (decoder-only archs)")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed-loop client count (--continuous)")
    ap.add_argument("--requests", type=int, default=16,
                    help="total requests to serve (--continuous)")
    from repro.serve import ServeConfig
    ServeConfig.add_arguments(ap)
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else smoke_config(args.arch)
    if args.continuous:
        if cfg.family == "audio":
            ap.error("--continuous serves decoder-only archs")
        row = serve_continuous(cfg, ServeConfig.from_flags(args),
                               args.concurrency, args.requests)
        print(f"arch={cfg.name} continuous serve:")
        for k, v in row.items():
            print(f"  {k}: {v}")
        return
    if cfg.family == "audio":
        res = serve_whisper(cfg, args.batch, args.steps)
    else:
        res = serve_decoder_only(cfg, args.batch, args.prompt_len, args.steps)
    toks = res.pop("tokens")
    print(f"arch={cfg.name} generated {toks.shape[1]} tokens x{toks.shape[0]} seqs")
    print({k: (round(v, 5) if isinstance(v, float) else v)
           for k, v in res.items()})
    print("sample:", toks[0][:12].tolist())


if __name__ == "__main__":
    main()
