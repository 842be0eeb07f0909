"""Benchmark harness — one function per paper table + kernel microbenches.

Prints ``name,us_per_call,derived`` CSV (derived = the table's headline
number or claim check).  ``--fast`` (default when run as module in CI)
uses reduced rounds; ``--full`` runs the paper-shaped versions.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table3,...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def bench_kernels() -> list[tuple[str, float, str]]:
    """Pallas kernels vs their jnp oracles (interpret mode on CPU)."""
    from repro.kernels import ref
    from repro.kernels.ops import (feature_resample, flash_attention,
                                   ssd_scan, topk_gating)
    rows = []
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    t_k = _time_fn(lambda: flash_attention(q, k, v))
    t_r = _time_fn(jax.jit(lambda: ref.flash_attention_ref(q, k, v)))
    rows.append(("kernel_flash_attention", t_k, f"ref_us={t_r:.0f}"))

    x = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(1, 256, 2)), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.normal(size=(2,)), jnp.float32))
    Bm = jnp.asarray(rng.normal(size=(1, 256, 2, 16)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(1, 256, 2, 16)), jnp.float32)
    t_k = _time_fn(lambda: ssd_scan(x, dt, A, Bm, Cm, chunk=64))
    t_r = _time_fn(jax.jit(lambda: ref.ssd_scan_ref(x, dt, A, Bm, Cm)[0]))
    rows.append(("kernel_ssd_scan", t_k, f"ref_us={t_r:.0f}"))

    logits = jnp.asarray(rng.normal(size=(1024, 64)), jnp.float32)
    t_k = _time_fn(lambda: topk_gating(logits, 8))
    t_r = _time_fn(jax.jit(lambda: ref.topk_gating_ref(logits, 8)))
    rows.append(("kernel_topk_gating", t_k, f"ref_us={t_r:.0f}"))

    src = jnp.asarray(rng.normal(size=(1024, 256)), jnp.float32)
    idx = jnp.asarray(rng.permutation(1024)[:512], jnp.int32)
    t_k = _time_fn(lambda: feature_resample(src, idx))
    t_r = _time_fn(jax.jit(lambda: ref.feature_resample_ref(src, idx)))
    rows.append(("kernel_feature_resample", t_k, f"ref_us={t_r:.0f}"))
    return rows


def bench_cyclesl_round() -> list[tuple[str, float, str]]:
    """Wall time of one jitted CycleSL round vs baselines (CPU, tiny)."""
    from benchmarks.common import BenchConfig, build, experiment_config
    from repro.api import Engine
    bc = BenchConfig(width=8)
    task, fed = build(bc, 0)
    rows = []
    for name in ("sflv2", "cyclesfl"):
        # donate=False: the timing loop re-feeds the same state buffers
        eng = Engine(experiment_config(bc, name, 0), task=task, fed=fed,
                     metric_key="accuracy", donate=False,
                     log=lambda *a, **k: None)
        state = eng.init_state()
        rng = np.random.default_rng(0)
        cohort, xs, ys, mask = eng.sample_round(rng)
        key = eng.round_key(1)
        t = _time_fn(
            lambda: eng.algo.round(state, cohort, xs, ys, key,
                                   mask)[1]["server_loss"],
            iters=3, warmup=1)
        live = len(cohort) if mask is None else int(mask.sum())
        rows.append((f"round_{name}", t,
                     f"cohort={live}/cap={len(cohort)}"))
    return rows


def bench_tables(fast: bool, only: set[str] | None) -> list[tuple[str, float, str]]:
    rows = []
    specs = [
        ("table3", "benchmarks.table3_accuracy"),
        ("table4", "benchmarks.table4_cutlayer"),
        ("table5", "benchmarks.table5_serverepoch"),
        ("table6", "benchmarks.table6_gradnorm"),
        ("table8", "benchmarks.table8_latency"),
    ]
    import importlib
    os.makedirs("benchmarks/results", exist_ok=True)
    for name, mod_name in specs:
        if only and name not in only:
            continue
        mod = importlib.import_module(mod_name)
        t0 = time.time()
        out = mod.main(fast=fast)
        dt = (time.time() - t0) * 1e6
        with open(f"benchmarks/results/{name}.json", "w") as f:
            json.dump(out, f, indent=1)
        claims = out.get("claims", {})
        derived = ";".join(f"{k}={v}" for k, v in claims.items()) or "see_json"
        rows.append((name, dt, derived))
    return rows


def bench_roofline(only) -> list[tuple[str, float, str]]:
    """Summarize the dry-run roofline table if the sweep artifact exists."""
    path = "benchmarks/results/dryrun_final.json"
    if not os.path.exists(path):
        path = "benchmarks/results/dryrun.json"
    if not os.path.exists(path) or (only and "roofline" not in only):
        return []
    from repro.launch.roofline import analyze_record
    with open(path) as f:
        recs = json.load(f)
    rows = []
    n_ok = 0
    doms = {}
    for rec in recs:
        a = analyze_record(rec)
        if a:
            n_ok += 1
            doms[a["dominant"]] = doms.get(a["dominant"], 0) + 1
    rows.append(("roofline_dryrun", 0.0,
                 f"ok={n_ok};dominant={json.dumps(doms).replace(' ', '')}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: kernels,round,table3..table8,roofline")
    args, _ = ap.parse_known_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    rows = []
    if only is None or "kernels" in only:
        rows += bench_kernels()
    if only is None or "round" in only:
        rows += bench_cyclesl_round()
    rows += bench_tables(fast=not args.full, only=only)
    rows += bench_roofline(only)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
