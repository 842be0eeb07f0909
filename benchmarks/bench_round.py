"""Round-latency benchmark: compile count, steady-state latency,
rounds/sec — the evidence behind the compile-once contract.

The Engine pads every cohort to the static capacity C_max and threads an
attendance mask through the jitted round, so ONE XLA trace serves every
live cohort size the protocol produces.  This harness measures, per
algorithm:

* ``padded``            — variable attendance, fixed shapes: compile
                          count (must be 1), steady-state round latency,
                          rounds/sec.
* ``unpadded_variable`` — the same variable-attendance stream without
                          padding: one retrace per distinct cohort size
                          (what wall-clock used to be dominated by).
* ``fixed_size_comparison`` — padded vs the legacy unpadded path at a
                          FIXED cohort size, interleaved measurement:
                          the steady-state baseline the padded path
                          must not regress against.
* ``by_cohort_size``    — padded rounds/sec across capacities.
* ``pipeline_comparison`` — (``--pipeline``) rounds/sec with the
                          pipelined scheduler off vs sync-barrier vs
                          async bounded-stale overlap at each ring depth
                          in ``--pipeline-depths`` (default 0,1,2,4),
                          per algorithm, with the trace-budget,
                          per-depth bounded-lag, and staleness-weighting
                          identity claims.
* ``device_sweep``      — (``--devices 1,2,4,8``) the weak-scaling
                          sweep: rounds/sec of the sharded Engine vs
                          device count at FIXED GLOBAL WORK, on the
                          pinned client-heavy cut=3 config, through the
                          device-resident run loop (donated buffers,
                          prefetch, sync_every).  Each count runs in a
                          fresh subprocess with
                          ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
                          (jax locks the device count at first init).
                          Per point: steady latency, the fused
                          gather+loss-inside-shard_map variant, and the
                          collective census with the no-pool-allgather
                          HLO assertion.  The sweep-level claim is
                          ``weak_scaling_efficiency`` = rps(max devices)
                          / rps(1 device) >= 1.0.
* ``shard_local``       — (``--shard-local [1,8]``) the sharded Engine
                          with ``cycle.shard_local_resample`` off vs on,
                          interleaved measurement per device count (one
                          subprocess each): the off/on steady-state
                          comparison behind the shard_map resample path,
                          plus the loss-equality claim (the two paths
                          must agree — shard-local is value-exact).

Writes ``BENCH_round_latency.json`` so every PR records the perf
trajectory (CI runs ``--smoke --devices 1,2,4`` and uploads the
artifact).

  PYTHONPATH=src python benchmarks/bench_round.py [--smoke] [--out PATH]
      [--devices 1,2,4,8] [--pipeline]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from dataclasses import replace

import jax
import numpy as np

from repro.api import Engine, ExperimentConfig
from repro.core.cyclesl import CycleConfig

ALGOS = ("psl", "cyclepsl", "cyclesfl")


def _drive(eng: Engine, rounds: int) -> list[float]:
    """Run ``rounds`` rounds through the Engine's sampling protocol and
    return per-round wall times (device-synced)."""
    state = eng.init_state()
    rng = np.random.default_rng(eng.cfg.seed + 1)
    times = []
    for rnd in range(rounds):
        cohort, xs, ys, mask = eng.sample_round(rng)
        t0 = time.perf_counter()
        if mask is None:
            state, m = eng.algo.round(state, cohort, xs, ys,
                                      eng.round_key(rnd))
        else:
            state, m = eng.algo.round(state, cohort, xs, ys,
                                      eng.round_key(rnd), mask)
        jax.block_until_ready(m["server_loss"])
        times.append(time.perf_counter() - t0)
    return times


def _steady(times: list[float], warmup: int = 2) -> float:
    tail = times[warmup:] or times
    return float(np.median(tail))


def _engine(cfg: ExperimentConfig) -> Engine:
    return Engine(cfg, donate=False, log=lambda *a, **k: None)


def _round_call(eng: Engine):
    """A zero-sampling-cost round closure over one drawn cohort."""
    state = eng.init_state()
    rng = np.random.default_rng(eng.cfg.seed + 1)
    cohort, xs, ys, mask = eng.sample_round(rng)
    key = eng.round_key(0)
    if mask is None:
        return lambda: eng.algo.round(state, cohort, xs, ys,
                                      key)[1]["server_loss"]
    return lambda: eng.algo.round(state, cohort, xs, ys, key,
                                  mask)[1]["server_loss"]


def _interleaved(call_a, call_b, iters: int) -> tuple[float, float]:
    """Median wall time of two compiled calls, alternated every
    iteration — and with the within-pair ORDER alternated too, so CPU
    frequency/cache drift and first-in-pair warmup bias hit both
    equally."""
    for call in (call_a, call_b):                   # compile + warm
        jax.block_until_ready(call())
        jax.block_until_ready(call())
    ta, tb = [], []
    for i in range(iters):
        first, second, tf, ts = ((call_a, call_b, ta, tb) if i % 2 == 0
                                 else (call_b, call_a, tb, ta))
        t0 = time.perf_counter()
        jax.block_until_ready(first())
        tf.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(second())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ta)), float(np.median(tb))


def bench_algo(algo: str, base: ExperimentConfig, rounds: int,
               capacities: tuple[int, ...]) -> dict:
    out = {}

    # 1. padded + variable attendance: the compile-once path
    eng = _engine(replace(base, algo=algo, variable_attendance=True,
                          pad_cohorts=True))
    times = _drive(eng, rounds)
    out["padded"] = {
        "compile_count": eng.algo.trace_count,
        "first_round_s": round(times[0], 4),
        "steady_ms": round(_steady(times) * 1e3, 3),
        "rounds_per_sec": round(1.0 / _steady(times), 2),
        "cohort_capacity": eng.cohort_capacity,
    }

    # 2. same variable-attendance stream, no padding: one retrace per
    #    distinct live cohort size
    eng = _engine(replace(base, algo=algo, variable_attendance=True,
                          pad_cohorts=False))
    times = _drive(eng, rounds)
    out["unpadded_variable"] = {
        "compile_count": eng.algo.trace_count,
        "total_s": round(sum(times), 3),
        "steady_ms": round(_steady(times) * 1e3, 3),
    }

    # 3. steady-state at a FIXED cohort size == capacity, padded vs the
    #    legacy unpadded path, interleaved so timer drift is shared:
    #    this is the "padding costs nothing once shapes are stable" claim
    eng_pad = _engine(replace(base, algo=algo, variable_attendance=False,
                              pad_cohorts=True))
    eng_fix = _engine(replace(base, algo=algo, variable_attendance=False,
                              pad_cohorts=False))
    pad_ms, fix_ms = _interleaved(_round_call(eng_pad), _round_call(eng_fix),
                                  iters=max(20, rounds))
    out["fixed_size_comparison"] = {
        "padded_steady_ms": round(pad_ms * 1e3, 3),
        "unpadded_steady_ms": round(fix_ms * 1e3, 3),
        "padded_over_unpadded": round(pad_ms / fix_ms, 3),
    }

    # 4. padded rounds/sec across cohort capacities
    by_size = {}
    for cap in capacities:
        att = cap / base.n_clients
        eng = _engine(replace(base, algo=algo, attendance=att,
                              variable_attendance=True, pad_cohorts=True))
        times = _drive(eng, max(4, rounds // 2))
        by_size[str(eng.cohort_capacity)] = {
            "steady_ms": round(_steady(times) * 1e3, 3),
            "rounds_per_sec": round(1.0 / _steady(times), 2),
            "compile_count": eng.algo.trace_count,
        }
    out["by_cohort_size"] = by_size

    out["claims"] = {
        "compile_once": out["padded"]["compile_count"] == 1,
        "unpadded_retraces_exceed_one":
            out["unpadded_variable"]["compile_count"] > 1,
        # steady-state: padded must not regress vs the legacy fixed-size
        # path (10% slack absorbs residual CPU timer noise at ms scale)
        "padded_steady_no_worse_than_unpadded_fixed":
            out["fixed_size_comparison"]["padded_over_unpadded"] <= 1.10,
    }
    return out


# ----------------------------------------------------- pipeline sweep
class _LossTrail:
    """Per-round server_loss recorder (for the weighting-identity claim)."""

    def __init__(self):
        self.vals = []

    def on_round(self, engine, rnd, state, metrics):
        self.vals.append(np.asarray(metrics["server_loss"]))


def pipeline_sweep(smoke: bool, depths: tuple = (0, 1, 2, 4)) -> dict:
    """Rounds/sec with the pipelined scheduler off vs on across ring
    depths (sync barrier + async bounded-stale overlap at each depth in
    ``depths``), per algorithm — the evidence behind the pipeline_depth
    knob.  Timing goes through the Engine's own collect_timing path
    (device-synced per round, compile round excluded), so what's
    measured is the schedule, not the harness.  Also runs the
    staleness-weighting identity check: a sync schedule (lag 0 every
    round) with ``staleness_weighting='inverse'`` must reproduce the
    unweighted sync run's per-round server_loss bit-for-bit."""
    base = ExperimentConfig(
        task="image", n_clients=24 if smoke else 60,
        attendance=0.25 if smoke else 0.2, batch=8 if smoke else 16,
        width=4 if smoke else 8, cut=2, seed=0, eval_every=10**9,
        rounds=8 if smoke else 16, collect_timing=True)
    async_depths = sorted(d for d in set(depths) if d >= 1)
    sync_depth = async_depths[0] if async_depths else 1
    modes = {"off": {"pipeline_depth": 0},
             "sync": {"pipeline_depth": sync_depth,
                      "pipeline_staleness": "sync"}}
    for d in async_depths:
        modes[f"async{d}"] = {"pipeline_depth": d,
                              "pipeline_staleness": "async"}
    out = {"depths": list(depths)}
    for algo in ALGOS:
        rec = {}
        sync_losses = None
        for mode, kw in modes.items():
            trail = _LossTrail()
            eng = Engine(replace(base, algo=algo, **kw), donate=False,
                         callbacks=(trail,), log=lambda *a, **k: None)
            res = eng.run()
            entry = {
                "depth": kw["pipeline_depth"],
                "steady_ms": round(res["round_time_s"] * 1e3, 3),
                "rounds_per_sec": round(1.0 / res["round_time_s"], 2),
            }
            if mode != "off":
                entry["extract_traces"] = eng.pipeline.extract_traces
                entry["tail_traces"] = eng.pipeline.tail_traces
                entry["max_theta_s_lag_rounds"] = \
                    res["pipeline"]["max_theta_s_lag_rounds"]
                entry["realized_lags"] = res["pipeline"]["realized_lags"]
            else:
                entry["compile_count"] = eng.algo.trace_count
            if mode == "sync":
                sync_losses = trail.vals
            rec[mode] = entry
        # weighting identity: sync + inverse weighting == sync unweighted
        # up to XLA fusion (w(0) is exactly 1.0, but the traced multiply
        # can reassociate downstream reductions by an ulp)
        trail_w = _LossTrail()
        Engine(replace(base, algo=algo, pipeline_depth=sync_depth,
                       staleness_weighting="inverse"), donate=False,
               callbacks=(trail_w,), log=lambda *a, **k: None).run()
        weighting_identity = (
            len(trail_w.vals) == len(sync_losses)
            and all(np.allclose(a, b, rtol=1e-5, atol=1e-7)
                    for a, b in zip(sync_losses, trail_w.vals)))
        pipe_modes = [m for m in rec if m != "off"]
        rec["claims"] = {
            # one extract + one tail trace — the "at most one warm-up
            # trace over the sequential budget" acceptance, at EVERY depth
            "pipeline_trace_budget": all(
                rec[m]["extract_traces"] == 1 and rec[m]["tail_traces"] == 1
                for m in pipe_modes),
            # async lag never exceeds the configured ring depth; sync is
            # lag-free whatever the depth says
            "depth_lag_bounded": {
                m: rec[m]["max_theta_s_lag_rounds"] <= rec[m]["depth"]
                for m in pipe_modes if m.startswith("async")},
            "sync_lag_zero": rec["sync"]["max_theta_s_lag_rounds"] == 0,
            "weighting_identity_at_none": weighting_identity,
            "sync_over_off":
                round(rec["sync"]["steady_ms"]
                      / rec["off"]["steady_ms"], 3),
            **{f"{m}_over_off":
               round(rec[m]["steady_ms"] / rec["off"]["steady_ms"], 3)
               for m in pipe_modes if m.startswith("async")},
            # the pipelined schedule must cost ~nothing even where it
            # cannot win: on a single-core host the two dispatches
            # serialize, so the bound is "no duplicated boundary
            # traffic", not "overlap speedup".  (The historical 1.44x
            # cyclepsl regression was the PipelineStage carrying the
            # cohort features twice — raw [C, b, ...] AND pooled — and
            # is fixed by the store-only handoff.)  Deeper rings add
            # only host-side bookkeeping per round, so they get the
            # same bound with a little extra timer slack.
            "async_overhead_bounded": all(
                rec[m]["steady_ms"] / rec["off"]["steady_ms"]
                <= (1.15 if rec[m]["depth"] <= 1 else 1.25)
                for m in pipe_modes if m.startswith("async")),
        }
        out[algo] = rec
        async_ms = " ".join(
            f"{m}={rec[m]['steady_ms']}ms(lag {rec[m]['max_theta_s_lag_rounds']})"
            for m in pipe_modes if m.startswith("async"))
        print(f"[pipeline {algo}] off={rec['off']['steady_ms']}ms "
              f"sync={rec['sync']['steady_ms']}ms {async_ms} "
              f"weighting_identity={weighting_identity}")
    return out


# -------------------------------------------------- shard-local sweep
def shard_local_worker(n_devices: int, smoke: bool) -> dict:
    """Shard-local resample off vs on at the CURRENT process's device
    count, interleaved so timer drift hits both paths equally.  The two
    runs share config, mesh, and cohort stream — only
    ``cycle.shard_local_resample`` differs — and must produce the same
    server loss (the path is value-exact)."""
    base = ExperimentConfig(
        algo="cyclesfl", task="image", rounds=1, n_clients=32,
        attendance=0.25, batch=8, width=4 if smoke else 8, cut=2, seed=0,
        eval_every=10**9, mesh_shape=(n_devices, 1),
        mesh_axes=("data", "model"),
        cycle=CycleConfig(server_epochs=2))
    eng_off = _engine(base)
    eng_on = _engine(base.with_cycle(shard_local_resample=True))
    off_ms, on_ms = _interleaved(_round_call(eng_off), _round_call(eng_on),
                                 iters=8 if smoke else 20)
    loss_off = float(_round_call(eng_off)())
    loss_on = float(_round_call(eng_on)())
    return {
        "backend": jax.default_backend(),
        "devices": n_devices,
        "jax_device_count": jax.device_count(),
        "off_steady_ms": round(off_ms * 1e3, 3),
        "on_steady_ms": round(on_ms * 1e3, 3),
        "on_over_off": round(on_ms / off_ms, 3),
        "compile_count_on": eng_on.algo.trace_count,
        "losses_equal": loss_off == loss_on,
    }


def _forced_device_sweep(worker_flag: str, devices: list[int], smoke: bool,
                         report) -> dict:
    """Shared subprocess scaffold for the per-device-count sweeps: one
    fresh process per count (XLA_FLAGS must bind before jax
    initializes), the worker's JSON record on the last stdout line,
    stderr captured on failure.  ``report(rec)`` formats the progress
    line for one successful record."""
    out = {}
    for n in devices:
        env = dict(os.environ)
        # append so user-set XLA flags survive (last occurrence wins for
        # the device count itself)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        cmd = [sys.executable, os.path.abspath(__file__), worker_flag,
               str(n)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if proc.returncode != 0:
            out[str(n)] = {"error": proc.stderr[-2000:]}
            continue
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        out[str(n)] = rec
        print(report(rec))
    return out


def child_backend(*sweeps: dict):
    """The backend the first successful child reported (None if none
    did) — read from the records, so the parent never initializes JAX."""
    for sweep in sweeps:
        for rec in sweep.values():
            if isinstance(rec, dict) and "backend" in rec:
                return rec["backend"]
    return None


def shard_local_sweep(devices: list[int], smoke: bool) -> dict:
    """One subprocess per device count, recording the off/on comparison."""
    return _forced_device_sweep(
        "--shard-local-worker", devices, smoke,
        lambda rec: (f"[shard-local devices={rec['devices']}] "
                     f"off={rec['off_steady_ms']}ms "
                     f"on={rec['on_steady_ms']}ms "
                     f"ratio={rec['on_over_off']} "
                     f"losses_equal={rec['losses_equal']}"))


# ------------------------------------------------------- device sweep
# The weak-scaling configuration is PINNED (independent of --smoke,
# which only shortens the timed run): cyclesfl at the client-heavy
# cut=3 split (server = the 2048->62 linear head), width 8, per-client
# batch 8, server batch 16, cohort capacity 8 — fixed GLOBAL work, so
# rounds/sec at N devices vs 1 device is directly comparable.  The
# feature pool at this cut is [cap*batch, 2048] f32; its byte geometry
# feeds the no-pool-allgather HLO assertion.
_WS_FEAT_DIM = 2048      # femnist_cnn stage-2 dense output (any width)
_WS_SB = 16


def _ws_config(n_devices: int, rounds: int) -> ExperimentConfig:
    return ExperimentConfig(
        algo="cyclesfl", task="image", rounds=rounds, n_clients=32,
        attendance=0.25, batch=8, width=8, cut=3, seed=0,
        eval_every=10**9, variable_attendance=True, collect_timing=True,
        sync_every=4, mesh_shape=(n_devices, 1),
        mesh_axes=("data", "model"),
        cycle=CycleConfig(shard_local_resample=True, server_batch=_WS_SB))


def _ws_run(cfg: ExperimentConfig, n_devices: int) -> tuple:
    """One weak-scaling measurement through the Engine's own run loop —
    the device-resident path (donated round buffers, prefetch
    double-buffer, sync_every telemetry cadence) is what's timed, not a
    harness loop — plus the compiled round's collective census and the
    pool-all-gather assertion."""
    from repro.utils import profiling
    from repro.utils.hlo_cost import assert_no_pool_allgather
    eng = Engine(cfg, donate=True, log=lambda *a, **k: None)
    res = eng.run()
    steady = res["round_time_s"]
    pool_bytes = eng.padded_capacity * cfg.batch * _WS_FEAT_DIM * 4
    sb_bytes = _WS_SB * _WS_FEAT_DIM * 4
    census = assert_no_pool_allgather(
        profiling.round_hlo(eng), pool_bytes, n_shards=n_devices,
        extra_sizes=(sb_bytes, sb_bytes // n_devices))
    rec = {
        "steady_ms": round(steady * 1e3, 3),
        "rounds_per_sec": round(1.0 / steady, 2),
        "compile_count": eng.algo.trace_count,
        "no_pool_allgather": True,
        "pool_bytes": pool_bytes,
        "collective_census": census,
    }
    return eng, rec


def sweep_worker(n_devices: int, smoke: bool) -> dict:
    """One weak-scaling point at the CURRENT process's device count:
    mesh (N, 1) over ('data', 'model'), shard-local resample, donated
    device-resident rounds, sync_every=4.  Records the plain shard-local
    round, the fused-in-shard_map variant (gather+head-loss computed
    inside the shard_map body, scalar psum across shards), and the
    collective census + no-pool-allgather assertion for both compiled
    rounds."""
    rounds = 6 if smoke else 10
    cfg = _ws_config(n_devices, rounds)
    eng, rec = _ws_run(cfg, n_devices)
    rec = {
        "backend": jax.default_backend(),
        "devices": n_devices,
        "jax_device_count": jax.device_count(),
        "cohort_capacity": eng.cohort_capacity,
        "padded_capacity": eng.padded_capacity,
        **rec,
    }
    _, frec = _ws_run(cfg.with_cycle(fused_gather_loss=True), n_devices)
    rec["fused"] = frec
    return rec


def device_sweep(devices: list[int], smoke: bool) -> dict:
    """One subprocess per device count, then the weak-scaling verdict:
    ``weak_scaling_efficiency`` = rounds/sec at the largest count over
    rounds/sec at the smallest, at fixed global work — the tracked
    claim is that the sharded runtime at N devices is no slower than at
    1 (>= 1.0), i.e. the 1->8 slowdown is gone."""
    out = _forced_device_sweep(
        "--sweep-worker", devices, smoke,
        lambda rec: (f"[devices={rec['devices']}] "
                     f"steady_ms={rec['steady_ms']} "
                     f"rounds_per_sec={rec['rounds_per_sec']} "
                     f"fused_ms={rec['fused']['steady_ms']} "
                     f"compile_count={rec['compile_count']}"))
    recs = {int(k): v for k, v in out.items() if "error" not in v}
    if len(recs) > 1:
        lo, hi = min(recs), max(recs)
        eff = (recs[hi]["rounds_per_sec"] / recs[lo]["rounds_per_sec"])
        fused_eff = (recs[hi]["fused"]["rounds_per_sec"]
                     / recs[lo]["fused"]["rounds_per_sec"])
        out["claims"] = {
            "workload": "fixed global work (cut=3 client-heavy split)",
            "weak_scaling_efficiency": round(eff, 3),
            "weak_scaling_recovered": eff >= 1.0,
            "fused_shard_map_efficiency": round(fused_eff, 3),
            "no_pool_allgather": all(
                r.get("no_pool_allgather")
                and r.get("fused", {}).get("no_pool_allgather")
                for r in recs.values()),
            "compile_once": all(r["compile_count"] == 1
                                for r in recs.values()),
        }
        print(f"[device sweep] weak_scaling_efficiency={eff:.3f} "
              f"(devices {lo}->{hi}) fused={fused_eff:.3f} "
              f"no_pool_allgather={out['claims']['no_pool_allgather']}")
    return out


def run(smoke: bool = False) -> dict:
    if smoke:
        base = ExperimentConfig(task="image", rounds=1, n_clients=24,
                                attendance=0.25, batch=8, width=4, cut=2,
                                seed=0, eval_every=10**9)
        rounds, capacities = 8, (3, 6)
    else:
        base = ExperimentConfig(task="image", rounds=1, n_clients=60,
                                attendance=0.2, batch=16, width=8, cut=2,
                                seed=0, eval_every=10**9)
        rounds, capacities = 16, (4, 8, 16)
    result = {
        "backend": jax.default_backend(),
        "mode": "smoke" if smoke else "full",
        "config": {"n_clients": base.n_clients, "attendance": base.attendance,
                   "batch": base.batch, "width": base.width,
                   "rounds_timed": rounds},
        "algos": {},
    }
    for algo in ALGOS:
        result["algos"][algo] = bench_algo(algo, base, rounds, capacities)
        c = result["algos"][algo]["claims"]
        fx = result["algos"][algo]["fixed_size_comparison"]
        print(f"[{algo}] compile_once={c['compile_once']} "
              f"padded_ms={fx['padded_steady_ms']} "
              f"unpadded_ms={fx['unpadded_steady_ms']} "
              f"ratio={fx['padded_over_unpadded']} "
              f"unpadded_variable_compiles="
              f"{result['algos'][algo]['unpadded_variable']['compile_count']}")
    return result


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI")
    ap.add_argument("--out", default="BENCH_round_latency.json")
    ap.add_argument("--devices", default=None,
                    help="comma-separated device counts for the sharded "
                         "Engine sweep, e.g. 1,2,4,8 (one subprocess per "
                         "count)")
    ap.add_argument("--pipeline", action="store_true",
                    help="also sweep the pipelined scheduler: rounds/sec "
                         "with pipeline_depth off vs sync vs async at "
                         "each ring depth in --pipeline-depths")
    ap.add_argument("--pipeline-depths", default="0,1,2,4",
                    help="comma-separated ring depths for the pipeline "
                         "sweep (0 = scheduler off)")
    ap.add_argument("--sweep-only", action="store_true",
                    help="skip the per-algorithm base benchmark and run "
                         "only the requested sweeps (the CI scaling leg "
                         "wants just the device sweep + its claims)")
    ap.add_argument("--shard-local", nargs="?", const="1,8", default=None,
                    help="also sweep the shard-local resample off vs on "
                         "at these device counts (default 1,8; one "
                         "subprocess per count)")
    ap.add_argument("--sweep-worker", type=int, default=None,
                    help=argparse.SUPPRESS)     # internal: one sweep point
    ap.add_argument("--shard-local-worker", type=int, default=None,
                    help=argparse.SUPPRESS)     # internal: one sweep point
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.sweep_worker is not None:
        print(json.dumps(sweep_worker(args.sweep_worker, args.smoke)))
        return {}
    if args.shard_local_worker is not None:
        print(json.dumps(shard_local_worker(args.shard_local_worker,
                                            args.smoke)))
        return {}
    # the per-device-count children run first: a parent that has touched
    # JAX holds the accelerator, and a child could then not open it
    sweeps = {}
    if args.devices:
        sweeps["device_sweep"] = device_sweep(
            [int(x) for x in args.devices.split(",")], args.smoke)
    if args.shard_local:
        sweeps["shard_local"] = shard_local_sweep(
            [int(x) for x in args.shard_local.split(",")], args.smoke)
    if args.sweep_only:
        result = {"backend": child_backend(*sweeps.values()),
                  "mode": "smoke" if args.smoke else "full"}
    else:
        result = run(smoke=args.smoke)
    if args.pipeline:
        result["pipeline_comparison"] = pipeline_sweep(
            args.smoke,
            tuple(int(x) for x in args.pipeline_depths.split(",")))
    result.update(sweeps)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
