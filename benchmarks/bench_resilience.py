"""Resilience runtime benchmark: guard overhead + recovery latency.

Each measurement drives the unmodified Engine (synthetic image task,
padded cohorts) under one resilience scenario:

* ``guard_off``      — the null config: the baseline every delta is
                       taken against (no guard phase, no controller).
* ``guard_on``       — in-trace health guards armed, no faults: the
                       steady-state cost of the checks themselves (one
                       [4]-vector host sync per round; history must stay
                       bit-for-bit the guard_off run's).
* ``nan_quarantine`` — persistent poisoned deliveries (NaN features
                       every attempt): only excising the blamed slot via
                       the attendance mask saves the round.
* ``nan_retry``      — transient NaN deliveries recovered by re-running
                       the round from its pre-round state.
* ``nan_rollback``   — same faults recovered from the last-good
                       snapshot ring.
* ``dispatch_error`` — injected dispatch exceptions (preempted host)
                       absorbed by the retry policy, guard OFF — the
                       controller alone handles them.

Per scenario: rounds/sec (Engine collect_timing — device-synced, compile
round excluded), recovery latency per faulted round (mean round time
minus the guard_on baseline, amortized over the rounds that needed
recovery), telemetry totals, and the claims block (guard-on history
bit-for-bit, one trace per run, every faulted run completed).

The device sweep mirrors bench_population: one fresh subprocess per
count with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and an
``(N, 1)`` ('data', 'model') mesh.  Writes ``BENCH_resilience.json``
(CI runs ``--smoke --devices 1,8`` and uploads the artifact).

  PYTHONPATH=src python benchmarks/bench_resilience.py [--smoke]
      [--devices 1,8] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

N_CLIENTS = 32
ATTENDANCE = 0.25           # capacity 8: divides every forced count
BATCH = 8

def _scenarios():
    # built lazily: the worker imports repro only after XLA_FLAGS bind
    from repro.resilience import FaultConfig, ResilienceConfig
    return {
        "guard_off": ResilienceConfig(),
        "guard_on": ResilienceConfig(guard=True),
        "nan_quarantine": ResilienceConfig(
            guard=True, on_nonfinite="quarantine",
            faults=FaultConfig(nan_rate=0.3, persist=10)),
        "nan_retry": ResilienceConfig(
            guard=True, on_nonfinite="retry",
            faults=FaultConfig(nan_rate=0.3)),
        "nan_rollback": ResilienceConfig(
            guard=True, on_nonfinite="rollback",
            faults=FaultConfig(nan_rate=0.3)),
        "dispatch_error": ResilienceConfig(
            faults=FaultConfig(error_rate=0.3)),
    }


def resilience_worker(n_devices: int, smoke: bool) -> dict:
    """All scenarios at the CURRENT process's device count."""
    import jax

    from repro.api import Engine, ExperimentConfig

    rounds = 8 if smoke else 24
    rows, base = {}, {}
    for name, rcfg in _scenarios().items():
        cfg = ExperimentConfig(
            algo="cyclesfl", task="image", rounds=rounds,
            n_clients=N_CLIENTS, attendance=ATTENDANCE, min_cohort=2,
            batch=BATCH, eval_every=rounds, width=16, cut=1, seed=0,
            collect_timing=True, mesh_shape=(n_devices, 1),
            mesh_axes=("data", "model"), resilience=rcfg)
        eng = Engine(cfg, log=lambda *a: None)
        res = eng.run()
        tel = res.get("resilience", {})
        rt = res["round_time_s"]
        if name in ("guard_off", "guard_on"):
            base[name] = {"rt": rt, "history": [
                {k: v for k, v in r.items() if k != "elapsed_s"}
                for r in res["history"]]}
        faulted = tel.get("faulted_rounds", 0)
        # extra wall-clock the recovery work cost, amortized over the
        # rounds that needed it (vs the armed-but-clean baseline)
        lat = (None if not faulted or "guard_on" not in base
               else max(0.0, (rt - base["guard_on"]["rt"]) * rounds
                        / faulted))
        rows[name] = {
            "rounds_per_sec": round(1.0 / rt, 2),
            "steady_ms": round(rt * 1e3, 3),
            "recovery_latency_ms_per_faulted_round":
                None if lat is None else round(lat * 1e3, 3),
            "faulted_rounds": faulted,
            "retries": tel.get("retries", 0),
            "rollbacks": tel.get("rollbacks", 0),
            "quarantine_events": tel.get("quarantine_events", 0),
            "quarantined_clients": len(tel.get("quarantined_clients", [])),
            "trace_count": eng.algo.trace_count,
        }
    off, on = base["guard_off"], base["guard_on"]
    return {
        "backend": jax.default_backend(),
        "devices": n_devices,
        "jax_device_count": jax.device_count(),
        "rounds": rounds,
        "scenarios": rows,
        "guard_overhead_pct": round(
            (off["rt"] and (on["rt"] - off["rt"]) / off["rt"]) * 100, 2),
        "claims": {
            "guard_on_bit_for_bit": on["history"] == off["history"],
            "compile_once": all(r["trace_count"] == 1
                                for r in rows.values()),
            "all_faulted_runs_recovered": all(
                r["faulted_rounds"] > 0 for n, r in rows.items()
                if n not in ("guard_off", "guard_on")),
        },
    }


def device_sweep(devices: list[int], smoke: bool) -> dict:
    """One fresh subprocess per device count (XLA_FLAGS must bind before
    jax initializes); the worker's JSON record is the last stdout line."""
    out = {}
    for n in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        cmd = [sys.executable, os.path.abspath(__file__),
               "--resilience-worker", str(n)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if proc.returncode != 0:
            out[str(n)] = {"error": proc.stderr[-2000:]}
            continue
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        out[str(n)] = rec
        print(f"[devices={n}] guard overhead "
              f"{rec['guard_overhead_pct']:+.2f}% claims={rec['claims']}")
        for name, row in rec["scenarios"].items():
            print(f"[devices={n} {name}] rps={row['rounds_per_sec']} "
                  f"faulted={row['faulted_rounds']} "
                  f"lat_ms={row['recovery_latency_ms_per_faulted_round']} "
                  f"traces={row['trace_count']}")
    return out


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer rounds for CI")
    ap.add_argument("--out", default="BENCH_resilience.json")
    ap.add_argument("--devices", default="1,8",
                    help="comma-separated forced-host device counts "
                         "(one subprocess per count)")
    ap.add_argument("--resilience-worker", type=int, default=None,
                    help=argparse.SUPPRESS)     # internal: one sweep point
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.resilience_worker is not None:
        print(json.dumps(resilience_worker(args.resilience_worker,
                                           args.smoke)))
        return {}
    # the parent never initializes JAX (it would hold the accelerator
    # the children need); the backend comes from the first child's record
    sweep = device_sweep([int(x) for x in args.devices.split(",")],
                         args.smoke)
    result = {
        "backend": next((r["backend"] for r in sweep.values()
                         if "backend" in r), None),
        "mode": "smoke" if args.smoke else "full",
        "n_clients": N_CLIENTS,
        "attendance": ATTENDANCE,
        "batch": BATCH,
        "device_sweep": sweep,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
