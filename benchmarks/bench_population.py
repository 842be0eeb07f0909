"""Population-scale scenario benchmark: 100k+ simulated clients, one
sharded server, heterogeneous/unreliable cohorts.

Each measurement drives the unmodified Engine over a
:class:`repro.scenario.population.PopulationFed` fleet (clients are
lazily materialized from a ``(seed, id)`` fold-in, so N=100 000 costs
nothing up front) under one churn scenario:

* ``no_churn``        — the null scenario (kind='none'): the baseline
                        every delta is taken against.
* ``dropout``         — uniform profiles, 15% per-round hazard: slots
                        drop MID-round (mask zeroed before ServerUpdate
                        consumes their features, commit skipped).
* ``straggler``       — pareto-straggler profiles: heavy-tailed compute,
                        lag beyond the staleness bound = deadline drop.
* ``straggler_async`` — same fleet under the async pipelined schedule,
                        where in-bound stragglers deliver against the
                        one-round-stale snapshot (realized lag <= 1).

Per scenario: rounds/sec (Engine collect_timing — device-synced, compile
round excluded), final eval accuracy + delta vs no_churn, churn
telemetry aggregates, and the compile-once claim (trace_count must stay
1 — churn is data through the attendance mask, never a retrace).

The device sweep mirrors bench_round: one fresh subprocess per count
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and an
``(N, 1)`` ('data', 'model') mesh.  Writes ``BENCH_population.json``
(CI runs ``--smoke --devices 1,8`` and uploads the artifact).

  PYTHONPATH=src python benchmarks/bench_population.py [--smoke]
      [--devices 1,8] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax

from repro.scenario.population import PopulationSpec, run_population
from repro.scenario.profiles import ScenarioConfig

N_CLIENTS = 100_000
COHORT = 32                 # divides every forced device count (1, 2, 4, 8)
BATCH = 8

SCENARIOS = {
    "no_churn": (ScenarioConfig(), {}),
    "dropout": (ScenarioConfig(kind="uniform", dropout=0.15), {}),
    "straggler": (ScenarioConfig(kind="pareto-straggler", straggler=1.0,
                                 staleness_bound=1), {}),
    "straggler_async": (ScenarioConfig(kind="pareto-straggler", straggler=1.0,
                                       staleness_bound=1),
                        {"pipeline_depth": 1, "pipeline_staleness": "async"}),
}


def population_worker(n_devices: int, smoke: bool) -> dict:
    """All scenarios at the CURRENT process's device count (the mesh is
    (N, 1) over ('data', 'model'); N=1 is the bit-for-bit unsharded
    baseline)."""
    rounds = 6 if smoke else 12
    spec = PopulationSpec(n_clients=N_CLIENTS)
    mesh = dict(mesh_shape=(n_devices, 1), mesh_axes=("data", "model"))
    rows, base_acc = {}, None
    for name, (scenario, overrides) in SCENARIOS.items():
        res = run_population(spec, scenario, cohort=COHORT, rounds=rounds,
                             batch=BATCH, **mesh, **overrides)
        acc = res["history"][-1]["accuracy"]
        if name == "no_churn":
            base_acc = acc
        tel = res.get("telemetry", {})
        rows[name] = {
            "rounds_per_sec": round(1.0 / res["round_time_s"], 2),
            "steady_ms": round(res["round_time_s"] * 1e3, 3),
            "final_accuracy": round(acc, 4),
            "accuracy_delta_vs_no_churn": round(acc - base_acc, 4),
            "trace_count": res["population"]["trace_count"],
            "clients_materialized": res["population"]["clients_materialized"],
            "live_cohort_mean": tel.get("live_cohort_mean"),
            "dropped_total": tel.get("dropped_total"),
            "drop_hazard_total": tel.get("drop_hazard_total"),
            "drop_deadline_total": tel.get("drop_deadline_total"),
            "max_realized_lag": tel.get("max_realized_lag"),
            "max_drawn_lag": tel.get("max_drawn_lag"),
        }
    return {
        "backend": jax.default_backend(),
        "devices": n_devices,
        "jax_device_count": jax.device_count(),
        "n_clients": N_CLIENTS,
        "cohort_capacity": COHORT,
        "rounds": rounds,
        "scenarios": rows,
        "claims": {
            "compile_once_under_churn": all(
                r["trace_count"] == 1 for r in rows.values()),
            "lazy_fleet": max(r["clients_materialized"]
                              for r in rows.values()) <= COHORT * rounds * 2,
            "async_lag_bounded":
                rows["straggler_async"]["max_realized_lag"] <= 1,
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
               "--population-worker", str(n)]
        if smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if proc.returncode != 0:
            out[str(n)] = {"error": proc.stderr[-2000:]}
            continue
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        out[str(n)] = rec
        for name, row in rec["scenarios"].items():
            print(f"[devices={n} {name}] "
                  f"rps={row['rounds_per_sec']} "
                  f"acc={row['final_accuracy']} "
                  f"(d={row['accuracy_delta_vs_no_churn']:+.4f}) "
                  f"dropped={row['dropped_total']} "
                  f"traces={row['trace_count']}")
    return out


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer rounds for CI (the fleet stays 100k)")
    ap.add_argument("--out", default="BENCH_population.json")
    ap.add_argument("--devices", default="1,8",
                    help="comma-separated forced-host device counts "
                         "(one subprocess per count)")
    ap.add_argument("--population-worker", type=int, default=None,
                    help=argparse.SUPPRESS)     # internal: one sweep point
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.population_worker is not None:
        print(json.dumps(population_worker(args.population_worker,
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
        "cohort": COHORT,
        "batch": BATCH,
        "device_sweep": sweep,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
