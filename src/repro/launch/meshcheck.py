"""Mesh-invariance checker: the sharded round must not change numerics.

For EVERY registered algorithm this driver runs the same padded rounds
(fixed capacity, varying live cohort sizes) three ways and compares:

  base   — the classic unsharded jitted round,
  mesh1  — a 1-device (1, 1) mesh: must match ``base`` BIT-FOR-BIT
           (sharding constraints pin layout, never values),
  meshN  — a forced N-device host mesh (N, 1) over ('data', 'model'):
           must match within float tolerance (cross-device psum
           reduction trees reorder float32 sums at ~1e-7) and must
           trace exactly ONCE across the varying cohort sizes.

Run as a subprocess so the forced host device count binds before jax
initializes (tests/test_mesh.py drives it this way; CI runs the whole
tier-1 suite under the same flag):

  PYTHONPATH=src python -m repro.launch.meshcheck --devices 8

Exit code 0 = every algorithm passed; the JSON report goes to stdout.
"""
import os
import sys


def _cli_devices(argv) -> int:
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return 8


if __name__ == "__main__":
    # must bind before the jax import below — jax locks the device count
    # at first initialization (same trick as launch/dryrun.py); appended
    # so inherited XLA flags survive (last device-count occurrence wins)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count="
        f"{_cli_devices(sys.argv[1:])}").strip()

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import PROGRAMS, build_algorithm, get_program
from repro.api.phases import build_pipelined_algorithm
from repro.core.cyclesl import CycleConfig
from repro.core.split import make_stage_task
from repro.launch.mesh import auto_mesh
from repro.models.cnn import mlp
from repro.optim import adam
from repro.sharding.specs import batch_spec, train_state_shardings

C, B, ROUNDS = 8, 8, 3          # capacity 8 divides every swept mesh


def _task_and_data():
    task = make_stage_task(mlp(8, [16], 4), cut=1, kind="xent")
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8, 4))
    xs = np.stack([rng.normal(size=(B, 8))
                   for _ in range(C)]).astype(np.float32)
    ys = np.argmax(xs @ w, axis=-1)
    return task, jnp.asarray(xs), jnp.asarray(ys)


def _masks(rounds: int = ROUNDS):
    """Varying live cohort sizes at fixed capacity (the compile-once
    stream the Engine produces under variable attendance)."""
    return [jnp.asarray((np.arange(C) < 5 + r % 3).astype(np.float32))
            for r in range(rounds)]


def _place(x, mesh):
    from jax.sharding import NamedSharding
    return jax.device_put(
        x, NamedSharding(mesh, batch_spec(mesh, x.shape[0], x.ndim - 1)))


def _drive(name, task, xs, ys, mesh=None, rounds: int = ROUNDS,
           shard_local: bool = False, pipelined: bool = False):
    """Run ``rounds`` padded rounds of one algorithm (optionally on a
    mesh with full TrainState/input placement) and return
    ``(state, metric rows, trace count)``.  tests/test_mesh.py reuses
    this so the in-process goldens and this subprocess checker drive the
    exact same protocol.

    ``shard_local`` turns on ``CycleConfig.shard_local_resample`` (the
    shard_map resample path); ``pipelined`` drives the (extract, tail)
    dispatch pair in sync-barrier order instead of the monolithic round
    (returns ``None`` for the fused sequential programs, which have no
    ExtractFeatures head to split on)."""
    opt = adam(5e-3)
    program = get_program(name)
    kw = {}
    if mesh is not None:
        a_state = jax.eval_shape(
            lambda: build_algorithm(program, task, opt, opt).init(
                jax.random.PRNGKey(0), C))
        kw = dict(mesh=mesh,
                  state_shardings=train_state_shardings(a_state, mesh))
    ccfg = CycleConfig(server_epochs=2, shard_local_resample=shard_local)
    if pipelined:
        algo = build_pipelined_algorithm(program, task, opt, opt, ccfg, **kw)
        if algo is None:
            return None
    else:
        algo = build_algorithm(program, task, opt, opt, ccfg, **kw)
    state = algo.init(jax.random.PRNGKey(0), n_clients=C)
    cohort = jnp.arange(C)
    if mesh is not None:
        state = jax.device_put(state, kw["state_shardings"])
        cohort, xs, ys = (_place(v, mesh) for v in (cohort, xs, ys))
    rows = []
    for r, mask in enumerate(_masks(rounds)):
        m = _place(mask, mesh) if mesh is not None else mask
        if pipelined:
            stage = algo.extract(state, cohort, xs, ys, m)
            state, mets = algo.tail(state, cohort, xs, ys,
                                    jax.random.PRNGKey(r), stage, m)
        else:
            state, mets = algo.round(state, cohort, xs, ys,
                                     jax.random.PRNGKey(r), m)
        rows.append({k: np.asarray(v) for k, v in mets.items()})
    return state, rows, algo.trace_count


def _max_diff(a_state, a_rows, b_state, b_rows) -> float:
    d = 0.0
    for la, lb in zip(jax.tree.leaves(a_state), jax.tree.leaves(b_state)):
        d = max(d, float(np.max(np.abs(np.asarray(la, np.float64)
                                       - np.asarray(lb, np.float64)))))
    for ra, rb in zip(a_rows, b_rows):
        for k in ra:
            d = max(d, float(np.max(np.abs(ra[k].astype(np.float64)
                                           - rb[k].astype(np.float64)))))
    return d


def check_algorithm(name, task, xs, ys, meshN, tol: float) -> dict:
    base_state, base_rows, _ = _drive(name, task, xs, ys)
    mesh1 = auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    s1, r1, _ = _drive(name, task, xs, ys, mesh1)
    sN, rN, traces = _drive(name, task, xs, ys, meshN)
    d1 = _max_diff(base_state, base_rows, s1, r1)
    dN = _max_diff(base_state, base_rows, sN, rN)
    rec = {"exact_1dev_diff": d1, "ndev_diff": dN, "ndev_traces": traces,
           "ok": bool(d1 == 0.0 and dN <= tol and traces == 1)}
    return rec


def check_shard_local(name, task, xs, ys, meshes) -> dict:
    """The shard-local acceptance golden: on every mesh, for both the
    monolithic round and the pipelined (extract, tail) schedule, the
    ``shard_local_resample`` path must be BIT-FOR-BIT the GSPMD
    gather-around-the-kernel path and still trace once per dispatch
    (the shard_map wrapper must not retrace across live cohort sizes).
    Non-cycle algorithms never touch the resample, so their equality is
    trivially exact — running them all pins that the knob is inert
    where it should be."""
    rec = {"ok": True}
    for label, mesh in meshes:
        for pipelined in (False, True):
            base = _drive(name, task, xs, ys, mesh, shard_local=False,
                          pipelined=pipelined)
            if base is None:        # fused sequential program: no split
                continue
            on = _drive(name, task, xs, ys, mesh, shard_local=True,
                        pipelined=pipelined)
            d = _max_diff(base[0], base[1], on[0], on[1])
            traces = on[2]
            budget = 2 if pipelined else 1
            key = f"{label}{'_pipelined' if pipelined else ''}"
            rec[key] = {"diff": d, "traces": traces}
            rec["ok"] = rec["ok"] and d == 0.0 and traces == budget
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--algos", default=None,
                    help="comma list (default: every registered algorithm)")
    ap.add_argument("--tol", type=float, default=1e-5,
                    help="max abs diff tolerated for the N-device mesh "
                         "(cross-device reduction reorder noise)")
    ap.add_argument("--shard-local", action="store_true",
                    help="run the shard-local-vs-GSPMD resample golden "
                         "instead of the sharded-vs-unsharded sweep")
    args = ap.parse_args()
    n = args.devices
    if jax.device_count() < n:
        print(json.dumps({"error": f"needs {n} devices, have "
                          f"{jax.device_count()} (run via python -m, the "
                          "__main__ guard forces the host device count)"}))
        return 2
    meshN = auto_mesh((n, 1), ("data", "model"), jax.devices()[:n])
    task, xs, ys = _task_and_data()
    algos = (args.algos.split(",") if args.algos else sorted(PROGRAMS))
    report = {"devices": n, "capacity": C, "rounds": ROUNDS, "algos": {}}
    if args.shard_local:
        mesh1 = auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])
        meshes = [("1dev", mesh1), (f"{n}dev", meshN)]
        report["mode"] = "shard_local"
        for name in algos:
            report["algos"][name] = check_shard_local(name, task, xs, ys,
                                                      meshes)
    else:
        for name in algos:
            report["algos"][name] = check_algorithm(name, task, xs, ys,
                                                    meshN, args.tol)
    report["ok"] = all(a["ok"] for a in report["algos"].values())
    print(json.dumps(report, indent=1))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
