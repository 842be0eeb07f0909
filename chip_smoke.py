#!/usr/bin/env python3
"""Smoke run of the CycleSL training round on a TPU.

Drives the main training path once, through ``repro.api.Engine``, at
the published width of the paper's FEMNIST CNN (``femnist_cnn(width=
32)``: conv 32, conv 64, fc 2048, 62 classes) on the synthetic ``image``
task.  Data and weights are made from ``--seed``; nothing is downloaded
or read from disk.

One chip (the default), three phases, one result line each:

  kernels   the Pallas kernels of the round at its shapes, compiled, vs
            their references: ``ops.resample_rows`` vs ``jnp.take``;
            ``ops.gather_loss_microbatch`` vs ``ref.py``; ``ops.
            fused_adam`` vs ``ref.py`` and the jnp Adam.
  cyclesfl  ``Engine(...).run()`` for 5 rounds; every round's server
            loss must be finite, and the compiled round must hold
            ``tpu_custom_call`` (the kernels ran compiled).
  cyclepsl  the same for cyclepsl (per-client stack, slot shard_map).

``--chips 4`` runs only the cyclesfl configuration on a (4, 1) mesh
with the cohort sharded and the shard-local resample on, and the same
configuration on a (1, 1) mesh; per-round losses must agree within
``MESH_RTOL`` and the round's cohort stack must be spread over the four
devices.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any
failed phase, or a backend other than TPU, exits non-zero without it.

  python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# the round configuration every Engine phase drives: the paper's FEMNIST
# CNN at its published width, 20 of 100 clients per round
ROUND_CFG = dict(task="image", width=32, cut=2, n_clients=100,
                 attendance=0.2, batch=32, rounds=5, eval_every=5)
KERNEL_TOL = {"resample_rows": 0.0, "gather_loss_vs_f64": 1e-4,
              "gather_loss_vs_ref_py": 1e-4,
              "fused_adam_vs_ref": 1e-6, "fused_adam_vs_jnp_adam": 1e-6}
MESH_RTOL = 1e-2            # 4-chip vs 1-chip per-round server loss


class PhaseError(RuntimeError):
    pass


def say(rec: dict):
    print(json.dumps(rec), flush=True)


def check(cond: bool, msg: str):
    if not cond:
        raise PhaseError(msg)


def compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


# ------------------------------------------------------------- kernels
def phase_kernels(seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.optim import adam
    from repro.optim.optimizer import apply_updates

    rng = np.random.default_rng(seed)
    f32 = lambda *s, scale=1.0: jnp.asarray(rng.normal(size=s) * scale,
                                            jnp.float32)
    errs, calls = {}, {}

    def maxerr(a, b) -> float:
        return float(np.max(np.abs(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64))))

    # resample gather over the pooled cut-2 features [T, 7*7*64]
    src = f32(512, 3136)
    idx = jnp.asarray(rng.integers(0, 512, size=64), jnp.int32)
    fn = jax.jit(ops.resample_rows)
    errs["resample_rows"] = maxerr(fn(src, idx), jnp.take(src, idx, axis=0))
    calls["resample_rows"] = compiled_text(fn, src, idx)

    # fused gather + head loss over the cut-3 features [T, 2048]
    src = f32(512, 2048)
    labels = jnp.asarray(rng.integers(0, 62, size=512), jnp.int32)
    w = f32(2048, 62, scale=0.03)
    fn = jax.jit(ops.gather_loss_microbatch)
    got = fn(src, labels, idx, w)
    f = np.asarray(src, np.float64)[np.asarray(idx)]
    logits = f @ np.asarray(w, np.float64)
    mx = logits.max(-1, keepdims=True)
    lse = (mx + np.log(np.exp(logits - mx).sum(-1, keepdims=True)))[:, 0]
    want = lse - logits[np.arange(64), np.asarray(labels)[np.asarray(idx)]]
    scale = max(1.0, float(np.max(np.abs(want))))
    errs["gather_loss_vs_f64"] = maxerr(got, want) / scale
    # the kernel's head matmul is float32 (HIGHEST); so is the oracle's here
    with jax.default_matmul_precision("highest"):
        want = ref.gather_loss_microbatch_ref(src, labels, idx, w)
    errs["gather_loss_vs_ref_py"] = maxerr(got, want) / scale
    calls["gather_loss"] = compiled_text(fn, src, labels, idx, w)

    # fused Adam over the server fc, the head and a client conv
    e_ref = e_jnp = 0.0
    opt = adam(1e-3, fused=False)
    for shape in ((3136, 2048), (62,), (5, 5, 1, 32)):
        p, g = f32(*shape), f32(*shape)
        m, v = f32(*shape, scale=0.1), jnp.abs(f32(*shape, scale=0.1))
        step = jnp.int32(3)
        fn = jax.jit(lambda p, g, m, v, s: ops.fused_adam(p, g, m, v, s,
                                                          lr=1e-3))
        p2, m2, v2 = fn(p, g, m, v, step)
        pr, mr, vr = ref.fused_adam_ref(p, g, m, v, step, lr=1e-3)
        e_ref = max(e_ref, maxerr(p2, pr), maxerr(m2, mr), maxerr(v2, vr))
        upd, st = opt.update(g, {"m": m, "v": v}, p, step)
        e_jnp = max(e_jnp, maxerr(p2, apply_updates(p, upd)),
                    maxerr(m2, st["m"]), maxerr(v2, st["v"]))
        calls[f"fused_adam{list(shape)}"] = compiled_text(fn, p, g, m, v,
                                                          step)
    errs["fused_adam_vs_ref"] = e_ref
    errs["fused_adam_vs_jnp_adam"] = e_jnp

    rec = {"max_err": errs, "tol": KERNEL_TOL,
           "tpu_custom_call": {k: t.count("tpu_custom_call")
                               for k, t in calls.items()}}
    for k, tol in KERNEL_TOL.items():
        check(errs[k] <= tol, f"{k}: max error {errs[k]} > {tol}")
    for k, n in rec["tpu_custom_call"].items():
        check(n > 0, f"{k}: no tpu_custom_call in the compiled program")
    return rec


# -------------------------------------------------------------- engine
class _Losses:
    """Engine callback: each round's server loss, and when round 0 ended."""

    def __init__(self):
        self.server_loss = []
        self.t_first = None

    def on_round(self, engine, rnd, state, metrics):
        self.server_loss.append(float(metrics["server_loss"]))
        if self.t_first is None:
            self.t_first = time.perf_counter()


def run_engine(algo: str, seed: int, **overrides):
    """One ``Engine.run()``; returns (engine, result, per-round losses,
    seconds to the end of the first round, seconds for the run)."""
    from repro.api import Engine, ExperimentConfig

    cfg = ExperimentConfig(algo=algo, seed=seed, **{**ROUND_CFG, **overrides})
    rec = _Losses()
    eng = Engine(cfg, callbacks=(rec,),
                 log=lambda *a, **k: print(*a, file=sys.stderr))
    t0 = time.perf_counter()
    res = eng.run()
    t1 = time.perf_counter()
    check(len(rec.server_loss) == cfg.rounds,
          f"{algo}: {len(rec.server_loss)} rounds ran, want {cfg.rounds}")
    check(all(math.isfinite(x) for x in rec.server_loss),
          f"{algo}: non-finite server loss {rec.server_loss}")
    return eng, res, rec.server_loss, rec.t_first - t0, t1 - t0


def round_program_text(eng, seed: int) -> str:
    """Compiled text of the Engine's jitted round at the run's shapes."""
    import numpy as np
    state = eng.init_state()
    cohort, xs, ys, mask = eng.sample_round(np.random.default_rng(seed))
    return compiled_text(eng.algo.round, state, cohort, xs, ys,
                         eng.round_key(0), mask)


def phase_engine(algo: str, seed: int) -> dict:
    eng, res, losses, t_first, t_run = run_engine(algo, seed)
    n_calls = round_program_text(eng, seed).count("tpu_custom_call")
    hist = res["history"][-1]
    check(n_calls > 0, f"{algo}: compiled round has no tpu_custom_call")
    check(math.isfinite(hist["test_loss"]),
          f"{algo}: non-finite test loss {hist['test_loss']}")
    return {"server_loss": losses,
            "test_loss": hist["test_loss"], "accuracy": hist.get("accuracy"),
            "trace_count": eng.algo.trace_count,
            "tpu_custom_call": n_calls,
            "setup_s_to_first_round": round(t_first, 3),
            "run_s": round(t_run, 3)}


def phase_mesh(seed: int) -> dict:
    """The cyclesfl configuration on a (4, 1) mesh vs a (1, 1) mesh."""
    from repro.core.cyclesl import CycleConfig

    mesh_kw = dict(shard_cohort=True,
                   cycle=CycleConfig(shard_local_resample=True))
    eng4, _, loss4, _, t4 = run_engine("cyclesfl", seed, mesh_shape=(4, 1),
                                       **mesh_kw)
    _, _, loss1, _, t1 = run_engine("cyclesfl", seed, mesh_shape=(1, 1),
                                    **mesh_kw)
    rel = max(abs(a - b) / max(abs(b), 1e-12) for a, b in zip(loss4, loss1))
    import numpy as np
    _, xs, _, _ = eng4.sample_round(np.random.default_rng(seed))
    rows = {}
    for shard in xs.addressable_shards:
        rows[shard.device.id] = rows.get(shard.device.id, 0) + int(
            shard.data.shape[0])
    rec = {"server_loss_4": loss4,
           "server_loss_1": loss1, "max_rel_diff": rel, "rtol": MESH_RTOL,
           "cohort_rows_per_device": rows, "cohort_rows": int(xs.shape[0]),
           "run_s_4": round(t4, 3), "run_s_1": round(t1, 3)}
    check(rel <= MESH_RTOL, f"4-chip vs 1-chip loss differs by {rel}")
    check(len(rows) == 4 and sum(rows.values()) == xs.shape[0],
          f"cohort stack not spread over 4 devices: {rows}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    phases = ([("mesh4_vs_mesh1", lambda: phase_mesh(args.seed))]
              if args.chips == 4 else
              [("kernels", lambda: phase_kernels(args.seed)),
               ("cyclesfl", lambda: phase_engine("cyclesfl", args.seed)),
               ("cyclepsl", lambda: phase_engine("cyclepsl", args.seed))])
    ok = True
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            rec = {"phase": name, **phase(), "ok": True}
        except Exception as e:  # noqa: BLE001 — report and fail the run
            import traceback
            traceback.print_exc()
            rec = {"phase": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        rec["phase_s"] = round(time.perf_counter() - t0, 3)
        say(rec)
    if not ok:
        return 1
    say({"ok": True, "device": {"platform": dev.platform,
                                "kind": dev.device_kind,
                                "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
