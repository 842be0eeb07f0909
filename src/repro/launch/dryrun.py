import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the two lines above MUST run before any other import (jax locks
# the device count at first initialization) — do not move or reorder.

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

For each combination this script:
  1. builds the production mesh (16,16) single-pod / (2,16,16) multi-pod,
  2. builds the right step (CycleSL train round / prefill / decode),
  3. ``jit(...).lower(...).compile()`` with ShapeDtypeStruct inputs only,
  4. records memory_analysis / cost_analysis / collective bytes parsed
     from the optimized HLO into benchmarks/results/dryrun.json.

Failures here are bugs in the sharding/distribution config, per the
deliverable contract.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import INPUT_SHAPES
from repro.configs.registry import get_config, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.utils import hlo, hlo_cost

# long_500k applicability (DESIGN.md §5): whisper is skipped outright;
# full-attention archs run their documented sliding-window serving
# variant (long_context=True), SSM/hybrid run natively.
LONG_SKIP = {"whisper-base": "enc-dec, 448-pos decoder horizon; full attn"}

# the chip the production meshes stand for (launch/mesh.py); the forced
# host devices only stand in for it, so records name it explicitly and
# launch/roofline.py reads its peaks from the record
TARGET_DEVICE_KIND = "TPU v5 lite"


def _mem_stats(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                out[attr] = int(v)
    except Exception as e:  # noqa: BLE001
        out["error"] = repr(e)
    return out


def _cost_stats(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:  # noqa: BLE001
        return {"error": repr(e)}


def run_one(arch: str, shape_name: str, multi_pod: bool,
            keep_hlo: bool = False, server_batch: int | None = None) -> dict:
    from repro.core.cyclesl import CycleConfig
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "device_kind": TARGET_DEVICE_KIND, "status": "ok"}
    if server_batch:
        rec["server_batch"] = server_batch
    if shape_name == "long_500k" and arch in LONG_SKIP:
        rec["status"] = "skipped"
        rec["reason"] = LONG_SKIP[arch]
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        bundle = build_step(cfg, mesh, shape,
                            cycle=CycleConfig(server_batch=server_batch))
        with mesh:
            jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                             out_shardings=bundle.out_shardings,
                             donate_argnums=bundle.donate)
            lowered = jitted.lower(*bundle.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        rec["step"] = bundle.name
        rec["lower_s"] = round(t_lower, 2)
        rec["compile_s"] = round(t_compile, 2)
        rec["memory"] = _mem_stats(compiled)
        rec["cost"] = _cost_stats(compiled)          # raw XLA (body-once)
        text = compiled.as_text()
        rec["collectives"] = hlo.collective_stats(text).summary()
        # loop-aware per-device cost model (trip-count-corrected)
        mc = hlo_cost.module_cost(text)
        rec["loop_aware"] = mc.summary()
        rec["n_devices"] = mesh.devices.size
        if keep_hlo:
            rec["hlo_len"] = len(text)
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = repr(e)
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*INPUT_SHAPES, None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun.json")
    ap.add_argument("--server-batch", type=int, default=None,
                    help="CycleSL server inner-loop batch (perf knob)")
    ap.add_argument("--resume", action="store_true",
                    help="skip combos already ok in --out")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    done = set()
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
        done = {(r["arch"], r["shape"], r["mesh"]) for r in results
                if r["status"] in ("ok", "skipped")}

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for mp in meshes:
        mesh_name = "2x16x16" if mp else "16x16"
        for arch in archs:
            for shape in shapes:
                if (arch, shape, mesh_name) in done:
                    continue
                rec = run_one(arch, shape, mp, server_batch=args.server_batch)
                results = [r for r in results
                           if not (r["arch"] == arch and r["shape"] == shape
                                   and r["mesh"] == mesh_name)]
                results.append(rec)
                flops = rec.get("cost", {}).get("flops", float("nan"))
                print(f"[{rec['status']:7s}] {mesh_name} {arch:22s} "
                      f"{shape:12s} {rec.get('total_s', 0):7.1f}s "
                      f"flops={flops:.3e} "
                      f"coll={rec.get('collectives', {}).get('total_bytes', 0):.3e}",
                      flush=True)
                if rec["status"] == "error":
                    print(rec["error"], flush=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors -> {args.out}")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
