"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Derives the three roofline terms per (arch × shape × mesh) from
``benchmarks/results/dryrun.json``:

    compute    = HLO_FLOPs_global    / (chips × peak FLOP/s bf16)
    memory     = HLO_bytes_global    / (chips × peak HBM B/s)
    collective = collective_bytes    / (chips × ICI B/s per link)

with the peaks of the record's ``device_kind`` looked up in
:data:`PEAKS`; a device that is not in the table raises.

Calibration notes (verified empirically in tests/test_roofline.py):
  * ``compiled.cost_analysis()`` on an SPMD-partitioned module reports
    *per-device* FLOPs/bytes, so globals = per-device × chips.
  * XLA counts a while/scan body ONCE regardless of trip count — fatal
    for scan-over-layers models.  The dry-run therefore records a
    loop-aware cost model (``repro.utils.hlo_cost``) that parses the
    optimized HLO, multiplies per-computation dot-FLOPs / HBM-boundary
    traffic / collective operand bytes by the product of enclosing
    ``known_trip_count``s, and is exact on nested-scan calibration
    cases.  Those numbers (also per-device) feed the terms below.

Also reports MODEL_FLOPS = 6·N·D (6·N_active·D for MoE) and the
usefulness ratio MODEL_FLOPS / HLO_FLOPs (catches remat/dispatch waste).

Usage:
  PYTHONPATH=src python -m repro.launch.roofline [--in dryrun.json] [--md]
"""
from __future__ import annotations

import argparse
import json

from repro.configs import INPUT_SHAPES
from repro.configs.registry import get_config

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s of interconnect (4 links x 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; an unknown device is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def model_flops(arch: str, shape_name: str) -> float:
    """6·N·D for train (fwd+bwd), 2·N·D for inference; MoE uses N_active.
    whisper: the decoder horizon is 448 and the encoder runs over 1500
    stub frames, so effective tokens = B·(448 + 1500) (coarse)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    n = cfg.n_active_params()
    if cfg.family == "audio":
        tokens = shape.global_batch * (min(shape.seq_len, 448) + 1500)
    else:
        tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def analyze_record(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    chips = rec.get("n_devices", 256)
    la = rec.get("loop_aware", {})
    flops_dev = la.get("flops") or rec.get("cost", {}).get("flops", 0.0)
    bytes_dev = (la.get("traffic_bytes")
                 or rec.get("cost", {}).get("bytes accessed", 0.0))
    # collective bytes: loop-aware number is per-device operand bytes
    coll_dev = (la.get("collective_bytes")
                or rec.get("collectives", {}).get("total_bytes", 0))
    peak = peaks(rec["device_kind"])
    flops_glob = flops_dev * chips
    bytes_glob = bytes_dev * chips
    t_compute = flops_glob / (chips * peak["flops"])
    t_memory = bytes_glob / (chips * peak["hbm_bytes_per_s"])
    t_coll = coll_dev / peak["ici_bytes_per_s_per_link"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    return {
        **{f"t_{k}_s": v for k, v in terms.items()},
        "dominant": dominant,
        "hlo_flops_global": flops_glob,
        "hlo_bytes_global": bytes_glob,
        "collective_bytes_per_dev": coll_dev,
        "model_flops": mf,
        "useful_ratio": (mf / flops_glob) if flops_glob else float("nan"),
        "chips": chips,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inp", default="benchmarks/results/dryrun.json")
    ap.add_argument("--out", default="benchmarks/results/roofline.json")
    ap.add_argument("--md", action="store_true", help="print markdown table")
    args = ap.parse_args()

    with open(args.inp) as f:
        records = json.load(f)
    rows = []
    for rec in sorted(records, key=lambda r: (r["mesh"], r["arch"], r["shape"])):
        a = analyze_record(rec)
        if a is None:
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": rec["mesh"], "status": rec["status"],
                         "reason": rec.get("reason", rec.get("error", ""))[:80]})
            continue
        rows.append({"arch": rec["arch"], "shape": rec["shape"],
                     "mesh": rec["mesh"], "status": "ok", **a})
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)

    if args.md:
        hdr = ("| arch | shape | mesh | compute s | memory s | coll s | "
               "dominant | useful |")
        print(hdr)
        print("|" + "---|" * 8)
        for r in rows:
            if r["status"] != "ok":
                print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — "
                      f"| {r['status']}: {r.get('reason','')} | — |")
                continue
            print(f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                  f"| {r['t_compute_s']:.4f} | {r['t_memory_s']:.4f} "
                  f"| {r['t_collective_s']:.4f} | {r['dominant']} "
                  f"| {r['useful_ratio']:.2f} |")
    print(f"wrote {args.out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
