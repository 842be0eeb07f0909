#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, the control and a
planted fault, over many seeds in one process.

  python3 benchmarks/chip/calibrate.py --workload <name> \\
      --seeds 11,12,13 [--control-seeds 11,12,13] [--out readings.jsonl]

For every seed the program runs through set-up as a benchmark run
does (its first three rounds are the compared steps, then one round of
window) and the reference follows those rounds: ``program`` is
``compare(program, reference)``.  For the control seeds also:

  ``control``     the reference in the program's place, computed in the
                  precision below the configuration's
                  (``harness.control_kwargs``)
  ``half_batch``  the reference with every batch mean taken over the
                  first half of the rows (half of the batch left out)

A step that returns its state unchanged reads 1 in ``change`` by
construction and needs no run.  One JSON line per seed goes to stdout
and to ``--out``.  Runs on the chip only, like ``run.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def readings(cell, seed: int, control: bool, witness: bool = False) -> dict:
    import jax

    from chipbench import harness
    t0 = time.perf_counter()
    _, prog, rounds, theta0 = harness.measure(cell, seed, 0.0, False, t0)
    ref = harness.reference_readings(cell, theta0, rounds)
    out = {"seed": seed, "program": harness.compare(prog, ref),
           "program_loss": prog["loss"], "reference_loss": ref["loss"]}
    if witness:
        # the reference at the program's own (default) matmul precision
        dflt = harness.reference_readings(
            cell, theta0, rounds, precision=jax.lax.Precision.DEFAULT)
        out["program_vs_default_ref"] = harness.compare(prog, dflt)
        out["default_ref"] = harness.compare(dflt, ref)
    if control:
        ctl = harness.reference_readings(cell, theta0, rounds,
                                         **harness.control_kwargs(cell))
        out["control"] = harness.compare(ctl, ref)
        out["half_batch"] = harness.compare(
            harness.reference_readings(cell, theta0, rounds, half=True), ref)
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    try:
        harness.check_devices(cell.chips)
    except SystemExit as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        line = json.dumps(readings(cell, seed, seed in ctl,
                                   bool(args.witness)))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
