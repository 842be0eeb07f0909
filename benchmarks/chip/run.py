#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
      --seconds <s> --trace <0|1>

The cell, its configuration, traffic, limits and per-layer metrics are
found by name (``BENCHMARK.json``, ``benchmarks/chip/``).  The program
under test is ``src/repro`` of the same checkout.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``: each number that decided ``correct``
beside its limit, which also end standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no
result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"run.py: no program under {CHECKOUT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    from chipbench import harness
    cell = harness.load_cell(args.workload)
    try:
        harness.check_devices(cell.chips)
    except SystemExit as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
