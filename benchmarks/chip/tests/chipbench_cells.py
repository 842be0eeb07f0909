"""Tiny copies of the benchmark's cells for the CPU tests: width 4, 10
classes, 3 of 12 clients a round, batch 8; every other setting is the
cell's own."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parents[1]
for p in (str(BENCH), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    c = cell.config
    kwargs = dict(c["program_model"]["kwargs"], width=4, n_classes=10)
    config = dict(c, width=4, n_classes=10,
                  program_model=dict(c["program_model"], kwargs=kwargs),
                  samples_per_client=10,
                  population=dict(c["population"], n_clients=12,
                                  test_per_client=1))
    traffic = dict(cell.traffic, attendance=0.25, batch=8, server_batch=8)
    return harness.Cell(f"tiny.{name}", 1, config, traffic, cell.limits,
                        cell.model, cell.end_to_end, cell.per_layer)


CELLS = [w["name"] for w in
         harness.read_json(CHECKOUT / "BENCHMARK.json")["workloads"]]
