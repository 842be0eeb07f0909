"""Tiny copies of the benchmark's cells for the CPU tests: the
configuration's own ``tiny()``, 3 of 12 clients a round, batch 8; every
other setting is the cell's own.

Besides the cells of ``BENCHMARK.json``, the token fixture under
``toy_lm/`` (a dense decoder fed token ids, with dict-shaped weights)
is laid out as a later configuration's files would be.
``fixture_checkout`` writes it into a checkout of its own, whose
``BENCHMARK.json`` names its cells, and ``tiny_cell`` finds it there
through ``harness.load_cell``, as the harness finds any cell."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parents[1]
for p in (str(BENCH), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "toy_lm"
FIXTURE_CELLS = {"toy_lm.cyclesfl": "cyclesfl.cut1",
                 "toy_lm.cyclepsl": "cyclepsl.cut1"}
CELLS = [w["name"] for w in
         harness.read_json(CHECKOUT / "BENCHMARK.json")["workloads"]]


def fixture_checkout(root: Path) -> Path:
    """``root`` as a checkout that holds the fixture's files under
    ``benchmarks/chip`` and a ``BENCHMARK.json`` that names its cells
    (and the metrics of this one)."""
    bench = root / "benchmarks" / "chip"
    if not bench.exists():
        shutil.copytree(FIXTURE, bench)
    manifest = harness.read_json(CHECKOUT / "BENCHMARK.json")
    manifest["configs"] = [{
        "name": "toy_lm-d64", "source": "test fixture",
        "file": "benchmarks/chip/configs/toy_lm-d64.json", "reduced": [],
        "why": "a token model with dict-shaped weights"}]
    manifest["workloads"] = [
        {"name": name, "config": "toy_lm-d64", "traffic": traffic,
         "chips": 1, "why": "the harness on a token model"}
        for name, traffic in FIXTURE_CELLS.items()]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


def tiny_cell(name: str, root: Path | None = None) -> harness.Cell:
    """The tiny copy of ``name``: a cell of ``BENCHMARK.json``, or one
    of ``FIXTURE_CELLS`` from a fixture checkout made at ``root``."""
    if name in FIXTURE_CELLS:
        checkout = fixture_checkout(root)
        cell = harness.load_cell(name, checkout=checkout,
                                 bench=checkout / "benchmarks" / "chip")
    else:
        cell = harness.load_cell(name)
    traffic = dict(cell.traffic, attendance=0.25, batch=8, server_batch=8)
    return harness.Cell(f"tiny.{name}", 1, cell.model.tiny(cell.config),
                        traffic, cell.limits, cell.model, cell.end_to_end,
                        cell.per_layer)
