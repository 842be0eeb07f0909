"""BENCHMARK.json keeps to its contract, and every cell finds its files
by name."""
from __future__ import annotations

import json
import re

import pytest

from chipbench_cells import BENCH, CELLS, CHECKOUT, harness

MANIFEST = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmarks/chip"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert not any(w.startswith("/") or ".." in w for w in MANIFEST["command"])


def test_names_and_units():
    entries = (MANIFEST["configs"] + MANIFEST["workloads"]
               + MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))


def test_metrics_contract():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    c = harness.load_cell(cell)
    entry = next(x for x in MANIFEST["configs"]
                 if x["name"] == c.config["name"])
    assert entry["file"] == f"benchmarks/chip/configs/{c.config['name']}.json"
    assert sorted(entry["reduced"]) == sorted(c.config["reduced"])
    assert set(c.limits) == {"loss", "m1", "change"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     f"r_{m['name']}")
        assert callable(reader.read)
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("no such chip")


def test_every_file_under_paths_is_named_from_name_characters():
    for p in BENCH.rglob("*"):
        if "__pycache__" in p.parts or not p.is_file():
            continue
        rel = p.relative_to(CHECKOUT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
