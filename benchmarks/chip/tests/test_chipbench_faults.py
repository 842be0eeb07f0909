"""A run whose timed path is broken comes out not ``correct``.

Each test drives ``harness.run`` (everything ``run.py`` does after its
look for a chip) on a tiny cell on the CPU, with one fault planted in
the program underneath."""
from __future__ import annotations

import dataclasses
import time

import pytest

from chipbench_cells import CELLS, harness, tiny_cell


def run(name):
    return harness.run(tiny_cell(name), 2 ** 33 + 5, 0.5, False,
                       time.perf_counter(), log=lambda *a: None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    assert run(name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_round_that_returns_its_state_unchanged(monkeypatch, name):
    import repro.api.engine as engine
    build = engine.build_algorithm

    def frozen(*a, **k):
        algo = build(*a, **k)
        return dataclasses.replace(
            algo, round=lambda state, *r: (state, algo.round(state, *r)[1]))

    monkeypatch.setattr(engine, "build_algorithm", frozen)
    out = run(name)
    assert not out["correct"]
    assert out["checks"]["change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out(monkeypatch, name):
    import repro.core.split as split
    xent = split.xent_loss

    def half(logits, y):
        n = y.shape[0] // 2
        return xent(logits[:n], y[:n])

    monkeypatch.setattr(split, "xent_loss", half)
    assert not run(name)["correct"]

