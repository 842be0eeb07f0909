"""A run whose timed path is broken comes out not ``correct``.

Each test drives ``harness.run`` (everything ``run.py`` does after its
look for a chip) on a tiny cell on the CPU, with one fault planted in
the program underneath: in the round the Engine builds, or in the task
that the configuration hands the Engine.  The cells are the
benchmark's and the token fixture's (``chipbench_cells``)."""
from __future__ import annotations

import dataclasses
import time

import pytest

from chipbench_cells import CELLS, FIXTURE_CELLS, harness, tiny_cell

ALL = CELLS + list(FIXTURE_CELLS)


def run(name, root):
    return harness.run(tiny_cell(name, root), 2 ** 33 + 5, 0.5, False,
                       time.perf_counter(), log=lambda *a: None)


@pytest.mark.parametrize("name", ALL)
def test_sound_run_is_correct(name, tmp_path):
    assert run(name, tmp_path)["correct"]


@pytest.mark.parametrize("name", ALL)
def test_round_that_returns_its_state_unchanged(monkeypatch, name,
                                                tmp_path):
    import repro.api.engine as engine
    build = engine.build_algorithm

    def frozen(*a, **k):
        algo = build(*a, **k)
        return dataclasses.replace(
            algo, round=lambda state, *r: (state, algo.round(state, *r)[1]))

    monkeypatch.setattr(engine, "build_algorithm", frozen)
    out = run(name, tmp_path)
    assert not out["correct"]
    assert out["checks"]["change"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", ALL)
def test_half_of_the_batch_left_out(monkeypatch, name, tmp_path):
    # the server sees the first half of each batch's features and
    # labels: the loss is the mean over those rows alone
    build = harness.experiment

    def halved(*a, **k):
        task, fed, cfg = build(*a, **k)
        first = lambda a: a[: a.shape[0] // 2]
        apply, loss = task.server_apply, task.loss
        task = dataclasses.replace(
            task, server_apply=lambda sp, f: apply(sp, first(f)),
            loss=lambda out, y: loss(out, first(y)))
        return task, fed, cfg

    monkeypatch.setattr(harness, "experiment", halved)
    assert not run(name, tmp_path)["correct"]
