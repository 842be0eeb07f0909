"""The Window times the same rounds with and without ``--trace 1``: the
traced rounds come after the window, and the host sections, the rounds
and the seconds that the per-layer metrics read are the window's."""
from __future__ import annotations

import gc
import math

import pytest

from chipbench_cells import harness, tiny_cell


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(harness.time, "perf_counter", c)
    return c


def drive(window, clock, losses, step_s=1.0):
    """Feed ``on_round`` the rounds after set-up, one ``step_s`` apart,
    each with a ``sample`` section of 0.25 s, until it ends the run."""
    window._open_window()
    try:
        for i, loss in enumerate(losses):
            clock.now += step_s
            window.sections.total_s["sample"] += 0.25
            try:
                window.on_round(None, harness.WARMUP + i, None,
                                {"server_loss": loss})
            except harness.WindowClosed:
                return i + 1
    finally:
        gc.unfreeze()
    raise AssertionError("the window never closed")


@pytest.mark.parametrize("traced", [False, True])
def test_window_is_the_same_with_and_without_trace(monkeypatch, clock,
                                                   traced):
    events = []
    monkeypatch.setattr(harness.Window, "_open_trace",
                        lambda self: events.append(("open", clock.now)))
    monkeypatch.setattr(harness.Window, "_close_trace",
                        lambda self: events.append(("close", clock.now)))
    cell = tiny_cell("femnist.cyclepsl")
    window = harness.Window(cell, 5.0, "trace" if traced else None, 0.0,
                            harness.Sections())
    fed = drive(window, clock, [1.0] * 100)
    assert window.times == [1.0] * 5
    assert window.t_end - window.t0 == 5.0
    assert window.window_sections == {"sample": 1.25}
    if traced:
        assert fed == 5 + harness.TRACE_ROUNDS
        assert window.traced == harness.TRACE_ROUNDS
        assert events == [("open", 105.0),
                          ("close", 105.0 + harness.TRACE_ROUNDS)]
    else:
        assert fed == 5 and window.traced == 0 and not events


def test_a_non_finite_loss_fails_its_round(monkeypatch, clock):
    monkeypatch.setattr(harness.Window, "_open_trace", lambda self: None)
    monkeypatch.setattr(harness.Window, "_close_trace", lambda self: None)
    window = harness.Window(tiny_cell("femnist.sglr"), 3.0, "trace", 0.0,
                            harness.Sections())
    # one NaN in the window and one in the traced rounds after it
    drive(window, clock, [1.0, math.nan, 1.0, 1.0, math.inf] + [1.0] * 30)
    assert window.failed == 2
