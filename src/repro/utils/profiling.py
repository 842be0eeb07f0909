"""Host-side round profiling: where a training round's wall time goes.

* :class:`RoundProfiler` — wall time of the HOST sections of
  ``Engine.run()`` (cohort sampling/staging, round dispatch, device
  sync, eval, and the host path between two rounds).  Pass one to
  ``Engine(..., profiler=...)``; the run loop wraps its sections and
  ``summary()`` reports totals, call counts, and per-call means.  Each
  section is also a ``jax.profiler.TraceAnnotation``, so a
  ``jax.profiler`` trace holds it on the device clock.  Zero overhead
  when no profiler is attached.

  The compiled round's PHASES are not timed here: every phase runs
  under a ``jax.named_scope`` of its class name
  (:func:`repro.api.phases.run_phase`), so a device trace attributes
  each op to its phase through the op's HLO ``op_name``.

* :func:`round_hlo` — the optimized HLO text of the engine's compiled
  monolithic round, for the collective census
  (:func:`repro.utils.hlo_cost.collective_census`) and the
  no-pool-all-gather assertion.
"""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Optional

import jax


class RoundProfiler:
    """Accumulates wall time of named host-side sections.

    Sections the Engine instruments: ``sample`` (cohort draw + padding +
    device placement), ``dispatch`` (the async round/extract/tail
    calls), ``sync`` (host blocks on round metrics), ``eval`` (test-set
    evaluation), and ``between_rounds`` (from one round's ``sync``
    returning to the next round's ``dispatch`` returning, around the
    ``sample`` and ``dispatch`` it contains; only where the host blocks
    on every round).  ``dispatch`` measuring ms instead of µs is the
    signal that rounds are NOT device-resident (the host is staging or
    blocking inside the dispatch path).

    A section left by an exception is closed (its trace span ends) but
    not counted: a run that ends inside ``between_rounds`` does not add
    the unfinished gap.
    """

    def __init__(self):
        self.total_s: dict[str, float] = defaultdict(float)
        self.calls: dict[str, int] = defaultdict(int)

    @contextmanager
    def section(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            yield
            self.total_s[name] += time.perf_counter() - t0
            self.calls[name] += 1

    def summary(self) -> dict:
        return {
            name: {
                "total_s": round(total, 6),
                "calls": self.calls[name],
                "mean_ms": round(total / max(1, self.calls[name]) * 1e3, 3),
            }
            for name, total in sorted(self.total_s.items())
        }


@contextmanager
def _borrow_sampler(eng):
    """Run one throwaway cohort draw without perturbing the engine's
    sampling clock or telemetry (both are restored on exit, so a
    profiled engine still replays the exact cohort stream)."""
    clock, ntel = eng._sample_clock, len(eng._telemetry)
    try:
        yield
    finally:
        eng._sample_clock = clock
        del eng._telemetry[ntel:]


def _one_round_args(eng):
    import numpy as np
    rng = np.random.default_rng(eng.cfg.seed + 1)
    state = eng.init_state()
    cohort, xs, ys, mask = eng.sample_round(rng)
    key = eng.round_key(0)
    args = (state, cohort, xs, ys, key)
    return args if mask is None else args + (mask,)


def round_hlo(eng, args: Optional[tuple] = None) -> str:
    """Optimized (post-GSPMD) HLO text of the compiled monolithic round
    for the engine's config — the input to the collective census."""
    with _borrow_sampler(eng):
        if args is None:
            args = _one_round_args(eng)
        return eng.algo.round.lower(*args).compile().as_text()
