"""The phase readers on a hand-built trace whose ops carry their scope
paths, the scope path read from a real (CPU) trace's metadata plane,
and the between-rounds reader."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench_cells import BENCH, harness  # noqa: F401  (sets sys.path)
from chipbench import scopes
from chipbench import trace as tr

ROUND = "jit(round_impl)/"


def op(path, s, e, name="%fusion.1 = f32[8]{0} fusion()"):
    return tr.Event(name, float(s), float(e), {"op_name": path})


@pytest.fixture
def ctx():
    # window [0, 1000] ns, two traced rounds.  Chip 0: the inner loop's
    # while op [100, 500] spans its body ops and a copy with no scope;
    # an entry copy with no scope; one op after the window.  Chip 1: the
    # same phases, shorter.
    su = ROUND + "ServerUpdate/while"
    d0 = [op(ROUND + "ExtractFeatures/vmap()/conv", 10, 90),
          op(su, 100, 500), op(su + "/body/dot_general", 120, 200),
          op("", 210, 230, "%copy.9 = f32[8]{0} copy(f32[8]{0} %q)"),
          op(su + "/body/closed_call/jvp()/add", 300, 400),
          op(ROUND + "FeatureGradients/transpose(jvp())/dot", 510, 560),
          op(ROUND + "ClientUpdate/vmap()/mul", 600, 700),
          op(ROUND + "Commit/scatter", 700, 750),
          op("", 800, 850, "%copy.5 = f32[8]{0} copy(f32[8]{0} %p)"),
          op(ROUND + "Commit/scatter", 1100, 1200)]
    d1 = [op(ROUND + "ExtractFeatures/vmap()/conv", 10, 50),
          op(su, 100, 300), op(su + "/body/dot_general", 150, 250),
          op(ROUND + "FeatureGradients/dot", 510, 530),
          op(ROUND + "ClientUpdate/mul", 600, 640),
          op(ROUND + "Commit/scatter", 700, 720)]
    trace = tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, [],
                     (0.0, 1000.0))
    return SimpleNamespace(trace=trace, traced_rounds=2)


# the union over both chips' ops of each phase, ns: chip 0 + chip 1
UNION = {"ExtractFeatures": 80 + 40, "ServerUpdate": 400 + 200,
         "FeatureGradients": 50 + 20, "ClientUpdate": 100 + 40,
         "Commit": 50 + 20}


def reader(phase):
    return harness.load_module(
        BENCH / "metrics" / f"phase.{phase}.ms_per_round.py", f"p_{phase}")


@pytest.mark.parametrize("phase", scopes.PHASES)
def test_phase_reader_is_the_union_per_round(ctx, phase):
    want = UNION[phase] / 2 / ctx.traced_rounds * 1e-6
    assert reader(phase).read(ctx) == pytest.approx(want)


def test_phases_and_unattributed_add_up_to_busy(ctx):
    per_dev = scopes.phase_ns(ctx.trace)
    assert per_dev["/device:TPU:0"][None] == 50   # the entry copy alone
    assert per_dev["/device:TPU:0"]["ServerUpdate"] == 400  # not 580
    assert per_dev["/device:TPU:1"][None] == 0
    busy = tr.busy_ns(ctx.trace)
    for dev, phases in per_dev.items():
        assert sum(phases.values()) == busy[dev]


@pytest.mark.parametrize("phase", scopes.PHASES)
def test_phase_reader_reads_nothing_without_traced_rounds(ctx, phase):
    ctx.traced_rounds = 0
    assert reader(phase).read(ctx) is None


def test_phase_reader_reads_nothing_without_scopes(ctx):
    # a program that names no phase: every op is unattributed
    ctx.trace = tr.Trace(
        {d: [op("jit(round_impl)/vmap()/dot", e.start_ns, e.end_ns)
             for e in ops] for d, ops in ctx.trace.devices.items()},
        [], ctx.trace.window)
    assert all(reader(p).read(ctx) is None for p in scopes.PHASES)


@pytest.mark.parametrize("path,phase", [
    (ROUND + "ServerUpdate/while/body/dot_general", "ServerUpdate"),
    (ROUND + "ClientUpdate/vmap()/Commit/mul", "ClientUpdate"),
    (ROUND + "HealthGuard/reduce_max", None),
    (ROUND + "vmap(ServerUpdateX)/dot", None),
    ("", None),
])
def test_phase_of_takes_the_first_phase_in_the_path(path, phase):
    assert scopes.phase_of(path) == phase


def test_module_op_names_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def round_impl(x):
        with jax.named_scope("ServerUpdate"):
            return jnp.sin(x) * 2.0

    round_impl(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        round_impl(jnp.ones(8)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    pb, = tmp_path.rglob("*.xplane.pb")
    names = scopes.module_op_names(pb.read_bytes())
    module, = [m for m in names if m.startswith("jit_round_impl(")]
    paths = set(names[module].values())
    assert any(scopes.phase_of(p) == "ServerUpdate" for p in paths)


def between(**sections):
    mod = harness.load_module(BENCH / "metrics" / "host.between_rounds_ms.py",
                              "between")
    return mod.read(SimpleNamespace(rounds=10, window_s=8.5,
                                    sections=sections))


def test_between_rounds_reader_clips_the_span_to_the_window():
    # the first count began before the window: 2.0 s counted, but sync
    # and the prefetch sample leave 1.5 s of the window
    assert between(between_rounds=2.0, sample=1.0, sync=6.0) == \
        pytest.approx(150.0)
    # a span inside the window is read as it is
    assert between(between_rounds=1.2, sample=1.0, sync=6.0) == \
        pytest.approx(120.0)
    assert between(sample=1.0, sync=6.0) is None
