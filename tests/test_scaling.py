"""Weak-scaling runtime contracts (ISSUE 8): device-resident rounds,
the sync_every telemetry cadence, donation goldens, shard-aligned
cohort padding, the HLO collective census, and the fused gather+loss
computed inside the shard_map body.

The tentpole contract: none of the latency work moves a value.  The
donated, prefetched, sync_every>1 round stream is bit-for-bit the
classic per-round-synced stream at the same donation setting; the
shard-aligned capacity round-up never changes which clients are drawn;
the fused shard-local loss equals the unsharded fused kernel path.  The
forced multi-device cases run in a subprocess because the host device
count binds at jax initialization.
"""
import json
import os
import re
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, ExperimentConfig
from repro.api.registry import PROGRAMS
from repro.core.feature_store import FeatureStore, shard_local_fused_loss
from repro.kernels import ops
from repro.launch.mesh import auto_mesh
from repro.utils.hlo_cost import assert_no_pool_allgather, collective_census
from repro.utils.profiling import RoundProfiler, round_hlo

TINY = dict(task="image", rounds=3, n_clients=8, attendance=0.5, batch=4,
            width=4, eval_every=3, seed=0)


class _Rec:
    def __init__(self):
        self.state = None

    def on_round(self, engine, rnd, state, metrics):
        self.state = state


def _run(cfg, donate):
    rec = _Rec()
    eng = Engine(cfg, donate=donate, callbacks=(rec,),
                 log=lambda *a, **k: None)
    res = eng.run()
    return eng, res, rec.state


def _assert_states_equal(a, b, msg):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=msg)


# ------------------------------------------------- sync_every cadence
def test_sync_every_is_value_exact_and_adds_no_traces():
    """The telemetry cadence is pure host-side bookkeeping: any
    sync_every produces bit-identical state and eval history, and the
    round still traces exactly once (the cadence lives outside the
    jitted dispatch)."""
    base = ExperimentConfig(algo="cyclesfl", collect_timing=True,
                            mesh_shape=(1, 1), **TINY)
    runs = {}
    for k in (1, 2, 5):
        eng, res, state = _run(replace(base, sync_every=k), donate=False)
        assert eng.algo.trace_count == 1, f"sync_every={k} retraced"
        runs[k] = (res, state)
    ref_res, ref_state = runs[1]
    for k in (2, 5):
        res, state = runs[k]
        _assert_states_equal(ref_state, state, f"sync_every={k} state")
        assert [h["test_loss"] for h in res["history"]] == \
            [h["test_loss"] for h in ref_res["history"]], k


def test_sync_every_validation_and_flag():
    with pytest.raises(ValueError, match="sync_every"):
        ExperimentConfig(sync_every=0).validate()
    # resilience guard needs per-round health verdicts: the engine must
    # fall back to per-round syncs, not skip guard windows
    cfg = ExperimentConfig(algo="cyclesfl", sync_every=4, **TINY)
    cfg = replace(cfg, resilience=replace(cfg.resilience, guard=True))
    cfg.validate()                       # cadence + guard may coexist


# --------------------------------------- donation + device residency
@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_donated_mesh_round_matches_unsharded(name):
    """The scaling path's golden, per registered algorithm: donated
    buffers + the prefetched device-resident input stream + sync_every>1
    on a 1-device mesh reproduce the donated unsharded Engine exactly.
    (Donation itself is compared at the SAME setting on both sides — it
    changes XLA fusion choices at ~1 ulp, which is why it stays opt-in
    on CPU.)  The 8-device version runs in the subprocess golden."""
    base = ExperimentConfig(algo=name, collect_timing=True, **TINY)
    _, ref_res, ref_state = _run(base, donate=True)
    eng, res, state = _run(
        replace(base, mesh_shape=(1, 1), sync_every=2), donate=True)
    assert eng.algo.trace_count == 1
    _assert_states_equal(ref_state, state, f"{name}: donated mesh state")
    assert [h["test_loss"] for h in res["history"]] == \
        [h["test_loss"] for h in ref_res["history"]], name


# ------------------------------------------------ shard-aligned padding
def test_padded_capacity_identity_off_mesh_and_at_one_device():
    """shard_aligned_capacity is identity when there is nothing to
    align: no mesh, or a single batch shard."""
    from repro.sharding.specs import shard_aligned_capacity
    assert shard_aligned_capacity(None, 6) == 6
    mesh1 = auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    assert shard_aligned_capacity(mesh1, 6) == 6
    eng = Engine(ExperimentConfig(algo="cyclesfl", mesh_shape=(1, 1),
                                  **TINY), donate=False,
                 log=lambda *a, **k: None)
    assert eng.padded_capacity == eng.cohort_capacity


# ---------------------------------------------------- collective census
_SYNTH_HLO = """\
HloModule synth

ENTRY %main (p0: f32[8,2048], p1: f32[98,2048]) -> f32[64,2048] {
  %p0 = f32[8,2048]{1,0} parameter(0)
  %p1 = f32[98,2048]{1,0} parameter(1)
  %wg = f32[784,2048]{1,0} all-gather(f32[98,2048]{1,0} %p1), dimensions={0}
  ROOT %ag = f32[64,2048]{1,0} all-gather(f32[8,2048]{1,0} %p0), dimensions={0}
}
"""


def test_collective_census_records_distinct_op_sizes():
    cen = collective_census(_SYNTH_HLO)
    ag = cen["all-gather"]
    assert ag["sites"] == 2
    # operand sizes: the 8x2048 pool shard (65536 B) and the 98x2048
    # weight shard (802816 B) — both distinct entries
    assert ag["op_bytes"] == [8 * 2048 * 4, 98 * 2048 * 4]
    assert ag["max_op_bytes"] == 98 * 2048 * 4


def test_assert_no_pool_allgather_is_size_targeted():
    """The assertion trips on a pool-shaped all-gather operand (one
    batch-axis shard of D_S^f) and ONLY on that: an FSDP weight
    rehydration gather that happens to be larger must pass."""
    pool_bytes = 64 * 2048 * 4
    with pytest.raises(AssertionError, match="pool-sized"):
        assert_no_pool_allgather(_SYNTH_HLO, pool_bytes, n_shards=8)
    # same module, pool geometry that matches nothing -> passes even
    # though a BIGGER (weight) all-gather is present
    cen = assert_no_pool_allgather(_SYNTH_HLO, 48 * 1000 * 4, n_shards=8)
    assert "all-gather" in cen


# ------------------------------------------- fused loss inside shard_map
def test_shard_local_fused_loss_matches_unsharded_fused_kernel():
    """Loss and head-weight gradient of the shard_map-interior fused
    gather+loss equal the unsharded fused path (the masked per-shard
    partials partition the minibatch, so only summation order differs).
    Runs the widest mesh this process has; the forced 8-shard case is
    covered by the subprocess golden."""
    n = 8 if jax.device_count() >= 8 else 1
    mesh = auto_mesh((n, 1), ("data", "model"), jax.devices()[:n])
    rng = np.random.default_rng(5)
    feats = jnp.asarray(rng.normal(size=(48, 24)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=(48,)), jnp.int32)
    idx = jnp.asarray(rng.integers(0, 48, size=(16,)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(24, 10)) * 0.1, jnp.float32)
    store = FeatureStore(feats, labels)
    ref_l, ref_dw = jax.value_and_grad(
        lambda w: ops.fused_gather_loss_mean(feats, labels, idx, w))(w)
    sl_l, sl_dw = jax.jit(jax.value_and_grad(
        lambda w: shard_local_fused_loss(store, idx, w, mesh)))(w)
    np.testing.assert_allclose(float(sl_l), float(ref_l), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sl_dw), np.asarray(ref_dw),
                               atol=1e-6)


def test_fused_shard_local_round_traces_once_and_trains():
    """cyclesfl with BOTH shard_local_resample and fused_gather_loss on
    a mesh (previously mutually exclusive) compiles once and produces
    finite losses at a cut that exposes the linear server head."""
    cfg = ExperimentConfig(algo="cyclesfl", mesh_shape=(1, 1), cut=3,
                           **TINY)
    cfg = cfg.with_cycle(shard_local_resample=True, fused_gather_loss=True)
    eng, res, _ = _run(cfg, donate=False)
    assert eng.algo.trace_count == 1
    assert np.isfinite(res["history"][-1]["test_loss"])


# -------------------------------------------------- profiler + phases
def test_profiler_sections_and_phase_scopes():
    """The opt-in RoundProfiler shows up in the run result with the
    host-side sections populated (``between_rounds`` once per round
    boundary when the host blocks on every round), and the compiled
    round's ops carry the scopes of all five phases."""
    prof = RoundProfiler()
    cfg = ExperimentConfig(algo="cyclesfl", collect_timing=True,
                           mesh_shape=(1, 1), sync_every=1, **TINY)
    eng = Engine(cfg, donate=False, profiler=prof,
                 log=lambda *a, **k: None)
    res = eng.run()
    assert set(res["profile"]) >= {"sample", "dispatch", "eval", "sync",
                                   "between_rounds"}
    assert res["profile"]["dispatch"]["calls"] == cfg.rounds
    assert res["profile"]["between_rounds"]["calls"] == cfg.rounds - 1
    text = round_hlo(eng)
    assert "HloModule" in text
    scopes = set(re.findall(r'op_name="jit\(round_impl\)/(\w+)/', text))
    assert scopes >= {"ExtractFeatures", "ServerUpdate", "FeatureGradients",
                      "ClientUpdate", "Commit"}


def test_profiler_sections_are_nested_spans_in_a_trace(tmp_path):
    """With a RoundProfiler attached, a ``jax.profiler`` trace holds the
    Engine's sections as host spans: ``between_rounds`` encloses the
    next round's cohort pick and ``dispatch``, and not the prefetch
    sample that follows the dispatch."""
    from jax.profiler import ProfileData
    cfg = ExperimentConfig(algo="cyclepsl", collect_timing=True,
                           sync_every=1, **{**TINY, "rounds": 2})
    eng = Engine(cfg, donate=False, profiler=RoundProfiler(),
                 log=lambda *a, **k: None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    pb, = tmp_path.rglob("*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("between_rounds", "dispatch", "sample"):
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    between, = spans["between_rounds"]
    inside = lambda name: [s for s in spans[name]
                           if between[0] <= s[0] and s[1] <= between[1]]
    assert len(spans["dispatch"]) == 2 and len(inside("dispatch")) == 1
    assert len(spans["sample"]) == 3 and len(inside("sample")) == 1


# ------------------------------------------------- forced 8-device golden
_SUBPROC = r"""
import json
from dataclasses import replace
import jax, numpy as np
from repro.api import Engine, ExperimentConfig
from repro.api.registry import PROGRAMS
import jax.numpy as jnp
from repro.core.feature_store import FeatureStore, shard_local_fused_loss
from repro.kernels import ops
from repro.launch.mesh import auto_mesh

quiet = lambda *a, **k: None
rep = {"devices": jax.device_count(), "algos": {}}
base = ExperimentConfig(task="image", rounds=2, n_clients=8, attendance=0.5,
                        batch=4, width=4, eval_every=2, seed=0)
for name in sorted(PROGRAMS):
    ref = Engine(replace(base, algo=name), donate=True, log=quiet).run()
    eng = Engine(replace(base, algo=name, mesh_shape=(8, 1),
                         mesh_axes=("data", "model"), sync_every=2,
                         collect_timing=True), donate=True, log=quiet)
    res = eng.run()
    rep["algos"][name] = {
        "diff": abs(res["history"][-1]["test_loss"]
                    - ref["history"][-1]["test_loss"]),
        "traces": eng.algo.trace_count,
    }

# shard-aligned padding: capacity 6 does not divide 8 shards
pcfg = replace(base, algo="cyclesfl", n_clients=12, attendance=0.5)
eng_u = Engine(pcfg, donate=False, log=quiet)
eng_m = Engine(replace(pcfg, mesh_shape=(8, 1),
                       mesh_axes=("data", "model")), donate=False, log=quiet)
ids_u = np.asarray(eng_u.sample_round(np.random.default_rng(3))[0])
cm, xm, ym, mask = eng_m.sample_round(np.random.default_rng(3))
rep["padding"] = {
    "cohort_capacity": eng_m.cohort_capacity,
    "padded_capacity": eng_m.padded_capacity,
    "live_prefix_equal": bool(
        (np.asarray(cm)[: eng_u.cohort_capacity] == ids_u).all()),
    "mask_live": float(np.asarray(mask).sum()),
}

# fused loss inside shard_map at 8 real shards
rng = np.random.default_rng(5)
feats = jnp.asarray(rng.normal(size=(48, 24)), jnp.float32)
labels = jnp.asarray(rng.integers(0, 10, size=(48,)), jnp.int32)
idx = jnp.asarray(rng.integers(0, 48, size=(16,)), jnp.int32)
w = jnp.asarray(rng.normal(size=(24, 10)) * 0.1, jnp.float32)
mesh = auto_mesh((8, 1), ("data", "model"), jax.devices()[:8])
store = FeatureStore(feats, labels)
ref_l, ref_dw = jax.value_and_grad(
    lambda w: ops.fused_gather_loss_mean(feats, labels, idx, w))(w)
sl_l, sl_dw = jax.jit(jax.value_and_grad(
    lambda w: shard_local_fused_loss(store, idx, w, mesh)))(w)
rep["fused_loss"] = {
    "loss_diff": abs(float(sl_l) - float(ref_l)),
    "dw_maxdiff": float(jnp.max(jnp.abs(sl_dw - ref_dw))),
}
print(json.dumps(rep))
"""


def test_forced_8_device_scaling_golden():
    """All registered algorithms under donation + device-resident rounds
    on a forced 8-device host mesh agree with the donated unsharded run
    to reduction-noise tolerance and trace once; capacity 6 pads to 8
    without changing the drawn cohort; the fused shard-local loss is
    exact at 8 real shards."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath("src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROC],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (
        f"scaling golden failed\nstdout: {proc.stdout[-3000:]}\n"
        f"stderr: {proc.stderr[-3000:]}")
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rep["devices"] == 8
    for name, rec in rep["algos"].items():
        assert rec["traces"] == 1, name
        assert rec["diff"] <= 1e-5, (name, rec)
    pad = rep["padding"]
    assert pad["cohort_capacity"] == 6 and pad["padded_capacity"] == 8
    assert pad["live_prefix_equal"] and pad["mask_live"] == 6.0
    fl = rep["fused_loss"]
    assert fl["loss_diff"] <= 1e-6 and fl["dw_maxdiff"] <= 1e-6
