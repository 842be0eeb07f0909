"""Mesh-native execution: sharding invariance, trace pins, resume, and
the kernel-dispatched resample gather.

The tentpole contract (ISSUE 3): sharding flows from config to kernel
without touching numerics — a 1-device mesh is bit-for-bit the
unsharded Engine, a forced multi-device host mesh agrees to float
reduction noise and still traces ONCE, and the FeatureStore resample
gather dispatches through ``kernels.ops.feature_resample``.  The full
per-algorithm multi-device comparison runs in a subprocess
(``repro.launch.meshcheck``) because the host device count binds at
jax initialization.
"""
import json
import os
import subprocess
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Engine, ExperimentConfig, build_algorithm, get_program
from repro.core.feature_store import FeatureStore, gather_batch
from repro.launch.mesh import auto_mesh
from repro.launch.meshcheck import C, _drive, _task_and_data
from repro.optim import adam
from repro.sharding.specs import train_state_shardings


@pytest.fixture(scope="module")
def setup():
    # the exact task/data/drive protocol the subprocess meshcheck runs —
    # shared so the in-process goldens and the 8-device sweep can't drift
    return _task_and_data()


def _mesh1():
    return auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def _assert_equal(a_state, a_rows, b_state, b_rows, msg):
    for ra, rb in zip(a_rows, b_rows):
        for k in ra:
            np.testing.assert_array_equal(ra[k], rb[k],
                                          err_msg=f"{msg}: metric {k}")
    for la, lb in zip(jax.tree.leaves(a_state), jax.tree.leaves(b_state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=f"{msg}: state")


# ------------------------------------------------------------ invariance
@pytest.mark.parametrize("name", ["cyclesfl", "psl", "sglr", "ssl"])
def test_one_device_mesh_is_bit_for_bit_unsharded(name, setup):
    """Sharding constraints pin layout, never values: the full mesh path
    (placed state, committed inputs, constrained phases, pinned output
    shardings) on ONE device reproduces the unsharded round exactly.
    The remaining algorithms are covered by the subprocess meshcheck."""
    task, xs, ys = setup
    base_state, base_rows, _ = _drive(name, task, xs, ys)
    s1, r1, _ = _drive(name, task, xs, ys, mesh=_mesh1())
    _assert_equal(base_state, base_rows, s1, r1, name)


@pytest.mark.parametrize("name", ["cyclesfl", "psl"])
def test_sharded_round_traces_exactly_once(name, setup):
    """Compile-once per (algo, config, mesh): the mesh path with pinned
    output shardings never retraces across varying live cohort sizes."""
    task, xs, ys = setup
    _, _, traces = _drive(name, task, xs, ys, mesh=_mesh1(), rounds=5)
    assert traces == 1, (f"{name}: sharded round traced {traces} times — "
                         "compile-once per (algo, config, mesh) broken")


def test_engine_mesh_matches_unsharded_engine():
    """Engine-level golden: cfg.mesh_shape=(1,1) drives the whole
    mesh-native stack (mesh build, NamedSharding placement, committed
    inputs, out_shardings) and must be bit-for-bit the classic path."""
    class Rec:
        def __init__(self):
            self.rows, self.state = [], None

        def on_round(self, engine, rnd, state, metrics):
            self.rows.append({k: np.asarray(v) for k, v in metrics.items()})
            self.state = state

    cfg = ExperimentConfig(algo="cyclesfl", task="image", rounds=3,
                           n_clients=8, attendance=0.5, batch=4, width=4,
                           eval_every=3, seed=0)
    r0, r1 = Rec(), Rec()
    Engine(cfg, callbacks=(r0,), log=lambda *a, **k: None).run()
    eng = Engine(replace(cfg, mesh_shape=(1, 1)), callbacks=(r1,),
                 log=lambda *a, **k: None)
    eng.run()
    assert eng.mesh is not None and eng.state_shardings is not None
    _assert_equal(r0.state, r0.rows, r1.state, r1.rows, "engine mesh")


def test_meshcheck_all_algorithms_on_forced_8_device_mesh():
    """The multi-device invariance sweep: every registered algorithm,
    unsharded vs 1-device mesh (exact) vs an 8-device CPU host mesh
    (reduction-noise tolerance), one trace each.  Subprocess because
    XLA_FLAGS must bind before jax initializes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath("src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.meshcheck", "--devices", "8"],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (
        f"meshcheck failed\nstdout: {proc.stdout[-3000:]}\n"
        f"stderr: {proc.stderr[-3000:]}")
    report = json.loads(proc.stdout)
    assert report["ok"] and report["devices"] == 8
    for name, rec in report["algos"].items():
        assert rec["exact_1dev_diff"] == 0.0, name
        assert rec["ndev_traces"] == 1, name


# ------------------------------------------------------------- config
def test_mesh_config_json_roundtrip():
    cfg = ExperimentConfig(algo="cyclesfl", mesh_shape=(8, 1),
                           mesh_axes=("data", "model"),
                           shard_cohort=False, resume=True)
    back = ExperimentConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert back == cfg
    assert isinstance(back.mesh_shape, tuple)
    assert isinstance(back.mesh_axes, tuple)


def test_from_dict_tolerates_legacy_batch_constraint_key():
    """Pre-mesh config JSONs carry cycle.batch_constraint=null (the
    removed callable hook); they must still load."""
    cfg = ExperimentConfig(algo="sglr", rounds=3)
    d = json.loads(json.dumps(cfg.to_dict()))
    d["cycle"]["batch_constraint"] = None
    assert ExperimentConfig.from_dict(d) == cfg


def test_run_places_caller_provided_state_on_mesh():
    """Engine.run(state=...) must commit the state to the mesh placement
    or round 1 would retrace against round 0's pinned out_shardings."""
    cfg = ExperimentConfig(algo="psl", task="image", rounds=3, n_clients=8,
                           attendance=0.5, batch=4, width=4, eval_every=3,
                           seed=0, mesh_shape=(1, 1))
    eng = Engine(cfg, log=lambda *a, **k: None)
    raw = eng.algo.init(jax.random.PRNGKey(cfg.seed), 8)   # unplaced
    eng.run(state=raw)
    assert eng.algo.trace_count == 1


def test_mesh_config_validates_shape_axes():
    with pytest.raises(ValueError, match="equal length"):
        ExperimentConfig(mesh_shape=(2, 2, 2)).validate()
    with pytest.raises(ValueError, match="positive"):
        ExperimentConfig(mesh_shape=(0, 1)).validate()


def test_train_state_shardings_roles(setup):
    """Client stack leading cohort dim takes the batch axes; server and
    client_global weights follow the path rules (replicated for mlp)."""
    task, _, _ = setup
    opt = adam(1e-3)
    mesh = _mesh1()
    for name, cohort_dim_expected in (("psl", "data"), ("cyclesfl", None)):
        algo = build_algorithm(get_program(name), task, opt, opt)
        a_state = jax.eval_shape(
            lambda a=algo: a.init(jax.random.PRNGKey(0), C))
        sh = train_state_shardings(a_state, mesh)
        server_leaf = jax.tree.leaves(sh.server)[0]
        assert all(a is None for a in server_leaf.spec)
        if name == "psl":
            assert sh.client_global is None
            leaf = jax.tree.leaves(sh.clients)[0]
            assert leaf.spec[0] == cohort_dim_expected
        else:
            assert sh.clients is None
            assert jax.tree.leaves(sh.client_global)[0] is not None
        # shard_cohort=False keeps the stack replicated
        sh_off = train_state_shardings(a_state, mesh, shard_cohort=False)
        if sh_off.clients is not None:
            assert jax.tree.leaves(sh_off.clients)[0].spec[0] is None


# ------------------------------------------------- shard-local resample
def _n_mesh():
    """The widest (N, 1) mesh this process can build: 8 under the CI
    devices8/kernels legs, 1 on the default single-CPU-device run (where
    the 8-device case is covered by the subprocess golden below)."""
    n = 8 if jax.device_count() >= 8 else 1
    return auto_mesh((n, 1), ("data", "model"), jax.devices()[:n])


@pytest.mark.kernels
@pytest.mark.parametrize("use_kernel", [False, True])
def test_shard_local_gather_matches_gspmd_gather(use_kernel):
    """Tentpole contract: the shard_map-wrapped resample (per-shard
    index translation + masked cross-shard fixup) is bit-for-bit the
    plain gather — multi-dim features, pytree labels, both the jnp and
    the (interpret) Pallas per-shard gather, and both the
    reduce-scatter (M divides shards) and all-reduce fixups."""
    from repro.core.feature_store import shard_local_gather
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _n_mesh()
    rng = np.random.default_rng(7)
    feats = jnp.asarray(rng.normal(size=(48, 4, 6)), jnp.float32)
    labels = {"y": jnp.asarray(rng.integers(0, 9, size=(48,)), jnp.int32),
              "aux": jnp.asarray(rng.normal(size=(48, 3)), jnp.float32)}
    place = lambda l: jax.device_put(
        l, NamedSharding(mesh, P("data", *([None] * (l.ndim - 1)))))
    store = FeatureStore(place(feats), jax.tree.map(place, labels))
    for m in (16, 13):          # 16 divides 8 shards (scatter), 13 not
        idx = jnp.asarray(rng.integers(0, 48, size=m), jnp.int32)
        f_ref, y_ref = gather_batch(store, idx, use_kernel=False)
        f, y = shard_local_gather(store, idx, mesh, use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(f), np.asarray(f_ref))
        for k in y_ref:
            np.testing.assert_array_equal(np.asarray(y[k]),
                                          np.asarray(y_ref[k]))


@pytest.mark.kernels
def test_shard_local_round_is_bit_for_bit_and_traces_once():
    """CycleConfig.shard_local_resample on a mesh must not change a bit
    of any round output, and the shard_map wrapper must not retrace
    across varying live cohort sizes (compile-once holds)."""
    task, xs, ys = _task_and_data()
    mesh = _n_mesh()
    base_state, base_rows, _ = _drive("cyclesfl", task, xs, ys, mesh=mesh,
                                      rounds=5)
    s, r, traces = _drive("cyclesfl", task, xs, ys, mesh=mesh, rounds=5,
                          shard_local=True)
    _assert_equal(base_state, base_rows, s, r, "shard-local cyclesfl")
    assert traces == 1, (f"shard-local round traced {traces} times — the "
                         "shard_map wrapper broke compile-once")


@pytest.mark.kernels
def test_meshcheck_shard_local_golden_all_algorithms_8_devices():
    """The acceptance golden: every registered algorithm, monolithic AND
    pipelined, on a 1-device and a forced 8-device mesh — shard-local
    resample bit-for-bit the GSPMD path, trace budget held.  Subprocess
    because XLA_FLAGS must bind before jax initializes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.abspath("src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.meshcheck", "--devices", "8",
         "--shard-local"],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (
        f"shard-local meshcheck failed\nstdout: {proc.stdout[-3000:]}\n"
        f"stderr: {proc.stderr[-3000:]}")
    report = json.loads(proc.stdout)
    assert report["ok"] and report["mode"] == "shard_local"
    for name, rec in report["algos"].items():
        assert rec["ok"], (name, rec)
        assert rec["8dev"]["diff"] == 0.0, name


def test_inner_loop_resample_use_kernel_override_is_threaded():
    """Satellite fix: CycleConfig.resample_use_kernel reaches the
    gather inside server_inner_loop.apply_step (it used to be dropped —
    gather_batch was always called with defaults), and the forced
    interpret-kernel path is bit-for-bit the jnp path."""
    from repro.api import build_algorithm, get_program
    from repro.core.cyclesl import CycleConfig
    from repro.optim import adam
    task, xs, ys = _task_and_data()
    opt = adam(5e-3)

    def drive(use_kernel):
        algo = build_algorithm(
            get_program("cyclesfl"), task, opt, opt,
            CycleConfig(server_epochs=2, resample_use_kernel=use_kernel))
        state = algo.init(jax.random.PRNGKey(0), n_clients=C)
        state, mets = algo.round(state, jnp.arange(C), xs, ys,
                                 jax.random.PRNGKey(0))
        return state, mets

    s_jnp, m_jnp = drive(False)
    s_krn, m_krn = drive(True)
    _assert_equal(s_jnp, [{k: np.asarray(v) for k, v in m_jnp.items()}],
                  s_krn, [{k: np.asarray(v) for k, v in m_krn.items()}],
                  "resample_use_kernel")


# ----------------------------------------------------- resample dispatch
def test_gather_batch_kernel_path_matches_jnp_take():
    """Satellite: the FeatureStore resample gather dispatched through
    kernels.ops.feature_resample (Pallas, interpret on CPU) is the exact
    jnp.take gather — for multi-dim features and pytree labels."""
    rng = np.random.default_rng(3)
    feats = jnp.asarray(rng.normal(size=(24, 4, 6)), jnp.float32)
    labels = {"y": jnp.asarray(rng.integers(0, 9, size=(24,)), jnp.int32),
              "aux": jnp.asarray(rng.normal(size=(24, 3)), jnp.float32)}
    store = FeatureStore(feats, labels)
    idx = jnp.asarray(rng.permutation(24)[:16], jnp.int32)
    f_ref, y_ref = gather_batch(store, idx, use_kernel=False)
    f_k, y_k = gather_batch(store, idx, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_k))
    for k in y_ref:
        np.testing.assert_array_equal(np.asarray(y_ref[k]),
                                      np.asarray(y_k[k]))


def test_gather_batch_auto_gate_off_tpu():
    """Backend gate mirrors fused_adam: off-TPU the default path is the
    XLA gather (the kernel is TPU-targeted)."""
    assert jax.default_backend() != "tpu"   # this container is CPU-only
    store = FeatureStore(jnp.ones((4, 2)), jnp.zeros((4,)))
    f, _ = gather_batch(store, jnp.asarray([1, 0]))
    assert f.shape == (2, 2)                # jnp path, no kernel invoked


# --------------------------------------------------------------- resume
def test_engine_resume_matches_uninterrupted_run(tmp_path):
    """Satellite: a run checkpointed at round 4 and resumed for rounds
    5..6 lands bit-for-bit on the uninterrupted 6-round run — state,
    final eval, and cadence all aligned (cohort stream replayed)."""
    base = ExperimentConfig(algo="cyclesfl", task="image", rounds=6,
                            n_clients=8, attendance=0.5, batch=4, width=4,
                            eval_every=2, seed=0)

    class Rec:
        def __init__(self):
            self.state = None

        def on_round(self, engine, rnd, state, metrics):
            self.state = state

    # uninterrupted reference
    ra = Rec()
    full = Engine(replace(base, ckpt_dir=str(tmp_path / "a")),
                  callbacks=(ra,), log=lambda *a, **k: None).run()
    # interrupted at round 4 (ckpts land at eval rounds 2, 4)...
    dir_b = str(tmp_path / "b")
    Engine(replace(base, rounds=4, ckpt_dir=dir_b),
           log=lambda *a, **k: None).run()
    # ...then resumed to 6
    rb = Rec()
    resumed = Engine(replace(base, ckpt_dir=dir_b, resume=True),
                     callbacks=(rb,), log=lambda *a, **k: None).run()
    assert resumed["resumed_from_round"] == 4
    for la, lb in zip(jax.tree.leaves(ra.state), jax.tree.leaves(rb.state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # eval cadence aligned: the resumed history covers rounds 6 only,
    # and its entries equal the reference's tail
    tail = [h for h in full["history"] if h["round"] > 4]
    assert [h["round"] for h in resumed["history"]] == \
        [h["round"] for h in tail]
    for got, want in zip(resumed["history"], tail):
        assert got["test_loss"] == want["test_loss"]


def test_engine_resume_noop_without_checkpoints(tmp_path):
    """resume=True with an empty ckpt_dir starts from scratch."""
    cfg = ExperimentConfig(algo="psl", task="image", rounds=2, n_clients=8,
                           attendance=0.5, batch=4, width=4, eval_every=2,
                           seed=0, ckpt_dir=str(tmp_path / "empty"),
                           resume=True)
    res = Engine(cfg, log=lambda *a, **k: None).run()
    assert "resumed_from_round" not in res
    assert len(res["history"]) == 1
