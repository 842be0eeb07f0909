"""The unified experiment API: registry, config round-trip, Engine-vs-
legacy equivalence, and grad clipping."""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (Engine, ExperimentConfig, PROGRAMS, build_algorithm,
                       build_task, get_program, register_program)
from repro.api.phases import (ClientUpdate, Commit, ExtractFeatures,
                              FeatureGradients, RoundProgram, ServerUpdate)
from repro.core.algorithms import ALGORITHMS, make_algorithm
from repro.core.cyclesl import (CycleConfig, client_updates, cyclesl_round,
                                server_inner_loop)
from repro.core.feature_store import FeatureStore
from repro.core.protocol import broadcast_entity, init_entity
from repro.core.split import make_stage_task
from repro.data.federated import sample_cohort
from repro.models.cnn import mlp
from repro.optim import adam, sgd


# ---------------------------------------------------------------- registry
def test_all_algorithms_resolve_through_registry():
    assert sorted(ALGORITHMS) == sorted(PROGRAMS)
    assert len(PROGRAMS) == 10
    for name in ALGORITHMS:
        prog = get_program(name)
        assert prog.name == name
        assert prog.phases


def test_cycle_variants_are_baselines_with_server_phase_swapped():
    """The paper's drop-in claim, structurally: cyclesfl == sflv1 with the
    server phase swapped to the CycleSL inner loop and feature gradients
    taken at the UPDATED server."""
    for base, cyc in (("sflv1", "cyclesfl"), ("psl", "cyclepsl"),
                      ("sglr", "cyclesglr")):
        b, c = get_program(base), get_program(cyc)
        assert [type(p) for p in b.phases] == [type(p) for p in c.phases]
        sb = next(p for p in b.phases if isinstance(p, ServerUpdate))
        sc = next(p for p in c.phases if isinstance(p, ServerUpdate))
        assert sb.mode != "cycle" and sc.mode == "cycle"
        fb = next(p for p in b.phases if isinstance(p, FeatureGradients))
        fc = next(p for p in c.phases if isinstance(p, FeatureGradients))
        assert not fb.use_updated and fc.use_updated
        cb = next(p for p in b.phases if isinstance(p, Commit))
        cc = next(p for p in c.phases if isinstance(p, Commit))
        assert cb.mode == cc.mode


def test_register_program_guards_duplicates():
    prog = get_program("psl")
    with pytest.raises(ValueError):
        register_program(prog)
    with pytest.raises(KeyError):
        get_program("definitely-not-an-algo")


def test_make_algorithm_is_deprecated_shim():
    task = make_stage_task(mlp(8, [16], 4), cut=1, kind="xent")
    with pytest.warns(DeprecationWarning):
        algo = make_algorithm("cyclesfl", task, adam(1e-3), adam(1e-3))
    assert algo.uses_global_client


# ------------------------------------------------------------------ config
def test_experiment_config_dict_roundtrip():
    cfg = ExperimentConfig(
        algo="cyclesglr", task="gaze", rounds=7, n_clients=13,
        attendance=0.4, lr_server=3e-4, seed=5, round_key_salt=7919,
        cycle=CycleConfig(server_epochs=3, server_batch=32, grad_clip=0.5,
                          avg_client_grads=True))
    assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg


def test_experiment_config_rejects_unknowns():
    with pytest.raises(KeyError):
        ExperimentConfig.from_dict({"not_a_field": 1})
    with pytest.raises(KeyError):
        ExperimentConfig(algo="nope").validate()
    with pytest.raises(KeyError):
        ExperimentConfig(task="nope").validate()


def test_experiment_config_from_flags():
    import argparse
    ap = argparse.ArgumentParser()
    ExperimentConfig.add_arguments(ap)
    args = ap.parse_args(["--algo", "sglr", "--rounds", "9",
                          "--server-epochs", "2", "--grad-clip", "0.1"])
    cfg = ExperimentConfig.from_flags(args)
    assert cfg.algo == "sglr" and cfg.rounds == 9
    assert cfg.cycle.server_epochs == 2 and cfg.cycle.grad_clip == 0.1


# ------------------------------------------- Engine vs legacy equivalence
class _Recorder:
    def __init__(self):
        self.rows = []
        self.state = None

    def on_round(self, engine, rnd, state, metrics):
        self.rows.append({k: np.asarray(v) for k, v in metrics.items()})
        self.state = state


def _legacy_loop(cfg, task, fed, with_mask=False):
    """The old hand-rolled driver, built on the deprecated shim.

    ``with_mask=True`` mirrors the Engine's padded-cohort protocol (an
    all-ones attendance mask at full capacity) — needed for the cycle
    algorithms, whose masked server resample plan is a different (shape-
    invariant) random stream than the dense unmasked plan.
    """
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        algo = make_algorithm(cfg.algo, task, adam(cfg.lr_server),
                              adam(cfg.lr_client), cfg.cycle)
    state = algo.init(jax.random.PRNGKey(cfg.seed), fed.n_clients)
    rng = np.random.default_rng(cfg.seed + 1)
    rows = []
    for rnd in range(cfg.rounds):
        cohort = sample_cohort(fed.n_clients, cfg.attendance, rng,
                               min_cohort=cfg.min_cohort)
        pairs = [fed.clients[c].sample_batch(rng, cfg.batch) for c in cohort]
        xs = jnp.asarray(np.stack([p[0] for p in pairs]))
        ys = jnp.asarray(np.stack([p[1] for p in pairs]))
        key = jax.random.PRNGKey(cfg.seed * cfg.round_key_salt + rnd)
        if with_mask:
            state, m = algo.round(state, jnp.asarray(cohort), xs, ys, key,
                                  jnp.ones(len(cohort), jnp.float32))
        else:
            state, m = algo.round(state, jnp.asarray(cohort), xs, ys, key)
        rows.append({k: np.asarray(v) for k, v in m.items()})
    return state, rows


def _checksum(tree):
    return float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
                     for l in jax.tree.leaves(tree)))


@pytest.mark.parametrize("algo", ["cyclesfl", "sglr"])
def test_engine_matches_legacy_path_round_for_round(algo):
    """Same seed, same task -> identical per-round metrics and final
    parameters for the Engine driver vs the legacy make_algorithm loop.

    sglr is compared against the truly unmasked legacy call, proving the
    Engine's padded execution (all-ones mask here: attendance * N is the
    capacity) is numerically transparent; cyclesfl mirrors the mask in
    the legacy loop because the cycle server phase's masked resample
    plan is a deliberately different random stream (see test_padded.py
    for the padded-vs-unpadded goldens).
    """
    task, fed, _ = build_task("image", 20, 0.5, 0, width=4, cut=2)
    cfg = ExperimentConfig(algo=algo, task="image", rounds=6, n_clients=20,
                           attendance=0.3, eval_every=6, width=4, seed=3)
    rec = _Recorder()
    Engine(cfg, task=task, fed=fed, callbacks=(rec,),
           log=lambda *a, **k: None).run()
    legacy_state, legacy_rows = _legacy_loop(cfg, task, fed,
                                             with_mask=(algo == "cyclesfl"))

    assert len(rec.rows) == len(legacy_rows) == cfg.rounds
    for got, want in zip(rec.rows, legacy_rows):
        assert sorted(got) == sorted(want)
        for k in want:
            # atol floor: sglr's feat_grad_norm_std is mathematically 0
            # (all cohort grads identical after averaging), so the two
            # summation orders differ only in ~1e-11 float noise
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6,
                                       atol=1e-9, err_msg=f"{algo}:{k}")
    np.testing.assert_allclose(_checksum(rec.state.server.params),
                               _checksum(legacy_state.server.params),
                               rtol=1e-6)


def test_programs_match_pre_refactor_golden_metrics():
    """Guard against semantic drift in the phase rewrites: per-round
    metrics + final param checksums recorded from the pre-refactor
    closure implementations (the deleted ``_psl_round``/``_sglr_round``/
    etc.), all 10 algorithms, 5 rounds on a fixed mlp task.

    (The Engine-vs-legacy test above can't catch this — make_algorithm
    is now a shim over the same phases — so the old numbers are pinned
    as a golden file instead.)
    """
    golden_path = os.path.join(os.path.dirname(__file__), "golden",
                               "legacy_algorithm_metrics.json")
    with open(golden_path) as f:
        golden = json.load(f)
    task = make_stage_task(mlp(8, [16], 4), cut=1, kind="xent")
    rng = np.random.default_rng(0)
    C, b = 4, 8
    w = rng.normal(size=(8, 4))
    xs, ys = [], []
    for _ in range(C):
        x = rng.normal(size=(b, 8))
        xs.append(x)
        ys.append(np.argmax(x @ w, axis=-1))
    xs = jnp.asarray(np.stack(xs), jnp.float32)
    ys = jnp.asarray(np.stack(ys))
    opt = adam(5e-3)
    for name, rows in golden.items():
        algo = build_algorithm(get_program(name), task, opt, opt,
                               CycleConfig(server_epochs=2))
        state = algo.init(jax.random.PRNGKey(0), n_clients=C)
        for r, want in enumerate(rows[:-1]):
            state, m = algo.round(state, jnp.arange(C), xs, ys,
                                  jax.random.PRNGKey(r))
            for k, v in want.items():
                np.testing.assert_allclose(
                    float(m[k]), v, rtol=1e-3, atol=1e-6,
                    err_msg=f"{name} round {r}: {k}")
        want_ck = rows[-1]
        np.testing.assert_allclose(
            _checksum(state.server.params), want_ck["server_ck"],
            rtol=1e-3, err_msg=f"{name}: server params")
        got_clients = (state.clients if state.clients is not None
                       else state.client_global)
        np.testing.assert_allclose(
            _checksum(got_clients.params), want_ck["clients_ck"],
            rtol=1e-3, err_msg=f"{name}: client params")


# --------------------------------------------------------------- grad clip
@pytest.fixture(scope="module")
def clip_setup():
    task = make_stage_task(mlp(8, [16], 4), cut=1, kind="xent")
    rng = np.random.default_rng(0)
    C, b = 3, 8
    # large-scale inputs so raw gradients comfortably exceed the clip
    xs = jnp.asarray(rng.normal(size=(C, b, 8)) * 50, jnp.float32)
    ys = jnp.asarray(rng.integers(0, 4, size=(C, b)))
    return task, xs, ys


def test_client_updates_clip_bounds_grad_norms(clip_setup):
    task, xs, ys = clip_setup
    opt = sgd(0.1)
    clients = broadcast_entity(
        init_entity(task.init_client(jax.random.PRNGKey(1)), opt), 3)
    fgrads = jnp.asarray(np.random.default_rng(1).normal(
        size=(3, 8, 16)) * 10, jnp.float32)
    _, gnorms_raw = client_updates(task, clients, opt, xs, fgrads)
    assert float(jnp.max(gnorms_raw)) > 1e-2      # unclipped: big
    clip = 1e-2
    _, gnorms = client_updates(task, clients, opt, xs, fgrads,
                               grad_clip=clip)
    assert float(jnp.max(gnorms)) <= clip * (1 + 1e-5)


def test_server_inner_loop_clip_bounds_param_steps(clip_setup):
    """With SGD(lr=1) and clip c, each inner step moves the server params
    by at most c in global norm -> total drift <= steps * c."""
    task, xs, ys = clip_setup
    opt = sgd(1.0)
    server = init_entity(task.init_server(jax.random.PRNGKey(0)), opt)
    feats = jax.vmap(lambda x: task.client_forward(
        task.init_client(jax.random.PRNGKey(1)), x))(xs)
    store = FeatureStore.pool(feats, ys)
    clip = 1e-3
    ccfg = CycleConfig(server_epochs=2, grad_clip=clip)
    server2, _ = server_inner_loop(task, server, opt, store,
                                   jax.random.PRNGKey(2), ccfg, batch=8)
    steps = int(server2.step)
    drift = jnp.sqrt(sum(
        jnp.sum(jnp.square(a - b)) for a, b in
        zip(jax.tree.leaves(server2.params), jax.tree.leaves(server.params))))
    assert steps > 0
    assert float(drift) <= steps * clip * (1 + 1e-4)
    # and the unclipped loop drifts much further
    server3, _ = server_inner_loop(task, server, opt, store,
                                   jax.random.PRNGKey(2),
                                   CycleConfig(server_epochs=2), batch=8)
    drift_raw = jnp.sqrt(sum(
        jnp.sum(jnp.square(a - b)) for a, b in
        zip(jax.tree.leaves(server3.params), jax.tree.leaves(server.params))))
    assert float(drift_raw) > float(drift) * 10


def test_cyclesl_round_respects_grad_clip(clip_setup):
    task, xs, ys = clip_setup
    opt = sgd(0.1)
    server = init_entity(task.init_server(jax.random.PRNGKey(0)), opt)
    clients = broadcast_entity(
        init_entity(task.init_client(jax.random.PRNGKey(1)), opt), 3)
    clip = 1e-3
    _, _, metrics = cyclesl_round(task, server, clients, opt, opt, xs, ys,
                                  jax.random.PRNGKey(2),
                                  CycleConfig(grad_clip=clip))
    assert float(metrics["client_grad_norm_mean"]) <= clip * (1 + 1e-5)


# ------------------------------------------------------------------ engine
def test_engine_runs_every_registered_algorithm():
    """Every registry entry compiles and learns through the one driver."""
    task = make_stage_task(mlp(8, [32], 4), cut=1, kind="xent")
    rng = np.random.default_rng(0)
    C, b = 4, 32
    w = rng.normal(size=(8, 4))
    xs, ys = [], []
    for _ in range(C):
        x = rng.normal(size=(b, 8))
        xs.append(x)
        ys.append(np.argmax(x @ w, axis=-1))
    xs = jnp.asarray(np.stack(xs), jnp.float32)
    ys = jnp.asarray(np.stack(ys))
    opt = adam(5e-3)
    for name in PROGRAMS:
        algo = build_algorithm(get_program(name), task, opt, opt,
                               CycleConfig(server_epochs=1))
        state = algo.init(jax.random.PRNGKey(0), n_clients=C)
        first = None
        for r in range(15):
            state, m = algo.round(state, jnp.arange(C), xs, ys,
                                  jax.random.PRNGKey(r))
            if first is None:
                first = float(m["server_loss"])
        assert float(m["server_loss"]) < first, name
