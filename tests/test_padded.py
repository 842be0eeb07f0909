"""Padded-cohort execution: the compile-once contract and its goldens.

Three guarantees, per registered algorithm:

1. **Padded == unpadded, bit-for-bit.**  A round executed at capacity
   C_max > live cohort (sentinel ids, zeroed batches, attendance mask)
   produces exactly the same TrainState and metrics as the same round
   executed at the live size.  For the cycle algorithms both sides run
   the mask-aware path (the masked resample plan is shape-invariant by
   construction); the plain-mean algorithms are additionally compared
   against the truly unmasked legacy call.
2. **One trace per (algo, config).**  Rounds with varying live cohort
   sizes (fixed capacity, varying mask) never retrace the jitted round.
3. **The fused Adam path is the jnp Adam.**  adam(fused=True) (Pallas,
   interpret mode on CPU) matches the tree-map reference through
   entity_step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import PROGRAMS, build_algorithm, get_program
from repro.api.phases import ServerUpdate
from repro.core.cyclesl import CycleConfig
from repro.core.feature_store import masked_resample_plan
from repro.core.protocol import init_entity, entity_step
from repro.core.split import make_stage_task
from repro.data.federated import sample_cohort
from repro.models.cnn import mlp
from repro.optim import adam

C, B, PAD = 4, 8, 3


@pytest.fixture(scope="module")
def setup():
    task = make_stage_task(mlp(8, [16], 4), cut=1, kind="xent")
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8, 4))
    xs = np.stack([rng.normal(size=(B, 8))
                   for _ in range(C)]).astype(np.float32)
    ys = np.argmax(xs @ w, axis=-1)
    return task, jnp.asarray(xs), jnp.asarray(ys)


def _padded(xs, ys):
    cohort = jnp.arange(C)
    xs_p = jnp.concatenate([xs, jnp.zeros((PAD,) + xs.shape[1:], xs.dtype)])
    ys_p = jnp.concatenate([ys, jnp.zeros((PAD,) + ys.shape[1:], ys.dtype)])
    cohort_p = jnp.concatenate([cohort, jnp.full((PAD,), C, cohort.dtype)])
    mask_p = jnp.concatenate([jnp.ones(C, jnp.float32),
                              jnp.zeros(PAD, jnp.float32)])
    return cohort, cohort_p, xs_p, ys_p, mask_p


def _assert_trees_equal(a, b, msg, exact=True):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if exact:
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                          err_msg=msg)
        else:
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-6, atol=1e-8, err_msg=msg)


def _is_cycle(name):
    return any(getattr(p, "mode", None) == "cycle"
               for p in get_program(name).phases)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_padded_round_matches_unpadded_bit_for_bit(name, setup):
    """The tentpole golden: executing at capacity C+PAD with a mask is
    bit-identical to executing at the live size C, for every algorithm,
    over multiple rounds (params, optimizer state, and metrics)."""
    task, xs, ys = setup
    cohort, cohort_p, xs_p, ys_p, mask_p = _padded(xs, ys)
    mask_live = jnp.ones(C, jnp.float32)
    opt = adam(5e-3)
    algo = build_algorithm(get_program(name), task, opt, opt,
                           CycleConfig(server_epochs=2))
    s_live = algo.init(jax.random.PRNGKey(0), n_clients=C)
    s_pad = algo.init(jax.random.PRNGKey(0), n_clients=C)
    for r in range(3):
        k = jax.random.PRNGKey(r)
        s_live, m_live = algo.round(s_live, cohort, xs, ys, k, mask_live)
        s_pad, m_pad = algo.round(s_pad, cohort_p, xs_p, ys_p, k, mask_p)
        for key in m_live:
            got, want = np.asarray(m_pad[key]), np.asarray(m_live[key])
            if key == "server_step_loss":
                # one entry per scan step: the padded loop runs the
                # capacity's steps, and its skipped steps read 0
                got, want = got[got != 0], want[want != 0]
            np.testing.assert_array_equal(
                want, got, err_msg=f"{name} round {r}: metric {key}")
    _assert_trees_equal(s_live.server, s_pad.server, f"{name}: server state")
    cl_live = s_live.clients if s_live.clients is not None \
        else s_live.client_global
    cl_pad = s_pad.clients if s_pad.clients is not None \
        else s_pad.client_global
    _assert_trees_equal(cl_live, cl_pad, f"{name}: client state")


@pytest.mark.parametrize("name",
                         sorted(n for n in PROGRAMS if not _is_cycle(n)))
def test_masked_all_ones_matches_legacy_unmasked(name, setup):
    """For every non-cycle algorithm the mask-aware path with an
    all-ones mask reproduces the legacy unmasked call (bit-for-bit,
    except ssl where the extra selects reorder XLA fusion at ~1e-9).
    The cycle algorithms are excluded by design: their masked server
    resample plan is a different — shape-invariant — random stream."""
    task, xs, ys = setup
    cohort = jnp.arange(C)
    opt = adam(5e-3)
    algo = build_algorithm(get_program(name), task, opt, opt,
                           CycleConfig(server_epochs=2))
    s_a = algo.init(jax.random.PRNGKey(0), n_clients=C)
    s_b = algo.init(jax.random.PRNGKey(0), n_clients=C)
    for r in range(3):
        k = jax.random.PRNGKey(r)
        s_a, _ = algo.round(s_a, cohort, xs, ys, k)
        s_b, _ = algo.round(s_b, cohort, xs, ys, k,
                            jnp.ones(C, jnp.float32))
    _assert_trees_equal(s_a.server.params, s_b.server.params,
                        f"{name}: server params", exact=(name != "ssl"))


@pytest.mark.parametrize("name", ["cyclesfl", "psl", "cyclessl"])
def test_round_traces_exactly_once_across_varying_cohorts(name, setup):
    """The compile-stability acceptance: with fixed padded shapes and a
    varying attendance mask, the round function is traced exactly once
    no matter how the live cohort size changes round to round."""
    task, xs, ys = setup
    _, cohort_p, xs_p, ys_p, _ = _padded(xs, ys)
    opt = adam(5e-3)
    algo = build_algorithm(get_program(name), task, opt, opt,
                           CycleConfig(server_epochs=1))
    state = algo.init(jax.random.PRNGKey(0), n_clients=C)
    cap = C + PAD
    for r in range(6):
        live = 2 + r % 3                       # live cohort size varies
        mask = jnp.asarray((np.arange(cap) < live).astype(np.float32))
        state, m = algo.round(state, cohort_p, xs_p, ys_p,
                              jax.random.PRNGKey(r), mask)
        assert np.isfinite(float(m["server_loss"]))
    assert algo.trace_count == 1, (
        f"{name}: round retraced {algo.trace_count} times across varying "
        "live cohort sizes — compile-once contract broken")


def test_masked_resample_plan_is_capacity_invariant():
    """The live-row sequence the plan yields must not depend on how much
    padding sits behind the live rows — the property the padded-vs-
    unpadded goldens rest on."""
    key = jax.random.PRNGKey(7)
    n_live, batch, epochs = 20, 5, 3
    for cap in (n_live, n_live + 7, n_live + 40):
        valid = jnp.concatenate([jnp.ones(n_live), jnp.zeros(cap - n_live)])
        plan, ok = masked_resample_plan(key, valid, epochs, batch)
        live_steps = n_live // batch
        assert bool(jnp.all(ok[:, :live_steps]))
        assert bool(jnp.all(~ok[:, live_steps:]))
        got = np.asarray(plan[:, :live_steps])
        if cap == n_live:
            want = got
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"capacity {cap}")
        # valid steps index live rows only, each epoch a permutation slice
        assert got.max() < n_live
        for e in range(epochs):
            flat = got[e].reshape(-1)
            assert len(set(flat.tolist())) == len(flat)


def test_sample_cohort_variable_attendance():
    rng = np.random.default_rng(0)
    sizes = {len(sample_cohort(100, 0.1, rng, min_cohort=2, variable=True,
                               max_cohort=15)) for _ in range(200)}
    assert len(sizes) > 1                      # sizes actually vary
    assert min(sizes) >= 2 and max(sizes) <= 15
    # deterministic protocol unchanged
    rng = np.random.default_rng(0)
    assert len(sample_cohort(100, 0.05, rng)) == 5


def test_fused_adam_matches_reference_through_entity_step():
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(33, 7)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape), jnp.float32), params)
    ref = adam(1e-3, weight_decay=0.01)
    fus = adam(1e-3, weight_decay=0.01, fused=True)   # Pallas (interpret)
    assert fus.apply is not None and ref.apply is None  # CPU auto-gates off
    e_r, e_f = init_entity(params, ref), init_entity(params, fus)
    for _ in range(3):
        e_r = entity_step(e_r, grads, ref)
        e_f = entity_step(e_f, grads, fus)
    assert int(e_r.step) == int(e_f.step) == 3
    for a, b in zip(jax.tree.leaves(e_r.params), jax.tree.leaves(e_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    for a, b in zip(jax.tree.leaves(e_r.opt_state),
                    jax.tree.leaves(e_f.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fused_adam_rejects_schedules():
    with pytest.raises(ValueError):
        adam(lambda s: 1e-3, fused=True)


def test_engine_capacity_matches_deterministic_sampler():
    """Deterministic attendance must never produce a dead padded slot:
    capacity == round(attendance * N) == the sampler's draw."""
    from repro.api import Engine, ExperimentConfig
    cfg = ExperimentConfig(algo="cyclesfl", task="image", rounds=1,
                           n_clients=20, attendance=0.21, width=4, seed=0)
    eng = Engine(cfg, log=lambda *a, **k: None)
    assert eng.cohort_capacity == 4          # round(4.2), not ceil
    _, _, _, mask = eng.sample_round(np.random.default_rng(0))
    assert float(mask.sum()) == eng.cohort_capacity
    # variable attendance bounds the Binomial with the ceil
    from dataclasses import replace
    eng = Engine(replace(cfg, variable_attendance=True),
                 log=lambda *a, **k: None)
    assert eng.cohort_capacity == 5


def test_engine_rejects_server_batch_exceeding_min_live_pool():
    """A static server batch larger than the smallest possible live
    pool would silently skip server training in sparse rounds."""
    from repro.api import Engine, ExperimentConfig
    cfg = ExperimentConfig(algo="cyclesfl", task="image", rounds=1,
                           n_clients=24, attendance=0.25, batch=8,
                           min_cohort=2, width=4, seed=0,
                           variable_attendance=True,
                           cycle=CycleConfig(server_batch=32))
    with pytest.raises(ValueError, match="server_batch"):
        Engine(cfg, log=lambda *a, **k: None)


def test_cycle_variants_share_masked_plan_semantics(setup):
    """A padded cycle round with server_steps capped still matches its
    live-size reference (the step-validity mask composes with the
    server_steps truncation)."""
    task, xs, ys = setup
    cohort, cohort_p, xs_p, ys_p, mask_p = _padded(xs, ys)
    opt = adam(5e-3)
    algo = build_algorithm(get_program("cyclesfl"), task, opt, opt,
                           CycleConfig(server_epochs=3, server_steps=2))
    s_live = algo.init(jax.random.PRNGKey(0), n_clients=C)
    s_pad = algo.init(jax.random.PRNGKey(0), n_clients=C)
    k = jax.random.PRNGKey(0)
    s_live, m_live = algo.round(s_live, cohort, xs, ys, k,
                                jnp.ones(C, jnp.float32))
    s_pad, m_pad = algo.round(s_pad, cohort_p, xs_p, ys_p, k, mask_p)
    np.testing.assert_array_equal(np.asarray(m_live["server_loss"]),
                                  np.asarray(m_pad["server_loss"]))
    _assert_trees_equal(s_live.server.params, s_pad.server.params,
                        "server_steps cap under padding")


@pytest.mark.parametrize("padded", [False, True], ids=["unpadded", "padded"])
def test_server_step_loss_mean_is_server_loss(padded, setup):
    """``server_step_loss`` holds every inner step's loss: its mean over
    the live steps is the round's ``server_loss``, and the steps the
    mask skips read 0."""
    task, xs, ys = setup
    cohort, cohort_p, xs_p, ys_p, mask_p = _padded(xs, ys)
    opt = adam(5e-3)
    epochs = 2
    algo = build_algorithm(get_program("cyclepsl"), task, opt, opt,
                           CycleConfig(server_epochs=epochs))
    state = algo.init(jax.random.PRNGKey(0), n_clients=C)
    k = jax.random.PRNGKey(0)
    if padded:
        _, m = algo.round(state, cohort_p, xs_p, ys_p, k, mask_p)
    else:
        _, m = algo.round(state, cohort, xs, ys, k)
    steps = np.asarray(m["server_step_loss"])
    live = steps[steps != 0]
    assert steps.shape == ((C + PAD if padded else C) * epochs,)
    assert live.shape == (C * epochs,)            # server batch = B
    np.testing.assert_allclose(live.mean(), float(m["server_loss"]),
                               rtol=1e-6)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_server_step_loss_only_in_cycle_programs(name, setup):
    """Only a cycle-mode ServerUpdate runs an inner loop, so only it
    reports the per-step loss."""
    task, xs, ys = setup
    opt = adam(5e-3)
    algo = build_algorithm(get_program(name), task, opt, opt, CycleConfig())
    state = algo.init(jax.random.PRNGKey(0), n_clients=C)
    _, m = algo.round(state, jnp.arange(C), xs, ys, jax.random.PRNGKey(0),
                      jnp.ones(C, jnp.float32))
    assert ("server_step_loss" in m) == _is_cycle(name)
