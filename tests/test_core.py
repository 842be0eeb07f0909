"""CycleSL core semantics: the properties that make it the paper's method."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.algorithms import ALGORITHMS, make_algorithm
from repro.core.cyclesl import (CycleConfig, cyclesl_round,
                                feature_gradients, server_inner_loop)
from repro.core.feature_store import FeatureStore, gather_batch, resample_plan
from repro.core.protocol import broadcast_entity, init_entity
from repro.core.split import make_stage_task
from repro.models.cnn import femnist_cnn, mlp
from repro.optim import adam, sgd


@pytest.fixture(scope="module")
def small_task():
    return make_stage_task(mlp(8, [16], 4), cut=1, kind="xent")


def _cohort_batches(rng, C=3, b=8, d=8, classes=4):
    xs = jnp.asarray(rng.normal(size=(C, b, d)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, classes, size=(C, b)))
    return xs, ys


def test_feature_store_pool_shapes(rng):
    f = jnp.arange(2 * 3 * 5, dtype=jnp.float32).reshape(2, 3, 5)
    y = jnp.arange(6).reshape(2, 3)
    store = FeatureStore.pool(f, y)
    assert store.features.shape == (6, 5)
    assert store.size == 6
    # pooling preserves (client, sample) order row-major
    np.testing.assert_array_equal(np.asarray(store.features[3]),
                                  np.asarray(f[1, 0]))


def test_resample_plan_is_per_epoch_permutation():
    plan = resample_plan(jax.random.PRNGKey(0), total=32, epochs=3, batch=8)
    assert plan.shape == (3, 4, 8)
    for e in range(3):
        seen = np.sort(np.asarray(plan[e]).ravel())
        np.testing.assert_array_equal(seen, np.arange(32))  # no replacement
    # different epochs shuffle differently
    assert not np.array_equal(np.asarray(plan[0]), np.asarray(plan[1]))


def test_resampled_batches_are_not_client_bound(rng):
    """Paper Eq. 3: resampled server batches mix clients."""
    C, b = 4, 16
    plan = resample_plan(jax.random.PRNGKey(1), total=C * b, epochs=1, batch=b)
    owners = np.asarray(plan[0]) // b
    # every server batch should touch >1 client with overwhelming prob.
    assert all(len(np.unique(row)) > 1 for row in owners)


def test_cyclical_order_client_grads_use_updated_server(small_task, rng):
    """Eq. 5: B_i^g must be computed with θ_S^{t+1}, not θ_S^t."""
    xs, ys = _cohort_batches(rng)
    opt = sgd(0.1)
    server = init_entity(small_task.init_server(jax.random.PRNGKey(0)), opt)
    clients = broadcast_entity(
        init_entity(small_task.init_client(jax.random.PRNGKey(1)), opt), 3)
    feats = jax.vmap(small_task.client_forward)(clients.params, xs)
    store = FeatureStore.pool(feats, ys)
    server2, _ = server_inner_loop(small_task, server, opt, store,
                                   jax.random.PRNGKey(2),
                                   CycleConfig(server_epochs=1), batch=8)
    g_new = feature_gradients(small_task, server2.params, feats, ys,
                              CycleConfig())
    g_old = feature_gradients(small_task, server.params, feats, ys,
                              CycleConfig())
    # the round must produce g_new (cyclical), which differs from g_old
    _, _, metrics = cyclesl_round(small_task, server, clients, opt, opt,
                                  xs, ys, jax.random.PRNGKey(2), CycleConfig())
    got = float(metrics["feat_grad_norm_mean"])
    fg = g_new.reshape(g_new.shape[0], -1)
    want_new = float(jnp.mean(jnp.linalg.norm(fg, axis=-1)
                              / jnp.sqrt(fg.shape[-1])))
    fo = g_old.reshape(g_old.shape[0], -1)
    want_old = float(jnp.mean(jnp.linalg.norm(fo, axis=-1)
                              / jnp.sqrt(fo.shape[-1])))
    assert abs(got - want_new) < 1e-5
    assert abs(got - want_old) > 1e-7  # and it is NOT the stale-server grad


def test_server_params_frozen_during_client_phase(small_task, rng):
    """No server gradient leaks into the client phase (stop_gradient wall)."""
    xs, ys = _cohort_batches(rng)
    opt = sgd(0.1)
    server = init_entity(small_task.init_server(jax.random.PRNGKey(0)), opt)
    clients = broadcast_entity(
        init_entity(small_task.init_client(jax.random.PRNGKey(1)), opt), 3)
    ccfg = CycleConfig(server_epochs=1)
    server2, _, _ = cyclesl_round(small_task, server, clients, opt, opt,
                                  xs, ys, jax.random.PRNGKey(2), ccfg)
    # server step count advanced exactly E*steps times (inner loop only)
    assert int(server2.step) == 3  # 3 cohort batches of size 8 / batch 8


def test_cyclesglr_broadcasts_mean_gradient(small_task, rng):
    xs, ys = _cohort_batches(rng)
    opt = sgd(0.1)
    server = init_entity(small_task.init_server(jax.random.PRNGKey(0)), opt)
    feats = jax.vmap(small_task.client_forward)(
        broadcast_entity(init_entity(
            small_task.init_client(jax.random.PRNGKey(1)), opt), 3).params, xs)
    g = feature_gradients(small_task, server.params, feats, ys,
                          CycleConfig(avg_client_grads=True))
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(g[1]), atol=1e-7)


def test_server_epochs_scale_server_steps(small_task, rng):
    xs, ys = _cohort_batches(rng)
    opt = adam(1e-3)
    server = init_entity(small_task.init_server(jax.random.PRNGKey(0)), opt)
    clients = broadcast_entity(
        init_entity(small_task.init_client(jax.random.PRNGKey(1)), opt), 3)
    for E in (1, 2, 4):
        s2, _, _ = cyclesl_round(small_task, server, clients, opt, opt, xs, ys,
                                 jax.random.PRNGKey(2),
                                 CycleConfig(server_epochs=E))
        assert int(s2.step) == 3 * E


def test_all_algorithms_reduce_loss_on_learnable_task(rng):
    """Each algorithm should beat init loss on an easy separable task."""
    task = make_stage_task(mlp(8, [32], 4), cut=1, kind="xent")
    C, b = 4, 32
    w = rng.normal(size=(8, 4))
    xs_all, ys_all = [], []
    for _ in range(C):
        x = rng.normal(size=(b, 8))
        y = np.argmax(x @ w, axis=-1)
        xs_all.append(x)
        ys_all.append(y)
    xs = jnp.asarray(np.stack(xs_all), jnp.float32)
    ys = jnp.asarray(np.stack(ys_all))
    opt = adam(5e-3)
    for name in ALGORITHMS:
        algo = make_algorithm(name, task, opt, opt, CycleConfig(server_epochs=1))
        state = algo.init(jax.random.PRNGKey(0), n_clients=C)
        first = None
        for r in range(25):
            state, m = algo.round(state, jnp.arange(C), xs, ys,
                                  jax.random.PRNGKey(r))
            if first is None:
                first = float(m["server_loss"])
        last = float(m["server_loss"])
        assert last < first, f"{name}: {first} -> {last}"


def test_stage_split_e2e_equals_composition(rng):
    model = femnist_cnn(n_classes=10, width=4)
    task = make_stage_task(model, cut=2)
    params = model.init(jax.random.PRNGKey(0))
    cp, sp = params[:2], params[2:]
    x = jnp.asarray(rng.normal(size=(3, 28, 28, 1)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(task.predict(cp, sp, x)),
        np.asarray(model.apply(params, x)), atol=1e-6)


def _row_shaped_loop(task, server, opt, store, key, epochs, sb, use_kernel):
    """Reference server inner loop that gathers every step's minibatch
    from the row-shaped [T, ...] pool (``gather_batch`` on the store as
    pooled): the same plan, steps, masking and loss accounting as
    :func:`server_inner_loop`."""
    from repro.core.feature_store import masked_resample_plan
    from repro.core.protocol import entity_step, select_entities
    if store.valid is None:
        plan = resample_plan(key, store.size, epochs, sb)
        ok = jnp.ones(plan.shape[:2], bool)
    else:
        plan, ok = masked_resample_plan(key, store.valid, epochs, sb)

    def one_step(carry, inp):
        entity, acc = carry
        idx, live = inp
        f, y = gather_batch(store, idx, use_kernel=use_kernel)
        loss, grads = jax.value_and_grad(task.server_loss)(entity.params,
                                                           f, y)
        stepped = entity_step(entity, grads, opt)
        loss = jnp.where(live, loss, 0.0)
        return (select_entities(live, stepped, entity), acc + loss), loss

    ok2 = ok.reshape(-1)
    (server, acc), per_step = jax.lax.scan(
        one_step, (server, jnp.zeros((), jnp.float32)),
        (plan.reshape(-1, sb), ok2))
    if store.valid is None:
        return server, jnp.mean(per_step), per_step
    return server, acc / jnp.maximum(jnp.sum(ok2.astype(acc.dtype)), 1.0), \
        per_step


@pytest.mark.parametrize("padded", [False, True], ids=["unpadded", "padded"])
@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_flattened_pool_loop_is_bit_for_bit_row_shaped(rng, use_kernel,
                                                       padded):
    """The inner loop gathers from the pool flattened once to [T, D] and
    reshapes each minibatch back to its row shape.  On a server half
    whose first layer is a conv (FEMNIST CNN, cut 1: rows [14, 14, w])
    that is bit-for-bit the loop gathering from the row-shaped pool:
    server params and optimizer state, the mean loss and every step's
    loss, for the jnp gather and the (interpreted) resample kernel."""
    task = make_stage_task(femnist_cnn(n_classes=10, width=4), cut=1,
                           kind="xent")
    C, b = 4, 8
    opt = adam(1e-3)
    server = init_entity(task.init_server(jax.random.PRNGKey(0)), opt)
    client = init_entity(task.init_client(jax.random.PRNGKey(1)), opt)
    xs = jnp.asarray(rng.normal(size=(C, b, 28, 28, 1)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 10, size=(C, b)))
    feats = jax.vmap(task.client_forward, in_axes=(None, 0))(client.params,
                                                             xs)
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0]) if padded else None
    store = FeatureStore.pool(feats, ys, mask=mask)
    assert store.features.shape == (C * b, 14, 14, 4)
    key = jax.random.PRNGKey(2)
    ccfg = CycleConfig(server_epochs=2, resample_use_kernel=use_kernel)

    got_s, got_l = jax.jit(lambda s, st: server_inner_loop(
        task, s, opt, st, key, ccfg, batch=b))(server, store)
    want_s, want_mean, want_steps = jax.jit(lambda s, st: _row_shaped_loop(
        task, s, opt, st, key, 2, b, use_kernel))(server, store)
    for g, w in zip(jax.tree.leaves(got_s), jax.tree.leaves(want_s)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got_l.mean),
                                  np.asarray(want_mean))
    np.testing.assert_array_equal(np.asarray(got_l.per_step),
                                  np.asarray(want_steps))
    assert int(got_s.step) == (2 * (3 if padded else 4))
