"""A plain reference of the split-learning round, independent of the program.

One round over a cohort of C clients, each with a batch of b samples:

  1. every client computes its smashed features  f_i = client(x_i)
  2. the server trains:
       ``cycle``       pool all C * b rows, order them by the round key
                       (row r draws ``uniform(fold_in(split(key, E)[e], r))``
                       and rows are taken in ascending order), and take one
                       Adam step per ``server_batch`` rows, E epochs
       ``mean_grad``   one Adam step on the cohort-mean of the per-client
                       gradients at the pre-round server
  3. feature gradients d loss_i / d f_i at the updated (``updated``) or
     the pre-round (``pre``) server, optionally averaged over the cohort
  4. each client pulls its feature gradient through its own forward
     (VJP) and takes one Adam step
  5. commit: per-client states are kept (``per_client``) or the cohort's
     states are averaged into the one shared client (``average``)

Adam: b1 0.9, b2 0.999, eps 1e-8, bias-corrected, the learning rate of
the traffic file.  The model comes from the configuration's own
reference module: its weights' split at the cut, its two forward
passes, and its loss, the mean softmax cross-entropy over every label
of a batch's rows.  ``dtype``/``precision`` set the arithmetic;
``half=True`` takes every batch mean over the first half of the rows
only (a planted fault).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def entity(params, dtype) -> dict:
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"params": params, "m": zeros, "v": zeros,
            "step": jnp.zeros((), jnp.int32)}


def adam(e: dict, g, lr: float) -> dict:
    t = e["step"].astype(jnp.float32) + 1.0
    c1, c2 = 1.0 - B1 ** t, 1.0 - B2 ** t

    def one(p, m, v, g):
        dt = p.dtype
        p, m, v, g = (a.astype(jnp.float32) for a in (p, m, v, g))
        m = B1 * m + (1.0 - B1) * g
        v = B2 * v + (1.0 - B2) * g * g
        p = p - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS)
        return p.astype(dt), m.astype(dt), v.astype(dt)

    out = jax.tree.map(one, e["params"], e["m"], e["v"], g)
    pick = lambda k: jax.tree.map(lambda o: o[k], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return {"params": pick(0), "m": pick(1), "v": pick(2),
            "step": e["step"] + 1}


def resample_order(key, rows: int, epochs: int, batch: int) -> jax.Array:
    """[epochs * steps, batch] row indices: each epoch's rows sorted by
    their own uniform draw; the tail that fills no batch is dropped."""
    steps = rows // batch

    def epoch(k):
        u = jax.vmap(lambda r: jax.random.uniform(jax.random.fold_in(k, r)))(
            jnp.arange(rows))
        return jnp.argsort(u)[: steps * batch].reshape(steps, batch)

    return jax.vmap(epoch)(jax.random.split(key, epochs)).reshape(-1, batch)


def make_round(model, mcfg: dict, traffic: dict, dtype, precision,
               half: bool = False):
    """jitted ``(server, cohort, xs, ys, key) -> (server, cohort, loss)``:
    ``cohort`` is the stacked client entities of the round's C clients."""
    cut = traffic["cut"]
    lr_s, lr_c = traffic["lr_server"], traffic["lr_client"]

    def client_fwd(cp, x):
        return model.client_forward(cp, x, mcfg, cut, precision, dtype)

    def server_loss(sp, f, y):
        return model.loss(model.server_forward(sp, f, mcfg, cut, precision),
                          y, half)

    def feat_grad(sp, f, y):
        return jax.grad(lambda ff: server_loss(sp, ff, y))(f)

    @jax.jit
    def round_fn(server, cohort, xs, ys, key):
        feats = jax.vmap(client_fwd)(cohort["params"], xs)
        pre = server["params"]
        mode = traffic["server_mode"]
        if mode == "cycle":
            c, b = ys.shape[:2]
            pool_f = feats.reshape((c * b,) + feats.shape[2:])
            pool_y = ys.reshape((c * b,) + ys.shape[2:])
            order = resample_order(key, c * b, traffic["server_epochs"],
                                   traffic["server_batch"])

            def step(e, idx):
                loss, g = jax.value_and_grad(server_loss)(
                    e["params"], pool_f[idx], pool_y[idx])
                return adam(e, g, lr_s), loss

            server, losses = jax.lax.scan(step, server, order)
        elif mode == "mean_grad":
            losses, gs = jax.vmap(jax.value_and_grad(server_loss),
                                  (None, 0, 0))(pre, feats, ys)
            server = adam(server, jax.tree.map(lambda g: jnp.mean(g, axis=0),
                                               gs), lr_s)
        else:
            raise ValueError(f"unknown server_mode {mode!r}")
        sp = (server["params"] if traffic["feature_grads_from"] == "updated"
              else pre)
        fg = jax.vmap(feat_grad, (None, 0, 0))(sp, feats, ys)
        if traffic["average_feature_grads"]:
            fg = jnp.broadcast_to(jnp.mean(fg, axis=0), fg.shape)

        def client_step(e, x, g):
            _, vjp = jax.vjp(lambda p: client_fwd(p, x), e["params"])
            return adam(e, vjp(g.astype(dtype))[0], lr_c)

        cohort = jax.vmap(client_step)(cohort, xs, fg)
        return server, cohort, jnp.mean(losses)

    return round_fn


def leaf_norms(tree) -> dict:
    """{path: float32 norm} of every leaf of a params-shaped tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        l.astype(jnp.float32)))) for p, l in flat}


def run(model, mcfg: dict, traffic: dict, theta0, rounds: list, *,
        dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
        half: bool = False) -> dict:
    """Drive the reference through ``rounds`` (each a dict of host
    ``cohort`` ids, ``xs``, ``ys`` of the live clients and the round
    ``key``) from the weights ``theta0`` (the whole model).

    Returns ``loss`` per round, the per-leaf norms of Adam's first
    moment after round 1 (``m1``: ``server/...`` and ``client/...``,
    client norms over every client) and of the parameters' change after
    the last round (``change``)."""
    client_theta0, server_theta0 = model.split(theta0, traffic["cut"])
    server = entity(server_theta0, dtype)
    client0 = entity(client_theta0, dtype)
    round_fn = make_round(model, mcfg, traffic, dtype, precision, half)
    shared = traffic["commit"] == "average"
    ids = sorted({int(c) for r in rounds for c in r["cohort"]})
    slot = {c: i for i, c in enumerate(ids)}
    # per-client states of every client the rounds touch (the others
    # keep theta0 and add nothing to a norm of change or of moments)
    stack = (client0 if shared else
             jax.tree.map(lambda a: jnp.broadcast_to(a, (len(ids),) + a.shape),
                          client0))
    out = {"loss": []}

    def norms(tree, name):
        return {f"{name}{k}": float(v) for k, v in leaf_norms(tree).items()}

    for i, r in enumerate(rounds):
        c = len(r["cohort"])
        idx = np.array([slot[int(k)] for k in r["cohort"]])
        cohort = (jax.tree.map(lambda a: jnp.broadcast_to(a, (c,) + a.shape),
                               stack) if shared else
                  jax.tree.map(lambda a: a[idx], stack))
        server, cohort, loss = round_fn(server, cohort, jnp.asarray(r["xs"]),
                                        jnp.asarray(r["ys"]), r["key"])
        if shared:
            stack = {**jax.tree.map(lambda a: jnp.mean(a, axis=0).astype(
                a.dtype), {k: cohort[k] for k in ("params", "m", "v")}),
                "step": stack["step"] + 1}
        else:
            stack = jax.tree.map(lambda s, v: s.at[idx].set(v), stack, cohort)
        out["loss"].append(float(loss))
        if i == 0:
            out["m1"] = {**norms(server["m"], "server"),
                         **norms(stack["m"], "client")}
    change = lambda new, old: jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old)
    out["change"] = {
        **norms(change(server["params"], entity(server_theta0, dtype)["params"]),
                "server"),
        **norms(change(stack["params"], client0["params"]), "client")}
    return out
