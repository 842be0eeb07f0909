"""Declarative round programs: every SL algorithm as a composition of
typed phases over one :class:`TrainState` pytree.

The paper's claim that CycleSL "can be seamlessly integrated with
existing methods" (§3) is made literal here: an algorithm is a
:class:`RoundProgram` — an ordered tuple of phases drawn from

    ExtractFeatures -> ServerUpdate -> FeatureGradients -> ClientUpdate
    -> Commit

so ``cyclepsl``/``cyclesfl``/``cyclesglr`` are exactly ``psl``/``sflv1``/
``sglr`` with ``ServerUpdate(mode=...)`` swapped to the CycleSL inner
loop and ``FeatureGradients`` pointed at the *updated* server (the
cyclical/BCD part, Eq. 5).  The inherently sequential algorithms
(``ssl``, ``sflv2``, ``fedavg``) keep their chained semantics as single
fused phases behind the same interface.

All phases transform a :class:`RoundVars` scratch record inside ONE jit
trace; :func:`build_algorithm` compiles a program into the
``(init, round)`` pair the drivers and the legacy
``repro.core.algorithms.make_algorithm`` shim consume.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.cyclesl import (CycleConfig, client_update_one,
                                client_updates, feature_gradients,
                                server_inner_loop)
from repro.core.feature_store import pool_store
from repro.core.protocol import (EntityState, broadcast_entity, entity_mean,
                                 entity_step, init_entity, masked_axis0_mean,
                                 masked_entity_mean, put_entities,
                                 select_entities, take_entities)
from repro.core.split import SplitTask
from repro.optim import Optimizer
from repro.resilience.guards import health_vector
from repro.sharding.specs import (cohort_entity_step, constrain_cohort,
                                  constrain_cohort_tree,
                                  constrain_entity_params,
                                  sharded_entity_step, slot_shard_map)


class TrainState(NamedTuple):
    """The single pytree every phase transforms (and checkpoints save).

    ``clients`` is the stacked [N, ...] persistent per-client store
    (PSL-family); ``client_global`` the one shared θ_C (SFL-family).
    Exactly one of the two is populated.
    """
    server: EntityState
    clients: Optional[EntityState]
    client_global: Optional[EntityState]


@dataclass(frozen=True)
class SLAlgorithm:
    """Compiled algorithm: what the drivers actually call.

    ``round`` accepts an optional trailing attendance ``mask`` ([C]
    float, 1.0 = live slot); without it the classic unpadded semantics
    apply.  ``trace_count`` exposes how many times the round function
    has been (re)traced by XLA — the compile-stability contract is ONE
    trace per (algo, config) for the whole experiment.
    """
    name: str
    init: Callable[..., TrainState]
    round: Callable[..., tuple[TrainState, dict]]
    uses_global_client: bool
    traces: Any = None

    @property
    def trace_count(self) -> int:
        return self.traces["count"] if self.traces else 0


@dataclass(frozen=True)
class PhaseContext:
    """Static (trace-time) inputs shared by every phase of a round.

    ``mesh`` (a ``jax.sharding.Mesh`` or ``None``) turns on the mesh-
    native execution path: phases thread ``with_sharding_constraint``
    through cohort-stacked activations (leading cohort dim over the
    batch axes), the pooled feature dataset (rows over 'data'), and the
    resampled server minibatches.  Constraints pin layout only, never
    values — the 1-device-mesh round is bit-for-bit the unsharded one.
    """
    task: SplitTask
    opt_server: Optimizer
    opt_client: Optimizer
    cycle: CycleConfig
    mesh: Any = None


@dataclass
class RoundVars:
    """Mutable scratch flowing phase-to-phase inside one jit trace.

    ``mask`` is the attendance mask over cohort SLOTS ([C] float, 1.0 =
    live client, 0.0 = padded slot), or ``None`` on the classic unpadded
    path.  Padded slots carry the out-of-range sentinel id N in
    ``cohort`` and zeroed ``xs``/``ys``; every phase excludes them from
    pooled/averaged quantities so the padded round is numerically
    identical to an unpadded round over the live slots alone.

    Scenario churn reuses the same contract with one difference: a
    mid-round dropout zeroes a LIVE slot's mask entry (the slot keeps
    its real client id and data).  The zero mask alone is sufficient —
    the slot's pooled rows are invalid before ServerUpdate consumes
    them, its feature gradients are excluded from masked means, and the
    Commit scatter/aggregate weighting drops its contribution — so
    churn needs no new phase logic and no retrace.
    """
    state: TrainState
    cohort: Any                       # [C] int client ids
    xs: Any                           # [C, b, ...] inputs
    ys: Any                           # [C, b, ...] labels
    key: Any
    mask: Any = None                  # [C] attendance mask (None = unpadded)
    ema: Any = None                   # loss-EMA carry (guard-on rounds only)
    cohort_clients: Optional[EntityState] = None
    server_prev: Any = None           # θ_S^t params, pre-ServerUpdate
    feats: Any = None                 # [C, b, ...] smashed data
    store: Any = None                 # prebuilt pooled D_S^f (pipelined
                                      # extract handoff); None = pool inline
    fgrads: Any = None                # [C, b, ...] feature gradients
    stale_w: Any = None               # traced staleness weight w(lag)
                                      # (None = unweighted; w scales the
                                      # server + feature gradients)
    metrics: dict = field(default_factory=dict)


class Phase:
    """A typed round phase: ``(PhaseContext, RoundVars) -> None``."""

    def __call__(self, ctx: PhaseContext, v: RoundVars) -> None:
        raise NotImplementedError


def run_phase(phase: Phase, ctx: PhaseContext, v: RoundVars) -> None:
    """Run ``phase`` under a ``jax.named_scope`` of its class name, so
    that every op it emits carries the phase in its HLO ``op_name`` and
    a device trace can attribute the op to it.  The scope is metadata
    only: values and fusions are unchanged."""
    with jax.named_scope(type(phase).__name__):
        phase(ctx, v)


def masked_mean(x, mask):
    """Mean over the live cohort slots (all slots when ``mask`` is None).
    With an all-ones mask this is bit-identical to ``jnp.mean``.  The
    denominator is floored at 1 so an all-dropped mask (every live slot
    zeroed by scenario churn — the Engine's min_live revival makes this
    unreachable in practice) yields 0, not NaN; with >= 1 live slot the
    floor is inert and the result is bit-identical to the plain ratio."""
    if mask is None:
        return jnp.mean(x)
    return (jnp.sum(jnp.where(mask > 0, x, 0))
            / jnp.maximum(jnp.sum(mask), 1.0))


def feat_grad_metrics(fgrads, mask=None) -> dict:
    fg = fgrads.reshape(fgrads.shape[0], -1).astype(jnp.float32)
    norms = jnp.linalg.norm(fg, axis=-1) / jnp.sqrt(fg.shape[-1])
    if mask is None:
        return {"feat_grad_norm_mean": jnp.mean(norms),
                "feat_grad_norm_std": jnp.std(norms)}
    mu = masked_mean(norms, mask)
    var = masked_mean(jnp.square(jnp.abs(norms - mu)), mask)
    return {"feat_grad_norm_mean": mu,
            "feat_grad_norm_std": jnp.sqrt(var)}


# ----------------------------------------------------------------- phases
@dataclass(frozen=True)
class ExtractFeatures(Phase):
    """Phase 1: select the cohort's client models and extract smashed
    data in parallel.  Also snapshots θ_S^t so later phases can choose
    the pre-update server (non-cycle algorithms)."""

    def __call__(self, ctx, v):
        state = v.state
        v.cohort_clients = (
            broadcast_entity(state.client_global, v.ys.shape[0])
            if state.clients is None
            else take_entities(state.clients, v.cohort))
        if ctx.mesh is not None:
            # cohort-parallel extraction: the [C, ...] client stack and
            # its smashed data live sharded over the batch axes
            v.cohort_clients = constrain_cohort_tree(v.cohort_clients,
                                                     ctx.mesh)
        v.server_prev = state.server.params
        # slot-parallel extraction runs INSIDE a shard_map: GSPMD
        # replicates the cohort-vmapped grouped convs (every device
        # computes all C slots, then slices its own), which is the bulk
        # of the 1->8 device weak-scaling loss (§Weak scaling)
        v.feats = slot_shard_map(
            jax.vmap(ctx.task.client_forward), ctx.mesh,
            (v.cohort_clients.params, v.xs))
        v.feats = constrain_cohort(v.feats, ctx.mesh)


def _pair_server_losses_and_grads(ctx, v):
    """Per-pair server loss/grad at θ_S^t over the cohort's features."""

    def one(f, y, sp):
        return jax.value_and_grad(ctx.task.server_loss)(sp, f, y)

    return slot_shard_map(jax.vmap(one, in_axes=(0, 0, None)), ctx.mesh,
                          (v.feats, v.ys), (v.state.server.params,))


@dataclass(frozen=True)
class ServerUpdate(Phase):
    """Phase 2, the axis the zoo varies along:

    ``cycle``        pool features into D_S^f and run the CycleSL inner
                     loop (E epochs of resampled minibatches, Eq. 3) —
                     the paper's standalone higher-level server task.
    ``replica_avg``  PSL/SFL-V1: per-pair server replica steps, then
                     replica (model) averaging.
    ``mean_grad``    SGLR: one server stepped with the cohort-mean
                     gradient (no model duplication).
    """
    mode: str = "cycle"

    def __call__(self, ctx, v):
        if self.mode == "cycle":
            # the pooled feature dataset D_S^f stays sharded over the
            # batch axes; the masked resample inside the inner loop is a
            # sharded permutation-gather (feature_resample kernel on TPU;
            # ctx.cycle.shard_local_resample routes it through the
            # shard_map wrapper so the gather stays shard-LOCAL, and
            # ctx.cycle.fused_gather_loss fuses it with the head loss —
            # both knobs ride CycleConfig, so the monolithic round and
            # the pipelined tail take the same path).  A pipelined
            # extract dispatch hands the finished pool over via v.store;
            # both paths build it with the same pool_store.
            store = (v.store if v.store is not None
                     else pool_store(v.feats, v.ys, mask=v.mask,
                                     mesh=ctx.mesh))
            server, sloss = server_inner_loop(
                ctx.task, v.state.server, ctx.opt_server, store, v.key,
                ctx.cycle, batch=jax.tree.leaves(v.ys)[0].shape[1],
                mesh=ctx.mesh, grad_scale=v.stale_w)
            v.metrics["server_loss"] = sloss.mean
            v.metrics["server_step_loss"] = sloss.per_step
        elif self.mode == "replica_avg":
            losses, gs = _pair_server_losses_and_grads(ctx, v)
            if v.stale_w is not None:
                gs = jax.tree.map(lambda g: g * v.stale_w, gs)
            rep = broadcast_entity(v.state.server, v.ys.shape[0])
            if ctx.mesh is not None:
                rep = constrain_cohort_tree(rep, ctx.mesh)
                gs = constrain_cohort_tree(gs, ctx.mesh)
            rep = slot_shard_map(
                jax.vmap(lambda e, g: entity_step(e, g, ctx.opt_server)),
                ctx.mesh, (rep, gs))
            server = (entity_mean(rep) if v.mask is None
                      else masked_entity_mean(rep, v.mask))
            v.metrics["server_loss"] = masked_mean(losses, v.mask)
        elif self.mode == "mean_grad":
            losses, gs = _pair_server_losses_and_grads(ctx, v)
            if v.mask is None:
                gmean = jax.tree.map(lambda g: jnp.mean(g, axis=0), gs)
            else:
                gmean = jax.tree.map(
                    lambda g: masked_axis0_mean(g, v.mask), gs)
            if v.stale_w is not None:
                gmean = jax.tree.map(lambda g: g * v.stale_w, gmean)
            server = sharded_entity_step(v.state.server, gmean,
                                         ctx.opt_server, ctx.mesh)
            v.metrics["server_loss"] = masked_mean(losses, v.mask)
        else:
            raise ValueError(f"unknown ServerUpdate mode {self.mode!r}")
        v.state = v.state._replace(server=server)


@dataclass(frozen=True)
class FeatureGradients(Phase):
    """Phase 3: B_i^g = ∇_{B_i^f} L(θ_S(B_i^f)) with θ_S frozen.

    ``use_updated=True`` reads θ_S^{t+1} (the cyclical part, Eq. 5);
    ``False`` reads the θ_S^t snapshot (classic SL back-prop order).
    ``average`` forces SGLR-style cohort-mean gradients on (True) or
    off (False); ``None`` defers to ``CycleConfig.avg_client_grads``.
    """
    use_updated: bool = True
    average: Optional[bool] = None

    def __call__(self, ctx, v):
        params = (v.state.server.params if self.use_updated
                  else v.server_prev)
        avg = (ctx.cycle.avg_client_grads if self.average is None
               else self.average)
        ccfg = (ctx.cycle if avg == ctx.cycle.avg_client_grads
                else replace(ctx.cycle, avg_client_grads=avg))
        fg = feature_gradients(ctx.task, params, v.feats, v.ys, ccfg,
                               mask=v.mask, mesh=ctx.mesh)
        if v.stale_w is not None:
            fg = fg * v.stale_w.astype(fg.dtype)
        v.fgrads = constrain_cohort(fg, ctx.mesh)
        v.metrics.update(feat_grad_metrics(v.fgrads, mask=v.mask))


@dataclass(frozen=True)
class ClientUpdate(Phase):
    """Phase 4: pull feature gradients through each client's local VJP.

    ``chained=True`` runs the sequential-SL variant: ONE client model
    scanned along the cohort (each update sees the previous one), used
    by ``cyclessl``.  Both paths share ``client_update_one`` and respect
    ``CycleConfig.grad_clip``.
    """
    record_gnorm: bool = False
    chained: bool = False

    def __call__(self, ctx, v):
        clip = ctx.cycle.grad_clip
        if self.chained:
            if v.mask is None:
                def body(entity, inp):
                    x, g = inp
                    return client_update_one(ctx.task, entity, x, g,
                                             ctx.opt_client, clip, ctx.mesh)
                v.cohort_clients, gnorms = jax.lax.scan(
                    body, v.state.client_global, (v.xs, v.fgrads))
            else:
                # padded slots pass the chained carry through unchanged
                def body(entity, inp):
                    x, g, m = inp
                    new, gn = client_update_one(ctx.task, entity, x, g,
                                                ctx.opt_client, clip,
                                                ctx.mesh)
                    return (select_entities(m, new, entity),
                            jnp.where(m > 0, gn, 0.0))
                v.cohort_clients, gnorms = jax.lax.scan(
                    body, v.state.client_global, (v.xs, v.fgrads, v.mask))
        else:
            v.cohort_clients, gnorms = client_updates(
                ctx.task, v.cohort_clients, ctx.opt_client, v.xs, v.fgrads,
                grad_clip=clip, mask=v.mask, mesh=ctx.mesh)
            if ctx.mesh is not None:
                # sharded VJPs: updated cohort entities stay cohort-sharded
                # into the commit scatter/average
                v.cohort_clients = constrain_cohort_tree(v.cohort_clients,
                                                         ctx.mesh)
        if self.record_gnorm:
            v.metrics["client_grad_norm_mean"] = masked_mean(gnorms, v.mask)


@dataclass(frozen=True)
class Commit(Phase):
    """Phase 5: write the updated cohort back into the train state.

    ``per_client``  scatter into the persistent [N, ...] client store
                    (PSL-family: clients are never aggregated).
    ``average``     FedAvg the cohort into the shared θ_C (SFL-family).
    ``global``      replace the shared θ_C wholesale (sequential chain).
    """
    mode: str = "per_client"

    def __call__(self, ctx, v):
        state, cc = v.state, v.cohort_clients
        if self.mode == "per_client":
            # padded slots carry the OOB sentinel id; put_entities'
            # mode="drop" scatter discards their (already zeroed) updates
            v.state = state._replace(
                clients=put_entities(state.clients, v.cohort, cc))
        elif self.mode == "average":
            v.state = state._replace(
                client_global=(entity_mean(cc) if v.mask is None
                               else masked_entity_mean(cc, v.mask)))
        elif self.mode == "global":
            v.state = state._replace(client_global=cc)
        else:
            raise ValueError(f"unknown Commit mode {self.mode!r}")


@dataclass(frozen=True)
class HealthGuard(Phase):
    """Trailing phase: fold the health verdict into the round's metrics.

    Appended by the builders only when ``ResilienceConfig.guard`` is on,
    so the guard-free program compiles to the identical HLO it always
    did (bit-for-bit when disabled).  Everything it reads — the committed
    state, the round loss, the cohort intermediates, the loss-EMA carry
    (``v.ema``, a device scalar the Engine threads round-to-round) — is
    already live inside the trace, so the check costs no extra dispatch;
    the Engine pays exactly one host sync reading ``metrics['health']``.
    See :mod:`repro.resilience.guards` for the vector layout.
    """
    alpha: float = 0.1
    spike_factor: float = 4.0

    def __call__(self, ctx, v):
        loss = v.metrics.get("server_loss", jnp.zeros(()))
        health, slot_bad = health_vector(
            v.state, loss, v.feats, v.fgrads, v.mask, v.ema,
            self.alpha, self.spike_factor)
        v.metrics["health"] = health
        v.metrics["health_slot_bad"] = slot_bad


# ----------------------------------------------- fused sequential rounds
# ssl / sflv2 / fedavg interleave client and server updates inside one
# scan, so they cannot be expressed as the 5-phase pipeline without
# changing semantics; they ride as single fused phases instead.
@dataclass(frozen=True)
class SequentialChainRound(Phase):
    """ssl: one shared client model passed client-to-client, end-to-end
    update per client (the O(N)-latency canon)."""

    def __call__(self, ctx, v):
        task, opt_s, opt_c = ctx.task, ctx.opt_server, ctx.opt_client
        masked = v.mask is not None

        def body(carry, inp):
            server, client = carry
            x, y = inp[:2]

            def loss_fn(c, s):
                return task.e2e_loss(c, s, x, y)
            loss, (gc, gs) = jax.value_and_grad(loss_fn, (0, 1))(
                client.params, server.params)
            f = task.client_forward(client.params, x)
            fg = jax.grad(lambda ff: task.server_loss(
                jax.lax.stop_gradient(server.params), ff, y))(f)
            new_s = sharded_entity_step(server, gs, opt_s, ctx.mesh)
            new_c = sharded_entity_step(client, gc, opt_c, ctx.mesh, "full")
            if masked:
                m = inp[2]
                new_s = select_entities(m, new_s, server)
                new_c = select_entities(m, new_c, client)
                loss = jnp.where(m > 0, loss, 0.0)
            return (new_s, new_c), (loss, fg)

        inputs = (v.xs, v.ys, v.mask) if masked else (v.xs, v.ys)
        (server, client), (losses, fg) = jax.lax.scan(
            body, (v.state.server, v.state.client_global), inputs)
        v.metrics.update(server_loss=masked_mean(losses, v.mask),
                         **feat_grad_metrics(fg, mask=v.mask))
        v.state = v.state._replace(server=server, client_global=client)


@dataclass(frozen=True)
class ServerSequentialRound(Phase):
    """sflv2: single server model, clients processed sequentially on the
    server side; client models FedAvg'd at round end."""

    def __call__(self, ctx, v):
        task, opt_s, opt_c = ctx.task, ctx.opt_server, ctx.opt_client
        masked = v.mask is not None
        cohort_clients = broadcast_entity(v.state.client_global,
                                          v.ys.shape[0])

        def body(server, inp):
            cp, x, y = inp[:3]

            def loss_fn(c, s):
                return task.e2e_loss(c, s, x, y)
            loss, (gc, gs) = jax.value_and_grad(loss_fn, (0, 1))(
                cp, server.params)
            f = task.client_forward(cp, x)
            fg = jax.grad(lambda ff: task.server_loss(
                jax.lax.stop_gradient(server.params), ff, y))(f)
            new_s = sharded_entity_step(server, gs, opt_s, ctx.mesh)
            if masked:
                m = inp[3]
                new_s = select_entities(m, new_s, server)
                loss = jnp.where(m > 0, loss, 0.0)
            return new_s, (loss, gc, fg)

        inputs = ((cohort_clients.params, v.xs, v.ys, v.mask) if masked
                  else (cohort_clients.params, v.xs, v.ys))
        server, (losses, gc, fg) = jax.lax.scan(
            body, v.state.server, inputs)
        stepped = cohort_entity_step(cohort_clients, gc, ctx.opt_client,
                                     ctx.mesh)
        client_global = (entity_mean(stepped) if not masked
                         else masked_entity_mean(stepped, v.mask))
        v.metrics.update(server_loss=masked_mean(losses, v.mask),
                         **feat_grad_metrics(fg, mask=v.mask))
        v.state = v.state._replace(server=server,
                                   client_global=client_global)


@dataclass(frozen=True)
class LocalFedAvgRound(Phase):
    """fedavg: clients train the FULL composed model locally; both halves
    are averaged (no split traffic — the non-SL yardstick)."""

    def __call__(self, ctx, v):
        task, opt_s, opt_c = ctx.task, ctx.opt_server, ctx.opt_client
        n = v.ys.shape[0]
        servers = broadcast_entity(v.state.server, n)
        clients = broadcast_entity(v.state.client_global, n)
        if ctx.mesh is not None:
            servers = constrain_cohort_tree(servers, ctx.mesh)
            clients = constrain_cohort_tree(clients, ctx.mesh)

        def one(se, ce, x, y):
            def loss_fn(c, s):
                return task.e2e_loss(c, s, x, y)
            loss, (gc, gs) = jax.value_and_grad(loss_fn, (0, 1))(
                ce.params, se.params)
            return (entity_step(se, gs, opt_s),
                    entity_step(ce, gc, opt_c), loss)

        new_servers, new_clients, losses = slot_shard_map(
            jax.vmap(one), ctx.mesh, (servers, clients, v.xs, v.ys))
        if v.mask is None:
            server, client = entity_mean(new_servers), entity_mean(new_clients)
        else:
            server = masked_entity_mean(new_servers, v.mask)
            client = masked_entity_mean(new_clients, v.mask)
        v.metrics.update(server_loss=masked_mean(losses, v.mask),
                         feat_grad_norm_mean=jnp.zeros(()),
                         feat_grad_norm_std=jnp.zeros(()))
        v.state = v.state._replace(server=server, client_global=client)


# ---------------------------------------------------------------- program
@dataclass(frozen=True)
class RoundProgram:
    """A named, declarative composition of phases = one SL algorithm."""
    name: str
    phases: tuple[Phase, ...]
    uses_global_client: bool

    def describe(self) -> str:
        return " -> ".join(type(p).__name__ for p in self.phases)


def init_train_state(key, n_clients: int, task: SplitTask,
                     opt_server: Optimizer, opt_client: Optimizer,
                     global_client: bool) -> TrainState:
    ks, kc = jax.random.split(key)
    server = init_entity(task.init_server(ks), opt_server)
    client0 = init_entity(task.init_client(kc), opt_client)
    if global_client:
        return TrainState(server, None, client0)
    # per-client persistent models — identical init (the paper initializes
    # every client the same way; heterogeneity comes from the data)
    return TrainState(server, broadcast_entity(client0, n_clients), None)


def build_algorithm(program: RoundProgram, task: SplitTask,
                    opt_server: Optimizer, opt_client: Optimizer,
                    cycle: CycleConfig = CycleConfig(),
                    donate: bool = False,
                    mesh: Any = None,
                    state_shardings: Any = None,
                    shard_data: bool = True,
                    resilience: Any = None) -> SLAlgorithm:
    """Compile a RoundProgram into the uniform algorithm interface.

    ``donate=True`` donates the TrainState buffers to the jitted round
    (in-place on accelerators; skipped by the Engine on CPU where XLA
    cannot honor donation).

    ``resilience`` (a :class:`~repro.resilience.ResilienceConfig` with
    ``guard=True``) appends the :class:`HealthGuard` phase and the round
    gains a trailing ``ema`` carry argument; ``None``/guard-off compiles
    the exact guard-free round (the ``ema=None`` default never enters
    the trace when the caller omits it).

    ``mesh`` + ``state_shardings`` switch on the mesh-native path:
    phases thread ``with_sharding_constraint`` (cohort activations and
    the pooled feature store over the batch axes, server minibatches
    data-parallel), and the jitted round pins its output TrainState to
    ``state_shardings`` — so round N+1's input sharding equals round N's
    output sharding and the compile-once contract holds per
    (algo, config, mesh).  ``shard_data=False`` keeps the weight
    placement but drops the cohort/data constraints
    (``ExperimentConfig.shard_cohort``).
    """
    ctx = PhaseContext(task, opt_server, opt_client, cycle,
                       mesh if shard_data else None)
    traces = {"count": 0}
    guard = (HealthGuard(resilience.ema_alpha, resilience.spike_factor)
             if resilience is not None and resilience.guard else None)

    def init(key, n_clients: int) -> TrainState:
        return init_train_state(key, n_clients, task, opt_server, opt_client,
                                program.uses_global_client)

    def round_impl(state, cohort, xs, ys, key, mask=None, ema=None):
        traces["count"] += 1          # executes at trace time only
        v = RoundVars(state=state, cohort=cohort, xs=xs, ys=ys, key=key,
                      mask=mask, ema=ema)
        for phase in program.phases:
            run_phase(phase, ctx, v)
        if guard is not None:
            run_phase(guard, ctx, v)
        return v.state, v.metrics

    jit_kwargs = {}
    if donate:
        jit_kwargs["donate_argnums"] = (0,)
    if state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        out_mesh = jax.tree.leaves(state_shardings)[0].mesh
        # metrics are scalars -> replicated; the state sharding pin is
        # what keeps round-over-round input shardings (and therefore the
        # trace count) stable
        jit_kwargs["out_shardings"] = (
            state_shardings, NamedSharding(out_mesh, PartitionSpec()))
    round_fn = jax.jit(round_impl, **jit_kwargs)
    return SLAlgorithm(program.name, init, round_fn,
                       program.uses_global_client, traces)


# ------------------------------------------------------ pipelined rounds
class PipelineStage(NamedTuple):
    """Everything the Extract dispatch hands to the in-flight tail.

    One stage per in-flight cohort: the selected client entities, the
    θ_S^t snapshot (read by non-cycle ``FeatureGradients``), the smashed
    data, and — for cycle programs — the already-pooled D_S^f so the
    tail's server phase starts on the handoff without re-pooling.

    ``clients`` is the [C, ...] gathered stack for per-client programs,
    but the SINGLE shared θ_C entity for global-client programs: the
    tail re-broadcasts it so the broadcast stays logical inside the
    trace — materializing C identical copies at the dispatch boundary
    perturbs conv-VJP bits, and the snapshot-in-stage semantics (async
    staleness rides the stage, never the tail's state) are unchanged.
    """
    clients: Any                      # [C, ...] stack, or shared θ_C entity
    server_prev: Any                  # θ_S^t params snapshot
    feats: Any                        # [C, b, ...] smashed data; None for
    #                                   cycle programs (the pooled store
    #                                   carries the same values — the tail
    #                                   rebuilds this view by reshape, so
    #                                   the boundary moves the cohort's
    #                                   features ONCE, not twice)
    store: Any                        # pooled FeatureStore (cycle) or None


@dataclass(frozen=True)
class PipelinedAlgorithm:
    """A RoundProgram compiled as TWO overlappable dispatches.

    ``extract(state, cohort, xs, ys[, mask]) -> PipelineStage`` runs the
    ExtractFeatures head on the cohort batch axes; ``tail(state, cohort,
    xs, ys, key, stage[, mask]) -> (state, metrics)`` runs the
    ServerUpdate/FeatureGradients/ClientUpdate/Commit remainder.  Their
    composition with a barrier is the sequential round; dispatching
    ``extract`` for cohort k+1 before ``tail`` of cohort k is the
    software pipeline.  ``traces`` tracks both functions — the compile
    contract is ONE trace each per (algo, config, mesh).
    """
    name: str
    init: Callable[..., TrainState]
    extract: Callable[..., PipelineStage]
    tail: Callable[..., tuple[TrainState, dict]]
    uses_global_client: bool
    traces: Any = None

    @property
    def extract_traces(self) -> int:
        return self.traces["extract"] if self.traces else 0

    @property
    def tail_traces(self) -> int:
        return self.traces["tail"] if self.traces else 0

    @property
    def trace_count(self) -> int:
        return self.extract_traces + self.tail_traces


def split_program(program: RoundProgram
                  ) -> Optional[tuple[Phase, tuple[Phase, ...]]]:
    """(head, tail) when the program starts with ExtractFeatures; None
    for the fused sequential programs (ssl/sflv2/fedavg interleave
    client and server updates inside one scan — there is nothing to
    overlap, and the Engine falls back to the monolithic round)."""
    if program.phases and isinstance(program.phases[0], ExtractFeatures):
        return program.phases[0], program.phases[1:]
    return None


def build_pipelined_algorithm(program: RoundProgram, task: SplitTask,
                              opt_server: Optimizer, opt_client: Optimizer,
                              cycle: CycleConfig = CycleConfig(),
                              donate: bool = False,
                              donate_state: bool = True,
                              mesh: Any = None,
                              state_shardings: Any = None,
                              shard_data: bool = True,
                              resilience: Any = None,
                              staleness_weighting: str = "none",
                              staleness_lambda: float = 0.5,
                              pin_stage: bool = False
                              ) -> Optional[PipelinedAlgorithm]:
    """Compile a RoundProgram into the (extract, tail) dispatch pair.

    The phases are the SAME objects the monolithic round runs — the
    split only moves the jit boundary to the ExtractFeatures/ServerUpdate
    seam (plus the D_S^f pooling, which rides the extract side via
    ``pool_store``), so ``tail(state, ..., extract(state, ...))`` is
    numerically the monolithic ``round``.  Returns None when the program
    has no ExtractFeatures head to split on.

    ``donate=True`` donates the stage buffers into the tail (they die
    with the round); ``donate_state`` additionally donates the TrainState
    — the Engine switches it off in async mode, where the pre-tail state
    is still in flight inside the next cohort's extract dispatch.

    ``staleness_weighting`` != 'none' gives the tail an extra traced
    ``lag`` scalar and scales the cohort's server + feature gradients by
    w(lag) (``1/(1+lag)`` or ``exp(-staleness_lambda*lag)``) — one tail
    trace across every realized lag, and 'none' keeps the exact
    pre-weighting signature so depth-1 goldens stay bit-for-bit.
    ``pin_stage`` (deep rings, L > 1) runs the extracted stage through
    :func:`repro.sharding.specs.constrain_stage` so every buffered
    stage holds one stable placement regardless of how many are in
    flight; off by default to leave the depth-1 lowering untouched.
    """
    split = split_program(program)
    if split is None:
        return None
    head, tail_phases = split
    ctx = PhaseContext(task, opt_server, opt_client, cycle,
                       mesh if shard_data else None)
    pools = any(getattr(p, "mode", None) == "cycle" for p in tail_phases)
    traces = {"extract": 0, "tail": 0}
    guard = (HealthGuard(resilience.ema_alpha, resilience.spike_factor)
             if resilience is not None and resilience.guard else None)

    def init(key, n_clients: int) -> TrainState:
        return init_train_state(key, n_clients, task, opt_server, opt_client,
                                program.uses_global_client)

    def extract_impl(state, cohort, xs, ys, mask=None):
        traces["extract"] += 1        # executes at trace time only
        v = RoundVars(state=state, cohort=cohort, xs=xs, ys=ys, key=None,
                      mask=mask)
        run_phase(head, ctx, v)
        store = None
        if pools:
            # the pooling is ServerUpdate's work in the monolithic round:
            # it keeps that phase's scope on this side of the split too
            with jax.named_scope(ServerUpdate.__name__):
                store = pool_store(v.feats, ys, mask=mask, mesh=ctx.mesh)
        # cycle programs: the pooled store IS the smashed data (a
        # stop_gradient + reshape of it), so handing both across the
        # dispatch boundary would materialize the cohort's features
        # twice; the tail rebuilds the [C, b, ...] view by the inverse
        # reshape (bit-identical values — FeatureGradients reads feats
        # as a point, never through its graph)
        feats = None if pools else v.feats
        # θ_S^t keeps its FSDP/TP weight placement while the cohort
        # tensors sit on the batch axes — the disjoint-axis layout that
        # lets XLA overlap this dispatch with the server inner loop
        server_prev = constrain_entity_params(v.server_prev, ctx.mesh)
        # global-client programs hand over the un-broadcast θ_C snapshot
        # (see PipelineStage); per-client programs the gathered stack
        clients = (state.client_global if program.uses_global_client
                   else v.cohort_clients)
        stage = PipelineStage(clients, server_prev, feats, store)
        if pin_stage and ctx.mesh is not None:
            from repro.sharding.specs import constrain_stage
            stage = constrain_stage(stage, ctx.mesh,
                                    program.uses_global_client)
        return stage

    def tail_impl(state, cohort, xs, ys, key, stage, mask=None, ema=None,
                  lag=None):
        traces["tail"] += 1           # executes at trace time only
        stale_w = None
        if staleness_weighting != "none":
            l = jnp.asarray(0.0 if lag is None else lag, jnp.float32)
            stale_w = (1.0 / (1.0 + l) if staleness_weighting == "inverse"
                       else jnp.exp(-staleness_lambda * l))
        cohort_clients = stage.clients
        if program.uses_global_client:
            # re-broadcast the snapshot INSIDE the trace so XLA keeps it
            # logical — bit-identical to the monolithic round's lowering
            cohort_clients = broadcast_entity(stage.clients,
                                              jax.tree.leaves(ys)[0].shape[0])
            if ctx.mesh is not None:
                cohort_clients = constrain_cohort_tree(cohort_clients,
                                                       ctx.mesh)
        feats = stage.feats
        if feats is None:              # rebuild the [C, b, ...] view
            pooled = stage.store.features
            cb = jax.tree.leaves(ys)[0].shape[:2]
            feats = pooled.reshape(cb + pooled.shape[1:])
        v = RoundVars(state=state, cohort=cohort, xs=xs, ys=ys, key=key,
                      mask=mask, ema=ema, cohort_clients=cohort_clients,
                      server_prev=stage.server_prev, feats=feats,
                      store=stage.store, stale_w=stale_w)
        for phase in tail_phases:
            run_phase(phase, ctx, v)
        if guard is not None:
            run_phase(guard, ctx, v)
        if stale_w is not None:
            v.metrics["stale_weight"] = stale_w
        return v.state, v.metrics

    tail_kwargs = {}
    if donate:
        # the stage dies with the round it feeds; the state is donated
        # only when the caller guarantees no other dispatch still reads it
        tail_kwargs["donate_argnums"] = ((0, 5) if donate_state else (5,))
    if state_shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        out_mesh = jax.tree.leaves(state_shardings)[0].mesh
        tail_kwargs["out_shardings"] = (
            state_shardings, NamedSharding(out_mesh, PartitionSpec()))
    return PipelinedAlgorithm(program.name, init,
                              jax.jit(extract_impl),
                              jax.jit(tail_impl, **tail_kwargs),
                              program.uses_global_client, traces)
