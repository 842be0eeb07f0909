"""CycleSL round — paper Algorithm 1, as one pure (jit-able) function.

The round is the paper's contribution verbatim:

  1. clients extract features        B_i^f = θ_C_i(B_i^x)      (parallel)
  2. server pools a feature dataset  D_S^f = ⨄ B_i^f           (Eq. 3)
  3. server trains E epochs on resampled shuffled mini-batches  (Eq. 3)
  4. server FREEZES θ_S^{t+1} and computes feature gradients
     B_i^g = ∇_{B_i^f} L(θ_S^{t+1}(B_i^f))                     (Eq. 5)
  5. clients pull B_i^g through their local VJP and step        (Eq. 5)

Step 4 uses the *updated* server (the cyclical/BCD part) and
``stop_gradient`` walls guarantee no server parameter traces gradients
during the client phase — the memory argument of paper §5.2.

SGLR integration (CycleSGLR): feature gradients are averaged over the
cohort before being returned, and client/server learning rates are
decoupled (both handled by the caller via ``CycleConfig``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.feature_store import (FeatureStore, gather_batch,
                                      masked_resample_plan, pool_store,
                                      resample_plan, shard_local_fused_loss,
                                      shard_local_gather)
from repro.core.protocol import (EntityState, masked_axis0_mean,
                                 select_entities)
from repro.core.split import SplitTask
from repro.optim import Optimizer, clip_by_global_norm
from repro.sharding.specs import sharded_entity_step


def _maybe_clip(grads, max_norm: Optional[float]):
    """Global-norm clipping when ``max_norm`` is set (CycleConfig.grad_clip)."""
    if max_norm is None:
        return grads
    clipped, _ = clip_by_global_norm(grads, max_norm)
    return clipped


@dataclass(frozen=True)
class CycleConfig:
    server_epochs: int = 1          # E in Algorithm 1 (Table 5 ablation)
    server_batch: Optional[int] = None  # default: the client batch size b
    # cap on resampled minibatch STEPS per epoch (None = full coverage of
    # D_S^f).  Algorithm 1's inner loop reads as one resampled batch per
    # server epoch; server_steps=1 gives that literal variant, None gives
    # the epoch reading implied by the paper's Table 8 server cost.
    server_steps: Optional[int] = None
    avg_client_grads: bool = False  # CycleSGLR: SGLR-style grad averaging
    # global-norm clip applied to every server inner-loop step and every
    # client VJP step (None = no clipping)
    grad_clip: Optional[float] = None
    # shard-LOCAL resample: route the server inner loop's gather through
    # the shard_map wrapper (per-shard index translation + masked
    # cross-shard fixup) instead of letting GSPMD gather the pooled
    # operand around the kernel.  Value-exact (bit-for-bit the GSPMD
    # path); only meaningful when the round runs on a mesh.
    shard_local_resample: bool = False
    # force the Pallas resample kernel on (True, interpret off-TPU) or
    # off (False, jnp.take); None = backend default (kernel on TPU).
    # This is the config-resolved choice gather_batch receives inside
    # the inner loop — tests and CPU users can pin either path.
    resample_use_kernel: Optional[bool] = None
    # fuse the resample gather with the server head's logits/loss
    # (kernels/gather_loss.py) so the gathered minibatch never
    # materializes and D_S^f is read once per epoch.  Engages only for
    # tasks exposing a linear head (SplitTask.server_head) with plain
    # integer labels; ignored (with the classic path kept) otherwise,
    # and superseded by shard_local_resample on a mesh.
    fused_gather_loss: bool = False
    # NOTE: the old ``batch_constraint`` callable hook is gone — server
    # batch sharding now flows from the mesh itself (the serializable
    # ``ExperimentConfig.mesh_shape`` knobs / the launcher's mesh) via
    # ``sharding.specs.constrain_server_batch``, threaded through the
    # ``mesh`` argument of :func:`server_inner_loop`.


class ServerLoss(NamedTuple):
    """The server inner loop's loss: ``mean`` over the live steps (the
    round's ``server_loss``) and ``per_step``, the loss of every step
    ([E*steps]; 0 where the attendance mask skipped the step)."""
    mean: jnp.ndarray
    per_step: jnp.ndarray


def server_inner_loop(task: SplitTask, server: EntityState, opt_s: Optimizer,
                      store: FeatureStore, key, ccfg: CycleConfig,
                      batch: int, mesh=None,
                      grad_scale=None) -> tuple[EntityState, ServerLoss]:
    """E epochs of minibatch training on the resampled feature dataset.

    When the store carries a row-validity mask (padded cohort), the plan
    comes from :func:`masked_resample_plan`: the scan always runs the
    static capacity's worth of steps, but steps whose rows are not all
    live are exact no-ops (the entity passes through unchanged, the loss
    is excluded from the mean) — so one compiled loop serves every live
    cohort size, with numerics identical to an unpadded pool of just the
    live rows.

    ``mesh`` pins every resampled minibatch data-parallel over the batch
    axes (:func:`repro.sharding.specs.constrain_server_batch`); the
    gather itself dispatches to the ``feature_resample`` Pallas kernel
    on TPU, with ``ccfg.resample_use_kernel`` as the explicit override
    (see :func:`gather_batch`).  ``ccfg.shard_local_resample`` + mesh
    routes the gather through :func:`shard_local_gather` instead — the
    shard_map wrapper whose per-shard index translation keeps the
    resample shard-LOCAL (bit-for-bit the GSPMD path).  On a TPU mesh of
    several devices the kernel paths always take that route: XLA cannot
    partition a compiled Pallas call.
    ``ccfg.fused_gather_loss`` additionally fuses gather and head loss
    through ``kernels.ops.fused_gather_loss_mean`` when the task
    exposes a linear server head.  ``mesh=None`` leaves placement to
    GSPMD — layout only, never values.  ``grad_scale`` (a traced scalar,
    or None) multiplies every clipped gradient before the optimizer
    step — the staleness-weighting hook; 1.0 is an exact no-op.
    Returns ``(server', ServerLoss)``.
    """
    sb = min(ccfg.server_batch or batch, store.size)
    # fused path: linear head + single integer label leaf (see below)
    fused = (ccfg.fused_gather_loss
             and getattr(task, "server_head", None) is not None
             and isinstance(store.labels, jax.Array)
             and jnp.issubdtype(store.labels.dtype, jnp.integer))
    # a compiled (Mosaic) kernel cannot be partitioned by XLA: on a
    # multi-device TPU mesh the kernel paths run inside the shard_map
    # wrappers, which are value-exact for the gather
    from repro.kernels.ops import default_interpret
    mosaic = not default_interpret()
    use_kernel = (mosaic if ccfg.resample_use_kernel is None
                  else ccfg.resample_use_kernel)
    shard_local = mesh is not None and (
        ccfg.shard_local_resample
        or (mosaic and mesh.size > 1 and (use_kernel or fused)))
    # minibatch layout: tensor-parallel (replicated rows) when the
    # server params are FSDP/TP-sharded on this mesh — row-sharding the
    # batch on the same axis as the weights forces a full weight
    # all-gather per scan step; data-parallel (rows over 'data') when
    # the weights are replicated.  Static (shapes + path rules only).
    if mesh is not None:
        from repro.sharding.specs import params_are_sharded
        tp_layout = params_are_sharded(server.params, mesh, "server")
    else:
        tp_layout = False
    # fused path on a sharded mesh: composes with the shard-local
    # resample through shard_local_fused_loss — the per-row loss runs
    # INSIDE the shard_map body over each shard's pool slice and only a
    # scalar psum crosses devices, so the fused kernel no longer
    # reintroduces the feature-pool all-gather the shard-local route
    # exists to avoid.
    if store.valid is None:
        plan = resample_plan(key, store.size, ccfg.server_epochs, sb)
        step_ok = None
    else:
        plan, step_ok = masked_resample_plan(key, store.valid,
                                             ccfg.server_epochs, sb)
    if ccfg.server_steps is not None:
        plan = plan[:, : ccfg.server_steps]
        if step_ok is not None:
            step_ok = step_ok[:, : ccfg.server_steps]
    plan2 = plan.reshape(-1, sb)                     # [E*steps, sb]
    # every gather reads the pool as 2-D [T, prod(row)] rows: flatten it
    # once here, not per step, or the scan body relayouts the whole pool
    # on every step (XLA does not hoist it); gathered rows are reshaped
    # back to ``row_shape``, which is value-exact
    row_shape = store.features.shape[1:]
    pool = store._replace(features=store.features.reshape((store.size, -1)))

    def fused_step_loss(params, idx):
        w = task.server_head(params)
        if shard_local:
            return shard_local_fused_loss(pool, idx, w, mesh,
                                          use_kernel=use_kernel)
        from repro.kernels import ops
        return ops.fused_gather_loss_mean(pool.features, pool.labels, idx, w)

    def apply_step(entity, idx):
        if fused:
            loss, grads = jax.value_and_grad(fused_step_loss)(entity.params,
                                                              idx)
        else:
            if shard_local:
                f, y = shard_local_gather(pool, idx, mesh,
                                          use_kernel=use_kernel,
                                          replicate_out=tp_layout)
            else:
                f, y = gather_batch(pool, idx, use_kernel=use_kernel)
            f = f.reshape((f.shape[0],) + row_shape)
            if mesh is not None:
                from repro.sharding.specs import constrain_server_batch
                f, y = constrain_server_batch(f, y, mesh,
                                              replicate=tp_layout)
            loss, grads = jax.value_and_grad(task.server_loss)(entity.params,
                                                               f, y)
        grads = _maybe_clip(grads, ccfg.grad_clip)
        if grad_scale is not None:
            # staleness weighting: a traced scalar so one trace serves
            # every realized lag; scale == 1.0 is an exact no-op
            grads = jax.tree.map(lambda g: g * grad_scale, grads)
        return sharded_entity_step(entity, grads, opt_s, mesh), loss

    if step_ok is None:
        server, losses = jax.lax.scan(apply_step, server, plan2)
        return server, ServerLoss(jnp.mean(losses), losses)

    # the loss sum rides the scan carry: sequential accumulation (with
    # exact-zero no-ops for masked steps) is invariant to how much
    # padding follows the live steps, unlike a post-hoc jnp.sum whose
    # SIMD reduction tree depends on the array length
    def one_step(carry, inp):
        entity, acc = carry
        idx, ok = inp
        stepped, loss = apply_step(entity, idx)
        live = jnp.where(ok, loss, 0.0)
        return (select_entities(ok, stepped, entity), acc + live), live

    ok2 = step_ok.reshape(-1)
    (server, loss_sum), per_step = jax.lax.scan(
        one_step, (server, jnp.zeros((), jnp.float32)), (plan2, ok2))
    denom = jnp.maximum(jnp.sum(ok2.astype(loss_sum.dtype)), 1.0)
    return server, ServerLoss(loss_sum / denom, per_step)


def feature_gradients(task: SplitTask, server_params, feats, ys,
                      ccfg: CycleConfig, mask=None, mesh=None):
    """B_i^g for every cohort member, with θ_S^{t+1} frozen (Eq. 5).

    ``mask`` ([C], 1.0 = live slot) restricts the SGLR-style cohort-mean
    to live slots so padded members neither contribute to nor dilute the
    averaged gradient.  With ``mesh`` set the per-slot grads run inside
    a shard_map (:func:`repro.sharding.specs.slot_shard_map`) so each
    device differentiates only its local slots.
    """
    frozen = jax.lax.stop_gradient(server_params)

    def per_client(f, y, sp):
        return jax.grad(lambda ff: task.server_loss(sp, ff, y))(f)

    from repro.sharding.specs import slot_shard_map
    grads = slot_shard_map(jax.vmap(per_client, in_axes=(0, 0, None)),
                           mesh, (feats, ys), (frozen,))  # [C, b, ...]
    if ccfg.avg_client_grads:
        mean = (jnp.mean(grads, axis=0) if mask is None
                else masked_axis0_mean(grads, mask))
        grads = jnp.broadcast_to(mean[None], grads.shape)
    return grads


def client_update_one(task: SplitTask, entity: EntityState, x, g,
                      opt_c: Optimizer,
                      grad_clip: Optional[float] = None,
                      mesh=None) -> tuple[EntityState, jnp.ndarray]:
    """One client's phase-5 step: pull its feature gradient ``g`` through
    the local VJP, optionally clip, and take one optimizer step.

    The single source of truth for the client update — the cohort-vmapped
    :func:`client_updates` and the sequential (cyclessl) chain both call it.
    ``mesh`` is for a shared client entity stepped outside any manual
    region (see :func:`repro.sharding.specs.sharded_entity_step`).
    Returns the stepped entity and the global norm of the applied grads.
    """
    def fwd(p):
        return task.client_forward(p, x)
    out, vjp = jax.vjp(fwd, entity.params)
    (grads,) = vjp(g.astype(out.dtype))
    grads = _maybe_clip(grads, grad_clip)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                         for l in jax.tree.leaves(grads)))
    return sharded_entity_step(entity, grads, opt_c, mesh, "full"), gnorm


def client_updates(task: SplitTask, clients: EntityState, opt_c: Optimizer,
                   xs, feat_grads,
                   grad_clip: Optional[float] = None,
                   mask=None, mesh=None) -> tuple[EntityState, jnp.ndarray]:
    """Pull B_i^g through each client's VJP and take one optimizer step.

    With ``mask`` set, padded slots receive a zeroed update: their entity
    (params, optimizer state, step counter) passes through unchanged and
    their grad norm reads 0, so the commit phase's scatter/average sees
    no contribution from them.  With ``mesh`` set the per-slot VJPs run
    inside a shard_map (each device updates only its local slots).
    """
    from repro.sharding.specs import slot_shard_map
    new_clients, gnorms = slot_shard_map(jax.vmap(
        lambda e, x, g: client_update_one(task, e, x, g, opt_c, grad_clip)),
        mesh, (clients, xs, feat_grads))
    if mask is not None:
        new_clients = select_entities(mask, new_clients, clients)
        gnorms = jnp.where(mask > 0, gnorms, 0.0)
    return new_clients, gnorms


def cyclesl_extract(task: SplitTask, clients: EntityState, xs, ys,
                    mesh=None) -> tuple[jnp.ndarray, FeatureStore]:
    """Phases 1-2 of Algorithm 1 as a standalone dispatch: parallel
    client feature extraction plus the pooled D_S^f handoff (Eq. 3).

    This is the half of the round that lives on the cohort/batch axes —
    the pipelined schedule dispatches it for cohort k+1 while cohort k's
    :func:`cyclesl_tail` occupies the server/model axes.  Composing the
    two inside one trace is exactly the monolithic :func:`cyclesl_round`.
    Returns ``(feats, store)``.
    """
    from repro.sharding.specs import constrain_cohort, slot_shard_map
    feats = slot_shard_map(jax.vmap(task.client_forward), mesh,
                           (clients.params, xs))
    if mesh is not None:
        feats = constrain_cohort(feats, mesh)
    return feats, pool_store(feats, ys, mesh=mesh)


def cyclesl_tail(task: SplitTask, server: EntityState, clients: EntityState,
                 opt_s: Optimizer, opt_c: Optimizer, xs, ys, key,
                 ccfg: CycleConfig, feats, store: FeatureStore, mesh=None):
    """Phases 3-5 of Algorithm 1, consuming an extract handoff: server
    inner epochs on the pooled store, frozen-server feature gradients
    (Eq. 5), and the client VJP steps.  Returns (server', clients',
    metrics)."""
    batch = jax.tree.leaves(ys)[0].shape[1]
    server, sloss = server_inner_loop(
        task, server, opt_s, store, key, ccfg, batch=batch, mesh=mesh)

    fgrads = feature_gradients(task, server.params, feats, ys, ccfg,
                               mesh=mesh)
    fg_flat = fgrads.reshape(fgrads.shape[0], -1).astype(jnp.float32)
    per_sample_norm = jnp.linalg.norm(
        fg_flat, axis=-1) / jnp.sqrt(fg_flat.shape[-1])

    clients, client_gnorms = client_updates(task, clients, opt_c, xs, fgrads,
                                            grad_clip=ccfg.grad_clip,
                                            mesh=mesh)

    metrics = {
        "server_loss": sloss.mean,
        "feat_grad_norm_mean": jnp.mean(per_sample_norm),
        "feat_grad_norm_std": jnp.std(per_sample_norm),
        "client_grad_norm_mean": jnp.mean(client_gnorms),
    }
    return server, clients, metrics


def cyclesl_round(task: SplitTask, server: EntityState,
                  clients: EntityState, opt_s: Optimizer, opt_c: Optimizer,
                  xs, ys, key, ccfg: CycleConfig, mesh=None):
    """One full CycleSL round (Algorithm 1).

    xs, ys: cohort-stacked batches [C, b, ...].
    clients: cohort-stacked EntityState.
    ``mesh`` shards the round end-to-end: cohort-stacked activations over
    the batch axes, the pooled feature dataset over 'data', and every
    resampled server minibatch data-parallel.
    Returns (server', clients', metrics).

    Implemented as extract ∘ tail so the monolithic round and the
    pipelined two-dispatch schedule share every op.
    """
    feats, store = cyclesl_extract(task, clients, xs, ys, mesh=mesh)
    return cyclesl_tail(task, server, clients, opt_s, opt_c, xs, ys, key,
                        ccfg, feats, store, mesh=mesh)
