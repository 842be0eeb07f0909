"""Path-regex sharding rules (t5x-style) for every repro model.

The production mesh is (data=16, model=16) per pod; multi-pod adds a
leading 'pod' axis used for batch/cohort parallelism only.  Weights are
sharded 2-D: FSDP over 'data' + tensor-parallel over 'model' — this is
what lets grok-1-314b fit 16 GiB/chip (DESIGN.md §3).

Rules give a spec *template for the trailing dims* of a leaf; leading
dims (stacked layer dim, stacked client dim in the CycleSL cohort) are
handled by role:

  role='server'/'full' — stacked-layer leading dim replicated.
  role='client'        — an extra leading cohort dim sharded over
                         ('pod','data'); the 'data' FSDP component inside
                         the rule is dropped (an axis may appear once).
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.utils.tree import map_with_path

# (regex over '/'-joined leaf path, trailing-dims spec template)
# templates use axis names; None = replicated dim.
RULES: list[tuple[str, tuple]] = [
    # embeddings / heads
    (r"embed/table$", ("model", "data")),
    (r"lm_head/w$", ("data", "model")),
    (r"(encoder|decoder)/pos$", (None, "data")),
    # attention projections
    (r"attn/wq$", ("data", "model")),
    (r"attn/wk$", ("data", "model")),
    (r"attn/wv$", ("data", "model")),
    (r"attn/wo$", ("model", "data")),
    # dense ffn
    (r"ffn/w_gate$", ("data", "model")),
    (r"ffn/w_up$", ("data", "model")),
    (r"ffn/w_down$", ("model", "data")),
    (r"ffn/w_in$", ("data", "model")),
    (r"ffn/b_in$", ("model",)),
    (r"ffn/w_out$", ("model", "data")),
    # moe (expert-parallel by default; grok overrides via shard_mode)
    (r"moe/router$", ("data", None)),
    (r"moe/w_gate$", ("model", "data", None)),
    (r"moe/w_up$", ("model", "data", None)),
    (r"moe/w_down$", ("model", None, "data")),
    # CNN/MLP dense layers (the CycleSL server stage at the deep cuts):
    # FSDP over the input dim + TP over the output dim.  Without this
    # the server inner loop all-reduces the FULL dense gradient and
    # runs full-size adam on every device each scan step — the dominant
    # ServerUpdate cost in the 1->8 device weak-scaling loss (§Weak
    # scaling, ARCHITECTURE.md).  shard_if_divisible drops either axis
    # when the dim doesn't divide.
    (r"lin/w$", ("data", "model")),
    # mamba2
    (r"mamba/w_in$", ("data", "model")),
    (r"mamba/conv_w$", (None, "model")),
    (r"mamba/w_out$", ("model", "data")),
    (r"mamba/(a_log|dt_bias|D)$", ("model",)),
    (r"mamba/gate_norm/scale$", ("model",)),
    # everything else (norms, biases, conv_b): replicated
    (r".*", ()),
]

MOE_FFN_MODE_RULES: list[tuple[str, tuple]] = [
    (r"moe/w_gate$", (None, "data", "model")),
    (r"moe/w_up$", (None, "data", "model")),
    (r"moe/w_down$", (None, "model", "data")),
]


def shard_if_divisible(dim: int, axis: Optional[str], mesh: Mesh):
    """Drop a sharding axis when the dim doesn't divide the axis size."""
    if axis is None:
        return None
    size = 1
    for a in (axis if isinstance(axis, tuple) else (axis,)):
        if a not in mesh.shape:
            return None
        size *= mesh.shape[a]
    return axis if dim % size == 0 else None


def _spec_for(path: str, shape: Sequence[int], mesh: Mesh,
              rules: list[tuple[str, tuple]], role: str) -> P:
    template: tuple = ()
    for pat, tpl in rules:
        if re.search(pat, path):
            template = tpl
            break
    nd = len(shape)
    nt = len(template)
    lead = [None] * (nd - nt)
    axes = list(lead) + list(template[:nd])
    if role == "client":
        # drop 'data' (used by the cohort dim), then shard the leading
        # cohort dim over ('pod','data') / 'data'.
        axes = [None if a == "data" else a for a in axes]
        cohort_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        if axes:
            axes[0] = cohort_axes if len(cohort_axes) > 1 else (
                cohort_axes[0] if cohort_axes else None)
    # divisibility guard, per dim
    out = []
    for d, a in zip(shape, axes):
        out.append(shard_if_divisible(d, a, mesh) if a is not None else None)
    return P(*out)


def param_specs(params, mesh: Mesh, role: str = "full",
                moe_shard_mode: str = "expert"):
    """Pytree of PartitionSpec matching ``params``.

    role: 'full'/'server' — plain model params;
          'client'        — params stacked with a leading cohort dim.
    """
    rules = RULES
    if moe_shard_mode == "ffn":
        rules = MOE_FFN_MODE_RULES + RULES
    return map_with_path(
        lambda path, leaf: _spec_for(path, leaf.shape, mesh, rules, role),
        params)


def named_shardings(params, mesh: Mesh, role: str = "full",
                    moe_shard_mode: str = "expert"):
    specs = param_specs(params, mesh, role, moe_shard_mode)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------------
# Activation-batch constraints.  GSPMD propagates FSDP *weight*
# shardings into activations (the 'data' axis lands on d_model and the
# batch dim silently replicates — §Perf iteration 5).  Model code calls
# ``constrain_batch`` after the embedding and after every block group;
# the launcher registers the mesh here before tracing.
_ACTIVATION_MESH: Mesh | None = None


def set_activation_mesh(mesh: Mesh | None):
    global _ACTIVATION_MESH
    _ACTIVATION_MESH = mesh


def get_activation_mesh():
    return _ACTIVATION_MESH


def constrain_batch(x, batch_dims: int = 1):
    """Constrain the leading dim(s) of an activation to the batch axes.

    batch_dims=2 handles cohort-stacked [C, b, ...] activations: C takes
    the batch axes, b stays unsharded.  No-op when no mesh registered
    (CPU tests) or the dim doesn't divide.
    """
    mesh = _ACTIVATION_MESH
    if mesh is None or not hasattr(x, "ndim") or x.ndim < batch_dims + 1:
        return x
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if not axes or x.shape[0] % size != 0:
        axes = ("data",) if "data" in mesh.shape else ()
        size = mesh.shape.get("data", 1) if axes else 1
        if not axes or x.shape[0] % size != 0:
            return x
    lead = axes if len(axes) > 1 else axes[0]
    spec = P(lead, *([None] * (x.ndim - 1)))
    try:
        from jax.sharding import NamedSharding
        from jax.lax import with_sharding_constraint
        return with_sharding_constraint(x, NamedSharding(mesh, spec))
    except Exception:  # outside jit/mesh context
        return x


def pool_shard_info(mesh: Optional[Mesh], total: int
                    ) -> Optional[tuple[tuple[str, ...], int, int]]:
    """Per-shard pool-slice geometry for the shard-local resample.

    Mirrors :func:`batch_spec`'s axis choice for a pooled ``[T, ...]``
    feature array (the leading rows over ``('pod', 'data')``, falling
    back to ``'data'`` alone when T doesn't divide the combined size) and
    returns ``(axes, n_shards, rows_per_shard)`` — shard ``s`` owns the
    contiguous global row slice ``[s * rows_per_shard, (s+1) *
    rows_per_shard)``.  ``None`` means the pool cannot be evenly
    sliced over any batch axis (the caller must keep the GSPMD gather).
    """
    if mesh is None:
        return None
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if not axes or total % size != 0:
        if "data" in mesh.shape and total % mesh.shape["data"] == 0:
            axes, size = ("data",), mesh.shape["data"]
        else:
            return None
    return axes, size, total // size


def pool_slice_spec(mesh: Mesh, total: int, ndim: int) -> Optional[P]:
    """PartitionSpec of one pooled ``[T, ...]`` array under the per-shard
    slice geometry of :func:`pool_shard_info` (leading rows over the
    batch axes, trailing dims replicated); ``None`` when the pool has no
    even slicing."""
    info = pool_shard_info(mesh, total)
    if info is None:
        return None
    axes, _, _ = info
    lead = axes if len(axes) > 1 else axes[0]
    return P(lead, *([None] * (ndim - 1)))


def batch_spec(mesh: Mesh, batch: int, extra_dims: int = 1) -> P:
    """Shard the leading batch dim over ('pod','data') if divisible."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if not axes or batch % size != 0:
        # try 'data' alone
        if "data" in mesh.shape and batch % mesh.shape["data"] == 0:
            return P("data", *([None] * extra_dims))
        return P(*([None] * (1 + extra_dims)))
    lead = axes if len(axes) > 1 else axes[0]
    return P(lead, *([None] * extra_dims))


# ------------------------------------------------------------------
# Mesh-native round execution: the constraint points every RoundProgram
# phase threads when the Engine runs on a mesh.  All of these are value-
# neutral (with_sharding_constraint only pins layout), which is what
# makes the 1-device-mesh path bit-for-bit equal to the unsharded one.
def _wsc(x, mesh: Mesh, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_cohort(x, mesh: Optional[Mesh]):
    """Constrain a [C, ...] cohort-stacked (or [T, ...] pooled-row) array:
    leading dim over the batch axes, trailing dims replicated.  No-op when
    the leading dim doesn't divide the batch axes (batch_spec guard)."""
    if mesh is None or not hasattr(x, "ndim") or x.ndim < 1:
        return x
    return _wsc(x, mesh, batch_spec(mesh, x.shape[0], x.ndim - 1))


def constrain_cohort_tree(tree, mesh: Optional[Mesh]):
    """constrain_cohort over every leaf of a cohort-stacked pytree (the
    [C, ...] EntityState stacks the phases carry)."""
    if mesh is None:
        return tree
    return jax.tree.map(lambda l: constrain_cohort(l, mesh), tree)


def constrain_entity_params(params, mesh: Optional[Mesh], role: str = "server"):
    """Pin a params pytree to its path-rule weight placement (FSDP/TP).

    The pipelined Engine threads this through the extract dispatch's
    θ_S^t snapshot: the snapshot stays on the model/weight axes while
    every other stage tensor sits on the batch axes — disjoint axis
    placement, so XLA can run cohort k+1's extraction concurrently with
    cohort k's server inner loop instead of serializing them on a shared
    axis.  Value-neutral (layout only); no-op off-mesh.
    """
    if mesh is None or params is None:
        return params
    specs = param_specs(params, mesh, role)
    return jax.tree.map(lambda l, s: _wsc(l, mesh, s), params, specs)


def params_are_sharded(params, mesh: Optional[Mesh],
                       role: str = "server") -> bool:
    """True when any leaf of ``params`` gets a non-replicated spec under
    the path rules — i.e. the entity runs FSDP/TP on this mesh.  Purely
    static (shapes + rules); safe to call at trace time."""
    if mesh is None or params is None:
        return False
    for spec in jax.tree.leaves(param_specs(params, mesh, role),
                                is_leaf=lambda x: isinstance(x, P)):
        if any(ax is not None for ax in spec):
            return True
    return False


def constrain_server_batch(f, y, mesh: Optional[Mesh],
                           replicate: bool = False):
    """Pin the CycleSL server inner loop's minibatch layout on the mesh.

    Default (``replicate=False``): data-parallel — GSPMD propagates FSDP
    *weight* shardings into the resampled feature batches (the 'data'
    axis lands on d_model and the batch dim silently replicates — §Perf
    iteration 3); this pins the resampled (features, labels) minibatch
    instead: rows over 'data', and for >=3-d transformer features the
    model dim over 'model' (falling back to sequence sharding when the
    server batch doesn't divide 'data').  Replaces the old
    un-serializable ``CycleConfig.batch_constraint`` callable hook.

    ``replicate=True``: tensor-parallel — used when the server params
    themselves are FSDP/TP-sharded (:func:`params_are_sharded`).  Row-
    sharding the minibatch on the same axis as the weights would force
    GSPMD to all-gather the full weight matrix every scan step; with the
    minibatch replicated the contraction partials travel instead (an
    activation-sized all-reduce, orders of magnitude smaller than the
    weights) and the optimizer update stays 1/n_shards per device
    (§Weak scaling, ARCHITECTURE.md).
    """
    if mesh is None:
        return f, y
    if replicate:
        f = _wsc(f, mesh, P(*([None] * f.ndim)))
        y = jax.tree.map(
            lambda l: _wsc(l, mesh, P(*([None] * l.ndim))), y)
        return f, y
    d_ax = shard_if_divisible(f.shape[0], "data", mesh)
    m_ax = "model" if "model" in mesh.shape else None
    if f.ndim >= 3:              # [sb, S, ..., d] transformer features
        seq_ax = None if d_ax else shard_if_divisible(f.shape[1], "data",
                                                      mesh)
        dm_ax = shard_if_divisible(f.shape[-1], m_ax, mesh) if m_ax else None
        f = _wsc(f, mesh, P(d_ax, seq_ax, *([None] * (f.ndim - 3)), dm_ax))
    elif f.ndim == 2:
        f = _wsc(f, mesh, P(d_ax, None))
    y = jax.tree.map(
        lambda l: _wsc(l, mesh, P(d_ax, *([None] * (l.ndim - 1)))), y)
    return f, y


def cohort_shard_axes(mesh: Optional[Mesh], n_slots: int
                      ) -> Optional[tuple]:
    """Batch-axis tuple the [C, ...] cohort dim shards over, or None when
    there is no mesh / the dim doesn't divide the combined axis size.
    Mirrors :func:`batch_spec`'s axis choice ('pod','data' then 'data'
    alone)."""
    if mesh is None:
        return None
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if axes and n_slots % size == 0:
        return axes
    if "data" in mesh.shape and n_slots % mesh.shape["data"] == 0:
        return ("data",)
    return None


def shard_aligned_capacity(mesh: Optional[Mesh], capacity: int) -> int:
    """Round a cohort capacity up to a multiple of the batch-axis shard
    count so no shard runs under-filled (and :func:`batch_spec` never
    falls back to replicated).  Padded rounds are capacity-invariant
    (the PR 2 masking property), which is what makes this round-up
    numerically free.  Identity off-mesh and at 1 device."""
    if mesh is None:
        return capacity
    size = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            size *= mesh.shape[a]
    if size <= 1:
        return capacity
    return ((capacity + size - 1) // size) * size


def slot_shard_map(fn, mesh: Optional[Mesh], slot_args: tuple,
                   rep_args: tuple = ()):
    """Run a purely slot-wise cohort computation inside a ``shard_map``
    over the batch axes, so each device computes only its ``C /
    n_shards`` local slots.

    ``fn(*slot_args, *rep_args)`` must be embarrassingly parallel over
    the leading dim of every ``slot_args`` leaf (slot ``i`` of every
    output depends only on slot ``i`` of the inputs — the vmapped
    client-forward / per-client VJP / per-replica step shape).
    ``rep_args`` leaves are replicated to every shard.

    Why not leave it to GSPMD: the cohort-vmapped convolutions lower to
    ``feature_group_count=C`` grouped convs whose slot dim is folded
    into the channel dims; GSPMD has no partitioning rule for that
    fold, so it *replicates* the grouped conv on every device and then
    dynamic-slices out the local slot — 8 devices each do all 8 slots'
    work (§Weak scaling, ARCHITECTURE.md).  The manual shard_map makes
    the slot partition structural instead of inferred.

    Falls back to the plain call when there is no mesh, when C doesn't
    divide the batch-axis shard count (the Engine's shard-aligned
    capacity makes the divisible case the steady state), or when an
    activation mesh is registered (``set_activation_mesh``): the
    launcher's transformer/whisper stages constrain their own
    activations via ``constrain_batch``, and a named-axis constraint is
    illegal inside the manual region — those stacks keep the GSPMD
    path.  Per-slot math is unchanged, so the result is bit-for-bit the
    GSPMD path's.
    """
    if mesh is None or _ACTIVATION_MESH is not None:
        return fn(*slot_args, *rep_args)
    leaves = [l for l in jax.tree.leaves(slot_args)
              if hasattr(l, "ndim") and l.ndim >= 1]
    if not leaves:
        return fn(*slot_args, *rep_args)
    C = leaves[0].shape[0]
    axes = cohort_shard_axes(mesh, C)
    if axes is None:
        return fn(*slot_args, *rep_args)
    lead = axes if len(axes) > 1 else axes[0]

    def sspec(l):
        return P(lead, *([None] * (l.ndim - 1)))

    def rspec(l):
        return P(*([None] * getattr(l, "ndim", 0)))

    out_shape = jax.eval_shape(lambda s, r: fn(*s, *r), slot_args, rep_args)
    wrapped = jax.shard_map(
        lambda s, r: fn(*s, *r), mesh=mesh,
        in_specs=(jax.tree.map(sspec, slot_args),
                  jax.tree.map(rspec, rep_args)),
        out_specs=jax.tree.map(sspec, out_shape),
        check_vma=False)
    return wrapped(slot_args, rep_args)


def sharded_entity_step(entity, grads, opt, mesh: Optional[Mesh],
                        role: str = "server"):
    """:func:`repro.core.protocol.entity_step` that compiles on a
    multi-device TPU mesh.

    A fused optimizer (``opt.apply``: the Pallas fused-Adam kernel) is a
    Mosaic custom call, and XLA cannot partition one — inside a jit over
    several devices it must sit in a ``shard_map``.  So on such a mesh
    the step runs inside a ``shard_map`` over the entity's path-rule
    placement for ``role`` ('server', or 'full' for a single shared
    client entity): each device updates its own block of every leaf.
    The update is elementwise, so the values are the unsharded step's.
    Off-mesh, on one device, or with an unfused optimizer this is the
    plain ``entity_step``.  Call it only outside a manual region.
    """
    from repro.core.protocol import entity_step
    if (mesh is None or mesh.size == 1
            or getattr(opt, "apply", None) is None):
        return entity_step(entity, grads, opt)
    especs = param_specs(entity, mesh, role)
    return jax.shard_map(
        lambda e, g: entity_step(e, g, opt), mesh=mesh,
        in_specs=(especs, param_specs(grads, mesh, role)),
        out_specs=especs, check_vma=False)(entity, grads)


def cohort_entity_step(entities, grads, opt, mesh: Optional[Mesh]):
    """Vmapped :func:`entity_step` over a [C, ...] cohort stack; with a
    fused optimizer it runs in :func:`slot_shard_map`, for the reason
    :func:`sharded_entity_step` gives."""
    from repro.core.protocol import entity_step
    step = jax.vmap(lambda e, g: entity_step(e, g, opt))
    if getattr(opt, "apply", None) is None:
        return step(entities, grads)
    return slot_shard_map(step, mesh, (entities, grads))


def train_state_shardings(state, mesh: Mesh, moe_shard_mode: str = "expert",
                          shard_cohort: bool = True):
    """NamedSharding tree for a TrainState-like NamedTuple
    ``(server, clients, client_global)``.

    server / client_global — plain model entities, FSDP/TP per the path
    rules (role 'server' / 'full'); clients — the persistent [N, ...]
    per-client stack, leading cohort dim over the batch axes (role
    'client') unless ``shard_cohort`` is off.  Works on concrete states
    and on ``jax.eval_shape`` abstractions alike.
    """
    def _field(sub, role):
        if sub is None:
            return None
        return named_shardings(sub, mesh, role, moe_shard_mode)

    return type(state)(
        _field(state.server, "server"),
        _field(state.clients, "client" if shard_cohort else "full"),
        _field(state.client_global, "full"))


def constrain_stage(stage, mesh: Optional[Mesh], uses_global_client: bool):
    """Pin every field of a pipelined :class:`PipelineStage` to its
    canonical placement — the buffer-placement rule for the depth-L
    staleness ring.

    At depth 1 the single in-flight stage inherits a stable layout from
    the constraints inside the extract trace, but with L stages buffered
    the compiler is free to place each ring slot differently (the stage
    outlives several dispatch boundaries).  Constraining at the stage
    boundary keeps all L slots on ONE layout: cohort-stacked tensors
    (per-client entity stacks, smashed data, the pooled store rows) on
    the batch axes, the θ_S^t snapshot — and the un-broadcast global θ_C
    snapshot — on the FSDP/TP weight axes.  Value-neutral (layout only);
    no-op off-mesh.
    """
    if mesh is None:
        return stage
    clients = (constrain_entity_params(stage.clients, mesh, role="full")
               if uses_global_client
               else constrain_cohort_tree(stage.clients, mesh))
    store = stage.store
    if store is not None:
        from repro.core.feature_store import constrain_store
        store = constrain_store(store, mesh)
    return stage._replace(
        clients=clients,
        server_prev=constrain_entity_params(stage.server_prev, mesh),
        feats=(None if stage.feats is None
               else constrain_cohort_tree(stage.feats, mesh)),
        store=store)
