"""The server-side global feature dataset + resampler (paper Eq. 3).

``D_S^f = ⨄_i B_i^f`` — client feature batches are pooled and the
server resamples *shuffled* mini-batches that are no longer client-
bound.  On a pod the pooled array stays sharded over the 'data' axis and
resampling is a sharded permutation-gather (the `feature_resample`
Pallas kernel covers the shard-local gather).

Two resampling plans live here:

* :func:`resample_plan` — the classic dense plan (one
  ``jax.random.permutation`` per server epoch) used when every pooled
  row is live.
* :func:`masked_resample_plan` — the padded-cohort plan: rows are
  ordered by per-row counter-based uniforms (``fold_in(key, row)``),
  with padded rows pushed past the live ones.  Because each row's sort
  key depends only on ``(key, row_index)`` — never on the pool's padded
  capacity — the sequence of live rows it yields is *identical* for any
  capacity ≥ the live count.  That shape-invariance is what makes the
  padded round bit-for-bit equal to the unpadded one (tests/test_padded).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


def valid_from_mask(mask, batch: int) -> jax.Array:
    """Broadcast a [C] cohort attendance mask to the [C*b] per-row
    validity mask over the pooled feature axis.

    Zeros may sit ANYWHERE in ``mask`` — trailing padded slots, or live
    slots zeroed mid-round by scenario churn (dropouts / deadline-missed
    stragglers) — and the pooled validity inherits that interleaving.
    :func:`masked_resample_plan` already handles arbitrary interleaved
    zeros (each row's sort key is a pure function of its index), so a
    churn-dropped slot's rows are pushed past every live row and never
    enter a valid server minibatch.
    """
    return jnp.repeat(jnp.asarray(mask, jnp.float32), batch)


class FeatureStore(NamedTuple):
    """Pooled smashed data: features [T, ...], labels pytree of [T, ...].

    ``valid`` is an optional [T] row mask (1.0 = live row, 0.0 = a row
    contributed by a padded or churn-dropped cohort slot); ``None``
    means every row is live (the classic unpadded pool).
    """
    features: jax.Array
    labels: jax.Array
    valid: Optional[jax.Array] = None

    @classmethod
    def pool(cls, feature_batches, label_batches, mask=None) -> "FeatureStore":
        """[C, b, ...] per-client batches -> pooled [C*b, ...].
        Labels may be any pytree of [C, b, ...] arrays.  ``mask`` is an
        optional [C] cohort attendance mask; it is broadcast to a per-row
        validity mask over the pooled axis."""
        merge = lambda a: a.reshape((-1,) + a.shape[2:])
        valid = None
        if mask is not None:
            valid = valid_from_mask(mask, feature_batches.shape[1])
        return cls(merge(feature_batches), jax.tree.map(merge, label_batches),
                   valid)

    @property
    def size(self) -> int:
        return self.features.shape[0]


def resample_plan(key, total: int, epochs: int, batch: int) -> jax.Array:
    """Index plan [epochs, steps, batch]: a fresh permutation per server
    epoch (random-reshuffling — the paper's analog of centralized
    shuffling, §3.1).  Truncates the tail that doesn't fill a batch."""
    steps = total // batch
    keys = jax.random.split(key, epochs)
    perms = jax.vmap(lambda k: jax.random.permutation(k, total))(keys)
    return perms[:, : steps * batch].reshape(epochs, steps, batch)


def masked_resample_plan(key, valid, epochs: int,
                         batch: int) -> tuple[jax.Array, jax.Array]:
    """Padded-pool plan: [epochs, steps, batch] indices + [epochs, steps]
    step-validity mask.

    Each row r draws a sort key from ``uniform(fold_in(key_e, r))`` —
    a pure function of (epoch key, row id), independent of the pool's
    padded capacity — and padded rows are pushed to +inf, so the sorted
    order lists the live rows first, in a capacity-invariant random
    order.  A step is valid iff all ``batch`` of its rows are live,
    which reproduces the dense plan's drop-the-tail truncation for the
    live row count.
    """
    total = valid.shape[0]
    steps = total // batch
    rows = jnp.arange(total)
    n_valid = jnp.sum(valid > 0)

    def one_epoch(k):
        u = jax.vmap(lambda r: jax.random.uniform(jax.random.fold_in(k, r))
                     )(rows)
        return jnp.argsort(jnp.where(valid > 0, u, jnp.inf))

    perms = jax.vmap(one_epoch)(jax.random.split(key, epochs))
    plan = perms[:, : steps * batch].reshape(epochs, steps, batch)
    step_ok = ((jnp.arange(steps) + 1) * batch <= n_valid)
    return plan, jnp.broadcast_to(step_ok, (epochs, steps))


def gather_batch(store: FeatureStore, idx,
                 use_kernel: Optional[bool] = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Resample one server minibatch: ``out[i] = store[idx[i]]``.

    Backend-gated like ``fused_adam``: on TPU the row gather dispatches
    to the ``kernels.ops.feature_resample`` scalar-prefetch Pallas
    kernel (indices in SMEM, 8 rows gathered per grid step); elsewhere
    the XLA ``jnp.take`` lowering is kept (``use_kernel=True`` forces
    the kernel in interpret mode, which is what the CPU equivalence test
    exercises).  Both paths compute the identical gather.

    Caveat: XLA cannot partition a compiled Pallas call, so in a jit
    over several TPU devices the kernel must run inside a ``shard_map``:
    :func:`shard_local_gather` is that wrapper, with per-shard index
    translation that keeps the gather local (the server inner loop
    routes there on a multi-device TPU mesh).  The jnp path, and the
    interpreted kernel, partition natively.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        from repro.kernels import ops
        take = lambda a: ops.resample_rows(a, idx)
    else:
        take = lambda a: jnp.take(a, idx, axis=0)
    return take(store.features), jax.tree.map(take, store.labels)


def shard_slice_indices(idx, shard: int, rows_per_shard: int
                        ) -> tuple[jax.Array, jax.Array]:
    """Translate global gather indices into ONE shard's pool-slice frame.

    The index-translation contract of the shard-local resample: shard
    ``s`` owns the contiguous global rows ``[s * rows_per_shard, (s+1) *
    rows_per_shard)``; a global index lands in exactly one shard's
    slice, so across shards the ``ok`` masks partition the gather.
    Returns ``(local, ok)`` — ``local`` is clipped into ``[0,
    rows_per_shard)`` so masked-off rows still index safely (their
    gathered values are zeroed by the caller before the cross-shard
    fixup sum).
    """
    local = idx - shard * rows_per_shard
    ok = (local >= 0) & (local < rows_per_shard)
    return jnp.clip(local, 0, rows_per_shard - 1).astype(jnp.int32), ok


def shard_local_gather(store: FeatureStore, idx, mesh,
                       use_kernel: Optional[bool] = None,
                       replicate_out: bool = False
                       ) -> tuple[jax.Array, jax.Array]:
    """Shard-LOCAL resample: ``out[i] = store[idx[i]]`` without gathering
    the pooled operand around the kernel.

    XLA cannot partition a compiled ``pallas_call``, so on a sharded
    TPU mesh the kernel path of :func:`gather_batch` needs a
    ``shard_map`` around it.  This wrapper keeps the gather local: a
    ``shard_map`` over the pool's batch axes gives each shard only its
    contiguous row slice, per-shard index translation
    (:func:`shard_slice_indices`) selects the plan rows that land in the
    slice, and rows that don't are fixed up by a masked cross-shard sum
    — every output row has exactly ONE live contribution (the masks
    partition the gather), so the psum is value-exact and the result is
    bit-for-bit the GSPMD gather.  The plan indices are uniform over
    shards (``resample_plan``/``masked_resample_plan`` permutations are
    computed from the replicated round key), which is what makes the
    replicated-``idx`` in_spec correct.

    Communication: a reduce-scatter (or all-reduce when the minibatch
    doesn't divide the shards) of the [M, ...] minibatch instead of an
    all-gather of the [T, ...] pool — M << T in every CycleSL setting.
    Falls back to :func:`gather_batch` when the pool rows don't divide
    the batch axes (``pool_shard_info`` returns None).

    ``replicate_out=True`` forces the all-reduce (psum) form so the
    minibatch comes out replicated — the tensor-parallel server layout,
    where FSDP/TP-sharded weights want full rows on every device.  The
    psum sums one live contribution and n_shards - 1 exact zeros per
    row, so the values are still bit-for-bit the GSPMD gather.
    """
    from repro.sharding.specs import pool_shard_info
    info = pool_shard_info(mesh, store.size) if mesh is not None else None
    if info is None:
        return gather_batch(store, idx, use_kernel=use_kernel)
    axes, n_shards, rows_per_shard = info
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    from jax.sharding import PartitionSpec as P

    lead = axes if len(axes) > 1 else axes[0]
    M = idx.shape[0]
    scatter = M % n_shards == 0 and not replicate_out

    def row_spec(a):
        return P(lead, *([None] * (a.ndim - 1)))

    def out_spec(a):
        return row_spec(a) if scatter else P(*([None] * a.ndim))

    def body(feats, labels, idx):
        shard = jnp.zeros((), jnp.int32)
        for a in axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        local, ok = shard_slice_indices(idx, shard, rows_per_shard)

        def take(a):
            if use_kernel:
                from repro.kernels import ops
                rows = ops.resample_rows(a, local)
            else:
                rows = jnp.take(a, local, axis=0)
            # mask off rows owned by other shards, then cross-shard
            # fixup: exactly one shard contributes each output row, so
            # summing the (n_shards - 1) zeros is value-exact
            rows = jnp.where(ok.reshape((-1,) + (1,) * (rows.ndim - 1)),
                             rows, jnp.zeros((), rows.dtype))
            if scatter:
                return jax.lax.psum_scatter(rows, lead,
                                            scatter_dimension=0, tiled=True)
            return jax.lax.psum(rows, lead)

        return take(feats), jax.tree.map(take, labels)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(row_spec(store.features),
                  jax.tree.map(row_spec, store.labels),
                  P(None)),
        out_specs=(out_spec(store.features),
                   jax.tree.map(out_spec, store.labels)),
        check_vma=False)
    return fn(store.features, store.labels, idx.astype(jnp.int32))


def shard_local_fused_loss(store: FeatureStore, idx, w, mesh,
                           use_kernel: Optional[bool] = None) -> jax.Array:
    """Mean fused gather+linear-head-loss over one server minibatch,
    computed INSIDE a ``shard_map`` over the pool's batch axes —
    differentiable in the head weights ``w`` only (D_S^f is data,
    paper Eq. 3).

    This is the shard-local composition of the two paths that could not
    previously coexist: the fused gather+loss kernel
    (``kernels.ops.fused_gather_loss_mean``) avoids materializing the
    gathered minibatch, but GSPMD has no partitioning rule for a bare
    ``pallas_call``, so on a sharded mesh it all-gathered D_S^f around
    the kernel — exactly the collective ``shard_local_gather`` exists to
    kill.  Here each shard runs the fused per-row loss over only the
    plan rows that land in ITS contiguous pool slice
    (:func:`shard_slice_indices`), masks the rest to exact zeros, and a
    scalar ``psum`` of the masked partial sums reassembles the
    minibatch-mean loss — one f32 scalar on the wire per step instead of
    the [T, ...] pool.  The backward pass is the analytic linear-head
    cross-entropy VJP computed the same way: per-shard
    ``dw = fᵀ dlogits`` partials over owned rows, psum'd.

    The masks partition the gather (each plan row has exactly one owner
    shard), so the loss equals the unsharded fused path up to summation
    order.  Falls back to ``fused_gather_loss_mean`` when the pool
    doesn't divide the batch axes.
    """
    from repro.kernels import ops
    from repro.sharding.specs import pool_shard_info
    info = pool_shard_info(mesh, store.size) if mesh is not None else None
    feats2 = store.features.reshape((store.size, -1))
    if info is None:
        return ops.fused_gather_loss_mean(feats2, store.labels, idx, w)
    axes, n_shards, rows_per_shard = info
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    from jax.sharding import PartitionSpec as P

    lead = axes if len(axes) > 1 else axes[0]
    M = idx.shape[0]

    def shard_id():
        s = jnp.zeros((), jnp.int32)
        for a in axes:
            s = s * mesh.shape[a] + jax.lax.axis_index(a)
        return s

    def fwd_body(f_loc, l_loc, idx, w):
        local, ok = shard_slice_indices(idx, shard_id(), rows_per_shard)
        if use_kernel:
            losses = ops.gather_loss_microbatch(f_loc, l_loc, local, w)
        else:
            f = jnp.take(f_loc, local, axis=0).astype(jnp.float32)
            logits = f @ w.astype(jnp.float32)
            y = jnp.take(l_loc, local, axis=0).astype(jnp.int32)
            losses = (jax.nn.logsumexp(logits, axis=-1)
                      - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0])
        losses = jnp.where(ok, losses, 0.0)
        return jax.lax.psum(jnp.sum(losses), lead) / M

    def bwd_body(f_loc, l_loc, idx, w, g):
        local, ok = shard_slice_indices(idx, shard_id(), rows_per_shard)
        f = jnp.take(f_loc, local, axis=0).astype(jnp.float32)
        logits = f @ w.astype(jnp.float32)
        y = jnp.take(l_loc, local, axis=0)
        p = jax.nn.softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(y, w.shape[1], dtype=jnp.float32)
        # rows owned by other shards contribute exact zeros to dw
        dlog = jnp.where(ok[:, None], (p - onehot) * (g / M), 0.0)
        return jax.lax.psum(f.T @ dlog, lead).astype(w.dtype)

    row = lambda a: P(lead, *([None] * (a.ndim - 1)))
    fwd_sm = jax.shard_map(fwd_body, mesh=mesh,
                           in_specs=(row(feats2), P(lead), P(None),
                                     P(None, None)),
                           out_specs=P(), check_vma=False)
    bwd_sm = jax.shard_map(bwd_body, mesh=mesh,
                           in_specs=(row(feats2), P(lead), P(None),
                                     P(None, None), P()),
                           out_specs=P(None, None), check_vma=False)

    @jax.custom_vjp
    def fused(feats2, labels, idx, w):
        return fwd_sm(feats2, labels, idx, w)

    def fused_fwd(feats2, labels, idx, w):
        return fused(feats2, labels, idx, w), (feats2, labels, idx, w)

    def fused_bwd(res, g):
        import numpy as np
        feats2, labels, idx, w = res
        dw = bwd_sm(feats2, labels, idx, w, g)
        zero = lambda x: (np.zeros(x.shape, jax.dtypes.float0)
                          if jnp.issubdtype(x.dtype, jnp.integer)
                          else jnp.zeros_like(x))
        return zero(feats2), zero(labels), zero(idx), dw

    fused.defvjp(fused_fwd, fused_bwd)
    return fused(feats2, store.labels, idx.astype(jnp.int32), w)


def pool_store(feats, ys, mask=None, mesh=None) -> FeatureStore:
    """Build the pooled, placement-pinned D_S^f handoff for one cohort.

    The single construction point both execution schedules share: the
    monolithic round pools inside ``ServerUpdate``, while the pipelined
    extract dispatch pools here and hands the finished store to the
    in-flight tail (``PipelineStage.store``) — identical ops either way
    (stop_gradient + reshape + the broadcast validity mask), which is
    what keeps the pipelined round bit-for-bit the sequential one.
    """
    return constrain_store(
        FeatureStore.pool(jax.lax.stop_gradient(feats), ys, mask=mask), mesh)


def constrain_store(store: FeatureStore, mesh) -> FeatureStore:
    """Pin the pooled arrays' row dim to the mesh batch axes so D_S^f
    stays sharded over 'data' through the server inner loop (the paper's
    pooled feature dataset is the one [C*b, ...] tensor per round whose
    placement GSPMD would otherwise replicate)."""
    from repro.sharding.specs import constrain_cohort
    if mesh is None:
        return store
    return store._replace(
        features=constrain_cohort(store.features, mesh),
        labels=jax.tree.map(lambda l: constrain_cohort(l, mesh),
                            store.labels),
        valid=(None if store.valid is None
               else constrain_cohort(store.valid, mesh)))


class RingEntry(NamedTuple):
    """One in-flight cohort awaiting its tail: the round it will be
    consumed at, the round whose pre-tail state its extract read
    (``src_round``; consumption round - src_round = realized θ_S lag),
    the extracted :class:`~repro.api.phases.PipelineStage`, and the
    host-side cohort inputs (clean + fault-injected) the tail and any
    recovery re-extract need."""
    round: int
    src_round: int
    stage: object
    inputs: object
    inj_inputs: object


class StaleFeatureRing:
    """Bounded buffer of in-flight extracted stages — the structure that
    delivers a round-k extract into the round-k+L pool.

    The Engine pushes ``extract(k+L)`` (dispatched against round k's
    pre-tail state) and pops entry ``k`` just before ``tail(k)``, so at
    most ``depth`` stages are ever in flight and the realized snapshot
    lag of any consumed entry is bounded by ``depth`` *by construction*
    (``push`` asserts the bound; ``pop`` asserts FIFO order and records
    the realized lag).  ``rewind`` is the recovery hook: after a
    retried/rolled-back round every buffered stage was extracted from a
    discarded state, so each is re-extracted from the accepted one.
    """

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError(f"ring depth must be >= 1, got {depth}")
        self.depth = depth
        self._entries: list[RingEntry] = []
        self.realized_lags: list[int] = []

    def __len__(self) -> int:
        return len(self._entries)

    def push(self, round: int, src_round: int, stage, inputs, inj_inputs):
        assert len(self._entries) < self.depth, \
            f"ring overflow: {len(self._entries)} stages in flight " \
            f"(depth {self.depth})"
        assert round - src_round <= self.depth, \
            f"stage for round {round} extracted at {src_round} would " \
            f"exceed the lag bound {self.depth}"
        if self._entries:
            assert round == self._entries[-1].round + 1, "non-contiguous push"
        self._entries.append(
            RingEntry(round, src_round, stage, inputs, inj_inputs))

    def pop(self, round: int) -> RingEntry:
        assert self._entries and self._entries[0].round == round, \
            f"expected round {round} at ring head, have " \
            f"{[e.round for e in self._entries]}"
        entry = self._entries.pop(0)
        self.realized_lags.append(entry.round - entry.src_round)
        return entry

    def rewind(self, extract_fn, src_round: int):
        """Re-extract every buffered stage from the accepted state
        (recovery rewound the run past the states they were read from).
        ``extract_fn(inj_inputs)`` must read the accepted state."""
        self._entries = [
            e._replace(stage=extract_fn(e.inj_inputs), src_round=src_round)
            for e in self._entries]

    @property
    def max_realized_lag(self) -> int:
        return max(self.realized_lags, default=0)
