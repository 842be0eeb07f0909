"""The ONE driver loop every entrypoint shares.

``Engine`` owns the cohort-sampling / round / eval / checkpoint cycle
that ``launch/train.py``, ``benchmarks/*``, and the examples used to
hand-roll: build (or accept) a task + federated dataset, compile the
algorithm's RoundProgram into a jitted round (TrainState buffers donated
off-CPU), then drive it for ``cfg.rounds`` rounds with the paper's
protocol (partial attendance, sample-wise eval split, fixed per-round
key stream).

Rounds are compile-once: every cohort is padded to the static capacity
``C_max = ceil(attendance * N)`` with an attendance mask threaded
through the round (see :mod:`repro.api.phases`), so the jitted round
traces exactly once per experiment no matter how live attendance varies
round to round — wall-clock measures the algorithm, not XLA retraces.

Mesh-native execution: with ``cfg.mesh_shape`` set the Engine builds
the device mesh ONCE, places the TrainState with ``NamedSharding`` (the
client stack's leading cohort dim over the batch axes, server weights
FSDP/TP per :mod:`repro.sharding.specs` path rules), commits every
round input to the batch axes, and pins the round's output shardings —
one trace per (algo, config, mesh), and the 1-device mesh is bit-for-
bit the unsharded path (constraints pin layout, never values).
``cfg.resume`` restores the latest checkpoint under ``ckpt_dir`` and
continues at the saved round with cadence and sampling stream aligned.

Pipelined rounds: ``cfg.pipeline_depth=L`` runs a software pipeline
over up to L+1 in-flight cohorts — cohorts k+1..k+L's ExtractFeatures
dispatches (batch axes) against cohort k's ServerUpdate..Commit tail
(model axes), with prefetched cohort sampling and an L-deep
:class:`~repro.core.feature_store.StaleFeatureRing` of buffered
:class:`~repro.api.phases.PipelineStage` stages.
``pipeline_staleness='sync'`` is bit-for-bit the sequential loop at any
depth (the ring degenerates to one barriered stage); ``'async'``
overlaps with at most L rounds of client/θ_S^t staleness, and
``cfg.staleness_weighting`` optionally scales each cohort's server and
feature gradients by its realized lag (see ARCHITECTURE.md "Pipelined
execution" and tests/test_pipeline.py).

Pluggable callbacks observe the loop without forking it::

    eng = Engine(ExperimentConfig(algo="cyclesfl", rounds=100))
    result = eng.run()           # {"history": [...], "grad_stability": ...}

Callbacks are any objects exposing ``on_round(engine, rnd, state,
metrics)`` and/or ``on_eval(engine, rnd, loss, mets)``.
"""
from __future__ import annotations

import math
import time
import warnings
from contextlib import ExitStack, nullcontext
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import ExperimentConfig
from repro.api.phases import (PipelinedAlgorithm, SLAlgorithm, TrainState,
                              build_algorithm, build_pipelined_algorithm,
                              init_train_state)
from repro.api.registry import get_program
from repro.api.tasks import build_task
from repro.checkpoint import (latest_step, load_checkpoint, load_metadata,
                              save_checkpoint)
from repro.core.drift import GradStabilityTracker
from repro.core.feature_store import StaleFeatureRing
from repro.core.split import SplitTask
from repro.data.federated import FederatedDataset, sample_cohort
from repro.launch.mesh import make_engine_mesh
from repro.optim import adam
from repro.resilience import (HEALTH_EMA, HEALTH_NONFINITE, HEALTH_SPIKE,
                              FaultInjectedError, RecoveryController,
                              ResilienceExhaustedError, build_fault_stream)
from repro.scenario.profiles import build_profile_stream
from repro.sharding.specs import batch_spec, train_state_shardings

_NULL_SECTION = nullcontext()     # reentrant no-op for unprofiled runs


def evaluate(task, state, fed, batch: int = 256, max_batches: int = 8,
             max_clients: int = 40):
    """Test metrics matching the paper's protocol (§4.1).

    SFL-family (global client model): pooled sample-wise test set.
    PSL-family (per-client models, never aggregated): per-client
    evaluation — each client's test samples are scored with THAT
    client's model, sample-weighted (a mean of unsynced client models
    is not a model anyone owns).
    """
    if state.client_global is not None:
        # pooled sample-wise test set: stack the full batches into ONE
        # vmapped device call, score the remainder in a second call, and
        # sync device->host once at the end (instead of a float() sync
        # per test batch, which serializes host and device)
        cp, sp = state.client_global.params, state.server.params
        # probe test_arrays() directly rather than scanning fed.clients
        # (which would materialize lazy population clients); with no
        # test data anywhere it raises on the empty concatenate
        try:
            xs, ys = fed.test_arrays()
        except ValueError:
            xs = ys = ()
        if not len(xs):
            warnings.warn("evaluate: pooled test set is empty; skipping "
                          "evaluation (NaN loss)", RuntimeWarning,
                          stacklevel=2)
            return float("nan"), {}
        n = min(len(xs), batch * max_batches)
        nfull, rem = divmod(n, batch)

        def one(x, y):
            out = task.predict(cp, sp, x)
            return task.loss(out, y), task.metrics(out, y)

        losses, mets, ws = [], [], []
        if nfull:
            xb = jnp.asarray(xs[:nfull * batch]).reshape(
                (nfull, batch) + xs.shape[1:])
            yb = jnp.asarray(ys[:nfull * batch]).reshape(
                (nfull, batch) + ys.shape[1:])
            lb, mb = jax.vmap(one)(xb, yb)
            losses.append(lb)
            mets.append(mb)
            ws += [batch] * nfull
        if rem:
            lr_, mr = one(jnp.asarray(xs[nfull * batch:n]),
                          jnp.asarray(ys[nfull * batch:n]))
            losses.append(jnp.reshape(lr_, (1,)))
            mets.append(jax.tree.map(lambda v: jnp.reshape(v, (1,)), mr))
            ws.append(rem)
        losses, mets = jax.device_get((jnp.concatenate(losses),
                                       {k: jnp.concatenate([m[k] for m in mets])
                                        for k in mets[0]}))
        agg = {k: float(np.average(v, weights=ws)) for k, v in mets.items()}
        return float(np.average(losses, weights=ws)), agg

    # per-client evaluation (vmapped: one trace, truncated to the common
    # test size so client stacks are rectangular)
    idxs = [i for i, c in enumerate(fed.clients) if len(c.x_test)][:max_clients]
    if not idxs:
        # no client holds test data (e.g. a train-only federation):
        # evaluation is undefined, not an error — report NaN and move on
        warnings.warn("evaluate: no sampled client has test data; "
                      "skipping per-client evaluation (NaN loss)",
                      RuntimeWarning, stacklevel=2)
        return float("nan"), {}
    t = min(len(fed.clients[i].x_test) for i in idxs)
    xs = jnp.asarray(np.stack([fed.clients[i].x_test[:t] for i in idxs]))
    ys = jnp.asarray(np.stack([fed.clients[i].y_test[:t] for i in idxs]))
    cps = jax.tree.map(lambda x: x[np.asarray(idxs)], state.clients.params)
    sp = state.server.params

    def one(cp, x, y):
        out = task.predict(cp, sp, x)
        return task.loss(out, y), task.metrics(out, y)

    losses, mets = jax.vmap(one)(cps, xs, ys)
    # one device->host sync for the whole eval (a float() per metric
    # would round-trip once per key)
    out = jax.device_get({"loss": jnp.mean(losses),
                          **{k: jnp.mean(v) for k, v in mets.items()}})
    return float(out["loss"]), {k: float(out[k]) for k in mets}


class Engine:
    """Compile once, drive the whole experiment."""

    def __init__(self, cfg: ExperimentConfig, *,
                 task: Optional[SplitTask] = None,
                 fed: Optional[FederatedDataset] = None,
                 metric_key: Optional[str] = None,
                 callbacks: Sequence = (),
                 donate: Optional[bool] = None,
                 profiler=None,
                 log=print):
        cfg.validate()
        if (task is None) != (fed is None):
            raise ValueError("pass BOTH task and fed (they come from one "
                             "generator) or neither")
        if task is None:
            task, fed, mk = build_task(cfg.task, cfg.n_clients, cfg.alpha,
                                       cfg.seed, cfg.width, cfg.cut)
            metric_key = metric_key or mk
        self.cfg = cfg
        self.task = task
        self.fed = fed
        self.metric_key = metric_key or "accuracy"
        self.callbacks = tuple(callbacks)
        self.log = log
        self.profiler = profiler
        if donate is None:
            # donation is supported on CPU too (run() threads the state
            # linearly, so it is SAFE), but aliasing changes XLA's
            # fusion choices at the ~1-ulp level, which would break the
            # bit-for-bit Engine goldens (pipelined == sequential,
            # mesh(1,1) == unsharded) that anchor this repo's
            # equivalence contracts.  Default it off on CPU; the
            # device-resident scaling path (bench workers, the CI
            # scaling leg) opts in with donate=True, and
            # tests/test_scaling.py pins the numerics it gets.
            donate = jax.default_backend() != "cpu"
        # ---- fault-tolerant runtime: the deterministic fault stream and
        # (per-run) recovery controller.  The null ResilienceConfig
        # builds neither and changes nothing downstream.  With recovery
        # active the TrainState buffers are NEVER donated — the pre-round
        # state and the snapshot ring must outlive every dispatch so a
        # faulted round can re-run from them.
        self.faults = build_fault_stream(cfg.resilience.faults, cfg.seed)
        self.recovery: Optional[RecoveryController] = None
        self._ema = None                  # loss-EMA carry (device scalar)
        self._ckpt_corruptions = 0
        if cfg.resilience.active:
            donate = False
        program = get_program(cfg.algo)
        opt_s, opt_c = adam(cfg.lr_server), adam(cfg.lr_client)
        # ---- mesh-native execution: build the mesh ONCE, derive the
        # TrainState placement from the path-regex rules (server weights
        # FSDP/TP, client stack's leading cohort dim over the batch
        # axes), and pin it as the jitted round's out_shardings so the
        # state sharding is stable round-over-round (compile-once per
        # (algo, config, mesh)).
        self.mesh = (make_engine_mesh(cfg.mesh_shape, cfg.mesh_axes)
                     if cfg.mesh_shape is not None else None)
        self.state_shardings = None
        if self.mesh is not None:
            a_state = jax.eval_shape(lambda: init_train_state(
                jax.random.PRNGKey(0), fed.n_clients, task, opt_s, opt_c,
                program.uses_global_client))
            self.state_shardings = train_state_shardings(
                a_state, self.mesh, shard_cohort=cfg.shard_cohort)
        # ---- client-population scenario: the profile stream feeding
        # per-round attendance weights + drop/lag events.  None for the
        # null scenario (kind='none') — every scenario branch below is
        # then skipped and the run is bit-for-bit scenario-free.
        self.scenario = build_profile_stream(cfg.scenario, fed.n_clients,
                                             cfg.seed)
        # resume-replay ledger window: draws for rounds below the cutoff
        # reconstruct the quarantine set the ORIGINAL run's sampler saw
        # at that round (from the persisted event history) instead of
        # the final restored set — see restore()
        self._ledger_cutoff = 0
        self._ledger_offset = 0
        self._sample_clock = 0            # rounds drawn so far (scenario
                                          # streams fold this in, resume
                                          # fast-forwards it)
        self._telemetry: list[dict] = []  # one row per sampled round
        # the θ staleness the schedule can realize: async pipelining at
        # depth L carries snapshots up to L rounds old; everything else
        # delivers fresh params (a straggler's *drawn* lag can exceed
        # this — its realized lag is capped by the schedule)
        self._sched_lag = (cfg.pipeline_depth
                           if cfg.pipeline_staleness == "async" else 0)
        churns = self.scenario is not None and self.scenario.churns
        if (cfg.pad_cohorts and (cfg.variable_attendance or churns)
                and any(getattr(p, "mode", None) == "cycle"
                        for p in program.phases)):
            # the masked inner loop's server batch is static; if it can
            # exceed the smallest possible live pool (min_cohort clients),
            # a low-attendance or churn-thinned round would fill ZERO
            # valid steps and the server would silently not train that
            # round — reject upfront
            sb = cfg.cycle.server_batch or cfg.batch
            if sb > cfg.batch * cfg.min_cohort:
                raise ValueError(
                    f"cycle.server_batch={sb} can exceed the smallest "
                    f"possible live feature pool (min_cohort={cfg.min_cohort}"
                    f" x batch={cfg.batch} = {cfg.min_cohort * cfg.batch} "
                    "rows) under variable attendance or scenario churn, "
                    "which would leave the server inner loop with zero "
                    "valid steps in sparse rounds; lower cycle.server_batch "
                    "or raise min_cohort")
        self.algo: SLAlgorithm = build_algorithm(
            program, task, opt_s, opt_c, cfg.cycle,
            donate=donate, mesh=self.mesh,
            state_shardings=self.state_shardings,
            shard_data=cfg.shard_cohort,
            resilience=cfg.resilience)
        # ---- pipelined rounds: compile the (extract, tail) dispatch
        # pair so cohort k+1's feature extraction can be in flight while
        # cohort k's server phase runs.  None for the fused sequential
        # programs (nothing to overlap) — the run loop falls back to the
        # monolithic round.  The TrainState is only donated into the
        # tail in sync mode: async mode keeps the pre-tail state alive
        # inside the next cohort's extract dispatch.
        self.pipeline: Optional[PipelinedAlgorithm] = None
        self.pipeline_stats: dict = {}
        if cfg.pipeline_depth > 0:
            self.pipeline = build_pipelined_algorithm(
                program, task, opt_s, opt_c, cfg.cycle,
                donate=donate,
                donate_state=(cfg.pipeline_staleness == "sync"),
                mesh=self.mesh, state_shardings=self.state_shardings,
                shard_data=cfg.shard_cohort,
                resilience=cfg.resilience,
                staleness_weighting=cfg.staleness_weighting,
                staleness_lambda=cfg.staleness_lambda,
                # deep rings buffer L stages across dispatch boundaries;
                # pin their placement (depth 1 keeps the PR-4 lowering)
                pin_stage=cfg.pipeline_depth > 1)
        if self.pipeline is None:
            # fused sequential programs fall back to monolithic rounds:
            # the schedule delivers fresh params whatever depth says
            self._sched_lag = 0

    @property
    def ring_depth(self) -> int:
        """In-flight extract stages the run loop keeps: the bounded
        staleness window L in async mode, one barriered stage in sync
        mode (any configured depth — sync extract(k+1) waits for
        Commit(k), so a deeper ring could never fill), 0 unpipelined."""
        if self.pipeline is None:
            return 0
        return self._sched_lag if self._sched_lag else 1

    # ------------------------------------------------------------ state
    def init_state(self) -> TrainState:
        state = self.algo.init(jax.random.PRNGKey(self.cfg.seed),
                               self.fed.n_clients)
        if self.state_shardings is not None:
            state = jax.device_put(state, self.state_shardings)
        return state

    def _place(self, arr):
        """Commit a [C, ...] round input to the mesh batch axes (leading
        cohort dim; no-op off-mesh or with cohort sharding disabled)."""
        x = jnp.asarray(arr)
        if self.mesh is None or not self.cfg.shard_cohort:
            return x
        from jax.sharding import NamedSharding
        spec = batch_spec(self.mesh, x.shape[0], x.ndim - 1)
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def round_key(self, rnd: int):
        return jax.random.PRNGKey(self.cfg.seed * self.cfg.round_key_salt
                                  + rnd)

    @property
    def cohort_capacity(self) -> int:
        """C_max: the static cohort shape every round is padded to.

        Deterministic attendance always draws exactly
        ``round(attendance * N)`` clients, so the capacity matches the
        sampler and no slot is ever padded; only variable attendance
        needs the ceil upper bound (Binomial draws above the mean are
        clipped to it).
        """
        cfg = self.cfg
        n = self.fed.n_clients
        if cfg.variable_attendance:
            # tolerant ceil: 0.3 * 20 is 6.000000000000001 in binary
            cap = math.ceil(cfg.attendance * n - 1e-9)
        else:
            cap = round(cfg.attendance * n)
        return min(max(cfg.min_cohort, cap), n)

    @property
    def padded_capacity(self) -> int:
        """The static cohort shape rounds are actually padded to:
        :attr:`cohort_capacity` rounded UP to a multiple of the mesh's
        batch-axis shard count, so every shard owns an equal slice of
        the slot dim (a ragged slot dim would make GSPMD pad the
        shard_map'd client phases with replicated compute).

        The SAMPLER still clips to the logical ``cohort_capacity``, so
        cohort draws are device-count-invariant; the alignment slots are
        always dead (sentinel id, zero mask) and every masked phase
        treats them exactly like attendance padding — numerics match the
        unaligned round bit-for-bit.  Identity off-mesh, at 1 device,
        and with cohort sharding disabled.
        """
        cap = self.cohort_capacity
        if self.mesh is None or not self.cfg.shard_cohort:
            return cap
        from repro.sharding.specs import shard_aligned_capacity
        return shard_aligned_capacity(self.mesh, cap)

    def _sample_cohort_ids(self, rng: np.random.Generator):
        """Draw one round's live cohort, advancing the sample clock.

        Called exactly once per round by both :meth:`sample_round` and
        :meth:`_replay_sampling`, so the clock (which time-varying
        scenario streams fold into their attendance weights) stays
        aligned across resume replays.  The null scenario contributes
        ``weights=None`` — ``rng.choice`` then takes the exact same
        draw path as the scenario-free Engine (bit-for-bit cohorts).
        """
        cfg = self.cfg
        rnd = self._sample_clock
        self._sample_clock = rnd + 1
        weights = (self.scenario.weights(rnd)
                   if self.scenario is not None else None)
        if self.recovery is not None:
            # quarantined clients draw weight 0 from here on; with no
            # quarantines this is a strict pass-through (None stays None,
            # so the null path keeps the exact scenario-free rng draws)
            ctl = self.recovery
            if rnd < self._ledger_cutoff:
                # resume replay: this draw happened BEFORE some of the
                # restored ledger's events — weight it with the set as
                # of its original draw time (pipelined runs draw one
                # round ahead of recovery, hence the offset)
                saved = ctl.quarantined
                ctl.quarantined = ctl.quarantined_as_of(
                    rnd - self._ledger_offset)
                weights = ctl.sampling_weights(weights)
                ctl.quarantined = saved
            else:
                weights = ctl.sampling_weights(weights)
        return sample_cohort(self.fed.n_clients, cfg.attendance, rng,
                             min_cohort=cfg.min_cohort,
                             variable=cfg.variable_attendance,
                             max_cohort=(self.cohort_capacity
                                         if cfg.pad_cohorts else None),
                             weights=weights)

    def _replay_sampling(self, rng: np.random.Generator, rounds: int):
        """Consume exactly the RNG draws ``rounds`` rounds of
        :meth:`sample_round` would make — cohort ids plus each member's
        batch indices — without materializing, padding, or placing any
        array.  Resume fast-forwards through this so round ``n`` of a
        resumed run draws the same cohort an uninterrupted run would."""
        for _ in range(rounds):
            for c in self._sample_cohort_ids(rng):
                self.fed.clients[c].sample_indices(rng, self.cfg.batch)

    def sample_round(self, rng: np.random.Generator):
        """Cohort ids, aligned per-client (x, y) batches, and the
        attendance mask for one round.

        With ``cfg.pad_cohorts`` (the default) the cohort is padded to
        the static :attr:`cohort_capacity`: padded slots carry the
        out-of-range sentinel id N (dropped by the commit scatter),
        zeroed batches, and a 0 in the mask — so the jitted round sees
        ONE shape for the whole experiment regardless of live
        attendance.  ``mask`` is ``None`` when padding is disabled.

        Scenario churn rides the same mask: a mid-round dropout (hazard
        draw, or a straggler whose drawn lag exceeds its staleness
        bound — a deadline miss) zeroes its LIVE slot, so its features
        never enter a valid server minibatch and its commit is skipped —
        exactly the padded-slot machinery, no new trace.  The client's
        batch is still drawn first, keeping the rng stream identical to
        a no-churn round.
        """
        cfg = self.cfg
        cap = self.padded_capacity if cfg.pad_cohorts else None
        cohort = self._sample_cohort_ids(rng)
        rnd = self._sample_clock - 1       # the round that draw was for
        live = len(cohort)
        pairs = [self.fed.clients[c].sample_batch(rng, cfg.batch)
                 for c in cohort]
        xs = np.stack([p[0] for p in pairs])
        ys = np.stack([p[1] for p in pairs])
        row = {"round": rnd, "cohort": live, "live": live, "dropped": 0,
               "drop_hazard": 0, "drop_deadline": 0, "lag_drawn_max": 0,
               "realized_lag": 0}
        if cap is None:
            self._telemetry.append(row)
            return (self._place(cohort), self._place(xs), self._place(ys),
                    None)
        pad = cap - live
        mask = np.ones(cap, np.float32)
        if pad:
            cohort = np.concatenate(
                [cohort, np.full(pad, self.fed.n_clients, cohort.dtype)])
            xs = np.concatenate([xs, np.zeros((pad,) + xs.shape[1:],
                                              xs.dtype)])
            ys = np.concatenate([ys, np.zeros((pad,) + ys.shape[1:],
                                              ys.dtype)])
            mask[-pad:] = 0.0
        if self.scenario is not None and self.scenario.churns:
            ev = self.scenario.events(rnd, cohort[:live],
                                      min_live=cfg.min_cohort)
            mask[:live] *= ev.keep
            kept = int(ev.keep.sum())
            row.update(live=kept, dropped=live - kept,
                       drop_hazard=ev.hazard_drops,
                       drop_deadline=ev.deadline_drops,
                       lag_drawn_max=int(ev.lag.max()) if live else 0)
        self._telemetry.append(row)
        return (self._place(cohort), self._place(xs), self._place(ys),
                self._place(mask))

    def _emit(self, hook: str, *args):
        for cb in self.callbacks:
            fn = getattr(cb, hook, None)
            if fn is not None:
                fn(self, *args)

    # ---------------------------------------------------------- resume
    def restore(self, rng: np.random.Generator
                ) -> tuple[Optional[TrainState], int]:
        """Load the latest checkpoint under ``cfg.ckpt_dir`` and return
        ``(state, start_round)``; ``(None, 0)`` when nothing to resume.

        The checkpoint step is the 1-based round it was saved after, so
        the run continues at exactly that round index and the eval/ckpt
        cadence (``(rnd + 1) % eval_every``) stays aligned.  The cohort-
        sampling stream is replayed through the skipped rounds so round
        ``start_round`` draws the same cohort an uninterrupted run would
        have drawn.
        """
        cfg = self.cfg
        step = latest_step(cfg.ckpt_dir) if cfg.ckpt_dir else None
        if step is None:
            return None, 0
        # structure/dtype template only — no init compute or placement
        template = jax.eval_shape(
            lambda: self.algo.init(jax.random.PRNGKey(cfg.seed),
                                   self.fed.n_clients))
        state, _ = load_checkpoint(cfg.ckpt_dir, template, step=step)
        if self.state_shardings is not None:
            state = jax.device_put(state, self.state_shardings)
        if self.recovery is not None:
            # restore the recovery carry BEFORE replaying the sampling
            # stream: replay reconstructs the per-round quarantine set
            # from the persisted event history, so the replayed draws
            # consume exactly the variates the original run's did
            # (rng.choice with weights takes a different draw path than
            # without).  Older checkpoints without the key keep the
            # fresh controller (their runs had nothing to remember).
            meta = load_metadata(cfg.ckpt_dir, step).get("resilience")
            if meta:
                self.recovery.restore_state(meta)
                if "ema" in meta:
                    self._ema = jnp.asarray(meta["ema"], jnp.float32)
            # pipelined runs draw round r's cohort ring_depth loop
            # iterations early (before rounds r-L..r-1's recovery), so
            # their draws trail the ledger by ring_depth rounds —
            # including the post-replay priming draws for rounds
            # `step..step+L-1` themselves
            self._ledger_offset = self.ring_depth
            self._ledger_cutoff = step + self._ledger_offset
        self._replay_sampling(rng, step)
        self.log(f"[{self.algo.name}] resumed from {cfg.ckpt_dir} at "
                 f"round {step}")
        return state, step

    # --------------------------------------------------------- pipeline
    def _extract(self, state, inputs):
        """Dispatch the ExtractFeatures head for one cohort."""
        cohort, xs, ys, mask = inputs
        if mask is None:
            return self.pipeline.extract(state, cohort, xs, ys)
        return self.pipeline.extract(state, cohort, xs, ys, mask)

    def _tail(self, state, inputs, stage, key, lag: int = 0):
        """Dispatch the ServerUpdate..Commit tail consuming ``stage``."""
        cohort, xs, ys, mask = inputs
        kw = {}
        if self.cfg.staleness_weighting != "none":
            # the realized lag rides in as a TRACED f32 scalar so one
            # tail trace serves every lag the ring can deliver; with
            # weighting 'none' the call keeps its exact historical
            # signature (bit-for-bit the pre-weighting trace)
            kw["lag"] = jnp.float32(lag)
        if self.cfg.resilience.guard:
            # guard-on rounds ALWAYS thread the EMA carry, so the tail
            # compiles once with the health phase folded in
            return self.pipeline.tail(state, cohort, xs, ys, key, stage,
                                      mask, self._ema, **kw)
        if mask is None:
            return self.pipeline.tail(state, cohort, xs, ys, key, stage,
                                      **kw)
        return self.pipeline.tail(state, cohort, xs, ys, key, stage, mask,
                                  **kw)

    def _round_call(self, state, inputs, key):
        """Dispatch the monolithic round (guard-off calls keep the exact
        historical signature, so the trace is bit-for-bit unchanged)."""
        cohort, xs, ys, mask = inputs
        if self.cfg.resilience.guard:
            return self.algo.round(state, cohort, xs, ys, key, mask,
                                   self._ema)
        if mask is None:
            return self.algo.round(state, cohort, xs, ys, key)
        return self.algo.round(state, cohort, xs, ys, key, mask)

    # ------------------------------------------------------- resilience
    def _inject_nan(self, inputs, rnd: int, attempt: int):
        """Fault hook: poison the drawn cohort's input batches with NaN
        per the deterministic stream (no-op without one)."""
        if self.faults is None or inputs is None:
            return inputs
        cohort, xs, ys, mask = inputs
        if not jnp.issubdtype(jnp.asarray(xs).dtype, jnp.inexact):
            return inputs
        live = int((np.asarray(cohort) < self.fed.n_clients).sum())
        slots = self.faults.nan_slots_for(rnd, attempt, live)
        if slots.size == 0:
            return inputs
        xs = self._place(jnp.asarray(xs).at[jnp.asarray(slots)]
                         .set(jnp.nan))
        self.log(f"[resilience] round {rnd} attempt {attempt}: injected "
                 f"NaN features in slots {slots.tolist()}")
        return (cohort, xs, ys, mask)

    def _verdict(self, metrics) -> Optional[str]:
        """Host-read the packed health vector — the ONE sync the guard
        costs per round.  Returns the fault kind or None (healthy)."""
        if not self.cfg.resilience.guard:
            return None
        h = jax.device_get(metrics["health"])
        if h[HEALTH_NONFINITE] > 0:
            return "nonfinite"
        if h[HEALTH_SPIKE] > 0 and self.recovery.spike_armed():
            return "spike"
        return None

    def _recover_round(self, state, inputs, inj0, rnd: int, stage=None,
                       pipelined: bool = False, lag: int = 0):
        """Drive round ``rnd`` to an accepted ``(state, metrics)`` under
        the recovery policy.

        ``inputs`` are the CLEAN sampled round inputs; ``inj0`` the
        attempt-0 fault-injected view of them (identical objects when no
        fault fired).  ``stage`` is the already-dispatched extract for
        ``inj0`` on the pipelined path — recovery attempts re-extract
        from the current candidate state, because the pooled store bakes
        the attendance mask in at extract time.

        Returns ``(state, metrics, attempts, healthy)``; raises
        :class:`ResilienceExhaustedError` past ``max_retries`` and lets
        an injected error escape unhandled only when every fallback
        action is exhausted.
        """
        ctl, rcfg = self.recovery, self.cfg.resilience
        key = self.round_key(rnd)
        cur_state, cur_inputs, cur_inj, cur_stage = state, inputs, inj0, stage
        kinds: list[str] = []
        actions: list[str] = []
        attempt = 0
        while True:
            site = ("extract" if pipelined and cur_stage is None
                    else ("tail" if pipelined else "round"))
            try:
                if self.faults is not None:
                    self.faults.check_dispatch(rnd, attempt, site)
                if pipelined:
                    # a re-extract reads the CURRENT candidate state, so
                    # its realized lag (and staleness weight) resets to 0
                    st, att_lag = cur_stage, lag
                    if st is None:
                        st, att_lag = self._extract(cur_state, cur_inj), 0
                    new_state, metrics = self._tail(cur_state, cur_inj,
                                                    st, key, lag=att_lag)
                else:
                    new_state, metrics = self._round_call(cur_state,
                                                          cur_inj, key)
                kind = self._verdict(metrics)
            except FaultInjectedError as e:
                self.log(f"[resilience] {e}")
                kind, new_state, metrics = "error", None, None
            if kind is None:
                break                      # healthy — accept
            kinds.append(kind)
            if len(kinds) > rcfg.max_retries:
                ctl.record_round(rnd, len(kinds), kinds, actions,
                                 len(ctl.quarantined))
                raise ResilienceExhaustedError(rnd, len(kinds), kinds)
            # resolve the configured action, escalating past the ones
            # that cannot apply (no blamable slot, empty snapshot ring)
            action = ctl.action_for(kind, attempt)
            applied = None
            while applied is None:
                if action == "ignore" and new_state is not None:
                    applied = "ignore"
                elif action == "quarantine":
                    mask = cur_inputs[3]
                    sb = (metrics.get("health_slot_bad")
                          if metrics is not None else None)
                    nm = (ctl.quarantine(np.asarray(cur_inputs[0]),
                                         np.asarray(mask), np.asarray(sb),
                                         rnd=rnd)
                          if mask is not None and sb is not None else None)
                    if nm is not None:
                        placed = self._place(nm)
                        cur_inputs = cur_inputs[:3] + (placed,)
                        cur_inj = cur_inj[:3] + (placed,)
                        applied = "quarantine"
                elif action == "retry":
                    applied = "retry"
                elif action == "rollback":
                    tgt = ctl.rollback()
                    if tgt is not None:
                        _, cur_state, self._ema = tgt
                        applied = "rollback"
                if applied is None:
                    nxt = ctl.escalate(action) if action else None
                    if nxt is None:
                        applied = "retry"  # last resort
                    else:
                        action = nxt
            actions.append(applied)
            if applied == "ignore":
                self.log(f"[resilience] round {rnd}: {kind} ignored "
                         "by policy")
                break
            self.log(f"[resilience] round {rnd}: {kind} -> {applied} "
                     f"(attempt {len(kinds)}/{rcfg.max_retries})")
            ctl.backoff(len(kinds))
            attempt += 1
            cur_stage = None               # stale: mask/state may differ
            cur_inj = self._inject_nan(cur_inputs, rnd, attempt)
        healthy = kind is None
        ctl.record_round(rnd, len(kinds), kinds, actions,
                         len(ctl.quarantined))
        return new_state, metrics, len(kinds), healthy

    # -------------------------------------------------------------- run
    def run(self, state: Optional[TrainState] = None) -> dict:
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed + 1)
        if cfg.resilience.active:
            # fresh controller per run: empty quarantine ledger, empty
            # snapshot ring, EMA at the unarmed sentinel.  Built BEFORE
            # any sampling so resume replays see the same (empty) ledger
            # the original run started with.
            self.recovery = RecoveryController(
                cfg.resilience, self.fed.n_clients,
                min_live=cfg.min_cohort, log=self.log)
            self._ema = jnp.zeros((), jnp.float32)
            self._ckpt_corruptions = 0
        start_round = 0
        if state is None and cfg.resume:
            state, start_round = self.restore(rng)
        if state is None:
            state = self.init_state()
        elif self.state_shardings is not None:
            # caller-provided (or restored) states must sit on the mesh
            # placement the jitted round's out_shardings pin, or round 1
            # would see a different input sharding than round 0 and
            # retrace — no-op when already placed
            state = jax.device_put(state, self.state_shardings)
        tracker = GradStabilityTracker()
        history = []
        round_time, timed_rounds = 0.0, 0
        t0 = time.time()
        prof = self.profiler
        sec = (prof.section if prof is not None
               else (lambda name: _NULL_SECTION))
        # telemetry sync cadence: the host blocks on round metrics only
        # at window boundaries (compile round, every sync_k-th round,
        # the last round) — in between, rounds dispatch back-to-back and
        # stay device-resident.  The resilience guard host-reads the
        # health verdict every round by design, so it pins sync_k to 1.
        sync_k = 1 if cfg.resilience.guard else max(1, cfg.sync_every)
        t_mark, r_mark = t0, start_round
        # ---- pipeline prime: sample the first ``ring_depth`` cohorts IN
        # ROUND ORDER (the rng/cohort stream stays bit-for-bit the
        # sequential one) and put their extractions in flight from the
        # initial state (async dispatches — they do not block the host).
        # Consumed at lags 0..L-1, under the L bound by construction.
        # On resume the restored state re-primes the ring, so every
        # post-resume stage reads the restored (fresh) params, exactly
        # like the uninterrupted run's warm-up rounds.
        pipelined = self.pipeline is not None
        ring_depth = self.ring_depth
        t_tel = len(self._telemetry)     # rows this run will append start here
        ring = StaleFeatureRing(ring_depth) if pipelined else None
        max_lag, cur_lag = 0, 0
        nxt_inputs = None                # non-pipelined double buffer
        if pipelined:
            for i in range(min(ring_depth, cfg.rounds - start_round)):
                p_inputs = self.sample_round(rng)
                # attempt-0 fault injection happens BEFORE the priming
                # extract so a poisoned delivery flows into the stage's
                # features (no-op without a fault stream)
                p_inj = self._inject_nan(p_inputs, start_round + i, 0)
                ring.push(start_round + i, start_round,
                          self._extract(state, p_inj), p_inputs, p_inj)
        # ``between_rounds``: the host path the device waits on when the
        # host blocks on every round, from a round's sync returning to
        # the next round's dispatch returning (tracker, callbacks, eval,
        # the next cohort's pick, its key, the jit call).  It spans the
        # loop boundary, so it is opened and closed by hand; a run that
        # ends inside it (a callback raising) leaves it uncounted.
        between_on = (prof is not None and cfg.collect_timing
                      and sync_k == 1 and not pipelined
                      and self.recovery is None)
        with ExitStack() as between:
            for rnd in range(start_round, cfg.rounds):
                attempts, healthy = 0, True
                if pipelined:
                    # host-side bookkeeping only: round k's stage leaves the
                    # ring before the k+L slot is pushed, so at most L stages
                    # are ever buffered and every consumed lag is <= L
                    entry = ring.pop(rnd)
                    inputs, inj_inputs = entry.inputs, entry.inj_inputs
                    cur_lag = rnd - entry.src_round
                    max_lag = max(max_lag, cur_lag)
                    # prefetch cohort k+L's sampling while round k's compute
                    # is (or is about to be) on the devices
                    with sec("sample"):
                        nxt_inputs = (self.sample_round(rng)
                                      if rnd + ring_depth < cfg.rounds
                                      else None)
                    nxt_inj = (self._inject_nan(nxt_inputs,
                                                rnd + ring_depth, 0)
                               if nxt_inputs is not None else None)
                    t_round = time.time()
                    if nxt_inputs is not None \
                            and cfg.pipeline_staleness == "async":
                        # overlap: extract(k+L) from the PRE-tail state — it
                        # shares no dependency with tail(k)'s outputs, so XLA
                        # can run it on the batch axes while the server inner
                        # loop occupies the model axes.  Clients and the
                        # θ_S^t snapshot are stale by exactly L rounds once
                        # the ring is warm (less during warm-up and rewinds).
                        ring.push(rnd + ring_depth, rnd,
                                  self._extract(state, nxt_inj),
                                  nxt_inputs, nxt_inj)
                    if self.recovery is None:
                        with sec("dispatch"):
                            state, metrics = self._tail(state, inj_inputs,
                                                        entry.stage,
                                                        self.round_key(rnd),
                                                        lag=cur_lag)
                    else:
                        (state, metrics, attempts,
                         healthy) = self._recover_round(
                            state, inputs, inj_inputs, rnd, stage=entry.stage,
                            pipelined=True, lag=cur_lag)
                        if attempts and len(ring):
                            # every in-flight prefetch read a pre-round state
                            # that recovery discarded — re-extract the whole
                            # ring from the accepted state, deterministically
                            # rewinding the schedule (the rewound stages are
                            # fresh: their lags restart from 0)
                            ring.rewind(lambda inj: self._extract(state, inj),
                                        src_round=rnd + 1)
                    if nxt_inputs is not None \
                            and cfg.pipeline_staleness != "async":
                        # sync barrier: extract(k+1) reads the post-Commit
                        # state — bit-for-bit the sequential schedule
                        ring.push(rnd + 1, rnd + 1,
                                  self._extract(state, nxt_inj),
                                  nxt_inputs, nxt_inj)
                else:
                    with sec("sample"):
                        # double buffer: round k-1 already sampled, padded,
                        # and device_put this round's inputs while round
                        # k-1's compute was in flight
                        inputs = (nxt_inputs if nxt_inputs is not None
                                  else self.sample_round(rng))
                        nxt_inputs = None
                    t_round = time.time()
                    if self.recovery is None:
                        with sec("dispatch"):
                            state, metrics = self._round_call(
                                state, inputs, self.round_key(rnd))
                        between.close()
                        if rnd + 1 < cfg.rounds:
                            # prefetch cohort k+1 behind the in-flight round
                            # (device_put is async; nothing here blocks)
                            with sec("sample"):
                                nxt_inputs = self.sample_round(rng)
                    else:
                        # recovery may re-draw quarantine weights mid-round,
                        # so the faulted path samples strictly per round
                        inj = self._inject_nan(inputs, rnd, 0)
                        state, metrics, attempts, healthy = \
                            self._recover_round(state, inputs, inj, rnd)
                if self.recovery is not None and cfg.resilience.guard:
                    # thread the EMA carry forward and snapshot last-good
                    # states — both stay on device (no extra host sync)
                    self._ema = metrics["health"][HEALTH_EMA]
                    if healthy:
                        self.recovery.note_accept(rnd, state, self._ema)
                # telemetry rows are appended at sample time (for pipelined
                # runs that's one round AHEAD of the tail); the θ staleness a
                # round actually saw is only known here, once its tail ran
                ti = t_tel + (rnd - start_round)
                if ti < len(self._telemetry):
                    self._telemetry[ti]["realized_lag"] = (
                        cur_lag if pipelined else 0)
                if cfg.collect_timing:
                    if sync_k == 1:
                        with sec("sync"):
                            jax.block_until_ready(metrics["server_loss"])
                        if between_on and rnd + 1 < cfg.rounds:
                            between.enter_context(sec("between_rounds"))
                        if rnd > start_round:         # skip the compile round
                            round_time += time.time() - t_round
                            timed_rounds += 1
                    elif rnd == start_round:
                        # compile round: sync it out of the first window
                        with sec("sync"):
                            jax.block_until_ready(metrics["server_loss"])
                        t_mark, r_mark = time.time(), rnd + 1
                    elif (rnd == cfg.rounds - 1
                          or (rnd + 1 - start_round) % sync_k == 0):
                        # window boundary: one sync covers the whole window,
                        # timing averages over its rounds
                        with sec("sync"):
                            jax.block_until_ready(metrics["server_loss"])
                        round_time += time.time() - t_mark
                        timed_rounds += rnd + 1 - r_mark
                        t_mark, r_mark = time.time(), rnd + 1
                tracker.update(metrics)
                self._emit("on_round", rnd, state, metrics)
                if (rnd + 1) % cfg.eval_every == 0 or rnd == cfg.rounds - 1:
                    with sec("eval"):
                        loss, mets = evaluate(self.task, state, self.fed)
                    history.append({
                        "round": rnd + 1, "test_loss": loss, **mets,
                        "train_loss": float(metrics["server_loss"]),
                        "elapsed_s": round(time.time() - t0, 1)})
                    self.log(f"[{self.algo.name}] round {rnd+1:4d} "
                             f"test_loss={loss:.4f} "
                             f"{self.metric_key}="
                             f"{mets.get(self.metric_key, float('nan')):.4f}")
                    if cfg.ckpt_dir:
                        meta = {"algo": self.algo.name}
                        if self.recovery is not None:
                            # persist the recovery carry a resumed run must
                            # not forget: the quarantine ledger (+ replayable
                            # event history) and the spike-EMA scalar
                            # (fp32 -> python float -> fp32 is exact)
                            meta["resilience"] = {
                                **self.recovery.export_state(),
                                "ema": float(jax.device_get(self._ema)),
                            }
                        save_checkpoint(cfg.ckpt_dir, rnd + 1, state,
                                        metadata=meta)
                        if self.faults is not None \
                                and self.faults.ckpt_corrupt(rnd + 1):
                            # tear the just-written step: restore must fall
                            # back past it to the newest valid one
                            self.faults.corrupt_checkpoint(cfg.ckpt_dir,
                                                           rnd + 1)
                            self._ckpt_corruptions += 1
                            self.log(f"[resilience] injected torn checkpoint "
                                     f"at step {rnd + 1}")
                    self._emit("on_eval", rnd, loss, mets)
        result = {"algo": self.algo.name, "task": cfg.task,
                  "history": history, "grad_stability": tracker.summary()}
        tel = self._telemetry[t_tel:]
        if tel:
            result["telemetry"] = {
                "per_round": tel,
                "live_cohort_mean": float(np.mean([r["live"] for r in tel])),
                "dropped_total": int(sum(r["dropped"] for r in tel)),
                "drop_hazard_total": int(sum(r["drop_hazard"] for r in tel)),
                "drop_deadline_total": int(sum(r["drop_deadline"]
                                               for r in tel)),
                "max_realized_lag": max(r["realized_lag"] for r in tel),
                "max_drawn_lag": max(r["lag_drawn_max"] for r in tel),
            }
        if self.recovery is not None:
            summary = self.recovery.summary()
            summary["ckpt_corruptions"] = self._ckpt_corruptions
            result["resilience"] = summary
        if start_round:
            result["resumed_from_round"] = start_round
        if cfg.collect_timing:
            result["round_time_s"] = round_time / max(1, timed_rounds)
        if cfg.pipeline_depth > 0:
            self.pipeline_stats = {
                "active": pipelined if cfg.rounds > start_round else False,
                "mode": cfg.pipeline_staleness,
                "depth": cfg.pipeline_depth,
                "ring_depth": ring_depth,
                "staleness_weighting": cfg.staleness_weighting,
                "max_theta_s_lag_rounds": max_lag if pipelined else 0,
                "realized_lags": (list(ring.realized_lags)
                                  if ring is not None else []),
                "extract_traces": (self.pipeline.extract_traces
                                   if pipelined else 0),
                "tail_traces": (self.pipeline.tail_traces
                                if pipelined else 0),
            }
            result["pipeline"] = self.pipeline_stats
        if prof is not None:
            result["profile"] = prof.summary()
        return result
