"""One run of one cell: build it from its files, time a window of
``Engine.run()``, read the per-layer metrics, and decide ``correct``.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its files,
found by name under ``benchmarks/chip``:

  configs/<config>.json    the model, its widths and its population
  configs/<reference>.py   the model's plain reference (named by the config)
                           and everything else that is the model's: the
                           weights, their split at the cut, the loss, the
                           population and the program's task (``INTERFACE``)
  traffic/<traffic>.json   algorithm, cut, attendance, batch, mesh, and the
                           round semantics the reference follows
  limits/<cell>.json       the limit of each number that decides ``correct``
  metrics/<metric>.py      one reader per per-layer metric

The window drives ``Engine.run()`` itself.  Its first ``WARMUP`` rounds
are set-up: the first compiles, and the three of them are the steps the
reference follows.  The rounds after them are timed from the Engine's
``on_round`` callback until ``seconds`` have passed.  With ``--trace 0``
the callback then ends the run; with ``--trace 1`` the same window runs
untraced (the host-clock and span metrics read it), and ``TRACE_ROUNDS``
more rounds follow under the profiler (the trace metrics read those).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import statistics
import sys
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parents[1]          # benchmarks/chip
CHECKOUT = BENCH.parents[1]
WARMUP = 3            # set-up rounds; the reference follows all three
TRACE_ROUNDS = 20     # rounds a --trace 1 run traces, after the window
GIB = float(1 << 30)
# what every configuration's reference module provides (PERF.md §4)
INTERFACE = ("init", "split", "client_forward", "server_forward", "loss",
             "population", "sample_spec", "program_task", "tiny")


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    model: object             # the configuration's reference module
    end_to_end: list
    per_layer: list           # BENCHMARK.json entries this cell reports


def load_cell(name: str, checkout: Path = CHECKOUT,
              bench: Path = BENCH) -> Cell:
    manifest = read_json(checkout / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}: {sorted(cells)}")
    w = cells[name]
    configs = bench / "configs"
    config = read_json(configs / f"{w['config']}.json")
    # a reference module may import its siblings under configs/
    if str(configs) not in sys.path:
        sys.path.append(str(configs))
    model = load_module(configs / f"{config['reference']}.py",
                        f"chipref_{config['reference']}")
    missing = [f for f in INTERFACE if not callable(getattr(model, f, None))]
    if missing:
        raise AttributeError(f"configs/{config['reference']}.py lacks "
                             f"{missing}")
    mine = lambda m: "workloads" not in m or name in m["workloads"]
    return Cell(
        name, w["chips"], config,
        read_json(bench / "traffic" / f"{w['traffic']}.json"),
        read_json(bench / "limits" / f"{name}.json"), model,
        [m for m in manifest["end_to_end"] if mine(m)],
        [m for m in manifest["per_layer"] if mine(m)])


def check_devices(chips: int) -> str:
    """Return the device kind, or raise when JAX finds no TPU or fewer
    chips than the cell needs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"needs {chips} chips; JAX found {len(devs)}")
    return devs[0].device_kind


def peaks(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}: {sorted(table)}")
    return table[kind]


def enable_compile_cache(checkout: Path = CHECKOUT) -> None:
    """JAX's persistent cache, at a fixed path inside the checkout, for
    every program however small."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(checkout / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def key_from_seed(seed: int):
    import jax
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                 seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


class Sections:
    """The Engine's profiler: host time per named section, each also a
    ``TraceAnnotation`` so that it lands in the device trace's clock."""

    def __init__(self):
        self.total_s = defaultdict(float)

    @contextmanager
    def section(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.total_s[name] += time.perf_counter() - t0

    def summary(self) -> dict:
        return dict(self.total_s)


class WindowClosed(Exception):
    """Raised from ``on_round`` to end ``Engine.run()`` when the window
    has lasted its seconds."""


# ------------------------------------------------------------ the cell
def federated(x: np.ndarray, y: np.ndarray, n_test: int):
    """The program's ``FederatedDataset`` over views of ``x``/``y``
    (``[N, n, ...]``): each client's last ``n_test`` samples are its
    test split."""
    from repro.data.federated import ClientData, FederatedDataset
    n_train = x.shape[1] - n_test
    return FederatedDataset([
        ClientData(x[i, :n_train], y[i, :n_train], x[i, n_train:],
                   y[i, n_train:]) for i in range(x.shape[0])])


def experiment(cell: Cell, seed: int):
    """The program's task, data and config for ``cell``."""
    from repro.api import ExperimentConfig
    from repro.core.cyclesl import CycleConfig

    c, t = cell.config, cell.traffic
    x, y = cell.model.population(c, seed)
    fed = federated(x, y, c["population"]["test_per_client"])
    task = cell.model.program_task(c, t)
    # the config's default ``task`` only has to name a registered task:
    # the Engine is handed the benchmark's own task and data
    cfg = ExperimentConfig(
        algo=t["algo"], rounds=10 ** 9,
        n_clients=fed.n_clients, attendance=t["attendance"],
        batch=t["batch"], lr_server=t["lr_server"], lr_client=t["lr_client"],
        seed=seed % (1 << 31), cut=t["cut"], eval_every=10 ** 9,
        collect_timing=True, sync_every=1,
        mesh_shape=tuple(t["mesh_shape"]) if t.get("mesh_shape") else None,
        shard_cohort=t.get("shard_cohort", True),
        cycle=CycleConfig(server_epochs=t["server_epochs"],
                          server_batch=t["server_batch"],
                          shard_local_resample=t.get("shard_local_resample",
                                                     False)))
    return task, fed, cfg


def initial_state(eng, cell: Cell, seed: int):
    """The program's TrainState with the benchmark's own weights, made
    on the device in one jitted call, and a copy of those weights."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(eng.init_state)

    def make(key):
        theta = cell.model.init(key, cell.config)
        client, server = cell.model.split(theta, cell.traffic["cut"])

        def fill(ent, params, n=None):
            if n is not None:
                params = jax.tree.map(
                    lambda p: jnp.broadcast_to(p, (n,) + p.shape), params)
            shape = lambda t: jax.tree.map(lambda a: a.shape, t)
            if shape(params) != shape(ent.params):
                raise ValueError("the reference's parameter layout differs "
                                 "from the program's")
            return ent._replace(
                params=params,
                opt_state=jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                       ent.opt_state),
                step=jnp.zeros(ent.step.shape, ent.step.dtype))

        state = shapes._replace(server=fill(shapes.server, server))
        if shapes.clients is not None:
            n = jax.tree.leaves(shapes.clients.params)[0].shape[0]
            state = state._replace(clients=fill(shapes.clients, client, n))
        else:
            state = state._replace(client_global=fill(shapes.client_global,
                                                      client))
        return state, theta

    out = None
    if eng.state_shardings is not None:
        out = (eng.state_shardings, None)
    return jax.jit(make, out_shardings=out)(key_from_seed(seed))


def _clients(state):
    return state.clients if state.clients is not None else state.client_global


def program_norms(cell: Cell):
    """jitted per-leaf norms of the program's state: ``m1(state)``
    (Adam's first moment) and ``change(state, theta0)`` (params minus
    the initial weights)."""
    import jax

    from chipbench.reference import leaf_norms

    def m1(state):
        return (leaf_norms(state.server.opt_state["m"]),
                leaf_norms(_clients(state).opt_state["m"]))

    def change(state, theta0):
        client0, server0 = cell.model.split(theta0, cell.traffic["cut"])
        sub = lambda a, b: jax.tree.map(lambda x, y: x - y, a, b)
        return (leaf_norms(sub(state.server.params, server0)),
                leaf_norms(sub(_clients(state).params, client0)))

    return jax.jit(m1), jax.jit(change)


class Window:
    """The Engine callback that drives set-up, the timed window and the
    traced rounds after it."""

    def __init__(self, cell: Cell, seconds: float, trace_dir, t_start,
                 sections: Sections):
        self.cell, self.seconds, self.trace_dir = cell, seconds, trace_dir
        self.t_start, self.sections = t_start, sections
        self.theta0 = None                # the initial weights, set by run()
        self.losses, self.norms = [], {}
        self.times, self.failed = [], 0
        self.t0 = self.t_prev = self.t_end = self.setup_s = None
        self.window_sections = {}         # host sections of the window
        self.compiles = 0
        self.gc_s, self._gc_t = [], None  # the window's collections
        self.gc_setup_s = 0.0
        self.span, self.traced = None, 0
        self._m1, self._change = program_norms(cell)

    def on_compile(self, event: str, *a, **k):
        if self.t0 is not None and self.t_end is None and "compile" in event:
            self.compiles += 1

    def on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None and self.t0 is not None \
                and self.t_end is None:
            self.gc_s.append(time.perf_counter() - self._gc_t)

    def on_round(self, engine, rnd, state, metrics):
        import jax
        now = time.perf_counter()
        loss = float(metrics["server_loss"])
        if rnd < WARMUP:
            self.losses.append(loss)
            if rnd == 0:
                self.norms["m1"] = jax.device_get(self._m1(state))
            if rnd == WARMUP - 1:
                self.norms["change"] = jax.device_get(self._change(
                    state, self.theta0))
                self._open_window()
            return
        self.failed += not math.isfinite(loss)
        if self.t_end is not None:        # a traced round after the window
            self.traced += 1
            if self.traced == TRACE_ROUNDS:
                self._close_trace()
                raise WindowClosed
            return
        self.times.append(now - self.t_prev)
        self.t_prev = now
        if now - self.t0 >= self.seconds:
            self.t_end = now
            self.window_sections = self.sections.summary()
            if self.trace_dir is None:
                raise WindowClosed
            self._open_trace()

    def _open_window(self):
        # what set-up made lives on: no collection walks it in the window
        t = time.perf_counter()
        gc.collect()
        gc.freeze()
        self.gc_setup_s = time.perf_counter() - t
        self.sections.total_s.clear()
        self.t0 = self.t_prev = time.perf_counter()
        self.setup_s = self.t0 - self.t_start

    def _open_trace(self):
        import jax
        # no Python tracer: it slows the Engine's host loop, which
        # would show as device idle time that untraced runs lack
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("window")
        self.span.__enter__()

    def _close_trace(self):
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


class Recorder:
    """Keeps what ``Engine.sample_round`` hands the first rounds."""

    def __init__(self, eng, rounds: int):
        self.inputs, self._sample, self._rounds = [], eng.sample_round, rounds
        eng.sample_round = self

    def __call__(self, rng):
        out = self._sample(rng)
        if len(self.inputs) < self._rounds:
            self.inputs.append(out)
        return out

    def host_rounds(self, eng) -> list:
        """Each recorded round's live clients: ids, batches and key."""
        out = []
        for r, (cohort, xs, ys, mask) in enumerate(self.inputs):
            live = (np.ones(len(cohort), bool) if mask is None
                    else np.asarray(mask) > 0)
            out.append({"cohort": np.asarray(cohort)[live],
                        "xs": np.asarray(xs)[live],
                        "ys": np.asarray(ys)[live],
                        "key": eng.round_key(r)})
        return out


# ----------------------------------------------------------- comparing
def gaps(prog: dict, ref: dict, floor_of=None) -> dict:
    """{leaf: |norm_prog - norm_ref| / max(norm_ref, median ref norm)}."""
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in ref}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``.

    ``loss``    the widest relative gap of a round's server loss
    ``m1``      the median leaf's gap of the norm of Adam's first moment
                after round 1 (for a client leaf, (1 - b1) x its first
                gradient); the worst leaf is a batch-norm leaf whose
                gap under bfloat16 passes is its own rounding (PERF.md)
    ``change``  the worst leaf's gap of the norm of the parameters'
                change after the last round, over the leaves whose
                reference first moment is at least a thousandth of the
                median leaf's (a leaf below that, like a conv bias under
                batch-norm, moves under Adam by round-off alone)
    """
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    med = statistics.median(ref["m1"].values())
    kept = {k for k, v in ref["m1"].items() if v >= 1e-3 * med}
    g_m1 = gaps(prog["m1"], ref["m1"])
    g_ch = gaps({k: prog["change"][k] for k in kept},
                {k: ref["change"][k] for k in kept})
    return {"loss": loss, "m1": statistics.median(g_m1.values()),
            "change": max(g_ch.values()),
            "worst_leaf": {"m1": max(g_m1, key=g_m1.get),
                           "change": max(g_ch, key=g_ch.get)},
            "left_out_of_change": sorted(set(ref["m1"]) - kept),
            "detail": {"loss": [abs(p - r) / abs(r) for p, r in
                                zip(prog["loss"], ref["loss"])],
                       "m1": g_m1, "change": g_ch}}


def program_readings(window: Window) -> dict:
    named = lambda prefix, d: {f"{prefix}{k}": float(v) for k, v in d.items()}
    out = {"loss": window.losses}
    for kind in ("m1", "change"):
        s, c = window.norms[kind]
        out[kind] = {**named("server", s), **named("client", c)}
    return out


def control_kwargs(cell: Cell) -> dict:
    """The control's arithmetic: the precision below the configuration's.
    float32 at ``highest`` -> ``high`` (three bfloat16 passes); float32
    at the default precision -> bfloat16 throughout."""
    import jax
    import jax.numpy as jnp
    if cell.config["matmul_precision"] == "highest":
        return {"dtype": jnp.float32, "precision": jax.lax.Precision.HIGH}
    return {"dtype": jnp.bfloat16, "precision": jax.lax.Precision.DEFAULT}


def reference_readings(cell: Cell, theta0, rounds, **kw) -> dict:
    import jax

    from chipbench import reference
    theta0 = jax.device_get(theta0)
    return reference.run(cell.model, cell.config, cell.traffic, theta0,
                         rounds, **kw)


# ------------------------------------------------------------- metrics
def forward_halves(cell: Cell) -> dict:
    """From shapes alone: the client's and the server's parameters
    (``client``, ``server``), one sample and its label (``x``, ``y``),
    its smashed features (``f``), and the forward FLOPs of the sample
    through each half (``client_flops``, ``server_flops``)."""
    import jax
    import jax.numpy as jnp

    from chipbench.flops import forward_flops
    c, cut, m = cell.config, cell.traffic["cut"], cell.model
    prec = jax.lax.Precision.DEFAULT
    cp, sp = jax.eval_shape(
        lambda: m.split(m.init(jax.random.PRNGKey(0), c), cut))
    xs, ys = m.sample_spec(c)
    x = jax.ShapeDtypeStruct((1, *xs.shape), xs.dtype)
    cf = lambda cp, x: m.client_forward(cp, x, c, cut, prec, jnp.float32)
    f = jax.eval_shape(cf, cp, x)
    sf = lambda sp, f: m.server_forward(sp, f, c, cut, prec)
    return {"client": cp, "server": sp, "x": x, "y": ys, "f": f,
            "client_flops": forward_flops(cf, cp, x),
            "server_flops": forward_flops(sf, sp, f)}


def round_work(cell: Cell) -> dict:
    """Per-round counts from the cell's shapes: live samples, the
    model FLOPs, and the kernels' useful bytes."""
    import jax

    from chipbench.flops import round_flops
    c, t = cell.config, cell.traffic
    live = round(t["attendance"] * c["population"]["n_clients"])
    rows = live * t["batch"]
    h = forward_halves(cell)
    mode = t["server_mode"]
    if mode == "cycle":
        steps = rows // t["server_batch"] * t["server_epochs"]
        stepped = steps * t["server_batch"]
    else:
        steps, stepped = 1, 0
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    nbytes = lambda a: math.prod(a.shape) * a.dtype.itemsize
    row_bytes = nbytes(h["f"]) + nbytes(h["y"])   # features and label
    return {
        "live": live, "rows": rows,
        "flops": round_flops(h["client_flops"], h["server_flops"], mode,
                             rows, stepped),
        "fused_adam_bytes": 7 * 4 * (size(h["server"]) * steps
                                     + size(h["client"]) * live),
        "feature_resample_bytes": (2 * stepped * row_bytes
                                   if mode == "cycle" else 0),
    }


def percentile(values, q: int) -> float:
    """The q-th percentile (``statistics.quantiles``, inclusive)."""
    if len(values) < 2:
        return values[0] if values else math.nan
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def per_layer(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"chipmetric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ----------------------------------------------------------------- run
def log_stderr(*a):
    print(*a, file=sys.stderr, flush=True)


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, log=log_stderr):
    """Set-up, the window and the metrics of one run.  Returns the
    result line without ``correct``, the program's readings, the
    recorded rounds and the initial weights; the program's state is
    freed."""
    import jax

    from repro.api import Engine

    task, fed, cfg = experiment(cell, seed)
    sections = Sections()
    trace_dir = None
    if trace:
        trace_dir = CHECKOUT / ".chipbench" / "trace" / cell.name
        if trace_dir.exists():
            import shutil
            shutil.rmtree(trace_dir)
    window = Window(cell, seconds, trace_dir, t_start, sections)
    eng = Engine(cfg, task=task, fed=fed, profiler=sections,
                 callbacks=(window,), log=lambda *a, **k: log(*a))
    state, theta0 = initial_state(eng, cell, seed)
    window.theta0 = theta0
    rec = Recorder(eng, WARMUP)
    jax.monitoring.register_event_duration_secs_listener(window.on_compile)
    gc.callbacks.append(window.on_gc)
    try:
        with jax.default_matmul_precision(cell.config["matmul_precision"]):
            eng.run(state=state)
    except WindowClosed:
        pass
    finally:
        gc.callbacks.remove(window.on_gc)
        gc.unfreeze()
    del state
    rounds = len(window.times)
    window_s = window.t_end - window.t0
    work = round_work(cell)
    device = device_info(cell.chips)
    log(f"[chipbench] compilations inside the window: {window.compiles}")
    log(f"[chipbench] garbage collections inside the window: "
        f"{len(window.gc_s)}, {sum(window.gc_s):.4f} s, longest "
        f"{max(window.gc_s, default=0.0):.4f} s; before it, one of "
        f"{window.gc_setup_s:.4f} s")
    result = {"correct": False, "attempted": rounds + window.traced,
              "failed": window.failed, "metrics": {}, "device": device}
    if trace:
        from chipbench import trace as tr
        t = tr.load(trace_dir, {"sample", "dispatch", "sync", "eval"})
        busy = tr.busy_ns(t)
        lo, hi = t.window
        device["busy_s"] = float(np.mean(list(busy.values()))) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        ctx = SimpleNamespace(
            cell=cell, rounds=rounds, window_s=window_s,
            sections=window.window_sections, trace=t,
            traced_rounds=window.traced, work=work,
            peak=peaks(device["kind"]), chips=cell.chips)
        result["metrics"] = per_layer(cell, ctx)
        result["breakdown"] = {"device_ops": tr.top_ops(t),
                               "idle_gaps": tr.idle_gaps(t)}
    else:
        e2e = {
            "samples_per_s": rounds * work["rows"] / window_s,
            "round_ms_p90": percentile(window.times, 90) * 1e3,
            "peak_hbm_gib": device["memory_peak_bytes"] / GIB,
            "setup_s": window.setup_s,
        }
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    if window.times:
        slow = int(np.argmax(window.times))
        log(f"[chipbench] {rounds} rounds in {window_s:.3f} s; "
            f"set-up {window.setup_s:.3f} s; round ms median "
            f"{statistics.median(window.times) * 1e3:.3f} max "
            f"{window.times[slow] * 1e3:.3f} (round {slow} of the window)")
    prog = program_readings(window)
    host_rounds = rec.host_rounds(eng)
    del eng, rec, window
    gc.collect()
    return result, prog, host_rounds, theta0


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        log=log_stderr) -> dict:
    """One run of ``cell``; returns the result line's object."""
    result, prog, host_rounds, theta0 = measure(cell, seed, seconds, trace,
                                                t_start, log)
    ref = reference_readings(cell, theta0, host_rounds)
    got = compare(prog, ref)
    checks = {k: {"value": got[k], "limit": cell.limits[k]}
              for k in ("loss", "m1", "change")}
    log(f"[chipbench] program loss {prog['loss']} reference {ref['loss']}")
    log(f"[chipbench] worst leaves {got['worst_leaf']}; left out of change "
        f"{got['left_out_of_change']}")
    result["correct"] = (result["attempted"] > 0 and result["failed"] == 0
                         and all(
        c["value"] <= c["limit"] for c in checks.values()))
    result["checks"] = checks
    return result
