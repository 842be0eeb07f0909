"""The traced rounds' device time by program phase.

The program runs each phase of a round under a ``jax.named_scope`` of
the phase's class name, so the phase is a component of the HLO
``op_name`` of every op it emits (``jit(round_impl)/ServerUpdate/while/
body/...``).  An op event of the trace names only its HLO instruction
(``%fusion.12 = ...``).  The trace keeps the HLO of every module it ran
in its ``/host:metadata`` plane (an ``Hlo Proto`` stat per module), and
each device's ``XLA Modules`` line says which module ran when; together
they give each op its ``op_name`` (:func:`scoped`).

A phase's time is the union of the intervals of its ops inside the
traced window, per chip: the union, because the inner loop's ``while``
op spans the ops of its body.  Busy time in which no phase's op runs
(entry copies, the separate ``PRNGKey`` executable) is unattributed.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

from chipbench import trace as tr

PHASES = ("ExtractFeatures", "ServerUpdate", "FeatureGradients",
          "ClientUpdate", "Commit")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"


def phase_of(op_name: str):
    """The first component of the scope path that names a phase, or
    None."""
    return next((p for p in op_name.split("/") if p in PHASES), None)


# ----------------------------------------------- the trace's own protos
def _fields(buf):
    """(field number, value) of a serialized protobuf message: an int for
    a varint, a memoryview for a length-delimited or fixed field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        x = shift = 0
        while True:
            c = buf[i]
            i += 1
            x |= (c & 0x7F) << shift
            shift += 7
            if c < 0x80:
                return x

    while i < n:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            yield number, varint()
        elif wire == 2:
            size = varint()
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield number, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _all(buf, number: int) -> list:
    return [v for f, v in _fields(buf) if f == number]


def _first(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def _text(buf, number: int) -> str:
    return bytes(_first(buf, number, b"")).decode()


def module_op_names(xspace: bytes) -> dict:
    """{module (as the ``XLA Modules`` line names it, ``name(id)``):
    {HLO instruction name: op_name}} from the metadata plane.

    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4,
    .stat_metadata 5 (map entries: value 2); XEventMetadata.name 2,
    .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1,
    .bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations
    3; HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out = {}
    for plane in _all(xspace, 1):
        if _text(plane, 2) != METADATA_PLANE:
            continue
        proto_ids = {_first(meta, 1, 0) for entry in _all(plane, 5)
                     for meta in _all(entry, 2)
                     if _text(meta, 2) == HLO_PROTO}
        for entry in _all(plane, 4):
            meta = _first(entry, 2, b"")
            for stat in _all(meta, 5):
                if _first(stat, 1, 0) not in proto_ids:
                    continue
                names = {}
                module = _first(_first(stat, 6, b""), 1, b"")
                for comp in _all(module, 3):
                    for ins in _all(comp, 2):
                        names[_text(ins, 1)] = _text(_first(ins, 7, b""), 2)
                out[_text(meta, 2)] = names
    return out


def trace_file(cell_name: str) -> Path:
    """The newest trace the harness wrote for the cell."""
    from chipbench.harness import CHECKOUT
    files = sorted((CHECKOUT / ".chipbench" / "trace" / cell_name)
                   .rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no trace of {cell_name!r}")
    return files[-1]


def with_op_names(trace: tr.Trace, path: Path) -> tr.Trace:
    """``trace`` with each device op's ``op_name`` in its stats ("" when
    its module's HLO is not in the trace)."""
    from jax.profiler import ProfileData
    names = module_op_names(Path(path).read_bytes())
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name in trace.devices:
            spans[plane.name] = sorted(
                (e.start_ns, e.end_ns, e.name) for line in plane.lines
                if line.name == MODULES_LINE for e in line.events)
    devices = {}
    for dev, ops in trace.devices.items():
        mods = spans.get(dev, [])
        starts = [s for s, _, _ in mods]
        out = []
        for e in ops:
            k = bisect.bisect_right(starts, e.start_ns) - 1
            mod = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] else ""
            ins = e.name.partition(" = ")[0].lstrip("%")
            out.append(tr.Event(e.name, e.start_ns, e.end_ns,
                                {**e.stats, "op_name":
                                 names.get(mod, {}).get(ins, "")}))
        devices[dev] = out
    return tr.Trace(devices, trace.host, trace.window)


_last = [None, None]      # the last trace scoped, and its scoped copy


def scoped(ctx) -> tr.Trace:
    """The run's trace with an ``op_name`` on every device op: as the
    ops carry it, or read from the trace file (once for the readers of
    one run)."""
    t = ctx.trace
    if all("op_name" in e.stats for ops in t.devices.values() for e in ops):
        return t
    if _last[0] is not t:
        _last[:] = [t, with_op_names(t, trace_file(ctx.cell.name))]
    return _last[1]


# ----------------------------------------------------------- the metric
def phase_ns(trace: tr.Trace) -> dict:
    """{device: {phase: ns in which an op of it ran, inside the window;
    None: busy ns in which no phase's op ran}}.  An op with no phase
    inside a phase's op (a copy in the inner loop's body, inside the
    ``while``) adds nothing to the unattributed time."""
    lo, hi = trace.window
    ns = lambda evs: sum(e - s for s, e in tr.union(evs, lo, hi))
    out = {}
    for dev, ops in trace.devices.items():
        by = defaultdict(list)
        for e in ops:
            by[phase_of(e.stats.get("op_name", ""))].append(e)
        named = [e for p, evs in by.items() if p is not None for e in evs]
        out[dev] = {p: ns(evs) for p, evs in by.items() if p is not None}
        out[dev][None] = ns(ops) - ns(named)
    return out


def phase_ms_per_round(ctx, phase: str):
    """Device ms a traced round spends in ``phase``, mean over the
    chips; None without traced rounds or where no op names the phase
    (a program without phase scopes)."""
    if not ctx.traced_rounds:
        return None
    per_dev = phase_ns(scoped(ctx))
    if not any(phase in d for d in per_dev.values()):
        return None
    ns = sum(d.get(phase, 0.0) for d in per_dev.values()) / len(per_dev)
    return ns / ctx.traced_rounds * 1e-6
