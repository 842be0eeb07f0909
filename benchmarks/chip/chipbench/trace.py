"""Reduction of a profiler trace to what the per-layer metrics read.

A trace is reduced to plain records first (:func:`load`), so that the
arithmetic below runs on a hand-built trace in the tests as it does on
one from the chip:

  ``devices``  {device plane name: [Event]}: the ops of each TPU
  ``host``     [Event]: the host spans (``TraceAnnotation``) whose
               names the caller asks for
  ``window``   (start_ns, end_ns) of the span named ``window``

Busy time is the union of a device's op intervals inside the window;
idle is the rest of the window.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

OPS_LINE = "XLA Ops"
OPCODE = re.compile(r"\s([a-z][\w.-]*)\(")


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class Trace:
    devices: dict
    host: list
    window: tuple


def load(path: Path, host_names: set, window_name: str = "window") -> Trace:
    """Read the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(str(files[-1]))
    devices, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            ops = [Event(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_name:
                        window = (e.start_ns, e.end_ns)
                    elif e.name in host_names:
                        host.append(Event(e.name, e.start_ns, e.end_ns))
    if window is None:
        raise ValueError(f"no host span named {window_name!r} in the trace")
    return Trace(devices, host, window)


def union(events, lo: float, hi: float) -> list:
    """Merged [start, end] intervals of ``events`` clipped to [lo, hi]."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                   if e.end_ns > lo and e.start_ns < hi)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(trace: Trace) -> dict:
    """{device: ns in which some op ran, inside the window}."""
    lo, hi = trace.window
    return {d: sum(e - s for s, e in union(ops, lo, hi))
            for d, ops in trace.devices.items()}


def in_window(trace: Trace, ops) -> list:
    lo, hi = trace.window
    return [e for e in ops if e.start_ns >= lo and e.end_ns <= hi]


def op_ns(trace: Trace, match) -> dict:
    """{device: summed duration of the window's ops for which
    ``match(event)`` holds}."""
    return {d: sum(e.dur_ns for e in in_window(trace, ops) if match(e))
            for d, ops in trace.devices.items()}


def short(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...), ...`` -> ``fusion.12 fusion``:
    the HLO instruction's name and its opcode."""
    lhs, _, rhs = name.partition(" = ")
    op = OPCODE.search(rhs)
    return f"{lhs.lstrip('%')} {op.group(1)}" if op else lhs.lstrip("%")


def top_ops(trace: Trace, k: int = 10) -> list:
    """[[op name, seconds]] of the ``k`` ops that took most time on the
    first device, summed over their calls."""
    first = sorted(trace.devices)[0]
    tot: dict = {}
    for e in in_window(trace, trace.devices[first]):
        n = short(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur_ns
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[[host span open the longest during the gap, seconds]] for the
    ``k`` longest idle gaps of the first device inside the window."""
    lo, hi = trace.window
    first = sorted(trace.devices)[0]
    busy = union(trace.devices[first], lo, hi)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        best, label = 0.0, "none"
        for h in trace.host:
            ov = min(e, h.end_ns) - max(s, h.start_ns)
            if ov > best:
                best, label = ov, h.name
        out.append([label, (e - s) * 1e-9])
    return out


def kernel(names: tuple, operands: str = None):
    """A matcher of Pallas kernels (``tpu_custom_call``): by the HLO
    instruction's name, which holds one of ``names``, or, where
    ``operands`` is given, by a regex on the call's operand list."""
    pat = re.compile(operands) if operands else None

    def match(e: Event) -> bool:
        if 'custom_call_target="tpu_custom_call"' not in e.name:
            return False
        lhs, _, rhs = e.name.partition(" = ")
        if any(n in lhs for n in names):
            return True
        return bool(pat and pat.search(rhs))
    return match


def roofline_pct(ctx, match, useful_bytes: float):
    """Useful bytes of the traced rounds over the chip's HBM bandwidth,
    as a share of the matched ops' device time (mean over the chips);
    None where no op matched."""
    t = op_ns(ctx.trace, match)
    ns = sum(t.values()) / len(t) if t else 0.0
    if ns <= 0 or not ctx.traced_rounds:
        return None
    least = useful_bytes * ctx.traced_rounds / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (ns * 1e-9)
