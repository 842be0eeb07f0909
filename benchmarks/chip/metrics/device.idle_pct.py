"""device.idle_pct: the share of the traced window in which no op ran on
the device, averaged over the chips used."""
from chipbench import trace as tr


def read(ctx):
    busy = tr.busy_ns(ctx.trace)
    if not busy:
        return None
    lo, hi = ctx.trace.window
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (hi - lo))
