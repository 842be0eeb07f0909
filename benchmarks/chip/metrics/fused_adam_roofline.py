"""fused_adam_roofline: the Pallas fused Adam's share of its HBM
roofline.  Useful bytes are 7 x 4 B per element stepped (p, g, m, v
read; p, m, v written), without the kernel's padding, for every server
step and every client step of a round; the time is the device time of
the kernel's calls in the trace (``%fused_adam.N`` custom calls)."""
from chipbench import trace as tr

MATCH = tr.kernel(("fused_adam",))


def read(ctx):
    return tr.roofline_pct(ctx, MATCH, ctx.work["fused_adam_bytes"])
