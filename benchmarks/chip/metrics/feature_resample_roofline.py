"""feature_resample_roofline: the Pallas resample gather's share of its
HBM roofline.  Useful bytes are the rows the server's inner loop
gathers (features and label), read once and written once; the kernel
reads 8 source rows per row gathered, so it reads low by design.  The
kernel's calls carry no name of their own in the trace: they are the
custom calls whose first operand is the int32 row index (scalar
prefetch) and whose next operand is the pooled source."""
from chipbench import trace as tr

MATCH = tr.kernel(("feature_resample",),
                  operands=r"^\S+ custom-call\(s32\[\d+\]\{[^}]*\} %[\w.-]+, "
                           r"(f32|s32)\[\d+,\d+\]")


def read(ctx):
    if not ctx.work["feature_resample_bytes"]:
        return None
    return tr.roofline_pct(ctx, MATCH, ctx.work["feature_resample_bytes"])
