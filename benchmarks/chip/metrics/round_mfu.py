"""round_mfu: the round's model FLOPs (the benchmark's counter, no
recomputed forward) times the rounds of the window, over the window's
host-clock seconds and the chips' bf16 peak: the same rounds and
seconds as ``samples_per_s``.  The bf16 peak is the ceiling at either
precision: the MXU takes float32 contractions as bfloat16 passes."""


def read(ctx):
    if not ctx.rounds:
        return None
    return (100.0 * ctx.work["flops"] * ctx.rounds / ctx.window_s
            / (ctx.chips * ctx.peak["bf16_flops_per_s"]))
