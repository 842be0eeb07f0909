"""host.sample_ms: host time in the Engine's ``sample`` section (cohort
draw, stacking, device_put), per round of the window."""


def read(ctx):
    if not ctx.rounds or "sample" not in ctx.sections:
        return None
    return ctx.sections["sample"] / ctx.rounds * 1e3
