"""host.dispatch_ms: host time in the Engine's ``dispatch`` section (the
asynchronous round call), per round of the window."""


def read(ctx):
    if not ctx.rounds or "dispatch" not in ctx.sections:
        return None
    return ctx.sections["dispatch"] / ctx.rounds * 1e3
