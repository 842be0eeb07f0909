"""host.between_rounds_ms: host time in the Engine's ``between_rounds``
section (from one round's sync returning to the next round's dispatch
returning), per round of the window.

The harness opens its window inside the section that follows the last
set-up round (its set-up work runs in that round's ``on_round``), so
the section's first count starts before the window.  Inside the window
the host is in ``between_rounds``, the prefetch ``sample`` after the
dispatch, or ``sync``: the section's total is clipped to what the other
two leave of the window."""


def read(ctx):
    s = ctx.sections
    if not ctx.rounds or "between_rounds" not in s:
        return None
    inside = ctx.window_s - s.get("sync", 0.0) - s.get("sample", 0.0)
    return min(s["between_rounds"], inside) / ctx.rounds * 1e3
