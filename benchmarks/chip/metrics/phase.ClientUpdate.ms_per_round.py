"""phase.ClientUpdate.ms_per_round: device ms a traced round spends in ops
under the program's ``ClientUpdate`` scope (the union of their intervals,
mean over the chips; ``chipbench/scopes.py``)."""
from chipbench import scopes


def read(ctx):
    return scopes.phase_ms_per_round(ctx, "ClientUpdate")
