"""Model FLOPs of a round, counted by the benchmark from shapes.

``forward_flops`` walks the jaxpr of a forward pass (sub-jaxprs too)
and counts the contractions, at 2 FLOPs per multiply-add: every
``dot_general`` and ``conv_general_dilated``.  Elementwise work, norms
and pooling are not counted.

``round_flops`` turns the forward FLOPs of one sample through each half
into the FLOPs a round needs, by what the round's phases do with each
sample.  A backward pass counts twice its forward (input and weight
gradients); a forward that only repeats one already counted, with the
same weights on the same input, is not counted.
"""
from __future__ import annotations

import math

import jax
from jax.extend import core as jcore


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, rc), (lb, _) = eqn.params["dimension_numbers"]
        lhs, rhs = (v.aval.shape for v in eqn.invars)
        contract = math.prod(lhs[d] for d in lc)
        batch = math.prod(lhs[d] for d in lb)
        free_l = math.prod(s for d, s in enumerate(lhs)
                           if d not in lc and d not in lb)
        free_r = math.prod(s for d, s in enumerate(rhs)
                           if d not in rc and d not in eqn.params[
                               "dimension_numbers"][1][1])
        return 2 * batch * free_l * free_r * contract
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval.shape
        rhs = eqn.invars[1].aval.shape
        dn = eqn.params["dimension_numbers"]
        k_in = rhs[dn.rhs_spec[1]]
        window = math.prod(rhs[d] for d in dn.rhs_spec[2:])
        return 2 * math.prod(out) * k_in * window
    return 0


def _subjaxprs(value):
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _subjaxprs(v)


def _jaxpr_flops(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        for sub in _subjaxprs(list(eqn.params.values())):
            total += _jaxpr_flops(sub)
    return total


def forward_flops(fn, *args) -> int:
    """FLOPs of ``fn(*args)`` (arrays or ``ShapeDtypeStruct``s)."""
    return _jaxpr_flops(jax.make_jaxpr(fn)(*args).jaxpr)


# Forward-FLOP multiples per sample, by the round's server mode:
#   client: extract (1) + backward through the client (2)
#   cycle: every resampled row takes a forward and backward (3 per row
#     stepped), and the feature gradients at the UPDATED server need a
#     new forward and an input backward (2 per pooled row)
#   mean_grad: one forward and backward at the pre-round server gives
#     the weight and the feature gradients (3 per row)
CLIENT_MULT = 3
SERVER_MULT = {"cycle": (3, 2), "mean_grad": (0, 3)}


def round_flops(client_fwd: int, server_fwd: int, server_mode: str,
                rows: int, rows_stepped: int) -> int:
    """FLOPs of one round: ``rows`` live samples in the cohort, and
    ``rows_stepped`` rows the server's inner loop steps on."""
    per_step, per_row = SERVER_MULT[server_mode]
    return (CLIENT_MULT * client_fwd * rows
            + server_fwd * (per_step * rows_stepped + per_row * rows))
