"""FLOPs the algorithm needs, counted from shapes.

`count(fn, *args)` traces `fn` to a jaxpr (nothing is compiled or run;
`args` may be `jax.ShapeDtypeStruct`s) and sums 2 x multiply-adds over
every `dot_general` and `conv_general_dilated`, entering every nested
jaxpr and multiplying a `scan` body by its length. The cells hand it
their step in its plain form (`allpairs`, no remat), so recomputed work
is never counted and a Pallas kernel's work is counted as the plain
path's. XLA's `cost_analysis()` is not used: it counts a `scan`/`while`
body once, counts remat recompute, and gives a Pallas call 0.

A `while` with a trip count the jaxpr does not state is an error, not a
body counted once.
"""

from __future__ import annotations

import math
from typing import Any

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr


def _dot_flops(eqn) -> int:
    (contract_l, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    out = eqn.outvars[0].aval.shape
    return 2 * math.prod(out) * math.prod(lhs[d] for d in contract_l)


def _conv_flops(eqn) -> int:
    """2 x outputs x (kernel taps x input features per group). A
    transposed convolution reaches XLA as an input-dilated one: of every
    `prod(lhs_dilation)` taps one meets a real input, and only those are
    work the algorithm needs."""
    dn = eqn.params["dimension_numbers"]
    rhs = eqn.invars[1].aval.shape
    out = eqn.outvars[0].aval.shape
    taps = math.prod(rhs[d] for d in dn.rhs_spec[2:])
    in_features = rhs[dn.rhs_spec[1]]
    dil = math.prod(eqn.params.get("lhs_dilation") or (1,))
    return 2 * math.prod(out) * taps * in_features // dil


def _sub_jaxprs(value: Any):
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def jaxpr_flops(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "while":
            raise ValueError(
                "a `while` has no trip count in the jaxpr: count the "
                "fixed-iteration (scan) form of the step")
        elif name == "cond":
            total += max(jaxpr_flops(j) for j in
                         _sub_jaxprs(eqn.params["branches"]))
        else:
            inner = sum(jaxpr_flops(j) for v in eqn.params.values()
                        for j in _sub_jaxprs(v))
            total += inner * (eqn.params["length"] if name == "scan" else 1)
    return total


def count(fn, *args, **kwargs) -> int:
    """FLOPs of one call of `fn(*args, **kwargs)`."""
    return jaxpr_flops(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)
