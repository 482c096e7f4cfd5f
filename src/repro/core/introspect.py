"""Trace introspection for the engine's compile-time claims.

The layout plan's whole value proposition is *structural*: pad/slice churn
is removed from the traced program, not merely made faster. The layout
tests and the serving benchmark therefore pin those claims on the jaxpr —
deterministic across backends, immune to interpret-mode timing noise —
through this one shared walker.
"""
from __future__ import annotations

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr


def prim_counts(fn, *specs) -> dict:
    """Primitive-name -> count over the jaxpr of ``fn(*specs)``, recursing
    into nested jaxprs (jit-wrapped kernels, pallas_call bodies)."""
    counts = {}

    def walk(jx):
        for eq in jx.eqns:
            counts[eq.primitive.name] = counts.get(eq.primitive.name, 0) + 1
            for v in eq.params.values():
                vs = v if isinstance(v, (tuple, list)) else [v]
                for u in vs:
                    if isinstance(u, ClosedJaxpr):
                        walk(u.jaxpr)
                    elif isinstance(u, Jaxpr):
                        walk(u)

    walk(jax.make_jaxpr(fn)(*specs).jaxpr)
    return counts
