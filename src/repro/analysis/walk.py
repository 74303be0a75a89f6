"""Jaxpr traversal utilities shared by every pass.

All passes operate on a ``ClosedJaxpr`` and must see the WHOLE program,
including the sub-jaxprs that higher-order primitives carry in their
params (``scan``/``cond``/``while_loop``/``pjit``/``custom_jvp``/
``pallas_call``/...). Rather than special-casing each primitive, the
walker scans every eqn param for anything jaxpr-shaped — the same trick
``tests/test_selection_equivalence._prim_counts`` used, now shared.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

from jax.extend.core import ClosedJaxpr, Jaxpr


def subjaxprs(eqn) -> List[Tuple[str, object]]:
    """(param_name, ClosedJaxpr-or-Jaxpr) for every sub-jaxpr of one eqn."""
    out = []
    for name, v in eqn.params.items():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if isinstance(item, (ClosedJaxpr, Jaxpr)):
                out.append((name, item))
            elif hasattr(item, "jaxpr") and isinstance(
                    getattr(item, "jaxpr"), (ClosedJaxpr, Jaxpr)):
                out.append((name, item.jaxpr))
    return out


def _as_open(jx):
    """Jaxpr of either a ClosedJaxpr or a raw Jaxpr."""
    return jx.jaxpr if isinstance(jx, ClosedJaxpr) else jx


def iter_eqns(closed) -> Iterator[Tuple[object, str]]:
    """Yield (eqn, path) over the whole program, depth-first.

    ``path`` names the enclosing higher-order chain, e.g.
    ``"scan/body/cond[branch1]"`` — stable across retraces of the same
    program, used in finding messages (never in keys).
    """
    def walk(jx, path):
        for eqn in _as_open(jx).eqns:
            yield eqn, path
            subs = subjaxprs(eqn)
            for i, (pname, sub) in enumerate(subs):
                tag = eqn.primitive.name if len(subs) == 1 else \
                    f"{eqn.primitive.name}[{pname}{i}]"
                yield from walk(sub, f"{path}/{tag}" if path else tag)

    yield from walk(closed, "")


def prim_histogram(closed) -> Dict[str, int]:
    """Primitive name -> count over the whole program (sub-jaxprs included).

    This is the shared implementation behind the constancy checker: two
    traces with equal histograms have the same op mix regardless of var
    naming, so "jaxpr constant in T/horizon/events" can be asserted
    without brittle string comparison.
    """
    hist: Dict[str, int] = {}
    for eqn, _ in iter_eqns(closed):
        hist[eqn.primitive.name] = hist.get(eqn.primitive.name, 0) + 1
    return hist


def n_eqns(closed) -> int:
    """Total eqn count over the whole program (sub-jaxprs included)."""
    return sum(1 for _ in iter_eqns(closed))
