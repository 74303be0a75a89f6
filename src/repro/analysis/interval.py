"""Interval analysis over jaxpr integer dataflow.

An ``Interval`` abstracts every element of an array as one ``[lo, hi]``
range plus an ``integral`` bit (the value is an exact integer — either an
int dtype or a float produced only by int conversions and exact ops).
The evaluator pushes intervals through a ``ClosedJaxpr`` eqn by eqn,
recursing into ``pjit``/``cond``/``scan``/``while`` sub-jaxprs, so the
overflow pass can answer two questions statically:

  * how fast can each scan-carried integer grow per tick (and therefore
    at what horizon does its dtype wrap)?
  * where does integer mass get converted into float32 beyond the 2^24
    exact-integer window (the silent-precision-loss pattern the fleet
    accumulators had)?

Sound-but-approximate by design: one interval per array (no per-element
tracking), unknown primitives produce their output dtype's full range
(recorded as a note, never silently), and scan carries are widened
linearly — ``carry_out <= carry_in + growth * length`` — which is exact
for the additive accumulators this codebase carries and conservative for
monotone ones. Trip-count-unknown ``while`` carries widen straight to the
dtype range.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np
from jax.extend.core import Literal

from repro.analysis.walk import ClosedJaxpr, subjaxprs

INF = math.inf
# exact-integer window of float32 (2^24): integers beyond this silently
# lose units when accumulated in f32
F32_EXACT = float(1 << 24)
F16_EXACT = float(1 << 11)


class Interval(NamedTuple):
    lo: float
    hi: float
    integral: bool = False

    def union(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi),
                        self.integral and other.integral)

    def contains(self, other: "Interval") -> bool:
        return self.lo <= other.lo and other.hi <= self.hi

    def bounded(self) -> bool:
        return math.isfinite(self.lo) and math.isfinite(self.hi)


BOOL = Interval(0, 1, True)
TOP_F = Interval(-INF, INF, False)


def dtype_interval(dtype) -> Interval:
    """The full representable range of a dtype (the TOP element)."""
    try:
        dtype = np.dtype(dtype)
    except TypeError:
        # extended dtypes (typed PRNG keys): opaque to interval analysis;
        # treat as unbounded so downstream casts stay conservative
        return TOP_F
    if dtype == np.bool_:
        return BOOL
    if np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        return Interval(float(info.min), float(info.max), True)
    return TOP_F


def value_interval(x) -> Interval:
    """Interval of a concrete constant/literal."""
    arr = np.asarray(x)
    if arr.size == 0:
        return Interval(0, 0, True)
    integral = bool(arr.dtype == np.bool_
                    or np.issubdtype(arr.dtype, np.integer))
    if np.issubdtype(arr.dtype, np.complexfloating):
        return TOP_F
    lo = float(arr.min())
    hi = float(arr.max())
    if not integral and np.issubdtype(arr.dtype, np.floating):
        # a float constant holding exact integers keeps the integral bit
        # (e.g. 0.0 seeds of integral accumulators)
        finite = np.isfinite(arr)
        integral = bool(finite.all() and (arr == np.round(arr)).all())
    return Interval(lo, hi, integral)


def _mul(a: float, b: float) -> float:
    if a == 0 or b == 0:
        return 0.0
    return a * b


def add_iv(a: Interval, b: Interval) -> Interval:
    return Interval(a.lo + b.lo, a.hi + b.hi, a.integral and b.integral)


def sub_iv(a: Interval, b: Interval) -> Interval:
    return Interval(a.lo - b.hi, a.hi - b.lo, a.integral and b.integral)


def mul_iv(a: Interval, b: Interval) -> Interval:
    cs = [_mul(a.lo, b.lo), _mul(a.lo, b.hi), _mul(a.hi, b.lo),
          _mul(a.hi, b.hi)]
    return Interval(min(cs), max(cs), a.integral and b.integral)


def scale_iv(a: Interval, n: float) -> Interval:
    """a summed over n independent draws: [min(n*lo, lo), max(n*hi, hi)]
    (covers reductions over masked/partial extents)."""
    lo = min(_mul(a.lo, n), a.lo, 0.0)
    hi = max(_mul(a.hi, n), a.hi, 0.0)
    return Interval(lo, hi, a.integral)


@dataclass
class Event:
    """One interval-analysis observation at a program point."""
    kind: str        # carry-overflow | cast-truncate | cast-precision
    path: str        # enclosing higher-order chain (walk.iter_eqns path)
    slug: str        # stable identity for baseline keys
    detail: str


@dataclass
class EvalContext:
    events: List[Event] = field(default_factory=list)
    unknown_prims: Dict[str, int] = field(default_factory=dict)
    _slug_seq: Dict[str, int] = field(default_factory=dict)

    def next_slug(self, base: str) -> str:
        k = self._slug_seq.get(base, 0)
        self._slug_seq[base] = k + 1
        return base if k == 0 else f"{base}#{k}"


def _reduce_extent(eqn) -> float:
    shape = eqn.invars[0].aval.shape
    axes = eqn.params.get("axes", tuple(range(len(shape))))
    n = 1
    for a in axes:
        n *= int(shape[a])
    return float(max(n, 1))


def _out_top(eqn) -> List[Interval]:
    return [dtype_interval(v.aval.dtype) if hasattr(v.aval, "dtype")
            else TOP_F for v in eqn.outvars]


class IntervalEvaluator:
    """Pushes intervals through one ClosedJaxpr (and its sub-jaxprs)."""

    def __init__(self, ctx: Optional[EvalContext] = None):
        self.ctx = ctx or EvalContext()

    # ------------------------------------------------------------------ env
    def eval_closed(self, closed: ClosedJaxpr, in_ivals: List[Interval],
                    path: str = "") -> List[Interval]:
        jaxpr = closed.jaxpr
        env: Dict[object, Interval] = {}
        for v, c in zip(jaxpr.constvars, closed.consts):
            env[v] = value_interval(c)
        if len(in_ivals) != len(jaxpr.invars):
            raise ValueError(f"expected {len(jaxpr.invars)} input intervals, "
                             f"got {len(in_ivals)}")
        for v, iv in zip(jaxpr.invars, in_ivals):
            env[v] = iv
        self._eval_eqns(jaxpr, env, path)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _read(self, env, v) -> Interval:
        if isinstance(v, Literal):
            return value_interval(v.val)
        if v not in env:
            # DropVar or untracked: fall back to dtype range
            return (dtype_interval(v.aval.dtype)
                    if hasattr(v.aval, "dtype") else TOP_F)
        return env[v]

    def _eval_eqns(self, jaxpr, env, path) -> None:
        for eqn in jaxpr.eqns:
            ivals = [self._read(env, v) for v in eqn.invars]
            outs = self._eval_eqn(eqn, ivals, path)
            for v, iv in zip(eqn.outvars, outs):
                # intersect with the output dtype's representable range:
                # whatever the op did, the array cannot hold more
                if hasattr(v.aval, "dtype"):
                    top = dtype_interval(v.aval.dtype)
                    try:
                        is_int = np.issubdtype(np.dtype(v.aval.dtype),
                                               np.integer)
                    except TypeError:   # extended dtype (typed PRNG key)
                        is_int = False
                    iv = Interval(max(iv.lo, top.lo), min(iv.hi, top.hi),
                                  iv.integral or top is BOOL or is_int
                                  if iv.integral is not None else iv.integral)
                env[v] = iv

    # ------------------------------------------------------------ dispatch
    def _eval_eqn(self, eqn, ivals: List[Interval],
                  path: str) -> List[Interval]:
        name = eqn.primitive.name
        fn = _RULES.get(name)
        if fn is not None:
            return fn(self, eqn, ivals, path)
        if name in _HIGHER_ORDER:
            return _HIGHER_ORDER[name](self, eqn, ivals, path)
        # single sub-jaxpr call-like primitives (custom_jvp, remat, ...):
        subs = subjaxprs(eqn)
        if len(subs) == 1 and isinstance(subs[0][1], ClosedJaxpr):
            sub = subs[0][1]
            if len(sub.jaxpr.invars) == len(ivals):
                outs = self.eval_closed(sub, ivals, f"{path}/{name}")
                if len(outs) == len(eqn.outvars):
                    return outs
        self.ctx.unknown_prims[name] = self.ctx.unknown_prims.get(name, 0) + 1
        return _out_top(eqn)

    # ----------------------------------------------------------- cast rule
    def _convert(self, eqn, ivals, path) -> List[Interval]:
        (a,) = ivals
        new_dtype = np.dtype(eqn.params["new_dtype"])
        top = dtype_interval(new_dtype)
        if np.issubdtype(new_dtype, np.integer):
            if a.bounded() and top.contains(Interval(a.lo, a.hi, True)):
                out = Interval(math.floor(a.lo), math.ceil(a.hi), True)
            else:
                # a *finite* bound provably exceeding the target range is a
                # real truncation; an unbounded one is usually analysis
                # over-approximation — downgraded to a note by the pass
                kind = "cast-truncate" if a.bounded() else "cast-unbounded"
                self.ctx.events.append(Event(
                    kind=kind, path=path,
                    slug=self.ctx.next_slug(f"cast-{new_dtype.name}@{path}"),
                    detail=f"cast to {new_dtype.name} from range "
                           f"[{a.lo:g}, {a.hi:g}] can wrap"))
                out = top
            return [out]
        if np.issubdtype(new_dtype, np.floating):
            exact = {2: F16_EXACT, 4: F32_EXACT}.get(new_dtype.itemsize)
            if (a.integral and exact is not None
                    and max(abs(a.lo), abs(a.hi)) > exact):
                self.ctx.events.append(Event(
                    kind="cast-precision", path=path,
                    slug=self.ctx.next_slug(
                        f"cast-{new_dtype.name}-precision@{path}"),
                    detail=f"integer mass up to {max(abs(a.lo), abs(a.hi)):g}"
                           f" cast to {new_dtype.name} (exact only to "
                           f"{exact:g}) — accumulation drops units"))
            return [Interval(a.lo, a.hi, a.integral)]
        return [TOP_F]

    # ------------------------------------------------------- higher order
    def _pjit(self, eqn, ivals, path) -> List[Interval]:
        sub = eqn.params["jaxpr"]
        return self.eval_closed(sub, ivals, f"{path}/pjit" if path else "pjit")

    def _cond(self, eqn, ivals, path) -> List[Interval]:
        branches = eqn.params["branches"]
        op_ivals = ivals[1:]
        outs: Optional[List[Interval]] = None
        for i, br in enumerate(branches):
            o = self.eval_closed(br, op_ivals, f"{path}/cond[{i}]")
            outs = o if outs is None else [a.union(b)
                                           for a, b in zip(outs, o)]
        return outs or []

    def _scan(self, eqn, ivals, path) -> List[Interval]:
        sub: ClosedJaxpr = eqn.params["jaxpr"]
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        length = float(eqn.params.get("length", 0) or 0)
        consts = ivals[:nc]
        carry0 = ivals[nc:nc + ncar]
        xs = ivals[nc + ncar:]          # per-slice interval == stacked interval
        spath = f"{path}/scan" if path else "scan"

        # Two-phase widening: growth measured between the first-iteration
        # output and a second evaluation at the union — a transient jump
        # (tier -1 -> 1, a saturated gather) settles at iteration two and
        # extrapolates to nothing; a genuine accumulator keeps its rate.
        out1 = self.eval_closed(sub, consts + carry0 + xs, spath)
        carryU = [c0.union(c1) for c0, c1 in zip(carry0, out1[:ncar])]
        out_u = self.eval_closed(sub, consts + carryU + xs, spath)
        widened: List[Interval] = []
        for j, (cu, c1, c2) in enumerate(zip(carryU, out1[:ncar],
                                             out_u[:ncar])):
            grow = max(c2.hi - c1.hi, 0.0)
            drop = min(c2.lo - c1.lo, 0.0)
            if grow == 0.0 and drop == 0.0:
                widened.append(cu.union(c2))
                continue
            lo = cu.lo if drop == 0 else cu.lo + _mul(drop, length)
            hi = cu.hi if grow == 0 else cu.hi + _mul(grow, length)
            w = Interval(lo, hi, cu.integral and c2.integral)
            widened.append(w)
            # overflow check against the carried var's dtype happens here,
            # where the growth rate and trip count are both known
            var = sub.jaxpr.invars[nc + j]
            dtype = getattr(var.aval, "dtype", None)
            if dtype is not None and np.issubdtype(np.dtype(dtype),
                                                   np.integer):
                top = dtype_interval(dtype)
                if not top.contains(w):
                    self.ctx.events.append(Event(
                        kind="carry-overflow", path=spath,
                        slug=self.ctx.next_slug(f"scan-carry{j}@{spath}"),
                        detail=(f"scan carry {j} ({np.dtype(dtype).name}) "
                                f"grows ~{grow:g}/iter over "
                                f"{int(length)} iters -> bound {w.hi:g} "
                                f"exceeds {np.dtype(dtype).name} range")))
            elif dtype is not None and np.issubdtype(np.dtype(dtype),
                                                     np.floating):
                # integer mass accumulated in a narrow float carry: exact
                # only below the mantissa window, then silently drops units
                exact = {2: F16_EXACT, 4: F32_EXACT}.get(
                    np.dtype(dtype).itemsize)
                if (exact is not None and w.integral
                        and max(abs(w.lo), abs(w.hi)) > exact):
                    self.ctx.events.append(Event(
                        kind="carry-precision", path=spath,
                        slug=self.ctx.next_slug(
                            f"scan-carry{j}-precision@{spath}"),
                        detail=(f"scan carry {j} accumulates integer counts "
                                f"in {np.dtype(dtype).name} up to "
                                f"{max(abs(w.lo), abs(w.hi)):g} (exact only "
                                f"to {exact:g}) over {int(length)} iters — "
                                f"accumulate in int32 and widen host-side")))
        out2 = self.eval_closed(sub, consts + widened + xs, spath)
        return out2[:ncar] + [iv.union(jv) for iv, jv in
                              zip(out1[ncar:], out2[ncar:])]

    def _while(self, eqn, ivals, path) -> List[Interval]:
        body: ClosedJaxpr = eqn.params["body_jaxpr"]
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        bconsts = ivals[cn:cn + bn]
        carry0 = ivals[cn + bn:]
        wpath = f"{path}/while" if path else "while"
        out1 = self.eval_closed(body, bconsts + carry0, wpath)
        # unknown trip count: any carry not already at fixpoint widens to
        # its dtype range
        outs = []
        for v, c0, c1 in zip(body.jaxpr.outvars, carry0, out1):
            if c0.contains(c1):
                outs.append(c0)
            elif hasattr(v.aval, "dtype"):
                outs.append(dtype_interval(v.aval.dtype))
            else:
                outs.append(TOP_F)
        return outs


# --------------------------------------------------------------- rules ----
def _r(fn: Callable) -> Callable:
    """Adapt a pure-interval rule (ivals -> [Interval])."""
    return lambda self, eqn, ivals, path: fn(eqn, ivals)


def _identity(eqn, ivals):
    return [ivals[0]]


def _union_all(eqn, ivals):
    out = ivals[0]
    for iv in ivals[1:]:
        out = out.union(iv)
    return [out]


def _bool_out(eqn, ivals):
    return [BOOL]


def _reduce_sum(eqn, ivals):
    return [scale_iv(ivals[0], _reduce_extent(eqn))]


def _cumsum(eqn, ivals):
    shape = eqn.invars[0].aval.shape
    axis = eqn.params.get("axis", 0)
    n = float(shape[axis]) if shape else 1.0
    return [scale_iv(ivals[0], n)]


def _iota(eqn, ivals):
    shape = eqn.outvars[0].aval.shape
    dim = eqn.params.get("dimension", 0)
    n = int(shape[dim]) if shape else 1
    return [Interval(0, float(max(n - 1, 0)), True)]


def _select_n(eqn, ivals):
    out = ivals[1]
    for iv in ivals[2:]:
        out = out.union(iv)
    return [out]


def _clamp(eqn, ivals):
    lo, x, hi = ivals
    return [Interval(max(x.lo, lo.lo), min(x.hi, hi.hi),
                     x.integral and lo.integral and hi.integral)]


def _div(eqn, ivals):
    a, b = ivals
    dtype = getattr(eqn.outvars[0].aval, "dtype", np.float32)
    integer = np.issubdtype(np.dtype(dtype), np.integer)
    if b.lo > 0:
        cs = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
        return [Interval(min(cs), max(cs), integer)]
    if b.hi < 0:
        cs = [a.lo / b.lo, a.lo / b.hi, a.hi / b.lo, a.hi / b.hi]
        return [Interval(min(cs), max(cs), integer)]
    return [dtype_interval(dtype) if integer else TOP_F]


def _rem(eqn, ivals):
    a, b = ivals
    m = max(abs(b.lo), abs(b.hi))
    if not math.isfinite(m):
        return _out_top(eqn)
    lo = 0.0 if a.lo >= 0 else -m
    hi = m if a.hi > 0 else 0.0
    return [Interval(lo, hi, a.integral and b.integral)]


def _neg(eqn, ivals):
    a = ivals[0]
    return [Interval(-a.hi, -a.lo, a.integral)]


def _abs(eqn, ivals):
    a = ivals[0]
    lo = 0.0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
    return [Interval(lo, max(abs(a.lo), abs(a.hi)), a.integral)]


def _max_iv(eqn, ivals):
    a, b = ivals
    return [Interval(max(a.lo, b.lo), max(a.hi, b.hi),
                     a.integral and b.integral)]


def _min_iv(eqn, ivals):
    a, b = ivals
    return [Interval(min(a.lo, b.lo), min(a.hi, b.hi),
                     a.integral and b.integral)]


def _floor_like(eqn, ivals):
    a = ivals[0]
    return [Interval(math.floor(a.lo) if math.isfinite(a.lo) else a.lo,
                     math.ceil(a.hi) if math.isfinite(a.hi) else a.hi, True)]


def _sort(eqn, ivals):
    return list(ivals)


def _top_k(eqn, ivals):
    n = int(eqn.invars[0].aval.shape[-1])
    return [ivals[0], Interval(0, float(max(n - 1, 0)), True)]


def _arg_reduce(eqn, ivals):
    shape = eqn.invars[0].aval.shape
    axes = eqn.params.get("axes", (0,))
    n = int(shape[axes[0]]) if shape else 1
    return [Interval(0, float(max(n - 1, 0)), True)]


def _scatter_add(eqn, ivals):
    operand, _idx, updates = ivals[0], ivals[1], ivals[2]
    upd_aval = eqn.invars[2].aval
    n = float(max(int(np.prod(upd_aval.shape)) if upd_aval.shape else 1, 1))
    return [add_iv(operand, scale_iv(updates, n))]


def _scatter_replace(eqn, ivals):
    return [ivals[0].union(ivals[2])]


def _scatter_minmax(which):
    def rule(eqn, ivals):
        return [ivals[0].union(ivals[2])]
    return rule


def _pad(eqn, ivals):
    return [ivals[0].union(ivals[1])]


def _dus(eqn, ivals):
    # dynamic_update_slice(operand, update, *starts)
    return [ivals[0].union(ivals[1])]


def _dot_general(eqn, ivals):
    a, b = ivals[0], ivals[1]
    dims = eqn.params["dimension_numbers"]
    (lhs_c, _rhs_c), _ = dims
    shape = eqn.invars[0].aval.shape
    k = 1
    for ax in lhs_c:
        k *= int(shape[ax])
    prod = mul_iv(a, b)
    return [scale_iv(prod, float(max(k, 1)))]


def _exp(eqn, ivals):
    a = ivals[0]
    return [Interval(math.exp(min(a.lo, 700)) if math.isfinite(a.lo) else 0.0,
                     math.exp(min(a.hi, 700)) if math.isfinite(a.hi) else INF,
                     False)]


def _log(eqn, ivals):
    return [TOP_F]


def _bounded(lo, hi):
    def rule(eqn, ivals):
        return [Interval(lo, hi, False)]
    return rule


def _sign(eqn, ivals):
    return [Interval(-1, 1, True)]


def _square_like(eqn, ivals):
    a = ivals[0]
    p = mul_iv(a, a)
    return [Interval(max(p.lo, 0.0), p.hi, a.integral)]


def _integer_pow(eqn, ivals):
    a = ivals[0]
    y = int(eqn.params.get("y", 2))
    if y == 2:
        return _square_like(eqn, ivals)
    if y >= 0 and a.bounded():
        cs = [a.lo ** y, a.hi ** y]
        if a.lo <= 0 <= a.hi:
            cs.append(0.0)
        return [Interval(min(cs), max(cs), a.integral)]
    return _out_top(eqn)


def _and_or(eqn, ivals):
    dtype = getattr(eqn.outvars[0].aval, "dtype", np.bool_)
    if np.dtype(dtype) == np.bool_:
        return [BOOL]
    return [dtype_interval(dtype)]


_RULES: Dict[str, Callable] = {
    "add": _r(lambda e, iv: [add_iv(iv[0], iv[1])]),
    "add_any": _r(lambda e, iv: [add_iv(iv[0], iv[1])]),
    "sub": _r(lambda e, iv: [sub_iv(iv[0], iv[1])]),
    "mul": _r(lambda e, iv: [mul_iv(iv[0], iv[1])]),
    "div": _r(_div),
    "rem": _r(_rem),
    "neg": _r(_neg),
    "abs": _r(_abs),
    "sign": _r(_sign),
    "max": _r(_max_iv),
    "min": _r(_min_iv),
    "clamp": _r(_clamp),
    "floor": _r(_floor_like),
    "ceil": _r(_floor_like),
    "round": _r(_floor_like),
    "nextafter": _r(_identity),
    "is_finite": _r(_bool_out),
    "eq": _r(_bool_out), "ne": _r(_bool_out), "lt": _r(_bool_out),
    "le": _r(_bool_out), "gt": _r(_bool_out), "ge": _r(_bool_out),
    "eq_to": _r(_bool_out), "lt_to": _r(_bool_out), "le_to": _r(_bool_out),
    "and": _r(_and_or), "or": _r(_and_or), "xor": _r(_and_or),
    "not": _r(_and_or),
    "select_n": _r(_select_n),
    "broadcast_in_dim": _r(_identity),
    "reshape": _r(_identity),
    "squeeze": _r(_identity),
    "expand_dims": _r(_identity),
    "transpose": _r(_identity),
    "rev": _r(_identity),
    "slice": _r(_identity),
    "dynamic_slice": _r(lambda e, iv: [iv[0]]),
    "dynamic_update_slice": _r(_dus),
    "gather": _r(lambda e, iv: [iv[0]]),
    "take_along_axis": _r(lambda e, iv: [iv[0]]),
    "concatenate": _r(_union_all),
    "pad": _r(_pad),
    "copy": _r(_identity),
    "stop_gradient": _r(_identity),
    "convert_element_type": IntervalEvaluator._convert,
    "reduce_sum": _r(_reduce_sum),
    "reduce_prod": _r(lambda e, iv: _out_top(e)),
    "reduce_max": _r(_identity),
    "reduce_min": _r(_identity),
    "reduce_and": _r(_bool_out),
    "reduce_or": _r(_bool_out),
    "argmax": _r(_arg_reduce),
    "argmin": _r(_arg_reduce),
    "cumsum": _r(_cumsum),
    "cummax": _r(_identity),
    "cummin": _r(_identity),
    "iota": _r(_iota),
    "sort": _r(_sort),
    "top_k": _r(_top_k),
    "scatter-add": _r(_scatter_add),
    "scatter": _r(_scatter_replace),
    "scatter-max": _r(_scatter_minmax("max")),
    "scatter-min": _r(_scatter_minmax("min")),
    "scatter-mul": _r(lambda e, iv: _out_top(e)),
    "dot_general": _r(_dot_general),
    "exp": _r(_exp),
    "log": _r(_log),
    "log1p": _r(_log),
    "logistic": _r(_bounded(0, 1)),
    "tanh": _r(_bounded(-1, 1)),
    "erf": _r(_bounded(-1, 1)),
    "sin": _r(_bounded(-1, 1)),
    "cos": _r(_bounded(-1, 1)),
    "sqrt": _r(lambda e, iv: [Interval(0, INF, False)]),
    "rsqrt": _r(lambda e, iv: [Interval(0, INF, False)]),
    "integer_pow": _r(_integer_pow),
    "square": _r(_square_like),
}

_HIGHER_ORDER: Dict[str, Callable] = {
    "pjit": IntervalEvaluator._pjit,
    "closed_call": IntervalEvaluator._pjit,
    "core_call": IntervalEvaluator._pjit,
    "cond": IntervalEvaluator._cond,
    "scan": IntervalEvaluator._scan,
    "while": IntervalEvaluator._while,
}
