"""Known-bad programs the analyzer must flag (and a clean one it must not).

These are the analyzer's own regression surface: each fixture plants
exactly the defect one pass exists to catch, so `tests/test_analysis.py`
(and `python -m repro.analysis --fixture <name> --gate`) can assert the
pass fires — and that the clean tick stays silent. Fixture findings are
never baselined.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.interval import Interval

FIXTURES = ("purity", "dtype", "overflow", "constancy", "donation", "lint",
            "clean")


# --------------------------------------------------------------- purity ----
def bad_purity():
    """A tick-shaped fn that re-enters Python: debug print + io_callback."""
    def f(c, x):
        jax.debug.print("tick {}", c)
        try:
            from jax.experimental import io_callback
            c = c + io_callback(lambda v: np.asarray(v, np.int32),
                                jax.ShapeDtypeStruct((), jnp.int32), x)
        except ImportError:  # pragma: no cover
            c = c + x
        return c, c
    return jax.make_jaxpr(f)(jnp.zeros((), jnp.int32),
                             jnp.ones((), jnp.int32))


# ---------------------------------------------------------------- dtype ----
def bad_dtype():
    """A float64 leak: traced under an enable_x64 escape hatch."""
    def f(x):
        y = jnp.asarray(x, jnp.float64)      # the leak
        return (y * 2.0).sum()

    with jax.enable_x64(True):
        return jax.make_jaxpr(f)(np.zeros((4,), np.float32))


# ------------------------------------------------------------- overflow ----
def bad_overflow_carry():
    """A per-tick counter growing ~L per tick: wraps int32 well inside the
    fleet horizon. Returns (closed, carry_pairs, input_ivals, horizon)."""
    L = 262_144

    def tick(counter, hits):
        return counter + hits.sum(), counter

    closed = jax.make_jaxpr(tick)(jnp.zeros((), jnp.int32),
                                  jnp.zeros((L,), jnp.int32))
    ivals = [Interval(0, 0, True), Interval(0, 1, True)]
    return closed, [(0, 0, "counter")], ivals, 10_000


def bad_overflow_scan():
    """The in-graph variant: a scan whose int32 carry wraps within the
    scanned length itself."""
    def f(c):
        def body(c, _):
            return c + 300_000, None
        c, _ = jax.lax.scan(body, c, None, length=10_000)
        return c
    closed = jax.make_jaxpr(f)(jnp.zeros((), jnp.int32))
    return closed, [], [Interval(0, 0, True)], 1


def bad_overflow_f32():
    """The old fleet accumulator shape: integer migration counts summed
    into a float32 scan carry — exact only to 2^24."""
    def f(acc, counts):
        def body(a, _):
            return a + counts.sum().astype(jnp.float32), None
        a, _ = jax.lax.scan(body, acc, None, length=5_000)
        return a
    closed = jax.make_jaxpr(f)(jnp.zeros((), jnp.float32),
                               jnp.zeros((64,), jnp.int32))
    return closed, [], [Interval(0, 0, True),
                        Interval(0, 32_768, True)], 1


# ------------------------------------------------------------ constancy ----
def bad_constancy_build(T: int):
    """A tenant-unrolled reduction: the jaxpr grows linearly in T."""
    def f(x):
        parts = []
        for t in range(T):                   # the defect: Python loop over T
            parts.append(x[t] * (t + 1))
        return sum(parts)
    return jax.make_jaxpr(f)(jnp.zeros((T, 8), jnp.float32))


def good_constancy_build(T: int):
    """The vectorized twin: constant structure at any T."""
    def f(x):
        w = jnp.arange(1, x.shape[0] + 1, dtype=jnp.float32)
        return (x * w[:, None]).sum(axis=0)
    return jax.make_jaxpr(f)(jnp.zeros((T, 8), jnp.float32))


# ------------------------------------------------------------- donation ----
def bad_donation():
    """Donates a buffer no output can alias (shape mismatch): XLA drops
    the donation silently. Returns (fn, args, donate_argnums)."""
    def f(a, b):
        return (a[:2] + b[:2]).sum()[None]
    a = jnp.zeros((8,), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    return f, (a, b), (0,)


def good_donation():
    """A donation that aliases: same shape/dtype in and out."""
    def f(a, b):
        return a + b
    a = jnp.zeros((8,), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    return f, (a, b), (0,)


# ----------------------------------------------------------------- lint ----
BAD_LINT_TENANT_LOOP = '''\
def make_tick(cfg):
    T = cfg.n_tenants
    def tick(state, inputs):
        acc = 0
        for ti in range(T):
            acc = acc + state[ti]
        return acc
    return tick
'''

BAD_LINT_NP_IN_GRAPH = '''\
import numpy as np
def make_tick(cfg):
    def tick(state, inputs):
        return np.maximum(state, 0) + inputs
    return tick
'''

BAD_LINT_SEAM_DEFAULT = '''\
def make_tick(cfg, detector=False, attrib=0):
    def tick(state, inputs):
        return state
    return tick
'''

CLEAN_LINT = '''\
import jax.numpy as jnp
def make_tick(cfg, detector=None, attrib=None):
    def tick(state, inputs):
        return jnp.maximum(state, 0) + inputs
    return tick
'''


# ---------------------------------------------------------------- clean ----
def clean_tick():
    """A real (small) unified tick: every jaxpr pass must stay silent at a
    modest horizon. Returns (closed, carry_pairs, input_ivals, horizon)."""
    from repro.analysis.targets import static_tick_target
    t = static_tick_target("equilibria", T=2, pages_per=8, k_max=4,
                           horizon=100)
    return t.closed, t.carry_pairs, t.input_ivals, t.horizon
