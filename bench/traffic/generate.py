"""The one traffic generator: a deployment's roster (its configuration file)
and a traffic mix (a data file beside this one) and a seed give the per-tick
inputs of the tick, as numpy arrays.

The inputs cover ``prefix + period`` ticks. Tick ``t`` reads row ``t`` while
``t < prefix + period`` and row ``prefix + (t - prefix) % period`` after
that, so a run of any length replays the period once arrivals and ramps
are over (``row_of``). What the seed chooses is listed in the mix's file.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from bench.traffic import patterns as PT

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Traffic:
    ownership: str                  # "static" | "dynamic"
    n_tenants: int
    n_pages: int
    n_fast: int
    prefix: int
    period: int
    lower_protection: Tuple[int, ...]
    upper_bound: Tuple[int, ...]
    owner: Optional[np.ndarray] = None     # static: [L] int32
    accesses: Optional[np.ndarray] = None  # static: [R, L] f32
    alive: Optional[np.ndarray] = None     # static: [R, L] bool
    want: Optional[np.ndarray] = None      # dynamic: [R, T] int32
    rates: Optional[np.ndarray] = None     # dynamic: [R, T, S] f32

    def row_of(self, t: int) -> int:
        return row_of(t, self.prefix, self.period)

    def inputs(self):
        """The per-row input arrays, in the order the tick takes them."""
        if self.ownership == "static":
            return (self.accesses, self.alive)
        return (self.rates, self.want)


def row_of(t, prefix: int, period: int):
    """Input row of tick ``t``: ``t`` up to the end of the first period,
    then ``prefix + (t - prefix) % period``. Plain arithmetic, so that
    Python integers and traced jax integers take the same rule."""
    return t - ((t - prefix) // period) * period * (t >= prefix + period)


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def static_trace(tenants, rows: int):
    """owner [L], accesses [rows, L] f32, alive [rows, L] bool for a fixed
    roster laid out contiguously in slot order."""
    sizes = [w.footprint for w in tenants]
    base = np.cumsum([0] + sizes)
    L = int(base[-1])
    owner = np.repeat(np.arange(len(tenants), dtype=np.int32), sizes)
    accesses = np.zeros((rows, L), np.float32)
    alive = np.zeros((rows, L), bool)
    for i, w in enumerate(tenants):
        lo = base[i]
        for t in range(rows):
            if t < w.arrival or (w.departure is not None
                                 and t >= w.departure):
                continue
            age = t - w.arrival
            f = PT.footprint_at(w, age)
            alive[t, lo:lo + f] = True
            accesses[t, lo:lo + f] = PT.rates_at(w, age, f)
    return owner, accesses, alive


def churn_schedule(slots, rows: int):
    """want [rows, T] int32 and rates [rows, T, S] f32 over each slot's
    tenant-local address space."""
    T = len(slots)
    S = max(s.workload.footprint for s in slots)
    want = np.zeros((rows, T), np.int32)
    rates = np.zeros((rows, T, S), np.float32)
    for i, slot in enumerate(slots):
        w = slot.workload
        for a, d in slot.episodes:
            for t in range(max(a, 0), min(d, rows)):
                age = t - a
                f = min(PT.footprint_at(w, age), S)
                want[t, i] = f
                rates[t, i, :f] = PT.rates_at(w, age, f)[:f]
    return want, rates


def _fast_pages(total: int, frac: float) -> int:
    return max((int(total * frac) // 64) * 64, 64)


def build(config: dict, mix: dict, seed: int) -> Traffic:
    """Expand a configuration's roster under a traffic mix and a seed."""
    roster = config["roster"]
    prefix, period = int(mix["prefix_ticks"]), int(config["horizon_ticks"])
    rows = prefix + period
    rng = np.random.default_rng(seed)
    if roster["builder"] == "stacked_heterogeneous":
        tenants = PT.stacked_heterogeneous(roster["n_tenants"],
                                           roster["base_footprint"])
        if mix.get("permute_arrivals"):
            # the same arrival ticks, dealt to other tenants: phases shift,
            # while the layout and the policy (compiled into the tick) stay
            arrivals = [w.arrival for w in tenants]
            for w, i in zip(tenants, rng.permutation(len(tenants))):
                w.arrival = arrivals[i]
        prot, bound = PT.suggest_policy(tenants)
        owner, accesses, alive = static_trace(tenants, rows)
        total = owner.shape[0]
        tr = Traffic("static", len(tenants), total,
                     _fast_pages(total, config["fast_frac"]), prefix, period,
                     prot, bound, owner=owner, accesses=accesses,
                     alive=alive)
    elif roster["builder"] == "churn_stacked":
        slots = PT.churn_stacked(roster["n_stable"], roster["n_poisson"],
                                 roster["n_serverless"], ticks=rows,
                                 seed=seed, scale=roster["scale"])
        prot, bound = PT.suggest_policy([s.workload for s in slots])
        want, rates = churn_schedule(slots, rows)
        total = sum(s.workload.footprint for s in slots)
        n_fast = _fast_pages(total, config["fast_frac"])
        tr = Traffic("dynamic", len(slots), n_fast + total, n_fast, prefix,
                     period, prot, bound, want=want, rates=rates)
    else:
        raise ValueError(f"unknown roster builder {roster['builder']!r}")
    for key, got in (("n_tenants", tr.n_tenants), ("n_pages", tr.n_pages),
                     ("n_fast_pages", tr.n_fast)):
        if config[key] != got:
            raise ValueError(f"{config['name']}: {key} is {config[key]} in "
                             f"the configuration but the roster gives {got}")
    return tr
