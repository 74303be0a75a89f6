"""Tenant access patterns and lifecycle draws for the benchmark's traffic.

A copy of the synthetic workload generators of ``repro.core.workloads``,
kept here so that the benchmark's traffic does not move when the program's
generators do. ``bench/tests/test_perfbench.py`` pins that, at a small size
and a fixed seed, these produce exactly what the program's generators do.

The production analogues follow the paper's §V descriptions: Cache (random
access, ~60% hot), Web (stable hot set), CI (spiky footprint), stream (a hot
window sweeping the footprint) and Spark (bursty, freshest data hot).
Serverless lifetimes follow arXiv:2309.01736.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np


@dataclass
class Workload:
    footprint: int
    arrival: int = 0
    departure: Optional[int] = None
    pattern: str = "hotcold"       # hotcold | uniform | stream | bursty
    hot_frac: float = 0.2
    hot_rate: float = 4.0
    cold_rate: float = 0.05
    ramp: int = 10
    stream_window: int = 0
    stream_step: int = 0
    phase_len: int = 0
    burst_low: float = 0.3
    rotate_hot_every: int = 0


def footprint_at(w: Workload, age: int) -> int:
    """Live footprint (pages) of a workload at episode-age ``age``."""
    n = w.footprint
    f = n if age >= w.ramp else max(int(n * (age + 1) / w.ramp), 1)
    if w.pattern == "bursty" and w.phase_len > 0:
        phase = (age // w.phase_len) % 2
        low = max(int(n * w.burst_low), 1)
        if phase == 1:
            f = low
        else:
            pa = age % w.phase_len
            grow = min(1.0, (pa + 1) / max(w.phase_len // 2, 1))
            f = low + int((n - low) * grow)
    return f


def rates_at(w: Workload, age: int, f: int) -> np.ndarray:
    """Per-page access rates over the tenant-local address space [0, f)."""
    rates = np.full(f, w.cold_rate, np.float32)
    if w.pattern == "uniform":
        rates[:] = w.hot_rate
    elif w.pattern in ("hotcold", "bursty"):
        h = max(int(f * w.hot_frac), 1)
        if w.pattern == "bursty" and w.rotate_hot_every == 0:
            start = max(f - h, 0)
        elif w.rotate_hot_every > 0:
            start = ((age // w.rotate_hot_every) * h) % max(f - h, 1)
        else:
            start = 0
        rates[start:start + h] = w.hot_rate
    elif w.pattern == "stream":
        win = min(max(w.stream_window, 1), f)
        start = (age * max(w.stream_step, 1)) % f
        end = start + win
        rates[start:min(end, f)] = w.hot_rate
        if end > f:
            rates[:end - f] = w.hot_rate
    return rates


def cache_like(footprint: int, arrival: int = 0) -> Workload:
    return Workload(footprint=footprint, arrival=arrival, pattern="hotcold",
                    hot_frac=0.6, hot_rate=3.0, cold_rate=0.3)


def web_like(footprint: int, arrival: int = 0) -> Workload:
    return Workload(footprint=footprint, arrival=arrival, pattern="hotcold",
                    hot_frac=0.35, hot_rate=4.0, cold_rate=0.02)


def ci_like(footprint: int, arrival: int = 0) -> Workload:
    return Workload(footprint=footprint, arrival=arrival, pattern="bursty",
                    phase_len=40, burst_low=0.35, hot_frac=0.5, hot_rate=3.0,
                    cold_rate=0.2, ramp=15)


def spark_like(footprint: int, arrival: int = 0) -> Workload:
    return Workload(footprint=footprint, arrival=arrival, pattern="bursty",
                    phase_len=30, burst_low=0.25, hot_frac=0.3, hot_rate=1.5,
                    cold_rate=0.05, ramp=8)


def stream_like(footprint: int, arrival: int = 0) -> Workload:
    return Workload(footprint=footprint, arrival=arrival, pattern="stream",
                    stream_window=max(footprint // 8, 4),
                    stream_step=max(footprint // 32, 1), hot_rate=3.0,
                    cold_rate=0.05)


PROD_KINDS = (cache_like, web_like, ci_like, stream_like, spark_like)


def stacked_heterogeneous(n_tenants: int, base_footprint: int
                          ) -> List[Workload]:
    """The §V production mix stacked on one host, in slot order."""
    out = []
    for i in range(n_tenants):
        footprint = base_footprint + 8 * ((i * 5) % 7)
        out.append(PROD_KINDS[i % len(PROD_KINDS)](footprint,
                                                   arrival=6 * (i % 5)))
    return out


def suggest_policy(tenants: List[Workload]
                   ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Per-tenant (lower_protection, upper_bound) from workload shape:
    protect the stable hot set of hot/cold workloads, cap streamers, leave
    bursty and uniform ones unconfigured."""
    prot, bound = [], []
    for w in tenants:
        if w.pattern == "hotcold":
            prot.append(int(w.footprint * w.hot_frac * 0.8))
            bound.append(0)
        elif w.pattern == "stream":
            prot.append(0)
            bound.append(max(2 * w.stream_window, 16))
        else:
            prot.append(0)
            bound.append(0)
    return tuple(prot), tuple(bound)


# ------------------------------------------------------------ lifecycles ----
@dataclass
class Slot:
    """A tenant slot: a workload shape and the half-open ``[arrival,
    departure)`` episodes during which a tenant occupies it."""
    workload: Workload
    episodes: List[Tuple[int, int]] = field(default_factory=list)


def episodes(rng, ticks: int, mean_life: float, mean_gap: float,
             min_life: int, first: int) -> List[Tuple[int, int]]:
    eps = []
    t = first
    while t < ticks:
        life = max(int(rng.exponential(mean_life)), min_life)
        eps.append((t, t + life))
        t = t + life + 1 + int(rng.exponential(mean_gap))
    return eps


def poisson_slots(n_slots: int, ticks: int, base_footprint: int, seed: int,
                  step: int = 8, arrival_rate: float = 0.05,
                  mean_life: float = 45.0) -> List[Slot]:
    """Poisson arrivals with exponential lifetimes, patterns cycling through
    the production menu."""
    rng = np.random.default_rng(seed)
    slots = []
    for i in range(n_slots):
        w = PROD_KINDS[i % len(PROD_KINDS)](base_footprint
                                            + step * ((i * 3) % 5))
        w.ramp = min(w.ramp, 6)
        eps = episodes(rng, ticks, mean_life, 1.0 / arrival_rate, min_life=8,
                       first=int(rng.exponential(1.0 / arrival_rate)))
        slots.append(Slot(w, eps))
    return slots


def serverless_slots(n_slots: int, ticks: int, footprint: int, seed: int,
                     mean_life: float = 6.0, mean_gap: float = 8.0
                     ) -> List[Slot]:
    """Short-lived, uniformly hot functions that arrive again almost at once
    and never reach a steady state (arXiv:2309.01736)."""
    rng = np.random.default_rng(seed)
    slots = []
    for _ in range(n_slots):
        w = Workload(footprint=footprint, pattern="uniform", hot_rate=4.0,
                     cold_rate=0.0, ramp=1)
        eps = episodes(rng, ticks, mean_life, mean_gap, min_life=2,
                       first=int(rng.integers(0, 6)))
        slots.append(Slot(w, eps))
    return slots


def churn_stacked(n_stable: int, n_poisson: int, n_serverless: int,
                  ticks: int, seed: int, scale: int = 1) -> List[Slot]:
    """Stable long-lived tenants, a Poisson-churned middle and a serverless
    tail, in slot order. ``scale`` multiplies every footprint: the same
    roster on a host ``scale`` times larger."""
    stable_kinds = (web_like, cache_like)
    slots = [Slot(stable_kinds[i % 2]((64 + 8 * (i % 3)) * scale),
                  [(3 * i, ticks)])
             for i in range(n_stable)]
    slots += poisson_slots(n_poisson, ticks, base_footprint=48 * scale,
                           seed=seed, step=8 * scale)
    slots += serverless_slots(n_serverless, ticks, footprint=56 * scale,
                              seed=seed + 1)
    return slots
