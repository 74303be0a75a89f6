"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``bench/reference/tick.py``).

For each kept chunk the reference starts from the state the program had at
the chunk's start, runs the same ticks over the same traffic rows, and the
two are compared: the state at the chunk's end and every tick's outputs.

Two numbers are compared, each with a limit from the configuration file:

* ``int_mismatch``: integer and boolean values that differ (page tiers and
  owners, recency, counters, thrash table, controller state, statistics'
  residency histogram / entry ticks / attempt, success and occupancy
  counts, the migration ring's tick / tenant / page / direction and its
  head, per-tick usage / moves / candidates / free pages). The decisions
  are exact, so the limit is 0.
* ``float_gap``: the widest gap of a float value (hotness, in the state
  and in the ring, promotion scale, windowed thrash / promotion / demotion
  rates, per-tick latency and throughput), as a share of the largest
  magnitude of that value in the reference.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

FLOATS = ("hot", "promo_scale", "throughput", "latency", "thrash_rate",
          "promo_rate", "demo_rate")


def gaps(cand_state: dict, cand_outs: List[dict], ref_state: dict,
         ref_outs: List[dict]) -> Tuple[int, float, str]:
    """(integer mismatches, widest float gap, name of the widest) of a
    candidate chunk against the reference's."""
    pairs = [(k, cand_state[k], ref_state[k]) for k in ref_state]
    for i, (co, ro) in enumerate(zip(cand_outs, ref_outs)):
        pairs += [(f"out.{k}[{i}]", co[k], ro[k]) for k in ro]
    mism, worst, where = 0, 0.0, ""
    for name, a, b in pairs:
        a, b = np.asarray(a), np.asarray(b)
        if name.split(".")[-1].split("[")[0] in FLOATS:
            a, b = a.astype(np.float64), b.astype(np.float64)
            scale = max(float(np.abs(b).max(initial=0.0)), 1e-30)
            g = float(np.abs(a - b).max(initial=0.0)) / scale
            if g > worst:
                worst, where = g, name
        else:
            mism += int((a.astype(np.int64) != b.astype(np.int64)).sum())
    return mism, worst, where


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each compared number beside its limit; ``ok`` when none is over."""
    out = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    return {"ok": all(numbers[k] <= limits[k] for k in limits),
            "compared": out}
