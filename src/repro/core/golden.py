"""Golden-trace scenarios: small deterministic runs (one static, one churn,
one sketch-hotness churn) and the telemetry each reports — cumulative
counters, ``tier_stat``-level summary metrics and the decoded migration
ring — in a JSON-ready form, plus the comparison that holds a run to its
recorded golden (``tests/golden/*.json``).

The test suite replays them on the CPU; ``chip_smoke.py`` replays them on
the TPU against the same files.
"""
from __future__ import annotations

from repro.configs.base import TieringConfig
from repro.core.simulator import CHURN_PRESETS, simulate, simulate_churn
from repro.core.workloads import (ChurnSlot, ci_like, microbenchmark,
                                  serverless_bursts, web_like)

RING_HEAD = 40          # decoded migration events pinned from each end


def _static_small():
    cfg = TieringConfig(n_tenants=3, n_fast_pages=128, n_slow_pages=256,
                        lower_protection=(48, 48, 0),
                        upper_bound=(0, 64, 0))
    tenants = [microbenchmark(80), web_like(90, arrival=8),
               ci_like(70, phase_len=16)]
    return simulate(cfg, tenants, 60, k_max=32)


def _churn_small():
    slots = [ChurnSlot(web_like(40), [(0, 80)]),
             ChurnSlot(microbenchmark(32, ramp=3), [(4, 30), (40, 70)]),
             *serverless_bursts(2, 80, footprint=24, seed=3)]
    cfg = TieringConfig(n_tenants=4, n_fast_pages=64, n_slow_pages=120,
                        lower_protection=(16, 8, 0, 0),
                        upper_bound=(0, 24, 0, 0))
    return simulate_churn(cfg, slots, 80, k_max=32)


def _churn16_sketch():
    """The sketch hotness provider on the churn16 preset: pins the
    provider's decisions (and its dense-hot telemetry in the ring) under
    dynamic ownership, so refactors can't silently shift sketch
    semantics."""
    cfg, slots = CHURN_PRESETS["churn16"]()
    cfg = cfg.with_(n_tenants=len(slots))
    return simulate_churn(cfg, slots, 100, k_max=64, hotness="sketch")


SCENARIOS = {"static_small": _static_small, "churn_small": _churn_small,
             "churn16_sketch": _churn16_sketch}


def _events_to_lists(ev) -> list:
    return [[int(e["tick"]), int(e["tenant"]), int(e["page"]),
             int(e["direction"]), round(float(e["hotness"]), 5)]
            for e in ev]


def collect(r) -> dict:
    """Everything an operator-facing telemetry surface reports."""
    ts = r.tier_stats
    return {
        "final_fast_usage": r.fast_usage[-1].tolist(),
        "final_slow_usage": r.slow_usage[-1].tolist(),
        "total_promotions": r.promotions.sum(0).tolist(),
        "total_demotions": r.demotions.sum(0).tolist(),
        "total_attempted": r.attempted.sum(0).tolist(),
        "final_thrash_events": r.thrash_events[-1].tolist(),
        "final_pool_free": int(r.pool_free[-1]),
        "promo_attempts": ts["promo_attempts"].tolist(),
        "promo_success": ts["promo_success"].tolist(),
        "demo_attempts": ts["demo_attempts"].tolist(),
        "demo_success": ts["demo_success"].tolist(),
        "resid_hist": ts["resid_hist"].tolist(),
        "resid_p50": ts["resid_p50"].tolist(),
        "resid_p99": ts["resid_p99"].tolist(),
        "contended_frac": [round(float(x), 6) for x in ts["contended_frac"]],
        "throttled_frac": [round(float(x), 6) for x in ts["throttled_frac"]],
        "below_protection_frac": [round(float(x), 6)
                                  for x in ts["below_protection_frac"]],
        "obs_ticks": int(ts["ticks"]),
        "ring_events_decoded": len(r.migrations),
        "ring_events_dropped": int(r.migrations_dropped),
        "ring_head": _events_to_lists(r.migrations[:RING_HEAD]),
        "ring_tail": _events_to_lists(r.migrations[-RING_HEAD:]),
    }


class GoldenMismatch(AssertionError):
    """A run differs from its golden; the message names the first path."""


def _expect(ok: bool, msg: str) -> None:
    if not ok:
        raise GoldenMismatch(msg)


def diff(got, want, path=""):
    """Exact on ints/strings, atol 1e-4 on floats, recursive on containers.
    Raises GoldenMismatch naming the first differing path."""
    if isinstance(want, list):
        _expect(isinstance(got, list), f"{path}: {got!r} is not a list")
        _expect(len(got) == len(want),
                f"{path}: length {len(got)} != {len(want)}")
        for i, (g, w) in enumerate(zip(got, want)):
            diff(g, w, f"{path}[{i}]")
    elif isinstance(want, bool) or isinstance(want, str):
        _expect(got == want, f"{path}: {got!r} != {want!r}")
    elif isinstance(want, int):
        _expect(int(got) == want, f"{path}: {got} != {want}")
    elif isinstance(want, float):
        _expect(abs(float(got) - want) <= 1e-4, f"{path}: {got} != {want}")
    else:
        _expect(got == want, f"{path}: {got!r} != {want!r}")


def diff_all(got: dict, want: dict) -> None:
    """Hold a collected run to its golden: same key set, every key diffed."""
    _expect(sorted(want) == sorted(got), "telemetry key set drifted")
    for key in sorted(want):
        diff(got[key], want[key], key)
