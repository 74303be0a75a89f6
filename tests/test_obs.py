"""Observability subsystem tests: residency bucketing, migration-ring
wraparound/decode round-trip, pathology detectors on synthetic traces,
fleet roll-up shapes under vmap, and in-graph collection on both the trace
engine and the KV serving path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TieringConfig
from repro.obs import pathology as PA
from repro.obs import stats as OS
from repro.obs import trace as OT


# ---------------------------------------------------- residency histogram ----
def test_residency_bucketing():
    ages = jnp.asarray([0, 1, 2, 3, 4, 7, 8, 15, 16, 1 << 20])
    buckets = np.asarray(OS.residency_bucket(ages, n_buckets=8))
    assert buckets.tolist() == [0, 0, 1, 1, 2, 2, 3, 3, 4, 7]  # clipped


def test_residency_hist_records_exits_per_tenant():
    stats = OS.init_stats(3, (8,), n_buckets=8)
    owners = jnp.asarray([0, 0, 1, 1, 2, 2, 2, 2], jnp.int32)
    stats = OS.record_fast_entries(
        stats, jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0], bool),
        jnp.asarray(10, jnp.int32))
    # exits at t=13: ages 3 -> bucket 1. page 4 never entered -> not counted
    stats = OS.record_fast_exits(
        stats, jnp.asarray([1, 0, 1, 0, 1, 0, 0, 0], bool), owners,
        jnp.asarray(13, jnp.int32))
    h = np.asarray(stats.resid_hist)
    assert h.sum() == 2
    assert h[0, 1] == 1 and h[1, 1] == 1 and h[2].sum() == 0
    # exited stamps cleared, survivors keep theirs
    assert np.asarray(stats.fast_since).tolist()[:4] == [-1, 10, -1, 10]


def test_stats_summary_percentiles():
    stats = OS.init_stats(1, (4,), n_buckets=8)
    hist = np.zeros((1, 8), np.int32)
    hist[0, 0] = 10   # 10 exits with residency < 2 ticks
    hist[0, 4] = 1    # one long residency (>= 16 ticks)
    stats = stats._replace(resid_hist=jnp.asarray(hist),
                           ticks=jnp.asarray(5, jnp.int32))
    s = OS.stats_summary(stats)
    assert s["resid_p50"][0] == 0
    assert s["resid_p99"][0] == 16


# ------------------------------------------------------- migration ring ----
def test_ring_wraparound_decode_roundtrip():
    ring = OT.init_ring(8)

    @jax.jit
    def push(ring, pages, t):
        mask = pages >= 0
        tenants = pages % 4
        hot = pages.astype(jnp.float32) / 10
        return OT.ring_record(ring, mask, pages, tenants, hot,
                              OT.DIR_PROMOTE, t)

    # 13 events across 3 calls -> 5 oldest overwritten
    ring = push(ring, jnp.asarray([0, 1, 2, 3, 4]), jnp.asarray(1))
    ring = push(ring, jnp.asarray([5, 6, -1, 7, 8]), jnp.asarray(2))
    ring = push(ring, jnp.asarray([9, 10, 11, 12, -1]), jnp.asarray(3))
    events, dropped = OT.decode_ring(ring)
    assert dropped == 5 and len(events) == 8
    assert events["page"].tolist() == [5, 6, 7, 8, 9, 10, 11, 12]
    assert events["tick"].tolist() == [2, 2, 2, 2, 3, 3, 3, 3]
    assert events["tenant"].tolist() == [(p % 4) for p in events["page"]]
    np.testing.assert_allclose(events["hotness"],
                               np.asarray(events["page"]) / 10, rtol=1e-6)


def test_ring_single_call_larger_than_capacity_keeps_newest():
    ring = OT.init_ring(4)
    pages = jnp.arange(10)
    ring = OT.ring_record(ring, jnp.ones((10,), bool), pages, pages % 2,
                          pages.astype(jnp.float32), OT.DIR_PROMOTE,
                          jnp.asarray(1))
    events, dropped = OT.decode_ring(ring)
    assert dropped == 6
    assert events["page"].tolist() == [6, 7, 8, 9]  # newest C, in order


def test_ring_partial_fill_decode():
    ring = OT.init_ring(16)
    ring = OT.ring_record(ring, jnp.asarray([True, False, True]),
                          jnp.asarray([7, 8, 9]), jnp.asarray([0, 1, 2]),
                          jnp.asarray([1.0, 2.0, 3.0]), OT.DIR_DEMOTE,
                          jnp.asarray(4))
    events, dropped = OT.decode_ring(ring)
    assert dropped == 0
    assert events["page"].tolist() == [7, 9]
    assert (events["direction"] == OT.DIR_DEMOTE).all()


# ------------------------------------------------------ pathology logic ----
def _flat(ticks, T, val=0.0):
    return np.full((ticks, T), val)


def test_detect_chronic_thrashing_only_sustained():
    ticks, T = 200, 2
    ev = np.zeros((ticks, T))
    ev[:, 0] = np.arange(ticks) * 10          # tenant0: 10 events/tick forever
    ev[100:120, 1] = np.arange(20) * 10       # tenant1: one 20-tick burst
    ev[120:, 1] = ev[119, 1]
    found = PA.detect_chronic_thrashing(ev, window=20, rate_threshold=4.0)
    assert [p.tenant for p in found] == [0]
    assert found[0].severity >= 1.0


def test_detect_protection_violation_exempts_cold_tenants():
    ticks, T = 120, 3
    fast = _flat(ticks, T, 100.0)
    slow = _flat(ticks, T, 100.0)
    fast[:, 0] = 20                            # tenant0 held below prot=80
    att = _flat(ticks, T, 1.0)                 # everyone wants promotion...
    att[:, 2] = 0
    fast[:, 2] = 20                            # tenant2 below but cold: exempt
    found = PA.detect_protection_violation(fast, slow, [80, 80, 80],
                                           attempted=att,
                                           demotions=_flat(ticks, T))
    assert [p.tenant for p in found] == [0]


def test_detect_noisy_neighbor_needs_dominance_and_degradation():
    ticks, T = 200, 3
    promo = _flat(ticks, T); demo = _flat(ticks, T)
    lat = _flat(ticks, T, 1.0)
    promo[100:, 0] = 50                        # tenant0 dominates migrations
    lat[100:, 1] = 1.5                         # neighbor's latency degrades
    found = PA.detect_noisy_neighbor(promo, demo, lat)
    assert [p.tenant for p in found] == [0]
    # same dominance, no degradation -> silent
    assert PA.detect_noisy_neighbor(promo, demo, _flat(ticks, T, 1.0)) == []


def test_detect_promotion_stall():
    ticks, T = 100, 2
    att = _flat(ticks, T, 5.0)
    promo = _flat(ticks, T, 4.0)
    promo[:, 1] = 0.0                          # tenant1 never succeeds
    found = PA.detect_promotion_stall(att, promo)
    assert [p.tenant for p in found] == [1]
    assert found[0].evidence["success_ratio"] == 0.0


# -------------------------------------------------- engine integration ----
def test_engine_stats_and_ring_collected():
    from repro.core.simulator import simulate
    from repro.core.workloads import microbenchmark
    cfg = TieringConfig(n_tenants=2, n_fast_pages=128, n_slow_pages=256,
                        lower_protection=(48, 48), upper_bound=(0, 0),
                        obs_ring_capacity=256)
    r = simulate(cfg, [microbenchmark(100), microbenchmark(100)], 80,
                 k_max=32)
    s = r.tier_stats
    # every demotion ends a residency -> histogram mass == total demotions
    assert s["resid_hist"].sum() == r.demotions.sum()
    assert (s["promo_success"] <= s["promo_attempts"]).all()
    assert s["ticks"] == 80
    # ring holds promote+demote events, newest-first semantics
    n_mig = int(r.promotions.sum() + r.demotions.sum())
    assert len(r.migrations) == min(n_mig, 256)
    assert r.migrations_dropped == max(n_mig - 256, 0)
    assert (np.diff(r.migrations["tick"]) >= 0).all()
    dirs = set(r.migrations["direction"].tolist())
    assert dirs <= {OT.DIR_PROMOTE, OT.DIR_DEMOTE}


def test_tier_stat_export_includes_obs_fields():
    import jax.numpy as jnp
    from repro.core.engine import run_engine
    from repro.core.state import tier_stat
    from repro.core.workloads import build_trace, microbenchmark
    cfg = TieringConfig(n_tenants=2, n_fast_pages=128, n_slow_pages=256,
                        lower_protection=(48, 48), upper_bound=(0, 0))
    owner, acc, alive = build_trace(
        [microbenchmark(100), microbenchmark(100)], 60)
    final, _ = run_engine(cfg, owner, acc, alive, k_max=32)
    oh = jnp.asarray((owner[None, :] == np.arange(2)[:, None]).astype(np.float32))
    stat = tier_stat(final, oh)
    for key in ("resid_p50", "promo_success_ratio", "contended_frac",
                "throttled_frac", "thrash_rate"):
        assert key in stat, key
        assert np.asarray(stat[key]).shape == (2,)


def test_tier_stat_works_under_jit():
    """tier_stat stays a pure-jnp export usable on traced state."""
    import jax.numpy as jnp
    from repro.core.state import init_state, tier_stat
    cfg = TieringConfig(n_tenants=2)
    state = init_state(cfg, 16)
    oh = jnp.ones((2, 16), jnp.float32) / 2
    stat = jax.jit(lambda s: tier_stat(s, oh))(state)
    assert np.asarray(stat["resid_p50"]).shape == (2,)
    assert np.asarray(stat["demo_success_ratio"]).shape == (2,)


def test_demo_success_ratio_bounded_with_sync_demotions():
    """Step-6b sync upper-bound demotions count as attempts too (ratio <= 1)."""
    from repro.core.simulator import simulate
    from repro.core.workloads import microbenchmark, thrasher
    cfg = TieringConfig(n_tenants=2, n_fast_pages=128, n_slow_pages=256,
                        lower_protection=(0, 48), upper_bound=(12, 0))
    r = simulate(cfg, [thrasher(80, fast_share=12), microbenchmark(80)],
                 120, k_max=32)
    assert (r.tier_stats["demo_success_ratio"] <= 1.0 + 1e-6).all()
    assert r.tier_stats["demo_success"][0] > 0


# ------------------------------------------------------ fleet under vmap ----
def test_fleet_rollup_shapes_and_detection():
    from repro.core.workloads import (as_churn_slots, build_churn_schedule,
                                      cache_like, ci_like, microbenchmark,
                                      spark_like, thrasher, web_like)
    from repro.obs.fleet import fleet_rollout, stack_schedules
    from repro.obs.streaming import KINDS
    H, T, ticks = 4, 3, 120
    cfg = TieringConfig(n_tenants=T, n_fast_pages=256, n_slow_pages=256,
                        lower_protection=(64, 64, 64), upper_bound=(0, 0, 0),
                        migration_cost=0.005, obs_ring_capacity=128)
    # static rosters sharing per-slot footprints; pattern and arrival vary
    # per host
    mixes = [
        [microbenchmark(80, arrival=4), ci_like(80, arrival=8),
         web_like(64, arrival=1)],
        [spark_like(80, arrival=8), cache_like(80, arrival=2),
         spark_like(64, arrival=3)],
        [cache_like(80, arrival=7), cache_like(80, arrival=3),
         ci_like(64, arrival=4)],
        [web_like(80, arrival=0), spark_like(80, arrival=6),
         spark_like(64, arrival=4)],
    ]

    def rollout(cfg, mixes):
        want, rates = stack_schedules(
            [build_churn_schedule(as_churn_slots(m, ticks), ticks)
             for m in mixes])
        return fleet_rollout(cfg, want, rates, ticks, chunk=60, k_max=32)

    res = rollout(cfg, mixes)
    assert res.n_hosts == H and res.ticks == ticks
    for arr in (res.latency_mean, res.throughput_mean,
                res.migrations_per_tick):
        assert arr.shape == (H,)
    assert (res.latency_mean >= 1.0).all()
    for h in range(H):
        assert res.host_stats(h)["resid_hist"].shape == \
            (T, cfg.obs_resid_buckets)
    assert res.pathology_flag_ticks().shape == (H, T, len(KINDS))
    # per-host ring decodes independently
    ev, _ = res.host_migrations(0)
    assert ev.dtype == OT.EVENT_DTYPE
    # an injected noisy neighbor is flagged; this clean fleet is not
    assert res.tenants_flagged() == []
    noisy_mixes = [[thrasher(80, fast_share=12, arrival=40)] + m[1:]
                   for m in mixes]
    noisy = rollout(cfg.with_(upper_bound=(12, 0, 0)), noisy_mixes)
    # on every host, and only the injected tenant
    assert noisy.tenants_flagged("chronic_thrashing") == \
        [(h, 0) for h in range(H)]
    assert all(t == 0 for _, t in noisy.tenants_flagged())


# --------------------------------------------------- serving-path stats ----
def test_kv_step_collects_stats_and_ring():
    from repro.configs import get_smoke_config
    from repro.core.state import make_policy
    from repro.memtier import kvcache as KC
    from repro.memtier.tiering import equilibria_kv_step
    cfg = dataclasses.replace(get_smoke_config("llama32_1b"), dtype="float32")
    tcfg = TieringConfig(n_tenants=2, page_tokens=4, thrash_table_slots=64,
                         lower_protection=(2, 2), upper_bound=(3, 3),
                         obs_ring_capacity=64)
    B, seq = 4, 32
    cache = KC.init_cache(cfg, tcfg, B, seq)
    policy = make_policy(tcfg)
    # hand-place hot slow pages so the step promotes
    M = cache.page_tier.shape[1]
    slow_page = cache.slow_page.at[:, 0].set(0)
    page_tier = cache.page_tier.at[:, 0].set(1)
    cache = cache._replace(slow_page=slow_page, page_tier=page_tier,
                           seq_len=jnp.full((B,), 4, jnp.int32))
    B_, Mf = cache.fast_page.shape
    Ms = cache.slow_page.shape[1]
    fast_mass = jnp.zeros((B, Mf), jnp.float32)
    slow_mass = jnp.full((B, Ms), 10.0, jnp.float32)

    step = jax.jit(lambda c: equilibria_kv_step(
        c, fast_mass, slow_mass, tcfg, policy, fast_budget=B * M))
    out = step(cache)
    assert int(out.counters.promotions.sum()) > 0
    s = OS.stats_summary(out.stats)
    assert s["promo_attempts"].sum() >= s["promo_success"].sum() > 0
    events, _ = OT.decode_ring(out.ring)
    assert len(events) == int(out.counters.promotions.sum())
    assert (events["direction"] == OT.DIR_PROMOTE).all()
    # promoted slots carry a residency stamp for later exit accounting
    assert (np.asarray(out.stats.fast_since) >= 0).any()
