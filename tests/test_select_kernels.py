"""Interpret-mode equivalence suite for the Pallas selection-core kernels
(kernels/select, kernels/migrate.commit_moves) and the kernel-backed
strategies (select.pallas_static_strategy / pallas_dynamic_strategy).

Three layers, all bit-exact (integer outputs array_equal, f32 outputs
bitwise — the kernels are compare-only / integer-associative, and the
float perf model stays on the shared jnp path):

  1. kernel vs ref oracle: seeded properties over random shapes, scores
     with ties and -inf, zero quotas, k saturation, ring overflow.
  2. strategy vs the jnp "batched" strategy: contiguous and permuted
     static owners, dynamic owners with FREE-sentinel holes.
  3. whole simulation: run_engine / simulate_churn / hotness providers
     with impl="pallas_interpret" vs "batched", every SimResult field
     (including the decoded migration event ring) compared bitwise.

Property cases run under hypothesis when available, else the seeded
fallback (tests/proputil.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proputil import seeded_property
from repro.configs.base import TieringConfig
from repro.core import select as S
from repro.core.engine import run_engine
from repro.core.hotness import SketchSpec
from repro.core.simulator import churn_roster_config, simulate, simulate_churn
from repro.core.workloads import (build_trace, ci_like, microbenchmark,
                                  poisson_churn)
from repro.kernels.migrate.ops import commit_moves, migrate_pages
from repro.kernels.select.ops import seg_reduce, seg_sums, seg_topk
from repro.obs.trace import MigrationRing, ring_record


# ------------------------------------------------------ kernel-level refs ----
def _topk_case(rng):
    # shapes drawn from a small fixed set so compiled kernels are reused
    # across property cases (each new shape is a fresh interpret trace)
    T = int(rng.choice([1, 4, 9]))
    Sn = int(rng.choice([1, 7, 64, 130]))
    if rng.random() < 0.5:      # integer scores force tie-break agreement
        score = rng.integers(-4, 4, (T, Sn)).astype(np.float32)
    else:
        score = rng.standard_normal((T, Sn)).astype(np.float32)
    score[rng.random((T, Sn)) < 0.1] = -np.inf
    valid = rng.random((T, Sn)) < rng.choice([0.3, 0.8, 1.0])
    quotas = rng.integers(0, Sn + 3, T).astype(np.int32)
    quotas[rng.integers(0, T)] = 0
    k = int(rng.choice([1, 5, Sn + 2]))
    return jnp.asarray(score), jnp.asarray(valid), jnp.asarray(quotas), k


@seeded_property(n_fallback=16)
def test_seg_topk_interpret_bit_exact(seed):
    rng = np.random.default_rng(seed)
    score, valid, quotas, k = _topk_case(rng)
    br = int(rng.choice([2, 8]))
    ref = seg_topk(score, valid, quotas, k, impl="ref")
    out = seg_topk(score, valid, quotas, k, impl="pallas_interpret",
                   block_rows=br)
    for name, r, o in zip(("cols", "take", "counts"), ref, out):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r),
                                      err_msg=name)


@seeded_property(n_fallback=16)
def test_seg_reduce_interpret_bit_exact(seed):
    rng = np.random.default_rng(seed)
    T = int(rng.choice([1, 5, 8]))
    Sn = int(rng.choice([1, 64, 200]))
    x = jnp.asarray(rng.integers(-8, 8, (T, Sn)).astype(np.int32))
    valid = jnp.asarray(rng.random((T, Sn)) < rng.choice([0.0, 0.5, 1.0]))
    br = int(rng.choice([2, 8]))
    rs, rp = seg_reduce(x, valid, impl="ref")
    os_, op = seg_reduce(x, valid, impl="pallas_interpret", block_rows=br)
    np.testing.assert_array_equal(np.asarray(os_), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(op), np.asarray(rp))
    np.testing.assert_array_equal(
        np.asarray(seg_sums(x, valid, impl="pallas_interpret", block_rows=br)),
        np.asarray(seg_sums(x, valid, impl="ref")))


@seeded_property(n_fallback=16)
def test_commit_moves_interpret_bit_exact(seed):
    """Fused tier scatter + ring append: interpret == ref == the tick's
    original ring_record + drop-scatter composition, including ring
    overflow (N > capacity keeps the newest C) and sentinel-L lanes."""
    rng = np.random.default_rng(seed)
    L = int(rng.choice([8, 48]))
    C = int(rng.choice([1, 4, 8]))
    N = int(rng.choice([1, 16, 33]))
    tier = jnp.asarray(rng.integers(0, 2, L).astype(np.int32))
    data = jnp.asarray(rng.integers(-5, 5, (C, 5)).astype(np.int32))
    head = jnp.asarray(np.int32(rng.integers(0, 3 * C)))
    take_np = rng.random(N) < 0.5
    pages_np = np.where(take_np, rng.integers(0, L, N), L).astype(np.int32)
    pages, take = jnp.asarray(pages_np), jnp.asarray(take_np)
    tenants = jnp.asarray(rng.integers(0, 7, N).astype(np.int32))
    hot = jnp.asarray(rng.standard_normal(N).astype(np.float32))
    t = jnp.asarray(np.int32(rng.integers(0, 100)))
    direction = int(rng.integers(0, 2))
    to_tier = int(rng.integers(0, 2))
    ref = commit_moves(tier, data, head, pages, take, tenants, hot, t,
                       direction=direction, to_tier=to_tier, impl="ref")
    out = commit_moves(tier, data, head, pages, take, tenants, hot, t,
                       direction=direction, to_tier=to_tier,
                       impl="pallas_interpret")
    for name, r, o in zip(("tier", "ring_data", "head"), ref, out):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r),
                                      err_msg=name)
    # and vs the unfused jnp composition the tick core originally ran
    ring2 = ring_record(MigrationRing(data=data, head=head), take, pages,
                        tenants, hot, direction, t)
    tier2 = tier.at[jnp.where(take, pages, L)].set(to_tier, mode="drop")
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(tier2))
    np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(ring2.data))
    np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(ring2.head))


@seeded_property(n_fallback=8)
def test_migrate_page_block_variants(seed):
    """migrate_pages tiling is parameterized: every page_block (including
    non-divisors, which the kernel rounds down) matches the ref."""
    rng = np.random.default_rng(seed)
    l = int(rng.choice([1, 4, 6]))
    b = int(rng.choice([1, 4]))
    msrc, mdst = int(rng.choice([2, 5])), int(rng.choice([2, 5]))
    src = jnp.asarray(rng.standard_normal((l, b, msrc, 2, 2, 8)), jnp.float32)
    dstn = rng.standard_normal((l, b, mdst, 2, 2, 8)).astype(np.float32)
    si = jnp.asarray(rng.integers(0, msrc, b), jnp.int32)
    di = jnp.asarray(rng.integers(0, mdst, b), jnp.int32)
    sel = jnp.asarray(rng.integers(0, 2, b).astype(bool))
    ref = migrate_pages(src, jnp.asarray(dstn), si, di, sel, impl="ref")
    for pb in (1, 3, 8):        # dst_pool is donated: fresh array per call
        out = migrate_pages(src, jnp.asarray(dstn), si, di, sel,
                            impl="pallas_interpret", page_block=pb)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=f"page_block={pb}")


# --------------------------------------------------------------- edge pins ----
def test_seg_topk_zero_quota_and_all_invalid():
    score = jnp.asarray(np.ones((2, 16), np.float32))
    valid = jnp.asarray(np.array([[True] * 16, [False] * 16]))
    quotas = jnp.asarray(np.array([0, 16], np.int32))
    cols, take, counts = seg_topk(score, valid, quotas, 8,
                                  impl="pallas_interpret")
    assert not np.asarray(take).any()
    np.testing.assert_array_equal(np.asarray(counts), [0, 0])
    np.testing.assert_array_equal(np.asarray(cols), np.full((2, 8), 16))


def test_seg_topk_tie_break_lowest_index():
    """Duplicate scores resolve to the lowest column (lax.top_k order)."""
    score = jnp.asarray(np.zeros((1, 32), np.float32))
    valid = jnp.asarray(np.ones((1, 32), bool))
    cols, take, counts = seg_topk(score, valid, jnp.asarray([4]), 8,
                                  impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(cols)[0, :4], [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(take)[0],
                                  [True] * 4 + [False] * 4)
    assert int(counts[0]) == 4


def test_seg_topk_saturation():
    """quota > eligible -> counts == eligible; quota > k -> counts == k."""
    score = jnp.asarray(np.arange(12, dtype=np.float32)[None])
    valid = jnp.asarray((np.arange(12) % 2 == 0)[None])   # 6 eligible
    _, _, counts = seg_topk(score, valid, jnp.asarray([100]), 12,
                            impl="pallas_interpret")
    assert int(counts[0]) == 6
    _, take, counts = seg_topk(score, jnp.asarray(np.ones((1, 12), bool)),
                               jnp.asarray([100]), 5,
                               impl="pallas_interpret")
    assert int(counts[0]) == 5 and int(np.asarray(take).sum()) == 5


def test_commit_moves_all_sentinel_is_noop():
    """A fully-untaken compact stream writes neither tier nor ring."""
    tier = jnp.asarray(np.zeros(8, np.int32))
    data = jnp.asarray(np.full((4, 5), -1, np.int32))
    out = commit_moves(tier, data, jnp.asarray(np.int32(0)),
                       jnp.asarray(np.full(6, 8, np.int32)),
                       jnp.asarray(np.zeros(6, bool)),
                       jnp.asarray(np.zeros(6, np.int32)),
                       jnp.asarray(np.zeros(6, np.float32)),
                       jnp.asarray(np.int32(3)), direction=1, to_tier=1,
                       impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(out[0]), np.zeros(8))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(data))
    assert int(out[2]) == 0


# ---------------------------------------------------------- strategy level ----
def _strategy_select_case(rng, T, owner):
    L = owner.shape[0]
    score = (rng.integers(-3, 3, L) if rng.random() < 0.5
             else rng.standard_normal(L)).astype(np.float32)
    active = rng.random(L) < rng.choice([0.3, 0.8, 1.0])
    quotas = rng.integers(0, L // max(T, 1) + 4, T).astype(np.int32)
    quotas[rng.integers(0, T)] = 0
    return jnp.asarray(score), jnp.asarray(active), jnp.asarray(quotas)


@seeded_property(n_fallback=10)
def test_static_strategy_contiguous_bit_exact(seed):
    rng = np.random.default_rng(seed)
    T = int(rng.choice([1, 3, 6]))
    counts = rng.choice([4, 17, 29], T)
    owner = np.repeat(np.arange(T), counts).astype(np.int32)
    k_max = int(rng.choice([3, 16, 64]))
    score, active, quotas = _strategy_select_case(rng, T, owner)
    base = S.static_strategy(owner, T, k_max, impl="batched")
    kern = S.static_strategy(owner, T, k_max, impl="pallas_interpret")
    a = base.select(score, jnp.asarray(owner), active, quotas)
    b = kern.select(score, jnp.asarray(owner), active, quotas)
    np.testing.assert_array_equal(np.asarray(b.mask), np.asarray(a.mask))
    np.testing.assert_array_equal(np.asarray(b.counts), np.asarray(a.counts))
    # the compact stream is consistent with the mask
    L = owner.shape[0]
    flat = np.where(np.asarray(b.take), np.asarray(b.pages), L).ravel()
    mask = np.zeros(L + 1, bool)
    mask[flat] = True
    np.testing.assert_array_equal(mask[:L], np.asarray(a.mask))
    # fused reductions agree with the jnp strategy
    xi = jnp.asarray(rng.integers(0, 5, L).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(kern.by_tenant(xi, jnp.asarray(owner))),
        np.asarray(base.by_tenant(xi, jnp.asarray(owner))))
    new = jnp.asarray(rng.random(L) < 0.4)
    ra, ca = kern.alloc_stats(new, jnp.asarray(owner))
    rr = base.alloc_ranks(new, jnp.asarray(owner))
    np.testing.assert_array_equal(
        np.asarray(ra)[np.asarray(new)], np.asarray(rr)[np.asarray(new)])
    np.testing.assert_array_equal(
        np.asarray(ca), np.asarray(base.by_tenant(
            new.astype(jnp.int32), jnp.asarray(owner))))


@seeded_property(n_fallback=8)
def test_static_strategy_permuted_bit_exact(seed):
    """Arbitrary owner permutations: mask-only selections stay bit-equal."""
    rng = np.random.default_rng(seed)
    T = int(rng.choice([1, 3, 6]))
    L = int(rng.choice([24, 61]))
    owner = rng.integers(0, T, L).astype(np.int32)
    score, active, quotas = _strategy_select_case(rng, T, owner)
    base = S.static_strategy(owner, T, 16, impl="batched")
    kern = S.static_strategy(owner, T, 16, impl="pallas_interpret")
    a = base.select(score, jnp.asarray(owner), active, quotas)
    b = kern.select(score, jnp.asarray(owner), active, quotas)
    if S.plan_layout(owner, T) is None:     # genuinely non-contiguous
        assert b.pages is None  # mask-only, like the jnp generic path
    np.testing.assert_array_equal(np.asarray(b.mask), np.asarray(a.mask))
    np.testing.assert_array_equal(np.asarray(b.counts), np.asarray(a.counts))
    xi = jnp.asarray(rng.integers(0, 5, L).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(kern.by_tenant(xi, jnp.asarray(owner))),
        np.asarray(base.by_tenant(xi, jnp.asarray(owner))))


@seeded_property(n_fallback=8)
def test_dynamic_strategy_holes_bit_exact(seed):
    """Runtime owner vectors with FREE-sentinel (owner == T) holes."""
    rng = np.random.default_rng(seed)
    T = int(rng.choice([1, 3, 5]))
    L = int(rng.choice([16, 57]))
    owner = rng.integers(0, T + 1, L).astype(np.int32)   # T = free pool
    score, active, quotas = _strategy_select_case(rng, T, owner)
    base = S.dynamic_strategy(T, 16, impl="batched")
    kern = S.dynamic_strategy(T, 16, impl="pallas_interpret")
    a = base.select(score, jnp.asarray(owner), active, quotas)
    b = kern.select(score, jnp.asarray(owner), active, quotas)
    np.testing.assert_array_equal(np.asarray(b.mask), np.asarray(a.mask))
    np.testing.assert_array_equal(np.asarray(b.counts), np.asarray(a.counts))


@pytest.mark.parametrize("build", [
    lambda impl: S.static_strategy(np.zeros(8, np.int32), 1, 4, impl=impl),
    lambda impl: S.dynamic_strategy(1, 4, impl=impl),
    lambda impl: S.pallas_static_strategy(np.zeros(8, np.int32), 1, 4, impl),
    lambda impl: S.pallas_dynamic_strategy(1, 4, impl),
    lambda impl: seg_sums(jnp.zeros((1, 8), jnp.int32),
                          jnp.ones((1, 8), bool), impl=impl),
], ids=["static", "dynamic", "pallas_static", "pallas_dynamic", "kernel"])
def test_unknown_impl_raises(build):
    """A misspelt backend is an error, never a silent interpret-mode run."""
    with pytest.raises(ValueError, match="impl"):
        build("palas")


# ----------------------------------------------------------- whole engine ----
def _assert_simresult_equal(a, b):
    for f in ("fast_usage", "slow_usage", "promotions", "demotions",
              "throughput", "latency", "promo_scale", "thrash_events",
              "attempted", "pool_free"):
        x, y = getattr(a, f), getattr(b, f)
        if x is None or y is None:
            assert x is None and y is None, f
            continue
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x),
                                      err_msg=f)
    assert a.migrations_dropped == b.migrations_dropped
    np.testing.assert_array_equal(b.migrations, a.migrations)
    assert set(a.tier_stats) == set(b.tier_stats)
    for k in a.tier_stats:
        np.testing.assert_array_equal(np.asarray(b.tier_stats[k]),
                                      np.asarray(a.tier_stats[k]), err_msg=k)


def _small_static():
    cfg = TieringConfig(n_tenants=3, n_fast_pages=256, n_slow_pages=256,
                        lower_protection=(96, 96, 0),
                        upper_bound=(0, 120, 0))
    tenants = [microbenchmark(150), microbenchmark(140, arrival=10),
               ci_like(120, phase_len=20)]
    return cfg, tenants


@pytest.mark.parametrize("mode", ["equilibria", "tpp", "memtis", "static"])
def test_engine_pallas_interpret_matches_batched(mode):
    """Whole-trace equivalence on all four policy modes: every TickOutput
    field of the kernel tick is bit-equal to the jnp tick — floats
    included (the perf model runs the same jnp ops in both)."""
    cfg, tenants = _small_static()
    owner, acc, alive = build_trace(tenants, 40)
    _, a = run_engine(cfg, owner, acc, alive, mode=mode, k_max=64,
                      impl="batched")
    _, b = run_engine(cfg, owner, acc, alive, mode=mode, k_max=64,
                      impl="pallas_interpret")
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(b, f)),
                                      np.asarray(getattr(a, f)), err_msg=f)


@pytest.mark.parametrize("impl", ["jnp", "pallas_ref"])
def test_engine_impl_aliases_match_batched(impl):
    """impl="jnp" is the batched path verbatim; impl="pallas_ref" runs the
    kernel algorithm through its compiled jnp oracle (the CPU/GPU fast
    path) and must also be bit-exact."""
    cfg, tenants = _small_static()
    owner, acc, alive = build_trace(tenants, 10)
    _, a = run_engine(cfg, owner, acc, alive, k_max=32, impl="batched")
    _, b = run_engine(cfg, owner, acc, alive, k_max=32, impl=impl)
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(b, f)),
                                      np.asarray(getattr(a, f)), err_msg=f)


def test_churn_pallas_interpret_matches_batched():
    """Dynamic-ownership engine (runtime owner vector, free pool, lifecycle
    events) through the kernel strategy: full SimResult bitwise equal,
    migration event ring included."""
    slots = poisson_churn(n_slots=4, ticks=60, seed=3)
    cfg = churn_roster_config(slots)
    a = simulate_churn(cfg, slots, 60, mode="equilibria", k_max=32,
                       impl="batched")
    b = simulate_churn(cfg, slots, 60, mode="equilibria", k_max=32,
                       impl="pallas_interpret")
    _assert_simresult_equal(a, b)


def test_hotness_sketch_pallas_matches_batched():
    """Sketch-provider compact streams (provider buffer width, not the
    strategy rowspace) flow through the commit_moves kernel bit-exactly —
    pins the lane-tenant derivation in the strategy's move hook."""
    cfg, tenants = _small_static()
    spec = SketchSpec(depth=2, width=1024, n_cand=16, n_cold=16, probe=256)
    a = simulate(cfg, tenants, 25, k_max=32, impl="batched", hotness=spec)
    b = simulate(cfg, tenants, 25, k_max=32, impl="pallas_interpret",
                 hotness=spec)
    _assert_simresult_equal(a, b)
