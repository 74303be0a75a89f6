"""Equivalence suite: the tenant-batched selection engine (core/select.py,
engine impl="batched") is pinned bit-exactly to the seed's per-tenant
unrolled loops (impl="unrolled") — randomized scores, quotas (zero, partial,
over-supply), masks, and tie cases, for T in {1, 3, 8} — plus trace-time
T-independence of the batched tick's jaxpr."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TieringConfig
from repro.core import select as S
from repro.core.engine import make_tick, run_engine
from repro.core.state import init_state
from repro.core.workloads import build_trace, ci_like, microbenchmark

L = 96  # fixed so every parametrized case reuses one compiled shape per T


def _unrolled_select(score, owner, active, quotas, T, k_cap):
    masks = jnp.asarray((owner[None] == np.arange(T)[:, None]) & active[None])
    return S.select_top_quota_unrolled(jnp.asarray(score), masks,
                                       jnp.asarray(quotas), k_cap)


def _batched_select(score, owner, active, quotas, T, k_cap):
    return S.select_top_quota(jnp.asarray(score), jnp.asarray(owner),
                              jnp.asarray(active), jnp.asarray(quotas), T,
                              k_cap)


@pytest.mark.parametrize("T", [1, 3, 8])
@pytest.mark.parametrize("seed", range(8))
def test_select_randomized_bit_exact(T, seed):
    rng = np.random.default_rng(1000 * T + seed)
    owner = rng.integers(0, T, L).astype(np.int32)
    # half the cases use integer-valued scores so duplicates force the
    # top_k/stable-sort tie-break (lower index wins) to agree
    if seed % 2 == 0:
        score = rng.integers(-4, 4, L).astype(np.float32)
    else:
        score = rng.standard_normal(L).astype(np.float32)
    active = rng.random(L) < rng.choice([0.2, 0.6, 1.0])
    if T >= 3:
        active &= owner != 1          # one tenant fully masked out
    # quotas mix: zero, partial, and over-supply (more than active pages)
    quotas = rng.integers(0, 2 * L, T).astype(np.int32)
    quotas[rng.integers(0, T)] = 0
    k_cap = int(rng.choice([3, 17, L + 8]))
    a = _batched_select(score, owner, active, quotas, T, k_cap)
    b = _unrolled_select(score, owner, active, quotas, T, k_cap)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("T", [1, 3, 8])
@pytest.mark.parametrize("seed", range(8))
def test_select_rows_contiguous_bit_exact(T, seed):
    """The padded-rows strategy (contiguous layouts) vs the unrolled loop."""
    rng = np.random.default_rng(7000 * T + seed)
    counts = rng.integers(0, 2 * L // max(T, 1), T)
    owner = np.repeat(np.arange(T), counts).astype(np.int32)
    Lc = owner.shape[0]
    if Lc == 0:
        owner = np.zeros(1, np.int32)
        Lc = 1
    layout = S.plan_layout(owner, T)
    assert layout is not None
    score = (rng.integers(-3, 3, Lc) if seed % 2 == 0
             else rng.standard_normal(Lc)).astype(np.float32)
    active = rng.random(Lc) < rng.choice([0.3, 1.0])
    quotas = rng.integers(0, Lc + 4, T).astype(np.int32)
    k_cap = int(rng.choice([2, 19, Lc + 8]))
    sel = S.select_top_quota_rows(jnp.asarray(score), jnp.asarray(active),
                                  jnp.asarray(quotas), layout, k_cap)
    masks = (owner[None] == np.arange(T)[:, None]) & active[None]
    ref = S.select_top_quota_unrolled(jnp.asarray(score), jnp.asarray(masks),
                                      jnp.asarray(quotas), k_cap)
    np.testing.assert_array_equal(np.asarray(sel.mask), np.asarray(ref))
    # the compact stream agrees with the mask
    np.testing.assert_array_equal(np.asarray(sel.counts),
                                  masks.astype(np.int64) @ np.asarray(ref))


ROW_ROSTERS = {                   # pages per tenant, in layout order
    "equal": (5, 5, 5, 5),
    "last_shorter": (6, 6, 6, 2),   # the last window runs past L
    "last_longest": (3, 4, 2, 9),
    "single": (7,),
    "zero_pages": (4, 0, 6, 0),     # empty tenants, one of them last
}
ROW_DTYPES = {"f32": (np.float32, -np.inf), "int32": (np.int32, 0),
              "bool": (np.bool_, False)}


@pytest.mark.parametrize("dtype", sorted(ROW_DTYPES))
@pytest.mark.parametrize("roster", sorted(ROW_ROSTERS))
def test_padded_rows_match_element_gather(roster, dtype):
    """The window-copy row builder equals the per-element gather through
    ``row_page`` with the ``row_valid`` fill, bit for bit."""
    counts = ROW_ROSTERS[roster]
    owner = np.repeat(np.arange(len(counts)), counts).astype(np.int32)
    layout = S.plan_layout(owner, len(counts))
    assert layout is not None
    np_dtype, fill = ROW_DTYPES[dtype]
    rng = np.random.default_rng(len(owner))
    x = (rng.random(owner.shape[0]) < 0.5 if np_dtype is np.bool_
         else rng.standard_normal(owner.shape[0]) * 100).astype(np_dtype)
    xj = jnp.asarray(x)
    got = np.asarray(S._padded_rows(xj, layout, fill))
    want = np.asarray(jnp.where(layout.row_valid, xj[layout.row_page],
                                jnp.asarray(fill, xj.dtype)))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


def test_plan_layout_rejects_non_contiguous():
    assert S.plan_layout(np.array([0, 1, 0, 1], np.int32), 2) is None
    assert S.plan_layout(np.array([1, 1, 0, 0], np.int32), 2) is None
    assert S.plan_layout(np.array([0, 0, 1, 1], np.int32), 2) is not None
    assert S.plan_layout(np.array([0, 0, 2, 2], np.int32), 3) is not None


@pytest.mark.parametrize("T", [1, 3, 8])
def test_allocation_ranks_match_unrolled(T):
    rng = np.random.default_rng(T)
    for seed in range(6):
        owner = rng.integers(0, T, L).astype(np.int32)
        new = rng.random(L) < rng.choice([0.0, 0.3, 1.0])
        ra = S.allocation_ranks(jnp.asarray(new), jnp.asarray(owner), T)
        rb = S.allocation_ranks_unrolled(jnp.asarray(new), jnp.asarray(owner),
                                         T)
        # ranks of non-new pages are unspecified in the batched version
        np.testing.assert_array_equal(np.asarray(ra)[new], np.asarray(rb)[new])


@pytest.mark.parametrize("mode", ["equilibria", "memtis", "tpp"])
def test_engine_batched_matches_unrolled(mode):
    """Whole-tick equivalence over a real trace: every integer output of the
    batched engine is bit-equal to the seed's unrolled engine."""
    cfg = TieringConfig(n_tenants=3, n_fast_pages=256, n_slow_pages=256,
                        lower_protection=(96, 96, 0),
                        upper_bound=(0, 120, 0))
    tenants = [microbenchmark(150), microbenchmark(140, arrival=10),
               ci_like(120, phase_len=20)]
    owner, acc, alive = build_trace(tenants, 80)
    _, a = run_engine(cfg, owner, acc, alive, mode=mode, k_max=64,
                      impl="batched")
    _, b = run_engine(cfg, owner, acc, alive, mode=mode, k_max=64,
                      impl="unrolled")
    for f in ("fast_usage", "slow_usage", "promotions", "demotions",
              "thrash_events", "attempted_promotions", "fast_free",
              "promo_scale"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)
    # float perf model: scatter-add vs matmul reduction order may differ
    np.testing.assert_allclose(np.asarray(a.latency), np.asarray(b.latency),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a.throughput),
                               np.asarray(b.throughput), rtol=1e-5)


def _tick_build(impl):
    def build(T):
        Lp = 16 * T
        owner = np.arange(Lp, dtype=np.int32) % T
        cfg = TieringConfig(n_tenants=T, n_fast_pages=Lp // 2,
                            lower_protection=(4,) * T, upper_bound=(8,) * T)
        tick = make_tick(cfg, owner, "equilibria", k_max=8, impl=impl)
        state = init_state(cfg, Lp)
        return tick, (state, (jnp.zeros((Lp,), jnp.float32),
                              jnp.ones((Lp,), bool)))
    return build


def test_batched_tick_trace_is_T_independent():
    """The batched tick's jaxpr signature (eqn count + primitive histogram,
    sub-jaxprs included) is identical for T=2 and T=16, with zero top_k
    ops on the equilibria path; the unrolled tick grows."""
    from repro.analysis.constancy import (assert_jaxpr_constant,
                                          sweep_signatures)

    sig = assert_jaxpr_constant(_tick_build("batched"), (2, 16),
                                label="batched tick: tenant count")
    assert sig.histogram().get("top_k", 0) == 0   # equilibria: no top_k ops

    (_, un_small), (_, un_big) = sweep_signatures(
        _tick_build("unrolled"), (2, 16))
    assert un_small != un_big                     # unrolled impl DOES grow
    assert un_big.histogram().get("top_k", 0) > \
        un_small.histogram().get("top_k", 0)
    assert un_big.n_eqns > un_small.n_eqns


# ---------------------------------------------- per-bucket dense forms ----
# ``bucket_sum`` and ``bucket_lookup`` pick their form by platform: the CPU
# runs the scatter-add and the gather, the TPU the dense compare-and-reduce.
# These cases call the dense forms directly, so a CPU run checks the path
# the chip takes against the CPU's.
LB = 1000                         # not a multiple of 128


def _owners(T, case, rng):
    if case == "all_sentinel":
        return np.full(LB, T, np.int32)
    owner = rng.integers(0, T + 1, LB).astype(np.int32)   # T = pool sentinel
    if case == "empty_tenants":
        owner[owner % 2 == 1] = T        # odd tenants own nothing
    return owner


@pytest.mark.parametrize("case", ["random", "all_sentinel", "empty_tenants"])
@pytest.mark.parametrize("dtype", ["bool", "int32"])
@pytest.mark.parametrize("T", [1, 16, 64])
def test_bucket_sum_dense_matches_scatter_add(T, dtype, case):
    rng = np.random.default_rng(T)
    owner = jnp.asarray(_owners(T, case, rng))
    x = (rng.random(LB) < 0.5 if dtype == "bool"
         else rng.integers(-2 ** 30, 2 ** 30, LB, dtype=np.int32))
    xi = jnp.asarray(x).astype(jnp.int32)
    want = np.asarray(jnp.zeros((T + 1,), jnp.int32).at[owner].add(xi))
    dense = np.asarray(jax.jit(S._bucket_sum_dense, static_argnums=2)(
        xi, owner, T + 1))
    got = np.asarray(jax.jit(S.bucket_sum, static_argnums=2)(
        jnp.asarray(x), owner, T + 1))
    assert dense.dtype == got.dtype == np.int32
    np.testing.assert_array_equal(dense, want)
    np.testing.assert_array_equal(got, want)
    if case != "random":
        assert (want[:T][1::2] == 0).all()


SPECIAL_F32 = np.array([-0.0, 0.0, np.inf, -np.inf, np.nan,
                        np.frombuffer(np.uint32(0x7FC01234).tobytes(),
                                      np.float32)[0]], np.float32)


@pytest.mark.parametrize("dtype", ["int32", "float32", "bool"])
@pytest.mark.parametrize("T", [1, 16, 64])
def test_bucket_lookup_dense_is_bitwise(T, dtype):
    rng = np.random.default_rng(100 + T)
    idx = jnp.asarray(rng.integers(0, T + 1, LB).astype(np.int32))
    if dtype == "float32":
        table = rng.standard_normal(T + 1).astype(np.float32)
        table[:len(SPECIAL_F32)] = SPECIAL_F32[:T + 1]
    elif dtype == "int32":
        table = rng.integers(-2 ** 31, 2 ** 31 - 1, T + 1, dtype=np.int32)
    else:
        table = rng.random(T + 1) < 0.5
    tab = jnp.asarray(table)
    want = np.asarray(tab[idx])
    for fn in (S._bucket_lookup_dense, S.bucket_lookup):
        got = np.asarray(jax.jit(fn)(tab, idx))
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


@pytest.fixture
def dense_forms(monkeypatch):
    """Route ``bucket_sum``/``bucket_lookup`` through the TPU's dense forms
    on any platform; yields the names of the forms traced."""
    traced = []

    def bucket_sum(x, idx, n):
        traced.append("sum")
        x = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
        return S._bucket_sum_dense(x, idx, n)

    def bucket_lookup(table, idx):
        traced.append("lookup")
        return S._bucket_lookup_dense(table, idx)
    monkeypatch.setattr(S, "bucket_sum", bucket_sum)
    monkeypatch.setattr(S, "bucket_lookup", bucket_lookup)
    return traced


@pytest.mark.parametrize("seed", range(4))
def test_segment_select_through_dense_forms(seed, request):
    """``segment_ranks`` and ``select_top_quota`` give the same bits with
    the dense forms as with the CPU's scatter and gather, on random owner
    permutations with pool-sentinel lanes and tied scores."""
    rng = np.random.default_rng(500 + seed)
    T = int(rng.choice([1, 5, 16]))
    owner = rng.integers(0, T + 1, L).astype(np.int32)
    score = rng.integers(-3, 3, L).astype(np.float32)
    active = rng.random(L) < 0.7
    quotas = rng.integers(0, L // 2, T).astype(np.int32)
    key = rng.integers(-4, 4, L).astype(np.int32)

    def run():
        ranks = S.segment_ranks(jnp.asarray(owner), jnp.asarray(key), T)
        sel = S.select_top_quota(jnp.asarray(score), jnp.asarray(owner),
                                 jnp.asarray(active), jnp.asarray(quotas),
                                 T, 17)
        sums = S.by_tenant_pooled(jnp.asarray(active).astype(jnp.int32),
                                  jnp.asarray(owner), T)
        return [np.asarray(a) for a in (ranks, sel, sums)]

    base = run()
    traced = request.getfixturevalue("dense_forms")
    for a, b in zip(run(), base):
        np.testing.assert_array_equal(a, b)
    assert {"sum", "lookup"} <= set(traced)


def test_churn_tick_through_dense_forms(request):
    """A whole churned run with the TPU's dense forms equals the CPU's
    scatter/gather run bit for bit: every state leaf and every output."""
    from repro.core.churn import ChurnSchedule, run_churn_engine
    cfg = TieringConfig(n_tenants=4, n_fast_pages=48, n_slow_pages=112,
                        lower_protection=(12, 12, 0, 0),
                        upper_bound=(0, 20, 0, 0))
    rng = np.random.default_rng(7)
    want = rng.integers(0, 30, (16, 4)).astype(np.int32)
    want[rng.random(want.shape) < 0.3] = 0
    rates = (rng.random((16, 4, 30)) * 5.0).astype(np.float32)
    sched = ChurnSchedule(want=want, rates=rates)
    base = run_churn_engine(cfg, sched, k_max=32)
    traced = request.getfixturevalue("dense_forms")
    dense = run_churn_engine(cfg, sched, k_max=32)
    assert {"sum", "lookup"} <= set(traced)
    flat_d, tree_d = jax.tree_util.tree_flatten(dense)
    flat_b, tree_b = jax.tree_util.tree_flatten(base)
    assert tree_d == tree_b
    for a, b in zip(flat_d, flat_b):
        np.testing.assert_array_equal(np.atleast_1d(a).view(np.uint8),
                                      np.atleast_1d(b).view(np.uint8))
