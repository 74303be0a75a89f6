"""Tenant-batched selection & reduction primitives — trace-time constant in T.

The engine's hot path repeatedly needs "take the `quota[t]` best pages of
every tenant t" (demotion picks coldest-first, promotion hottest-first),
"rank each tenant's new pages in index order" (allocation gating), and
per-tenant sums. The seed implementation unrolled a Python loop over tenants
at trace time — one `top_k` per tenant per call site, plus [T, L] one-hot
matmul reductions — so compile time, jaxpr size and kernel count all grew
linearly with T. Everything here is one fixed-size op chain regardless of T.

Two batched strategies, chosen at trace time from the static owner vector:

* **contiguous layout** (what `core/workloads.build_trace` always produces:
  tenant t owns pages [bounds[t], bounds[t+1])): selection copies T
  contiguous windows into padded [T, S] rows + ONE batched masked `top_k`;
  integer per-tenant sums reduce those rows, float sums and segmented
  index-ranks are a single `cumsum` + static boundary gathers. On one TPU
  v5e the [64, 4120] row build of a 262,120-page vector takes 64-72 us as
  window copies and 1.8-2.0 ms as a per-element gather.
* **generic fallback** (arbitrary owner permutation): one stable
  lexicographic sort by (segment, key) — `segment_ranks` — and per-bucket
  sums and lookups (`bucket_sum`, `bucket_lookup`: compare-and-reduce over
  [T+1, L] on the TPU, scatter-add and gather on the CPU). Still constant
  in T. Because the owner vector enters as a runtime array (never a trace
  constant), this is also the path the dynamic-ownership engine
  (core/churn.py) routes every churned layout through: the same compiled
  sort serves any ownership the lifecycle events produce.

Tie-breaking matches `jax.lax.top_k` exactly in both strategies ("lower
index wins" on equal scores), so results are bit-equal to the unrolled
reference (`select_top_quota_unrolled`, kept for the equivalence suite and
the scale benchmark's baseline).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Selection(NamedTuple):
    """Result of a per-tenant quota selection.

    ``mask`` is always present. The compact fields are set by the
    contiguous-rows strategy only: they expose the [T, k] candidate stream
    the batched top_k already produced, so downstream accounting (migration
    ring, residency histograms, thrash table) can run over T*k lanes instead
    of L — at L=256k that is the difference between ~1ms and ~30ms scatters.
    """
    mask: jax.Array                  # [L] bool: selected pages
    pages: Optional[jax.Array]       # [T, k] int32 page ids (or None)
    take: Optional[jax.Array]        # [T, k] bool: lane actually selected
    counts: Optional[jax.Array]      # [T] int32: selected per tenant


# ------------------------------------------------------ contiguous layout ----
class ContiguousLayout(NamedTuple):
    """Static (trace-time) description of a contiguous ownership layout."""
    n_tenants: int
    n_pages: int
    row_page: jax.Array    # [T, S] int32 page id per tenant row (pads clamped)
    row_valid: jax.Array   # [T, S] bool
    bounds: jax.Array      # [T+1] int32: tenant t owns [bounds[t], bounds[t+1])
    page_start: jax.Array  # [L] int32: segment start of each page's tenant


def plan_layout(owner: np.ndarray, n_tenants: int
                ) -> Optional[ContiguousLayout]:
    """Build the static layout if ``owner`` is sorted-contiguous, else None."""
    owner = np.asarray(owner)
    counts = np.bincount(owner, minlength=n_tenants)
    if counts.shape[0] > n_tenants:
        return None
    if not np.array_equal(owner, np.repeat(np.arange(n_tenants), counts)):
        return None
    L = owner.shape[0]
    S = max(int(counts.max()) if counts.size else 0, 1)
    bounds = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    col = np.arange(S)[None, :]
    row_page = bounds[:-1, None] + col
    row_valid = col < counts[:, None]
    row_page = np.where(row_valid, row_page, 0).astype(np.int32)
    return ContiguousLayout(
        n_tenants=n_tenants, n_pages=L,
        row_page=jnp.asarray(row_page), row_valid=jnp.asarray(row_valid),
        bounds=jnp.asarray(bounds),
        page_start=jnp.asarray(bounds[owner], jnp.int32))


def _padded_rows(x: jax.Array, layout: ContiguousLayout,
                 fill) -> jax.Array:
    """The padded [T, S] row view of an [L] vector: row t holds
    ``x[bounds[t]:bounds[t] + counts[t]]``, then ``fill``.

    One windowed gather of T contiguous width-S windows (``vmap`` of
    ``dynamic_slice``), so the trace is constant in T. ``x`` is first
    padded with S fill lanes: a window that ran past the end would be
    clamped, shifting the last tenant's row instead of raising."""
    S = layout.row_valid.shape[1]
    fill = jnp.asarray(fill, x.dtype)
    xp = jnp.concatenate([x, jnp.full((S,), fill)])
    win = jax.vmap(lambda b: jax.lax.dynamic_slice(xp, (b,), (S,)))(
        layout.bounds[:-1])
    return jnp.where(layout.row_valid, win, fill)


def select_top_quota_rows(score: jax.Array, active: jax.Array,
                          quotas: jax.Array, layout: ContiguousLayout,
                          k_cap: int) -> Selection:
    """Contiguous-layout quota select: window copies to [T, S] rows, one
    batched masked top_k, scatter the winners back. Bit-equal to the
    unrolled per-tenant top_k loop."""
    L = layout.n_pages
    T, S = layout.row_page.shape
    s2 = jnp.where(_padded_rows(active, layout, False),
                   _padded_rows(score, layout, -jnp.inf), -jnp.inf)
    k = min(k_cap, S)
    vals, cols = jax.lax.top_k(s2, k)
    take = (jnp.arange(k)[None, :] < quotas[:, None]) & jnp.isfinite(vals)
    pages = jnp.take_along_axis(layout.row_page, cols, axis=1)
    flat = jnp.where(take, pages, L).reshape(-1)       # L = OOB -> dropped
    mask = jnp.zeros((L,), bool).at[flat].set(True, mode="drop")
    return Selection(mask=mask, pages=pages, take=take,
                     counts=take.sum(axis=1).astype(jnp.int32))


def by_tenant_contiguous(x: jax.Array, layout: ContiguousLayout) -> jax.Array:
    """Per-tenant sum, O(L), no scatter.

    Integers sum associatively, so the int path reduces the padded rows
    (``_padded_rows``: window copies, 64-72 us for [64, 4120] rows on one
    TPU v5e) along S. Floats keep the original cumsum + boundary-gather
    association: the golden traces pin the f32 perf-model reductions
    bitwise, and a reassociated sum would shift them."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if jnp.issubdtype(x.dtype, jnp.integer):
        return _padded_rows(x, layout, 0).sum(axis=1, dtype=x.dtype)
    cs = jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)])
    return cs[layout.bounds[1:]] - cs[layout.bounds[:-1]]


def allocation_ranks_contiguous(new: jax.Array,
                                layout: ContiguousLayout) -> jax.Array:
    """Index-order rank of each new page among its tenant's new pages:
    exclusive cumsum minus the value at the (static) segment start."""
    L = new.shape[0]
    cs0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(new.astype(jnp.int32))])
    return cs0[:L] - cs0[layout.page_start]


# ------------------------------------------------------- generic (sorted) ----
def segment_ranks(seg: jax.Array, key: jax.Array, n_seg: int) -> jax.Array:
    """Within-segment rank of every element, ordered by (key asc, index asc).

    seg: [L] int32 segment id in [0, n_seg]; use ``n_seg`` as the sentinel
    for inactive elements (they still get ranks, callers just never select
    them). One stable lexicographic sort of length L regardless of the
    number of segments.
    """
    L = seg.shape[0]
    seg = seg.astype(jnp.int32)
    idx = jnp.arange(L, dtype=jnp.int32)
    seg_s, _, idx_s = jax.lax.sort((seg, key, idx), num_keys=2)
    counts = bucket_sum(jnp.ones((L,), jnp.int32), seg, n_seg + 1)
    starts = jnp.cumsum(counts) - counts          # exclusive prefix sum
    rank_s = jnp.arange(L, dtype=jnp.int32) - bucket_lookup(starts, seg_s)
    return jnp.zeros((L,), jnp.int32).at[idx_s].set(rank_s)


def select_top_quota(score: jax.Array, owner: jax.Array, active: jax.Array,
                     quotas: jax.Array, n_tenants: int,
                     k_cap: int) -> jax.Array:
    """Select up to quotas[t] highest-score active elements of each tenant
    for an ARBITRARY owner permutation (one composite sort). The per-tenant
    take is capped at ``min(k_cap, L)``, mirroring the unrolled top_k's
    window; non-finite scores are never selected."""
    L = score.shape[0]
    active = active & jnp.isfinite(score)
    seg = jnp.where(active, owner, n_tenants).astype(jnp.int32)
    ranks = segment_ranks(seg, -score, n_tenants)
    q = jnp.minimum(quotas.astype(jnp.int32), min(k_cap, L))
    q_ext = jnp.concatenate([q, jnp.zeros((1,), jnp.int32)])
    return active & (ranks < bucket_lookup(q_ext, seg))


def by_tenant_scatter(x: jax.Array, owner: jax.Array,
                      n_tenants: int) -> jax.Array:
    """Per-tenant sum for arbitrary owner vectors (scatter-add)."""
    return jnp.zeros((n_tenants,), x.dtype).at[owner].add(x)


def by_tenant_pooled(x: jax.Array, owner: jax.Array,
                     n_tenants: int) -> jax.Array:
    """Per-tenant sum tolerant of the free-pool sentinel ``owner ==
    n_tenants``: sentinel lanes land in a scratch bucket instead of being
    clipped onto the last real tenant (XLA's default scatter mode clips
    out-of-bounds indices). Integer sums are ``bucket_sum``s; floats keep
    the scatter-add, whose association the golden traces pin bitwise."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        return jnp.zeros((n_tenants + 1,), x.dtype).at[owner].add(
            x)[:n_tenants]
    return bucket_sum(x, owner, n_tenants + 1)[:n_tenants]


def _bucket_hits(idx: jax.Array, n: int) -> jax.Array:
    """[n, L] bool, lane axis minor: lane l falls in bucket b."""
    return idx[None, :] == jnp.arange(n, dtype=idx.dtype)[:, None]


def _bucket_sum_dense(x: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """``bucket_sum`` as one compare-and-reduce over [n, L]: the compare
    and select fuse into the reduce's input, so no [n, L] array is
    written."""
    return jnp.where(_bucket_hits(idx, n), x[None, :], 0).sum(
        axis=1, dtype=x.dtype)


def _bucket_lookup_dense(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``bucket_lookup`` with no gather: each lane's bit pattern is the one
    term of a compare-and-reduce over [n, L], so every dtype comes back
    bit for bit (-0.0, infinities and NaN payloads included). Ids outside
    [0, n) read 0."""
    if table.dtype == jnp.bool_:
        return _bucket_lookup_dense(table.astype(jnp.int32),
                                    idx).astype(jnp.bool_)
    bits = jnp.dtype(f"uint{8 * table.dtype.itemsize}")
    tb = jax.lax.bitcast_convert_type(table, bits)
    got = jnp.where(_bucket_hits(idx, table.shape[0]), tb[:, None],
                    jnp.zeros((), bits)).sum(axis=0, dtype=bits)
    return jax.lax.bitcast_convert_type(got, table.dtype)


# XLA lowers a TPU scatter-add, or a gather from a small table, to a
# near-serial loop over its L indices, while the dense forms above are
# vector work there: over 65 buckets and 386,048 lanes on one TPU v5e the
# sum takes 3,406 us as a scatter-add and 38 us dense, the lookup 3,096 us
# as a gather and 56 us dense. On XLA's CPU the scatter and the gather are
# the fast forms. All forms agree bit for bit, so the platform picks: the
# choice is made when the program is lowered, and the compiler never sees
# a conditional.
def bucket_sum(x: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """Per-bucket sum of an integer (or bool, counted as int32) [L] vector
    over bucket ids ``idx`` in [0, n). Integer addition is associative, so
    every form gives the same bits."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    return jax.lax.platform_dependent(
        x, idx, cpu=lambda x, i: jnp.zeros((n,), x.dtype).at[i].add(x),
        default=lambda x, i: _bucket_sum_dense(x, i, n))


def bucket_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for an [n] table and bucket ids ``idx`` in [0, n)."""
    return jax.lax.platform_dependent(
        table, idx, cpu=lambda t, i: t[i], default=_bucket_lookup_dense)


def select_global(score: jax.Array, mask: jax.Array, quota: jax.Array,
                  k_max: int) -> jax.Array:
    """Tenant-blind top-quota select (the TPP baseline's global scan)."""
    L = score.shape[0]
    k = min(k_max, L)
    s = jnp.where(mask, score, -jnp.inf)
    vals, idx = jax.lax.top_k(s, k)
    take = (jnp.arange(k) < quota) & jnp.isfinite(vals)
    return jnp.zeros((L,), bool).at[idx].set(take)


def pool_grant(free_mask: jax.Array, need: jax.Array) -> jax.Array:
    """Partition the free pool among tenants requesting pages (churn grant).

    free_mask: [L] bool — pages currently in the free pool; need: [T] int32
    pages each tenant wants granted this tick. Free pages are ranked in index
    order and tenant t receives the rank interval
    ``[cumsum(need)[t-1], cumsum(need)[t])`` — deterministic, one pass,
    constant in T. When the pool is over-subscribed the intervals simply run
    off the end of the pool: lower slot ids win (admission priority),
    trailing tenants get partial or empty grants.

    Returns [L] int32: the granting tenant id per page, or ``n_tenants``
    (the FREE sentinel) where no grant happens.
    """
    T = need.shape[0]
    rank = masked_rank(free_mask)
    cum = jnp.cumsum(need.astype(jnp.int32))
    tenant = jnp.searchsorted(cum, rank, side="right").astype(jnp.int32)
    granted = free_mask & (rank < cum[-1]) & (tenant < T)
    return jnp.where(granted, tenant, T)


def allocation_ranks(new: jax.Array, owner: jax.Array,
                     n_tenants: int) -> jax.Array:
    """Index-order rank of each new page among its tenant's new pages,
    arbitrary owner permutation. Values outside ``new`` are unspecified."""
    L = new.shape[0]
    seg = jnp.where(new, owner, n_tenants).astype(jnp.int32)
    return segment_ranks(seg, jnp.zeros((L,), jnp.int32), n_tenants)


# ------------------------------------------------------------------------
# Unrolled references (seed behavior). Kept verbatim so the equivalence
# suite can pin the batched implementations to them bit-exactly and the
# scale benchmark can measure the speedup against the real baseline.
# ------------------------------------------------------------------------
def masked_rank(mask: jax.Array) -> jax.Array:
    """Rank of each True element among True elements (by index order)."""
    return jnp.cumsum(mask.astype(jnp.int32)) - mask.astype(jnp.int32)


def select_top_quota_unrolled(score: jax.Array, masks: jax.Array,
                              quotas: jax.Array, k_max: int) -> jax.Array:
    """Per-tenant top_k unroll (one kernel per tenant). masks: [T, L]."""
    T, L = masks.shape
    sel = jnp.zeros((L,), jnp.int32)
    k = min(k_max, L)
    for ti in range(T):
        s = jnp.where(masks[ti], score, -jnp.inf)
        vals, idx = jax.lax.top_k(s, k)
        take = (jnp.arange(k) < quotas[ti]) & jnp.isfinite(vals)
        sel = sel.at[idx].max(take.astype(jnp.int32))
    return sel.astype(bool)


def allocation_ranks_unrolled(new: jax.Array, owner: jax.Array,
                              n_tenants: int) -> jax.Array:
    """Per-tenant masked-cumsum unroll (seed engine step 2)."""
    ranks = jnp.zeros(new.shape, jnp.int32)
    for ti in range(n_tenants):
        m = new & (owner == ti)
        ranks = jnp.where(m, masked_rank(m), ranks)
    return ranks


# ------------------------------------------------------------------------
# Selection strategies: the seam between the unified tick core (core/tick.py)
# and the per-tenant primitives above. A Strategy bundles the three
# owner-dependent operations the tick needs; every callable takes the
# *runtime* owner vector so one tick body serves both a trace-constant
# ownership (static engine — the owner argument is ignored in favor of the
# layout baked in at trace time) and ownership-as-state (churn engine).
# ------------------------------------------------------------------------
class Strategy(NamedTuple):
    """Owner-parameterized selection/reduction strategy for one tick flavor.

    by_tenant(x [L], owner [L]) -> [T] per-tenant sum
    select(score [L], owner [L], active [L], quotas [T]) -> Selection
    alloc_ranks(new [L], owner [L]) -> [L] index-order rank among the
        tenant's ``new`` pages (values outside ``new`` unspecified)

    The two optional members are fused-kernel upgrades (None on the jnp
    strategies; the tick core falls back to its composed jnp ops):

    alloc_stats(new [L], owner [L]) -> (ranks [L], counts [T]) — one fused
        pass producing both the allocation ranks and the per-tenant new-page
        counts (otherwise two separate reductions).
    move(tier [L], ring_data [C,5], head, sel: Selection, hotv [L],
         direction, to_tier, t) -> (tier', ring_data', head') — commits a
        compact selection as *the* page-move primitive: tier scatter +
        migration-ring append in one kernel pass, bit-identical to the
        separate ``jnp.where`` + ``obs/trace.ring_record``. Only set when
        ``select`` produces the compact [T, k] stream.
    """
    by_tenant: Callable[[jax.Array, jax.Array], jax.Array]
    select: Callable[..., Selection]
    alloc_ranks: Callable[[jax.Array, jax.Array], jax.Array]
    alloc_stats: Optional[Callable[..., tuple]] = None
    move: Optional[Callable[..., tuple]] = None


# engine impl -> kernel-wrapper impl (kernels/select, kernels/migrate):
# "pallas" lowers through Mosaic, "pallas_interpret" runs the same kernel
# bodies on the Pallas interpreter, "pallas_ref" compiles their jnp oracles
KERNEL_IMPLS = {"pallas": "pallas", "pallas_interpret": "pallas_interpret",
                "pallas_ref": "ref"}


def _check_impl(impl: str, allowed: tuple) -> None:
    if impl not in allowed:
        raise ValueError(f"unknown selection impl {impl!r}; expected one of "
                         f"{sorted(set(allowed) | set(KERNEL_IMPLS))}")


def static_strategy(owner: np.ndarray, n_tenants: int, k_max: int,
                    impl: str = "batched") -> Strategy:
    """Strategy for a trace-constant owner vector. Picks the fastest
    applicable primitive set (padded-row batched top_k for contiguous
    layouts, composite-sort fallback for arbitrary permutations, or the
    seed's unrolled per-tenant loops for the equivalence suite).
    ``impl="jnp"`` is an alias for the default "batched" path;
    "pallas"/"pallas_interpret"/"pallas_ref" route the selection core
    through the Pallas kernels (``kernels/select``, ``kernels/migrate``;
    "pallas_ref" runs the kernels' jnp oracles compiled by XLA — the
    kernel *algorithm* on backends without a Mosaic lowering)."""
    T = n_tenants
    if impl == "jnp":
        impl = "batched"
    if impl in KERNEL_IMPLS:
        return pallas_static_strategy(owner, n_tenants, k_max, impl)
    _check_impl(impl, ("batched", "unrolled"))
    owner_j = jnp.asarray(owner, jnp.int32)
    if impl == "unrolled":
        owner_oh = jnp.asarray(
            (owner[None, :] == np.arange(T)[:, None]).astype(np.float32))
        owner_oh_i = owner_oh.astype(jnp.int32)

        def by_tenant(x: jax.Array, _owner: jax.Array) -> jax.Array:
            m = owner_oh if jnp.issubdtype(x.dtype, jnp.floating) else owner_oh_i
            return m @ x

        def select(score, _owner, active, quotas):
            mask = select_top_quota_unrolled(
                score, owner_oh.astype(bool) & active[None], quotas, k_max)
            return Selection(mask, None, None, None)

        def alloc_ranks(new, _owner):
            return allocation_ranks_unrolled(new, owner_j, T)
    elif (layout := plan_layout(owner, T)) is not None:
        # contiguous ownership (build_trace's layout): padded-row top_k and
        # cumsum/boundary-gather reductions — the fastest path by far
        def by_tenant(x: jax.Array, _owner: jax.Array) -> jax.Array:
            return by_tenant_contiguous(x, layout)

        def select(score, _owner, active, quotas):
            return select_top_quota_rows(score, active, quotas, layout, k_max)

        def alloc_ranks(new, _owner):
            return allocation_ranks_contiguous(new, layout)
    else:
        # arbitrary owner permutation: composite-sort ranks + scatter-adds
        def by_tenant(x: jax.Array, _owner: jax.Array) -> jax.Array:
            return by_tenant_scatter(x, owner_j, T)

        def select(score, _owner, active, quotas):
            return Selection(
                select_top_quota(score, owner_j, active, quotas, T, k_max),
                None, None, None)

        def alloc_ranks(new, _owner):
            return allocation_ranks(new, owner_j, T)
    return Strategy(by_tenant, select, alloc_ranks)


def dynamic_strategy(n_tenants: int, k_max: int,
                     impl: str = "batched") -> Strategy:
    """Strategy for ownership-as-state: the owner vector is a runtime array
    (never a trace constant), so every call routes through the segment-sort
    fallback and the pool-sentinel-tolerant scatter reductions.
    "pallas"/"pallas_interpret"/"pallas_ref" swap the selection step for
    the tiled segmented top-k kernel (see ``pallas_dynamic_strategy``)."""
    if impl == "jnp":
        impl = "batched"
    if impl in KERNEL_IMPLS:
        return pallas_dynamic_strategy(n_tenants, k_max, impl)
    _check_impl(impl, ("batched",))
    T = n_tenants

    def by_tenant(x: jax.Array, owner: jax.Array) -> jax.Array:
        return by_tenant_pooled(x, owner, T)

    def select(score, owner, active, quotas):
        return Selection(
            select_top_quota(score, owner, active, quotas, T, k_max),
            None, None, None)

    def alloc_ranks(new, owner):
        return allocation_ranks(new, owner, T)

    return Strategy(by_tenant, select, alloc_ranks)


# ------------------------------------------------------------------------
# Pallas strategies: same seam, kernel-backed selection core. Bit-exactness
# contract (pinned by tests/test_select_kernels.py): the interpret-mode
# strategies produce ticks bitwise identical to the "batched" jnp default.
# Three facts make that possible without giving up kernel reordering
# freedom: (1) selection is compare-only — the segmented top-k's
# (score desc, index asc) extraction order is exactly ``jax.lax.top_k``'s
# "lower index wins" and the stable composite sort's tie-break; (2) the
# integer reductions (counts, usage, allocation ranks) are associative, so
# the kernels' tiled order is bit-equal to any jnp association; (3) the f32
# perf-model reductions are NOT reassociated — they stay on the
# golden-pinned jnp cumsum/scatter paths.
# ------------------------------------------------------------------------
def _static_rows(owner: np.ndarray, n_tenants: int) -> np.ndarray:
    """[T, S] page-id rows (index order within tenant, -1 pads) for an
    arbitrary trace-constant owner permutation."""
    owner = np.asarray(owner)
    L = owner.shape[0]
    counts = np.bincount(owner, minlength=n_tenants)[:n_tenants]
    S = max(int(counts.max()) if counts.size else 0, 1)
    rows = np.full((n_tenants, S), -1, np.int32)
    order = np.argsort(owner, kind="stable")
    seg = owner[order]
    starts = np.concatenate([[0], np.cumsum(counts)])
    rows[seg, np.arange(L) - starts[seg]] = order
    return rows


def _rows_select(KSEL, score, active, quotas, page_rows, valid_rows,
                 page_rows_pad, k: int, L: int, kimpl: str,
                 compact: bool) -> Selection:
    """Shared body: gather scores into [T, S] rows, run the segmented
    top-k kernel, scatter winners back to an [L] mask."""
    elig = valid_rows & active[page_rows]
    cols, take, counts = KSEL.seg_topk(score[page_rows], elig, quotas, k,
                                       impl=kimpl)
    pages = jnp.take_along_axis(page_rows_pad, cols, axis=1)
    flat = jnp.where(take, pages, L).reshape(-1)       # L = OOB -> dropped
    mask = jnp.zeros((L,), bool).at[flat].set(True, mode="drop")
    if not compact:
        # mask-only, matching the jnp generic path's Selection shape so the
        # [L]-lane downstream accounting (and the migration-ring event
        # order) stays bitwise identical
        return Selection(mask, None, None, None)
    return Selection(mask=mask, pages=pages, take=take, counts=counts)


def pallas_static_strategy(owner: np.ndarray, n_tenants: int, k_max: int,
                           impl: str = "pallas_interpret") -> Strategy:
    """Kernel-backed strategy for a trace-constant owner vector.

    Contiguous layouts get the full treatment: segmented top-k selection,
    fused rank+count reduction, and the ``commit_moves`` page-move kernel
    over the compact [T, k] stream. Arbitrary permutations still run the
    kernels over a precomputed [T, S] rowspace but return mask-only
    selections (the jnp generic path's shape), keeping event order
    bit-identical."""
    from repro.kernels.migrate import ops as KMIG
    from repro.kernels.select import ops as KSEL
    _check_impl(impl, tuple(KERNEL_IMPLS))
    kimpl = KERNEL_IMPLS[impl]
    T = n_tenants
    owner_np = np.asarray(owner)
    owner_j = jnp.asarray(owner_np, jnp.int32)
    L = owner_np.shape[0]
    layout = plan_layout(owner_np, T)
    contiguous = layout is not None
    if contiguous:
        page_rows, valid_rows = layout.row_page, layout.row_valid
        col_j = jnp.asarray(
            np.arange(L, dtype=np.int32) - np.asarray(layout.page_start))
    else:
        rows_np = _static_rows(owner_np, T)
        page_rows = jnp.asarray(np.maximum(rows_np, 0))
        valid_rows = jnp.asarray(rows_np >= 0)
    S = page_rows.shape[1]
    k = min(k_max, S)
    page_rows_pad = jnp.concatenate(
        [jnp.where(valid_rows, page_rows, L),
         jnp.full((T, 1), L, jnp.int32)], axis=1)

    def select(score, _owner, active, quotas):
        return _rows_select(KSEL, score, active, quotas, page_rows,
                            valid_rows, page_rows_pad, k, L, kimpl,
                            compact=contiguous)

    def by_tenant(x: jax.Array, _owner: jax.Array) -> jax.Array:
        if jnp.issubdtype(x.dtype, jnp.floating):
            # golden-pinned f32 association: keep the jnp reduction order
            return (by_tenant_contiguous(x, layout) if contiguous
                    else by_tenant_scatter(x, owner_j, T))
        xi = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
        return KSEL.seg_sums(xi[page_rows], valid_rows,
                             impl=kimpl).astype(xi.dtype)

    def alloc_stats(new, _owner):
        sums, pre = KSEL.seg_reduce(new.astype(jnp.int32)[page_rows],
                                    valid_rows, impl=kimpl)
        if contiguous:
            ranks = pre[owner_j, col_j]
        else:
            flat = jnp.where(valid_rows, page_rows, L).reshape(-1)
            ranks = jnp.zeros((L,), jnp.int32).at[flat].set(
                pre.reshape(-1), mode="drop")
        return ranks, sums

    def alloc_ranks(new, _owner):
        return alloc_stats(new, _owner)[0]

    move = None
    if contiguous:
        def move(tier, ring_data, head, sel: Selection, hotv, direction,
                 to_tier, t):
            # lane tenant from the Selection's own row shape: hotness
            # providers hand the tick compact streams of their *buffer*
            # width, not the strategy rowspace's k
            tenants = jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[:, None],
                sel.take.shape).reshape(-1)
            return KMIG.commit_moves(
                tier, ring_data, head, sel.pages.reshape(-1),
                sel.take.reshape(-1), tenants,
                hotv[sel.pages].reshape(-1), t, direction=direction,
                to_tier=to_tier, impl=kimpl)

    return Strategy(by_tenant, select, alloc_ranks, alloc_stats, move)


def pallas_dynamic_strategy(n_tenants: int, k_max: int,
                            impl: str = "pallas_interpret",
                            s_max: Optional[int] = None) -> Strategy:
    """Kernel-backed strategy for ownership-as-state. The rowspace is
    rebuilt every call from the runtime owner vector (one zero-key segment
    sort — the same primitive the jnp path spends on ranking — then a
    scatter into [T, S] rows), so the segmented top-k kernel replaces the
    composite-key sort proper. Equivalence-focused: the [T, S] rowspace
    defaults to S = L (``s_max`` caps it when the max per-tenant footprint
    is known), so the perf target remains the static contiguous strategy;
    reductions stay on the pool-sentinel-tolerant jnp scatters."""
    from repro.kernels.select import ops as KSEL
    _check_impl(impl, tuple(KERNEL_IMPLS))
    kimpl = KERNEL_IMPLS[impl]
    T = n_tenants

    def by_tenant(x: jax.Array, owner: jax.Array) -> jax.Array:
        return by_tenant_pooled(x, owner, T)

    def select(score, owner, active, quotas):
        L = score.shape[0]
        S = min(s_max, L) if s_max else L
        owned = owner < T
        seg = jnp.where(owned, owner, T).astype(jnp.int32)
        col = segment_ranks(seg, jnp.zeros((L,), jnp.int32), T)
        row = jnp.where(owned, seg, T)
        page_rows = jnp.full((T, S), L, jnp.int32).at[row, col].set(
            jnp.arange(L, dtype=jnp.int32), mode="drop")
        valid_rows = page_rows < L
        page_rows_pad = jnp.concatenate(
            [page_rows, jnp.full((T, 1), L, jnp.int32)], axis=1)
        return _rows_select(KSEL, score, active,
                            quotas, jnp.minimum(page_rows, L - 1),
                            valid_rows, page_rows_pad, min(k_max, S), L,
                            kimpl, compact=False)

    def alloc_ranks(new, owner):
        return allocation_ranks(new, owner, T)

    return Strategy(by_tenant, select, alloc_ranks)
