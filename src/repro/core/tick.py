"""The unified tick core: ONE regulated promotion/demotion pipeline serving
every deployment shape the paper targets.

Equilibria's contribution is a single control plane (hotness -> Eq.1
demotion scan -> Eq.2 promotion scan -> upper-bound sync demotion -> thrash
mitigation -> §IV-C telemetry). Before this module the repo carried two
near-identical copies of that pipeline — ``core/engine.py`` (static
ownership) and ``core/churn.py`` (ownership-as-state) — which had already
drifted once. Here the pipeline exists exactly once, parameterized by an
**ownership provider**:

  static ownership  — the owner vector is a trace-time constant; per-tick
                      inputs are ``(accesses [L], alive [L])``; the
                      lifecycle step frees pages whose tenant trace died;
                      selection uses the fastest layout-aware primitives
                      (``select.static_strategy``).
  dynamic ownership — the owner vector is state (FREE sentinel = T); per-
                      tick inputs are ``(rates [T, S], want [T])``; the
                      lifecycle step reclaims/grants pages, resets reused
                      slots and re-partitions policy; selection routes
                      through the runtime-owner fallback
                      (``select.dynamic_strategy``).

The static trace is the degenerate case of the churn schedule (owner fixed
after the first grant, free pool empty): ``tests/test_tick_unification.py``
pins that a constant-roster scenario produces identical integer
trajectories through both providers, so the two paths can never disagree on
shared semantics again.

A provider contributes only:

  * ``prepare(state, inputs) -> Prepared`` — the ownership/lifecycle step
    (tick step 1): which pages are live, what they are accessed at, the
    effective policy, the controller carry-ins, and any lifecycle mutations
    of tier/hot/table/stats.
  * ``strategy`` — the three owner-parameterized selection/reduction ops
    (``select.Strategy``).
  * ``pool_free(owner, tier)`` — the provider's definition of "unused
    pages" for telemetry.

Everything downstream of step 1 — allocation gating, hotness, contention,
Eq.1/Eq.2-regulated migration, sync upper-bound demotion, counters, obs,
the periodic thrash controller and the perf model — is written once below
and is bit-exact with the pre-unification engines (the golden-trace
fixtures pass unregenerated).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TieringConfig
from repro.core import hotness as HOT
from repro.core import policy as P
from repro.core import select as SEL
from repro.core.state import (TIER_FAST, TIER_NONE, TIER_SLOW, Counters,
                              TenantPolicy, ThrashTable, TierState,
                              make_policy)
from repro.obs import attribution as AT
from repro.obs import stats as OS
from repro.obs import streaming as DS
from repro.obs import trace as OT

MODES = ("equilibria", "tpp", "memtis", "static")

# The tick's stages. Every op of the tick runs under ``named_scope("tick")``
# and exactly one of these scopes below it, so the HLO ``op_name`` of each
# compiled op reads ``.../tick/<stage>/...`` and a device trace's own time
# splits by stage. ``select`` and ``commit`` nest a site scope (``demote``,
# ``promote``, ``sync_demote``), the dynamic provider's ``ownership`` one
# per lifecycle block, ``telemetry`` ``detect`` and ``attrib`` for the
# streaming detectors (step 9b) and the attribution ledger (step 9c) when
# the tick carries them.
STAGES = ("ownership", "alloc", "hotness", "regulate", "select", "commit",
          "telemetry", "control")


class TickOutput(NamedTuple):
    fast_usage: jax.Array      # [T] pages
    slow_usage: jax.Array      # [T]
    promotions: jax.Array      # [T] this tick
    demotions: jax.Array       # [T]
    throughput: jax.Array      # [T] accesses per latency-unit (1.0 = all-fast)
    latency: jax.Array         # [T] mean access latency (units of lat_fast)
    promo_scale: jax.Array     # [T]
    thrash_events: jax.Array   # [T] cumulative
    fast_free: jax.Array       # scalar
    attempted_promotions: jax.Array  # [T] candidates this tick (obs)
    pool_free: jax.Array       # scalar: unallocated pages (churn: free pool)


class Prepared(NamedTuple):
    """Everything tick step 1 (the ownership/lifecycle step) hands to the
    shared pipeline. Controller fields are the *carry-ins* for this tick —
    the static provider passes state through (plus ``freed_since``
    accumulation); the dynamic provider resets them for reused slots."""
    owner: jax.Array          # [L] effective owner this tick
    owner_c: jax.Array        # [L] gather-safe owner (sentinel clamped)
    alive: jax.Array          # [L] bool
    active: jax.Array         # [T] bool tenant roster this tick — the SAME
    #                           definition the offline detectors judge with
    #                           (static: any live page; dynamic: want > 0)
    accesses: jax.Array       # [L] f32
    tier: jax.Array           # [L] int32, post-lifecycle
    hot: jax.Array            # [L] f32, post-lifecycle
    table: ThrashTable        # post-invalidation
    stats: object             # TierStats, lifecycle exits recorded
    ring: object              # MigrationRing
    pol: TenantPolicy         # effective policy this tick
    freed_t: jax.Array        # [T] pages freed by the lifecycle step
    rows: Callable[[], HOT.RowSpace]  # lazy tenant-local page rowspace for
    #                           hotness providers that iterate per-tenant
    #                           footprints (sketch probes, neomem reports).
    #                           A thunk: the exact provider never calls it,
    #                           so the default tick carries zero extra ops.
    promo_scale: jax.Array    # [T] controller carry-ins --------------------
    steady: jax.Array
    mitigated_prev: jax.Array
    thrash_prev: jax.Array
    usage_prev: jax.Array
    freed_since: jax.Array


class OwnershipProvider(NamedTuple):
    """The seam between a deployment shape and the shared tick pipeline."""
    n_pages: int
    strategy: SEL.Strategy
    prepare: Callable[[TierState, tuple], Prepared]
    pool_free: Callable[[jax.Array, jax.Array], jax.Array]


def static_ownership(cfg: TieringConfig, owner: np.ndarray, k_max: int,
                     impl: str = "batched") -> OwnershipProvider:
    """Fixed tenant roster: ``owner`` [L] is a trace-time constant, per-tick
    inputs are ``(accesses [L] f32, alive [L] bool)`` from a prebuilt trace.
    The lifecycle step only frees pages whose trace liveness ended."""
    T = cfg.n_tenants
    owner_j = jnp.asarray(owner, jnp.int32)
    strategy = SEL.static_strategy(owner, T, k_max, impl=impl)
    pol = make_policy(cfg)
    rs_cache: list = []   # rowspace is a trace-time constant; build once

    def rows() -> HOT.RowSpace:
        if not rs_cache:
            rs_cache.append(HOT.static_rowspace(np.asarray(owner), T))
        return rs_cache[0]

    def prepare(state: TierState, inputs) -> Prepared:
        accesses, alive = inputs
        t = state.t
        tier = state.tier.astype(jnp.int32)
        died = (tier != TIER_NONE) & ~alive
        freed_t = strategy.by_tenant(died.astype(jnp.int32), owner_j)
        # fast-resident pages that die end their residency here (obs).
        # Deaths are rare (most ticks: none), and the [L]-lane residency
        # scatter is the single most expensive op in the tick at scale —
        # cond-skip it on death-free ticks (an empty mask is a value no-op,
        # so trajectories are unchanged).
        stats = jax.lax.cond(
            died.any(),
            lambda s: OS.record_fast_exits(
                s, died & (tier == TIER_FAST), owner_j, t),
            lambda s: s, state.stats)
        tier = jnp.where(died, TIER_NONE, tier)
        # roster for the streaming detectors: any live page this tick —
        # identical to the offline harness's ``tenant_activity``
        active = strategy.by_tenant(alive.astype(jnp.int32), owner_j) > 0
        # carry the state's owner through (it never changes); gathers use
        # the trace-time constant ``owner_j`` exactly as the seed engine did
        return Prepared(
            owner=state.owner, owner_c=owner_j, alive=alive, active=active,
            accesses=accesses,
            tier=tier, hot=state.hot, table=state.table, stats=stats,
            ring=state.ring, pol=pol, freed_t=freed_t, rows=rows,
            promo_scale=state.promo_scale, steady=state.steady,
            mitigated_prev=state.mitigated_prev,
            thrash_prev=state.thrash_prev, usage_prev=state.usage_prev,
            freed_since=state.freed_since + freed_t)

    return OwnershipProvider(
        n_pages=owner_j.shape[0], strategy=strategy, prepare=prepare,
        pool_free=lambda owner_, tier_: (tier_ == TIER_NONE).sum())


def dynamic_ownership(cfg: TieringConfig, n_pages: int, k_max: int,
                      impl: str = "batched") -> OwnershipProvider:
    """Tenant lifecycle as in-graph events: ``TierState.owner`` is mutated
    every tick by a ``(rates [T, S], want [T])`` schedule — reclaim
    (departure/shrink, coldest-first demote-and-free), rank-interval pool
    grants, slot-reuse controller resets and per-tick policy re-partition.
    The static trace is this provider's degenerate case (constant ``want``,
    empty pool after the first grant)."""
    T = cfg.n_tenants
    L = n_pages
    FREE = T
    n_fast = cfg.n_fast_pages
    wmark = max(int(np.ceil(n_fast * cfg.watermark_free)), 1)
    strategy = SEL.dynamic_strategy(T, k_max, impl=impl)
    base_pol = make_policy(cfg)
    weights = None
    if cfg.tenant_weights:
        w = np.ones(T, np.float32)
        for i, v in enumerate(cfg.tenant_weights[:T]):
            w[i] = v
        weights = jnp.asarray(w)

    def prepare(state: TierState, inputs) -> Prepared:
        rates, want = inputs
        S = rates.shape[1]
        t = state.t
        owner = state.owner

        # ---- reclaim (departure & shrink), coldest-first ----------------
        with jax.named_scope("reclaim"):
            tier = state.tier.astype(jnp.int32)
            hot = state.hot
            want = want.astype(jnp.int32)
            active = want > 0
            owned = owner < FREE
            cnt = strategy.by_tenant(owned.astype(jnp.int32), owner)
            delta = want - cnt
            arrived = (cnt == 0) & (delta > 0)
            release_q = jnp.minimum(jnp.maximum(-delta, 0), cnt)
            cold0 = HOT.cold_score(t, state.last_access, hot)
            # k_cap = L: a departing tenant frees its whole footprint now
            reclaimed = SEL.select_top_quota(cold0, owner, owned, release_q,
                                             T, L)
            owner_c = jnp.minimum(owner, T - 1)
            rec_fast = reclaimed & (tier == TIER_FAST)
            # reclaims are event-driven (departure/shrink ticks only): cond-
            # skip the [L]-lane residency scatter on quiet ticks (empty-mask
            # no-op)
            stats = jax.lax.cond(
                rec_fast.any(),
                lambda s: OS.record_fast_exits(s, rec_fast, owner_c, t),
                lambda s: s, state.stats)
            freed_t = strategy.by_tenant(reclaimed.astype(jnp.int32), owner)
            owner = jnp.where(reclaimed, FREE, owner)
            tier = jnp.where(reclaimed, TIER_NONE, tier)
            hot = jnp.where(reclaimed, 0.0, hot)
            # a reclaimed page's thrash-table entry is stale: without this,
            # a page promoted by the old tenant and re-granted soon after
            # would count a false thrash hit against its new owner
            tp = state.table.page
            stale = (tp >= 0) & reclaimed[jnp.maximum(tp, 0)]
            table = ThrashTable(page=jnp.where(stale, -1, tp),
                                tick=jnp.where(stale, 0, state.table.tick))

        # ---- grant from the free pool -----------------------------------
        with jax.named_scope("grant"):
            need = jnp.maximum(delta, 0)
            grant_owner = SEL.pool_grant(owner == FREE, need)
            granted = grant_owner < FREE
            owner = jnp.where(granted, grant_owner, owner)
            owner_c = jnp.minimum(owner, T - 1)
            owned = owner < FREE

        # ---- slot reuse: fresh arrivals get clean controller state ------
        with jax.named_scope("slot_reuse"):
            promo_scale0 = jnp.where(arrived, 1.0, state.promo_scale)
            steady0 = jnp.where(arrived, False, state.steady)
            mitigated0 = jnp.where(arrived, False, state.mitigated_prev)
            thrash_prev0 = jnp.where(arrived, state.counters.thrash_events,
                                     state.thrash_prev)
            usage_prev0 = jnp.where(arrived, 0, state.usage_prev)
            freed_since0 = jnp.where(arrived, 0,
                                     state.freed_since + freed_t)

        # ---- per-page accesses from the tenant-local schedule -----------
        with jax.named_scope("schedule"):
            prank = SEL.segment_ranks(jnp.where(owned, owner, T),
                                      jnp.zeros((L,), jnp.int32), T)
            accesses = jnp.where(
                owned, rates[owner_c, jnp.minimum(prank, S - 1)], 0.0)

        # ---- policy re-partition on membership --------------------------
        with jax.named_scope("repartition"):
            pol = P.repartition_policy(base_pol, active, n_fast - wmark,
                                       weights)

        # tenant rowspace from the live owner vector, built only when a
        # hotness provider asks (one [T, S] scatter; the exact provider's
        # trace never contains it)
        owner_f, owned_f, prank_f = owner, owned, prank

        def rows() -> HOT.RowSpace:
            row = jnp.where(owned_f, owner_f, T)
            col = jnp.where(owned_f & (prank_f < S), prank_f, S)
            page = jnp.full((T, S), -1, jnp.int32).at[row, col].set(
                jnp.arange(L, dtype=jnp.int32), mode="drop")
            return HOT.RowSpace(page=page, valid=page >= 0)

        return Prepared(
            owner=owner, owner_c=owner_c, alive=owned, active=active,
            accesses=accesses,
            tier=tier, hot=hot, table=table, stats=stats, ring=state.ring,
            pol=pol, freed_t=freed_t, rows=rows,
            promo_scale=promo_scale0, steady=steady0,
            mitigated_prev=mitigated0, thrash_prev=thrash_prev0,
            usage_prev=usage_prev0, freed_since=freed_since0)

    return OwnershipProvider(
        n_pages=L, strategy=strategy, prepare=prepare,
        pool_free=lambda owner_, tier_: (owner_ == FREE).sum())


def make_tick_core(cfg: TieringConfig, provider: OwnershipProvider,
                   mode: str = "equilibria", k_max: int = 256,
                   detector: Optional[DS.DetectorSpec] = None,
                   attrib: Optional[AT.AttributionSpec] = None,
                   hotness=None):
    """Build the jittable unified tick over an ownership provider.

    One compiled tick per provider serves any schedule data: trace size,
    jaxpr size and kernel count are constant in T (tenant-batched
    selection) and in the number of lifecycle events (ownership is scan
    data, not structure).

    ``detector``: optional streaming-pathology spec (obs/streaming.py). When
    set, the state must carry a matching ``DetectorState`` (build it via
    ``init_state(..., detector=spec)``) and step 9b folds this tick's
    telemetry into it; the spec's window geometry is baked in as constants,
    so jaxpr size stays independent of the horizon it was built for.

    ``attrib``: optional slowdown-attribution spec (obs/attribution.py).
    When set, the state must carry a matching ``AttributionState``
    (``init_state(..., attrib=spec)``) and step 9c folds the promotion
    pipeline's quota cascade into the per-tenant stall ledger.

    ``hotness``: optional hotness-provider spec (core/hotness.py) — a
    provider name (``"exact"``/``"sampled"``/``"sketch"``/``"neomem"``), a
    spec NamedTuple, or a prebuilt ``HotnessProvider``. None (the default)
    is the exact dense EWMA, bit-exact with the pre-seam tick. Stateful
    providers must be paired with ``init_state(..., hotness=spec)``.
    """
    assert mode in MODES, mode
    T = cfg.n_tenants
    if detector is not None:
        assert detector.n_tenants == T, (detector.n_tenants, T)
    if attrib is not None:
        assert attrib.n_tenants == T, (attrib.n_tenants, T)
    L = provider.n_pages
    n_fast = cfg.n_fast_pages
    wmark = max(int(np.ceil(n_fast * cfg.watermark_free)), 1)
    strategy = provider.strategy
    by_tenant = strategy.by_tenant
    alloc_ranks = strategy.alloc_ranks
    hot_provider = HOT.resolve_hotness(hotness, cfg, L, k_max)

    @jax.named_scope("tick")
    def tick(state: TierState, inputs) -> Tuple[TierState, TickOutput]:
        t = state.t
        with jax.named_scope("commit"):   # the helpers' [L] lane fallback
            page_ids = jnp.arange(L, dtype=jnp.int32)

        # ---- 1. ownership / lifecycle (the provider seam) -----------------
        with jax.named_scope("ownership"):
            prep = provider.prepare(state, inputs)
        owner, owner_c = prep.owner, prep.owner_c
        alive, accesses = prep.alive, prep.accesses
        tier, stats, ring = prep.tier, prep.stats, prep.ring
        pol = prep.pol

        # Migration accounting (thrash table, residency histogram, event
        # ring) runs over the selection's compact [T, k] candidate stream
        # when available (contiguous batched path) — scatters over T*k lanes
        # instead of L — and falls back to the full [L] masks otherwise.
        # These helpers run under the ``commit`` scope of their call site.
        def sel_counts(sel: SEL.Selection) -> jax.Array:
            if sel.counts is not None:
                return sel.counts
            return by_tenant(sel.mask.astype(jnp.int32), owner)

        def sel_tenants(sel: SEL.Selection) -> jax.Array:
            return jnp.broadcast_to(
                jnp.arange(T, dtype=jnp.int32)[:, None], sel.take.shape)

        def sel_thrash(tbl, sel: SEL.Selection) -> jax.Array:
            if sel.pages is None:
                return by_tenant(P.thrash_hits(
                    tbl, page_ids, sel.mask, t, cfg).astype(jnp.int32), owner)
            hits = P.thrash_hits(tbl, sel.pages, sel.take, t, cfg)
            return hits.sum(axis=1).astype(jnp.int32)

        def sel_record_promos(tbl, sel: SEL.Selection):
            if sel.pages is None:
                return P.thrash_record_promotions(tbl, page_ids, sel.mask, t)
            return P.thrash_record_promotions(tbl, sel.pages, sel.take, t)

        def sel_exits(st, sel: SEL.Selection):
            if sel.pages is None:
                return OS.record_fast_exits(st, sel.mask, owner_c, t)
            return OS.record_fast_exits_at(st, sel.pages, sel.take,
                                           sel_tenants(sel), t)

        def sel_ring(rg, sel: SEL.Selection, hotv, direction):
            if sel.pages is None:
                return OT.ring_record(rg, sel.mask, page_ids, owner_c, hotv,
                                      direction, t)
            return OT.ring_record(rg, sel.take, sel.pages, sel_tenants(sel),
                                  hotv[sel.pages], direction, t)

        def move_pages(tier_, ring_, sel: SEL.Selection, hotv, direction,
                       to_tier):
            """Commit a selection's page moves: tier scatter + migration-ring
            append. When the strategy provides the fused page-move kernel
            (kernels/migrate commit_moves) and the selection carries the
            compact [T, k] stream, both come out of one kernel pass —
            bit-identical to the composed jnp ops of the fallback."""
            if strategy.move is not None and sel.pages is not None:
                tier2, data2, head2 = strategy.move(
                    tier_, ring_.data, ring_.head, sel, hotv, direction,
                    to_tier, t)
                return tier2, OT.MigrationRing(data=data2, head=head2)
            ring2 = sel_ring(ring_, sel, hotv, direction)
            return jnp.where(sel.mask, to_tier, tier_), ring2

        # ---- 2. allocate new pages ----------------------------------------
        # Allocation is event-driven (first grant / arrivals); most ticks
        # have no new pages, so the whole block — the [L] rank cumsums and
        # the entry stamps — runs under a cond. With ``new`` empty every
        # branch output equals the pass-through (wheres over a False mask,
        # a zero by_tenant, an empty entry stamp), so values are unchanged.
        with jax.named_scope("alloc"):
            new = alive & (tier == TIER_NONE)
            fast_usage = by_tenant((tier == TIER_FAST).astype(jnp.int32),
                                   owner)
            fast_free = n_fast - fast_usage.sum()

            def do_alloc(args):
                tier_, stats_ = args
                alloc_ = None
                # per-tenant upper bound gating of *fast* placement
                if mode in ("equilibria", "memtis") and cfg.enable_upper_bound:
                    if strategy.alloc_stats is not None:
                        # fused kernel pass: allocation ranks + per-tenant
                        # new-page counts from one segmented reduction
                        ranks, alloc_ = strategy.alloc_stats(new, owner)
                    else:
                        ranks = alloc_ranks(new, owner)
                    bound = pol.upper_bound[owner_c]
                    under_bound = ((bound == 0)
                                   | (fast_usage[owner_c] + ranks < bound))
                else:
                    under_bound = jnp.ones((L,), bool)
                elig = new & under_bound
                grank = SEL.masked_rank(elig)
                go_fast = elig & (grank < jnp.maximum(fast_free - wmark, 0))
                tier_ = jnp.where(go_fast, TIER_FAST,
                                  jnp.where(new, TIER_SLOW, tier_))
                if alloc_ is None:
                    alloc_ = by_tenant(new.astype(jnp.int32), owner)
                return tier_, alloc_, OS.record_fast_entries(stats_, go_fast,
                                                             t)

            tier, alloc_t, stats = jax.lax.cond(
                new.any(), do_alloc,
                lambda args: (args[0], jnp.zeros((T,), jnp.int32), args[1]),
                (tier, stats))

        # ---- 3. hotness / recency (the hotness-provider seam) -------------
        with jax.named_scope("hotness"):
            last_access = jnp.where(new | (accesses > 0), t,
                                    state.last_access)
            hview = hot_provider.step(HOT.HotCtx(
                hstate=state.hotness, prev_hot=prep.hot, accesses=accesses,
                alive=alive, new=new, tier=tier, last_access=last_access,
                owner=owner, owner_c=owner_c, t=t, rows=prep.rows,
                strategy=provider.strategy))
        hot = hview.hot

        # ---- 4. contention ------------------------------------------------
        # Local memory is contended when free space cannot absorb both the
        # watermark and the pending promotion demand (kswapd-style: promotion
        # pressure drives background demotion, §IV-D).
        with jax.named_scope("regulate"):
            fast_usage = by_tenant((tier == TIER_FAST).astype(jnp.int32),
                                   owner)
            fast_free = n_fast - fast_usage.sum()
            demand_t = jnp.minimum(hview.demand_t, k_max)
            promo_demand = jnp.minimum(demand_t.sum(), k_max)
            contended = fast_free < wmark + promo_demand

            # ---- 5. demotion -----------------------------------------------
            sync_quota = jnp.zeros((T,), jnp.int32)
            if mode == "equilibria":
                d_scan = P.eq1_demotion_scan(fast_usage, fast_usage, pol,
                                             contended)
                if not cfg.enable_protection:
                    # ablation: proportional pressure without protection
                    d_scan = jnp.where(contended,
                                       fast_usage.astype(jnp.float32), 0.0)
                # Eq.1 sets each tenant's *share* of reclaim work; the total
                # is kswapd-style demand-driven: free enough for the
                # watermark plus pending promotions, no more (work-conserving
                # donation, §V-B3). A tenant's OWN promotion demand never
                # drives its own demotion (that would be pure churn); only
                # neighbors' demand evicts it.
                demand_other = jnp.minimum(promo_demand - demand_t, k_max)
                needed_t = jnp.maximum(wmark + demand_other - fast_free, 0)
                total_scan = jnp.maximum(d_scan.sum(), 1.0)
                share = jnp.ceil(d_scan * jnp.minimum(
                    needed_t.astype(jnp.float32) / total_scan, 1.0)
                ).astype(jnp.int32)
                if cfg.enable_upper_bound:
                    sync_quota = P.upper_bound_demotion(fast_usage, pol)
                quota = jnp.minimum(share + sync_quota, k_max)
            elif mode == "tpp":
                needed = jnp.maximum(2 * wmark - fast_free, 0)
                quota = jnp.minimum(needed, k_max * T)  # global
            elif mode == "memtis":
                sync_quota = P.upper_bound_demotion(fast_usage, pol)
                quota = jnp.minimum(sync_quota, k_max)
            else:  # static
                quota = jnp.zeros((T,), jnp.int32)

        with jax.named_scope("select"), jax.named_scope("demote"):
            fast_mask = tier == TIER_FAST
            if mode == "tpp":
                dsel = hview.demote_global(fast_mask, quota)
            elif mode == "static":
                dsel = SEL.Selection(jnp.zeros((L,), bool), None, None, None)
            else:
                dsel = hview.demote(fast_mask, quota)
        demoted = dsel.mask

        with jax.named_scope("commit"), jax.named_scope("demote"):
            demo_t = sel_counts(dsel)
            # thrash detection on demotions (§IV-F)
            thrash_new = sel_thrash(prep.table, dsel)
            stats = sel_exits(stats, dsel)
            tier, ring = move_pages(tier, ring, dsel, hot, OT.DIR_DEMOTE,
                                    TIER_SLOW)
        with jax.named_scope("regulate"):
            fast_usage = fast_usage - demo_t
            fast_free = n_fast - fast_usage.sum()

        # ---- 6. promotion ---------------------------------------------------
        # just-demoted pages are not promotion candidates this tick
        with jax.named_scope("select"), jax.named_scope("promote"):
            pcand = hview.promo_cand(tier, demoted)
        cand_t = pcand.cand_t
        with jax.named_scope("regulate"):
            throttled = jnp.zeros((T,), bool)
            q_base = q_eq2 = q_mit = None   # attribution quota cascade (9c)
            if mode == "equilibria":
                p_base = jnp.full((T,), float(cfg.p_base), jnp.float32)
                if cfg.enable_promo_throttle:
                    p_scan, throttled = P.eq2_promotion_scan(
                        p_base, fast_usage, pol, contended, cfg)
                else:
                    p_scan = p_base
                p_eq2 = p_scan                        # pre-mitigation scan
                p_scan = p_scan * prep.promo_scale    # thrash mitigation
                p_quota = jnp.minimum(p_scan.astype(jnp.int32), k_max)
                if attrib is not None:
                    # telescoping quota cascade: each stage capped the same
                    # way the pipeline caps p_quota below (min with cand and
                    # k_max), so successive differences are the deferral
                    # components
                    c0 = jnp.minimum(cand_t, k_max)
                    q_base = jnp.minimum(jnp.full((T,), int(cfg.p_base),
                                                  jnp.int32), c0)
                    q_eq2 = jnp.minimum(
                        jnp.minimum(p_eq2.astype(jnp.int32), k_max), c0)
                    q_mit = jnp.minimum(p_quota, c0)
            elif mode in ("tpp", "memtis"):
                p_quota = jnp.full((T,), cfg.p_base, jnp.int32)  # unregulated
                if attrib is not None:
                    # no throttle / mitigation stages: the whole cascade is
                    # the unregulated scan budget
                    q_base = q_eq2 = q_mit = jnp.minimum(
                        p_quota, jnp.minimum(cand_t, k_max))
            else:
                p_quota = jnp.zeros((T,), jnp.int32)
                if attrib is not None:   # no promotion path at all
                    q_base = q_eq2 = q_mit = p_quota

            # never overfill: cap total promotions by free fast capacity.
            # NOTE: promotions may transiently exceed a tenant's upper bound
            # — the allocating thread then demotes synchronously in the same
            # tick (paper §IV-D); that promote->sync-demote cycle is exactly
            # the thrashing signature §IV-F detects.
            p_quota = jnp.minimum(p_quota, jnp.minimum(cand_t, k_max))
            headroom = jnp.maximum(fast_free - wmark, 0)
            total = p_quota.sum()
            scale = jnp.where(
                total > headroom,
                headroom.astype(jnp.float32) / jnp.maximum(total, 1), 1.0)
            p_quota = jnp.floor(p_quota.astype(jnp.float32) * scale
                                ).astype(jnp.int32)

        with jax.named_scope("select"), jax.named_scope("promote"):
            if mode == "tpp":
                psel = pcand.select_global(p_quota.sum())
            elif mode == "static":
                psel = SEL.Selection(jnp.zeros((L,), bool), None, None, None)
            else:
                psel = pcand.select(p_quota)
        promoted = psel.mask
        with jax.named_scope("commit"), jax.named_scope("promote"):
            promo_t = sel_counts(psel)
            tier, ring = move_pages(tier, ring, psel, hot, OT.DIR_PROMOTE,
                                    TIER_FAST)
            table = sel_record_promos(prep.table, psel)
            stats = OS.record_fast_entries(stats, promoted, t)

        # ---- 6b. synchronous upper-bound demotion (allocation path, §IV-D):
        # promotions that pushed a tenant past its bound are shed in the same
        # tick by the "allocating thread" — these demotions hit the thrash
        # table immediately when they evict recently-promoted pages.
        with jax.named_scope("commit"):
            sync2_t = jnp.zeros((T,), jnp.int32)
        if mode in ("equilibria", "memtis") and cfg.enable_upper_bound:
            with jax.named_scope("regulate"):
                fast_usage2 = by_tenant(
                    (tier == TIER_FAST).astype(jnp.int32), owner)
                over2 = jnp.where(
                    pol.upper_bound > 0,
                    jnp.maximum(fast_usage2 - pol.upper_bound, 0), 0)
                over2 = jnp.minimum(over2, k_max)
            with jax.named_scope("select"), jax.named_scope("sync_demote"):
                ssel = hview.demote(tier == TIER_FAST, over2)
            with jax.named_scope("commit"), jax.named_scope("sync_demote"):
                thr2 = sel_thrash(table, ssel)
                thrash_new = thrash_new + thr2
                stats = sel_exits(stats, ssel)
                tier, ring = move_pages(tier, ring, ssel, hot,
                                        OT.DIR_DEMOTE, TIER_SLOW)
                sync2_t = sel_counts(ssel)
                demo_t = demo_t + sync2_t

        with jax.named_scope("telemetry"):
            # ---- 7. counters ------------------------------------------------
            c = state.counters
            counters = Counters(
                promotions=c.promotions + promo_t,
                demotions=c.demotions + demo_t,
                attempted_promotions=c.attempted_promotions + cand_t,
                reclaims=c.reclaims + prep.freed_t,
                allocations=c.allocations + alloc_t,
                thrash_events=c.thrash_events + thrash_new,
                sync_demotions=c.sync_demotions
                + jnp.minimum(sync_quota, demo_t) + sync2_t,
            )
            fast_usage = by_tenant((tier == TIER_FAST).astype(jnp.int32),
                                   owner)
            slow_usage = by_tenant((tier == TIER_SLOW).astype(jnp.int32),
                                   owner)

            # ---- 7b. observability (obs/, §IV-C) ----------------------------
            # tpp's quota is one global scan budget; split it evenly so
            # demo_success_ratio stays comparable across modes
            demo_att = (jnp.broadcast_to((quota + T - 1) // T, (T,))
                        if quota.ndim == 0 else quota)
            below_prot = OS.below_protection(fast_usage, slow_usage,
                                             pol.lower_protection)
            # sync upper-bound demotions (6b) bypass the step-5 quota; count
            # them on both sides so demo_success_ratio stays <= 1
            stats = OS.update_tick(
                stats, promo_attempts=cand_t, promo_success=promo_t,
                demo_attempts=jnp.minimum(demo_att, k_max) + sync2_t,
                demo_success=demo_t,
                thrash_new=thrash_new, contended=contended,
                throttled=throttled, below_protection=below_prot,
                decay=cfg.obs_window_decay)

        with jax.named_scope("control"):
            new_state = TierState(
                tier=tier.astype(jnp.int8), hot=hot, last_access=last_access,
                owner=owner,
                counters=counters, promo_scale=prep.promo_scale,
                thrash_prev=prep.thrash_prev, usage_prev=prep.usage_prev,
                freed_since=prep.freed_since, steady=prep.steady,
                mitigated_prev=prep.mitigated_prev,
                table=table, stats=stats, ring=ring, t=t + 1, det=state.det,
                attrib=state.attrib, hotness=hview.hstate)

            # ---- 8. periodic controller (§IV-F) -----------------------------
            def run_ctrl(s: TierState) -> TierState:
                out = P.thrash_controller(s, fast_usage + slow_usage, cfg)
                return s._replace(promo_scale=out.promo_scale,
                                  steady=out.steady, table=out.table,
                                  thrash_prev=out.thrash_prev,
                                  usage_prev=out.usage_prev,
                                  freed_since=out.freed_since,
                                  mitigated_prev=out.mitigated_prev)

            new_state = jax.lax.cond(
                (t + 1) % cfg.controller_period == 0, run_ctrl, lambda s: s,
                new_state)

            # ---- 9. perf model ----------------------------------------------
            a_fast = by_tenant(accesses * (tier == TIER_FAST), owner)
            a_slow = by_tenant(accesses * (tier == TIER_SLOW), owner)
            a_tot = a_fast + a_slow
            migrations = (promo_t + demo_t).sum().astype(jnp.float32)
            lat = jnp.where(
                a_tot > 0,
                (a_fast * cfg.lat_fast + a_slow * cfg.lat_slow)
                / jnp.maximum(a_tot, 1e-9),
                cfg.lat_fast) + migrations * cfg.migration_cost
            thru = jnp.where(a_tot > 0, a_tot / lat, 0.0)

        # ---- 9b. streaming pathology detectors (obs/streaming.py) ----------
        # fed the exact per-tick values the offline detectors read from
        # TickOutput traces, so the streamed verdicts can agree bit-for-bit
        if detector is not None:
            with jax.named_scope("telemetry"), jax.named_scope("detect"):
                new_state = new_state._replace(det=DS.update_detector(
                    detector, state.det,
                    DS.DetectorSignals(
                        active=prep.active, thrash_new=thrash_new,
                        fast_usage=fast_usage, slow_usage=slow_usage,
                        attempted=cand_t, promotions=promo_t,
                        demotions=demo_t, latency=lat), t))

        # ---- 9c. slowdown attribution ledger (obs/attribution.py) ----------
        # the promotion pipeline's quota cascade, telescoped into additive
        # per-tenant stall components; conservation against Counters is
        # bit-exact because cand_t / promo_t / freed_t are the SAME values
        # step 7 accumulates into attempted/promotions/reclaims
        if attrib is not None:
            with jax.named_scope("telemetry"), jax.named_scope("attrib"):
                new_state = new_state._replace(attrib=AT.update_attribution(
                    attrib, state.attrib,
                    AT.AttribSignals(
                        cand=cand_t, promoted=promo_t, quota_base=q_base,
                        quota_eq2=q_eq2, quota_mit=q_mit, freed=prep.freed_t,
                        a_fast=a_fast, a_slow=a_slow, latency=lat)))

        with jax.named_scope("control"):
            out = TickOutput(
                fast_usage=fast_usage, slow_usage=slow_usage,
                promotions=promo_t, demotions=demo_t,
                throughput=thru, latency=lat,
                promo_scale=new_state.promo_scale,
                thrash_events=counters.thrash_events,
                fast_free=n_fast - fast_usage.sum(),
                attempted_promotions=cand_t,
                pool_free=provider.pool_free(owner, tier))
        return new_state, out

    return tick
