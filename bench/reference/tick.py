"""Plain reference of one Equilibria host-tick, in numpy.

Written from the semantics the program documents (paper §IV: allocation,
EWMA hotness, Eq.1-regulated demotion, Eq.2-regulated promotion, the
upper-bound sync demotion, the thrash table and its periodic controller,
the tier perf model; and for dynamic ownership the reclaim / grant
lifecycle step), one page array at a time, with no import from the program.

It models the state the tick's decisions read and write, and the
telemetry that operators read from it (paper §IV-C): the per-tenant
statistics (fast-tier residency in log2 buckets of ticks, attempted and
successful promotions and demotions, ticks spent contended, throttled or
below protection, windowed thrash / promotion / demotion rates) and the
migration-event ring, with the configuration's tiering parameters (its
``tiering`` group). The state ``Host.tick`` keeps is what is compared.

The ring keeps the newest ``obs_ring_capacity`` moves as (tick, tenant,
page, direction, hotness) in the order they are committed: the tick's
demotions, then its promotions, then its upper-bound sync demotions, each
in selection order.

Selections rank with (score descending, page index ascending). When two
pages promoted in one tick map to the same thrash-table slot, the one
promoted last in selection order is kept (static: tenant by tenant, hottest
first; dynamic: page index order).

``hot_dtype`` is the precision the EWMA hotness is kept in: float32 as the
configuration states, or bfloat16 for the control that must fail.
``fused_ewma`` says whether ``decay * hot + accesses`` is rounded to float32
once (a fused multiply-add) or after each operation. Both are float32
arithmetic; which one a compiler emits depends on the backend (the TPU
v5e rounds after each operation; XLA's CPU backend fuses the static
tick's), and the choice matters: a page accessed at 0.3 per tick converges on 2.0, the
promotion threshold, and the last bit decides whether it is a candidate.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

F32 = np.float32
TIER_NONE, TIER_FAST, TIER_SLOW = -1, 0, 1

COUNTERS = ("promotions", "demotions", "attempted_promotions", "reclaims",
            "allocations", "thrash_events", "sync_demotions")
STAT_SUMS = ("promo_attempts", "promo_success", "demo_attempts",
             "demo_success", "contended_ticks", "throttled_ticks",
             "below_protection_ticks")
STAT_RATES = ("thrash_rate", "promo_rate", "demo_rate")
DIR_PROMOTE, DIR_DEMOTE = 0, 1


def _sum_by(x: np.ndarray, owner: np.ndarray, T: int) -> np.ndarray:
    """Per-tenant count of a mask, or exact sum of a float array; pages of
    the free pool (owner == T) are dropped."""
    if x.dtype == bool:
        return np.bincount(owner[x], minlength=T + 1)[:T]
    return np.bincount(owner, weights=x.astype(np.float64),
                       minlength=T + 1)[:T]


def _rank_in(seg: np.ndarray, T: int) -> np.ndarray:
    """Rank of each page among the pages of its segment, in index order."""
    order = np.argsort(seg, kind="stable")
    counts = np.bincount(seg, minlength=T + 1)
    starts = np.cumsum(counts) - counts
    rank = np.empty_like(order)
    rank[order] = np.arange(seg.shape[0]) - starts[seg[order]]
    return rank


def log2_bucket(age: np.ndarray, n_buckets: int) -> np.ndarray:
    """Residency bucket of an age in ticks: 0-1 -> 0, 2-3 -> 1, 4-7 -> 2,
    ..., clipped to the last bucket (exact integer log2)."""
    a = np.maximum(age, 1).astype(np.int64)
    b = np.zeros_like(a)
    for k in range(1, n_buckets):
        b += a >= (1 << k)
    return b


class Telemetry:
    """The tick's statistics and migration ring, updated in commit order."""

    def __init__(self, st: Dict[str, np.ndarray], t: int):
        self.t = t
        self.fs = st["stats.fast_since"].astype(np.int64).copy()
        self.hist = st["stats.resid_hist"].astype(np.int64).copy()
        self.events = st["ring.events"].astype(np.int64).copy()
        self.rhot = st["ring.hot"].astype(F32).copy()
        self.head = int(st["ring.head"])

    def enter(self, pages: np.ndarray) -> None:
        self.fs[pages] = self.t

    def leave(self, pages: np.ndarray, owner: np.ndarray) -> None:
        """Pages leaving the fast tier: bucket their residency by tenant."""
        pages = pages[self.fs[pages] >= 0]
        b = log2_bucket(self.t - self.fs[pages], self.hist.shape[1])
        np.add.at(self.hist, (owner[pages], b), 1)
        self.fs[pages] = -1

    def record(self, pages: np.ndarray, owner: np.ndarray, hot: np.ndarray,
               direction: int) -> None:
        """Append moves in order; the newest ``capacity`` survive."""
        cap, n = self.events.shape[0], pages.shape[0]
        kept = pages[max(n - cap, 0):]
        slot = (self.head + np.arange(n - kept.shape[0], n)) % cap
        self.events[slot] = np.stack(
            [np.full(kept.shape, self.t), owner[kept], kept,
             np.full(kept.shape, direction)], axis=1)
        self.rhot[slot] = hot[kept]
        self.head += n

    def store(self, st: Dict[str, np.ndarray]) -> None:
        st.update({"stats.fast_since": self.fs, "stats.resid_hist": self.hist,
                   "ring.events": self.events, "ring.hot": self.rhot,
                   "ring.head": self.head})


def top_quota(score: np.ndarray, owner: np.ndarray, mask: np.ndarray,
              quota: np.ndarray, cap: int, T: int) -> np.ndarray:
    """Each tenant's ``min(quota, cap)`` highest-score pages under ``mask``;
    returns their page ids tenant by tenant, best first."""
    idx = np.flatnonzero(mask & np.isfinite(score))
    if idx.size == 0:
        return idx
    o = owner[idx]
    order = np.lexsort((idx, -score[idx], o))
    idx, o = idx[order], o[order]
    counts = np.bincount(o, minlength=T + 1)
    starts = np.cumsum(counts) - counts
    rank = np.arange(idx.shape[0]) - starts[o]
    q = np.minimum(np.append(quota, 0), cap)
    return idx[rank < q[o]]


class Host:
    """One host's tick over the benchmark's traffic rows."""

    def __init__(self, traffic, params: dict, k_max: int,
                 hot_dtype=np.float32, fused_ewma: bool = False):
        self.tr = traffic
        self.p = params
        self.T = traffic.n_tenants
        self.L = traffic.n_pages
        self.n_fast = traffic.n_fast
        self.k_max = k_max
        self.hot_dtype = hot_dtype
        self.fused_ewma = fused_ewma
        self.wmark = max(int(np.ceil(self.n_fast * self.p["watermark_free"])),
                         1)
        self.prot = np.asarray(traffic.lower_protection, np.int64)
        self.bound = np.asarray(traffic.upper_bound, np.int64)

    # ------------------------------------------------------------ policy --
    def _repartition(self, active):
        prot = np.where(active, self.prot, 0).astype(F32)
        ask = np.where(active, F32(1.0), F32(0.0)) * prot
        total_ask = max(F32(ask.sum(dtype=F32)), F32(1.0))
        cap = F32(self.n_fast - self.wmark)
        over = prot.sum(dtype=F32) > cap
        scaled = np.floor(cap * ask / total_ask)
        prot_eff = np.where(over, np.minimum(scaled, prot), prot)
        return (prot_eff.astype(np.int64),
                np.where(active, self.bound, 0))

    @staticmethod
    def _sync_quota(fu, bound):
        bf = bound.astype(F32)
        near_thr = np.ceil(F32(0.95) * bf - F32(1e-4)).astype(np.int64)
        target = np.round(F32(0.9) * bf).astype(np.int64)
        gentle = np.maximum(fu - target, 0)
        over = np.maximum(fu - bound, 0)
        q = np.where(fu >= near_thr, np.maximum(gentle, over), over)
        return np.where(bound > 0, q, 0)

    def _commit_order(self, pages: np.ndarray) -> np.ndarray:
        """Order a selection's pages are committed in: tenant by tenant,
        best first, for the static layout's batched rows; page index order
        for the dynamic pool's mask."""
        return pages if self.tr.ownership == "static" else np.sort(pages)

    def _thrash_hits(self, pages, table_page, table_tick, t, owner):
        slots = table_page.shape[0]
        s = pages % slots
        hit = (table_page[s] == pages) & (
            (t - table_tick[s]) < self.p["t_resident"])
        return np.bincount(owner[pages[hit]], minlength=self.T + 1)[:self.T]

    # -------------------------------------------------------------- tick --
    def tick(self, st: Dict[str, np.ndarray], inputs) -> Dict:
        """Advance ``st`` (a dict of host numpy arrays) by one tick in place
        and return the tick's outputs."""
        T, L, k = self.T, self.L, self.k_max
        t = int(st["t"])
        tier = st["tier"].astype(np.int64)
        hot = st["hot"].astype(F32)
        owner = st["owner"].astype(np.int64)
        tpage, ttick = st["table_page"].copy(), st["table_tick"].copy()
        c = {n: st["counters." + n].astype(np.int64) for n in COUNTERS}
        ps, steady = st["promo_scale"].astype(F32), st["steady"].copy()
        mit, thr_prev = st["mitigated_prev"].copy(), st["thrash_prev"].copy()
        use_prev, freed_since = st["usage_prev"].copy(), st["freed_since"]
        tel = Telemetry(st, t)

        # ---- 1. ownership / lifecycle
        if self.tr.ownership == "static":
            accesses, alive = inputs
            accesses = accesses.astype(F32)
            died = (tier != TIER_NONE) & ~alive
            freed_t = _sum_by(died, owner, T)
            gone = np.flatnonzero(died & (tier == TIER_FAST))
            tel.leave(gone, owner)
            tier[died] = TIER_NONE
            freed_since = freed_since + freed_t
            prot, bound = self.prot, self.bound
        else:
            rates, want = inputs
            S = rates.shape[1]
            want = want.astype(np.int64)
            active = want > 0
            owned = owner < T
            cnt = _sum_by(owned, owner, T)
            delta = want - cnt
            arrived = (cnt == 0) & (delta > 0)
            release = np.minimum(np.maximum(-delta, 0), cnt)
            cold0 = (t - st["last_access"]).astype(F32) * F32(1e3) \
                - st["hot"].astype(F32)
            rec = top_quota(cold0, owner, owned, release, L, T)
            freed_t = np.bincount(owner[rec], minlength=T + 1)[:T]
            gone = rec[tier[rec] == TIER_FAST]
            tel.leave(gone, owner)
            owner[rec] = T
            tier[rec] = TIER_NONE
            hot[rec] = 0.0
            reclaimed = np.zeros(L, bool)
            reclaimed[rec] = True
            stale = (tpage >= 0) & reclaimed[np.maximum(tpage, 0)]
            tpage[stale], ttick[stale] = -1, 0
            # grant: free pages in index order, as consecutive intervals
            free = owner == T
            frank = np.cumsum(free) - free
            cum = np.cumsum(np.maximum(delta, 0))
            gt = np.searchsorted(cum, frank, side="right")
            granted = free & (frank < cum[-1]) & (gt < T)
            owner[granted] = gt[granted]
            owned = owner < T
            ps = np.where(arrived, F32(1.0), ps)
            steady = np.where(arrived, False, steady)
            mit = np.where(arrived, False, mit)
            thr_prev = np.where(arrived, c["thrash_events"], thr_prev)
            use_prev = np.where(arrived, 0, use_prev)
            freed_since = np.where(arrived, 0, freed_since + freed_t)
            prank = _rank_in(np.where(owned, owner, T), T)
            oc = np.minimum(owner, T - 1)
            accesses = np.where(owned, rates[oc, np.minimum(prank, S - 1)],
                                F32(0.0)).astype(F32)
            alive = owned
            prot, bound = self._repartition(active)

        oc = np.minimum(owner, T - 1)
        # ---- 2. allocation
        new = alive & (tier == TIER_NONE)
        fu = _sum_by(tier == TIER_FAST, owner, T)
        fast_free = self.n_fast - fu.sum()
        alloc_t = np.zeros(T, np.int64)
        if new.any():
            ranks = _rank_in(np.where(new, owner, T), T)
            b = bound[oc]
            elig = new & ((b == 0) | (fu[oc] + ranks < b))
            grank = np.cumsum(elig) - elig
            go_fast = elig & (grank < max(fast_free - self.wmark, 0))
            tier = np.where(go_fast, TIER_FAST,
                            np.where(new, TIER_SLOW, tier))
            tel.enter(np.flatnonzero(go_fast))
            alloc_t = _sum_by(new, owner, T)

        # ---- 3. hotness and recency
        last = np.where(new | (accesses > 0), t,
                        st["last_access"]).astype(np.int64)
        decay = F32(self.p["hot_decay"])
        if self.fused_ewma:      # decay * hot + accesses, rounded once
            ewma = (np.float64(decay) * hot + accesses.astype(np.float64)
                    ).astype(F32)
        else:                    # rounded after the product and the sum
            ewma = decay * hot + accesses
        hot = np.where(alive, ewma, F32(0.0)).astype(F32)
        if self.hot_dtype is not np.float32:
            hot = hot.astype(self.hot_dtype).astype(F32)
        thr = F32(self.p["promo_hot_threshold"])
        demand = _sum_by((tier == TIER_SLOW) & (hot >= thr) & alive, owner, T)
        cold = (t - last).astype(F32) * F32(1e3) - hot

        # ---- 4. contention
        fu = _sum_by(tier == TIER_FAST, owner, T)
        fast_free = self.n_fast - fu.sum()
        demand = np.minimum(demand, k)
        pdem = min(demand.sum(), k)
        contended = fast_free < self.wmark + pdem

        # ---- 5. Eq.1 demotion
        n = fu.astype(F32)
        over = np.maximum(n - prot.astype(F32), F32(0.0))
        d = np.where(n > 0, n * over / np.maximum(n, F32(1.0)), F32(0.0))
        d = np.where(contended, d, F32(0.0)).astype(F32)
        needed = np.maximum(self.wmark + np.minimum(pdem - demand, k)
                            - fast_free, 0)
        total_scan = max(F32(d.sum(dtype=F32)), F32(1.0))
        share = np.ceil(d * np.minimum(needed.astype(F32) / total_scan,
                                       F32(1.0))).astype(np.int64)
        sync_q = self._sync_quota(fu, bound)
        quota = np.minimum(share + sync_q, k)
        dpages = top_quota(cold, owner, tier == TIER_FAST, quota, k, T)
        tel.leave(dpages, owner)
        tel.record(self._commit_order(dpages), owner, hot, DIR_DEMOTE)
        demoted = np.zeros(L, bool)
        demoted[dpages] = True
        demo_t = np.bincount(owner[dpages], minlength=T + 1)[:T]
        thrash_new = self._thrash_hits(dpages, tpage, ttick, t, owner)
        tier[dpages] = TIER_SLOW
        fu = fu - demo_t
        fast_free = self.n_fast - fu.sum()

        # ---- 6. Eq.2 promotion
        cand = (tier == TIER_SLOW) & (hot >= thr) & alive & ~demoted
        cand_t = _sum_by(cand, owner, T)
        usage = fu.astype(F32)
        pf, bf = prot.astype(F32), bound.astype(F32)
        throttled = ((pf > 0) & (usage > pf) & contended) | (
            (bf > 0) & (usage >= F32(0.95) * bf))
        ref = np.where(pf > 0, pf, np.where(bf > 0, bf, usage))
        ratio = np.where(usage > 0, ref / np.maximum(usage, F32(1.0)),
                         F32(1.0)).astype(F32)
        r2 = ratio * ratio
        factor = np.clip(r2 * r2, F32(self.p["promo_floor"]), F32(1.0))
        p_base = F32(self.p["p_base"])
        p = np.where(throttled, p_base * factor, p_base).astype(F32) * ps
        pq = np.minimum(p.astype(np.int64), k)
        pq = np.minimum(pq, np.minimum(cand_t, k))
        headroom = max(fast_free - self.wmark, 0)
        total = pq.sum()
        scale = (F32(headroom) / F32(max(total, 1)) if total > headroom
                 else F32(1.0))
        pq = np.floor(pq.astype(F32) * scale).astype(np.int64)
        ppages = top_quota(hot, owner, cand, pq, k, T)
        promo_t = np.bincount(owner[ppages], minlength=T + 1)[:T]
        tier[ppages] = TIER_FAST
        rec_order = self._commit_order(ppages)
        tel.record(rec_order, owner, hot, DIR_PROMOTE)
        tel.enter(ppages)
        slots = tpage.shape[0]
        tpage[rec_order % slots] = rec_order   # later lanes overwrite
        ttick[rec_order % slots] = t

        # ---- 6b. upper-bound sync demotion
        fu2 = _sum_by(tier == TIER_FAST, owner, T)
        over2 = np.minimum(np.where(bound > 0, np.maximum(fu2 - bound, 0), 0),
                           k)
        spages = top_quota(cold, owner, tier == TIER_FAST, over2, k, T)
        tel.leave(spages, owner)
        tel.record(self._commit_order(spages), owner, hot, DIR_DEMOTE)
        thrash_new = thrash_new + self._thrash_hits(spages, tpage, ttick, t,
                                                    owner)
        tier[spages] = TIER_SLOW
        sync2 = np.bincount(owner[spages], minlength=T + 1)[:T]
        demo_t = demo_t + sync2

        # ---- 7. counters
        c["promotions"] += promo_t
        c["demotions"] += demo_t
        c["attempted_promotions"] += cand_t
        c["reclaims"] += freed_t
        c["allocations"] += alloc_t
        c["thrash_events"] += thrash_new
        c["sync_demotions"] += np.minimum(sync_q, demo_t) + sync2
        fu = _sum_by(tier == TIER_FAST, owner, T)
        su = _sum_by(tier == TIER_SLOW, owner, T)

        # ---- 7b. statistics
        below = (prot > 0) & (fu < prot) & (fu + su >= prot)
        sums = dict(promo_attempts=cand_t, promo_success=promo_t,
                    demo_attempts=np.minimum(quota, k) + sync2,
                    demo_success=demo_t,
                    contended_ticks=np.full(T, int(contended)),
                    throttled_ticks=throttled.astype(np.int64),
                    below_protection_ticks=below.astype(np.int64))
        wdecay = F32(self.p["obs_window_decay"])
        rates_new = dict(thrash_rate=thrash_new, promo_rate=promo_t,
                         demo_rate=demo_t)
        stats = {n: st["stats." + n].astype(np.int64) + sums[n]
                 for n in STAT_SUMS}
        stats.update({n: wdecay * st["stats." + n].astype(F32)
                      + rates_new[n].astype(F32) for n in STAT_RATES})
        stats["ticks"] = int(st["stats.ticks"]) + 1

        # ---- 8. periodic thrash controller
        if (t + 1) % self.p["controller_period"] == 0:
            rate = (c["thrash_events"] - thr_prev).astype(F32)
            u, prev = (fu + su).astype(F32), use_prev.astype(F32)
            denom = np.maximum(np.maximum(u, prev), F32(1.0))
            steady = (np.abs(u - prev) / denom < F32(
                self.p["steady_active_delta"])) & (
                freed_since.astype(F32) / denom < F32(
                    self.p["steady_free_rate"]))
            thrashing = rate > F32(self.p["r_thrashing"])
            mitigate = steady & thrashing
            recover = ~thrashing & ~mit
            ps = np.where(mitigate, np.maximum(ps * F32(0.5), F32(1 / 64)), ps)
            ps = np.where(recover, np.minimum(ps * F32(2.0), F32(1.0)), ps)
            tpage[:], ttick[:] = -1, 0
            thr_prev, use_prev = c["thrash_events"].copy(), fu + su
            freed_since, mit = np.zeros(T, np.int64), mitigate

        # ---- 9. perf model (exact sums; the program sums in float32)
        a_fast = _sum_by(accesses * (tier == TIER_FAST), owner, T)
        a_slow = _sum_by(accesses * (tier == TIER_SLOW), owner, T)
        a_tot = a_fast + a_slow
        moves = float((promo_t + demo_t).sum())
        lat = np.where(a_tot > 0, (a_fast * self.p["lat_fast"]
                                   + a_slow * self.p["lat_slow"])
                       / np.maximum(a_tot, 1e-9), self.p["lat_fast"]) \
            + moves * self.p["migration_cost"]
        thru = np.where(a_tot > 0, a_tot / lat, 0.0)
        pool_free = int((tier == TIER_NONE).sum()) \
            if self.tr.ownership == "static" else int((owner == T).sum())

        st.update(tier=tier, hot=hot, last_access=last, owner=owner,
                  promo_scale=ps, thrash_prev=thr_prev, usage_prev=use_prev,
                  freed_since=freed_since, steady=steady,
                  mitigated_prev=mit, table_page=tpage, table_tick=ttick,
                  t=t + 1)
        st.update({"counters." + n: v for n, v in c.items()})
        st.update({"stats." + n: v for n, v in stats.items()})
        tel.store(st)
        return dict(fast_usage=fu, slow_usage=su, promotions=promo_t,
                    demotions=demo_t, throughput=thru, latency=lat,
                    promo_scale=ps, thrash_events=c["thrash_events"].copy(),
                    fast_free=self.n_fast - fu.sum(),
                    attempted_promotions=cand_t, pool_free=pool_free)

    def run(self, st: Dict[str, np.ndarray], ticks: int
            ) -> Tuple[Dict[str, np.ndarray], List[Dict]]:
        """Run ``ticks`` ticks from ``st`` (copied) over the traffic rows."""
        st = {k_: np.array(v, copy=True) for k_, v in st.items()}
        outs = []
        for _ in range(ticks):
            row = self.tr.row_of(int(st["t"]))
            outs.append(self.tick(st, tuple(a[row] for a in
                                            self.tr.inputs())))
        return st, outs
