"""Plain reference of a fleet host's chunk, in numpy.

One fleet host runs the churn tick of ``bench/reference/tick.py`` (its
``Host``), and after it, as the program documents them:

* the streaming pathology detectors (tick step 9b): tumbling thrash
  windows over the steady half of the run, protection violation,
  promotion stall and noisy-neighbour counters, and each tick's running
  verdicts, with the window geometry derived from the run's horizon;
* the slowdown-attribution ledger (tick step 9c): the promotion pipeline's
  quota cascade telescoped into five stall components per tenant
  (hot_resident, throttled, mitigated, reclaim, contention), the perf
  model's fast and slow access mass, the modeled stall latency, and a
  histogram of each tenant-tick's stall units (exact buckets below 128,
  four per octave above, lower edges ``128 * 2^(j/4)``, the last open);
* the fleet rollout's per-host sums over the chunk: the tenant-mean
  latency, the summed throughput and the page moves of each tick.

The detector and ledger read what the tick decided: candidates, moves and
reclaims from its outputs and counters, whether a tenant was throttled
from its statistics, the three selections (step-5 demotion,
promotion, upper-bound sync demotion) as the tick commits them. Floats of
the detector are kept in float32 as the program states them; the ledger's
access masses and the sums exactly.

Nothing here imports the program. ``conservation_violations`` counts the
hosts of a fleet whose pages or attribution ledger do not conserve.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench.reference.tick import (COUNTERS, F32, TIER_FAST, TIER_SLOW, Host,
                                  _rank_in, _sum_by)

# pathology thresholds and window geometry (the offline detectors' rules)
STEADY_FRAC = 0.5
THRASH_WINDOW = 20
RESIDENT_MIN_FRAC = 0.5
THRASH_RATE_THRESHOLD = 4.0
THRASH_FRAC_THRESHOLD = 0.5
PROT_TOLERANCE = 0.05
PROT_FRAC_THRESHOLD = 0.25
NOISY_DOMINANCE = 0.5
NOISY_DEGRADE = 1.10
STALL_MIN_ATTEMPTS = 1.0
STALL_SUCCESS = 0.02

SKETCH_LINEAR, SKETCH_LOG, SKETCH_SUB = 128, 36, 4
SKETCH_EDGES = SKETCH_LINEAR * 2.0 ** (
    np.arange(1, SKETCH_LOG, dtype=np.float64) / SKETCH_SUB)

DETECTOR = ("win_events", "win_resident", "windows_resident", "windows_bad",
            "events_resident", "viol_ticks", "fast_sum", "att_steady",
            "promo_steady", "mig_steady", "lat_base_sum", "lat_steady_sum",
            "active_steady", "active_last", "flag_ticks", "first_flag")
# the float fields of the detector, the ledger and the sums
FLOATS = ("det.fast_sum", "det.lat_base_sum", "det.lat_steady_sum",
          "att.acc_fast", "att.acc_slow", "att.stall_sum", "sums.lat",
          "sums.thr")


def geometry(horizon: int) -> Dict[str, int]:
    """Steady window = the last half of the run; thrash window shrunk to a
    quarter of it when two do not fit; noisy baseline = the first
    quarter."""
    s0 = int(horizon * (1 - STEADY_FRAC))
    window = THRASH_WINDOW
    if horizon - s0 < 2 * window:
        window = max((horizon - s0) // 4, 1)
    return {"steady_start": s0, "window": window,
            "base_ticks": max(horizon // 4, 1)}


def sketch_bucket(v: np.ndarray) -> np.ndarray:
    v = np.maximum(np.asarray(v, np.float64), 0.0)
    lin = np.minimum(v.astype(np.int64), SKETCH_LINEAR - 1)
    log = SKETCH_LINEAR + np.searchsorted(SKETCH_EDGES, v, side="right")
    return np.where(v < SKETCH_LINEAR, lin, log)


class _Tap(Host):
    """The host tick, recording each selection in commit order."""

    def _commit_order(self, pages):
        self.selections.append(pages)
        return super()._commit_order(pages)


class FleetHost:
    """One fleet host: ``traffic`` is its archetype's schedule (row ``t %
    period`` at tick ``t``), ``horizon`` the run's planned length."""

    def __init__(self, traffic, params: dict, k_max: int, horizon: int,
                 hot_dtype=np.float32, fused_ewma: bool = False):
        self.host = _Tap(traffic, params, k_max, hot_dtype=hot_dtype,
                         fused_ewma=fused_ewma)
        self.tr, self.p, self.k = traffic, params, k_max
        self.T = traffic.n_tenants
        self.geo = geometry(horizon)
        self.prot0 = np.zeros(self.T, F32)
        lp = np.asarray(traffic.lower_protection[:self.T], F32)
        self.prot0[:lp.shape[0]] = lp

    def run(self, st: Dict[str, np.ndarray], ticks: int
            ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
        """``ticks`` ticks from ``st`` (copied): the state after, and the
        chunk's sums ``sums.lat``, ``sums.thr``, ``sums.mig``."""
        st = {k: np.array(v, copy=True) for k, v in st.items()}
        lat_sum, thr_sum, mig_sum = 0.0, 0.0, 0
        for _ in range(ticks):
            t = int(st["t"])
            rates, want = (a[self.tr.row_of(t)] for a in self.tr.inputs())
            sig = self._tick(st, rates, want)
            self._detect(st, sig, t)
            self._attribute(st, sig)
            lat_sum += float(sig["latency"].mean())
            thr_sum += float(sig["throughput"].sum())
            mig_sum += int((sig["promotions"] + sig["demotions"]).sum())
        sums = {"sums.lat": np.float64(lat_sum),
                "sums.thr": np.float64(thr_sum),
                "sums.mig": np.int64(mig_sum)}
        return st, sums

    # -------------------------------------------------------------- tick --
    def _tick(self, st, rates, want) -> Dict[str, np.ndarray]:
        """The host tick, and the signals steps 9b and 9c read from it."""
        T, k, h = self.T, self.k, self.host
        owner0 = st["owner"].astype(np.int64)
        cnt = np.bincount(owner0[owner0 < T], minlength=T)[:T]
        want = want.astype(np.int64)
        arrived = (cnt == 0) & (want - cnt > 0)
        ps = np.where(arrived, F32(1.0), st["promo_scale"].astype(F32))
        c0 = {n: st["counters." + n].astype(np.int64) for n in COUNTERS}
        thr0 = st["stats.throttled_ticks"].astype(np.int64)
        h.selections = []
        out = h.tick(st, (rates, want))
        _, promoted, synced = h.selections
        owner = st["owner"].astype(np.int64)
        c1 = {n: st["counters." + n].astype(np.int64) for n in COUNTERS}
        throttled = st["stats.throttled_ticks"].astype(np.int64) > thr0

        # Eq.2 on the fast usage it saw: after the step-5 demotions, before
        # the promotions and the sync demotions
        promo_t = np.bincount(owner[promoted], minlength=T + 1)[:T]
        sync_t = np.bincount(owner[synced], minlength=T + 1)[:T]
        usage = (out["fast_usage"] - promo_t + sync_t).astype(F32)
        prot, bound = h._repartition(want > 0)
        pf, bf = prot.astype(F32), bound.astype(F32)
        ref = np.where(pf > 0, pf, np.where(bf > 0, bf, usage))
        ratio = np.where(usage > 0, ref / np.maximum(usage, F32(1.0)),
                         F32(1.0)).astype(F32)
        r2 = ratio * ratio
        factor = np.clip(r2 * r2, F32(self.p["promo_floor"]), F32(1.0))
        p_base = F32(self.p["p_base"])
        p_eq2 = np.where(throttled, p_base * factor, p_base).astype(F32)
        cand = out["attempted_promotions"].astype(np.int64)
        cap = np.minimum(cand, k)
        q_base = np.minimum(int(self.p["p_base"]), cap)
        q_eq2 = np.minimum(np.minimum(p_eq2.astype(np.int64), k), cap)
        q_mit = np.minimum(np.minimum((p_eq2 * ps).astype(np.int64), k),
                           cap)

        # the perf model's access mass by tier, after the tick's moves
        S = rates.shape[1]
        owned = owner < T
        prank = _rank_in(np.where(owned, owner, T), T)
        acc = np.where(owned, rates[np.minimum(owner, T - 1),
                                    np.minimum(prank, S - 1)],
                       F32(0.0)).astype(F32)
        tier = st["tier"].astype(np.int64)
        return dict(
            out, active=want > 0,
            thrash_new=c1["thrash_events"] - c0["thrash_events"],
            freed=c1["reclaims"] - c0["reclaims"],
            q_base=q_base, q_eq2=q_eq2, q_mit=q_mit,
            a_fast=_sum_by(acc * (tier == TIER_FAST), owner, T),
            a_slow=_sum_by(acc * (tier == TIER_SLOW), owner, T))

    # ------------------------------------------------------- 9b. detect --
    def _detect(self, st, sig, t: int) -> None:
        d = {n: st["det." + n].copy() for n in DETECTOR}
        s0, W, base = (self.geo[k] for k in ("steady_start", "window",
                                             "base_ticks"))
        in_steady, past_s0 = t >= s0, t > s0
        active = sig["active"]
        win = np.where(in_steady and past_s0,
                       d["win_events"] + sig["thrash_new"], 0)
        boundary = in_steady and (t - s0) % W == 0
        closed = boundary and past_s0
        bad = win.astype(F32) > F32(THRASH_RATE_THRESHOLD)
        res_ok = d["win_resident"].astype(bool)
        d["windows_resident"] = d["windows_resident"] + (closed & res_ok)
        d["windows_bad"] = d["windows_bad"] + (closed & res_ok & bad)
        d["events_resident"] = d["events_resident"] + np.where(
            closed & res_ok, win, 0)
        d["win_events"] = np.where(closed, 0, win)
        d["win_resident"] = (active if boundary else
                             (res_ok & active if in_steady else res_ok))

        fu = sig["fast_usage"].astype(F32)
        su = sig["slow_usage"].astype(F32)
        prot = self.prot0
        viol = ((prot > 0) & (fu + su >= prot)
                & (fu < prot * F32(1.0 - PROT_TOLERANCE)) & active
                & ((sig["attempted_promotions"] > 0)
                   | (sig["demotions"] > 0)))
        steady = np.full(self.T, in_steady)
        d["viol_ticks"] = d["viol_ticks"] + (steady & viol)
        d["fast_sum"] = (d["fast_sum"] + np.where(steady, fu, F32(0.0))
                         ).astype(F32)
        d["att_steady"] = d["att_steady"] + np.where(
            steady, sig["attempted_promotions"], 0)
        d["promo_steady"] = d["promo_steady"] + np.where(
            steady, sig["promotions"], 0)
        d["active_steady"] = d["active_steady"] + (steady & active)
        d["active_last"] = active if in_steady else d["active_last"]
        mig = sig["promotions"] + sig["demotions"]
        d["mig_steady"] = d["mig_steady"] + np.where(steady, mig, 0)
        lat = sig["latency"].astype(F32)
        d["lat_base_sum"] = (d["lat_base_sum"] + np.where(
            t < base, lat, F32(0.0))).astype(F32)
        d["lat_steady_sum"] = (d["lat_steady_sum"] + np.where(
            steady, lat, F32(0.0))).astype(F32)

        so_far = F32(max(t - s0 + 1, 1))
        n_res = d["windows_resident"].astype(F32)
        f_thrash = (d["windows_resident"] >= 1) & (
            d["windows_bad"].astype(F32) >= F32(THRASH_FRAC_THRESHOLD)
            * n_res)
        gate = active & (d["active_steady"].astype(F32)
                         >= F32(RESIDENT_MIN_FRAC) * so_far)
        f_prot = steady & gate & (prot > 0) & (
            d["viol_ticks"].astype(F32) >= F32(PROT_FRAC_THRESHOLD) * so_far)
        attf = d["att_steady"].astype(F32)
        ratio = d["promo_steady"].astype(F32) / np.maximum(attf, F32(1.0))
        f_stall = (steady & gate & (attf >= F32(STALL_MIN_ATTEMPTS) * so_far)
                   & (ratio < F32(STALL_SUCCESS)))
        if self.T >= 2:
            total = F32(d["mig_steady"].sum())
            share = d["mig_steady"].astype(F32) / max(total, F32(1.0))
            n_base = F32(min(t + 1, base))
            lat_base = np.maximum(d["lat_base_sum"] / max(n_base, F32(1.0)),
                                  F32(1e-9))
            degrade = (d["lat_steady_sum"] / so_far) / lat_base
            top = np.sort(degrade)[::-1]
            worst_other = np.where(degrade >= top[0], top[1], top[0])
            f_noisy = (steady & (total > 0) & (share > F32(NOISY_DOMINANCE))
                       & (worst_other > F32(NOISY_DEGRADE)))
        else:
            f_noisy = np.zeros(self.T, bool)
        flags = np.stack([f_thrash, f_prot, f_noisy, f_stall], axis=-1)
        d["flag_ticks"] = d["flag_ticks"] + flags
        d["first_flag"] = np.where(flags & (d["first_flag"] < 0), t,
                                   d["first_flag"])
        for n, v in d.items():
            kind = (F32 if n in ("fast_sum", "lat_base_sum", "lat_steady_sum")
                    else bool if n in ("win_resident", "active_last")
                    else np.int64)
            st["det." + n] = np.asarray(v).astype(kind)

    # ------------------------------------------------------ 9c. attrib ---
    def _attribute(self, st, sig) -> None:
        cand = sig["attempted_promotions"].astype(np.int64)
        promoted = sig["promotions"].astype(np.int64)
        x4 = sig["q_mit"] - promoted
        comp = np.stack([cand - sig["q_base"] + np.minimum(x4, 0),
                         sig["q_base"] - sig["q_eq2"],
                         sig["q_eq2"] - sig["q_mit"], sig["freed"],
                         np.maximum(x4, 0)], axis=-1)
        total = comp.sum(axis=-1)
        stall = np.maximum(sig["latency"] - self.p["lat_fast"], 0.0)
        sketch = st["att.sketch"].astype(np.int64).copy()
        np.add.at(sketch, sketch_bucket(total), 1)
        st.update({
            "att.comp": st["att.comp"].astype(np.int64) + comp,
            "att.total": st["att.total"].astype(np.int64) + total,
            "att.acc_fast": st["att.acc_fast"].astype(np.float64)
            + sig["a_fast"],
            "att.acc_slow": st["att.acc_slow"].astype(np.float64)
            + sig["a_slow"],
            "att.stall_sum": st["att.stall_sum"].astype(np.float64) + stall,
            "att.ticks": int(st["att.ticks"]) + 1,
            "att.sketch": sketch})


def conservation_violations(tier: np.ndarray, owner: np.ndarray,
                            n_fast: int, counters: dict, comp: np.ndarray,
                            total: np.ndarray) -> int:
    """Hosts ([H, ...] arrays) where a page is not exactly one of fast,
    slow or free, the fast tier holds more than its pages, or the
    attribution ledger does not add up: components that are negative or do
    not sum to the total, or a total other than ``attempted_promotions -
    promotions + reclaims``."""
    T = comp.shape[1]
    placed = (tier == TIER_FAST) | (tier == TIER_SLOW)
    free = owner == T
    pages_bad = (placed == free).any(axis=1)
    over = (tier == TIER_FAST).sum(axis=1) > n_fast
    expect = (counters["attempted_promotions"] - counters["promotions"]
              + counters["reclaims"])
    att_bad = ((comp.sum(axis=-1) != total).any(axis=1)
               | (comp < 0).any(axis=(1, 2)) | (total != expect).any(axis=1))
    return int((pages_bad | over | att_bad).sum())
