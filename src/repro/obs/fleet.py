"""Fleet telemetry harness: the unified tick (core/tick.py) stacked across
N simulated hosts.

This is the ROADMAP's fleet-scale evaluation vehicle, rebuilt on the
unified tick core so a fleet is a batch of *heterogeneous* hosts — static
rosters and churned rosters side by side under ONE ``vmap`` (every host
runs the dynamic-ownership provider; a static host is simply the
degenerate schedule with constant ``want``). Three execution surfaces:

  ``run_fleet``        — the original static-layout fleet (hosts share one
                         owner vector; heterogeneity from workload data).
                         Kept for the obs acceptance property and as the
                         cheapest path when no host churns.
  ``run_mixed_fleet``  — heterogeneous static+churn hosts under one vmap,
                         full per-tick telemetry + pathology detection.
  ``fleet_rollout``    — the long-horizon engine: chunked ``lax.scan``
                         rollouts with donated carries (no host round-trips
                         inside a chunk, O(chunk) not O(horizon) output
                         memory), schedule archetypes gathered in-graph
                         (hosts sharing a schedule cost one copy), tiled
                         periodically so a 10k-tick horizon streams through
                         a fixed-size schedule, and sharded across devices
                         via ``pmap`` when more than one is available.

In-graph obs state (TierStats + migration ring) is collected per host with
zero extra tracing work — ``vmap`` batches the scatter/adds along the host
axis. Host-side, telemetry is decoded per host and rolled up fleet-wide:
latency percentiles, migration rates, pathology counts from
``obs.pathology``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TieringConfig
from repro.core.churn import ChurnSchedule, make_churn_tick
from repro.core.engine import make_tick
from repro.core.simulator import tenant_activity
from repro.core.state import init_state, stack_states
from repro.core.workloads import (ChurnSlot, TenantWorkload, as_churn_slots,
                                  build_churn_schedule, build_trace,
                                  cache_like, ci_like, microbenchmark,
                                  spark_like, thrasher, web_like)
from repro.obs.attribution import (COMPONENTS, AttributionSpec,
                                   attribution_conserved, fast_hit_fraction,
                                   make_attribution)
from repro.obs.pathology import Pathology, count_by_kind, detect_all
from repro.obs.sketch import sketch_merge, sketch_percentiles
from repro.obs.stats import stats_summary
from repro.obs.streaming import (KINDS, DetectorSpec, make_detector,
                                 streaming_pathologies)
from repro.obs.trace import decode_ring

# stable-pattern menu for clean hosts (hot sets that mostly fit fast tier)
MIX_MENU = ("web", "cache", "micro", "ci", "spark")


def heterogeneous_mixes(footprints: Sequence[int], n_hosts: int,
                        seed: int = 0, menu: Sequence[str] = MIX_MENU,
                        stagger: int = 8) -> List[List[TenantWorkload]]:
    """One tenant mix per host. Footprints are fixed per tenant *slot* (every
    host shares the static page-ownership layout ``run_fleet`` needs); the
    workload pattern and arrival of each slot vary per host."""
    rng = np.random.default_rng(seed)
    mk = {
        "web": lambda f, a: web_like(f, arrival=a),
        "cache": lambda f, a: cache_like(f, arrival=a),
        "micro": lambda f, a: microbenchmark(f, arrival=a),
        "ci": lambda f, a: ci_like(f, arrival=a),
        "spark": lambda f, a: spark_like(f, arrival=a),
    }
    mixes = []
    for _ in range(n_hosts):
        mix = []
        for f in footprints:
            kind = menu[int(rng.integers(len(menu)))]
            arrival = int(rng.integers(0, stagger + 1))
            mix.append(mk[kind](f, arrival))
        mixes.append(mix)
    return mixes


def inject_noisy_neighbor(mixes: List[List[TenantWorkload]], tenant: int,
                          fast_share: int,
                          hosts: Optional[Sequence[int]] = None,
                          arrival: Optional[int] = None
                          ) -> List[List[TenantWorkload]]:
    """Replace ``tenant``'s workload with a thrasher (promotion-hot pages
    never re-accessed before demotion — the §V-B5 noisy neighbor) on the
    given hosts (default: all). Footprint is preserved so the fleet keeps a
    common ownership layout. A late ``arrival`` gives detectors a clean
    baseline window before the noise starts."""
    hosts = set(range(len(mixes))) if hosts is None else set(hosts)
    out = []
    for h, mix in enumerate(mixes):
        mix = list(mix)
        if h in hosts:
            a = mix[tenant].arrival if arrival is None else arrival
            mix[tenant] = thrasher(mix[tenant].footprint, fast_share,
                                   arrival=a)
        out.append(mix)
    return out


@dataclass
class FleetResult:
    mode: str
    n_hosts: int
    # [H, ticks, T] each
    fast_usage: np.ndarray
    slow_usage: np.ndarray
    promotions: np.ndarray
    demotions: np.ndarray
    throughput: np.ndarray
    latency: np.ndarray
    thrash_events: np.ndarray
    attempted: np.ndarray
    lower_protection: tuple
    # per-host decoded telemetry
    stats: List[dict] = field(default_factory=list)   # stats_summary per host
    pathologies: List[List[Pathology]] = field(default_factory=list)
    # [H, ticks, T] bool per-host tenant roster (tenant has live pages);
    # detectors and roll-ups use it to tolerate mid-window departures
    active: Optional[np.ndarray] = None
    _final_state: object = None

    def steady_window(self, frac: float = 0.5) -> slice:
        n = self.latency.shape[1]
        return slice(int(n * (1 - frac)), n)

    def host_migrations(self, host: int):
        """Decode one host's migration ring -> (events, n_dropped)."""
        ring = jax.tree_util.tree_map(lambda x: x[host],
                                      self._final_state.ring)
        return decode_ring(ring)

    def pathology_counts(self) -> Dict[str, int]:
        """Fleet-wide counts by kind, keys sorted (stable across runs)."""
        out: Dict[str, int] = {}
        for ps in self.pathologies:
            for k, v in count_by_kind(ps).items():
                out[k] = out.get(k, 0) + v
        return dict(sorted(out.items()))

    def tenants_flagged(self, kind: Optional[str] = None
                        ) -> List[Tuple[int, int]]:
        """Sorted unique (host, tenant) pairs flagged, optionally for one
        pathology kind — deterministic order, safe for golden tests."""
        out = set()
        for h, ps in enumerate(self.pathologies):
            for p in ps:
                if kind is None or p.kind == kind:
                    out.add((h, p.tenant))
        return sorted(out)

    def rollup(self) -> dict:
        """Fleet-wide operator summary. Latency/throughput aggregates cover
        only resident tenant-ticks (``active``) so hosts with mid-window
        departures don't dilute percentiles with the idle-slot constant."""
        w = self.steady_window()
        lat = self.latency[:, w]
        mig = self.promotions[:, w] + self.demotions[:, w]
        hosts_bad = sum(1 for ps in self.pathologies if ps)
        if self.active is not None:
            act = np.asarray(self.active[:, w], bool)
            act = act if act.any() else np.ones_like(act)
            lat_vals = lat[act]
            thru_vals = self.throughput[:, w][act]
            worst_host = max(
                float(np.percentile(lat[h][act[h]], 99))
                for h in range(self.n_hosts) if act[h].any())
        else:
            lat_vals, thru_vals = lat, self.throughput[:, w]
            worst_host = float(np.percentile(lat, 99, axis=(1, 2)).max())
        return {
            "hosts": self.n_hosts,
            "ticks": self.latency.shape[1],
            "tenants": self.latency.shape[2],
            "latency_p50": float(np.percentile(lat_vals, 50)),
            "latency_p99": float(np.percentile(lat_vals, 99)),
            "latency_worst_host_p99": worst_host,
            "throughput_mean": float(thru_vals.mean()),
            "migrations_per_tick": float(mig.sum(axis=2).mean()),
            "thrash_total": int(self.thrash_events[:, -1].sum()),
            "pathology_counts": self.pathology_counts(),
            "hosts_with_pathology": hosts_bad,
        }


def _fleet_result(mode: str, cfg: TieringConfig, finals, outs,
                  active: np.ndarray, detect: bool) -> FleetResult:
    """One FleetResult builder shared by the static and mixed fleets."""
    H = active.shape[0]
    res = FleetResult(
        mode=mode, n_hosts=H,
        fast_usage=np.asarray(outs.fast_usage),
        slow_usage=np.asarray(outs.slow_usage),
        promotions=np.asarray(outs.promotions),
        demotions=np.asarray(outs.demotions),
        throughput=np.asarray(outs.throughput),
        latency=np.asarray(outs.latency),
        thrash_events=np.asarray(outs.thrash_events),
        attempted=np.asarray(outs.attempted_promotions),
        lower_protection=tuple(cfg.lower_protection[:cfg.n_tenants]),
        active=active,
        _final_state=finals)
    res.stats = [stats_summary(jax.tree_util.tree_map(lambda x: x[h],
                                                      finals.stats))
                 for h in range(H)]
    if detect:
        res.pathologies = [
            detect_all(res.fast_usage[h], res.slow_usage[h],
                       res.promotions[h], res.demotions[h], res.latency[h],
                       res.thrash_events[h], attempted=res.attempted[h],
                       lower_protection=res.lower_protection,
                       active=res.active[h])
            for h in range(H)]
    return res


def run_fleet(cfg: TieringConfig, host_mixes: List[List[TenantWorkload]],
              ticks: int, mode: str = "equilibria", k_max: int = 64,
              detect: bool = True) -> FleetResult:
    """Run every host's trace through one vmapped static-provider tick.

    All hosts must share the tenant footprint layout (same owner vector);
    ``heterogeneous_mixes`` guarantees that by construction. For fleets
    mixing static and churned hosts, use ``run_mixed_fleet``.
    """
    traces = [build_trace(mix, ticks) for mix in host_mixes]
    owner = traces[0][0]
    for o, _, _ in traces[1:]:
        if not np.array_equal(o, owner):
            raise ValueError("all hosts must share the footprint layout "
                             "(same per-tenant page counts)")
    cfg = cfg.with_(n_tenants=len(host_mixes[0]))
    H = len(host_mixes)
    accesses = jnp.asarray(np.stack([t[1] for t in traces]), jnp.float32)
    alive = jnp.asarray(np.stack([t[2] for t in traces]), bool)

    tick = make_tick(cfg, owner, mode, k_max)
    states = stack_states(init_state(cfg, owner.shape[0], owner=owner), H)

    @jax.jit
    @jax.vmap
    def run_host(state, acc, alv):
        return jax.lax.scan(tick, state, (acc, alv))

    finals, outs = run_host(states, accesses, alive)
    active = np.stack([tenant_activity(owner, np.asarray(tr[2]),
                                       cfg.n_tenants) for tr in traces])
    return _fleet_result(mode, cfg, finals, outs, active, detect)


# --------------------------------------------------------- mixed fleets ----
def stack_schedules(schedules: List[ChurnSchedule]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Stack per-host churn schedules into fleet arrays, padding every
    host's rates to the fleet-wide max slot footprint.

    Returns (want [H, ticks, T] int32, rates [H, ticks, T, S] f32). Hosts
    must share slot count and horizon; footprints may differ freely (the
    pad rows are dead weight only for hosts with smaller slots).
    """
    ticks, T = schedules[0].want.shape
    for s in schedules[1:]:
        if s.want.shape != (ticks, T):
            raise ValueError("all hosts must share slot count and horizon; "
                             f"got {s.want.shape} vs {(ticks, T)}")
    S = max(s.rates.shape[2] for s in schedules)
    H = len(schedules)
    want = np.stack([s.want for s in schedules]).astype(np.int32)
    rates = np.zeros((H, ticks, T, S), np.float32)
    for h, s in enumerate(schedules):
        rates[h, :, :, :s.rates.shape[2]] = s.rates
    return want, rates


def mixed_fleet_hosts(static_mixes: List[List[TenantWorkload]],
                      churn_hosts: List[List[ChurnSlot]],
                      ticks: int) -> List[List[ChurnSlot]]:
    """Normalize a heterogeneous fleet to churn-slot rosters: static hosts
    become single-episode slots (the degenerate schedule)."""
    return [as_churn_slots(mix, ticks) for mix in static_mixes] + \
        [list(slots) for slots in churn_hosts]


def run_mixed_fleet(cfg: TieringConfig, hosts: List[List[ChurnSlot]],
                    ticks: int, mode: str = "equilibria", k_max: int = 64,
                    detect: bool = True,
                    n_pages: Optional[int] = None) -> FleetResult:
    """Heterogeneous fleet: static and churned hosts side by side under one
    vmap of the unified dynamic-ownership tick. ``hosts`` is one churn-slot
    roster per host (``mixed_fleet_hosts`` builds it from static mixes +
    churn rosters); every host needs the same slot count, nothing else.
    """
    T = len(hosts[0])
    for slots in hosts[1:]:
        if len(slots) != T:
            raise ValueError("all hosts must have the same slot count")
    cfg = cfg.with_(n_tenants=T)
    want, rates = stack_schedules(
        [build_churn_schedule(slots, ticks) for slots in hosts])
    H = want.shape[0]
    L = n_pages if n_pages is not None else \
        cfg.n_fast_pages + cfg.n_slow_pages
    tick = make_churn_tick(cfg, L, mode=mode, k_max=k_max)
    states = stack_states(init_state(cfg, L), H)

    @jax.jit
    @jax.vmap
    def run_host(state, r, w):
        return jax.lax.scan(tick, state, (r, w))

    finals, outs = run_host(states, jnp.asarray(rates, jnp.float32),
                            jnp.asarray(want, jnp.int32))
    return _fleet_result(mode, cfg, finals, outs, want > 0, detect)


# ----------------------------------------------- long-horizon rollouts ----
_WRAP32 = 1 << 32


class CounterLedger:
    """Wrap-safe host-side int64 widening of in-graph int32 counters.

    x64 is globally disabled, so the scan-carried cumulative counters
    (``Counters``, the attribution ledger) are int32 *in-graph* and wrap at
    fleet horizons (the overflow pass proves e.g. ``attempted_promotions``
    unsafe past ~2^31/L ticks). Rather than widening device state, the
    ledger promotes at the chunk boundary: counters are monotone mod 2^32,
    so ``(now - prev) mod 2^32`` is the *exact* per-chunk growth whenever a
    single chunk grows a counter by < 2^32 — true by construction (a chunk
    of C ticks grows any per-tenant counter by at most C * L). The int64
    running totals therefore stay exact at any horizon while the device
    carry stays int32.
    """

    def __init__(self, tree):
        self.prev = jax.tree_util.tree_map(
            lambda x: np.asarray(x).astype(np.int64), tree)
        self.total = jax.tree_util.tree_map(np.zeros_like, self.prev)

    def absorb(self, tree) -> None:
        now = jax.tree_util.tree_map(
            lambda x: np.asarray(x).astype(np.int64), tree)
        self.total = jax.tree_util.tree_map(
            lambda t, p, n: t + ((n - p) % _WRAP32),
            self.total, self.prev, now)
        self.prev = now


def make_fleet_chunk(vtick, want_j: jax.Array, rates_j: jax.Array,
                     period: int, n: int):
    """The chunk program: one ``lax.scan`` of ``n`` ticks over the vmapped
    tick, schedule columns gathered per host in-graph, per-tick outputs
    reduced to [H] running sums inside the scan.

    Module-level so the jaxpr auditor can trace it directly (purity /
    dtype / overflow / donation targets) and so its carries are visible in
    tests. The migration accumulator is deliberately **int32**: promotions
    and demotions are integer counts, and accumulating them in float32
    silently drops units past 2^24 (the overflow pass's carry-precision
    rule flags exactly that regression); the int32 carry is exact up to
    2^31 per chunk and is widened to int64 host-side (``CounterLedger`` /
    ``absorb``).
    """
    def chunk_fn(states, arch, t0):
        zero_f = jnp.zeros(arch.shape, jnp.float32)
        zero_i = jnp.zeros(arch.shape, jnp.int32)

        def body(carry, i):
            st, lat, thr, mig = carry
            tm = jnp.mod(t0 + i, period)
            w = jax.lax.dynamic_index_in_dim(want_j, tm, axis=1,
                                             keepdims=False)
            r = jax.lax.dynamic_index_in_dim(rates_j, tm, axis=1,
                                             keepdims=False)
            st, out = vtick(st, (r[arch], w[arch]))
            lat = lat + out.latency.mean(axis=-1)
            thr = thr + out.throughput.sum(axis=-1)
            mig = mig + (out.promotions + out.demotions).sum(axis=-1)
            return (st, lat, thr, mig), None

        (states, lat, thr, mig), _ = jax.lax.scan(
            body, (states, zero_f, zero_f, zero_i),
            jnp.arange(n, dtype=jnp.int32))
        return states, (lat, thr, mig)
    return chunk_fn


@dataclass
class RolloutSummary:
    """Chunked-rollout result: final fleet state plus streamed per-host
    reductions (full per-tick arrays are never materialized — output memory
    is O(1) in the horizon)."""
    n_hosts: int
    ticks: int
    chunk: int
    sharded: bool
    elapsed_s: float                 # wall time of the rollout loop
    latency_mean: np.ndarray         # [H] mean per-tick tenant-mean latency
    throughput_mean: np.ndarray      # [H] mean per-tick total throughput
    migrations_per_tick: np.ndarray  # [H]
    final_state: object = None       # batched TierState [H, ...]
    detector: Optional[DetectorSpec] = None
    attribution: Optional[AttributionSpec] = None
    # host-side int64 widening of the in-graph int32 cumulative counters
    # ({"counters": Counters, "att": {...}}), exact at any horizon
    ledger: Optional[CounterLedger] = None

    @property
    def host_ticks_per_s(self) -> float:
        return self.n_hosts * self.ticks / max(self.elapsed_s, 1e-9)

    def host_stats(self, host: int) -> dict:
        return stats_summary(jax.tree_util.tree_map(
            lambda x: x[host], self.final_state.stats))

    def counters(self):
        """Cumulative per-tenant counters [H, T]. With the chunk-boundary
        ledger (the default rollout path) these are int64 and exact even
        where the in-graph int32 carry wrapped."""
        if self.ledger is not None:
            return self.ledger.total["counters"]
        return jax.tree_util.tree_map(np.asarray, self.final_state.counters)

    def host_migrations(self, host: int):
        """Decode one host's migration ring -> (events, n_dropped)."""
        ring = jax.tree_util.tree_map(lambda x: x[host],
                                      self.final_state.ring)
        return decode_ring(ring)

    # ---- streaming pathology telemetry (obs/streaming.py) ----------------
    def host_pathologies(self, host: int) -> List[Pathology]:
        """One host's end-of-run pathologies from its streamed counters."""
        if self.detector is None:
            raise ValueError("rollout ran with detect=False")
        det = jax.tree_util.tree_map(lambda x: x[host], self.final_state.det)
        return streaming_pathologies(self.detector, det)

    def pathology_flag_ticks(self) -> np.ndarray:
        """[H, T, len(KINDS)] int32: ticks each running flag held."""
        return np.asarray(self.final_state.det.flag_ticks)

    def pathology_first_flag(self) -> np.ndarray:
        """[H, T, len(KINDS)] int32: first tick each flag held (-1 never)."""
        return np.asarray(self.final_state.det.first_flag)

    def pathology_counts(self) -> Dict[str, int]:
        """Fleet-wide end-of-run counts by kind, keys sorted."""
        out: Dict[str, int] = {}
        for h in range(self.n_hosts):
            for k, v in count_by_kind(self.host_pathologies(h)).items():
                out[k] = out.get(k, 0) + v
        return dict(sorted(out.items()))

    def tenants_flagged(self, kind: Optional[str] = None
                        ) -> List[Tuple[int, int]]:
        """Sorted unique (host, tenant) pairs flagged end-of-run."""
        out = set()
        for h in range(self.n_hosts):
            for p in self.host_pathologies(h):
                if kind is None or p.kind == kind:
                    out.add((h, p.tenant))
        return sorted(out)

    # ---- slowdown attribution ledger (obs/attribution.py) ----------------
    def _att(self):
        if self.attribution is None:
            raise ValueError("rollout ran with attrib=False")
        return self.final_state.attrib

    def _att_ledger(self) -> Optional[dict]:
        if self.ledger is not None and "att" in self.ledger.total:
            if self.attribution is None:
                raise ValueError("rollout ran with attrib=False")
            return self.ledger.total["att"]
        return None

    def attribution_components(self) -> np.ndarray:
        """[H, T, len(COMPONENTS)] int64 cumulative stall units by cause
        (ledger-widened: exact past int32 wrap on the default path)."""
        led = self._att_ledger()
        if led is not None:
            return led["comp"]
        return np.asarray(self._att().comp, np.int64)

    def attribution_totals(self) -> np.ndarray:
        """[H, T] int64 cumulative stall units (== components summed)."""
        led = self._att_ledger()
        if led is not None:
            return led["total"]
        return np.asarray(self._att().total, np.int64)

    def fast_hit_fraction(self) -> np.ndarray:
        """[H, T] fraction of access mass served from the fast tier."""
        return fast_hit_fraction(self._att())

    def stall_sketch(self) -> np.ndarray:
        """Fleet-merged per-tick stall-unit histogram ([SKETCH_BUCKETS])."""
        led = self._att_ledger()
        if led is not None:
            return sketch_merge(led["sketch"])
        return sketch_merge(self._att().sketch)

    def stall_percentiles(self, qs=(0.5, 0.95, 0.99)) -> np.ndarray:
        """Fleet-wide per-tick total-stall percentiles from the merged
        sketch — O(1) output memory at any horizon or fleet size."""
        return np.asarray(sketch_percentiles(self.stall_sketch(), qs))

    def attribution_conserved(self) -> bool:
        """Every host's ledger conserves: components sum to the total and
        the total matches the counter identity, bit-exact. On the default
        path the identity is checked on the int64-widened values, so it
        holds even past the in-graph int32 wrap point."""
        led = self._att_ledger()
        if led is not None:
            c = self.counters()
            comp, total = led["comp"], led["total"]
            expect = (np.asarray(c.attempted_promotions, np.int64)
                      - np.asarray(c.promotions, np.int64)
                      + np.asarray(c.reclaims, np.int64))
            return bool((comp.sum(axis=-1) == total).all()
                        and (comp >= 0).all()
                        and (total == expect).all())
        return attribution_conserved(self._att(), self.final_state.counters)

    def attribution_rollup(self) -> dict:
        """Operator roll-up: fleet component shares, worst tenants, sketch
        percentiles (O(H * T) host memory, like ``pathology_rollup``)."""
        comp = self.attribution_components()
        total = self.attribution_totals()
        fleet = comp.sum(axis=(0, 1))
        denom = max(int(fleet.sum()), 1)
        worst = np.unravel_index(np.argmax(total), total.shape)
        p50, p95, p99 = self.stall_percentiles((0.5, 0.95, 0.99))
        return {
            "hosts": self.n_hosts,
            "ticks": self.ticks,
            "stall_units_total": int(total.sum()),
            "component_totals": {k: int(v)
                                 for k, v in zip(COMPONENTS, fleet)},
            "component_shares": {k: float(v) / denom
                                 for k, v in zip(COMPONENTS, fleet)},
            "worst_tenant": (int(worst[0]), int(worst[1])),
            "worst_tenant_stall": int(total[worst]),
            "stall_p50": float(p50),
            "stall_p95": float(p95),
            "stall_p99": float(p99),
            "conserved": self.attribution_conserved(),
        }

    def pathology_rollup(self) -> dict:
        """Operator roll-up of the streamed pathology state (the fleet-scale
        analogue of ``FleetResult.rollup``, O(H * T) not O(H * ticks))."""
        flagged = self.tenants_flagged()
        first = self.pathology_first_flag()
        return {
            "hosts": self.n_hosts,
            "ticks": self.ticks,
            "pathology_counts": self.pathology_counts(),
            "tenants_flagged": flagged,
            "hosts_with_pathology": len({h for h, _ in flagged}),
            "earliest_flag_tick": (int(first[first >= 0].min())
                                   if (first >= 0).any() else -1),
        }


def fleet_rollout(cfg: TieringConfig, want: np.ndarray, rates: np.ndarray,
                  ticks: int, *, host_arch: Optional[np.ndarray] = None,
                  mode: str = "equilibria", k_max: int = 64,
                  chunk: int = 256, n_pages: Optional[int] = None,
                  shard: bool = True, warmup: bool = False,
                  detect: bool = True, attrib: bool = True) -> RolloutSummary:
    """Advance a fleet over a long horizon without host round-trips or
    memory blowup.

    want [A, P, T] / rates [A, P, T, S] are schedule *archetypes* over a
    period P; ``host_arch`` [H] maps each host to its archetype (default:
    one host per archetype). The schedule is tiled in time (tick t reads
    column ``t % P``) and gathered per host in-graph, so H hosts over a
    10k-tick horizon cost O(A * P) schedule memory, not O(H * ticks).

    Execution is chunked: one jitted ``lax.scan`` of ``chunk`` ticks with
    the fleet state donated between chunks (XLA reuses the carry buffers;
    per-tick outputs are reduced inside the scan to [H] running sums).
    With more than one local device and ``shard=True``, chunks run under
    ``pmap`` with hosts sharded across devices; H must then divide over the
    device count (ValueError otherwise — ``shard=False`` runs on one).

    ``warmup=True`` runs one throwaway chunk on a scratch fleet state
    before the timed rollout so ``elapsed_s`` measures steady-state
    execution, not XLA compilation (the benchmark gate's tick-rate).

    ``detect=True`` (default) carries the streaming pathology detectors
    (obs/streaming.py) in the fleet state: per-host per-tenant flag counters
    and first-flag ticks at any horizon, O(H * T) extra memory — the
    observability the chunked rollout exists to keep while never
    materializing ``[ticks, ...]`` traces.

    ``attrib=True`` (default) additionally carries the per-tenant slowdown
    attribution ledger (obs/attribution.py): cumulative stall units by
    cause, fast-tier access mass, and a fixed-size mergeable stall sketch —
    again O(H * T) state, so fleet attribution percentiles come out of a
    10k-tick rollout in O(1) output memory (``attribution_rollup``).
    """
    want = np.asarray(want)
    rates = np.asarray(rates)
    A, period, T = want.shape
    host_arch = np.arange(A) if host_arch is None else np.asarray(host_arch)
    if host_arch.size and (host_arch.min() < 0 or host_arch.max() >= A):
        # XLA gathers clamp out-of-range indices silently — fail loudly here
        raise ValueError(f"host_arch must map into [0, {A}) archetypes")
    H = host_arch.shape[0]
    L = n_pages if n_pages is not None else \
        cfg.n_fast_pages + cfg.n_slow_pages
    cfg = cfg.with_(n_tenants=T)
    det_spec = (make_detector(ticks, T, cfg.lower_protection)
                if detect else None)
    att_spec = make_attribution(T, cfg.lat_fast) if attrib else None
    tick = make_churn_tick(cfg, L, mode=mode, k_max=k_max, detector=det_spec,
                           attrib=att_spec)
    vtick = jax.vmap(tick)
    want_j = jnp.asarray(want, jnp.int32)
    rates_j = jnp.asarray(rates, jnp.float32)

    def make_chunk_fn(n: int):
        return make_fleet_chunk(vtick, want_j, rates_j, period, n)

    chunk = max(min(chunk, ticks), 1)
    D = jax.local_device_count()
    use_pmap = bool(shard) and D > 1
    if use_pmap and H % D:
        raise ValueError(f"fleet_rollout shards hosts across devices: "
                         f"H={H} hosts do not divide over D={D} devices; "
                         f"pass shard=False to run on one device")
    states = stack_states(init_state(cfg, L, detector=det_spec,
                                     attrib=att_spec), H)
    if use_pmap:
        def resh(x):
            return jnp.reshape(x, (D, H // D) + x.shape[1:])
        states = jax.tree_util.tree_map(resh, states)
        arch = jnp.asarray(host_arch.reshape(D, H // D))

        def compile_chunk(n):
            return jax.pmap(make_chunk_fn(n), in_axes=(0, 0, None),
                            donate_argnums=(0,))
    else:
        arch = jnp.asarray(host_arch)

        def compile_chunk(n):
            return jax.jit(make_chunk_fn(n), donate_argnums=(0,))

    run_chunk = compile_chunk(chunk)
    n_full, rem = divmod(ticks, chunk)
    run_rem = compile_chunk(rem) if rem else None

    if warmup:
        # compile (and once-run) every chunk program on a scratch state —
        # donation consumes the scratch buffers, the real fleet is untouched
        scratch = stack_states(init_state(cfg, L, detector=det_spec,
                                          attrib=att_spec), H)
        if use_pmap:
            scratch = jax.tree_util.tree_map(resh, scratch)
        scratch, _ = run_chunk(scratch, arch, 0)
        if run_rem is not None:
            jax.block_until_ready(
                jax.tree_util.tree_leaves(run_rem(scratch, arch, 0)[0])[0])
        else:
            jax.block_until_ready(jax.tree_util.tree_leaves(scratch)[0])

    lat_sum = np.zeros(H, np.float64)
    thr_sum = np.zeros(H, np.float64)
    mig_sum = np.zeros(H, np.int64)

    def host_view(tree):
        """Pull a device subtree to host with a flat [H, ...] host axis."""
        return jax.tree_util.tree_map(
            lambda x: np.asarray(x).reshape((H,) + np.shape(x)[2:])
            if use_pmap else np.asarray(x), tree)

    def ledger_view(st):
        tree = {"counters": st.counters}
        if att_spec is not None:
            tree["att"] = {"comp": st.attrib.comp, "total": st.attrib.total,
                           "sketch": st.attrib.sketch}
        return host_view(tree)

    ledger = CounterLedger(ledger_view(states))

    def absorb(acc):
        nonlocal lat_sum, thr_sum, mig_sum
        lat, thr, mig = (np.asarray(a).reshape(H) for a in acc)
        lat_sum = lat_sum + lat
        thr_sum = thr_sum + thr
        # the chunk's int32 migration count, widened wrap-safe like the
        # cumulative counters (exact while one chunk migrates < 2^32 pages)
        mig_sum = mig_sum + (mig.astype(np.int64) % _WRAP32)

    t0_wall = time.perf_counter()
    t = 0
    for _ in range(n_full):
        states, acc = run_chunk(states, arch, t)
        absorb(acc)
        ledger.absorb(ledger_view(states))
        t += chunk
    if run_rem is not None:
        states, acc = run_rem(states, arch, t)
        absorb(acc)
        ledger.absorb(ledger_view(states))
        t += rem
    jax.block_until_ready(jax.tree_util.tree_leaves(states)[0])
    elapsed = time.perf_counter() - t0_wall

    if use_pmap:
        states = jax.tree_util.tree_map(
            lambda x: jnp.reshape(x, (H,) + x.shape[2:]), states)
    return RolloutSummary(
        n_hosts=H, ticks=ticks, chunk=chunk, sharded=use_pmap,
        elapsed_s=elapsed,
        latency_mean=lat_sum / ticks,
        throughput_mean=thr_sum / ticks,
        migrations_per_tick=mig_sum / ticks,
        final_state=states, detector=det_spec, attribution=att_spec,
        ledger=ledger)
