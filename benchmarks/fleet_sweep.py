"""Fleet sweep: hosts x horizon through the unified tick's chunked rollout
(obs/fleet.fleet_rollout) — mixed static+churn fleets, schedule archetypes
gathered in-graph, donated carries, pmap-sharded when devices allow.

  PYTHONPATH=src python -m benchmarks.fleet_sweep          # full sweep -> fleet.json
  PYTHONPATH=src python -m benchmarks.fleet_sweep --smoke  # CI gate (128 hosts x 10k)

The smoke is the PR-5 acceptance run: a 128-host fleet mixing static and
churned rosters advances a 10,000-tick horizon through the chunked rollout,
and its host-tick rate must be no worse than the pre-refactor
``scale_sweep`` baseline's tick rate (benchmarks/results/scale.json,
equilibria/batched at T=16, L=16k): the fleet harness must deliver
simulated host-ticks at least as fast as the prior single-host engine
delivered ticks, or batching has regressed. Conservation (fast + slow +
free == L on every host) is asserted on the final fleet state.

When only one device is visible, the smoke re-execs itself with
``--xla_force_host_platform_device_count`` so the pmap-sharded path runs in
CI (on CPU the forced devices share cores; the speedup is modest but the
code path is exercised).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SMOKE_HOSTS = 128
SMOKE_TICKS = 10_000
SMOKE_CHUNK = 500
SMOKE_BUDGET_S = 420.0
HOSTS = (8, 32, 128)
HORIZONS = (1_000, 10_000)
RESULTS = os.path.join(os.path.dirname(__file__), "results", "fleet.json")
SCALE_RESULTS = os.path.join(os.path.dirname(__file__), "results",
                             "scale.json")


def _archetypes(period: int):
    """Tiny mixed rosters (T=3 slots per host): two static archetypes
    (single-episode slots — the degenerate schedule) and two churned ones
    (random lifecycle episodes). Small footprints keep the CI smoke's
    128 x 10k host-tick volume inside budget on CPU."""
    from repro.core.workloads import (ChurnSlot, as_churn_slots, cache_like,
                                      spark_like, stream_like, web_like)

    def churn_roster(seed: int):
        rng = np.random.default_rng(seed)
        kinds = (web_like, cache_like, spark_like)
        slots = []
        for i in range(3):
            w = kinds[(i + seed) % 3](6 + 2 * i)
            w.ramp = 2
            eps, t = [], int(rng.integers(0, 10))
            while t < period:
                life = 8 + int(rng.integers(0, 30))
                eps.append((t, min(t + life, period)))
                t += life + 1 + int(rng.integers(2, 12))
            slots.append(ChurnSlot(w, eps))
        return slots

    static = [as_churn_slots([web_like(6), cache_like(8), stream_like(10)],
                             period),
              as_churn_slots([cache_like(6), web_like(10), spark_like(8)],
                             period)]
    churned = [churn_roster(0), churn_roster(1)]
    return static + churned


def _config():
    from repro.configs.base import TieringConfig
    # protections fit fast - wmark; a bound on slot 2 exercises the sync path
    return TieringConfig(n_tenants=3, n_fast_pages=16, n_slow_pages=24,
                         lower_protection=(3, 3, 0), upper_bound=(0, 0, 6))


def _build_fleet(period: int):
    from repro.core.workloads import build_churn_schedule
    from repro.obs.fleet import stack_schedules
    archs = _archetypes(period)
    want, rates = stack_schedules(
        [build_churn_schedule(slots, period) for slots in archs])
    return want, rates


def _baseline_tick_rate() -> float:
    """ticks/s of the pre-refactor scale_sweep baseline (equilibria,
    batched, T=16, L=16384). Falls back to measuring it if scale.json is
    missing."""
    try:
        with open(SCALE_RESULTS) as f:
            for r in json.load(f)["sweep"]:
                if (r["mode"] == "equilibria" and r["impl"] == "batched"
                        and r["T"] == 16 and r["L"] == 16384):
                    return 1e3 / r["tick_ms"]
    except (OSError, KeyError, ValueError):
        pass
    from benchmarks.scale_sweep import bench_tick
    return 1e3 / bench_tick(16, 16384, "equilibria", n_ticks=20)["tick_ms"]


def rollout(H: int, ticks: int, chunk: int, warmup: bool = True,
            shard: bool = True):
    """The sweep's mixed fleet of H hosts over ``ticks`` through
    ``fleet_rollout`` (detectors and attribution on). Returns
    (cfg, summary, lifecycle events)."""
    from repro.core.churn import churn_events
    from repro.obs.fleet import fleet_rollout
    period = min(SMOKE_CHUNK, ticks)
    want, rates = _build_fleet(period)
    A = want.shape[0]
    host_arch = np.arange(H) % A
    cfg = _config()
    summary = fleet_rollout(cfg, want, rates, ticks, host_arch=host_arch,
                            chunk=chunk, k_max=16, warmup=warmup,
                            shard=shard)
    per_arch = [sum(churn_events(want[a])) for a in range(A)]
    events = sum(per_arch[a] for a in host_arch)
    return cfg, summary, events


def conserved(cfg, summary) -> bool:
    """fast + slow + free == L on every host of the final fleet state."""
    from repro.core.state import TIER_FAST, TIER_SLOW
    tier = np.asarray(summary.final_state.tier)
    owner = np.asarray(summary.final_state.owner)
    L = tier.shape[1]
    fast = (tier == TIER_FAST).sum(axis=1)
    slow = (tier == TIER_SLOW).sum(axis=1)
    free = (owner == cfg.n_tenants).sum(axis=1)
    return bool((fast + slow + free == L).all())


def _fork_for_devices() -> None:
    """Re-exec with forced host devices so the pmap-sharded path runs even
    on a single-device CPU install (no-op if already multi-device)."""
    if os.environ.get("REPRO_FLEET_NO_FORK"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    # largest power of two <= min(cores, 8): always divides the 128-host
    # smoke fleet (fleet_rollout refuses a host count that does not divide)
    n = 1 << (min(os.cpu_count() or 1, 8).bit_length() - 1)
    if n < 2:
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (flags + " "
                        f"--xla_force_host_platform_device_count={n}").strip()
    env["REPRO_FLEET_NO_FORK"] = "1"
    os.execve(sys.executable,
              [sys.executable, "-m", "benchmarks.fleet_sweep"] + sys.argv[1:],
              env)


def smoke() -> int:
    _fork_for_devices()
    import jax
    t0 = time.perf_counter()
    base_rate = _baseline_tick_rate()
    cfg, summary, events = rollout(SMOKE_HOSTS, SMOKE_TICKS, SMOKE_CHUNK)
    elapsed = time.perf_counter() - t0
    L = cfg.n_fast_pages + cfg.n_slow_pages
    rate = summary.host_ticks_per_s
    ok_conserved = conserved(cfg, summary)
    # streaming pathology telemetry rode along in the fleet carry: flag
    # counters at any horizon with O(H * T) memory, never [ticks, ...]
    detected = summary.detector is not None
    flags = summary.pathology_flag_ticks() if detected else None
    ok = (rate >= base_rate and ok_conserved and elapsed < SMOKE_BUDGET_S
          and events > 0 and detected)
    print(f"fleet smoke: {SMOKE_HOSTS} mixed hosts (static+churn, "
          f"{events} lifecycle events) x {SMOKE_TICKS} ticks, "
          f"chunk={summary.chunk}, sharded={summary.sharded} "
          f"({jax.local_device_count()} devices)")
    if detected:
        from repro.obs.streaming import KINDS
        per_kind = {k: int(flags[:, :, i].sum())
                    for i, k in enumerate(KINDS)}
        hosts_flagged = int((flags.sum(axis=(1, 2)) > 0).sum())
        print(f"  pathology flag-ticks (streamed, {flags.shape} counters): "
              f"{per_kind}; hosts with any flag: {hosts_flagged}; "
              f"end-of-run counts: {summary.pathology_counts()}")
    print(f"  rollout {summary.elapsed_s:.1f}s steady -> "
          f"{rate:,.0f} host-ticks/s "
          f"({rate * L:,.0f} page-ticks/s), baseline {base_rate:,.1f} "
          f"ticks/s; conserved={ok_conserved} "
          f"total={elapsed:.1f}s budget={SMOKE_BUDGET_S:.0f}s "
          f"-> {'OK' if ok else 'FAIL'}")
    if not summary.sharded:
        print("  note: single device visible — the pmap-sharded path was "
              "NOT exercised this run")
    return 0 if ok else 1


def main() -> int:
    if "--smoke" in sys.argv:
        return smoke()
    _fork_for_devices()
    import jax
    base_rate = _baseline_tick_rate()
    sweep = []
    for H in HOSTS:
        for ticks in HORIZONS:
            cfg, summary, events = rollout(H, ticks, SMOKE_CHUNK)
            L = cfg.n_fast_pages + cfg.n_slow_pages
            r = {"hosts": H, "ticks": ticks, "chunk": summary.chunk,
                 "sharded": summary.sharded,
                 "lifecycle_events": events,
                 "steady_s": round(summary.elapsed_s, 2),
                 "host_ticks_per_s": round(summary.host_ticks_per_s, 1),
                 "page_ticks_per_s": round(summary.host_ticks_per_s * L, 1),
                 "fleet_tick_ms": round(
                     summary.elapsed_s / ticks * 1e3, 3),
                 "conserved": conserved(cfg, summary)}
            sweep.append(r)
            print(f"H={H:4d} ticks={ticks:6d} sharded={r['sharded']!s:5s} "
                  f"tick={r['fleet_tick_ms']:7.2f}ms "
                  f"host-ticks/s={r['host_ticks_per_s']:10,.0f} "
                  f"conserved={r['conserved']}", flush=True)
    out = {
        "meta": {"backend": jax.default_backend(),
                 "devices": jax.local_device_count(),
                 "baseline_ticks_per_s": round(base_rate, 2),
                 "note": "mixed static+churn fleets through the unified "
                         "tick's chunked rollout; host_ticks_per_s is the "
                         "gate metric vs the scale_sweep single-host "
                         "baseline tick rate"},
        "sweep": sweep,
    }
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    from benchmarks.run import write_result
    write_result(RESULTS, out, config=cfg)
    print(f"wrote {RESULTS}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
