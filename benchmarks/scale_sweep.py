"""Scale sweep for the tiering engine: tick wall-time and compile time
across T (tenants) x L (pages) x mode, batched vs the seed's unrolled
engine — the repo's perf trajectory baseline (benchmarks/results/scale.json).

  PYTHONPATH=src python -m benchmarks.scale_sweep          # full sweep -> scale.json
  PYTHONPATH=src python -m benchmarks.scale_sweep --smoke  # CI: T=16, L=16k budget check

The batched engine's trace is T-independent (one segmented sort per
selection site, scatter-add reductions), so one compiled tick serves any
tenant count; the unrolled baseline pays one top_k per tenant per selection
site. The sweep records both so future PRs have a number to beat.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

TS = (4, 16, 64)
LS = (16384, 65536, 262144)
MODES = ("equilibria", "tpp", "memtis", "static")
SMOKE_BUDGET_S = 120.0          # compile + 50 ticks, T=16, L=16k (CI gate)
RESULTS = os.path.join(os.path.dirname(__file__), "results", "scale.json")


def scale_point(T: int, L: int):
    """The sweep's host: T equal contiguous tenants over L pages, a fast
    tier of L/4, protections at half and bounds at twice the fair share.
    Returns (cfg, owner [L])."""
    from repro.configs.base import TieringConfig
    share = L // (4 * T)        # fast tier is L/4 pages; share = fair split
    cfg = TieringConfig(
        n_tenants=T, n_fast_pages=L // 4, n_slow_pages=L,
        lower_protection=(max(share // 2, 1),) * T,
        upper_bound=(2 * share,) * T)   # exercises Eq.1/Eq.2 + sync path
    owner = np.repeat(np.arange(T, dtype=np.int32), L // T)
    return cfg, owner


def _build(T: int, L: int, mode: str, impl: str):
    import jax.numpy as jnp
    from repro.core.engine import make_tick
    from repro.core.state import init_state

    cfg, owner = scale_point(T, L)
    tick = make_tick(cfg, owner, mode, k_max=256, impl=impl)
    state = init_state(cfg, L)
    rng = np.random.default_rng(0)
    accesses = np.where(rng.random(L) < 0.3, 4.0, 0.1).astype(np.float32)
    inputs = (jnp.asarray(accesses), jnp.ones((L,), bool))
    return tick, state, inputs


def bench_tick(T: int, L: int, mode: str, impl: str = "batched",
               n_ticks: int = 100) -> dict:
    import jax
    tick, state, inputs = _build(T, L, mode, impl)
    tick = jax.jit(tick)
    t0 = time.perf_counter()
    state, out = tick(state, inputs)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n_ticks):
        state, out = tick(state, inputs)
    jax.block_until_ready(out)
    tick_ms = (time.perf_counter() - t0) / n_ticks * 1e3
    return {"mode": mode, "T": T, "L": L, "impl": impl,
            "compile_s": round(compile_s, 3), "tick_ms": round(tick_ms, 3),
            "n_ticks": n_ticks}


def trace_eqns(T: int, L: int, mode: str, impl: str) -> int:
    """Jaxpr equation count of one tick (trace only, no compile)."""
    import jax
    tick, state, inputs = _build(T, L, mode, impl)
    return len(jax.make_jaxpr(tick)(state, inputs).jaxpr.eqns)


def smoke() -> int:
    """CI gate: compile + 50 ticks at T=16, L=16k inside the budget."""
    t0 = time.perf_counter()
    r = bench_tick(16, 16384, "equilibria", "batched", n_ticks=50)
    elapsed = time.perf_counter() - t0
    ok = elapsed < SMOKE_BUDGET_S
    print(f"scale smoke: T=16 L=16384 compile={r['compile_s']:.2f}s "
          f"tick={r['tick_ms']:.2f}ms total={elapsed:.1f}s "
          f"budget={SMOKE_BUDGET_S:.0f}s -> {'OK' if ok else 'OVER BUDGET'}")
    return 0 if ok else 1


def main() -> int:
    if "--smoke" in sys.argv:
        return smoke()
    import jax
    sweep = []
    n_for = {16384: 100, 65536: 50, 262144: 25}
    for mode in MODES:
        for T in TS:
            for L in LS:
                r = bench_tick(T, L, mode, n_ticks=n_for[L])
                sweep.append(r)
                print(f"{mode:10s} T={T:3d} L={L:6d} batched   "
                      f"compile={r['compile_s']:7.2f}s tick={r['tick_ms']:8.3f}ms",
                      flush=True)
    # unrolled baseline at T=64 (the seed engine; fewer ticks, it's slow)
    speedup = {}
    for L in LS:
        u = bench_tick(64, L, "equilibria", impl="unrolled", n_ticks=20)
        sweep.append(u)
        b = next(r for r in sweep
                 if r["impl"] == "batched" and r["mode"] == "equilibria"
                 and r["T"] == 64 and r["L"] == L)
        speedup[f"T=64,L={L}"] = round(u["tick_ms"] / b["tick_ms"], 2)
        print(f"equilibria T= 64 L={L:6d} unrolled  "
              f"compile={u['compile_s']:7.2f}s tick={u['tick_ms']:8.3f}ms "
              f"-> speedup {speedup[f'T=64,L={L}']}x", flush=True)
    eqns = {f"T={T}": trace_eqns(T, 16384, "equilibria", "batched")
            for T in TS}
    out = {
        "meta": {"backend": jax.default_backend(), "k_max": 256,
                 "note": "tick wall-time (ms) and compile time (s) per "
                         "(mode, T, L); speedup = unrolled/batched tick_ms "
                         "at T=64; jaxpr_eqns shows trace T-independence"},
        "jaxpr_eqns_batched": eqns,
        "speedup_vs_unrolled": speedup,
        "sweep": sweep,
    }
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    from benchmarks.run import write_result
    write_result(RESULTS, out,
                 config={"modes": MODES, "TS": TS, "LS": LS, "k_max": 256})
    print(f"wrote {RESULTS}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
