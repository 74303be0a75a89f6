#!/usr/bin/env python3
"""Chip smoke test: drives the tiering system's main paths once on a TPU,
through the entry points a user calls, and checks what comes out.

  python3 chip_smoke.py               # one chip: phases 1-5
  python3 chip_smoke.py --four-chip   # four chips: phase 1, then the
                                      # sharded fleet against one chip

Phases, all in this one process (a chip belongs to one process); the first
failed check exits non-zero:

  1. device  — the platform must be "tpu": there is no CPU fallback.
  2. golden  — the golden-trace scenarios (``repro.core.golden``) replayed
               and diffed against ``tests/golden/*.json`` (exact integers,
               floats to 1e-4).
  3. engine  — the scale point (T=64, L=262144, fast tier L/4,
               equilibria) for 50 ticks through ``run_engine``, "batched"
               against "pallas" (Mosaic kernels): pages conserved every tick
               and the two results bitwise equal; then the same on the
               churn16 preset under dynamic ownership.
  4. fleet   — ``fleet_rollout`` over 128 mixed hosts x 2000 ticks with
               detectors and attribution on: pages conserved on every host
               and the attribution ledger's identity.
  5. serve   — llama32_1b at its published widths (random weights from a
               seed) through the tiered-KV serve step as ``repro.launch.serve``
               builds it: 4 tenants, batch 8, 64 greedy decode steps under
               equilibria with a fast-tier bound. The bf16 logits are held
               to ``model_forward`` on the same tokens within SERVE_BF16_TOL,
               and an f32 run with exact matmuls within SERVE_F32_TOL.

Each phase prints one line with its wall seconds and the seconds XLA spent
compiling in it (less on a second run: the persistent compile cache is on).
The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Serving against the full-sequence forward on the same tokens. In bf16,
# as the model is configured, both graphs round at every one of some
# hundred ops along 16 layers, in different places (the decode step attends
# over the tiered page pools one token at a time), so they drift apart like
# a random walk of bf16 roundings: sqrt(100) x 2^-8 ~ 0.04 of the logits'
# scale. The bound allows 2.5x that, on the largest logit error relative to
# the largest logit, and on every position's relative RMS error.
SERVE_BF16_TOL = 0.1
# A greedy token may then differ from the forward's only at a near-tie: the
# forward's logit for the served token is within that same bound of its
# best. Positions with such a flip stay a minority.
SERVE_GREEDY_MIN = 0.8
# The same serve step with f32 activations and exact (highest-precision)
# matmuls is a witness that the tiered path itself computes the forward's
# function: it must agree to f32 accumulation-order noise.
SERVE_F32_TOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums XLA backend-compile seconds (cache hits compile nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.total = 0.0

        def listen(event, secs, **_):
            if event == self.EVENT:
                self.total += secs
        jax.monitoring.register_event_duration_secs_listener(listen)


def run_phase(name, fn, clock, *args):
    t0, c0 = time.perf_counter(), clock.total
    detail = fn(*args)
    print(f"[{name}] OK {detail} | wall {time.perf_counter() - t0:.1f}s "
          f"compile {clock.total - c0:.1f}s", flush=True)


def bitwise_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype \
                or x.tobytes() != y.tobytes():
            return False
    return True


# ------------------------------------------------------------- phases ----
def phase_device(n_chips: int) -> dict:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"JAX found no usable device: {e}") from None
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    check(d.platform == "tpu",
          f"no TPU: JAX found platform {d.platform!r}; this smoke test runs "
          f"only on a TPU")
    check(len(devs) >= n_chips,
          f"{n_chips} chips needed, JAX sees {len(devs)}")
    return info


def phase_golden(golden_dir: str) -> str:
    from repro.core.golden import SCENARIOS, GoldenMismatch, collect, diff_all
    for name in sorted(SCENARIOS):
        with open(os.path.join(golden_dir, f"{name}.json")) as f:
            want = json.load(f)
        try:
            diff_all(collect(SCENARIOS[name]()), want)
        except GoldenMismatch as e:
            raise SmokeFailure(f"golden {name}: {e}") from None
    return f"{len(SCENARIOS)} scenarios match {golden_dir}"


def _conserved_ticks(outs, n_pages: int, n_fast: int) -> bool:
    """fast + slow + free == L and fast <= fast capacity, every tick."""
    import numpy as np
    fast = np.asarray(outs.fast_usage).sum(-1)
    slow = np.asarray(outs.slow_usage).sum(-1)
    free = np.asarray(outs.pool_free)
    return bool((fast + slow + free == n_pages).all()
                and (fast <= n_fast).all())


def phase_engine(T: int = 64, L: int = 262144, ticks: int = 50,
                 churn_ticks: int = 240, seed: int = 0) -> str:
    import numpy as np
    from benchmarks.scale_sweep import scale_point
    from repro.core.churn import churn_events, run_churn_engine
    from repro.core.engine import run_engine
    from repro.core.simulator import CHURN_PRESETS
    from repro.core.workloads import build_churn_schedule

    cfg, owner = scale_point(T, L)
    rng = np.random.default_rng(seed)
    acc = np.where(rng.random((ticks, L)) < 0.3, 4.0, 0.1).astype(np.float32)
    alive = np.ones((ticks, L), bool)
    runs = {i: run_engine(cfg, owner, acc, alive, mode="equilibria",
                          k_max=256, impl=i) for i in ("batched", "pallas")}
    outs = runs["batched"][1]
    check(_conserved_ticks(outs, L, cfg.n_fast_pages),
          f"scale point: pages not conserved (T={T}, L={L})")
    moved = int(np.asarray(outs.promotions).sum()
                + np.asarray(outs.demotions).sum())
    check(moved > 0, "scale point: no page moved")
    check(bitwise_equal(runs["batched"], runs["pallas"]),
          "scale point: 'pallas' differs from 'batched'")

    ccfg, slots = CHURN_PRESETS["churn16"]()
    ccfg = ccfg.with_(n_tenants=len(slots))
    sched = build_churn_schedule(slots, churn_ticks)
    cl = ccfg.n_fast_pages + ccfg.n_slow_pages
    churn = {i: run_churn_engine(ccfg, sched, mode="equilibria", k_max=128,
                                 impl=i) for i in ("batched", "pallas")}
    check(_conserved_ticks(churn["batched"][1], cl, ccfg.n_fast_pages),
          "churn16: pages not conserved")
    check(bitwise_equal(churn["batched"], churn["pallas"]),
          "churn16: 'pallas' differs from 'batched'")
    arr, dep = churn_events(sched.want)
    return (f"T={T} L={L} {ticks} ticks: conserved, {moved} page moves, "
            f"pallas==batched bitwise; churn16 L={cl} {churn_ticks} ticks "
            f"({arr} arrivals, {dep} departures): conserved, "
            f"pallas==batched bitwise")


def phase_fleet(hosts: int = 128, ticks: int = 2000, chunk: int = 500) -> str:
    from benchmarks.fleet_sweep import conserved, rollout
    cfg, summary, events = rollout(hosts, ticks, chunk)
    check(summary.detector is not None and summary.attribution is not None,
          "fleet: detectors or attribution not carried")
    check(conserved(cfg, summary), "fleet: pages not conserved")
    check(summary.attribution_conserved(),
          "fleet: attribution ledger does not conserve")
    check(events > 0, "fleet: no lifecycle events")
    return (f"{hosts} hosts x {ticks} ticks ({events} lifecycle events): "
            f"conserved, attribution conserved, sharded={summary.sharded}; "
            f"{summary.host_ticks_per_s:.0f} host-ticks/s "
            f"(host clock, information only)")


def _serve_drift(cfg, steps: int) -> dict:
    """Decode through the tiered-KV server as the launcher builds it (4
    tenants, batch 8, equilibria with a fast-tier bound of 3 pages) and
    compare each step's logits with ``model_forward`` on the fed tokens."""
    import jax
    import numpy as np
    from repro.launch.serve import run_server
    from repro.models.transformer import model_forward

    run = run_server(cfg, tenants=4, batch=8, steps=steps,
                     mode="equilibria", protection=8, bound=3)
    ref, _ = jax.jit(lambda p, t: model_forward(p, {"tokens": t}, cfg,
                                                remat="none"))(
        run.params, run.tokens)
    got = np.asarray(run.logits, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"serve: {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), "serve: non-finite logits")
    scale = float(np.abs(ref).max())
    rms = (np.linalg.norm(got - ref, axis=-1)
           / np.linalg.norm(ref, axis=-1)).max()
    fed = np.asarray(run.tokens)[:, 1:]
    flips = ref[:, :-1].argmax(-1) != fed
    margin = (ref[:, :-1].max(-1)
              - np.take_along_axis(ref[:, :-1], fed[..., None], -1)[..., 0])
    return {"rel": float(np.abs(got - ref).max()) / scale,
            "rms": float(rms), "greedy": 1.0 - float(flips.mean()),
            "flip_margin": float(margin[flips].max(initial=0.0)) / scale,
            "slow": int((np.asarray(run.state["kv"].slow_page) >= 0).sum())}


def phase_serve(steps: int = 64) -> str:
    import dataclasses

    import jax
    from repro.configs import get_config

    cfg = get_config("llama32_1b")
    b = _serve_drift(cfg, steps)
    line = (f"{cfg.name} ({cfg.dtype}) B=8 x {steps} steps, {b['slow']} "
            f"slow pages: max logit err {b['rel']:.4g} of max|logit|, "
            f"worst position RMS {b['rms']:.4g} (tol {SERVE_BF16_TOL}); "
            f"greedy agreement {b['greedy']:.4f} (min {SERVE_GREEDY_MIN}), "
            f"largest flip margin {b['flip_margin']:.4g} of max|logit|")
    check(b["slow"] > 0, f"serve: no page in the slow tier: {line}")
    check(max(b["rel"], b["rms"], b["flip_margin"]) <= SERVE_BF16_TOL,
          f"serve: {line}")
    check(b["greedy"] >= SERVE_GREEDY_MIN, f"serve: {line}")
    with jax.default_matmul_precision("highest"):
        f = _serve_drift(dataclasses.replace(cfg, dtype="float32"), steps)
    line += (f"; f32/highest witness: max logit err {f['rel']:.4g} of "
             f"max|logit| (tol {SERVE_F32_TOL})")
    check(max(f["rel"], f["rms"]) <= SERVE_F32_TOL, f"serve: {line}")
    return line


def phase_four_chip(hosts: int = 128, ticks: int = 2000, chunk: int = 500,
                    n_chips: int = 4) -> str:
    import numpy as np
    from benchmarks.fleet_sweep import conserved, rollout
    cfg, a, _ = rollout(hosts, ticks, chunk, shard=True)
    check(a.sharded, "four-chip: the rollout did not shard")
    devs = a.final_state.tier.sharding.device_set
    check(len(devs) == n_chips,
          f"four-chip: state on {len(devs)} devices, not {n_chips}")
    _, b, _ = rollout(hosts, ticks, chunk, shard=False)
    check(not b.sharded, "four-chip: the reference rollout sharded")
    check(conserved(cfg, a), "four-chip: pages not conserved")
    check(bitwise_equal(a.final_state, b.final_state),
          "four-chip: final fleet state differs from one chip")
    check(bitwise_equal(a.counters(), b.counters()),
          "four-chip: ledger counters differ from one chip")
    check(bitwise_equal(a.attribution_components(),
                        b.attribution_components())
          and bool(np.array_equal(a.migrations_per_tick,
                                  b.migrations_per_tick)),
          "four-chip: attribution or migrations differ from one chip")
    return (f"{hosts} hosts x {ticks} ticks sharded={a.sharded} over "
            f"{len(devs)} devices: final state, ledger counters and "
            f"attribution bit-equal to the one-chip rollout")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded fleet on 4 chips against "
                         "the same fleet on one")
    args = ap.parse_args()
    n_chips = 4 if args.four_chip else 1
    try:
        info = phase_device(n_chips)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"FAIL: the repro package is not next to chip_smoke.py ({e})",
              file=sys.stderr)
        return 1
    print(f"[cache] {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chip:
            run_phase("four-chip", phase_four_chip, clock)
        else:
            run_phase("golden", phase_golden, clock,
                      os.path.join(ROOT, "tests", "golden"))
            run_phase("engine", phase_engine, clock)
            run_phase("fleet", phase_fleet, clock)
            run_phase("serve", phase_serve, clock)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[total] wall {time.perf_counter() - t0:.1f}s compile "
          f"{clock.total:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
