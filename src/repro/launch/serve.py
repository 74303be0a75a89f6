"""Multi-tenant serving launcher: Equilibria-tiered paged-KV decode.

  PYTHONPATH=src python -m repro.launch.serve --arch llama32_1b --smoke \
      --tenants 4 --batch 8 --steps 64 --mode equilibria

Runs a continuous-batching decode loop: every sequence belongs to a tenant;
the Equilibria policy (lower protection / upper bound / Eq.1 / Eq.2 / thrash
mitigation) manages the shared fast-tier page budget inside the compiled
step. Prints the per-tenant cgroup-style tier_stat counters at the end.
"""
from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import TieringConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.params import init_params
from repro.models.transformer import model_specs
from repro.serve.decode import build_serve_step, init_serve_state
from repro.sharding.context import set_mesh_context


class ServeRun(NamedTuple):
    params: dict
    tokens: jax.Array       # [B, steps] int32: the token fed at each step
    logits: jax.Array       # [B, steps, V]: the step's output logits
    state: dict             # final serve state
    seconds: float          # wall time of the decode loop, compile included


def run_server(cfg, *, tenants: int = 4, batch: int = 8, steps: int = 64,
               page_tokens: int = 4, mode: str = "equilibria",
               protection: int = 8, bound: int = 0,
               production: bool = False) -> ServeRun:
    """Build the tiered-KV server for ``cfg`` with seeded random weights
    and decode ``steps`` tokens greedily for ``batch`` sequences, starting
    from one random token each."""
    tcfg = TieringConfig(
        n_tenants=tenants, page_tokens=page_tokens,
        thrash_table_slots=256,
        lower_protection=(protection,) * tenants,
        upper_bound=(bound,) * tenants)
    mesh = make_production_mesh() if production else make_host_mesh()
    set_mesh_context(mesh)

    with mesh:
        params = init_params(jax.random.PRNGKey(0), model_specs(cfg))
        state = init_serve_state(cfg, tcfg, batch, steps)
        step = jax.jit(build_serve_step(cfg, tcfg, batch, steps, mode=mode))
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(key, (batch, 1), 0, cfg.vocab_size)
        fed, outs = [], []
        t0 = time.time()
        for _ in range(steps):
            logits, state = step(params, state, tokens)
            fed.append(tokens)
            outs.append(logits)
            tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(logits)
        dt = time.time() - t0
    return ServeRun(params, jnp.concatenate(fed, axis=1),
                    jnp.concatenate(outs, axis=1), state, dt)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--page-tokens", type=int, default=4)
    ap.add_argument("--mode", default="equilibria",
                    choices=["equilibria", "tpp", "static"])
    ap.add_argument("--protection", type=int, default=8,
                    help="fast-tier lower protection per tenant (pages)")
    ap.add_argument("--bound", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    run = run_server(cfg, tenants=args.tenants, batch=args.batch,
                     steps=args.steps, page_tokens=args.page_tokens,
                     mode=args.mode, protection=args.protection,
                     bound=args.bound, production=args.production)
    state, dt = run.state, run.seconds

    print(f"arch={cfg.name} mode={args.mode} decoded "
          f"{args.steps} tokens x {args.batch} seqs in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    if "kv" in state:
        from repro.obs.stats import format_tier_stat, stats_summary
        from repro.obs.trace import decode_ring
        kv = state["kv"]
        fast = np.zeros(args.tenants, int)
        slow = np.zeros(args.tenants, int)
        ten = np.asarray(kv.tenant)
        fp = np.asarray(kv.fast_page >= 0).sum(1)
        sp = np.asarray(kv.slow_page >= 0).sum(1)
        for b in range(args.batch):
            fast[ten[b]] += fp[b]
            slow[ten[b]] += sp[b]
        c = kv.counters
        from repro.memtier.kvcache import kv_layer_count
        # one page slot holds k+v for every KV layer (pools are [L, B, Mf, ...])
        page_bytes = (2 * args.page_tokens * cfg.num_kv_heads
                      * cfg.resolved_head_dim * 2 * kv_layer_count(cfg))
        stat = {
            "local_usage_bytes": fast * page_bytes,
            "cxl_usage_bytes": slow * page_bytes,
            "pgpromote": c.promotions, "pgdemote": c.demotions,
            "pgpromote_attempted": c.attempted_promotions,
            "pgalloc": c.allocations, "thrash_events": c.thrash_events,
        }
        summary = stats_summary(kv.stats)
        print("\nper-tenant tier_stat (cgroup-style observability, §IV-C):")
        for t in range(args.tenants):
            print(f"tenant{t} (promo_scale="
                  f"{float(kv.promo_scale[t]):.3f}):")
            print(format_tier_stat(stat, summary, t))
        events, dropped = decode_ring(kv.ring)
        print(f"\nmigration trace: {len(events)} events buffered "
              f"({dropped} older events overwritten); last 5:")
        for e in events[-5:]:
            d = "promote" if e["direction"] == 0 else "demote"
            print(f"  step={e['tick']} tenant={e['tenant']} "
                  f"page={e['page']} {d} hotness={e['hotness']:.3f}")


if __name__ == "__main__":
    main()
