"""Window loop for one host: the program's tick (``make_tick`` for static
ownership, ``make_churn_tick`` for dynamic) and ``init_state`` under one
jitted ``lax.scan`` of ``chunk_ticks`` ticks — the body of ``run_engine`` /
``run_churn_engine``, chunked so that a run of any length fits.

The traffic rows live on the device; tick ``t`` reads its row in-graph
(``row_of``), so every chunk is the same compiled program. One ``step``
dispatches a chunk and waits until its stacked ``TickOutput`` is on the
host. A kept step also holds on to the state it started from, for the
comparison with the reference after the window.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic.generate import row_of


class Kept(NamedTuple):
    state_in: object      # TierState (device, then host)
    state_out: object
    outs: object          # stacked TickOutput on the host


# Faults a test plants under the timed path, to see ``correct`` turn false:
# the state returned unchanged, half the tenants' traffic lost, one output
# altered, and the statistics or the migration ring left as they were.
FAULTS = ("stale_state", "half_batch", "altered_answer", "stats_skipped",
          "ring_skipped")


class Runner:

    def __init__(self, config: dict, traffic, fault: Optional[str] = None):
        self.config, self.tr, self.fault = config, traffic, fault
        self.C = int(config["chunk_ticks"])
        self.kept: List[Kept] = []

    # ------------------------------------------------------------ set-up --
    def setup(self) -> None:
        from repro.configs.base import TieringConfig
        from repro.core.churn import make_churn_tick
        from repro.core.engine import make_tick
        from repro.core.state import init_state

        cfg_j, tr = self.config, self.tr
        cfg = TieringConfig(n_tenants=tr.n_tenants, n_fast_pages=tr.n_fast,
                            n_slow_pages=tr.n_pages,
                            lower_protection=tr.lower_protection,
                            upper_bound=tr.upper_bound, **cfg_j["tiering"])
        kw = dict(mode=cfg_j["mode"], k_max=cfg_j["k_max"],
                  impl=cfg_j["impl"], hotness=cfg_j["hotness"])
        if tr.ownership == "static":
            tick = make_tick(cfg, tr.owner, **kw)
            state = init_state(cfg, tr.n_pages, owner=tr.owner,
                               hotness=cfg_j["hotness"])
        else:
            tick = make_churn_tick(cfg, tr.n_pages, **kw)
            state = init_state(cfg, tr.n_pages, hotness=cfg_j["hotness"])
        prefix, period, C, T = tr.prefix, tr.period, self.C, tr.n_tenants
        fault = self.fault

        def chunk(state, *rows):
            def body(s, _):
                r = row_of(s.t, prefix, period)
                inp = tuple(jax.lax.dynamic_index_in_dim(a, r, keepdims=False)
                            for a in rows)
                if fault == "half_batch":   # half the tenants' traffic lost
                    inp = _drop_half(inp, T, tr.ownership)
                return tick(s, inp)
            out_state, outs = jax.lax.scan(body, state, None, length=C)
            if fault == "stale_state":
                out_state = state._replace(t=out_state.t)
            elif fault == "altered_answer":
                outs = outs._replace(promotions=outs.promotions.at[-1, 0]
                                     .add(1))
            elif fault == "stats_skipped":
                out_state = out_state._replace(stats=state.stats)
            elif fault == "ring_skipped":
                out_state = out_state._replace(ring=state.ring)
            return out_state, outs

        self.chunk = jax.jit(chunk)
        self.rows = tuple(jax.device_put(a) for a in tr.inputs())
        self.state = state
        for _ in range(int(cfg_j["warmup_chunks"])):
            self.step()

    # ------------------------------------------------------------ window --
    def step(self, keep: bool = False) -> int:
        """One chunk, from dispatch until its outputs are on the host."""
        with jax.profiler.TraceAnnotation("dispatch"):
            new_state, outs = self.chunk(self.state, *self.rows)
        with jax.profiler.TraceAnnotation("pull_outputs"):
            outs_h = jax.device_get(outs)
        if keep:
            self.kept.append(Kept(self.state, new_state, outs_h))
        self.state = new_state
        return self.C

    def shapes(self) -> dict:
        """The shapes ``bench/bytes.py`` counts a host-tick's bytes from."""
        tr = self.tr
        if tr.ownership == "static":
            return {"ownership": "static", "n_pages": tr.n_pages}
        return {"ownership": "dynamic", "n_pages": tr.n_pages,
                "n_tenants": tr.n_tenants, "slot_pages": tr.rates.shape[2]}

    def release(self) -> List[Kept]:
        """Bring the kept states to the host and free the device."""
        kept = [Kept(jax.device_get(k.state_in), jax.device_get(k.state_out),
                     k.outs) for k in self.kept]
        self.kept, self.state, self.rows, self.chunk = [], None, None, None
        return kept


def _drop_half(inp, T: int, ownership: str):
    """Zero the traffic of the upper half of the tenants."""
    if ownership == "static":
        acc, alive = inp
        half = acc.shape[0] // 2
        keep = jnp.arange(acc.shape[0]) < half
        return acc * keep, alive
    rates, want = inp
    keep = jnp.arange(T) < T // 2
    return rates * keep[:, None], want


def state_dict(state) -> dict:
    """The host state's fields the reference models, by the reference's
    names."""
    d = {"tier": state.tier, "hot": state.hot,
         "last_access": state.last_access, "owner": state.owner,
         "promo_scale": state.promo_scale, "thrash_prev": state.thrash_prev,
         "usage_prev": state.usage_prev, "freed_since": state.freed_since,
         "steady": state.steady, "mitigated_prev": state.mitigated_prev,
         "table_page": state.table.page, "table_tick": state.table.tick,
         "t": state.t}
    for name in state.counters._fields:
        d["counters." + name] = getattr(state.counters, name)
    for name in state.stats._fields:
        d["stats." + name] = getattr(state.stats, name)
    ring = np.asarray(state.ring.data)
    d["ring.events"] = ring[:, :4]
    d["ring.hot"] = np.ascontiguousarray(ring[:, 4]).view(np.float32)
    d["ring.head"] = state.ring.head
    return {k: np.asarray(v) for k, v in d.items()}


def outputs_list(outs, C: int) -> list:
    """Stacked TickOutput -> one dict per tick."""
    return [{f: np.asarray(getattr(outs, f))[i] for f in outs._fields}
            for i in range(C)]


def compare(kept: List[Kept], traffic, config: dict, control: bool = False,
            fused_ewma: bool = False) -> dict:
    """The compared numbers over the kept chunks. ``control`` puts the
    reference kept in bfloat16 hotness in the program's place;
    ``fused_ewma`` has the reference round the hotness EWMA once, as XLA's
    CPU backend compiles the static tick's (the TPU rounds twice)."""
    import ml_dtypes

    from bench.check import gaps
    from bench.reference.tick import Host
    C = int(config["chunk_ticks"])
    ref = Host(traffic, config["tiering"], config["k_max"],
               fused_ewma=fused_ewma)
    low = Host(traffic, config["tiering"], config["k_max"],
               hot_dtype=ml_dtypes.bfloat16, fused_ewma=fused_ewma) \
        if control else None
    mism, worst, where, failed = 0, 0.0, "", 0
    for k in kept:
        s_in = state_dict(k.state_in)
        r_state, r_outs = ref.run(s_in, C)
        if low is not None:
            c_state, c_outs = low.run(s_in, C)
        else:
            c_state, c_outs = state_dict(k.state_out), outputs_list(k.outs, C)
        m, g, w = gaps(c_state, c_outs, r_state, r_outs)
        failed += int(m > 0)
        mism += m
        if g > worst:
            worst, where = g, w
    return {"numbers": {"int_mismatch": mism, "float_gap": worst},
            "widest_float": where, "chunks_compared": len(kept),
            "chunks_differing": failed}
