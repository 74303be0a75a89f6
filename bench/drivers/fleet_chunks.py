"""Window loop for a fleet: one ``FleetRollout`` (``repro.obs.fleet``) built
at set-up and advanced ``chunk_ticks`` ticks per step, the path
``fleet_rollout`` runs. Every host is the dynamic-ownership churn tick with
the streaming detectors and the attribution ledger; hosts are sharded over
the local chips under ``pmap`` when there are several.

The configuration's roster gives one host's schedule (``bench/traffic``);
its steady period, the ``horizon_ticks`` rows after the mix's prefix, is
cut into ``n_archetypes`` archetypes, the same period started
``archetype_shift`` ticks apart, and host ``h`` reads archetype ``h mod
n_archetypes``. All hosts share one roster and one policy.

Set-up replays the first ``replay_ticks`` ticks of the detectors' horizon
(``rollout_ticks``) on one host per archetype (hosts of one archetype run
the same inputs from the same state, so they stay equal), gives each host
its archetype's state and ledger (``FleetRollout.resume``), and runs
``warmup_chunks`` chunks, so that the configuration can place the
window's chunks in the detectors' steady half.

One ``step`` dispatches a chunk (``dispatch``) and waits while the
rollout pulls the cumulative counters of every host into its int64 ledger
(``pull_outputs``). A kept step also copies, on the device, the state of
the checked hosts (``check_hosts``, drawn from the traffic, so from the
seed, an equal number on each chip) before and after the chunk, and keeps
their sums and ledger growth, for the comparison with the reference after
the window.
"""
from __future__ import annotations

import sys
import zlib
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.engine_chunks import state_dict
from bench.traffic.generate import Traffic
from repro.obs.fleet import FleetRollout


class Kept(NamedTuple):
    hosts: np.ndarray     # [n] checked host ids
    state_in: object      # their TierState [n, ...] (device, then host)
    state_out: object
    sums: dict            # the chunk's sums.lat / sums.thr / sums.mig [n]
    ledger: dict          # their ledger growth over the chunk, [n, ...]


class Release(NamedTuple):
    chunks: List[Kept]
    final: dict           # every host's tier, owner and ledger totals


# Faults a test plants under the timed path, to see ``correct`` turn false:
# one chip's hosts left as they were, the detector or the attribution fold
# skipped, one checked host fed another archetype, one host's ledger
# growth altered.
FAULTS = ("stale_shard", "detect_skipped", "attrib_skipped",
          "arch_misassigned", "ledger_altered")


def archetypes(traffic: Traffic, config: dict):
    """want [A, P, T] and rates [A, P, T, S]: the steady period started
    ``archetype_shift * a`` ticks in, for each archetype ``a``."""
    P, A = traffic.period, int(config["n_archetypes"])
    shift = int(config["archetype_shift"])
    rows = traffic.prefix + (np.arange(A)[:, None] * shift
                             + np.arange(P)[None, :]) % P
    return traffic.want[rows], traffic.rates[rows]


def checked_hosts(traffic: Traffic, config: dict, n_devices: int):
    """[D, n/D] host ids local to each device, drawn from the traffic."""
    H = int(config["n_hosts"])
    per = min(int(config["check_hosts"]) // n_devices, H // n_devices)
    rng = np.random.default_rng(zlib.crc32(traffic.want.tobytes()))
    return np.stack([np.sort(rng.choice(H // n_devices, per, replace=False))
                     for _ in range(n_devices)])


class _Faulty(FleetRollout):
    """The rollout with a fault planted in its chunk program."""

    fault = ""

    def chunk_fn(self, n: int):
        run, fault, sharded = super().chunk_fn(n), self.fault, self.sharded

        def faulty(states, *args):
            out, sums = run(states, *args)
            if fault == "stale_shard":
                first = (jax.lax.axis_index(self.AXIS) == 0) if sharded \
                    else True
                out = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(first, a, b),
                    states._replace(t=out.t), out)
            elif fault == "detect_skipped":
                out = out._replace(det=states.det)
            elif fault == "attrib_skipped":
                out = out._replace(attrib=states.attrib)
            return out, sums
        return faulty


class Runner:

    def __init__(self, config: dict, traffic: Traffic,
                 fault: Optional[str] = None):
        self.config, self.tr, self.fault = config, traffic, fault
        self.C = int(config["chunk_ticks"])
        self.H = int(config["n_hosts"])
        self.kept: List[Kept] = []

    # ------------------------------------------------------------ set-up --
    def setup(self) -> None:
        from repro.configs.base import TieringConfig

        c, tr = self.config, self.tr
        cfg = TieringConfig(n_tenants=tr.n_tenants, n_fast_pages=tr.n_fast,
                            n_slow_pages=tr.n_pages,
                            lower_protection=tr.lower_protection,
                            upper_bound=tr.upper_bound, **c["tiering"])
        want, rates = archetypes(tr, c)
        A = int(c["n_archetypes"])
        host_arch = np.arange(self.H) % A
        horizon, lead_ticks = int(c["rollout_ticks"]), int(c["replay_ticks"])
        D = jax.local_device_count()
        local = checked_hosts(tr, c, D)
        self.hosts = (np.arange(D)[:, None] * (self.H // D)
                      + local).reshape(-1)
        if self.fault == "arch_misassigned":
            h = self.hosts[0]
            host_arch = host_arch.copy()
            host_arch[h] = (host_arch[h] + 1) % A
        kw = dict(mode=c["mode"], k_max=int(c["k_max"]), n_pages=tr.n_pages,
                  detect=bool(c["detect"]), attrib=bool(c["attrib"]))
        lead = FleetRollout(cfg, want, rates, horizon, chunk=lead_ticks,
                            shard=A % D == 0, **kw)
        lead.advance(lead_ticks)
        done = lead.summary()
        start = jax.tree_util.tree_map(lambda x: np.asarray(x)[host_arch],
                                       done.final_state)
        # the ledger counts from the resumed state; conservation at release
        # needs the totals since tick 0
        self.start_totals = jax.tree_util.tree_map(lambda x: x[host_arch],
                                                   done.ledger.total)
        del lead, done
        cls = FleetRollout
        if self.fault in ("stale_shard", "detect_skipped", "attrib_skipped"):
            cls = type("Faulty", (_Faulty,), {"fault": self.fault})
        self.fleet = cls(cfg, want, rates, horizon, host_arch=host_arch,
                         chunk=self.C, **kw)
        self.fleet.resume(start, lead_ticks)
        del start
        if self.fleet.sharded:
            self.pick = jax.pmap(_take)
            self.idx = jnp.asarray(local)
        else:
            self.pick = jax.jit(_take)
            self.idx = jnp.asarray(self.hosts)
        # on a scratch fleet: a first chunk takes the state as set-up
        # placed it, a second as the chunk program leaves it
        self.fleet.warmup(self.C, self.C)
        jax.block_until_ready(self.pick(self.fleet.states, self.idx))
        for _ in range(int(c["warmup_chunks"])):
            self.step()

    # the chunk program and its arguments, as ``bench/stages.py`` lowers it
    @property
    def chunk(self):
        return self.fleet.program(self.C)

    @property
    def state(self):
        return self.fleet.states

    @property
    def rows(self):
        f = self.fleet
        return (f.arch, f.t) + f.schedule

    # ------------------------------------------------------------ window --
    def step(self, keep: bool = False) -> int:
        """One chunk, from dispatch until the ledger holds its counters."""
        f = self.fleet
        horizon = int(self.config["rollout_ticks"])
        if f.t + self.C > horizon:
            raise RuntimeError(f"the run outlasts the detectors' horizon of "
                               f"{horizon} ticks")
        if keep:
            s_in = self.pick(f.states, self.idx)
            led_in = self._ledger_rows()
        with jax.profiler.TraceAnnotation("dispatch"):
            f.run(self.C)
        with jax.profiler.TraceAnnotation("pull_outputs"):
            f.collect()
            if self.fault == "ledger_altered":
                cnt = f.ledger.total["counters"]
                cnt.promotions[self.hosts[0], 0] += 1
        if keep:
            led_out = self._ledger_rows()
            sums = {f"sums.{k}": v[self.hosts]
                    for k, v in zip(("lat", "thr", "mig"), f.last_sums)}
            self.kept.append(Kept(
                self.hosts, s_in, self.pick(f.states, self.idx), sums,
                jax.tree_util.tree_map(lambda a, b: a - b, led_out, led_in)))
        return self.H * self.C

    def _ledger_rows(self) -> dict:
        return jax.tree_util.tree_map(lambda x: x[self.hosts].copy(),
                                      self.fleet.ledger.total)

    def shapes(self) -> dict:
        """The shapes ``bench/bytes.py`` counts a host-tick's bytes from."""
        tr = self.tr
        return {"ownership": "dynamic", "n_pages": tr.n_pages,
                "n_tenants": tr.n_tenants, "slot_pages": tr.rates.shape[2]}

    def release(self) -> Release:
        """Bring the kept states and every host's final pages and ledger to
        the host, and free the device."""
        n = self.hosts.shape[0]

        def flat(tree):
            return jax.tree_util.tree_map(
                lambda x: np.asarray(x).reshape((n,) + x.shape[2:])
                if self.fleet.sharded else np.asarray(x), tree)
        chunks = [k._replace(state_in=flat(k.state_in),
                             state_out=flat(k.state_out))
                  for k in self.kept]
        f = self.fleet
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()[:f.n_devices]]
        print(f"[window] peak device memory by chip {peaks} bytes",
              file=sys.stderr, flush=True)
        view = f.host_view({"tier": f.states.tier, "owner": f.states.owner})
        final = dict(view, n_fast=f.cfg.n_fast_pages,
                     **jax.tree_util.tree_map(np.add, self.start_totals,
                                              f.ledger.total))
        self.kept, self.fleet = [], None
        return Release(chunks, final)


def _take(states, idx):
    return jax.tree_util.tree_map(lambda x: x[idx], states)


def host_dict(state, j: int) -> dict:
    """Host ``j`` of a batched host-side TierState: the core fields by the
    reference's names, and the detector and ledger under ``det.`` and
    ``att.``."""
    one = jax.tree_util.tree_map(lambda x: x[j], state)
    d = state_dict(one)
    d.update({"det." + k: np.asarray(v) for k, v in one.det._asdict().items()})
    d.update({"att." + k: np.asarray(v)
              for k, v in one.attrib._asdict().items()})
    return d


def _ledger_of(delta: dict, j: int) -> dict:
    d = {"ledger.counters." + k: v[j]
         for k, v in delta["counters"]._asdict().items()}
    d.update({"ledger.att." + k: v[j] for k, v in delta["att"].items()})
    return d


def _ref_ledger(ref: dict, start: dict) -> dict:
    d = {"ledger.counters." + k[9:]: ref[k].astype(np.int64)
         - start[k].astype(np.int64)
         for k in ref if k.startswith("counters.")}
    d.update({"ledger.att." + k: ref["att." + k].astype(np.int64)
              - start["att." + k].astype(np.int64)
              for k in ("comp", "total", "sketch")})
    return d


def compare(kept: Release, traffic: Traffic, config: dict,
            control: bool = False, fused_ewma: bool = False) -> dict:
    """The compared numbers over the kept chunks and the fleet's final
    state. ``control`` puts the reference kept in bfloat16 hotness in the
    program's place; ``fused_ewma`` has the reference round the hotness
    EWMA once."""
    import ml_dtypes

    from bench.check import gaps
    from bench.reference.fleet import (FLOATS, FleetHost,
                                       conservation_violations)
    C, A = int(config["chunk_ticks"]), int(config["n_archetypes"])
    want, rates = archetypes(traffic, config)

    def host_ref(a, hot_dtype=np.float32):
        tr = Traffic("dynamic", traffic.n_tenants, traffic.n_pages,
                     traffic.n_fast, 0, traffic.period,
                     traffic.lower_protection, traffic.upper_bound,
                     want=want[a], rates=rates[a])
        return FleetHost(tr, config["tiering"], int(config["k_max"]),
                         int(config["rollout_ticks"]), hot_dtype=hot_dtype,
                         fused_ewma=fused_ewma)
    refs = [host_ref(a) for a in range(A)]
    lows = [host_ref(a, ml_dtypes.bfloat16) for a in range(A)] \
        if control else None
    mism, worst, where, failed = 0, 0.0, "", 0
    for k in kept.chunks:
        bad = 0
        for j, h in enumerate(k.hosts):
            a = int(h) % A
            s_in = host_dict(k.state_in, j)
            r_st, r_sums = refs[a].run(s_in, C)
            ref = dict(r_st, **r_sums, **_ref_ledger(r_st, s_in))
            if lows is not None:
                c_st, c_sums = lows[a].run(s_in, C)
                cand = dict(c_st, **c_sums, **_ref_ledger(c_st, s_in))
            else:
                cand = dict(host_dict(k.state_out, j),
                            **{n: v[j] for n, v in k.sums.items()},
                            **_ledger_of(k.ledger, j))
            core = [n for n in ref if n.split(".")[0] not in (
                "det", "att", "sums", "ledger")]
            m, g, w = gaps({n: cand[n] for n in core}, [],
                           {n: ref[n] for n in core}, [])
            for n in ref:
                if n in core:
                    continue
                x, y = np.asarray(cand[n]), np.asarray(ref[n])
                if n in FLOATS:
                    y = y.astype(np.float64)
                    scale = max(float(np.abs(y).max(initial=0.0)), 1e-30)
                    gap = float(np.abs(x.astype(np.float64) - y).max(
                        initial=0.0)) / scale
                    if gap > g:
                        g, w = gap, n
                else:
                    m += int((x.astype(np.int64) != y.astype(np.int64))
                             .sum())
            bad += int(m > 0)
            mism += m
            if g > worst:
                worst, where = g, f"host {h}: {w}"
        failed += int(bad > 0)
    if not control and kept.chunks:
        print(detector_activity(kept.chunks[-1], config), file=sys.stderr,
              flush=True)
    f = kept.final
    viol = conservation_violations(f["tier"], f["owner"], f["n_fast"],
                                   f["counters"]._asdict(), f["att"]["comp"],
                                   f["att"]["total"])
    return {"numbers": {"int_mismatch": mism, "float_gap": worst,
                        "conservation_violations": viol},
            "widest_float": where, "chunks_compared": len(kept.chunks),
            "chunks_differing": failed}


def detector_activity(k: Kept, config: dict) -> str:
    """What the detectors of the checked hosts hold after a kept chunk:
    steady ticks, closed thrash windows, violations and flags, summed over
    hosts and tenants."""
    from bench.reference.fleet import geometry
    det = k.state_out.det
    geo = geometry(int(config["rollout_ticks"]))
    t = np.asarray(k.state_out.t)
    total = {n: int(np.asarray(getattr(det, n), np.int64).sum())
             for n in ("active_steady", "windows_resident", "windows_bad",
                       "viol_ticks", "att_steady", "mig_steady")}
    flags = np.asarray(det.flag_ticks, np.int64).sum(axis=(0, 1))
    flagged = int((np.asarray(det.first_flag) >= 0).sum())
    return (f"[check] detectors of the {t.shape[0]} checked hosts at tick "
            f"{int(t.min())}-{int(t.max())} (steady from "
            f"{geo['steady_start']}, windows of {geo['window']}): "
            + ", ".join(f"{n} {v}" for n, v in total.items())
            + f", flag_ticks (thrash, protection, noisy, stall) "
            f"{flags.tolist()}, first flags {flagged}")
