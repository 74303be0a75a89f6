"""Mixed-fleet harness (obs/fleet.py on the unified tick core).

Pins the PR-5 fleet properties: static and churned hosts run side by side
under ONE vmap of the unified dynamic-ownership tick (the host mix is
data, not structure — same jaxpr regardless of mix), a noisy neighbor
injected on a *churned* host is flagged while the clean mixed fleet stays
silent, and the chunked long-horizon rollout (donated carries, schedule
archetypes gathered in-graph, periodic tiling) is bit-equal to the
single-scan execution.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TieringConfig
from repro.core.churn import make_churn_tick
from repro.core.state import init_state, stack_states
from repro.core.workloads import (ChurnSlot, build_churn_schedule,
                                  cache_like, spark_like, thrasher, web_like)
from repro.obs.fleet import (FleetRollout, fleet_rollout, mixed_fleet_hosts,
                             run_mixed_fleet, stack_schedules)

_TICKS = 160
# slot footprints shared fleet-wide (slot count must match across hosts;
# footprints need not, but keeping them equal makes the A/B injection clean)
_FOOT = (32, 40, 40, 24)


def _cfg():
    total = sum(_FOOT)
    fast = int(total * 1.15)   # ample fast tier: a clean fleet must be clean
    # slot-0 bound: harmless for the clean web/cache hot sets (~11 pages),
    # the squeeze that turns an injected thrasher into §IV-F churn
    return TieringConfig(n_tenants=4, n_fast_pages=fast, n_slow_pages=total,
                         lower_protection=(8, 12, 12, 8),
                         upper_bound=(24, 0, 0, 0),
                         migration_cost=0.005)


def _hosts(noisy_host=None):
    """2 static + 2 churned hosts, T=4 slots each."""
    static_mixes = [
        [web_like(_FOOT[0]), cache_like(_FOOT[1]), spark_like(_FOOT[2]),
         web_like(_FOOT[3])],
        [web_like(_FOOT[0], hot_pages=10), cache_like(_FOOT[1]),
         web_like(_FOOT[2]), cache_like(_FOOT[3])],
    ]
    churned = []
    for seed in (0, 1):
        churned.append([
            ChurnSlot(web_like(_FOOT[0]), [(0, _TICKS)]),
            ChurnSlot(cache_like(_FOOT[1]), [(5, _TICKS)]),
            # mid-run departure + re-arrival: slot reuse on a live fleet
            ChurnSlot(cache_like(_FOOT[2]), [(0, 60 + 10 * seed),
                                             (90, _TICKS)]),
            ChurnSlot(web_like(_FOOT[3]), [(8 * seed, _TICKS)]),
        ])
    hosts = mixed_fleet_hosts(static_mixes, churned, _TICKS)
    if noisy_host is not None:
        # §V-B5 noisy neighbor on a churned host: promotion-hot pages never
        # re-accessed before demotion, squeezed under slot 0's bound; late
        # arrival leaves the detectors a clean baseline window
        hosts[noisy_host][0] = ChurnSlot(thrasher(_FOOT[0], fast_share=12),
                                         [(30, _TICKS)])
    return hosts


def test_mixed_fleet_clean_is_silent():
    res = run_mixed_fleet(_cfg(), _hosts(), _TICKS, k_max=32)
    assert res.n_hosts == 4
    assert res.latency.shape == (4, _TICKS, 4)
    assert res.tenants_flagged() == [], res.pathology_counts()
    # the churned hosts really churned: slot 2 left and came back
    assert not res.active[2, 70, 2] and res.active[2, 100, 2]
    roll = res.rollup()
    assert roll["hosts_with_pathology"] == 0
    assert roll["latency_p99"] >= roll["latency_p50"] >= 1.0


def test_noisy_neighbor_on_churned_host_is_flagged():
    noisy_host = 2                      # a churned host
    res = run_mixed_fleet(_cfg(), _hosts(noisy_host=noisy_host), _TICKS,
                          k_max=32)
    flagged = res.tenants_flagged("chronic_thrashing")
    assert (noisy_host, 0) in flagged, res.pathology_counts()
    # the injection is host-local: nobody else in the fleet is flagged
    assert {h for h, _ in res.tenants_flagged()} == {noisy_host}
    # per-host in-graph stats saw the churn too
    assert res.stats[noisy_host]["thrash_rate"][0] > 0


def test_fleet_jaxpr_constant_in_host_mix():
    """The unified tick traces once regardless of host mix: an all-static
    fleet and a mixed static+churn fleet produce IDENTICAL vmapped jaxprs
    (the mix lives in the schedule data), and the trace's equation count is
    independent of the host count."""
    cfg = _cfg()
    L = cfg.n_fast_pages + cfg.n_slow_pages
    tick = make_churn_tick(cfg, L, k_max=32)

    from repro.analysis.constancy import assert_jaxpr_constant

    def build(H):
        vt = jax.vmap(tick)
        states = stack_states(init_state(cfg, L), H)
        S = max(_FOOT)
        inp = (jnp.ones((H, 4, S), jnp.float32),
               jnp.full((H, 4), 16, jnp.int32))
        return vt, (states, inp)

    # retrace at the same H is deterministic; doubling H leaves the
    # vmapped program's eqn count and primitive mix untouched
    assert_jaxpr_constant(build, (4, 4, 8),
                          label="vmapped tick: host count")

    # same program, different *data*: all-static vs mixed fleets share the
    # compiled scan — pin by running both through one jitted runner and
    # checking the runner compiled exactly once
    hosts_static = mixed_fleet_hosts(
        [[web_like(f) for f in _FOOT]] * 2, [], 32)
    hosts_mixed = _hosts()
    n_compiles = 0

    def counting_run(s, r, w):
        nonlocal n_compiles
        n_compiles += 1
        return jax.lax.scan(tick, s, (r, w))

    run = jax.jit(jax.vmap(counting_run))
    for hosts in (hosts_static[:2], hosts_mixed[:2]):
        want, rates = stack_schedules(
            [build_churn_schedule(s, 32) for s in hosts])
        S = max(_FOOT)
        pad = np.zeros(rates.shape[:3] + (S - rates.shape[3],), np.float32)
        rates = np.concatenate([rates, pad], axis=3)
        states = stack_states(init_state(cfg, L), 2)
        run(states, jnp.asarray(rates), jnp.asarray(want))
    assert n_compiles == 1


def test_chunked_rollout_matches_single_scan():
    """fleet_rollout chunking (donated carries, periodic schedule tiling)
    is bit-exact: chunk=ticks (one scan) == chunk=7 (chunks + remainder)."""
    cfg = _cfg()
    hosts = _hosts()
    ticks = 30
    want, rates = stack_schedules(
        [build_churn_schedule(s, ticks) for s in hosts])
    runs = [fleet_rollout(cfg, want, rates, ticks, chunk=c, k_max=32)
            for c in (ticks, 7)]
    c0, c1 = (r.counters() for r in runs)
    for name in c0._fields:
        np.testing.assert_array_equal(getattr(c0, name), getattr(c1, name),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(runs[0].final_state.tier),
                                  np.asarray(runs[1].final_state.tier))
    np.testing.assert_array_equal(np.asarray(runs[0].final_state.owner),
                                  np.asarray(runs[1].final_state.owner))
    np.testing.assert_allclose(runs[0].latency_mean, runs[1].latency_mean,
                               rtol=1e-6)
    np.testing.assert_allclose(runs[0].migrations_per_tick,
                               runs[1].migrations_per_tick, rtol=1e-6)


def test_rollout_advanced_in_pieces_matches_one_call():
    """A FleetRollout advanced in uneven pieces that end on chunk
    boundaries (7 + 14 + 3 ticks in chunks of 7) runs the same chunks as one
    fleet_rollout call: the same RolloutSummary and final state, bit for
    bit."""
    cfg = _cfg()
    hosts = _hosts()
    ticks = 24
    want, rates = stack_schedules(
        [build_churn_schedule(s, ticks) for s in hosts])
    whole = fleet_rollout(cfg, want, rates, ticks, chunk=7, k_max=32)
    fleet = FleetRollout(cfg, want, rates, ticks, chunk=7, k_max=32)
    for n in (7, 14, 3):
        fleet.advance(n)
    pieces = fleet.summary()
    assert (pieces.n_hosts, pieces.ticks, pieces.chunk, pieces.sharded) == \
        (whole.n_hosts, whole.ticks, whole.chunk, whole.sharded)
    for name in ("latency_mean", "throughput_mean", "migrations_per_tick"):
        np.testing.assert_array_equal(getattr(pieces, name),
                                      getattr(whole, name), err_msg=name)
    for a, b in zip(jax.tree_util.tree_leaves(pieces.final_state),
                    jax.tree_util.tree_leaves(whole.final_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(pieces.ledger.total),
                    jax.tree_util.tree_leaves(whole.ledger.total)):
        np.testing.assert_array_equal(a, b)
    assert pieces.attribution_rollup() == whole.attribution_rollup()
    assert pieces.pathology_rollup() == whole.pathology_rollup()


def test_resumed_rollout_continues_the_fleet():
    """A FleetRollout resumed from another's state after 14 ticks and
    advanced 10 more ends where 24 ticks of one rollout end; the two
    ledgers add up to the one."""
    cfg = _cfg()
    hosts = _hosts()
    ticks = 24
    want, rates = stack_schedules(
        [build_churn_schedule(s, ticks) for s in hosts])
    whole = FleetRollout(cfg, want, rates, ticks, chunk=7, k_max=32)
    whole.advance(ticks)
    first = FleetRollout(cfg, want, rates, ticks, chunk=7, k_max=32)
    first.advance(14)
    rest = FleetRollout(cfg, want, rates, ticks, chunk=7, k_max=32)
    rest.resume(first.host_view(first.states), 14)
    rest.advance(ticks - 14)
    done = rest.summary()
    assert (done.ticks, rest.t) == (ticks - 14, ticks)
    for a, b in zip(jax.tree_util.tree_leaves(done.final_state),
                    jax.tree_util.tree_leaves(whole.summary().final_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b, c in zip(jax.tree_util.tree_leaves(first.ledger.total),
                       jax.tree_util.tree_leaves(done.ledger.total),
                       jax.tree_util.tree_leaves(whole.ledger.total)):
        np.testing.assert_array_equal(a + b, c)


def test_rollout_archetype_tiling_matches_explicit_hosts():
    """host_arch tiling (several hosts sharing one schedule archetype) is
    identical to materializing the schedule per host. Archetype 0 is static
    and archetype 1 churns (departure + re-arrival inside the horizon) so
    the two produce genuinely different counters — a wrong-axis gather in
    the in-graph schedule lookup cannot pass by accident."""
    cfg = _cfg()
    hosts = [_hosts()[0], _hosts()[2]]     # one static, one churned
    ticks = 100                            # covers depart@60 / re-arrive@90
    want, rates = stack_schedules(
        [build_churn_schedule(s, ticks) for s in hosts])
    tiled = fleet_rollout(cfg, want, rates, ticks,
                          host_arch=np.array([0, 1, 0, 1]), chunk=32,
                          k_max=32)
    explicit = fleet_rollout(cfg, want[[0, 1, 0, 1]], rates[[0, 1, 0, 1]],
                             ticks, chunk=32, k_max=32)
    ce, ct = explicit.counters(), tiled.counters()
    for name in ct._fields:
        np.testing.assert_array_equal(getattr(ct, name), getattr(ce, name),
                                      err_msg=name)
    # non-vacuous: the archetypes disagree (the churned host reclaimed)
    assert not np.array_equal(ct.reclaims[0], ct.reclaims[1])
    assert not np.array_equal(ct.allocations[0], ct.allocations[1])


def test_rollout_refuses_uneven_shard(monkeypatch):
    """With several devices, a host count that does not divide over them
    is an error naming both, not a silent one-device run."""
    cfg = _cfg()
    want, rates = stack_schedules(
        [build_churn_schedule(s, 8) for s in _hosts()])
    monkeypatch.setattr(jax, "local_device_count", lambda: 4)
    with pytest.raises(ValueError, match="H=3 .* D=4"):
        fleet_rollout(cfg, want, rates, 8, host_arch=np.arange(3) % 2,
                      chunk=4, k_max=8)


@pytest.mark.slow
def test_rollout_pmap_shard_path_matches():
    """With >1 device the rollout shards hosts via pmap; results are
    bit-equal to the vmap path. Exercised in a subprocess with forced host
    devices (jax is already initialized single-device in this process)."""
    script = textwrap.dedent("""
        import numpy as np
        from repro.configs.base import TieringConfig
        from repro.core.workloads import (build_churn_schedule,
                                          as_churn_slots, web_like,
                                          cache_like)
        from repro.obs.fleet import fleet_rollout, stack_schedules
        import jax
        assert jax.local_device_count() == 2, jax.local_device_count()
        ticks = 20
        hosts = [as_churn_slots([web_like(8), cache_like(10)], ticks),
                 as_churn_slots([cache_like(8), web_like(10)], ticks)]
        cfg = TieringConfig(n_tenants=2, n_fast_pages=12, n_slow_pages=20,
                            lower_protection=(3, 3), upper_bound=(0, 6))
        want, rates = stack_schedules(
            [build_churn_schedule(s, ticks) for s in hosts])
        ha = np.array([0, 1, 0, 1])
        a = fleet_rollout(cfg, want, rates, ticks, host_arch=ha, chunk=8,
                          k_max=8, shard=True)
        b = fleet_rollout(cfg, want, rates, ticks, host_arch=ha, chunk=8,
                          k_max=8, shard=False)
        assert a.sharded and not b.sharded
        ca, cb = a.counters(), b.counters()
        for name in ca._fields:
            np.testing.assert_array_equal(getattr(ca, name),
                                          getattr(cb, name), err_msg=name)
        np.testing.assert_array_equal(np.asarray(a.final_state.tier),
                                      np.asarray(b.final_state.tier))
        print("SHARD_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARD_OK" in out.stdout
