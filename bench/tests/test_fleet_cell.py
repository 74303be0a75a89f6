"""CPU tests of the fleet cell (``bench/drivers/fleet_chunks.py``): a small
fleet through ``run_cell`` is correct and its control is not, every planted
fault turns ``correct`` false, and on four devices the sharded rollout
equals the one-device rollout bit for bit.

Test size: the cell's roster at scale 1 (T=16, L=1472), 8 hosts of 2
archetypes, chunks of one tick. Set-up replays 135 ticks, into the
detectors' steady half (from tick 120 of a 240-tick horizon), so the kept
chunks fold steady ticks and close a thrash window (tick 140).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.drivers.fleet_chunks import FAULTS  # noqa: E402
from bench.traffic import generate as G  # noqa: E402

CELL = "fleet512-churnmix-x4"
SEED = 2**31 + 11


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def small_config(hosts: int = 8) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", "fleet512.json")) as f:
        cfg = json.load(f)
    cfg["roster"] = dict(cfg["roster"], scale=1)
    cfg.update(n_hosts=hosts, n_archetypes=2, archetype_shift=120,
               n_pages=1472, n_fast_pages=448, rollout_ticks=240,
               replay_ticks=135, warmup_chunks=1, check_span=6,
               check_steps=6, check_hosts=8)
    return cfg


def _run(fault=None, control=False, chips=1):
    import bench.run as R
    bench = _bench()
    cell = dict(next(w for w in bench["workloads"] if w["name"] == CELL),
                chips=chips)
    return R.run_cell(cell, small_config(), G.load_mix(cell["traffic"]),
                      SEED, 0.3, False, bench, require_tpu=False,
                      fault=fault, control=control)


def test_small_fleet_is_correct_and_control_is_not():
    out = _run(control=True)
    assert out["correct"], out["compared"]
    assert out["compared"]["conservation_violations"]["value"] == 0
    limits = small_config()["limits"]
    assert any(out["control"][k] > limits[k] for k in limits), out["control"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    out = _run(fault=fault)
    assert not out["correct"], (fault, out["compared"])


def test_sharded_fleet_matches_one_device():
    """Four forced host devices: the cell is correct through ``run_cell``
    with the hosts sharded, and the runner's fleet equals a one-device
    rollout of the same archetypes bit for bit."""
    script = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r},
                        {os.path.dirname(__file__)!r}]
        import jax
        import numpy as np
        assert jax.local_device_count() == 4, jax.local_device_count()
        from test_fleet_cell import _run, small_config, SEED
        from bench.drivers import fleet_chunks as FC
        from bench.traffic import generate as G
        from repro.obs.fleet import FleetRollout
        out = _run(chips=4)
        assert out["correct"], out["compared"]
        cfg = dict(small_config(), replay_ticks=0, warmup_chunks=5)
        tr = G.build(cfg, G.load_mix("lifecycle"), SEED)
        run = FC.Runner(cfg, tr)
        run.setup()
        for _ in range(3):
            run.step()
        a = run.fleet.summary()
        want, rates = FC.archetypes(tr, cfg)
        one = FleetRollout(run.fleet.cfg, want, rates, cfg["rollout_ticks"],
                           host_arch=np.arange(8) % 2, k_max=cfg["k_max"],
                           chunk=1, n_pages=tr.n_pages, shard=False)
        one.advance(8)
        b = one.summary()
        assert a.sharded and not b.sharded and a.ticks == b.ticks == 8
        for x, y in zip(jax.tree_util.tree_leaves(a.final_state),
                        jax.tree_util.tree_leaves(b.final_state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree_util.tree_leaves(a.ledger.total),
                        jax.tree_util.tree_leaves(b.ledger.total)):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(a.latency_mean, b.latency_mean)
        np.testing.assert_array_equal(a.migrations_per_tick,
                                      b.migrations_per_tick)
        print("SHARD_OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARD_OK" in out.stdout


def test_fleet_chunk_names_its_scopes(monkeypatch):
    """The compiled fleet chunk keeps ``tick/<stage>`` in its ops' names
    under the host vmap, so ``bench/stages.py`` splits it by stage, with
    the detector and ledger folds under ``telemetry``; the chunk's own
    gather and sums are the rows ``fleet/schedule`` and ``fleet/fold`` of
    ``bench/fleet_scopes.py``."""
    import jax

    from bench import fleet_scopes
    from bench import stage_reduce as SR
    from bench.drivers import fleet_chunks as FC
    from repro.core.tick import STAGES
    cfg = dict(small_config(), replay_ticks=0, warmup_chunks=0)
    run = FC.Runner(cfg, G.build(cfg, G.load_mix("lifecycle"), SEED))
    run.setup()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = run.chunk.lower(run.state, *run.rows).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    stage_paths = {op.path for op in SR.hlo_ops(text).values()}
    assert {p[0] for p in stage_paths} == set(STAGES) | {SR.OUTSIDE}
    assert {("telemetry", "detect"), ("telemetry", "attrib")} <= stage_paths
    monkeypatch.setattr(SR, "scope_path", fleet_scopes.scope_path)
    paths = {op.path for op in SR.hlo_ops(text).values()}
    assert {("fleet", "schedule"), ("fleet", "fold")} <= paths
    assert paths - stage_paths == {("fleet", "schedule"), ("fleet", "fold")}
