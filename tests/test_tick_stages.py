"""The tick's named stage scopes, read back from compiled HLO.

Every op of the tick runs under ``tick/<stage>`` for a stage of
``core.tick.STAGES``, so a device trace's own time splits by stage. These
tests compile the static and the dynamic tick (equilibria, batched, small
T and L) on the CPU and check the ``op_name`` of every instruction that
carries a name stack: a scope dropped or misspelt by a later refactor
fails here, not as time silently moved under another stage.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TieringConfig
from repro.core.churn import make_churn_tick
from repro.core.engine import make_tick
from repro.core.state import init_state
from repro.core.tick import STAGES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, L, S, K = 4, 256, 64, 16
OP_NAME = re.compile(r'op_name="([^"]*)"')
SITES = {"select": {"demote", "promote", "sync_demote"},
         "commit": {"demote", "promote", "sync_demote"}}
LIFECYCLE = {"reclaim", "grant", "slot_reuse", "schedule", "repartition"}
FOLDS = {"detect", "attrib"}        # steps 9b and 9c, under telemetry


@pytest.fixture(scope="module")
def hlo():
    """Compiled HLO text of both ticks. The persistent cache stays out of
    it: its key leaves op_name metadata out, so an entry compiled before a
    scope moved would be served in place of this build."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        cfg = TieringConfig(n_tenants=T, n_fast_pages=96, n_slow_pages=L,
                            lower_protection=(8,) * T,
                            upper_bound=(0, 40, 40, 40))
        owner = np.repeat(np.arange(T), L // T).astype(np.int32)
        static = make_tick(cfg, owner, mode="equilibria", k_max=K,
                           impl="batched")
        dynamic = make_churn_tick(cfg, L, mode="equilibria", k_max=K,
                                  impl="batched")
        text = {
            "static": jax.jit(static).lower(
                init_state(cfg, L, owner=owner),
                (jnp.ones(L, jnp.float32), jnp.ones(L, bool))),
            "dynamic": jax.jit(dynamic).lower(
                init_state(cfg, L),
                (jnp.ones((T, S), jnp.float32), jnp.full((T,), 40,
                                                         jnp.int32))),
        }
        yield {k: low.compile().as_text() for k, low in text.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _scopes(text):
    """The scope path after ``tick/`` of every op_name with a name stack
    (bare names, "state.tier" or a comparator's "lt", carry none; of the
    stacks XLA joins with ";" when it merges instructions, the first)."""
    out = []
    for name in OP_NAME.findall(text):
        name = name.split(";")[0]
        if "/" not in name:
            continue
        parts = name.split("/")
        assert "tick" in parts[:-1], f"op outside the tick scope: {name}"
        i = parts.index("tick")
        out.append(tuple(parts[i + 1:-1]))
    return out


@pytest.mark.parametrize("prog", ["static", "dynamic"])
def test_every_named_op_lies_under_a_stage(hlo, prog):
    scopes = _scopes(hlo[prog])
    assert scopes
    bad = {s for s in scopes if not s or s[0] not in STAGES}
    assert not bad, f"ops outside every stage: {sorted(bad)[:5]}"
    for stage, sites in SITES.items():
        assert {s[1] for s in scopes if s[0] == stage and len(s) > 1} \
            >= sites, stage


def test_every_stage_appears(hlo):
    seen = {s[0] for text in hlo.values() for s in _scopes(text) if s}
    assert seen == set(STAGES)
    lifecycle = {s[1] for s in _scopes(hlo["dynamic"])
                 if s[0] == "ownership" and len(s) > 1}
    assert lifecycle == LIFECYCLE


def test_fleet_folds_have_their_own_scopes():
    """A churn tick built with the streaming detectors and the attribution
    ledger runs them under ``telemetry/detect`` and ``telemetry/attrib``;
    built without them, it has the op set it had before: no such scope."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.obs.attribution import make_attribution
    from repro.obs.streaming import make_detector
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        cfg = TieringConfig(n_tenants=T, n_fast_pages=96, n_slow_pages=L,
                            lower_protection=(8,) * T,
                            upper_bound=(0, 40, 40, 40))
        det = make_detector(64, T, cfg.lower_protection)
        att = make_attribution(T, cfg.lat_fast)
        tick = make_churn_tick(cfg, L, mode="equilibria", k_max=K,
                               impl="batched", detector=det, attrib=att)
        text = jax.jit(tick).lower(
            init_state(cfg, L, detector=det, attrib=att),
            (jnp.ones((T, S), jnp.float32), jnp.full((T,), 40, jnp.int32))
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    scopes = _scopes(text)
    assert all(s and s[0] in STAGES for s in scopes)
    folds = {s[:2] for s in scopes if len(s) > 1 and s[1] in FOLDS}
    assert folds == {("telemetry", f) for f in FOLDS}


def test_ticks_without_the_folds_have_no_fold_scopes(hlo):
    for text in hlo.values():
        assert not {s[1] for s in _scopes(text) if len(s) > 1} & FOLDS


def test_bench_stage_metrics_name_the_stages():
    tail = "_us_per_host_tick.py"
    names = {f[:-len(tail)]
             for f in os.listdir(os.path.join(ROOT, "bench", "metrics"))
             if f.endswith(tail)} - {"device"}
    assert names == set(STAGES) | {"outside_tick"}
