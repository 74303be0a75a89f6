"""Streaming pathology detection (obs/streaming.py) + telemetry exporters.

The differential contract: the streaming detectors — windowed state
machines folded tick-by-tick inside the compiled engine — must agree with
the offline trace detectors (obs/pathology.py) when fed the same runs.
Integer-counter detectors (chronic thrashing, protection violation,
promotion stall) agree exactly; noisy neighbor replaces f64 trace means
with running f32 sums (documented <= 5% tolerance, exact on every scenario
pinned here). Three acceptance scenarios: a clean mixed fleet (both
silent), an injected noisy thrasher on a churned host (both flag it,
nobody else), and a churned thrasher through the single-host engine.

Also pinned: jaxpr size constant in horizon, detector boundary conditions
(departure exactly at a window edge, single-tick windows, steady_frac 0/1,
mid-window arrival gating), the unified histogram-percentile spec, and the
exporter validators (Chrome trace + Prometheus text exposition).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TieringConfig
from repro.core.churn import make_churn_tick, run_churn_engine
from repro.core.simulator import simulate_churn
from repro.core.state import init_state
from repro.core.workloads import (ChurnSlot, build_churn_schedule,
                                  cache_like, spark_like, thrasher, web_like)
from repro.obs import pathology as PA
from repro.obs.export import (chrome_trace, fleet_exposition,
                              rollout_exposition, validate_chrome_trace,
                              validate_exposition)
from repro.obs.fleet import fleet_rollout, mixed_fleet_hosts, stack_schedules
from repro.obs.pathology import detect_all, detect_chronic_thrashing
from repro.obs.stats import bucket_edges, hist_percentile, hist_percentile_j
from repro.obs.streaming import (KINDS, make_detector, run_detector,
                                 streaming_pathologies)
from repro.obs.trace import DIR_DEMOTE, DIR_PROMOTE, EVENT_DTYPE

_TICKS = 160
_FOOT = (32, 40, 40, 24)


def _cfg():
    total = sum(_FOOT)
    return TieringConfig(n_tenants=4, n_fast_pages=int(total * 1.15),
                         n_slow_pages=total,
                         lower_protection=(8, 12, 12, 8),
                         upper_bound=(24, 0, 0, 0), migration_cost=0.005)


def _hosts(noisy_host=None):
    """2 static + 2 churned hosts (the PR-5 fleet scenario)."""
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
            ChurnSlot(cache_like(_FOOT[2]), [(0, 60 + 10 * seed),
                                             (90, _TICKS)]),
            ChurnSlot(web_like(_FOOT[3]), [(8 * seed, _TICKS)]),
        ])
    hosts = mixed_fleet_hosts(static_mixes, churned, _TICKS)
    if noisy_host is not None:
        hosts[noisy_host][0] = ChurnSlot(thrasher(_FOOT[0], fast_share=12),
                                         [(30, _TICKS)])
    return hosts


def _keyset(pathologies):
    return sorted((p.kind, p.tenant) for p in pathologies)


def _assert_agree(online, offline):
    """Streaming and offline verdicts agree: same (kind, tenant) set, and
    severity/evidence within float tolerance (the noisy detector's running
    f32 sums vs offline f64 means)."""
    assert _keyset(online) == _keyset(offline)
    off = {(p.kind, p.tenant): p for p in offline}
    for p in online:
        q = off[(p.kind, p.tenant)]
        assert p.severity == pytest.approx(q.severity, rel=5e-2)
        for k, v in q.evidence.items():
            assert p.evidence[k] == pytest.approx(v, rel=5e-2, abs=1e-6)


def _offline_per_host(hosts):
    """The offline side of the fleet differential: each host alone through
    the single-host churn engine, full traces, offline detectors."""
    return [simulate_churn(_cfg(), slots, _TICKS, k_max=32).pathologies()
            for slots in hosts]


def _flagged(per_host):
    """Sorted unique (host, tenant) pairs over per-host pathology lists."""
    return sorted({(h, p.tenant) for h, ps in enumerate(per_host)
                   for p in ps})


def _offline_from_run(cfg, outs, active):
    return detect_all(
        np.asarray(outs.fast_usage), np.asarray(outs.slow_usage),
        np.asarray(outs.promotions), np.asarray(outs.demotions),
        np.asarray(outs.latency), np.asarray(outs.thrash_events),
        attempted=np.asarray(outs.attempted_promotions),
        lower_protection=tuple(cfg.lower_protection[:cfg.n_tenants]),
        active=active)


# ------------------------------------------- differential: 3 scenarios ----
def test_differential_churned_thrasher_single_host():
    """Scenario: a churned thrasher through the single-host engine. The
    in-tick streamed state, the host-side replay (run_detector on the same
    telemetry), and the offline trace detectors all agree."""
    cfg = _cfg()
    slots = [
        ChurnSlot(thrasher(_FOOT[0], fast_share=12), [(30, _TICKS)]),
        ChurnSlot(cache_like(_FOOT[1]), [(5, _TICKS)]),
        ChurnSlot(cache_like(_FOOT[2]), [(0, 60), (90, _TICKS)]),
        ChurnSlot(web_like(_FOOT[3]), [(0, _TICKS)]),
    ]
    schedule = build_churn_schedule(slots, _TICKS)
    spec = make_detector(_TICKS, 4, cfg.lower_protection)
    final, outs = run_churn_engine(cfg, schedule, k_max=32, detector=spec)

    online = streaming_pathologies(spec, final.det)
    active = np.asarray(schedule.want) > 0
    offline = _offline_from_run(cfg, outs, active)
    assert ("chronic_thrashing", 0) in _keyset(offline)   # non-vacuous
    _assert_agree(online, offline)

    # host-side replay through the same scan update == the in-tick state
    cum = np.asarray(outs.thrash_events)
    replay = run_detector(
        spec, active=active,
        thrash_new=np.diff(cum, axis=0, prepend=np.zeros((1, 4))),
        fast_usage=np.asarray(outs.fast_usage),
        slow_usage=np.asarray(outs.slow_usage),
        attempted=np.asarray(outs.attempted_promotions),
        promotions=np.asarray(outs.promotions),
        demotions=np.asarray(outs.demotions),
        latency=np.asarray(outs.latency))
    for f in replay._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(replay, f)), np.asarray(getattr(final.det, f)),
            rtol=1e-6, err_msg=f)


def test_differential_clean_fleet_silent():
    """Scenario: clean mixed fleet. Offline (each host through
    simulate_churn, full traces) and streaming (fleet_rollout, O(1) trace
    memory) both stay silent."""
    hosts = _hosts()
    offline = _offline_per_host(hosts)
    assert _flagged(offline) == []

    want, rates = stack_schedules(
        [build_churn_schedule(s, _TICKS) for s in hosts])
    roll = fleet_rollout(_cfg(), want, rates, _TICKS, chunk=64, k_max=32)
    assert roll.tenants_flagged() == []
    assert roll.pathology_counts() == {}
    assert roll.pathology_rollup()["hosts_with_pathology"] == 0
    for h in range(roll.n_hosts):
        _assert_agree(roll.host_pathologies(h), offline[h])


def test_differential_noisy_fleet_flagged():
    """Scenario: thrasher injected on churned host 2. Both paths flag
    exactly (host 2, tenant 0) and agree on the per-host verdicts."""
    noisy = 2
    hosts = _hosts(noisy_host=noisy)
    offline = _offline_per_host(hosts)
    want, rates = stack_schedules(
        [build_churn_schedule(s, _TICKS) for s in hosts])
    roll = fleet_rollout(_cfg(), want, rates, _TICKS, chunk=64, k_max=32)

    assert (noisy, 0) in roll.tenants_flagged("chronic_thrashing")
    assert roll.tenants_flagged() == _flagged(offline)
    assert roll.pathology_counts() == \
        PA.count_by_kind([p for ps in offline for p in ps])
    for h in range(roll.n_hosts):
        _assert_agree(roll.host_pathologies(h), offline[h])
    # online-only signals: the flag was raised while the run was live
    k = KINDS.index("chronic_thrashing")
    first = roll.pathology_first_flag()
    assert 0 <= first[noisy, 0, k] < _TICKS
    assert roll.pathology_flag_ticks()[noisy, 0, k] > 0
    # deterministic ordering (satellite: sorted, list not set)
    assert roll.tenants_flagged() == sorted(roll.tenants_flagged())
    assert isinstance(roll.tenants_flagged(), list)


def test_detector_jaxpr_constant_in_horizon():
    """The detector seam adds a fixed number of equations: jaxpr size of the
    detector-carrying tick is identical at a 200-tick and a 10k-tick horizon
    (window geometry is baked in as Python constants, horizon is data)."""
    from repro.analysis.constancy import assert_jaxpr_constant

    cfg = _cfg()
    L = cfg.n_fast_pages + cfg.n_slow_pages
    S = max(_FOOT)

    def build(horizon):
        spec = make_detector(horizon, 4, cfg.lower_protection)
        tick = make_churn_tick(cfg, L, k_max=32, detector=spec)
        state = init_state(cfg, L, detector=spec)
        inp = (jnp.ones((4, S), jnp.float32), jnp.full((4,), 16, jnp.int32))
        return tick, (state, inp)

    assert_jaxpr_constant(build, (200, 10_000),
                          label="detector tick: horizon")

    # and the streamed state itself is O(T): no leaf scales with horizon
    spec = make_detector(10_000, 4, cfg.lower_protection)
    state = init_state(cfg, L, detector=spec)
    for leaf in jax.tree_util.tree_leaves(state.det):
        assert leaf.size <= 4 * len(KINDS)


# ------------------------------------------------ boundary conditions ----
def _synthetic(horizon, T, *, active, thrash_per_tick=None, fast=None,
               slow=None, attempted=None, promotions=None, demotions=None,
               latency=None):
    """[ticks, T] telemetry set with offline/streaming-compatible shapes."""
    z = np.zeros((horizon, T))
    sig = dict(
        active=np.asarray(active, bool),
        thrash_new=z if thrash_per_tick is None else thrash_per_tick,
        fast_usage=z if fast is None else fast,
        slow_usage=z if slow is None else slow,
        attempted=z if attempted is None else attempted,
        promotions=z if promotions is None else promotions,
        demotions=z if demotions is None else demotions,
        latency=np.ones((horizon, T)) if latency is None else latency)
    return {k: np.asarray(v) for k, v in sig.items()}


def _both(spec, sig, lower_protection=()):
    online = streaming_pathologies(spec, run_detector(spec, **sig))
    offline = detect_all(
        sig["fast_usage"], sig["slow_usage"], sig["promotions"],
        sig["demotions"], sig["latency"],
        np.cumsum(sig["thrash_new"], axis=0),
        attempted=sig["attempted"], lower_protection=lower_protection,
        active=sig["active"])
    return online, offline


def test_departure_exactly_at_window_edge():
    """A thrasher departing exactly at a window boundary is still judged
    over the windows it fully resided in — and its final (just-closed)
    window counts, because the closing tick's events belong to it."""
    H, T, W = 80, 2, 20                      # s0=40: windows [40,60),[60,80)
    active = np.ones((H, T), bool)
    active[60:, 0] = False                   # departs exactly at the edge
    ev = np.zeros((H, T))
    ev[:60, 0] = 6                           # 6 events/tick while resident
    sig = _synthetic(H, T, active=active, thrash_per_tick=ev)
    online, offline = _both(make_detector(H, T), sig)
    assert ("chronic_thrashing", 0) in _keyset(offline)
    _assert_agree(online, offline)
    # offline evidence: exactly ONE resident window ([40,60)), all bad
    p = next(p for p in online if p.tenant == 0)
    assert p.evidence["bad_window_frac"] == 1.0

    # current-state detectors (stall) skip the departed tenant: demand that
    # vanished with the tenant is churn, not a stalled promoter
    att = np.zeros((H, T))
    att[:60, 0] = 8                          # heavy demand, zero successes
    sig = _synthetic(H, T, active=active, attempted=att)
    online, offline = _both(make_detector(H, T), sig)
    assert _keyset(online) == _keyset(offline) == []


def test_single_tick_windows():
    """window=1: every steady tick is its own window; a tenant over the
    rate threshold every tick flags, one under it never does."""
    H, T = 40, 2
    active = np.ones((H, T), bool)
    ev = np.zeros((H, T))
    ev[:, 0] = 5                             # > 4.0/window -> every window bad
    ev[:, 1] = 3                             # under threshold -> never bad
    cum = np.cumsum(ev, axis=0)
    offline = detect_chronic_thrashing(cum, window=1, active=active)
    spec = make_detector(H, T, window=1)
    assert spec.window == 1
    online = [p for p in streaming_pathologies(
        spec, run_detector(spec, **_synthetic(H, T, active=active,
                                              thrash_per_tick=ev)))
        if p.kind == "chronic_thrashing"]
    _assert_agree(online, offline)
    assert _keyset(online) == [("chronic_thrashing", 0)]


def test_steady_frac_extremes():
    """steady_frac=0 -> empty steady window, nothing judged, nothing
    crashes; steady_frac=1 -> the whole run is steady and window geometry
    follows the same shrink rule as offline."""
    H, T = 40, 2
    active = np.ones((H, T), bool)
    ev = np.zeros((H, T))
    ev[:, 0] = 6
    sig = _synthetic(H, T, active=active, thrash_per_tick=ev)

    spec0 = make_detector(H, T, steady_frac=0.0)
    assert spec0.n_steady == 0
    assert streaming_pathologies(spec0, run_detector(spec0, **sig)) == []

    spec1 = make_detector(H, T, steady_frac=1.0)
    assert spec1.steady_start == 0 and spec1.n_steady == H
    # 40 fits exactly two 20-tick windows: no shrink (offline rule is <)
    assert spec1.window == 20
    out = streaming_pathologies(spec1, run_detector(spec1, **sig))
    p = next(p for p in out if p.kind == "chronic_thrashing")
    assert p.tenant == 0 and p.evidence["bad_window_frac"] == 1.0
    # one closed window ([0,20), judged at t=20; the run ends before t=40
    # closes the second), holding the events of ticks 1..20
    assert p.evidence["mean_rate"] == pytest.approx(120.0)

    # a horizon that can't fit two windows shrinks: 30 // 4 = 7
    spec_small = make_detector(30, T, steady_frac=1.0)
    assert spec_small.window == 7


def test_mid_window_arrival_gating():
    """A tenant arriving mid-steady-window is gated exactly as offline:
    thrash windows it only partially covers don't count, and the
    protection-violation roster gate (resident >= 50% of steady) skips it
    until it has real residency."""
    H, T = 80, 2                             # s0=40
    active = np.ones((H, T), bool)
    active[:70, 0] = False                   # arrives at t=70: 25% of steady
    fast = np.zeros((H, T))
    slow = np.zeros((H, T))
    att = np.zeros((H, T))
    slow[:, 0] = 10                          # demand covers protection of 8,
    att[:, 0] = 2                            # wants promotion, fast stays 0
    ev = np.zeros((H, T))
    ev[70:, 0] = 6                           # thrashing, but only 10 ticks
    sig = _synthetic(H, T, active=active, thrash_per_tick=ev, fast=fast,
                     slow=slow, attempted=att)
    online, offline = _both(make_detector(H, T, (8, 0)), sig,
                            lower_protection=(8, 0))
    # window [60,80) not fully resident; 25% < 50% residency gates the rest
    assert _keyset(online) == _keyset(offline) == []

    # same signals with an early arrival (t=44: covers window [60,80) fully
    # and 90% of steady): both paths now flag protection violation + stall
    active2 = np.ones((H, T), bool)
    active2[:44, 0] = False
    ev2 = np.zeros((H, T))
    ev2[44:, 0] = 6
    sig2 = _synthetic(H, T, active=active2, thrash_per_tick=ev2, fast=fast,
                      slow=slow, attempted=att)
    online2, offline2 = _both(make_detector(H, T, (8, 0)), sig2,
                              lower_protection=(8, 0))
    assert ("protection_violation", 0) in _keyset(offline2)
    assert ("promotion_stall", 0) in _keyset(offline2)
    _assert_agree(online2, offline2)


# -------------------------------------------------- percentile spec ----
def test_hist_percentile_edge_cases():
    NB = 8
    edges = bucket_edges(NB)
    empty = np.zeros((1, NB), np.int64)
    last = np.zeros((2, NB), np.int64)
    last[:, -1] = 7                          # all mass in the last bucket
    mid = np.zeros((1, NB), np.int64)
    mid[0, 2] = 3
    mid[0, 5] = 1

    for q in (0.0, 0.5, 1.0):
        assert hist_percentile(empty, q)[0] == 0.0
    assert hist_percentile(last, 0.5).tolist() == [edges[-1]] * 2
    assert hist_percentile(last, 1.0).tolist() == [edges[-1]] * 2
    assert hist_percentile(last, 0.0).tolist() == [0.0] * 2   # cum[0] >= 0
    assert hist_percentile(mid, 0.0)[0] == 0.0
    assert hist_percentile(mid, 0.5)[0] == edges[2]
    assert hist_percentile(mid, 1.0)[0] == edges[5]           # last non-empty

    rng = np.random.default_rng(0)
    h = rng.integers(0, 9, size=(16, NB))
    h[3] = 0                                                  # an empty row
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        np.testing.assert_array_equal(
            hist_percentile(h, q), np.asarray(hist_percentile_j(h, q)),
            err_msg=f"q={q}")


# ------------------------------------------------------- exporters ----
def _events(rows):
    return np.array(rows, dtype=EVENT_DTYPE)


def test_chrome_trace_span_pairing():
    ev = _events([
        (2, 0, 5, DIR_PROMOTE, 1.5), (4, 0, 5, DIR_DEMOTE, 0.2),   # thrash
        (3, 1, 9, DIR_PROMOTE, 2.0), (50, 1, 9, DIR_DEMOTE, 1.0),  # resident
        (6, 0, 7, DIR_DEMOTE, 0.1),          # promote lost to ring wrap
        (55, 1, 11, DIR_PROMOTE, 3.0),       # never demoted: open at horizon
    ])
    tr = chrome_trace({0: ev}, t_resident=8, horizon=60)
    assert validate_chrome_trace(tr) == 4
    validate_chrome_trace(json.dumps(tr))    # text form round-trips
    by_name = {e["name"]: e for e in tr["traceEvents"] if e["ph"] != "M"}
    assert by_name["thrash"]["args"]["residency_ticks"] == 2
    assert by_name["fast_resident"]["args"]["residency_ticks"] == 47
    assert by_name["fast_resident_open"]["args"]["residency_ticks"] == 5
    assert by_name["demote"]["ph"] == "i"
    assert by_name["thrash"]["pid"] == 0 and by_name["thrash"]["tid"] == 0


def test_chrome_trace_validator_rejects():
    with pytest.raises(ValueError):
        validate_chrome_trace([1, 2, 3])               # not an object
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "a",
                                                "pid": 0, "tid": 0}]})  # no ts
    bad = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 10, "dur": 1},
        {"ph": "X", "name": "b", "pid": 0, "tid": 0, "ts": 5, "dur": 1},
    ]}
    with pytest.raises(ValueError, match="monotone"):
        validate_chrome_trace(bad)
    # same timestamps on DIFFERENT tracks are fine
    bad["traceEvents"][1]["tid"] = 1
    assert validate_chrome_trace(bad) == 2


def test_exposition_grammar_and_histograms():
    counters = {"promotions": np.array([[3, 0], [1, 9]])}
    hist = np.zeros((2, 2, 4), np.int64)
    hist[0, 0, 1] = 5
    hist[1, 1, 3] = 2
    flag = np.zeros((2, 2, len(KINDS)), np.int32)
    flag[1, 0, 0] = 7
    first = np.full((2, 2, len(KINDS)), -1, np.int32)
    first[1, 0, 0] = 40
    text = fleet_exposition(counters, resid_hist=hist, flag_ticks=flag,
                            first_flag=first)
    n = validate_exposition(text)
    assert n > 0
    assert ('equilibria_pathology_flag_ticks_total{host="1",tenant="0",'
            'kind="chronic_thrashing"} 7') in text
    # first_flag gauge emitted only for tenants that actually flagged
    assert text.count("first_flag_tick{") == 1
    # histogram: le series cumulative, +Inf present, _count matches
    assert 'le="+Inf"' in text and "_count{" in text


def test_exposition_validator_rejects():
    with pytest.raises(ValueError, match="no TYPE"):
        validate_exposition('undeclared_metric 1\n')
    with pytest.raises(ValueError, match="not a valid sample"):
        validate_exposition('# TYPE m counter\nm{bad-label="x"} 1\n')
    bad_hist = "\n".join([
        "# HELP h x", "# TYPE h histogram",
        'h_bucket{le="1"} 5', 'h_bucket{le="2"} 3',   # not cumulative
        'h_bucket{le="+Inf"} 5', "h_count 5", "h_sum 1"])
    with pytest.raises(ValueError, match="cumulative"):
        validate_exposition(bad_hist)
    no_inf = "\n".join([
        "# HELP h x", "# TYPE h histogram",
        'h_bucket{le="1"} 5', "h_count 5"])
    with pytest.raises(ValueError, match=r"\+Inf"):
        validate_exposition(no_inf)
    mismatch = "\n".join([
        "# HELP h x", "# TYPE h histogram",
        'h_bucket{le="1"} 5', 'h_bucket{le="+Inf"} 5', "h_count 6"])
    with pytest.raises(ValueError, match="_count"):
        validate_exposition(mismatch)


def test_rollout_exposition_end_to_end():
    """A real (tiny) rollout exports valid exposition including the
    pathology counter families."""
    hosts = _hosts()[:2]
    ticks = 40
    want, rates = stack_schedules(
        [build_churn_schedule(s, ticks) for s in hosts])
    roll = fleet_rollout(_cfg(), want, rates, ticks, chunk=16, k_max=32)
    text = rollout_exposition(roll)
    assert validate_exposition(text) > 0
    assert "equilibria_pathology_flag_ticks_total" in text
    assert "equilibria_fast_residency_ticks_bucket" in text
