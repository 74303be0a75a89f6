"""CPU tests of the on-chip benchmark under ``bench/``: its traffic, its byte
count, its file layout, its refusal to run without a TPU, its trace
reduction, and the comparison that decides ``correct`` (sound runs pass,
the control and every planted fault fail).

The harness runs here at test sizes with the look for a TPU skipped; JAX
is only touched inside the tests.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import bytes as B  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402
from bench.drivers.engine_chunks import FAULTS  # noqa: E402
from bench.traffic import generate as G  # noqa: E402
from bench.traffic import patterns as PT  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name):
    entry = next(c for c in _bench()["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


# Test sizes: the same rosters and mixes, a few tenants of small footprint.
SMALL = {
    "stacked64": dict(roster={"builder": "stacked_heterogeneous",
                              "n_tenants": 8, "base_footprint": 512},
                      n_tenants=8, n_pages=4264, n_fast_pages=2304, k_max=64),
    "churn64": dict(roster={"builder": "churn_stacked", "n_stable": 3,
                            "n_poisson": 3, "n_serverless": 2, "scale": 4},
                    n_tenants=8, n_pages=2912, n_fast_pages=896),
}
# XLA's CPU backend fuses the static tick's hotness EWMA into one rounding
# and leaves the dynamic tick's as two (the TPU rounds both twice); the
# reference is told which, so that the last bit of a hotness near the
# promotion threshold decides alike.
CPU_FUSED = {"stacked64": True, "churn64": False}


def _small(name):
    cfg = _config(name)
    cfg.update(SMALL[name], warmup_chunks=2, check_span=6, check_steps=6)
    return cfg


# ------------------------------------------------------------- traffic ----
def test_static_traffic_matches_program_generator():
    from repro.core import workloads as W
    ticks = 130
    ours = G.static_trace(PT.stacked_heterogeneous(10, 96), ticks)
    theirs = W.build_trace(W.stacked_heterogeneous(10, 96), ticks)
    for a, b in zip(ours, theirs):
        np.testing.assert_array_equal(a, b)
    assert PT.suggest_policy(PT.stacked_heterogeneous(10, 96)) == \
        W.suggest_policy(W.stacked_heterogeneous(10, 96))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_churn_traffic_matches_program_generator(seed):
    from repro.core import workloads as W
    ticks = 240
    want, rates = G.churn_schedule(PT.churn_stacked(6, 6, 4, ticks, seed),
                                   ticks)
    theirs = W.build_churn_schedule(W.churn_stacked(6, 6, 4, ticks, seed),
                                    ticks)
    np.testing.assert_array_equal(want, theirs.want)
    np.testing.assert_array_equal(rates, theirs.rates)


def test_traffic_is_a_function_of_the_seed():
    cfg = _small("stacked64")
    mix = G.load_mix("prodmix")
    a, b = G.build(cfg, mix, 3), G.build(cfg, mix, 3)
    c = G.build(cfg, mix, 4)
    np.testing.assert_array_equal(a.accesses, b.accesses)
    assert not np.array_equal(a.accesses, c.accesses)
    # another seed deals the same arrivals: the layout and policy stay
    np.testing.assert_array_equal(a.owner, c.owner)
    assert a.lower_protection == c.lower_protection


def test_full_size_rosters_give_the_configured_sizes():
    for name in ("stacked64", "churn64"):
        cfg = _config(name)
        r = cfg["roster"]
        if r["builder"] == "stacked_heterogeneous":
            total = sum(w.footprint for w in PT.stacked_heterogeneous(
                r["n_tenants"], r["base_footprint"]))
            assert total == cfg["n_pages"]
        else:
            slots = PT.churn_stacked(r["n_stable"], r["n_poisson"],
                                     r["n_serverless"], 8, 0, r["scale"])
            total = sum(s.workload.footprint for s in slots)
            assert G._fast_pages(total, cfg["fast_frac"]) + total == \
                cfg["n_pages"]


# ---------------------------------------------------------- byte count ----
def test_byte_count_by_hand():
    # static, L=1000: 5 B inputs + 1 B tier + 8 B hot read and write
    assert B.host_tick_bytes("static", 1000) == 14 * 1000
    # dynamic, L=1000, T=4, S=100: rates 4*4*100 + want 4*4, then 13 B/page
    assert B.host_tick_bytes("dynamic", 1000, 4, 100) == 1616 + 13 * 1000


# -------------------------------------------------------------- layout ----
def test_benchmark_file_names_and_references_resolve():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    names = [c["name"] for c in bench["configs"]] + \
        [w["name"] for w in bench["workloads"]] + \
        [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = _config(c["name"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) <= set(cfg)
        assert os.path.isfile(os.path.join(ROOT, "bench", "drivers",
                                           cfg["entry"] + ".py"))
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert NAME.match(w["traffic"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)["devices"]


def test_run_without_tpu_exits_nonzero_and_prints_no_result():
    cell = _bench()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", cell, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct": true' not in p.stdout
    assert "no TPU" in p.stderr


# ----------------------------------------------------- trace reduction ----
def test_trace_reduce_by_hand():
    ev = {"devices": {"/device:TPU:0": [("a", 0, 10), ("b", 5, 10),
                                         ("a", 30, 20), ("c", 90, 40)]},
          "spans": [("dispatch", 0, 40), ("pull_outputs", 40, 60)]}
    red = TR.reduce(ev)
    assert red["window_s"] == pytest.approx(100e-9)
    # busy: [0,15] + [30,50] + [90,100] = 45 ns
    assert red["busy_s"]["/device:TPU:0"] == pytest.approx(45e-9)
    # own time: "b" overlaps the first "a" by 5 ns, which is b's
    assert dict(red["top_ops"])["a"] == pytest.approx(25e-9)
    assert dict(red["top_ops"])["b"] == pytest.approx(10e-9)
    assert red["idle_gaps"][0] == ("pull_outputs", pytest.approx(40e-9))


def test_trace_reduce_reproduces_recorded_trace():
    with open(os.path.join(ROOT, "bench", "testdata",
                           "trace_stacked64.json")) as f:
        rec = json.load(f)
    red = TR.reduce(rec["events"])
    exp = rec["expected"]
    assert red["window_s"] == pytest.approx(exp["window_s"], rel=1e-12)
    for dev, b in exp["busy_s"].items():
        assert red["busy_s"][dev] == pytest.approx(b, rel=1e-12)
    assert [n for n, _ in red["top_ops"]] == [n for n, _ in exp["top_ops"]]
    # an independent count of busy time: every op interval painted on a
    # 1 ns grid inside the window
    for dev, ops in rec["events"]["devices"].items():
        w0 = rec["events"]["spans"][0][1]
        w1 = max(s + d for _, s, d in rec["events"]["spans"])
        grid = np.zeros(int(w1 - w0), bool)
        for _, s, d in ops:
            a, b = int(max(s, w0) - w0), int(min(s + d, w1) - w0)
            if b > a:
                grid[a:b] = True
        assert red["busy_s"][dev] == pytest.approx(grid.sum() * 1e-9,
                                                   rel=1e-6)


# ------------------------------------------------------------- correct ----
def _run(name, cell, fault=None, control=False, trace=False):
    import bench.run as R
    bench = _bench()
    c = next(w for w in bench["workloads"] if w["name"] == cell)
    return R.run_cell(c, _small(name), G.load_mix(c["traffic"]), 2**31 + 9,
                      0.5, trace, bench, require_tpu=False, fault=fault,
                      control=control, ewma_fused=CPU_FUSED[name])


CELLS = [("stacked64", "stacked64-prodmix"), ("churn64", "churn64-lifecycle")]


@pytest.mark.parametrize("name,cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(name, cell):
    out = _run(name, cell, control=True)
    assert out["correct"], out["compared"]
    assert list(out)[-1] == "compared"
    limits = _small(name)["limits"]
    assert any(out["control"][k] > limits[k] for k in limits), out["control"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name,cell", CELLS)
def test_planted_fault_is_not_correct(name, cell, fault):
    out = _run(name, cell, fault=fault)
    assert not out["correct"], (fault, out["compared"])


def test_traced_run_reports_breakdown_without_a_device_trace():
    out = _run("stacked64", "stacked64-prodmix", trace=True)
    # the CPU trace has no TPU plane: no device metric is made up
    assert out["metrics"] == {}
    assert out["correct"]
