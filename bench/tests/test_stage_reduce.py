"""CPU tests of the benchmark's reduction of device time by tick stage
(``bench/stage_reduce.py``): a by-hand HLO program and trace, and the
replay of a slice recorded from the chip in the ``churn64-lifecycle`` cell.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import stage_reduce as SR  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402


# ------------------------------------------------ reduction by stage ----
HLO_BY_HAND = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/tick/alloc/add"}
  ROOT %gather.2 = f32[8]{0} multiply(%add.1, %add.1), metadata={op_name="jit(f)/while/body/tick/select/demote/jit(_where)/gather"}
}

%cmp (a: f32[], b: f32[]) -> pred[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %lt = pred[] compare(%a, %b), direction=LT, metadata={op_name="lt"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %sort.2 = f32[8]{0} sort(%fusion.1), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(f)/while/body/tick/ownership/reclaim/sort"}
  %copy.3 = f32[8]{0} copy(%sort.2)
  %add.4 = f32[8]{0} add(%copy.3, %copy.3), metadata={op_name="jit(f)/while/body/tick/telemetry/add"}
  %add.5 = s32[] add(%i, %i), metadata={op_name="jit(f)/while/body/add"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%add.5, %add.4)
}

%cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %c = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%i.1, %c), direction=LT, metadata={op_name="jit(f)/while/cond/lt"}
}

ENTRY %main (x.0: f32[8]) -> f32[8] {
  %x.0 = f32[8]{0} parameter(0)
  %z = s32[] constant(0)
  %t.0 = (s32[], f32[8]{0}) tuple(%z, %x.0)
  %while.0 = (s32[], f32[8]{0}) while(%t.0), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.0), index=1
}
"""


def test_stage_reduce_by_hand():
    ops = SR.hlo_ops(HLO_BY_HAND)
    # a fusion takes its root's path; the comparator is no op of its own
    assert ops["fusion.1"] == SR.HloOp(("select", "demote"),
                                       frozenset({"alloc", "select"}), False)
    assert "lt" not in ops and ops["lt.1"].path == (SR.OUTSIDE,)
    # no op_name: the path of the named op it feeds
    assert ops["copy.3"] == SR.HloOp(("telemetry",), frozenset(), True)
    # one loop iteration, nested in the while op: 15 ns of it is its own
    ev = {"devices": {"/device:TPU:0": [
        ("%while.0 = (s32[], f32[8]{0}) while(...)", 0, 100),
        ("%fusion.1", 0, 30), ("%sort.2", 30, 20), ("%copy.3", 50, 5),
        ("%add.4", 55, 15), ("%add.5", 70, 10), ("%lt.1", 80, 5)]},
        "spans": [("dispatch", 0, 100)]}
    st = SR.reduce(ev, ops)
    own = {p: pytest.approx(t * 1e-9) for p, t in {
        "select": 30, "select/demote": 30, "ownership": 20,
        "ownership/reclaim": 20, "telemetry": 20, SR.OUTSIDE: 30}.items()}
    assert st["own_s"] == own
    assert st["ops"][SR.OUTSIDE] == 3 and st["ops"]["telemetry"] == 2
    assert st["cross_stage_s"] == pytest.approx(30e-9)
    assert st["inferred_s"] == pytest.approx(5e-9)
    # the stages and outside_tick add up to the device's busy time
    top = [t for p, t in st["own_s"].items() if "/" not in p]
    assert sum(top) == pytest.approx(st["total_s"])
    assert st["total_s"] == pytest.approx(
        TR.reduce(ev)["busy_s"]["/device:TPU:0"])
    ctx = {"stages": st, "host_ticks_traced": 2}
    assert SR.us_per_host_tick(ctx, "select") == pytest.approx(0.015)
    assert SR.us_per_host_tick(ctx, "hotness") == 0.0
    # a program without the scopes reads nothing
    bare = {n: SR.HloOp((SR.OUTSIDE,), frozenset(), False) for n in ops}
    assert SR.us_per_host_tick({"stages": SR.reduce(ev, bare),
                                "host_ticks_traced": 2}, "select") is None


def test_stage_reduce_reproduces_recorded_trace():
    with open(os.path.join(ROOT, "bench", "testdata",
                           "trace_churn64_stages.json")) as f:
        rec = json.load(f)
    st = SR.reduce(rec["events"], SR.recorded_ops(rec["paths"]))
    exp = rec["expected"]
    for k in ("window_s", "total_s", "cross_stage_s", "inferred_s"):
        assert st[k] == pytest.approx(exp[k], rel=1e-9), k
    assert st["own_s"] == {p: pytest.approx(t, rel=1e-9)
                           for p, t in exp["own_s"].items()}
    assert st["ops"] == exp["ops"]
    # the slice starts a chunk: the dynamic provider's lifecycle blocks
    assert {p for p in st["own_s"] if p.startswith("ownership/")} >= {
        "ownership/reclaim", "ownership/grant", "ownership/schedule"}
    top = [t for p, t in st["own_s"].items() if "/" not in p]
    assert sum(top) == pytest.approx(st["total_s"])
    busy = TR.reduce(rec["events"])["busy_s"]
    assert st["total_s"] == pytest.approx(sum(busy.values()), rel=1e-6)
