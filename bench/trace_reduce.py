"""From a profiler trace to the numbers the per-layer metrics read.

``load`` pulls what the reduction needs out of an ``.xplane.pb``: each
device's op events (the ``XLA Ops`` line of every ``/device:TPU:<n>``
plane) and the harness's host spans (``jax.profiler.TraceAnnotation``
events named in ``SPANS``). ``reduce`` then works on that plain data, so a
recorded fixture checks it without a chip.

The traced window runs from the start of the first harness span to the end
of the last. A device is busy where any of its ops runs; busy time is the
union of the op intervals inside the window. Ops nest on that line (a
``while`` or ``conditional`` spans the ops of its body), so an op's own
time is its duration less that of the ops inside it; the top ops are ranked
by it, under XLA's instruction name. An idle gap is a stretch of the window
with no op on that device, named by the harness span open on the host at
its middle.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

SPANS = ("dispatch", "pull_outputs")
OPS_LINE = "XLA Ops"
NAME_CHARS = 120        # an op is shown by the head of its HLO text

Event = Tuple[str, float, float]   # name, start_ns, duration_ns


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name[:NAME_CHARS], float(e.start_ns),
                         float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events if e.name in SPANS]
    return {"devices": devices, "spans": sorted(spans, key=lambda e: e[1])}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _span_at(spans: List[Event], t: float) -> str:
    for name, s, d in spans:
        if s <= t <= s + d:
            return name
    return "between_spans"


def own_times(ops: List[Event]) -> Dict[str, Tuple[str, float]]:
    """Per XLA instruction name: (shown name, summed own time in ns)."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    inner = [0.0] * len(ops)
    open_: List[int] = []
    for i, (_, s, d) in enumerate(ops):
        while open_ and ops[open_[-1]][1] + ops[open_[-1]][2] <= s:
            open_.pop()
        if open_:
            p = ops[open_[-1]]
            inner[open_[-1]] += min(s + d, p[1] + p[2]) - s
        open_.append(i)
    out: Dict[str, Tuple[str, float]] = {}
    for (name, _, d), sub in zip(ops, inner):
        key = name.split(" = ")[0]
        shown, t = out.get(key, (name, 0.0))
        out[key] = (shown, t + d - sub)
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """busy_s per device, window_s, the top device ops by own time and the
    longest idle gaps, all in seconds."""
    spans = events["spans"]
    if not spans or not events["devices"]:
        return {}
    w0 = spans[0][1]
    w1 = max(s + d for _, s, d in spans)
    busy: Dict[str, float] = {}
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for dev, ops in sorted(events["devices"].items()):
        inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in ops if s + d > w0 and s < w1]
        merged = _union([(s, s + d) for _, s, d in inside])
        busy[dev] = sum(b - a for a, b in merged) * 1e-9
        for shown, t in own_times(inside).values():
            op_time[shown] = op_time.get(shown, 0.0) + t * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_span_at(spans, (a + b) / 2), (b - a) * 1e-9))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy,
        "top_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
    }
