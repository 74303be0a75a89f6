#!/usr/bin/env python3
"""Device time of each stage of the tick in one cell of ``BENCHMARK.json``.

  python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>
                         [--record <path> --record-ms <ms>]

Set-up is ``bench/run.py``'s, compiling anew. Then the window runs
``--seconds`` untraced and ``--seconds`` traced, back to back in one
process, so that the two host-tick rates give the cost of tracing. The traced stretch is reduced by
``bench/stage_reduce.py`` against the chunk program's compiled HLO. The
nested stage table goes to stderr; the last line of stdout is one JSON
object: each stage's metric (``bench/metrics/<stage>_us_per_host_tick.py``)
and their sum beside ``device_us_per_host_tick`` of the same trace, the
share of own time in cross-stage fusions and in ops placed by data flow,
the longest ops, and the two rates. ``--record`` writes
``--record-ms`` of the trace from the start of a chunk, the paths of its
ops and the reduction of that slice, as a fixture for ``bench/tests``.
Without a TPU it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as R  # noqa: E402
from bench import stage_reduce as SR  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402


def metric_names() -> list:
    from repro.core.tick import STAGES
    return [f"{s}_us_per_host_tick" for s in STAGES + (SR.OUTSIDE,)]


def _window(runner, seconds: float):
    ticks, start = 0, time.perf_counter()
    while True:
        ticks += runner.step()
        if time.perf_counter() - start >= seconds:
            return ticks, time.perf_counter() - start


def _slice(events: dict, ops: dict, ms: float) -> dict:
    """``ms`` of the trace from the device's first op after the second
    ``dispatch`` span, clipped to it, ops named by instruction only."""
    dispatch = [s for n, s, _ in events["spans"] if n == "dispatch"]
    t0 = dispatch[min(1, len(dispatch) - 1)]
    dev, dev_ops = sorted(events["devices"].items())[0]
    a = min(s for _, s, _ in dev_ops if s >= t0)
    b = a + ms * 1e6

    def clip(evs):
        return [[n, max(s, a), min(s + d, b) - max(s, a)]
                for n, s, d in evs if s + d > a and s < b]
    sl = {"devices": {dev: [[n.split(" = ")[0], s, d]
                            for n, s, d in clip(dev_ops)]},
          "spans": clip(events["spans"])}
    if not sl["spans"] or sl["spans"][0][1] > a:
        sl["spans"].insert(0, ["between_spans", a, 0.0])
    names = {n.lstrip("%") for n, _, _ in sl["devices"][dev]}
    return {"events": sl,
            "paths": {n: [list(ops[n].path), sorted(ops[n].stages),
                          ops[n].inferred] for n in sorted(names) if n in ops}}


def measure(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
            record: str, record_ms: float) -> dict:
    import jax
    devs = R.device_info(int(cell["chips"]), require_tpu=True)
    # No persistent cache: its key leaves op_name metadata out, so it may
    # serve a build of the program from before a scope moved.
    jax.config.update("jax_enable_compilation_cache", False)

    from bench.traffic.generate import build
    traffic = build(config, mix, seed)
    entry = importlib.import_module(f"bench.drivers.{config['entry']}")
    runner = entry.Runner(config, traffic)
    runner.setup()
    setup_s = time.perf_counter() - T_START
    ops = SR.hlo_ops(runner.chunk.lower(runner.state, *runner.rows)
                     .compile().as_text())
    if all(op.path == (SR.OUTSIDE,) for op in ops.values()):
        raise RuntimeError("the chunk program names no tick stage: it was "
                           "built without the stage scopes")

    ticks0, secs0 = _window(runner, seconds)
    shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(R.TRACE_DIR)
    ticks1, secs1 = _window(runner, seconds)
    jax.profiler.stop_trace()
    runner.release()
    pb = sorted(glob.glob(os.path.join(R.TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True))
    events = TR.load(pb[-1]) if pb else {"devices": {}, "spans": []}
    shutil.rmtree(R.TRACE_DIR, ignore_errors=True)

    stages = SR.reduce(events, ops)
    ctx = {"trace": TR.reduce(events), "stages": stages,
           "host_ticks_traced": ticks1}
    values = {}
    for name in metric_names():
        v = importlib.import_module(f"bench.metrics.{name}").read(ctx)
        if v is not None:
            values[name] = v
    from bench.metrics import device_us_per_host_tick
    out = {"device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)},
           "setup_s": setup_s,
           "host_ticks_per_s": {"untraced": ticks0 / secs0,
                                "traced": ticks1 / secs1},
           "host_ticks_traced": ticks1,
           "metrics": values,
           "device_us_per_host_tick": device_us_per_host_tick.read(ctx),
           "stage_sum_us": sum(values.values()) if values else None}
    if stages:
        tot = stages["total_s"]
        out.update(cross_stage_pct=100 * stages["cross_stage_s"] / tot,
                   inferred_pct=100 * stages["inferred_s"] / tot,
                   top_ops=stages["top_ops"], top_cross=stages["top_cross"])
        print(SR.table(stages, ticks1), file=sys.stderr, flush=True)
        if record:
            rec = _slice(events, ops, record_ms)
            rec["about"] = (
                f"{record_ms:g} ms of a profiler trace of {cell['name']} "
                f"on one {devs[0].device_kind} (bench/stages.py --record), "
                "from the start of a chunk: the device's XLA Ops events by "
                "instruction name and the harness's host spans, clipped to "
                "the slice; 'paths' is what bench/stage_reduce.hlo_ops gave "
                "for those ops from the compiled chunk program; 'expected' "
                "is what reduce() gave on the slice.")
            red = SR.reduce(rec["events"], SR.recorded_ops(rec["paths"]))
            rec["expected"] = {k: red[k] for k in (
                "window_s", "own_s", "ops", "total_s", "cross_stage_s",
                "inferred_s")}
            with open(record, "w") as f:
                json.dump(rec, f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", default="",
                    help="write a slice of the trace here, as a fixture")
    ap.add_argument("--record-ms", type=float, default=80.0)
    args = ap.parse_args(argv)
    bench = R.load_bench()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    from bench.traffic.generate import load_mix
    try:
        out = measure(cell, cfg, load_mix(cell["traffic"]), args.seed,
                      args.seconds, record=args.record,
                      record_ms=args.record_ms)
    except R.NoChip as e:
        print(f"stages: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
