#!/usr/bin/env python3
"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1
                      [--control 1]

Set-up builds the cell's traffic from the seed (``bench/traffic``), hands
it to the entry its configuration names (``bench/drivers/<entry>.py``),
and warms up every shape the window uses. The window then calls the entry
back to back for ``--seconds``; nothing compiles inside it. Afterwards the
kept chunks are compared with the plain reference (``bench/check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
a stretch of the window with the profiler and reports the cell's per-layer
metrics (``bench/metrics/<name>.py``), ``busy_s``/``window_s`` and a
``breakdown``. The last line of stdout is one JSON object; the compared
numbers, each beside its limit, are the last lines of stderr and the last
key of that object. ``--control 1`` adds the control's compared numbers
under ``control``, the readings the limits are set from. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
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

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(Exception):
    pass


class CompileClock:
    """Counts XLA backend compiles and their seconds (a persistent-cache
    hit compiles nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count, self.secs = 0, 0.0

        def listen(event, secs, **_):
            if event == self.EVENT:
                self.count += 1
                self.secs += secs
        jax.monitoring.register_event_duration_secs_listener(listen)


def load_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m["workloads"] or (
                "workloads" not in m and m["moves"] in moves)]


def device_info(chips: int, require_tpu: bool):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from None
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def run_cell(cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, bench: dict, require_tpu: bool = True,
             fault=None, control: bool = False,
             ewma_fused: bool = False) -> dict:
    """One run of a cell. ``fault`` plants one of the driver's faults under
    the timed path (tests only). ``control`` also reports, under the key
    ``control``, the numbers the control (the reference in bfloat16 put in
    the program's place) gives on the same chunks. ``ewma_fused`` has the
    reference round the hotness EWMA once, as XLA's CPU backend compiles
    the static tick's (tests only)."""
    import numpy as np

    from repro.launch.compile_cache import enable_compile_cache
    devs = device_info(int(cell["chips"]), require_tpu)
    enable_compile_cache()
    import jax
    clock = CompileClock()

    from bench.traffic.generate import build
    traffic = build(config, mix, seed)
    driver = importlib.import_module(f"bench.drivers.{config['entry']}")
    runner = driver.Runner(config, traffic, fault=fault)
    runner.setup()
    setup_s = time.perf_counter() - T_START

    # steps whose result is compared with the reference: drawn from the seed
    rng = np.random.default_rng(seed)
    keep = set(rng.choice(int(config["check_span"]),
                          size=int(config["check_steps"]),
                          replace=False).tolist())
    trace_s = float(config["trace_seconds"])
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    times, ticks, traced_ticks, tracing = [], 0, 0, False
    compiles0 = clock.count
    start = time.perf_counter()
    if trace:
        jax.profiler.start_trace(TRACE_DIR)
        tracing = True
    while True:
        t0 = time.perf_counter()
        n = runner.step(keep=len(times) in keep)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        ticks += n
        if tracing:
            traced_ticks += n
            if t1 - start >= trace_s:
                jax.profiler.stop_trace()
                tracing = False
        if t1 - start >= seconds:
            break
    window_s = t1 - start
    window_compiles = clock.count - compiles0
    if tracing:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    kept = runner.release()

    print(f"[window] {len(times)} chunks of {ticks // len(times)} "
          f"host-ticks in {window_s:.3f} s; compiles inside: "
          f"{window_compiles}; setup {setup_s:.3f} s "
          f"(compiling {clock.secs:.3f} s)", file=sys.stderr, flush=True)
    print(f"[window] peak device memory {peak} bytes", file=sys.stderr,
          flush=True)

    wanted = cell_metrics(bench, cell, trace)
    values = {}
    result_extra = {}
    if trace:
        from bench import bytes as B
        from bench import trace_reduce as TR
        pb = sorted(glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                              recursive=True))
        red = TR.reduce(TR.load(pb[-1])) if pb else {}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        kind = devs[0].device_kind
        if require_tpu and kind not in peaks:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           f"bench/peaks.json")
        ctx = {"trace": red, "host_ticks_traced": traced_ticks,
               "peak": peaks.get(kind),
               "tick_bytes": B.host_tick_bytes(**runner.shapes())}
        for m in wanted:
            mod = importlib.import_module(f"bench.metrics.{m['name']}")
            v = mod.read(ctx)
            if v is not None:
                values[m["name"]] = v
        if red:
            busy = red["busy_s"]
            result_extra["device"] = {
                "busy_s": sum(busy.values()) / len(busy),
                "window_s": red["window_s"]}
            result_extra["breakdown"] = {
                "device_ops": [[n, s] for n, s in red["top_ops"]],
                "idle_gaps": [[n, s] for n, s in red["idle_gaps"]]}
            print(f"[trace] {traced_ticks} host-ticks traced over "
                  f"{red['window_s']:.4f} s", file=sys.stderr, flush=True)
    else:
        e2e = {"host_ticks_per_s": ticks / window_s,
               "chunk_p95_ms": float(np.percentile(times, 95)) * 1e3,
               "setup_s": setup_s}
        print(f"[window] chunk time: {len(times)} samples, median "
              f"{np.median(times) * 1e3:.4f} ms", file=sys.stderr,
              flush=True)
        values = {m["name"]: e2e[m["name"]] for m in wanted}

    from bench.check import judge
    t_check = time.perf_counter()
    cmp = driver.compare(kept, traffic, config, fused_ewma=ewma_fused)
    verdict = judge(cmp["numbers"], config["limits"])
    correct = verdict["ok"] and cmp["chunks_compared"] > 0 \
        and window_compiles == 0
    print(f"[check] {cmp['chunks_compared']} chunks compared, "
          f"{cmp['chunks_differing']} with integer differences; widest "
          f"float gap at {cmp['widest_float'] or '-'} "
          f"({time.perf_counter() - t_check:.3f} s)", file=sys.stderr,
          flush=True)

    units = {m["name"]: m["unit"] for m in wanted}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    device.update(result_extra.pop("device", {}))
    out = {"correct": bool(correct), "attempted": len(times),
           "failed": cmp["chunks_differing"],
           "metrics": {k: {"value": v, "unit": units[k]}
                       for k, v in values.items()},
           "device": device}
    out.update(result_extra)
    if control:
        out["control"] = driver.compare(kept, traffic, config, control=True,
                                        fused_ewma=ewma_fused)["numbers"]
    out["compared"] = dict(verdict["compared"], window_compiles={
        "value": window_compiles, "limit": 0})
    for name, v in out["compared"].items():
        print(f"[check] {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: also report the control's compared numbers, "
                    "for setting the limits of correct")
    args = ap.parse_args(argv)
    bench = load_bench()
    cell = next(c for c in bench["workloads"] if c["name"] == args.workload)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    from bench.traffic.generate import load_mix
    try:
        out = run_cell(cell, cfg, load_mix(cell["traffic"]), args.seed,
                       args.seconds, bool(args.trace), bench,
                       control=bool(args.control))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
