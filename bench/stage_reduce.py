"""Device time by tick stage: from a profiler trace and the compiled chunk
program to each op's own time under the tick's named scopes.

Every op of the tick runs under ``tick/<stage>`` (``repro.core.tick.STAGES``)
and its HLO ``op_name`` metadata says which, with the site and sub-stage
scopes below the stage (``select/demote``, ``ownership/reclaim``). A trace
event names its op by HLO instruction. ``hlo_ops`` reads the compiled
program's HLO text: the scope path of each instruction the device runs,
for a fusion that of its root, and the stages its fused instructions come
from. ``reduce`` puts each op's own time (``trace_reduce.own_times``, over
the same window as ``trace_reduce.reduce``) under that path, at each of its
first three levels; ops with no ``tick/`` in their name, the scan
machinery and the chunk runner's row indexing, go under ``outside_tick``.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import trace_reduce as TR

OUTSIDE = "outside_tick"
LEVELS = 3               # stage / site / sub-stage
# instructions that compute nothing of their own
NON_COMPUTING = {"parameter", "constant", "get-tuple-element", "tuple",
                 "bitcast", "after-all"}

_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLEES = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


class HloOp(NamedTuple):
    path: Tuple[str, ...]    # scope path after ``tick/`` (``scope_path``)
    stages: frozenset        # stages of the fused instructions' op_names
    inferred: bool           # no op_name of its own: path from data flow


class _Instr(NamedTuple):
    name: str
    opcode: str
    op_name: Optional[str]
    root: bool
    operands: Tuple[str, ...]
    callees: Tuple[Tuple[str, str], ...]   # (attribute, computation)


def scope_path(op_name: Optional[str]) -> Tuple[str, ...]:
    """The scopes after ``tick/``, at most ``LEVELS`` deep: the stage, then
    the names below it up to the first transform (``jit(_where)``) or the
    primitive; ``(OUTSIDE,)`` for an op outside the tick. Of the name
    stacks XLA joins with ``;`` when it merges instructions, the first."""
    parts = (op_name or "").split(";")[0].split("/")
    if "tick" not in parts[:-1]:
        return (OUTSIDE,)
    i = parts.index("tick")
    path = parts[i + 1:-1][:LEVELS]
    for k in range(1, len(path)):
        if "(" in path[k]:
            return tuple(path[:k])
    return tuple(path) or ("tick",)


def _named(op_name: Optional[str]) -> bool:
    """A name stack, not a bare primitive name: XLA passes and some
    lowerings leave ops a bare name ("reduce_window_sum") or none."""
    return bool(op_name) and "/" in op_name


def _operands(rest: str, start: int) -> Tuple[str, ...]:
    depth, i = 0, start
    while i < len(rest):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            break
        i += 1
    return tuple(_OPERAND.findall(rest[start:i]))


def _parse(text: str) -> Tuple[Dict[str, List[_Instr]], Optional[str]]:
    comps: Dict[str, List[_Instr]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m and not line.startswith(" "):
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = " " + m.group(3)
        op = _OPCODE.search(rest)
        name = _OP_NAME.search(rest)
        callees = [(k, v) for k, v in _CALLEES.findall(rest)]
        for b in _BRANCHES.findall(rest):
            callees += [("branch", c.strip().lstrip("%"))
                        for c in b.split(",") if c.strip()]
        cur.append(_Instr(m.group(2), op.group(1) if op else "",
                          name.group(1) if name else None, bool(m.group(1)),
                          _operands(rest, op.end() - 1) if op else (),
                          tuple(callees)))
    return comps, entry


def _nearest(start: str, step, known: Dict[str, Tuple[str, ...]]):
    """Breadth-first along ``step`` (users or operands) from ``start`` to
    the first instruction with a path of its own."""
    seen, frontier = {start}, [start]
    while frontier:
        nxt = []
        for n in frontier:
            for m in step(n):
                if m in known:
                    return known[m]
                if m not in seen:
                    seen.add(m)
                    nxt.append(m)
        frontier = nxt
    return None


def hlo_ops(text: str) -> Dict[str, HloOp]:
    """Every instruction the device runs as an op of its own (those of the
    entry computation and of the bodies and branches of its control flow,
    not the insides of fusions, reducers or comparators), by name.

    An op's path is that of its ``op_name``, for a fusion its root's (else
    the fusion's own, else that of its last named fused instruction). An
    op with no named ``op_name`` takes the path of the nearest named op it
    feeds, else of the nearest named op that feeds it, else ``OUTSIDE``."""
    comps, entry = _parse(text)

    def fused(comp: str, seen: set):
        root, last, stages = None, None, set()
        for ins in comps.get(comp, []):
            for attr, callee in ins.callees:
                if attr == "calls" and callee not in seen:
                    seen.add(callee)
                    sub = fused(callee, seen)
                    stages |= sub[2]
                    last = sub[1] or last
            if _named(ins.op_name):
                last = ins.op_name
                if ins.opcode not in NON_COMPUTING:
                    stages.add(scope_path(ins.op_name)[0])
            if ins.root:
                root = ins.op_name
        return root, last, frozenset(stages)

    out: Dict[str, HloOp] = {}
    todo, seen = ([entry] if entry else []), set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        instrs = comps.get(comp, [])
        known: Dict[str, Tuple[str, ...]] = {}
        stages: Dict[str, frozenset] = {}
        for ins in instrs:
            names = [ins.op_name]
            for attr, callee in ins.callees:
                if ins.opcode == "fusion" and attr == "calls":
                    root, last, stages[ins.name] = fused(callee, {callee})
                    names = [root, ins.op_name, last]
                elif attr != "to_apply" or ins.opcode == "call":
                    todo.append(callee)
            name = next((n for n in names if _named(n)), None)
            if name:
                known[ins.name] = scope_path(name)
        users: Dict[str, List[str]] = {}
        operands = {ins.name: ins.operands for ins in instrs}
        for ins in instrs:
            for o in ins.operands:
                users.setdefault(o, []).append(ins.name)
        for ins in instrs:
            path = known.get(ins.name)
            if path is None:
                path = (_nearest(ins.name, lambda n: users.get(n, ()), known)
                        or _nearest(ins.name, lambda n: operands.get(n, ()),
                                    known) or (OUTSIDE,))
            out[ins.name] = HloOp(path, stages.get(ins.name, frozenset()),
                                  ins.name not in known)
    return out


def recorded_ops(paths: dict) -> Dict[str, HloOp]:
    """``hlo_ops``' output back from a fixture's ``{name: [path, stages,
    inferred]}``."""
    return {n: HloOp(tuple(p), frozenset(s), bool(i))
            for n, (p, s, i) in paths.items()}


def reduce(events: dict, ops: Dict[str, HloOp], top: int = 12) -> dict:
    """Own device time (s) and distinct op count under each scope path
    (``"select"``, ``"select/demote"``, ...) over the window of
    ``trace_reduce.reduce``; the total; the own time of fusions whose
    instructions span more than one stage (``cross_stage_s``) and of ops
    placed by data flow (``inferred_s``); the ``top`` ops by own time with
    their paths, and the ``top`` cross-stage fusions with their stages.
    ``ops`` is ``hlo_ops`` of the traced program; an op it does not name
    goes under ``OUTSIDE``."""
    spans = events["spans"]
    if not spans or not events["devices"]:
        return {}
    w0 = spans[0][1]
    w1 = max(s + d for _, s, d in spans)
    time: Dict[str, float] = {}
    count: Dict[str, int] = {}
    total = cross = inferred = 0.0
    unknown = HloOp((OUTSIDE,), frozenset(), False)
    each: List[Tuple[str, HloOp, float]] = []
    for dev_ops in events["devices"].values():
        inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in dev_ops if s + d > w0 and s < w1]
        for key, (_, t) in TR.own_times(inside).items():
            op = ops.get(key.lstrip("%"), unknown)
            t *= 1e-9
            for k in range(1, len(op.path) + 1):
                p = "/".join(op.path[:k])
                time[p] = time.get(p, 0.0) + t
                count[p] = count.get(p, 0) + 1
            total += t
            cross += t * (len(op.stages) > 1)
            inferred += t * op.inferred
            each.append((key.lstrip("%"), op, t))
    each.sort(key=lambda e: -e[2])
    return {"window_s": (w1 - w0) * 1e-9, "devices": len(events["devices"]),
            "own_s": time, "ops": count, "total_s": total,
            "cross_stage_s": cross, "inferred_s": inferred,
            "top_ops": [(n, "/".join(op.path), t)
                        for n, op, t in each[:top]],
            "top_cross": [(n, "+".join(sorted(op.stages)), t)
                          for n, op, t in each if len(op.stages) > 1][:top]}


def us_per_host_tick(ctx: dict, stage: str) -> Optional[float]:
    """Own device time under ``stage`` per host-tick traced, averaged over
    the devices; None where the trace shows no tick stage at all (a
    program without the scopes)."""
    st = ctx.get("stages")
    if not st or not ctx.get("host_ticks_traced") or \
            set(st["own_s"]) <= {OUTSIDE}:
        return None
    return (st["own_s"].get(stage, 0.0) / st["devices"]
            / ctx["host_ticks_traced"] * 1e6)


def table(st: dict, host_ticks: int) -> str:
    """The nested stage table: us per host-tick, share of own time, ops."""
    rows = [f"{'scope':<36} {'us/host-tick':>13} {'share':>7} {'ops':>7}"]
    per = 1e6 / max(host_ticks, 1) / st["devices"]
    for p in sorted(st["own_s"], key=lambda p: (
            -st["own_s"][p.split("/")[0]], p)):
        depth = p.count("/")
        label = "  " * depth + p.split("/")[-1]
        rows.append(f"{label:<36} {st['own_s'][p] * per:>13.1f} "
                    f"{100 * st['own_s'][p] / st['total_s']:>6.2f}% "
                    f"{st['ops'][p]:>7}")
    return "\n".join(rows)
