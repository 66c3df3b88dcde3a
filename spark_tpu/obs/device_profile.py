"""`df.explain(mode="device")`: which operator of a query holds the chip.

One warm run, then one run under `jax.profiler.trace`. The trace names a
device event by its HLO instruction (`while.34`) and nothing else; the
operator an instruction belongs to is in the compiled program's text
(`op_name="jit(whole_query_<hash>)/m03.HashJoin/probe/while"`, written by
the `jax.named_scope`s of physical/whole_query.py and ops/). So the
profile is a join of the two, which only the program can make: every
instant of a program's run goes to the innermost event covering it, the
event to the innermost `mNN.Kind` component of its op_name, and `mNN` is
row NN of the program's `members`. Runs nowhere on the hot path.

The report ends with the device's gaps in the traced run as the engine
saw them (`device.gap` spans, obs/tracing.DEVICE): each instant of a gap
goes to the innermost engine span covering it, summed by span name,
"outside the engine" where none does. Beside it, the trace's own idle
over the same run, laid on the engine's clock by the `pc` arg of the
`st:` annotations.

The stage tier has no such program: a query is hundreds of launches of
small kernels (`jit_<kind>_<hash>`: physical/compile.stage_jit), each
shared by every operator whose structure gives its key, so the operator
is not in a kernel's text. It is in the launch: `capture_programs` notes
the executing operator of every KernelCache launch, and the n-th run of a
module in the trace is its n-th launch (`attribute_launches`). An operator
row there is `mNN.Kind` with NN the operator's `_metric_id`, its place in
the physical plan from the root down.

On a TPU the events are the lines `XLA Ops` and `XLA Modules` of
`/device:TPU:<n>`; the CPU backend has no device plane and names its HLO
events by stats on host threads, which `_cpu_planes` brings to the same
form, so that the path is tested without a chip. A time read there is a
host thread's, and the report says so.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import tempfile
import time
from typing import Sequence

__all__ = ["explain", "scope_map", "operator_of", "attribute",
           "attribute_launches", "gap_table", "render", "render_launches"]

DEVICE_PLANE = "/device:TPU:"
CPU_PLANE = "/host:XLA-CPU"      # what _cpu_planes calls its one plane
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
UNATTRIBUTED = "unattributed"
OUTSIDE = "outside the engine"
TOP_INSTRUCTIONS = 10

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*\bop_name=\"([^\"]*)\"")
_ROW = re.compile(r"^m(\d+)\.\w+$")
# components of an op_name that jax writes itself, between a scope of
# ours and the primitive at the end
_JAX_WORDS = frozenset(("while", "body", "cond", "scan", "switch",
                        "closed_call", "checkpoint"))


def scope_map(hlo_text: str) -> dict:
    """instruction name -> op_name, from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def operator_of(op_name: str | None) -> tuple:
    """(`mNN.Kind` or None, phase or None): the innermost operator scope
    of an op_name and the scope right under it (`sort`, `probe`, ...)."""
    parts = (op_name or "").split("/")
    rows = [i for i, p in enumerate(parts) if _ROW.match(p)]
    if not rows:
        return None, None
    i = rows[-1]
    under = parts[i + 1:-1]        # the last component is the primitive
    phase = under[0] if under and not under[0].startswith("jit(") \
        and under[0] not in _JAX_WORDS else None
    return parts[i], phase


def _instruction(event_name: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(events: list) -> dict:
    """{key: ns} with every instant given to the innermost event that
    covers it, so nested events (a `while` and its body) are not counted
    twice. `events` are (start, end, key); an event whose key is None
    takes the key of the event it lies in."""
    out: dict = {}
    stack: list = []               # [end, key], innermost last
    t = 0

    def close(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, key = stack.pop()
            out[key] = out.get(key, 0) + max(0, end - t)
            t = max(t, end)

    for start, end, key in sorted(events, key=lambda e: (e[0], -e[1])):
        close(start)
        if stack:
            out[stack[-1][1]] = out.get(stack[-1][1], 0) \
                + max(0, start - t)
            if key is None:
                key = stack[-1][1]
        t = max(t, start) if stack else start
        stack.append([end, key])
    close(float("inf"))
    return out


def _device_lines(planes: dict) -> dict:
    """The lines of the first device plane, or of the CPU's stand-in."""
    names = sorted(p for p in planes
                   if p.startswith(DEVICE_PLANE) or p == CPU_PLANE)
    return planes[names[0]] if names else {}


def attribute(planes: dict, scopes: dict) -> list:
    """One record per run of a program named in `scopes`
    ({module name: {instruction: op_name}}), in time order, from the
    first device plane of `planes` ({plane: {line: [(name, start_ns,
    duration_ns)]}}, the form of perfbench/trace/sample_planes.json):
    {program, start_ns, device_ns, groups: {(label, phase): ns},
    instructions: [(name, inclusive ns, label, phase)]}. The groups and
    `unattributed` (events no operator scope names, and the instants of
    the run in which no event ran) add up to device_ns."""
    lines = _device_lines(planes)
    ops = sorted((s, s + d, _instruction(n))
                 for n, s, d in lines.get(OPS_LINE, []))
    runs = []
    for name, start, dur in sorted(lines.get(MODULES_LINE, []),
                                   key=lambda e: e[1]):
        program = name.split("(", 1)[0]
        if program not in scopes:
            continue
        where = {i: operator_of(o) for i, o in scopes[program].items()}
        inside = [(s, e, i) for s, e, i in ops if start <= s < start + dur]
        times = _self_times(
            [(s, e, where[i] if where.get(i, (None,))[0] else None)
             for s, e, i in inside])
        groups = {k: v for k, v in times.items() if k is not None and v}
        groups[(UNATTRIBUTED, None)] = dur - sum(groups.values())
        inclusive: dict = {}
        for s, e, i in inside:
            inclusive[i] = inclusive.get(i, 0) + (e - s)
        top = sorted(inclusive.items(), key=lambda kv: -kv[1])
        runs.append({"program": program, "start_ns": start,
                     "device_ns": dur, "groups": groups,
                     "instructions": [
                         (i, ns) + where.get(i, (None, None))
                         for i, ns in top[:TOP_INSTRUCTIONS]]})
    return runs


def attribute_launches(planes: dict, launches: list, skip=()) -> dict:
    """The device time of the kernels a query launched one at a time, by
    the operator that launched each. `launches` are `capture_programs`'
    (module name, kind, (row, operator name) or None) in launch order;
    a module's runs on the first device plane are taken in time order
    against its launches. Modules in `skip` (the whole-query programs,
    which `attribute` reads) are left out. Returns {"rows": {(row,
    operator name): {kind: [ns, runs]}}, "unnamed": {module: [ns,
    runs]} for the runs no launch answers for (eager jax.numpy
    operations between kernels, or more runs than launches),
    "device_ns": the sum of both}."""
    queues: dict = {}
    for program, kind, op in launches:
        if program is not None and program not in skip:
            queues.setdefault(program, collections.deque()).append(
                (kind, op))
    rows: dict = {}
    unnamed: dict = {}
    total = 0
    for name, _start, dur in sorted(
            _device_lines(planes).get(MODULES_LINE, []),
            key=lambda e: e[1]):
        program = name.split("(", 1)[0]
        if program in skip:
            continue
        total += dur
        queue = queues.get(program)
        if not queue:
            cell = unnamed.setdefault(program, [0, 0])
        else:
            kind, op = queue.popleft()
            cell = rows.setdefault(op or (None, UNATTRIBUTED), {}) \
                .setdefault(kind, [0, 0])
        cell[0] += dur
        cell[1] += 1
    return {"rows": rows, "unnamed": unnamed, "device_ns": total}


def render_launches(found: dict, plan_rows: dict) -> list:
    """The stage tier's part of the report, as lines. `plan_rows` is
    {row: (label, text)} of the traced plan's operators."""
    total = found["device_ns"] or 1
    named = sum(ns for kinds in found["rows"].values()
                for ns, _n in kinds.values())
    runs = sum(n for kinds in found["rows"].values()
               for _ns, n in kinds.values())
    out = [f"stage tier: {runs} kernel launches, "
           f"{found['device_ns'] / 1e6:.3f} ms on the device, "
           f"{100.0 * named / total:.2f} % of it in kernels an operator "
           "launched"]

    def place(op):
        row, name = op
        return (row if isinstance(row, int) and row in plan_rows
                else float("inf"), str(name))

    for op in sorted(found["rows"], key=place):
        kinds = found["rows"][op]
        ns = sum(v[0] for v in kinds.values())
        label, text = plan_rows.get(op[0], (
            op[1].removesuffix("Exec") if op[1] else UNATTRIBUTED, ""))
        out.append(f"  {label:<24} {ns / 1e6:>12.3f} ms "
                   f"{100.0 * ns / total:>6.2f} %  {text}")
        for kind, (kns, n) in sorted(kinds.items(),
                                     key=lambda kv: -kv[1][0]):
            out.append(f"    {kind:<22} {kns / 1e6:>12.3f} ms "
                       f"{100.0 * kns / total:>6.2f} %  x{n}")
    rest = sum(v[0] for v in found["unnamed"].values())
    out.append(f"  {UNATTRIBUTED:<24} {rest / 1e6:>12.3f} ms "
               f"{100.0 * rest / total:>6.2f} %  modules no kernel launch "
               "answers for")
    for module, (ns, n) in sorted(found["unnamed"].items(),
                                  key=lambda kv: -kv[1][0])[:TOP_INSTRUCTIONS]:
        out.append(f"    {module:<38} {ns / 1e6:>12.3f} ms  x{n}")
    return out


def render(runs: list, programs: dict, attempts: list, head: str,
           stage_lines: Sequence = (), tail: Sequence = ()) -> str:
    """The report. `programs` is {module name: the capture_programs
    record}; `attempts` the traced query's `whole_query.attempt` spans
    in time order, one per run, which say which runs were discarded."""
    out = ["== Device Profile ==", head]
    flags = [a.get("args", {}).get("discarded") for a in attempts] \
        if len(attempts) == len(runs) else [None] * len(runs)
    for n, (run, discarded) in enumerate(zip(runs, flags)):
        total = run["device_ns"] or 1
        what = {True: "discarded: its verdict bumped a capacity",
                False: "final", None: "attempt not known"}[discarded]
        out.append(f"program {run['program']} (run {n}, {what}): "
                   f"{run['device_ns'] / 1e6:.3f} ms on the device")
        by_row: dict = {}
        for (label, phase), ns in run["groups"].items():
            by_row.setdefault(label, {})[phase] = ns
        rec = programs.get(run["program"], {})
        labels = [s for s in rec.get("scopes", []) if s] + [UNATTRIBUTED]
        members = {s: m.split("\n", 1)[0] for s, m in
                   zip(rec.get("scopes", []), rec.get("members", []))}
        for label in labels:
            phases = by_row.get(label, {})
            ns = sum(phases.values())
            out.append(f"  {label:<24} {ns / 1e6:>12.3f} ms "
                       f"{100.0 * ns / total:>6.2f} %  "
                       f"{members.get(label, '')}")
            for phase, pns in sorted(phases.items(), key=lambda kv: -kv[1]):
                if phase is not None:
                    out.append(f"    {phase:<22} {pns / 1e6:>12.3f} ms "
                               f"{100.0 * pns / total:>6.2f} %")
        out.append("  instructions by inclusive time (nested ones count "
                   "in their parents too):")
        for name, ns, label, phase in run["instructions"]:
            place = UNATTRIBUTED if label is None \
                else label + ("/" + phase if phase else "")
            out.append(f"    {name:<28} {ns / 1e6:>12.3f} ms  {place}")
    out.extend(stage_lines)
    if not runs and not stage_lines:
        out.append("the traced run launched no named program or kernel on "
                   "a device plane of the trace (answered from the result "
                   "cache)")
    out.extend(tail)
    return "\n".join(out)


def gap_table(spans: list, t0: float, t1: float) -> dict:
    """{span name: seconds} of the `device.gap` spans among `spans`
    (`recorded_spans` dicts), clipped to [t0, t1): each instant of a gap
    to the innermost (shortest) other span covering it, OUTSIDE where
    none does."""
    from .tracing import GAP_SPAN

    iv = [(s["name"], s["ts"], s["ts"] + s["dur_ms"] / 1000.0)
          for s in spans]
    out: dict = {}
    for name, a, b in iv:
        if name != GAP_SPAN:
            continue
        a, b = max(a, t0), min(b, t1)
        if b <= a:
            continue
        over = [(n, lo, hi) for n, lo, hi in iv
                if n != GAP_SPAN and lo < b and hi > a]
        cuts = sorted({a, b} | {x for _n, lo, hi in over
                                for x in (lo, hi) if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2
            under = [(hi - lo, n) for n, lo, hi in over if lo <= mid < hi]
            where = min(under)[1] if under else OUTSIDE
            out[where] = out.get(where, 0.0) + (y - x)
    return out


def _busy_ns(intervals: list, lo: int, hi: int) -> int:
    """Length of the union of (start, duration) intervals within
    [lo, hi)."""
    busy, end = 0, lo
    for start, dur in sorted(intervals):
        a, b = max(start, end), min(start + dur, hi)
        if b > a:
            busy += b - a
        end = max(end, min(start + dur, hi))
    return busy


def _render_gaps(table: dict, t0: float, t1: float,
                trace_idle_s: float | None) -> list:
    gap_s = sum(table.values())
    idle = "not in the trace" if trace_idle_s is None \
        else f"{trace_idle_s * 1000:.3f} ms"
    out = [f"device gaps the engine saw in the traced run: "
           f"{gap_s * 1000:.3f} ms of {(t1 - t0) * 1000:.3f} ms; the "
           f"trace's idle over the same run: {idle}"]
    for name, sec in sorted(table.items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:<32} {sec * 1000:>12.3f} ms "
                   f"{100.0 * sec / (gap_s or 1):>6.2f} %")
    return out


# ---------------------------------------------------------------------------
# from the profiler's file to planes
# ---------------------------------------------------------------------------

def _clock_offset_ns(xplane_path: str) -> float | None:
    """The profiler's clock less the engine's (perf_counter, in ns), from
    the `pc` arg the `st:` annotations carry: the median over them."""
    from jax.profiler import ProfileData

    from .tracing import ANNOTATION_PREFIX

    offs = []
    for plane in ProfileData.from_file(xplane_path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    pc = dict(e.stats).get("pc")
                    if pc is not None:
                        offs.append(e.start_ns - float(pc) * 1e9)
    offs.sort()
    return offs[len(offs) // 2] if offs else None


def _load_planes(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    found = list(ProfileData.from_file(xplane_path).planes)
    planes = {
        plane.name: {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
        for plane in found if plane.name.startswith(DEVICE_PLANE)}
    if planes:
        return planes
    cpu_events = []
    for plane in found:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_op" in stats and "hlo_module" in stats:
                    cpu_events.append((stats, e.start_ns, e.duration_ns))
    return {CPU_PLANE: _cpu_planes(cpu_events)} if cpu_events else {}


def _cpu_planes(events: list) -> dict:
    """The CPU backend's HLO events as the two lines of a device plane;
    a module's run is from its first event to its last."""
    ops, bounds = [], {}
    for stats, start, dur in events:
        ops.append((str(stats["hlo_op"]), start, dur))
        run = (str(stats["hlo_module"]), str(stats.get("run_id")))
        lo, hi = bounds.get(run, (start, start + dur))
        bounds[run] = (min(lo, start), max(hi, start + dur))
    return {OPS_LINE: ops,
            MODULES_LINE: [(module, lo, hi - lo)
                           for (module, _run), (lo, hi) in bounds.items()]}


def explain(qe) -> str:
    """Run `qe`'s query warm once and traced once, and render where its
    whole-query programs, and the kernels it launched one at a time on
    the stage tier, spent their device time, operator by operator.
    The programs' texts come from compiling their lowerings again, which
    the persistent compile cache serves where it is on."""
    import jax

    from ..exec.query_execution import QueryExecution
    from ..physical.compile import capture_programs
    from .tracing import recorded_spans

    def run():
        traced = QueryExecution(qe.session, qe.logical)
        traced.to_arrow()
        return traced

    run()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with tempfile.TemporaryDirectory(prefix="spark_tpu_devprof_") as tmp:
        with capture_programs() as captured:
            jax.profiler.start_trace(tmp, profiler_options=options)
            t0 = time.perf_counter()
            try:
                traced = run()
            finally:
                t1 = time.perf_counter()
                jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            tmp, "plugins", "profile", "*", "*.xplane.pb")))
        planes = _load_planes(found[-1]) if found else {}
        offset = _clock_offset_ns(found[-1]) if found else None
    programs = {rec["program"]: rec for rec in captured}
    scopes = {name: scope_map(rec["kernel"]._kernel.lower(*rec["args"])
                              .compile().as_text())
              for name, rec in programs.items()}
    attempts = [s for s in recorded_spans(t0, t1)
                if s["name"] == "whole_query.attempt"]
    dev = jax.devices()[0]
    head = (f"platform {dev.platform} ({dev.device_kind}), "
            f"{len(planes)} device plane(s) in the trace, the first one "
            f"read; traced run {t1 - t0:.3f} s on the host, after one "
            "warm run")
    if CPU_PLANE in planes:
        head += ("\nno device plane: these are the CPU backend's host "
                 "threads, not a device's times")
    found = attribute_launches(planes, captured.launches, skip=scopes)
    stage_lines = render_launches(found, _plan_rows(traced.physical)) \
        if found["rows"] else ()
    idle = None
    if offset is not None and planes:
        lo, hi = int(t0 * 1e9 + offset), int(t1 * 1e9 + offset)
        modules = next(iter(planes.values())).get(MODULES_LINE, ())
        idle = (hi - lo - _busy_ns([(st, d) for _m, st, d in modules],
                                   lo, hi)) / 1e9
    gaps = _render_gaps(
        gap_table(recorded_spans(float("-inf"), t1), t0, t1), t0, t1, idle)
    return render(attribute(planes, scopes), programs, attempts, head,
                  stage_lines, gaps)


def _plan_rows(physical) -> dict:
    """{row: (`mNN.Kind`, the operator's line)} of a physical plan, rows
    as `QueryExecution` numbers them (`_metric_id`)."""
    from .metrics import iter_metric_nodes

    rows = {}
    for n in iter_metric_nodes(physical):
        row = getattr(n, "_metric_id", None)
        if row is not None:
            kind = type(n).__name__.removesuffix("Exec")
            rows[row] = (f"m{row:02d}.{kind}",
                         n.simple_string().split("\n", 1)[0][:100])
    return rows
