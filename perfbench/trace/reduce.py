"""From a profiler trace (`.xplane.pb`) to device busy time, the device
operations that took most time, and the idle gaps by what the host was
doing. Read with `jax.profiler.ProfileData`, nothing else.

What the planes hold (looked at by hand on a v5e trace, PR 24): one plane
`/device:TPU:<n>` a chip, whose line `XLA Ops` has one event per executed
HLO operation and whose line `XLA Modules` has one per program run; one
plane `/host:CPU` with a line per host thread, where the benchmark's own
`TraceAnnotation` spans appear under the names it gave them (all start
with `pb:`). All planes share one clock, in nanoseconds.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "pb:"
WINDOW_SPAN = "pb:window"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Disjoint sorted intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def short(op: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `fusion.3`: the trace names an
    operation by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%")[:80]


def reduce_planes(planes: dict) -> dict:
    """`planes` is {plane name: {line name: [(event name, start_ns,
    duration_ns), ...]}} — the trace with the protobuf taken off, which
    is also the form of the recorded sample beside this file."""
    spans = []                      # the benchmark's own host spans
    for pname, lines in planes.items():
        if pname.startswith(DEVICE_PLANE):
            continue
        for events in lines.values():
            spans += [(n, s, s + d) for n, s, d in events
                      if n.startswith(SPAN_PREFIX)]
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    spans = [x for x in spans if x[0] != WINDOW_SPAN]

    devices = sorted(p for p in planes if p.startswith(DEVICE_PLANE))
    busy_ns, op_ns, module_ns, gaps = [], {}, {}, {}
    for pname in devices:
        ops = planes[pname].get(OPS_LINE, [])
        for n, _s, d in ops:
            op_ns[short(n)] = op_ns.get(short(n), 0) + d
        for n, _s, d in planes[pname].get(MODULES_LINE, []):
            module_ns[n] = module_ns.get(n, 0) + d
        busy = union(_clip([(s, s + d) for _n, s, d in ops], lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                doing = sorted({n for n, s, e in spans if s <= mid < e})
                name = "+".join(doing) if doing else "no_span"
                gaps[name] = gaps.get(name, 0) + (b - a)
    n_dev = max(1, len(devices))

    def top(d: dict) -> list:
        return [[k, v / 1e9 / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"devices": len(devices),
            "window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns) / 1e9 / n_dev if devices else 0.0,
            "device_ops": top(op_ns),
            "device_modules": top(module_ns),
            "idle_gaps": top(gaps)}


def load_planes(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    keep_host = lambda n: n.startswith(SPAN_PREFIX)     # noqa: E731
    planes = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events
                      if device or keep_host(e.name)]
            if events:      # threads may share a name: keep them all
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return planes


def reduce_file(xplane_path: str) -> dict:
    return reduce_planes(load_planes(xplane_path))
