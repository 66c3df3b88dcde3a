"""Span-based query tracing with a Perfetto/Chrome-trace JSON exporter.

Role of the reference's SQL-tab timeline + task-event timeline (the
AppStatusListener-fed execution timeline the UI renders): every phase of
the query lifecycle (parse → analyze → optimize → plan → per-stage
per-partition execute → shuffle/exchange → collect) records a completed
span. Spans are plain host bookkeeping — two perf_counter reads and one
list append each — so tracing stays ON by default; async partition
pipelining is visible because `ExecContext.par_map` lanes record their
spans from their own threads (distinct `tid` tracks in the trace).

Three cross-cutting mechanisms ride every span:

  * query scope — a contextvar tag (`push_query`/`pop_query`) stamps
    each span with the query it belongs to at record time. Because
    contextvars follow the work into `par_map` lanes (copied Context per
    lane) and into cluster tasks (the tag ships with the task), two
    concurrent collects on one shared session get DISJOINT span sets —
    the buffer-offset mark()/since() slicing that assumed sequential
    queries is kept only as a compatibility surface.

  * flow graph — spans opened with `flow=True` allocate a process-unique
    flow id and parent themselves to the enclosing flow span via a
    second contextvar, which crosses thread (copied Context) and process
    (shipped span args) boundaries. The exporter turns every resolved
    parent→child pair into Perfetto flow arrows ("s"/"f" events), so the
    rendered timeline draws query → stage → partition-lane/worker arrows
    plus shuffle map-task → reduce-fetch edges.

  * cross-process ingest — `Tracer.ingest` merges spans recorded by a
    worker-process tracer into this one, rebasing perf_counter
    timestamps through paired (wall, perf) anchors and prefixing thread
    tracks with the worker's identity so worker spans render as their
    own named tracks.

  * the profiler's clock — every span also enters a
    `jax.profiler.TraceAnnotation("st:" + name)` carrying the query id and
    the span's scalar args as they are at entry. With no profiler session
    that is a flag test; with one, the engine's spans lie on `/host:CPU`
    beside the device's timeline, so an idle gap of the device can be
    read off against what the engine was doing.

  * why the device waits — three kinds of span say what the host does
    while the chip has nothing to run. Every blocking device→host read
    goes through `utils/device_memo.device_read` and is a span of
    category `sync` named by its site. `DEVICE` counts the
    programs `KernelCache` launches and the syncs that drain them: a
    sync that returns with nothing launched since it began opens a gap,
    and the next launch closes it as a `device.gap` span (category
    `gap`, args `after` the sync's site and `before` the launch's
    kind). A `gc.callbacks` hook makes every collection of the oldest
    generation, and any of 1 ms or more, a `py.gc` span (category `gc`).
    The account sees only what `KernelCache` launches: an eager `jnp`
    call outside it runs on a device the account thinks idle, and the
    launch's own dispatch and the device's wake-up are not in a gap.
    Each `st:` annotation carries its span's start on this clock as the
    arg `pc`, which lays the spans no annotation carries (the gaps) on
    the profiler's clock.

`recorded_spans(t_from, t_to)` reads the spans of every live tracer of
the process on the `time.perf_counter()` clock — what a benchmark's
readers and an in-process debug endpoint call.

Export is the Chrome trace-event format ("traceEvents" complete events,
microsecond timestamps), loadable in Perfetto (ui.perfetto.dev) or
chrome://tracing.
"""

from __future__ import annotations

import collections
import contextvars
import gc
import json
import threading
import time
import uuid
import weakref
from typing import Optional

__all__ = ["DEVICE", "DeviceAccount", "Tracer", "current_flow",
           "current_query", "pop_query", "push_query", "recorded_spans",
           "span_here", "to_chrome_trace"]

# prefix of the engine's spans in a profiler trace (`/host:CPU`)
ANNOTATION_PREFIX = "st:"
GAP_SPAN, GC_SPAN = "device.gap", "py.gc"
GAP_TRACK = "device"     # the gaps' own track: they cross threads' spans
GC_MIN_S = 1e-3     # a young collection shorter than this is not a span


# ---------------------------------------------------------------------------
# Query scope: which query's collect is executing on this thread/lane
# ---------------------------------------------------------------------------

# contextvars (not thread-locals) so scheduler.par_map's copied lane
# contexts and the cluster task payload both carry the tag — spans from
# concurrent queries on one session stay disjoint (ROADMAP follow-on)
_QUERY: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_query_scope", default=None)

# the innermost flow-enabled span: children opened under it (same thread,
# copied lane context, or shipped worker task) parent their flow arrow here
_FLOW: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_flow_scope", default=None)


# the tracer of the query in scope, for code that no ExecContext reaches
# (the process-global KernelCache): set beside the query tag, read by
# span_here
_TRACER: "contextvars.ContextVar" = contextvars.ContextVar(
    "spark_tpu_tracer_scope", default=None)


# the tracer of the last query scope entered with one: where a gap or a
# collection that no query's scope covers is recorded
_LAST_TRACER: list = [None]


def push_query(query_id: str, tracer: "Tracer | None" = None):
    """Enter a query scope (and, when given, its tracer's); returns the
    reset token for pop_query."""
    if tracer is not None:
        _LAST_TRACER[0] = weakref.ref(tracer)
    return (_QUERY.set(query_id),
            None if tracer is None else _TRACER.set(tracer))


def pop_query(token) -> None:
    qtoken, ttoken = token
    if ttoken is not None:
        _TRACER.reset(ttoken)
    _QUERY.reset(qtoken)


def current_query() -> str | None:
    return _QUERY.get()


def span_here(name: str, cat: str = "exec", args: Optional[dict] = None):
    """A span on the tracer of the query in scope; a no-op outside a
    query or with tracing off."""
    tracer = _TRACER.get()
    return _NULL_SPAN if tracer is None else tracer.span(name, cat, args)


def current_flow() -> str | None:
    """Flow id of the innermost flow span (for handing across an
    explicit boundary, e.g. into a cluster task payload)."""
    return _FLOW.get()


class _NullSpan:
    """Disabled-tracer span: context-manager no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_args(self, args) -> None:
        pass


_NULL_SPAN = _NullSpan()

_TraceAnnotation = None     # jax.profiler.TraceAnnotation, on first use


def _annotation(name: str, qid, args, pc: float):
    """The span as the profiler sees it: `st:<name>` with the query id,
    the scalar args and `pc`, the span's start on the perf_counter
    clock. Entered here, exited by the span."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    kw = {"pc": pc} if qid is None else {"query": qid, "pc": pc}
    if args:
        kw.update((k, v) for k, v in args.items()
                  if isinstance(v, (str, int, float)))
    ann = _TraceAnnotation(ANNOTATION_PREFIX + name, **kw)
    ann.__enter__()
    return ann


class _Span:
    __slots__ = ("tracer", "name", "cat", "args", "t0", "flow", "_ftoken",
                 "_qid", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args,
                 flow: bool = False):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = 0.0
        self.flow = flow
        self._ftoken = None
        self._qid = None
        self._ann = None

    def set_args(self, args) -> None:
        """Attach/merge args before exit (per-span kernel attribution)."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)

    def __enter__(self):
        if self.flow:
            # explicit flow_id (deterministic cross-process ids, e.g. a
            # shuffle's map-task span) wins over a fresh allocation
            fid = (self.args or {}).get("flow_id") \
                or self.tracer._next_flow_id()
            parent = (self.args or {}).get("flow_parent") or _FLOW.get()
            args = {"flow_id": fid}
            if parent is not None:
                args["flow_parent"] = parent
            self.set_args(args)
            self._ftoken = _FLOW.set(fid)
        self._qid = _QUERY.get()
        self.t0 = time.perf_counter()
        self._ann = _annotation(self.name, self._qid, self.args, self.t0)
        # live telemetry reads in-flight spans: register open, drop on
        # close (two dict ops per span — still pure host bookkeeping)
        self.tracer._open_add(self)
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(None, None, None)
        if self._ftoken is not None:
            _FLOW.reset(self._ftoken)
        self.tracer._open_remove(self)
        t = threading.current_thread()
        self.tracer._record(self.name, self.cat, self.t0, dur,
                            t.ident, t.name, self.args, _QUERY.get())
        return False


class Tracer:
    """Thread-safe accumulator of completed spans.

    `conf`-backed tracers re-read spark.tpu.trace.enabled per span() so a
    session can flip tracing without rebuilding the tracer (maxSpans is
    refreshed on the same read — span close never touches conf, so the
    hot _record path takes no lock but the tracer's own). The buffer is a
    RING of the latest maxSpans spans: a long-lived session (connect
    server, streaming, shell) keeps tracing its most recent queries
    instead of going permanently dark once a cap fills; evicted-oldest
    spans count in `dropped`, and mark()/since() use monotonic sequence
    numbers so slices stay correct across eviction.

    Per-QUERY spans come from the query-scope contextvar tag
    (`spans_for`); mark()/since() buffer slicing is kept for sequential
    callers but concurrent collects should read their own query tag.
    """

    def __init__(self, conf=None, enabled: bool = True,
                 max_spans: int = 100_000):
        import collections

        self._conf = conf
        self._enabled = enabled
        self._max_spans = max_spans
        # ring of (name, cat, t0, dur, tid, tname, args, query_id)
        self._spans: "collections.deque" = collections.deque()
        self._seq = 0              # total spans ever recorded
        self._lock = threading.Lock()
        self.dropped = 0
        # flow ids must stay unique across processes (worker spans are
        # ingested into the driver tracer verbatim)
        self._uid = uuid.uuid4().hex[:8]
        self._flow_n = 0
        # paired clocks for cross-process timestamp rebasing: a worker's
        # perf_counter domain maps into ours through the wall clock
        self.anchor = (time.time(), time.perf_counter())
        # spans currently inside __enter__/__exit__ (live telemetry view)
        self._open: dict[int, "_Span"] = {}
        with _TRACERS_LOCK:
            _TRACERS.add(self)
            if _gc_callback not in gc.callbacks:
                gc.callbacks.append(_gc_callback)

    @property
    def enabled(self) -> bool:
        if self._conf is not None:
            from ..config import TRACE_ENABLED, TRACE_MAX_SPANS

            on = bool(self._conf.get(TRACE_ENABLED))
            if on:  # piggyback the cap refresh on the same conf visit
                self._max_spans = int(self._conf.get(TRACE_MAX_SPANS))
            return on
        return self._enabled

    @property
    def max_spans(self) -> int:
        return self._max_spans

    def span(self, name: str, cat: str = "exec",
             args: Optional[dict] = None, flow: bool = False):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args, flow=flow)

    def _next_flow_id(self) -> str:
        with self._lock:
            self._flow_n += 1
            return f"{self._uid}:{self._flow_n}"

    def _open_add(self, span: "_Span") -> None:
        with self._lock:
            self._open[id(span)] = span

    def _open_remove(self, span: "_Span") -> None:
        with self._lock:
            self._open.pop(id(span), None)

    def open_spans(self) -> list[dict]:
        """Snapshot of spans currently in flight, as JSON-friendly dicts
        with elapsed-so-far (the live-telemetry 'what is this task doing
        RIGHT NOW' view). Pure host bookkeeping."""
        with self._lock:
            spans = list(self._open.values())
        now = time.perf_counter()
        out = []
        for s in spans:
            out.append({"name": s.name, "cat": s.cat,
                        "elapsed_ms": round((now - s.t0) * 1000, 3),
                        **({"query": s._qid} if s._qid is not None
                           else {})})
        return out

    def _record(self, name, cat, t0, dur, tid, tname, args,
                qid=None) -> None:
        with self._lock:
            self._spans.append((name, cat, t0, dur, tid, tname, args, qid))
            self._seq += 1
            while len(self._spans) > self._max_spans:
                self._spans.popleft()  # ring: evict oldest, keep tracing
                self.dropped += 1

    def ingest(self, spans: list, anchor: tuple | None = None,
               track: str | None = None, query_id: str | None = None) -> int:
        """Merge spans recorded by ANOTHER process's tracer (a cluster
        worker) into this buffer: timestamps rebase through the paired
        (wall, perf) anchors, thread tracks get `track/` prefixed so
        worker spans render as their own named tracks, and every span is
        re-tagged to `query_id` (the driver's query scope — the worker's
        own tag is task-local). Pure host bookkeeping."""
        if not spans:
            return 0
        off = 0.0
        if anchor is not None:
            # worker wall time of a span = w_wall + (t0 - w_perf); map it
            # into our perf domain: t0' = t0 + (w_wall - w_perf) -
            # (our_wall - our_perf)
            off = (anchor[0] - anchor[1]) - (self.anchor[0] - self.anchor[1])
        n = 0
        with self._lock:
            for s in spans:
                name, cat, t0, dur, ident, tname, args = s[:7]
                qid = s[7] if len(s) > 7 else None
                self._spans.append((
                    name, cat, t0 + off, dur, ident,
                    f"{track}/{tname}" if track else tname, args,
                    query_id if query_id is not None else qid))
                self._seq += 1
                while len(self._spans) > self._max_spans:
                    self._spans.popleft()
                    self.dropped += 1
                n += 1
        return n

    # -- reading ----------------------------------------------------------
    def mark(self) -> int:
        """Monotonic sequence number — pass to since() to slice one
        query's spans out of a session-lived tracer (valid across ring
        eviction). Assumes sequential queries; concurrent collects should
        use spans_for(query_id)."""
        with self._lock:
            return self._seq

    @staticmethod
    def _span_dict(s) -> dict:
        name, cat, t0, dur, _tid, tname, args = s[:7]
        qid = s[7] if len(s) > 7 else None
        return {"name": name, "cat": cat, "ts": round(t0, 6),
                "dur_ms": round(dur * 1000, 3), "thread": tname,
                **({"args": args} if args else {}),
                **({"query": qid} if qid is not None else {})}

    def since(self, mark: int) -> list[dict]:
        """Spans recorded after mark(), as JSON-friendly dicts (spans the
        ring already evicted are gone — only the tail can be lost)."""
        _drain_gc()
        with self._lock:
            first = self._seq - len(self._spans)  # seq of oldest buffered
            spans = list(self._spans)[max(0, mark - first):]
        return [self._span_dict(s) for s in spans]

    def spans_for(self, query_id: str) -> list[dict]:
        """All buffered spans tagged with one query scope, as
        JSON-friendly dicts — the concurrency-safe per-query slice.
        The lock covers only the ring snapshot (same profile as
        since()); the tag filter runs outside it so a full 100k-span
        ring never stalls concurrent span recording."""
        _drain_gc()
        with self._lock:
            spans = list(self._spans)
        return [self._span_dict(s) for s in spans
                if len(s) > 7 and s[7] == query_id]

    def spans(self) -> list:
        _drain_gc()
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    # -- export -----------------------------------------------------------
    def to_chrome_trace(self, process_name: str = "spark_tpu") -> dict:
        return to_chrome_trace(self.spans(), process_name=process_name)

    def write_chrome_trace(self, path: str,
                           process_name: str = "spark_tpu") -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(process_name), f)
        return path


# every live tracer of the process, for recorded_spans
_TRACERS: "weakref.WeakSet" = weakref.WeakSet()
_TRACERS_LOCK = threading.Lock()


def recorded_spans(t_from: float = float("-inf"),
                   t_to: float = float("inf")) -> list[dict]:
    """The spans of every live tracer of this process that started in
    [t_from, t_to) on the `time.perf_counter()` clock, oldest first, as
    the dicts `Tracer.since` gives (`ts` in seconds, `dur_ms`, `args`,
    `query`). Spans a ring has evicted are gone."""
    with _TRACERS_LOCK:
        tracers = list(_TRACERS)
    spans = [s for t in tracers for s in t.spans() if t_from <= s[2] < t_to]
    gap = DEVICE.open_gap()
    if gap is not None and t_from <= gap[2] < t_to:
        spans.append(gap)
    spans.sort(key=lambda s: s[2])
    return [Tracer._span_dict(s) for s in spans]


# ---------------------------------------------------------------------------
# Why the device waits: the launch/sync account and the collector's pauses
# ---------------------------------------------------------------------------

def _recording_tracer(scoped):
    """`scoped` (the tracer of a query scope), else the last one a query
    scope was entered with; None where there is none or it is off."""
    t = scoped
    if t is None:
        ref = _LAST_TRACER[0]
        t = None if ref is None else ref()
    return t if t is not None and t.enabled else None


class DeviceAccount:
    """When the device has work, as the engine knows it.

    `launched` is the sequence number of the last program `KernelCache`
    launched, `by_query` the last one each query scope launched, and
    `drained` the highest launch a returned sync has waited for: the
    device runs programs in launch order, so a sync of a query whose last
    launch was s has, when it returns, drained every program launched up
    to s — and not another query's launched since (a tenant's read while
    the other tenant's program runs). When a sync returns with `drained
    == launched` a gap opens; the next launch, from any thread, closes
    it and records a `device.gap` span, on a track of its own, on the
    tracer in scope there, else the last one a query ran with. Cost: a
    lock and a clock read per sync, a lock per launch and a clock read
    per launch that closes a gap. Blind to device work outside
    `KernelCache` (eager `jnp` calls), to the launch's own dispatch and
    to the device's wake-up: they are idle here and busy in a device
    trace, or the other way round."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.lock = threading.Lock()
        self.launched = 0
        self.by_query: dict = {}     # query id -> its last launch
        self.drained = 0
        self.gap = None          # (start, the site of the sync) while open

    def launch(self, kind) -> None:
        qid = _QUERY.get()
        with self.lock:
            self.launched += 1
            self.by_query[qid] = self.launched
            if len(self.by_query) > _MAX_QUERIES:
                del self.by_query[next(iter(self.by_query))]
            gap, self.gap = self.gap, None
        if gap is not None:
            tracer = _recording_tracer(_TRACER.get())
            if tracer is not None:
                tracer._record(GAP_SPAN, "gap", gap[0],
                               self.clock() - gap[0], 0, GAP_TRACK,
                               {"after": gap[1], "before": str(kind)},
                               qid)

    def sync_begin(self) -> int:
        """The last launch of the query in scope: what the sync drains."""
        return self.by_query.get(_QUERY.get(), 0)

    def sync_end(self, begun: int, site: str) -> None:
        now = self.clock()
        with self.lock:
            if begun > self.drained:
                self.drained = begun
            if self.gap is None and self.drained == self.launched:
                self.gap = (now, site)

    def open_gap(self):
        """The gap open now, up to now, as a raw span of the last
        tracer a query ran with; None if none is open or tracing is
        off there."""
        gap = self.gap
        if gap is None or _recording_tracer(None) is None:
            return None
        return (GAP_SPAN, "gap", gap[0], self.clock() - gap[0], 0,
                GAP_TRACK, {"after": gap[1], "before": ""}, None)


_MAX_QUERIES = 1024     # query scopes the account remembers a launch of
DEVICE = DeviceAccount()

# collections waiting to become spans: the collector may run inside any
# lock a tracer or the conf holds, so its callback takes none and the
# next read of spans records them
_GC_PENDING: "collections.deque" = collections.deque(maxlen=4096)
# race-lint: ignore[worker-reinit] — the start of the collection now
# running in this process: each process times its own collector
_GC_START = [0.0]


def _gc_callback(phase: str, info: dict) -> None:
    if phase == "start":
        _GC_START[0] = time.perf_counter()
        return
    t0 = _GC_START[0]
    dur = time.perf_counter() - t0
    gen = info.get("generation", 0)
    if gen < 2 and dur < GC_MIN_S:
        return
    t = threading.current_thread()
    _GC_PENDING.append((t0, dur, t.ident, t.name,
                        {"generation": gen,
                         "collected": info.get("collected", 0)},
                        _TRACER.get(), _QUERY.get()))


def _drain_gc() -> None:
    while _GC_PENDING:
        try:
            t0, dur, ident, tname, args, scoped, qid = \
                _GC_PENDING.popleft()
        except IndexError:
            return
        tracer = _recording_tracer(scoped)
        if tracer is not None:
            tracer._record(GC_SPAN, "gc", t0, dur, ident, tname, args, qid)


def _flow_events(complete: list) -> list:
    """Perfetto flow arrows from span args: every span carrying a
    `flow_parent` that resolves to another span's `flow_id` emits one
    "s" (start, anchored inside the parent slice) + "f" (finish, binding
    to the enclosing child slice) pair with a fresh numeric id. Parents
    that did not make it into the trace (disabled worker tracer, ring
    eviction) emit nothing — the exporter never leaves a dangling arrow
    (tests/test_observability.py's `_flow_edges` checks exactly that)."""
    by_fid = {}
    for ev in complete:
        fid = (ev.get("args") or {}).get("flow_id")
        if fid is not None:
            by_fid[fid] = ev
    out = []
    edge = 0
    for ev in complete:
        parents = (ev.get("args") or {}).get("flow_parent")
        if parents is None:
            continue
        if not isinstance(parents, (list, tuple)):
            parents = [parents]
        for parent in parents:
            src = by_fid.get(parent)
            if src is None or src is ev:
                continue
            edge += 1
            out.append({"ph": "s", "id": edge, "pid": src["pid"],
                        "tid": src["tid"], "ts": src["ts"],
                        "name": "flow", "cat": "flow"})
            out.append({"ph": "f", "bp": "e", "id": edge, "pid": ev["pid"],
                        "tid": ev["tid"], "ts": ev["ts"],
                        "name": "flow", "cat": "flow"})
    return out


def to_chrome_trace(spans: list, process_name: str = "spark_tpu",
                    pid: int = 1) -> dict:
    """Raw tracer spans → Chrome trace-event JSON dict.

    Complete ("ph": "X") events with microsecond timestamps relative to
    the earliest span; one tid track per recording thread, labeled with
    the thread name via metadata events (par_map lanes show as their own
    pipelined tracks; ingested worker spans as `worker:<id>/...`
    tracks). Spans carrying flow_id/flow_parent args additionally emit
    Perfetto flow arrows ("s"/"f" events) linking query → stage →
    lane/worker spans and shuffle map → reduce-fetch edges across
    threads and processes."""
    events = [{"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
               "args": {"name": process_name}}]
    if not spans:
        return {"traceEvents": events, "displayTimeUnit": "ms"}
    tmin = min(s[2] for s in spans)
    # key tracks by (ident, name): lane threads are ephemeral and Python
    # reuses idents, so ident alone would merge distinct threads into one
    # mislabeled track
    tid_map: dict = {}
    complete = []
    for s in spans:
        name, cat, t0, dur, ident, tname, args = s[:7]
        qid = s[7] if len(s) > 7 else None
        tid = tid_map.get((ident, tname))
        if tid is None:
            tid = tid_map[(ident, tname)] = len(tid_map) + 1
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name", "args": {"name": tname}})
        ev = {"ph": "X", "pid": pid, "tid": tid, "name": name, "cat": cat,
              "ts": round((t0 - tmin) * 1e6, 3),
              "dur": round(dur * 1e6, 3)}
        if args or qid is not None:
            ev["args"] = dict(args or {})
            if qid is not None:
                ev["args"]["query"] = qid
        events.append(ev)
        complete.append(ev)
    events.extend(_flow_events(complete))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
