"""Service metrics plane: one process-wide registry of typed metric
instruments with mergeable histograms and Prometheus-style export.

Role of the reference's MetricsSystem + sinks (core/metrics/
MetricsSystem.scala routing Codahale registries into the
PrometheusServlet / JmxSink / CSV sinks), re-shaped for a serving
engine whose operational signals already exist but are scattered:
KernelCache launch/compile/disk-hit counters, transport retry stats,
result/compile cache hits, DeviceLedger HBM occupancy, fair-pool
queue depths, straggler/regression finding counts. This module unifies
them under stable dotted names with ``{pool, session, executor}``
labels and exports them three ways:

  * **Prometheus text format** — ``render_prometheus()`` backs the
    history server's ``/metrics`` endpoint and the SQL endpoint's
    ``{"metrics": true}`` request. ``parse_prometheus()`` is the
    round-trip reader the tests scrape with.

  * **a bounded time-series ring** — a ticker thread samples the gauge
    surface every ``spark.tpu.metrics.tickInterval`` seconds into a
    fixed ring (``spark.tpu.metrics.ringSize``), feeding sparkline data
    into serve status and the drain-time snapshot.

  * **per-executor deltas on the heartbeat** — workers attach
    ``executor_payload()`` (cumulative counter snapshots: lost beats
    lose nothing, the next one carries the totals) to the existing obs
    heartbeat; the driver stores them per executor id and its scrape
    renders worker-labeled series — the same merge path a fleet broker
    aggregating N replicas will use (ROADMAP direction 2).

**Mergeable histograms.** Latency distributions use FIXED log-spaced
bucket bounds shared by every process (``BUCKET_BOUNDS``): merging two
histograms is element-wise bucket addition, so a two-process merge
reproduces the single-registry quantile buckets EXACTLY — the property
sample-ring percentiles fundamentally lack (you cannot merge two p99s).
``quantile()`` answers from the cumulative bucket counts and is
therefore identical before and after any merge of the same
observations.

Obs contract (same as the rest of obs/): everything here is pure host
bookkeeping — zero kernel launches, no device syncs — and the plane is
structurally zero-overhead when ``spark.tpu.metrics.export`` is off:
call sites gate on the module bool ``ENABLED`` (one attribute read, the
utils/faults.py discipline), the ticker thread never starts, heartbeats
carry no metrics field, and source collection only ever runs at scrape
time. Locked instruments follow the utils/counters.LockedCounter
discipline: mutation under an internal lock, the lock slot
lockwatch-registered, ``check_guard`` probes inside the critical
section.
"""

from __future__ import annotations

import re
import sys
import threading
import time
from bisect import bisect_left
from collections import deque

from ..utils import lockwatch

__all__ = [
    "BUCKET_BOUNDS", "ENABLED", "Histogram", "MetricsRegistry",
    "REGISTRY", "configure", "executor_payload", "parse_prometheus",
    "register_default_sources", "render_prometheus", "start_ticker",
    "stop_ticker", "timeseries_snapshot",
]

# fast-path flag (utils/faults.py discipline): instrumented call sites
# read ONE module attribute before doing anything — export off means no
# registry work, no ticker, no heartbeat field, structurally
ENABLED = False

# ---------------------------------------------------------------------------
# fixed log-spaced histogram buckets
# ---------------------------------------------------------------------------

# Bucket bounds are a PROCESS-INDEPENDENT constant: every histogram in
# every process uses these exact upper edges (ms), so cross-process
# merge is element-wise addition and quantiles are merge-invariant.
# 0.05ms * sqrt(2)^i for 44 buckets spans 0.05ms .. ~154s — sub-ms
# cache hits through multi-minute drains at ~41% bucket resolution.
_BUCKET_BASE_MS = 0.05
_BUCKET_RATIO = 2.0 ** 0.5
_NUM_BUCKETS = 44
BUCKET_BOUNDS: tuple = tuple(
    _BUCKET_BASE_MS * _BUCKET_RATIO ** i for i in range(_NUM_BUCKETS))


class Histogram:
    """Fixed log-bucket mergeable histogram (counts per BUCKET_BOUNDS
    upper edge plus one overflow bucket). Thread-safe behind its own
    per-instance lock (wrapped by lockwatch when watching is live at
    creation — the per-instance `maybe_wrap` path)."""

    __slots__ = ("_lock", "counts", "count", "sum", "min", "max")

    def __init__(self):
        self._lock = lockwatch.maybe_wrap("obs.export.Histogram._lock",
                                          threading.Lock())
        self.counts = [0] * (_NUM_BUCKETS + 1)
        self.count = 0
        self.sum = 0.0
        self.min = None
        self.max = None

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect_left(BUCKET_BOUNDS, v)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += v
            self.min = v if self.min is None else min(self.min, v)
            self.max = v if self.max is None else max(self.max, v)

    # -- merge (the cross-process leg) ------------------------------------
    def merge(self, other: "Histogram") -> "Histogram":
        """Fold `other` into self (element-wise bucket addition: exact,
        order-independent). Returns self for chaining. snapshot() takes
        other's lock; never both locks at once (no ordering to get
        wrong between two instances of the same class)."""
        return self.merge_snapshot(other.snapshot())

    def merge_snapshot(self, snap: dict) -> "Histogram":
        counts = snap.get("counts") or []
        if len(counts) != _NUM_BUCKETS + 1:
            raise ValueError(
                f"histogram merge: {len(counts)} buckets != "
                f"{_NUM_BUCKETS + 1} — bucket layouts must be identical")
        with self._lock:
            for i, c in enumerate(counts):
                self.counts[i] += int(c)
            self.count += int(snap.get("count", 0))
            self.sum += float(snap.get("sum", 0.0))
            for k, pick in (("min", min), ("max", max)):
                v = snap.get(k)
                if v is not None:
                    cur = getattr(self, k)
                    setattr(self, k,
                            v if cur is None else pick(cur, v))
        return self

    @classmethod
    def from_snapshot(cls, snap: dict) -> "Histogram":
        return cls().merge_snapshot(snap)

    # -- reads ------------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            return {"counts": list(self.counts), "count": self.count,
                    "sum": self.sum, "min": self.min, "max": self.max}

    def quantile(self, q: float):
        """Upper edge of the bucket holding the q-quantile (a bound, not
        an interpolation: merge-invariant by construction). Overflow
        observations answer with the observed max. None when empty."""
        lo, hi = self.quantile_bounds(q)
        return hi

    def quantile_bounds(self, q: float) -> tuple:
        """(lower, upper) edges of the q-quantile's bucket: the true
        sample quantile of the observed values is always inside."""
        with self._lock:
            if self.count == 0:
                return (None, None)
            target = max(1, int(q * self.count + 0.999999))
            cum = 0
            for i, c in enumerate(self.counts):
                cum += c
                if cum >= target:
                    lo = BUCKET_BOUNDS[i - 1] if i > 0 else 0.0
                    hi = (BUCKET_BOUNDS[i] if i < _NUM_BUCKETS
                          else self.max)
                    return (lo, hi)
            return (0.0, self.max)       # unreachable; guards drift

    def percentile_ms(self, q: float):
        """Display form: the quantile bucket's upper edge rounded for
        status payloads (the serve status p50/p95/p99 surface)."""
        v = self.quantile(q)
        return None if v is None else round(float(v), 3)


# ---------------------------------------------------------------------------
# registry of typed instruments
# ---------------------------------------------------------------------------

class _Counter:
    """A registry counter: mutation under the owning registry's lock
    (LockedCounter discipline — the registry lock is the registered,
    guard-checked slot shared by the instrument family)."""

    __slots__ = ("name", "labels", "_registry", "_value")

    def __init__(self, name: str, labels: tuple, registry):
        self.name = name
        self.labels = labels
        self._registry = registry
        self._value = 0

    def inc(self, n: int = 1) -> int:
        reg = self._registry
        with reg._lock:
            if lockwatch.ENABLED and reg._guard:
                lockwatch.check_guard(f"obs.export.counter.{self.name}",
                                      reg._guard)
            self._value += int(n)
            return self._value

    @property
    def value(self) -> int:
        with self._registry._lock:
            return self._value


class _Gauge:
    """Lazily-sampled gauge: holds a zero-argument callable evaluated
    only at collect/scrape/tick time — never on the query hot path."""

    __slots__ = ("name", "labels", "fn")

    def __init__(self, name: str, labels: tuple, fn):
        self.name = name
        self.labels = labels
        self.fn = fn

    def sample(self):
        try:
            v = self.fn()
        except Exception:
            return None
        return None if v is None else float(v)


class MetricsRegistry:
    """Process-wide table of typed instruments plus pluggable external
    sources (scrape-time pulls of counters that already live elsewhere:
    the KernelCache, RETRY_STATS, the device ledger, pool states...).

    `slot` names the lockwatch registration for the registry lock; only
    the module-global REGISTRY registers (secondary instances in tests
    stay unwatched — their mutations are still locked, just not
    guard-probed)."""

    def __init__(self, slot: str | None = None):
        self._lock = threading.Lock()
        self._guard = None
        if slot:
            lockwatch.register(slot, self, "_lock")
            self._guard = slot
        self._counters: dict = {}     # (name, labels) -> _Counter
        self._gauges: dict = {}       # (name, labels) -> _Gauge
        self._hists: dict = {}        # (name, labels) -> Histogram
        self._sources: dict = {}      # key -> fn() -> [sample, ...]

    # -- instrument access (get-or-create) --------------------------------
    @staticmethod
    def _label_key(labels: dict) -> tuple:
        return tuple(sorted((str(k), str(v)) for k, v in labels.items()))

    def counter(self, name: str, **labels) -> _Counter:
        key = (name, self._label_key(labels))
        with self._lock:
            c = self._counters.get(key)
            if c is None:
                c = self._counters[key] = _Counter(name, key[1], self)
            return c

    def gauge(self, name: str, fn, **labels) -> _Gauge:
        key = (name, self._label_key(labels))
        with self._lock:
            g = self._gauges.get(key)
            if g is None:
                g = self._gauges[key] = _Gauge(name, key[1], fn)
            else:
                g.fn = fn             # re-bind: newest provider wins
            return g

    def histogram(self, name: str, **labels) -> Histogram:
        key = (name, self._label_key(labels))
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = Histogram()
            return h

    def add_source(self, key: str, fn) -> None:
        """Register (idempotently, newest wins) a scrape-time pull:
        `fn()` returns [(kind, name, labels_tuple, value_or_snapshot)].
        Sources run ONLY at collect time — a source for a hot counter
        costs the hot path nothing."""
        with self._lock:
            self._sources[key] = fn

    def remove_source(self, key: str) -> None:
        with self._lock:
            self._sources.pop(key, None)

    # -- collection -------------------------------------------------------
    def collect(self) -> list:
        """Every sample the registry can produce right now:
        [(kind, name, labels_tuple, value)] with histogram values as
        snapshot dicts. Gauges and sources are evaluated HERE (lazy);
        a failing gauge/source is skipped, never raised."""
        with self._lock:
            counters = [(c.name, c.labels, c._value)
                        for c in self._counters.values()]
            gauges = list(self._gauges.values())
            hists = list(self._hists.items())
            sources = list(self._sources.values())
        out = [("counter", n, lbl, v) for n, lbl, v in counters]
        for g in gauges:
            v = g.sample()
            if v is not None:
                out.append(("gauge", g.name, g.labels, v))
        for (name, labels), h in hists:
            out.append(("histogram", name, labels, h.snapshot()))
        for fn in sources:
            try:
                out.extend(fn())
            except Exception:
                continue
        return out

    def render_prometheus(self) -> str:
        return _render(self.collect())

    def reset(self) -> None:
        """Per-test re-init (worker-reinit rule): drop instruments and
        sources; the registered lock slot stays."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._sources.clear()


REGISTRY = MetricsRegistry(slot="obs.export.MetricsRegistry._lock")


# ---------------------------------------------------------------------------
# Prometheus text exposition (render + round-trip parse)
# ---------------------------------------------------------------------------

_NAME_PREFIX = "spark_tpu_"


def _prom_name(name: str) -> str:
    return _NAME_PREFIX + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _escape(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"')


def _prom_labels(labels) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"' for k, v in labels)
    return "{" + inner + "}"


def _render(samples: list) -> str:
    """Prometheus text format v0.0.4: one TYPE header per metric name,
    histogram expansion into _bucket{le=...}/_sum/_count."""
    by_name: dict = {}
    for kind, name, labels, value in samples:
        by_name.setdefault((name, kind), []).append((labels, value))
    lines = []
    for (name, kind) in sorted(by_name):
        pname = _prom_name(name)
        lines.append(f"# TYPE {pname} {kind}")
        for labels, value in by_name[(name, kind)]:
            labels = tuple(labels or ())
            if kind == "histogram":
                snap = value
                cum = 0
                for i, c in enumerate(snap["counts"]):
                    cum += int(c)
                    le = ("+Inf" if i >= _NUM_BUCKETS
                          else repr(round(BUCKET_BOUNDS[i], 6)))
                    lines.append(
                        f"{pname}_bucket"
                        f"{_prom_labels(labels + (('le', le),))} {cum}")
                lines.append(f"{pname}_sum{_prom_labels(labels)} "
                             f"{snap['sum']!r}")
                lines.append(f"{pname}_count{_prom_labels(labels)} "
                             f"{int(snap['count'])}")
            else:
                v = int(value) if float(value).is_integer() else value
                lines.append(f"{pname}{_prom_labels(labels)} {v}")
    return "\n".join(lines) + "\n"


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> dict:
    """Round-trip reader for the text format: returns
    {"types": {name: kind}, "samples": {(name, labels_tuple): float}}.
    Histogram series come back as their expanded _bucket/_sum/_count
    sample names — exactly what a real scraper stores."""
    types: dict = {}
    samples: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            raise ValueError(f"unparseable exposition line: {line!r}")
        name, _, rawlabels, rawval = m.groups()
        labels = tuple(sorted(
            (k, v.replace('\\"', '"').replace("\\\\", "\\"))
            for k, v in _LABEL_RE.findall(rawlabels or "")))
        samples[(name, labels)] = float(rawval)
    return {"types": types, "samples": samples}


# ---------------------------------------------------------------------------
# configuration + module-level export surface
# ---------------------------------------------------------------------------

_TICK_INTERVAL_S = 5.0
_RING_SIZE = 120


def configure(conf) -> None:
    """Apply a session/worker conf to the process-global switches
    (spark.tpu.metrics.export / tickInterval / ringSize). Called by
    TpuSession.__init__ and the worker-side begin_stage_obs — the
    registry itself stays process-global like the KernelCache."""
    global ENABLED, _TICK_INTERVAL_S, _RING_SIZE

    from ..config import (
        METRICS_EXPORT, METRICS_RING_SIZE, METRICS_TICK_INTERVAL,
    )

    # conf values are host data — never touches a device
    ENABLED = bool(conf.get(METRICS_EXPORT))  # tpulint: ignore[host-sync]
    _TICK_INTERVAL_S = max(
        float(conf.get(METRICS_TICK_INTERVAL)), 0.05)
    _RING_SIZE = max(int(conf.get(METRICS_RING_SIZE)), 8)
    if not ENABLED:
        stop_ticker()


def render_prometheus() -> str:
    """The process scrape (history server /metrics, SQL endpoint
    {"metrics": true})."""
    return REGISTRY.render_prometheus()


def register_default_sources(session=None, scheduler=None) -> None:
    """Wire the scrape-time pulls over the counter families that
    already exist (idempotent; newest session/scheduler wins). Pure
    host reads — each pull is a locked snapshot of host counters."""
    REGISTRY.add_source("kernel_cache", _kernel_cache_source)
    REGISTRY.add_source("transport", _transport_source)
    REGISTRY.add_source("ledger", _ledger_source)
    if session is not None:
        name = getattr(session, "name", "") or "session"
        REGISTRY.add_source(
            "session", lambda s=session, n=name: _session_source(s, n))
        live = getattr(session, "live_obs", None)
        if live is not None:
            REGISTRY.add_source(
                "live", lambda lv=live: _live_source(lv))
            REGISTRY.add_source(
                "executors", lambda lv=live: _executor_source(lv))
    if scheduler is not None:
        REGISTRY.add_source(
            "pools", lambda sc=scheduler: sc.metrics_samples())


def _kernel_cache_source() -> list:
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    out = [
        ("counter", "kernel.launches", (), int(KC.launches)),
        ("counter", "kernel.cache_hits", (), int(KC.hits)),
        ("counter", "kernel.compiles", (), int(KC.misses)),
        ("counter", "kernel.compile_ms", (), float(KC.compile_ms)),
        ("counter", "kernel.disk_hit_compiles", (),
         int(KC.disk_hit_compiles)),
    ]
    for kind, n in sorted(dict(KC.launches_by_kind).items()):
        out.append(("counter", "kernel.launches_by_kind",
                    (("kind", kind),), int(n)))
    return out


def _transport_source() -> list:
    from ..net.transport import RETRY_STATS

    snap = RETRY_STATS.snapshot()
    return [("counter", "net.retry." + k, (), int(v))
            for k, v in sorted(snap.items())]


def _ledger_source() -> list:
    from .resources import GLOBAL_LEDGER

    snap = GLOBAL_LEDGER.snapshot()
    return [
        ("gauge", "hbm.bytes", (), float(snap["bytes"])),
        ("gauge", "hbm.peak_bytes", (), float(snap["peak"])),
        ("gauge", "hbm.arrays", (), float(snap["arrays"])),
    ]


def _session_source(session, name: str) -> list:
    """Session Metrics counters (result_cache.*, cache.*, compile.*)
    under a {session} label."""
    try:
        counters = session._metrics.snapshot()["counters"]
    except Exception:
        return []
    keep = ("result_cache.", "compile.", "cache.", "obs.")
    return [("counter", "session." + k, (("session", name),), int(v))
            for k, v in sorted(counters.items())
            if k.startswith(keep)]


def _live_source(live) -> list:
    """Straggler / regression / SLO finding counts from the live store
    plus its own health counters."""
    try:
        by_kind: dict = {}
        with live._lock:
            for q in live._queries.values():
                for f in q["findings"]:
                    k = f.get("kind", "?")
                    by_kind[k] = by_kind.get(k, 0) + 1
            late = live.late_dropped
            errs = live.telemetry_errors
            ev = getattr(live, "evictions", 0)
    except Exception:
        return []
    out = [("counter", "obs.findings", (("kind", k),), int(n))
           for k, n in sorted(by_kind.items())]
    out.append(("counter", "obs.heartbeat.late_dropped", (), int(late)))
    out.append(("counter", "obs.telemetry_errors", (), int(errs)))
    # finished-query ring evictions: a non-zero series is the signal
    # that serving load outran the 64-query live window and telemetry
    # (findings, progress) for the evicted queries is gone
    out.append(("counter", "obs.live.evictions", (), int(ev)))
    return out


def _executor_source(live) -> list:
    """Worker-labeled series from the per-executor registry payloads
    that rode the heartbeat (LiveObs.executors[eid]["metrics"]) — the
    driver scrape's merge of N worker processes."""
    out = []
    with live._lock:
        rows = [(eid, dict(e.get("metrics") or {}),
                 e.get("hbm_bytes"), e.get("hbm_peak"))
                for eid, e in sorted(live.executors.items())]
    for eid, metrics, hbm_bytes, hbm_peak in rows:
        lbl = (("executor", eid),)
        for name, v in sorted(metrics.items()):
            out.append(("counter", "executor." + name, lbl, v))
        if hbm_bytes is not None:
            out.append(("gauge", "executor.hbm.bytes", lbl,
                        float(hbm_bytes)))
        if hbm_peak is not None:
            out.append(("gauge", "executor.hbm.peak_bytes", lbl,
                        float(hbm_peak)))
    return out


def executor_payload() -> dict:
    """Cumulative counter snapshot a WORKER attaches to its heartbeat
    (exec/worker_main.heartbeat_loop). Snapshots, not increments: a
    lost beat loses nothing, the next one carries the totals — the
    at-least-once discipline the rest of the heartbeat already uses."""
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC
    from ..net.transport import RETRY_STATS

    out = {
        "kernel.launches": int(KC.launches),
        "kernel.compiles": int(KC.misses),
        "kernel.compile_ms": round(float(KC.compile_ms), 3),
        "kernel.disk_hit_compiles": int(KC.disk_hit_compiles),
    }
    for k, v in RETRY_STATS.snapshot().items():
        out["net.retry." + k] = int(v)
    try:
        from ..exec.worker_main import FLUSH_OVERFLOWS
        out["obs.flush_overflows"] = int(FLUSH_OVERFLOWS.value)
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# time-series ring + ticker thread
# ---------------------------------------------------------------------------

_TS_LOCK = threading.Lock()
lockwatch.register("obs.export._TS_LOCK", sys.modules[__name__],
                   "_TS_LOCK")
_TS_RING: deque = deque(maxlen=_RING_SIZE)
_TICKER = None


class _Ticker:
    """Interval sampler of the gauge/counter surface into the bounded
    ring. One daemon thread per process, started only when export is on
    (start_ticker) and joined on stop_ticker — the drain path."""

    def __init__(self, interval_s: float):
        self.interval_s = interval_s
        self._stop = threading.Event()
        # race-lint: ignore[bare-submit] — process-lifetime service
        # thread: samples host counters on a wall-clock interval and
        # must NOT pin any query's contextvar scope (a scoped thread
        # would charge its reads to whatever query started the ticker)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="metrics-ticker")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            tick_once()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)


def tick_once(now: float | None = None) -> None:
    """Sample the current scalar surface into the ring (the ticker's
    body; callable directly by tests and the drain snapshot)."""
    point: dict = {}
    for kind, name, labels, value in REGISTRY.collect():
        if kind == "histogram":
            # scalar view of a distribution: its count (rate via ring
            # deltas) — full buckets stay on the scrape surface
            point[_series_key(name + ".count", labels)] = \
                int(value["count"])
        else:
            point[_series_key(name, labels)] = value
    with _TS_LOCK:
        _TS_RING.append((time.time() if now is None else now, point))


def _series_key(name: str, labels) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labels)
    return f"{name}{{{inner}}}"


def start_ticker() -> None:
    """Start (or resize) the interval sampler. No-op when export is
    off — the off path never creates the thread."""
    global _TICKER, _TS_RING
    if not ENABLED:
        return
    with _TS_LOCK:
        if _TS_RING.maxlen != _RING_SIZE:
            _TS_RING = deque(_TS_RING, maxlen=_RING_SIZE)
    if _TICKER is None or not _TICKER._thread.is_alive():
        _TICKER = _Ticker(_TICK_INTERVAL_S)


def stop_ticker() -> None:
    global _TICKER
    t, _TICKER = _TICKER, None
    if t is not None:
        t.stop()


def timeseries_snapshot(series_prefix: str | None = None,
                        limit: int | None = None) -> dict:
    """The ring as {"interval_s", "series": {key: [[t, v], ...]}} —
    the drain-time snapshot and the sparkline feed for serve status."""
    with _TS_LOCK:
        points = list(_TS_RING)
    if limit:
        points = points[-int(limit):]
    series: dict = {}
    for t, point in points:
        for key, v in point.items():
            if series_prefix and not key.startswith(series_prefix):
                continue
            series.setdefault(key, []).append([round(t, 3), v])
    return {"interval_s": _TICK_INTERVAL_S, "series": series}


def sparklines(series_prefix: str = "serve.",
               limit: int = 32) -> dict:
    """Just the recent values per series (no timestamps) — the compact
    sparkline payload serve status embeds."""
    snap = timeseries_snapshot(series_prefix=series_prefix, limit=limit)
    return {k: [v for _t, v in pts]
            for k, pts in snap["series"].items()}
