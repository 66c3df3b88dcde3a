"""Service metrics plane (spark_tpu/obs/export.py + serve wiring).

Contract under test: fixed log-bucket histograms merge EXACTLY (a
two-process merge reproduces the single-registry quantile buckets),
the registry's typed instruments follow get-or-create/label-separation
semantics with lazily-evaluated gauges, the Prometheus text exposition
round-trips through its own parser, the plane is structurally
zero-overhead (identical kernel-launch deltas with export on and off,
fusion on or off), SLO burn accounting raises obs.slo findings that
reach pool status and the live store, and a 2-worker cluster's
heartbeat-shipped executor payloads render as executor-labeled series
in the driver scrape that reconcile with the stored payloads.
"""

import time

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.config import SQLConf
from spark_tpu.obs import export as mx
from spark_tpu.obs.export import BUCKET_BOUNDS, Histogram, MetricsRegistry
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.serve import FairScheduler, QueryService


@pytest.fixture(autouse=True)
def _restore_export():
    """Every test leaves the process-global plane OFF with a clean
    registry — the module-bool discipline other suites rely on."""
    yield
    mx.stop_ticker()
    mx.configure(SQLConf({}))          # export off, defaults restored
    mx.REGISTRY.reset()


def _session(name, extra=None):
    from spark_tpu import TpuSession

    conf = {"spark.sql.shuffle.partitions": 2,
            "spark.tpu.batch.capacity": 1 << 11,
            "spark.tpu.fusion.minRows": "0",
            "spark.tpu.cache.result.enabled": "false"}
    conf.update(extra or {})
    return TpuSession(name, conf)


def _seed(s, view="mx_t", n=4000, seed=23):
    rng = np.random.default_rng(seed)
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 12, n).astype(np.int64),
        "v": rng.integers(-30, 100, n).astype(np.int64),
    })).createOrReplaceTempView(view)


# ---------------------------------------------------------------------------
# histograms: buckets, quantile bounds, exact merge
# ---------------------------------------------------------------------------

class TestHistogram:
    def test_observe_counts_and_stats(self):
        h = Histogram()
        for v in (0.01, 0.5, 5.0, 50.0, 500.0):
            h.observe(v)
        assert h.count == 5
        assert h.sum == pytest.approx(555.51)
        assert h.min == 0.01 and h.max == 500.0
        assert sum(h.counts) == 5

    def test_quantile_bounds_contain_true_quantile(self):
        rng = np.random.default_rng(3)
        vals = rng.lognormal(mean=1.0, sigma=1.5, size=2000)
        h = Histogram()
        for v in vals:
            h.observe(float(v))
        for q in (0.5, 0.95, 0.99):
            lo, hi = h.quantile_bounds(q)
            true_q = float(np.quantile(vals, q))
            assert lo <= true_q <= hi, (q, lo, true_q, hi)

    def test_overflow_bucket_answers_with_observed_max(self):
        h = Histogram()
        h.observe(1e9)                    # far past the last bound
        assert h.counts[-1] == 1
        assert h.quantile(0.99) == 1e9

    def test_merge_is_exact_two_process_reproduction(self):
        """The acceptance identity: two 'processes' each observe half
        the samples; merging their histograms reproduces the single
        histogram's buckets — and therefore its quantiles — EXACTLY."""
        rng = np.random.default_rng(7)
        vals = rng.lognormal(mean=0.5, sigma=2.0, size=1001).tolist()
        single = Histogram()
        a, b = Histogram(), Histogram()
        for i, v in enumerate(vals):
            single.observe(v)
            (a if i % 2 else b).observe(v)
        # simulate the cross-process leg: b's SNAPSHOT (what a heartbeat
        # or scrape ships) folds into a
        merged = Histogram.from_snapshot(a.snapshot()) \
            .merge_snapshot(b.snapshot())
        assert merged.counts == single.counts
        assert merged.count == single.count
        assert merged.sum == pytest.approx(single.sum)
        for q in (0.5, 0.9, 0.99):
            assert merged.quantile_bounds(q) == single.quantile_bounds(q)

    def test_merge_quantiles_bound_pooled_samples(self):
        rng = np.random.default_rng(11)
        va = rng.exponential(5.0, 500)
        vb = rng.exponential(50.0, 500)
        a, b = Histogram(), Histogram()
        for v in va:
            a.observe(float(v))
        for v in vb:
            b.observe(float(v))
        a.merge(b)
        pooled = np.concatenate([va, vb])
        for q in (0.5, 0.95):
            lo, hi = a.quantile_bounds(q)
            assert lo <= float(np.quantile(pooled, q)) <= hi

    def test_merge_rejects_foreign_bucket_layout(self):
        with pytest.raises(ValueError):
            Histogram().merge_snapshot({"counts": [0] * 10, "count": 0,
                                        "sum": 0.0})

    def test_empty_quantile_is_none(self):
        assert Histogram().quantile(0.5) is None
        assert Histogram().percentile_ms(0.99) is None

    def test_bounds_are_shared_process_constants(self):
        assert len(BUCKET_BOUNDS) == 44
        assert BUCKET_BOUNDS[0] == pytest.approx(0.05)
        ratios = [BUCKET_BOUNDS[i + 1] / BUCKET_BOUNDS[i]
                  for i in range(len(BUCKET_BOUNDS) - 1)]
        assert all(r == pytest.approx(2.0 ** 0.5) for r in ratios)


# ---------------------------------------------------------------------------
# registry: typed instruments, labels, lazy gauges, sources
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_get_or_create_and_label_separation(self):
        reg = MetricsRegistry()
        c1 = reg.counter("q.count", pool="dash")
        c2 = reg.counter("q.count", pool="dash")
        c3 = reg.counter("q.count", pool="batch")
        assert c1 is c2 and c1 is not c3
        c1.inc()
        c1.inc(4)
        assert c1.value == 5 and c3.value == 0

    def test_gauge_is_lazy_and_rebinds(self):
        reg = MetricsRegistry()
        calls = {"n": 0}

        def probe():
            calls["n"] += 1
            return 7.0

        reg.gauge("hbm.now", probe)
        assert calls["n"] == 0              # never eagerly evaluated
        samples = reg.collect()
        assert calls["n"] == 1
        assert ("gauge", "hbm.now", (), 7.0) in samples
        reg.gauge("hbm.now", lambda: 9.0)   # newest provider wins
        assert ("gauge", "hbm.now", (), 9.0) in reg.collect()

    def test_failing_gauge_and_source_are_skipped(self):
        reg = MetricsRegistry()
        reg.gauge("bad", lambda: 1 / 0)
        reg.add_source("boom", lambda: 1 / 0)
        reg.counter("ok").inc()
        samples = reg.collect()
        assert ("counter", "ok", (), 1) in samples
        assert not any(name == "bad" for _k, name, _l, _v in samples)

    def test_histogram_instrument_and_reset(self):
        reg = MetricsRegistry()
        reg.histogram("lat", pool="a").observe(3.0)
        kinds = [k for k, *_ in reg.collect()]
        assert "histogram" in kinds
        reg.reset()
        assert reg.collect() == []


# ---------------------------------------------------------------------------
# Prometheus text exposition round-trip
# ---------------------------------------------------------------------------

class TestPrometheus:
    def test_render_parse_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("kernel.launches").inc(42)
        reg.gauge("hbm.bytes", lambda: 1024.0)
        h = reg.histogram("serve.pool.e2e_ms", pool="dash")
        for v in (0.2, 3.0, 700.0):
            h.observe(v)
        text = reg.render_prometheus()
        out = mx.parse_prometheus(text)
        assert out["types"]["spark_tpu_kernel_launches"] == "counter"
        assert out["types"]["spark_tpu_hbm_bytes"] == "gauge"
        assert out["types"]["spark_tpu_serve_pool_e2e_ms"] == "histogram"
        assert out["samples"][("spark_tpu_kernel_launches", ())] == 42
        assert out["samples"][("spark_tpu_hbm_bytes", ())] == 1024.0
        assert out["samples"][
            ("spark_tpu_serve_pool_e2e_ms_count",
             (("pool", "dash"),))] == 3
        assert out["samples"][
            ("spark_tpu_serve_pool_e2e_ms_sum",
             (("pool", "dash"),))] == pytest.approx(703.2)
        # bucket series are CUMULATIVE and end at the +Inf total
        buckets = {lbls: v for (n, lbls), v in out["samples"].items()
                   if n == "spark_tpu_serve_pool_e2e_ms_bucket"}
        inf = [v for lbls, v in buckets.items()
               if dict(lbls).get("le") == "+Inf"]
        assert inf == [3.0]
        assert all(v <= 3.0 for v in buckets.values())

    def test_label_escaping_round_trips(self):
        reg = MetricsRegistry()
        reg.counter("odd", session='a"b\\c').inc(2)
        out = mx.parse_prometheus(reg.render_prometheus())
        assert out["samples"][
            ("spark_tpu_odd", (("session", 'a"b\\c'),))] == 2

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            mx.parse_prometheus("this is { not exposition format")

    def test_histogram_merge_from_two_scrapes(self):
        """Quantiles computed from two scraped bucket vectors added
        element-wise equal the single-registry answer — the fleet
        aggregation path (ROADMAP direction 2)."""
        h1, h2, both = Histogram(), Histogram(), Histogram()
        rng = np.random.default_rng(5)
        for v in rng.exponential(10.0, 400):
            h1.observe(float(v))
            both.observe(float(v))
        for v in rng.exponential(100.0, 400):
            h2.observe(float(v))
            both.observe(float(v))
        merged = Histogram.from_snapshot(h1.snapshot()).merge(h2)
        assert merged.counts == both.counts


# ---------------------------------------------------------------------------
# configure / ticker / time series
# ---------------------------------------------------------------------------

class TestTickerAndRing:
    def test_configure_flips_module_bool(self):
        mx.configure(SQLConf({"spark.tpu.metrics.export": "true"}))
        assert mx.ENABLED
        mx.configure(SQLConf({}))
        assert not mx.ENABLED

    def test_off_never_starts_ticker(self):
        mx.configure(SQLConf({}))
        mx.start_ticker()
        assert mx._TICKER is None

    def test_tick_once_samples_into_ring(self):
        mx.configure(SQLConf({"spark.tpu.metrics.export": "true",
                              "spark.tpu.metrics.ringSize": "16"}))
        mx.REGISTRY.reset()
        c = mx.REGISTRY.counter("serve.requests")
        h = mx.REGISTRY.histogram("serve.pool.e2e_ms", pool="a")
        c.inc(3)
        h.observe(1.0)
        mx.tick_once(now=100.0)
        c.inc(2)
        h.observe(2.0)
        mx.tick_once(now=101.0)
        snap = mx.timeseries_snapshot()
        assert snap["series"]["serve.requests"] == [[100.0, 3],
                                                    [101.0, 5]]
        # histograms ride the ring as their scalar count
        assert snap["series"]["serve.pool.e2e_ms.count{pool=a}"] == \
            [[100.0, 1], [101.0, 2]]
        sparks = mx.sparklines(series_prefix="serve.")
        assert sparks["serve.requests"] == [3, 5]


# ---------------------------------------------------------------------------
# zero-overhead guard: launch deltas identical with export on/off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fusion", ["true", "false"])
def test_export_on_adds_zero_launches(fusion):
    s = _session(f"mx-overhead-{fusion}",
                 {"spark.tpu.fusion.enabled": fusion})
    try:
        _seed(s)
        q = "select k, sum(v) s from mx_t where v > 0 group by k"

        def warm_delta():
            s.sql(q).toArrow()
            before = dict(KC.launches_by_kind)
            s.sql(q).toArrow()
            return {k: v - before.get(k, 0)
                    for k, v in KC.launches_by_kind.items()
                    if v != before.get(k, 0)}

        off = warm_delta()
        assert off, "probe query launched nothing — vacuous comparison"
        s.conf.set("spark.tpu.metrics.export", "true")
        mx.configure(s.conf)
        mx.register_default_sources(session=s)
        mx.start_ticker()
        on = warm_delta()
        assert on == off, (
            f"metrics export changed kernel dispatches: {on} vs {off}")
        # and the scrape itself is device-free: same launch count after
        before = KC.launches
        mx.render_prometheus()
        assert KC.launches == before
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# SLO burn accounting
# ---------------------------------------------------------------------------

class TestSLO:
    def test_release_returns_burn_finding(self):
        conf = SQLConf({"spark.tpu.scheduler.pools": "a:1",
                        "spark.tpu.serve.pool.a.sloMs": "0.0001"})
        sched = FairScheduler(conf)
        t = sched.submit("a")
        sched.wait(t, timeout=1.0)
        sched.note_query(t, "q-slo-1")
        time.sleep(0.002)               # guarantee the breach
        finding = sched.release(t)
        assert finding is not None
        assert finding["kind"] == "obs.slo"
        assert finding["pool"] == "a"
        assert finding["query"] == "q-slo-1"
        assert finding["e2e_ms"] > finding["slo_ms"]
        assert finding["burn_rate"] == 1.0
        st = sched.status()["pools"]["a"]["slo"]
        assert st["breaches"] == 1 and st["ok"] == 0

    def test_within_slo_returns_none_and_counts_ok(self):
        conf = SQLConf({"spark.tpu.scheduler.pools": "a:1",
                        "spark.tpu.serve.pool.a.sloMs": "60000"})
        sched = FairScheduler(conf)
        t = sched.submit("a")
        sched.wait(t, timeout=1.0)
        assert sched.release(t) is None
        st = sched.status()["pools"]["a"]["slo"]
        assert st["ok"] == 1 and st["breaches"] == 0
        assert st["burn_rate"] == 0.0

    def test_no_slo_configured_no_accounting(self):
        sched = FairScheduler(SQLConf({}))
        t = sched.submit("default")
        sched.wait(t, timeout=1.0)
        assert sched.release(t) is None
        assert "slo" not in sched.status()["pools"]["default"]

    def test_slo_finding_reaches_live_store_end_to_end(self):
        s = _session("mx-slo", {
            "spark.tpu.scheduler.pools": "dash:1",
            "spark.tpu.serve.pool.dash.sloMs": "0.0001",
        })
        try:
            _seed(s)
            svc = QueryService(s)
            c = svc.open_session()
            c.conf.set("spark.tpu.scheduler.pool", "dash")
            svc.execute_sql(
                c, "select k, sum(v) s from mx_t group by k")
            st = svc.status()["pools"]["dash"]
            assert st["slo"]["breaches"] >= 1
            # the finding landed on the query's live record and the
            # pool status surfaces it through recent_findings
            finds = st.get("slo_findings") or []
            assert any(f.get("kind") == "obs.slo" for f in finds), st
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# serve wiring: pool histograms on the scrape, count identity
# ---------------------------------------------------------------------------

def test_pool_histograms_on_scrape_count_admitted_queries():
    s = _session("mx-serve", {
        "spark.tpu.scheduler.pools": "dash:2,batch:1",
        "spark.tpu.metrics.export": "true",
        "spark.tpu.metrics.tickInterval": "0.1",
    })
    try:
        _seed(s)
        svc = QueryService(s)
        c = svc.open_session()
        q = "select k, sum(v) s from mx_t group by k"
        for _ in range(3):
            svc.execute_sql(c, q)
        out = mx.parse_prometheus(mx.render_prometheus())
        e2e = sum(v for (n, _l), v in out["samples"].items()
                  if n == "spark_tpu_serve_pool_e2e_ms_count")
        assert int(e2e) == 3
        # drain freezes the ring into the status surface
        assert svc.drain(timeout=10.0)
        assert svc.drain_snapshot is not None
        status = svc.status()
        assert "drain_timeseries" in status
    finally:
        s.stop()


def test_concurrent_load_with_export_on_counts_every_admitted_query(
        tmp_path):
    """Under a concurrent load with the plane on: every admitted query
    is released through exactly one pool histogram, the default sources
    are on the scrape, the stored profiles still sum to the KernelCache's
    delta, and the drain froze a ring the ticker had sampled into."""
    from spark_tpu.obs.history import ProfileStore
    from spark_tpu.serve.loadgen import run_serve_load

    s = _session("mx-load", {
        "spark.tpu.obs.profileDir": str(tmp_path),
        "spark.tpu.scheduler.pools": "dash:2,batch:1",
        "spark.tpu.serve.maxConcurrent": 2,
        "spark.tpu.metrics.export": "true",
        "spark.tpu.metrics.tickInterval": "0.1",
    })
    try:
        _seed(s)
        queries = ["select k, sum(v) s from mx_t group by k",
                   "select k, v from mx_t where v > 0 order by v limit 16"]
        svc = QueryService(s)
        before = KC.launches
        warm = svc.open_session()
        for q in queries:
            svc.execute_sql(warm, q)
        report = run_serve_load(svc, queries, sessions=6, reps=2,
                                pools=("dash", "batch"))
        assert not report["errors"], report["errors"]
        out = mx.parse_prometheus(mx.render_prometheus())
        e2e = sum(v for (n, _l), v in out["samples"].items()
                  if n == "spark_tpu_serve_pool_e2e_ms_count")
        assert int(e2e) == len(queries) * (1 + 6 * 2)
        assert "spark_tpu_kernel_launches" in out["types"]
        store = ProfileStore(str(tmp_path))
        attributed = sum(int(p["launch_total"])
                         for qk in store.query_keys()
                         for p in store.profiles(qk))
        assert attributed == KC.launches - before
        assert svc.drain(timeout=10.0)
        assert (svc.drain_snapshot or {}).get("series"), \
            "the drain froze an empty ring: the ticker never sampled"
    finally:
        s.stop()


def test_executor_payload_shape():
    p = mx.executor_payload()
    assert "kernel.launches" in p and "kernel.compiles" in p
    assert all(isinstance(v, (int, float)) for v in p.values())
    assert any(k.startswith("net.retry.") for k in p)


# ---------------------------------------------------------------------------
# 2-worker cluster leg: executor-labeled series in the driver scrape
# ---------------------------------------------------------------------------

def test_cluster_executor_labeled_series_reconcile():
    s = _session("mx-cluster", {
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.heartbeat.interval": "0.2",
        "spark.tpu.metrics.export": "true",
    })
    try:
        _seed(s, n=4000)
        # a bare group-by over the 1-partition in-memory view collapses
        # into the (driver-local) result stage — a join forces shuffle
        # exchanges, i.e. remote map stages on the workers
        s.createDataFrame(pa.table({
            "k": np.arange(12).astype(np.int64),
            "name": [f"n{i}" for i in range(12)],
        })).createOrReplaceTempView("mx_dim")
        svc = QueryService(s)
        c = svc.open_session()
        q = ("select d.name, sum(t.v) s from mx_t t "
             "join mx_dim d on t.k = d.k group by d.name")
        svc.execute_sql(c, q)
        # workers attach their registry payload to the NEXT heartbeat
        # after begin_stage_obs configured export — poll with a deadline
        deadline = time.monotonic() + 20.0
        with_metrics = {}
        while time.monotonic() < deadline:
            with s.live_obs._lock:
                with_metrics = {
                    eid: dict(e["metrics"])
                    for eid, e in s.live_obs.executors.items()
                    if e.get("metrics")}
            if len(with_metrics) >= 2:
                break
            svc.execute_sql(c, q)       # keep both workers busy
            time.sleep(0.25)
        assert len(with_metrics) >= 2, (
            f"executor metrics payloads never arrived: "
            f"{list(with_metrics)}")
        out = mx.parse_prometheus(mx.render_prometheus())
        for eid, payload in with_metrics.items():
            key = ("spark_tpu_executor_kernel_launches",
                   (("executor", eid),))
            assert key in out["samples"], (eid, "missing from scrape")
            # the scrape renders exactly the payload the heartbeat
            # shipped (cumulative totals — driver and worker agree)
            assert out["samples"][key] >= \
                float(payload["kernel.launches"]) - 1e-9
        total_worker = sum(float(p["kernel.launches"])
                           for p in with_metrics.values())
        assert total_worker > 0, "workers reported zero launches"
        assert svc.drain(timeout=10.0)
    finally:
        s.stop()
