"""Query black box (spark_tpu/obs/blackbox.py + obs/diagnose.py).

Contract under test: anomaly findings (obs.slo at ticket release,
query.failed, admission rejection) trigger EXACTLY one self-contained
diagnostic bundle per query — manifest, Chrome trace, plan reports
rendered without re-execution, metrics scrape, profile with embedded
same-key history — under a flock-safe bounded retention ring; healthy
runs capture nothing and the armed-untriggered kernel-launch delta is
identical to off (fusion on or off); the postmortem renderer works from
the bundle directory alone; `/*+ POOL(x) */` statement hints route
through the fair scheduler with unknown pools a typed error; the live
store counts its 64-query ring evictions; and a 2-worker cluster's
bundle carries the pulled per-executor diagnostic rings.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.config import SQLConf
from spark_tpu.errors import PoolQueueFull, UnknownPoolError
from spark_tpu.obs import blackbox
from spark_tpu.obs import export as mx
from spark_tpu.obs.diagnose import render_index, render_postmortem
from spark_tpu.obs.live import LiveObs
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.serve import QueryService


@pytest.fixture(autouse=True)
def _restore_blackbox():
    """Every test leaves the process-global capture layer OFF with
    clean registries — the module-bool discipline other suites rely
    on."""
    yield
    blackbox.reset()
    mx.configure(SQLConf({}))
    mx.REGISTRY.reset()


def _session(name, tmp_path=None, extra=None):
    from spark_tpu import TpuSession

    conf = {"spark.sql.shuffle.partitions": 2,
            "spark.tpu.batch.capacity": 1 << 11,
            "spark.tpu.fusion.minRows": "0",
            "spark.tpu.cache.result.enabled": "false"}
    if tmp_path is not None:
        conf["spark.tpu.obs.bundles"] = "true"
        conf["spark.tpu.obs.bundleDir"] = str(tmp_path / "bundles")
    conf.update(extra or {})
    return TpuSession(name, conf)


def _seed(s, view="bb_t", n=2000, seed=5):
    rng = np.random.default_rng(seed)
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 12, n).astype(np.int64),
        "v": rng.integers(-30, 100, n).astype(np.int64),
    })).createOrReplaceTempView(view)


def _qid(df):
    return df.query_execution._last_ctx.query_id


# ---------------------------------------------------------------------------
# triggers: post-close SLO finding, failure, rejection, healthy sampling
# ---------------------------------------------------------------------------

class TestTriggers:
    def test_off_by_default(self, tmp_path):
        s = _session("bb-off")
        try:
            assert not blackbox.ENABLED
            _seed(s)
            s.sql("select k, sum(v) s from bb_t group by k").collect()
            assert blackbox.list_bundles(str(tmp_path)) == []
        finally:
            s.stop()

    def test_healthy_armed_run_captures_nothing(self, tmp_path):
        s = _session("bb-healthy", tmp_path)
        try:
            assert blackbox.ENABLED
            _seed(s)
            for _ in range(3):
                s.sql("select k, sum(v) s from bb_t group by k").collect()
            assert blackbox.list_bundles(
                str(tmp_path / "bundles")) == []
        finally:
            s.stop()

    def test_post_close_slo_finding_captures_once(self, tmp_path):
        """The obs.slo verdict lands on ticket release — AFTER execute()
        returned. The finding sink must still capture against the
        recently closed execution, and capture-once dedup must hold when
        the same query breaches again."""
        s = _session("bb-slo", tmp_path)
        try:
            _seed(s)
            df = s.sql("select k, sum(v) s from bb_t group by k")
            df.collect()
            qid = _qid(df)
            breach = {"severity": "warning", "kind": "obs.slo",
                      "msg": "e2e 120.0ms over pool slo 50.0ms"}
            s.live_obs.add_finding(qid, breach)
            entries = blackbox.list_bundles(str(tmp_path / "bundles"))
            assert len(entries) == 1
            assert entries[0]["trigger_kind"] == "obs.slo"
            assert entries[0]["query_id"] == qid
            # second breach of the SAME query: capture-once dedup
            s.live_obs.add_finding(qid, dict(breach))
            assert len(blackbox.list_bundles(
                str(tmp_path / "bundles"))) == 1
        finally:
            s.stop()

    def test_info_findings_never_trigger(self, tmp_path):
        s = _session("bb-info", tmp_path)
        try:
            _seed(s)
            df = s.sql("select k from bb_t limit 5")
            df.collect()
            s.live_obs.add_finding(_qid(df), {
                "severity": "info", "kind": "obs.slo", "msg": "ok"})
            s.live_obs.add_finding(_qid(df), {
                "severity": "warning", "kind": "obs.drift", "msg": "x"})
            assert blackbox.list_bundles(
                str(tmp_path / "bundles")) == []
        finally:
            s.stop()

    def test_query_failure_captures_bundle(self, tmp_path):
        """A mid-execution fault (chaos kernel.dispatch raise) must
        leave a query.failed bundle behind while the error still
        propagates to the caller."""
        s = _session("bb-fail", tmp_path, extra={
            "spark.tpu.faults.enabled": "true",
            "spark.tpu.faults.seed": "3",
            "spark.tpu.faults.points": "kernel.dispatch=always",
        })
        try:
            from spark_tpu.utils import faults

            faults.configure(s.conf)
            _seed(s)
            with pytest.raises(Exception):
                s.sql("select k, sum(v) s from bb_t group by k") \
                    .collect()
            entries = blackbox.list_bundles(str(tmp_path / "bundles"))
            assert len(entries) == 1
            assert entries[0]["trigger_kind"] == "query.failed"
            assert entries[0]["reason"] == "failure"
        finally:
            s.stop()
            from spark_tpu.utils import faults

            faults.reset()

    def test_rejection_capture_is_rate_limited(self, tmp_path):
        s = _session("bb-rej", tmp_path)
        try:
            err = PoolQueueFull("etl", 8)
            bid = blackbox.record_rejection(s, err, pool="etl")
            assert bid is not None
            entries = blackbox.list_bundles(str(tmp_path / "bundles"))
            assert len(entries) == 1
            assert entries[0]["trigger_kind"] == "serve.rejected"
            # a saturated pool rejecting a burst must not turn capture
            # into its own overload: within the gap, no second bundle
            assert blackbox.record_rejection(s, err, pool="etl") is None
            assert len(blackbox.list_bundles(
                str(tmp_path / "bundles"))) == 1
        finally:
            s.stop()

    def test_healthy_sampling_is_deterministic(self, tmp_path):
        s = _session("bb-sample", tmp_path, extra={
            "spark.tpu.obs.bundle.sampleHealthy": "2"})
        try:
            _seed(s)
            for i in range(4):
                s.sql(f"select k, sum(v) s from bb_t where v > {i} "
                      "group by k").collect()
            entries = blackbox.list_bundles(str(tmp_path / "bundles"))
            assert len(entries) == 2            # 1-in-2 of 4 queries
            assert all(e["reason"] == "sampled" for e in entries)
            assert all(e["trigger_kind"] is None for e in entries)
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# bundle contents: self-contained, renderable offline, bounded ring
# ---------------------------------------------------------------------------

class TestBundleContents:
    def test_manual_capture_is_complete_and_renders_offline(
            self, tmp_path):
        s = _session("bb-manual", tmp_path, extra={
            "spark.tpu.obs.profileDir": str(tmp_path / "profiles"),
            "spark.tpu.metrics.export": "true"})
        try:
            _seed(s)
            df = s.sql("select k, sum(v) s from bb_t group by k")
            df.collect()
            bid = s.capture_diagnostics(df)
            assert bid is not None
            bdir = str(tmp_path / "bundles")
            bundle = os.path.join(bdir, f"bundle-{bid}")
            for fname in ("bundle.json", "trace.json",
                          "explain_simple.txt", "explain_analysis.txt",
                          "explain_analyze.txt", "metrics.prom"):
                assert os.path.isfile(os.path.join(bundle, fname)), fname
            with open(os.path.join(bundle, "bundle.json")) as f:
                manifest = json.load(f)
            assert manifest["id"] == bid
            assert manifest["reason"] == "manual"
            assert manifest["query_id"] == _qid(df)
            assert manifest["plan"]["query_key"]
            assert manifest["profile"] is not None
            assert manifest["conf_overrides"].get(
                "spark.tpu.obs.bundles") == "true"
            # the analyze report came from RECORDED metrics — the
            # launch counter must not move while rendering reports
            # (asserted by the launch-identity test below); here the
            # report text itself must carry per-operator rows
            with open(os.path.join(bundle,
                                   "explain_analyze.txt")) as f:
                assert "rows" in f.read()
            # postmortem renders from the directory alone
            report = render_postmortem(bdir, bid)
            assert "Trigger timeline" in report
            assert "Counter drift vs same-key baseline" in report
            assert bid in render_index(bdir)
        finally:
            s.stop()

    def test_capture_without_dataframe_uses_most_recent(self, tmp_path):
        s = _session("bb-recent", tmp_path)
        try:
            _seed(s)
            df = s.sql("select k from bb_t limit 3")
            df.collect()
            bid = s.capture_diagnostics()
            manifest = blackbox.load_bundle(
                str(tmp_path / "bundles"), bid)
            assert manifest["query_id"] == _qid(df)
        finally:
            s.stop()

    def test_profile_history_embedded_for_drift(self, tmp_path):
        """Re-running the same query key embeds the PRIOR runs as the
        bundle's baseline history — diagnose's drift section must not
        need the profile store."""
        s = _session("bb-hist", tmp_path, extra={
            "spark.tpu.obs.profileDir": str(tmp_path / "profiles")})
        try:
            _seed(s)
            q = "select k, sum(v) s from bb_t group by k"
            for _ in range(3):
                df = s.sql(q)
                df.collect()
            bid = s.capture_diagnostics(df)
            manifest = blackbox.load_bundle(
                str(tmp_path / "bundles"), bid)
            hist = manifest["profile_history"]
            assert len(hist) >= 1
            assert all(p["query_key"] == manifest["plan"]["query_key"]
                       for p in hist)
            report = render_postmortem(str(tmp_path / "bundles"), bid)
            assert "baselines:" in report
        finally:
            s.stop()

    def test_retention_ring_prunes_oldest(self, tmp_path):
        s = _session("bb-ring", tmp_path, extra={
            "spark.tpu.obs.bundle.ring": "2"})
        try:
            _seed(s)
            df = s.sql("select k from bb_t limit 2")
            df.collect()
            bids = [s.capture_diagnostics(df) for _ in range(4)]
            bdir = str(tmp_path / "bundles")
            entries = blackbox.list_bundles(bdir)
            assert len(entries) <= 2
            assert entries[0]["id"] == bids[-1]    # newest survives
            dirs = [d for d in os.listdir(bdir)
                    if d.startswith("bundle-")]
            assert len(dirs) <= 2
            assert blackbox.load_bundle(bdir, bids[0]) is None
        finally:
            s.stop()

    def test_unknown_bundle_id_raises(self, tmp_path):
        (tmp_path / "bundles").mkdir()
        with pytest.raises(KeyError):
            render_postmortem(str(tmp_path / "bundles"), "nope")


# ---------------------------------------------------------------------------
# obs contract: armed-untriggered launch identity, fusion on and off
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    @pytest.mark.parametrize("fusion_min", ["0", "1000000000"])
    def test_launch_delta_identical_armed_vs_off(self, tmp_path,
                                                 fusion_min):
        s = _session("bb-zero", extra={
            "spark.tpu.fusion.minRows": fusion_min})
        try:
            _seed(s)
            q = "select k, sum(v) s from bb_t group by k"
            s.sql(q).collect()                    # compile warmup
            l0 = KC.launches
            s.sql(q).collect()
            delta_off = KC.launches - l0
            assert delta_off > 0
            s.conf.set("spark.tpu.obs.bundles", "true")
            s.conf.set("spark.tpu.obs.bundleDir",
                       str(tmp_path / "bundles"))
            blackbox.configure(s.conf)
            l0 = KC.launches
            s.sql(q).collect()
            assert KC.launches - l0 == delta_off
            assert blackbox.list_bundles(
                str(tmp_path / "bundles")) == []
        finally:
            s.stop()

    def test_lock_is_watched(self):
        import spark_tpu.exec.worker_main  # noqa: F401 — registers slot
        from spark_tpu.utils import lockwatch

        names = set(lockwatch.registered_names())
        assert "obs.blackbox._LOCK" in names
        assert "exec.worker_main._DIAG_LOCK" in names


# ---------------------------------------------------------------------------
# satellite: /*+ POOL(x) */ statement hints
# ---------------------------------------------------------------------------

class TestPoolHints:
    def test_hint_routes_statement_to_pool(self, tmp_path):
        s = _session("bb-pool", extra={
            "spark.tpu.scheduler.pools": "etl:2"})
        try:
            _seed(s)
            service = QueryService(s)
            t = service.execute_sql(
                s, "/*+ POOL(etl) */ select k, sum(v) s from bb_t "
                   "group by k")
            assert t.num_rows == 12
            pools = service.status()["pools"]
            assert pools["etl"]["admitted"] == 1
            assert pools["default"]["admitted"] == 0
        finally:
            s.stop()

    def test_unknown_pool_is_typed_error_naming_pools(self):
        s = _session("bb-pool-err", extra={
            "spark.tpu.scheduler.pools": "etl:2,adhoc"})
        try:
            _seed(s)
            with pytest.raises(UnknownPoolError) as ei:
                s.sql("/*+ POOL(etk) */ select k from bb_t limit 1")
            e = ei.value
            assert e.error_class == "UNKNOWN_POOL"
            assert e.pool == "etk"
            assert e.valid == ["adhoc", "default", "etl"]
            for name in ("adhoc", "default", "etl"):
                assert name in str(e)
        finally:
            s.stop()

    def test_hint_is_stripped_before_parse(self):
        s = _session("bb-pool-strip")
        try:
            _seed(s)
            df = s.sql("select /*+ pool(default) */ k, sum(v) s "
                       "from bb_t group by k")
            assert df._pool_hint == "default"
            assert df.toArrow().num_rows == 12
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# satellite: live-store ring eviction counting
# ---------------------------------------------------------------------------

class TestLiveEvictions:
    def test_ring_evictions_counted_and_surfaced(self):
        live = LiveObs()
        for i in range(70):
            live.add_finding(f"q{i:03d}", {
                "severity": "info", "kind": "obs.note", "msg": "x"})
        assert live.evictions == 70 - 64
        assert live.snapshot()["evictions"] == 6
        samples = mx._live_source(live)
        assert ("counter", "obs.live.evictions", (), 6) in samples

    def test_no_evictions_under_ring_capacity(self):
        live = LiveObs()
        for i in range(10):
            live.add_finding(f"q{i}", {
                "severity": "info", "kind": "obs.note", "msg": "x"})
        assert live.evictions == 0
        samples = mx._live_source(live)
        assert ("counter", "obs.live.evictions", (), 0) in samples


# ---------------------------------------------------------------------------
# cluster: pull-on-anomaly fleet state
# ---------------------------------------------------------------------------

class TestClusterPull:
    def test_bundle_pulls_worker_diagnostic_rings(self, tmp_path):
        """The bundle's fleet state comes from the workers'
        diagnostic_state RPC at capture time: bounded post-task rings
        with executor-labeled spans, never shipped on the healthy
        path."""
        s = _session("bb-cluster", tmp_path, extra={
            "spark.sql.adaptive.enabled": "false",
            "spark.tpu.cluster.enabled": "true",
            "spark.tpu.cluster.workers": "2"})
        try:
            _seed(s, n=4000)
            df = s.table("bb_t").repartition(2)
            assert df.toArrow().num_rows == 4000
            bid = s.capture_diagnostics(df)
            bdir = str(tmp_path / "bundles")
            manifest = blackbox.load_bundle(bdir, bid)
            workers = manifest["workers"]
            assert workers                      # every worker answered
            tasks = [t for w in workers.values()
                     for t in (w.get("tasks") or [])]
            assert tasks and any(t["spans"] for t in tasks)
            assert all("faults" in w and "lockwatch" in w
                       for w in workers.values())
            with open(os.path.join(bdir, f"bundle-{bid}",
                                   "trace.json")) as f:
                trace = json.load(f)
            procs = {e["args"]["name"]
                     for e in trace["traceEvents"]
                     if e.get("name") == "process_name"}
            assert any(str(p).startswith("executor ") for p in procs)
            # postmortem's executor map renders the pulled rings
            assert "pulled ring:" in render_postmortem(bdir, bid)
        finally:
            s.stop()

    def test_slo_breach_on_the_cluster_captures_exactly_one_bundle(
            self, tmp_path):
        """The whole chain, nothing simulated: every worker task sleeps
        past the pool's SLO (an injected fault; the rows stay right), the
        service's release raises obs.slo, and that one finding captures
        one bundle holding the workers' rings, their fault registries
        and the profile, which the postmortem renders by itself."""
        from spark_tpu.utils import faults

        s = _session("bb-breach", tmp_path, extra={
            "spark.sql.adaptive.enabled": "false",
            "spark.tpu.cluster.enabled": "true",
            "spark.tpu.cluster.workers": "2",
            "spark.tpu.obs.profileDir": str(tmp_path / "profiles"),
            "spark.tpu.metrics.export": "true",
            "spark.tpu.serve.sloMs": "50",
            "spark.tpu.faults.enabled": "true",
            "spark.tpu.faults.seed": "7",
            "spark.tpu.faults.points": "worker.task=always:sleep:0.2"})
        try:
            _seed(s, n=4000)
            svc = QueryService(s)
            table = svc.collect(s, s.table("bb_t").repartition(2))
            want = s.table("bb_t").toArrow()
            assert sorted(zip(*(c.to_pylist() for c in table.columns))) \
                == sorted(zip(*(c.to_pylist() for c in want.columns)))
            bdir = str(tmp_path / "bundles")
            entries = blackbox.list_bundles(bdir)
            assert [e["trigger_kind"] for e in entries] == ["obs.slo"], \
                entries
            bid = entries[0]["id"]
            manifest = blackbox.load_bundle(bdir, bid)
            workers = manifest["workers"]
            assert workers
            assert any(t.get("spans") for w in workers.values()
                       for t in (w.get("tasks") or []))
            assert any((w.get("faults") or {}).get("fired")
                       for w in workers.values()), \
                "no worker says its injected rule fired"
            assert manifest["profile"] is not None
            for fname in ("trace.json", "explain_analyze.txt",
                          "metrics.prom"):
                assert os.path.isfile(
                    os.path.join(bdir, f"bundle-{bid}", fname)), fname
            report = render_postmortem(bdir, bid)
            for marker in ("Trigger timeline", "obs.slo",
                           "Per-executor straggler / HBM map"):
                assert marker in report, marker
        finally:
            faults.reset()
            s.stop()
