"""Multi-tenant serving (spark_tpu/serve/ + connect/sql_endpoint.py).

Contract under test: weighted fair pools grant contended slots in
weight proportion (deterministically — stride scheduling over a
submit/release schedule), bounded queues reject on timeout/overflow,
HBM admission holds queries back against the aggregate in-flight
reservation, per-connection cloned sessions isolate SET/temp views
while sharing the engine, concurrent collects produce scope-exact
disjoint counter deltas (zero `overlapped` profiles, attributed totals
summing to the global KernelCache delta), drain finishes in-flight
work and rejects new work with typed errors, and the serving layer
present-but-idle adds zero kernel launches.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.config import SQLConf
from spark_tpu.errors import (
    AdmissionTimeout, PoolQueueFull, ServerDraining,
)
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.serve import FairScheduler, QueryService, pool_configs
from spark_tpu.serve.loadgen import run_serve_load


def _session(name, extra=None):
    from spark_tpu import TpuSession

    # capacity 1<<11 on purpose: kernels are cached per (structure,
    # signature, CAPACITY) process-globally, and test_profile_history
    # asserts cold-compile deltas on same-shaped queries at 1<<12 — a
    # shared capacity would let this file warm its kernels and break
    # that suite under reordering (pytest-xdist, --lf, subsets)
    conf = {"spark.sql.shuffle.partitions": 2,
            "spark.tpu.batch.capacity": 1 << 11,
            "spark.tpu.fusion.minRows": "0"}
    conf.update(extra or {})
    return TpuSession(name, conf)


def _seed(s, view="srv_t", n=4000, seed=9):
    rng = np.random.default_rng(seed)
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 12, n).astype(np.int64),
        "v": rng.integers(-30, 100, n).astype(np.int64),
    })).createOrReplaceTempView(view)


QA = "select k, sum(v) s from srv_t where v > 0 group by k"
QB = "select k, v from srv_t where v > 50 order by v limit 16"


# ---------------------------------------------------------------------------
# pools: config, fairness, rejection, HBM admission
# ---------------------------------------------------------------------------

class TestFairScheduler:
    def test_pool_config_parsing(self):
        conf = SQLConf({
            "spark.tpu.scheduler.pools": "dash:2, batch , etl:0.5",
            "spark.tpu.scheduler.pool.batch.weight": "3",
            "spark.tpu.scheduler.pool.batch.maxConcurrent": "1",
            "spark.tpu.scheduler.pool.batch.queueSize": "7",
            "spark.tpu.scheduler.pool.batch.queueTimeout": "0.25",
            "spark.tpu.scheduler.pool.batch.hbmBudget": "4096",
            "spark.tpu.serve.queueSize": "9",
        })
        pools = pool_configs(conf)
        assert set(pools) == {"default", "dash", "batch", "etl"}
        assert pools["dash"].weight == 2.0
        assert pools["etl"].weight == 0.5
        assert pools["default"].weight == 1.0
        # per-pool keys override the declaration and the global default
        b = pools["batch"]
        assert (b.weight, b.max_concurrent, b.queue_size,
                b.queue_timeout_s, b.hbm_budget) == (3.0, 1, 7, 0.25,
                                                     4096)
        assert pools["dash"].queue_size == 9     # global default applies

    def test_weighted_fair_share_is_deterministic(self):
        conf = SQLConf({"spark.tpu.scheduler.pools": "a:2,b:1",
                        "spark.tpu.serve.maxConcurrent": 1})
        sched = FairScheduler(conf)
        tickets = []
        for _ in range(9):
            tickets.append(sched.submit("a"))
            tickets.append(sched.submit("b"))
        for _ in range(len(tickets)):
            running = [t for t in tickets
                       if t.granted and not t.released]
            assert len(running) == 1, "maxConcurrent=1 violated"
            sched.release(running[0])
        assert all(t.released for t in tickets)
        grants = sched.contended_grants()
        # stride scheduling: while both queues are backlogged the 2:1
        # weights yield a 2:1 grant ratio, deterministically
        assert grants["a"] + grants["b"] >= 9
        assert abs(grants["a"] - 2 * grants["b"]) <= 2, grants
        assert sched.fairness_ratio() <= 1.25
        assert sched.balanced()

    def test_idle_pool_banks_no_credit(self):
        conf = SQLConf({"spark.tpu.scheduler.pools": "a:1,b:1",
                        "spark.tpu.serve.maxConcurrent": 1})
        sched = FairScheduler(conf)
        # pool a runs alone for a while
        for _ in range(6):
            t = sched.submit("a")
            sched.wait(t, timeout=1.0)
            sched.release(t)
        # b wakes: it must NOT get 6 catch-up grants in a row
        tickets = [sched.submit(p) for p in
                   ("a", "b", "a", "b", "a", "b")]
        order = []
        for _ in range(len(tickets)):
            running = [t for t in tickets
                       if t.granted and not t.released]
            assert len(running) == 1
            order.append(running[0].pool)
            sched.release(running[0])
        assert order.count("b") == 3
        assert "a" in order[:3], \
            f"idle pool b monopolized the contended window: {order}"

    def test_queue_timeout_rejection(self):
        conf = SQLConf({"spark.tpu.serve.maxConcurrent": 1})
        sched = FairScheduler(conf)
        holder = sched.submit("default")
        sched.wait(holder, timeout=1.0)
        blocked = sched.submit("default")
        t0 = time.perf_counter()
        with pytest.raises(AdmissionTimeout):
            sched.wait(blocked, timeout=0.05)
        assert time.perf_counter() - t0 < 2.0
        st = sched.status()["pools"]["default"]
        assert st["rejected_timeout"] == 1
        sched.release(holder)
        assert sched.balanced()

    def test_queue_full_rejection(self):
        conf = SQLConf({
            "spark.tpu.serve.maxConcurrent": 1,
            "spark.tpu.scheduler.pool.default.queueSize": "1",
        })
        sched = FairScheduler(conf)
        holder = sched.submit("default")
        sched.wait(holder, timeout=1.0)
        sched.submit("default")          # fills the single queue slot
        with pytest.raises(PoolQueueFull):
            sched.submit("default")
        assert sched.status()["pools"]["default"]["rejected_full"] == 1

    def test_hbm_admission_reserves_and_releases(self):
        conf = SQLConf({"spark.tpu.memory.budget": 100})
        sched = FairScheduler(conf)
        big = sched.submit("default", hbm=70)
        sched.wait(big, timeout=1.0)
        small = sched.submit("default", hbm=50)
        with pytest.raises(AdmissionTimeout):
            sched.wait(small, timeout=0.05)   # 70+50 > 100: must wait
        tiny = sched.submit("default", hbm=20)
        sched.wait(tiny, timeout=1.0)         # 70+20 <= 100: admitted
        sched.release(tiny)
        small = sched.submit("default", hbm=50)
        sched.release(big)
        sched.wait(small, timeout=1.0)        # freed budget admits it
        sched.release(small)
        assert sched.balanced()

    def test_per_pool_hbm_budget(self):
        conf = SQLConf({
            "spark.tpu.scheduler.pools": "tight",
            "spark.tpu.scheduler.pool.tight.hbmBudget": "64",
        })
        sched = FairScheduler(conf)
        a = sched.submit("tight", hbm=50)
        sched.wait(a, timeout=1.0)
        b = sched.submit("tight", hbm=30)
        with pytest.raises(AdmissionTimeout):
            sched.wait(b, timeout=0.05)
        # the default pool has no budget of its own — unaffected
        c = sched.submit("default", hbm=10_000)
        sched.wait(c, timeout=1.0)
        sched.release(a)
        sched.release(c)
        assert sched.in_flight() == 0


# ---------------------------------------------------------------------------
# session isolation
# ---------------------------------------------------------------------------

class TestSessionIsolation:
    def test_clone_isolates_set_and_temp_views(self):
        s = _session("srv-clone")
        try:
            _seed(s)
            c1 = s.newSession()
            c2 = s.newSession()
            # parent temp views read through to every clone
            assert c1.sql(QA).toArrow().num_rows > 0
            # SET is clone-local
            c1.sql("SET spark.sql.shuffle.partitions=5")
            assert int(c1.conf.get("spark.sql.shuffle.partitions")) == 5
            assert int(c2.conf.get("spark.sql.shuffle.partitions")) == 2
            assert int(s.conf.get("spark.sql.shuffle.partitions")) == 2
            # temp views are clone-local
            c1.sql("create temporary view c1v as select 1 a")
            assert c1.catalog.tableExists("c1v")
            assert not c2.catalog.tableExists("c1v")
            assert not s.catalog.tableExists("c1v")
            # clone stop() leaves the parent serviceable
            c1.stop()
            assert s.sql(QA).toArrow().num_rows > 0
        finally:
            s.stop()

    def test_clone_results_match_parent(self):
        s = _session("srv-clone-eq")
        try:
            _seed(s)
            want = s.sql(QA).toArrow().to_pylist()
            got = s.newSession().sql(QA).toArrow().to_pylist()
            assert sorted(got, key=str) == sorted(want, key=str)
        finally:
            s.stop()

    def test_shared_mode_optin(self):
        s = _session("srv-shared")
        try:
            svc = QueryService(s)
            assert svc.open_session("shared") is s
            assert svc.open_session() is not s
            s.conf.set("spark.tpu.serve.sessionMode", "shared")
            assert svc.open_session() is s
        finally:
            s.stop()

    def test_endpoint_connection_isolation(self):
        from spark_tpu.connect.sql_endpoint import SQLEndpoint, connect

        s = _session("srv-ep")
        try:
            _seed(s)
            ep = SQLEndpoint(s).start()
            try:
                with connect("127.0.0.1", ep.port) as a, \
                        connect("127.0.0.1", ep.port) as b:
                    ca, cb = a.cursor(), b.cursor()
                    # both connections see the server's temp view
                    ca.execute(QA)
                    assert ca.rowcount > 0
                    # SET on one connection is invisible on the other
                    ca.execute("SET spark.sql.shuffle.partitions=7")
                    cb.execute("SET spark.sql.shuffle.partitions")
                    assert cb.fetchall()[0][1] == "2"
                    # temp view on one connection is invisible too
                    ca.execute("create temporary view av "
                               "as select 41 x")
                    from spark_tpu.connect.sql_endpoint import Error

                    with pytest.raises(Error):
                        cb.execute("select * from av")
                    ca.execute("select * from av")
                    assert ca.fetchall() == [(41,)]
                    # per-pool status rides the wire
                    st = a.server_status()
                    assert "default" in st["pools"]
                    assert st["sessions_opened"] >= 2
            finally:
                ep.stop()
        finally:
            s.stop()

    def test_endpoint_shared_session_optin(self):
        from spark_tpu.connect.sql_endpoint import SQLEndpoint, connect

        s = _session("srv-ep-shared",
                     {"spark.tpu.serve.sessionMode": "shared"})
        try:
            _seed(s)
            ep = SQLEndpoint(s).start()
            try:
                with connect("127.0.0.1", ep.port) as a, \
                        connect("127.0.0.1", ep.port) as b:
                    ca, cb = a.cursor(), b.cursor()
                    ca.execute("SET spark.sql.shuffle.partitions=7")
                    cb.execute("SET spark.sql.shuffle.partitions")
                    # legacy shared-session server: SET visible across
                    assert cb.fetchall()[0][1] == "7"
            finally:
                ep.stop()
            s.conf.set("spark.sql.shuffle.partitions", 2)
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# concurrent counter isolation (the PR 12 carry-over, fixed)
# ---------------------------------------------------------------------------

class TestCounterIsolation:
    def test_concurrent_collects_attribute_disjoint_deltas(self,
                                                           tmp_path):
        s = _session("srv-conc",
                     {"spark.tpu.obs.profileDir": str(tmp_path)})
        try:
            _seed(s)
            # serial baselines (warm: compile + memo probes done)
            per_query = {}
            for q in (QA, QB):
                s.sql(q).toArrow()
                df = s.sql(q)
                df.toArrow()
                per_query[q] = dict(
                    df.query_execution._last_profile["launches_by_kind"])
            before = dict(KC.launches_by_kind)
            results = {}

            def run(q, rounds=3):
                out = []
                for _ in range(rounds):
                    df = s.sql(q)
                    df.toArrow()
                    out.append(df.query_execution._last_profile)
                results[q] = out

            threads = [threading.Thread(target=run, args=(q,))
                       for q in (QA, QB)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            delta = {k: v - before.get(k, 0)
                     for k, v in KC.launches_by_kind.items()
                     if v != before.get(k, 0)}
            merged: dict = {}
            for q, profs in results.items():
                for p in profs:
                    assert p is not None
                    assert not p.get("overlapped"), \
                        "scope-exact deltas must not need the guard"
                    # each racing profile reads exactly its own serial
                    # warm launch set — zero cross-contamination
                    assert p["launches_by_kind"] == per_query[q], \
                        (q, p["launches_by_kind"], per_query[q])
                    for k, v in p["launches_by_kind"].items():
                        merged[k] = merged.get(k, 0) + v
            # and the per-query deltas SUM to the global counter delta
            assert merged == delta
        finally:
            s.stop()

    def test_concurrent_load_zero_regressions(self, tmp_path):
        s = _session("srv-conc-reg",
                     {"spark.tpu.obs.profileDir": str(tmp_path)})
        try:
            _seed(s)
            s.sql(QA).toArrow()     # cold baseline profile
            svc = QueryService(s)
            report = run_serve_load(svc, [QA], sessions=4, reps=2)
            assert not report["errors"]
            # warm concurrent replays of an identical query must never
            # raise DETERMINISTIC regressions (scope-exact deltas,
            # increase-only gate); advisory wall-drift info findings
            # are timing-dependent on a loaded box and not asserted
            df = s.sql(QA)
            df.toArrow()
            errors = [f for f in df.query_execution._last_regressions
                      if f["severity"] == "error"]
            assert errors == [], errors
        finally:
            s.stop()


    def test_pooled_load_attributes_every_launch_to_one_query(
            self, tmp_path):
        """Eight cloned sessions over two weighted pools through the
        service: the launch totals of the stored profiles sum to the
        KernelCache's delta, no profile needed the overlap guard, and
        the pools were granted contended slots near their 2:1 weights."""
        from spark_tpu.obs.history import ProfileStore
        from spark_tpu.obs.resources import GLOBAL_LEDGER

        s = _session("srv-pooled", {
            "spark.tpu.obs.profileDir": str(tmp_path),
            "spark.tpu.scheduler.pools": "dash:2,batch:1",
            "spark.tpu.serve.maxConcurrent": 2})
        try:
            _seed(s)
            svc = QueryService(s)
            before = KC.launches
            report = run_serve_load(svc, [QA, QB], sessions=8, reps=3,
                                    pools=("dash", "batch"))
            assert not report["errors"], report["errors"]
            delta = KC.launches - before
            store = ProfileStore(str(tmp_path))
            profiles = [p for qk in store.query_keys()
                        for p in store.profiles(qk)]
            assert len(profiles) == 8 * 3 * 2
            assert not [p for p in profiles if p.get("overlapped")]
            assert sum(int(p["launch_total"]) for p in profiles) == delta
            grants = report["contended_grants"] or {}
            # few contended grants: the stride's rounding by one decides
            # (the exact 2:1 is test_weighted_fair_share_is_deterministic)
            if len(grants) >= 2 and sum(grants.values()) >= 12:
                assert report["fairness_ratio"] <= 1.25, report
            assert svc.drain(timeout=10.0)
            assert svc.scheduler.balanced()
            assert GLOBAL_LEDGER.verify() == []
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# service: admission + drain semantics
# ---------------------------------------------------------------------------

class TestServiceAndDrain:
    def test_execute_sql_routes_pools_and_commands(self):
        s = _session("srv-svc", {
            "spark.tpu.scheduler.pools": "dash:2,batch:1"})
        try:
            _seed(s)
            svc = QueryService(s)
            c = svc.open_session()
            svc.execute_sql(c, "SET spark.tpu.scheduler.pool=dash")
            out = svc.execute_sql(c, QA)
            assert out.num_rows > 0
            st = svc.status()
            assert st["pools"]["dash"]["completed"] == 1
            # SET itself never took an admission slot
            assert st["pools"]["dash"]["admitted"] == 1
        finally:
            s.stop()

    def test_over_budget_query_rejects_plan_time(self):
        s = _session("srv-budget")
        try:
            _seed(s)
            svc = QueryService(s)
            c = svc.open_session()
            c.conf.set("spark.tpu.memory.budget", 512)
            from spark_tpu.obs.resources import MemoryBudgetExceeded

            launches = KC.launches
            with pytest.raises(MemoryBudgetExceeded):
                svc.execute_sql(c, QA)
            assert KC.launches == launches, \
                "admission rejection must dispatch nothing"
            assert svc.scheduler.balanced()
        finally:
            s.stop()

    def test_drain_finishes_inflight_rejects_new(self):
        s = _session("srv-drain")
        try:
            _seed(s)
            svc = QueryService(s)
            inflight = svc.scheduler.submit("default")
            svc.scheduler.wait(inflight, timeout=1.0)
            done = {}

            def drain():
                done["ok"] = svc.drain(timeout=10.0)

            th = threading.Thread(target=drain, daemon=True)
            th.start()
            deadline = time.monotonic() + 2.0
            while not svc.scheduler.draining \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            with pytest.raises(ServerDraining):
                svc.execute_sql(s, QA)
            with pytest.raises(ServerDraining):
                svc.open_session()
            svc.scheduler.release(inflight)   # in-flight work completes
            th.join(10.0)
            assert done.get("ok") is True
            assert svc.scheduler.balanced()
        finally:
            s.stop()

    def test_endpoint_stop_drains(self):
        from spark_tpu.connect.sql_endpoint import SQLEndpoint

        s = _session("srv-ep-drain")
        try:
            _seed(s)
            ep = SQLEndpoint(s).start()
            assert ep.stop() is True
            with pytest.raises(ServerDraining):
                ep.service.execute_sql(s, QA)
        finally:
            s.stop()


# ---------------------------------------------------------------------------
# cluster serving leg
# ---------------------------------------------------------------------------

def test_cluster_serving_leg():
    s = _session("srv-cluster", {
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.scheduler.pools": "dash:2,batch:1",
        "spark.tpu.serve.maxConcurrent": "2",
    })
    try:
        _seed(s)
        want = sorted(s.sql(QA).toArrow().to_pylist(), key=str)
        svc = QueryService(s)
        report = run_serve_load(svc, [QA], sessions=4, reps=2,
                                pools=("dash", "batch"))
        assert not report["errors"], report["errors"]
        assert report["pools"]["dash"]["completed"] == 4
        assert report["pools"]["batch"]["completed"] == 4
        # cloned serving sessions share the one cluster and agree with
        # the parent session's answer
        c = svc.open_session()
        got = sorted(svc.execute_sql(c, QA).to_pylist(), key=str)
        assert got == want
        assert svc.drain(timeout=10.0)
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# zero-launch guard: serving layer present but idle
# ---------------------------------------------------------------------------

def test_serving_layer_idle_is_zero_launch():
    from spark_tpu.connect.sql_endpoint import SQLEndpoint

    s = _session("srv-idle")
    try:
        _seed(s)

        def warm_delta():
            s.sql(QA).toArrow()
            before = dict(KC.launches_by_kind)
            s.sql(QA).toArrow()
            return {k: v - before.get(k, 0)
                    for k, v in KC.launches_by_kind.items()
                    if v != before.get(k, 0)}

        without = warm_delta()
        svc = QueryService(s)
        ep = SQLEndpoint(s, service=svc).start()
        try:
            svc.status()
            with_serving = warm_delta()
        finally:
            ep.stop()
        assert with_serving == without, (
            f"idle serving layer changed kernel dispatches: "
            f"{with_serving} vs {without}")
    finally:
        s.stop()
