"""Standalone deploy: master + worker daemons as SEPARATE processes
(no shared Python state), executor placement, worker-churn recovery
(reference: core/deploy/master/Master.scala, worker/Worker.scala,
client/StandaloneAppClient.scala)."""

import os
import pickle
import secrets
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_daemon(module: str, args: list, announce: str,
                  secret: str) -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_TPU_MASTER_SECRET"] = secret
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", module, *args,
         "--announce-file", announce],
        env=env, cwd=REPO)


def _read_announce(path: str, timeout: float = 30.0) -> str:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            with open(path) as f:
                return f.read().strip()
        time.sleep(0.1)
    raise TimeoutError(f"no announce file at {path}")


@pytest.fixture()
def standalone(tmp_path):
    """A master and two worker daemons, each its own OS process."""
    secret = secrets.token_hex(16)
    procs = []
    try:
        m = _spawn_daemon("spark_tpu.deploy.master", [],
                          str(tmp_path / "master.addr"), secret)
        procs.append(m)
        master_addr = _read_announce(str(tmp_path / "master.addr"))
        for i in range(2):
            w = _spawn_daemon("spark_tpu.deploy.worker", [master_addr],
                              str(tmp_path / f"worker{i}.addr"), secret)
            procs.append(w)
            _read_announce(str(tmp_path / f"worker{i}.addr"))
        yield {"master_addr": master_addr, "secret": secret,
               "procs": procs}
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_master_places_executors_and_replaces_lost_worker(standalone):
    """The master's schedule loop: two requested executors placed on the
    worker fleet; killing a worker DAEMON re-places its executor on the
    survivor (Master.scala:744 schedule after worker timeout)."""
    from spark_tpu.deploy.standalone import StandaloneCluster
    from spark_tpu.net.transport import RpcClient

    cluster = StandaloneCluster(
        f"grpc://{standalone['master_addr']}", standalone["secret"],
        num_executors=2, app_name="placement")
    try:
        assert cluster.num_alive() == 2
        assert cluster.run_task(lambda x: x * 3, 14) == 42
        # kill one EXECUTOR process: its worker daemon reaps the child,
        # its next heartbeat reports the deficit, and the master's
        # reconcile loop launches a replacement
        victim = next(iter(cluster._workers.values()))
        os.kill(victim.pid, signal.SIGKILL)
        deadline = time.monotonic() + 10
        while cluster.num_alive() > 1 and time.monotonic() < deadline:
            # poke the dead executor so the driver notices the loss
            try:
                cluster.run_task(lambda x: x, 0)
            except Exception:
                pass
            time.sleep(0.2)
        cluster.wait_for_executors(2, timeout=60)
        assert cluster.run_task(lambda x: x + 1, 41) == 42
        # the master's state endpoint converges on the replaced fleet
        # (worker heartbeats report launches on a 1s tick)
        with RpcClient(standalone["master_addr"],
                       standalone["secret"]) as c:
            deadline = time.monotonic() + 15
            while True:
                state = pickle.loads(
                    c.call("master_state", b"", timeout=10))
                placed = sum(sum(w["apps"].values())
                             for w in state["workers"])
                if placed >= 2 or time.monotonic() > deadline:
                    break
                time.sleep(0.3)
        assert len(state["workers"]) == 2
        assert state["apps"] and state["apps"][0]["desired"] == 2
        assert placed >= 2, state
    finally:
        cluster.stop()


def test_tpcds_q3_completes_despite_executor_kill_midquery(standalone):
    """The VERDICT's end-to-end bar: a real app (TPC-DS q3) against a
    standalone master with two remote workers; an executor dies
    mid-query; the query still returns correct rows (driver task retry
    + master re-placement)."""
    from tests.tpcds.datagen import gen_tpcds_full

    import spark_tpu.exec.cluster_sql as CS
    from spark_tpu.api.session import TpuSession
    from spark_tpu.deploy.standalone import StandaloneCluster

    spark = TpuSession("q3-standalone",
                       {"spark.sql.shuffle.partitions": "3"})
    cluster = StandaloneCluster(
        f"grpc://{standalone['master_addr']}", standalone["secret"],
        num_executors=2, app_name="q3")
    spark.attachSqlCluster(cluster)

    tables = gen_tpcds_full(scale=0.01)
    for name in ("date_dim", "store_sales", "item"):
        spark.createDataFrame(tables[name]).createOrReplaceTempView(name)

    state = {"killed": False}
    orig = CS.ClusterDAGScheduler._run_remote

    def kill_one_executor_after_first_map(self, stage):
        status = orig(self, stage)
        if not state["killed"]:
            state["killed"] = True
            w = cluster._workers[status.executor_id]
            if w.pid:
                os.kill(w.pid, signal.SIGKILL)
        return status

    CS.ClusterDAGScheduler._run_remote = kill_one_executor_after_first_map
    try:
        sql = open(os.path.join(
            REPO, "tests", "tpcds", "queries", "q3.sql")).read()
        t = spark.sql(sql).toArrow()
        assert state["killed"], "kill hook never fired"
        # correctness against the single-process engine
        CS.ClusterDAGScheduler._run_remote = orig
        spark.detachSqlCluster()
        expect = spark.sql(sql).toArrow()

        def rows(tab):
            return sorted(tuple(r.values()) for r in tab.to_pylist())

        assert rows(t) == rows(expect)
    finally:
        CS.ClusterDAGScheduler._run_remote = orig
        spark.stop()
        cluster.stop()
