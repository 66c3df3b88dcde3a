"""The SQL endpoint's token (PR 33): a connection that has not shown it
gets nothing parsed, planned or run; the right token is served; a server
without a token binds loopback addresses only."""

import json
import os
import signal
import socket
import subprocess
import sys

import pyarrow as pa
import pytest

from spark_tpu import TpuSession
from spark_tpu.connect import sql_endpoint
from spark_tpu.connect.sql_endpoint import SQLEndpoint, connect
from spark_tpu.obs.tracing import recorded_spans
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOKEN = "s3cret-for-the-test"
SQL = "select k, sum(v) sv from auth_t group by k order by k"


@pytest.fixture(scope="module")
def served():
    s = TpuSession("auth-endpoint", {"spark.sql.shuffle.partitions": 2})
    s.createDataFrame(pa.table({"k": [1, 1, 2], "v": [10, 20, 30]})) \
        .createOrReplaceTempView("auth_t")
    ep = SQLEndpoint(s, port=0, token=TOKEN).start()
    try:
        yield s, ep
    finally:
        ep.stop(1.0)
        s.stop()


def _counters(session) -> dict:
    return dict(session._metrics.snapshot()["counters"])


def _first_lines(ep, *lines) -> list:
    """Raw lines sent on a new connection; the responses until the
    server closes it or has answered them all."""
    out = []
    with socket.create_connection((ep.host, ep.port), timeout=30) as sock:
        f = sock.makefile("rwb")
        for line in lines:
            f.write(line + b"\n")
            f.flush()
            got = f.readline()
            if not got:
                break
            out.append(json.loads(got))
    return out


FIRST_LINES = {
    "sql_and_no_token": json.dumps({"sql": SQL}).encode(),
    "a_wrong_token": json.dumps({"auth": TOKEN + "x"}).encode(),
    "a_token_that_is_a_prefix": json.dumps({"auth": TOKEN[:-1]}).encode(),
    "a_token_that_is_no_string": json.dumps({"auth": 17}).encode(),
    "sql_beside_a_wrong_token": json.dumps(
        {"auth": "no", "sql": SQL}).encode(),
    "a_status_request": json.dumps({"status": True}).encode(),
    "a_shared_session_request": json.dumps({"session": "shared"}).encode(),
    "a_line_that_is_no_json": b"select 1",
}


@pytest.mark.parametrize("first", sorted(FIRST_LINES))
def test_anything_but_the_token_first_is_refused_and_runs_nothing(
        served, first):
    session, ep = served
    before, launches = _counters(session), KC.launches
    opened = ep.service.sessions_opened
    # the statement behind the refused line is never answered
    got = _first_lines(ep, FIRST_LINES[first],
                       json.dumps({"sql": SQL}).encode())
    assert len(got) == 1
    assert got[0]["error_class"] == sql_endpoint.UNAUTHENTICATED
    assert "rows" not in got[0] and "status" not in got[0]
    after = _counters(session)
    assert after["endpoint.auth_refused"] \
        == before.get("endpoint.auth_refused", 0) + 1
    assert after.get("endpoint.requests", 0) \
        == before.get("endpoint.requests", 0)
    assert KC.launches == launches
    assert ep.service.sessions_opened == opened
    assert ep.service.sessions() == []


@pytest.mark.parametrize("token", [None, "wrong", ""])
def test_the_dbapi_client_without_the_token_gets_a_typed_error(
        served, token):
    _session, ep = served
    launches = KC.launches
    with pytest.raises(sql_endpoint.Error) as e:
        with connect(ep.host, ep.port, token=token) as conn:
            conn.cursor().execute(SQL)
    assert e.value.error_class == sql_endpoint.UNAUTHENTICATED
    assert KC.launches == launches


def test_the_right_token_is_served(served):
    session, ep = served
    before = _counters(session)
    with connect(ep.host, ep.port, token=TOKEN) as conn:
        cur = conn.cursor()
        cur.execute(SQL)
        assert cur.fetchall() == [(1, 30), (2, 30)]
        assert [d[0] for d in cur.description] == ["k", "sv"]
        assert conn.server_status()["sessions_opened"] >= 1
        assert len(ep.service.sessions()) == 1
    after = _counters(session)
    assert after.get("endpoint.auth_refused", 0) \
        == before.get("endpoint.auth_refused", 0)
    # the statement and the status request; the token's line is neither
    assert after["endpoint.requests"] \
        == before.get("endpoint.requests", 0) + 2


def test_a_token_sent_again_is_acknowledged(served):
    _session, ep = served
    auth = json.dumps({"auth": TOKEN}).encode()
    got = _first_lines(ep, auth, auth, json.dumps({"sql": SQL}).encode())
    assert got[0] == got[1] == {"ok": True, "auth": True}
    assert got[2]["rows"] == [[1, 30], [2, 30]]


def test_a_served_statement_leaves_the_serving_spans_and_counters(served):
    import time

    _session, ep = served
    t0 = time.perf_counter()
    with connect(ep.host, ep.port, token=TOKEN) as conn:
        conn.cursor().execute(SQL)
        tenant, = ep.service.sessions()     # kept: its tracer has the spans
        counters = _counters(tenant)
        # a span is recorded once its response is flushed: the next
        # response says that it was
        conn.server_status()
    spans = {}          # the first of each name: the statement's own
    for s in recorded_spans(t0, time.perf_counter()):
        spans.setdefault(s["name"], s)
    assert {"endpoint.request", "endpoint.encode", "serve.admission",
            "serve.execute"} <= set(spans)
    assert spans["endpoint.encode"]["args"]["rows"] == 2
    assert spans["endpoint.encode"]["args"]["bytes"] > 20
    assert spans["serve.admission"]["args"]["pool"] == "default"
    assert spans["serve.execute"]["args"]["pool"] == "default"
    assert spans["serve.execute"]["args"]["query"]
    request, execute = spans["endpoint.request"], spans["serve.execute"]
    assert request["ts"] <= spans["serve.admission"]["ts"] <= execute["ts"]
    assert request["dur_ms"] >= execute["dur_ms"]
    assert counters["serve.granted"] == 1
    assert counters["serve.running_peak"] == 1
    assert "serve.rejected_full" not in counters


@pytest.mark.parametrize("host,loopback", [
    ("127.0.0.1", True), ("127.8.9.1", True), ("localhost", True),
    ("::1", True), ("0.0.0.0", False), ("::", False),
    ("192.168.1.7", False), ("example.org", False), ("", False)])
def test_what_counts_as_a_loopback_address(host, loopback):
    assert sql_endpoint._is_loopback(host) is loopback


@pytest.mark.parametrize("host", ["0.0.0.0", "", "::"])
def test_a_server_without_a_token_will_not_bind_beyond_loopback(
        served, host):
    session, _ep = served
    with pytest.raises(ValueError, match="without a token"):
        SQLEndpoint(session, host=host, port=0)
    with pytest.raises(ValueError, match="without a token"):
        SQLEndpoint(session, host=host, port=0, token="")


def test_a_server_without_a_token_serves_loopback_as_before(served):
    session, _ep = served
    ep = SQLEndpoint(session, port=0).start()
    try:
        for token in (None, "offered-all-the-same"):
            with connect(ep.host, ep.port, token=token) as conn:
                cur = conn.cursor()
                cur.execute(SQL)
                assert cur.fetchall() == [(1, 30), (2, 30)]
    finally:
        ep.stop(1.0)


def test_the_server_command_prints_its_token_and_asks_for_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "spark_tpu.connect.sql_endpoint_main",
         "--port", "0"], env=env, stdout=subprocess.PIPE, text=True)
    try:
        said = json.loads(proc.stdout.readline())
        assert set(said) == {"host", "port", "token"}
        assert len(said["token"]) >= 32
        with connect(said["host"], said["port"],
                     token=said["token"]) as conn:
            cur = conn.cursor()
            cur.execute("select 1 + 1 two")
            assert cur.fetchall() == [(2,)]
        with pytest.raises(sql_endpoint.Error) as e:
            with connect(said["host"], said["port"]) as conn:
                conn.cursor().execute("select 1")
        assert e.value.error_class == sql_endpoint.UNAUTHENTICATED
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(60)
        except subprocess.TimeoutExpired:
            proc.kill()
    assert proc.returncode == 0
