"""PR 33's additions: the configuration `tpcds_sf10_server` (the TPC-DS
throughput test's streams as tenants of the SQL server), its cell
`tpcds_sf10_server.tenants2`, the entry that is a DB-API client of
`SQLEndpoint` behind a token, and four per-layer readers. The cell
rehearses correct; an answer given to the wrong query, a row dropped on
the wire and a statement shed do not."""

import os
import sys
import threading
import time
from decimal import Decimal

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402
from perfbench import run as pb  # noqa: E402
from perfbench.entries import session as session_entry  # noqa: E402
from test_perfbench_faults import _wrap_clients, rehearse  # noqa: E402
from test_perfbench_spans import _plant, _span  # noqa: E402


class _Current:
    """`perfbench.entries.endpoint` as `run.py` and the readers find it
    now (another test file takes the package out of `sys.modules`)."""

    def __getattr__(self, name):
        import importlib

        return getattr(importlib.import_module(
            "perfbench.entries.endpoint"), name)


endpoint = _Current()
CELL = "tpcds_sf10_server.tenants2"
LOADED = spec.cell(CELL)
CONFIG = LOADED["config"]
POWER2 = spec.cell("tpcds_sf10_session.power2")["config"]
QUERIES = ("q3", "q7")
SCALE = 0.004
BIG_SEED = 2 ** 31 + 3303


@pytest.fixture(scope="module")
def data():
    return gen.generate(CONFIG, BIG_SEED, SCALE)


@pytest.fixture(scope="module")
def served(data):
    """The deployment at a small scale: the server session with the
    tables, and the entry's endpoint over it."""
    from spark_tpu import TpuSession

    s = TpuSession("pb-server", dict(CONFIG["session_conf"]))
    entry = None
    try:
        for name, tab in gen.arrow_tables(data).items():
            s.createDataFrame(tab).createOrReplaceTempView(name)
        entry = endpoint.Entry(s, CONFIG)
        yield s, entry
    finally:
        if entry is not None:
            entry.stop()
        s.stop()


# ---------------------------------------------------------------------------
# the configuration and the cell, as files
# ---------------------------------------------------------------------------

def test_the_cell_is_two_streams_of_three_whole_rounds():
    assert LOADED["chips"] == 1 and CONFIG["entry"] == "endpoint"
    assert LOADED["traffic"]["streams"] == [["q3", "q7"], ["q3", "q7"]]
    assert LOADED["traffic"]["rounds_at_most"] == 3
    # the second tenant arrives 1.25 s after the first (PR 36): inside
    # the 0.8-1.9 s in which its first program queues behind the first
    # tenant's second, clear of the tie that gave query_s.p50 two levels
    assert LOADED["traffic"]["start_offsets_s"] == [0.0, 1.25]
    assert CONFIG["streams"] == 2
    assert CONFIG["stream_order"] == LOADED["traffic"]["streams"]
    assert CONFIG["published"]["minimum_streams"] == 4
    assert {m["name"] for m in LOADED["end_to_end"]} == {
        "fact_rows_per_s", "query_s.p50", "query_s.p95", "setup_s"}
    # the four readers of PR 33, the ten that every cell reports (PR 36),
    # and `dispatch_ms`, which lists the cell
    assert {m["name"] for m in LOADED["per_layer"]} == {
        "serve_admission_wait_ms", "serve_execute_s_per_query",
        "wire_encode_ms", "statements_shed", "compiles_in_window",
        "hbm_roofline_pct", "device_s_per_query", "device_idle_pct",
        "programs_per_query", "discarded_program_s_per_query",
        "stage_launches_per_query", "collect_ms", "setup_h2d_s",
        "setup_program_load_s", "dispatch_ms"}


@pytest.mark.parametrize("key", ["fact_tables", "tables", "foreign_domains",
                                 "query_templates", "distributions",
                                 "session_conf", "rehearsal", "benchmark",
                                 "scale_factor"])
def test_the_plans_and_programs_are_power2s(key):
    """The same tables, texts and session conf, letter for letter: the
    same plans, fingerprints and programs, and nothing new to compile."""
    assert CONFIG[key] == POWER2[key]


def test_guarantees_are_power2s_and_the_servers():
    for name, text in POWER2["guarantees"].items():
        assert CONFIG["guarantees"][name] == text
    assert set(CONFIG["guarantees"]) - set(POWER2["guarantees"]) == {
        "every_statement_answered", "tenant_isolation",
        "connection_local_state", "authentication"}
    assert "one process" in CONFIG["deployment"].lower()
    assert set(CONFIG["assumed"]) == {"spark.tpu.batch.capacity",
                                      "one_process"}


def test_the_fact_table_is_tpcds_sf10_sessions_key_for_key(data):
    assert CONFIG["seeding"]["structure_seed"] \
        == POWER2["seeding"]["structure_seed"] == 2147750005
    assert CONFIG["seeding"]["from_the_run_seed"] \
        == POWER2["seeding"]["from_the_run_seed"]
    assert len(CONFIG["seeding"]["from_the_run_seed"]) == 6
    assert gen.table_rows(CONFIG) == gen.table_rows(POWER2)
    other = gen.generate(POWER2, BIG_SEED, SCALE)
    assert list(other) == list(data)
    for table, cols in data.items():
        for c, col in cols.items():
            assert np.array_equal(col.values, other[table][c].values), c
            assert col.valid is other[table][c].valid is None \
                or np.array_equal(col.valid, other[table][c].valid), c


# ---------------------------------------------------------------------------
# the wire
# ---------------------------------------------------------------------------

TYPES_SQL = ("select cast(null as decimal(7,2)) dn, "
             "cast(1.50 as decimal(7,2)) d, cast(null as double) xn, "
             "cast(0.1 as double) + cast(0.2 as double) x, "
             "cast(3 as double) whole, cast(null as int) i_n, 7 i, "
             "cast(null as string) sn, 'it''s' s")


@pytest.mark.parametrize("text", [spec.query_text("q3"),
                                  spec.query_text("q7"), TYPES_SQL],
                         ids=["q3", "q7", "every_type_and_its_null"])
def test_the_wires_rows_are_toarrows_rows(served, text):
    """Value for value and type for type: a decimal is a Decimal of the
    column's scale, a double the same float64, NULL is None."""
    s, entry = served
    direct = session_entry.Client(s)
    want = direct.rows(direct.run(text, pb.no_span)[0])
    client = entry.client(0)
    try:
        raw, info = client.run(text, pb.no_span)
        got = client.rows(raw)
    finally:
        client.close()
    assert info == {}
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        assert g == w
        assert [type(v) for v in g] == [type(v) for v in w]
        for a, b in zip(g, w):
            if isinstance(b, Decimal):
                assert a.as_tuple() == b.as_tuple()
    if text == TYPES_SQL:
        assert got[0][3] == 0.1 + 0.2 and got[0][4] == 3.0
        assert {type(v) for v in got[0]} == {type(None), Decimal, float,
                                             int, str}


def test_two_tenants_at_once_each_match_the_reference(served, data):
    """q3 on one connection while q7 runs on the other, three times
    over, then the other way round: every answer is its query's."""
    s, entry = served
    want = {q: reference.load(q).run(data, reference.Exact())
            for q in QUERIES}
    clients = [entry.client(0), entry.client(1)]
    got, errors = [], []
    start = threading.Barrier(2)

    def tenant(i):
        try:
            order = QUERIES if i == 0 else QUERIES[::-1]
            start.wait(30)
            for _ in range(3):
                for q in order:
                    raw, _info = clients[i].run(spec.query_text(q),
                                                pb.no_span)
                    got.append((i, q, clients[i].rows(raw)))
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=tenant, args=(i,)) for i in (0, 1)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
    finally:
        for c in clients:
            c.close()
    assert not errors, errors
    assert len(got) == 12
    for i, q, rows in got:
        n = check.compare_rows(rows, want[q], reference.load(q))
        assert not check.over(n), (i, q, n)


def test_set_on_one_connection_is_not_seen_on_the_other(served):
    _s, entry = served
    a, b = entry.client(0), entry.client(1)
    key = "spark.sql.shuffle.partitions"
    try:
        a.cursor.execute(f"SET {key}=3")
        a.cursor.execute(f"SET {key}")
        b.cursor.execute(f"SET {key}")
        assert a.cursor.fetchall()[0][-1] == "3"
        assert b.cursor.fetchall()[0][-1] != "3"
        a.cursor.execute("create temporary view only_mine as select 1 one")
        a.cursor.execute("select one from only_mine")
        assert a.cursor.fetchall() == [(1,)]
        with pytest.raises(Exception, match="only_mine"):
            b.cursor.execute("select one from only_mine")
    finally:
        a.close()
        b.close()


def test_the_second_tenant_finds_the_first_tenants_programs(served):
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    _s, entry = served
    first, second = entry.client(0), entry.client(1)
    try:
        for q in QUERIES:
            first.run(spec.query_text(q), pb.no_span)
        misses, hits = KC.misses, KC.hits
        for q in QUERIES:
            second.run(spec.query_text(q), pb.no_span)
        assert KC.misses == misses and KC.hits > hits
    finally:
        first.close()
        second.close()


def test_tenants_whose_first_statements_meet_copy_a_table_once():
    """Two connections scan a table for the first time at the same
    moment: one `ingest.h2d`, and both answers right."""
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.connect.sql_endpoint import SQLEndpoint, connect
    from spark_tpu.obs.tracing import recorded_spans

    s = TpuSession("pb-server-ingest", {})
    rng = np.random.default_rng(33)
    values = rng.integers(0, 1000, 200_000)
    s.createDataFrame(pa.table({"k": values % 7, "v": values})) \
        .createOrReplaceTempView("met_t")
    ep = SQLEndpoint(s, port=0, token="t").start()
    n = 4
    start = threading.Barrier(n)
    got, kept = [], []

    def tenant():
        with connect(ep.host, ep.port, token="t") as conn:
            cur = conn.cursor()
            cur.execute("select 1")          # the session is cloned here
            kept.extend(ep.service.sessions())
            start.wait(30)
            cur.execute("select sum(v) from met_t")
            got.append(cur.fetchall())

    t0 = time.perf_counter()
    threads = [threading.Thread(target=tenant) for _ in range(n)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        # (`select 1` copies its one row too: a kilobyte a connection)
        spans = [x for x in recorded_spans(t0, time.perf_counter())
                 if x["name"] == "ingest.h2d"
                 and x["args"]["bytes"] >= 8 * len(values)]
    finally:
        ep.stop(1.0)
        s.stop()
    assert got == [[(int(values.sum()),)]] * n
    assert len(spans) == 1, spans


def test_entry_sessions_holds_every_tenants_session(served):
    s, entry = served
    clients = [entry.client(i) for i in range(3)]
    try:
        for c in clients:
            c.cursor.execute("select 1")
        open_now = entry.endpoint.service.sessions()
        assert len(open_now) >= 3
        held = entry.sessions()
        assert held[0] is s
        assert all(any(t is h for h in held) for t in open_now)
        assert len({id(h) for h in held}) == len(held)
    finally:
        for c in clients:
            c.close()
    # a connection's end takes its session from the service, not from
    # the entry: its spans and counters are still to be read
    deadline = time.time() + 10
    while entry.endpoint.service.sessions() and time.time() < deadline:
        time.sleep(0.01)
    assert all(any(t is h for h in entry.sessions()) for t in open_now)
    assert endpoint.NOTES[-1][1]["serve.granted"] >= 3


def test_a_program_whose_endpoint_takes_no_token_cannot_run_the_cell(
        monkeypatch, capsys):
    """What the parent commit of PR 33 does under this PR's benchmark
    files: the run ends at set-up, with the reason and no result line."""
    from spark_tpu.connect import sql_endpoint

    class Tokenless(sql_endpoint.SQLEndpoint):
        def __init__(self, session, host="127.0.0.1", port=0, service=None):
            super().__init__(session, host, port, service)

    monkeypatch.setattr(sql_endpoint, "SQLEndpoint", Tokenless)
    with pytest.raises(SystemExit) as e:
        rehearse(CELL, seconds=1)
    assert "cannot run configuration 'tpcds_sf10_server'" in str(e.value)
    assert "takes no token" in str(e.value)
    assert capsys.readouterr().out == ""


def test_an_endpoint_that_lets_a_stranger_in_ends_the_run(monkeypatch):
    from spark_tpu.connect import sql_endpoint

    monkeypatch.setattr(sql_endpoint.SQLEndpoint, "_authenticates",
                        lambda self, line: True)
    with pytest.raises(SystemExit, match="had not authenticated"):
        rehearse(CELL, seconds=1)


# ---------------------------------------------------------------------------
# the cell, rehearsed; and the timed path broken underneath
# ---------------------------------------------------------------------------

def test_cell_rehearses_correct_and_prints_no_metric(capsys):
    import json

    rc = pb.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                  "--seconds", "30", "--trace", "0", "--rehearse"])
    cap = capsys.readouterr()
    out = json.loads(cap.out.strip().splitlines()[-1])
    assert rc == 0
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["window"]["rounds"] == [3, 3]
    assert out["window"]["queries"] == {"q3": 6, "q7": 6}
    assert out["metrics"] == {}
    for c in out["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]
    # both streams' executions are in the line of the window
    assert "(0, 'q7'" in cap.err and "(1, 'q7'" in cap.err
    # the window's notes: nothing was shed
    state = {"records": [{"t_submit": endpoint.NOTES[-3][0],
                          "t_done": endpoint.NOTES[-2][0], "error": None}]}
    assert spec.metric_reader("statements_shed").read(state) == 0.0


def the_other_querys_answer(entry, session, tables):
    """Stream 1's q3 is answered with what q7 returns."""
    q3, q7 = spec.query_text("q3"), spec.query_text("q7")
    make = entry.client

    def client(i):
        c = make(i)
        run = c.run
        if i == 1:
            c.run = lambda text, annotate: run(
                q7 if text == q3 else text, annotate)
        return c
    entry.client = client


def a_row_dropped_on_the_wire(entry, session, tables):
    def around(run, text, annotate):
        (description, rows), info = run(text, annotate)
        return (description, rows[:-1]), info
    _wrap_clients(entry, around)


def a_statement_shed(entry, session, tables):
    """One slot and no patience: a statement that meets the other
    tenant's is turned away (the warm-up's run one after the other)."""
    cfg = entry.endpoint.service.scheduler._pool_state("default").cfg
    cfg.max_concurrent, cfg.queue_timeout_s = 1, 0.0


@pytest.mark.parametrize("fault,number", [
    (the_other_querys_answer, "rows_wrong"),
    (a_row_dropped_on_the_wire, "rows_wrong"),
    (a_statement_shed, "unanswered")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_broken_serving_path_is_not_correct(fault, number):
    out = rehearse(CELL, fault, seconds=30)
    assert out["correct"] is False
    c = out["compared"][number]
    assert c["value"] > c["limit"], out["compared"]
    assert out["failed"] >= 1
    if fault is a_statement_shed:
        # the counters say what the wire said
        before, after = endpoint.NOTES[-3][1], endpoint.NOTES[-2][1]
        assert after["serve.rejected_timeout"] \
            - before["serve.rejected_timeout"] == c["value"]
        state = {"records": [{"t_submit": endpoint.NOTES[-3][0],
                              "t_done": endpoint.NOTES[-2][0],
                              "error": None}]}
        assert spec.metric_reader("statements_shed").read(state) \
            == float(c["value"])


def test_a_degrade_counter_on_a_tenants_session_is_seen():
    """`hidden_counters_moved` is read over every tenant's session, not
    the server's alone."""
    def fault(entry, session, tables):
        def around(run, text, annotate):
            tenants = entry.endpoint.service.sessions()
            if tenants:
                tenants[-1]._metrics.add("whole_query.runtime_degraded")
            return run(text, annotate)
        _wrap_clients(entry, around)

    out = rehearse(CELL, fault, seconds=30)
    assert out["correct"] is False
    c = out["compared"]["hidden_counters_moved"]
    assert c["value"] > c["limit"], out["compared"]


# ---------------------------------------------------------------------------
# the four readers
# ---------------------------------------------------------------------------

RECORDS = [{"t_submit": 100.0, "t_done": 104.0, "error": None},
           {"t_submit": 100.0, "t_done": 106.0, "error": None},
           {"t_submit": 104.1, "t_done": 111.0, "error": None},
           {"t_submit": 106.1, "t_done": 114.0, "error": None}]

SPANS = [
    _span("endpoint.request", 60.0, 9000.0),                    # warm-up
    _span("serve.admission", 60.0, 0.4, pool="default"),
    _span("serve.execute", 60.0, 8000.0, pool="default", query="q-0"),
    _span("endpoint.encode", 68.0, 70.0, rows=9, bytes=99),
    _span("endpoint.request", 100.0, 4000.0),
    _span("serve.admission", 100.01, 0.2, pool="default"),
    _span("serve.execute", 100.02, 3900.0, pool="default", query="q-1"),
    _span("endpoint.encode", 103.93, 10.0, rows=200, bytes=9000),
    _span("serve.admission", 100.01, 0.6, pool="default"),
    _span("serve.execute", 100.03, 5800.0, pool="default", query="q-2"),
    _span("endpoint.encode", 105.9, 14.0, rows=200, bytes=9000),
    _span("serve.admission", 104.2, 0.4, pool="default"),
    _span("serve.execute", 104.2, 6700.0, pool="default", query="q-3"),
    _span("endpoint.encode", 110.9, 90.0, rows=51000, bytes=4000000),
    _span("serve.admission", 106.2, 0.4, pool="default"),
    _span("serve.execute", 106.2, 7600.0, pool="default", query="q-4"),
    _span("endpoint.encode", 113.8, 86.0, rows=51000, bytes=4000000),
    _span("serve.execute", 120.0, 5000.0, pool="default", query="later"),
]
NOTES = [(50.0, {"serve.rejected_full": 0, "serve.rejected_timeout": 0,
                 "endpoint.auth_refused": 2}),
         (99.9, {"serve.rejected_full": 0, "serve.rejected_timeout": 1,
                 "endpoint.auth_refused": 2}),
         (114.1, {"serve.rejected_full": 2, "serve.rejected_timeout": 2,
                  "endpoint.auth_refused": 3}),
         (130.0, {"serve.rejected_full": 9, "serve.rejected_timeout": 9,
                  "endpoint.auth_refused": 9})]
EXPECTED = {"serve_admission_wait_ms": (0.2 + 0.6 + 0.4 + 0.4) / 4,
            "serve_execute_s_per_query": (3.9 + 5.8 + 6.7 + 7.6) / 4,
            "wire_encode_ms": (10 + 14 + 90 + 86) / 4,
            "statements_shed": 4.0}


def _plant_notes(monkeypatch, notes):
    import importlib

    monkeypatch.setattr(importlib.import_module(
        "perfbench.entries.endpoint"), "NOTES", notes)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_planted_window(name, monkeypatch):
    _plant(monkeypatch, SPANS)
    _plant_notes(monkeypatch, NOTES)
    value = spec.metric_reader(name).read({"records": RECORDS})
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_silent_where_there_is_nothing_to_read(name, monkeypatch):
    """No span so named, a program without `recorded_spans`, a run
    through another door (no note), a note on one side of the window
    only, or no query at all: None, and no exception."""
    import spark_tpu.obs.tracing as tracing

    reader = spec.metric_reader(name)
    _plant(monkeypatch, [_span("collect", 100.9, 90.0)])
    _plant_notes(monkeypatch, [])
    assert reader.read({"records": RECORDS}) is None
    _plant_notes(monkeypatch, NOTES[:2])
    assert reader.read({"records": RECORDS}) is None
    _plant(monkeypatch, SPANS)
    _plant_notes(monkeypatch, NOTES)
    assert reader.read({"records": []}) is None
    monkeypatch.delattr(tracing, "recorded_spans")
    _plant_notes(monkeypatch, [])
    assert reader.read({"records": RECORDS}) is None


def test_a_failed_statement_does_not_count_as_a_query(monkeypatch):
    _plant(monkeypatch, SPANS)
    records = [dict(r) for r in RECORDS]
    records[3]["error"] = "Error: ADMISSION_TIMEOUT"
    assert spec.metric_reader("wire_encode_ms").read(
        {"records": records}) == pytest.approx(200 / 3)


def _tenant_of(entry, client):
    """The session the service clones for the client's first statement."""
    service = entry.endpoint.service
    before = service.sessions()
    client.cursor.execute("select 1")
    new = [s for s in service.sessions()
           if not any(s is b for b in before)]
    assert len(new) == 1
    return new[0]


def _kernel_counters(tenant) -> dict:
    c = tenant._metrics.snapshot()["counters"]
    return {k: c.get(k, 0) for k in ("kernel.launches", "kernel.misses")}


def _moved(tenant, before: dict) -> dict:
    return {k: v - before[k] for k, v in _kernel_counters(tenant).items()}


def test_a_tenants_kernel_counters_are_its_own(served):
    """`kernel.launches` and `kernel.misses` of a tenant's session count
    its own programs, whatever the other tenant launches meanwhile (they
    were differences of the process's counters across the query)."""
    _s, entry = served
    alone = {}
    probe = entry.client(0)
    try:
        tenant = _tenant_of(entry, probe)
        for q in QUERIES:
            probe.run(spec.query_text(q), pb.no_span)       # warm
            before = _kernel_counters(tenant)
            probe.run(spec.query_text(q), pb.no_span)
            alone[q] = _moved(tenant, before)
    finally:
        probe.close()
    assert all(a["kernel.launches"] >= 1 and a["kernel.misses"] == 0
               for a in alone.values())
    clients = [entry.client(i) for i in (0, 1)]
    start = threading.Barrier(2)
    rounds = 4

    def stream(i):
        start.wait(30)
        for _ in range(rounds):
            clients[i].run(spec.query_text(QUERIES[i]), pb.no_span)

    threads = [threading.Thread(target=stream, args=(i,)) for i in (0, 1)]
    try:
        tenants = [_tenant_of(entry, c) for c in clients]
        before = [_kernel_counters(t) for t in tenants]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        for i in (0, 1):
            assert _moved(tenants[i], before[i]) == {
                k: rounds * v for k, v in alone[QUERIES[i]].items()}, i
    finally:
        for c in clients:
            c.close()
