"""PR 29's additions: the configuration `tpcds_sf10_window` (a `store`
table, q89 and q47 with their plain references), its cell
`tpcds_sf10_window.dev2`, the entry that asks the planner for the tier
before anything runs and puts the warm-start manifest in place, and three
per-layer readers. The cell rehearses
correct; the float32 control, an average over the wrong partition, a
rank that does not restart and a program that plans the reports off the
whole-query tier do not."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import check, gen, reference, spec  # noqa: E402
from test_perfbench_faults import _wrap_clients, rehearse  # noqa: E402
from test_perfbench_spans import _plant, _span  # noqa: E402

CELL = "tpcds_sf10_window.dev2"
CONFIG = spec.cell(CELL)["config"]
QUERIES = ("q89", "q47")
SCALE = 0.004
BIG_SEED = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def data():
    return gen.generate(CONFIG, BIG_SEED, SCALE)


# ---------------------------------------------------------------------------
# the data
# ---------------------------------------------------------------------------

def test_store_is_dsdgens_in_counts_and_domains(data):
    from perfbench.gen.tables import store

    s = data["store"]
    assert list(s) == ["s_store_sk", "s_store_name", "s_company_name"]
    assert s["s_store_sk"].values.tolist() == list(range(1, 103))
    names = s["s_store_name"].strings().tolist()
    assert set(names) == set(store.SYLLABLES) and len(set(names)) == 10
    assert names[:3] == ["ought", "able", "pri"] and names[9] == "bar"
    assert all(len(n) <= 5 for n in names)          # mk_word's room
    counts = np.bincount(s["s_store_name"].values)
    assert counts.min() == 10 and counts.max() == 11
    assert set(s["s_company_name"].strings()) == {"Unknown"}
    assert all(c.valid is None for c in s.values())  # no nulls: `reduced`
    # every sale's store is a row of the table
    sk = data["store_sales"]["ss_store_sk"]
    assert sk.values[sk.valid].min() >= 1 and sk.values[sk.valid].max() <= 102


def test_the_fact_table_is_tpcds_sf10_sessions_key_for_key(data):
    """One `store_sales` for both configurations: the same structure
    seed, the same run-seed streams, the same dimension sizes behind the
    foreign keys, whether a configuration makes the dimension or names it
    under `foreign_domains`."""
    session = spec.cell("tpcds_sf10_session.power2")["config"]
    assert CONFIG["seeding"]["structure_seed"] \
        == session["seeding"]["structure_seed"] == 2147750005
    assert CONFIG["seeding"]["from_the_run_seed"] \
        == session["seeding"]["from_the_run_seed"]
    assert gen.table_rows(CONFIG) | {"store": 102} \
        == gen.table_rows(session) | {"store": 102}
    other = gen.generate(session, BIG_SEED, SCALE)["store_sales"]
    for c, col in data["store_sales"].items():
        assert np.array_equal(col.values, other[c].values), c
        assert col.valid is other[c].valid is None \
            or np.array_equal(col.valid, other[c].valid), c


def test_item_brands_share_names_across_brand_ids(data):
    """Why the references group by the string: `i_brand` is coded by
    brand id and one name stands under several codes."""
    from perfbench.reference.q89 import by_string

    brand = data["item"]["i_brand"]
    assert len(brand.pool) == 2720 and len(set(brand.pool)) == 850
    names, code = by_string(brand)
    assert len(names) == 850 and names == sorted(names)
    assert [names[c] for c in code[:50]] == brand.strings()[:50].tolist()


@pytest.mark.parametrize("q", QUERIES)
def test_queries_are_the_repo_templates(q):
    with open(os.path.join(REPO, "tests", "tpcds", "queries",
                           q + ".sql"), "rb") as f, \
            open(os.path.join(REPO, "perfbench", "queries", q + ".sql"),
                 "rb") as g:
        assert f.read() == g.read()
    assert "limit" not in spec.query_text(q).lower().split()[-2:]


def test_planned_names_only_queries_that_plan_without_running():
    """Entry `session_whole` plans `planned`'s queries at set-up: one with
    a CTE would execute it there."""
    assert CONFIG["entry"] == "session_whole"
    assert CONFIG["planned"] == {"q89": "whole"}
    for q in CONFIG["planned"]:
        assert "with " not in spec.query_text(q).lower()[:10]


def test_warm_start_file_is_a_manifest_as_the_engine_writes_it():
    """`warm_start`: one record a plan, in `record_manifest`'s shape, with
    capacities only (no key spans: the configuration pins the dense probe
    off, and says why under `assumed`)."""
    import json

    conf = CONFIG["session_conf"]
    assert conf["spark.tpu.cache.dir"].startswith(".cache/")   # git-ignored
    assert conf["spark.tpu.cache.result.enabled"] is False
    assert conf["spark.tpu.fusion.denseKeys"] is False
    assert set(conf) == set(CONFIG["assumed"])
    path = os.path.join(REPO, CONFIG["warm_start"])
    with open(path) as f:
        records = [json.loads(line) for line in f]
    assert len({r["fp"] for r in records}) == len(records) == 2
    for r in records:
        assert set(r) == {"fp", "stages", "tier", "join_caps", "mesh_quotas",
                          "join_spans", "observed_rows"}
        assert r["tier"] == "whole" and len(r["join_caps"]) == 3
        assert all(c >= 1024 and c & (c - 1) == 0 for c in r["join_caps"])
        assert r["join_spans"] == [] and r["mesh_quotas"] == {}


def test_entry_adds_the_warm_start_records_the_manifest_lacks(tmp_path):
    """Once each, after what is there; a record the engine wrote for the
    same plan stays the only one."""
    import json

    from perfbench.entries.session_whole import Entry

    with open(os.path.join(REPO, CONFIG["warm_start"])) as f:
        ours = [json.loads(line) for line in f]
    root = tmp_path / "warm"
    config = dict(CONFIG, planned={},
                  session_conf={"spark.tpu.cache.dir": str(root)})
    Entry(None, config)
    manifest = root / "manifest.jsonl"
    assert [json.loads(x) for x in manifest.read_text().splitlines()] == ours
    Entry(None, config)                        # a second run: nothing new
    assert len(manifest.read_text().splitlines()) == len(ours)
    engines = dict(ours[0], join_caps=[1024, 2048, 4096])
    manifest.write_text(json.dumps({"fp": "another"}) + "\n"
                        + json.dumps(engines) + "\n")
    Entry(None, config)
    have = [json.loads(x) for x in manifest.read_text().splitlines()]
    assert have == [{"fp": "another"}, engines, ours[1]]


def test_a_plan_the_manifest_knows_runs_one_program(tmp_path):
    """What the warm-start directory is for: the first session climbs the
    capacity ladder of q89 and of q47's `v1` and the engine records where
    it ended; a session restarted onto the directory builds each plan's
    program once, at those capacities, and (dense keys off) adds no
    record, so the next one does the same."""
    from spark_tpu import TpuSession

    conf = dict(CONFIG["session_conf"])
    conf["spark.tpu.cache.dir"] = str(tmp_path / "warm")
    manifest = tmp_path / "warm" / "manifest.jsonl"
    # the rehearsal's scale: under it the planner keeps the reports by stages
    data = gen.generate(CONFIG, BIG_SEED, CONFIG["rehearsal"]["scale"])
    seen = []
    for _ in range(3):
        s = TpuSession("pb-window-restart", dict(conf))
        try:
            for name, tab in gen.arrow_tables(data).items():
                s.createDataFrame(tab).createOrReplaceTempView(name)
            rows = [s.sql(spec.query_text(q)).toArrow() for q in QUERIES]
            c = s._metrics.snapshot()["counters"]
        finally:
            s.stop()
        seen.append((rows, c.get("whole_query.capacity_retries", 0),
                     c.get("cache.capacity_seeded", 0),
                     manifest.read_text()))
    (rows0, climbed, seeded0, _), (rows1, again, seeded1, text1), \
        (rows2, third, seeded2, text2) = seen
    assert climbed > 0 and seeded0 <= 1      # q47's v1 runs once a q47
    assert again == third == 0 and seeded1 == seeded2 >= 2
    assert text1 == text2
    assert all(a.equals(b) and a.equals(c)
               for a, b, c in zip(rows0, rows1, rows2))


# ---------------------------------------------------------------------------
# the references
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sqlite_rows(data):
    from tests.tpcds.oracle import load_sqlite, rewrite_for_sqlite

    conn = load_sqlite(gen.arrow_tables(data))
    try:
        return {q: conn.execute(rewrite_for_sqlite(
            spec.query_text(q), q)).fetchall() for q in QUERIES}
    finally:
        conn.close()


@pytest.mark.parametrize("q", QUERIES)
def test_reference_agrees_with_the_sqlite_oracle(q, data, sqlite_rows):
    from tests.tpcds.oracle import compare_rows

    ref = reference.load(q)
    want = ref.run(data, reference.Exact())
    assert len(want) > 50, "too few rows to prove anything"
    ok, msg = compare_rows(want, sqlite_rows[q])
    assert ok, msg
    keys = [ref.order_key(r) for r in want]
    assert keys == sorted(keys)
    n = check.compare_rows(list(want), want, ref)
    assert not any(n.values())


def test_q47_has_no_row_for_a_partitions_first_and_last_month(data):
    """The self-join on rn - 1 and rn + 1 is an inner join: December 1998
    and January 2000 only lend their sums, and a partition whose 1999
    starts or ends without a neighbour loses that month."""
    want = reference.load("q47").run(data, reference.Exact())
    assert {r[4] for r in want} == {1999}
    first = {}
    for r in want:
        first.setdefault(r[:4], []).append(r[5])
    assert all(len(set(m)) == len(m) for m in first.values())
    assert any(r[8] is not None and r[9] is not None for r in want)


@pytest.mark.parametrize("total,average,far", [
    ("1150.47", "1278.300000", False),     # 127.83 below: just a tenth
    ("1150.46", "1278.300000", True),
    ("1406.13", "1278.300000", False),     # 127.83 above
    ("1406.14", "1278.300000", True),
    ("0.00", "0.000000", False),           # CASE WHEN avg > 0 ... END: NULL
    (None, "1278.300000", False),
    ("1150.47", None, False)],
    ids=["a_tenth_below", "over_a_tenth_below", "a_tenth_above",
         "over_a_tenth_above", "no_sales", "no_sum", "no_average"])
def test_a_month_just_a_tenth_from_the_average_is_not_returned(
        total, average, far):
    """The driver's seed 544970488 drew 1150.47 against 1278.300000 in
    q47: the quotient is 0.1, and `> 0.1` keeps no such row, whoever
    rounds."""
    from decimal import Decimal

    from perfbench.reference.q89 import far_from_average

    assert far_from_average(total and Decimal(total),
                            average and Decimal(average)) is far


def test_float32_control_comes_out_not_correct(data):
    """The references with sums and averages carried in float32, in the
    program's place: not correct, by the window's averages (a monthly
    sum of a few dozen prices is still exact to the cent in float32; a
    quotient to six places is not)."""
    total = {"unanswered": 0, "tier_mismatch": 0,
             "hidden_counters_moved": 0}
    for q in QUERIES:
        ref = reference.load(q)
        check.merge(total, check.compare_rows(
            ref.run(data, reference.Float32()),
            ref.run(data, reference.Exact()), ref))
    ok, compared = check.verdict(total)
    assert not ok
    assert compared["decimal_avg_max_abs_units"]["value"] \
        > compared["decimal_avg_max_abs_units"]["limit"]


# ---------------------------------------------------------------------------
# the cell, rehearsed; and the timed path broken underneath
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seconds,rounds", [(0, 1), (3600, 3)],
                         ids=["the_round_that_always_runs",
                              "the_three_the_traffic_file_allows"])
def test_cell_rehearses_correct(seconds, rounds):
    """dev2.json's `rounds_at_most` is 3 since PR 36 (two queries a
    window left the rate to one host pause): with room for more the
    window is three whole rounds, every execution compared."""
    assert spec.cell(CELL)["traffic"]["rounds_at_most"] == 3
    out = rehearse(CELL, seconds=seconds)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] == 2 * rounds
    assert out["window"]["queries"] == {"q89": rounds, "q47": rounds}
    assert out["window"]["rounds"] == [rounds]
    assert out["metrics"] == {}
    for c in out["compared"].values():
        assert c["value"] is not None and c["value"] <= c["limit"]


def test_the_cells_queries_build_their_programs_once(data):
    """Nothing compiles inside the window: the second execution of q89
    and of q47 (its `v1` and its self-join) finds every program the first
    built. On the chip each execution had compiled anew (PR 29, before
    `physical/compile.canonical_key` left CaseWhen's `branches` out)."""
    from spark_tpu import TpuSession
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    s = TpuSession("pb-window-warm", dict(CONFIG["session_conf"]))
    try:
        for name, tab in gen.arrow_tables(data).items():
            s.createDataFrame(tab).createOrReplaceTempView(name)
        for q in QUERIES:
            first = s.sql(spec.query_text(q)).toArrow()
            misses = KC.misses
            assert s.sql(spec.query_text(q)).toArrow().equals(first)
            assert KC.misses == misses, q
    finally:
        s.stop()


def _rewrite(old, new):
    """A client that sends the query with `old` replaced by `new`."""
    def fault(entry, session, tables):
        seen = []

        def around(run, text, annotate):
            if old in text:
                seen.append(1)
                text = text.replace(old, new)
            return run(text, annotate)
        _wrap_clients(entry, around)
        fault.seen = seen
    return fault


@pytest.mark.parametrize("old,new,number", [
    # q89: the brand's average over all stores, not the store's
    ("(PARTITION BY i_category, i_brand, s_store_name, s_company_name)",
     "(PARTITION BY i_category, i_brand, s_company_name)",
     "decimal_avg_max_abs_units"),
    # q47: one rank over all of v1 by month, so rn +- 1 finds no
    # neighbour within the four keys
    ("(PARTITION BY i_category, i_brand,\n      s_store_name, "
     "s_company_name\n      ORDER BY d_year, d_moy) rn",
     "(ORDER BY d_year, d_moy) rn", "rows_wrong")],
    ids=["average_over_the_wrong_partition", "rank_that_does_not_restart"])
def test_a_broken_window_is_not_correct(old, new, number):
    fault = _rewrite(old, new)
    out = rehearse(CELL, fault, seconds=3)
    assert fault.seen, "the fault was never planted"
    assert out["correct"] is False
    c = out["compared"][number]
    assert c["value"] > c["limit"], out["compared"]
    assert out["failed"] >= 1


def test_a_program_that_plans_the_reports_by_stages_cannot_run_the_cell(
        monkeypatch, capsys):
    """What the parent commit of PR 29 does: its planner refuses the
    window, q89 is planned on the stage tier, and the run ends at set-up
    with the planner's reason and no result line."""
    import spark_tpu.physical.whole_query as wq

    real = wq.supported_whole_query

    def refuse_windows(plan, conf, history_ok=False):
        if any(type(n).__name__ == "WindowExec"
               for n in wq._iter_inner(plan)):
            return False, "operator WindowExec has no whole-query lowering"
        return real(plan, conf, history_ok)

    monkeypatch.setattr(wq, "supported_whole_query", refuse_windows)
    with pytest.raises(SystemExit) as e:
        rehearse(CELL, seconds=1)
    assert "cannot run configuration 'tpcds_sf10_window'" in str(e.value)
    assert "plans it on 'stage'" in str(e.value)
    assert "WindowExec has no whole-query lowering" in str(e.value)
    assert capsys.readouterr().out == ""


# ---------------------------------------------------------------------------
# the three readers
# ---------------------------------------------------------------------------

RECORDS = [{"t_submit": 100.0, "t_done": 110.0, "error": None},
           {"t_submit": 110.0, "t_done": 126.0, "error": None}]


SPANS = [
    _span("cte.materialize", 60.0, 15000.0, cte="v1", rows=9),   # warm-up
    _span("whole_query.attempt", 100.1, 700.0, discarded=True,
          window_members=1),
    _span("whole_query.attempt", 100.9, 9000.0, discarded=False,
          window_members=1),
    _span("cte.materialize", 110.1, 15400.0, cte="v1", rows=9),
    _span("whole_query.attempt", 110.2, 15000.0, discarded=False,
          window_members=2),
    _span("whole_query.attempt", 125.6, 400.0, discarded=False,
          window_members=0),
]
COUNTERS = {"before": {"counters": {"by_kind": {"whole_query": 7,
                                                "pipeline": 3}}},
            "after": {"counters": {"by_kind": {"whole_query": 11,
                                               "pipeline": 7, "sort": 1,
                                               "mesh_whole": 2}}}}
EXPECTED = {"cte_materialize_s_per_query": 15.4 / 2,
            "window_program_s_per_query": (9.0 + 15.0) / 2,
            "stage_launches_per_query": (4 + 1) / 2}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_planted_window(name, monkeypatch):
    _plant(monkeypatch, SPANS)
    value = spec.metric_reader(name).read({"records": RECORDS, **COUNTERS})
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_silent_where_there_is_nothing_to_read(name, monkeypatch):
    """No span so named, the parent's spans (an attempt says nothing of
    `window_members`), a program without `recorded_spans`, counters
    without kinds, or no query answered: None, and no exception."""
    import spark_tpu.obs.tracing as tracing

    reader = spec.metric_reader(name)
    empty = {"before": {"counters": {}}, "after": {"counters": {}}}
    _plant(monkeypatch, [_span("whole_query.attempt", 100.9, 9000.0,
                               discarded=False)])
    assert reader.read({"records": RECORDS, **empty}) is None
    assert reader.read({"records": [], **COUNTERS}) is None
    monkeypatch.delattr(tracing, "recorded_spans")
    assert reader.read({"records": RECORDS, **empty}) is None


def test_no_stage_launch_reads_zero():
    same = {"before": COUNTERS["before"],
            "after": {"counters": {"by_kind": {"whole_query": 9,
                                               "pipeline": 3}}}}
    assert spec.metric_reader("stage_launches_per_query").read(
        {"records": RECORDS, **same}) == 0.0
