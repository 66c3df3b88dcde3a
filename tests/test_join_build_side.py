"""An inner join by stages builds its index over the smaller side (PR
37): `ReorderJoins` leaves a filtered dimension on the left and the fact
table's flow on the right, which is the build side as planned; where the
left holds a quarter of the right's slots or fewer, `HashJoinExec` turns
the roles around at run time and puts the columns back in order."""

import numpy as np
import pyarrow as pa
import pytest

N_FACT, N_DIM = 40000, 60


@pytest.fixture(scope="module")
def session():
    from spark_tpu import TpuSession
    from spark_tpu.physical.operators import HashJoinExec

    # the rule at the tests' size: its floor of 4 Mi slots taken away
    floor = HashJoinExec.BUILD_LEFT_MIN_SLOTS
    HashJoinExec.BUILD_LEFT_MIN_SLOTS = 0

    s = TpuSession("join-build-side", {
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.compile.tier": "stage",
        "spark.tpu.cache.result.enabled": "false",
        # the planner must not broadcast the dimension by itself
        "spark.sql.adaptive.enabled": "false"})
    rng = np.random.default_rng(5)
    key = rng.integers(0, N_DIM + 20, N_FACT).astype(np.int32)
    s.createDataFrame(pa.table({
        "fk": pa.array(key, mask=rng.random(N_FACT) < 0.05),
        "v": rng.integers(0, 1000, N_FACT).astype(np.int64)})) \
        .createOrReplaceTempView("bs_fact")
    s.createDataFrame(pa.table({
        "dk": np.arange(N_DIM, dtype=np.int32),
        "w": (np.arange(N_DIM) % 7).astype(np.int32)})) \
        .createOrReplaceTempView("bs_dim")
    yield s
    s.stop()
    HashJoinExec.BUILD_LEFT_MIN_SLOTS = floor


def _run(session, text):
    df = session.sql(text)
    table = df.toArrow()
    counters = df.query_execution._last_ctx.metrics.local_counters()
    return table, counters.get("join.build_swapped", 0)


def _rows(table):
    return sorted(zip(*[c.to_pylist() for c in table.columns]),
                  key=lambda r: tuple((x is None, x) for x in r))


# the dimension first, as ReorderJoins leaves a chain: it is the left side
SMALL_LEFT = ("select dk, w, fk, v from bs_dim join bs_fact on dk = fk "
              "where w < 5")


def test_the_smaller_left_side_is_built_and_the_rows_are_the_same(
        session, monkeypatch):
    from spark_tpu.physical.operators import HashJoinExec

    got, swapped = _run(session, SMALL_LEFT)
    assert swapped == 1
    assert got.column_names == ["dk", "w", "fk", "v"]
    monkeypatch.setattr(HashJoinExec, "BUILD_LEFT_RATIO", 10 ** 9)
    planned, unswapped = _run(session, SMALL_LEFT)
    assert unswapped == 0
    assert planned.schema == got.schema
    assert _rows(planned) == _rows(got) and got.num_rows > N_FACT // 3
    # and they are the join: every fact row with a key among the
    # dimension's rows that pass the filter, once
    fact = session.sql("select fk, v from bs_fact").toArrow().to_pydict()
    want = sorted((k, k % 7, k, v) for k, v in zip(fact["fk"], fact["v"])
                  if k is not None and k < N_DIM and k % 7 < 5)
    assert _rows(got) == sorted(
        want, key=lambda r: tuple((x is None, x) for x in r))


def test_an_aggregate_above_the_swapped_join_reads_the_right_columns(
        session):
    got, swapped = _run(session, "select w, count(*) c, sum(v) s from "
                        "bs_dim join bs_fact on dk = fk group by w "
                        "order by w")
    assert swapped == 1
    fact = session.sql("select fk, v from bs_fact").toArrow().to_pydict()
    want = {}
    for k, v in zip(fact["fk"], fact["v"]):
        if k is not None and k < N_DIM:
            c, t = want.get(k % 7, (0, 0))
            want[k % 7] = (c + 1, t + v)
    assert [tuple(r) for r in zip(*[c.to_pylist() for c in got.columns])] \
        == [(w, c, t) for w, (c, t) in sorted(want.items())]


@pytest.mark.parametrize("text", [
    # the larger side on the left already: built on the right, as planned
    "select fk, v, w from bs_fact join bs_dim on fk = dk",
    # an outer join's sides are not interchangeable
    "select dk, v from bs_dim left join bs_fact on dk = fk",
    # two sides of a size
    "select a.dk, b.w from bs_dim a join bs_dim b on a.dk = b.dk",
], ids=["large_left", "left_outer", "equal_sides"])
def test_other_joins_build_the_side_the_planner_chose(session, text):
    table, swapped = _run(session, text)
    assert swapped == 0 and table.num_rows > 0


def test_the_rule_reads_capacities_and_one_partition_a_side(monkeypatch):
    from spark_tpu.physical.operators import HashJoinExec, _SchemaOnly

    monkeypatch.setattr(HashJoinExec, "BUILD_LEFT_MIN_SLOTS", 1 << 22)

    join = HashJoinExec([], [], "inner", _SchemaOnly([]), _SchemaOnly([]))
    Mi = 1 << 20
    rule = join.builds_the_larger_side
    assert rule([[Mi]], [[4 * Mi]])
    assert rule([[Mi]], [[2 * Mi] * 2])
    assert rule([[8192]], [[4 * Mi] * 7])
    assert not rule([[Mi]], [[2 * Mi]])
    # under the floor the planner's sides stand, whatever the ratio
    assert not rule([[1024]], [[2 * Mi]])
    assert not rule([[4 * Mi]], [[Mi]])
    assert not rule([[]], [[4 * Mi]])
    assert not rule([[Mi], [Mi]], [[4 * Mi], [4 * Mi]])
    join.join_type = "left_outer"
    assert not rule([[Mi]], [[4 * Mi]])


def test_the_plan_analyzer_says_the_sides_turn_around(session, capsys):
    """Its launch model is the planned sides': where the rule fires it
    says so and claims no exactness for that join."""
    session.sql(SMALL_LEFT).explain(mode="analysis")
    out = capsys.readouterr().out
    assert "built on the left at run time" in out
    assert "builds its smaller (left) side at run time" in out
    session.sql("select fk, v, w from bs_fact join bs_dim on fk = dk") \
        .explain(mode="analysis")
    assert "built on the left" not in capsys.readouterr().out


@pytest.mark.parametrize("kind", ["join", "left join"])
def test_a_dense_joins_build_key_is_the_probe_key_where_matched(session,
                                                               kind):
    """The dense probe does not fetch the build side's key column: a
    matched row's build key is its probe key, an unmatched row's NULL;
    its other columns are fetched by the build row as before. The key
    types differ here (int64 against int32) and the probe key has NULLs."""
    import pyarrow as pa

    session.createDataFrame(pa.table({
        "dk64": np.arange(10, 10 + N_DIM, dtype=np.int64),
        "w": (np.arange(N_DIM) % 7).astype(np.int32)})) \
        .createOrReplaceTempView("bs_dim64")
    df = session.sql(f"select fk, v, dk64, w from bs_fact {kind} bs_dim64 "
                     "on fk = dk64")
    got = df.toArrow()
    counters = df.query_execution._last_ctx.metrics.local_counters()
    assert counters["join.dense_fast_path"] == 1
    assert "join.build_swapped" not in counters
    assert got.schema.field("dk64").type == pa.int64()
    fact = session.sql("select fk, v from bs_fact").toArrow().to_pydict()
    want = []
    for k, v in zip(fact["fk"], fact["v"]):
        hit = k is not None and 10 <= k < 10 + N_DIM
        if hit:
            want.append((k, v, k, (k - 10) % 7))
        elif kind == "left join":
            want.append((k, v, None, None))
    assert _rows(got) == sorted(
        want, key=lambda r: tuple((x is None, x) for x in r))
