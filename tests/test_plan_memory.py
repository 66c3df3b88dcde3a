"""What a plan learned, remembered by the process
(exec/persist_cache.PlanMemory behind persist_cache.plan_seed).

Contract under test: without spark.tpu.cache.dir the capacity ladder of a
whole-query plan is climbed once a process — the next execution of the
same plan, from the same session or a cloned one, starts at the join
capacities the last one ended with, which is the key of the ladder's
final program, so nothing compiles; a remembered capacity is a first
guess (data that drifts under one fingerprint re-enters the ordinary
retry loop, or runs in a roomier program) and never a span (the dense
probe is reached through the manifest alone); with a cache dir the
manifest seeds as before and a steady execution appends nothing; the
memory is bounded, locked and can be emptied (tests/conftest.py empties
it before every test)."""

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pytest

import spark_tpu.exec.persist_cache as pc
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.physical.compile import capture_programs

N_FACT = 3000
QUERY = ("select pm_fact.fk, count(*) n, sum(fv) s from pm_fact "
         "join pm_dim on pm_fact.fk = pm_dim.dk group by pm_fact.fk "
         "order by pm_fact.fk")


def _dim(session, matches: int):
    """64 dimension rows whatever `matches` is, each of the fact table's
    eight keys `matches` times among them, between the same five keys at
    either end (a scan's line in the fingerprint shows a table's schema
    and its first and last values): the same fingerprint, another join
    output."""
    held = np.repeat(np.arange(8, dtype=np.int64), matches)
    keys = np.concatenate([
        np.arange(100, 105), held,
        np.arange(200, 200 + 54 - len(held)), np.arange(105, 110)])
    session.createDataFrame(pa.table({
        "dk": keys.astype(np.int64), "tag": np.arange(64, dtype=np.int64),
    })).createOrReplaceTempView("pm_dim")


@pytest.fixture()
def whole(spark):
    rng = np.random.default_rng(38)
    spark.createDataFrame(pa.table({
        "fk": rng.integers(0, 8, N_FACT),
        "fv": rng.integers(0, 50, N_FACT),
    })).createOrReplaceTempView("pm_fact")
    _dim(spark, 2)
    spark.conf.set("spark.tpu.fusion.minRows", "0")
    spark.conf.set("spark.tpu.compile.tier", "whole")
    yield spark
    for k in ("spark.tpu.compile.tier", "spark.tpu.fusion.minRows"):
        spark.conf.unset(k)


def _run(session, query=QUERY):
    """One execution: (rows, programs launched in order, counters moved,
    KernelCache misses)."""
    before = dict(session._metrics.snapshot()["counters"])
    misses = KC.misses
    with capture_programs() as programs:
        rows = session.sql(query).toArrow().to_pylist()
    after = session._metrics.snapshot()["counters"]
    moved = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    return (rows, [p["program"] for p in programs], moved,
            KC.misses - misses)


def _stage_rows(session, query=QUERY):
    session.conf.set("spark.tpu.compile.tier", "stage")
    try:
        return session.sql(query).toArrow().to_pylist()
    finally:
        session.conf.set("spark.tpu.compile.tier", "whole")


def _fingerprint(session, query=QUERY):
    return session.sql(query).query_execution.plan_fingerprint()[
        "fingerprint"]


def test_second_execution_is_the_ladders_final_program(whole):
    want = _stage_rows(whole)
    rows, ladder, moved, _ = _run(whole)
    assert rows == want
    assert len(ladder) >= 2 and len(set(ladder)) == len(ladder), ladder
    assert moved["whole_query.capacity_retries"] == len(ladder) - 1
    assert "cache.capacity_seeded" not in moved
    assert pc.PLAN_MEMORY.get(_fingerprint(whole))
    rows, programs, moved, misses = _run(whole)
    # the seeded first attempt has the key of the ladder's final program,
    # which the first execution compiled: one launch, no compile
    assert programs == ladder[-1:], (programs, ladder)
    assert misses == 0
    assert moved["whole_query.dispatches"] == 1
    assert "whole_query.capacity_retries" not in moved
    assert moved["cache.capacity_seeded"] == 1
    assert moved["cache.capacity_remembered"] == 1
    # capacities only: no span comes back, so no dense variant is lowered
    assert "cache.join_span_seeded" not in moved
    assert "whole_query.dense_probe" not in moved
    assert rows == want


def test_analysis_mirrors_the_memory(whole):
    """The analyzer goes through the same lookup: the ladder before the
    plan has run, one launch after."""
    qe = whole.sql(QUERY).query_execution
    cold = qe.analysis_report()
    _rows, ladder, _moved, _ = _run(whole)
    assert cold.exact and cold.predicted_launches \
        == {"whole_query": len(ladder)}, cold.render()
    warm = whole.sql(QUERY).query_execution.analysis_report()
    assert warm.exact and warm.predicted_launches == {"whole_query": 1}
    pc.PLAN_MEMORY.clear()
    assert whole.sql(QUERY).query_execution.analysis_report() \
        .predicted_launches == cold.predicted_launches


@pytest.mark.parametrize("matches,resumes", [(6, True), (1, False)],
                         ids=["more_matches", "fewer_matches"])
def test_drift_under_one_fingerprint(whole, matches, resumes):
    """A view replaced by a table of the same row count: the remembered
    capacity is a first guess. Too small now, the ordinary retry loop
    goes on from it and the memory takes the new outcome; too large, the
    same rows come from a roomier program."""
    fp = _fingerprint(whole)
    _run(whole)
    learned = pc.PLAN_MEMORY.get(fp)
    _dim(whole, matches)
    assert _fingerprint(whole) == fp
    want = _stage_rows(whole)
    rows, programs, moved, _ = _run(whole)
    assert rows == want and want[0]["n"] > 0
    assert moved["cache.capacity_remembered"] == 1
    if resumes:
        assert moved["whole_query.capacity_retries"] == len(programs) - 1 \
            >= 1
        now = pc.PLAN_MEMORY.get(fp)
        assert len(now) == len(learned) and now != learned
        assert all(a >= b for a, b in zip(now, learned))
        rows, programs, moved, _ = _run(whole)
        assert rows == want and len(programs) == 1
    else:
        assert len(programs) == 1
        assert "whole_query.capacity_retries" not in moved
        assert pc.PLAN_MEMORY.get(fp) == learned


def test_cloned_session_starts_from_what_the_parent_learned(whole):
    want, ladder, _moved, _ = _run(whole)
    clone = whole.newSession()
    rows, programs, moved, misses = _run(clone)
    assert rows == want
    assert programs == ladder[-1:] and misses == 0
    assert moved["cache.capacity_remembered"] == 1


def test_two_threads_on_one_plan_give_equal_rows(whole):
    want = _stage_rows(whole)
    sessions = [whole.newSession(), whole.newSession()]
    gate = threading.Barrier(2)

    def tenant(session):
        gate.wait(timeout=60)
        return [session.sql(QUERY).toArrow().to_pylist() for _ in range(3)]

    with ThreadPoolExecutor(2) as pool:
        answers = list(pool.map(tenant, sessions))
    assert all(rows == want for got in answers for rows in got)
    rows, programs, moved, _ = _run(whole)
    assert rows == want and len(programs) == 1
    assert moved["cache.capacity_remembered"] == 1


def test_with_a_cache_dir_the_manifest_seeds_and_stays_steady(tmp_path):
    from spark_tpu import TpuSession

    s = TpuSession("pm-manifest", {
        "spark.sql.shuffle.partitions": 4,
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.compile.tier": "whole",
        "spark.tpu.cache.dir": str(tmp_path),
        "spark.tpu.cache.result.enabled": "false",
    })
    try:
        rng = np.random.default_rng(38)
        s.createDataFrame(pa.table({
            "fk": rng.integers(0, 8, N_FACT),
            "fv": rng.integers(0, 50, N_FACT),
        })).createOrReplaceTempView("pm_fact")
        _dim(s, 2)
        manifest = pc._manifest(s.conf)
        want, ladder, _moved, _ = _run(s)
        assert len(ladder) >= 2
        fp = _fingerprint(s)
        written = manifest.load()
        assert [r["fp"] for r in written] == [fp]
        # one door: with a cache dir it hands out the manifest's record,
        # whole (record_manifest compares every field of it), and the
        # memory's capacities only where the manifest has none
        seed = pc.plan_seed(s.conf, fp)
        assert seed == pc.manifest_seed(s.conf, fp) == written[0]
        assert "remembered" not in seed
        assert list(pc.PLAN_MEMORY.get(fp)) == seed["join_caps"]
        rows, programs, moved, _ = _run(s)
        assert rows == want and len(programs) == 1
        assert moved["cache.capacity_seeded"] == 1
        assert "cache.capacity_remembered" not in moved
        _run(s)
        assert manifest.load() == written       # steady: nothing appended
        # a remembered record carries capacities and nothing else
        s.conf.set("spark.tpu.cache.dir", "")
        assert _fingerprint(s) == fp
        assert pc.plan_seed(s.conf, fp) == {
            "join_caps": seed["join_caps"], "remembered": True}
    finally:
        s.stop()


def test_the_memory_is_bounded_and_can_be_emptied():
    mem = pc.PlanMemory(max_size=2)
    mem.put("a", [1024])
    mem.put("b", [2048, 4096])
    mem.put("a", [8192])            # the newest again: "b" is the oldest
    mem.put("c", [1024])
    assert len(mem) == 2
    assert mem.get("b") is None
    assert mem.get("a") == (8192,) and mem.get("c") == (1024,)
    mem.clear()
    assert len(mem) == 0 and mem.get("a") is None
    from spark_tpu.config import SQLConf

    assert pc.plan_seed(SQLConf({}), "never-run") is None
