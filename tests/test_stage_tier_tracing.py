"""The stage tier under the engine's own names (PR 37): a plan the whole
tier does not take leaves one `stage.run` span a stage and one
`shuffle.host` span an exchange, its kernels are XLA modules
`jit_<kind>_<hash>` under the same names in every process, and
explain(mode="device") gives their device time to the operators that
launched them."""

import os
import re
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = "select k, sum(v) s, count(*) c from st_t group by k order by k"
KEYLESS = "select sum(v) s, count(*) c from st_t where k < 7"
# three partitions: not a power of two, so every exchange goes through the
# host whatever devices the test process sees (parallel/mesh_exchange)
CONF = {"spark.sql.shuffle.partitions": 3,
        "spark.tpu.batch.capacity": 1 << 12,
        "spark.tpu.cache.result.enabled": "false"}
STAGE_SPANS = ("stage.run", "shuffle.host")


def _register(session):
    rng = np.random.default_rng(11)
    n = 20000
    t = pa.table({"k": rng.integers(0, 50, n).astype(np.int32),
                  "v": rng.integers(0, 1000, n).astype(np.int64)})
    # three partitions of tiles: the group-by is partial, exchanged, final
    session.createDataFrame(t).repartition(3) \
        .createOrReplaceTempView("st_t")


@pytest.fixture(scope="module")
def session():
    from spark_tpu import TpuSession

    s = TpuSession("stage-tracing", {**CONF,
                                     "spark.tpu.compile.tier": "stage"})
    _register(s)
    yield s
    s.stop()


def _traced(session, text):
    """(spans of one warm execution, launches by kind it moved, the
    launches `capture_programs` noted)."""
    from spark_tpu.obs.tracing import recorded_spans
    from spark_tpu.physical.compile import (GLOBAL_KERNEL_CACHE as KC,
                                            capture_programs)

    session.sql(text).toArrow()
    before = dict(KC.launches_by_kind)
    t0 = time.perf_counter()
    with capture_programs() as captured:
        session.sql(text).toArrow()
    t1 = time.perf_counter()
    moved = {k: v - before.get(k, 0) for k, v in KC.launches_by_kind.items()
             if v != before.get(k, 0)}
    return recorded_spans(t0, t1), moved, captured.launches


def test_every_stage_and_every_exchange_leaves_a_span(session):
    spans, moved, _launches = _traced(session, QUERY)
    stages = [s["args"] for s in spans if s["name"] == "stage.run"]
    exchanges = [s["args"] for s in spans if s["name"] == "shuffle.host"]
    assert [a["stage"] for a in stages] == list(range(1, len(stages) + 1))
    assert len(stages) >= 3 and all(a["attempt"] == 1 for a in stages)
    # every kernel of the query was launched inside some stage
    assert sum(a["launches"] for a in stages) == sum(moved.values()) > 0
    assert not {"whole_query", "mesh_whole"} & set(moved)
    assert "HashAggregate" in stages[1]["operators"]
    assert stages[-1]["operators"].startswith("Sort")
    assert all(a["tiles"] >= 1 for a in stages)
    # one exchange a stage below the last, each with its partitions
    assert len(exchanges) == len(stages) - 1
    assert all(a["partitions"] == 3 for a in exchanges)
    assert {a["kind"] for a in exchanges} <= {"round_robin", "hash",
                                              "fused", "range"}


def test_an_exchanges_bytes_are_the_batches_it_moved(session):
    """Through the host: what `shuffle.bytes_shipped` counts on the way
    out, and the rebuilt device batches on the way back."""
    from spark_tpu.obs.tracing import recorded_spans

    session.sql(QUERY).toArrow()
    t0 = time.perf_counter()
    df = session.sql(QUERY)
    df.toArrow()
    t1 = time.perf_counter()
    exchanges = [s["args"] for s in recorded_spans(t0, t1)
                 if s["name"] == "shuffle.host"]
    counters = df.query_execution._last_ctx.metrics.local_counters()
    shipped = sum(a["bytes_d2h"] for a in exchanges)
    assert shipped > 0 and all(a["bytes_h2d"] > 0 for a in exchanges)
    # the first exchange moves the whole table: 20 000 rows of an int32
    # key and an int64 value, neither nullable
    assert exchanges[0]["bytes_d2h"] == 20000 * (4 + 8)
    # the rebuilt tiles are whole capacities: never less than the rows
    assert all(a["bytes_h2d"] >= a["bytes_d2h"] for a in exchanges)
    assert counters["shuffle.bytes_shipped"] == shipped


def test_a_broadcast_stays_on_the_device_and_says_so(session):
    """A one-row subquery cross-joined with another: the exchange is a
    broadcast of device batches, and its span carries no bytes."""
    text = ("select * from (select count(*) a from st_t where k < 7) x, "
            "(select count(*) b from st_t where k >= 7) y")
    spans, _moved, _launches = _traced(session, text)
    got = [s["args"] for s in spans if s["name"] == "shuffle.host"
           and s["args"]["kind"] == "broadcast"]
    assert got and all(a["bytes_d2h"] == 0 == a["bytes_h2d"]
                       and a["partitions"] == 1 for a in got)
    table = session.sql(text).toArrow().to_pylist()
    assert table[0]["a"] + table[0]["b"] == 20000


def test_stage_kernels_are_modules_named_by_kind(session):
    _spans, moved, launches = _traced(session, QUERY)
    assert len(launches) == sum(moved.values())
    for program, kind, op in launches:
        assert re.fullmatch(rf"jit_{re.escape(kind)}_[0-9a-f]{{10}}",
                            program), (program, kind)
        row, name = op               # the operator that launched it
        assert isinstance(row, int) and name.endswith("Exec")
    assert {kind for _p, kind, _op in launches} == set(moved)


_CHILD = r'''
import sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import test_stage_tier_tracing as T
from spark_tpu import TpuSession
from spark_tpu.physical.compile import capture_programs
s = TpuSession("names", {{**T.CONF, "spark.tpu.compile.tier": "stage"}})
T._register(s)
with capture_programs() as captured:
    s.sql(T.QUERY).toArrow()
print("KERNELS", ",".join(sorted({{p for p, _k, _o in captured.launches}})))
s.stop()
'''


def test_two_processes_give_a_stage_kernel_the_same_name(session):
    """The module name is part of XLA's disk-cache key: a name that moved
    between processes would compile every kernel on every start."""
    code = _CHILD.format(repo=REPO, tests=os.path.join(REPO, "tests"))
    names = []
    for seed in ("1", "2"):       # hash() of a str differs between them
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env={**os.environ, "PYTHONHASHSEED": seed})
        assert out.returncode == 0, out.stderr[-2000:]
        names.append(re.search(r"^KERNELS (.*)$", out.stdout, re.M).group(1))
    assert names[0] == names[1]
    _spans, _moved, launches = _traced(session, QUERY)
    assert ",".join(sorted({p for p, _k, _o in launches})) == names[0]


def test_stage_jit_needs_the_key_it_is_built_under():
    from spark_tpu.physical import compile as C

    with pytest.raises(RuntimeError, match="get_or_build"):
        C.stage_jit(lambda x: x)
    cache = C.KernelCache()
    kernel = cache.get_or_build(("st_probe_kind", 3, "int32"),
                                lambda: C.stage_jit(lambda x: x + 1))
    assert re.fullmatch(r"jit_st_probe_kind_[0-9a-f]{10}",
                        C.module_name(kernel))
    again = C.KernelCache().get_or_build(
        ("st_probe_kind", 4, "int32"), lambda: C.stage_jit(lambda x: x + 1))
    assert C.module_name(again) != C.module_name(kernel)
    assert int(kernel(np.int32(1))) == 2


def test_a_whole_tier_query_leaves_no_stage_span():
    from spark_tpu import TpuSession
    from spark_tpu.obs.tracing import recorded_spans

    s = TpuSession("stage-tracing-whole",
                   {**CONF, "spark.tpu.compile.tier": "whole"})
    try:
        _register(s)
        t0 = time.perf_counter()
        s.sql(QUERY).toArrow()
        spans = recorded_spans(t0, time.perf_counter())
    finally:
        s.stop()
    names = {sp["name"] for sp in spans}
    assert "whole_query.attempt" in names
    assert not names & set(STAGE_SPANS)


def test_explain_device_names_the_operators_of_a_stage_tier_query(
        session, capsys):
    session.sql(QUERY).explain(mode="device")
    out = capsys.readouterr().out
    assert "== Device Profile ==" in out
    assert "not a device's times" in out      # the CPU backend says so
    head = re.search(r"^stage tier: (\d+) kernel launches, [\d.]+ ms on "
                     r"the device, ([\d.]+) % of it in kernels an operator "
                     r"launched$", out, re.M)
    assert head and int(head.group(1)) > 0 and float(head.group(2)) > 50
    rows = re.findall(r"^  (m\d+\.\w+) +[\d.]+ ms +[\d.]+ %  (\S.*)$", out,
                      re.M)
    kinds = {label.split(".")[1] for label, _text in rows}
    assert {"Sort", "HashAggregate", "ShuffleExchange"} <= kinds
    assert any(text.startswith("HashAggregate[partial]")
               for _label, text in rows)
    # under an operator's row, its kernels by kind with their launches
    assert re.search(r"^    sort +[\d.]+ ms +[\d.]+ %  x1$", out, re.M)
    assert re.search(r"^  unattributed +[\d.]+ ms", out, re.M)
    assert "launched no named program" not in out


def test_launches_are_matched_to_module_runs_in_order():
    from spark_tpu.obs.device_profile import attribute_launches

    planes = {"/device:TPU:0": {"XLA Modules": [
        ("jit_pipeline_aa(1)", 100, 10), ("jit_gagg_bb(2)", 120, 30),
        ("jit_pipeline_aa(1)", 160, 12), ("jit_concatenate(3)", 180, 5),
        ("jit_whole_query_cc(4)", 200, 1000),
        ("jit_pipeline_aa(1)", 1300, 7)], "XLA Ops": []}}
    launches = [("jit_pipeline_aa", "pipeline", (4, "ProjectExec")),
                ("jit_gagg_bb", "gagg", (2, "HashAggregateExec")),
                ("jit_pipeline_aa", "pipeline", (2, "HashAggregateExec")),
                ("jit_whole_query_cc", "whole_query", None)]
    found = attribute_launches(planes, launches,
                               skip={"jit_whole_query_cc"})
    assert found["rows"] == {
        (4, "ProjectExec"): {"pipeline": [10, 1]},
        (2, "HashAggregateExec"): {"gagg": [30, 1], "pipeline": [12, 1]}}
    # a run past the launches, and a module no kernel launch names
    assert found["unnamed"] == {"jit_concatenate": [5, 1],
                                "jit_pipeline_aa": [7, 1]}
    assert found["device_ns"] == 10 + 30 + 12 + 5 + 7


def test_a_projection_keeps_a_row_count_the_host_has():
    """A filter makes the count unknown again; a projection does not."""
    from spark_tpu.columnar.batch import ColumnarBatch
    from spark_tpu.expr.expressions import (AttributeReference,
                                            GreaterThan, Literal)
    from spark_tpu.physical.compile import ExprPipeline
    from spark_tpu.physical.operators import attrs_schema
    from spark_tpu.types import LongType

    a = AttributeReference("a", LongType(), False)
    schema = attrs_schema([a])
    batch = ColumnarBatch.from_numpy(schema, [np.arange(8, dtype=np.int64)])
    assert batch._num_rows == 8
    kept = ExprPipeline([a], [], [a], schema).run(batch)
    assert kept._num_rows == 8
    cut = ExprPipeline([a], [GreaterThan(a, Literal(3, LongType()))], [a],
                       schema).run(batch)
    assert cut._num_rows is None and cut.num_rows() == 4


def test_a_cross_join_of_one_row_results_reads_no_count_off_the_device(
        session, monkeypatch):
    """TPC-DS's one-row reports are cross joins of keyless aggregates:
    every side's count is the host's (1), so is the pairs', and no
    `num_rows()` has to ask the device (each such read waits for all the
    kernels queued before it)."""
    from spark_tpu.columnar.batch import ColumnarBatch

    text = ("select * from (select count(*) a, sum(v) s from st_t where "
            "k < 7) x, (select count(*) b from st_t where k >= 7) y, "
            "(select max(v) m from st_t) z")
    want = session.sql(text).toArrow().to_pylist()
    asked = []
    real = ColumnarBatch.num_rows

    def spy(self):
        if self._num_rows is None:
            asked.append(self.schema)
        return real(self)

    monkeypatch.setattr(ColumnarBatch, "num_rows", spy)
    df = session.sql(text)
    assert df.toArrow().to_pylist() == want and len(want) == 1
    assert asked == []
    assert want[0]["a"] + want[0]["b"] == 20000
