"""The device's timeline under the engine's own names (PR 25): named
scopes and stable names in the whole-query program, the tracer's spans on
the profiler's clock, `recorded_spans`, and explain(mode="device")."""

import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERY = ("select a, b, sum(v) s from dt_fact join dt_d1 on dt_fact.k1 = "
         "dt_d1.k1 join dt_d2 on dt_fact.k2 = dt_d2.k2 where a < 5 "
         "group by a, b order by a, b")


def _register(session):
    rng = np.random.default_rng(3)
    n = 5000
    tables = {
        "dt_fact": pa.table({"k1": rng.integers(0, 100, n),
                             "k2": rng.integers(0, 50, n),
                             "v": rng.integers(0, 1000, n)}),
        # two rows a key: the join expands past its first capacity
        "dt_d1": pa.table({"k1": np.repeat(np.arange(100), 2),
                           "a": np.arange(200) % 7}),
        "dt_d2": pa.table({"k2": np.arange(50), "b": np.arange(50) % 3}),
    }
    for name, t in tables.items():
        session.createDataFrame(t).createOrReplaceTempView(name)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    from spark_tpu import TpuSession

    s = TpuSession("device-tracing", {
        "spark.sql.shuffle.partitions": 4,
        "spark.tpu.compile.tier": "whole",
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cache.result.enabled": "false",
        "spark.tpu.cache.dir": str(tmp_path_factory.mktemp("manifest")),
    })
    _register(s)
    yield s
    s.stop()


def _attempts(spans):
    return [s for s in spans if s["name"] == "whole_query.attempt"]


def test_recorded_spans_say_which_attempts_were_discarded(session):
    """A join capacity too small throws the first programs away; with the
    capacities seeded from the manifest the first attempt stands."""
    import time

    from spark_tpu.obs.tracing import recorded_spans

    t0 = time.perf_counter()
    cold = session.sql(QUERY).toArrow()
    t1 = time.perf_counter()
    attempts = _attempts(recorded_spans(t0, t1))
    assert len(attempts) >= 2, attempts
    assert [a["args"]["discarded"] for a in attempts] \
        == [True] * (len(attempts) - 1) + [False]
    assert len({a["query"] for a in attempts}) == 1
    for a in attempts:
        assert a["args"]["program"].startswith("jit_whole_query_")
        assert a["args"]["est_resident_bytes"] > 0
        assert a["args"]["ledger_bytes"] >= 0
    inside = {s["name"] for s in recorded_spans(t0, t1)}
    assert {"whole_query.lower", "whole_query.launch", "whole_query.verdict",
            "ingest.h2d", "kernel.first_launch", "collect", "collect.d2h",
            "collect.arrow", "whole_query.program"} <= inside
    # the second run starts from the manifest's capacities
    warm = session.sql(QUERY).toArrow()
    seeded = _attempts(recorded_spans(t1, time.perf_counter()))
    assert [a["args"]["discarded"] for a in seeded] == [False]
    assert warm.equals(cold)
    assert recorded_spans(t0, t0) == []


def test_phase_times_keep_their_keys(session):
    """`plan_ms` sums every key but `execution`: a new one would move it.
    (The keys are these four; `collect` is a span, not a phase time.)"""
    df = session.sql(QUERY)
    df.toArrow()
    assert set(df.query_execution.phase_times) \
        == {"analysis", "optimization", "planning", "execution"}


def test_engine_spans_are_on_the_profilers_clock(session, tmp_path):
    import jax
    from jax.profiler import ProfileData

    session.sql(QUERY).toArrow()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        session.sql(QUERY).toArrow()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("st:"):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    for name in ("st:execution", "st:whole_query.attempt",
                 "st:collect.d2h", "st:whole_query.launch", "st:collect"):
        assert name in found, sorted(found)
    ex, = found["st:execution"]
    at, = found["st:whole_query.attempt"]
    d2h, = found["st:collect.d2h"]
    assert ex[0] <= at[0] <= at[1] <= ex[1] <= d2h[0] <= d2h[1]
    # the query id and the scalar args ride along
    assert ex[2]["query"] == at[2]["query"] == d2h[2]["query"]
    assert str(at[2]["attempt"]) == "0"
    launch, = found["st:whole_query.launch"]
    assert at[0] <= launch[0] <= launch[1] <= at[1]


def test_compiled_text_names_the_members_rows(session):
    from spark_tpu.obs.device_profile import operator_of, scope_map
    from spark_tpu.physical.compile import capture_programs

    with capture_programs() as programs:
        session.sql(QUERY).toArrow()
    assert programs
    rec = programs[-1]
    assert re.fullmatch(r"jit_whole_query_[0-9a-f]{10}", rec["program"])
    assert len(rec["scopes"]) == len(rec["members"])
    text = rec["kernel"]._kernel.lower(*rec["args"]).compile().as_text()
    assert text.startswith("HloModule " + rec["program"])
    named = {operator_of(o)[0] for o in scope_map(text).values()} - {None}
    # no instruction under a row the builder does not know
    assert named <= {s for s in rec["scopes"] if s}
    # every row that does work of its own is named by some instruction
    # (a scan of one whole tile or a pure column selection leaves none)
    for label, member in zip(rec["scopes"], rec["members"]):
        if label and re.match(r"m\d+\.(HashJoin|HashAggregate|Sort)$",
                              label):
            assert label in named, (label, member)
    phases = {operator_of(o) for o in scope_map(text).values()}
    for phase in ("build_sort", "probe", "expand", "gather", "group_sort",
                  "segment_reduce", "sort"):
        assert any(p == phase for _label, p in phases), phase


_CHILD = r'''
import sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import test_device_tracing as T
from spark_tpu import TpuSession
from spark_tpu.physical.compile import capture_programs
s = TpuSession("names", {{"spark.sql.shuffle.partitions": 4,
                          "spark.tpu.compile.tier": "whole"}})
T._register(s)
with capture_programs() as programs:
    s.sql(T.QUERY).toArrow()
print("PROGRAMS", ",".join(p["program"] for p in programs))
s.stop()
'''


def test_two_processes_give_a_program_the_same_name():
    """XLA's disk-cache key includes the module name: a name that moved
    between processes would recompile every program on every start."""
    code = _CHILD.format(repo=REPO, tests=os.path.join(REPO, "tests"))
    names = []
    for seed in ("1", "2"):       # hash() of a str differs between them
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env={**os.environ, "PYTHONHASHSEED": seed})
        assert out.returncode == 0, out.stderr[-2000:]
        names.append(re.search(r"^PROGRAMS (.*)$", out.stdout, re.M).group(1))
    assert names[0] == names[1] and names[0].count("jit_whole_query_") >= 2


def test_named_jit_hashes_key_labels_and_scopes_version(monkeypatch):
    from spark_tpu.physical import compile as C

    def name(key, labels=()):
        return C.named_jit("k", key, lambda x: x, labels=labels).__name__

    base = name(("a", 1), ("m00.Scan",))
    assert base == name(("a", 1), ["m00.Scan"])
    assert re.fullmatch(r"k_[0-9a-f]{10}", base)
    assert base != name(("a", 2), ("m00.Scan",))
    assert base != name(("a", 1), ("m00.Range",))
    monkeypatch.setattr(C, "SCOPES_VERSION", C.SCOPES_VERSION + 1)
    assert base != name(("a", 1), ("m00.Scan",))


# ---------------------------------------------------------------------------
# explain(mode="device")
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(REPO, "tests", "data",
                           "device_profile_planes.json")) as f:
        return json.load(f)


def test_device_profile_reduction_on_a_recorded_fixture(fixture):
    from spark_tpu.obs.device_profile import UNATTRIBUTED, attribute

    runs = attribute(fixture["planes"], fixture["scopes"])
    assert [r["program"] for r in runs] == [
        "jit_whole_query_aaaa000001", "jit_whole_query_bbbb000002"]
    first, final = runs
    assert first["device_ns"] == 1000 and final["device_ns"] == 10000
    for r in runs:       # the groups and `unattributed` are the run
        assert sum(r["groups"].values()) == r["device_ns"]
    assert first["groups"] == {("m01.HashJoin", "build_sort"): 600,
                               ("m01.HashJoin", "gather"): 300,
                               (UNATTRIBUTED, None): 100}
    # while.34 holds fusion.275, fusion.276 and copy.9, so its 6000 ns
    # count once: 1000 to the scan (fusion.276), the rest to the probe —
    # fusion.275's 2000, the while's own 2800, and the 200 of copy.9,
    # which no scope names and which takes the event's it lies in
    assert final["groups"][("m01.HashJoin", "probe")] == 5000
    assert final["groups"][("m00.LocalTableScan", None)] == 1000
    assert final["groups"][("m02.HashAggregate", "group_sort")] == 2500
    assert final["groups"][("m02.HashAggregate", "segment_reduce")] == 1000
    # copy.11 outside every scope, and the gap from 13000 to its end
    assert final["groups"][(UNATTRIBUTED, None)] == 500
    top = {name: (ns, label, phase)
           for name, ns, label, phase in final["instructions"]}
    assert top["while.34"] == (6000, "m01.HashJoin", "probe")
    assert top["copy.11"] == (400, None, None)


def test_operator_of_takes_the_innermost_row():
    from spark_tpu.obs.device_profile import operator_of

    assert operator_of("jit(wq)/m07.Sort/m03.HashJoin/expand/cumsum") \
        == ("m03.HashJoin", "expand")
    assert operator_of("jit(wq)/m00.LocalTableScan/concatenate") \
        == ("m00.LocalTableScan", None)
    assert operator_of("jit(wq)/m02.Compute/jit(_take)/select_n") \
        == ("m02.Compute", None)
    assert operator_of("jit(wq)/m02.HashAggregate/while/body/add") \
        == ("m02.HashAggregate", None)
    assert operator_of("x") == (None, None) == operator_of(None)


def test_explain_device_renders_every_program_of_the_run(session, capsys,
                                                         monkeypatch):
    from spark_tpu.exec.persist_cache import PLAN_MEMORY

    # replay the ladder in the traced run: no manifest, and a process
    # that forgets what the warm run learned
    session.conf.set("spark.tpu.cache.dir", "")
    monkeypatch.setattr(PLAN_MEMORY, "put", lambda *_a: None)
    try:
        session.sql(QUERY).explain(mode="device")
    finally:
        session.conf.unset("spark.tpu.cache.dir")
    out = capsys.readouterr().out
    assert "== Device Profile ==" in out
    runs = re.findall(r"^program (jit_whole_query_\w+) \(run \d+, (\w+)",
                      out, re.M)
    assert len(runs) >= 2 and [w for _p, w in runs] \
        == ["discarded"] * (len(runs) - 1) + ["final"]
    assert "not a device's times" in out      # the CPU backend says so
    assert re.search(r"^  m\d+\.HashJoin .* ms .* %  \w+HashJoin", out, re.M)
    assert re.search(r"^    build_sort ", out, re.M)
    assert re.search(r"^  unattributed ", out, re.M)
