"""Persistent compile & result caches (spark_tpu/exec/persist_cache.py +
utils/diskstore.py): fingerprint-keyed warm restarts and zero-launch
repeated queries.

Contract under test: everything is OFF while spark.tpu.cache.dir is
unset (the tier-1 default); with a dir configured, a repeated identical
query answers from the on-disk Arrow payload with ZERO kernel launches
and plan_lint predicts that hit path exactly; the key folds in the leaf
data identity, so a table append/overwrite invalidates (both through
the catalog write-path hook and by construction of the key); the
on-disk LRU stays inside its byte budget; non-deterministic plans
bypass the cache; the warm-start manifest collapses whole-tier
capacity retries; and fingerprints + XLA compile-cache entries survive
into REAL fresh processes (two-subprocess leg)."""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pyarrow as pa
import pytest

import spark_tpu.exec.persist_cache as pc
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
from spark_tpu.utils.diskstore import JsonlRing


def _session(name, extra=None):
    from spark_tpu import TpuSession

    # capacity 2^11, not the 2^12 the other suites use: kernel-cache
    # keys include capacity, so these tests must not pre-compile kernel
    # shapes that test_profile_history's cold-compile assertions (which
    # run later in the same process) expect to be cold
    conf = {"spark.sql.shuffle.partitions": 2,
            "spark.tpu.batch.capacity": 1 << 11,
            "spark.tpu.fusion.minRows": "0"}
    conf.update(extra or {})
    return TpuSession(name, conf)


def _seed_table(s, view="pc_t", n=4000, seed=3):
    rng = np.random.default_rng(seed)
    s.createDataFrame(pa.table({
        "k": rng.integers(0, 9, n),
        "v": rng.integers(-20, 80, n),
    })).createOrReplaceTempView(view)


Q = "select k, sum(v) s from pc_t where v > 0 group by k"


def _launch_delta(fn):
    before = dict(KC.launches_by_kind)
    out = fn()
    return out, {k: v - before.get(k, 0)
                 for k, v in KC.launches_by_kind.items()
                 if v != before.get(k, 0)}


# ---------------------------------------------------------------------------
# shared disk store
# ---------------------------------------------------------------------------

def test_diskstore_roundtrip_ring_and_torn_tail(tmp_path):
    ring = JsonlRing(str(tmp_path / "r.jsonl"), ring=4)
    for i in range(11):
        ring.append({"i": i})
    recs = ring.load()
    # compaction keeps the NEWEST ring-worth once the file doubles it
    assert [r["i"] for r in recs][-1] == 10
    assert len(recs) <= 8 and recs == sorted(recs, key=lambda r: r["i"])
    # torn tail from a concurrent append is skipped, not fatal
    with open(ring.path, "a") as f:
        f.write('{"i": 99, "tru')
    assert [r["i"] for r in ring.load()] == [r["i"] for r in recs]
    # re-entrant locked(): an append inside a locked block must not
    # deadlock (flock is per open-file-description)
    with ring.locked():
        ring.append({"i": 100})
    assert ring.load()[-1]["i"] == 100


# ---------------------------------------------------------------------------
# default-off safety
# ---------------------------------------------------------------------------

def test_caches_inert_without_cache_dir():
    s = _session("pc-off")
    try:
        _seed_table(s)
        assert pc.cache_root(s.conf) == ""
        assert pc.xla_cache_dir(s.conf) is None  # harness pins it off
        assert not pc.result_cache_active(s.conf)
        assert pc.result_cache_for(s.conf) is None
        s.sql(Q).toArrow()
        _out, delta = _launch_delta(lambda: s.sql(Q).toArrow())
        # the warm second run still LAUNCHES (no result cache): the
        # exact-count suites' ground rules are untouched by default
        assert sum(delta.values()) > 0
        counters = s._metrics.snapshot()["counters"]
        assert "result_cache.hit" not in counters
        assert "result_cache.miss" not in counters
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# result cache: zero-launch hits, exact plan prediction
# ---------------------------------------------------------------------------

def test_result_cache_hit_zero_launches_and_exact_prediction(tmp_path):
    s = _session("pc-hit", {"spark.tpu.cache.dir": str(tmp_path)})
    try:
        _seed_table(s)
        first = s.sql(Q).toArrow()          # populates
        rep = s.sql(Q).query_execution.analysis_report()
        assert rep.predicted_launches == {}, rep.predicted_launches
        assert rep.exact
        assert any("RESULT CACHE HIT" in n
                   for st in rep.stages for n in st.get("notes", ()))
        again, delta = _launch_delta(lambda: s.sql(Q).toArrow())
        assert delta == {}, f"result-cache hit launched kernels: {delta}"
        assert again.equals(first)
        counters = s._metrics.snapshot()["counters"]
        assert counters.get("result_cache.hit", 0) >= 1
        assert counters.get("result_cache.store", 0) == 1
    finally:
        s.stop()


def test_result_cache_distinguishes_data_and_literals(tmp_path):
    s = _session("pc-keys", {"spark.tpu.cache.dir": str(tmp_path)})
    try:
        _seed_table(s, n=4000, seed=3)
        a = s.sql(Q).toArrow()
        # different literal -> different fingerprint -> no stale hit
        b = s.sql(Q.replace("v > 0", "v > 50")).toArrow()
        assert not a.equals(b)
        # same schema + row count, different VALUES -> different
        # data-version component -> no stale hit
        _seed_table(s, n=4000, seed=4)
        c = s.sql(Q).toArrow()
        assert not a.equals(c)
    finally:
        s.stop()


def test_result_key_survives_fingerprint_sanitizer_collisions(tmp_path):
    """The telemetry fingerprint sanitizes hex-literal-like tokens
    (obs/history._VOLATILE) — fine for profile keying, unsound as the
    sole correctness key. The result key's exact-detail component must
    keep two queries apart that differ ONLY in a sanitized-away hex
    string literal, and a redefined same-name deterministic UDF must
    not serve the old function's cached answer."""
    import pyarrow.compute as pc_  # noqa: F401  (pa only)

    import spark_tpu.api.functions as F
    from spark_tpu.types import LongType

    s = _session("pc-collide", {"spark.tpu.cache.dir": str(tmp_path)})
    try:
        s.createDataFrame(pa.table({
            "id": pa.array(["a1b2c3d4e5f6a1b2", "ffffffffffff0000"]),
            "v": pa.array([1, 2], type=pa.int64()),
        })).createOrReplaceTempView("hex_t")
        qa = "select v from hex_t where id = 'a1b2c3d4e5f6a1b2'"
        qb = "select v from hex_t where id = 'ffffffffffff0000'"
        # sanity: both literals DO collide under the sanitized
        # fingerprint — the exact-detail component is what saves us
        from spark_tpu.obs.history import _sanitize
        assert _sanitize(qa) == _sanitize(qb)
        a = s.sql(qa).toArrow()          # populates under key(qa)
        b = s.sql(qb).toArrow()
        assert a.to_pylist() == [{"v": 1}]
        assert b.to_pylist() == [{"v": 2}], \
            "sanitizer collision served the wrong query's cached rows"
        # redefined same-name deterministic UDF: new code => new key
        u1 = F.udf(lambda x: x + 1, LongType(), deterministic=True)
        df1 = s.table("hex_t").select(u1(F.col("v")).alias("u"))
        r1 = df1.toArrow().to_pylist()
        u2 = F.udf(lambda x: x + 100, LongType(), deterministic=True)
        df2 = s.table("hex_t").select(u2(F.col("v")).alias("u"))
        r2 = df2.toArrow().to_pylist()
        assert r1 == [{"u": 2}, {"u": 3}]
        assert r2 == [{"u": 101}, {"u": 102}], \
            "redefined UDF served the old function's cached answer"
        # literals SHAPED like expr-id tokens (#N) must not ride the
        # expr-id ordinal remap: '#901' vs '#902' queries are distinct
        ta = s.sql("select '#901' tag, sum(v) s from hex_t").toArrow()
        tb = s.sql("select '#902' tag, sum(v) s from hex_t").toArrow()
        assert ta.to_pylist()[0]["tag"] == "#901"
        assert tb.to_pylist()[0]["tag"] == "#902", \
            "#N-shaped literal rode the expr-id remap into a collision"
    finally:
        s.stop()


def test_result_key_distinguishes_lossy_display_params(tmp_path):
    """Several operators' display strings are lossy — HashAggregateExec
    omits AggSpec.param (percentile's q), WindowExec omits partition/
    order keys and frame bounds — so a display-keyed result cache
    served one query's rows for another. The exact-detail component
    renders full node state (_render_value), keeping them apart, while
    the expr-id ordinal remap still lets an identical re-parsed query
    hit."""
    s = _session("pc-lossy", {"spark.tpu.cache.dir": str(tmp_path)})
    try:
        s.createDataFrame(pa.table({
            "k": pa.array([i % 3 for i in range(100)], type=pa.int64()),
            "v": pa.array(list(range(100)), type=pa.int64()),
        })).createOrReplaceTempView("t")
        p50 = s.sql("select percentile(v, 0.5) p from t").toArrow()
        p90 = s.sql("select percentile(v, 0.9) p from t").toArrow()
        assert p50.to_pylist() == [{"p": 49.0}]
        assert p90.to_pylist() == [{"p": 89.0}], \
            "percentile-param collision served the cached p50 answer"
        w1 = s.sql("select sum(v) over (partition by k order by v rows "
                   "between 1 preceding and current row) w from t").toArrow()
        w3 = s.sql("select sum(v) over (partition by k order by v rows "
                   "between 3 preceding and current row) w from t").toArrow()
        assert not w1.equals(w3), \
            "window-frame collision served the cached 1-preceding answer"
        wp = s.sql("select sum(v) over (partition by k) w from t").toArrow()
        wo = s.sql("select sum(v) over (order by k) w from t").toArrow()
        assert not wp.equals(wo), \
            "window-spec collision served the cached partition-by answer"
        # identical repeated query (fresh parse, fresh expr-ids) still
        # HITS: the ordinal remap keeps the exact detail stable
        _out, delta = _launch_delta(
            lambda: s.sql("select percentile(v, 0.5) p from t").toArrow())
        assert delta == {}, f"repeat missed the result cache: {delta}"
    finally:
        s.stop()


def test_result_key_distinguishes_slices_of_one_parent(tmp_path):
    """Slices share their parent table's buffers (the offset lives on
    the Array, not the buffer), so a raw-buffer content hash would make
    two DIFFERENT-valued slices collide — and with equal length, schema,
    and identical head/tail previews (the plan-detail preview elides the
    middle), nothing else in the key separates them. The IPC-stream
    content hash must keep them apart end to end."""
    a_vals = list(range(50))
    # same first/last 5 values as `a`, different middle
    b_vals = a_vals[:5] + [x + 1000 for x in a_vals[5:45]] + a_vals[45:]
    parent = pa.table({"v": pa.array(a_vals + b_vals, type=pa.int64())})
    a, b = parent.slice(0, 50), parent.slice(50, 50)
    assert not a.equals(b)
    assert pc._arrow_content_hash(a) != pc._arrow_content_hash(b)
    # equal values built independently still share one hash (the
    # cross-process sharing direction)
    assert pc._arrow_content_hash(pa.table(
        {"v": pa.array(a_vals, type=pa.int64())})) \
        == pc._arrow_content_hash(a)
    s = _session("pc-slice", {"spark.tpu.cache.dir": str(tmp_path)})
    try:
        s.createDataFrame(a).createOrReplaceTempView("slice_t")
        ra = s.sql("select sum(v) s from slice_t").toArrow()
        assert ra.to_pylist() == [{"s": sum(a_vals)}]
        s.createDataFrame(b).createOrReplaceTempView("slice_t")
        rb = s.sql("select sum(v) s from slice_t").toArrow()
        assert rb.to_pylist() == [{"s": sum(b_vals)}], \
            "slice-aliased content hash served the other slice's rows"
    finally:
        s.stop()


def test_nondeterministic_udf_bypasses_result_cache(tmp_path):
    import spark_tpu.api.functions as F
    from spark_tpu.types import LongType

    s = _session("pc-nondet", {"spark.tpu.cache.dir": str(tmp_path)})
    try:
        _seed_table(s)
        calls = {"n": 0}

        def bump(x):
            calls["n"] += 1
            return x

        udf = F.udf(bump, LongType(), deterministic=False)
        df = s.table("pc_t").select(udf(F.col("v")).alias("u"))
        key, _deps = pc.result_key(df.query_execution.physical, s.conf)
        assert key is None, "non-deterministic plan must be uncacheable"
        # nested carriers too: the determinism gate rides the render
        # walk, so a non-deterministic expression inside an aggregate's
        # AggSpec (not a direct node attribute) is still caught
        agg = s.table("pc_t").groupBy("k") \
            .agg(F.sum(udf(F.col("v"))).alias("u"))
        key2, _d2 = pc.result_key(agg.query_execution.physical, s.conf)
        assert key2 is None, \
            "non-deterministic agg input escaped the determinism gate"
        df.toArrow()
        _out, delta = _launch_delta(
            lambda: s.table("pc_t")
            .select(udf(F.col("v")).alias("u")).toArrow())
        assert sum(delta.values()) > 0, \
            "non-deterministic repeat must re-execute"
    finally:
        s.stop()


def test_result_cache_lru_stays_in_byte_budget(tmp_path):
    budget = 64 << 10
    s = _session("pc-lru", {"spark.tpu.cache.dir": str(tmp_path),
                            "spark.tpu.cache.result.maxBytes":
                            str(budget)})
    try:
        # 13 distinct queries (distinct literals -> distinct keys), each
        # result ~6.4 KiB — under the per-entry bound (budget/8), but
        # together well past the 64 KiB budget, so the LRU must evict
        rng = np.random.default_rng(9)
        s.createDataFrame(pa.table({
            "k": rng.integers(0, 1000, 4000),
            "v": rng.integers(0, 100, 4000),
        })).createOrReplaceTempView("lru_t")
        for i in range(13):
            s.sql(f"select k, v from lru_t where v >= {i} "
                  "limit 400").toArrow()
        rc = pc.result_cache_for(s.conf)
        assert rc.total_bytes() <= budget, \
            f"{rc.total_bytes()} > budget {budget}"
        counters = s._metrics.snapshot()["counters"]
        assert counters.get("result_cache.store", 0) >= 2
    finally:
        s.stop()


def test_hit_enforces_max_rows_miss_attributed_manifest_deduped(tmp_path):
    """Review-hardening contract: (a) a result-cache HIT still enforces
    spark.tpu.collect.maxRows (the limit is not part of the key — a
    lowered limit must reject the oversized cached answer exactly like
    the executed path would); (b) the executed run's QueryProfile
    attributes its own result_cache.miss (counted after the recorder
    baseline); (c) a seeded steady-state run whose capacity outcomes
    match its seed appends NO duplicate manifest record."""
    s = _session("pc-limits", {
        "spark.tpu.cache.dir": str(tmp_path),
        "spark.tpu.obs.profileDir": str(tmp_path / "profiles"),
    })
    try:
        _seed_table(s)
        q = "select k, v from pc_t where v > 0"
        df = s.sql(q)
        out = df.toArrow()                        # miss → execute → store
        assert out.num_rows > 10
        prof = df.query_execution._last_profile or {}
        assert (prof.get("counters") or {}).get("result_cache.miss") == 1, \
            "executed profile must attribute its own result-cache miss"
        s.conf.set("spark.tpu.collect.maxRows", "10")
        with pytest.raises(RuntimeError, match="maxRows"):
            s.sql(q).toArrow()                    # hit path, same key
        s.conf.unset("spark.tpu.collect.maxRows")
        # (c): record_manifest skips an append whose outcomes equal the
        # prior seed record — capacity CHANGES are recorded, repeats not
        fp = {"fingerprint": "fp-dedup", "stages": []}
        pc.record_manifest(s.conf, fp, {"tier": "whole"}, [8], None)
        rec = pc.manifest_seed(s.conf, "fp-dedup")
        assert rec and rec["join_caps"] == [8]
        pc.record_manifest(s.conf, fp, {"tier": "whole"}, [8], None,
                           prior=rec)
        records = [r for r in pc._manifest(s.conf).load()
                   if r.get("fp") == "fp-dedup"]
        assert len(records) == 1, "identical seeded outcome re-appended"
        pc.record_manifest(s.conf, fp, {"tier": "whole"}, [16], None,
                           prior=rec)             # a CHANGE does append
        assert pc.manifest_seed(s.conf, "fp-dedup")["join_caps"] == [16]
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# invalidation through the catalog write path
# ---------------------------------------------------------------------------

def test_result_cache_invalidated_on_append_and_overwrite(tmp_path):
    wh = tmp_path / "warehouse"
    s = _session("pc-inval", {
        "spark.tpu.cache.dir": str(tmp_path / "cache"),
        "spark.sql.warehouse.dir": str(wh),
    })
    try:
        base = pa.table({"k": np.arange(6) % 3,
                         "v": np.arange(6, dtype=np.int64)})
        s.createDataFrame(base).write.mode("overwrite") \
            .saveAsTable("sales")
        q = "select k, sum(v) s from sales group by k"
        a = s.sql(q).toArrow()                      # populates
        rc = pc.result_cache_for(s.conf)
        assert rc.total_bytes() > 0
        _hit, delta = _launch_delta(lambda: s.sql(q).toArrow())
        assert delta == {}, "warm-up: repeat must hit before the write"
        # APPEND through the catalog write path: the entry dies (hook)
        # AND the file identity in the key changes (construction)
        s.createDataFrame(pa.table({
            "k": np.array([0, 1], dtype=np.int64),
            "v": np.array([100, 200], dtype=np.int64),
        })).write.insertInto("sales")
        b = s.sql(q).toArrow()
        assert not b.equals(a), "append must be visible — stale hit!"
        assert {r["k"]: r["s"] for r in s.sql(q).collect()} == \
            {0: 3 + 100, 1: 5 + 200, 2: 7}
        # OVERWRITE: again a fresh answer
        s.createDataFrame(pa.table({
            "k": np.zeros(2, dtype=np.int64),
            "v": np.array([7, 8], dtype=np.int64),
        })).write.mode("overwrite").saveAsTable("sales")
        c = s.sql(q).toArrow()
        assert {r["k"]: r["s"] for r in s.sql(q).collect()} == {0: 15}
        assert not c.equals(b)
        counters = s._metrics.snapshot()["counters"]
        assert counters.get("result_cache.store", 0) >= 2
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# warm-start manifest: whole-tier capacity seeding
# ---------------------------------------------------------------------------

def test_whole_query_capacity_seed_collapses_retries(tmp_path):
    s = _session("pc-seed", {
        "spark.tpu.cache.dir": str(tmp_path),
        "spark.tpu.cache.result.enabled": "false",
        "spark.tpu.compile.tier": "whole",
        "spark.sql.adaptive.enabled": "false",
    })
    try:
        _seed_table(s)
        s.createDataFrame(pa.table({
            "k": np.repeat(np.arange(9), 3), "tag": np.arange(27),
        })).createOrReplaceTempView("pc_dim")
        jq = ("select p.k, count(*) n from pc_t p join pc_dim d "
              "on p.k = d.k group by p.k")

        def run():
            c0 = dict(s._metrics.snapshot()["counters"])
            out = s.sql(jq).toArrow()
            c1 = dict(s._metrics.snapshot()["counters"])
            return out, {
                k: c1.get(k, 0) - c0.get(k, 0)
                for k in ("whole_query.dispatches",
                          "whole_query.capacity_retries",
                          "cache.capacity_seeded")}

        cold_out, cold = run()
        assert cold["whole_query.capacity_retries"] >= 1, \
            f"3x-expanding join never overflowed: {cold}"
        # the manifest recorded the final caps under this fingerprint
        fp = s.sql(jq).query_execution.plan_fingerprint()["fingerprint"]
        rec = pc.manifest_seed(s.conf, fp)
        assert rec and rec.get("join_caps"), rec
        # "warm restart" semantics: with a cache dir the manifest's
        # record is what the first attempt starts from, in this process
        # as in a restarted one (the process's own memory of the
        # capacities stands behind it: tests/test_plan_memory.py) —
        # one dispatch, zero retries, identical answer
        warm_out, warm = run()
        assert warm["whole_query.capacity_retries"] == 0, warm
        assert warm["whole_query.dispatches"] == 1, warm
        assert warm["cache.capacity_seeded"] == 1, warm
        assert warm_out.equals(cold_out)
        # plan_lint mirrors the seeded attempt count
        rep = s.sql(jq).query_execution.analysis_report()
        assert rep.predicted_launches.get("whole_query") == 1
        assert rep.exact
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# cross-process durability (two REAL subprocesses)
# ---------------------------------------------------------------------------

_CHILD = r'''
import json, os, sys
import numpy as np, pyarrow as pa
from spark_tpu import TpuSession
from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
import spark_tpu.exec.persist_cache as pc

s = TpuSession("pc-child", {
    "spark.tpu.cache.dir": sys.argv[1],
    "spark.tpu.cache.result.enabled": "false",
    "spark.sql.shuffle.partitions": 2,
    "spark.tpu.batch.capacity": 1 << 12,
    "spark.tpu.fusion.minRows": "0",
    "spark.sql.adaptive.enabled": "false",
    "spark.tpu.obs.profileDir": os.path.join(sys.argv[1], "profiles"),
})
rng = np.random.default_rng(3)
s.createDataFrame(pa.table({
    "k": rng.integers(0, 9, 4000), "v": rng.integers(-20, 80, 4000),
})).createOrReplaceTempView("pc_t")
s.createDataFrame(pa.table({
    "k": np.repeat(np.arange(9), 3), "tag": np.arange(27),
})).createOrReplaceTempView("pc_dim")

# 1: the compile cache. The first run is the one that compiles (warm: is
# served from disk), so its profile carries the attribution.
q = lambda: s.sql("select k, sum(v) s from pc_t where v > 0 group by k")
df = q()
out = df.toArrow()
prof = df.query_execution._last_profile or {}

# 2: the whole tier's capacity ladder. The 3x-expanding join overflows
# its first output bucket; a restart finds the final capacities in the
# manifest.
s.conf.set("spark.tpu.compile.tier", "whole")
jq = lambda: s.sql("select p.k, count(*) n from pc_t p join pc_dim d "
                   "on p.k = d.k group by p.k")
jrep = jq().query_execution.analysis_report()
c0 = dict(s._metrics.snapshot()["counters"])
jout = jq().toArrow()
c1 = dict(s._metrics.snapshot()["counters"])
s.conf.unset("spark.tpu.compile.tier")
delta = lambda k: c1.get(k, 0) - c0.get(k, 0)

# 3: the result cache: fill it, then ask again
s.conf.set("spark.tpu.cache.result.enabled", "true")
a1 = q().toArrow()
l0 = KC.launches
a2 = q().toArrow()
print("CHILD " + json.dumps({
    "fingerprint": df.query_execution.plan_fingerprint()["fingerprint"],
    "compiles": KC.misses,
    "disk": pc.disk_counters(),
    "disk_hit_compiles": KC.disk_hit_compiles,
    "rows": out.num_rows,
    "profile_compiles": prof.get("compiles"),
    "profile_disk_hit": prof.get("compiles_disk_hit"),
    "profile_counters": sorted(prof.get("counters") or {}),
    "wq": {"predicted": jrep.predicted_launches.get("whole_query"),
           "exact": jrep.exact,
           "dispatches": delta("whole_query.dispatches"),
           "retries": delta("whole_query.capacity_retries"),
           "rows": jout.to_pylist()},
    "rc_hits": int(s._metrics.snapshot()["counters"]
                   .get("result_cache.hit", 0)),
    "rc_repeat_launches": KC.launches - l0,
    "rc_equal": a1.equals(a2),
}))
'''


@pytest.fixture(scope="module")
def cold_and_warm(tmp_path_factory):
    """The same three legs in two REAL processes sharing one
    spark.tpu.cache.dir: the first finds it empty, the second is the
    restart."""
    cache = str(tmp_path_factory.mktemp("pc_restart"))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the harness pins the XLA disk cache off (conftest); this is the
    # test that asks for it
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def child(tag):
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD, cache],
            env=env, cwd=root, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, timeout=300)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("CHILD ")]
        assert proc.returncode == 0 and lines, \
            f"{tag} child failed: {proc.stderr[-500:]}"
        return json.loads(lines[-1][len("CHILD "):])

    return child("cold"), child("warm")


def test_fingerprint_and_compile_cache_across_subprocesses(cold_and_warm):
    """The satellite's durability proof: a cold subprocess populates the
    XLA disk cache; a FRESH subprocess re-runs the same query with the
    identical fingerprint and ZERO true cold XLA compiles (every
    backend compile served from disk)."""
    cold, warm = cold_and_warm
    assert cold["fingerprint"] == warm["fingerprint"], \
        "fingerprint unstable across processes — persistent keys dead"
    assert cold["disk"]["compile.disk_miss"] >= 1
    assert warm["disk"]["compile.disk_miss"] == 0, \
        f"warm restart paid true cold compiles: {warm['disk']}"
    assert warm["disk"]["compile.disk_hit"] >= 1
    assert warm["disk_hit_compiles"] >= 1, \
        "no kernel classified as disk-served on the warm restart"
    assert warm["rows"] == cold["rows"]


def test_restarted_process_profile_says_its_compiles_came_from_disk(
        cold_and_warm):
    cold, warm = cold_and_warm
    assert cold["profile_compiles"] >= 1 and not cold["profile_disk_hit"], \
        cold
    assert warm["profile_disk_hit"] >= 1, warm
    assert warm["profile_disk_hit"] == warm["profile_compiles"], \
        "a restart's profile shows a compile the disk did not serve"
    assert "compile.disk_hit" in warm["profile_counters"], \
        warm["profile_counters"]


def test_manifest_seed_collapses_the_ladder_in_a_restarted_process(
        cold_and_warm):
    cold, warm = cold_and_warm
    assert cold["wq"]["retries"] >= 1 and cold["wq"]["dispatches"] >= 2, \
        f"the join never overflowed, so the seed has nothing to show: {cold}"
    assert cold["wq"]["exact"] \
        and cold["wq"]["predicted"] == cold["wq"]["dispatches"]
    assert warm["wq"]["retries"] == 0 and warm["wq"]["dispatches"] == 1, \
        f"the restart climbed the ladder again: {warm['wq']}"
    # the launch model reads the same seed
    assert warm["wq"]["exact"] and warm["wq"]["predicted"] == 1
    assert warm["wq"]["rows"] == cold["wq"]["rows"]


def test_result_cache_answers_a_restarted_process_without_a_launch(
        cold_and_warm):
    cold, warm = cold_and_warm
    for leg in (cold, warm):
        assert leg["rc_repeat_launches"] == 0 and leg["rc_equal"], leg
    # the restart's first ask is already a hit: the cold process stored it
    assert warm["rc_hits"] > cold["rc_hits"] >= 1, (cold, warm)
