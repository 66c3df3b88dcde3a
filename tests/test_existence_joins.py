"""Semi and anti joins decide existence, one output slot a probe row they
may keep (`ops/joining._exists`): against the expansion they replace and
against loops over numpy rows, on one integer key (the exact index) and on the
hash (one key sent to it, two keys); with NULL keys, duplicate build keys
and an empty build side. A planted hash collision (every key one hash)
leaves rows undecided: the join says so, the whole-query program is
lowered again with that join on the expansion, the stage tier probes
again, and the rows are exact either way. The whole tier's attempt spans
and the statement's counters say which path each join took."""

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from join_reference import join_oracle
from spark_tpu.ops import joining as J

SETOPS = ["left_semi", "left_anti"]
CASES = ["as_is", "nullable_keys", "duplicate_build", "empty_build",
         "no_usable_probe"]


def _case(name, seed):
    """(build keys, their validity, build mask, probe keys, their
    validity, probe mask): two int32 keys a side; the first alone is the
    one-key case."""
    rng = np.random.default_rng(seed)
    bcap, pcap = 96, 160
    bk = [rng.integers(0, 25, bcap), rng.integers(0, 3, bcap)]
    pk = [rng.integers(-3, 28, pcap), rng.integers(0, 3, pcap)]
    if name == "duplicate_build":       # every key many times over
        bk = [rng.integers(0, 4, bcap), rng.integers(0, 2, bcap)]
        pk = [rng.integers(-1, 5, pcap), rng.integers(0, 2, pcap)]
    nullable = name in ("nullable_keys", "no_usable_probe")
    bvalid = [rng.random(bcap) > (0.2 if nullable else 0.0) for _ in bk]
    pvalid = [rng.random(pcap) > (0.2 if nullable else 0.0) for _ in pk]
    bmask = rng.random(bcap) > 0.2
    pmask = rng.random(pcap) > 0.1
    if name == "empty_build":
        bmask[:] = False
    if name == "no_usable_probe":
        pvalid[0] &= ~pmask
    return ([jnp.asarray(k.astype(np.int32)) for k in bk],
            [jnp.asarray(v) for v in bvalid], jnp.asarray(bmask),
            [jnp.asarray(k.astype(np.int32)) for k in pk],
            [jnp.asarray(v) for v in pvalid], jnp.asarray(pmask))


def _oracle(case, nkeys, join_type):
    """The probe rows a semi (anti) join keeps, by loops over the rows."""
    bk, bv, bm, pk, pv, pm = (x if not isinstance(x, list) else x[:nkeys]
                              for x in case)
    if nkeys == 1:
        return [p for p, _ in join_oracle(bk[0], bv[0], bm, pk[0], pv[0],
                                          pm, join_type)]
    build = {tuple(int(k[b]) for k in bk)
             for b in range(len(bm))
             if bm[b] and all(bool(v[b]) for v in bv)}
    out = []
    for p in range(len(pm)):
        if not pm[p]:
            continue
        usable = all(bool(v[p]) for v in pv)
        found = usable and tuple(int(k[p]) for k in pk) in build
        if found == (join_type == "left_semi"):
            out.append(p)
    return out


def _join(case, nkeys, join_type, key, expand=False, oc=1 << 11):
    bk, bv, bm, pk, pv, pm = case
    bi = J.build_index(bk[:nkeys], bv[:nkeys], bm, key)
    return J.probe_join(bi, bk[:nkeys], bv[:nkeys], pk[:nkeys], pv[:nkeys],
                        pm, oc, join_type, key, expand=expand)


@pytest.mark.parametrize("seed", [41, 2 ** 31 + 41])
@pytest.mark.parametrize("keys", ["exact", "hash_one_key", "hash_two_keys"])
@pytest.mark.parametrize("join_type", SETOPS)
@pytest.mark.parametrize("case", CASES)
def test_existence_is_the_expansion_and_the_oracle(case, join_type, keys,
                                                   seed):
    data = _case(case, seed)
    nkeys = 2 if keys == "hash_two_keys" else 1
    key = "exact" if keys == "exact" else "hash"
    r = _join(data, nkeys, join_type, key)
    want = _oracle(data, nkeys, join_type)
    # one slot a row it may keep: a semi join's rows with a match (no
    # two keys share a hash here), an anti join's live rows, in probe order
    live = np.asarray(data[5])
    candidates = len(_oracle(data, nkeys, "left_semi")) \
        if join_type == "left_semi" else int(live.sum())
    assert int(r.needed) == candidates <= r.out_mask.shape[0]
    assert (r.unsure is None) == (key == "exact")
    assert key == "exact" or int(r.unsure) == 0
    got = np.asarray(r.probe_idx)[np.asarray(r.out_mask)].tolist()
    assert got == sorted(got)
    e = _join(data, nkeys, join_type, key, expand=True)
    expanded = sorted(np.asarray(e.probe_idx)[np.asarray(e.out_mask)]
                      .tolist())
    assert got == expanded == want
    if case == "empty_build":
        assert got == ([] if join_type == "left_semi"
                       else np.flatnonzero(live).tolist())


@pytest.fixture()
def one_hash(monkeypatch):
    """Every key of every join one 64-bit hash: each probe row's range is
    the whole live build side."""
    monkeypatch.setattr(J, "hash_columns",
                        lambda cols, valids: jnp.zeros(cols[0].shape[0],
                                                       jnp.int64))


@pytest.mark.parametrize("join_type", SETOPS)
def test_a_planted_collision_is_counted_and_the_expansion_is_exact(
        join_type, one_hash):
    data = _case("duplicate_build", 7)
    r = _join(data, 2, join_type, "hash")
    assert int(r.unsure) > 0
    # each live probe row pairs with every live build row
    e = _join(data, 2, join_type, "hash", expand=True, oc=1 << 14)
    assert int(e.needed) <= 1 << 14
    assert sorted(np.asarray(e.probe_idx)[np.asarray(e.out_mask)]
                  .tolist()) == _oracle(data, 2, join_type)


# ---------------------------------------------------------------------------
# through the tiers
# ---------------------------------------------------------------------------

@pytest.fixture()
def tables(spark):
    rng = np.random.default_rng(4101)
    n = 3001
    words = [f"w{i}" for i in range(37)]
    for name, size in (("ej_a", n), ("ej_b", n // 2), ("ej_c", n // 5)):
        spark.createDataFrame(pa.table({
            "s": pa.array([words[i] for i in rng.integers(0, 37, size)],
                          mask=rng.random(size) < 0.1),
            "k": pa.array(rng.integers(0, 41, size).astype(np.int32),
                          mask=rng.random(size) < 0.1),
        })).createOrReplaceTempView(name)
    yield spark
    spark.conf.unset("spark.tpu.compile.tier")


SET_QUERIES = {
    "intersect": "select s, k from ej_a intersect select s, k from ej_b "
                 "intersect select s, k from ej_c",
    "except": "select s, k from ej_a except select s, k from ej_b "
              "except select s, k from ej_c",
}


def _sqlite_rows(spark, query):
    import sqlite3

    conn = sqlite3.connect(":memory:")
    for name in ("ej_a", "ej_b", "ej_c"):
        t = spark.sql(f"select s, k from {name}").toArrow()
        conn.execute(f"create table {name} (s, k)")
        conn.executemany(f"insert into {name} values (?, ?)",
                         zip(*[c.to_pylist() for c in t.columns]))
    rows = conn.execute(query).fetchall()
    conn.close()
    return sorted(rows, key=repr)


def _counters(spark):
    c = spark._metrics.snapshot()["counters"]
    return {k: c.get(k, 0) for k in ("join.semi_exists", "join.anti_exists",
                                      "join.setop_expanded")}


def _run(spark, tier, query):
    spark.conf.set("spark.tpu.compile.tier", tier)
    before = _counters(spark)
    rows = sorted(map(tuple, (r.values() for r in
                              spark.sql(query).toArrow().to_pylist())),
                  key=repr)
    return rows, {k: v - before[k] for k, v in _counters(spark).items()}


@pytest.mark.parametrize("tier", ["whole", "stage"])
@pytest.mark.parametrize("op", list(SET_QUERIES))
def test_set_operations_decide_existence_on_each_tier(tables, tier, op):
    """Two INTERSECTs (EXCEPTs): each a semi (anti) join on four keys (a
    NULL flag and a value a column), NULL equal to NULL as sqlite has it;
    every join decides existence and none expands."""
    from spark_tpu.obs.tracing import recorded_spans
    import time

    query = SET_QUERIES[op]
    t0 = time.perf_counter()
    rows, moved = _run(tables, tier, query)
    t1 = time.perf_counter()
    assert rows == _sqlite_rows(tables, query) and rows
    kind = "join.semi_exists" if op == "intersect" else "join.anti_exists"
    assert moved[kind] >= 2 and moved["join.setop_expanded"] == 0
    if tier == "whole":
        att = [s["args"] for s in recorded_spans(t0, t1)
               if s["name"] == "whole_query.attempt"]
        assert att and not any(a["discarded"] for a in att)
        assert att[-1]["setop_members"] == 2
        assert att[-1]["setop_expanded"] == 0
        assert att[-1]["setop_slots"] > 0
        shown = tables.sql(query).query_execution.explain_string("device")
        assert "key=hash exists" in shown and " expand\n" not in shown


@pytest.mark.parametrize("tier", ["whole", "stage"])
def test_a_planted_collision_expands_and_stays_exact(tables, tier,
                                                     monkeypatch):
    """Every join key one hash: each existence test leaves rows undecided,
    so the whole tier lowers the program again with the set operation's
    joins on the expansion (the first attempt discarded), and the stage
    tier probes each batch again (on the expansion, whose capacity the
    pairs of one hash then climb); the rows are sqlite's."""
    from spark_tpu.obs.tracing import recorded_spans
    import time

    # keys and a filter no other test has: the programs and kernels are
    # built here, with the planted hash
    query = SET_QUERIES["except"].replace(
        "s, k", "s, cast(k as bigint) k").replace("ej_c", "ej_c where k <> 40")
    want = _sqlite_rows(tables, query)
    monkeypatch.setattr(J, "hash_columns",
                        lambda cols, valids: jnp.zeros(cols[0].shape[0],
                                                       jnp.int64))
    t0 = time.perf_counter()
    rows, moved = _run(tables, tier, query)
    t1 = time.perf_counter()
    assert rows == want and rows
    assert moved["join.setop_expanded"] >= 2
    if tier == "whole":
        att = [s["args"] for s in recorded_spans(t0, t1)
               if s["name"] == "whole_query.attempt"]
        # the existence attempt, then the expansion's capacity ladder (all
        # pairs of one hash are candidates)
        assert [a["discarded"] for a in att] == [True] * (len(att) - 1) \
            + [False]
        assert att[0]["setop_expanded"] == 0
        assert all(a["setop_expanded"] == a["setop_members"] == 2
                   for a in att[1:])
        assert att[-1]["setop_slots"] > att[0]["setop_slots"]
