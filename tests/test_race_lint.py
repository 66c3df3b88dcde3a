"""Race & lock-discipline analysis (spark_tpu/analysis/race_lint.py) and
its runtime half (spark_tpu/utils/lockwatch.py, utils/counters.py).

Contract under test: the static model flags spawn-reachable mutations of
process-global state with no common lock, opposite-order lock nestings,
bare context-losing thread spawns in obs-scoped code, and worker-global
state without a re-init path — while `# guarded-by:` annotations,
`# race-lint: ignore[rule]` pragmas, locked-counter state, and the
sanctioned scoped_submit/par_map wrappers all stay clean; the repo
itself is clean against the checked-in baseline; lockwatch records
acquisition orders and held sets when enabled and is STRUCTURALLY
zero-overhead when idle (raw locks in every slot, maybe_wrap a
pass-through); and the locked counters lose no updates under racing
threads while validating their own guard under watching.
"""

import functools
import json
import os
import subprocess
import sys
import threading

from spark_tpu.analysis import race_lint
from spark_tpu.utils import lockwatch
from spark_tpu.utils.counters import LockedCounter, LockedCounterMap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# paths chosen to land in the rule-scoped directory sets
EXEC = "spark_tpu/exec/fx_mod.py"        # obs-scoped AND worker-shipped
API = "spark_tpu/api/fx_api.py"          # neither

_RAW_LOCK_TYPE = type(threading.Lock())


@functools.lru_cache(maxsize=None)
def _repo_model():
    """The static model of the repo as it stands: one whole-repo AST pass
    for every test that reads it."""
    return race_lint.build_model([os.path.join(REPO, "spark_tpu")],
                                 repo_root=REPO)


def _rules(sources):
    return [(v.rule, v.path, v.line) for v in race_lint.lint_sources(sources)]


def _only(sources, rule):
    return [(p, ln) for r, p, ln in _rules(sources) if r == rule]


# ---------------------------------------------------------------------------
# shared-mutation
# ---------------------------------------------------------------------------

UNGUARDED = (
    "import threading\n"
    "STATS = {}\n"
    "def work():\n"
    "    STATS['n'] = STATS.get('n', 0) + 1\n"
    "def start():\n"
    "    threading.Thread(target=work, daemon=True).start()\n"
)


def test_spawn_reachable_unguarded_mutation_flagged():
    hits = _only({EXEC: UNGUARDED}, "shared-mutation")
    assert hits == [(EXEC, 4)]


def test_unreachable_mutation_not_flagged():
    """No spawn site reaches the mutating function → single-threaded by
    the model, no finding."""
    src = ("STATS = {}\n"
           "def work():\n"
           "    STATS['n'] = 1\n")
    assert _only({EXEC: src}, "shared-mutation") == []


def test_common_lock_clears_shared_mutation():
    src = ("import threading\n"
           "LOCK = threading.Lock()\n"
           "STATS = {}\n"
           "def work():\n"
           "    with LOCK:\n"
           "        STATS['n'] = STATS.get('n', 0) + 1\n"
           "def start():\n"
           "    threading.Thread(target=work, daemon=True).start()\n")
    assert _only({EXEC: src}, "shared-mutation") == []


def test_guard_must_be_common_across_all_sites():
    """Two mutation sites under DIFFERENT locks: the intersection is
    empty, so both spawn-reachable sites are flagged."""
    src = ("import threading\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "STATS = {}\n"
           "def work():\n"
           "    with A:\n"
           "        STATS['n'] = 1\n"
           "def other():\n"
           "    with B:\n"
           "        STATS['m'] = 2\n"
           "def start():\n"
           "    threading.Thread(target=work, daemon=True).start()\n"
           "    threading.Thread(target=other, daemon=True).start()\n")
    assert len(_only({EXEC: src}, "shared-mutation")) == 2


def test_guarded_by_annotation_trusted_and_exported():
    src = ("import threading\n"
           "LOCK = threading.Lock()\n"
           "STATS = {}\n"
           "def work():\n"
           "    STATS['n'] = 1  # guarded-by: LOCK\n"
           "def start():\n"
           "    threading.Thread(target=work, daemon=True).start()\n")
    model = race_lint.build_model_from_sources({EXEC: src})
    assert [v for v in model.violations if v.rule == "shared-mutation"] == []
    assert any(a["lock"].endswith("LOCK") for a in model.annotations)


def test_locked_counter_state_is_exempt():
    src = ("import threading\n"
           "from spark_tpu.utils.counters import LockedCounter\n"
           "N = LockedCounter('fx.N')\n"
           "def work():\n"
           "    N.bump()\n"
           "def start():\n"
           "    threading.Thread(target=work, daemon=True).start()\n")
    assert _only({EXEC: src}, "shared-mutation") == []


def test_pragma_suppresses_shared_mutation():
    src = UNGUARDED.replace(
        "    STATS['n'] = STATS.get('n', 0) + 1\n",
        "    # race-lint: ignore[shared-mutation] — test justification\n"
        "    STATS['n'] = STATS.get('n', 0) + 1\n")
    assert _only({EXEC: src}, "shared-mutation") == []


def test_comment_pragma_reaches_through_justification_block():
    """A comment-only pragma covers its continuation comment lines AND
    the next code line — multi-line written justifications work."""
    src = UNGUARDED.replace(
        "    STATS['n'] = STATS.get('n', 0) + 1\n",
        "    # race-lint: ignore[shared-mutation] — a justification that\n"
        "    # spans several comment lines before the flagged statement\n"
        "    STATS['n'] = STATS.get('n', 0) + 1\n")
    assert _only({EXEC: src}, "shared-mutation") == []


def test_pragma_for_wrong_rule_does_not_suppress():
    src = UNGUARDED.replace(
        "    STATS['n'] = STATS.get('n', 0) + 1\n",
        "    STATS['n'] = STATS.get('n', 0) + 1"
        "  # race-lint: ignore[lock-order]\n")
    assert len(_only({EXEC: src}, "shared-mutation")) == 1


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------

INVERTED = (
    "import threading\n"
    "A = threading.Lock()\n"
    "B = threading.Lock()\n"
    "def f():\n"
    "    with A:\n"
    "        with B:\n"
    "            pass\n"
    "def g():\n"
    "    with B:\n"
    "        with A:\n"
    "            pass\n"
)


def test_opposite_nesting_orders_flagged():
    assert len(_only({EXEC: INVERTED}, "lock-order")) >= 1


def test_consistent_nesting_order_clean():
    src = INVERTED.replace(
        "def g():\n    with B:\n        with A:\n",
        "def g():\n    with A:\n        with B:\n")
    assert _only({EXEC: src}, "lock-order") == []


def test_transitive_acquire_through_calls_flagged():
    """f holds A and CALLS g which takes B; h nests B→A directly — the
    cycle only exists through the call graph."""
    src = ("import threading\n"
           "A = threading.Lock()\n"
           "B = threading.Lock()\n"
           "def g():\n"
           "    with B:\n"
           "        pass\n"
           "def f():\n"
           "    with A:\n"
           "        g()\n"
           "def h():\n"
           "    with B:\n"
           "        with A:\n"
           "            pass\n")
    assert len(_only({EXEC: src}, "lock-order")) >= 1


def test_lock_order_pragma_removes_edge_from_model():
    src = INVERTED.replace(
        "    with B:\n        with A:\n",
        "    with B:\n"
        "        # race-lint: ignore[lock-order] — test justification\n"
        "        with A:\n")
    model = race_lint.build_model_from_sources({EXEC: src})
    assert [v for v in model.violations if v.rule == "lock-order"] == []
    # the suppressed nesting is an assertion it cannot happen: the
    # exported edge set (what the --race gate unions with runtime
    # observations) must not contain the pragma'd B→A edge
    assert all(not (a.endswith(".B") and b.endswith(".A"))
               for a, b in model.lock_edges)


# ---------------------------------------------------------------------------
# bare-submit
# ---------------------------------------------------------------------------

def test_bare_thread_flagged_in_obs_scoped_dirs_only():
    src = ("import threading\n"
           "def go(fn):\n"
           "    threading.Thread(target=fn, daemon=True).start()\n")
    assert len(_only({EXEC: src}, "bare-submit")) == 1
    assert _only({API: src}, "bare-submit") == []


def test_bare_submit_of_known_function_flagged():
    # the rdd._parallel shape before its conversion to scoped_submit
    src = ("def work(s):\n"
           "    return s\n"
           "def run(pool, splits):\n"
           "    return [pool.submit(work, s) for s in splits]\n")
    assert len(_only({EXEC: src}, "bare-submit")) == 1


def test_scoped_submit_and_par_map_are_sanctioned():
    src = ("from spark_tpu.obs.metrics import scoped_submit\n"
           "def work(s):\n"
           "    return s\n"
           "def run(pool, splits):\n"
           "    return [scoped_submit(pool, work, s) for s in splits]\n"
           "def run2(splits):\n"
           "    return par_map(work, splits)\n")
    assert _only({EXEC: src}, "bare-submit") == []


def test_bare_submit_inside_scoped_submit_definition_exempt():
    """The wrapper itself must call the raw pool — the exemption is what
    makes the sanctioned wrapper expressible at all."""
    src = ("def scoped_submit(pool, fn, *a):\n"
           "    return pool.submit(fn, *a)\n")
    assert _only({EXEC: src}, "bare-submit") == []


def test_bare_submit_pragma_with_justification():
    src = ("import threading\n"
           "def go(fn):\n"
           "    # race-lint: ignore[bare-submit] — process-lifetime\n"
           "    # service thread, must not inherit a query scope\n"
           "    threading.Thread(target=fn, daemon=True).start()\n")
    assert _only({EXEC: src}, "bare-submit") == []


# ---------------------------------------------------------------------------
# worker-reinit
# ---------------------------------------------------------------------------

def test_worker_global_without_reinit_path_flagged():
    src = ("CACHE = {}\n"
           "def add(k, v):\n"
           "    CACHE[k] = v\n")
    assert len(_only({EXEC: src}, "worker-reinit")) == 1
    # outside worker-shipped dirs the rule does not apply
    assert _only({API: src}, "worker-reinit") == []


def test_reinit_path_clears_worker_reinit():
    src = ("CACHE = {}\n"
           "def add(k, v):\n"
           "    CACHE[k] = v\n"
           "def reset_cache():\n"
           "    CACHE.clear()\n")
    assert _only({EXEC: src}, "worker-reinit") == []


def test_locked_counter_has_builtin_reinit_path():
    """LockedCounter.reset() IS the re-init path — exempt by kind."""
    src = ("from spark_tpu.utils.counters import LockedCounter\n"
           "N = LockedCounter('fx.N')\n"
           "def add():\n"
           "    N.bump()\n")
    assert _only({EXEC: src}, "worker-reinit") == []


# ---------------------------------------------------------------------------
# baseline semantics + the CI gate: repo clean vs checked-in baseline
# ---------------------------------------------------------------------------

def test_baseline_blocks_only_new_violations(tmp_path):
    v1 = race_lint.lint_sources({EXEC: UNGUARDED})
    path = tmp_path / "base.json"
    race_lint.write_baseline(str(path), v1)
    baseline = race_lint.load_baseline(str(path))
    assert race_lint.new_violations(v1, baseline) == []
    v2 = race_lint.lint_sources({EXEC: UNGUARDED.replace(
        "def start():",
        "def mutate2():\n    STATS['m'] = 2\ndef start():\n"
        "    threading.Thread(target=mutate2, daemon=True).start()")})
    extra = race_lint.new_violations(v2, baseline)
    # the second spawn-reachable mutation site is NEW; so is the second
    # bare Thread spawn (EXEC is obs-scoped) — both beyond the baseline
    assert any(v.rule == "shared-mutation" for v in extra)
    assert race_lint.new_violations(v1, baseline) == []


def test_repo_clean_against_checked_in_baseline():
    violations = race_lint.lint_paths([os.path.join(REPO, "spark_tpu")],
                                      repo_root=REPO)
    baseline = race_lint.load_baseline(
        os.path.join(REPO, "dev", "race_baseline.json"))
    offending = race_lint.new_violations(violations, baseline)
    msg = "\n".join(str(v) for v in offending[:20])
    assert not offending, (
        f"race_lint found NEW violations beyond dev/race_baseline.json "
        f"(fix them, suppress with '# race-lint: ignore[rule]' plus a "
        f"written justification, or regenerate via "
        f"`python dev/racecheck.py --write-baseline`):\n{msg}")


def test_repo_baseline_is_empty():
    """The concurrency debt is fully paid: the committed baseline grants
    no allowance at all, so ANY finding is a hard failure."""
    baseline = race_lint.load_baseline(
        os.path.join(REPO, "dev", "race_baseline.json"))
    assert baseline == {}


def test_static_lock_graph_is_acyclic():
    model = _repo_model()
    cyc = lockwatch.find_cycle(model.lock_edges)
    assert cyc is None, f"static lock-order cycle: {cyc}"


def test_registered_watch_slots_are_in_the_static_inventory():
    """The static and the runtime half share one lock namespace: a slot
    registered for watching that the static model does not know means
    the two have drifted apart. The metrics plane's and the black box's
    locks are registered, so neither left the net."""
    import spark_tpu.api.session  # noqa: F401  (registers the engine's slots)
    import spark_tpu.obs.blackbox  # noqa: F401
    import spark_tpu.obs.export  # noqa: F401

    names = {n for n in lockwatch.registered_names()
             if not n.startswith(("counter.", "t_"))}   # t_: this file's
    assert {"obs.export.MetricsRegistry._lock", "obs.export._TS_LOCK",
            "obs.blackbox._LOCK"} <= names, sorted(names)
    model = _repo_model()
    assert names <= set(model.locks), sorted(names - set(model.locks))


def test_cli_runs_clean_and_fails_on_new(tmp_path):
    cli = os.path.join(REPO, "dev", "racecheck.py")
    r = subprocess.run(
        [sys.executable, cli, os.path.join(REPO, "spark_tpu"),
         "--baseline", os.path.join(REPO, "dev", "race_baseline.json")],
        capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    bad = tmp_path / "spark_tpu" / "exec" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(UNGUARDED)
    r = subprocess.run(
        [sys.executable, cli, str(tmp_path / "spark_tpu"),
         "--format", "json"],
        capture_output=True, text=True, timeout=180)
    assert r.returncode == 1
    data = json.loads(r.stdout)
    assert data["total"] >= 1
    assert data["new"][0]["rule"] in race_lint.RULES


# ---------------------------------------------------------------------------
# lockwatch: order recording, held sets, guard checks, idle overhead
# ---------------------------------------------------------------------------

class _Box:
    pass


def _two_watched(prefix):
    box = _Box()
    box.a = threading.Lock()
    box.b = threading.Lock()
    lockwatch.register(f"{prefix}.A", box, "a")
    lockwatch.register(f"{prefix}.B", box, "b")
    return box


def test_lockwatch_records_order_and_held_sets():
    box = _two_watched("t_order")
    lockwatch.enable()
    lockwatch.reset_observations()
    try:
        with box.a:
            assert lockwatch.held_locks() == ("t_order.A",)
            with box.b:
                assert lockwatch.held_locks() == ("t_order.A", "t_order.B")
        assert lockwatch.held_locks() == ()
        edges = lockwatch.order_edges()
        assert edges.get(("t_order.A", "t_order.B")) == 1
        assert ("t_order.B", "t_order.A") not in edges
        assert lockwatch.acquire_counts()["t_order.A"] == 1
    finally:
        lockwatch.disable()
        lockwatch.reset_observations()


def test_lockwatch_observed_inversion_closes_cycle():
    box = _two_watched("t_cyc")
    lockwatch.enable()
    lockwatch.reset_observations()
    try:
        with box.a:
            with box.b:
                pass
        with box.b:
            with box.a:
                pass
        cyc = lockwatch.find_cycle(lockwatch.order_edges())
        assert cyc is not None and cyc[0] == cyc[-1]
    finally:
        lockwatch.disable()
        lockwatch.reset_observations()


def test_check_guard_held_vs_missing():
    box = _two_watched("t_guard")
    lockwatch.enable()
    lockwatch.reset_observations()
    try:
        with box.a:
            assert lockwatch.check_guard("site1", "t_guard.A")
        assert not lockwatch.check_guard("site1", "t_guard.A")
        assert lockwatch.guard_checks() == {("site1", "t_guard.A"): 1}
        v = lockwatch.violations()
        assert len(v) == 1 and v[0]["site"] == "site1"
    finally:
        lockwatch.disable()
        lockwatch.reset_observations()


def test_idle_is_structurally_zero_overhead():
    """Off means OFF: raw lock objects in every registered slot, no
    proxy frame on acquire, maybe_wrap a pass-through, and the counters'
    guard self-check never reached (fast-path bool)."""
    assert not lockwatch.ENABLED
    box = _two_watched("t_idle")
    assert isinstance(box.a, _RAW_LOCK_TYPE)
    raw = threading.Lock()
    assert lockwatch.maybe_wrap("t_idle.X", raw) is raw
    before = dict(lockwatch.guard_checks())
    c = LockedCounter("t_idle.N")
    assert isinstance(c._lock, _RAW_LOCK_TYPE)
    c.bump()
    assert lockwatch.guard_checks() == before
    # enable swaps proxies in, disable restores the SAME raw locks
    lockwatch.enable()
    try:
        assert isinstance(box.a, lockwatch.WatchedLock)
        assert isinstance(c._lock, lockwatch.WatchedLock)
        assert isinstance(lockwatch.maybe_wrap("t_idle.X", raw),
                          lockwatch.WatchedLock)
    finally:
        lockwatch.disable()
        lockwatch.reset_observations()
    assert isinstance(box.a, _RAW_LOCK_TYPE)
    assert isinstance(c._lock, _RAW_LOCK_TYPE)


def test_find_cycle_ignores_self_loops():
    assert lockwatch.find_cycle([("A", "A")]) is None
    assert lockwatch.find_cycle([("A", "B"), ("B", "C")]) is None
    cyc = lockwatch.find_cycle([("A", "B"), ("B", "C"), ("C", "A")])
    assert cyc is not None and cyc[0] == cyc[-1]


# ---------------------------------------------------------------------------
# locked counters: no lost updates under racing threads, guard self-check
# ---------------------------------------------------------------------------

def _hammer(fn, threads=8, each=400):
    barrier = threading.Barrier(threads)

    def run():
        barrier.wait()
        for _ in range(each):
            fn()

    ts = [threading.Thread(target=run) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return threads * each


def test_locked_counter_loses_no_updates():
    c = LockedCounter("t_race.N")
    expect = _hammer(c.bump)
    assert c.value == expect
    c.reset()
    assert c.value == 0


def test_locked_counter_map_loses_no_updates():
    m = LockedCounterMap("t_race.M", ("a", "b"))
    expect = _hammer(lambda: m.bump("a"))
    assert m["a"] == expect and m["b"] == 0
    assert m.snapshot() == {"a": expect, "b": 0}


def test_retry_stats_regression_racing_threads():
    """The PR's satellite fix: net/transport.RETRY_STATS was a bare
    dict += (lost updates under the retry loop + par_map lanes); the
    locked replacement must count exactly under contention."""
    from spark_tpu.net.transport import RETRY_STATS
    before = RETRY_STATS["absorbed"]
    added = _hammer(lambda: RETRY_STATS.bump("absorbed"))
    assert RETRY_STATS["absorbed"] - before == added


def test_flush_overflows_regression_racing_threads():
    from spark_tpu.exec import worker_main as wm
    before = wm.FLUSH_OVERFLOWS.value
    added = _hammer(wm.FLUSH_OVERFLOWS.bump)
    assert wm.FLUSH_OVERFLOWS.value - before == added


def test_counter_bump_validates_own_guard_when_watched():
    c = LockedCounter("t_race.G")
    lockwatch.enable()
    lockwatch.reset_observations()
    try:
        c.bump()
        assert lockwatch.guard_checks() == {
            ("t_race.G", "counter.t_race.G"): 1}
        assert lockwatch.violations() == []
    finally:
        lockwatch.disable()
        lockwatch.reset_observations()


def test_transport_retry_counts_under_its_own_lock_when_watched():
    """A real absorbed flap drives RETRY_STATS' instrumented bump: the
    guard check fires at that site, finds the lock held, and disable()
    puts the raw lock back."""
    from spark_tpu.config import SQLConf
    from spark_tpu.net.transport import (
        RETRY_STATS, RetryPolicy, RpcClient, RpcServer,
    )
    from spark_tpu.utils import faults

    server = RpcServer("rl")
    server.register("echo", lambda p: p)
    addr = server.start()
    lockwatch.enable()
    lockwatch.reset_observations()
    try:
        faults.configure(SQLConf({
            "spark.tpu.faults.enabled": "true",
            "spark.tpu.faults.points": "rpc.call=first:1"}))
        before = RETRY_STATS["absorbed"]
        with RpcClient(addr, "rl") as c:
            assert c.call("echo", b"y", retry=RetryPolicy(
                attempts=3, base_ms=1.0, deadline_s=5.0)) == b"y"
        assert RETRY_STATS["absorbed"] > before
        assert any(site.startswith("net.transport.RETRY_STATS")
                   for site, _lock in lockwatch.guard_checks()), \
            lockwatch.guard_checks()
        assert lockwatch.violations() == []
    finally:
        faults.reset()
        server.stop()
        lockwatch.disable()
        lockwatch.reset_observations()
    assert isinstance(RETRY_STATS._lock, _RAW_LOCK_TYPE)


# ---------------------------------------------------------------------------
# integration: a real concurrent serve load under lockwatch
# ---------------------------------------------------------------------------

def test_concurrent_serve_load_under_lockwatch(tmp_path):
    """The gate's serve leg in miniature: cloned sessions collecting
    concurrently with every registered lock watched — zero guard
    violations, observed acquisition orders union the static nesting
    graph acyclic, and attribution untouched by the proxies."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.obs.history import ProfileStore
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC
    from spark_tpu.serve import QueryService
    from spark_tpu.serve.loadgen import run_serve_load

    lockwatch.enable()
    lockwatch.reset_observations()
    session = TpuSession("race-lint-it", {
        "spark.sql.shuffle.partitions": 2,
        "spark.tpu.batch.capacity": 1 << 11,
        "spark.tpu.fusion.minRows": "0",
        "spark.tpu.serve.maxConcurrent": 2,
        "spark.tpu.obs.profileDir": str(tmp_path),
    })
    try:
        rng = np.random.default_rng(3)
        session.createDataFrame(pa.table({
            "k": rng.integers(0, 8, 1500).astype(np.int64),
            "v": rng.integers(-20, 60, 1500).astype(np.int64),
        })).createOrReplaceTempView("rl_t")
        service = QueryService(session)
        before = KC.launches
        report = run_serve_load(
            service, ["select k, sum(v) s from rl_t group by k"],
            sessions=3, reps=1)
        assert not report["errors"], report["errors"]
        store = ProfileStore(str(tmp_path))
        assert sum(int(p["launch_total"]) for qk in store.query_keys()
                   for p in store.profiles(qk)) == KC.launches - before, \
            "the proxies perturbed what the obs layer attributes"
        assert lockwatch.violations() == []
        model = _repo_model()
        merged = set(lockwatch.order_edges()) \
            | {tuple(e) for e in model.lock_edges}
        assert lockwatch.find_cycle(merged) is None
        # watching was actually live during the load
        assert lockwatch.acquire_counts()
    finally:
        session.stop()
        lockwatch.disable()
        lockwatch.reset_observations()


def test_cluster_workers_watch_and_report_over_rpc(monkeypatch):
    """The executor half: with SPARK_TPU_LOCKWATCH=1 in the environment
    the two workers of a cluster watch their own locks, a query that
    meets a block-fetch flap is still right, and what each worker
    observed (the `lockwatch_edges` RPC) holds to the same rules as the
    driver: no guard violation, no slot the static model does not know,
    and no acquisition order that closes a cycle with the static graph."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.utils import faults

    monkeypatch.setenv("SPARK_TPU_LOCKWATCH", "1")
    lockwatch.enable()
    lockwatch.reset_observations()
    session = TpuSession("race-lint-cluster", {
        "spark.sql.shuffle.partitions": "2",
        "spark.tpu.batch.capacity": 1 << 11,
        "spark.sql.adaptive.enabled": "false",
        "spark.tpu.cluster.enabled": "true",
        "spark.tpu.cluster.workers": "2",
        "spark.tpu.faults.enabled": "true",
        "spark.tpu.faults.seed": "13",
        "spark.tpu.faults.points": "block.fetch=first:2",
    })
    try:
        rng = np.random.default_rng(13)
        keys = rng.integers(0, 24, 3000)
        vals = rng.integers(-40, 90, 3000)
        session.createDataFrame(pa.table({"k": keys, "v": vals})) \
            .createOrReplaceTempView("rl_c")
        faults.configure(session.conf)
        got = sorted((r["k"], r["v"]) for r in
                     session.table("rl_c").repartition(2).collect())
        assert got == sorted(zip(keys.tolist(), vals.tolist()))
        workers = session._sql_cluster.lockwatch_edges()
        observed = set(lockwatch.order_edges())
        assert lockwatch.violations() == []
    finally:
        faults.reset()
        session.stop()
        lockwatch.disable()
        lockwatch.reset_observations()
    model = _repo_model()
    assert len(workers) == 2, f"workers that answered: {sorted(workers)}"
    for eid, w in sorted(workers.items()):
        assert w["enabled"], f"{eid} did not inherit the watching"
        assert not w["violations"], (eid, w)
        unknown = [n for n in w["names"] if not n.startswith("counter.")
                   and n not in model.locks]
        assert not unknown, (eid, unknown)
        observed |= {(a, b) for a, b, _n in w["edges"]}
    # both may not get a task, but whoever ran one took watched locks
    assert sum(w["acquires"] for w in workers.values()) > 0
    cyc = lockwatch.find_cycle(
        observed | {tuple(e) for e in model.lock_edges})
    assert cyc is None, f"observed orders close a cycle: {cyc}"
