"""Concurrent load generator for the serving layer.

The serving tests' driver (tests/test_serving.py, test_race_lint.py,
test_metrics_export.py; ROADMAP R-B5 replaces it with the benchmark's
server cell): N concurrent per-connection sessions replay a
mixed dashboard-style query set through one QueryService, and the
report carries the numbers the serving tests assert on — per-pool
completion counts and p50/p99 latency, peak queue depth, the
weight-normalized fairness ratio, and the driver KernelCache launch
delta across the run (to reconcile against the per-query attributed
totals in the stored profiles).

Worker threads are handed their work through `obs.metrics.scoped_submit`
(the obs-layer contract for thread pools): the submitting context rides
into the pool thread, so any scope active at submit time — and every
span/launch the queries record — stays correctly attributed.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

from ..config import SERVE_POOL
from ..obs.export import Histogram
from ..obs.metrics import scoped_submit

__all__ = ["run_serve_load"]


def run_serve_load(service, queries, sessions: int = 8, reps: int = 2,
                   pools=("default",), pool_of=None,
                   session_mode: str | None = None) -> dict:
    """Drive `sessions` concurrent cloned sessions through `service`,
    each replaying `reps` rounds of the `queries` list under its pool
    (`pool_of(i)` or round-robin over `pools`). Returns the load
    report; individual query failures are recorded, not raised."""
    from ..physical.compile import GLOBAL_KERNEL_CACHE as KC

    kinds_before = dict(KC.launches_by_kind)
    # shared-mode workers use the server session, whose Metrics is
    # cumulative across its lifetime — baseline it so the report's
    # counters cover THIS load only (isolated clones start at zero)
    shared_before = service.session._metrics.snapshot()["counters"]
    t_start = time.perf_counter()

    def worker(i: int):
        sess = service.open_session(session_mode)
        pool = pool_of(i) if pool_of is not None \
            else pools[i % len(pools)]
        sess.conf.set(SERVE_POOL, pool)
        out = []
        for _ in range(int(reps)):
            for q in queries:
                t0 = time.perf_counter()
                err = None
                try:
                    service.execute_sql(sess, q)
                except Exception as e:
                    err = f"{type(e).__name__}: {e}"
                out.append((pool, (time.perf_counter() - t0) * 1000,
                            err))
        # isolated sessions count their own metrics — ship this clone's
        # counters so the report can aggregate (result_cache.hit etc.);
        # a shared-session worker ships None (summing the one shared
        # Metrics once per worker would multiply-count it)
        return out, (sess._metrics.snapshot()["counters"]
                     if sess is not service.session else None)

    results = []
    counters: dict = {}
    with ThreadPoolExecutor(max_workers=int(sessions),
                            thread_name_prefix="serve-load") as px:
        futs = [scoped_submit(px, worker, i) for i in range(int(sessions))]
        shared_any = False
        for f in futs:
            out, snap = f.result()
            results.extend(out)
            if snap is None:
                shared_any = True
                continue
            for k, v in snap.items():
                counters[k] = counters.get(k, 0) + v
    if shared_any:
        for k, v in service.session._metrics.snapshot()[
                "counters"].items():
            d = v - shared_before.get(k, 0)
            if d:
                counters[k] = counters.get(k, 0) + d
    wall_s = time.perf_counter() - t_start

    per_pool: dict = {}
    errors = []
    for pool, ms, err in results:
        ent = per_pool.setdefault(pool, {"completed": 0, "errors": 0,
                                         "hist": Histogram()})
        if err is None:
            ent["completed"] += 1
            ent["hist"].observe(ms)
        else:
            ent["errors"] += 1
            errors.append(err)
    status = service.status()
    report = {"wall_s": round(wall_s, 3),
              "sessions": int(sessions),
              "queries_total": len(results),
              "queue_depth_peak": max(
                  (p["queue_peak"] for p in status["pools"].values()),
                  default=0),
              "errors": errors[:8],
              "counters": {k: v for k, v in sorted(counters.items())
                           if k.startswith(("result_cache.", "compile.",
                                            "cache.", "obs."))},
              "pools": {}}
    for pool, ent in sorted(per_pool.items()):
        st = status["pools"].get(pool, {})
        weight = st.get("weight", 1.0) or 1.0
        # histogram-derived percentiles (mergeable fixed log buckets —
        # the same numbers a cross-process scrape merge would report)
        hist = ent["hist"]
        report["pools"][pool] = {
            "weight": weight,
            "completed": ent["completed"],
            "errors": ent["errors"],
            "p50_ms": hist.percentile_ms(0.50),
            "p95_ms": hist.percentile_ms(0.95),
            "p99_ms": hist.percentile_ms(0.99),
            "wait_p99_ms": st.get("wait_p99_ms"),
            "throughput_qps": round(ent["completed"] / max(wall_s, 1e-9),
                                    3),
        }
    # fairness under CONTENTION: total completions converge once the
    # lighter pool runs alone after the heavy pool drains, so the
    # honest share is the grant ratio while several pools had backlog
    report["contended_grants"] = service.scheduler.contended_grants()
    report["fairness_ratio"] = service.scheduler.fairness_ratio()
    kinds_after = dict(KC.launches_by_kind)
    report["driver_launch_delta"] = int(
        sum(kinds_after.values()) - sum(kinds_before.values()))
    return report
