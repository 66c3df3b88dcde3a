"""Fair-scheduler pools: weighted admission control for serving.

Role of the reference's fair scheduler (core/scheduler/Pool.scala,
SchedulableBuilder.scala — FairSchedulableBuilder parsing
fairscheduler.xml pools with weight/minShare, selected per thread via
spark.scheduler.pool), re-shaped for an engine whose unit of admission
is a whole QUERY and whose scarce resources are device dispatch slots
and HBM:

  * **Pools** come from `spark.tpu.scheduler.pools` declarations
    ('name[:weight]') plus per-pool override keys
    `spark.tpu.scheduler.pool.<name>.{weight,maxConcurrent,queueSize,
    queueTimeout,hbmBudget}`. The 'default' pool always exists; a
    session picks its pool with `SET spark.tpu.scheduler.pool`.

  * **Weighted fairness** is stride scheduling over grant counts: each
    grant advances the pool's virtual time by 1/weight and the next
    slot goes to the backlogged pool with the LOWEST post-grant virtual
    time (ties break by arrival order). A pool waking from idle is
    advanced to the global virtual clock first, so sleeping never banks
    credit. Under sustained backlog two pools with weights 2:1 are
    granted slots 2:1 — deterministically, independent of timing.

  * **Admission** is plan-time and zero-launch: a slot is granted only
    when the global `spark.tpu.serve.maxConcurrent` cap, the pool's own
    `maxConcurrent`, and the HBM reservation all allow it. The HBM leg
    aggregates the plan analyzer's predicted peak (the same number the
    existing `check_memory_budget` pre-flight rejects on) across
    IN-FLIGHT queries: a query that fits the budget alone but not next
    to the current in-flight set WAITS in its pool's queue instead of
    dispatching into an XLA OOM. Queues are bounded (`queueSize`, full
    ⇒ immediate PoolQueueFull) and timed (`queueTimeout`, expiry ⇒
    AdmissionTimeout). Admitted queries execute exactly as they would
    without the serving layer — plan_lint's launch model is untouched.

Pure host bookkeeping throughout: no kernel launches, no device syncs.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass

from ..config import (
    MEMORY_BUDGET, SERVE_MAX_CONCURRENT, SERVE_POOL, SERVE_POOL_SLO,
    SERVE_POOLS, SERVE_QUEUE_SIZE, SERVE_QUEUE_TIMEOUT, SERVE_SLO_MS,
)
from ..errors import AdmissionTimeout, PoolQueueFull, ServerDraining
from ..obs.export import Histogram

__all__ = ["FairScheduler", "PoolConfig", "pool_configs"]

_QIDS = 32      # recent query ids retained per pool (SLO finding join)
_SLO_WINDOW = 64    # recent SLO verdicts per pool (rolling burn rate)


@dataclass
class PoolConfig:
    name: str
    weight: float = 1.0
    max_concurrent: int = 0      # 0 = only the global cap applies
    queue_size: int = 64
    queue_timeout_s: float = 30.0
    hbm_budget: int = 0          # 0 = inherit spark.tpu.memory.budget
    slo_ms: float = 0.0          # end-to-end latency SLO; 0 = off


def _one_pool(conf, name: str, weight: float | None = None) -> PoolConfig:
    base = SERVE_POOL.key   # "spark.tpu.scheduler.pool" (registered)

    def get(suffix, default, cast):
        v = conf.get(f"{base}.{name}.{suffix}", None)
        return cast(v) if v is not None else default

    # SLO targets live under the serve.* family (registered template
    # spark.tpu.serve.pool.<name>.sloMs; default spark.tpu.serve.sloMs)
    slo = conf.get(SERVE_POOL_SLO.key.replace("<name>", name), None)
    return PoolConfig(
        name=name,
        weight=max(get("weight", weight if weight is not None else 1.0,
                       float), 1e-9),
        max_concurrent=get("maxConcurrent", 0, int),
        queue_size=get("queueSize", int(conf.get(SERVE_QUEUE_SIZE)), int),
        queue_timeout_s=get("queueTimeout",
                            float(conf.get(SERVE_QUEUE_TIMEOUT)), float),
        hbm_budget=get("hbmBudget", 0, int),
        slo_ms=float(slo) if slo is not None
        else float(conf.get(SERVE_SLO_MS)))


def pool_configs(conf) -> dict[str, PoolConfig]:
    """Declared pools (+ the always-present 'default')."""
    names: dict[str, float | None] = {"default": None}
    for part in str(conf.get(SERVE_POOLS) or "").split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            n, w = part.split(":", 1)
            names[n.strip()] = float(w)
        else:
            names[part] = None
    return {n: _one_pool(conf, n, w) for n, w in names.items()}


class _Ticket:
    __slots__ = ("pool", "hbm", "seq", "granted", "released", "enq_t",
                 "grant_t", "query_id", "running_at_grant")

    def __init__(self, pool: str, hbm: int, seq: int):
        self.pool = pool
        self.hbm = int(hbm)
        self.seq = seq
        self.granted = False
        self.released = False
        self.enq_t = time.perf_counter()
        self.grant_t = 0.0
        self.query_id = None
        self.running_at_grant = 0   # slots held, this one included


class _PoolState:
    __slots__ = ("cfg", "queue", "running", "hbm_inflight", "served",
                 "granted", "completed", "rejected_timeout",
                 "rejected_full", "queue_peak", "hist_wait", "hist_exec",
                 "hist_e2e", "busy_ms", "recent_qids", "slo_breaches",
                 "slo_ok", "slo_window")

    def __init__(self, cfg: PoolConfig):
        self.cfg = cfg
        self.queue: deque[_Ticket] = deque()
        self.running = 0
        self.hbm_inflight = 0
        self.served = 0.0    # stride virtual-time counter (float: idle
        self.granted = 0     # catch-up snaps it to the clock); `granted`
        #                      is the integer lifetime grant count
        self.completed = 0
        self.rejected_timeout = 0
        self.rejected_full = 0
        self.queue_peak = 0
        # mergeable fixed log-bucket latency distributions (replacing
        # the PR 15 sample rings): admission wait (enqueue→grant),
        # execution (grant→release), end-to-end (enqueue→release) —
        # cross-process merge reproduces single-registry quantiles
        self.hist_wait = Histogram()
        self.hist_exec = Histogram()
        self.hist_e2e = Histogram()
        self.busy_ms = 0.0
        self.recent_qids: deque = deque(maxlen=_QIDS)
        # SLO burn accounting (cfg.slo_ms > 0): lifetime ok/breach
        # counters plus a rolling verdict window for the burn rate
        self.slo_breaches = 0
        self.slo_ok = 0
        self.slo_window: deque = deque(maxlen=_SLO_WINDOW)

    def burn_rate(self):
        """Fraction of recent completions over the SLO target (rolling
        window); None before any SLO-tracked completion."""
        if not self.slo_window:
            return None
        return round(sum(self.slo_window) / len(self.slo_window), 4)


class FairScheduler:
    """Weighted fair admission over pools. submit() enqueues (raises
    PoolQueueFull/ServerDraining), wait() blocks for the grant (raises
    AdmissionTimeout, which also dequeues the ticket), release() frees
    the slot and dispatches the next winner (QueryService.collect is
    the canonical submit → wait → try/finally-release caller)."""

    def __init__(self, conf):
        self._conf = conf
        self._cond = threading.Condition()
        self._pools: dict[str, _PoolState] = {
            name: _PoolState(cfg)
            for name, cfg in pool_configs(conf).items()}
        self._seq = 0
        self._running_total = 0
        self._hbm_total = 0
        self._vclock = 0.0      # global virtual time (stride scheduling)
        self._draining = False
        # (granted pool, pools-with-queued-demand-at-grant): the
        # fairness evidence — only grants made while SEVERAL pools had
        # backlog say anything about weighted share (after one pool's
        # demand drains, the survivor rightly takes every slot)
        self.grant_log: deque = deque(maxlen=4096)

    # -- admission --------------------------------------------------------
    def _pool_state(self, name: str) -> _PoolState:
        st = self._pools.get(name)
        if st is None:
            # undeclared pool: created on demand with default settings
            # (the reference logs a warning and falls back similarly)
            st = self._pools[name] = _PoolState(_one_pool(self._conf,
                                                          name))
        return st

    def submit(self, pool: str = "default", hbm: int = 0) -> _Ticket:
        with self._cond:
            if self._draining:
                raise ServerDraining()
            st = self._pool_state(pool)
            if len(st.queue) >= max(int(st.cfg.queue_size), 1):
                st.rejected_full += 1
                raise PoolQueueFull(pool, st.cfg.queue_size)
            if not st.queue and st.running == 0:
                # waking from idle: advance to the global virtual clock
                # so an idle period never banks scheduling credit
                st.served = max(st.served,
                                self._vclock * st.cfg.weight)
            self._seq += 1
            t = _Ticket(pool, hbm, self._seq)
            st.queue.append(t)
            st.queue_peak = max(st.queue_peak, len(st.queue))
            self._dispatch()
            return t

    def wait(self, ticket: _Ticket, timeout: float | None = None) -> None:
        with self._cond:
            st = self._pool_state(ticket.pool)
            if timeout is None:
                timeout = st.cfg.queue_timeout_s
            deadline = ticket.enq_t + max(float(timeout), 0.0)
            while not ticket.granted:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
            if not ticket.granted:
                try:
                    st.queue.remove(ticket)
                except ValueError:
                    pass
                st.rejected_timeout += 1
                # the removal may unblock another pool's head
                self._dispatch()
                raise AdmissionTimeout(ticket.pool, float(timeout))

    def release(self, ticket: _Ticket) -> dict | None:
        """Free the slot and dispatch the next winner. Records the
        ticket's execution and end-to-end latency into the pool's
        mergeable histograms; with an SLO target configured, returns
        the obs.slo finding when this completion breached it (the
        caller — QueryService.collect — forwards it to the live store,
        which feeds EXPLAIN ANALYZE and pool status), else None."""
        with self._cond:
            if ticket.released or not ticket.granted:
                return None
            ticket.released = True
            now = time.perf_counter()
            st = self._pool_state(ticket.pool)
            st.running -= 1
            st.hbm_inflight -= ticket.hbm
            st.completed += 1
            lat = (now - ticket.grant_t) * 1000
            e2e = (now - ticket.enq_t) * 1000
            st.hist_exec.observe(lat)
            st.hist_e2e.observe(e2e)
            st.busy_ms += lat
            self._running_total -= 1
            self._hbm_total -= ticket.hbm
            finding = None
            slo = st.cfg.slo_ms
            if slo > 0:
                breached = e2e > slo
                st.slo_window.append(1 if breached else 0)
                if breached:
                    st.slo_breaches += 1
                    finding = {
                        "severity": "warning", "kind": "obs.slo",
                        "query": ticket.query_id, "pool": ticket.pool,
                        "slo_ms": slo, "e2e_ms": round(e2e, 3),
                        "burn_rate": st.burn_rate(),
                        "msg": f"SLO burn: pool {ticket.pool!r} query "
                               f"took {e2e:.1f}ms end-to-end against a "
                               f"{slo:.0f}ms target (burn rate "
                               f"{st.burn_rate():.0%} of recent "
                               "completions)"}
                else:
                    st.slo_ok += 1
            self._dispatch()
            self._cond.notify_all()
        return finding

    def note_query(self, ticket: _Ticket, query_id: str | None) -> None:
        """Associate an executed query id with the ticket's pool so
        status() can surface the query's live findings as pool SLO
        signals."""
        if not query_id:
            return
        ticket.query_id = query_id
        with self._cond:
            self._pool_state(ticket.pool).recent_qids.append(query_id)

    # -- the weighted pick ------------------------------------------------
    def _dispatch(self) -> None:
        """Grant every slot currently grantable (caller holds the lock).
        Pure host arithmetic — the decision reads plan-time metadata
        only."""
        mx = int(self._conf.get(SERVE_MAX_CONCURRENT))
        gbudget = int(self._conf.get(MEMORY_BUDGET))
        granted_any = False
        while True:
            if mx > 0 and self._running_total >= mx:
                break
            best = None
            for st in self._pools.values():
                if not st.queue:
                    continue
                cfg = st.cfg
                if cfg.max_concurrent > 0 \
                        and st.running >= cfg.max_concurrent:
                    continue
                head = st.queue[0]
                pbudget = cfg.hbm_budget or gbudget
                # HBM reservation: wait for in-flight queries to free
                # budget. An EMPTY pool/process always admits its head —
                # the per-query check_memory_budget pre-flight already
                # rejected anything that cannot fit alone, so this can
                # never deadlock on an impossible reservation.
                if pbudget > 0 and st.hbm_inflight + head.hbm > pbudget \
                        and st.running > 0:
                    continue
                if gbudget > 0 and self._hbm_total + head.hbm > gbudget \
                        and self._running_total > 0:
                    continue
                key = ((st.served + 1.0) / cfg.weight, head.seq)
                if best is None or key < best[0]:
                    best = (key, st)
            if best is None:
                break
            st = best[1]
            self.grant_log.append(
                (st.cfg.name,
                 frozenset(n for n, s in self._pools.items() if s.queue)))
            t = st.queue.popleft()
            t.granted = True
            t.grant_t = time.perf_counter()
            st.running += 1
            st.served += 1
            st.granted += 1
            st.hbm_inflight += t.hbm
            st.hist_wait.observe((t.grant_t - t.enq_t) * 1000)
            self._running_total += 1
            t.running_at_grant = self._running_total
            self._hbm_total += t.hbm
            self._vclock = max(self._vclock, st.served / st.cfg.weight)
            granted_any = True
        if granted_any:
            self._cond.notify_all()

    # -- drain / status ---------------------------------------------------
    def drain(self) -> None:
        """Reject new submissions from now on; already-queued queries
        are accepted work and still run to completion."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()

    @property
    def draining(self) -> bool:
        return self._draining

    def in_flight(self) -> int:
        with self._cond:
            return self._running_total + sum(len(st.queue)
                                             for st in self._pools.values())

    def quiesce(self, timeout: float = 30.0) -> bool:
        """Wait until nothing is running or queued (True) or the
        timeout passes (False)."""
        deadline = time.monotonic() + max(timeout, 0.0)
        with self._cond:
            while self._running_total > 0 or any(
                    st.queue for st in self._pools.values()):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def status(self, live_obs=None, findings_limit: int = 8) -> dict:
        """Per-pool live serving status: queued/running/rejected depths,
        admission latency percentiles, HBM reservations, and — when the
        session's live store is passed — the straggler/regression
        findings raised for this pool's recent queries (per-pool SLO
        signals)."""
        with self._cond:
            pools = {}
            qids = {}
            for name, st in self._pools.items():
                pools[name] = {
                    "weight": st.cfg.weight,
                    "running": st.running,
                    "queued": len(st.queue),
                    "queue_peak": st.queue_peak,
                    "admitted": st.granted,
                    "completed": st.completed,
                    "rejected_timeout": st.rejected_timeout,
                    "rejected_full": st.rejected_full,
                    "busy_ms": round(st.busy_ms, 3),
                    "hbm_inflight": st.hbm_inflight,
                    # histogram-derived percentiles (bucket upper edges
                    # — identical across any process merge)
                    "p50_ms": st.hist_exec.percentile_ms(0.50),
                    "p95_ms": st.hist_exec.percentile_ms(0.95),
                    "p99_ms": st.hist_exec.percentile_ms(0.99),
                    "wait_p50_ms": st.hist_wait.percentile_ms(0.50),
                    "wait_p99_ms": st.hist_wait.percentile_ms(0.99),
                    "e2e_p50_ms": st.hist_e2e.percentile_ms(0.50),
                    "e2e_p99_ms": st.hist_e2e.percentile_ms(0.99),
                }
                if st.cfg.slo_ms > 0:
                    pools[name]["slo"] = {
                        "slo_ms": st.cfg.slo_ms,
                        "ok": st.slo_ok,
                        "breaches": st.slo_breaches,
                        "burn_rate": st.burn_rate(),
                    }
                qids[name] = list(st.recent_qids)
            out = {"draining": self._draining,
                   "running": self._running_total,
                   "hbm_inflight": self._hbm_total,
                   "pools": pools}
        if live_obs is not None:
            for name, ids in qids.items():
                try:
                    f = live_obs.recent_findings(ids,
                                                 limit=findings_limit)
                except Exception:
                    f = []
                if f:
                    out["pools"][name]["slo_findings"] = f
        return out

    def metrics_samples(self) -> list:
        """Scrape-time pull for the metrics registry (obs/export.py):
        per-pool counters, depth gauges, SLO burn counters, and the
        three latency histograms under a {pool} label. Pure host reads
        under the scheduler lock."""
        out = []
        with self._cond:
            out.append(("gauge", "serve.running", (),
                        float(self._running_total)))
            out.append(("gauge", "serve.hbm_inflight", (),
                        float(self._hbm_total)))
            for name, st in self._pools.items():
                lbl = (("pool", name),)
                out.extend([
                    ("gauge", "serve.pool.running", lbl,
                     float(st.running)),
                    ("gauge", "serve.pool.queued", lbl,
                     float(len(st.queue))),
                    ("counter", "serve.pool.admitted", lbl, st.granted),
                    ("counter", "serve.pool.completed", lbl,
                     st.completed),
                    ("counter", "serve.pool.rejected_timeout", lbl,
                     st.rejected_timeout),
                    ("counter", "serve.pool.rejected_full", lbl,
                     st.rejected_full),
                    ("histogram", "serve.pool.wait_ms", lbl,
                     st.hist_wait.snapshot()),
                    ("histogram", "serve.pool.exec_ms", lbl,
                     st.hist_exec.snapshot()),
                    ("histogram", "serve.pool.e2e_ms", lbl,
                     st.hist_e2e.snapshot()),
                ])
                if st.cfg.slo_ms > 0:
                    out.append(("counter", "serve.pool.slo_breaches",
                                lbl, st.slo_breaches))
                    out.append(("counter", "serve.pool.slo_ok", lbl,
                                st.slo_ok))
        return out

    def contended_grants(self) -> dict:
        """Per-pool slot grants made while at least two pools had queued
        demand — the weighted-fairness evidence: for uniform queries the
        contended-grant ratio IS the throughput share under contention
        (2:1 weights ⇒ 2:1 grants, by the stride pick)."""
        with self._cond:
            log = list(self.grant_log)
        out: dict = {}
        for name, waiters in log:
            if len(waiters) >= 2:
                out[name] = out.get(name, 0) + 1
        return out

    def fairness_ratio(self) -> float | None:
        """max/min of weight-normalized contended-grant shares across
        pools that saw contention (1.0 = perfectly proportional); None
        when fewer than two pools ever contended."""
        grants = self.contended_grants()
        if len(grants) < 2:
            return None
        with self._cond:
            shares = [grants[n] / max(self._pools[n].cfg.weight, 1e-9)
                      for n in grants]
        lo = min(shares)
        return round(max(shares) / lo, 3) if lo > 0 else None

    def balanced(self) -> bool:
        """True when every reservation has been returned — the
        drain-gate invariant (no leaked slots, no leaked HBM)."""
        with self._cond:
            return (self._running_total == 0 and self._hbm_total == 0
                    and all(st.running == 0 and st.hbm_inflight == 0
                            and not st.queue
                            for st in self._pools.values()))


