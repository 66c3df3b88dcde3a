"""Cluster backend: multi-process executors over the gRPC transport.

Role of the reference's cluster scheduling backend + local-cluster test
mode (core/scheduler/cluster/CoarseGrainedSchedulerBackend.scala:372
makeOffers/:426 launchTasks; core/SparkContext.scala:3464 local-cluster
regex → core/deploy/LocalSparkCluster.scala:38): the driver runs a
control-plane RpcServer (executor registration + heartbeats), workers
dial in by ADDRESS with the cluster secret and are scheduled tasks over
their own task/block endpoint. Registration is address-based, so any
process that can reach the driver endpoint joins the same way the
reference's standalone workers do — LocalCluster merely spawns its
initial workers itself. Defaults bind 127.0.0.1 (same-host process
groups, the local-cluster test mode); a genuine multi-host deployment
passes bind_host=<reachable IP> here and in worker_env.

Tasks ship as cloudpickle payloads (the ClosureCleaner/serializer role).
Executor loss is detected on RPC failure (UNAVAILABLE ≙ Netty channel
inactive), recorded in the HealthTracker, and the task retries on
another executor (TaskSetManager.maxFailures role).
"""

from __future__ import annotations

import contextvars
import os
import pickle
import secrets
import subprocess
import sys
import threading
import time
from typing import Any, Callable

import cloudpickle

from ..net.transport import (
    RemoteRpcError, RpcClient, RpcServer, RpcUnavailableError,
)
from .scheduler import ExecutorRegistry, HealthTracker


class RemoteTaskError(RuntimeError):
    """The task itself raised on the worker (no retry — deterministic)."""


class ExecutorLostError(RuntimeError):
    pass


class _Worker:
    def __init__(self, client: RpcClient, executor_id: str, host: str,
                 pid: int | None = None,
                 proc: subprocess.Popen | None = None):
        self.client = client
        self.executor_id = executor_id
        self.host = host
        self.pid = pid
        self.proc = proc
        self.lock = threading.Lock()  # one in-flight task per slot
        self.busy = False
        self.idle_since = time.monotonic()

    def try_acquire(self) -> bool:
        if self.lock.acquire(blocking=False):
            self.busy = True
            return True
        return False

    def release(self) -> None:
        self.busy = False
        self.idle_since = time.monotonic()
        try:
            self.lock.release()
        except RuntimeError:
            pass

    def run_locked(self, payload: bytes) -> Any:
        """Execute with the slot already held by the caller."""
        raw = self.client.call("launch_task", payload)
        try:
            decoded = pickle.loads(raw)
            status, result = decoded[0], decoded[1]
        except Exception as e:
            raise RemoteTaskError(f"undecodable task reply: {e}")
        if status == "err":
            err = RemoteTaskError(result)
            # a failed stage task ships its packaged obs alongside the
            # traceback (chaos salvage) — ride it on the exception so
            # the retry loop can hand the wasted-work record upward
            if len(decoded) > 2 and decoded[2] is not None:
                err.salvaged_obs = decoded[2]
            raise err
        return result

    def run(self, payload: bytes) -> Any:
        """Acquire the slot (blocking), execute, release."""
        self.lock.acquire()
        self.busy = True
        try:
            return self.run_locked(payload)
        finally:
            self.release()

    def close(self):
        self.client.close()
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def worker_env(driver_addr: str, token: str,
               host_label: str = "localhost",
               bind_host: str = "127.0.0.1",
               heartbeat_interval: float | None = None) -> dict:
    """Environment for a worker process: CPU-pinned jax (a chip belongs
    to one process, and that process is the driver — the process cluster
    is a CPU-worker path) + driver coordinates. `bind_host` is the address the worker's own server
    binds AND advertises; a worker on another machine sets it to an IP
    the driver and peer workers can reach. `heartbeat_interval` sets the
    executor heartbeat/live-obs flush cadence in seconds
    (spark.tpu.heartbeat.interval)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPARK_TPU_WORKER_KEY"] = token
    env["SPARK_TPU_DRIVER_ADDR"] = driver_addr
    env["SPARK_TPU_WORKER_HOST"] = host_label
    env["SPARK_TPU_BIND_HOST"] = bind_host
    if heartbeat_interval is not None:
        env["SPARK_TPU_HEARTBEAT_INTERVAL"] = str(heartbeat_interval)
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    return env


class LocalCluster:
    """Spawns num_workers executor processes and schedules tasks on them.
    More executors — including ones labeled as other "hosts" — may join
    at any time via the driver address + secret. With
    dynamic_allocation=True an allocation thread grows the pool when
    tasks back up behind busy executors and retires idle ones back to
    num_workers (role of core/ExecutorAllocationManager.scala:102 —
    backlog-driven scale-out, idle-timeout scale-in)."""

    def __init__(self, num_workers: int = 2, max_task_failures: int = 3,
                 bind_host: str = "127.0.0.1",
                 speculation: bool = False,
                 speculation_multiplier: float = 1.5,
                 speculation_interval: float | None = None,
                 dynamic_allocation: bool = False,
                 max_workers: int | None = None,
                 executor_idle_timeout: float = 10.0,
                 shuffle_service: bool = False,
                 push_shuffle: bool = False,
                 heartbeat_interval: float | None = None):
        self.max_task_failures = max_task_failures
        self.registry = ExecutorRegistry()
        # timed exclusion by default (excludeOnFailure semantics); the
        # SQL scheduler re-configures from session conf at query time
        self.health = HealthTracker(self.registry, max_failures=2,
                                    exclude_s=30.0)
        self.token = secrets.token_hex(16)
        self.bind_host = bind_host
        self.heartbeat_interval = heartbeat_interval
        # live-telemetry sink: executor heartbeats carry obs deltas of
        # running stage tasks; the owning session points this at its
        # LiveObs.on_heartbeat (obs/live.py). None = deltas dropped.
        self.obs_sink = None
        # straggler signal hook (obs/live.LiveObs.active_stragglers):
        # when it reports flagged tasks, speculation launches the backup
        # copy immediately instead of waiting out the duration-history
        # threshold
        self.speculation_signal = None
        # speculative execution (TaskSetManager.scala:80-88 checkSpeculatableTasks
        # role): when a task runs longer than multiplier × median of
        # completed tasks (or the fixed interval), a second copy launches
        # on another executor; first success wins. Exactly-one-commit for
        # file outputs is the OutputCommitCoordinator's job (io/commit.py).
        self.speculation = speculation
        self.speculation_multiplier = speculation_multiplier
        self.speculation_interval = speculation_interval
        self._durations: list[float] = []
        self.stats: dict[str, int] = {}
        self._workers: dict[str, _Worker] = {}
        self._rr = 0
        self._lock = threading.Lock()
        self._joined = threading.Condition(self._lock)
        self._slot_free = threading.Condition()
        self._barriers: dict[str, dict] = {}
        self._barrier_cv = threading.Condition()
        # FAIR scheduler pools (core/scheduler/Pool.scala +
        # SchedulableBuilder.scala FAIR mode): when tasks from several
        # pools contend for slots, the pool with the smallest
        # running/weight ratio is offered the next free slot
        self.pool_weights: dict[str, float] = {"default": 1.0}
        self._pool_running: dict[str, int] = {}
        self._pool_waiting: dict[str, int] = {}

        # 64 handler threads: barrier_sync PARKS a thread per waiting gang
        # member (see _on_barrier), and heartbeats must still get served
        # while a gang waits — run_barrier_job caps gangs at half this
        self._server = RpcServer(self.token, host=bind_host,
                                 max_workers=64)
        self._server.register("register_executor", self._on_register)
        self._server.register("heartbeat", self._on_heartbeat)
        self._server.register("barrier_sync", self._on_barrier)
        self.driver_addr = self._server.start()

        # external shuffle service: blocks survive executor loss
        # (exec/shuffle_service.py; ExternalShuffleService.scala role)
        self.shuffle_service = None
        self.shuffle_service_addr: str | None = None
        self._shuffle_dir: str | None = None
        self.push_shuffle = push_shuffle
        if shuffle_service or push_shuffle:
            import tempfile

            from .shuffle_service import ExternalShuffleService

            self._shuffle_dir = tempfile.mkdtemp(prefix="sparktpu-shuffle-")
            self.shuffle_service = ExternalShuffleService(
                self._shuffle_dir, self.token, host=bind_host)
            self.shuffle_service_addr = self.shuffle_service.start()

        procs = [self._spawn() for _ in range(num_workers)]
        self._await_workers(num_workers, procs)

        self.min_workers = num_workers
        self.max_workers = max_workers or num_workers * 4
        self.idle_timeout = executor_idle_timeout
        self._active_tasks = 0
        self._stopping = False
        if dynamic_allocation:
            # race-lint: ignore[bare-submit] — executor-fleet sizing
            # loop: session-lifetime, aggregates across queries
            threading.Thread(target=self._allocation_loop,
                             daemon=True).start()

    # -- control-plane handlers (run on server threads) -----------------
    def _on_register(self, payload: bytes) -> bytes:
        info = pickle.loads(payload)
        client = RpcClient(info["addr"], self.token)
        # Connect BEFORE registering: a fresh channel's first call can
        # fail UNAVAILABLE transiently while TCP/HTTP2 set up, which the
        # task path would misread as executor loss — and an unreachable
        # worker must not become a ghost registry entry.
        try:
            client.wait_ready(10)
        except Exception:
            client.close()
            raise
        eid = self.registry.register(host=info["host"], slots=1)
        with self._lock:
            self._workers[eid] = _Worker(client, eid, info["host"],
                                         pid=info.get("pid"))
            self._joined.notify_all()
        return eid.encode()

    def _on_heartbeat(self, payload: bytes) -> bytes:
        """Heartbeat = liveness + live telemetry (HeartbeatReceiver +
        the reference's executor metrics/accumulator-update channel in
        one call): the payload is a pickled {eid, obs} dict whose obs
        list carries per-task mid-stage snapshots, routed to the
        session's LiveObs. Bare-eid payloads (externally-started legacy
        workers) stay accepted."""
        try:
            msg = pickle.loads(payload)
        except Exception:
            msg = {"eid": payload.decode()}
        eid = msg["eid"]
        ok = self.registry.heartbeat(eid)
        sink = self.obs_sink
        if ok and sink is not None and (
                msg.get("obs") or msg.get("hbm") is not None
                or msg.get("metrics") is not None):
            try:
                # the sink is LiveObs.on_heartbeat, which takes the
                # executor-level resource fields too (per-executor HBM
                # occupancy, the flush-budget overflow counter, and —
                # with the metrics plane on — the worker's registry
                # counter snapshot for worker-labeled scrape series)
                sink(eid, msg.get("obs") or [],
                     hbm=msg.get("hbm"),
                     overflows=msg.get("obs_overflows"),
                     metrics=msg.get("metrics"))
            except Exception:
                # telemetry must never fail a liveness heartbeat — but a
                # sink bug must not vanish either: count every swallowed
                # error where live status can see it (a bare `pass` here
                # once hid every sink regression)
                with self._lock:
                    self.stats["heartbeat.telemetry_errors"] = \
                        self.stats.get("heartbeat.telemetry_errors", 0) + 1
                owner = getattr(sink, "__self__", None)
                if owner is not None:
                    try:
                        owner.telemetry_errors += 1
                    except Exception:
                        pass
        return b"ok" if ok else b"unknown"

    # ------------------------------------------------------------------
    def _spawn(self, host_label: str = "localhost") -> subprocess.Popen:
        env = worker_env(self.driver_addr, self.token, host_label,
                         bind_host=self.bind_host,
                         heartbeat_interval=self.heartbeat_interval)
        if self.push_shuffle:
            # push mode: blocks travel over the network to the service —
            # the cross-host deployment (no shared filesystem assumed)
            env["SPARK_TPU_SHUFFLE_PUSH_ADDR"] = self.shuffle_service_addr
        elif self._shuffle_dir:
            env["SPARK_TPU_SHUFFLE_DIR"] = self._shuffle_dir
        return subprocess.Popen(
            [sys.executable, "-m", "spark_tpu.exec.worker_main"], env=env)

    def _await_workers(self, expect: int, procs: list, timeout: float = 60.0):
        deadline = time.monotonic() + timeout
        with self._lock:
            while len(self._workers) < expect:
                rest = deadline - time.monotonic()
                if rest <= 0 or not self._joined.wait(timeout=rest):
                    raise RuntimeError(
                        f"only {len(self._workers)}/{expect} workers "
                        f"registered within {timeout}s")
        # adopt process handles BY PID (registration order ≠ spawn order;
        # a swapped handle would make _Worker.close() terminate the wrong
        # — possibly healthy — process)
        with self._lock:
            by_pid = {p.pid: p for p in procs}
            for w in self._workers.values():
                if w.proc is None and w.pid in by_pid:
                    w.proc = by_pid.pop(w.pid)

    def add_worker(self, host_label: str = "localhost") -> None:
        """Join one more executor process (dynamic allocation growth)."""
        before = len(self._workers)
        proc = self._spawn(host_label)
        self._await_workers(before + 1, [proc])

    # ------------------------------------------------------------------
    def _pick_free(self, timeout: float | None = None,
                   avoid: frozenset | set = frozenset()) -> _Worker | None:
        """ACQUIRE a free executor slot (central task queue semantics —
        TaskSchedulerImpl.resourceOffers: tasks go to whichever executor
        has a free slot, instead of binding to one at submit and queueing
        behind it, which would leave executors added by dynamic
        allocation idle). Caller must release().

        `avoid` de-prioritizes executors that already failed THIS task
        (TaskSetManager's per-task attempt excludelist role): avoided
        executors are offered the slot only when no other executor is
        free — progress beats purity on a shrunken cluster. Executors
        excluded cluster-wide (HealthTracker window exclusion) never
        appear at all: registry.alive() filters them."""
        deadline = None if timeout is None else time.monotonic() + timeout
        override_counted = False
        while True:
            with self._lock:
                alive = [self._workers[e.executor_id]
                         for e in self.registry.alive()
                         if e.executor_id in self._workers]
                if not alive:
                    # distinguish a DEAD cluster from a fully-EXCLUDED
                    # one: excluded executors are alive processes that
                    # will rejoin at their re-inclusion horizon —
                    # failing the query with 'no alive executors' would
                    # be both misleading and a needless abort. Schedule
                    # on excluded executors rather than starve (the
                    # reference aborts the task set here; overriding
                    # keeps liveness and the override is counted).
                    registered = [self._workers[e.executor_id]
                                  for e in self.registry.registered()
                                  if e.executor_id in self._workers]
                    if not registered:
                        raise ExecutorLostError("no alive executors")
                    alive = registered
                    if not override_counted:
                        override_counted = True
                        self.stats["exclusion_overridden"] = \
                            self.stats.get("exclusion_overridden", 0) + 1
                order = alive[self._rr % len(alive):] + \
                    alive[:self._rr % len(alive)]
                self._rr += 1
            if avoid:
                order = [w for w in order
                         if w.executor_id not in avoid] + \
                        [w for w in order if w.executor_id in avoid]
            for w in order:
                if w.try_acquire():
                    return w
            if deadline is not None and time.monotonic() >= deadline:
                return None
            with self._slot_free:
                self._slot_free.wait(timeout=0.05)

    def set_pool_weight(self, pool: str, weight: float) -> None:
        self.pool_weights[pool] = float(weight)

    def run_task(self, fn: Callable, *args, pool: str = "default") -> Any:
        return self.run_task_traced(fn, *args, pool=pool)[0]

    def run_task_traced(self, fn: Callable, *args,
                        pool: str = "default", task_key=None,
                        on_failed_attempt: Callable | None = None) -> tuple:
        """Run a task; returns (result, worker) so callers can register
        which executor holds the outputs (MapOutputTracker role).
        `task_key` identifies the task to the live straggler signal
        (cluster_sql passes (shuffle id, map id)) so speculation scopes
        its decision to THIS task. `on_failed_attempt(executor_id, err,
        salvaged_obs)` is invoked (best-effort) for every attempt the
        retry loop absorbs — transient task failures and executor
        losses — so the caller can record the wasted work the failed
        attempt's salvaged obs describes."""
        payload = cloudpickle.dumps((fn, args))
        with self._lock:
            self._active_tasks += 1
        try:
            return self._run_with_retries(payload, pool, task_key,
                                          on_failed_attempt)
        finally:
            with self._lock:
                self._active_tasks -= 1

    def _pool_turn(self, pool: str) -> bool:
        """FAIR arbitration: this pool may take the next slot iff no
        contending pool (one with waiters) has a smaller
        running/weight share."""
        with self._lock:
            my = self._pool_running.get(pool, 0) / \
                self.pool_weights.get(pool, 1.0)
            for p, waiting in self._pool_waiting.items():
                if p == pool or waiting <= 0:
                    continue
                share = self._pool_running.get(p, 0) / \
                    self.pool_weights.get(p, 1.0)
                if share < my:
                    return False
            return True

    def _is_transient_task_error(self, e: Exception) -> bool:
        """Worker-side task failures worth retrying on ANOTHER executor
        (and counting against the reporting executor's excludeOnFailure
        window): injected chaos faults and runtime resource exhaustion.
        FetchFailed is NOT one — it must reach the DAG scheduler intact
        so lineage regenerates the lost map stage. Everything else stays
        deterministic (retrying a genuine task bug elsewhere fails the
        same way and wastes an executor's failure budget)."""
        from ..utils.faults import is_transient_marker
        from .map_output import FetchFailedError

        text = str(e)
        if FetchFailedError.MARKER in text:
            return False
        return is_transient_marker(text)

    def _record_failure(self, executor_id: str, lost: bool) -> None:
        """Count a task failure / executor loss in the HealthTracker
        (window-based exclusion) and the cluster stats."""
        with self._lock:
            k = "executor_losses" if lost else "transient_task_failures"
            self.stats[k] = self.stats.get(k, 0) + 1
        try:
            self.health.record_failure(executor_id)
        except Exception:
            pass

    @staticmethod
    def _notify_failed_attempt(cb, eid: str, e: Exception) -> None:
        """Best-effort wasted-work notification — the retry path must
        never fail because the obs side-channel did."""
        if cb is None:
            return
        try:
            cb(eid, e, getattr(e, "salvaged_obs", None))
        except Exception:
            pass

    def _run_with_retries(self, payload: bytes,
                          pool: str = "default", task_key=None,
                          on_failed_attempt: Callable | None = None) -> tuple:
        last: Exception | None = None
        avoid: set = set()   # executors that already failed THIS task
        with self._lock:
            self._pool_waiting[pool] = self._pool_waiting.get(pool, 0) + 1
        waiting = True  # balances _pool_waiting on EVERY exit path
        try:
            for _ in range(self.max_task_failures):
                # fairness must be re-checked every time a slot frees: a
                # task already spinning in _pick_free would otherwise race
                # slots it is not entitled to
                w = None
                while w is None:
                    if not self._pool_turn(pool):
                        with self._slot_free:
                            self._slot_free.wait(timeout=0.05)
                        continue
                    w = self._pick_free(timeout=0.05, avoid=avoid)
                with self._lock:
                    self._pool_waiting[pool] -= 1
                    waiting = False
                    self._pool_running[pool] = \
                        self._pool_running.get(pool, 0) + 1
                try:
                    if self.speculation:
                        return self._run_speculative(payload, w, task_key)
                    try:
                        return w.run_locked(payload), w
                    finally:
                        w.release()
                        self._notify_slot_free()
                except (RemoteTaskError, RemoteRpcError) as e:
                    # only a TASK-side raise can be transient: a
                    # RemoteRpcError (oversized payload, bad auth) has
                    # RESOURCE_EXHAUSTED-shaped text but is the CALL
                    # failing deterministically, not the executor
                    if isinstance(e, RemoteTaskError) and \
                            self._is_transient_task_error(e):
                        # transient worker-side failure (injected fault /
                        # resource exhaustion): the executor is alive but
                        # suspect — count it toward exclusion and retry
                        # the task elsewhere (TaskSetManager.maxFailures).
                        # Under speculation the raiser may be the BACKUP
                        # copy's executor, stamped on the exception.
                        last = e
                        failed_eid = getattr(e, "failing_executor",
                                             w.executor_id)
                        self._record_failure(failed_eid, lost=False)
                        self._notify_failed_attempt(on_failed_attempt,
                                                    failed_eid, e)
                        avoid.add(failed_eid)
                        with self._lock:  # retry waits for a slot again
                            self._pool_waiting[pool] += 1
                            waiting = True
                        continue
                    # the task (or its payload) failed deterministically —
                    # retrying on another healthy executor won't help, and
                    # the executor that reported it is NOT dead
                    raise
                except (RpcUnavailableError, OSError) as e:
                    last = e
                    self._record_failure(w.executor_id, lost=True)
                    self._notify_failed_attempt(on_failed_attempt,
                                                w.executor_id, e)
                    self.registry.remove(w.executor_id)  # executor lost
                    avoid.add(w.executor_id)
                    w.close()
                    self._notify_slot_free()
                    with self._lock:  # retry waits for a slot again
                        self._pool_waiting[pool] += 1
                        waiting = True
                finally:
                    with self._lock:
                        self._pool_running[pool] -= 1
        finally:
            if waiting:
                with self._lock:
                    self._pool_waiting[pool] -= 1
        if last is not None and not isinstance(
                last, (RpcUnavailableError, OSError, ExecutorLostError)):
            raise last  # transient task failures exhausted the budget
        raise ExecutorLostError(
            f"task failed after {self.max_task_failures} executor losses: "
            f"{last}")

    def _notify_slot_free(self) -> None:
        with self._slot_free:
            self._slot_free.notify_all()

    # -- dynamic allocation (ExecutorAllocationManager.scala:102) --------
    def _allocation_loop(self):
        backlog_ticks = 0
        while not self._stopping:
            time.sleep(0.5)
            alive = self.registry.alive()
            n = len(alive)
            with self._lock:
                backlog = self._active_tasks - n
            backlog_ticks = backlog_ticks + 1 if backlog > 0 else 0
            if backlog_ticks >= 2 and n < self.max_workers:
                try:
                    self.add_worker()
                    self.stats["executors_added"] = \
                        self.stats.get("executors_added", 0) + 1
                except Exception:
                    pass
                backlog_ticks = 0
            elif n > self.min_workers:
                now = time.monotonic()
                with self._lock:
                    idle = [w for e in alive
                            if (w := self._workers.get(e.executor_id))
                            is not None and not w.busy
                            and w.proc is not None
                            and now - w.idle_since > self.idle_timeout]
                if idle and len(alive) > self.min_workers:
                    w = max(idle, key=lambda x: now - x.idle_since)
                    self.registry.remove(w.executor_id)
                    with self._lock:
                        self._workers.pop(w.executor_id, None)
                    w.close()
                    self.stats["executors_retired"] = \
                        self.stats.get("executors_retired", 0) + 1

    # -- speculation -----------------------------------------------------
    def _speculation_threshold(self) -> float | None:
        if self.speculation_interval is not None:
            return self.speculation_interval
        with self._lock:
            hist = sorted(self._durations)
        if len(hist) < 3:  # not enough history to call a straggler
            return None
        return max(0.1, self.speculation_multiplier
                   * hist[len(hist) // 2])

    def _signal_flags(self, task_key) -> bool:
        """Does the live straggler signal (obs/live.py via
        cluster_sql's keyed lambda) flag THIS task? Scoping the check
        to the task key keeps one straggler from collapsing the
        speculation threshold for every in-flight task — which is also
        why a task WITHOUT a key never consumes the signal: an unkeyed
        run_task with 'is any task anywhere straggling?' semantics
        would double-launch every unrelated task the moment one
        straggler is flagged. Keyless tasks rely on the
        duration-history threshold alone."""
        sig = self.speculation_signal
        if sig is None or task_key is None:
            return False
        try:
            try:
                # host list truthiness (LiveObs findings), never device
                return bool(sig(task_key))  # tpulint: ignore[host-sync]
            except TypeError:
                return bool(sig())  # tpulint: ignore[host-sync]
        except Exception:
            return False

    def _run_speculative(self, payload: bytes, primary: _Worker,
                         task_key=None) -> tuple:
        """First-success-wins across up to two attempts. `primary`
        arrives with its slot already acquired; each attempt thread
        releases its own slot. The straggler's reply (it still completes
        eventually) is discarded; any file commits it tries are
        arbitrated by the OutputCommitCoordinator."""
        import queue

        q: queue.Queue = queue.Queue()
        in_flight = [0]

        def attempt(w: _Worker):
            t0 = time.monotonic()
            try:
                q.put(("ok", w.run_locked(payload), w,
                       time.monotonic() - t0))
            except (RemoteTaskError, RemoteRpcError) as e:
                q.put(("task_err", e, w, 0.0))
            except Exception as e:
                q.put(("lost", e, w, 0.0))
            finally:
                w.release()
                self._notify_slot_free()

        def launch(w: _Worker):
            in_flight[0] += 1
            # the attempt dispatches THIS query's task: copy the
            # caller's contextvar scope onto the runner thread so any
            # obs recorded around the RPC keeps its query attribution
            ctx = contextvars.copy_context()
            # race-lint: ignore[bare-submit] — scope propagated
            # explicitly via ctx.run on the line above
            threading.Thread(target=ctx.run, args=(attempt, w),
                             daemon=True).start()

        launch(primary)
        threshold = self._speculation_threshold()
        sig = self.speculation_signal
        first = None
        backup_launched = False
        deadline = (time.monotonic() + threshold) \
            if threshold is not None else None
        # wait for the primary: the duration-history threshold bounds
        # the wait, and the live straggler signal — polled, scoped to
        # THIS task — cuts it short the moment the task is flagged
        # mid-flight (a straggler is only ever flagged AFTER launch, so
        # a one-shot check at launch time would never fire)
        while first is None and not backup_launched:
            if deadline is None and sig is None:
                break  # no speculation trigger possible: plain wait below
            now = time.monotonic()
            if (deadline is not None and now >= deadline) or \
                    self._signal_flags(task_key):
                try:
                    backup = self._pick_free(timeout=0)
                except ExecutorLostError:
                    backup = None
                if backup is not None:
                    self.stats["speculative_launched"] = \
                        self.stats.get("speculative_launched", 0) + 1
                    launch(backup)
                backup_launched = True
                break
            step = 0.1 if sig is not None else deadline - now
            if deadline is not None:
                step = min(step, max(deadline - now, 0.0))
            try:
                first = q.get(timeout=max(step, 0.0))
            except queue.Empty:
                pass
        while True:
            kind, val, w, dur = first if first is not None else q.get()
            first = None
            in_flight[0] -= 1
            if kind == "ok":
                with self._lock:
                    self._durations.append(dur)
                if in_flight[0] > 0:
                    self.stats["speculative_wins"] = \
                        self.stats.get("speculative_wins", 0) + 1
                return val, w
            if kind == "task_err":
                # the failure may come from the BACKUP copy — stamp the
                # actually-failing executor so the retry loop's failure
                # accounting does not blame the (possibly healthy)
                # primary
                try:
                    val.failing_executor = w.executor_id
                except Exception:
                    pass
                raise val
            # executor lost: drop it; if a copy is still running, let it
            # decide the task, else surface to the retry loop
            self.registry.remove(w.executor_id)
            w.close()
            if in_flight[0] == 0:
                raise val

    # -- barrier (BarrierTaskContext.scala barrier()/allGather()) --------
    def _on_barrier(self, payload: bytes) -> bytes:
        # bid carries the epoch (barrier_id#round) — see
        # exec/barrier.py BarrierTaskContext._sync
        bid, task_id, num_tasks, message, timeout = pickle.loads(payload)
        deadline = time.monotonic() + timeout
        with self._barrier_cv:
            st = self._barriers.setdefault(
                bid, {"msgs": {}, "done": False})
            st["msgs"][task_id] = message
            if len(st["msgs"]) >= num_tasks:
                st["done"] = True
                st["out"] = [st["msgs"][t] for t in sorted(st["msgs"])]
                self._barrier_cv.notify_all()
            else:
                while not st["done"]:
                    rest = deadline - time.monotonic()
                    if rest <= 0 or not self._barrier_cv.wait(timeout=rest):
                        st["msgs"].pop(task_id, None)
                        raise TimeoutError(
                            f"barrier {bid}: {len(st['msgs'])}/"
                            f"{num_tasks} tasks after {timeout}s")
            out = st["out"]
            st["returned"] = st.get("returned", 0) + 1
            if st["returned"] >= num_tasks:
                self._barriers.pop(bid, None)
            return pickle.dumps(out)

    def alive_workers(self) -> list:
        with self._lock:
            return [self._workers[e.executor_id]
                    for e in self.registry.alive()
                    if e.executor_id in self._workers]

    def registered_workers(self) -> list:
        """Every connected worker INCLUDING excluded ones — cleanup
        paths must reach executors that exclusion removed from
        scheduling (their block stores still hold data)."""
        with self._lock:
            return [self._workers[e.executor_id]
                    for e in self.registry.registered()
                    if e.executor_id in self._workers]

    def lockwatch_edges(self) -> dict:
        """Collect each worker's lockwatch observations (order edges,
        registered slot names, guard violations) over RPC so
        tests/test_race_lint.py can fold executor-process lock behaviour
        into the same cross-check it runs on the driver. Unreachable
        workers are skipped — the caller asserts on who DID answer."""
        with self._lock:
            workers = list(self._workers.items())
        out: dict = {}
        for eid, w in workers:
            try:
                raw = w.client.call("lockwatch_edges", b"", timeout=15)
                out[eid] = pickle.loads(raw)
            except Exception:
                continue
        return out

    def diagnostic_state(self) -> dict:
        """Black-box fleet state pull (obs/blackbox): each worker's
        bounded post-task diagnostic ring plus its fault-registry,
        lockwatch, and metrics state, fetched over RPC ONLY while a
        diagnostic bundle is being assembled — the healthy path never
        calls this, so heartbeat payloads stay unchanged. Unreachable
        workers are skipped (the bundle records who answered)."""
        with self._lock:
            workers = list(self._workers.items())
        out: dict = {}
        for eid, w in workers:
            try:
                raw = w.client.call("diagnostic_state", b"", timeout=15)
                out[eid] = pickle.loads(raw)
            except Exception:
                continue
        return out

    def run_task_on(self, worker, fn: Callable, *args) -> Any:
        """Run on a SPECIFIC executor (barrier gangs need distinct
        executors — two gang members queued on one worker's slot would
        deadlock at the sync point)."""
        return worker.run(cloudpickle.dumps((fn, args)))

    def map(self, fn: Callable, items) -> list:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=max(len(self._workers), 1)) as p:
            return list(p.map(lambda x: self.run_task(fn, x), items))

    def num_alive(self) -> int:
        return len(self.registry.alive())

    @property
    def authkey_hex(self) -> str:
        """Cluster secret (name kept from the pipe-transport era; it is
        the auth token FetchExec ships to consumers)."""
        return self.token

    def stop(self):
        self._stopping = True
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            w.close()
        if self.shuffle_service is not None:
            self.shuffle_service.stop()
            import shutil

            shutil.rmtree(self._shuffle_dir, ignore_errors=True)
        self._server.stop()
