"""Deterministic fault injection: named fault points with seeded rules.

Role of the reference's chaos/fault-injection test hooks (the
DistributedSuite kill-executor tests, FailureSuite's deterministic task
failures, and the excludeOnFailure/HealthTracker suites all hand-roll
their faults) generalized into one seeded, process-local registry the
chaos suite (tests/test_chaos.py) drives through regular session conf:

  spark.tpu.faults.enabled  master switch (default off)
  spark.tpu.faults.seed     deterministic seed for probabilistic rules
  spark.tpu.faults.points   ';'-separated rules, each
                            point=trigger[:arg][:action[:arg]][@scope]

Named points are threaded through the stack at the seams where real
deployments fail:

  rpc.call         control-plane unary call about to be issued
  block.fetch      shuffle-block fetch about to stream
  worker.task      cluster stage task body (worker process)
  heartbeat.flush  executor heartbeat about to be sent
  kernel.compile   KernelCache miss about to build/compile
  kernel.dispatch  cached kernel about to launch
  shuffle.write    map output block about to be stored

Triggers: `once` (first matching call), `nth:N` (exactly the Nth,
1-based), `first:N` (calls 1..N), `after:N` (every call past the Nth —
the blackout shape: let N through, then fail forever), `prob:P`
(seeded coin per call), `always`. Actions: default raises the site's
transport/fault error;
`kill` hard-exits the process (os._exit — the worker-death chaos mode);
`sleep:S` injects S seconds of latency and returns (the straggler
chaos mode). An optional `@scope` suffix restricts the rule to
processes whose host label matches OR to calls whose detail string
contains the scope (e.g. `kernel.dispatch=once@whole_query`).

Contract: with the registry disabled (the default) every fault point is
a single module-bool check — zero kernel launches, zero syncs, no
allocation — so the obs layer's zero-overhead guards hold with the
layer compiled in but idle. Counters are process-local; rules ship to
worker processes with the rest of the session conf and are installed by
exec/worker_main.begin_stage_obs, exactly like the encoding/resource
switches.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import zlib

from . import lockwatch

__all__ = ["ENABLED", "InjectedFault", "KernelCompileError", "configure",
           "maybe_fail", "fire_counts", "reset", "is_transient_marker",
           "is_runtime_fault"]

# fast-path flag: fault points check this module bool before anything
# else, so a healthy run pays one attribute read per instrumented call
ENABLED = False

# process identity for @scope matching: the worker's host label (set
# from SPARK_TPU_WORKER_HOST when rules install), "driver" elsewhere
HOST_LABEL = "driver"

_LOCK = threading.Lock()
lockwatch.register("utils.faults._LOCK", sys.modules[__name__], "_LOCK")
_RULES: dict[str, "_Rule"] = {}
_FIRED: dict[str, int] = {}
_SEED = 0
# last-installed (enabled, seed, spec): configure() is called per stage
# task on workers, and an unchanged spec must NOT reset the per-rule
# call counters (nth/first count over the process lifetime, not per
# task — resetting would make `nth:1` fire on every task)
_INSTALLED: tuple | None = None


class InjectedFault(RuntimeError):
    """A deterministic injected failure. The MARKER survives pickling
    and cross-process traceback stringification, so the driver can
    classify a worker-side injected fault as TRANSIENT (retry the task
    elsewhere, count the executor failure) rather than deterministic."""

    MARKER = "SPARK_TPU_INJECTED_FAULT"
    # markers the cluster retry loop treats as transient task failures
    # (retried on another executor up to max_task_failures, counted
    # against the executor's excludeOnFailure window). A real runtime
    # RESOURCE_EXHAUSTED on a worker is the same class of event.
    TRANSIENT_MARKERS = (MARKER, "RESOURCE_EXHAUSTED")

    def __init__(self, point: str, detail: str = ""):
        suffix = f" ({detail})" if detail else ""
        super().__init__(f"{self.MARKER}[{point}]{suffix}")
        self.point = point


class KernelCompileError(RuntimeError):
    """The backend refused to COMPILE a kernel (Mosaic/XLA rejection,
    VMEM or HBM exhaustion at compile time, unimplemented op). jax raises
    the same XlaRuntimeError for a compile refusal and an execution
    fault, so KernelCache classifies at the call site: an error out of a
    kernel that has never yet run is its compile (physical/compile.py).
    Never a runtime fault — a program the chip refuses must surface, not
    quietly re-execute on a smaller tier."""


def is_runtime_fault(e: BaseException) -> bool:
    """Is this a RUNTIME failure of a compiled program (XLA runtime
    error, device resource exhaustion, injected dispatch/compile chaos)
    rather than a logic error or a compile refusal? Runtime faults are
    recoverable by degrading to a smaller execution granularity — the
    whole-query tier re-executes stage-at-a-time, a mesh gang retries
    then falls back to the host shuffle. Logic errors and compile
    refusals must keep propagating: re-executing a deterministic bug
    elsewhere hides it."""
    if isinstance(e, InjectedFault):
        return True
    if isinstance(e, KernelCompileError):
        return False
    name = type(e).__name__
    if name in ("XlaRuntimeError", "JaxRuntimeError", "InternalError",
                "ResourceExhaustedError"):
        return True
    text = str(e)
    return ("RESOURCE_EXHAUSTED" in text or "XlaRuntimeError" in text
            or InjectedFault.MARKER in text)


def is_transient_marker(text: str) -> bool:
    """Does an error's text identify a TRANSIENT task failure (worth
    retrying on another executor) rather than a deterministic one?
    Callers must check FetchFailed FIRST (lineage regen, not task
    retry) — this helper only knows the transient markers."""
    return any(m in text for m in InjectedFault.TRANSIENT_MARKERS)


class _Rule:
    __slots__ = ("point", "trigger", "arg", "action", "action_arg",
                 "scope", "calls", "rng")

    def __init__(self, point: str, trigger: str, arg: float,
                 action: str, action_arg: float, scope: str):
        self.point = point
        self.trigger = trigger      # once|nth|first|prob|always
        self.arg = arg
        self.action = action        # raise|kill|sleep
        self.action_arg = action_arg
        self.scope = scope
        self.calls = 0              # matching (in-scope) calls so far
        # per-rule deterministic stream: same seed + same call order →
        # same fault schedule, independent of other points' traffic
        import random

        self.rng = random.Random(_SEED ^ zlib.crc32(point.encode()))

    def should_fire(self) -> bool:
        self.calls += 1
        n = self.calls
        if self.trigger == "once":
            return n == 1
        if self.trigger == "nth":
            return n == int(self.arg)
        if self.trigger == "first":
            return n <= int(self.arg)
        if self.trigger == "after":
            return n > int(self.arg)
        if self.trigger == "prob":
            return self.rng.random() < self.arg
        return True  # always


def _parse_rule(spec: str) -> _Rule:
    spec = spec.strip()
    point, _, rhs = spec.partition("=")
    if not rhs:
        raise ValueError(f"bad fault rule {spec!r} (want point=trigger)")
    rhs, _, scope = rhs.partition("@")
    toks = rhs.split(":")
    trigger = toks.pop(0).strip().lower()
    if trigger not in ("once", "nth", "first", "after", "prob", "always"):
        raise ValueError(f"unknown fault trigger {trigger!r} in {spec!r}")
    arg = 1.0
    if trigger in ("nth", "first", "after", "prob"):
        if not toks:
            raise ValueError(f"trigger {trigger!r} needs an argument "
                             f"in {spec!r}")
        arg = float(toks.pop(0))
    action, action_arg = "raise", 0.0
    if toks:
        action = toks.pop(0).strip().lower()
        if action not in ("kill", "sleep", "raise"):
            raise ValueError(f"unknown fault action {action!r} in {spec!r}")
        if action == "sleep":
            if not toks:
                raise ValueError(f"sleep action needs seconds in {spec!r}")
            action_arg = float(toks.pop(0))
    if toks:
        raise ValueError(f"trailing tokens {toks} in fault rule {spec!r}")
    return _Rule(point.strip(), trigger, arg, action, action_arg,
                 scope.strip())


def configure(conf) -> None:
    """(Re)install the registry from session conf. Called per session on
    the driver (TpuSession.__init__) and per stage task on workers
    (exec/worker_main.begin_stage_obs) — the same shipping path every
    other process-global switch takes. Idempotent on an UNCHANGED spec
    (per-rule call counters keep counting across tasks); a changed spec
    reinstalls with fresh counters, so one test's consumed `once` rule
    never leaks into the next."""
    global ENABLED, HOST_LABEL, _SEED, _INSTALLED

    from ..config import FAULTS_ENABLED, FAULTS_POINTS, FAULTS_SEED

    # conf values are host data — never a device read
    enabled = bool(conf.get(FAULTS_ENABLED))  # tpulint: ignore[host-sync]
    seed = int(conf.get(FAULTS_SEED))  # tpulint: ignore[host-sync]
    spec = str(conf.get(FAULTS_POINTS) or "")
    with _LOCK:
        want = (enabled, seed, spec)
        if want == _INSTALLED:
            return
        _INSTALLED = want
        if not enabled:
            ENABLED = False
            _RULES.clear()
            _FIRED.clear()
            return
        _SEED = seed
        HOST_LABEL = os.environ.get("SPARK_TPU_WORKER_HOST", "driver")
        _RULES.clear()
        _FIRED.clear()
        for part in spec.replace(",", ";").split(";"):
            if not part.strip():
                continue
            rule = _parse_rule(part)
            _RULES[rule.point] = rule
        ENABLED = bool(_RULES)


def reset() -> None:
    """Disable the registry and drop all rules/counters (test teardown)."""
    global ENABLED, _INSTALLED
    with _LOCK:
        ENABLED = False
        _INSTALLED = None
        _RULES.clear()
        _FIRED.clear()


def fire_counts() -> dict[str, int]:
    with _LOCK:
        return dict(_FIRED)


def maybe_fail(point: str, detail: str = "", exc=None) -> None:
    """Evaluate one fault point. No-op unless a rule for `point` is
    installed and in scope; otherwise fires per the rule's trigger:
    raises `exc(message)` (default InjectedFault), kills the process, or
    sleeps. Call sites guard with `if faults.ENABLED:` so the idle cost
    is one module-bool read."""
    if not ENABLED:
        return
    with _LOCK:
        rule = _RULES.get(point)
        if rule is None:
            return
        if rule.scope and rule.scope != HOST_LABEL \
                and rule.scope not in detail:
            return
        fire = rule.should_fire()
        if fire:
            _FIRED[point] = _FIRED.get(point, 0) + 1
            action, action_arg = rule.action, rule.action_arg
    if not fire:
        return
    if action == "kill":
        os._exit(17)
    if action == "sleep":
        time.sleep(action_arg)
        return
    if exc is not None:
        raise exc(f"{InjectedFault.MARKER}[{point}] injected "
                  f"({detail or 'no detail'})")
    raise InjectedFault(point, detail)
