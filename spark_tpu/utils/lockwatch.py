"""Runtime lock-discipline validation: the dynamic half of race_lint.

The static analyzer (analysis/race_lint.py) builds a whole-repo model of
shared mutable state, the locks guarding it, and the lock-acquisition
nesting graph — but a static model is only a claim. This module proves
the claims at runtime, under the real concurrent loads the tests run
(tests/test_race_lint.py: a serve load, a 2-worker cluster chaos leg):

  * **acquisition orders** — every acquire of a watched lock while other
    watched locks are held records a (held → acquired) edge. The tests
    union the observed edges with the static nesting graph and fail on
    any cycle the static model missed
    (a deadlock hazard that only manifests under a rare interleaving is
    still a hazard).

  * **held-lock sets at flagged mutation sites** — instrumented sites
    (the utils/counters.py locked counters, plus explicit `check_guard`
    probes at `# guarded-by:` annotated sites) record whether the lock
    the static model claims guards the mutation was ACTUALLY held.
    Every annotation must be held where claimed.

Zero overhead when idle — by construction, not by measurement:

  * Watched locks are NOT proxies installed unconditionally. Modules
    `register()` the (owner, attribute) slot their lock lives in;
    `enable()` swaps a `WatchedLock` into the slot and `disable()` swaps
    the raw lock back. An idle process runs raw `threading.Lock`s with
    no wrapper frame on any acquire.
  * Per-instance locks created after `enable()` go through
    `maybe_wrap()`, which returns the raw lock untouched when idle.
  * Instrumented mutation sites gate on the module bool `ENABLED`
    (one attribute read — the same fast-path discipline utils/faults.py
    uses for its injection points).

Activation: `enable()` / `disable()` (tests), the
`SPARK_TPU_LOCKWATCH=1` environment variable (covers module-import-time
lock creation and ships to cluster workers through the inherited
environment), or `spark.tpu.lockwatch.enabled` via `configure(conf)`
(per-session, the config.py-registered surface).

Pure host bookkeeping: never launches a kernel, never touches a device
array, and the observation structures are guarded by a dedicated leaf
lock (`_OBS_LOCK`) that is only ever acquired last — the watcher cannot
introduce the deadlocks it exists to find.
"""

from __future__ import annotations

import os
import threading

__all__ = ["ENABLED", "WatchedLock", "acquire_counts", "check_guard",
           "configure", "disable", "enable", "find_cycle", "guard_checks",
           "held_locks", "maybe_wrap", "order_edges", "register",
           "registered_names", "reset_observations", "violations"]

# fast-path flag: instrumented sites check this module bool before doing
# anything else, so an idle process pays one attribute read per probe
ENABLED = os.environ.get("SPARK_TPU_LOCKWATCH", "") == "1"

# observation state: a dedicated LEAF lock — acquired only momentarily
# inside record paths and never while calling out, so watching locks can
# never deadlock against the watcher itself
_OBS_LOCK = threading.Lock()
_REGISTRY: dict[str, tuple] = {}       # name -> (owner, attr)
_EDGES: dict[tuple, int] = {}          # (held_name, acquired_name) -> n
_ACQUIRES: dict[str, int] = {}         # name -> successful acquires
_GUARD_CHECKS: dict[tuple, int] = {}   # (site, lock_name) -> n held-ok
_VIOLATIONS: list[dict] = []           # {site, lock, held} guard misses
_MAX_VIOLATIONS = 256                  # bound the list on a broken run

# per-thread stack of held watched-lock names, innermost last
_HELD = threading.local()


def _stack() -> list:
    st = getattr(_HELD, "stack", None)
    if st is None:
        st = _HELD.stack = []
    return st


class WatchedLock:
    """Proxy around a raw lock recording acquisition order and held
    sets. Same blocking semantics as the wrapped lock — the record step
    happens after a successful acquire and before release, under the
    leaf observation lock only."""

    __slots__ = ("_raw", "name")

    def __init__(self, name: str, raw):
        self.name = name
        self._raw = raw

    # -- lock protocol ---------------------------------------------------
    def acquire(self, blocking: bool = True, timeout: float = -1):
        ok = self._raw.acquire(blocking, timeout)
        if ok:
            self._record_acquired()
        return ok

    def release(self) -> None:
        self._record_released()
        self._raw.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._raw.locked()

    # -- recording -------------------------------------------------------
    def _record_acquired(self) -> None:
        st = _stack()
        with _OBS_LOCK:
            _ACQUIRES[self.name] = _ACQUIRES.get(self.name, 0) + 1
            for held in st:
                # one edge per held lock (not just the innermost): a
                # cycle through any pair of simultaneously-held locks
                # is a deadlock hazard
                e = (held, self.name)
                _EDGES[e] = _EDGES.get(e, 0) + 1
        st.append(self.name)

    def _record_released(self) -> None:
        st = _stack()
        # remove the LAST occurrence — watched locks release LIFO on the
        # happy path, but a try/finally unwind may release out of order
        for i in range(len(st) - 1, -1, -1):
            if st[i] == self.name:
                del st[i]
                break


# ---------------------------------------------------------------------------
# Registration and activation
# ---------------------------------------------------------------------------

def register(name: str, owner, attr: str) -> None:
    """Declare that `getattr(owner, attr)` is a lock worth watching
    (`owner` is a module or a long-lived singleton). Cheap at import
    time: one dict insert. When lockwatch is (or becomes) enabled the
    slot is swapped to a WatchedLock; `disable()` swaps the raw lock
    back, so the idle process always runs unwrapped locks."""
    with _OBS_LOCK:
        _REGISTRY[name] = (owner, attr)
    if ENABLED:
        _swap_in(name, owner, attr)


def maybe_wrap(name: str, lock):
    """Wrap a freshly created per-instance lock when lockwatch is live;
    return it untouched (zero overhead, no proxy) when idle. For locks
    on objects created after `enable()` — module-level locks should use
    `register()` so they can be swapped at any time."""
    if not ENABLED:
        return lock
    return WatchedLock(name, lock)


def _swap_in(name: str, owner, attr: str) -> None:
    cur = getattr(owner, attr, None)
    if cur is None or isinstance(cur, WatchedLock):
        return
    setattr(owner, attr, WatchedLock(name, cur))


def _swap_out(owner, attr: str) -> None:
    cur = getattr(owner, attr, None)
    if isinstance(cur, WatchedLock):
        setattr(owner, attr, cur._raw)


def enable() -> None:
    """Turn watching on and swap every registered lock slot to its
    watched proxy. Safe to call at any point; locks acquired before the
    swap simply record nothing for that holding."""
    global ENABLED
    ENABLED = True
    with _OBS_LOCK:
        items = list(_REGISTRY.items())
    for name, (owner, attr) in items:
        _swap_in(name, owner, attr)


def disable() -> None:
    """Swap raw locks back into every registered slot and stop
    recording. Observations are kept until reset_observations()."""
    global ENABLED
    ENABLED = False
    with _OBS_LOCK:
        items = list(_REGISTRY.items())
    for _name, (owner, attr) in items:
        _swap_out(owner, attr)


def configure(conf) -> None:
    """Per-session switch through the registered config surface
    (spark.tpu.lockwatch.enabled). Never turns an env-var-enabled
    process off — SPARK_TPU_LOCKWATCH=1 is how cluster workers
    inherit watching through their spawn environment."""
    from ..config import LOCKWATCH_ENABLED

    want = bool(conf.get(LOCKWATCH_ENABLED))
    if want and not ENABLED:
        enable()
    elif not want and ENABLED \
            and os.environ.get("SPARK_TPU_LOCKWATCH", "") != "1":
        disable()


# ---------------------------------------------------------------------------
# Instrumented mutation sites
# ---------------------------------------------------------------------------

def check_guard(site: str, lock_name: str) -> bool:
    """Record whether `lock_name` is held by the current thread at the
    flagged mutation site `site`. Instrumented sites call this INSIDE
    their critical section, gated on the `ENABLED` fast path:

        if lockwatch.ENABLED:
            lockwatch.check_guard("net.transport.RETRY_STATS",
                                  "counter.net.transport.RETRY_STATS")

    A miss lands in `violations()` — the --race gate fails on any."""
    held = tuple(_stack())
    ok = lock_name in held
    with _OBS_LOCK:
        if ok:
            k = (site, lock_name)
            _GUARD_CHECKS[k] = _GUARD_CHECKS.get(k, 0) + 1
        elif len(_VIOLATIONS) < _MAX_VIOLATIONS:
            _VIOLATIONS.append({"site": site, "lock": lock_name,
                                "held": held})
    return ok


def held_locks() -> tuple:
    """Watched-lock names the current thread holds, outermost first."""
    return tuple(_stack())


# ---------------------------------------------------------------------------
# Observations (the read surface)
# ---------------------------------------------------------------------------

def order_edges() -> dict[tuple, int]:
    """(held, acquired) watched-lock name pairs observed, with counts."""
    with _OBS_LOCK:
        return dict(_EDGES)


def acquire_counts() -> dict[str, int]:
    with _OBS_LOCK:
        return dict(_ACQUIRES)


def guard_checks() -> dict[tuple, int]:
    """(site, lock) -> times the guard was verified held."""
    with _OBS_LOCK:
        return dict(_GUARD_CHECKS)


def violations() -> list[dict]:
    """Guard checks that found the claimed lock NOT held."""
    with _OBS_LOCK:
        return list(_VIOLATIONS)


def registered_names() -> list[str]:
    with _OBS_LOCK:
        return sorted(_REGISTRY)


def reset_observations() -> None:
    """Drop recorded edges/checks/violations (registry stays)."""
    with _OBS_LOCK:
        _EDGES.clear()
        _ACQUIRES.clear()
        _GUARD_CHECKS.clear()
        del _VIOLATIONS[:]


# ---------------------------------------------------------------------------
# Cycle detection (shared shape with race_lint's static check)
# ---------------------------------------------------------------------------

def find_cycle(edges) -> list | None:
    """First directed cycle in an iterable of (src, dst) name pairs, as
    a node path [a, b, ..., a]; None when acyclic. Self-loops are
    ignored: same-NAME edges come from distinct per-instance locks of
    one class (the watcher buckets by name), which cannot deadlock a
    single holder."""
    adj: dict[str, list] = {}
    for a, b in edges:
        if a == b:
            continue
        adj.setdefault(a, []).append(b)
    WHITE, GREY, BLACK = 0, 1, 2
    color: dict[str, int] = {}
    path: list[str] = []

    def dfs(u: str):
        color[u] = GREY
        path.append(u)
        for v in sorted(adj.get(u, ())):
            c = color.get(v, WHITE)
            if c == GREY:
                return path[path.index(v):] + [v]
            if c == WHITE:
                found = dfs(v)
                if found:
                    return found
        path.pop()
        color[u] = BLACK
        return None

    for node in sorted(adj):
        if color.get(node, WHITE) == WHITE:
            found = dfs(node)
            if found:
                return found
    return None
