"""race_lint: whole-repo static race & lock-discipline analyzer.

tpulint (analysis/lint.py) guards the engine's PERFORMANCE contracts
(host syncs, recompiles); this pass guards its CONCURRENCY contracts —
the bug class every review-hardening pass since the engine went
multi-threaded (par_map lanes, heartbeat flushers, serve pools, RPC
handler threads, speculation watchers) has been fixing by hand.

Pure AST, no jax import, whole-repo: unlike tpulint's per-file rules,
these properties only exist at the repo level — a mutation in one
module is racy because of a thread spawned in another.

The model, built in two passes:

  1. Per module: process-global mutable state (module-level dict/list/
     set/counter assignments, attributes of singleton instances like
     ``GLOBAL_KERNEL_CACHE = KernelCache()``), every mutation site of
     that state, lock definitions (module-level ``X = threading.Lock()``
     and ``self.X = threading.Lock()`` class locks), ``with <lock>:``
     guard structure, call/reference names per function, and thread
     spawn sites (``threading.Thread``, ``pool.submit``,
     ``scoped_submit``, ``par_map``).
  2. Whole repo: a name-based call graph links spawn roots to every
     mutation they can reach; guard sets are inferred from enclosing
     ``with`` blocks (plus ``# guarded-by: <lock>`` annotations where
     the lock is held by a caller the AST cannot see); a lock-nesting
     graph is built from lexical ``with`` nesting plus transitive
     acquires of functions called under a held lock.

Rules:

  * ``shared-mutation`` — a process-global object mutated at sites
    reachable from a thread root with NO lock common to all of its
    mutation sites. Fix with a shared lock, the utils/counters.py
    locked-counter helpers (recognized as internally guarded), or a
    ``# guarded-by:`` annotation naming the caller-held lock.
  * ``lock-order`` — a cycle in the inferred lock-acquisition nesting
    graph (deadlock hazard). Same-name self-loops are ignored: the
    graph buckets per-instance locks by class, and two instances of one
    class cannot deadlock a single holder ordering.
  * ``bare-submit`` — a bare ``threading.Thread(...)`` or
    ``pool.submit(fn)`` in obs-scoped code: pool/thread entry without
    ``scoped_submit``/``par_map`` drops the contextvar query scope (the
    PR 4/6 attribution-loss bug class, now a rule instead of a
    test-by-test hunt). Long-lived service threads that never dispatch
    query-scoped work carry a pragma with a written justification.
  * ``worker-reinit`` — mutated process-global state in worker-shipped
    modules with no re-init path (no reset/configure-style function
    reassigning or clearing it): a forked/spawned worker inherits or
    re-imports the module and the state silently diverges from the
    driver's.

Suppression mirrors tpulint: a ``# race-lint: ignore[rule]`` pragma on
(or immediately above) the offending line, or the checked-in
per-(file,rule)-count baseline ``dev/race_baseline.json`` so existing
debt doesn't block CI while NEW violations do.

The model is also the contract the runtime half validates
(utils/lockwatch.py, driven by tests/test_race_lint.py): exported
``lock_edges`` are unioned with OBSERVED acquisition orders (no cycle
may appear), and every ``# guarded-by:`` annotation must be held where
claimed at instrumented mutation sites.
"""

from __future__ import annotations

import ast
import builtins as _builtins
import json
import os
import re
from dataclasses import dataclass, field

_BUILTIN_NAMES = frozenset(dir(_builtins))

__all__ = ["RULES", "RepoModel", "Violation", "baseline_counts",
           "build_model", "build_model_from_sources", "find_cycle",
           "lint_paths", "lint_sources", "load_baseline",
           "new_violations", "write_baseline"]

RULES = ("shared-mutation", "lock-order", "bare-submit", "worker-reinit")

# directories whose code may run with the obs query scope active: thread
# handoffs there must propagate contextvars (scoped_submit / par_map)
_OBS_DIRS = ("exec", "serve", "obs", "rdd", "streaming", "connect",
             "deploy")
# modules shipped to (re-imported by) cluster worker processes: mutated
# globals there need an explicit re-init path
_WORKER_DIRS = ("exec", "net", "obs", "utils", "columnar", "ops",
                "physical", "parallel")

_PRAGMA_RE = re.compile(r"#\s*race-lint:\s*ignore(?:\[([a-z\-,\s]+)\])?")
_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z0-9_.]+)")
_REINIT_RE = re.compile(r"(reset|configure|install|init|clear)", re.I)

_LOCK_CTORS = {"threading.Lock", "Lock", "threading.RLock", "RLock"}
# state kinds created by these constructors never count as bare shared
# state: contextvars/thread-locals are per-context by design, the
# locked counters are internally guarded (utils/counters.py)
_EXEMPT_CTORS = {"ContextVar", "contextvars.ContextVar", "local",
                 "threading.local", "LockedCounter", "LockedCounterMap",
                 "Event", "threading.Event"}
_CONTAINER_CTORS = {"dict", "list", "set", "collections.Counter",
                    "Counter", "collections.OrderedDict", "OrderedDict",
                    "collections.defaultdict", "defaultdict",
                    "collections.deque", "deque"}
_MUTATORS = {"append", "extend", "add", "update", "setdefault", "pop",
             "popitem", "clear", "remove", "discard", "insert",
             "appendleft", "popleft"}


# ---------------------------------------------------------------------------
# Findings
# ---------------------------------------------------------------------------

@dataclass
class Violation:
    rule: str
    path: str            # repo-relative
    line: int
    col: int
    snippet: str
    message: str

    @property
    def bucket(self) -> str:
        """Baseline bucket: stable under line shifts."""
        return f"{self.path}::{self.rule}"

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: [{self.rule}] "
                f"{self.message}\n    {self.snippet}")


# ---------------------------------------------------------------------------
# Per-module scan products
# ---------------------------------------------------------------------------

@dataclass
class _Mutation:
    state: str           # repo-global state id ("net.transport.RETRY_STATS")
    path: str
    line: int
    col: int
    func: str            # enclosing function qualname
    guards: frozenset    # resolved lock ids + annotation lock names
    annotated: tuple     # guarded-by annotation lock names on this site


@dataclass
class _Spawn:
    kind: str            # thread | submit | scoped_submit | par_map
    target: str          # bare callable name ("" unknown, "<lambda>")
    target_is_func: bool  # Name/Attribute/Lambda (callable-shaped arg)
    path: str
    line: int
    col: int
    func: str            # spawning function qualname


@dataclass
class _WithBlock:
    expr: tuple          # (dotted, class_ctx) — resolved in pass 2
    line: int
    col: int
    nested: list         # inner _WithBlock list
    calls: list          # (call descriptor, line) made under the lock


@dataclass
class _Func:
    qualname: str
    bare: str
    modname: str
    path: str
    class_ctx: str | None
    calls: set = field(default_factory=set)     # call descriptors
    refs: set = field(default_factory=set)      # bare Name loads
    withs: list = field(default_factory=list)   # top-level _WithBlocks


@dataclass
class _Module:
    path: str
    modname: str
    lines: list
    pragmas: dict
    locks: dict = field(default_factory=dict)        # local name -> id
    class_locks: dict = field(default_factory=dict)  # (cls, attr) -> id
    # state id -> kind ("container" | "scalar" | "exempt")
    states: dict = field(default_factory=dict)
    state_lines: dict = field(default_factory=dict)  # state id -> def line
    singleton_classes: set = field(default_factory=set)
    funcs: dict = field(default_factory=dict)        # qualname -> _Func
    mutations: list = field(default_factory=list)
    # (class, attr) self-mutations kept until singleton filter in pass 2
    attr_mutations: list = field(default_factory=list)
    spawns: list = field(default_factory=list)
    # function bare name -> set of state names it re-initializes
    reinits: dict = field(default_factory=dict)
    annotations: list = field(default_factory=list)  # {path,line,lock,state}


# ---------------------------------------------------------------------------
# Small AST helpers (same idioms as analysis/lint.py)
# ---------------------------------------------------------------------------

def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._rl_parent = node  # type: ignore[attr-defined]


def _parent(node):
    return getattr(node, "_rl_parent", None)


def _dotted(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _call_desc(func_node) -> tuple | None:
    """Call-site descriptor ("bare"|"self"|"qual"|"obj", recv, name) —
    the key pass 2 resolves into call-graph edges. Precision over
    recall: an unresolvable receiver yields "obj", which only links when
    the method name is UNIQUE repo-wide (so `cache.get_or_build(...)`
    links but a dict's `.get(...)` links nowhere)."""
    if isinstance(func_node, ast.Name):
        return ("bare", None, func_node.id)
    if isinstance(func_node, ast.Attribute):
        name = func_node.attr
        recv = func_node.value
        if isinstance(recv, ast.Name):
            if recv.id == "self":
                return ("self", None, name)
            return ("qual", recv.id, name)
        return ("obj", None, name)
    return None


def _pragmas(source_lines: list) -> dict:
    """line -> suppressed rule set (None = all). A trailing pragma
    covers its own line; a comment-only pragma line covers itself, any
    continuation comment lines below it (the written justification the
    bare-submit rule asks for), and the next CODE line."""
    out: dict = {}
    for i, line in enumerate(source_lines, start=1):
        m = _PRAGMA_RE.search(line)
        if not m:
            continue
        rules = None
        if m.group(1):
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        if line[:m.start()].strip():
            targets = [i]
        else:
            targets = [i]
            j = i + 1
            while j <= len(source_lines):
                text = source_lines[j - 1].strip()
                targets.append(j)
                if text and not text.startswith("#"):
                    break   # the code line the pragma governs
                j += 1
        for ln in targets:
            prev = out.get(ln, set())
            out[ln] = None if rules is None or prev is None \
                else prev | rules
    return out


def _is_suppressed(pragmas: dict, line: int, rule: str) -> bool:
    if line not in pragmas:
        return False
    rules = pragmas[line]
    return rules is None or rule in rules


def _guard_annotations(source_lines: list, line: int) -> tuple:
    """guarded-by lock names annotated on `line` (trailing) or on a
    comment-only line immediately above."""
    out = []
    for ln in (line, line - 1):
        if not (0 < ln <= len(source_lines)):
            continue
        text = source_lines[ln - 1]
        m = _GUARDED_BY_RE.search(text)
        if not m:
            continue
        if ln == line or not text[:m.start()].strip():
            out.append(m.group(1))
    return tuple(out)


def _modname(relpath: str) -> str:
    p = relpath.replace(os.sep, "/")
    if p.startswith("spark_tpu/"):
        p = p[len("spark_tpu/"):]
    if p.endswith(".py"):
        p = p[:-3]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


def _in_dirs(relpath: str, dirs) -> bool:
    parts = relpath.replace(os.sep, "/").split("/")
    return any(d in parts[:-1] for d in dirs)


def _enclosing(node):
    """(class_ctx, qualname suffix parts) from the parent chain."""
    parts: list = []
    cls = None
    cur = _parent(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            parts.append(cur.name)
        elif isinstance(cur, ast.ClassDef):
            if cls is None:
                cls = cur.name
            parts.append(cur.name)
        cur = _parent(cur)
    return cls, list(reversed(parts))


def _is_lock_ctor(value: ast.AST) -> bool:
    if not isinstance(value, ast.Call):
        return False
    d = _dotted(value.func)
    if d in _LOCK_CTORS:
        return True
    # lockwatch.maybe_wrap("name", threading.Lock()) keeps lock-ness
    if d.endswith("maybe_wrap") and len(value.args) >= 2:
        return _is_lock_ctor(value.args[1])
    return False


def _state_kind(value: ast.AST) -> str | None:
    """Classify a module-level assignment's value as shared-state
    candidate kind, or None when it is not mutable shared state."""
    if isinstance(value, (ast.Dict, ast.List, ast.Set, ast.ListComp,
                          ast.DictComp, ast.SetComp)):
        return "container"
    if isinstance(value, ast.Constant) and isinstance(
            value.value, (int, float)) and not isinstance(value.value, bool):
        return "scalar"
    if isinstance(value, ast.Constant) and isinstance(value.value, bool):
        return "scalar"
    if isinstance(value, ast.Call):
        d = _dotted(value.func)
        tail = d.rsplit(".", 1)[-1]
        if d in _EXEMPT_CTORS or tail in {t.rsplit(".", 1)[-1]
                                          for t in _EXEMPT_CTORS}:
            return "exempt"
        if d in _CONTAINER_CTORS:
            return "container"
    return None


# ---------------------------------------------------------------------------
# Pass 1: scan one module
# ---------------------------------------------------------------------------

def _scan_module(source: str, relpath: str) -> _Module | None:
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return None
    _attach_parents(tree)
    lines = source.splitlines()
    mod = _Module(path=relpath, modname=_modname(relpath), lines=lines,
                  pragmas=_pragmas(lines))

    class_names = {n.name for n in tree.body if isinstance(n, ast.ClassDef)}

    # ---- module level: locks, states, singletons -----------------------
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1 \
                or not isinstance(node.targets[0], ast.Name):
            continue
        name = node.targets[0].id
        if name.startswith("__"):
            continue
        if _is_lock_ctor(node.value):
            mod.locks[name] = f"{mod.modname}.{name}"
            continue
        if isinstance(node.value, ast.Call):
            d = _dotted(node.value.func)
            if d in class_names:
                mod.singleton_classes.add(d)
                continue
        kind = _state_kind(node.value)
        if kind is not None:
            sid = f"{mod.modname}.{name}"
            mod.states[sid] = kind
            mod.state_lines[sid] = node.lineno

    # ---- class locks ----------------------------------------------------
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Attribute) \
                and isinstance(node.targets[0].value, ast.Name) \
                and node.targets[0].value.id == "self" \
                and _is_lock_ctor(node.value):
            cls, _parts = _enclosing(node)
            if cls is not None:
                attr = node.targets[0].attr
                mod.class_locks[(cls, attr)] = \
                    f"{mod.modname}.{cls}.{attr}"

    # ---- functions ------------------------------------------------------
    fn_nodes = [n for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fn_nodes:
        cls, parts = _enclosing(fn)
        qual = ".".join([mod.modname] + parts + [fn.name])
        info = _Func(qualname=qual, bare=fn.name, modname=mod.modname,
                     path=relpath, class_ctx=cls)
        mod.funcs[qual] = info
        _scan_function(mod, fn, info)

    # ---- re-init paths --------------------------------------------------
    for fn in fn_nodes:
        if not _REINIT_RE.search(fn.name):
            continue
        names: set = set()
        declared: set = set()
        for n in _body_walk(fn):
            if isinstance(n, ast.Global):
                declared.update(n.names)
        for n in _body_walk(fn):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                tgt = n.targets[0] if isinstance(n, ast.Assign) else n.target
                if isinstance(tgt, ast.Name) and tgt.id in declared:
                    names.add(tgt.id)
                if isinstance(tgt, ast.Subscript) and isinstance(
                        tgt.value, ast.Name):
                    names.add(tgt.value.id)
            elif isinstance(n, ast.Call) and isinstance(
                    n.func, ast.Attribute) and isinstance(
                    n.func.value, ast.Name) \
                    and n.func.attr in ("clear", "reset", "update"):
                names.add(n.func.value.id)
        if names:
            mod.reinits.setdefault(fn.name, set()).update(names)
    return mod


def _body_walk(fn: ast.AST):
    """Walk a function body WITHOUT descending into nested function
    definitions (they are separate call-graph nodes); lambdas stay in
    the parent (they execute inline where they are invoked)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def _scan_function(mod: _Module, fn: ast.AST, info: _Func) -> None:
    declared_globals: set = set()
    in_init = info.bare == "__init__"

    def enclosing_withs(node) -> list:
        out = []
        cur = _parent(node)
        while cur is not None and cur is not fn:
            if isinstance(cur, ast.With):
                for item in cur.items:
                    out.append(_dotted(item.context_expr))
            cur = _parent(cur)
        return out

    def add_mutation(state_local: str | None, attr_pair, node) -> None:
        anns = _guard_annotations(mod.lines, node.lineno)
        raw_guards = tuple(enclosing_withs(node))
        entry = (raw_guards, anns, node.lineno,
                 getattr(node, "col_offset", 0), info)
        if state_local is not None:
            mod.mutations.append((f"{mod.modname}.{state_local}",) + entry)
        else:
            mod.attr_mutations.append((attr_pair,) + entry)

    for node in _body_walk(fn):
        if isinstance(node, ast.Global):
            declared_globals.update(node.names)

    for node in _body_walk(fn):
        # ---- mutations of module-level names ---------------------------
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Name) \
                        and tgt.id in declared_globals:
                    add_mutation(tgt.id, None, node)
                elif isinstance(tgt, ast.Subscript):
                    base = tgt.value
                    if isinstance(base, ast.Name):
                        add_mutation(base.id, None, node)
                    elif _is_self_attr(base) and not in_init \
                            and info.class_ctx:
                        add_mutation(None,
                                     (info.class_ctx, base.attr), node)
                elif _is_self_attr(tgt) and not in_init \
                        and info.class_ctx:
                    add_mutation(None, (info.class_ctx, tgt.attr), node)
        elif isinstance(node, ast.Delete):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript) and isinstance(
                        tgt.value, ast.Name):
                    add_mutation(tgt.value.id, None, node)
        elif isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute) and node.func.attr in _MUTATORS:
            recv = node.func.value
            if isinstance(recv, ast.Name):
                add_mutation(recv.id, None, node)
            elif _is_self_attr(recv) and not in_init and info.class_ctx:
                add_mutation(None, (info.class_ctx, recv.attr), node)

        # ---- calls / references ----------------------------------------
        if isinstance(node, ast.Call):
            desc = _call_desc(node.func)
            if desc is not None:
                info.calls.add(desc)
            _maybe_spawn(mod, node, _dotted(node.func), info)
        elif isinstance(node, ast.Name) and isinstance(
                node.ctx, ast.Load):
            # bare references are potential callbacks handed to pools or
            # registries; reachability (only) follows them SAME-MODULE
            info.refs.add(node.id)

    # ---- with structure (lexical lock nesting) -------------------------
    def build_with(node: ast.With) -> list:
        out = []
        for item in node.items:
            wb = _WithBlock(expr=(_dotted(item.context_expr),
                                  info.class_ctx),
                            line=node.lineno,
                            col=node.col_offset, nested=[], calls=[])
            _fill_with_body(wb, node)
            out.append(wb)
        return out

    def _fill_with_body(wb: _WithBlock, node: ast.With) -> None:
        stack = list(node.body)
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(n, ast.With):
                wb.nested.extend(build_with(n))
                continue   # inner with records its own body
            if isinstance(n, ast.Call):
                desc = _call_desc(n.func)
                if desc is not None:
                    wb.calls.append((desc, n.lineno))
            stack.extend(ast.iter_child_nodes(n))

    for node in _body_walk(fn):
        if isinstance(node, ast.With):
            p = _parent(node)
            # only top-level withs here; nested ones ride wb.nested
            inside = False
            while p is not None and p is not fn:
                if isinstance(p, ast.With):
                    inside = True
                    break
                p = _parent(p)
            if not inside:
                info.withs.extend(build_with(node))


def _is_self_attr(node) -> bool:
    return isinstance(node, ast.Attribute) and isinstance(
        node.value, ast.Name) and node.value.id == "self"


def _maybe_spawn(mod: _Module, node: ast.Call, dotted: str,
                 info: _Func) -> None:
    tail = dotted.rsplit(".", 1)[-1] if dotted else ""

    def describe(arg) -> tuple:
        if isinstance(arg, ast.Lambda):
            return "<lambda>", True
        if isinstance(arg, ast.Name):
            return arg.id, True
        if isinstance(arg, ast.Attribute):
            return arg.attr, True
        return "", False

    if tail == "Thread" and dotted in ("Thread", "threading.Thread"):
        target = None
        for kw in node.keywords:
            if kw.arg == "target":
                target = kw.value
        if target is None and node.args:
            target = node.args[0]
        name, is_fn = describe(target) if target is not None else ("",
                                                                   False)
        mod.spawns.append(_Spawn("thread", name, is_fn, mod.path,
                                 node.lineno, node.col_offset,
                                 info.qualname))
    elif tail == "scoped_submit" and len(node.args) >= 2:
        name, is_fn = describe(node.args[1])
        mod.spawns.append(_Spawn("scoped_submit", name, is_fn, mod.path,
                                 node.lineno, node.col_offset,
                                 info.qualname))
    elif tail == "par_map" and node.args:
        name, is_fn = describe(node.args[0])
        mod.spawns.append(_Spawn("par_map", name, is_fn, mod.path,
                                 node.lineno, node.col_offset,
                                 info.qualname))
    elif tail == "submit" and node.args:
        name, is_fn = describe(node.args[0])
        mod.spawns.append(_Spawn("submit", name, is_fn, mod.path,
                                 node.lineno, node.col_offset,
                                 info.qualname))


# ---------------------------------------------------------------------------
# Pass 2: the repo model
# ---------------------------------------------------------------------------

@dataclass
class RepoModel:
    violations: list = field(default_factory=list)
    lock_edges: list = field(default_factory=list)   # (A, B) post-pragma
    annotations: list = field(default_factory=list)  # {path,line,lock,state}
    locks: set = field(default_factory=set)
    states: dict = field(default_factory=dict)       # id -> kind
    spawns: list = field(default_factory=list)

    def to_dict(self) -> dict:
        return {"lock_edges": sorted(self.lock_edges),
                "annotations": list(self.annotations),
                "locks": sorted(self.locks),
                "states": dict(sorted(self.states.items())),
                "spawn_sites": len(self.spawns)}


def find_cycle(edges) -> list | None:
    """First directed cycle over (src, dst) pairs as [a, b, ..., a];
    self-loops ignored (per-instance locks bucket by class)."""
    adj: dict = {}
    for a, b in edges:
        if a == b:
            continue
        adj.setdefault(a, []).append(b)
    color: dict = {}
    path: list = []

    def dfs(u):
        color[u] = 1
        path.append(u)
        for v in sorted(adj.get(u, ())):
            c = color.get(v, 0)
            if c == 1:
                return path[path.index(v):] + [v]
            if c == 0:
                found = dfs(v)
                if found:
                    return found
        path.pop()
        color[u] = 2
        return None

    for node in sorted(adj):
        if color.get(node, 0) == 0:
            found = dfs(node)
            if found:
                return found
    return None


def _resolve_lock(mods: dict, mod: _Module, class_ctx: str | None,
                  dotted: str, attr_index: dict) -> str | None:
    """Map a `with <expr>:` dotted text to a repo lock id, or None when
    the expression is not recognizably a lock (plain context managers
    stay out of the nesting graph)."""
    if not dotted:
        return None
    if "." not in dotted:
        return mod.locks.get(dotted)
    head, _, attr = dotted.rpartition(".")
    if head == "self" and class_ctx is not None:
        lid = mod.class_locks.get((class_ctx, attr))
        if lid is not None:
            return lid
        if "lock" in attr.lower():
            # a lock attribute the scan did not see assigned (inherited,
            # conditional): still class-scoped identity
            return f"{mod.modname}.{class_ctx}.{attr}"
        return None
    # module-qualified: "othermod._LOCK" via import
    tail = head.rsplit(".", 1)[-1]
    for other in mods.values():
        if other.modname == tail or other.modname.endswith("." + tail):
            lid = other.locks.get(attr)
            if lid is not None:
                return lid
    cands = attr_index.get(attr, ())
    if len(cands) == 1:
        return next(iter(cands))
    if "lock" in attr.lower():
        # ambiguous or unknown owner: opaque module-scoped identity so
        # the lexical guard still counts at its own sites
        return f"{mod.modname}.<{dotted}>"
    return None


def _build(mods: list) -> RepoModel:
    model = RepoModel()
    by_name = {m.modname: m for m in mods}

    # ---- singleton classes across the repo -----------------------------
    singleton_classes: set = set()
    for m in mods:
        singleton_classes.update(m.singleton_classes)

    # ---- lock attr index (attr name -> lock ids) -----------------------
    attr_index: dict = {}
    for m in mods:
        for name, lid in m.locks.items():
            attr_index.setdefault(name, set()).add(lid)
            model.locks.add(lid)
        for (_cls, attr), lid in m.class_locks.items():
            attr_index.setdefault(attr, set()).add(lid)
            model.locks.add(lid)

    # ---- functions + call graph ----------------------------------------
    funcs: dict = {}
    all_index: dict = {}    # bare -> [qual], every function (spawn targets)
    func_index: dict = {}   # bare -> [qual], module-level/nested only
    method_index: dict = {} # bare -> [qual], methods only
    permod: dict = {}       # (modname, bare) -> [qual], class_ctx None
    percls: dict = {}       # (modname, cls, bare) -> [qual]
    modtail: dict = {}      # module tail segment -> [modname]
    for m in mods:
        modtail.setdefault(m.modname.rsplit(".", 1)[-1],
                           []).append(m.modname)
    for m in mods:
        for qual, f in m.funcs.items():
            funcs[qual] = f
            all_index.setdefault(f.bare, []).append(qual)
            if f.class_ctx is None:
                func_index.setdefault(f.bare, []).append(qual)
                permod.setdefault((f.modname, f.bare), []).append(qual)
            else:
                method_index.setdefault(f.bare, []).append(qual)
                percls.setdefault((f.modname, f.class_ctx, f.bare),
                                  []).append(qual)

    def resolve_call(f: _Func, desc: tuple) -> list:
        """Call descriptor -> function qualnames. Precise first (same
        module, own class, module-qualified); an opaque receiver only
        links when the method name is unique repo-wide."""
        kind, recv, name = desc
        if kind == "bare":
            local = permod.get((f.modname, name))
            if local:
                return local
            if name in _BUILTIN_NAMES:
                return []
            cands = func_index.get(name, ())
            return list(cands) if len(cands) == 1 else []
        if kind == "self":
            if f.class_ctx is not None:
                own = percls.get((f.modname, f.class_ctx, name))
                if own:
                    return own
            cands = method_index.get(name, ())
            return list(cands) if len(cands) == 1 else []
        if kind == "qual":
            for modname in modtail.get(recv, ()):
                hit = permod.get((modname, name))
                if hit:
                    return hit
            for m2 in mods:
                hit = percls.get((m2.modname, recv, name))
                if hit:   # ClassName.method(...) static-style call
                    return hit
        cands = all_index.get(name, ())
        return list(cands) if len(cands) == 1 else []

    def callees(f: _Func):
        out = []
        for desc in f.calls:
            out.extend(resolve_call(f, desc))
        return out

    def reach_callees(f: _Func):
        # reachability additionally follows same-module bare references
        # (callbacks registered/handed off without an explicit call)
        out = callees(f)
        for name in f.refs:
            out.extend(permod.get((f.modname, name), ()))
        return out

    # ---- states ---------------------------------------------------------
    for m in mods:
        for sid, kind in m.states.items():
            model.states[sid] = kind

    # resolve mutations: module-name states + singleton attrs
    mutations: list = []
    for m in mods:
        for (sid, raw_guards, anns, line, col, f) in m.mutations:
            if sid in model.states:
                mutations.append((m, sid, raw_guards, anns, line, col, f))
        for ((cls, attr), raw_guards, anns, line, col, f) \
                in m.attr_mutations:
            if cls not in singleton_classes:
                continue
            if (cls, attr) in m.class_locks:
                continue    # the lock slot itself
            sid = f"{m.modname}.{cls}.{attr}"
            model.states.setdefault(sid, "singleton-attr")
            mutations.append((m, sid, raw_guards, anns, line, col, f))

    # guard resolution + annotation collection
    resolved: list = []
    for (m, sid, raw_guards, anns, line, col, f) in mutations:
        guards = set()
        for g in raw_guards:
            lid = _resolve_lock(by_name, m, f.class_ctx, g, attr_index)
            if lid is not None:
                guards.add(lid)
        for a in anns:
            lid = _resolve_annotation(a, model.locks)
            guards.add(lid)
            model.annotations.append({"path": m.path, "line": line,
                                      "lock": lid, "state": sid})
        resolved.append(_Mutation(sid, m.path, line, col, f.qualname,
                                  frozenset(guards), anns))

    # ---- spawns + reachability -----------------------------------------
    for m in mods:
        model.spawns.extend(m.spawns)

    reach_cache: dict = {}

    def reachable_from(bare: str) -> set:
        cached = reach_cache.get(bare)
        if cached is not None:
            return cached
        seen: set = set()
        frontier = list(all_index.get(bare, ()))
        seen.update(frontier)
        while frontier:
            q = frontier.pop()
            for cq in reach_callees(funcs[q]):
                if cq not in seen:
                    seen.add(cq)
                    frontier.append(cq)
        reach_cache[bare] = seen
        return seen

    spawn_roots: list = []   # (spawn, reachable qualname set)
    for sp in model.spawns:
        if not sp.target or sp.target == "<lambda>":
            # unknown body: treat the SPAWNING function's callees as the
            # root frontier (the lambda closes over them)
            spawn_roots.append((sp, reachable_from(
                sp.func.rsplit(".", 1)[-1])))
        else:
            spawn_roots.append((sp, reachable_from(sp.target)))

    def spawn_reaching(func_qual: str) -> list:
        return [sp for sp, reach in spawn_roots if func_qual in reach]

    # ---- rule: shared-mutation -----------------------------------------
    by_state: dict = {}
    for mu in resolved:
        by_state.setdefault(mu.state, []).append(mu)
    for sid, sites in sorted(by_state.items()):
        if model.states.get(sid) == "exempt":
            continue
        active = [mu for mu in sites
                  if not _is_suppressed(_pragmas_of(mods, mu.path),
                                        mu.line, "shared-mutation")]
        if not active:
            continue
        common = frozenset.intersection(*[mu.guards for mu in active])
        if common:
            continue
        roots: list = []
        for mu in active:
            roots.extend(spawn_reaching(mu.func))
        if not roots:
            continue    # only ever mutated on the spawning/main thread
        root_desc = sorted({f"{sp.kind}@{sp.path}:{sp.line}"
                            for sp in roots})[:3]
        guard_desc = sorted({lid for mu in active for lid in mu.guards})
        for mu in active:
            _emit(model, mods, "shared-mutation", mu.path, mu.line,
                  mu.col,
                  f"process-global '{sid}' is mutated on thread roots "
                  f"({', '.join(root_desc)}) with no lock common to all "
                  f"{len(active)} mutation site(s)"
                  + (f" (guards seen: {', '.join(guard_desc)})"
                     if guard_desc else " (no guards seen)")
                  + " — guard every site with one lock, use a "
                    "utils/counters.py locked counter, or annotate the "
                    "caller-held lock with '# guarded-by: <lock>'")

    # ---- rule: lock-order ----------------------------------------------
    # acq*: transitive lock acquisitions per function (fixpoint)
    direct_acq: dict = {}
    for qual, f in funcs.items():
        mod = by_name[f.modname]
        acc: set = set()

        def collect(wb: _WithBlock):
            lid = _resolve_lock(by_name, mod, wb.expr[1], wb.expr[0],
                                attr_index)
            if lid is not None:
                acc.add(lid)
            for nb in wb.nested:
                collect(nb)

        for wb in f.withs:
            collect(wb)
        direct_acq[qual] = acc

    trans_acq = {q: set(s) for q, s in direct_acq.items()}
    changed = True
    while changed:
        changed = False
        for qual, f in funcs.items():
            cur = trans_acq[qual]
            before = len(cur)
            for cq in callees(f):
                cur |= trans_acq.get(cq, ())
            if len(cur) != before:
                changed = True

    edges: dict = {}   # (A, B) -> (path, line, col)

    def add_edge(a: str, b: str, path: str, line: int, col: int) -> None:
        if a == b:
            return
        edges.setdefault((a, b), (path, line, col))

    for qual, f in funcs.items():
        mod = by_name[f.modname]

        def walk_wb(wb: _WithBlock):
            lid = _resolve_lock(by_name, mod, wb.expr[1], wb.expr[0],
                                attr_index)
            if lid is not None:
                for nb in wb.nested:
                    nlid = _resolve_lock(by_name, mod, nb.expr[1],
                                         nb.expr[0], attr_index)
                    if nlid is not None:
                        add_edge(lid, nlid, f.path, nb.line, nb.col)
                for (desc, line) in wb.calls:
                    for cq in resolve_call(f, desc):
                        for b in trans_acq.get(cq, ()):
                            add_edge(lid, b, f.path, line, 0)
            for nb in wb.nested:
                walk_wb(nb)

        for wb in f.withs:
            walk_wb(wb)

    # pragma'd edges leave both the findings AND the exported graph (a
    # suppressed edge is an assertion the nesting cannot happen)
    kept = {}
    for (a, b), (path, line, col) in edges.items():
        if _is_suppressed(_pragmas_of(mods, path), line, "lock-order"):
            continue
        kept[(a, b)] = (path, line, col)
    model.lock_edges = sorted(kept)

    graph_edges = set(kept)
    while True:
        cyc = find_cycle(graph_edges)
        if cyc is None:
            break
        cyc_edges = list(zip(cyc, cyc[1:]))
        site_edge = min(cyc_edges, key=lambda e: kept[e])
        path, line, col = kept[site_edge]
        _emit(model, mods, "lock-order", path, line, col,
              "lock-acquisition-order cycle (deadlock hazard): "
              + " -> ".join(cyc)
              + " — invert one nesting or suppress the impossible edge "
                "with '# race-lint: ignore[lock-order]' and a written "
                "justification", force=True)
        # break the cycle and keep scanning for independent ones
        graph_edges.discard(site_edge)

    # ---- rule: bare-submit ---------------------------------------------
    for sp in model.spawns:
        if sp.kind in ("scoped_submit", "par_map"):
            continue
        if not _in_dirs(sp.path, _OBS_DIRS):
            continue
        encl_bare = sp.func.rsplit(".", 1)[-1]
        if encl_bare in ("scoped_submit", "par_map"):
            continue    # the sanctioned context-propagating wrappers:
            # their own pool.submit/Thread IS the propagation mechanism
        if sp.kind == "submit":
            known_fn = sp.target_is_func and (
                sp.target == "<lambda>" or sp.target in all_index)
            if not known_fn:
                continue    # admission tickets etc., not an executor
            msg = (f"bare pool.submit({sp.target}) in obs-scoped code: "
                   "worker threads start with an EMPTY contextvars "
                   "context, so kernel launches lose query/operator "
                   "attribution and spans lose their query tag — route "
                   "through obs.metrics.scoped_submit")
        else:
            msg = ("bare threading.Thread in obs-scoped code: the new "
                   "thread drops the contextvar query scope "
                   "(attribution, span tags, kernel ledger); use "
                   "scoped_submit/par_map for query-scoped work, or "
                   "pragma with a justification for process-lifetime "
                   "service threads")
        _emit(model, mods, "bare-submit", sp.path, sp.line, sp.col, msg)

    # ---- rule: worker-reinit -------------------------------------------
    mutated_states = {mu.state for mu in resolved}
    for m in mods:
        if not _in_dirs(m.path, _WORKER_DIRS):
            continue
        reinit_names: set = set()
        for names in m.reinits.values():
            reinit_names.update(names)
        for sid, kind in sorted(m.states.items()):
            if kind == "exempt" or sid not in mutated_states:
                continue
            local = sid.rsplit(".", 1)[-1]
            if local in reinit_names:
                continue
            _emit(model, mods, "worker-reinit", m.path,
                  m.state_lines.get(sid, 1), 0,
                  f"process-global '{sid}' is mutated at runtime but has "
                  "no re-init path: a cluster worker re-imports this "
                  "module and the state silently diverges from the "
                  "driver's — add a reset()/configure() that restores "
                  "it, or pragma if per-process divergence is the "
                  "intended semantics")

    model.violations.sort(key=lambda v: (v.path, v.line, v.col, v.rule))
    return model


def _pragmas_of(mods: list, path: str) -> dict:
    for m in mods:
        if m.path == path:
            return m.pragmas
    return {}


def _resolve_annotation(name: str, locks: set) -> str:
    if name in locks:
        return name
    tails = [lid for lid in locks if lid.endswith("." + name)
             or lid.rsplit(".", 1)[-1] == name]
    if len(tails) == 1:
        return tails[0]
    return name


def _emit(model: RepoModel, mods: list, rule: str, path: str, line: int,
          col: int, message: str, force: bool = False) -> None:
    if not force and _is_suppressed(_pragmas_of(mods, path), line, rule):
        return
    lines = next((m.lines for m in mods if m.path == path), [])
    snippet = lines[line - 1].strip() if 0 < line <= len(lines) else ""
    model.violations.append(Violation(rule, path, line, col, snippet,
                                      message))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _iter_py(root: str):
    if os.path.isfile(root):
        yield root
        return
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in sorted(filenames):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def _rel(path: str, root: str) -> str:
    try:
        return os.path.relpath(path, root).replace(os.sep, "/")
    except ValueError:
        return path


def build_model_from_sources(sources: dict) -> RepoModel:
    """Build the repo model from in-memory {relpath: source} — the
    fixture surface the rule-engine unit tests drive."""
    mods = []
    for relpath, src in sorted(sources.items()):
        m = _scan_module(src, relpath)
        if m is not None:
            mods.append(m)
    return _build(mods)


def build_model(paths, repo_root: str | None = None) -> RepoModel:
    paths = [paths] if isinstance(paths, str) else list(paths)
    repo_root = repo_root or os.path.commonpath(
        [os.path.abspath(p) for p in paths])
    if os.path.isfile(repo_root):
        repo_root = os.path.dirname(repo_root)
    sources: dict = {}
    for p in paths:
        for path in _iter_py(p):
            try:
                sources[_rel(os.path.abspath(path), repo_root)] = open(
                    path, encoding="utf-8").read()
            except OSError:
                continue
    return build_model_from_sources(sources)


def lint_sources(sources: dict) -> list:
    return build_model_from_sources(sources).violations


def lint_paths(paths, repo_root: str | None = None) -> list:
    return build_model(paths, repo_root=repo_root).violations


# ---------------------------------------------------------------------------
# Baseline (same shape and semantics as tpulint's)
# ---------------------------------------------------------------------------

def baseline_counts(violations) -> dict:
    counts: dict = {}
    for v in violations:
        counts[v.bucket] = counts.get(v.bucket, 0) + 1
    return counts


def write_baseline(path: str, violations) -> dict:
    data = {"version": 1, "counts": baseline_counts(violations)}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def load_baseline(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return dict(data.get("counts", {}))


def new_violations(violations, baseline: dict) -> list:
    """Violations beyond the baselined count per (file, rule) bucket."""
    by_bucket: dict = {}
    for v in violations:
        by_bucket.setdefault(v.bucket, []).append(v)
    out: list = []
    for bucket, vs in sorted(by_bucket.items()):
        allowed = baseline.get(bucket, 0)
        if len(vs) > allowed:
            out.extend(vs[allowed:])
    return out
