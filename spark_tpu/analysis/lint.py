"""tpulint source pass: AST lint for host-sync / recompile / fusion hazards.

Role of the reference's style+semantics gates (scalastyle rules banning
`Await.result` on hot paths, Catalyst's sanity checks) adapted to the XLA
execution model, where the expensive mistakes are different:

  * ``host-sync`` — device→host round-trips on operator hot paths:
    ``.item()``, ``int()/float()/bool()`` over computed values,
    ``np.asarray(...)`` on device arrays, ``block_until_ready`` outside
    bench code. One sync stalls the async dispatch pipeline
    (utils/device_memo.memo_device_scalars exists precisely to kill
    these, and utils/device_memo.device_read is the one door of those
    that remain: what it returns is host data); on transfer-bound
    transports each is a permanent tax.
  * ``row-loop`` — Python-level per-row loops inside ops/ and physical/:
    a ``for`` over ``range(num_rows/capacity)`` is the antithesis of the
    one-dispatch-per-batch contract.
  * ``raw-jit`` — ``jax.jit`` calls that bypass the structurally-keyed
    ``KernelCache``: uncached jits recompile per call site/instance and
    never show up in the launch counters the fusion regression tests key
    on (physical/compile.KernelCache).
  * ``config-key`` — ``spark.tpu.*`` keys read by string literal but never
    registered as a typed ConfigEntry: typos read defaults silently and
    config loses its single source of truth (config.py registry).

Suppression: a trailing/preceding ``# tpulint: ignore[rule]`` pragma, or a
checked-in baseline (dev/tpulint_baseline.json) so existing debt doesn't
block CI while NEW violations do.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field

__all__ = ["Violation", "lint_source", "lint_paths", "load_baseline",
           "write_baseline", "new_violations", "RULES"]

RULES = ("host-sync", "row-loop", "raw-jit", "config-key")

# directories (relative to the package root) whose code is operator/kernel
# hot path: host syncs there stall the dispatch pipeline
_HOT_DIRS = ("ops", "physical", "columnar", "exec", "parallel")
# per-row Python loops are only outlawed where kernels live
_LOOP_DIRS = ("ops", "physical")

_KEY_RE = re.compile(r"^spark\.tpu\.[A-Za-z0-9_.]+$")
_PRAGMA_RE = re.compile(r"#\s*tpulint:\s*ignore(?:\[([a-z\-,\s]+)\])?")

_ROW_LOOP_NAMES = {"num_rows", "n_rows", "nrows", "capacity"}


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
# pragma handling
# ---------------------------------------------------------------------------

def _pragmas(source_lines: list[str]) -> dict[int, set[str] | None]:
    """line number (1-based) → suppressed rule set (None = all rules).
    A trailing pragma suppresses its own line only; a comment-ONLY pragma
    line also suppresses the following line (so it can sit above a long
    statement) — a trailing pragma must not grandfather whatever lands on
    the next line."""
    out: dict[int, set[str] | None] = {}
    for i, line in enumerate(source_lines, start=1):
        m = _PRAGMA_RE.search(line)
        if not m:
            continue
        rules = None
        if m.group(1):
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        targets = (i,) if line[:m.start()].strip() else (i, i + 1)
        for ln in targets:
            prev = out.get(ln, set())
            if rules is None or prev is None:
                out[ln] = None
            else:
                out[ln] = prev | rules
    return out


def _is_suppressed(pragmas, line: int, rule: str) -> bool:
    if line not in pragmas:
        return False
    rules = pragmas[line]
    return rules is None or rule in rules


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._tpulint_parent = node  # type: ignore[attr-defined]


def _enclosing_functions(node: ast.AST, lambdas: bool = False):
    """Enclosing function scopes, innermost first."""
    kinds = (ast.FunctionDef, ast.AsyncFunctionDef)
    if lambdas:
        kinds = kinds + (ast.Lambda,)
    out = []
    cur = getattr(node, "_tpulint_parent", None)
    while cur is not None:
        if isinstance(cur, kinds):
            out.append(cur)
        cur = getattr(cur, "_tpulint_parent", None)
    return out


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a call target ('jax.jit', 'np.asarray')."""
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _contains_call(fn: ast.AST, names: tuple) -> bool:
    for n in ast.walk(fn):
        if isinstance(n, ast.Call):
            tgt = n.func
            if isinstance(tgt, ast.Attribute) and tgt.attr in names:
                return True
            if isinstance(tgt, ast.Name) and tgt.id in names:
                return True
    return False


def _contains_get_or_build(fn: ast.AST) -> bool:
    return _contains_call(fn, ("get_or_build",))


_MEMO_NAMES = ("memo_device_scalars", "_memo_device_scalars",
               "seed_dense_range_memo")


def _memo_protected(tree: ast.AST) -> tuple[set, set]:
    """(function names, lambda node ids) passed as arguments to a
    memo_device_scalars-family call — ONLY those closures run once per
    array identity; code merely near a memo call still syncs per call."""
    names: set[str] = set()
    lams: set[int] = set()
    for n in ast.walk(tree):
        if not isinstance(n, ast.Call):
            continue
        tgt = n.func
        tname = tgt.attr if isinstance(tgt, ast.Attribute) else (
            tgt.id if isinstance(tgt, ast.Name) else "")
        if tname not in _MEMO_NAMES:
            continue
        for arg in list(n.args) + [kw.value for kw in n.keywords]:
            if isinstance(arg, ast.Lambda):
                lams.add(id(arg))
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Name):
                    names.add(sub.id)
    return names, lams


def _memoized_context(node: ast.AST, memo_names: set,
                      memo_lambdas: set) -> bool:
    """True when `node`'s INNERMOST enclosing function/lambda is itself the
    closure handed to a memo_device_scalars call — the sanctioned
    once-per-array-identity wrapper for host reads (utils/device_memo.py).
    Code outside that closure gets no exemption, even in the same
    function."""
    encl = _enclosing_functions(node, lambdas=True)
    if not encl:
        return False
    inner = encl[0]
    if isinstance(inner, ast.Lambda):
        return id(inner) in memo_lambdas
    return inner.name in memo_names


_READ_NAMES = ("device_read",)


def _call_name(node: ast.Call) -> str:
    tgt = node.func
    return tgt.attr if isinstance(tgt, ast.Attribute) else (
        tgt.id if isinstance(tgt, ast.Name) else "")


def _host_read_names(tree: ast.AST) -> set[tuple]:
    """(enclosing function, name) of every name bound to what a
    utils/device_memo.device_read call returned: host copies, read in
    the one sanctioned transfer of their site."""
    out: set[tuple] = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                and _call_name(n.value) in _READ_NAMES:
            encl = _enclosing_functions(n, lambdas=True)
            scope = id(encl[0]) if encl else None
            for t in n.targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        out.add((scope, sub.id))
    return out


def _reads_host_copy(node: ast.AST, host_names: set) -> bool:
    """True when `node` is (an element, attribute or method result of)
    a device_read call or a name bound to one: a host value."""
    while isinstance(node, (ast.Subscript, ast.Attribute, ast.Call)):
        if isinstance(node, ast.Call):
            if _call_name(node) in _READ_NAMES:
                return True
            node = node.func
        else:
            node = node.value
    if not isinstance(node, ast.Name):
        return False
    encl = _enclosing_functions(node, lambdas=True)
    return (id(encl[0]) if encl else None, node.id) in host_names


def _names_used_in_cache_builders(tree: ast.AST) -> set[str]:
    """Function names referenced inside any get_or_build(...) call's
    arguments — module-level kernel builders wrapped at the call site
    (`get_or_build(key, lambda: _group_kernel(...))`)."""
    out: set[str] = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "get_or_build":
            for arg in list(n.args) + [kw.value for kw in n.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name):
                        out.add(sub.id)
    return out


# ---------------------------------------------------------------------------
# registered config keys
# ---------------------------------------------------------------------------

def registered_config_keys(root: str) -> set[str]:
    """Every key registered as `ConfigEntry("<key>", ...)` anywhere under
    `root` (config.py is the canonical registry; memory.py et al. register
    their own entries through the same type)."""
    keys: set[str] = set()
    for path in _iter_py(root):
        try:
            tree = ast.parse(open(path, encoding="utf-8").read())
        except SyntaxError:
            continue
        for n in ast.walk(tree):
            if isinstance(n, ast.Call) and _dotted(n.func).endswith(
                    "ConfigEntry") and n.args:
                a0 = n.args[0]
                if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
                    keys.add(a0.value)
    return keys


def _config_entry_arg_lines(tree: ast.AST) -> set[int]:
    """Lines where a string literal is the ConfigEntry key itself."""
    out: set[int] = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Call) and _dotted(n.func).endswith(
                "ConfigEntry") and n.args:
            a0 = n.args[0]
            if isinstance(a0, ast.Constant):
                out.add(a0.lineno)
    return out


# ---------------------------------------------------------------------------
# the lint proper
# ---------------------------------------------------------------------------

def _rel(path: str, root: str) -> str:
    try:
        return os.path.relpath(path, root).replace(os.sep, "/")
    except ValueError:
        return path


def _in_dirs(relpath: str, dirs) -> bool:
    parts = relpath.split("/")
    return any(d in parts[:-1] for d in dirs)


def lint_source(source: str, relpath: str,
                registered_keys: set[str] | None = None) -> list[Violation]:
    """Lint one module's source. `relpath` decides hot-path scoping."""
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Violation("host-sync", relpath, e.lineno or 0, 0, "",
                          f"syntax error prevents linting: {e.msg}")]
    _attach_parents(tree)
    lines = source.splitlines()
    pragmas = _pragmas(lines)
    builder_names = _names_used_in_cache_builders(tree)
    memo_names, memo_lambdas = _memo_protected(tree)
    host_names = _host_read_names(tree)
    entry_lines = _config_entry_arg_lines(tree)
    hot = _in_dirs(relpath, _HOT_DIRS)
    loopable = _in_dirs(relpath, _LOOP_DIRS)
    is_registry = relpath.endswith("config.py")
    is_cache = relpath.endswith("physical/compile.py")

    out: list[Violation] = []

    def emit(rule: str, node: ast.AST, message: str):
        line = getattr(node, "lineno", 0)
        if _is_suppressed(pragmas, line, rule):
            return
        snippet = lines[line - 1].strip() if 0 < line <= len(lines) else ""
        out.append(Violation(rule, relpath, line,
                             getattr(node, "col_offset", 0), snippet,
                             message))

    for node in ast.walk(tree):
        # ---- host-sync -------------------------------------------------
        if isinstance(node, ast.Call):
            target = _dotted(node.func)
            memoized = hot and _memoized_context(node, memo_names,
                                                 memo_lambdas)
            if memoized or (hot and node.args and _reads_host_copy(
                    node.args[0], host_names)):
                # inside the closure handed to memo_device_scalars (the
                # pull runs once per array identity), or over what a
                # device_read brought home — sanctioned
                pass
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args and hot:
                emit("host-sync", node,
                     ".item() syncs one scalar per call on a hot path — "
                     "memoize via utils/device_memo.memo_device_scalars or batch the reads")
            elif target in ("np.asarray", "numpy.asarray") and hot:
                emit("host-sync", node,
                     "np.asarray on a device array is a device→host "
                     "transfer; hoist it out of per-batch loops or memoize "
                     "(utils/device_memo.memo_device_scalars)")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready":
                emit("host-sync", node,
                     "block_until_ready stalls the dispatch pipeline; it "
                     "belongs in bench/test code, not the engine")
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("int", "float", "bool") \
                    and hot and len(node.args) == 1 \
                    and isinstance(node.args[0],
                                   (ast.Call, ast.Attribute, ast.Subscript)):
                emit("host-sync", node,
                     f"{node.func.id}() over a computed value host-syncs "
                     "if it is a device scalar — memoize "
                     "(utils/device_memo) or keep it on device")
            # ---- raw-jit -----------------------------------------------
            if target in ("jax.jit", "jit") and target and not is_cache:
                encl = _enclosing_functions(node)
                ok = any(_contains_get_or_build(f) for f in encl) \
                    or any(f.name in builder_names for f in encl)
                if not ok:
                    emit("raw-jit", node,
                         "jax.jit outside KernelCache.get_or_build: the "
                         "kernel recompiles per call site and its launches "
                         "are invisible to the dispatch-count regression "
                         "counters (physical/compile.KernelCache)")
        # ---- row-loop --------------------------------------------------
        if isinstance(node, ast.For) and loopable:
            it = node.iter
            flagged = False
            if isinstance(it, ast.Call) and _dotted(it.func) == "range":
                for a in it.args:
                    for sub in ast.walk(a):
                        if (isinstance(sub, ast.Name)
                                and sub.id in _ROW_LOOP_NAMES) or \
                           (isinstance(sub, ast.Attribute)
                                and sub.attr in _ROW_LOOP_NAMES):
                            flagged = True
            elif isinstance(it, ast.Call) and isinstance(it.func,
                                                         ast.Attribute) \
                    and it.func.attr in ("to_pylist", "tolist"):
                flagged = True
            if flagged:
                emit("row-loop", node,
                     "Python-level per-row loop in a kernel module — this "
                     "breaks the one-dispatch-per-batch contract; express "
                     "it as a masked device kernel")
        # ---- config-key ------------------------------------------------
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and _KEY_RE.match(node.value) and not is_registry \
                and node.lineno not in entry_lines \
                and registered_keys is not None \
                and node.value not in registered_keys:
            emit("config-key", node,
                 f"config key '{node.value}' read by literal but never "
                 "registered as a ConfigEntry — register it in config.py "
                 "so defaults/typing have one source of truth")
    return out


def _iter_py(root: str):
    if os.path.isfile(root):
        yield root
        return
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in sorted(filenames):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def _package_root(path: str) -> str:
    """Topmost enclosing python package of `path` (ascends while an
    __init__.py is present) — the scope ConfigEntry registrations are
    collected over, so linting a single file still sees the sibling
    config.py registry."""
    p = os.path.abspath(path)
    if os.path.isfile(p):
        p = os.path.dirname(p)
    while os.path.isfile(os.path.join(os.path.dirname(p), "__init__.py")):
        parent = os.path.dirname(p)
        if parent == p:
            break
        p = parent
    if os.path.isfile(os.path.join(p, "__init__.py")):
        return p
    return path


def lint_paths(paths, repo_root: str | None = None) -> list[Violation]:
    """Lint every .py under `paths`. Registered config keys are collected
    over each path's whole enclosing PACKAGE (not just the linted subset),
    so linting one file never produces false config-key violations."""
    paths = [paths] if isinstance(paths, str) else list(paths)
    repo_root = repo_root or os.path.commonpath(
        [os.path.abspath(p) for p in paths])
    if os.path.isfile(repo_root):
        repo_root = os.path.dirname(repo_root)
    keys: set[str] = set()
    for p in paths:
        keys |= registered_config_keys(_package_root(p))
    out: list[Violation] = []
    for p in paths:
        for path in _iter_py(p):
            rel = _rel(os.path.abspath(path), repo_root)
            try:
                src = open(path, encoding="utf-8").read()
            except OSError:
                continue
            out.extend(lint_source(src, rel, registered_keys=keys))
    out.sort(key=lambda v: (v.path, v.line, v.col))
    return out


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def baseline_counts(violations) -> dict[str, int]:
    counts: dict[str, int] = {}
    for v in violations:
        counts[v.bucket] = counts.get(v.bucket, 0) + 1
    return counts


def write_baseline(path: str, violations) -> dict:
    data = {"version": 1, "counts": baseline_counts(violations)}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def load_baseline(path: str) -> dict[str, int]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return dict(data.get("counts", {}))


def new_violations(violations, baseline: dict[str, int]) -> list[Violation]:
    """Violations beyond the baselined count per (file, rule) bucket.
    Counted per bucket (line-shift tolerant); the overflow sites reported
    are the LAST ones in the file — newest code tends to sit lowest."""
    by_bucket: dict[str, list[Violation]] = {}
    for v in violations:
        by_bucket.setdefault(v.bucket, []).append(v)
    out: list[Violation] = []
    for bucket, vs in sorted(by_bucket.items()):
        allowed = baseline.get(bucket, 0)
        if len(vs) > allowed:
            out.extend(vs[allowed:])
    return out
