"""tpulint source-lint pass (spark_tpu/analysis/lint.py): rule detection,
pragma suppression, the memoized-wrapper exemption, baseline semantics —
plus the tier-1 CI gate: the repo must be clean against its checked-in
baseline (AST only, no device work)."""

import json
import os
import subprocess
import sys

from spark_tpu.analysis import lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HOT = "spark_tpu/physical/fake_op.py"        # hot-path module path
COLD = "spark_tpu/api/fake_api.py"           # not a hot path


def _rules(src, relpath=HOT, keys=frozenset()):
    return [(v.rule, v.line) for v in
            lint.lint_source(src, relpath, registered_keys=set(keys))]


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

def test_item_flagged_on_hot_path():
    src = "def f(x):\n    return x.item()\n"
    assert ("host-sync", 2) in _rules(src)
    assert _rules(src, relpath=COLD) == []  # not a hot path


def test_np_asarray_and_casts_flagged():
    src = ("import numpy as np\n"
           "def f(col, d):\n"
           "    a = np.asarray(col.data)\n"
           "    n = int(d.sum())\n"
           "    return a, n\n")
    rules = [r for r, _ in _rules(src)]
    assert rules.count("host-sync") == 2


def test_block_until_ready_flagged_everywhere():
    src = "def f(x):\n    x.block_until_ready()\n"
    assert ("host-sync", 2) in _rules(src, relpath=COLD)


def test_memoized_wrapper_exempts_host_sync():
    src = ("import numpy as np\n"
           "def rng(col, mask):\n"
           "    def compute():\n"
           "        return int(np.asarray(col.data)[mask].min())\n"
           "    return memo_device_scalars(('r',), (col.data,), compute)\n")
    assert _rules(src) == []
    lam = ("def rng(col, d):\n"
           "    return memo_device_scalars(('r',), (col.data,),\n"
           "                               lambda: int(d.min()))\n")
    assert _rules(lam) == []


def test_memo_exemption_limited_to_the_closure():
    """A sync OUTSIDE the compute closure is still per-call — flagged even
    though the same function also calls memo_device_scalars."""
    src = ("import numpy as np\n"
           "def rng(col, mask, batch):\n"
           "    n = int(batch.row_mask.sum())\n"
           "    def compute():\n"
           "        return int(np.asarray(col.data)[mask].min())\n"
           "    return memo_device_scalars(('r', n), (col.data,), compute)\n")
    assert [(r, ln) for r, ln in _rules(src)] == [("host-sync", 3)]


def test_pragma_suppresses_rule():
    src = ("def f(x):\n"
           "    return x.item()  # tpulint: ignore[host-sync]\n")
    assert _rules(src) == []
    src2 = ("def f(x):\n"
            "    # tpulint: ignore\n"
            "    return x.item()\n")
    assert _rules(src2) == []
    src3 = ("def f(x):\n"
            "    return x.item()  # tpulint: ignore[raw-jit]\n")
    assert ("host-sync", 2) in _rules(src3)  # wrong rule listed


def test_trailing_pragma_does_not_leak_to_next_line():
    src = ("def f(x, y):\n"
           "    a = x.item()  # tpulint: ignore[host-sync]\n"
           "    b = y.item()\n"
           "    return a, b\n")
    assert _rules(src) == [("host-sync", 3)]
    # a comment-only pragma still covers the following statement
    src2 = ("def f(x):\n"
            "    # tpulint: ignore[host-sync]\n"
            "    return x.item()\n")
    assert _rules(src2) == []


# ---------------------------------------------------------------------------
# row-loop / raw-jit / config-key
# ---------------------------------------------------------------------------

def test_row_loop_flagged_in_kernel_dirs():
    src = ("def f(batch):\n"
           "    for i in range(batch.num_rows):\n"
           "        pass\n")
    assert ("row-loop", 2) in _rules(src)
    assert _rules(src, relpath="spark_tpu/ml/fake.py") == []


def test_raw_jit_flagged_unless_cached():
    src = ("import jax\n"
           "def f():\n"
           "    return jax.jit(lambda x: x)\n")
    assert ("raw-jit", 3) in _rules(src)
    cached = ("import jax\n"
              "def op(cache):\n"
              "    def build():\n"
              "        return jax.jit(lambda x: x)\n"
              "    return cache.get_or_build(('k',), build)\n")
    assert _rules(cached) == []
    # module-level builder referenced from a get_or_build call site
    helper = ("import jax\n"
              "def _kern():\n"
              "    return jax.jit(lambda x: x)\n"
              "def op(cache):\n"
              "    return cache.get_or_build(('k',), lambda: _kern())\n")
    assert _rules(helper) == []


def test_config_key_requires_registration():
    src = "def f(conf):\n    return conf.get('spark.tpu.made.up', 1)\n"
    assert ("config-key", 2) in _rules(src)
    assert _rules(src, keys={"spark.tpu.made.up"}) == []


def test_registry_collects_config_entries(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("X = _register(ConfigEntry('spark.tpu.some.key', 1,\n"
                   "    'doc', int))\n")
    assert lint.registered_config_keys(str(tmp_path)) == \
        {"spark.tpu.some.key"}


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def test_baseline_blocks_only_new_violations(tmp_path):
    v1 = lint.lint_source("def f(x):\n    return x.item()\n", HOT,
                          registered_keys=set())
    path = tmp_path / "base.json"
    lint.write_baseline(str(path), v1)
    baseline = lint.load_baseline(str(path))
    assert lint.new_violations(v1, baseline) == []
    v2 = lint.lint_source(
        "def f(x):\n    return x.item()\ndef g(y):\n    return y.item()\n",
        HOT, registered_keys=set())
    extra = lint.new_violations(v2, baseline)
    assert len(extra) == 1 and extra[0].rule == "host-sync"


# ---------------------------------------------------------------------------
# CI gate: the repo itself must be clean against its baseline
# ---------------------------------------------------------------------------

def test_repo_clean_against_checked_in_baseline():
    violations = lint.lint_paths([os.path.join(REPO, "spark_tpu")],
                                 repo_root=REPO)
    baseline = lint.load_baseline(
        os.path.join(REPO, "dev", "tpulint_baseline.json"))
    offending = lint.new_violations(violations, baseline)
    msg = "\n".join(str(v) for v in offending[:20])
    assert not offending, (
        f"tpulint found NEW violations beyond dev/tpulint_baseline.json "
        f"(fix them, suppress with '# tpulint: ignore[rule]' where "
        f"justified, or regenerate the baseline via "
        f"`python dev/tpulint.py --write-baseline`):\n{msg}")


def test_no_unregistered_config_keys_at_all():
    """config-key debt is fully paid: single source of truth holds."""
    violations = lint.lint_paths([os.path.join(REPO, "spark_tpu")],
                                 repo_root=REPO)
    bad = [v for v in violations if v.rule == "config-key"]
    assert not bad, "\n".join(str(v) for v in bad)


def test_cli_runs_clean_and_fails_on_new(tmp_path):
    cli = os.path.join(REPO, "dev", "tpulint.py")
    r = subprocess.run(
        [sys.executable, cli, os.path.join(REPO, "spark_tpu"),
         "--baseline", os.path.join(REPO, "dev", "tpulint_baseline.json")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    # a file with a fresh violation and no baseline → exit 1 + json output
    bad = tmp_path / "spark_tpu" / "ops" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def f(x):\n    return x.item()\n")
    r = subprocess.run(
        [sys.executable, cli, str(tmp_path / "spark_tpu"),
         "--format", "json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    data = json.loads(r.stdout)
    assert data["total"] == 1 and data["new"][0]["rule"] == "host-sync"


# ---------------------------------------------------------------------------
# every registered option is read: a key nothing reads tells its user nothing
# ---------------------------------------------------------------------------

# Spark's own names, which client code written for Spark sets and reads
# back: registered so that setting them is no error, honoured by nothing
# yet (ROADMAP D3: a PR that touches the analyzer honours or refuses them).
ACCEPTED_AND_IGNORED = {
    "spark.sql.ansi.enabled": "no ANSI overflow or cast errors in expr/",
    "spark.sql.session.timeZone": "timestamps are UTC throughout",
}


def _unread_options(sources, keys):
    """Keys of `keys` whose constant and literal occur nowhere in
    `sources` ({relpath: text}) but where they are registered. A
    `SQLConf` property that reads the constant counts only if the
    property's name is itself read outside config.py."""
    import re
    from collections import Counter

    conf_rel = next(r for r in sources if r.endswith("spark_tpu/config.py"))
    everything = "\n".join(sources.values())
    outside_conf = "\n".join(t for r, t in sources.items() if r != conf_rel)
    constant = {m.group(2): m.group(1) for m in re.finditer(
        r'^(\w+) = _register\(ConfigEntry\(\s*"([^"]+)"', everything, re.M)}
    props = {}          # constant -> the properties that read it
    for m in re.finditer(
            r"@property\s+def (\w+)\(self\)[^\n]*\n\s+return [^\n]*"
            r"self\.get\((\w+)\)", sources[conf_rel]):
        props.setdefault(m.group(2), []).append(m.group(1))
    words = Counter(re.findall(r"\w+", everything))
    quoted = Counter(re.findall(r"""["']([\w.]+)["']""", everything))

    unread = []
    for key in keys:
        const = constant[key]
        mine = props.get(const, ())
        # the registering statement is one occurrence of each
        if words[const] - 1 - len(mine) > 0 or quoted[key] - 1 > 0:
            continue
        if any(re.search(rf"\.{p}\b", outside_conf) for p in mine):
            continue
        unread.append(key)
    return sorted(unread)


def test_every_registered_option_is_read_somewhere():
    import spark_tpu  # noqa: F401  (every registering module is imported)
    from spark_tpu import config

    sources = {}
    for root, _dirs, files in os.walk(os.path.join(REPO, "spark_tpu")):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    sources[os.path.relpath(path, REPO)] = fh.read()
    assert _unread_options(sources, config.registry()) \
        == sorted(ACCEPTED_AND_IGNORED)
    # and the rule sees a key that only its registration names
    planted = dict(sources)
    planted["spark_tpu/config.py"] += (
        '\nPLANTED_UNREAD = _register(ConfigEntry(\n'
        '    "spark.tpu.planted.unread", 1, "", int))\n'
        "\n    @property\n    def planted_unread(self) -> int:\n"
        "        return int(self.get(PLANTED_UNREAD))\n")
    assert "spark.tpu.planted.unread" in _unread_options(
        planted, [*config.registry(), "spark.tpu.planted.unread"])
