"""Nothing in the program hides the device: no
compile refusal classified as a runtime fault, no guessed peak table or HBM
size on an accelerator, a compile cache that is placed from outside, and a
native library rebuilt when it is older than its source."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# compile refusal vs runtime fault
# ---------------------------------------------------------------------------

def _refusal():
    from jax.errors import JaxRuntimeError

    return JaxRuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space vmem.")


def test_kernel_cache_classifies_first_call_failure_as_compile_refusal():
    from spark_tpu.physical.compile import KernelCache
    from spark_tpu.utils.faults import KernelCompileError, is_runtime_fault

    state = {"refuse": True}

    def kernel(x):
        if state["refuse"]:
            raise _refusal()
        return x + 1

    k = KernelCache().get_or_build(("whole_query", "t"), lambda: kernel)
    assert is_runtime_fault(_refusal())        # the exception alone can't tell
    for _ in range(2):                         # a retry is still its compile
        with pytest.raises(KernelCompileError) as ei:
            k(1)
        assert not is_runtime_fault(ei.value)
        assert "RESOURCE_EXHAUSTED" in str(ei.value)
    state["refuse"] = False
    assert k(1) == 2                           # one completed launch …
    state["refuse"] = True
    with pytest.raises(Exception) as ei:       # … and the same error is now
        k(1)                                   # an execution-time fault
    assert is_runtime_fault(ei.value)


def test_whole_tier_compile_refusal_propagates_instead_of_degrading(
        monkeypatch):
    """The chaos suite pins injected faults degrading the whole tier; a
    non-injected XLA error at the program's FIRST invocation must not."""
    import jax
    import numpy as np
    import pyarrow as pa

    from spark_tpu import TpuSession
    from spark_tpu.utils.faults import KernelCompileError

    real_jit = jax.jit

    def refusing_jit(fn, *a, **kw):
        if getattr(fn, "__name__", "").startswith("whole_query_"):
            def refuse(*_a, **_k):
                raise _refusal()
            return refuse
        return real_jit(fn, *a, **kw)

    s = TpuSession("refusal", {"spark.tpu.compile.tier": "whole",
                               "spark.sql.shuffle.partitions": "2",
                               "spark.tpu.batch.capacity": 1 << 9})
    try:
        rng = np.random.default_rng(5)
        s.createDataFrame(pa.table({
            "k": rng.integers(0, 7, 300), "v": rng.integers(0, 50, 300),
        })).createOrReplaceTempView("refusal_t")
        monkeypatch.setattr(jax, "jit", refusing_jit)
        with pytest.raises(KernelCompileError, match="compile refusal"):
            s.sql("select k, sum(v) s from refusal_t group by k").toArrow()
        counters = s._metrics.snapshot()["counters"]
        assert not counters.get("whole_query.runtime_degraded")
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# device descriptors: exact, or an error
# ---------------------------------------------------------------------------

class _FakeTpu:
    platform = "tpu"

    def __init__(self, kind="TPU v5 lite", stats=None):
        self.device_kind = kind
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_peak_table_is_keyed_by_exact_device_kind(monkeypatch):
    import jax

    from spark_tpu.obs import resources

    assert resources.device_peak_gbps() is None          # cpu: no roofline
    monkeypatch.setattr(jax, "local_devices", lambda: [_FakeTpu()])
    assert resources.device_peak_gbps() == 819.0
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [_FakeTpu("TPU v5")])     # no substring match
    with pytest.raises(RuntimeError, match="TPU v5'"):
        resources.device_peak_gbps()


def test_auto_budget_needs_the_accelerator_to_report_its_memory(
        monkeypatch):
    import jax

    from spark_tpu.exec import memory

    assert memory._auto_budget() == 4 << 30              # cpu reports none
    monkeypatch.setattr(jax, "local_devices", lambda: [
        _FakeTpu(stats={"bytes_limit": 16909336064})])
    assert memory._auto_budget() == 16909336064 // 2
    monkeypatch.setattr(jax, "local_devices", lambda: [_FakeTpu()])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        memory._auto_budget()


# ---------------------------------------------------------------------------
# the compile cache is placed from outside
# ---------------------------------------------------------------------------

_CACHE_CHILD = r'''
import json, os, sys
import numpy as np, pyarrow as pa
import jax
from spark_tpu import TpuSession
import spark_tpu.exec.persist_cache as pc
conf = {"spark.tpu.batch.capacity": 1 << 9}
if sys.argv[1]:
    conf["spark.tpu.cache.dir"] = sys.argv[1]
s = TpuSession("cache-child", conf)
s.createDataFrame(pa.table({"v": np.arange(100)})).createOrReplaceTempView("t")
s.sql("select sum(v + 1) s from t").toArrow()
print("CHILD " + json.dumps({"dir": jax.config.jax_compilation_cache_dir,
                             **pc.disk_counters()}))
'''


def _cache_child(conf_dir: str, placed: str | None = None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true")   # the harness pins it off
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    r = subprocess.run([sys.executable, "-c", _CACHE_CHILD, conf_dir],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("CHILD ")]
    assert r.returncode == 0 and lines, r.stderr[-2000:]
    return json.loads(lines[-1][len("CHILD "):])


def test_env_var_places_the_xla_cache_even_with_a_conf_dir(tmp_path):
    placed, conf_dir = tmp_path / "placed", tmp_path / "conf"
    got = _cache_child(str(conf_dir), placed=str(placed))
    assert got["dir"] == str(placed)
    assert got["compile.disk_miss"] >= 1          # listener is installed
    assert any(placed.iterdir()), "no cache entry where the env var points"
    assert not (conf_dir / "xla").exists(), "<spark.tpu.cache.dir>/xla used"


def test_unplaced_the_xla_cache_is_the_fixed_in_checkout_path():
    import spark_tpu.exec.persist_cache as pc

    fixed = os.path.join(REPO, ".cache", "xla")
    assert pc._DEFAULT_XLA_DIR == fixed
    got = _cache_child("")
    assert got["dir"] == fixed
    # an earlier run may have left the entries: a hit proves the place too
    assert got["compile.disk_miss"] + got["compile.disk_hit"] >= 1
    assert os.listdir(fixed)


def test_xla_cache_dir_precedence(monkeypatch):
    import jax

    import spark_tpu.exec.persist_cache as pc
    from spark_tpu.config import SQLConf

    plain, rooted = SQLConf({}), SQLConf({"spark.tpu.cache.dir": "/c"})
    assert pc.xla_cache_dir(plain) is None        # the harness pins it off
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert pc.xla_cache_dir(plain) == pc._DEFAULT_XLA_DIR
        assert pc.xla_cache_dir(rooted) == "/c/xla"
        assert pc.xla_cache_dir(SQLConf({
            "spark.tpu.cache.compile.enabled": "false"})) is None
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed")
        assert pc.xla_cache_dir(rooted) == "/placed"
    finally:
        jax.config.update("jax_enable_compilation_cache", False)


def test_uncreatable_cache_directory_is_an_error(tmp_path, monkeypatch):
    import jax

    import spark_tpu.exec.persist_cache as pc
    from spark_tpu.config import SQLConf

    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        with pytest.raises(RuntimeError, match="cannot be created"):
            pc.configure(SQLConf({"spark.tpu.cache.dir": str(blocker)}))
    finally:
        jax.config.update("jax_enable_compilation_cache", False)


# ---------------------------------------------------------------------------
# native library
# ---------------------------------------------------------------------------

def test_native_library_is_rebuilt_when_older_than_its_source():
    code = ("import os; from spark_tpu.utils import native as n; "
            "n._load(); os.utime(n._SO_PATH, (1, 1)); "
            "n._load_with_origin.cache_clear(); print(n.status())")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1000:]
    assert r.stdout.strip().endswith("built"), r.stdout
