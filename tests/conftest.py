"""Test harness: CPU backend with 8 virtual devices (SURVEY.md §4 —
the local-cluster analog for distributed logic on one host).

The environment alone decides the platform, so the variables below (set
before jax is imported, and inherited by every child process a test
spawns) plus one jax.config.update for x64 are the whole set-up."""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# the exact-count suites assume no persistent XLA cache unless a test asks
# for one: pinned off with jax's own switch (exec/persist_cache.py honours
# it); the tests that exercise the disk cache turn it on in their children
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def thread_audit():
    """Thread-leak check (role of the reference's ThreadAudit,
    core/src/test/.../ThreadAudit.scala): snapshot threads at session start,
    warn on leaks at the end (daemon pools excluded)."""
    import threading
    import warnings

    before = {t.name for t in threading.enumerate()}
    yield
    after = [t for t in threading.enumerate()
             if t.name not in before and not t.daemon and t.is_alive()]
    if after:
        warnings.warn(f"possible thread leak: {[t.name for t in after]}")


@pytest.fixture(autouse=True)
def fresh_plan_memory():
    """The process remembers each plan's final join capacities
    (exec/persist_cache.PLAN_MEMORY) and the `spark` fixture lives as
    long as the process: emptied before every test, so a test that
    counts a capacity ladder counts the same one in any order."""
    from spark_tpu.exec.persist_cache import PLAN_MEMORY

    PLAN_MEMORY.clear()


@pytest.fixture(scope="session")
def spark():
    from spark_tpu import TpuSession

    conf = {"spark.sql.shuffle.partitions": 4,
            "spark.tpu.batch.capacity": 1 << 12}
    import os as _os
    if _os.environ.get("SPARK_TPU_TEST_FUSION"):
        conf["spark.tpu.fusion.enabled"] = _os.environ["SPARK_TPU_TEST_FUSION"]
    if os.environ.get("SPARK_TPU_VALIDATE") == "1":
        conf["spark.tpu.debug.validateBatches"] = "true"
    s = TpuSession("tests", conf)
    yield s
    s.stop()


@pytest.fixture()
def people(spark):
    df = spark.createDataFrame(pa.table({
        "name": ["alice", "bob", "carol", "dave", "eve", None],
        "age": [25, 32, 25, None, 41, 25],
        "dept": ["eng", "sales", "eng", "eng", "hr", "sales"],
        "salary": [100.0, 80.5, 120.0, 95.0, None, 70.0],
    }))
    df.createOrReplaceTempView("people")
    return df
