#!/usr/bin/env python
"""The quickest proof that the query path still starts on the chip.

One process drives the engine's main path once, through the doors a user
calls, at the full width of one plan family the repo supports: TPC-DS
q3, q7 and q19 over a 2 880 000-row `store_sales`, the row count of
real SF1 — `TpuSession` → `session.sql(text)` → analyzer →
optimizer → planner → `choose_tier` → `KernelCache` → `.toArrow()`, at
default conf. Then the same three queries through `SQLEndpoint` (what
`bin/sparktpu-sqlserver` starts) from the jax-free DB-API client, and,
on a host with four chips, q3 again on the two mesh tiers. Rows are
compared with an engine-independent sqlite oracle. The run fails if the
platform is not `tpu`, if any phase raises, on any mismatch, and if any
degrade/fallback counter moved — a chip that refuses the flagship
program must not be hidden behind right rows from a smaller tier.

    python chip_smoke.py          # on the machine with the chip
    python chip_smoke.py --cpu    # tiny size on the CPU (tier-1 test)

The last stdout line is one JSON object, {"ok": true, "device": {...}}.
Wall times printed here are observations, not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from decimal import Decimal

HERE = os.path.dirname(os.path.abspath(__file__))

# tests/tpcds/datagen.py scale 1.0 is 30 000 store_sales rows; 96 gives
# the 2 880 000 of real SF1
FULL_SCALE = 96.0
CPU_SCALE = 4.0          # --cpu: big enough that every query returns rows
SEED = 17
QUERIES = ("q3", "q7", "q19")

# default conf — no forced tier, no forced capacity: the path a user
# gets. (Tests inject spark.tpu.faults.* here.)
SESSION_CONF: dict = {}

# what the three queries read; the Decimal conversion of an unread fact
# column costs minutes of host time at this scale
KEEP = {
    "date_dim": {"d_date_sk", "d_year", "d_moy"},
    "item": {"i_item_sk", "i_item_id", "i_brand_id", "i_brand",
             "i_manufact_id", "i_manufact", "i_manager_id"},
    "customer_demographics": {"cd_demo_sk", "cd_gender",
                              "cd_marital_status", "cd_education_status"},
    "household_demographics": {"hd_demo_sk"},   # sized, never queried
    "promotion": {"p_promo_sk", "p_channel_email", "p_channel_event"},
    "customer": {"c_customer_sk", "c_current_addr_sk"},
    "customer_address": {"ca_address_sk", "ca_zip"},
    "store": {"s_store_sk", "s_zip"},
    "store_sales": {"ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                    "ss_promo_sk", "ss_customer_sk", "ss_store_sk",
                    "ss_quantity", "ss_list_price", "ss_coupon_amt",
                    "ss_sales_price", "ss_ext_sales_price"},
}
# generation order matters: facts size themselves from the dimensions
GEN_ORDER = ("date_dim", "item", "customer_address",
             "customer_demographics", "household_demographics", "customer",
             "store", "promotion", "store_sales")

# counters that mean "the device said no and the engine went around it"
HIDDEN = ("whole_query.runtime_degraded", "whole_query.mesh_gang_retries",
          "exchange.mesh_fallback", "exchange.mesh_runtime_fallback",
          "exchange.mesh_gang_failures", "scheduler.stage_retries")


class SmokeFailure(Exception):
    """A check of the smoke failed (as opposed to a phase raising)."""


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Name the phase a failure belongs to; never swallow it."""
    t0 = time.perf_counter()
    say(f"phase {name}: start")
    try:
        yield
    except BaseException:
        print(f"[smoke] phase {name}: FAILED", file=sys.stderr, flush=True)
        raise
    say(f"phase {name}: ok ({time.perf_counter() - t0:.1f} s)")


# ---------------------------------------------------------------------------
# data + oracle
# ---------------------------------------------------------------------------

def generate_tables(scale: float) -> dict:
    from tests.tpcds.datagen import _Gen

    g = _Gen(scale, SEED, keep=KEEP)
    for name in GEN_ORDER:
        getattr(g, name)()
    return {n: t for n, t in g.tables.items()
            if n != "household_demographics"}


def query_text(qname: str) -> str:
    from tests.tpcds.oracle import strip_trailing_limit

    path = os.path.join(HERE, "tests", "tpcds", "queries", f"{qname}.sql")
    with open(path) as f:
        return strip_trailing_limit(f.read())


def oracle_rows(tables: dict) -> dict:
    """The three answers from sqlite over the same tables (host only —
    runs on a thread beside the first compile)."""
    from tests.tpcds.oracle import load_sqlite, rewrite_for_sqlite

    conn = load_sqlite(tables)
    try:
        return {q: conn.execute(
            rewrite_for_sqlite(query_text(q), q)).fetchall()
            for q in QUERIES}
    finally:
        conn.close()


def arrow_rows(table) -> list:
    cols = [c.to_pylist() for c in table.columns]
    return list(zip(*cols)) if cols else []


def check_rows(what: str, got: list, want: list) -> None:
    from tests.tpcds.oracle import compare_rows

    ok, msg = compare_rows(got, want)
    if not ok:
        raise SmokeFailure(f"{what}: rows differ — {msg}")
    if not want:
        raise SmokeFailure(f"{what}: the oracle returned no rows — the "
                           "comparison proves nothing at this scale")


# ---------------------------------------------------------------------------
# what ran, and where
# ---------------------------------------------------------------------------

def counters_now() -> dict:
    import spark_tpu.exec.persist_cache as pc
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    c = KC.counters()
    return {"misses": c["kernel_cache.misses"],
            "launches": c["kernel_cache.launches"],
            "compile_ms": c["kernel_cache.compile_ms"],
            **pc.disk_counters(),
            "by_kind": dict(KC.launches_by_kind)}


def delta(before: dict, after: dict) -> dict:
    out = {k: round(after[k] - before[k], 1) for k in after
           if k != "by_kind"}
    out["by_kind"] = {k: v - before["by_kind"].get(k, 0)
                      for k, v in after["by_kind"].items()
                      if v != before["by_kind"].get(k, 0)}
    return out


def announced_tier(physical):
    dec = getattr(physical, "decision", None) \
        or getattr(physical, "_tier_decision", None)
    if dec is None:
        raise SmokeFailure("the planner left no TierDecision on the plan")
    return dec


def executed_tier(by_kind: dict) -> str:
    """The tier that dispatched, read off the launched kernel kinds."""
    if by_kind.get("mesh_whole"):
        return "mesh-whole"
    if by_kind.get("whole_query"):
        return "whole"
    return "stage"


def check_nothing_hidden(session, where: str) -> None:
    counters = session._metrics.snapshot()["counters"]
    moved = {k: counters[k] for k in HIDDEN if counters.get(k)}
    if moved:
        raise SmokeFailure(f"{where}: degrade/fallback counters moved: "
                           f"{moved}")


def plane_devices(parts, platform: str) -> set:
    """Devices the result planes live on; every one must be a device of
    the platform under test."""
    devs = set()
    for part in parts:
        for batch in part:
            for col in batch.columns:
                for arr in (col.data, col.validity):
                    if arr is not None:
                        devs |= set(arr.devices())
            devs |= set(batch.row_mask.devices())
    off = sorted(str(d) for d in devs if d.platform != platform)
    if off or not devs:
        raise SmokeFailure(f"result planes not on {platform} devices: "
                           f"{off or 'no planes'}")
    return devs


def run_query(session, qname: str, platform: str, oracle) -> dict:
    """Cold then warm through session.sql(text).toArrow(), then one more
    execution kept on the device to see where the result planes live."""
    sql = query_text(qname)
    runs = []
    for label in ("cold", "warm"):
        c0 = counters_now()
        t0 = time.perf_counter()
        df = session.sql(sql)
        table = df.toArrow()
        wall_ms = (time.perf_counter() - t0) * 1000
        d = delta(c0, counters_now())
        runs.append((label, wall_ms, d, table, df))
        check_nothing_hidden(session, f"{qname} {label}")
    dec = announced_tier(runs[0][4].query_execution.physical)
    say(f"{qname}: tier={dec.tier} reason={dec.reason!r} "
        f"details={dec.details}")
    for label, wall_ms, d, table, _df in runs:
        say(f"{qname} {label}: wall_ms={wall_ms:.0f} rows={table.num_rows} "
            f"kernel_cache.misses={d['misses']} "
            f"launches={d['launches']} compile_ms={d['compile_ms']} "
            f"compile.disk_hit={d['compile.disk_hit']} "
            f"compile.disk_miss={d['compile.disk_miss']} "
            f"kinds={d['by_kind']}")
        ran = executed_tier(d["by_kind"])
        if ran != dec.tier:
            raise SmokeFailure(
                f"{qname} {label}: choose_tier announced '{dec.tier}' but "
                f"the '{ran}' tier dispatched ({d['by_kind']})")
        if d["launches"] <= 0:
            raise SmokeFailure(f"{qname} {label}: kernel_cache.launches "
                               "did not move")
    rows = arrow_rows(runs[1][3])
    check_rows(f"{qname} cold vs warm", arrow_rows(runs[0][3]), rows)
    check_rows(f"{qname} vs sqlite oracle", rows, oracle()[qname])
    devs = plane_devices(session.sql(sql).query_execution.execute(),
                         platform)
    say(f"{qname}: matches the oracle ({len(rows)} rows); result planes "
        f"on {sorted(str(d) for d in devs)}")
    return {"rows": rows, "warm_kinds": runs[1][2]["by_kind"]}


# ---------------------------------------------------------------------------
# the server answers
# ---------------------------------------------------------------------------

def _client(host: str, port: int) -> tuple:
    """The jax-free DB-API client: three queries and one status."""
    from spark_tpu.connect.sql_endpoint import connect

    out = {}
    with connect(host, port, timeout=900.0) as conn:
        for q in QUERIES:
            cur = conn.cursor().execute(query_text(q))
            dec_cols = [i for i, d in enumerate(cur.description)
                        if d[1].startswith("decimal")]
            rows = []
            for r in cur.fetchall():   # decimals ride the wire as text
                r = list(r)
                for i in dec_cols:
                    r[i] = None if r[i] is None else Decimal(r[i])
                rows.append(tuple(r))
            out[q] = rows
        status = conn.server_status()
    return out, status


def serve_phase(session, direct: dict) -> None:
    from spark_tpu.connect.sql_endpoint import SQLEndpoint

    c0 = counters_now()
    ep = SQLEndpoint(session, port=0).start()
    try:
        with ThreadPoolExecutor(1, thread_name_prefix="smoke-client") as ex:
            answers, status = ex.submit(_client, ep.host, ep.port).result()
    finally:
        drained = ep.stop()
    if not drained:
        raise SmokeFailure("SQLEndpoint.stop() did not drain")
    for q in QUERIES:
        check_rows(f"{q} through SQLEndpoint vs direct", answers[q],
                   direct[q]["rows"])
    # connections run on cloned sessions with their own counters, so the
    # no-degrade proof here is the launch ledger: the same plans must have
    # dispatched the same kernel kinds as the direct warm runs
    want: Counter = Counter()
    for q in QUERIES:
        want.update(direct[q]["warm_kinds"])
    got = delta(c0, counters_now())["by_kind"]
    if got != dict(want):
        raise SmokeFailure(f"server launches {got} != direct warm "
                           f"launches {dict(want)}")
    say(f"server: 3 queries equal the direct answers, launches {got}, "
        f"drained={drained}, sessions_opened="
        f"{status.get('sessions_opened')}, "
        f"pools={sorted(status.get('pools', {}))}")


# ---------------------------------------------------------------------------
# several chips
# ---------------------------------------------------------------------------

MESH_DEVICES = 4


@contextlib.contextmanager
def _watch_exchange_outputs(seen: list):
    """Record how many devices each mesh exchange's output planes span
    (they are handed on as per-device shards, so the sharded array is
    only visible at the point the exchange builds its result)."""
    from spark_tpu.parallel import mesh_exchange as MX

    orig = MX._build_result

    def spy(schema, col_arrays, valid_arrays, new_mask, *a, **kw):
        seen.extend(len(x.sharding.device_set)
                    for x in [*col_arrays, new_mask])
        return orig(schema, col_arrays, valid_arrays, new_mask, *a, **kw)

    MX._build_result = spy
    try:
        yield
    finally:
        MX._build_result = orig


def mesh_phase(tables: dict, platform: str, want_rows: list) -> None:
    for tier in ("stage", "mesh-whole"):
        mesh_tier(tier, tables, platform, want_rows)


def mesh_tier(tier: str, tables: dict, platform: str,
              want_rows: list) -> None:
    """q3 on one mesh tier over MESH_DEVICES devices. The fact table is
    registered with four partitions and broadcast joins are off, so every
    join and the aggregate shuffle — at any scale."""
    from spark_tpu import TpuSession

    sql = query_text("q3")
    session = TpuSession(f"chip-smoke-{tier}", {
        **SESSION_CONF,
        "spark.sql.shuffle.partitions": MESH_DEVICES,
        "spark.tpu.compile.tier": tier,
        "spark.sql.autoBroadcastJoinThreshold": -1})
    try:
        for name, tab in tables.items():
            df = session.createDataFrame(tab)
            if name == "store_sales":
                df = df.repartition(MESH_DEVICES)
            df.createOrReplaceTempView(name)
        c0 = counters_now()
        t0 = time.perf_counter()
        rows = arrow_rows(session.sql(sql).toArrow())
        wall_ms = (time.perf_counter() - t0) * 1000
        d = delta(c0, counters_now())
        check_rows(f"q3 on the {tier} tier vs the one-device answer",
                   rows, want_rows)
        seen: list = []
        with _watch_exchange_outputs(seen):
            qe = session.sql(sql).query_execution
            parts = qe.execute()
        devs = plane_devices(parts, platform)
        counters = session._metrics.snapshot()["counters"]
        check_nothing_hidden(session, f"mesh {tier}")
        dec = announced_tier(qe.physical)
        if tier == "stage":
            if not counters.get("exchange.mesh"):
                raise SmokeFailure("stage tier: no exchange ran on the "
                                   "mesh (exchange.mesh == 0)")
            if not seen or set(seen) != {MESH_DEVICES}:
                raise SmokeFailure(
                    f"stage tier: exchange output planes span {seen} "
                    f"devices, want {MESH_DEVICES} each")
            proof = (f"exchange.mesh={counters['exchange.mesh']} "
                     f"exchange.mesh_fused="
                     f"{counters.get('exchange.mesh_fused', 0)} "
                     f"exchange planes on {MESH_DEVICES} devices")
        else:
            if dec.tier != "mesh-whole":
                raise SmokeFailure(f"mesh-whole refused: {dec.reason}")
            # two executions so far (toArrow + execute): every step of
            # each must be exactly one mesh_whole launch
            steps = counters.get("mesh_whole.dispatches", 0)
            launches = counters_now()["by_kind"].get("mesh_whole", 0) \
                - c0["by_kind"].get("mesh_whole", 0)
            if not launches or launches != steps:
                raise SmokeFailure(f"mesh-whole: {launches} mesh_whole "
                                   f"launches for {steps} steps")
            if len(devs) != MESH_DEVICES:
                raise SmokeFailure(
                    f"mesh-whole: result planes on {len(devs)} devices, "
                    f"want {MESH_DEVICES}")
            proof = (f"{launches} mesh_whole launches for {steps} steps, "
                     f"result planes on {len(devs)} devices")
        say(f"mesh {tier}: tier={dec.tier} wall_ms={wall_ms:.0f} "
            f"rows={len(rows)} equal the one-device answer; {proof}; "
            f"compile_ms={d['compile_ms']} kinds={d['by_kind']}")
    finally:
        session.stop()


# ---------------------------------------------------------------------------

def identity(platform: str) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from spark_tpu.utils import native

    dev = jax.devices()[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "none"
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device: {json.dumps(device)} bytes_limit="
        f"{stats.get('bytes_limit')}")
    say(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} python={sys.version.split()[0]}")
    say(f"native: {native.status()}")
    if dev.platform != platform:
        raise SmokeFailure(
            f"platform is '{dev.platform}', not '{platform}': this run "
            "proves nothing about the chip (the CPU is reachable only "
            "through --cpu)")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu", action="store_true",
                    help="run at a tiny size on the CPU backend (the "
                         "tier-1 test); never a default, never inferred")
    args = ap.parse_args(argv)
    platform = "tpu"
    scale = FULL_SCALE
    if args.cpu:
        # decided before jax is touched: the environment alone picks the
        # platform, and eight virtual devices give the mesh phase a mesh
        platform, scale = "cpu", CPU_SCALE
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
    if HERE not in sys.path:
        sys.path.insert(0, HERE)

    with phase("identity"):
        import jax

        from spark_tpu import TpuSession

        session = TpuSession("chip-smoke", dict(SESSION_CONF))
        device = identity(platform)
        say(f"compile cache: {jax.config.jax_compilation_cache_dir} "
            f"(JAX_COMPILATION_CACHE_DIR="
            f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')})")

    oracle_pool = ThreadPoolExecutor(1, thread_name_prefix="smoke-oracle")
    try:
        with phase("data"):
            tables = generate_tables(scale)
            n_fact = tables["store_sales"].num_rows
            say(f"generated {len(tables)} tables from seed {SEED}, "
                f"store_sales={n_fact} rows")
            oracle_future = oracle_pool.submit(oracle_rows, tables)
            for name, tab in tables.items():
                session.createDataFrame(tab).createOrReplaceTempView(name)

        direct = {}
        for q in QUERIES:
            with phase(f"query {q}"):
                direct[q] = run_query(session, q, platform,
                                      oracle_future.result)

        with phase("server"):
            serve_phase(session, direct)
            check_nothing_hidden(session, "after the server phase")
    finally:
        oracle_pool.shutdown(wait=True, cancel_futures=True)
        session.stop()

    if device["count"] >= MESH_DEVICES:
        with phase("mesh"):
            mesh_phase(tables, platform, direct["q3"]["rows"])
    else:
        say(f"phase mesh: skipped — {device['count']} device(s), the mesh "
            f"tiers need {MESH_DEVICES}")

    totals = counters_now()
    del totals["by_kind"]
    say(f"totals: {totals}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
