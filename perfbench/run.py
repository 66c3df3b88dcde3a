#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (everything before the window): make the tables from the seed,
start the session, register the tables, run every query of the cell once
through the cell's own door. `setup_s` is process start to the window's
start, less the time the main thread waited for the benchmark's own
generator once the backend was up (`setup_seconds`): what a user of the
engine pays, who has their tables. The window: each stream of the traffic
file runs its list of queries in its fixed order, one after the other (a
closed loop: the only kind there is), in whole rounds (`stream_loop` has
the rule), a stream's first submit as many seconds after the window
opens as the traffic file's `start_offsets_s` says (none: all at once);
the window is from the first submit to the last completion, and every
query started counts. After the window: read the device's
peak, stop the program, compute the plain numpy references and compare
every execution's rows with them.

The last line of standard output is one JSON object. Without a TPU the
run fails, unless `--rehearse` asks for the CPU rehearsal: a tiny scale,
counts and `correct`, and no metric at all.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import contextlib                  # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import threading                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import check, gen, reference, spec   # noqa: E402

# counters that mean "the device said no and the engine went around it"
HIDDEN = ("whole_query.runtime_degraded", "whole_query.mesh_gang_retries",
          "exchange.mesh_fallback", "exchange.mesh_runtime_fallback",
          "exchange.mesh_gang_failures", "scheduler.stage_retries")
TRACE_DIR = os.path.join(ROOT, ".cache", "perfbench_trace")
ANSWER_WAIT_S = 120.0    # a query that is late is late, not wrong


class NoChip(Exception):
    """The machine does not hold what the cell asks for."""


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def no_span(_name: str):
    """What `annotate` is when no trace is taken."""
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def find_device(chips: int, rehearse: bool) -> dict:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        return device
    if device["platform"] != "tpu":
        raise NoChip(f"platform is {device['platform']!r}, not 'tpu': a "
                     "number from here would not be a device's (the CPU is "
                     "reachable only through --rehearse)")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax sees {len(devs)}")
    spec.peaks(device["kind"])     # an unknown kind is an error, early
    return device


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def engine_counters() -> dict:
    import spark_tpu.exec.persist_cache as pc
    from spark_tpu.physical.compile import GLOBAL_KERNEL_CACHE as KC

    return {**KC.counters(), **pc.disk_counters(),
            "by_kind": dict(KC.launches_by_kind)}


def hidden_moved(sessions: list) -> int:
    total = 0
    for s in sessions:
        counters = s._metrics.snapshot()["counters"]
        total += sum(int(counters.get(k, 0)) for k in HIDDEN)
    return total


def tier_of_kind(kind: str) -> str:
    return {"mesh_whole": "mesh-whole", "whole_query": "whole"}.get(
        kind, "stage")


def announced_tier(session, text: str) -> str:
    """What the planner says it will run, without running it."""
    physical = session.sql(text).query_execution.physical
    dec = getattr(physical, "decision", None) \
        or getattr(physical, "_tier_decision", None)
    if dec is None:
        raise RuntimeError("the planner left no TierDecision on the plan")
    return dec.tier


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def stream_loop(index, client, queries, texts, seconds, gate, annotate,
                records, at_most=None, clock=time.perf_counter,
                offset=0.0, sleep=time.sleep):
    """One stream's part of the window, in whole rounds. A round is the
    stream's whole list. The first round always starts; another starts
    only if the time passed plus the length of the round just finished
    is under `seconds`, and never more than the traffic file's
    `rounds_at_most`; a round that is started is finished. So every
    query of the list has the same count in every window, and a round a
    little shorter or longer changes the count by a whole round or not
    at all. A stream with an `offset` submits its first query that many
    seconds after the window opened (tenants do not arrive in the same
    millisecond; the window's clock does not wait for it). (`clock` and
    `sleep` are the tests' alone.)"""
    gate.wait()
    t0 = gate.t0
    if offset > clock() - t0:
        sleep(offset - (clock() - t0))
    rounds = 0
    while True:
        t_round = clock()
        for q in queries:
            rec = {"stream": index, "query": q, "round": rounds,
                   "raw": None, "info": {}, "error": None}
            rec["t_submit"] = clock()
            try:
                rec["raw"], rec["info"] = client.run(texts[q], annotate)
            except Exception as e:  # a failed query is a finding, counted
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["t_done"] = clock()
            records.append(rec)
        rounds += 1
        now = clock()
        if rounds == at_most or (now - t0) + (now - t_round) >= seconds:
            return


class Gate(threading.Event):
    t0 = 0.0

    def open(self):
        self.t0 = time.perf_counter()
        self.set()


def run_window(clients, streams, texts, seconds, at_most, annotate,
               offsets=None) -> list:
    """The first stream runs in the caller's thread, the one that warmed
    up (a window in a new thread had a slow first round: PR 28); every
    other stream has a thread of its own."""
    records: list = []
    gate = Gate()
    offsets = offsets or [0.0] * len(streams)

    def loop(i):
        stream_loop(i, clients[i], streams[i], texts, seconds, gate,
                    annotate, records, at_most, offset=float(offsets[i]))

    threads = [threading.Thread(target=loop, name=f"pb-stream-{i}",
                                args=(i,), daemon=True)
               for i in range(1, len(streams))]
    for t in threads:
        t.start()
    with annotate("window"):
        gate.open()
        loop(0)
        for t in threads:
            t.join(seconds + ANSWER_WAIT_S)
    alive = [t.name for t in threads if t.is_alive()]
    if alive:
        raise RuntimeError(f"streams never finished: {alive}")
    return sorted(records, key=lambda r: r["t_submit"])


def percentile(values: list, p: float) -> float:
    """Nearest-rank percentile of all the values: no interpolation, so a
    tail is a latency some query really had."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, -(-len(v) * p // 100) - 1))]


def window_values(records: list, fact_rows: int) -> dict:
    """The window's three end-to-end numbers, from its records alone: the
    window is from the first submit to the last completion, every query
    started is in the latencies, every query answered in the rate."""
    window_s = max(r["t_done"] for r in records) \
        - min(r["t_submit"] for r in records)
    latencies = [r["t_done"] - r["t_submit"] for r in records]
    answered = sum(r["error"] is None for r in records)
    return {"window_s": window_s, "latencies": latencies,
            "fact_rows_per_s": fact_rows * answered / window_s,
            "query_s.p50": percentile(latencies, 50),
            "query_s.p95": percentile(latencies, 95)}


def setup_seconds(marks: dict) -> float:
    """`setup_s`: process start to the window's start, less the time the
    main thread waited for the benchmark's own generator once the backend
    was up. A user of the engine has their tables; they pay for the
    process, the backend, the session, registration, the copy to the
    device, the programs' load or compile, and the warm-up pass."""
    return marks["window opens"] - marks["generator wait"]


def compare_window(records: list, want: dict) -> tuple:
    """Every execution against its reference: (the numbers compared, how
    many executions failed or were wrong)."""
    numbers = {"unanswered": 0}
    failed = 0
    for rec in records:
        where = f"query {rec['query']} on stream {rec['stream']}"
        if rec["error"] is not None:
            say(f"{where} failed: {rec['error']}")
            numbers["unanswered"] += 1
            failed += 1
            continue
        n = check.compare_rows(rec["rows"], want[rec["query"]],
                               reference.load(rec["query"]))
        if check.over(n):
            say(f"{where} is wrong: {n}")
            failed += 1
        check.merge(numbers, n)
    return numbers, failed


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's rehearsal "
                         "scale: counts and `correct`, no metric")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        say(f"no chip: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


def run(args, break_path=None) -> dict:
    """`break_path`, used by the tests alone, gets the entry, the session
    and the Arrow tables before warm-up, so that a fault can be planted
    under the timed path."""
    if importlib.util.find_spec("spark_tpu") is None:
        raise SystemExit("perfbench measures the spark_tpu of its own "
                         "checkout, and there is none beside it")
    cell = spec.cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    marks = {}

    def mark(name):
        marks[name] = time.perf_counter() - T_START
        say(f"set-up: {name} at {marks[name]:.1f} s")

    def device_bytes():
        stats = jax.devices()[0].memory_stats() or {}
        return (f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB in use, "
                f"peak {stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")

    # numpy makes the tables on a thread while the backend starts here;
    # without the chip the run ends at once and the thread with it
    made = {}
    scale = float(config["rehearsal"]["scale"]) if args.rehearse else 1.0

    def make_tables():
        try:
            made["data"] = gen.generate(config, args.seed, scale)
            made["tables"] = gen.arrow_tables(made["data"])
        except BaseException as e:
            made["error"] = e

    maker = threading.Thread(target=make_tables, name="pb-tables",
                             daemon=True)
    maker.start()
    device = find_device(cell["chips"], args.rehearse)
    mark("backend up")
    t_wait = time.perf_counter()
    maker.join()
    marks["generator wait"] = time.perf_counter() - t_wait   # a length
    if "error" in made:
        raise made["error"]
    data, tables = made.pop("data"), made.pop("tables")
    mark("tables made")

    import jax

    from spark_tpu import TpuSession

    session = TpuSession("perfbench", dict(config["session_conf"]))
    entry = None
    tracing = bool(args.trace) and not args.rehearse
    try:
        for name, tab in tables.items():
            session.createDataFrame(tab).createOrReplaceTempView(name)
        entry = importlib.import_module(
            f"perfbench.entries.{config['entry']}").Entry(session, config)
        if break_path is not None:
            break_path(entry, session, tables)
        del tables
        streams = [list(s) for s in traffic["streams"]]
        distinct = list(dict.fromkeys(q for s in streams for q in s))
        texts = {q: spec.query_text(q) for q in distinct}
        announced = {q: announced_tier(session, texts[q]) for q in distinct}
        clients = [entry.client(i) for i in range(len(streams))]
        mark("session up")

        # warm-up: every stream's list once through its own client, here
        # on the main thread (on a cold run this is where the programs
        # compile)
        for i, (c, qs) in enumerate(zip(clients, streams)):
            for q in qs:
                t0 = time.perf_counter()
                c.run(texts[q], no_span)
                say(f"warm-up {q} on stream {i}: "
                    f"{time.perf_counter() - t0:.2f} s, {device_bytes()}")
        mark("warm")

        annotate = no_span
        if tracing:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)

            def annotate(name):
                return jax.profiler.TraceAnnotation("pb:" + name)

        before = {"counters": engine_counters(),
                  "hidden": hidden_moved(entry.sessions())}
        mark("window opens")
        records = run_window(clients, streams, texts, args.seconds,
                             traffic.get("rounds_at_most"), annotate,
                             traffic.get("start_offsets_s"))
        after = {"counters": engine_counters(),
                 "hidden": hidden_moved(entry.sessions())}
        if tracing:
            jax.profiler.stop_trace()
        peak = memory_peak_bytes()
        for rec in records:       # the wire's rows, as Python rows
            if rec["error"] is None:
                rec["rows"] = clients[rec["stream"]].rows(rec.pop("raw"))
        for c in clients:
            c.close()
    finally:
        if entry is not None:
            entry.stop()
        session.stop()

    # the rate counts the rows of the configuration's first fact table
    first_fact = data[config["fact_tables"][0]]
    fact_rows = len(next(iter(first_fact.values())).values)
    values = window_values(records, fact_rows)
    values["setup_s"] = setup_seconds(marks)
    window_s, latencies = values["window_s"], values["latencies"]

    # the references run once the program is stopped: host numpy only
    t_ref = time.perf_counter()
    want = {q: reference.load(q).run(data, reference.Exact())
            for q in distinct}
    numbers, failed = compare_window(records, want)
    kinds = {k: v - before["counters"]["by_kind"].get(k, 0)
             for k, v in after["counters"]["by_kind"].items()
             if v != before["counters"]["by_kind"].get(k, 0)}
    ran = {tier_of_kind(k) for k in kinds}
    said = set(announced.values())
    numbers["tier_mismatch"] = len(ran ^ said)
    numbers["hidden_counters_moved"] = after["hidden"] - before["hidden"]
    correct, compared = check.verdict(numbers)
    ref_s = time.perf_counter() - t_ref
    say(f"window {window_s:.2f} s, {len(records)} queries "
        f"(stream, query, start, seconds: "
        f"{[(r['stream'], r['query'], round(r['t_submit'] - records[0]['t_submit'], 3), round(r['t_done'] - r['t_submit'], 3)) for r in records]}), "
        f"launched {kinds}, announced {announced}, reference {ref_s:.1f} s")

    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": {}, "device": device}
    if not args.rehearse:
        device["memory_peak_bytes"] = peak
        state = {"cell": cell, "records": records, "window_s": window_s,
                 "latencies": latencies, "fact_rows": fact_rows,
                 "before": before, "after": after, "data": data,
                 "want": want, "device": device, "setup_s": values["setup_s"],
                 "peaks": spec.peaks(device["kind"]), "trace": None}
        if tracing:
            from perfbench.trace import reduce as tr

            state["trace"] = t = tr.reduce_file(tr.find_xplane(TRACE_DIR))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            say(f"programs by device time: {t['device_modules']}")
            device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
            result["breakdown"] = {"device_ops": t["device_ops"],
                                   "idle_gaps": t["idle_gaps"]}
            for m in cell["per_layer"]:
                value = spec.metric_reader(m["name"]).read(state)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
        else:
            for m in cell["end_to_end"]:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    result["window"] = {
        "seconds": window_s,
        "rounds": [1 + max(r["round"] for r in records if r["stream"] == i)
                   for i in range(len(streams))],
        "queries": {q: sum(r["query"] == q for r in records)
                    for q in distinct}}
    result["set_up"] = marks
    result["compared"] = compared
    for name, c in compared.items():
        say(f"compared {name}: {c['value']} (limit {c['limit']})")
    return result


if __name__ == "__main__":
    sys.exit(main())
