"""BENCHMARK.json and the files it names, found by name.

A cell names a configuration and a traffic mix; a configuration names
its entry (the door the clients use) and its tables; a traffic mix names
the queries of each stream. Queries, references, entries, table
generators and per-layer metrics are each a file under perfbench/ named
after the thing, so adding one is adding a file.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
_TRAILING_LIMIT = re.compile(r"\blimit\s+\d+\s*;?\s*$", re.I)


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str) -> dict:
    """The workload entry with its configuration and traffic loaded."""
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    return {"name": name, "chips": int(w["chips"]),
            "config": _json(os.path.join(ROOT, cfg_entry["file"])),
            "traffic": _json(os.path.join(HERE, "traffic",
                                          w["traffic"] + ".json")),
            "end_to_end": [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]}


def query_text(query: str, keep_limit: bool = False) -> str:
    """The query as the cells run it: the trailing LIMIT is dropped,
    since the configurations promise complete result sets."""
    with open(os.path.join(HERE, "queries", query + ".sql")) as f:
        text = f.read().rstrip()
    return text if keep_limit else _TRAILING_LIMIT.sub("", text).rstrip()


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"perfbench/peaks.json ({sorted(table)}): add it "
                       "with its source, there is no default")
    return table[device_kind]


def metric_reader(name: str):
    """perfbench/metrics/<name>.py, loaded by path (names hold dots)."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"per-layer metric {name!r} has no reader "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
