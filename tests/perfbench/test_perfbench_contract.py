"""BENCHMARK.json against the contract's letter, and the files it names:
every name and unit is of the allowed characters, every cell finds its
configuration, traffic, queries and references, and every per-layer
metric has a reader that declares what BENCHMARK.json says of it."""

import importlib
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import spec  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    # the whole check has to fit with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_command_stays_inside_paths():
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/")
        assert ".." not in word.split("/")
    assert BENCH["command"][1].split("/")[0] in BENCH["paths"]


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    assert NAME.match(f), os.path.join(d, f)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    e2e = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert allowed <= set(metric) <= allowed | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert LINE.match(metric["layer"])
        moved = [m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"]]
        assert len(moved) == 1
        # every cell that reports the metric reports what it moves
        assert set(metric.get("workloads", cells)) <= set(
            moved[0].get("workloads", cells))


def test_names_are_unique_and_setup_s_is_there():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_that_agrees(metric):
    reader = spec.metric_reader(metric["name"])
    assert reader.LAYER == metric["layer"]
    assert reader.SOURCE == metric["source"]
    assert reader.MOVES == metric["moves"]
    assert reader.UNIT == metric["unit"]
    assert callable(reader.read)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert LINE.match(config["source"]) and LINE.match(config["why"])
    assert config["file"].split("/")[0] in BENCH["paths"]
    assert len(config["reduced"]) <= 16
    with open(os.path.join(REPO, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    for key in config["reduced"]:
        assert NAME.match(key) and key in body and key in body["reduced"]
        assert not key.endswith(("_dim", "_rank"))
    assert body["guarantees"] and body["assumed"]
    # no table is made that no query of the configuration reads; a
    # dimension has the columns read and no others; each table the file
    # lists under `fact_tables` is whole, as a deployment holds it: every
    # column its generator module declares, as many as are published
    from perfbench import reference
    read = {}
    for q in body["query_templates"]:
        for table, cols in reference.load(q).READS.items():
            read.setdefault(table, set()).update(cols)
    assert body["fact_tables"] and set(body["fact_tables"]) <= set(read)
    for table in body["fact_tables"]:
        whole = importlib.import_module(
            f"perfbench.gen.tables.{table}").COLUMNS
        read[table] = set(whole)
        assert len(whole) == body["published"][f"{table}_columns"], table
    assert {t["name"]: set(t["columns"]) for t in body["tables"]} == read
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    sources = [c["source"] for c in BENCH["configs"]]
    assert len(sources) == len(set(sources))
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_finds_everything_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and LINE.match(cell["why"])
    loaded = spec.cell(cell["name"])
    traffic = loaded["traffic"]
    assert set(traffic) - {"rounds_at_most", "start_offsets_s"} \
        == {"why", "streams"}
    offsets = traffic.get("start_offsets_s", [0.0] * len(traffic["streams"]))
    assert len(offsets) == len(traffic["streams"]) and min(offsets) == 0.0
    names = [m["name"] for m in loaded["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert loaded["per_layer"]
    from perfbench import reference
    for stream in loaded["traffic"]["streams"]:
        for q in stream:
            assert "limit" not in spec.query_text(q).lower().split()[-2:]
            ref = reference.load(q)
            tables = {t["name"]: t["columns"]
                      for t in loaded["config"]["tables"]}
            for table, cols in ref.READS.items():
                assert set(cols) <= set(tables[table]), (q, table)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_every_file_of_the_benchmark_serves_a_cell():
    """No entry, traffic mix, query, reference, configuration, reader or
    column module without a cell in BENCHMARK.json that uses it."""
    cells = [spec.cell(w["name"]) for w in BENCH["workloads"]]
    used = {"entries": {c["config"]["entry"] for c in cells},
            "traffic": {w["traffic"] for w in BENCH["workloads"]},
            "configs": {os.path.basename(c["file"])[:-5]
                        for c in BENCH["configs"]},
            "metrics": {m["name"] for m in BENCH["per_layer"]}}
    used["queries"] = used["reference"] = {
        q for c in cells for s in c["traffic"]["streams"] for q in s}
    for folder, names in used.items():
        have = {os.path.splitext(f)[0]
                for f in os.listdir(os.path.join(REPO, "perfbench", folder))
                if not f.startswith("__")}
        assert have == names, folder
    # a column module makes a column some configuration keeps of its
    # table, and no column is made by two of them
    from perfbench import gen
    kept = {}
    for c in cells:
        for t in c["config"]["tables"]:
            kept.setdefault(t["name"], set()).update(t["columns"])
    for table in sorted(os.listdir(gen.COLUMNS_DIR)):
        if table.startswith("__"):
            continue
        made = []
        for name, mod in gen.column_modules(table).items():
            assert set(mod.MAKES) & kept.get(table, set()), (table, name)
            made += mod.MAKES
        assert len(made) == len(set(made)), (table, sorted(made))


def test_peaks_hold_only_what_a_reader_reads():
    with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    for kind, peaks in table.items():
        assert set(peaks) == {"hbm_bytes_per_s"}, kind


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no default"):
        spec.peaks("TPU v9 imaginary")


def test_queries_are_the_repo_templates():
    for q in ("q3", "q7"):
        with open(os.path.join(REPO, "tests", "tpcds", "queries",
                               q + ".sql")) as f:
            assert f.read().rstrip() == spec.query_text(q, keep_limit=True)
