"""Vectorised TPC-DS data from a seed: numpy columns, Arrow tables.

A configuration's `tables` lists each table with its row count and the
columns kept; the module under `tables/` of the table's name makes it,
and a column that module does not make is made by the one module under
`columns/<table>/` that lists it in its `MAKES` (`more_columns`), so a
later PR widens a table by adding a file. Every draw is from a stream
keyed by (seed, table, name), so the data of one column does not depend
on which others a configuration keeps, and a widened table is the old
one plus columns. The program sees only the Arrow tables; the references
read the numpy columns the Arrow tables were built from.

Which seed a stream takes is the configuration's `seeding`: the streams it
lists `from_the_run_seed` take `--seed`, every other stream takes its
`structure_seed`. So every run of the configuration has the same keys,
dates, tickets and nulls (the sizes of every join, filter and group: the
work), and other measures (the answers).
"""

from __future__ import annotations

import importlib
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np
import pyarrow as pa


ARROW_THREADS = 4
COLUMNS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "columns")


@dataclass
class Col:
    """One column on the host. `values` holds int32 numbers, unscaled
    int64 decimals, or int32 codes into `pool` for strings."""
    values: np.ndarray
    valid: np.ndarray | None = None      # bool per row; None: no nulls
    pool: list | None = None             # strings, by code
    scale: int | None = None             # decimal scale; precision below
    precision: int | None = None

    @property
    def kind(self) -> str:
        if self.pool is not None:
            return "string"
        return "decimal" if self.scale is not None else "int32"

    def take(self, idx) -> "Col":
        return Col(self.values[idx],
                   None if self.valid is None else self.valid[idx],
                   self.pool, self.scale, self.precision)

    def strings(self) -> np.ndarray:
        return np.asarray(self.pool, dtype=object)[self.values]


@dataclass(frozen=True)
class Seeds:
    """What a table's generator is given as its seed."""
    run: int
    structure: int
    from_run: frozenset          # "table.stream"


def rng_for(seeds: Seeds, table: str, column: str) -> np.random.Generator:
    seed = seeds.run if f"{table}.{column}" in seeds.from_run \
        else seeds.structure
    return np.random.default_rng(
        [seed, zlib.crc32(table.encode()), zlib.crc32(column.encode())])


def _validity_buffer(valid):
    if valid is None:
        return None
    return pa.py_buffer(np.packbits(valid, bitorder="little"))


def _int32_array(values, valid) -> pa.Array:
    return pa.Array.from_buffers(
        pa.int32(), len(values),
        [_validity_buffer(valid),
         pa.py_buffer(np.ascontiguousarray(values, dtype=np.int32))])


def to_arrow(col: Col) -> pa.Array:
    if col.kind == "string":
        return pa.DictionaryArray.from_arrays(
            _int32_array(col.values, col.valid),
            pa.array(col.pool, pa.string())).cast(pa.string())
    if col.kind == "decimal":
        # decimal128 is a little-endian 128-bit integer: low word, then
        # the sign extension
        n = len(col.values)
        words = np.zeros((n, 2), np.int64)
        words[:, 0] = col.values
        if n and col.values.min() < 0:
            words[:, 1] = col.values >> 63
        return pa.Array.from_buffers(
            pa.decimal128(col.precision, col.scale), n,
            [_validity_buffer(col.valid), pa.py_buffer(words)])
    return _int32_array(col.values, col.valid)


def table_rows(config: dict, scale: float = 1.0) -> dict:
    """Row count of every table, and of the dimensions that foreign keys
    point into without the configuration making them (`foreign_domains`).
    `scale` below 1 is the CPU rehearsal's: tables marked `scales` shrink
    with it, fixed domains stay whole."""
    out = dict(config.get("foreign_domains", {}))
    for spec in config["tables"]:
        rows = int(spec["rows"])
        if scale != 1.0 and spec.get("scales", True):
            rows = max(1, int(rows * scale))
        out[spec["name"]] = rows
    return out


def generate(config: dict, seed: int, scale: float = 1.0) -> dict:
    """{table: {column: Col}} for the configuration, from the seed."""
    sizes = table_rows(config, scale)
    seeding = config["seeding"]
    seeds = Seeds(int(seed), int(seeding["structure_seed"]),
                  frozenset(seeding["from_the_run_seed"]))
    data = {}
    for spec in config["tables"]:
        name = spec["name"]
        mod = importlib.import_module(f"perfbench.gen.tables.{name}")
        cols = mod.generate(seeds, sizes[name], list(spec["columns"]), sizes)
        missing = [c for c in spec["columns"] if c not in cols]
        if missing:
            cols = more_columns(name, seeds, sizes, missing, cols)
        data[name] = {c: cols[c] for c in spec["columns"]}
    return data


def column_modules(table: str) -> dict:
    """{name: module} of `columns/<table>/`, in name order."""
    folder = os.path.join(COLUMNS_DIR, table)
    if not os.path.isdir(folder):
        return {}
    names = sorted(f[:-3] for f in os.listdir(folder)
                   if f.endswith(".py") and not f.startswith("__"))
    return {n: importlib.import_module(f"perfbench.gen.columns.{table}.{n}")
            for n in names}


def more_columns(table, seeds, sizes, missing, made) -> dict:
    """`made`, the columns the table's own module made, plus the
    `missing` ones, each from the one module under `columns/<table>/`
    whose `MAKES` lists it: `generate(seeds, rows, columns, sizes, made)`,
    `made` being the table's columns so far, the earlier modules' (by
    name) among them."""
    mods = column_modules(table)
    for c in missing:
        by = [n for n, m in mods.items() if c in m.MAKES]
        if len(by) != 1:
            raise KeyError(
                f"{table}.{c}: perfbench/gen/tables/{table}.py does not "
                f"make it, and of the modules under "
                f"{os.path.join(COLUMNS_DIR, table)} {by or 'none'} list "
                "it in MAKES: exactly one has to")
    made = dict(made)
    for mod in mods.values():
        ask = [c for c in missing if c in mod.MAKES]
        if ask:
            got = mod.generate(seeds, sizes[table], ask, sizes, made)
            made.update({c: got[c] for c in ask})
    return made


def arrow_tables(data: dict) -> dict:
    """Columns are converted on a few threads: the copies are numpy's and
    Arrow's, which let go of the interpreter."""
    flat = [(t, c, col) for t, cols in data.items()
            for c, col in cols.items()]
    with ThreadPoolExecutor(ARROW_THREADS) as pool:
        arrays = list(pool.map(lambda x: to_arrow(x[2]), flat))
    out = {t: {} for t in data}
    for (t, c, _col), arr in zip(flat, arrays):
        out[t][c] = arr
    return {t: pa.table(cols) for t, cols in out.items()}
