"""Plain numpy references for the benchmark's queries.

Each query has a file of its own, `<query>.py`, found by name. It reads
the generator's numpy columns (never anything the program made) and
returns the full result set as Python rows in the query's select order:
ints and strings for the grouping columns, `decimal.Decimal` for decimal
aggregates, float for double aggregates. A reference module declares

    READS        {table: [columns]} — the planes the query needs, which
                 perfbench/bytes_model.py turns into the bytes of a roofline
    KEY_COLUMNS  positions of the grouping columns in a result row
    AVG_COLUMNS  (optional) positions of decimal averages, which the engine
                 divides in float64 and which are compared under a limit of
                 their own; every other decimal is a sum and is exact
    run(t, arith)  the rows, ordered as the query's ORDER BY asks
    order_key(row) the sort key of that ORDER BY, for checking a result's order

`arith` is the arithmetic of the aggregates: `Exact` is what the
configurations state (64-bit integer sums of unscaled decimals, decimal
averages rounded half up, double averages in float64); `Float32` is the
control, the same queries with every sum and average carried in float32.
"""

from __future__ import annotations

import importlib
from decimal import Decimal

import numpy as np


def load(query: str):
    return importlib.import_module(f"perfbench.reference.{query}")


def position(key_col, dim_key_col) -> np.ndarray:
    """Row of the dimension that each foreign key points at. Dimension
    keys are dense and ascending, which is checked, so this is a
    subtraction; rows whose key is null get a valid position to read and
    are masked by the caller."""
    dk = dim_key_col.values
    if len(dk) > 1 and not (dk[-1] - dk[0] == len(dk) - 1
                            and np.all(np.diff(dk) == 1)):
        raise ValueError("dimension key is not dense and ascending")
    pos = key_col.values.astype(np.int64) - int(dk[0])
    if key_col.valid is not None:
        pos = np.where(key_col.valid, pos, 0)
    if pos.size and (pos.min() < 0 or pos.max() >= len(dk)):
        raise ValueError("foreign key outside its dimension")
    return pos


def valid(col) -> np.ndarray:
    return np.ones(len(col.values), bool) if col.valid is None else col.valid


def group(*codes):
    """Distinct combinations of integer codes: (one row per group as a
    2-d array, group number of each input row)."""
    stacked = np.stack([np.asarray(c, np.int64) for c in codes], axis=1)
    uniq, inverse = np.unique(stacked, axis=0, return_inverse=True)
    return uniq, inverse.reshape(-1)


def to_decimal(units: int, scale: int) -> Decimal:
    return Decimal(int(units)).scaleb(-scale)


def _measure(inverse, col):
    """The rows of a measure that count: SQL's aggregates skip nulls."""
    if col.valid is None:
        return inverse, col.values
    return inverse[col.valid], col.values[col.valid]


class Exact:
    """The arithmetic the configurations state. A group none of whose
    values is non-null has the aggregate NULL (None)."""
    name = "exact"

    def sum_units(self, inverse, units, ngroups):
        out = np.zeros(ngroups, np.int64)
        np.add.at(out, inverse, units.astype(np.int64))
        return out

    def count(self, inverse, ngroups):
        return np.bincount(inverse, minlength=ngroups).astype(np.int64)

    def sum_decimal(self, inverse, col, ngroups):
        """SUM(decimal(p,s)) -> decimal(p+10,s), per group."""
        inv, values = _measure(inverse, col)
        u = self.sum_units(inv, values, ngroups)
        n = self.count(inv, ngroups)
        return [to_decimal(x, col.scale) if c else None
                for x, c in zip(u.tolist(), n.tolist())]

    def avg_decimal(self, inverse, col, ngroups):
        """AVG(decimal(p,s)) -> decimal(p+4,s+4), rounded half up."""
        inv, values = _measure(inverse, col)
        u = self.sum_units(inv, values, ngroups)
        n = self.count(inv, ngroups)
        out = []
        for s, c in zip(u.tolist(), n.tolist()):
            if not c:
                out.append(None)
                continue
            num = abs(s) * 10 ** 4
            q = (2 * num + c) // (2 * c)
            out.append(to_decimal(q if s >= 0 else -q, col.scale + 4))
        return out

    def avg_int(self, inverse, col, ngroups):
        """AVG(int) -> double."""
        inv, values = _measure(inverse, col)
        u = self.sum_units(inv, values, ngroups)
        n = self.count(inv, ngroups)
        return [s / c if c else None
                for s, c in zip(u.astype(np.float64).tolist(), n.tolist())]


class Float32(Exact):
    """The control: sums and averages carried in float32, the step that
    would tempt a change on a chip whose 64-bit arithmetic is emulated."""
    name = "float32"

    def _sum32(self, inverse, values32, ngroups):
        out = np.zeros(ngroups, np.float32)
        np.add.at(out, inverse, values32)
        return out

    @staticmethod
    def _decimals(values32, counts, scale):
        q = Decimal(1).scaleb(-scale)
        return [Decimal(repr(float(x))).quantize(q) if c else None
                for x, c in zip(values32, counts.tolist())]

    def _sum_and_count(self, inverse, col, ngroups, unit):
        inv, values = _measure(inverse, col)
        dollars = values.astype(np.float32) / np.float32(unit)
        return self._sum32(inv, dollars, ngroups), self.count(inv, ngroups)

    def sum_decimal(self, inverse, col, ngroups):
        s, n = self._sum_and_count(inverse, col, ngroups, 10 ** col.scale)
        return self._decimals(s, n, col.scale)

    def avg_decimal(self, inverse, col, ngroups):
        s, n = self._sum_and_count(inverse, col, ngroups, 10 ** col.scale)
        with np.errstate(invalid="ignore", divide="ignore"):
            a = s / n.astype(np.float32)
        return self._decimals(np.where(n > 0, a, 0), n, col.scale + 4)

    def avg_int(self, inverse, col, ngroups):
        s, n = self._sum_and_count(inverse, col, ngroups, 1)
        with np.errstate(invalid="ignore", divide="ignore"):
            a = s / n.astype(np.float32)
        return [float(x) if c else None for x, c in zip(a, n.tolist())]
