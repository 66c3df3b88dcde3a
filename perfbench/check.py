"""The comparison that decides `correct`.

Every execution the window completed is compared, row by row, with the
plain reference's result over the same tables. Each number compared has
a limit in limits.json; a run is correct when none is over its limit.
"""

from __future__ import annotations

import json
import os
from decimal import Decimal

HERE = os.path.dirname(os.path.abspath(__file__))


def limits() -> dict:
    with open(os.path.join(HERE, "limits.json")) as f:
        return json.load(f)


def compare_rows(got: list, want: list, ref) -> dict:
    """Numbers for one execution: `got` are the rows the timed path
    returned, `want` the reference's, `ref` the reference module."""
    keys = ref.KEY_COLUMNS
    averages = getattr(ref, "AVG_COLUMNS", ())
    n = {"rows_wrong": 0, "order_breaks": 0, "decimal_sum_max_abs_units": 0,
         "decimal_avg_max_abs_units": 0, "double_max_rel": 0.0}

    def key(row):
        return tuple(row[k] for k in keys)

    want_by_key = {key(r): r for r in want}
    seen = set()
    for row in got:
        k = key(row)
        w = want_by_key.get(k)
        if w is None or k in seen or len(row) != len(w):
            n["rows_wrong"] += 1          # extra, repeated or misshapen
            continue
        seen.add(k)
        for j, (g, x) in enumerate(zip(row, w)):
            if j in keys:
                continue
            if isinstance(x, Decimal):
                if not isinstance(g, Decimal) \
                        or g.as_tuple().exponent != x.as_tuple().exponent:
                    n["rows_wrong"] += 1
                    break
                units = int(abs(g - x).scaleb(-x.as_tuple().exponent))
                name = "decimal_avg_max_abs_units" if j in averages \
                    else "decimal_sum_max_abs_units"
                n[name] = max(n[name], units)
            elif isinstance(x, float):
                if not isinstance(g, float):
                    n["rows_wrong"] += 1
                    break
                rel = abs(g - x) / max(abs(x), 1e-300)
                n["double_max_rel"] = max(n["double_max_rel"], rel)
            elif g != x:
                n["rows_wrong"] += 1
                break
    n["rows_wrong"] += len(want_by_key) - len(seen)      # missing
    try:
        order = [ref.order_key(r) for r in got]
        n["order_breaks"] = sum(a > b for a, b in zip(order, order[1:]))
    except TypeError:      # a cell of the wrong type cannot be ordered
        n["order_breaks"] = len(got)
    return n


def merge(total: dict, part: dict) -> dict:
    """Counts add up, widest gaps are kept."""
    for k, v in part.items():
        if k.endswith(("_max_abs_units", "_max_rel")):
            total[k] = max(total.get(k, 0), v)
        else:
            total[k] = total.get(k, 0) + v
    return total


def over(numbers: dict) -> list:
    """The names of the numbers that are over their limits."""
    lim = limits()
    return [k for k, v in numbers.items() if k in lim and v > lim[k]]


def verdict(numbers: dict) -> tuple:
    """(correct, {name: {"value": v, "limit": l}}): every limit has to
    have been read, and none may be passed."""
    lim = limits()
    compared = {k: {"value": numbers.get(k), "limit": l}
                for k, l in lim.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in compared.values())
    return ok, compared
