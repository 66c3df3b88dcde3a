"""sqlite-based result oracle for the TPC-DS suite.

Role of the reference's committed `tpcds-query-results` (which are tied
to dsdgen SF1 data we cannot regenerate): an independent engine executes
the same query over the same generated tables and the row sets are
compared. sqlite 3.40 covers the full dialect except GROUPING
SETS/ROLLUP (those queries are validated by cross-config self-checks in
the harness instead).

The rewrite layer translates the handful of constructs sqlite spells
differently (date INTERVAL arithmetic, DECIMAL casts, stddev_samp via a
registered Python aggregate). Dates live as ISO text so BETWEEN/compare
work lexically.
"""

from __future__ import annotations

import datetime
import math
import re
import sqlite3
from decimal import Decimal

import pyarrow as pa


class _StddevSamp:
    def __init__(self):
        self.vals = []

    def step(self, v):
        if v is not None:
            self.vals.append(float(v))

    def finalize(self):
        n = len(self.vals)
        if n < 2:
            return None
        m = sum(self.vals) / n
        return math.sqrt(sum((x - m) ** 2 for x in self.vals) / (n - 1))


class _VarSamp(_StddevSamp):
    def finalize(self):
        n = len(self.vals)
        if n < 2:
            return None
        m = sum(self.vals) / n
        return sum((x - m) ** 2 for x in self.vals) / (n - 1)


def _concat(*args):
    if any(a is None for a in args):
        return None
    return "".join(str(a) for a in args)


def load_sqlite(tables) -> sqlite3.Connection:
    conn = sqlite3.connect(":memory:")
    conn.create_aggregate("stddev_samp", 1, _StddevSamp)
    conn.create_aggregate("var_samp", 1, _VarSamp)
    conn.create_aggregate("stddev", 1, _StddevSamp)
    conn.create_function("concat", -1, _concat)
    for name, tab in tables.items():
        cols = tab.column_names
        conn.execute(f"CREATE TABLE {name} ({', '.join(cols)})")
        pycols = []
        for c in cols:
            col = tab.column(c)
            if pa.types.is_decimal(col.type):
                # in arrow, not cell by cell: a fact table's decimal
                # columns are most of the load time at SF1 volume
                col = col.cast(pa.float64())
            vals = col.to_pylist()
            if pa.types.is_date(col.type) or pa.types.is_timestamp(col.type):
                vals = [None if v is None else v.isoformat()[:10]
                        for v in vals]
            pycols.append(vals)
        conn.executemany(
            f"INSERT INTO {name} VALUES ({','.join('?' * len(cols))})",
            zip(*pycols))
    conn.commit()
    return conn


_INTERVAL = re.compile(
    r"\(\s*cast\s*\(\s*'(\d{4}-\d{2}-\d{2})'\s+as\s+date\s*\)\s*"
    r"([+-])\s*interval\s+(\d+)\s+days?\s*\)", re.I)
_INTERVAL_COL = re.compile(
    r"\(\s*cast\s*\(\s*([\w.]+)\s+as\s+date\s*\)\s*"
    r"([+-])\s*interval\s+(\d+)\s+days?\s*\)", re.I)
_CAST_DATE = re.compile(
    r"cast\s*\(\s*'(\d{4}-\d{2}-\d{2})'\s+as\s+date\s*\)", re.I)
_DECIMAL_T = re.compile(r"decimal\s*\(\s*\d+\s*,\s*\d+\s*\)", re.I)
# sqlite rejects parenthesized members of compound selects:
# "... UNION ALL (SELECT" / ") UNION ..." — unwrap the parens
_COMPOUND_OPEN = re.compile(
    r"\b(UNION\s+ALL|UNION|INTERSECT|EXCEPT)\s*\(\s*(SELECT)\b", re.I)


_COMPOUND_CLOSE = re.compile(
    r"\)\s*(UNION\s+ALL|UNION|INTERSECT|EXCEPT)\b", re.I)


def _unwrap_compound(sql: str) -> str:
    """Remove parentheses around compound-select members (sqlite rejects
    them): both `UNION (SELECT ...)` and `(SELECT ...) UNION`, matching
    parens by depth and unwrapping only when the paren directly wraps a
    SELECT."""
    while True:
        m = _COMPOUND_OPEN.search(sql)
        if not m:
            break
        open_idx = sql.index("(", m.end(1))
        depth, i = 0, open_idx
        while i < len(sql):
            if sql[i] == "(":
                depth += 1
            elif sql[i] == ")":
                depth -= 1
                if depth == 0:
                    break
            i += 1
        sql = (sql[:open_idx] + " " + sql[open_idx + 1:i] + " " +
               sql[i + 1:])
    # leading members: `) UNION` whose matching `(` directly wraps SELECT
    while True:
        changed = False
        for m in _COMPOUND_CLOSE.finditer(sql):
            close_idx = m.start()
            depth, i = 0, close_idx
            while i >= 0:
                if sql[i] == ")":
                    depth += 1
                elif sql[i] == "(":
                    depth -= 1
                    if depth == 0:
                        break
                i -= 1
            prev = sql[:i].rstrip()[-1:] if i > 0 else ""
            # only a member-wrapper when directly wrapping SELECT and not
            # an expression paren (e.g. `IN (SELECT ...)` before UNION)
            if (i >= 0 and re.match(r"\(\s*SELECT\b", sql[i:], re.I)
                    and (prev == "(" or prev == "")):
                sql = (sql[:i] + " " + sql[i + 1:close_idx] + " " +
                       sql[close_idx + 1:])
                changed = True
                break
        if not changed:
            return sql


# per-query disambiguation patches: sqlite binds unqualified ORDER BY
# names to input tables before output aliases and reports ambiguity where
# the reference dialect resolves to the select-list alias
QUERY_PATCHES = {
    "q58": [("ORDER BY item_id", "ORDER BY ss_items.item_id")],
    "q72": [("w_warehouse_name, d_week_seq",
             "w_warehouse_name, d1.d_week_seq")],
}


def _matching_paren(sql: str, open_idx: int) -> int:
    depth = 0
    for i in range(open_idx, len(sql)):
        if sql[i] == "(":
            depth += 1
        elif sql[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    raise ValueError("unbalanced parens")


def _split_top_commas(text: str) -> list[str]:
    out, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(text[start:i].strip())
            start = i + 1
    out.append(text[start:].strip())
    return out


_ROLLUP = re.compile(r"GROUP\s+BY\s+ROLLUP\s*\(", re.I)
_SELECT_KW = re.compile(r"\bSELECT\b", re.I)
_FROM_KW = re.compile(r"\bFROM\b", re.I)


def _owning_select(sql: str, group_idx: int) -> int:
    """Index of the SELECT that owns the clause at group_idx: nearest
    preceding SELECT with zero net paren balance between them."""
    balance = 0
    i = group_idx - 1
    while i >= 0:
        ch = sql[i]
        if ch == ")":
            balance += 1
        elif ch == "(":
            balance -= 1
        elif balance == 0 and sql[i:i + 6].upper() == "SELECT" and \
                (i == 0 or not (sql[i - 1].isalnum() or sql[i - 1] == "_")):
            return i
        i -= 1
    raise ValueError("no owning SELECT for ROLLUP clause")


def expand_rollup(sql: str) -> str:
    """Rewrite `GROUP BY ROLLUP (c1..cn)` into a UNION ALL of plain
    GROUP BY prefixes — the GROUPING SETS expansion sqlite cannot do
    itself — so rollup queries get real oracle verification instead of
    exec-only pins. Per branch with the first k columns grouped:
    `grouping(c)` becomes the literal 0/1 and each non-grouped rollup
    column becomes NULL (aliased when it was a bare select item). The
    ORDER BY (and anything else after the clause) moves outside a
    wrapping subselect so output-alias scoping is preserved. Window
    functions in the select list stay per-branch, which is exact
    whenever their partition key contains the grouping level (q36/q70/
    q86 partition on grouping()+grouping()); q67's cross-branch window
    already lives OUTSIDE the rollup subquery in the committed text.
    Limitation (unused by q1-q99): a rollup column referenced inside an
    aggregate argument would be nulled too."""
    while True:
        m = _ROLLUP.search(sql)
        if not m:
            return sql
        open_idx = m.end() - 1
        close_idx = _matching_paren(sql, open_idx)
        cols = _split_top_commas(sql[open_idx + 1:close_idx])
        suffix = sql[close_idx + 1:]
        sel_idx = _owning_select(sql, m.start())
        prefix = sql[:sel_idx]
        seg = sql[sel_idx:m.start()]
        # top-level FROM splits select list from relation/where text
        depth = 0
        from_idx = None
        for fm in _FROM_KW.finditer(seg):
            depth = seg[:fm.start()].count("(") - seg[:fm.start()].count(")")
            if depth == 0:
                from_idx = fm.start()
                break
        if from_idx is None:
            raise ValueError("ROLLUP select without top-level FROM")
        select_list = seg[len("SELECT"):from_idx]
        body = seg[from_idx:]
        items = _split_top_commas(select_list)

        def branch(k: int) -> str:
            grouped = set(cols[:k])
            out_items = []
            for item in items:
                t = item
                for c in cols:
                    t = re.sub(r"grouping\s*\(\s*%s\s*\)" % re.escape(c),
                               "0" if c in grouped else "1", t, flags=re.I)
                for c in cols[k:]:
                    if re.fullmatch(re.escape(c), t.strip(), re.I):
                        t = f"NULL AS {c}"
                    else:
                        t = re.sub(r"\b%s\b" % re.escape(c), "NULL", t,
                                   flags=re.I)
                out_items.append(t)
            b = "SELECT " + ", ".join(out_items) + " " + body
            if k:
                b += " GROUP BY " + ", ".join(cols[:k])
            return b

        union = " UNION ALL ".join(branch(k)
                                   for k in range(len(cols), -1, -1))
        sql = prefix + "SELECT * FROM (" + union + ") rollup_u " + suffix


def rewrite_for_sqlite(sql: str, qname: str | None = None) -> str:
    for old, new in QUERY_PATCHES.get(qname or "", []):
        sql = sql.replace(old, new)
    sql = expand_rollup(sql)
    sql = _INTERVAL.sub(lambda m: f"date('{m.group(1)}', "
                        f"'{m.group(2)}{m.group(3)} day')", sql)
    sql = _INTERVAL_COL.sub(lambda m: f"date({m.group(1)}, "
                            f"'{m.group(2)}{m.group(3)} day')", sql)
    sql = _CAST_DATE.sub(lambda m: f"'{m.group(1)}'", sql)
    sql = _DECIMAL_T.sub("REAL", sql)
    # the reference dialect divides integers as doubles; sqlite truncates —
    # float-promote the known int/int division sites (q21/q34/q73)
    sql = re.sub(r"\b(hd_dep_count|inv_after)\s*/",
                 r"\1 * 1.0 /", sql)
    sql = _unwrap_compound(sql)
    return sql


_TRAILING_LIMIT = re.compile(r"\blimit\s+\d+\s*;?\s*$", re.I)


def strip_trailing_limit(sql: str) -> str:
    """Drop the final LIMIT so tie-broken top-N rows can't produce
    spurious mismatches between engines (the full sorted sets compare
    deterministically)."""
    return _TRAILING_LIMIT.sub("", sql.rstrip())


def _norm_cell(v):
    if v is None:
        return None
    if isinstance(v, Decimal):
        v = float(v)
    if isinstance(v, float):
        if math.isnan(v):
            return "nan"
        return round(v, 2)
    if isinstance(v, (datetime.date, datetime.datetime)):
        return v.isoformat()[:10]
    if isinstance(v, bool):
        return int(v)
    return v


def _sort_key(row):
    return tuple((x is None, str(x)) for x in row)


def compare_rows(engine_rows, oracle_rows, rel_tol=1e-4, abs_tol=0.02):
    """Multiset comparison, order-insensitive, with numeric tolerance.
    Returns (ok, message)."""
    a = sorted([tuple(_norm_cell(c) for c in r) for r in engine_rows],
               key=_sort_key)
    b = sorted([tuple(_norm_cell(c) for c in r) for r in oracle_rows],
               key=_sort_key)
    if len(a) != len(b):
        return False, f"row count {len(a)} != oracle {len(b)}"
    for i, (ra, rb) in enumerate(zip(a, b)):
        if len(ra) != len(rb):
            return False, f"col count {len(ra)} != {len(rb)}"
        for ca, cb in zip(ra, rb):
            if ca == cb:
                continue
            if isinstance(ca, (int, float)) and isinstance(cb, (int, float)):
                if math.isclose(float(ca), float(cb), rel_tol=rel_tol,
                                abs_tol=abs_tol):
                    continue
            return False, (f"row {i}: {ra} != oracle {rb}")
    return True, "ok"
