"""The bytes a query has to move, whatever implements it: every plane it
reads of every table, once, plus its result, once. A function of the
configuration (row counts) and the reference's READS; nothing of the
program's own accounting (XLA's cost_analysis counts what a program
touches, not what the query needs)."""

from __future__ import annotations

# bytes of one value as the engine holds it on the device: 32-bit ints,
# unscaled 64-bit decimals, 32-bit dictionary codes for strings
PLANE_BYTES = {"int32": 4, "decimal": 8, "string": 4}


def query_bytes(reads: dict, data: dict, result_rows: int,
                result_columns: int) -> int:
    total = 0
    for table, columns in reads.items():
        for c in columns:
            col = data[table][c]
            total += len(col.values) * PLANE_BYTES[col.kind]
    return total + result_rows * result_columns * 8
