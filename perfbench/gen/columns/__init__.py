"""Columns a table's own module (`gen/tables/<table>.py`) does not make.

`columns/<table>/<name>.py` declares `MAKES`, the columns it can make,
and `generate(seeds, rows, columns, sizes, made)`, which returns
{column: Col} for the `columns` asked (all of them in `MAKES`); `made`
holds the table's columns so far, so that `d_date` can be made from
`d_date_sk`. Random draws come from `rng_for(seeds, table, column)`, as
in the table modules, so a column never depends on which others are
kept. `gen.generate` finds the modules by the table's name and asks them
in name order; a column that two of them list, or none, is an error.
"""
