"""date_dim as the spec lays it out: one row a day from 1900-01-02,
d_date_sk the Julian day number (2415022 for the first)."""

import numpy as np

from perfbench.gen import Col

FIRST_SK = 2415022
FIRST_DAY = np.datetime64("1900-01-02")


def generate(seed, rows, columns, sizes):
    days = FIRST_DAY + np.arange(rows)
    months = days.astype("datetime64[M]")
    return {
        "d_date_sk": Col((FIRST_SK + np.arange(rows)).astype(np.int32)),
        "d_year": Col((days.astype("datetime64[Y]").astype(np.int64)
                       + 1970).astype(np.int32)),
        "d_moy": Col((months.astype(np.int64) % 12 + 1).astype(np.int32)),
        "d_dom": Col(((days - months).astype(np.int64) + 1)
                     .astype(np.int32)),
    }
