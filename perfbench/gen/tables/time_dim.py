"""time_dim as dsdgen makes it (tools v2.13.0, `w_timetbl.c`; recalled,
see store_sales.py): one row a second of the day, 86 400 rows,
`t_time_sk` the second itself from 0 (the domain `store_sales.py` draws
`ss_sold_time_sk` from), hour and minute its digits."""

import numpy as np

from perfbench.gen import Col


def generate(seed, rows, columns, sizes):
    sk = np.arange(rows, dtype=np.int32)
    return {
        "t_time_sk": Col(sk),
        "t_hour": Col(sk // np.int32(3600)),
        "t_minute": Col(sk // np.int32(60) % np.int32(60)),
    }
