"""household_demographics as dsdgen makes it (tools v2.13.0,
`w_household_demographics.c`; recalled, see store_sales.py): 7 200 rows,
dense keys, the cross product of its four domains in mixed-radix order
of the key, income band fastest: 20 income bands x 6 buy potentials x
10 dependant counts (0..9) x 6 vehicle counts (-1..4). That the radix
starts at the key less one is assumed (the configuration's
`reduced.distributions` says so)."""

import numpy as np

from perfbench.gen import Col

BUY_POTENTIAL = [">10000", "5001-10000", "1001-5000", "501-1000", "0-500",
                 "Unknown"]
DOMAINS = (("hd_income_band_sk", list(range(1, 21))),
           ("hd_buy_potential", BUY_POTENTIAL),
           ("hd_dep_count", list(range(10))),
           ("hd_vehicle_count", list(range(-1, 5))))


def generate(seed, rows, columns, sizes):
    idx = np.arange(rows)
    out = {"hd_demo_sk": Col((idx + 1).astype(np.int32))}
    radix = 1
    for name, dom in DOMAINS:
        code = ((idx // radix) % len(dom)).astype(np.int32)
        radix *= len(dom)
        if isinstance(dom[0], str):
            out[name] = Col(code, pool=list(dom))
        else:
            out[name] = Col(np.asarray(dom, np.int32)[code])
    return out
