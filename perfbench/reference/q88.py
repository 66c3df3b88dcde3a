"""TPC-DS q88: eight half-hour counts, 8:30 to 12:30, of the sales of the
stores named 'ese' to households of three dependant and vehicle counts;
one row, the counts side by side."""

import numpy as np

from perfbench.reference import position, valid

READS = {"store_sales": ["ss_sold_time_sk", "ss_hdemo_sk", "ss_store_sk"],
         "household_demographics": ["hd_demo_sk", "hd_dep_count",
                                    "hd_vehicle_count"],
         "time_dim": ["t_time_sk", "t_hour", "t_minute"],
         "store": ["s_store_sk", "s_store_name"]}
KEY_COLUMNS = ()                       # one row: nothing tells rows apart

# (hour, second half of it): h8_30_to_9 ... h12_to_12_30
HALF_HOURS = ((8, True), (9, False), (9, True), (10, False), (10, True),
              (11, False), (11, True), (12, False))
DEPENDANTS = (4, 2, 0)       # hd_dep_count = d AND hd_vehicle_count <= d + 2
STORE_NAME = "ese"


def run(t, arith):
    ss, hd = t["store_sales"], t["household_demographics"]
    td, s = t["time_dim"], t["store"]
    tpos = position(ss["ss_sold_time_sk"], td["t_time_sk"])
    hpos = position(ss["ss_hdemo_sk"], hd["hd_demo_sk"])
    spos = position(ss["ss_store_sk"], s["s_store_sk"])
    dep, cars = hd["hd_dep_count"].values, hd["hd_vehicle_count"].values
    hd_ok = np.zeros(len(dep), bool)
    for d in DEPENDANTS:
        hd_ok |= (dep == d) & (cars <= d + 2)
    names = s["s_store_name"]
    s_ok = names.values == names.pool.index(STORE_NAME)
    keep = valid(ss["ss_sold_time_sk"]) & valid(ss["ss_hdemo_sk"]) \
        & valid(ss["ss_store_sk"]) & hd_ok[hpos] & s_ok[spos]
    at = tpos[keep]
    hour, minute = td["t_hour"].values[at], td["t_minute"].values[at]
    return [tuple(int(np.count_nonzero(
        (hour == h) & ((minute >= 30) if late else (minute < 30))))
        for h, late in HALF_HOURS)]


def order_key(row):
    """No ORDER BY: one row."""
    return ()
