"""customer as dsdgen makes it (tools v2.13.0, `w_customer.c`, `names.dst`,
`nulls.c`; recalled, not at hand): dense keys from 1, a first and a last
name drawn from weighted pools (`pick_distribution` over the `first_names`
and `last_names` distributions), and `nullSet`'s nulls. Only the three
columns TPC-DS q38 and q87 read are made.

The pools here are built, not dsdgen's lists: first names and last names
of two or three syllables, weighted by a Zipf law of rank as census
frequencies fall (a few names very common, a long tail). The names are
drawn from the run's seed (who is called what is the answer, not the
work); the null bitmap is the structure's.
"""

import numpy as np

from perfbench.gen import Col, rng_for

COLUMNS = ("c_customer_sk", "c_first_name", "c_last_name")
NOT_NULL = ("c_customer_sk",)
NULL_ROWS_IN_10000 = 700          # tdefs.h customer nNullPct, as assumed
SYLLABLES = ("al", "an", "ar", "be", "bo", "ca", "da", "de", "el", "en",
             "er", "fa", "ga", "ha", "in", "ja", "ka", "la", "le", "li",
             "lo", "ma", "me", "mi", "na", "ne", "no", "ra", "re", "ri",
             "ro", "sa", "se", "ta", "te", "to", "va", "wi", "ya", "za")
FIRST_NAMES = 1600                # two syllables: 40 x 40
LAST_NAMES = 4800                 # three syllables, the first 4 800
ZIPF_S = 0.7                      # weight of rank k (from 1): k ** -s


def _pool(parts: int, size: int) -> list:
    """`size` names of `parts` syllables, capitalised, in a fixed order
    (the order is the rank of the weights)."""
    n = len(SYLLABLES)
    return ["".join(SYLLABLES[i // n ** p % n]
                    for p in range(parts - 1, -1, -1)).capitalize()
            for i in range(size)]


def _weights(size: int) -> np.ndarray:
    w = np.arange(1, size + 1, dtype=np.float64) ** -ZIPF_S
    return np.cumsum(w) / w.sum()


def _names(seed, column, rows, size, parts):
    u = rng_for(seed, "customer", column).random(rows)
    code = np.minimum(np.searchsorted(_weights(size), u, side="right"),
                      size - 1).astype(np.int32)
    return code, _pool(parts, size)


def generate(seed, rows, columns, sizes):
    rng = rng_for(seed, "customer", "_nulls")
    hit = rng.integers(0, 10000, rows, dtype=np.int32) < NULL_ROWS_IN_10000
    bits = rng.integers(1, 2 ** 31 - 1, rows, dtype=np.int32, endpoint=True)
    bits[~hit] = 0

    def valid(column):
        if column in NOT_NULL:
            return None
        return (bits & np.int32(1 << COLUMNS.index(column))) == 0

    def name(column, size, parts):
        code, pool = _names(seed, column, rows, size, parts)
        return Col(code, valid(column), pool=pool)

    makers = {
        "c_customer_sk": lambda: Col(np.arange(1, rows + 1, dtype=np.int32)),
        "c_first_name": lambda: name("c_first_name", FIRST_NAMES, 2),
        "c_last_name": lambda: name("c_last_name", LAST_NAMES, 3),
    }
    return {c: makers[c]() for c in columns if c in makers}
