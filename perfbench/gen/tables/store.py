"""store as dsdgen makes it (tools v2.13.0, `w_store.c`; recalled, see
store_sales.py): dense keys, 102 rows at SF10. `s_store_name` is
`mk_word` over the `syllables` distribution with room for five
characters, so the name is the one syllable of the key's last digit and
there are ten names; `s_company_name` is "Unknown" on every row. That the
name follows the row's key and not its business id's first revision, and
that no attribute is null (0.5 % of the rows in dsdgen), is assumed (the
configuration's `reduced.distributions` says so)."""

import numpy as np

from perfbench.gen import Col

# tpcds.dst `syllables`, by the digit that picks each
SYLLABLES = ["bar", "ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "n st"]


def generate(seed, rows, columns, sizes):
    sk = np.arange(1, rows + 1, dtype=np.int32)
    return {
        "s_store_sk": Col(sk),
        "s_store_name": Col(sk % np.int32(len(SYLLABLES)),
                            pool=list(SYLLABLES)),
        "s_company_name": Col(np.zeros(rows, np.int32), pool=["Unknown"]),
    }
