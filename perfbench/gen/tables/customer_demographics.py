"""customer_demographics: the spec's cross product of its domains, in
mixed-radix order of the surrogate key (gender fastest)."""

import numpy as np

from perfbench.gen import Col
from perfbench.gen.pools import CREDIT, EDUCATION, MARITAL

PURCHASE = list(range(500, 10001, 500))          # 20 bands
DOMAINS = (("cd_gender", ["M", "F"]), ("cd_marital_status", MARITAL),
           ("cd_education_status", EDUCATION),
           ("cd_purchase_estimate", PURCHASE), ("cd_credit_rating", CREDIT),
           ("cd_dep_count", list(range(7))),
           ("cd_dep_employed_count", list(range(7))),
           ("cd_dep_college_count", list(range(7))))


def generate(seed, rows, columns, sizes):
    idx = np.arange(rows)
    out = {"cd_demo_sk": Col((idx + 1).astype(np.int32))}
    radix = 1
    for name, dom in DOMAINS:
        code = ((idx // radix) % len(dom)).astype(np.int32)
        radix *= len(dom)
        if isinstance(dom[0], str):
            out[name] = Col(code, pool=list(dom))
        else:
            out[name] = Col(np.asarray(dom, np.int32)[code])
    return out
