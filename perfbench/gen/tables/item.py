"""item as dsdgen makes it (tools v2.13.0, `w_item.c`, `scd.c`; recalled,
see store_sales.py): a slowly changing dimension. Rows come in sixes: one
business id with one revision, one with two, one with three, so there are
half as many `i_item_id`s as rows, and `matchSCDSK` gives a sale the
revision in force on its date. `i_item_id` is `mk_bkey`'s 16 letters.
`i_manufact_id` is uniform in 1..1000, `i_manager_id` in 1..100; category,
class and brand are a hierarchy whose numbers make `i_brand_id`
(category x 1 000 000 + class x 1000 + brand), and whose brand name is
syllables of the brand's number. The hierarchy's weights and the changes
between revisions of one id are assumed (the configuration's
`reduced.distributions` says so)."""

import numpy as np

from perfbench.gen import Col, rng_for
from perfbench.gen.pools import BRAND_SYLLABLES, CATEGORIES, CLASSES
from perfbench.gen.tables.date_dim import FIRST_DAY, FIRST_SK

# scd.c: the data's dates, and the cuts between an id's revisions
DATA_FROM = np.datetime64("1998-01-01")
DATA_TO = np.datetime64("2003-12-31")
CLASSES_PER_CATEGORY = 16
BRANDS_PER_CLASS = 17


def _sk(day) -> int:
    return FIRST_SK + int((day - FIRST_DAY).astype(np.int64))


_SPAN = _sk(DATA_TO) - _sk(DATA_FROM)
HALF = _sk(DATA_FROM) + _SPAN // 2
THIRD_1 = _sk(DATA_FROM) + _SPAN // 3
THIRD_2 = THIRD_1 + _SPAN // 3


def id_of_row(row):
    """Business id (from 1) of the 1-based row(s) of the table."""
    r = np.asarray(row, np.int64) - 1
    return 3 * (r // 6) + np.asarray([1, 2, 2, 3, 3, 3])[r % 6]


def id_count(rows: int) -> int:
    return int(id_of_row(rows))


def match_scd_sk(ids, date_sk, rows: int) -> np.ndarray:
    """`matchSCDSK`: the surrogate key of each id's revision on its date."""
    ids = np.asarray(ids, np.int32)
    date_sk = np.asarray(date_sk, np.int32)
    third, revisions = np.divmod(ids, np.int32(3))
    # revisions 1: one, 2: two, 0: three revisions of the id
    sk = third * np.int32(6) + np.asarray([-2, 1, 2], np.int32)[revisions]
    sk += (revisions == 2) & (date_sk > HALF)
    sk += (revisions == 0) & (date_sk > THIRD_1)
    sk += (revisions == 0) & (date_sk > THIRD_2)
    return np.minimum(sk, np.int32(rows))


def bkey(number: int) -> str:
    """`mk_bkey`: 8 letters for the high word, 8 for the low, a letter a
    nibble from 'A', least significant first."""
    low = "".join(chr(ord("A") + ((number >> (4 * i)) & 15))
                  for i in range(8))
    return "AAAAAAAA" + low


def brand_name(category, cls, brand) -> str:
    n = (category * CLASSES_PER_CATEGORY + cls) % len(BRAND_SYLLABLES)
    m = (cls + brand) % len(BRAND_SYLLABLES)
    return f"{BRAND_SYLLABLES[n]}{BRAND_SYLLABLES[m]} #{brand}"


def generate(seed, rows, columns, sizes):
    def rng(c):
        return rng_for(seed, "item", c)

    seq = np.arange(1, rows + 1)
    ids = id_of_row(seq)
    cat = rng("i_category").integers(0, len(CATEGORIES), rows,
                                     dtype=np.int32)
    cls = rng("i_class").integers(0, CLASSES_PER_CATEGORY, rows,
                                  dtype=np.int32)
    brand = rng("i_brand").integers(1, BRANDS_PER_CLASS + 1, rows,
                                    dtype=np.int32)
    brand_id = (cat + 1) * 1_000_000 + (cls + 1) * 1000 + brand
    made_ids, brand_code = np.unique(brand_id, return_inverse=True)
    brand_pool = [brand_name(int(b) // 1_000_000 - 1,
                             int(b) // 1000 % 1000 - 1, int(b) % 1000)
                  for b in made_ids]
    return {
        "i_item_sk": Col(seq.astype(np.int32)),
        "i_item_id": Col((ids - 1).astype(np.int32),
                         pool=[bkey(i) for i in range(1, int(ids[-1]) + 1)]),
        "i_brand_id": Col(brand_id.astype(np.int32)),
        "i_brand": Col(brand_code.astype(np.int32), pool=brand_pool),
        "i_class_id": Col(cls + 1),
        "i_class": Col((cat * CLASSES_PER_CATEGORY + cls).astype(np.int32)
                       % len(CLASSES), pool=list(CLASSES)),
        "i_category_id": Col(cat + 1),
        "i_category": Col(cat, pool=list(CATEGORIES)),
        "i_manufact_id": Col(rng("i_manufact_id").integers(
            1, 1001, rows, dtype=np.int32)),
        "i_manager_id": Col(rng("i_manager_id").integers(
            1, 101, rows, dtype=np.int32)),
    }
