"""promotion as dsdgen makes it (tools v2.13.0, `w_promotion.c`;
recalled, see store_sales.py): dense keys, and channel flags that are all
'N' but `p_channel_dmail`: the generator draws the flags as bits of one
number and shifts it the wrong way after the first, so every later flag
reads 0. q7's `p_channel_email = 'N' OR p_channel_event = 'N'` therefore
keeps every sale that has a promotion."""

import numpy as np

from perfbench.gen import Col, rng_for

CHANNELS = ("p_channel_dmail", "p_channel_email", "p_channel_catalog",
            "p_channel_tv", "p_channel_radio", "p_channel_press",
            "p_channel_event", "p_channel_demo")


def generate(seed, rows, columns, sizes):
    out = {"p_promo_sk": Col(np.arange(1, rows + 1, dtype=np.int32))}
    flags = rng_for(seed, "promotion", "p_channel_dmail").integers(
        0, 2, rows, dtype=np.int32)
    for name in CHANNELS:
        out[name] = Col(flags if name == "p_channel_dmail"
                        else np.zeros(rows, np.int32), pool=["N", "Y"])
    return out
