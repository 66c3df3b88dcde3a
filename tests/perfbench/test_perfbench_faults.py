"""The rest of a run with the timed path broken underneath: an answer
altered where it is produced, half the fact table left out, a query that
raises, a degrade counter that moves. Each time `correct` comes out
false, by the number that is there to catch it. (The harness's look for a
chip is skipped by the rehearsal switch; a state returned unchanged and an
exchange between chips left out are faults this system's cells cannot
have.)"""

import argparse
import os
import sys
from decimal import Decimal

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from perfbench import run as pb  # noqa: E402

SESSION = "tpcds_sf10_session.power2"


def rehearse(workload, break_path=None, seconds=2.5, seed=2 ** 31 + 7):
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0, rehearse=True)
    return pb.run(args, break_path)



def _wrap_clients(entry, around):
    make = entry.client

    def client(i):
        c = make(i)
        run = c.run
        c.run = lambda text, annotate: around(run, text, annotate)
        return c

    entry.client = client


def altered_answer(entry, session, tables):
    """One cent on one sum, where the answer is produced."""
    def around(run, text, annotate):
        import pyarrow as pa

        table, info = run(text, annotate)
        if "sum_agg" in table.column_names and table.num_rows:
            col = table.column("sum_agg").to_pylist()
            col[0] += Decimal("0.01")
            table = table.set_column(
                table.column_names.index("sum_agg"), "sum_agg",
                pa.array(col, table.schema.field("sum_agg").type))
        return table, info
    _wrap_clients(entry, around)


def half_the_fact_table(entry, session, tables):
    """Half of the batch left out: the engine answers over the rest."""
    ss = tables["store_sales"]
    session.createDataFrame(ss.slice(0, ss.num_rows // 2)) \
        .createOrReplaceTempView("store_sales")


def a_query_raises(entry, session, tables):
    """q7 raises in the window (its first call is the warm-up's)."""
    seen = []

    def around(run, text, annotate):
        if "avg(ss_quantity)" in text:
            seen.append(text)
            if len(seen) > 1:
                raise RuntimeError("planted")
        return run(text, annotate)
    _wrap_clients(entry, around)


def a_degrade_counter_moves(entry, session, tables):
    def around(run, text, annotate):
        session._metrics.add("whole_query.runtime_degraded")
        return run(text, annotate)
    _wrap_clients(entry, around)


@pytest.mark.parametrize("fault,number", [
    (altered_answer, "decimal_sum_max_abs_units"),
    (half_the_fact_table, "rows_wrong"),
    (a_query_raises, "unanswered"),
    (a_degrade_counter_moves, "hidden_counters_moved")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_broken_timed_path_is_not_correct(fault, number):
    out = rehearse(SESSION, fault)
    assert out["correct"] is False
    c = out["compared"][number]
    assert c["value"] > c["limit"], out["compared"]
    if number != "hidden_counters_moved":
        assert out["failed"] >= 1
