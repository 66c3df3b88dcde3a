"""An answer becomes Arrow from its host planes: every column of a flat
type is built from its data, validity and selection as they lie, with no
Python object a value (`ColumnarBatch.to_arrow`), and the table is the
one the per-value assembly built, schema, types, values and nulls."""

import decimal
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu.columnar.batch import Column, ColumnarBatch, StringDict
from spark_tpu.types import (ArrayType, BooleanType, ByteType, DateType,
                             DecimalType, DoubleType, FloatType, IntegerType,
                             LongType, MapType, NullType, ShortType,
                             StringType, StructField, StructType,
                             TimestampType, to_arrow_type)


def reference_to_arrow(batch):
    """The per-value assembly `to_arrow` did before it read the planes:
    a Python Decimal a decimal, an object array a nullable column, a
    Python string a row."""
    sel = batch.selection_indices()
    arrays = []
    for f, c in zip(batch.schema.fields, batch.columns):
        vals = c.to_numpy(sel)
        at = to_arrow_type(f.dataType)
        if isinstance(f.dataType, NullType):
            arrays.append(pa.nulls(len(sel)))
        elif isinstance(f.dataType, DecimalType):
            raw = np.asarray(c.data)[sel]
            valid = (np.asarray(c.validity)[sel]
                     if c.validity is not None else None)
            scale = f.dataType.scale
            py = [None if (valid is not None and not valid[i])
                  else decimal.Decimal(int(raw[i])).scaleb(-scale)
                  for i in range(len(raw))]
            arrays.append(pa.array(py, type=at))
        elif isinstance(f.dataType, MapType):
            arrays.append(pa.array(
                [None if v is None else list(v.items())
                 for v in vals], type=at))
        elif isinstance(f.dataType, (StringType, ArrayType, StructType)):
            arrays.append(pa.array(list(vals), type=at))
        else:
            mask = None
            if c.validity is not None:
                mask = ~np.asarray(c.validity)[sel]
            if vals.dtype == object and (
                    str(at) == "date32[day]"
                    or str(at).startswith("timestamp")):
                vals = np.asarray([0 if v is None else v for v in vals])
            if f.dataType.device_dtype == np.dtype(np.int32) \
                    and str(at) == "date32[day]":
                arrays.append(pa.array(np.asarray(vals, np.int32),
                                       type=at, mask=mask))
            elif str(at).startswith("timestamp"):
                arrays.append(pa.array(np.asarray(vals, np.int64),
                                       type=at, mask=mask))
            else:
                vals2 = np.asarray([v if v is not None else 0
                                    for v in vals]) \
                    if vals.dtype == object else vals
                arrays.append(pa.array(vals2, type=at, mask=mask))
    return pa.table(arrays, names=batch.schema.names)


N = 300
WORDS = ["alpha", "", "βeta", "gamma delta", "z" * 40]


def _values(dt, rng):
    """N values of the type's device representation: its extremes, a
    negative, a zero, and random fill."""
    if isinstance(dt, BooleanType):
        return rng.integers(0, 2, N).astype(bool)
    if isinstance(dt, StringType):
        return rng.integers(0, len(WORDS), N).astype(np.int32)
    if isinstance(dt, DecimalType):
        top = 10 ** dt.precision - 1
        v = rng.integers(-top, top, N, endpoint=True, dtype=np.int64)
        v[:4] = [top, -top, 0, -1]
        return v
    if isinstance(dt, (FloatType, DoubleType)):
        v = rng.standard_normal(N).astype(dt.device_dtype) * 1e6
        v[:3] = [-0.0, np.finfo(dt.device_dtype).max,
                 np.finfo(dt.device_dtype).min]
        return v
    info = np.iinfo(dt.device_dtype)
    lo, hi = ((-719162, 2932896) if isinstance(dt, DateType)
              else (info.min, info.max))
    v = rng.integers(lo, hi, N, endpoint=True, dtype=dt.device_dtype)
    v[:3] = [lo, hi, -1]
    return v


def _batch(dt, case, rng, *, words=WORDS, data=None):
    """One column of `dt` in the shape `case` names: no validity, about
    5 % NULL, a row mask that drops rows (NULLs too), or no row."""
    n = 0 if case == "empty" else N
    data = _values(dt, rng)[:n] if data is None else data
    valid = None
    if case in ("nulls", "selection"):
        valid = rng.random(n) >= 0.05
        valid[:2] = True
    schema = StructType((StructField("c", dt),))
    d = StringDict(words) if isinstance(dt, StringType) else None
    b = ColumnarBatch.from_numpy(schema, [data], [d], [valid],
                                 capacity=512)
    if case == "selection":
        import jax.numpy as jnp

        mask = np.zeros(512, bool)
        mask[:n] = rng.random(n) >= 0.3
        b = b.with_columns(schema, b.columns, row_mask=jnp.asarray(mask))
    return b


FLAT = [ByteType(), ShortType(), IntegerType(), LongType(), FloatType(),
        DoubleType(), BooleanType(), DateType(), TimestampType(),
        StringType(), DecimalType(7, 2), DecimalType(18, 6),
        DecimalType(18, 0), DecimalType(18, 18)]
CASES = ["dense", "nulls", "selection", "empty"]


def _same(batch):
    ref = reference_to_arrow(batch)
    out = batch.to_arrow()
    out.validate(full=True)
    assert out.schema == ref.schema
    assert out.equals(ref, check_metadata=True)
    assert out.column(0).null_count == ref.column(0).null_count
    return out


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dt", FLAT, ids=lambda dt: dt.simple_string())
def test_a_flat_column_is_the_per_value_table(dt, case):
    rng = np.random.default_rng(42)
    out = _same(_batch(dt, case, rng))
    if case == "dense":
        assert out.num_rows == N


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("words", [WORDS, []], ids=["dict", "empty_dict"])
def test_a_code_past_the_dictionary_reads_its_sentinel(words, case):
    """Codes below 0 or at and past the dictionary's end are clipped as
    `Column.to_numpy` clips them: past the end is the empty string, and
    every code is, where the dictionary is empty."""
    rng = np.random.default_rng(7)
    n = 0 if case == "empty" else N
    codes = rng.integers(0, max(len(words), 1), n).astype(np.int32)
    if n:
        codes[:4] = [-1, len(words), len(words) + 3, 0]
    out = _same(_batch(StringType(), case, rng, words=words, data=codes))
    if case == "dense":
        assert out.column(0).type == pa.string()
        assert out.column(0)[1].as_py() == ""


@pytest.mark.parametrize("where", ["live", "null"])
@pytest.mark.parametrize("dt", [DecimalType(7, 2), DecimalType(18, 6)],
                         ids=lambda dt: dt.simple_string())
def test_a_decimal_past_its_precision_still_raises(dt, where):
    rng = np.random.default_rng(3)
    data = _values(dt, rng)
    data[5] = 10 ** dt.precision
    data[6] = -(10 ** dt.precision)
    b = _batch(dt, "nulls", rng, data=data)
    valid = np.asarray(b.columns[0].validity).copy()
    valid[5:7] = where == "live"
    import jax.numpy as jnp

    b.columns[0] = b.columns[0].with_data(b.columns[0].data,
                                          jnp.asarray(valid))
    if where == "null":        # a slot that is NULL is not read
        _same(b)
        return
    with pytest.raises(pa.ArrowInvalid):
        reference_to_arrow(b)
    with pytest.raises(pa.ArrowInvalid):
        b.to_arrow()


@pytest.mark.parametrize("nested", [False, True], ids=["flat", "array"])
def test_by_value_counts_the_nested_columns(session, nested):
    """An array column still goes value by value, and equals the
    per-value table; `collect.arrow` and the statement's counters say how
    many of the answer's columns did."""
    import jax.numpy as jnp

    from spark_tpu.obs.tracing import recorded_spans

    rng = np.random.default_rng(11)
    b = _batch(DecimalType(18, 6), "nulls", rng)
    schema, cols = b.schema, list(b.columns)
    if nested:
        codes = _batch(StringType(), "nulls", rng).columns[0]
        cols.append(Column(ArrayType(IntegerType()),
                           jnp.asarray(np.asarray(codes.data) % 3),
                           codes.validity, StringDict([[1, 2], [], [3]])))
        schema = schema.add("a", ArrayType(IntegerType()))
    b = b.with_columns(schema, cols)
    assert b.to_arrow().equals(reference_to_arrow(b), check_metadata=True)

    t0 = time.perf_counter()
    df = session.sql("select k, array(n, 1) a from ca_sales" if nested
                     else "select k, n from ca_sales")
    out = df.toArrow()
    t1 = time.perf_counter()
    assert pa.types.is_list(out.schema.types[1]) == nested
    counters = df.query_execution._last_ctx.metrics.local_counters()
    assert counters.get("collect.columns_by_value", 0) == int(nested)
    arrow = [s["args"] for s in recorded_spans(t0, t1)
             if s["name"] == "collect.arrow"]
    assert arrow == [{"rows": out.num_rows, "by_value": int(nested)}]


def test_a_flat_column_of_object_data_is_refused():
    """A flat column's data is a numeric plane; one that is not is a
    fault, not a column to build a value at a time."""
    b = _batch(IntegerType(), "dense", np.random.default_rng(5))
    b.columns[0] = Column(IntegerType(),
                          np.asarray(list(range(512)), dtype=object))
    with pytest.raises(TypeError, match="numeric plane"):
        b.to_arrow()


@pytest.fixture(scope="module")
def session():
    from spark_tpu import TpuSession

    s = TpuSession("collect-arrow", {
        "spark.sql.shuffle.partitions": 4,
        "spark.tpu.cache.result.enabled": "false",
    })
    rng = np.random.default_rng(19)
    n = 3000
    price = rng.integers(-10 ** 6, 10 ** 6, n)
    s.createDataFrame(pa.table({
        "k": pa.array(rng.choice(["north", "south", "east", "west", "λ"],
                                 n)),
        "n": pa.array(rng.integers(-50, 50, n),
                      mask=rng.random(n) < 0.05, type=pa.int32()),
        "p": pa.array([decimal.Decimal(int(v)).scaleb(-2) for v in price],
                      type=pa.decimal128(9, 2),
                      mask=rng.random(n) < 0.05),
    })).createOrReplaceTempView("ca_sales")
    yield s
    s.stop()


@pytest.mark.parametrize("text", [
    "select k, sum(p) s, avg(p) a, max(n) m, count(n) c from ca_sales "
    "group by k order by k",
    "select k, n, p from ca_sales where n < 0 or n is null order by p, n",
], ids=["aggregate", "rows"])
def test_a_query_collects_the_per_value_table(session, text):
    from spark_tpu.obs.tracing import recorded_spans

    t0 = time.perf_counter()
    df = session.sql(text)
    out = df.toArrow()
    t1 = time.perf_counter()
    qe = session.sql(text).query_execution
    batches = [b for p in qe.execute(finalize_rows=False) for b in p]
    ref = pa.concat_tables([reference_to_arrow(b) for b in batches])
    assert out.num_rows > 0
    assert out.equals(ref, check_metadata=True)
    types = out.schema.types
    assert types[0] == pa.string() and pa.int32() in types + [pa.int64()]
    assert any(pa.types.is_decimal(t) for t in types)
    counters = df.query_execution._last_ctx.metrics.local_counters()
    assert counters.get("collect.columns_by_value", 0) == 0
    arrow = [s["args"] for s in recorded_spans(t0, t1)
             if s["name"] == "collect.arrow"]
    assert arrow == [{"rows": out.num_rows, "by_value": 0}]
