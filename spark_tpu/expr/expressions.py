"""Expression tree.

Role of the reference's ~700 expression classes (sqlcat/expressions/
Expression.scala, Cast.scala, aggregate/interfaces.scala, conditionalExpressions,
stringExpressions, datetimeExpressions...). Each expression here implements a
single `eval(ctx)` that serves both the host metadata pass and the jit trace
pass (see expr/eval.py) — the TPU analog of the reference's dual
interpreted-eval/doGenCode contract.

SQL three-valued logic is carried by optional validity masks; string
computations ride dictionary lookup tables registered through the aux channel.
"""

from __future__ import annotations

import datetime
import math
import re
from typing import Any, Optional, Sequence

import numpy as np

from ..errors import AnalysisException, TypeCheckError, UnsupportedOperationError
from ..plan.tree import TreeNode, next_id
from ..columnar.batch import StringDict, _hash_str
from ..types import (
    ArrayType, BooleanType, ByteType, DataType, DateType, DecimalType,
    DoubleType, FloatType, FractionalType, IntegerType, IntegralType, LongType,
    MapType, NullType, NumericType, ShortType, StringType, StructField,
    StructType, TimestampType,
    boolean, common_type, date, dict_encoded, float32, float64, infer_type,
    int8, int16, int32, int64, null_type, string, timestamp,
)


def _dict_empty(dt):
    """Placeholder dictionary entry for an absent nested value."""
    if isinstance(dt, ArrayType):
        return []
    if isinstance(dt, (MapType, StructType)):
        return {}
    return ""


def _to_device_value(dt, v):
    """Convert a host python value (as arrow to_pylist yields it) to the
    type's device representation — nested dictionaries hold date/
    timestamp/Decimal objects that numeric LUTs must re-encode."""
    if isinstance(v, bool) or v is None:
        return v
    if isinstance(dt, DateType) and isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if isinstance(dt, TimestampType) and isinstance(v, datetime.datetime):
        epoch = datetime.datetime(1970, 1, 1, tzinfo=v.tzinfo)
        return int((v - epoch).total_seconds() * 1_000_000)
    if isinstance(dt, DecimalType):
        import decimal as _d

        if isinstance(v, _d.Decimal):
            return int(v.scaleb(dt.scale).to_integral_value())
    return v
from .eval import EvalCtx, Val

__all__ = [
    "Expression", "Literal", "AttributeReference", "UnresolvedAttribute",
    "UnresolvedStar", "UnresolvedFunction", "Alias", "SortOrder",
    "Add", "Subtract", "Multiply", "Divide", "Remainder", "UnaryMinus",
    "Abs", "Pow", "Sqrt", "Exp", "Log", "Log10", "Floor", "Ceil", "Round",
    "EqualTo", "EqualNullSafe", "NotEqualTo", "LessThan", "LessThanOrEqual",
    "GreaterThan", "GreaterThanOrEqual", "And", "Or", "Not",
    "IsNull", "IsNotNull", "IsNaN", "In", "Like", "RLike", "StartsWith",
    "EndsWith", "Contains", "CaseWhen", "If", "Coalesce", "Cast", "NullIf",
    "Greatest", "Least",
    "Upper", "Lower", "Substring", "Length", "Trim", "LTrim", "RTrim",
    "Concat", "StringReplace", "Lpad", "Rpad",
    "Year", "Month", "DayOfMonth", "Quarter", "DayOfWeek", "DayOfYear",
    "WeekOfYear", "DateAdd", "DateSub", "DateDiff", "TruncDate", "MakeDate",
    "AggregateFunction", "Sum", "Count", "Min", "Max", "Average", "First",
    "AnyValue", "StddevSamp", "StddevPop", "VarianceSamp", "VariancePop",
    "CollectSet",
]


def _jnp():
    import jax.numpy as jnp

    return jnp


# ---------------------------------------------------------------------------
# Base
# ---------------------------------------------------------------------------

class Expression(TreeNode):
    @property
    def dtype(self) -> DataType:
        raise NotImplementedError(type(self).__name__)

    @property
    def nullable(self) -> bool:
        return True

    @property
    def resolved(self) -> bool:
        return all(c.resolved for c in self.children)

    @property
    def foldable(self) -> bool:
        return all(getattr(c, "foldable", False) for c in self.children) \
            and bool(self.children)

    def references(self) -> set[int]:
        out: set[int] = set()
        for n in self.iter_nodes():
            if isinstance(n, AttributeReference):
                out.add(n.expr_id)
        return out

    def eval(self, ctx: EvalCtx) -> Val:
        raise NotImplementedError(type(self).__name__)

    # helpers for DSL composition (api/column wraps these)
    def sql_name(self) -> str:
        return type(self).__name__.lower()


# ---------------------------------------------------------------------------
# Leaves & named expressions
# ---------------------------------------------------------------------------

class Literal(Expression):
    child_fields = ()

    def __init__(self, value: Any, dtype: DataType | None = None):
        if isinstance(value, float) and math.isnan(value):
            pass
        self.value = value
        self._dtype = dtype if dtype is not None else infer_type(value)
        if isinstance(value, datetime.datetime):
            epoch = datetime.datetime(1970, 1, 1, tzinfo=value.tzinfo)
            self.value = int((value - epoch).total_seconds() * 1_000_000)
        elif isinstance(value, datetime.date):
            self.value = (value - datetime.date(1970, 1, 1)).days
        else:
            import decimal as _d

            if isinstance(value, _d.Decimal):
                dt = self._dtype
                assert isinstance(dt, DecimalType)
                self.value = int(value.scaleb(dt.scale).to_integral_value())

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self.value is None

    @property
    def resolved(self) -> bool:
        return True

    @property
    def foldable(self) -> bool:
        return True

    def _data_args(self) -> tuple:
        return (("value", self.value), ("dtype", str(self._dtype)))

    def eval(self, ctx: EvalCtx) -> Val:
        jnp = _jnp()
        dt = self._dtype
        if self.value is None:
            if not ctx.is_trace:
                return Val(dt, None, True,
                           StringDict([_dict_empty(dt)])
                           if dict_encoded(dt) else None)
            z = jnp.zeros((), dtype=dt.device_dtype)
            return Val(dt, z, jnp.zeros((), dtype=bool), None)
        if dict_encoded(dt):
            # string/array/map/struct literal: a one-entry dictionary,
            # all rows code 0
            if not ctx.is_trace:
                return Val(dt, None, None, StringDict([self.value]))
            return Val(dt, jnp.zeros((), dtype=jnp.int32), None, None)
        if not ctx.is_trace:
            return Val(dt, None, None, None)
        v = self.value
        return Val(dt, jnp.asarray(v, dtype=dt.device_dtype), None, None)

    def simple_string(self) -> str:
        return f"lit({self.value!r})"


class AttributeReference(Expression):
    """A resolved column (reference: sqlcat/expressions/namedExpressions.scala
    AttributeReference with exprId for self-join disambiguation)."""

    child_fields = ()

    def __init__(self, name: str, dtype: DataType, nullable: bool = True,
                 expr_id: int | None = None, qualifier: tuple[str, ...] = ()):
        self.name = name
        self._dtype = dtype
        self._nullable = nullable
        self.expr_id = next_id() if expr_id is None else expr_id
        self.qualifier = tuple(qualifier)

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nullable(self) -> bool:
        return self._nullable

    @property
    def resolved(self) -> bool:
        return True

    @property
    def foldable(self) -> bool:
        return False

    def with_nullability(self, nullable: bool) -> "AttributeReference":
        return AttributeReference(self.name, self._dtype, nullable,
                                  self.expr_id, self.qualifier)

    def renamed(self, name: str) -> "AttributeReference":
        return AttributeReference(name, self._dtype, self._nullable,
                                  self.expr_id, self.qualifier)

    def new_instance(self) -> "AttributeReference":
        return AttributeReference(self.name, self._dtype, self._nullable,
                                  None, self.qualifier)

    def eval(self, ctx: EvalCtx) -> Val:
        return ctx.attribute(self.expr_id)

    def _data_args(self) -> tuple:
        return (("expr_id", self.expr_id),)

    def simple_string(self) -> str:
        return f"{self.name}#{self.expr_id}"


class UnresolvedAttribute(Expression):
    child_fields = ()

    def __init__(self, name_parts: Sequence[str]):
        self.name_parts = tuple(name_parts)

    @property
    def name(self) -> str:
        return ".".join(self.name_parts)

    @property
    def resolved(self) -> bool:
        return False

    @property
    def foldable(self) -> bool:
        return False

    def simple_string(self) -> str:
        return f"'{self.name}"


class UnresolvedStar(Expression):
    child_fields = ()

    def __init__(self, target: Optional[str] = None):
        self.target = target

    @property
    def resolved(self) -> bool:
        return False


class UnresolvedFunction(Expression):
    child_fields = ("args",)

    def __init__(self, name: str, args: Sequence[Expression],
                 distinct: bool = False):
        self.fname = name
        self.args = list(args)
        self.distinct = distinct

    @property
    def resolved(self) -> bool:
        return False


class Alias(Expression):
    child_fields = ("child",)

    def __init__(self, child: Expression, name: str, expr_id: int | None = None):
        self.child = child
        self.name = name
        self.expr_id = next_id() if expr_id is None else expr_id

    @property
    def dtype(self) -> DataType:
        return self.child.dtype

    @property
    def nullable(self) -> bool:
        return self.child.nullable

    def to_attribute(self) -> AttributeReference:
        dt = self.child.dtype if self.child.resolved else null_type
        return AttributeReference(self.name, dt, self.child.nullable,
                                  self.expr_id)

    def eval(self, ctx: EvalCtx) -> Val:
        return ctx.eval(self.child)

    def _data_args(self) -> tuple:
        return (("name", self.name), ("expr_id", self.expr_id))

    def simple_string(self) -> str:
        return f"{self.child.simple_string()} AS {self.name}#{self.expr_id}"


class SortOrder(Expression):
    """Sort direction wrapper (reference: sqlcat/expressions/SortOrder.scala)."""

    child_fields = ("child",)

    def __init__(self, child: Expression, ascending: bool = True,
                 nulls_first: bool | None = None):
        self.child = child
        self.ascending = ascending
        self.nulls_first = nulls_first

    @property
    def dtype(self) -> DataType:
        return self.child.dtype

    def eval(self, ctx: EvalCtx) -> Val:
        return ctx.eval(self.child)


# ---------------------------------------------------------------------------
# Cast
# ---------------------------------------------------------------------------

_TRUE_STRINGS = {"t", "true", "y", "yes", "1"}
_FALSE_STRINGS = {"f", "false", "n", "no", "0"}


def _parse_date(s: str) -> int | None:
    s = s.strip()
    try:
        return (datetime.date.fromisoformat(s[:10]) - datetime.date(1970, 1, 1)).days
    except ValueError:
        return None


def _parse_ts(s: str) -> int | None:
    s = s.strip().replace("T", " ")
    for fmt in ("%Y-%m-%d %H:%M:%S.%f", "%Y-%m-%d %H:%M:%S", "%Y-%m-%d"):
        try:
            d = datetime.datetime.strptime(s, fmt)
            return int((d - datetime.datetime(1970, 1, 1)).total_seconds() * 1e6)
        except ValueError:
            continue
    return None


class Cast(Expression):
    child_fields = ("child",)

    def __init__(self, child: Expression, to: DataType, ansi: bool = False):
        self.child = child
        self.to = to
        self.ansi = ansi

    @property
    def dtype(self) -> DataType:
        return self.to

    @property
    def nullable(self) -> bool:
        frm = self.child.dtype if self.child.resolved else null_type
        if isinstance(frm, StringType) and not isinstance(self.to, StringType):
            return True  # parse failures produce null
        return self.child.nullable

    def eval(self, ctx: EvalCtx) -> Val:
        c = ctx.eval(self.child)
        return cast_val(ctx, c, self.to)

    def simple_string(self) -> str:
        return f"cast({self.child.simple_string()} as {self.to.simple_string()})"


def cast_val(ctx: EvalCtx, c: Val, to: DataType) -> Val:
    jnp = _jnp()
    frm = c.dtype
    if type(frm) is type(to) and frm == to:
        return c
    if isinstance(frm, NullType):
        if not ctx.is_trace:
            return Val(to, None, True,
                       StringDict([""]) if isinstance(to, StringType) else None)
        z = jnp.zeros((), dtype=to.device_dtype)
        return Val(to, z, jnp.zeros((), dtype=bool), None)

    # ---- string source: parse the dictionary host-side --------------------
    if isinstance(frm, StringType) and not isinstance(to, StringType):
        def parse_arrays():
            vals = c.sdict.values if c.sdict else [""]
            out = np.zeros(max(len(vals), 1), dtype=to.device_dtype)
            ok = np.zeros(max(len(vals), 1), dtype=bool)
            for i, s in enumerate(vals):
                p = _parse_str(s, to)
                if p is not None:
                    out[i] = p
                    ok[i] = True
            return out, ok

        if not ctx.is_trace:
            data_lut = ctx.aux(lambda: parse_arrays()[0])
            ok_lut = ctx.aux(lambda: parse_arrays()[1])
            return Val(to, None, True, None)
        data_lut = ctx.aux(None)
        ok_lut = ctx.aux(None)
        codes = jnp.clip(c.data, 0, data_lut.shape[0] - 1)
        data = jnp.take(data_lut, codes)
        ok = jnp.take(ok_lut, codes)
        v = ok if c.validity is None else (ok & c.validity)
        return Val(to, data, v, None)

    # ---- to string: only foldable/dictionary sources supported ------------
    if isinstance(to, StringType):
        raise UnsupportedOperationError(
            f"cast({frm.simple_string()} as string) requires host "
            "materialization (not yet supported on device)")

    if not ctx.is_trace:
        return Val(to, None, c.validity, None)

    data = c.data
    v = c.validity
    # decimal handling
    if isinstance(frm, DecimalType) and isinstance(to, DecimalType):
        delta = to.scale - frm.scale
        if delta >= 0:
            data = data * (10 ** delta)
        else:
            f = 10 ** (-delta)
            half = f // 2
            data = jnp.where(data >= 0, (data + half) // f, -((-data + half) // f))
        return Val(to, data, v, None)
    if isinstance(frm, DecimalType):
        scaled = data.astype(jnp.float64) / (10.0 ** frm.scale)
        return cast_val(ctx, Val(float64, scaled, v, None), to)
    if isinstance(to, DecimalType):
        if jnp.issubdtype(data.dtype, jnp.integer) or data.dtype == jnp.bool_:
            d = data.astype(jnp.int64) * (10 ** to.scale)
        else:
            d = jnp.rint(data.astype(jnp.float64) * (10.0 ** to.scale)).astype(jnp.int64)
        return Val(to, d, v, None)
    # date/timestamp
    if isinstance(frm, DateType) and isinstance(to, TimestampType):
        return Val(to, data.astype(jnp.int64) * 86_400_000_000, v, None)
    if isinstance(frm, TimestampType) and isinstance(to, DateType):
        return Val(to, jnp.floor_divide(data, 86_400_000_000).astype(jnp.int32), v, None)
    if isinstance(frm, (DateType, TimestampType)) and isinstance(to, NumericType):
        return Val(to, data.astype(to.device_dtype), v, None)
    # bool
    if isinstance(to, BooleanType):
        return Val(to, data != 0, v, None)
    if isinstance(frm, BooleanType):
        return Val(to, data.astype(to.device_dtype), v, None)
    # float -> int truncates toward zero
    if isinstance(frm, FractionalType) and isinstance(to, IntegralType):
        t = jnp.nan_to_num(jnp.trunc(data), nan=0.0, posinf=0.0, neginf=0.0)
        return Val(to, t.astype(to.device_dtype), v, None)
    return Val(to, data.astype(to.device_dtype), v, None)


def _parse_str(s: str, to: DataType):
    s = s.strip()
    try:
        if isinstance(to, BooleanType):
            ls = s.lower()
            if ls in _TRUE_STRINGS:
                return True
            if ls in _FALSE_STRINGS:
                return False
            return None
        if isinstance(to, IntegralType):
            return int(float(s)) if ("." in s or "e" in s.lower()) else int(s)
        if isinstance(to, DecimalType):
            import decimal as _d

            return int(_d.Decimal(s).scaleb(to.scale).to_integral_value(
                rounding=_d.ROUND_HALF_UP))
        if isinstance(to, FractionalType):
            return float(s)
        if isinstance(to, DateType):
            return _parse_date(s)
        if isinstance(to, TimestampType):
            return _parse_ts(s)
    except (ValueError, ArithmeticError):
        return None
    return None


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

class BinaryExpression(Expression):
    child_fields = ("left", "right")
    symbol = "?"

    def __init__(self, left: Expression, right: Expression):
        self.left = left
        self.right = right

    def simple_string(self) -> str:
        return (f"({self.left.simple_string()} {self.symbol} "
                f"{self.right.simple_string()})")


class BinaryArithmetic(BinaryExpression):
    @property
    def dtype(self) -> DataType:
        lt, rt = self.left.dtype, self.right.dtype
        ct = common_type(lt, rt)
        if ct is None or not isinstance(ct, (NumericType,)):
            if isinstance(lt, (DateType,)) or isinstance(rt, (DateType,)):
                return self._date_result(lt, rt)
            raise TypeCheckError(
                f"{type(self).__name__} needs numeric operands, got "
                f"{lt.simple_string()}, {rt.simple_string()}")
        return self._result_type(ct)

    def _date_result(self, lt, rt) -> DataType:
        raise TypeCheckError(f"cannot apply {self.symbol} to dates")

    def _result_type(self, ct: DataType) -> DataType:
        return ct

    def eval(self, ctx: EvalCtx) -> Val:
        l = ctx.eval(self.left)
        r = ctx.eval(self.right)
        v = ctx.and_valid(l, r)
        out = self.dtype
        if not ctx.is_trace:
            return Val(out, None, v, None)
        jnp = _jnp()
        ld, rd = self._align(ctx, l, r, out)
        data, extra_null = self._op(ld, rd)
        if extra_null is not None:
            v = extra_null if v is None else (v & extra_null)
        return Val(out, data, v, None)

    def _align(self, ctx, l: Val, r: Val, out: DataType):
        jnp = _jnp()
        if isinstance(out, DecimalType):
            lc = cast_val(ctx, l, out) if not isinstance(l.dtype, DecimalType) else l
            rc = cast_val(ctx, r, out) if not isinstance(r.dtype, DecimalType) else r
            return lc.data, rc.data
        dd = out.device_dtype
        return l.data.astype(dd), r.data.astype(dd)

    def _op(self, l, r):
        raise NotImplementedError


class Add(BinaryArithmetic):
    symbol = "+"

    @property
    def dtype(self):
        if isinstance(self.right, IntervalLiteral):
            return self.left.dtype
        if isinstance(self.left, IntervalLiteral):
            return self.right.dtype
        return super().dtype

    def _date_result(self, lt, rt):
        if isinstance(lt, DateType) and isinstance(rt, IntegralType):
            return date
        if isinstance(rt, DateType) and isinstance(lt, IntegralType):
            return date
        raise TypeCheckError("date + non-int")

    def _result_type(self, ct):
        if isinstance(ct, DecimalType):
            return DecimalType(min(ct.precision + 1, DecimalType.MAX_PRECISION),
                               ct.scale)
        return ct

    def eval(self, ctx):
        if isinstance(self.right, IntervalLiteral) or \
                isinstance(self.left, IntervalLiteral):
            iv = self.right if isinstance(self.right, IntervalLiteral) \
                else self.left
            other = self.left if iv is self.right else self.right
            side = ctx.eval(other)
            if not ctx.is_trace:
                out_dt = side.dtype
                return Val(out_dt, None, side.validity, None)
            return _apply_interval(ctx, side, iv)
        lt = self.left.dtype if self.left.resolved else null_type
        rt = self.right.dtype if self.right.resolved else null_type
        if isinstance(lt, DateType) or isinstance(rt, DateType):
            l, r = ctx.eval(self.left), ctx.eval(self.right)
            v = ctx.and_valid(l, r)
            if not ctx.is_trace:
                return Val(date, None, v, None)
            jnp = _jnp()
            if isinstance(lt, DateType):
                return Val(date, l.data + r.data.astype(jnp.int32), v, None)
            return Val(date, r.data + l.data.astype(jnp.int32), v, None)
        return super().eval(ctx)

    def _op(self, l, r):
        return l + r, None


class Subtract(BinaryArithmetic):
    symbol = "-"

    @property
    def dtype(self):
        if isinstance(self.right, IntervalLiteral):
            return self.left.dtype
        return super().dtype

    def _date_result(self, lt, rt):
        if isinstance(lt, DateType) and isinstance(rt, DateType):
            return int32
        if isinstance(lt, DateType) and isinstance(rt, IntegralType):
            return date
        raise TypeCheckError("unsupported date subtraction")

    def _result_type(self, ct):
        if isinstance(ct, DecimalType):
            return DecimalType(min(ct.precision + 1, DecimalType.MAX_PRECISION),
                               ct.scale)
        return ct

    def eval(self, ctx):
        if isinstance(self.right, IntervalLiteral):
            side = ctx.eval(self.left)
            if not ctx.is_trace:
                return Val(side.dtype, None, side.validity, None)
            return _apply_interval(ctx, side, self.right.negated())
        lt = self.left.dtype if self.left.resolved else null_type
        rt = self.right.dtype if self.right.resolved else null_type
        if isinstance(lt, DateType):
            l, r = ctx.eval(self.left), ctx.eval(self.right)
            v = ctx.and_valid(l, r)
            out = self._date_result(lt, rt)
            if not ctx.is_trace:
                return Val(out, None, v, None)
            jnp = _jnp()
            return Val(out, (l.data - r.data).astype(jnp.int32), v, None)
        return super().eval(ctx)

    def _op(self, l, r):
        return l - r, None


class Multiply(BinaryArithmetic):
    symbol = "*"

    @staticmethod
    def _decimal_types(lt, rt):
        def as_dec(t):
            if isinstance(t, DecimalType):
                return t
            if isinstance(t, IntegralType):
                p = {1: 3, 2: 5, 4: 10, 8: 19}[t.device_dtype.itemsize]
                return DecimalType(p, 0)
            return None

        ld, rd = as_dec(lt), as_dec(rt)
        if ld is not None and rd is not None and (
                isinstance(lt, DecimalType) or isinstance(rt, DecimalType)):
            return ld, rd
        return None

    def _result_type(self, ct):
        if isinstance(ct, DecimalType):
            lt = self.left.dtype
            rt = self.right.dtype
            dd = self._decimal_types(lt, rt)
            if dd is not None:
                p = dd[0].precision + dd[1].precision
                s = dd[0].scale + dd[1].scale
                if p <= DecimalType.MAX_PRECISION:
                    return DecimalType(p, s)  # exact scaled-int64 product
            # precision exceeds int64 → float64 (documented deviation)
            return float64
        return ct

    def _align(self, ctx, l, r, out):
        if isinstance(out, DecimalType):
            # exact path: raw scaled int64 product, scales add
            ld = l.data if isinstance(l.dtype, DecimalType) \
                else l.data.astype(_jnp().int64)
            rd = r.data if isinstance(r.dtype, DecimalType) \
                else r.data.astype(_jnp().int64)
            return ld, rd
        if isinstance(out, FractionalType) and (
                isinstance(l.dtype, DecimalType) or isinstance(r.dtype, DecimalType)):
            lc = cast_val(ctx, l, float64)
            rc = cast_val(ctx, r, float64)
            return lc.data, rc.data
        return super()._align(ctx, l, r, out)

    def _op(self, l, r):
        return l * r, None


class TryAdd(Add):
    """try_add: NULL on integral overflow instead of wrapping (reference:
    Add with EvalMode.TRY, sqlcat/expressions/arithmetic.scala)."""

    def _op(self, l, r):
        data, _ = super()._op(l, r)
        jnp = _jnp()
        if not jnp.issubdtype(data.dtype, jnp.signedinteger):
            return data, None
        # signed add overflows iff operands share a sign the result lost
        ok = ~(((l >= 0) == (r >= 0)) & ((data >= 0) != (l >= 0)))
        return data, ok


class TrySubtract(Subtract):
    """try_subtract: NULL on integral overflow instead of wrapping."""

    def _op(self, l, r):
        data, _ = super()._op(l, r)
        jnp = _jnp()
        if not jnp.issubdtype(data.dtype, jnp.signedinteger):
            return data, None
        ok = ~(((l >= 0) != (r >= 0)) & ((data >= 0) != (l >= 0)))
        return data, ok


class TryMultiply(Multiply):
    """try_multiply: NULL on integral overflow instead of wrapping."""

    def _op(self, l, r):
        data, _ = super()._op(l, r)
        jnp = _jnp()
        if not jnp.issubdtype(data.dtype, jnp.signedinteger):
            return data, None
        info = jnp.iinfo(data.dtype)
        if info.bits < 64:
            wide = l.astype(jnp.int64) * r.astype(jnp.int64)
            return data, (wide >= info.min) & (wide <= info.max)
        # int64: division check is exact — wrapped result res = l*r - k*2^64
        # with floor(res/l) == r forces k == 0; only the (-1, INT64_MIN)
        # pair needs special-casing (its quotient itself wraps)
        nz = jnp.where(l == 0, jnp.ones_like(l), l)
        ok = (l == 0) | (jnp.floor_divide(data, nz) == r)
        ok = ok & ~((l == -1) & (r == info.min))
        return data, ok


class Divide(BinaryArithmetic):
    symbol = "/"

    def _result_type(self, ct):
        return float64

    def _align(self, ctx, l, r, out):
        return (cast_val(ctx, l, float64).data, cast_val(ctx, r, float64).data)

    def _op(self, l, r):
        jnp = _jnp()
        zero = r == 0
        safe = jnp.where(zero, _jnp().ones_like(r), r)
        return l / safe, ~zero  # x/0 => NULL (non-ANSI Spark semantics)


class Remainder(BinaryArithmetic):
    symbol = "%"

    def _op(self, l, r):
        jnp = _jnp()
        zero = r == 0
        safe = jnp.where(zero, jnp.ones_like(r), r)
        # Spark % keeps the sign of the dividend (like Java), numpy keeps divisor's
        if jnp.issubdtype(l.dtype, jnp.floating):
            m = l - jnp.trunc(l / safe) * safe
        else:
            m = l - jnp.sign(l) * (jnp.abs(l) // jnp.abs(safe)) * jnp.abs(safe)
        return m, ~zero


class UnaryExpression(Expression):
    child_fields = ("child",)

    def __init__(self, child: Expression):
        self.child = child

    def simple_string(self) -> str:
        return f"{self.sql_name()}({self.child.simple_string()})"


class UnaryMinus(UnaryExpression):
    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        return Val(self.dtype, -c.data, c.validity, None)


class Abs(UnaryExpression):
    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        return Val(self.dtype, _jnp().abs(c.data), c.validity, None)


class _MathUnary(UnaryExpression):
    fn = None
    domain_check = None  # optional lambda returning ok-mask

    @property
    def dtype(self):
        return float64

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(float64, None, True if (self.domain_check or c.has_validity) else None, None)
        jnp = _jnp()
        x = cast_val(ctx, c, float64).data
        v = c.validity
        if self.domain_check is not None:
            ok = self.domain_check(x)
            x = jnp.where(ok, x, jnp.ones_like(x))
            v = ok if v is None else (v & ok)
        data = self.fn(x)
        return Val(float64, data, v, None)


class Sqrt(_MathUnary):
    fn = staticmethod(lambda x: _jnp().sqrt(x))
    domain_check = staticmethod(lambda x: x >= 0)


class Exp(_MathUnary):
    fn = staticmethod(lambda x: _jnp().exp(x))


class Log(_MathUnary):
    fn = staticmethod(lambda x: _jnp().log(x))
    domain_check = staticmethod(lambda x: x > 0)


class Log10(_MathUnary):
    fn = staticmethod(lambda x: _jnp().log10(x))
    domain_check = staticmethod(lambda x: x > 0)


class Sin(_MathUnary):
    fn = staticmethod(lambda x: _jnp().sin(x))


class Cos(_MathUnary):
    fn = staticmethod(lambda x: _jnp().cos(x))


class Tan(_MathUnary):
    fn = staticmethod(lambda x: _jnp().tan(x))


class Asin(_MathUnary):
    fn = staticmethod(lambda x: _jnp().arcsin(x))
    domain_check = staticmethod(lambda x: _jnp().abs(x) <= 1)


class Acos(_MathUnary):
    fn = staticmethod(lambda x: _jnp().arccos(x))
    domain_check = staticmethod(lambda x: _jnp().abs(x) <= 1)


class Atan(_MathUnary):
    fn = staticmethod(lambda x: _jnp().arctan(x))


class Sinh(_MathUnary):
    fn = staticmethod(lambda x: _jnp().sinh(x))


class Cosh(_MathUnary):
    fn = staticmethod(lambda x: _jnp().cosh(x))


class Tanh(_MathUnary):
    fn = staticmethod(lambda x: _jnp().tanh(x))


class Log2(_MathUnary):
    fn = staticmethod(lambda x: _jnp().log2(x))
    domain_check = staticmethod(lambda x: x > 0)


class Log1p(_MathUnary):
    fn = staticmethod(lambda x: _jnp().log1p(x))
    domain_check = staticmethod(lambda x: x > -1)


class Expm1(_MathUnary):
    fn = staticmethod(lambda x: _jnp().expm1(x))


class Degrees(_MathUnary):
    fn = staticmethod(lambda x: _jnp().degrees(x))


class Radians(_MathUnary):
    fn = staticmethod(lambda x: _jnp().radians(x))


class Cbrt(_MathUnary):
    fn = staticmethod(lambda x: _jnp().cbrt(x))


class Atan2(BinaryArithmetic):
    symbol = "atan2"

    def _result_type(self, ct):
        return float64

    def _align(self, ctx, l, r, out):
        return (cast_val(ctx, l, float64).data, cast_val(ctx, r, float64).data)

    def _op(self, l, r):
        return _jnp().arctan2(l, r), None


class Signum(UnaryExpression):
    @property
    def dtype(self):
        return float64

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(float64, None, c.validity, None)
        jnp = _jnp()
        return Val(float64, jnp.sign(c.data.astype(jnp.float64)),
                   c.validity, None)


class Floor(UnaryExpression):
    @property
    def dtype(self):
        ct = self.child.dtype
        return ct if isinstance(ct, (IntegralType, DecimalType)) else int64

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if isinstance(c.dtype, (IntegralType,)):
            return c
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        jnp = _jnp()
        if isinstance(c.dtype, DecimalType):
            f = 10 ** c.dtype.scale
            d = jnp.where(c.data >= 0, c.data // f, -((-c.data + f - 1) // f)) * f
            return Val(c.dtype, d, c.validity, None)
        return Val(int64, jnp.floor(c.data).astype(jnp.int64), c.validity, None)


class Ceil(UnaryExpression):
    @property
    def dtype(self):
        ct = self.child.dtype
        return ct if isinstance(ct, (IntegralType, DecimalType)) else int64

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if isinstance(c.dtype, (IntegralType,)):
            return c
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        jnp = _jnp()
        if isinstance(c.dtype, DecimalType):
            f = 10 ** c.dtype.scale
            d = jnp.where(c.data >= 0, (c.data + f - 1) // f, -((-c.data) // f)) * f
            return Val(c.dtype, d, c.validity, None)
        return Val(int64, jnp.ceil(c.data).astype(jnp.int64), c.validity, None)


class NanVl(Expression):
    """nanvl(a, b): b where a is NaN (mathExpressions.scala NaNvl)."""

    child_fields = ("left", "right")

    def __init__(self, left: Expression, right: Expression):
        self.left = left
        self.right = right

    @property
    def dtype(self):
        return float64

    def eval(self, ctx):
        a = ctx.eval(cast_if(self.left, float64))
        b = ctx.eval(cast_if(self.right, float64))
        if not ctx.is_trace:
            return Val(float64, None,
                       True if a.has_validity or b.has_validity else None,
                       None)
        jnp = _jnp()
        nan = jnp.isnan(a.data)
        data = jnp.where(nan, jnp.broadcast_to(b.data, jnp.shape(
            jnp.broadcast_to(a.data, (ctx.capacity,)))), a.data)
        valid = None
        if a.validity is not None or b.validity is not None:
            av = a.validity if a.validity is not None else jnp.ones((), bool)
            bv = b.validity if b.validity is not None else jnp.ones((), bool)
            # a NULL left operand stays NULL even if its masked payload
            # is NaN (Spark: the null check precedes the NaN check)
            valid = jnp.broadcast_to(jnp.where(nan, av & bv, av),
                                     (ctx.capacity,))
        return Val(float64, data, valid, None)


class Round(Expression):
    child_fields = ("child", "scale_expr")

    def __init__(self, child: Expression, scale_expr: Expression | None = None):
        self.child = child
        self.scale_expr = scale_expr if scale_expr is not None else Literal(0)

    @property
    def dtype(self):
        ct = self.child.dtype
        return ct if isinstance(ct, (IntegralType, DecimalType)) else float64

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not isinstance(self.scale_expr, Literal):
            raise UnsupportedOperationError("round() scale must be a literal")
        s = int(self.scale_expr.value or 0)
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        jnp = _jnp()
        if isinstance(c.dtype, DecimalType):
            delta = c.dtype.scale - s
            if delta <= 0:
                return c
            f = 10 ** delta
            half = f // 2
            d = jnp.where(c.data >= 0, (c.data + half) // f, -((-c.data + half) // f)) * f
            return Val(c.dtype, d, c.validity, None)
        if isinstance(c.dtype, IntegralType):
            return c
        x = cast_val(ctx, c, float64).data
        f = 10.0 ** s
        # HALF_UP like Spark (not banker's rounding)
        d = jnp.trunc(x * f + jnp.where(x >= 0, 0.5, -0.5)) / f
        return Val(float64, d, c.validity, None)


class BRound(Round):
    """bround: HALF_EVEN (banker's) rounding — Spark's bround vs round
    split (mathExpressions.scala BRound)."""

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not isinstance(self.scale_expr, Literal):
            raise UnsupportedOperationError(
                "bround() scale must be a literal")
        s = int(self.scale_expr.value or 0)
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        jnp = _jnp()
        if isinstance(c.dtype, DecimalType):
            delta = c.dtype.scale - s
            if delta <= 0:
                return c
            f = 10 ** delta
            half = f // 2
            sign = jnp.where(c.data >= 0, 1, -1)
            a = jnp.abs(c.data)
            q = a // f
            r = a - q * f
            up = (r > half) | ((r == half) & (q % 2 == 1))  # half-to-even
            d = sign * (q + up.astype(q.dtype)) * f
            return Val(c.dtype, d, c.validity, None)
        if isinstance(c.dtype, IntegralType):
            return c
        x = cast_val(ctx, c, float64).data
        f = 10.0 ** s
        d = jnp.rint(x * f) / f  # rint = round-half-to-even
        return Val(float64, d, c.validity, None)


class BitwiseAnd(BinaryArithmetic):
    symbol = "&"

    def _op(self, l, r):
        return l & r, None


class BitwiseOr(BinaryArithmetic):
    symbol = "|"

    def _op(self, l, r):
        return l | r, None


class BitwiseXor(BinaryArithmetic):
    symbol = "^"

    def _op(self, l, r):
        return l ^ r, None


class BitwiseNot(UnaryExpression):
    @property
    def dtype(self):
        return self.child.dtype

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(self.dtype, None, c.validity, None)
        return Val(self.dtype, ~c.data, c.validity, None)


class ShiftLeft(BinaryArithmetic):
    symbol = "<<"

    def _op(self, l, r):
        return l << r, None


class ShiftRight(BinaryArithmetic):
    symbol = ">>"

    def _op(self, l, r):
        return l >> r, None


class Pow(BinaryArithmetic):
    symbol = "^"

    def _result_type(self, ct):
        return float64

    def _align(self, ctx, l, r, out):
        return (cast_val(ctx, l, float64).data, cast_val(ctx, r, float64).data)

    def _op(self, l, r):
        return l ** r, None


# ---------------------------------------------------------------------------
# Comparisons (string-aware)
# ---------------------------------------------------------------------------

def _string_eq_domain(ctx: EvalCtx, v: Val):
    """Map a string Val's codes to 64-bit value hashes via an aux lut."""
    jnp = _jnp()
    if not ctx.is_trace:
        lut = ctx.aux(lambda: (v.sdict.hashes if v.sdict and len(v.sdict)
                               else np.zeros(1, np.int64)))
        return None
    lut = ctx.aux(None)
    codes = jnp.clip(v.data, 0, lut.shape[0] - 1)
    return jnp.take(lut, codes)


def _string_rank_domain(ctx: EvalCtx, l: Val, r: Val):
    """Map two string Vals into a common ordering domain (merged-dict ranks)."""
    jnp = _jnp()

    def make_luts():
        a = l.sdict or StringDict([""])
        b = r.sdict or StringDict([""])
        allv = sorted(set(a.values) | set(b.values))
        pos = {v: i for i, v in enumerate(allv)}
        la = np.array([pos[v] for v in a.values] or [0], dtype=np.int64)
        lb = np.array([pos[v] for v in b.values] or [0], dtype=np.int64)
        return la, lb

    if not ctx.is_trace:
        ctx.aux(lambda: make_luts()[0])
        ctx.aux(lambda: make_luts()[1])
        return None, None
    la = ctx.aux(None)
    lb = ctx.aux(None)
    ld = jnp.take(la, jnp.clip(l.data, 0, la.shape[0] - 1))
    rd = jnp.take(lb, jnp.clip(r.data, 0, lb.shape[0] - 1))
    return ld, rd


class BinaryComparison(BinaryExpression):
    @property
    def dtype(self):
        return boolean

    def eval(self, ctx):
        l = ctx.eval(self.left)
        r = ctx.eval(self.right)
        lt, rt = l.dtype, r.dtype
        is_string = isinstance(lt, StringType) and isinstance(rt, StringType)
        if is_string:
            v = ctx.and_valid(l, r)
            if type(self) in (EqualTo, NotEqualTo, EqualNullSafe):
                ld = _string_eq_domain(ctx, l)
                rd = _string_eq_domain(ctx, r)
            else:
                ld, rd = _string_rank_domain(ctx, l, r)
            if not ctx.is_trace:
                return Val(boolean, None, v, None)
        else:
            # casts run in BOTH modes: string→X casts register dictionary
            # parse tables through the aux channel (host/trace symmetry)
            ct = common_type(lt, rt) or lt
            lc = cast_val(ctx, l, ct)
            rc = cast_val(ctx, r, ct)
            v = ctx.and_valid(lc, rc)
            if not ctx.is_trace:
                return Val(boolean, None, v, None)
            ld, rd = lc.data, rc.data
        return Val(boolean, self._cmp(ld, rd), v, None)

    def _cmp(self, l, r):
        raise NotImplementedError


class EqualTo(BinaryComparison):
    symbol = "="

    def _cmp(self, l, r):
        return l == r


class NotEqualTo(BinaryComparison):
    symbol = "!="

    def _cmp(self, l, r):
        return l != r


class EqualNullSafe(BinaryComparison):
    symbol = "<=>"

    def eval(self, ctx):
        l = ctx.eval(self.left)
        r = ctx.eval(self.right)
        is_string = isinstance(l.dtype, StringType) and isinstance(r.dtype, StringType)
        if is_string:
            ld = _string_eq_domain(ctx, l)
            rd = _string_eq_domain(ctx, r)
            lc, rc = l, r
        else:
            ct = common_type(l.dtype, r.dtype) or l.dtype
            lc = cast_val(ctx, l, ct)
            rc = cast_val(ctx, r, ct)
        if not ctx.is_trace:
            return Val(boolean, None, None, None)
        jnp = _jnp()
        if not is_string:
            ld, rd = lc.data, rc.data
        eq = ld == rd
        lv = lc.validity if lc.validity is not None else jnp.ones((), bool)
        rv = rc.validity if rc.validity is not None else jnp.ones((), bool)
        both_null = (~lv) & (~rv)
        data = jnp.where(lv & rv, eq, both_null)
        return Val(boolean, data, None, None)

    @property
    def nullable(self):
        return False


class LessThan(BinaryComparison):
    symbol = "<"

    def _cmp(self, l, r):
        return l < r


class LessThanOrEqual(BinaryComparison):
    symbol = "<="

    def _cmp(self, l, r):
        return l <= r


class GreaterThan(BinaryComparison):
    symbol = ">"

    def _cmp(self, l, r):
        return l > r


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="

    def _cmp(self, l, r):
        return l >= r


_COMPARISONS = {c.symbol: c for c in (EqualTo, NotEqualTo, LessThan,
                                      LessThanOrEqual, GreaterThan,
                                      GreaterThanOrEqual)}


def exact_numeric(dt: DataType) -> bool:
    """DECIMAL or integral: a value that is an integer at a known scale."""
    return isinstance(dt, (DecimalType, IntegralType))


class QuotientComparison(Expression):
    """`num / den <op> other` over DECIMAL or integral operands, decided on
    the unscaled 64-bit integers: with e = scale(den) + scale(other) -
    scale(num), `num * 10^e <op> other * den` for a positive `den`, both
    sides negated for a negative one. `Divide` gives a float64 quotient,
    and a float64 cannot say on which side of 0.1 a quotient of exactly a
    tenth lies: the v5e, whose float64 is emulated, puts 127.83 / 1278.3
    above it. NULL as the quotient is (a NULL operand, `den` = 0); where
    64 bits do not hold the products, the float64 comparison's answer.
    Written by the optimizer (`DecideQuotientComparisons`) alone."""

    child_fields = ("num", "den", "other")

    def __init__(self, num: Expression, den: Expression, other: Expression,
                 op: str):
        assert op in _COMPARISONS, op
        self.num = num
        self.den = den
        self.other = other
        self.op = op

    @property
    def dtype(self):
        return boolean

    def simple_string(self) -> str:
        return (f"(({self.num.simple_string()} / {self.den.simple_string()})"
                f" {self.op} {self.other.simple_string()})")

    def eval(self, ctx):
        inexact = _COMPARISONS[self.op](Divide(self.num, self.den),
                                        self.other)
        approx = ctx.eval(inexact)
        vals = [ctx.eval(e) for e in (self.num, self.den, self.other)]
        sn, sm, sk = (getattr(v.dtype, "scale", 0) for v in vals)
        e = sm + sk - sn
        up_l, up_r = 10 ** max(e, 0), 10 ** max(-e, 0)
        if not ctx.is_trace or max(up_l, up_r) >= 2 ** 62:
            return approx
        jnp = _jnp()
        n, m, k = (v.data.astype(jnp.int64) for v in vals)
        nf, mf, kf = (jnp.abs(x.astype(jnp.float64)) for x in (n, m, k))
        fits = (nf * float(up_l) < 2.0 ** 62) \
            & (kf * mf * float(up_r) < 2.0 ** 62)
        sign = jnp.sign(m)
        exact = inexact._cmp(sign * n * up_l, sign * m * k * up_r)
        return Val(boolean, jnp.where(fits, exact, approx.data),
                   approx.validity, None)


# ---------------------------------------------------------------------------
# Boolean logic — Kleene three-valued
# ---------------------------------------------------------------------------

class And(BinaryExpression):
    symbol = "AND"

    @property
    def dtype(self):
        return boolean

    def eval(self, ctx):
        l = ctx.eval(self.left)
        r = ctx.eval(self.right)
        if not ctx.is_trace:
            v = ctx.and_valid(l, r)
            return Val(boolean, None, v, None)
        jnp = _jnp()
        lv, rv = l.validity, r.validity
        ld, rd = l.data, r.data
        if lv is None and rv is None:
            return Val(boolean, ld & rd, None, None)
        lvv = lv if lv is not None else jnp.ones((), bool)
        rvv = rv if rv is not None else jnp.ones((), bool)
        # Kleene AND: FALSE wins over NULL; result known iff both known or
        # either side is a known FALSE
        known = (lvv & rvv) | (lvv & ~ld) | (rvv & ~rd)
        t_l = jnp.where(lvv, ld, False)
        t_r = jnp.where(rvv, rd, False)
        return Val(boolean, t_l & t_r, known, None)


class Or(BinaryExpression):
    symbol = "OR"

    @property
    def dtype(self):
        return boolean

    def eval(self, ctx):
        l = ctx.eval(self.left)
        r = ctx.eval(self.right)
        if not ctx.is_trace:
            v = ctx.and_valid(l, r)
            return Val(boolean, None, v, None)
        jnp = _jnp()
        lv, rv = l.validity, r.validity
        ld, rd = l.data, r.data
        if lv is None and rv is None:
            return Val(boolean, ld | rd, None, None)
        lvv = lv if lv is not None else jnp.ones((), bool)
        rvv = rv if rv is not None else jnp.ones((), bool)
        known = (lvv & rvv) | (lvv & ld) | (rvv & rd)
        t_l = jnp.where(lvv, ld, False)
        t_r = jnp.where(rvv, rd, False)
        return Val(boolean, t_l | t_r, known, None)


class Not(UnaryExpression):
    @property
    def dtype(self):
        return boolean

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(boolean, None, c.validity, None)
        return Val(boolean, ~c.data, c.validity, None)


# ---------------------------------------------------------------------------
# Null predicates / conditionals
# ---------------------------------------------------------------------------

class IsNull(UnaryExpression):
    @property
    def dtype(self):
        return boolean

    @property
    def nullable(self):
        return False

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(boolean, None, None, None)
        jnp = _jnp()
        if c.validity is None:
            return Val(boolean, jnp.zeros((), bool), None, None)
        return Val(boolean, ~c.validity, None, None)


class IsNotNull(UnaryExpression):
    @property
    def dtype(self):
        return boolean

    @property
    def nullable(self):
        return False

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(boolean, None, None, None)
        jnp = _jnp()
        if c.validity is None:
            return Val(boolean, jnp.ones((), bool), None, None)
        return Val(boolean, c.validity, None, None)


class IsNaN(UnaryExpression):
    @property
    def dtype(self):
        return boolean

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            return Val(boolean, None, c.validity, None)
        jnp = _jnp()
        if jnp.issubdtype(c.data.dtype, jnp.floating):
            return Val(boolean, jnp.isnan(c.data), c.validity, None)
        return Val(boolean, jnp.zeros((), bool), c.validity, None)


class If(Expression):
    child_fields = ("pred", "then", "otherwise")

    def __init__(self, pred, then, otherwise):
        self.pred = pred
        self.then = then
        self.otherwise = otherwise

    @property
    def dtype(self):
        return common_type(self.then.dtype, self.otherwise.dtype) or self.then.dtype

    def eval(self, ctx):
        return CaseWhen([(self.pred, self.then)], self.otherwise).eval(ctx)


class CaseWhen(Expression):
    child_fields = ("branch_exprs", "else_expr")
    equality_excluded_fields = ("branches",)  # same nodes as branch_exprs

    def __init__(self, branches: Sequence[tuple[Expression, Expression]],
                 else_expr: Expression | None = None):
        self.branches = [(p, v) for p, v in branches]
        self.branch_exprs = [e for pv in self.branches for e in pv]
        self.else_expr = else_expr if else_expr is not None else Literal(None)

    def copy(self, **overrides):
        if "branch_exprs" in overrides:
            be = overrides.pop("branch_exprs")
            overrides["branches"] = [(be[i], be[i + 1]) for i in range(0, len(be), 2)]
            new = object.__new__(type(self))
            new.__dict__.update(self.__dict__)
            new.__dict__.update(overrides)
            new.__dict__["branch_exprs"] = list(be)
            new.__dict__.pop("_hash", None)
            new.__dict__.pop("_dtype_memo", None)  # branches changed
            return new
        return super().copy(**overrides)

    @property
    def dtype(self):
        # memoized: a chain of nested CASEs (greatest/least expansion)
        # revisits each level's dtype from every ancestor — uncached
        # recursion is exponential in chain depth
        memo = self.__dict__.get("_dtype_memo")
        if memo is not None:
            return memo
        dt: DataType = null_type
        for _, v in self.branches:
            dt = common_type(dt, v.dtype) or v.dtype
        dt = common_type(dt, self.else_expr.dtype) or dt
        self.__dict__["_dtype_memo"] = dt
        return dt

    def eval(self, ctx):
        out = self.dtype
        jnp = _jnp()
        if isinstance(out, StringType):
            return self._eval_string(ctx)
        vals = [(ctx.eval(p), ctx.eval(cast_if(v, out))) for p, v in
                [(p, v) for p, v in self.branches]]
        ev = ctx.eval(cast_if(self.else_expr, out))
        if not ctx.is_trace:
            anynull = any(v.has_validity for _, v in vals) or ev.has_validity or \
                any(p.has_validity for p, _ in vals)
            return Val(out, None, True if anynull else None, None)
        data = jnp.broadcast_to(ev.data, (ctx.capacity,)) if ev.data.ndim == 0 else ev.data
        valid = ev.validity if ev.validity is not None else jnp.ones((), bool)
        valid = jnp.broadcast_to(valid, (ctx.capacity,))
        data = jnp.broadcast_to(data, (ctx.capacity,))
        decided = jnp.zeros((ctx.capacity,), bool)
        # evaluate branches first-match-wins
        for p, v in vals:
            pd = p.data
            if p.validity is not None:
                pd = pd & p.validity
            hit = jnp.broadcast_to(pd, (ctx.capacity,)) & ~decided
            vd = jnp.broadcast_to(v.data, (ctx.capacity,))
            vv = v.validity if v.validity is not None else jnp.ones((), bool)
            vv = jnp.broadcast_to(vv, (ctx.capacity,))
            data = jnp.where(hit, vd, data)
            valid = jnp.where(hit, vv, valid)
            decided = decided | hit
        has_any_null = (ev.validity is not None) or \
            any(v.validity is not None for _, v in vals)
        return Val(out, data, valid if has_any_null else None, None)

    def _eval_string(self, ctx):
        """String CASE: merge branch dictionaries into one output dict."""
        jnp = _jnp()
        branch_vals = [(ctx.eval(p), ctx.eval(v)) for p, v in self.branches]
        ev = ctx.eval(self.else_expr)
        all_strs = branch_vals + [(None, ev)]

        def merged_dict():
            md: list[str] = []
            idx: dict[str, int] = {}
            luts = []
            for _, v in all_strs:
                sd = v.sdict or StringDict([""])
                lut = np.zeros(max(len(sd), 1), np.int32)
                for i, s in enumerate(sd.values or [""]):
                    j = idx.get(s)
                    if j is None:
                        j = len(md)
                        md.append(s)
                        idx[s] = j
                    lut[i] = j
                luts.append(lut)
            return StringDict(md or [""]), luts

        if not ctx.is_trace:
            sd, luts = merged_dict()
            for lut in luts:
                ctx.aux(lambda l=lut: l)
            anynull = any(v.has_validity for _, v in all_strs) or \
                any(p.has_validity for p, _ in branch_vals)
            return Val(string, None, True if anynull else None, sd)
        luts = [ctx.aux(None) for _ in all_strs]
        elut = luts[-1]
        data = jnp.take(elut, jnp.clip(jnp.broadcast_to(ev.data, (ctx.capacity,)),
                                       0, elut.shape[0] - 1))
        valid = ev.validity if ev.validity is not None else jnp.ones((), bool)
        valid = jnp.broadcast_to(valid, (ctx.capacity,))
        decided = jnp.zeros((ctx.capacity,), bool)
        for (p, v), lut in zip(branch_vals, luts[:-1]):
            pd = p.data
            if p.validity is not None:
                pd = pd & p.validity
            hit = jnp.broadcast_to(pd, (ctx.capacity,)) & ~decided
            vd = jnp.take(lut, jnp.clip(jnp.broadcast_to(v.data, (ctx.capacity,)),
                                        0, lut.shape[0] - 1))
            vv = v.validity if v.validity is not None else jnp.ones((), bool)
            data = jnp.where(hit, vd, data)
            valid = jnp.where(hit, jnp.broadcast_to(vv, (ctx.capacity,)), valid)
            decided = decided | hit
        has_any_null = any(v.validity is not None for _, v in all_strs)
        return Val(string, data, valid if has_any_null else None, None)


def cast_if(e: Expression, to: DataType) -> Expression:
    if e.resolved and e.dtype == to:
        return e
    c = getattr(e, "_cast_cache", None)
    if c is not None and c.to == to:
        return c
    c = Cast(e, to)
    try:
        e._cast_cache = c
    except Exception:
        pass
    return c


class Coalesce(Expression):
    child_fields = ("args",)

    def __init__(self, args: Sequence[Expression]):
        self.args = list(args)

    @property
    def dtype(self):
        dt: DataType = null_type
        for a in self.args:
            dt = common_type(dt, a.dtype) or a.dtype
        return dt

    @property
    def nullable(self):
        return all(a.nullable for a in self.args)

    def eval(self, ctx):
        # rewrite as CASE WHEN a IS NOT NULL THEN a ... for uniform handling
        branches = [(IsNotNull(a), a) for a in self.args[:-1]]
        return CaseWhen(branches, self.args[-1]).eval(ctx)


class NullIf(BinaryExpression):
    @property
    def dtype(self):
        return self.left.dtype

    def eval(self, ctx):
        return CaseWhen([(EqualTo(self.left, self.right), Literal(None, self.left.dtype))],
                        self.left).eval(ctx)


class Greatest(Expression):
    child_fields = ("args",)
    _reduce = "maximum"

    def __init__(self, args: Sequence[Expression]):
        self.args = list(args)

    @property
    def dtype(self):
        dt = self.args[0].dtype
        for a in self.args[1:]:
            dt = common_type(dt, a.dtype) or dt
        return dt

    def eval(self, ctx):
        out = self.dtype
        if isinstance(out, StringType):
            # dictionary-encoded strings can't reduce by code arithmetic
            # (codes from different dictionaries aren't ordered) — expand
            # into the null-skipping CASE chain, which rides the existing
            # dictionary comparison machinery
            cmp_cls = GreaterThan if self._reduce == "maximum" else LessThan
            acc = self.args[0]
            for a in self.args[1:]:
                acc = CaseWhen([(IsNull(a), acc), (IsNull(acc), a),
                                (cmp_cls(acc, a), acc)], a)
            return ctx.eval(acc)
        vals = [ctx.eval(cast_if(a, out)) for a in self.args]
        v = ctx.and_valid(*vals)  # Spark: null only if ALL null; simplify: any-null→null? Spark Greatest skips nulls
        if not ctx.is_trace:
            return Val(out, None, True if any(x.has_validity for x in vals) else None, None)
        jnp = _jnp()
        fn = getattr(jnp, self._reduce)
        ident = None
        data = None
        valid = None
        for x in vals:
            xv = x.validity if x.validity is not None else jnp.ones((), bool)
            if data is None:
                data = x.data
                valid = jnp.broadcast_to(xv, jnp.shape(jnp.broadcast_to(x.data, (ctx.capacity,))))
                data = jnp.broadcast_to(data, (ctx.capacity,))
            else:
                xd = jnp.broadcast_to(x.data, (ctx.capacity,))
                xvv = jnp.broadcast_to(xv, (ctx.capacity,))
                both = valid & xvv
                data = jnp.where(both, fn(data, xd), jnp.where(xvv, xd, data))
                valid = valid | xvv
        has_null = any(x.validity is not None for x in vals)
        return Val(out, data, valid if has_null else None, None)


class Least(Greatest):
    _reduce = "minimum"


# ---------------------------------------------------------------------------
# IN / LIKE / string predicates
# ---------------------------------------------------------------------------

class In(Expression):
    child_fields = ("child", "items")

    def __init__(self, child: Expression, items: Sequence[Expression]):
        self.child = child
        self.items = list(items)

    @property
    def dtype(self):
        return boolean

    def eval(self, ctx):
        # SQL three-valued IN: TRUE on a match; else NULL when the list
        # holds a NULL (or the probe is NULL); else FALSE (reference:
        # predicates.scala In.eval null handling)
        c = ctx.eval(self.child)
        jnp = _jnp()
        if isinstance(c.dtype, StringType):
            targets = []
            has_null_item = False
            for it in self.items:
                if not isinstance(it, Literal):
                    raise UnsupportedOperationError("IN over strings needs literals")
                if it.value is None:
                    has_null_item = True
                else:
                    targets.append(it.value)

            def make_lut():
                sd = c.sdict or StringDict([""])
                tset = set(targets)
                return np.array([v in tset for v in (sd.values or [""])], bool)

            if not ctx.is_trace:
                ctx.aux(make_lut)
                valid = c.validity
                if has_null_item:
                    valid = True  # validity becomes data-dependent
                return Val(boolean, None, valid, None)
            lut = ctx.aux(None)
            data = jnp.take(lut, jnp.clip(c.data, 0, lut.shape[0] - 1))
            valid = c.validity
            if has_null_item:
                # unmatched rows are UNKNOWN, not false
                valid = data if valid is None else (valid & data)
            return Val(boolean, data, valid, None)
        vals = [ctx.eval(cast_if(i, c.dtype)) for i in self.items]
        if not ctx.is_trace:
            may_null_item = any(
                x.validity is not None or
                (isinstance(i, Literal) and i.value is None)
                for x, i in zip(vals, self.items))
            valid = c.validity
            if may_null_item:
                valid = True
            return Val(boolean, None, valid, None)
        matched = jnp.zeros((), bool)
        null_any = jnp.zeros((), bool)
        for x in vals:
            if x.validity is None:
                xv = jnp.ones((), bool)
            else:
                xv = x.validity
            matched = matched | ((c.data == x.data) & xv)
            null_any = null_any | ~xv
        valid = matched | ~null_any     # unmatched + null item → NULL
        if c.validity is not None:
            valid = valid & c.validity
        return Val(boolean, matched, valid, None)


def _like_to_regex(pattern: str, escape: str = "\\") -> str:
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return "^" + "".join(out) + "$"


class _StringPredicate(Expression):
    child_fields = ("child",)

    def __init__(self, child: Expression, pattern: str):
        self.child = child
        self.pattern = pattern

    @property
    def dtype(self):
        return boolean

    def matcher(self):
        raise NotImplementedError

    def eval(self, ctx):
        c = ctx.eval(self.child)
        jnp = _jnp()

        def make_lut():
            sd = c.sdict or StringDict([""])
            m = self.matcher()
            return np.array([bool(m(v)) for v in (sd.values or [""])], bool)

        if not ctx.is_trace:
            ctx.aux(make_lut)
            return Val(boolean, None, c.validity, None)
        lut = ctx.aux(None)
        data = jnp.take(lut, jnp.clip(c.data, 0, lut.shape[0] - 1))
        return Val(boolean, data, c.validity, None)


class Like(_StringPredicate):
    def matcher(self):
        rx = re.compile(_like_to_regex(self.pattern), re.DOTALL)
        return lambda s: rx.match(s) is not None


class RLike(_StringPredicate):
    def matcher(self):
        rx = re.compile(self.pattern)
        return lambda s: rx.search(s) is not None


class StartsWith(_StringPredicate):
    def matcher(self):
        p = self.pattern
        return lambda s: s.startswith(p)


class EndsWith(_StringPredicate):
    def matcher(self):
        p = self.pattern
        return lambda s: s.endswith(p)


class Contains(_StringPredicate):
    def matcher(self):
        p = self.pattern
        return lambda s: p in s


# ---------------------------------------------------------------------------
# String functions — dictionary transforms
# ---------------------------------------------------------------------------

class _DictTransform(Expression):
    """String→string function applied to dictionary values host-side;
    device codes pass through unchanged."""

    child_fields = ("child",)

    def __init__(self, child: Expression):
        self.child = child

    @property
    def dtype(self):
        return string

    def transform(self, s: str) -> str:
        raise NotImplementedError

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if not ctx.is_trace:
            sd = (c.sdict or StringDict([""])).map_values(self.transform)
            return Val(string, None, c.validity, sd)
        return Val(string, c.data, c.validity, None)


class Upper(_DictTransform):
    def transform(self, s):
        return s.upper()


class Lower(_DictTransform):
    def transform(self, s):
        return s.lower()


class Trim(_DictTransform):
    def transform(self, s):
        return s.strip()


class LTrim(_DictTransform):
    def transform(self, s):
        return s.lstrip()


class RTrim(_DictTransform):
    def transform(self, s):
        return s.rstrip()


class Substring(_DictTransform):
    def __init__(self, child: Expression, pos: Expression, length: Expression | None = None):
        super().__init__(child)
        if not isinstance(pos, Literal) or (length is not None and not isinstance(length, Literal)):
            raise UnsupportedOperationError("substring pos/len must be literals")
        self.pos = int(pos.value)
        self.length = None if length is None else int(length.value)

    def transform(self, s):
        # SQL 1-based; pos 0 treated as 1
        p = self.pos
        start = max(p - 1, 0) if p > 0 else max(len(s) + p, 0)
        if self.length is None:
            return s[start:]
        return s[start:start + max(self.length, 0)]


class StringReplace(_DictTransform):
    def __init__(self, child: Expression, search: Expression, replace: Expression):
        super().__init__(child)
        if not isinstance(search, Literal) or not isinstance(replace, Literal):
            raise UnsupportedOperationError("replace args must be literals")
        self.search = str(search.value)
        self.replace = str(replace.value)

    def transform(self, s):
        return s.replace(self.search, self.replace)


class Lpad(_DictTransform):
    def __init__(self, child, length: Expression, pad: Expression):
        super().__init__(child)
        self.length = int(length.value)
        self.pad = str(pad.value)

    def transform(self, s):
        if len(s) >= self.length:
            return s[: self.length]
        need = self.length - len(s)
        p = (self.pad * need)[:need]
        return p + s


class Rpad(Lpad):
    def transform(self, s):
        if len(s) >= self.length:
            return s[: self.length]
        need = self.length - len(s)
        p = (self.pad * need)[:need]
        return s + p


class Concat(Expression):
    """Concat where at most ONE argument is a non-literal string column (dict
    transform); general column||column needs dictionary products (later)."""

    child_fields = ("args",)

    def __init__(self, args: Sequence[Expression]):
        self.args = list(args)

    @property
    def dtype(self):
        return string

    def eval(self, ctx):
        # SQL concat is null-intolerant: any NULL argument nulls the result
        if any(isinstance(a, Literal) and a.value is None for a in self.args):
            return Literal(None, string).eval(ctx)
        col_idx = [i for i, a in enumerate(self.args) if not isinstance(a, Literal)]
        if len(col_idx) == 0:
            s = "".join(str(a.value) for a in self.args)
            return Literal(s).eval(ctx)
        if len(col_idx) > 1:
            raise UnsupportedOperationError(
                "concat of multiple string columns not yet supported")
        i = col_idx[0]
        prefix = "".join(str(a.value) for a in self.args[:i])
        suffix = "".join(str(a.value) for a in self.args[i + 1:])

        class _C(_DictTransform):
            def transform(self, s, _p=prefix, _s=suffix):
                return _p + s + _s

        return _C(self.args[i]).eval(ctx)


class RegexpExtract(_DictTransform):
    def __init__(self, child, pattern: Expression, group: Expression):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self.group = int(group.value)
        self._rx = re.compile(self.pattern)

    def _data_args(self):
        return (("pattern", self.pattern), ("group", self.group))

    def transform(self, s):
        m = self._rx.search(s)
        if m is None:
            return ""
        try:
            return m.group(self.group) or ""
        except IndexError:
            return ""


class DateFormat(Expression):
    """date_format(d, fmt): Java-style pattern subset mapped to strftime,
    evaluated per-row host-side (value universe unknown) via the UDF
    fallback at planning time — this node only resolves the type."""

    child_fields = ("child",)

    _JAVA_TO_STRF = [("yyyy", "%Y"), ("MM", "%m"), ("dd", "%d"),
                     ("HH", "%H"), ("mm", "%M"), ("ss", "%S"),
                     ("EEEE", "%A"), ("E", "%a"), ("yy", "%y")]

    def __init__(self, child: Expression, fmt: Expression):
        self.child = child
        self.fmt = str(fmt.value)

    @property
    def dtype(self):
        return string

    @classmethod
    def to_strftime(cls, fmt: str) -> str:
        for a, b in cls._JAVA_TO_STRF:
            fmt = fmt.replace(a, b)
        return fmt

    def eval(self, ctx):
        raise UnsupportedOperationError(
            "date_format must be rewritten to a host UDF (optimizer rule "
            "RewriteHostOnlyExpressions)")


class Split(_DictTransform):
    """string → array<string> by a regex delimiter: one regex run per
    DICTIONARY value, producing a list-valued dictionary (see ArrayType).
    Under explode(), GenerateExec uses split_lists directly."""

    def __init__(self, child: Expression, delim: Expression):
        super().__init__(child)
        self.delim = str(delim.value)
        self._rx = re.compile(self.delim)

    @property
    def dtype(self):
        return ArrayType(string)

    def split_lists(self, values: list[str]) -> list[list[str]]:
        return [[p for p in self._rx.split(v)] for v in values]

    def transform(self, s):
        return self._rx.split(s)


class Grouping(UnaryExpression):
    """grouping(col) over GROUPING SETS/ROLLUP/CUBE (reference:
    sqlcat/expressions/grouping.scala Grouping) — folded to a 0/1 literal
    per branch when ExpandGroupingSets expands the sets."""

    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        raise AnalysisException(
            "grouping() is only valid with GROUPING SETS/ROLLUP/CUBE",
            error_class="UNSUPPORTED_GROUPING_EXPRESSION")


class GroupingID(Expression):
    """grouping_id(...) — bitmask of non-grouped keys, most-significant bit
    first (reference: grouping.scala GroupingID). Empty args = all keys."""

    child_fields = ("args",)

    def __init__(self, args: list[Expression]):
        self.args = list(args)

    @property
    def dtype(self):
        return int64

    def simple_string(self) -> str:
        return f"grouping_id({', '.join(a.simple_string() for a in self.args)})"

    def eval(self, ctx):
        raise AnalysisException(
            "grouping_id() is only valid with GROUPING SETS/ROLLUP/CUBE",
            error_class="UNSUPPORTED_GROUPING_EXPRESSION")


class Explode(Expression):
    """Generator marker (reference: sqlcat/expressions/generators.scala
    Explode) — extracted into a Generate operator by the analyzer."""

    child_fields = ("child",)

    def __init__(self, child: Expression):
        self.child = child

    @property
    def dtype(self):
        ct = self.child.dtype
        return ct.element_type if isinstance(ct, ArrayType) else ct

    def eval(self, ctx):
        raise UnsupportedOperationError(
            "explode() must be planned as a Generate operator")


class Length(UnaryExpression):
    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        c = ctx.eval(self.child)
        jnp = _jnp()
        if not isinstance(c.dtype, StringType):
            raise TypeCheckError("length() needs a string")

        def make_lut():
            sd = c.sdict or StringDict([""])
            return np.array([len(v) for v in (sd.values or [""])], np.int32)

        if not ctx.is_trace:
            ctx.aux(make_lut)
            return Val(int32, None, c.validity, None)
        lut = ctx.aux(None)
        return Val(int32, jnp.take(lut, jnp.clip(c.data, 0, lut.shape[0] - 1)),
                   c.validity, None)


class Initcap(_DictTransform):
    def transform(self, s):
        return " ".join(w[:1].upper() + w[1:].lower() if w else w
                        for w in s.split(" "))


class Reverse(_DictTransform):
    def transform(self, s):
        return s[::-1]


class Repeat(_DictTransform):
    def __init__(self, child, n: Expression):
        super().__init__(child)
        self.n = int(n.value)

    def transform(self, s):
        return s * self.n


class SubstringIndex(_DictTransform):
    def __init__(self, child, delim: Expression, count: Expression):
        super().__init__(child)
        self.delim = str(delim.value)
        self.count = int(count.value)

    def transform(self, s):
        parts = s.split(self.delim)
        if self.count > 0:
            return self.delim.join(parts[: self.count])
        if self.count < 0:
            return self.delim.join(parts[self.count:])
        return ""


class RegexpExtract(_DictTransform):
    """regexp_extract(col, pattern, idx) (reference:
    sqlcat/expressions/regexpExpressions.scala RegExpExtract) — one regex
    match per DICTIONARY value, codes pass through."""

    def __init__(self, child, pattern: Expression, idx: Expression = None):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self.idx = 1 if idx is None else int(idx.value)
        self._rx = re.compile(self.pattern)

    def transform(self, s):
        m = self._rx.search(s)
        if m is None:
            return ""
        g = m.group(self.idx)
        return "" if g is None else g


class RegexpReplace(_DictTransform):
    """regexp_replace(col, pattern, replacement) (reference:
    regexpExpressions.scala RegExpReplace)."""

    def __init__(self, child, pattern: Expression, repl: Expression):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self.repl = str(repl.value)
        self._rx = re.compile(self.pattern)

    def transform(self, s):
        # Spark/Java replacement uses $1 group refs; python wants \1
        return self._rx.sub(re.sub(r"\$(\d)", r"\\\1", self.repl), s)


class Left(_DictTransform):
    def __init__(self, child, n: Expression):
        super().__init__(child)
        self.n = int(n.value)

    def transform(self, s):
        return s[: self.n] if self.n >= 0 else ""


class Right(_DictTransform):
    def __init__(self, child, n: Expression):
        super().__init__(child)
        self.n = int(n.value)

    def transform(self, s):
        return s[-self.n:] if self.n > 0 else ""


class Overlay(_DictTransform):
    """overlay(s, replace, pos[, len]) — 1-based."""

    def __init__(self, child, repl: Expression, pos: Expression,
                 length: Expression | None = None):
        super().__init__(child)
        self.repl = str(repl.value)
        self.pos = int(pos.value)
        self.length = len(self.repl) if length is None else int(length.value)

    def transform(self, s):
        p = self.pos - 1
        return s[:p] + self.repl + s[p + self.length:]


class Soundex(_DictTransform):
    _CODES = {**{c: "1" for c in "bfpv"}, **{c: "2" for c in "cgjkqsxz"},
              **{c: "3" for c in "dt"}, "l": "4",
              **{c: "5" for c in "mn"}, "r": "6"}

    def transform(self, s):
        if not s or not s[0].isalpha():
            return s
        out = s[0].upper()
        prev = self._CODES.get(s[0].lower(), "")
        for ch in s[1:].lower():
            code = self._CODES.get(ch, "")
            if code and code != prev:
                out += code
            if ch not in "hw":
                prev = code
            if len(out) == 4:
                break
        return out.ljust(4, "0")


class Md5(_DictTransform):
    def transform(self, s):
        import hashlib

        return hashlib.md5(s.encode()).hexdigest()


class Sha1(_DictTransform):
    def transform(self, s):
        import hashlib

        return hashlib.sha1(s.encode()).hexdigest()


class Sha2(_DictTransform):
    def __init__(self, child, bits: Expression):
        super().__init__(child)
        self.bits = int(bits.value) or 256

    def transform(self, s):
        import hashlib

        if self.bits not in (224, 256, 384, 512):
            return None  # reference returns NULL for unsupported lengths
        h = hashlib.new(f"sha{self.bits}")
        h.update(s.encode())
        return h.hexdigest()


class Base64(_DictTransform):
    def transform(self, s):
        import base64 as b64

        return b64.b64encode(s.encode()).decode()


class Unbase64(_DictTransform):
    def transform(self, s):
        import base64 as b64

        try:
            return b64.b64decode(s.encode()).decode()
        except Exception:
            return None  # reference returns NULL for invalid base64


class FormatNumber(Expression):
    """format_number(x, d) — host-only (numeric → string has no bounded
    dictionary); RewriteHostOnlyExpressions lowers it to a vectorized
    host UDF."""

    child_fields = ("child",)

    def __init__(self, child: Expression, d: Expression):
        self.child = child
        self.d = int(d.value)

    @property
    def dtype(self):
        return string

    def format_fn(self):
        d = self.d

        def fn(a):
            out = []
            for v in a:
                out.append(None if v is None else f"{float(v):,.{d}f}")
            return np.array(out, dtype=object)

        return fn

    def eval(self, ctx):
        raise UnsupportedOperationError(
            "format_number must be lowered to a host UDF")


class Translate(_DictTransform):
    def __init__(self, child, matching: Expression, replace: Expression):
        super().__init__(child)
        self.table = str.maketrans(
            str(matching.value),
            str(replace.value).ljust(len(str(matching.value)))[
                : len(str(matching.value))])

    def transform(self, s):
        return s.translate(self.table)


class _ArrayLut(Expression):
    """Array function computed ONCE PER DICTIONARY ENTRY into value +
    validity lookup tables; device codes gather through them (arrays are
    dictionary-encoded — see ArrayType). Reference:
    sqlcat/expressions/collectionOperations.scala."""

    child_fields = ("child",)

    def __init__(self, child: Expression):
        self.child = child

    def value_of(self, lst):
        """→ (value, is_valid) for one dictionary list."""
        raise NotImplementedError

    def eval(self, ctx):
        c = ctx.eval(self.child)
        jnp = _jnp()

        def has_lut():
            sd = c.sdict or StringDict([[]])
            return np.array([self.value_of(v)[1]
                             for v in (sd.values or [[]])], bool)

        if dict_encoded(self.dtype):
            # dictionary-encoded result (string element, nested struct /
            # map / array): per-entry result value, codes pass through;
            # validity folds in per-entry presence
            if not ctx.is_trace:
                sd = c.sdict or StringDict([[]])
                out = StringDict([self.value_of(v)[0] if self.value_of(v)[1]
                                  else _dict_empty(self.dtype)
                                  for v in (sd.values or [[]])])
                ctx.aux(has_lut)
                return Val(self.dtype, None, True, out)
            hl = ctx.aux(None)
            codes = jnp.clip(c.data, 0, hl.shape[0] - 1)
            has = jnp.take(hl, codes)
            validity = has if c.validity is None else (c.validity & has)
            return Val(self.dtype, c.data, validity, None)

        dd = self.dtype.device_dtype

        def vals_lut():
            sd = c.sdict or StringDict([[]])
            vs = sd.values or [[]]
            out = np.zeros(len(vs), dd)
            for i, v in enumerate(vs):
                val, ok = self.value_of(v)
                out[i] = _to_device_value(self.dtype, val) if ok else 0
            return out

        if not ctx.is_trace:
            ctx.aux(vals_lut)
            ctx.aux(has_lut)
            return Val(self.dtype, None, True, None)
        vl = ctx.aux(None)
        hl = ctx.aux(None)
        codes = jnp.clip(c.data, 0, vl.shape[0] - 1)
        data = jnp.take(vl, codes)
        has = jnp.take(hl, codes)
        validity = has if c.validity is None else (c.validity & has)
        return Val(self.dtype, data, validity, None)


class Size(_ArrayLut):
    @property
    def dtype(self):
        return int32

    def value_of(self, lst):
        return len(lst), True


class ArrayContains(_ArrayLut):
    def __init__(self, child: Expression, value: Expression):
        super().__init__(child)
        self.value = value.value  # literal

    @property
    def dtype(self):
        return boolean

    def value_of(self, lst):
        return (self.value in lst), True


class ArrayMin(_ArrayLut):
    @property
    def dtype(self):
        ct = self.child.dtype
        return ct.element_type if isinstance(ct, ArrayType) else ct

    def value_of(self, lst):
        vals = [v for v in lst if v is not None]
        return (min(vals), True) if vals else (0, False)


class ArrayMax(ArrayMin):
    def value_of(self, lst):
        vals = [v for v in lst if v is not None]
        return (max(vals), True) if vals else (0, False)


class ElementAt(_ArrayLut):
    """element_at(arr, i) — 1-based, negative from the end; numeric
    elements gather through a LUT, string elements go through a
    dictionary transform (see build_element_at)."""

    def __init__(self, child: Expression, idx: Expression):
        super().__init__(child)
        self.idx = int(idx.value)

    @property
    def dtype(self):
        ct = self.child.dtype
        return ct.element_type if isinstance(ct, ArrayType) else ct

    def value_of(self, lst):
        i = self.idx - 1 if self.idx > 0 else len(lst) + self.idx
        if 0 <= i < len(lst) and lst[i] is not None:
            return lst[i], True
        return 0, False


class GetStructField(_ArrayLut):
    """struct.field access (reference: complexTypeExtractors.scala
    GetStructField) — per-dictionary-entry field extraction into a LUT
    (numeric fields) or a derived dictionary (string/nested fields)."""

    def __init__(self, child: Expression, name: str):
        super().__init__(child)
        self.field_name = name

    @property
    def dtype(self):
        ct = self.child.dtype
        if isinstance(ct, StructType):
            ft = ct.field_type(self.field_name)
            if ft is not None:
                return ft
        return null_type

    @property
    def nullable(self):
        return True

    def value_of(self, d):
        if isinstance(d, dict) and d.get(self.field_name) is not None:
            return d[self.field_name], True
        return 0, False

    def simple_string(self):
        return f"{self.child.simple_string()}.{self.field_name}"


class GetMapValue(_ArrayLut):
    """map[key] / element_at(map, key) (reference:
    complexTypeExtractors.scala GetMapValue) — per-entry lookup LUT."""

    def __init__(self, child: Expression, key: Expression):
        super().__init__(child)
        self.key = key.value  # literal key

    @property
    def dtype(self):
        ct = self.child.dtype
        return ct.value_type if isinstance(ct, MapType) else null_type

    def value_of(self, m):
        if isinstance(m, dict) and m.get(self.key) is not None:
            return m[self.key], True
        return 0, False


class MapContainsKey(_ArrayLut):
    def __init__(self, child: Expression, key: Expression):
        super().__init__(child)
        self.key = key.value

    @property
    def dtype(self):
        return boolean

    @property
    def nullable(self):
        return self.child.nullable

    def value_of(self, m):
        return (self.key in m) if isinstance(m, dict) else False, True


class _ArrayDictTransform(_DictTransform):
    """list → list function over dictionary values (codes unchanged)."""

    @property
    def dtype(self):
        return self.child.dtype


class MapKeys(_ArrayDictTransform):
    @property
    def dtype(self):
        ct = self.child.dtype
        return ArrayType(ct.key_type) if isinstance(ct, MapType) \
            else ArrayType()

    def transform(self, m):
        return list(m.keys()) if isinstance(m, dict) else []


class MapValues(_ArrayDictTransform):
    @property
    def dtype(self):
        ct = self.child.dtype
        return ArrayType(ct.value_type) if isinstance(ct, MapType) \
            else ArrayType()

    def transform(self, m):
        return list(m.values()) if isinstance(m, dict) else []


class SortArray(_ArrayDictTransform):
    def __init__(self, child: Expression, asc: Expression | None = None):
        super().__init__(child)
        self.asc = True if asc is None else bool(asc.value)

    def transform(self, lst):
        return sorted(lst, reverse=not self.asc)


class ArrayDistinct(_ArrayDictTransform):
    def transform(self, lst):
        return list(dict.fromkeys(lst))


class Flatten(_ArrayLut):
    """flatten(array<array<T>>) → array<T> (one level). A NULL
    sub-array nulls the whole result, per the reference
    (collectionOperations.scala Flatten) — the per-dictionary-entry
    validity fold carries the NULL."""

    @property
    def dtype(self):
        ct = self.child.dtype
        return ct.element_type if isinstance(ct, ArrayType) and \
            isinstance(ct.element_type, ArrayType) else ct

    def value_of(self, lst):
        out = []
        for sub in lst:
            if sub is None:
                return [], False
            out.extend(sub)
        return out, True


class Slice(_ArrayDictTransform):
    """slice(arr, start, length) — 1-based, negative start from the end
    (collectionOperations.scala Slice)."""

    def __init__(self, child: Expression, start: Expression,
                 length: Expression):
        super().__init__(child)
        self.start = int(start.value)
        self.length = int(length.value)
        if self.start == 0:
            raise AnalysisException(
                "Unexpected value for start in function slice: "
                "SQL array indices start at 1")

    def transform(self, lst):
        s = self.start - 1 if self.start > 0 else len(lst) + self.start
        if s < 0:
            return []
        return lst[s:s + self.length]


class ArrayRemove(_ArrayDictTransform):
    def __init__(self, child: Expression, value: Expression):
        super().__init__(child)
        self.value = value.value

    def transform(self, lst):
        return [v for v in lst if v != self.value]


class ArrayJoin(_ArrayLut):
    """array_join(arr, sep[, null_replacement]) → string."""

    def __init__(self, child: Expression, sep: Expression,
                 null_replacement: Expression | None = None):
        super().__init__(child)
        self.sep = str(sep.value)
        self.null_rep = None if null_replacement is None \
            else str(null_replacement.value)

    @property
    def dtype(self):
        return string

    def value_of(self, lst):
        parts = []
        for v in lst:
            if v is None:
                if self.null_rep is not None:
                    parts.append(self.null_rep)
            else:
                parts.append(str(v))
        return self.sep.join(parts), True


class ArrayPosition(_ArrayLut):
    """array_position(arr, value) → 1-based index of first match, 0 if
    absent (collectionOperations.scala ArrayPosition)."""

    def __init__(self, child: Expression, value: Expression):
        super().__init__(child)
        self.value = value.value

    @property
    def dtype(self):
        return int64

    def value_of(self, lst):
        for i, v in enumerate(lst):
            if v == self.value:
                return i + 1, True
        return 0, True


class GetJsonObject(_ArrayLut):
    """get_json_object(json_str, '$.path') — JsonPath subset: dotted
    fields and [n] indexing (reference: jsonExpressions.scala
    GetJsonObject). Misses and JSON nulls are real NULLs (per-entry
    validity fold); non-scalar results re-serialize as JSON, matching
    the reference."""

    def __init__(self, child: Expression, path: Expression):
        super().__init__(child)
        self.path = str(path.value)

    @property
    def dtype(self):
        return string

    def _data_args(self):
        return (("path", self.path),)

    def value_of(self, s):
        import json as _json
        import re as _re

        try:
            cur = _json.loads(s)
        except (ValueError, TypeError):
            return "", False
        p = self.path
        if p.startswith("$"):
            p = p[1:]
        # the whole path must tokenize — an unsupported segment ($[*],
        # quoted keys, odd characters) means NULL, not a partial walk
        tokens = list(_re.finditer(r"\.([A-Za-z_][\w]*)|\[(\d+)\]", p))
        consumed = "".join(m.group(0) for m in tokens)
        if consumed != p:
            return "", False
        for name, idx in ((m.group(1), m.group(2)) for m in tokens):
            if name:
                if not isinstance(cur, dict) or name not in cur:
                    return "", False
                cur = cur[name]
            else:
                i = int(idx)
                if not isinstance(cur, list) or i >= len(cur):
                    return "", False
                cur = cur[i]
        if cur is None:
            return "", False
        if isinstance(cur, (dict, list)):
            return _json.dumps(cur), True
        if isinstance(cur, bool):
            return ("true" if cur else "false"), True
        return str(cur), True


class Crc32(_ArrayLut):
    """crc32(string) → bigint over dictionary values (hash.scala Crc32)."""

    @property
    def dtype(self):
        return int64

    def value_of(self, s):
        import zlib

        return zlib.crc32(str(s).encode()), True


class ElementAtString(_ArrayLut):
    """element_at over array<string>: per-entry extraction with a real
    NULL for out-of-bounds / null elements (complexTypeExtractors.scala
    ElementAt null semantics, carried by the validity fold)."""

    def __init__(self, child: Expression, idx: Expression):
        super().__init__(child)
        self.idx = int(idx.value)

    @property
    def dtype(self):
        return string

    def _data_args(self):
        return (("idx", self.idx),)

    def value_of(self, lst):
        i = self.idx - 1 if self.idx > 0 else len(lst) + self.idx
        if 0 <= i < len(lst) and lst[i] is not None:
            return lst[i], True
        return "", False


def build_element_at(child: Expression, idx: Expression) -> Expression:
    if not isinstance(idx, Literal):
        from ..errors import AnalysisException

        raise AnalysisException(
            "element_at / [] requires a literal key; column-valued keys "
            "are not supported yet")
    ct = child.dtype
    if isinstance(ct, MapType):
        return GetMapValue(child, idx)
    if isinstance(ct, ArrayType) and isinstance(ct.element_type, StringType):
        return ElementAtString(child, idx)
    return ElementAt(child, idx)


def build_struct_ctor(args, names=None) -> Expression:
    """struct(...) / named_struct('n1', v1, ...) — a host-vectorized
    constructor producing a dictionary-encoded struct column (reference:
    complexTypeCreator.scala CreateNamedStruct)."""
    from .pyudf import PythonUDF

    if names is None:
        names, vals = [], []
        for i, a in enumerate(args):
            if isinstance(a, Alias):
                names.append(a.name)
                vals.append(a.child)
            elif isinstance(a, AttributeReference):
                names.append(a.name)
                vals.append(a)
            elif isinstance(a, GetStructField):
                names.append(a.field_name)
                vals.append(a)
            else:
                names.append(f"col{i + 1}")
                vals.append(a)
    else:
        vals = list(args)
    st = StructType(tuple(StructField(n, v.dtype, True)
                          for n, v in zip(names, vals)))
    captured = list(names)

    def make_struct(*cols):
        return dict(zip(captured, cols))

    return PythonUDF(make_struct, vals, st, name="named_struct",
                     vectorized=False)


def build_named_struct(args) -> Expression:
    if len(args) % 2 != 0:
        from ..errors import AnalysisException

        raise AnalysisException("named_struct expects name/value pairs")
    names = [str(a.value) for a in args[0::2]]
    return build_struct_ctor(args[1::2], names=names)


def build_array_ctor(args) -> Expression:
    """array(e1, e2, ...) (reference: complexTypeCreator.scala
    CreateArray) — host-evaluated dictionary-encoded array column."""
    from .pyudf import PythonUDF

    et: DataType = null_type
    for a in args:
        et = common_type(et, a.dtype) or a.dtype
    if not args:
        # array() — a single dummy input keeps the eval pipeline shaped
        return PythonUDF(lambda _x: [], [Literal(0)], ArrayType(et),
                         name="array", vectorized=False)

    def make_array(*cols):
        return list(cols)

    return PythonUDF(make_array, list(args), ArrayType(et), name="array",
                     vectorized=False)


class ArraySortNullsLast(_ArrayDictTransform):
    """array_sort(arr) — ascending with NULLs LAST, unlike sort_array's
    nulls-first (collectionOperations.scala ArraySort default)."""

    def transform(self, lst):
        return sorted([v for v in lst if v is not None]) + \
            [None] * sum(1 for v in lst if v is None)


def build_map_ctor(args) -> Expression:
    """map(k1, v1, k2, v2, ...) (reference: complexTypeCreator.scala
    CreateMap) — host-vectorized dictionary-encoded map column."""
    from ..errors import AnalysisException
    from .pyudf import PythonUDF

    if len(args) % 2 != 0:
        raise AnalysisException("map expects key/value pairs")
    kt: DataType = null_type
    vt: DataType = null_type
    for k in args[0::2]:
        kt = common_type(kt, k.dtype) or k.dtype
    for v in args[1::2]:
        vt = common_type(vt, v.dtype) or v.dtype
    n_pairs = len(args) // 2

    def make_map(*cols):
        return {cols[2 * i]: cols[2 * i + 1] for i in range(n_pairs)}

    return PythonUDF(make_map, list(args), MapType(kt, vt), name="map",
                     vectorized=False)


class _StringIntLut(Expression):
    """String function producing an integer per dictionary entry."""

    child_fields = ("child",)

    def __init__(self, child: Expression):
        self.child = child

    @property
    def dtype(self):
        return int32

    def int_of(self, s: str) -> int:
        raise NotImplementedError

    def eval(self, ctx):
        c = ctx.eval(self.child)
        jnp = _jnp()

        def make_lut():
            sd = c.sdict or StringDict([""])
            return np.array([self.int_of(v) for v in (sd.values or [""])],
                            np.int32)

        if not ctx.is_trace:
            ctx.aux(make_lut)
            return Val(int32, None, c.validity, None)
        lut = ctx.aux(None)
        return Val(int32, jnp.take(lut, jnp.clip(c.data, 0, lut.shape[0] - 1)),
                   c.validity, None)



class RegexpExtractAll(_ArrayLut):
    """regexp_extract_all(str, regexp[, idx]) → array<string>
    (reference: regexpExpressions.scala RegExpExtractAll)."""

    def __init__(self, child, pattern: Expression, group: Expression | None = None):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self._rx = re.compile(self.pattern)
        if group is None:
            # like the reference: default group 1, but a group-less
            # pattern extracts the full match
            self.group = 1 if self._rx.groups >= 1 else 0
        else:
            self.group = int(group.value)
            if self.group > self._rx.groups:
                raise AnalysisException(
                    f"regexp_extract_all: regex group count is "
                    f"{self._rx.groups}, but the specified group index "
                    f"is {self.group}")

    @property
    def dtype(self):
        return ArrayType(string)

    def _data_args(self):
        return (("pattern", self.pattern), ("group", self.group))

    def value_of(self, s):
        return [m.group(self.group) or ""
                for m in self._rx.finditer(s)], True


class RegexpSubstr(_ArrayLut):
    """regexp_substr(str, regexp) → first match or NULL
    (RegExpSubStr)."""

    def __init__(self, child, pattern: Expression):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self._rx = re.compile(self.pattern)

    @property
    def dtype(self):
        return string

    def _data_args(self):
        return (("pattern", self.pattern),)

    def value_of(self, s):
        m = self._rx.search(s)
        return (m.group(0), True) if m is not None else ("", False)


class RegexpInstr(_StringIntLut):
    """regexp_instr(str, regexp) → 1-based position of the first match,
    0 when none (RegExpInStr)."""

    def __init__(self, child, pattern: Expression):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self._rx = re.compile(self.pattern)

    def _data_args(self):
        return (("pattern", self.pattern),)

    def int_of(self, s):
        m = self._rx.search(s)
        return (m.start() + 1) if m is not None else 0


class RegexpCount(_StringIntLut):
    """regexp_count(str, regexp) (RegExpCount)."""

    def __init__(self, child, pattern: Expression):
        super().__init__(child)
        self.pattern = str(pattern.value)
        self._rx = re.compile(self.pattern)

    def _data_args(self):
        return (("pattern", self.pattern),)

    def int_of(self, s):
        return sum(1 for _ in self._rx.finditer(s))


class ToNumber(_ArrayLut):
    """to_number / try_to_number(str, format) → decimal per the format
    ('9'/'0' digits, D or . decimal point, G or , grouping, S sign,
    $ currency — numberFormatExpressions.scala ToNumber). Strict mode
    raises on a non-conforming string; try mode yields NULL."""

    def __init__(self, child, fmt: Expression, strict: bool = False):
        super().__init__(child)
        self.fmt = str(fmt.value)
        self.strict = strict
        f = self.fmt.upper().replace("D", ".").replace("G", ",")
        self.scale = len(f.split(".", 1)[1].replace(",", "")) \
            if "." in f else 0
        digits = sum(1 for c in f if c in "90")
        self.precision = max(digits, 1)

    @property
    def dtype(self):
        return DecimalType(self.precision, self.scale)

    def _data_args(self):
        return (("fmt", self.fmt), ("strict", self.strict))

    def _miss(self, s):
        if self.strict:
            from ..errors import ExecutionError

            raise ExecutionError(
                f"to_number: {s!r} does not match format {self.fmt!r}")
        return 0, False

    def value_of(self, s):
        import decimal as _d
        import re as _re

        # validate against the format: the format's shape (digits,
        # grouping, decimal point, sign, currency) compiled to a regex —
        # a non-conforming string errors in strict mode (ToNumber) and
        # NULLs in try mode (TryToNumber)
        pat = []
        for ch in self.fmt.upper():
            if ch in "90":
                pat.append(r"\d")
            elif ch in "G,":
                pat.append(",?")
            elif ch in "D.":
                pat.append(r"\.?")
            elif ch == "S":
                pat.append("[+-]?")
            elif ch == "$":
                pat.append(r"\$?")
            else:
                return self._miss(s)
        rx = "[+-]?" + "".join(pat) if "S" not in self.fmt.upper() \
            else "".join(pat)
        t = s.strip()
        if not _re.fullmatch(rx.replace(r"\d", r"\d?"), t):
            return self._miss(s)
        neg = t.startswith("-") or t.endswith("-")
        t = t.strip("+-").replace(",", "").replace("$", "")
        try:
            v = _d.Decimal(t)
        except _d.InvalidOperation:
            return self._miss(s)
        if neg:
            v = -v
        return int(v.scaleb(self.scale).to_integral_value()), True


class Levenshtein(_StringIntLut):
    def __init__(self, child, other: Expression):
        super().__init__(child)
        self.other = str(other.value)

    def int_of(self, s):
        a, b = s, self.other
        if len(a) < len(b):
            a, b = b, a
        prev = list(range(len(b) + 1))
        for i, ca in enumerate(a, 1):
            cur = [i]
            for j, cb in enumerate(b, 1):
                cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                               prev[j - 1] + (ca != cb)))
            prev = cur
        return prev[-1]

class Ascii(_StringIntLut):
    def int_of(self, s):
        return ord(s[0]) if s else 0


class Instr(_StringIntLut):
    def __init__(self, child, sub: Expression):
        super().__init__(child)
        self.sub = str(sub.value)

    def int_of(self, s):
        return s.find(self.sub) + 1  # 1-based; 0 = not found


class ConcatWs(Expression):
    child_fields = ("args",)

    def __init__(self, sep: Expression, args: Sequence[Expression]):
        self.sep = str(sep.value)
        self.args = list(args)

    @property
    def dtype(self):
        return string

    def eval(self, ctx):
        col_idx = [i for i, a in enumerate(self.args)
                   if not isinstance(a, Literal)]
        if len(col_idx) > 1:
            raise UnsupportedOperationError(
                "concat_ws over multiple string columns not yet supported")
        if not col_idx:
            return Literal(self.sep.join(
                str(a.value) for a in self.args)).eval(ctx)
        i = col_idx[0]
        prefix = self.sep.join(str(a.value) for a in self.args[:i])
        suffix = self.sep.join(str(a.value) for a in self.args[i + 1:])
        sep = self.sep

        class _C(_DictTransform):
            def transform(self, s, _p=prefix, _s=suffix, _sep=sep):
                mid = s
                out = mid if not _p else _p + _sep + mid
                return out if not _s else out + _sep + _s

        return _C(self.args[i]).eval(ctx)


# ---------------------------------------------------------------------------
# Date/time — civil-calendar integer math on device
# ---------------------------------------------------------------------------

def _civil_from_days(days):
    """days-since-epoch → (year, month, day); Hinnant's algorithm in int32."""
    jnp = _jnp()
    z = days.astype(_jnp().int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - jnp.floor_divide(doe, 1460) + jnp.floor_divide(doe, 36524)
        - jnp.floor_divide(doe, 146096), 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + jnp.floor_divide(yoe, 4) - jnp.floor_divide(yoe, 100))
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def _days_from_civil(y, m, d):
    jnp = _jnp()
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = jnp.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + jnp.floor_divide(yoe, 4) - jnp.floor_divide(yoe, 100) + doy
    return (era * 146097 + doe - 719468).astype(jnp.int32)


class IntervalLiteral(Expression):
    """Calendar interval (months, days, microseconds) — only valid as an
    operand of date/timestamp +/- (reference: CalendarIntervalType)."""

    child_fields = ()

    def __init__(self, months: int = 0, days: int = 0, micros: int = 0):
        self.months = months
        self.days = days
        self.micros = micros

    @property
    def dtype(self):
        raise TypeCheckError(
            "INTERVAL can only be added to/subtracted from dates/timestamps")

    @property
    def resolved(self):
        return True

    @property
    def nullable(self):
        return False

    def negated(self) -> "IntervalLiteral":
        return IntervalLiteral(-self.months, -self.days, -self.micros)

    def simple_string(self):
        return f"interval({self.months}mo {self.days}d {self.micros}us)"


def build_make_interval(y, mo, w, d, h, mi, s) -> IntervalLiteral:
    """make_interval(years, months, weeks, days, hours, mins, secs) —
    literal arguments only, like interval literals themselves
    (intervalExpressions.scala MakeInterval)."""
    def val(e, default=0):
        if e is None:
            return default
        if isinstance(e, Literal) and e.value is not None:
            return e.value
        from ..errors import AnalysisException

        raise AnalysisException("make_interval expects literal arguments")

    months = int(val(y)) * 12 + int(val(mo))
    days = int(val(w)) * 7 + int(val(d))
    secs = val(s)
    micros = int(val(h)) * 3_600_000_000 + int(val(mi)) * 60_000_000 + \
        int(round(float(secs) * 1_000_000))
    return IntervalLiteral(months, days, micros)


def _apply_interval(ctx, side: "Val", iv: IntervalLiteral) -> "Val":
    jnp = _jnp()
    if isinstance(side.dtype, DateType):
        data = side.data
        if iv.days or iv.micros:
            extra_days = iv.days + iv.micros // 86_400_000_000
            data = data + jnp.int32(extra_days)
        out = Val(date, data, side.validity, None)
        if iv.months:
            tmp = AddMonths.__new__(AddMonths)
            # reuse the month-clamping math directly
            y, m, d = _civil_from_days(out.data)
            total = (y.astype(jnp.int64) * 12 + (m - 1)) + iv.months
            ny = jnp.floor_divide(total, 12).astype(jnp.int32)
            nm = (jnp.mod(total, 12) + 1).astype(jnp.int32)
            nmt = total + 1
            nmy = jnp.floor_divide(nmt, 12).astype(jnp.int32)
            nmm = (jnp.mod(nmt, 12) + 1).astype(jnp.int32)
            one = jnp.ones_like(nm)
            dim = (_days_from_civil(nmy, nmm, one)
                   - _days_from_civil(ny, nm, one)).astype(jnp.int32)
            nd = jnp.minimum(d, dim)
            out = Val(date, _days_from_civil(ny, nm, nd), side.validity, None)
        return out
    if isinstance(side.dtype, TimestampType):
        if iv.months:
            raise UnsupportedOperationError(
                "month intervals on timestamps not supported yet")
        delta = iv.days * 86_400_000_000 + iv.micros
        return Val(timestamp, side.data + jnp.int64(delta), side.validity,
                   None)
    raise TypeCheckError(
        f"cannot add INTERVAL to {side.dtype.simple_string()}")


class _DatePart(UnaryExpression):
    part = "year"

    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if isinstance(c.dtype, TimestampType):
            c = cast_val(ctx, c, date)
        if not ctx.is_trace:
            return Val(int32, None, c.validity, None)
        jnp = _jnp()
        y, m, d = _civil_from_days(c.data)
        data = self._part(jnp, c.data, y, m, d)
        return Val(int32, data, c.validity, None)

    def _part(self, jnp, days, y, m, d):
        raise NotImplementedError


class Year(_DatePart):
    def _part(self, jnp, days, y, m, d):
        return y


class Month(_DatePart):
    def _part(self, jnp, days, y, m, d):
        return m


class DayOfMonth(_DatePart):
    def _part(self, jnp, days, y, m, d):
        return d


class Quarter(_DatePart):
    def _part(self, jnp, days, y, m, d):
        return (m - 1) // 3 + 1


class DayOfWeek(_DatePart):
    """1 = Sunday … 7 = Saturday (Spark semantics)."""

    def _part(self, jnp, days, y, m, d):
        return ((days.astype(jnp.int64) + 4) % 7 + 1).astype(jnp.int32)


class DayOfYear(_DatePart):
    def _part(self, jnp, days, y, m, d):
        jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        return (days - jan1 + 1).astype(jnp.int32)


class WeekOfYear(_DatePart):
    """ISO week number."""

    def _part(self, jnp, days, y, m, d):
        # ISO: week containing the year's first Thursday is week 1
        dow = ((days.astype(jnp.int64) + 3) % 7)  # 0=Mon
        thursday = days.astype(jnp.int64) - dow + 3
        ty, _, _ = _civil_from_days(thursday)
        jan1 = _days_from_civil(ty, jnp.ones_like(m), jnp.ones_like(d)).astype(jnp.int64)
        return (jnp.floor_divide(thursday - jan1, 7) + 1).astype(jnp.int32)


class TruncDate(UnaryExpression):
    """trunc(date, fmt) / date_trunc(fmt, ts). `allow_day` is True only
    for date_trunc — Spark's trunc returns NULL for day-level formats
    (Cast-style graceful null, not an error)."""

    def __init__(self, child, fmt: str = "month", allow_day: bool = False):
        super().__init__(child)
        self.fmt = fmt.lower()
        self.allow_day = allow_day

    @property
    def dtype(self):
        return date

    def eval(self, ctx):
        c = ctx.eval(self.child)
        if isinstance(c.dtype, TimestampType):
            c = cast_val(ctx, c, date)
        if not ctx.is_trace:
            return Val(date, None, c.validity, None)
        jnp = _jnp()
        y, m, d = _civil_from_days(c.data)
        one = jnp.ones_like(m)
        if self.fmt in ("year", "yyyy", "yy"):
            data = _days_from_civil(y, one, one)
        elif self.fmt in ("quarter",):
            qm = ((m - 1) // 3) * 3 + 1
            data = _days_from_civil(y, qm, one)
        elif self.fmt in ("month", "mon", "mm"):
            data = _days_from_civil(y, m, one)
        elif self.fmt in ("week",):
            dow = ((c.data.astype(jnp.int64) + 3) % 7).astype(jnp.int32)  # 0=Mon
            data = (c.data - dow).astype(jnp.int32)
        elif self.fmt in ("day", "dd"):
            if not self.allow_day:  # trunc(): day-level → NULL (Spark)
                return Val(date, jnp.zeros_like(c.data),
                           jnp.zeros((ctx.capacity,), bool), None)
            data = c.data  # already truncated to days by the date cast
        else:
            raise UnsupportedOperationError(f"trunc format {self.fmt}")
        return Val(date, data, c.validity, None)


class MakeDate(Expression):
    child_fields = ("y", "m", "d")

    def __init__(self, y, m, d):
        self.y = y
        self.m = m
        self.d = d

    @property
    def dtype(self):
        return date

    def eval(self, ctx):
        y = ctx.eval(cast_if(self.y, int32))
        m = ctx.eval(cast_if(self.m, int32))
        d = ctx.eval(cast_if(self.d, int32))
        v = ctx.and_valid(y, m, d)
        if not ctx.is_trace:
            return Val(date, None, v, None)
        return Val(date, _days_from_civil(y.data, m.data, d.data), v, None)


class DateAdd(BinaryExpression):
    @property
    def dtype(self):
        return date

    def eval(self, ctx):
        l = ctx.eval(self.left)
        r = ctx.eval(cast_if(self.right, int32))
        v = ctx.and_valid(l, r)
        if not ctx.is_trace:
            return Val(date, None, v, None)
        return Val(date, l.data + r.data, v, None)


class DateSub(BinaryExpression):
    @property
    def dtype(self):
        return date

    def eval(self, ctx):
        l = ctx.eval(self.left)
        r = ctx.eval(cast_if(self.right, int32))
        v = ctx.and_valid(l, r)
        if not ctx.is_trace:
            return Val(date, None, v, None)
        return Val(date, l.data - r.data, v, None)


class DateDiff(BinaryExpression):
    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        l = ctx.eval(cast_if(self.left, date))
        r = ctx.eval(cast_if(self.right, date))
        v = ctx.and_valid(l, r)
        if not ctx.is_trace:
            return Val(int32, None, v, None)
        return Val(int32, (l.data - r.data).astype(_jnp().int32), v, None)


class Hour(UnaryExpression):
    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        c = ctx.eval(cast_if(self.child, timestamp))
        if not ctx.is_trace:
            return Val(int32, None, c.validity, None)
        jnp = _jnp()
        us_in_day = jnp.mod(c.data, 86_400_000_000)
        return Val(int32, (us_in_day // 3_600_000_000).astype(jnp.int32),
                   c.validity, None)


class Minute(UnaryExpression):
    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        c = ctx.eval(cast_if(self.child, timestamp))
        if not ctx.is_trace:
            return Val(int32, None, c.validity, None)
        jnp = _jnp()
        us = jnp.mod(c.data, 3_600_000_000)
        return Val(int32, (us // 60_000_000).astype(jnp.int32),
                   c.validity, None)


class Second(UnaryExpression):
    @property
    def dtype(self):
        return int32

    def eval(self, ctx):
        c = ctx.eval(cast_if(self.child, timestamp))
        if not ctx.is_trace:
            return Val(int32, None, c.validity, None)
        jnp = _jnp()
        us = jnp.mod(c.data, 60_000_000)
        return Val(int32, (us // 1_000_000).astype(jnp.int32),
                   c.validity, None)


class UnixTimestamp(UnaryExpression):
    @property
    def dtype(self):
        return int64

    def eval(self, ctx):
        c = ctx.eval(cast_if(self.child, timestamp))
        if not ctx.is_trace:
            return Val(int64, None, c.validity, None)
        return Val(int64, _jnp().floor_divide(c.data, 1_000_000),
                   c.validity, None)


class FromUnixtime(UnaryExpression):
    @property
    def dtype(self):
        return timestamp

    def eval(self, ctx):
        c = ctx.eval(cast_if(self.child, int64))
        if not ctx.is_trace:
            return Val(timestamp, None, c.validity, None)
        return Val(timestamp, c.data * 1_000_000, c.validity, None)


class AddMonths(BinaryExpression):
    @property
    def dtype(self):
        return date

    def eval(self, ctx):
        l = ctx.eval(cast_if(self.left, date))
        r = ctx.eval(cast_if(self.right, int32))
        v = ctx.and_valid(l, r)
        if not ctx.is_trace:
            return Val(date, None, v, None)
        jnp = _jnp()
        y, m, d = _civil_from_days(l.data)
        total = (y.astype(jnp.int64) * 12 + (m - 1)) + r.data
        ny = jnp.floor_divide(total, 12).astype(jnp.int32)
        nm = (jnp.mod(total, 12) + 1).astype(jnp.int32)
        # clamp day to end of month
        next_month_total = total + 1
        nmy = jnp.floor_divide(next_month_total, 12).astype(jnp.int32)
        nmm = (jnp.mod(next_month_total, 12) + 1).astype(jnp.int32)
        one = jnp.ones_like(nm)
        days_in_month = (_days_from_civil(nmy, nmm, one)
                         - _days_from_civil(ny, nm, one)).astype(jnp.int32)
        nd = jnp.minimum(d, days_in_month)
        return Val(date, _days_from_civil(ny, nm, nd), v, None)


class LastDay(UnaryExpression):
    @property
    def dtype(self):
        return date

    def eval(self, ctx):
        c = ctx.eval(cast_if(self.child, date))
        if not ctx.is_trace:
            return Val(date, None, c.validity, None)
        jnp = _jnp()
        y, m, d = _civil_from_days(c.data)
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        one = jnp.ones_like(m)
        return Val(date,
                   (_days_from_civil(ny, nm, one) - 1).astype(jnp.int32),
                   c.validity, None)


class MonthsBetween(BinaryExpression):
    @property
    def dtype(self):
        return float64

    def eval(self, ctx):
        l = ctx.eval(cast_if(self.left, date))
        r = ctx.eval(cast_if(self.right, date))
        v = ctx.and_valid(l, r)
        if not ctx.is_trace:
            return Val(float64, None, v, None)
        jnp = _jnp()
        ly, lm, ld = _civil_from_days(l.data)
        ry, rm, rd = _civil_from_days(r.data)
        months = (ly - ry) * 12 + (lm - rm)
        frac = (ld - rd).astype(jnp.float64) / 31.0
        return Val(float64, months.astype(jnp.float64) + frac, v, None)


# ---------------------------------------------------------------------------
# Aggregate functions (evaluated by the aggregation operator, not eval())
# ---------------------------------------------------------------------------

class AggregateFunction(Expression):
    child_fields = ("child",)

    def __init__(self, child: Expression | None):
        self.child = child

    @property
    def nullable(self):
        return True

    def eval(self, ctx):
        raise AnalysisException(
            f"aggregate function {type(self).__name__} cannot be evaluated "
            "outside an aggregation")


class Sum(AggregateFunction):
    @property
    def dtype(self):
        ct = self.child.dtype
        if isinstance(ct, DecimalType):
            return DecimalType(DecimalType.MAX_PRECISION, ct.scale)
        if isinstance(ct, IntegralType):
            return int64
        return float64


class Count(AggregateFunction):
    def __init__(self, child: Expression | None = None, distinct: bool = False):
        super().__init__(child)
        self.distinct = distinct

    @property
    def dtype(self):
        return int64

    @property
    def nullable(self):
        return False


class Min(AggregateFunction):
    @property
    def dtype(self):
        return self.child.dtype


class Max(AggregateFunction):
    @property
    def dtype(self):
        return self.child.dtype


class Mode(AggregateFunction):
    """mode(col) — most frequent non-null value (reference:
    sqlcat/expressions/aggregate/Mode.scala). Never lowered directly:
    the optimizer rewrites it into count-per-value + max-count join +
    min-value tie-break (RewriteModeAggregate), so it runs on the same
    device segment kernels as every other aggregate. Deterministic on
    ties (smallest value), where the reference is unspecified."""

    @property
    def dtype(self):
        return self.child.dtype


class BitAndAgg(AggregateFunction):
    """bit_and(col) (reference: sqlcat/expressions/aggregate/
    bitwiseAggregates.scala) — device bit-plane segment reduce.
    Result keeps the input's integral type, like the reference."""

    kind = "and"

    @property
    def dtype(self):
        ct = self.child.dtype
        if not isinstance(ct, IntegralType):
            raise TypeCheckError(
                f"bit_{self.kind} requires an integral column, got "
                f"{ct.simple_string()}")
        return ct


class BitOrAgg(BitAndAgg):
    kind = "or"


class BitXorAgg(BitAndAgg):
    kind = "xor"


class Average(AggregateFunction):
    @property
    def dtype(self):
        ct = self.child.dtype
        if isinstance(ct, DecimalType):
            return DecimalType(
                min(ct.precision + 4, DecimalType.MAX_PRECISION),
                min(ct.scale + 4, 10))
        return float64


class First(AggregateFunction):
    def __init__(self, child: Expression, ignore_nulls: bool = True):
        super().__init__(child)
        self.ignore_nulls = ignore_nulls

    @property
    def dtype(self):
        return self.child.dtype


class AnyValue(First):
    pass


class _CentralMoment(AggregateFunction):
    ddof = 1

    @property
    def dtype(self):
        return float64


class StddevSamp(_CentralMoment):
    ddof = 1


class StddevPop(_CentralMoment):
    ddof = 0


class VarianceSamp(_CentralMoment):
    ddof = 1


class VariancePop(_CentralMoment):
    ddof = 0


class Percentile(AggregateFunction):
    """Exact percentile (the reference's percentile_approx computed exactly;
    non-mergeable, so the planner gathers before aggregating)."""

    def __init__(self, child: Expression, q: float):
        super().__init__(child)
        self.q = float(q)

    @property
    def dtype(self):
        ct = self.child.dtype
        return ct if isinstance(ct, (IntegralType, DateType, TimestampType,
                                     DecimalType)) else float64


class Median(Percentile):
    def __init__(self, child: Expression):
        super().__init__(child, 0.5)


class CollectSet(AggregateFunction):
    """collect_set (reference: sqlcat/expressions/aggregate/collect.scala)
    — non-mergeable here: the planner gathers to one partition; the lists
    are built host-side and dictionary-encoded (see ArrayType)."""

    @property
    def dtype(self):
        return ArrayType(self.child.dtype)


class CollectList(AggregateFunction):
    """collect_list (reference: collect.scala Collect/CollectList)."""

    @property
    def dtype(self):
        return ArrayType(self.child.dtype)
