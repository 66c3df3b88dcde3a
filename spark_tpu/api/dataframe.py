"""DataFrame API.

Role of the reference's Dataset (sql/api .../Dataset.scala; classic impl
sql/core/.../classic/Dataset.scala) / pyspark.sql.DataFrame: a lazy wrapper
over a logical plan bound to a session.
"""

from __future__ import annotations

from typing import Any, Iterable, Sequence

import pyarrow as pa

from ..errors import AnalysisException
from ..exec.query_execution import QueryExecution
from ..expr import expressions as E
from ..plan import logical as L
from .column import Column, _expr


class Row(dict):
    """Dict-backed row with attribute access (pyspark.sql.Row analog)."""

    def __getattr__(self, k):
        try:
            return self[k]
        except KeyError as e:
            raise AttributeError(k) from e

    def __repr__(self):
        inner = ", ".join(f"{k}={v!r}" for k, v in self.items())
        return f"Row({inner})"


def _to_expr_list(cols, allow_str=True) -> list[E.Expression]:
    out = []
    for c in cols:
        if isinstance(c, Column):
            out.append(c.expr)
        elif isinstance(c, E.Expression):
            out.append(c)
        elif isinstance(c, str) and allow_str:
            if c == "*":
                out.append(E.UnresolvedStar())
            else:
                out.append(E.UnresolvedAttribute(c.split(".")))
        else:
            out.append(E.Literal(c))
    return out


class DataFrame:
    def __init__(self, session, plan: L.LogicalPlan):
        self.session = session
        self.plan = plan
        self._qe: QueryExecution | None = None

    # ------------------------------------------------------------------
    def _with(self, plan: L.LogicalPlan) -> "DataFrame":
        df = DataFrame(self.session, plan)
        df._watermark = getattr(self, "_watermark", None)
        return df

    # --- streaming -----------------------------------------------------
    @property
    def isStreaming(self) -> bool:
        from ..streaming.query import StreamingRelation

        return any(isinstance(n, StreamingRelation)
                   for n in self.plan.iter_nodes())

    def withWatermark(self, column: str, delay: str) -> "DataFrame":
        parts = delay.split()
        v = float(parts[0])
        unit = parts[1] if len(parts) > 1 else "seconds"
        mult = {"millisecond": 1e-3, "second": 1.0, "minute": 60.0,
                "hour": 3600.0, "day": 86400.0}
        for k, m in mult.items():
            if unit.startswith(k) or unit.rstrip("s").startswith(k):
                v *= m
                break
        df = self._with(L.EventTimeWatermark(column, int(v * 1e6),
                                             self.plan))
        df._watermark = (column, v)
        return df

    @property
    def writeStream(self):
        from ..streaming.api import DataStreamWriter

        return DataStreamWriter(self)

    @property
    def query_execution(self) -> QueryExecution:
        if self._qe is None:
            self._qe = QueryExecution(self.session, self.plan)
        return self._qe

    # --- schema -------------------------------------------------------
    @property
    def schema(self):
        return self.query_execution.analyzed.schema()

    @property
    def columns(self) -> list[str]:
        return [a.name for a in self.query_execution.analyzed.output]

    @property
    def dtypes(self) -> list[tuple[str, str]]:
        return [(f.name, f.dataType.simple_string()) for f in self.schema]

    def printSchema(self) -> None:
        for f in self.schema:
            print(f" |-- {f.name}: {f.dataType.simple_string()} "
                  f"(nullable = {str(f.nullable).lower()})")

    def __getitem__(self, item):
        if isinstance(item, str):
            for a in self.query_execution.analyzed.output:
                if a.name == item:
                    return Column(a)
            from ..errors import UnresolvedColumnError

            raise UnresolvedColumnError(item, self.columns[:5])
        if isinstance(item, (list, tuple)):
            return self.select(*item)
        if isinstance(item, Column):
            return self.filter(item)
        raise TypeError(f"cannot index DataFrame with {type(item)}")

    # --- transformations ----------------------------------------------
    def select(self, *cols) -> "DataFrame":
        if not cols:
            cols = ("*",)
        return self._with(L.Project(_to_expr_list(cols), self.plan))

    def selectExpr(self, *exprs: str) -> "DataFrame":
        from ..sql.parser import parse_expression

        return self._with(L.Project(
            [parse_expression(e) for e in exprs], self.plan))

    def filter(self, condition) -> "DataFrame":
        if isinstance(condition, str):
            from ..sql.parser import parse_expression

            cond = parse_expression(condition)
        else:
            cond = _expr(condition)
        return self._with(L.Filter(cond, self.plan))

    where = filter

    def withColumn(self, name: str, col: Column) -> "DataFrame":
        exprs: list[E.Expression] = []
        replaced = False
        for a in self.query_execution.analyzed.output:
            if a.name == name:
                exprs.append(E.Alias(_expr(col), name))
                replaced = True
            else:
                exprs.append(a)
        if not replaced:
            exprs.append(E.Alias(_expr(col), name))
        return self._with(L.Project(exprs, self.plan))

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = []
        for a in self.query_execution.analyzed.output:
            if a.name == old:
                exprs.append(E.Alias(a, new))
            else:
                exprs.append(a)
        return self._with(L.Project(exprs, self.plan))

    def drop(self, *names: str) -> "DataFrame":
        keep = [a for a in self.query_execution.analyzed.output
                if a.name not in names]
        return self._with(L.Project(keep, self.plan))

    def alias(self, alias: str) -> "DataFrame":
        return self._with(L.SubqueryAlias(alias, self.plan))

    def distinct(self) -> "DataFrame":
        return self._with(L.Distinct(self.plan))

    def dropDuplicates(self, subset: Sequence[str] | None = None) -> "DataFrame":
        if subset is None:
            return self.distinct()
        group = _to_expr_list(subset)
        out = []
        names = set(subset)
        for a in self.query_execution.analyzed.output:
            if a.name in names:
                out.append(a)
            else:
                out.append(E.Alias(E.First(a), a.name))
        return self._with(L.Aggregate(group, out, self.plan))

    def limit(self, n: int) -> "DataFrame":
        return self._with(L.Limit(n, self.plan))

    def offset(self, n: int) -> "DataFrame":
        return self._with(L.Offset(n, self.plan))

    def sort(self, *cols, ascending=None) -> "DataFrame":
        orders = []
        exprs = _to_expr_list(cols)
        if ascending is None:
            asc_list = [True] * len(exprs)
        elif isinstance(ascending, bool):
            asc_list = [ascending] * len(exprs)
        else:
            asc_list = list(ascending)
        for e, a in zip(exprs, asc_list):
            if isinstance(e, E.SortOrder):
                orders.append(e)
            else:
                orders.append(E.SortOrder(e, a))
        return self._with(L.Sort(orders, True, self.plan))

    orderBy = sort

    def sortWithinPartitions(self, *cols) -> "DataFrame":
        exprs = _to_expr_list(cols)
        orders = [e if isinstance(e, E.SortOrder) else E.SortOrder(e, True)
                  for e in exprs]
        return self._with(L.Sort(orders, False, self.plan))

    def repartition(self, num_or_col, *cols) -> "DataFrame":
        if isinstance(num_or_col, int):
            exprs = _to_expr_list(cols)
            return self._with(L.Repartition(num_or_col, True, exprs, self.plan))
        exprs = _to_expr_list((num_or_col,) + cols)
        return self._with(L.Repartition(None, True, exprs, self.plan))

    def coalesce(self, n: int) -> "DataFrame":
        return self._with(L.Repartition(n, False, [], self.plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._with(L.Union([self.plan, other.plan]))

    unionAll = union

    def join(self, other: "DataFrame", on=None, how: str = "inner") -> "DataFrame":
        cond = None
        if on is not None:
            if isinstance(on, Column):
                cond = on.expr
            elif isinstance(on, str):
                on = [on]
            if isinstance(on, (list, tuple)):
                conds = None
                for name in on:
                    c = E.EqualTo(
                        _resolve_using(self, name),
                        _resolve_using(other, name))
                    conds = c if conds is None else E.And(conds, c)
                cond = conds
                # USING semantics: output merges the key columns
                joined = L.Join(self.plan, other.plan, how, cond)
                df = self._with(joined)
                drop_ids = {_resolve_using(other, name).expr_id for name in on}
                keep = [a for a in df.query_execution.analyzed.output
                        if a.expr_id not in drop_ids]
                return df._with(L.Project(
                    keep, df.query_execution.analyzed))
        return self._with(L.Join(self.plan, other.plan, how, cond))

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return self._with(L.Join(self.plan, other.plan, "cross", None))

    def groupBy(self, *cols) -> "GroupedData":
        return GroupedData(self, _to_expr_list(cols))

    groupby = groupBy

    def rollup(self, *cols) -> "GroupedData":
        return GroupedData(self, _to_expr_list(cols), sets_kind="rollup")

    def cube(self, *cols) -> "GroupedData":
        return GroupedData(self, _to_expr_list(cols), sets_kind="cube")

    def agg(self, *cols) -> "DataFrame":
        return GroupedData(self, []).agg(*cols)

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        return self._with(L.Sample(fraction, seed, self.plan))

    def mapInPandas(self, fn, schema) -> "DataFrame":
        """Apply fn(pandas.DataFrame) -> pandas.DataFrame per partition
        (reference: Dataset.mapInPandas over MapInPandasExec). Host
        evaluation: partitions cross as Arrow, results re-enter the engine."""
        import pandas as pd
        import pyarrow as pa

        if isinstance(schema, str):
            from ..sql.parser import parse_data_type  # noqa: F401

            raise ValueError("pass a StructType schema")
        parts = self.query_execution.execute()
        from ..physical.operators import attrs_schema
        from ..types import to_arrow_type

        out_tables = []
        for p in parts:
            for b in p:
                pdf = b.to_arrow().to_pandas()
                res = fn(pdf)
                out_tables.append(pa.Table.from_pandas(
                    res, preserve_index=False))
        merged = pa.concat_tables(out_tables, promote_options="permissive") \
            if out_tables else pa.table(
                {f.name: pa.array([], to_arrow_type(f.dataType))
                 for f in schema.fields})
        return self.session.createDataFrame(merged)

    def describe(self, *cols: str) -> "DataFrame":
        """Summary statistics for numeric columns
        (reference: Dataset.describe / StatFunctions)."""
        import pyarrow as pa

        from ..types import NumericType

        targets = [f.name for f in self.schema
                   if isinstance(f.dataType, NumericType)
                   and (not cols or f.name in cols)]
        if not targets:
            return self.session.createDataFrame(
                pa.table({"summary": pa.array([], pa.string())}))
        import spark_tpu.api.functions as FN

        aggs = []
        for c in targets:
            aggs += [FN.count(c).alias(f"count_{c}"),
                     FN.avg(c).alias(f"mean_{c}"),
                     FN.stddev(c).alias(f"stddev_{c}"),
                     FN.min(c).alias(f"min_{c}"),
                     FN.max(c).alias(f"max_{c}")]
        row = self.agg(*aggs).collect()[0]
        stats = ["count", "mean", "stddev", "min", "max"]
        data = {"summary": stats}
        for c in targets:
            data[c] = [str(row[f"{s}_{c}"]) for s in stats]
        return self.session.createDataFrame(pa.table(data))

    summary = describe

    # --- actions -------------------------------------------------------
    def toArrow(self) -> pa.Table:
        return self.query_execution.to_arrow()

    def toPandas(self):
        return self.toArrow().to_pandas()

    def collect(self) -> list[Row]:
        t = self.toArrow()
        return [Row(zip(t.column_names, vals))
                for vals in zip(*[c.to_pylist() for c in t.columns])] \
            if t.num_columns else []

    def count(self) -> int:
        agg = L.Aggregate([], [E.Alias(E.Count(None), "count")], self.plan)
        t = QueryExecution(self.session, agg).to_arrow()
        return int(t.column(0)[0].as_py())

    def first(self) -> Row | None:
        rows = self.limit(1).collect()
        return rows[0] if rows else None

    def head(self, n: int = 1):
        rows = self.limit(n).collect()
        return rows[0] if n == 1 and rows else rows

    def take(self, n: int) -> list[Row]:
        return self.limit(n).collect()

    def isEmpty(self) -> bool:
        return len(self.take(1)) == 0

    def show(self, n: int = 20, truncate: bool = True) -> None:
        t = self.limit(n).toArrow()
        names = t.column_names
        rows = [[_fmt(v, truncate) for v in col.to_pylist()]
                for col in t.columns]
        widths = [max([len(nm)] + [len(r[i]) for i in range(len(r))])
                  for nm, r in zip(names, rows)] if t.num_rows else \
                 [len(nm) for nm in names]
        sep = "+" + "+".join("-" * (w + 2) for w in widths) + "+"
        print(sep)
        print("|" + "|".join(f" {nm:<{w}} " for nm, w in zip(names, widths)) + "|")
        print(sep)
        for ri in range(t.num_rows):
            print("|" + "|".join(
                f" {rows[ci][ri]:<{widths[ci]}} " for ci in range(len(names)))
                + "|")
        print(sep)

    def explain(self, mode: str = "formatted") -> None:
        """Print the query plans. mode="analysis" additionally runs the
        static plan analyzer (spark_tpu/analysis/plan_lint.py): predicted
        kernel launches per batch per stage, fusion-boundary explanations,
        recompile/overflow hazards — the EXPLAIN CODEGEN analog.
        mode="analyze" EXECUTES the query (one warm run + one measured
        run) and renders the physical plan annotated with measured
        per-operator metrics — rows, wall-ms, attributed kernel launches
        and compile-ms, including inside whole-stage fused operators —
        side by side with the static predictions, flagging drift
        (obs/metrics.AnalyzedReport; the EXPLAIN ANALYZE analog).
        mode="device" EXECUTES the query too (one warm run + one run
        under the jax profiler) and renders, for each whole-query program
        the run launched, the device time of every operator and of the
        kernel bodies inside it (sort, probe, expand, segment_reduce,
        ...), discarded attempts apart (obs/device_profile.py)."""
        print(self.query_execution.explain_string(mode))

    def createOrReplaceTempView(self, name: str) -> None:
        self.session.catalog_.register(name, self.plan)

    def cache(self) -> "DataFrame":
        return self.session._cache_df(self)

    persist = cache

    def unpersist(self) -> "DataFrame":
        return self.session._uncache_df(self)

    def write_parquet(self, path: str) -> None:
        import pyarrow.parquet as pq

        pq.write_table(self.toArrow(), path)

    @property
    def write(self):
        from .readwriter import DataFrameWriter

        return DataFrameWriter(self)

    @property
    def stat(self):
        from .stat import DataFrameStatFunctions

        return DataFrameStatFunctions(self)

    @property
    def na(self):
        from .na import DataFrameNaFunctions

        return DataFrameNaFunctions(self)

    @property
    def rdd(self):
        """Materialize into the RDD layer as Row objects (reference:
        Dataset.rdd). Partition structure is preserved."""
        from ..rdd import RDDContext

        parts = self.query_execution.execute()
        names = self.columns
        rows: list[Row] = []
        splits: list[int] = []
        for p in parts:
            start = len(rows)
            for b in p:
                d = b.to_pydict()
                for vals in zip(*[d[n] for n in names]) if names else []:
                    rows.append(Row(zip(names, vals)))
            splits.append(len(rows) - start)
        sc = getattr(self.session, "_rdd_context", None)
        if sc is None:
            sc = RDDContext(parallelism=max(len(parts), 1))
            self.session._rdd_context = sc
        return sc.parallelize(rows, max(len(parts), 1))

    def fillna(self, value, subset=None) -> "DataFrame":
        return self.na.fill(value, subset)

    def dropna(self, how: str = "any", subset=None) -> "DataFrame":
        return self.na.drop(how, subset)

    def replace(self, to_replace, value=None, subset=None) -> "DataFrame":
        return self.na.replace(to_replace, value, subset)

    def unpivot(self, ids, values, variableColumnName: str = "variable",
                valueColumnName: str = "value") -> "DataFrame":
        """Wide→long (reference: Dataset.unpivot / melt): a union of one
        projection per value column."""
        ids = [ids] if isinstance(ids, str) else list(ids)
        values = [values] if isinstance(values, str) else list(values)
        branches = []
        for v in values:
            branches.append(self.select(
                *ids,
                Column(E.Alias(E.Literal(v), variableColumnName)),
                Column(E.Alias(E.UnresolvedAttribute([v]),
                               valueColumnName))).plan)
        return self._with(L.Union(branches))

    melt = unpivot


def _fmt(v, truncate: bool) -> str:
    s = "NULL" if v is None else str(v)
    if truncate and len(s) > 20:
        s = s[:17] + "..."
    return s


def _resolve_using(df: DataFrame, name: str) -> E.AttributeReference:
    for a in df.query_execution.analyzed.output:
        if a.name.lower() == name.lower():
            return a
    raise AnalysisException(f"USING column {name} not found")


class GroupedData:
    """Role of RelationalGroupedDataset."""

    def __init__(self, df: DataFrame, grouping: list[E.Expression],
                 pivot_col: str | None = None,
                 pivot_values: list | None = None,
                 sets_kind: str | None = None):
        self.df = df
        self.grouping = grouping
        self._pivot_col = pivot_col
        self._pivot_values = pivot_values
        self._sets_kind = sets_kind

    def pivot(self, pivot_col: str, values: list | None = None
              ) -> "GroupedData":
        """Pivot (reference: RelationalGroupedDataset.pivot): each pivot
        value becomes a conditional aggregate column."""
        if values is None:
            import spark_tpu.api.functions as FN

            vals = (self.df.select(pivot_col).distinct()
                    .orderBy(pivot_col).toArrow().column(0).to_pylist())
            values = [v for v in vals if v is not None]
        return GroupedData(self.df, self.grouping, pivot_col, list(values))

    def agg(self, *cols) -> DataFrame:
        aggs = _to_expr_list(cols, allow_str=False)
        if self._pivot_col is not None:
            aggs = self._pivot_aggs(aggs)
        out = list(self.grouping) + aggs
        if self._sets_kind is not None:
            n = len(self.grouping)
            if self._sets_kind == "rollup":
                sets = [list(range(n - i)) for i in range(n + 1)]
            else:  # cube
                import itertools as _it

                sets = [list(c) for k in range(n, -1, -1)
                        for c in _it.combinations(range(n), k)]
            return self.df._with(
                L.GroupingSets(sets, self.grouping, out, self.df.plan))
        return self.df._with(L.Aggregate(self.grouping, out, self.df.plan))

    def _pivot_aggs(self, aggs: list[E.Expression]) -> list[E.Expression]:
        pivot_attr = E.UnresolvedAttribute([self._pivot_col])
        out: list[E.Expression] = []
        for v in self._pivot_values:
            for a in aggs:
                inner = a.child if isinstance(a, E.Alias) else a
                base = a.name if isinstance(a, E.Alias) else None

                def guard(x: E.Expression) -> E.Expression:
                    if isinstance(x, E.AggregateFunction) and \
                            x.child is not None:
                        return x.copy(child=E.If(
                            E.EqualTo(pivot_attr, E.Literal(v)),
                            x.child, E.Literal(None)))
                    if isinstance(x, E.Count) and x.child is None:
                        return E.Count(E.If(
                            E.EqualTo(pivot_attr, E.Literal(v)),
                            E.Literal(1), E.Literal(None)))
                    return x

                guarded = inner.transform_up(guard)
                name = str(v) if len(aggs) == 1 and base is None \
                    else (f"{v}_{base}" if base else f"{v}_{len(out)}")
                out.append(E.Alias(guarded, name))
        return out

    def count(self) -> DataFrame:
        return self.agg(Column(E.Alias(E.Count(None), "count")))

    def applyInPandasWithState(self, fn, schema) -> DataFrame:
        """Arbitrary stateful grouped-map (reference:
        applyInPandasWithState / flatMapGroupsWithState): lazy — on a
        streaming frame each micro-batch calls
        fn(key_tuple, pandas_frame, GroupState); on a static frame one
        pass runs with empty initial state."""
        from ..streaming.stateful_map import StatefulMapGroups

        key_names = []
        for g in self.grouping:
            if isinstance(g, E.UnresolvedAttribute):
                key_names.append(g.name_parts[-1])
            elif isinstance(g, (E.AttributeReference, E.Alias)):
                key_names.append(g.name)
            else:
                raise ValueError("grouping keys must be columns")
        out_attrs = [E.AttributeReference(f.name, f.dataType, True)
                     for f in schema.fields]
        return self.df._with(StatefulMapGroups(
            key_names, fn, out_attrs, self.df.plan))

    def applyInPandas(self, fn, schema=None) -> DataFrame:
        """Grouped-map pandas UDF (reference: FlatMapGroupsInPandasExec /
        RelationalGroupedDataset.applyInPandas): the full frame crosses to
        the host once, pandas groups by the keys, fn runs per group."""
        import pandas as pd
        import pyarrow as pa

        key_names = []
        for g in self.grouping:
            if isinstance(g, E.UnresolvedAttribute):
                key_names.append(g.name_parts[-1])
            elif isinstance(g, E.AttributeReference):
                key_names.append(g.name)
            elif isinstance(g, E.Alias):
                key_names.append(g.name)
            else:
                raise ValueError(
                    "applyInPandas grouping keys must be columns")
        pdf = self.df.toPandas()
        outs = []
        if len(pdf):
            for _, grp in pdf.groupby(key_names, sort=True, dropna=False):
                outs.append(fn(grp.reset_index(drop=True)))
        if outs:
            merged = pa.concat_tables(
                [pa.Table.from_pandas(o, preserve_index=False)
                 for o in outs], promote_options="permissive")
        else:
            from ..types import to_arrow_type

            merged = pa.table(
                {f.name: pa.array([], to_arrow_type(f.dataType))
                 for f in (schema.fields if schema else [])})
        return self.df.session.createDataFrame(merged)

    def sum(self, *names: str) -> DataFrame:  # noqa: A003
        return self.agg(*[Column(E.Sum(E.UnresolvedAttribute([n])))
                          for n in names])

    def avg(self, *names: str) -> DataFrame:
        return self.agg(*[Column(E.Average(E.UnresolvedAttribute([n])))
                          for n in names])

    mean = avg

    def min(self, *names: str) -> DataFrame:  # noqa: A003
        return self.agg(*[Column(E.Min(E.UnresolvedAttribute([n])))
                          for n in names])

    def max(self, *names: str) -> DataFrame:  # noqa: A003
        return self.agg(*[Column(E.Max(E.UnresolvedAttribute([n])))
                          for n in names])
