"""Aggregate specs for group-by queries.

The reference delegates aggregation to Spark — its indexes accelerate the
scans and joins *below* an Aggregate (the TPC-H Q17 shape of the north
star: an aggregate over an index-rewritten join). This framework owns the
whole query path, so it carries a small aggregate layer: specs name an
input column, or an arithmetic expression over columns (``expr``, e.g.
TPC-H Q3's ``sum(l_extendedprice * (1 - l_discount))``), and a function;
the executor groups by factorized key codes and reduces with vectorized
segment operations. An expression's input is computed before grouping,
so its NULLs (NaN) are skipped like a column's.

NULL semantics follow SQL: NULL group keys form their own group;
``count(col)`` counts non-NULL values (string NULLs and float NaNs);
sum/avg/min/max skip NULLs; ``count(*)`` counts rows.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple, Union

from ..exceptions import HyperspaceException
from .expr import Col, Expr

_FNS = ("sum", "count", "min", "max", "avg")


@dataclass(frozen=True)
class AggSpec:
    fn: str  # sum | count | min | max | avg
    column: Optional[str]  # None for count(*) and for an expression input
    name: str  # output column name
    # an arithmetic input (plan.expr.Arith); ``column`` is then None
    expr: Optional[Expr] = None

    def __post_init__(self):
        if self.fn not in _FNS:
            raise HyperspaceException(
                f"Unknown aggregate {self.fn!r}; use one of {_FNS}."
            )
        if self.expr is not None and self.column is not None:
            raise HyperspaceException("An aggregate takes a column or an expression.")
        if self.column is None and self.expr is None and self.fn != "count":
            raise HyperspaceException(f"{self.fn} requires a column.")

    def input_columns(self) -> list:
        """The child columns this aggregate reads."""
        if self.expr is not None:
            return sorted(self.expr.columns())
        return [self.column] if self.column is not None else []

    def describe(self) -> str:
        arg = repr(self.expr) if self.expr is not None else (self.column or "*")
        return f"{self.fn}({arg}) AS {self.name}"


def _spec(fn: str, arg: Union[str, Expr, None], name: Optional[str]) -> AggSpec:
    """An AggSpec over a column name, a ``col(...)``, or an arithmetic
    expression (which needs an output ``name``)."""
    if isinstance(arg, Col):
        arg = arg.name
    if isinstance(arg, Expr):
        if name is None:
            raise HyperspaceException(f"{fn} over an expression needs a name.")
        return AggSpec(fn, None, name, arg)
    return AggSpec(fn, arg, name or (f"{fn}_{arg}" if arg else fn))


def agg_sum(column: Union[str, Expr], name: Optional[str] = None) -> AggSpec:
    return _spec("sum", column, name)


def agg_count(column: Union[str, Expr, None] = None, name: Optional[str] = None) -> AggSpec:
    return _spec("count", column, name)


def agg_min(column: Union[str, Expr], name: Optional[str] = None) -> AggSpec:
    return _spec("min", column, name)


def agg_max(column: Union[str, Expr], name: Optional[str] = None) -> AggSpec:
    return _spec("max", column, name)


def agg_avg(column: Union[str, Expr], name: Optional[str] = None) -> AggSpec:
    return _spec("avg", column, name)


def input_dtype(spec: AggSpec, schema) -> Optional[str]:
    """The dtype of one aggregate's input over the child ``schema``: the
    column's, the expression's (plan.expr.value_dtype), or None for
    count(*)."""
    if spec.expr is not None:
        from .expr import value_dtype

        return value_dtype(spec.expr, schema)
    return schema.get(spec.column) if spec.column else None


def output_dtype(spec: AggSpec, input_dtype: Optional[str]) -> str:
    """Result dtype of one aggregate (SQL-ish promotion rules)."""
    if spec.fn == "count":
        return "int64"
    if spec.fn == "avg":
        return "float64"
    if spec.fn == "sum":
        if input_dtype is None:
            return "int64"
        return "float64" if input_dtype.startswith("float") else "int64"
    return input_dtype or "string"  # min/max keep the input dtype


def validate_specs(specs: Tuple[AggSpec, ...], group_by: Tuple[str, ...]) -> None:
    seen = set(group_by)
    for s in specs:
        if s.name in seen:
            raise HyperspaceException(
                f"Duplicate output column {s.name!r} in aggregation."
            )
        seen.add(s.name)
