"""DataFrame: the user-facing lazy query handle over a logical plan.

The reference piggybacks on Spark's DataFrame; here the framework owns it.
``collect()`` runs the optimizer batch (when the session has Hyperspace
enabled) and executes on the session's mesh/device. Index usage telemetry
is emitted exactly when a rewrite fired (HyperspaceEvent.scala:150-156).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Union

from .exceptions import HyperspaceException
from .plan.expr import Expr
from .plan.ir import Filter, Join, LogicalPlan, Project
from .session import HyperspaceSession
from .storage.columnar import ColumnarBatch
from .telemetry import HyperspaceIndexUsageEvent
from .telemetry.logging import EventLogging


@dataclass(frozen=True)
class SortOrder:
    """One ORDER BY key: a column and its direction (``asc``/``desc``)."""

    column: str
    ascending: bool = True


def asc(column: str) -> SortOrder:
    return SortOrder(column, True)


def desc(column: str) -> SortOrder:
    return SortOrder(column, False)


class DataFrame(EventLogging):
    def __init__(self, session: HyperspaceSession, plan: LogicalPlan):
        self.session = session
        self.plan = plan

    # -- transformations -----------------------------------------------------
    def filter(self, condition: Expr) -> "DataFrame":
        # analyzer-style normalization: Col references resolve to the
        # child schema's canonical case (Spark's case-insensitive
        # resolution, which the reference inherits)
        from .plan.expr import resolve_expr_columns

        condition = resolve_expr_columns(
            condition, self.plan.output_columns()
        )
        return DataFrame(self.session, Filter(condition, self.plan))

    where = filter

    def select(self, *columns: str) -> "DataFrame":
        missing = [
            c for c in columns
            if c.lower() not in {o.lower() for o in self.plan.output_columns()}
        ]
        if missing:
            raise HyperspaceException(f"Unknown columns: {missing}.")
        resolved = []
        out = self.plan.output_columns()
        for c in columns:
            resolved.append(next(o for o in out if o.lower() == c.lower()))
        return DataFrame(self.session, Project(tuple(resolved), self.plan))

    def join(self, other: "DataFrame", condition: Expr, how: str = "inner") -> "DataFrame":
        if self.session is not other.session:
            raise HyperspaceException("Cannot join DataFrames from different sessions.")
        from .plan.expr import resolve_expr_columns

        condition = resolve_expr_columns(
            condition,
            list(self.plan.output_columns()) + list(other.plan.output_columns()),
        )
        return DataFrame(self.session, Join(self.plan, other.plan, condition, how))

    def create_or_replace_temp_view(self, name: str) -> None:
        """Register this DataFrame's logical plan under ``name``
        (Spark's createOrReplaceTempView): ``session.table(name)``
        queries rewrite against indexes exactly like this DataFrame."""
        self.session.catalog.create_or_replace_temp_view(name, self)

    def group_by(self, *columns: str) -> "GroupedData":
        """Hash-aggregate entry point: ``df.group_by("k").agg(agg_sum("v"))``
        (specs from plan.aggregates). No columns = global aggregate."""
        from .utils import resolver

        out = self.plan.output_columns()
        resolved = []
        for c in columns:
            match = resolver.resolve(c, out)
            if match is None:
                raise HyperspaceException(f"Unknown group-by column: {c}.")
            resolved.append(match)
        return GroupedData(self, tuple(resolved))

    groupBy = group_by

    def order_by(self, *keys: Union[str, SortOrder]) -> "DataFrame":
        """ORDER BY: each key a column name (ascending) or ``asc(c)`` /
        ``desc(c)``."""
        from .plan.ir import Sort
        from .utils import resolver

        if not keys:
            raise HyperspaceException("order_by() needs at least one key.")
        out = self.plan.output_columns()
        names, ascending = [], []
        for k in keys:
            k = k if isinstance(k, SortOrder) else SortOrder(k)
            match = resolver.resolve(k.column, out)
            if match is None:
                raise HyperspaceException(f"Unknown sort column: {k.column}.")
            names.append(match)
            ascending.append(k.ascending)
        return DataFrame(self.session, Sort(tuple(names), tuple(ascending), self.plan))

    orderBy = order_by

    def limit(self, n: int) -> "DataFrame":
        """LIMIT: the first ``n`` rows."""
        from .plan.ir import Limit

        return DataFrame(self.session, Limit(int(n), self.plan))

    # -- actions -------------------------------------------------------------
    def optimized_plan(self, log_usage: bool = False) -> LogicalPlan:
        """The plan after the Hyperspace rule batch (identity when
        disabled). Usage telemetry is emitted only from executed queries
        (``log_usage=True``, set by collect()) — one event per execution,
        as in HyperspaceEvent.scala:150-156."""
        from .plan.rules.column_pruning import prune_columns
        from .plan.rules.predicate_pushdown import push_filters_through_joins

        # Catalyst's normalization batches run before the reference's rules
        # see a plan; ours must too: side predicates move through inner
        # joins (so filtered-join shapes stay linear for the index rules),
        # then column pruning narrows every scan.
        pruned = prune_columns(push_filters_through_joins(self.plan))
        if not self.session.is_hyperspace_enabled():
            return pruned
        from .actions import states
        from .plan.rules import apply_hyperspace_rules

        indexes = self.session.collection_manager.get_indexes(
            [states.ACTIVE], prefer_stable=True
        )
        new_plan, applied = apply_hyperspace_rules(pruned, indexes, self.session.conf)
        if applied and log_usage:
            self.log_event(
                self.session.conf,
                HyperspaceIndexUsageEvent(
                    indexes=[e.name for e in applied],
                    plan_before=self.plan.tree_string(),
                    plan_after=new_plan.tree_string(),
                ),
            )
        return new_plan

    def collect(self) -> ColumnarBatch:
        from .exec.executor import Executor
        from .telemetry.metrics import metrics
        from .telemetry.recorder import flight_recorder
        from .telemetry.trace import span, start_trace

        import contextlib

        executor = Executor(self.session.conf, mesh=self.session.mesh)
        # per-query span trace (telemetry.trace): plan -> execute stage
        # boundaries, recorded into the flight recorder on completion;
        # its meta is the ONE record explain(verbose) renders from
        tracing = self.session.conf.telemetry_tracing_enabled()
        trace_cm = (
            start_trace("query.collect") if tracing else contextlib.nullcontext()
        )
        with trace_cm as qtrace:
            try:
                with span("plan.optimize"):
                    plan = self.optimized_plan(log_usage=True)
                profile_dir = self.session.conf.profile_dir()
                if profile_dir:
                    # XLA-level trace (per-op device timing, HLO) for
                    # this query — view with tensorboard/xprof;
                    # complements the engine-level metrics registry
                    # (SURVEY §5.1)
                    import jax

                    tracer = jax.profiler.trace(profile_dir)
                else:
                    tracer = contextlib.nullcontext()
                # per-query scoped registry: global counters accumulate
                # exactly as before, and this query's own share lands on
                # the trace meta for explain(verbose) — concurrent
                # queries each see only their own
                with tracer, metrics.scoped() as query_metrics:
                    with span("query.execute"):
                        result = executor.execute(plan)
            except BaseException as e:
                # a FAILED query is exactly the trace a post-mortem
                # needs: finish it errored and ring it before re-raising
                # (the serve path records errored tickets the same way)
                if qtrace is not None:
                    qtrace.finish(e)
                    flight_recorder.record(qtrace)
                raise
        if qtrace is not None:
            qtrace.meta["metrics"] = query_metrics.snapshot()
            # whole-plan compilation attribution: which pipeline (fused
            # subtree boundary, serving tier) the query rode
            pipeline = executor.last_pipeline
            qtrace.meta["pipeline"] = (
                pipeline.describe() if pipeline is not None else None
            )
            qtrace.finish()
            self.session.last_trace = qtrace
            flight_recorder.record(qtrace)
        else:
            # tracing off: clear the attribution rather than let
            # explain(verbose) describe a PREVIOUS query as this one
            self.session.last_trace = None
        return result

    def to_pandas(self):
        return self.collect().to_pandas()

    def show(self, n: int = 20) -> None:
        """Print the first ``n`` rows (the df.show() notebook idiom the
        reference exposes through Spark; SPARK/sql/hyperspace/utils
        showString shim). Only the shown rows are converted to pandas."""
        import numpy as np

        batch = self.collect()
        head = batch.take(np.arange(min(n, batch.num_rows)))
        print(head.to_pandas().to_string(index=False))
        if batch.num_rows > n:
            print(f"... ({batch.num_rows - n} more rows)")

    def count(self) -> int:
        return self.collect().num_rows

    def columns(self) -> List[str]:
        return self.plan.output_columns()

    def explain(self, verbose: bool = False) -> str:
        from .plananalysis.plan_analyzer import explain_string

        return explain_string(self, verbose=verbose)


class GroupedData:
    """``df.group_by(...)`` result: call ``agg`` with AggSpecs (or use the
    ``count`` shorthand) to get the aggregated DataFrame."""

    def __init__(self, df: DataFrame, group_by):
        self._df = df
        self._group_by = group_by

    def agg(self, *specs) -> DataFrame:
        from dataclasses import replace as dc_replace

        from .plan.aggregates import AggSpec, input_dtype, validate_specs
        from .plan.expr import resolve_expr_columns
        from .plan.ir import Aggregate
        from .utils import resolver

        if not specs:
            raise HyperspaceException("agg() needs at least one AggSpec.")
        out = self._df.plan.output_columns()
        resolved = []
        for s in specs:
            if not isinstance(s, AggSpec):
                raise HyperspaceException(f"Not an AggSpec: {s!r}.")
            if s.column is not None:
                match = resolver.resolve(s.column, out)
                if match is None:
                    raise HyperspaceException(
                        f"Unknown aggregate column: {s.column}."
                    )
                s = dc_replace(s, column=match)
            if s.expr is not None:
                s = dc_replace(s, expr=resolve_expr_columns(s.expr, out))
                input_dtype(s, self._df.plan.output_schema())  # raises if unknown
            resolved.append(s)
        validate_specs(tuple(resolved), self._group_by)
        return DataFrame(
            self._df.session,
            Aggregate(self._group_by, tuple(resolved), self._df.plan),
        )

    def count(self) -> DataFrame:
        from .plan.aggregates import agg_count

        return self.agg(agg_count())
