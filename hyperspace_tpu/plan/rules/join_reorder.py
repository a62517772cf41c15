"""JoinReorderRule: reorder a tree of inner equi-joins so that a pair of
sides with compatible covering indexes is joined first.

A departure from the reference. JoinIndexRule.scala rewrites a join only
when both of its children are linear single-relation plans (:149-150),
so it never fires on a join whose pair of indexable sides sits in
different subtrees. TPC-H Q3 in the spec's FROM order is such a tree:
``(customer ⋈ orders) ⋈ lineitem`` puts ``orders`` and ``lineitem``,
which covering indexes on the order key make a shuffle-free bucketed
join, in different subtrees, and neither join is rewritten. Spark's
cost-based reorder (``spark.sql.cbo.joinReorder.enabled``) would move
them together given statistics; this rule does so given indexes.

The rule runs before JoinIndexRule. For a tree of inner joins whose
conditions are all ``Col == Col`` conjunctions over sides with distinct
column names, it looks for the first pair of leaves (in the tree's own
leaf order) that JoinIndexRule would rewrite if they were joined
directly. When the tree's innermost join is already such a pair, or no
pair qualifies, or some leaf could then only be joined by a cross join,
the tree is left alone. Otherwise the pair is joined first and the other
leaves follow in their order, each joined on every condition that links
it to those before it. Inner joins commute and associate, so the rows
are the same; a Project restores the tree's column order.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from ...config import HyperspaceConf
from ...index.log_entry import IndexLogEntry
from ...utils import resolver
from ..expr import Col, Expr
from ..ir import Join, LogicalPlan, Project
from . import rule_utils
from .join_rule import JoinIndexRule, extract_equi_condition
from .predicate_pushdown import conjoin


def _strip(plan: LogicalPlan) -> LogicalPlan:
    while isinstance(plan, Project):
        plan = plan.child
    return plan


def _is_join(plan: LogicalPlan) -> bool:
    inner = _strip(plan)
    return isinstance(inner, Join) and inner.join_type == "inner"


def _flatten(node: Join):
    """(leaves, equi pairs, the leaf pairs the tree joins directly) of a
    tree of inner equi-joins, looking through the Projects between
    them; None for any other condition."""
    leaves: List[LogicalPlan] = []
    pairs: List[Tuple[str, str]] = []
    direct: List[Tuple[int, int]] = []

    def walk(plan: LogicalPlan) -> bool:
        if not _is_join(plan):
            leaves.append(plan)
            return True
        inner = _strip(plan)
        got = extract_equi_condition(inner.condition)
        if got is None:
            return False
        pairs.extend(got)
        if not _is_join(inner.left) and not _is_join(inner.right):
            direct.append((len(leaves), len(leaves) + 1))
        return walk(inner.left) and walk(inner.right)

    return (leaves, pairs, direct) if walk(node) else None


def _owner(name: str, leaves: List[LogicalPlan]) -> Optional[int]:
    hits = [i for i, leaf in enumerate(leaves) if resolver.resolve(name, leaf.output_columns())]
    return hits[0] if len(hits) == 1 else None


def _condition(pairs: List[Tuple[str, str]]) -> Expr:
    return conjoin([Col(a) == Col(b) for a, b in pairs])


class JoinReorderRule:
    def apply(
        self,
        plan: LogicalPlan,
        indexes: List[IndexLogEntry],
        conf: HyperspaceConf,
    ) -> LogicalPlan:
        if not indexes:
            return plan

        def rewrite(node: LogicalPlan) -> Optional[LogicalPlan]:
            if not isinstance(node, Join) or node.join_type != "inner":
                return None
            if not _is_join(node.left) and not _is_join(node.right):
                return None  # two leaves: JoinIndexRule's own shape
            return self._reorder(node, indexes, conf)

        return plan.transform_up(rewrite)

    def _rewritable(self, a, b, pairs, indexes, conf) -> bool:
        probe = Join(a, b, _condition(pairs))
        return JoinIndexRule()._try_rewrite(probe, indexes, conf, []) is not None

    def _reorder(self, node: Join, indexes, conf) -> Optional[LogicalPlan]:
        flat = _flatten(node)
        if flat is None:
            return None
        leaves, pairs, direct = flat
        names = [c.lower() for leaf in leaves for c in leaf.output_columns()]
        if len(names) != len(set(names)):
            return None  # a column on two sides: conditions are ambiguous
        owned = []
        for a, b in pairs:
            ia, ib = _owner(a, leaves), _owner(b, leaves)
            if ia is None or ib is None or ia == ib:
                return None
            owned.append((ia, ib, a, b))

        def between(i: int, js) -> List[Tuple[str, str]]:
            return [(a, b) for ia, ib, a, b in owned
                    if (ia == i and ib in js) or (ib == i and ia in js)]

        def indexable(i: int, j: int) -> bool:
            link = between(i, {j})
            return bool(link) and all(
                rule_utils.is_linear(leaves[k]) and rule_utils.single_scan(leaves[k]) is not None
                for k in (i, j)
            ) and self._rewritable(leaves[i], leaves[j], link, indexes, conf)

        if any(indexable(i, j) for i, j in direct):
            return None  # the tree as written already joins such a pair
        first = next(((i, j) for i in range(len(leaves)) for j in range(i + 1, len(leaves))
                      if indexable(i, j)), None)
        if first is None:
            return None
        i, j = first
        acc: LogicalPlan = Join(leaves[i], leaves[j], _condition(between(i, {j})))
        done = {i, j}
        rest = [k for k in range(len(leaves)) if k not in done]
        while rest:
            k = next((k for k in rest if between(k, done)), None)
            if k is None:
                return None  # only a cross join would attach it
            acc = Join(acc, leaves[k], _condition(between(k, done)))
            done.add(k)
            rest.remove(k)
        out = node.output_columns()
        return acc if acc.output_columns() == out else Project(tuple(out), acc)
