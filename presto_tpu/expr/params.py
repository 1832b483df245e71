"""Literals as program inputs.

A traced program that holds its literals is a program for one statement:
the next DATE or DISCOUNT is another plan value, another key in the
program cache, another trace, lowering and compile. Before an island is
lowered, `lift_plan` takes the literals of its Filter / Project / join
filter expressions whose value changes neither a shape nor a dictionary
out of the plan: each becomes a `Param` (type and place kept, value
gone) and its value a 0-d array of the parameter tuple the jitted island
takes beside its pages. The blanked plan is what the program cache, the
plan fingerprint and the learned capacities are keyed by.

Lifted: BOOLEAN, the integers, DATE, TIMESTAMP, REAL / DOUBLE and short
DECIMAL, where not NULL and where the expression compiler evaluates the
literal as a column. Kept in the plan, and so in the key: strings (their
dictionary work is trace-time), NULLs, long decimals, and a literal that
a function reads while it is traced (a LIKE pattern, `substr`'s
positions, `round`'s digits, `date_trunc`'s unit). `IN`-list lengths,
LIMIT / TopN counts and VALUES rows are plan structure and stay.

The host descales: a short-decimal literal that the compiler would cast
to a double (compared with, multiplied by, or cast to one) becomes that
double here, by Python's correctly rounded division. The TPU emulates
float64, and its division rounds low: `5 / 100` on the device is not
0.05, and `l_discount >= 0.05` lost every row at the edge.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

from presto_tpu.expr.compile import binding, compile_expr
from presto_tpu.expr.nodes import (
    Call, Form, Literal, Param, RowExpression, SpecialForm,
)
from presto_tpu.plan.nodes import FilterNode, JoinNode, ProjectNode
from presto_tpu.types import DOUBLE, DecimalType, Type

_ARITH = frozenset(("add", "subtract", "multiply", "divide", "modulus"))
_COMPARE = frozenset(("eq", "ne", "lt", "le", "gt", "ge"))
#: calls that take their argument's float64 image (`compile._as_f64`)
_F64_MATH = frozenset(("sqrt", "ln", "log10", "exp", "floor", "ceil",
                       "power", "cbrt", "log2", "truncate"))
#: calls that evaluate every argument as a column. Any other call may
#: read a literal argument while it is traced, so its literals stay
_VALUE_CALLS = _ARITH | _COMPARE | _F64_MATH | frozenset((
    "not", "negate", "abs", "sign", "cast", "date_add_days", "greatest",
    "least", "extract_year", "extract_month", "extract_day", "year",
    "month", "day"))


def liftable(t: Type) -> bool:
    """A type whose literal may be a program input: fixed width, no
    dictionary, one lane."""
    if isinstance(t, DecimalType):
        return not t.uses_int128
    return (t.name == "boolean" or t.is_integer or t.is_floating
            or t.is_temporal)


def descale(unscaled: int, scale: int) -> float:
    """The double nearest a decimal's value (one correctly rounded
    division, as `float(Decimal(text))` gives)."""
    return int(unscaled) / 10 ** scale


def _meets_double(node: RowExpression, i: int) -> bool:
    """Whether the compiler casts argument `i` of `node` to a double."""
    args = node.args
    if isinstance(node, SpecialForm):
        if node.form in (Form.BETWEEN, Form.IN):
            return i > 0 and args[0].type.is_floating
        if node.form == Form.IF:
            return i > 0 and args[3 - i].type.is_floating
        return False
    if node.name in _COMPARE:
        return args[1 - i].type.is_floating
    if node.name in _F64_MATH:
        return True
    return node.type.is_floating and (
        node.name in _ARITH or node.name in ("cast", "greatest", "least"))


def lift_expr(e: RowExpression, values: List[np.ndarray]) -> RowExpression:
    """`e` with its liftable literals replaced by `Param`s, their values
    appended to `values`; `e` itself where it holds none."""
    if isinstance(e, Literal):         # a projected or filtering constant
        return _lift_literal(e, e.type, values)
    if not isinstance(e, (Call, SpecialForm)):
        return e
    evaluated = isinstance(e, SpecialForm) or e.name in _VALUE_CALLS
    args = []
    for i, a in enumerate(e.args):
        if not isinstance(a, Literal):
            args.append(lift_expr(a, values))
        elif not evaluated:
            args.append(a)
        else:
            double = a.type.is_decimal and _meets_double(e, i)
            args.append(_lift_literal(a, DOUBLE if double else a.type,
                                      values))
    if all(new is old for new, old in zip(args, e.args)):
        return e
    return dataclasses.replace(e, args=tuple(args))


def _lift_literal(lit: Literal, as_type: Type, values: List[np.ndarray]
                  ) -> RowExpression:
    """The `Param` for `lit`, handed in as `as_type` (its own, or DOUBLE
    for a decimal the host descales); `lit` where it has to stay."""
    if lit.value is None or not liftable(lit.type):
        return lit
    value = lit.value
    if as_type is not lit.type:
        value = descale(value, lit.type.scale)
    values.append(np.asarray(value, dtype=as_type.dtype))
    return Param(len(values) - 1, as_type)


def evaluate(e: RowExpression, page):
    """`e` over `page` at once, outside a lowered program (the chains the
    batched and spilling runners interpret on the host): lifted like a
    program's, so the same literals are descaled by the same division."""
    values: List[np.ndarray] = []
    lifted = lift_expr(e, values)
    with binding(tuple(values)):
        return compile_expr(lifted)(page)


class Lifted(NamedTuple):
    plan: object                       # the plan with its literals blanked
    values: Tuple[np.ndarray, ...]     # what the program is handed
    origin: Dict[int, object]          # id(rebuilt node) -> the plan's node


def lift_plan(plan) -> Lifted:
    """Blank the liftable literals of a plan's expressions. Values are
    numbered in pre-order, so equal plan shapes number them alike; a
    subtree that holds none keeps its identity."""
    values: List[np.ndarray] = []
    origin: Dict[int, object] = {}
    done: Dict[int, object] = {}       # shared subtrees lift once

    def walk(node):
        if node is None:
            return None
        if id(node) in done:
            return done[id(node)]
        repl = {}
        if isinstance(node, FilterNode):
            repl["predicate"] = lift_expr(node.predicate, values)
        elif isinstance(node, ProjectNode):
            repl["expressions"] = tuple(
                lift_expr(x, values) for x in node.expressions)
        elif isinstance(node, JoinNode) and node.filter is not None:
            repl["filter"] = lift_expr(node.filter, values)
        names = {f.name for f in dataclasses.fields(node)}
        if "sources" in names:
            repl["sources"] = tuple(walk(s) for s in node.sources)
        else:
            for side in ("source", "probe", "build"):
                if side in names:
                    repl[side] = walk(getattr(node, side))
        same = all(same_objects(getattr(node, k), v) for k, v in repl.items())
        new = node if same else dataclasses.replace(node, **repl)
        if new is not node:
            origin[id(new)] = node
        done[id(node)] = new
        return new

    try:
        return Lifted(walk(plan), tuple(values), origin)
    finally:
        del walk       # it calls itself: a cycle around the plan


def same_objects(old, new) -> bool:
    """`new` is `old`, or a tuple of the objects `old` is a tuple of."""
    return new is old or (
        isinstance(old, tuple) and isinstance(new, tuple)
        and len(old) == len(new)
        and all(a is b for a, b in zip(old, new)))
