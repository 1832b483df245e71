"""Scalar expression IR — the engine's analogue of Presto's RowExpression
(reference: presto-spi/src/main/java/com/facebook/presto/spi/relation/ —
InputReferenceExpression, ConstantExpression, CallExpression,
SpecialFormExpression). This IR is what plans carry and what the JAX
compiler (expr/compile.py) lowers; it is also the wire form the worker
deserializes from coordinator PlanFragments (protocol layer).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Tuple

from presto_tpu.types import Type


class RowExpression:
    type: Type

    def children(self) -> Tuple["RowExpression", ...]:
        return ()


@dataclasses.dataclass(frozen=True)
class InputRef(RowExpression):
    """Reference to input column `field` of the operator's input page."""
    field: int
    type: Type

    def __str__(self):
        return f"$({self.field}):{self.type}"


@dataclasses.dataclass(frozen=True)
class Literal(RowExpression):
    """Constant. For VARCHAR, `value` is the python string; for DECIMAL the
    *unscaled* int; for DATE days-since-epoch; value None == typed NULL."""
    value: Any
    type: Type

    def __str__(self):
        return f"{self.value!r}:{self.type}"


@dataclasses.dataclass(frozen=True)
class Param(RowExpression):
    """A literal whose value the program takes as input `index` of its
    parameter tuple (expr/params.py lifts it out of the plan before an
    island is lowered): the plan keeps the type and the place, so every
    value of it runs one traced program."""
    index: int
    type: Type

    def __str__(self):
        return f"?{self.index}:{self.type}"


@dataclasses.dataclass(frozen=True)
class Call(RowExpression):
    """Scalar function call. `name` is the registry key (expr/compile.py):
    arithmetic ('add','subtract','multiply','divide','modulus','negate'),
    comparisons ('eq','ne','lt','le','gt','ge'), 'not', 'cast', 'like',
    'extract_year', 'substr', ... Mirrors the reference's function-resolution
    surface (presto-main-base/.../metadata/FunctionAndTypeManager.java:145)
    without the multi-namespace machinery."""
    name: str
    args: Tuple[RowExpression, ...]
    type: Type

    def children(self):
        return self.args

    def __str__(self):
        return f"{self.name}({', '.join(map(str, self.args))})"


class Form(enum.Enum):
    IF = "if"                  # if(cond, then, else)
    AND = "and"
    OR = "or"
    COALESCE = "coalesce"
    IN = "in"                  # in(value, c1, c2, ...)
    IS_NULL = "is_null"
    SWITCH = "switch"          # switch(operand?, when..., default) — lowered
    BETWEEN = "between"        # between(v, lo, hi)


@dataclasses.dataclass(frozen=True)
class SpecialForm(RowExpression):
    form: Form
    args: Tuple[RowExpression, ...]
    type: Type

    def children(self):
        return self.args

    def __str__(self):
        return f"{self.form.value}({', '.join(map(str, self.args))})"
