"""RowExpression -> JAX compiler.

The engine's analogue of the reference's bytecode expression compiler
(presto-main-base/.../sql/gen/ExpressionCompiler.java:62,
PageFunctionCompiler.java): instead of emitting JVM bytecode per expression,
we emit a Python closure over jax.numpy ops that evaluates the whole
expression tree vectorized over a Page. The closure runs under `jit` as part
of a whole-fragment program, so XLA fuses everything into the surrounding
kernel (no per-expression dispatch at all — strictly more fusion than the
reference's per-operator loop).

SQL three-valued NULL logic is carried as an explicit bool lane per
sub-expression. String operations exploit the sorted-dictionary invariant
(data/column.py): comparisons run on int32 codes; LIKE and string transforms
evaluate host-side over the (static) dictionary at trace time and become a
single device gather.

Divergence from the reference, by design: row-level runtime errors (division
by zero, overflow) yield NULL instead of failing the query — a data-parallel
engine cannot raise per-row. (reference behavior: throws
PrestoException DIVISION_BY_ZERO).
"""

from __future__ import annotations

import contextlib
import re
import threading
from functools import reduce
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from presto_tpu.data.column import Column, Page, StringDict
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, TIMESTAMP, VARCHAR, DecimalType,
    Type,
)
from presto_tpu.expr.nodes import (
    Call, Form, InputRef, Literal, Param, RowExpression, SpecialForm,
)
from presto_tpu.obs.metrics import counter
from presto_tpu.utils.tracing import TRACER

_DICT_PREDICATE_WORDS = counter(
    "presto_tpu_dict_predicate_words_total",
    "Dictionary words a string predicate (LIKE, starts_with, "
    "regexp_like) evaluated on the host while a program was lowered")

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _const_column(value, typ: Type, cap: int,
                  dictionary: Optional[StringDict] = None) -> Column:
    if value is None:
        vals = jnp.full((cap,), typ.null_sentinel(), dtype=typ.dtype)
        return Column(vals, jnp.ones((cap,), dtype=bool), typ, dictionary)
    vals = jnp.full((cap,), value, dtype=typ.dtype)
    return Column(vals, jnp.zeros((cap,), dtype=bool), typ, dictionary)


def _bool(values: jnp.ndarray, nulls: jnp.ndarray) -> Column:
    return Column(values.astype(bool), nulls, BOOLEAN, None)


def _merge_dicts(a: StringDict, b: StringDict):
    """Merge two sorted dictionaries; returns (merged, map_a, map_b) where
    map_x[i] is the merged code of x's word i. Host-side, trace-time."""
    wa, wb = np.asarray(a.words, dtype=object), np.asarray(b.words, dtype=object)
    merged = sorted(set(a.words) | set(b.words))
    md = StringDict(merged)
    marr = np.asarray(merged, dtype=object)
    map_a = np.searchsorted(marr.astype(str), wa.astype(str)).astype(np.int32)
    map_b = np.searchsorted(marr.astype(str), wb.astype(str)).astype(np.int32)
    return md, jnp.asarray(map_a), jnp.asarray(map_b)


def align_string_columns(x: Column, y: Column):
    """Recode two VARCHAR columns onto one shared sorted dictionary.
    An empty-dictionary side (all-NULL literal column) keeps zero codes —
    nothing to remap."""
    if x.dictionary is y.dictionary:
        return x, y
    md, ma, mb = _merge_dicts(x.dictionary, y.dictionary)
    xv = (jnp.take(ma, jnp.clip(x.values, 0, len(x.dictionary) - 1))
          if len(x.dictionary) else jnp.zeros_like(x.values))
    yv = (jnp.take(mb, jnp.clip(y.values, 0, len(y.dictionary) - 1))
          if len(y.dictionary) else jnp.zeros_like(y.values))
    return (Column(xv, x.nulls, x.type, md),
            Column(yv, y.nulls, y.type, md))


def _civil_from_days(z: jnp.ndarray):
    """days-since-epoch -> (year, month, day), vectorized integer math
    (public-domain civil_from_days algorithm)."""
    z = z.astype(jnp.int32) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = mp + jnp.where(mp < 10, 3, -9)
    y = y + (m <= 2)
    return y, m, d


def days_from_civil(y: int, m: int, d: int) -> int:
    """Host-side inverse (for date literals)."""
    y -= m <= 2
    era = (y if y >= 0 else y - 399) // 400
    yoe = y - era * 400
    doy = (153 * (m + (-3 if m > 2 else 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


_LIKE_CACHE: dict = {}


def _like_regex(pattern: str, escape: Optional[str] = None) -> "re.Pattern":
    key = (pattern, escape)
    if key not in _LIKE_CACHE:
        out, i = [], 0
        while i < len(pattern):
            ch = pattern[i]
            if escape and ch == escape and i + 1 < len(pattern):
                out.append(re.escape(pattern[i + 1])); i += 2; continue
            if ch == "%":
                out.append(".*")
            elif ch == "_":
                out.append(".")
            else:
                out.append(re.escape(ch))
            i += 1
        _LIKE_CACHE[key] = re.compile("^" + "".join(out) + "$", re.DOTALL)
    return _LIKE_CACHE[key]


def _valid_rows(page: Page, *cols) -> jnp.ndarray:
    """Rows that participate in checked-arithmetic detection: inside
    page.num_rows and non-NULL in every operand (padding slots carry
    arbitrary values; NULL propagation beats overflow in Presto)."""
    cap = cols[0].capacity
    v = jnp.arange(cap) < page.num_rows
    for c in cols:
        v = v & ~c.nulls.astype(bool)
    return v


def _rescale_decimal(v: jnp.ndarray, from_scale: int, to_scale: int,
                     valid=None):
    if to_scale == from_scale:
        return v
    if to_scale > from_scale:
        out = v * (10 ** (to_scale - from_scale))
        if valid is not None:
            from presto_tpu.expr import errors as E
            f = jnp.asarray(10 ** (to_scale - from_scale), v.dtype)
            E.record(E.OVF_DECIMAL, jnp.any(
                E.mul_overflows(v, f, out) & valid))
        return out
    f = 10 ** (from_scale - to_scale)  # round half away from zero
    return jnp.where(v >= 0, (v + f // 2) // f, -((-v + f // 2) // f))


def _cast(col: Column, to: Type, valid=None) -> Column:
    """`valid`: rows participating in checked range/overflow detection
    (user-facing CASTs pass it; internal coercions — widening promotions,
    comparisons — leave it None and stay unchecked, matching the
    reference where implicit coercions are always-safe widenings)."""
    from presto_tpu.expr import errors as E

    frm = col.type
    if frm == to:
        return col
    if _is_wide(col) or (isinstance(to, DecimalType) and to.uses_int128):
        return _cast_wide(col, to, valid)
    if frm.name == "unknown":  # typed NULL literal
        sent = jnp.asarray(to.null_sentinel(), dtype=to.dtype)
        return Column(jnp.full(col.values.shape, sent, dtype=to.dtype),
                      jnp.ones_like(col.nulls), to,
                      StringDict([]) if to.is_string else None)
    v, n = col.values, col.nulls

    def _check_int_range(vals, dt):
        if valid is None or not jnp.issubdtype(vals.dtype, jnp.integer):
            return
        info = jnp.iinfo(dt)
        if jnp.iinfo(vals.dtype).bits <= info.bits:
            return
        E.record(E.OVF_CAST, jnp.any(
            ((vals < info.min) | (vals > info.max)) & valid))

    if isinstance(to, DecimalType):
        if isinstance(frm, DecimalType):
            return Column(
                _rescale_decimal(v, frm.scale, to.scale, valid), n, to)
        if frm.is_integer:
            out = v.astype(jnp.int64) * (10 ** to.scale)
            if valid is not None and to.scale:
                f = jnp.asarray(10 ** to.scale, jnp.int64)
                E.record(E.OVF_DECIMAL, jnp.any(E.mul_overflows(
                    v.astype(jnp.int64), f, out) & valid))
            return Column(out, n, to)
        if frm.is_floating:
            scaled = v * (10 ** to.scale)
            if valid is not None:
                E.record(E.OVF_DECIMAL, jnp.any(
                    (jnp.abs(scaled) >= 2.0 ** 63) & valid))
            return Column(jnp.round(scaled).astype(jnp.int64), n, to)
        raise NotImplementedError(f"cast {frm} -> {to}")
    if isinstance(frm, DecimalType):
        if to.is_floating:
            return Column((v / (10 ** frm.scale)).astype(to.dtype), n, to)
        if to.is_integer:
            unscaled = _rescale_decimal(v, frm.scale, 0)
            _check_int_range(unscaled, to.dtype)
            return Column(unscaled.astype(to.dtype), n, to)
        raise NotImplementedError(f"cast {frm} -> {to}")
    if to.is_floating or to.is_integer:
        if frm.is_floating and to.is_integer:
            r = jnp.round(v)
            if valid is not None:
                # check the ROUNDED value; 2^(bits-1) is exactly
                # representable in float64, so use it as the exclusive
                # upper bound (iinfo.max itself rounds up to 2^63 for
                # bigint and would let exactly-2^63 slip through)
                hi = 2.0 ** (jnp.iinfo(to.dtype).bits - 1)
                E.record(E.OVF_CAST, jnp.any(
                    ((r >= hi) | (r < -hi)) & valid))
            return Column(r.astype(to.dtype), n, to)
        if frm.name == "boolean":
            return Column(v.astype(to.dtype), n, to)
        if frm.is_integer or frm.is_floating or frm.is_temporal:
            if to.is_integer:
                _check_int_range(v, to.dtype)
            return Column(v.astype(to.dtype), n, to)
    if to == DATE and frm.is_string:
        words = col.dictionary.words
        mapped = np.array([_parse_date_host(w) for w in words],
                          dtype=np.int32)
        return Column(jnp.take(jnp.asarray(mapped),
                               jnp.clip(v, 0, len(words) - 1)), n, to)
    if to == TIMESTAMP and frm == DATE:
        return Column(v.astype(jnp.int64) * 86_400_000_000, n, to)
    if to == BOOLEAN and (frm.is_integer or frm.is_floating):
        return Column(v != 0, n, to)
    if to.is_string and frm.is_string:
        return Column(v, n, to, col.dictionary)
    raise NotImplementedError(f"cast {frm} -> {to}")


def _cast_wide(col, to: Type, valid=None):
    """Casts touching the 128-bit limb representation."""
    from presto_tpu.data import int128 as I
    from presto_tpu.data.column import Decimal128Column

    frm = col.type
    if _is_wide(col):
        if to.is_floating:
            img = (col.l3.astype(jnp.float64) * float(2 ** 96)
                   + col.l2.astype(jnp.float64) * float(2 ** 64)
                   + col.l1.astype(jnp.float64) * float(2 ** 32)
                   + col.l0.astype(jnp.float64))
            return Column((img / (10 ** frm.scale)).astype(to.dtype),
                          col.nulls, to)
        if isinstance(to, DecimalType) and to.uses_int128:
            lanes = _wide_lanes(col, to.scale, valid)
            return Decimal128Column(*lanes, col.nulls, to)
        if to.is_integer or isinstance(to, DecimalType):
            # downscale to scale 0 (integers) or to.scale, then the
            # value must FIT the narrow representation — range-checked
            from presto_tpu.expr import errors as E
            target_scale = to.scale if isinstance(to, DecimalType) else 0
            lanes = _wide_lanes(col, target_scale, valid)
            t3, n2, n1, n0 = I.normalize(lanes)
            v64 = (n1 << 32) | n0          # low 64 bits, signed image
            sign = v64 >> 63               # 0 or -1
            fits = (t3 == sign) & (n2 == (sign & jnp.int64(0xFFFFFFFF)))
            if valid is not None:
                E.record(E.OVF_CAST, jnp.any(~fits & valid))
            if to.is_integer and to.dtype != jnp.int64:
                info = jnp.iinfo(to.dtype)
                if valid is not None:
                    E.record(E.OVF_CAST, jnp.any(
                        ((v64 < info.min) | (v64 > info.max)) & valid))
            return Column(v64.astype(to.dtype), col.nulls, to)
        raise NotImplementedError(f"cast {frm} -> {to}")
    if frm.name == "unknown":
        z = jnp.zeros(col.capacity, jnp.int64)
        return Decimal128Column(z, z, z, z,
                                jnp.ones(col.capacity, bool), to)
    if frm.is_floating:
        # double -> DECIMAL(38): floats carry 53 significant bits, so a
        # float-space limb decomposition is already exact wherever the
        # input was
        x = jnp.round(col.values.astype(jnp.float64) * (10 ** to.scale))
        l3 = jnp.floor(x / 2.0 ** 96)
        x = x - l3 * 2.0 ** 96
        l2 = jnp.floor(x / 2.0 ** 64)
        x = x - l2 * 2.0 ** 64
        l1 = jnp.floor(x / 2.0 ** 32)
        l0 = x - l1 * 2.0 ** 32
        lanes = tuple(a.astype(jnp.int64) for a in (l3, l2, l1, l0))
        return Decimal128Column(*lanes, col.nulls, to)
    if frm.is_integer or isinstance(frm, DecimalType):
        lanes = _wide_lanes(col, to.scale, valid)
        return Decimal128Column(*lanes, col.nulls, to)
    raise NotImplementedError(f"cast {frm} -> {to}")


def _parse_date_host(s: str) -> int:
    y, m, d = s.strip().split("-")
    return days_from_civil(int(y), int(m), int(d))


def _common_numeric(x: Column, y: Column):
    """Promote two numeric/temporal columns to a common device dtype for
    comparison; decimals are aligned by scale (exact int64 path)."""
    if isinstance(x.type, DecimalType) or isinstance(y.type, DecimalType):
        if isinstance(x.type, DecimalType) and isinstance(y.type, DecimalType):
            s = max(x.type.scale, y.type.scale)
            t = DecimalType(18, s)
            return _cast(x, t), _cast(y, t)
        t = DOUBLE if (x.type.is_floating or y.type.is_floating) else None
        if t is None:
            s = (x.type if isinstance(x.type, DecimalType) else y.type).scale
            t = DecimalType(18, s)
        return _cast(x, t), _cast(y, t)
    dt = jnp.promote_types(x.values.dtype, y.values.dtype)
    return (Column(x.values.astype(dt), x.nulls, x.type),
            Column(y.values.astype(dt), y.nulls, y.type))


# ---------------------------------------------------------------------------
# the compiler
# ---------------------------------------------------------------------------

Compiled = Callable[[Page], Column]


def compile_expr(expr: RowExpression) -> Compiled:
    """Compile a RowExpression into fn(Page) -> Column. The returned closure
    is trace-friendly: dictionary work happens at trace time (static aux)."""

    def ev(e: RowExpression, page: Page) -> Column:
        cap = page.capacity
        if isinstance(e, InputRef):
            return page.columns[e.field]
        if isinstance(e, Literal):
            return _literal_column(e, cap)
        if isinstance(e, Param):
            return _param_column(e, cap)
        if isinstance(e, SpecialForm):
            return _special(e, page, ev)
        if isinstance(e, Call):
            return _call(e, page, ev)
        raise NotImplementedError(f"expression {e!r}")

    return lambda page: ev(expr, page)


def _literal_column(e: Literal, cap: int) -> Column:
    t = e.type
    if t.is_string:
        if e.value is None:
            return _const_column(None, t, cap, StringDict([]))
        d = StringDict([e.value])
        return _const_column(0, t, cap, d)
    if isinstance(t, DecimalType) and t.uses_int128:
        # literal decimal values are stored UNSCALED in the Literal
        from presto_tpu.data import int128 as I
        from presto_tpu.data.column import Decimal128Column
        if e.value is None:
            z = jnp.zeros(cap, jnp.int64)
            return Decimal128Column(z, z, z, z, jnp.ones(cap, bool), t)
        lanes = I.from_python_int(int(e.value), (cap,))
        return Decimal128Column(*lanes, jnp.zeros(cap, bool), t)
    return _const_column(e.value, t, cap)


_BOUND = threading.local()


@contextlib.contextmanager
def binding(params):
    """The parameter tuple a program was handed, for the `Param`s its
    trace evaluates (per thread: tasks trace side by side)."""
    outer = getattr(_BOUND, "params", None)
    _BOUND.params = params
    try:
        yield
    finally:
        _BOUND.params = outer


def _param_column(e: Param, cap: int) -> Column:
    """A lifted literal (expr/params.py): the 0-d value the program took
    as an input, broadcast where `_literal_column` builds a constant."""
    value = jnp.asarray(_BOUND.params[e.index], dtype=e.type.dtype)
    return Column(jnp.broadcast_to(value, (cap,)),
                  jnp.zeros((cap,), dtype=bool), e.type, None)


def _special(e: SpecialForm, page: Page, ev) -> Column:
    f = e.form
    if f == Form.AND:
        cols = [ev(a, page) for a in e.args]
        val = reduce(jnp.logical_and,
                     [jnp.where(c.nulls, True, c.values.astype(bool))
                      for c in cols])
        any_false = reduce(jnp.logical_or,
                           [~c.nulls & ~c.values.astype(bool) for c in cols])
        any_null = reduce(jnp.logical_or, [c.nulls for c in cols])
        return _bool(val, ~any_false & any_null)
    if f == Form.OR:
        cols = [ev(a, page) for a in e.args]
        val = reduce(jnp.logical_or,
                     [jnp.where(c.nulls, False, c.values.astype(bool))
                      for c in cols])
        any_true = reduce(jnp.logical_or,
                          [~c.nulls & c.values.astype(bool) for c in cols])
        any_null = reduce(jnp.logical_or, [c.nulls for c in cols])
        return _bool(val, ~any_true & any_null)
    if f == Form.IS_NULL:
        c = ev(e.args[0], page)
        return _bool(c.nulls, jnp.zeros_like(c.nulls))
    if f == Form.IF:
        c = ev(e.args[0], page)
        t = ev(e.args[1], page)
        el = ev(e.args[2], page)
        if t.type.is_string and el.type.is_string:
            t, el = align_string_columns(t, el)
        elif t.type != el.type:
            t, el = _common_numeric(t, el)
        take_then = ~c.nulls & c.values.astype(bool)
        return Column(jnp.where(take_then, t.values, el.values),
                      jnp.where(take_then, t.nulls, el.nulls),
                      t.type if not t.type.is_string else t.type,
                      t.dictionary)
    if f == Form.COALESCE:
        cols = [ev(a, page) for a in e.args]
        out = cols[0]
        for c in cols[1:]:
            if out.type.is_string:
                out, c = align_string_columns(out, c)
            out = Column(jnp.where(out.nulls, c.values, out.values),
                         out.nulls & c.nulls, out.type, out.dictionary)
        return out
    if f == Form.BETWEEN:
        v, lo, hi = (ev(a, page) for a in e.args)
        return _and2(_compare("ge", v, lo), _compare("le", v, hi))
    if f == Form.IN:
        v = ev(e.args[0], page)
        eqs = [_compare("eq", v, ev(a, page)) for a in e.args[1:]]
        val = reduce(jnp.logical_or, [~c.nulls & c.values for c in eqs])
        any_null = reduce(jnp.logical_or, [c.nulls for c in eqs])
        return _bool(val, ~val & (any_null | v.nulls))
    raise NotImplementedError(f"special form {f}")


def _and2(a: Column, b: Column) -> Column:
    val = (jnp.where(a.nulls, True, a.values.astype(bool))
           & jnp.where(b.nulls, True, b.values.astype(bool)))
    any_false = (~a.nulls & ~a.values.astype(bool)) | \
                (~b.nulls & ~b.values.astype(bool))
    return _bool(val, ~any_false & (a.nulls | b.nulls))


_CMP = {
    "eq": lambda x, y: x == y, "ne": lambda x, y: x != y,
    "lt": lambda x, y: x < y, "le": lambda x, y: x <= y,
    "gt": lambda x, y: x > y, "ge": lambda x, y: x >= y,
}


def _is_wide(col) -> bool:
    from presto_tpu.data.column import Decimal128Column
    return isinstance(col, Decimal128Column)


def _wide_lanes(col, scale_to: int, valid=None):
    """Column -> 128-bit limb lanes at scale_to (reference:
    UnscaledDecimal128Arithmetic.rescale). Narrow int64 decimals /
    integers decompose device-side; upscaling multiplies by 10^d with
    overflow recorded."""
    from presto_tpu.data import int128 as I
    from presto_tpu.expr import errors as E
    if _is_wide(col):
        lanes = col.value_lanes
        frm = col.type.scale
    else:
        lanes = I.from_int64(col.values)
        frm = col.type.scale if isinstance(col.type, DecimalType) else 0
    d = scale_to - frm
    if d > 0:
        lanes, ovf = I.mul_pow10(lanes, d)
        if valid is not None:
            E.record(E.OVF_DECIMAL, jnp.any(ovf & valid))
    elif d < 0:
        lanes = I.div_pow10(lanes, -d)   # HALF_UP, exact
    return lanes


def _compare(op: str, x: Column, y: Column) -> Column:
    if _is_wide(x) or _is_wide(y):
        # exact 128-bit comparison at the common scale
        from presto_tpu.data import int128 as I
        xs = x.type.scale if isinstance(x.type, DecimalType) else 0
        ys = y.type.scale if isinstance(y.type, DecimalType) else 0
        s = max(xs, ys)
        lt, eq = I.compare(_wide_lanes(x, s), _wide_lanes(y, s))
        v = {"eq": eq, "ne": ~eq, "lt": lt, "le": lt | eq,
             "gt": ~(lt | eq), "ge": ~lt}[op]
        return _bool(v, x.nulls | y.nulls)
    if x.type.is_string and y.type.is_string:
        x, y = align_string_columns(x, y)
        return _bool(_CMP[op](x.values, y.values), x.nulls | y.nulls)
    # varchar <-> date coercion (Presto: cast('1998-09-02' as date) implied)
    if x.type.is_temporal and y.type.is_string:
        y = _cast(y, x.type if x.type.name == "date" else DATE)
    elif y.type.is_temporal and x.type.is_string:
        x = _cast(x, y.type if y.type.name == "date" else DATE)
    x, y = _common_numeric(x, y)
    return _bool(_CMP[op](x.values, y.values), x.nulls | y.nulls)


def _arith(op: str, e: Call, x: Column, y: Column, page: Page) -> Column:
    """Checked arithmetic (reference: BigintOperators.java:73 — the
    Math.addExact family): integer/decimal overflow on valid rows sets
    the program's error lane (expr/errors.py) and the executor raises
    NUMERIC_VALUE_OUT_OF_RANGE after the device round-trip."""
    from presto_tpu.expr import errors as E

    rt = e.type
    nulls = x.nulls | y.nulls
    valid = _valid_rows(page, x, y)
    wide_in = _is_wide(x) or _is_wide(y)
    if isinstance(rt, DecimalType) and (rt.uses_int128 or wide_in):
        return _arith_wide(op, rt, x, y, nulls, valid)
    if wide_in:
        # non-decimal result (decimal division types as DOUBLE): wide
        # operands go through their float image like any decimal/double
        # mix
        x = _cast_wide(x, DOUBLE) if _is_wide(x) else x
        y = _cast_wide(y, DOUBLE) if _is_wide(y) else y
    if isinstance(rt, DecimalType):
        xs = x.type.scale if isinstance(x.type, DecimalType) else 0
        ys = y.type.scale if isinstance(y.type, DecimalType) else 0
        xv = x.values.astype(jnp.int64)
        yv = y.values.astype(jnp.int64)
        if op == "multiply":
            v = xv * yv
            E.record(E.OVF_DECIMAL,
                     jnp.any(E.mul_overflows(xv, yv, v) & valid))
            return Column(
                _rescale_decimal(v, xs + ys, rt.scale, valid), nulls, rt)
        xv = _rescale_decimal(xv, xs, rt.scale, valid)
        yv = _rescale_decimal(yv, ys, rt.scale, valid)
        if op == "add":
            v = xv + yv
            E.record(E.OVF_DECIMAL,
                     jnp.any(E.add_overflows(xv, yv, v) & valid))
            return Column(v, nulls, rt)
        if op == "subtract":
            v = xv - yv
            E.record(E.OVF_DECIMAL,
                     jnp.any(E.sub_overflows(xv, yv, v) & valid))
            return Column(v, nulls, rt)
        raise NotImplementedError(f"decimal {op}")
    x = _cast(x, rt, valid)
    y = _cast(y, rt, valid)
    xv, yv = x.values, y.values
    checked = rt.is_integer
    if op == "add":
        v = xv + yv
        if checked:
            E.record(E.OVF_ADD, jnp.any(E.add_overflows(xv, yv, v) & valid))
    elif op == "subtract":
        v = xv - yv
        if checked:
            E.record(E.OVF_SUB, jnp.any(E.sub_overflows(xv, yv, v) & valid))
    elif op == "multiply":
        v = xv * yv
        if checked:
            E.record(E.OVF_MUL, jnp.any(E.mul_overflows(xv, yv, v) & valid))
    elif op == "divide":
        if rt.is_integer:
            zero = yv == 0
            v = jax.lax.div(xv, jnp.where(zero, 1, yv))
            nulls = nulls | zero
            # the single non-representable quotient: MIN / -1
            lo = jnp.asarray(jnp.iinfo(v.dtype).min, v.dtype)
            E.record(E.OVF_DIV, jnp.any(
                (xv == lo) & (yv == -1) & valid))
        else:
            zero = yv == 0
            v = xv / jnp.where(zero, 1, yv)
            nulls = nulls | zero
    elif op == "modulus":
        zero = yv == 0
        v = jax.lax.rem(xv, jnp.where(zero, 1, yv))
        nulls = nulls | zero
    else:
        raise NotImplementedError(op)
    return Column(v, nulls, rt)


def _arith_wide(op: str, rt, x: Column, y: Column, nulls, valid) -> "Column":
    """DECIMAL arithmetic on the 128-bit limb-lane representation
    (reference: UnscaledDecimal128Arithmetic.java add/subtract/multiply).
    Presto's decimal type rules make multiply's result scale exactly
    xs + ys (no rescale after the product) and add/subtract's the max
    input scale — so the only rescales here are upscales, which the
    limb multiply handles exactly."""
    from presto_tpu.data import int128 as I
    from presto_tpu.data.column import Decimal128Column
    from presto_tpu.expr import errors as E

    if not isinstance(rt, DecimalType):
        raise NotImplementedError(f"wide decimal {op} -> {rt}")
    xs = x.type.scale if isinstance(x.type, DecimalType) else 0
    ys = y.type.scale if isinstance(y.type, DecimalType) else 0
    if op == "multiply":
        if rt.scale != xs + ys:
            raise NotImplementedError(
                f"decimal multiply rescale {xs}+{ys}->{rt.scale}")
        lanes, ovf = I.mul(_wide_lanes(x, xs, valid),
                           _wide_lanes(y, ys, valid))
        # representation wrap (>= 2^127) OR past the DECIMAL(38)
        # value bound (Decimals.MAX_UNSCALED_DECIMAL = 10^38-1)
        E.record(E.OVF_DECIMAL, jnp.any(
            (ovf | I.exceeds_decimal38(lanes)) & valid))
    elif op in ("add", "subtract"):
        xl = _wide_lanes(x, rt.scale, valid)
        yl = _wide_lanes(y, rt.scale, valid)
        lanes = I.add(xl, yl) if op == "add" else I.sub(xl, yl)
        E.record(E.OVF_DECIMAL,
                 jnp.any(I.exceeds_decimal38(lanes) & valid))
    else:
        raise NotImplementedError(f"DECIMAL(38) {op} (128-bit division)")
    lanes = tuple(jnp.where(nulls, 0, ln) for ln in lanes)
    return Decimal128Column(*lanes, nulls, rt)


def _dict_transform(col: Column, fn) -> Column:
    """Apply a host string->string fn over the dictionary, producing a new
    sorted dictionary + device code remap (one gather)."""
    words = [fn(w) for w in col.dictionary.words]
    newd, codes = StringDict.build(words) if words else (StringDict([]), np.zeros(0, np.int32))
    remap = jnp.asarray(codes) if len(words) else jnp.zeros((1,), jnp.int32)
    nv = jnp.take(remap, jnp.clip(col.values, 0, max(len(words) - 1, 0)))
    return Column(nv, col.nulls, col.type, newd)


def _dict_predicate(col: Column, fn, name: str) -> Column:
    """Host predicate over dictionary words -> device boolean via gather.
    The host pass runs once a lowering, under a `dict_predicate` span
    (`words` the dictionary's size, `kept` those that satisfy `fn`,
    `fn` the SQL function's `name`)."""
    words = col.dictionary.words
    if not words:
        return _bool(jnp.zeros_like(col.nulls), col.nulls)
    with TRACER.span(None, "dict_predicate", words=len(words),
                     fn=name) as sp:
        kept = np.array([bool(fn(w)) for w in words])
        sp.attributes["kept"] = int(kept.sum())
    _DICT_PREDICATE_WORDS.inc(len(words))
    tbl = jnp.asarray(kept)
    v = jnp.take(tbl, jnp.clip(col.values, 0, len(words) - 1))
    return _bool(v, col.nulls)


def _as_f64(col: Column) -> jnp.ndarray:
    """Column values as float64 LOGICAL values (decimals descale)."""
    v = col.values.astype(jnp.float64)
    if col.type.is_decimal:
        v = v / (10 ** col.type.scale)
    return v


def _dict_transform_nullable(col: Column, fn) -> Column:
    """Like _dict_transform, but fn may return None: those codes become
    NULL rows (split_part past the last field, regexp_extract with no
    match, json paths that miss)."""
    words = col.dictionary.words if col.dictionary else ()
    out = [fn(w) for w in words]
    null_tbl = np.array([o is None for o in out], dtype=bool)
    filled = ["" if o is None else o for o in out]
    newd, codes = StringDict.build(filled) if filled \
        else (StringDict([]), np.zeros(0, np.int32))
    remap = jnp.asarray(codes) if filled else jnp.zeros((1,), jnp.int32)
    idx = jnp.clip(col.values, 0, max(len(words) - 1, 0))
    nv = jnp.take(remap, idx)
    extra_null = (jnp.take(jnp.asarray(null_tbl), idx)
                  if len(words) else jnp.zeros_like(col.nulls))
    return Column(nv, col.nulls | extra_null, col.type, newd)


def _dict_int(col: Column, fn) -> Column:
    """Host string->int fn over the dictionary -> device BIGINT gather."""
    words = col.dictionary.words if col.dictionary else ()
    tbl = jnp.asarray(np.array([int(fn(w)) for w in words], np.int64)
                      if words else np.zeros(1, np.int64))
    v = jnp.take(tbl, jnp.clip(col.values, 0, max(len(words) - 1, 0)))
    return Column(v, col.nulls, BIGINT)


def _days_from_civil_dev(y, m, d):
    """Vectorized (year, month, day) -> days-since-epoch (inverse of
    _civil_from_days; public-domain days_from_civil algorithm)."""
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _pad_word(w: str, size: int, pad: str, left: bool) -> str:
    """Presto lpad/rpad: truncate to size, else fill with `pad`
    repeated."""
    if size <= len(w):
        return w[:size]
    fill = (pad * size)[:size - len(w)] if pad else ""
    return fill + w if left else w + fill


def _regex_cache(pattern: str):
    import re
    key = ("re", pattern)
    rx = _LIKE_CACHE.get(key)
    if rx is None:
        rx = _LIKE_CACHE[key] = re.compile(pattern)
    return rx


def _json_scalar_path(doc: str, path: str):
    """Minimal $.a.b[0] JSONPath subset for json_extract_scalar."""
    import json as _json
    import re as _re
    try:
        v = _json.loads(doc)
    except Exception:   # noqa: BLE001 — bad JSON -> NULL (Presto)
        return None
    if not path.startswith("$"):
        return None
    for tok in _re.findall(r"\.([^.\[\]]+)|\[(\d+)\]", path[1:]):
        key, idx = tok
        try:
            v = v[int(idx)] if idx else v[key]
        except Exception:   # noqa: BLE001 — missing path -> NULL
            return None
    if v is None or isinstance(v, (dict, list)):
        return None
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float) and v == int(v):
        return str(int(v))
    return str(v)


def _call(e: Call, page: Page, ev) -> Column:
    name = e.name
    if name in ("add", "subtract", "multiply", "divide", "modulus"):
        return _arith(name, e, ev(e.args[0], page), ev(e.args[1], page),
                      page)
    if name in _CMP:
        return _compare(name, ev(e.args[0], page), ev(e.args[1], page))
    if name == "not":
        c = ev(e.args[0], page)
        return _bool(~c.values.astype(bool), c.nulls)
    if name == "negate":
        c = ev(e.args[0], page)
        if _is_wide(c):
            from presto_tpu.data import int128 as I
            from presto_tpu.data.column import Decimal128Column
            return Decimal128Column(*I.negate(c.value_lanes), c.nulls,
                                    c.type)
        if c.type.is_integer:   # -MIN is not representable
            from presto_tpu.expr import errors as E
            lo = jnp.asarray(jnp.iinfo(c.values.dtype).min, c.values.dtype)
            E.record(E.OVF_NEG, jnp.any(
                (c.values == lo) & _valid_rows(page, c)))
        return Column(-c.values, c.nulls, c.type)
    if name == "abs":
        c = ev(e.args[0], page)
        if _is_wide(c):
            from presto_tpu.data import int128 as I
            from presto_tpu.data.column import Decimal128Column
            neg = I.is_negative(c.value_lanes)
            lanes = tuple(jnp.where(neg, -x, x) for x in c.value_lanes)
            return Decimal128Column(*lanes, c.nulls, c.type)
        if c.type.is_integer:   # abs(MIN) is not representable
            from presto_tpu.expr import errors as E
            lo = jnp.asarray(jnp.iinfo(c.values.dtype).min, c.values.dtype)
            E.record(E.OVF_ABS, jnp.any(
                (c.values == lo) & _valid_rows(page, c)))
        return Column(jnp.abs(c.values), c.nulls, c.type)
    if name == "cast":
        c = ev(e.args[0], page)
        return _cast(c, e.type, _valid_rows(page, c))
    if name in ("extract_year", "extract_month", "extract_day", "year",
                "month", "day"):
        c = ev(e.args[0], page)
        days = c.values if c.type == DATE else c.values // 86_400_000_000
        y, m, d = _civil_from_days(days)
        part = {"year": y, "month": m, "day": d}[name.replace("extract_", "")]
        return Column(part.astype(jnp.int64), c.nulls, BIGINT)
    if name == "like":
        c = ev(e.args[0], page)
        pat = e.args[1]
        assert isinstance(pat, Literal), "LIKE pattern must be a literal"
        esc = e.args[2].value if len(e.args) > 2 else None
        rx = _like_regex(pat.value, esc)
        return _dict_predicate(c, lambda w: rx.match(w) is not None,
                               "like")
    if name == "substr":
        c = ev(e.args[0], page)
        start = e.args[1].value  # 1-based, literal
        length = e.args[2].value if len(e.args) > 2 else None
        if length is None:
            return _dict_transform(c, lambda w: w[start - 1:])
        return _dict_transform(c, lambda w: w[start - 1:start - 1 + length])
    if name in ("lower", "upper", "trim", "ltrim", "rtrim"):
        c = ev(e.args[0], page)
        fn = {"lower": str.lower, "upper": str.upper, "trim": str.strip,
              "ltrim": str.lstrip, "rtrim": str.rstrip}[name]
        return _dict_transform(c, fn)
    if name == "length":
        c = ev(e.args[0], page)
        words = c.dictionary.words
        tbl = jnp.asarray(np.array([len(w) for w in words], dtype=np.int64)
                          if words else np.zeros(1, np.int64))
        v = jnp.take(tbl, jnp.clip(c.values, 0, max(len(words) - 1, 0)))
        return Column(v, c.nulls, BIGINT)
    if name == "concat":
        a, b = ev(e.args[0], page), ev(e.args[1], page)
        if isinstance(e.args[1], Literal):
            return _dict_transform(a, lambda w: w + e.args[1].value)
        if isinstance(e.args[0], Literal):
            return _dict_transform(b, lambda w: e.args[0].value + w)
        # General column || column: the result dictionary is the sorted
        # cross product of both dictionaries (|A| x |B| words — bounded;
        # code-like columns keep this tiny) with a host-built (ca, cb) ->
        # combined-code LUT; the per-row work is one gather. Concatenated
        # strings do NOT sort in (a, b)-code order, hence the re-sort.
        aw = a.dictionary.words if a.dictionary else ("",)
        bw = b.dictionary.words if b.dictionary else ("",)
        if len(aw) * len(bw) > 1_000_000:
            raise NotImplementedError(
                f"concat dictionary product too large "
                f"({len(aw)}x{len(bw)})")
        from presto_tpu.data.column import StringDict
        pairs = [x + y for x in aw for y in bw]
        union = sorted(set(pairs))
        uarr = np.asarray(union, dtype=object).astype(str)
        lut = np.searchsorted(
            uarr, np.asarray(pairs, dtype=object).astype(str)
        ).astype(np.int32)
        d = StringDict(union)
        ca = jnp.clip(a.values, 0, len(aw) - 1).astype(jnp.int32)
        cb = jnp.clip(b.values, 0, len(bw) - 1).astype(jnp.int32)
        v = jnp.take(jnp.asarray(lut), ca * len(bw) + cb, mode="clip")
        return Column(v, a.nulls | b.nulls, VARCHAR, d)
    if name in ("sqrt", "ln", "log10", "exp", "floor", "ceil", "round"):
        c = ev(e.args[0], page)
        if name == "round" and len(e.args) > 1:
            nd = e.args[1].value
            f = 10.0 ** nd
            v = jnp.round(_as_f64(c) * f) / f
            return Column(v, c.nulls, DOUBLE)
        fn = {"sqrt": jnp.sqrt, "ln": jnp.log, "log10": jnp.log10,
              "exp": jnp.exp, "floor": jnp.floor, "ceil": jnp.ceil,
              "round": jnp.round}[name]
        v = fn(_as_f64(c))
        if name in ("floor", "ceil", "round") and c.type.is_integer:
            return Column(c.values, c.nulls, c.type)
        return Column(v, c.nulls, DOUBLE)
    if name == "date_add_days":
        c = ev(e.args[0], page)
        k = ev(e.args[1], page)
        return Column(c.values + k.values.astype(c.values.dtype),
                      c.nulls | k.nulls, c.type)

    # ---- string functions over the dictionary (operator/scalar/
    # String*.java family; host transform + device code gather) --------
    def _litstr(i: int, what: str) -> str:
        a = e.args[i]
        if not isinstance(a, Literal):
            raise NotImplementedError(f"{name} {what} must be a literal")
        return a.value

    def _litint(i: int, what: str) -> int:
        a = e.args[i]
        if not isinstance(a, Literal):
            raise NotImplementedError(f"{name} {what} must be a literal")
        return int(a.value)

    if name == "replace":
        c = ev(e.args[0], page)
        find = _litstr(1, "search")
        repl = _litstr(2, "replacement") if len(e.args) > 2 else ""
        return _dict_transform(c, lambda w: w.replace(find, repl))
    if name == "reverse":
        c = ev(e.args[0], page)
        return _dict_transform(c, lambda w: w[::-1])
    if name in ("lpad", "rpad"):
        c = ev(e.args[0], page)
        size = _litint(1, "size")
        pad = _litstr(2, "padstring") if len(e.args) > 2 else " "
        left = name == "lpad"
        return _dict_transform(
            c, lambda w: _pad_word(w, size, pad, left))
    if name == "split_part":
        c = ev(e.args[0], page)
        delim = _litstr(1, "delimiter")
        index = _litint(2, "index")
        if index <= 0:
            raise NotImplementedError("split_part index must be > 0")

        def part(w):
            ps = w.split(delim) if delim else [w]
            return ps[index - 1] if index <= len(ps) else None
        return _dict_transform_nullable(c, part)
    if name == "strpos":
        c = ev(e.args[0], page)
        sub = _litstr(1, "substring")
        return _dict_int(c, lambda w: w.find(sub) + 1)
    if name == "starts_with":
        c = ev(e.args[0], page)
        pre = _litstr(1, "prefix")
        return _dict_predicate(c, lambda w: w.startswith(pre),
                               "starts_with")
    if name == "regexp_like":
        c = ev(e.args[0], page)
        rx = _regex_cache(_litstr(1, "pattern"))
        return _dict_predicate(c, lambda w: rx.search(w) is not None,
                               "regexp_like")
    if name == "regexp_extract":
        c = ev(e.args[0], page)
        rx = _regex_cache(_litstr(1, "pattern"))
        group = _litint(2, "group") if len(e.args) > 2 else 0

        def extract(w):
            m = rx.search(w)
            return m.group(group) if m else None
        return _dict_transform_nullable(c, extract)
    if name == "regexp_replace":
        c = ev(e.args[0], page)
        rx = _regex_cache(_litstr(1, "pattern"))
        repl = _litstr(2, "replacement") if len(e.args) > 2 else ""
        # Presto capture refs are $1; python's are \1
        import re as _re
        py_repl = _re.sub(r"\$(\d+)", r"\\\1", repl)
        return _dict_transform(c, lambda w: rx.sub(py_repl, w))
    if name == "json_extract_scalar":
        c = ev(e.args[0], page)
        path = _litstr(1, "path")
        return _dict_transform_nullable(
            c, lambda w: _json_scalar_path(w, path))
    if name.startswith("url_extract_"):
        c = ev(e.args[0], page)
        part = name[len("url_extract_"):]
        from urllib.parse import urlparse

        def url_part(w):
            try:
                u = urlparse(w)
                v = {"host": u.hostname, "path": u.path,
                     "protocol": u.scheme, "query": u.query,
                     "fragment": u.fragment}.get(part)
            except Exception:   # noqa: BLE001 — bad URL -> NULL
                return None
            return None if v in (None, "") and part != "path" else str(v)
        if part == "port":
            # NULL when absent/malformed (Presto UrlFunctions.java)
            words = c.dictionary.words if c.dictionary else ()
            ports = []
            for w in words:
                try:
                    ports.append(urlparse(w).port)
                except Exception:   # noqa: BLE001 — bad port -> NULL
                    ports.append(None)
            null_tbl = np.array([p is None for p in ports], bool)
            val_tbl = np.array([0 if p is None else p for p in ports],
                               np.int64)
            idx = jnp.clip(c.values, 0, max(len(words) - 1, 0))
            if not words:
                return Column(jnp.zeros_like(c.values, jnp.int64),
                              jnp.ones_like(c.nulls), BIGINT)
            v = jnp.take(jnp.asarray(val_tbl), idx)
            extra = jnp.take(jnp.asarray(null_tbl), idx)
            return Column(v, c.nulls | extra, BIGINT)
        return _dict_transform_nullable(c, url_part)

    # ---- date functions (operator/scalar/DateTimeFunctions.java) -----
    if name in ("date_trunc", "day_of_week", "day_of_year", "quarter",
                "week", "last_day_of_month"):
        di = 1 if name == "date_trunc" else 0
        c = ev(e.args[di], page)
        days = c.values if c.type == DATE \
            else c.values // 86_400_000_000
        y, m, d = _civil_from_days(days)
        if name == "date_trunc":
            unit = _litstr(0, "unit").lower()
            if unit == "day":
                out = days
            elif unit == "week":      # ISO week starts Monday
                out = days - (days + 3) % 7
            elif unit == "month":
                out = _days_from_civil_dev(y, m, jnp.ones_like(d))
            elif unit == "quarter":
                qm = ((m - 1) // 3) * 3 + 1
                out = _days_from_civil_dev(y, qm, jnp.ones_like(d))
            elif unit == "year":
                out = _days_from_civil_dev(y, jnp.ones_like(m),
                                           jnp.ones_like(d))
            else:
                raise NotImplementedError(f"date_trunc unit {unit!r}")
            if c.type != DATE:      # TIMESTAMP: back to microseconds
                out = out * 86_400_000_000
            return Column(out.astype(c.values.dtype), c.nulls, c.type)
        if name == "day_of_week":
            return Column(((days + 3) % 7 + 1).astype(jnp.int64),
                          c.nulls, BIGINT)
        if name == "day_of_year":
            jan1 = _days_from_civil_dev(y, jnp.ones_like(m),
                                        jnp.ones_like(d))
            return Column((days - jan1 + 1).astype(jnp.int64),
                          c.nulls, BIGINT)
        if name == "quarter":
            return Column(((m + 2) // 3).astype(jnp.int64), c.nulls,
                          BIGINT)
        if name == "week":
            # ISO 8601 week of year: the week containing this date's
            # Thursday, counted within that Thursday's calendar year
            thu = days - (days + 3) % 7 + 3
            ty, _tm, _td = _civil_from_days(thu)
            jan1 = _days_from_civil_dev(ty, jnp.ones_like(m),
                                        jnp.ones_like(d))
            return Column(((thu - jan1) // 7 + 1).astype(jnp.int64),
                          c.nulls, BIGINT)
        # last_day_of_month: first day of next month - 1
        ny = y + (m == 12)
        nm = m % 12 + 1
        out = _days_from_civil_dev(ny, nm, jnp.ones_like(d)) - 1
        return Column(out.astype(c.values.dtype), c.nulls, DATE)
    if name == "date_diff":
        unit = _litstr(0, "unit").lower()
        a = ev(e.args[1], page)
        b = ev(e.args[2], page)
        da = a.values if a.type == DATE else a.values // 86_400_000_000
        db = b.values if b.type == DATE else b.values // 86_400_000_000
        nulls = a.nulls | b.nulls
        if unit == "day":
            return Column((db - da).astype(jnp.int64), nulls, BIGINT)
        if unit == "week":
            return Column(((db - da) // 7).astype(jnp.int64), nulls,
                          BIGINT)
        if unit in ("month", "quarter", "year"):
            ya, ma, dda = _civil_from_days(da)
            yb, mb, ddb = _civil_from_days(db)
            months = (yb - ya) * 12 + (mb - ma)
            # complete months only, with end-of-month clamping (Joda
            # monthsBetween: Jan-31 -> Feb-29 IS one month because the
            # clamped add lands on the month's last day)
            ones = jnp.ones_like(ma)

            def eom_day(y, m):
                ny = y + (m == 12)
                nm = m % 12 + 1
                return (_days_from_civil_dev(ny, nm, ones)
                        - _days_from_civil_dev(y, m, ones))
            short_fwd = (ddb < dda) & (ddb < eom_day(yb, mb))
            short_back = (ddb > dda) & (dda < eom_day(ya, ma))
            months = months - ((db >= da) & short_fwd) \
                + ((db < da) & short_back)
            div = {"month": 1, "quarter": 3, "year": 12}[unit]
            # truncate toward zero
            q = jnp.sign(months) * (jnp.abs(months) // div)
            return Column(q.astype(jnp.int64), nulls, BIGINT)
        raise NotImplementedError(f"date_diff unit {unit!r}")

    # ---- math (operator/scalar/MathFunctions.java) -------------------
    if name == "power":
        x = ev(e.args[0], page)
        p = ev(e.args[1], page)
        v = jnp.power(_as_f64(x), _as_f64(p))
        return Column(v, x.nulls | p.nulls, DOUBLE)
    if name == "cbrt":
        c = ev(e.args[0], page)
        return Column(jnp.cbrt(_as_f64(c)), c.nulls, DOUBLE)
    if name == "log2":
        c = ev(e.args[0], page)
        return Column(jnp.log2(_as_f64(c)), c.nulls, DOUBLE)
    if name == "sign":
        c = ev(e.args[0], page)
        if c.type.is_decimal:     # sign of the unscaled == sign of the
            return Column(jnp.sign(c.values), c.nulls, BIGINT)  # value
        return Column(jnp.sign(c.values), c.nulls, c.type)
    if name == "truncate":
        c = ev(e.args[0], page)
        if c.type.is_integer:
            return Column(c.values, c.nulls, c.type)
        return Column(jnp.trunc(_as_f64(c)), c.nulls, DOUBLE)
    if name in ("pi", "e"):
        import math
        val = math.pi if name == "pi" else math.e
        cap = page.capacity
        return Column(jnp.full((cap,), val, jnp.float64),
                      jnp.zeros((cap,), bool), DOUBLE)
    if name in ("greatest", "least"):
        binop = jnp.maximum if name == "greatest" else jnp.minimum
        if e.type.is_string:
            # dictionary codes only order within ONE dictionary: align
            # pairwise, fold on aligned codes
            acc_col = ev(e.args[0], page)
            for a in e.args[1:]:
                x, y = align_string_columns(acc_col, ev(a, page))
                acc_col = Column(binop(x.values, y.values),
                                 x.nulls | y.nulls, VARCHAR,
                                 x.dictionary)
            return acc_col
        # coerce every arg to the common result type first (mixed
        # decimal scales compare wrong as raw unscaled ints)
        cols = [_cast(ev(a, page), e.type) for a in e.args]
        acc = cols[0].values
        nulls = cols[0].nulls
        for c in cols[1:]:
            acc = binop(acc, c.values.astype(acc.dtype))
            nulls = nulls | c.nulls     # Presto: any NULL arg -> NULL
        return Column(acc, nulls, e.type)

    # plugin-registered vectorized scalar functions (spi.ScalarFunction:
    # jnp arrays in, jnp array out — the UDF compiles into the fragment
    # program like a built-in)
    from presto_tpu.spi import manager as _plugins
    pf = _plugins.get_function(name)
    if pf is not None:
        cols = [ev(a, page) for a in e.args]
        arrs = [(_as_f64(c) if pf.descale_decimals and c.type.is_decimal
                 else c.values) for c in cols]
        v = pf.impl(*arrs)
        nulls = jnp.zeros((page.capacity,), bool)
        for c in cols:
            nulls = nulls | c.nulls     # NULL propagates
        return Column(jnp.asarray(v), nulls, e.type)
    rf = _plugins.get_remote_function(name)
    if rf is not None:
        cols = [ev(a, page) for a in e.args]
        return _remote_function_call(rf, cols, e.type, page)
    raise NotImplementedError(f"function {name}")


def _remote_function_call(rf, cols, rt: Type, page: Page) -> Column:
    """Evaluate a sidecar-served scalar function (reference:
    RemoteFunctionRegisterer + RemoteProjectOperator): the compiled
    program calls the host through jax.pure_callback at run time, the
    host POSTs the page's argument values as JSON to the function's
    REST endpoint and feeds the response back into the program — shapes
    stay static, the call site stays inside the fragment."""
    import jax

    cap = page.capacity
    out_dtype = rt.dtype
    dictionaries = [c.dictionary for c in cols]
    # decimals travel as LOGICAL values (unscaled ints would be wrong
    # by 10^scale on the sidecar side — same default as
    # ScalarFunction.descale_decimals)
    scales = [c.type.scale if c.type.is_decimal else None for c in cols]
    sentinel = rt.null_sentinel()
    if rt.is_decimal:
        raise NotImplementedError(
            f"remote function {rf.name!r}: DECIMAL return types are "
            "not supported (no exact wire form); return DOUBLE")

    def host(num_rows, *flat):
        import json as _json

        from presto_tpu.protocol.transport import get_client
        n = int(num_rows)
        values, nullcols = [], []
        for i in range(0, len(flat), 2):
            arr, nl = flat[i][:n], flat[i + 1][:n]
            d = dictionaries[i // 2]
            sc = scales[i // 2]
            if d is not None:
                words = d.words
                col_vals = [None if nl[j] else words[int(arr[j])]
                            for j in range(n)]
            elif sc is not None:
                col_vals = [None if nl[j]
                            else arr[j].item() / (10 ** sc)
                            for j in range(n)]
            else:
                col_vals = [None if nl[j] else arr[j].item()
                            for j in range(n)]
            values.append(col_vals)
            nullcols.append([bool(x) for x in nl])
        body = _json.dumps({"function": rf.name, "values": values,
                            "nulls": nullcols}).encode()
        # the sidecar call is a pure function of its inputs, so
        # transport-level retries cannot change the result
        doc = get_client().post(
            rf.url, body,
            headers={"Content-Type": "application/json",
                     # marks the request EXTERNAL: the internal-auth
                     # opener must not attach the cluster JWT to a
                     # sidecar outside the trust boundary
                     "X-Presto-External": "true"},
            request_class="remote_function").json()
        rv = doc["values"]
        rn = doc.get("nulls") or [v is None for v in rv]
        out = np.full(cap, sentinel, dtype=out_dtype)
        out_nulls = np.ones(cap, dtype=bool)
        for j in range(n):
            out_nulls[j] = bool(rn[j])
            if not out_nulls[j]:
                out[j] = rv[j]
        return out, out_nulls

    flat = []
    for c in cols:
        flat.append(c.values)
        flat.append(c.nulls)
    vals, nulls = jax.pure_callback(
        host,
        (jax.ShapeDtypeStruct((cap,), out_dtype),
         jax.ShapeDtypeStruct((cap,), jnp.bool_)),
        page.num_rows, *flat)
    return Column(vals, nulls, rt)
