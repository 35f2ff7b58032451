"""Expression binding + vectorized compilation over pyarrow.compute.

This replaces the reference's DataFusion physical expressions
(/root/reference/crates/arroyo-planner/src/physical.rs): every scalar SQL
expression compiles to a closure RecordBatch -> pa.Array executed by the
stateless operators. Arrow C++ kernels keep the host path vectorized; the
device (JAX) path is reserved for keyed aggregation where the FLOPs are.
"""

from __future__ import annotations

import dataclasses
import json
from typing import (
    Callable,
    Dict,
    FrozenSet,
    List,
    NamedTuple,
    Optional,
    Tuple,
)

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .ast import (
    Between,
    BinaryOp,
    Case,
    Cast,
    Column,
    Expr,
    FieldAccess,
    FuncCall,
    InList,
    Interval,
    IsNull,
    Literal,
    Star,
    UnaryOp,
    expr_children,
)
from ..obs import timeline
from .lexer import SqlError
from .types import common_type, sql_type_to_arrow

# ---------------------------------------------------------------------------
# Name scope
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ScopeCol:
    qualifier: Optional[str]
    name: str
    index: int
    dtype: pa.DataType


class Scope:
    """Column name resolution for one relation's output schema."""

    def __init__(self):
        self.cols: List[ScopeCol] = []

    @staticmethod
    def from_schema(schema: pa.Schema, qualifier: Optional[str] = None) -> "Scope":
        s = Scope()
        for i, f in enumerate(schema):
            s.add(qualifier, f.name, i, f.type)
        return s

    def add(self, qualifier, name, index, dtype):
        self.cols.append(ScopeCol(qualifier, name, index, dtype))

    def merge(self, other: "Scope", offset: int) -> "Scope":
        out = Scope()
        out.cols = list(self.cols) + [
            ScopeCol(c.qualifier, c.name, c.index + offset, c.dtype)
            for c in other.cols
        ]
        return out

    def resolve(self, name: str, qualifier: Optional[str] = None) -> ScopeCol:
        matches = [
            c
            for c in self.cols
            if c.name == name and (qualifier is None or c.qualifier == qualifier)
        ]
        if not matches:
            raise SqlError(
                f"unknown column {qualifier + '.' if qualifier else ''}{name}"
            )
        if len({m.index for m in matches}) > 1:
            raise SqlError(f"ambiguous column {name}")
        return matches[0]

    def try_resolve(self, name, qualifier=None) -> Optional[ScopeCol]:
        try:
            return self.resolve(name, qualifier)
        except SqlError:
            return None

    def names(self) -> List[str]:
        return [c.name for c in self.cols]


# ---------------------------------------------------------------------------
# Bound (compiled) expressions
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BoundExpr:
    fn: Callable[[pa.RecordBatch], object]  # -> pa.Array | pa.Scalar
    dtype: pa.DataType
    name: str
    # the parsed expression and the scope `bind` bound it against; None
    # for a closure built by hand
    source: Optional[Tuple[Expr, "Scope"]] = None

    @property
    def reads(self) -> Optional[FrozenSet[Tuple[int, Tuple[str, ...]]]]:
        """What the expression reads of its relation: (column index, path
        of child names below it) pairs, () = the column as a value. A
        walk of the parsed expression, because the bound one is a
        closure; None for one built by hand, which does not say."""
        return None if self.source is None else column_reads(*self.source)

    def eval(self, batch: pa.RecordBatch) -> pa.Array:
        out = self.fn(batch)
        if isinstance(out, pa.Scalar):
            out = pa.array([out.as_py()] * batch.num_rows, type=self.dtype)
        elif isinstance(out, pa.ChunkedArray):
            out = out.combine_chunks()
        return out


_NANOS = pa.timestamp("ns")


def bind(expr: Expr, scope: Scope) -> BoundExpr:
    be = _bind(expr, scope)
    be.source = (expr, scope)
    return be


def _column_path(expr: Expr, scope: Scope):
    """(column index, path of child names below it, the type there)
    where `expr` names a column of the relation or a struct child below
    one, resolved as `_bind` resolves it (`a.b`: the qualified column
    first, then child `b` of a struct column `a`). None for anything
    computed, and for a name the binder will refuse."""
    if isinstance(expr, Column):
        col = scope.try_resolve(expr.name, expr.table)
        if col is not None:
            return col.index, (), col.dtype
        if expr.table is None:
            return None
        expr = FieldAccess(Column(expr.table), expr.name)
    if isinstance(expr, FieldAccess):
        at = _column_path(expr.base, scope)
        if at is None or not pa.types.is_struct(at[2]):
            return None
        fidx = at[2].get_field_index(expr.field)
        if fidx < 0:
            return None
        return at[0], at[1] + (expr.field,), at[2].field(fidx).type
    return None


def column_reads(expr: Expr, scope: Scope) -> FrozenSet[Tuple[int, tuple]]:
    """`BoundExpr.reads` of `expr` bound against `scope`."""
    out = set()

    def walk(e: Expr) -> None:
        at = _column_path(e, scope)
        if at is not None:
            out.add(at[:2])
            return
        for c in expr_children(e):
            walk(c)

    walk(expr)
    return frozenset(out)


def _struct_child(b, idx: int, path: Tuple[str, ...]) -> pa.Array:
    """Child `path` of struct column `idx`, null where an ancestor is."""
    if isinstance(b, _LazyFilteredBatch):
        return b.child(idx, path)
    return pc.struct_field(b.column(idx), path)


def _bind(expr: Expr, scope: Scope) -> BoundExpr:
    if isinstance(expr, Column):
        if expr.table is not None:
            # `a.b` is ambiguous: qualified column OR struct field access
            # (e.g. window.start). Prefer the qualified column; fall back to
            # a struct column named `a`.
            col = scope.try_resolve(expr.name, expr.table)
            if col is None:
                base = scope.try_resolve(expr.table)
                if base is not None and pa.types.is_struct(base.dtype):
                    return bind(
                        FieldAccess(Column(expr.table), expr.name), scope
                    )
                raise SqlError(f"unknown column {expr.table}.{expr.name}")
        else:
            col = scope.resolve(expr.name)
        idx = col.index
        return BoundExpr(lambda b: b.column(idx), col.dtype, expr.name)
    if isinstance(expr, FieldAccess):
        at = _column_path(expr, scope)
        if at is not None:
            # a child below a plain column: a predicate's view may filter
            # the child alone and leave the struct's other children be
            idx, path, ftype = at
            return BoundExpr(
                lambda b: _struct_child(b, idx, path), ftype, expr.field
            )
        base = bind(expr.base, scope)
        if not pa.types.is_struct(base.dtype):
            raise SqlError(f"{base.name} is not a struct; cannot access "
                           f".{expr.field}")
        fidx = base.dtype.get_field_index(expr.field)
        if fidx < 0:
            raise SqlError(f"struct {base.name} has no field {expr.field}")
        return BoundExpr(
            lambda b: pc.struct_field(base.eval(b), expr.field),
            base.dtype.field(fidx).type,
            expr.field,
        )
    if isinstance(expr, Literal):
        v = expr.value
        if v is None:
            return BoundExpr(lambda b: pa.scalar(None, pa.null()), pa.null(), "NULL")
        t = _literal_type(v)
        return BoundExpr(lambda b: pa.scalar(v, t), t, str(v))
    if isinstance(expr, Interval):
        nanos = expr.nanos
        return BoundExpr(
            lambda b: pa.scalar(nanos, pa.int64()), pa.duration("ns"),
            "interval",
        )
    if isinstance(expr, BinaryOp):
        return _bind_binary(expr, scope)
    if isinstance(expr, UnaryOp):
        operand = bind(expr.operand, scope)
        if expr.op == "NOT":
            return BoundExpr(
                lambda b: pc.invert(operand.eval(b)), pa.bool_(),
                f"NOT {operand.name}",
            )
        return BoundExpr(
            lambda b: pc.negate(operand.eval(b)), operand.dtype,
            f"-{operand.name}",
        )
    if isinstance(expr, Cast):
        operand = bind(expr.operand, scope)
        target = sql_type_to_arrow(expr.type_name)
        return BoundExpr(
            lambda b: _cast(operand.eval(b), target), target, operand.name
        )
    if isinstance(expr, IsNull):
        operand = bind(expr.operand, scope)
        if expr.negated:
            return BoundExpr(
                lambda b: pc.is_valid(operand.eval(b)), pa.bool_(), "is_not_null"
            )
        return BoundExpr(
            lambda b: pc.is_null(operand.eval(b)), pa.bool_(), "is_null"
        )
    if isinstance(expr, InList):
        operand = bind(expr.operand, scope)
        values = [it.value for it in expr.items if isinstance(it, Literal)]
        if len(values) != len(expr.items):
            raise SqlError("IN list items must be literals")
        vset = pa.array(values, type=operand.dtype if not pa.types.is_null(
            operand.dtype) else None)

        def in_fn(b):
            out = pc.is_in(operand.eval(b), value_set=vset)
            return pc.invert(out) if expr.negated else out

        return BoundExpr(in_fn, pa.bool_(), "in")
    if isinstance(expr, Between):
        operand = bind(expr.operand, scope)
        lo = bind(expr.low, scope)
        hi = bind(expr.high, scope)

        def between_fn(b):
            v = operand.eval(b)
            out = pc.and_kleene(
                pc.greater_equal(v, lo.fn(b)), pc.less_equal(v, hi.fn(b))
            )
            return pc.invert(out) if expr.negated else out

        return BoundExpr(between_fn, pa.bool_(), "between")
    if isinstance(expr, Case):
        return _bind_case(expr, scope)
    if isinstance(expr, FuncCall):
        return bind_scalar_function(expr, scope)
    if isinstance(expr, Star):
        raise SqlError("* is only valid directly in a SELECT list")
    raise SqlError(f"unsupported expression {expr!r}")


def _literal_type(v) -> pa.DataType:
    if isinstance(v, bool):
        return pa.bool_()
    if isinstance(v, int):
        return pa.int64()
    if isinstance(v, float):
        return pa.float64()
    if isinstance(v, str):
        return pa.string()
    raise SqlError(f"unsupported literal {v!r}")


def _cast(arr, target: pa.DataType):
    if isinstance(arr, pa.Scalar):
        return pa.scalar(arr.as_py(), target)
    if pa.types.is_string(target) and pa.types.is_timestamp(arr.type):
        return pc.strftime(arr, format="%Y-%m-%dT%H:%M:%S.%f")
    if pa.types.is_timestamp(target) and pa.types.is_string(arr.type):
        # tolerant ISO8601 parse
        return pc.cast(arr, target)
    return pc.cast(arr, target, safe=False)


_ARITH = {"+": pc.add, "-": pc.subtract, "*": pc.multiply, "/": pc.divide}
_CMP = {
    "=": pc.equal,
    "!=": pc.not_equal,
    "<": pc.less,
    "<=": pc.less_equal,
    ">": pc.greater,
    ">=": pc.greater_equal,
}


def _bind_binary(expr: BinaryOp, scope: Scope) -> BoundExpr:
    left = bind(expr.left, scope)
    right = bind(expr.right, scope)
    op = expr.op
    name = f"{left.name}{op}{right.name}"
    if op in ("AND", "OR"):
        f = pc.and_kleene if op == "AND" else pc.or_kleene
        return BoundExpr(lambda b: f(left.eval(b), right.eval(b)), pa.bool_(),
                         name)
    if op in _CMP:
        if pa.types.is_struct(left.dtype) and pa.types.is_struct(right.dtype):
            if op != "=":
                raise SqlError("structs only support equality comparison")
            fields = [f.name for f in left.dtype]

            def struct_eq(b):
                lv, rv = left.eval(b), right.eval(b)
                out = None
                for fname in fields:
                    e = pc.equal(pc.struct_field(lv, fname),
                                 pc.struct_field(rv, fname))
                    out = e if out is None else pc.and_kleene(out, e)
                return out

            return BoundExpr(struct_eq, pa.bool_(), name)
        f = _CMP[op]
        return BoundExpr(
            lambda b: f(*_coerce_pair(left, right, b)), pa.bool_(), name
        )
    if op == "||":
        return BoundExpr(
            lambda b: pc.binary_join_element_wise(
                _to_str(left.eval(b)), _to_str(right.eval(b)), ""
            ),
            pa.string(),
            name,
        )
    if op in ("->", "->>"):
        return _bind_json_access(left, right, op)
    if op in _ARITH:
        return _bind_arith(left, right, op, name)
    if op == "%":
        def mod_fn(b):
            lv, rv = _coerce_pair(left, right, b)
            return _numpy_binary(np.mod, lv, rv)

        return BoundExpr(mod_fn, common_type(_num(left.dtype), _num(right.dtype)),
                         name)
    raise SqlError(f"unsupported operator {op}")


def _num(t: pa.DataType) -> pa.DataType:
    return pa.int64() if pa.types.is_null(t) else t


def _bind_arith(left: BoundExpr, right: BoundExpr, op: str, name: str) -> BoundExpr:
    lt, rt = left.dtype, right.dtype

    # timestamp +- interval arithmetic in int64 nanos
    if pa.types.is_timestamp(lt) and pa.types.is_duration(rt):
        f = pc.add if op == "+" else pc.subtract

        def ts_fn(b):
            lv = pc.cast(left.eval(b), pa.int64())
            return pc.cast(f(lv, right.fn(b)), _NANOS)

        return BoundExpr(ts_fn, _NANOS, name)
    if pa.types.is_duration(lt) and pa.types.is_timestamp(rt) and op == "+":
        def ts_fn2(b):
            rv = pc.cast(right.eval(b), pa.int64())
            return pc.cast(pc.add(rv, left.fn(b)), _NANOS)

        return BoundExpr(ts_fn2, _NANOS, name)
    if pa.types.is_timestamp(lt) and pa.types.is_timestamp(rt) and op == "-":
        def diff_fn(b):
            return pc.subtract(
                pc.cast(left.eval(b), pa.int64()), pc.cast(right.eval(b), pa.int64())
            )

        return BoundExpr(diff_fn, pa.duration("ns"), name)
    out_t = common_type(_num(lt), _num(rt))
    if op == "/" and pa.types.is_integer(out_t):
        # SQL integer division truncates
        def idiv(b):
            lv, rv = _coerce_pair(left, right, b)
            return _numpy_binary(
                lambda a, c: (a // c).astype(np.int64), lv, rv
            )

        return BoundExpr(idiv, out_t, name)
    f = _ARITH[op]
    return BoundExpr(lambda b: f(*_coerce_pair(left, right, b)), out_t, name)


def _coerce_pair(left: BoundExpr, right: BoundExpr, b) -> Tuple:
    lv = left.fn(b)
    rv = right.fn(b)
    if isinstance(lv, pa.ChunkedArray):
        lv = lv.combine_chunks()
    if isinstance(rv, pa.ChunkedArray):
        rv = rv.combine_chunks()
    lt, rt = left.dtype, right.dtype
    if pa.types.is_null(lt) or pa.types.is_null(rt):
        return lv, rv
    if not lt.equals(rt):
        t = common_type(lt, rt)
        if not lt.equals(t):
            lv = _cast_any(lv, t)
        if not rt.equals(t):
            rv = _cast_any(rv, t)
    return lv, rv


def _cast_any(v, t):
    if isinstance(v, pa.Scalar):
        return pa.scalar(v.as_py(), t)
    return pc.cast(v, t, safe=False)


def _numpy_binary(f, lv, rv):
    la = lv.as_py() if isinstance(lv, pa.Scalar) else np.asarray(
        lv.to_numpy(zero_copy_only=False))
    ra = rv.as_py() if isinstance(rv, pa.Scalar) else np.asarray(
        rv.to_numpy(zero_copy_only=False))
    return pa.array(f(la, ra))


def _to_str(v):
    t = v.type if not isinstance(v, pa.Scalar) else v.type
    if pa.types.is_string(t):
        return v
    return _cast_any(v, pa.string())


def _bind_json_access(left: BoundExpr, right: BoundExpr, op: str) -> BoundExpr:
    """Postgres-style json access over string columns (python fallback)."""

    def fn(b):
        docs = left.eval(b).to_pylist()
        key = right.fn(b)
        key = key.as_py() if isinstance(key, pa.Scalar) else None
        out = []
        for d in docs:
            try:
                obj = json.loads(d) if isinstance(d, str) else d
                v = obj[key] if not isinstance(key, int) else obj[key]
            except Exception:
                v = None
            if op == "->":
                out.append(json.dumps(v) if v is not None else None)
            else:
                out.append(
                    v if isinstance(v, str) or v is None else json.dumps(v)
                )
        return pa.array(out, type=pa.string())

    return BoundExpr(fn, pa.string(), "json_access")


def _bind_case(expr: Case, scope: Scope) -> BoundExpr:
    branches = []
    for when, then in expr.branches:
        if expr.operand is not None:
            cond = bind(BinaryOp("=", expr.operand, when), scope)
        else:
            cond = bind(when, scope)
        branches.append((cond, bind(then, scope)))
    else_b = bind(expr.else_, scope) if expr.else_ is not None else None
    out_t = branches[0][1].dtype
    for _, t in branches[1:]:
        if not pa.types.is_null(t.dtype):
            out_t = t.dtype if pa.types.is_null(out_t) else common_type(out_t, t.dtype)
    if else_b is not None and not pa.types.is_null(else_b.dtype):
        out_t = else_b.dtype if pa.types.is_null(out_t) else common_type(
            out_t, else_b.dtype)

    def fn(b):
        n = b.num_rows
        result = (
            _cast_any(else_b.eval(b), out_t)
            if else_b is not None
            else pa.array([None] * n, type=out_t)
        )
        for cond, then in reversed(branches):
            c = cond.eval(b)
            result = pc.if_else(c, _cast_any(then.eval(b), out_t), result)
        return result

    return BoundExpr(fn, out_t, "case")


# ---------------------------------------------------------------------------
# Scalar functions
# ---------------------------------------------------------------------------

_SIMPLE_FUNCS: Dict[str, Tuple[Callable, Optional[pa.DataType]]] = {
    # name -> (pc function, fixed output type or None=same as input)
    "abs": (pc.abs, None),
    "ceil": (pc.ceil, None),
    "floor": (pc.floor, None),
    "sqrt": (pc.sqrt, pa.float64()),
    "exp": (pc.exp, pa.float64()),
    "ln": (pc.ln, pa.float64()),
    "log10": (pc.log10, pa.float64()),
    "log2": (pc.log2, pa.float64()),
    "sin": (pc.sin, pa.float64()),
    "cos": (pc.cos, pa.float64()),
    "tan": (pc.tan, pa.float64()),
    "asin": (pc.asin, pa.float64()),
    "acos": (pc.acos, pa.float64()),
    "atan": (pc.atan, pa.float64()),
    "upper": (pc.utf8_upper, pa.string()),
    "lower": (pc.utf8_lower, pa.string()),
    "length": (pc.utf8_length, pa.int64()),
    "char_length": (pc.utf8_length, pa.int64()),
    "character_length": (pc.utf8_length, pa.int64()),
    "trim": (pc.utf8_trim_whitespace, pa.string()),
    "ltrim": (pc.utf8_ltrim_whitespace, pa.string()),
    "rtrim": (pc.utf8_rtrim_whitespace, pa.string()),
    "reverse": (pc.utf8_reverse, pa.string()),
}

_EXTRACT_FUNCS = {
    "year": pc.year,
    "month": pc.month,
    "day": pc.day,
    "hour": pc.hour,
    "minute": pc.minute,
    "second": pc.second,
    "millisecond": pc.millisecond,
    "dow": pc.day_of_week,
    "doy": pc.day_of_year,
    "week": pc.iso_week,
    "quarter": pc.quarter,
    "epoch": None,  # special-cased
}


def bind_scalar_function(expr: FuncCall, scope: Scope) -> BoundExpr:
    from ..udf import registry as udf_registry

    name = expr.name
    args = [bind(a, scope) for a in expr.args]
    if name in _SIMPLE_FUNCS:
        f, out_t = _SIMPLE_FUNCS[name]
        a = args[0]
        return BoundExpr(lambda b: f(a.eval(b)), out_t or a.dtype, name)
    if name in ("power", "pow"):
        return BoundExpr(
            lambda b: pc.power(args[0].eval(b), args[1].fn(b)), pa.float64(), name
        )
    if name == "round":
        nd = 0
        if len(args) > 1:
            nd_expr = expr.args[1]
            nd = nd_expr.value if isinstance(nd_expr, Literal) else 0
        a = args[0]
        return BoundExpr(
            lambda b: pc.round(a.eval(b), ndigits=nd), a.dtype, name
        )
    if name == "coalesce":
        out_t = next(
            (a.dtype for a in args if not pa.types.is_null(a.dtype)), pa.null()
        )

        def coalesce_fn(b):
            result = _cast_any(args[-1].eval(b), out_t)
            for a in reversed(args[:-1]):
                v = _cast_any(a.eval(b), out_t)
                result = pc.if_else(pc.is_valid(v), v, result)
            return result

        return BoundExpr(coalesce_fn, out_t, name)
    if name == "nullif":
        a, c = args[0], args[1]
        return BoundExpr(
            lambda b: pc.if_else(
                pc.equal(a.eval(b), c.fn(b)),
                pa.scalar(None, a.dtype),
                a.eval(b),
            ),
            a.dtype,
            name,
        )
    if name == "concat":
        def concat_fn(b):
            parts = [_to_str(a.eval(b)) for a in args]
            return pc.binary_join_element_wise(*parts, "")

        return BoundExpr(concat_fn, pa.string(), name)
    if name in ("substr", "substring"):
        a = args[0]

        def substr_fn(b):
            start = args[1].fn(b)
            start_v = start.as_py() if isinstance(start, pa.Scalar) else 1
            length = None
            if len(args) > 2:
                lv = args[2].fn(b)
                length = lv.as_py() if isinstance(lv, pa.Scalar) else None
            stop = (start_v - 1 + length) if length is not None else None
            return pc.utf8_slice_codeunits(
                a.eval(b), start=start_v - 1, stop=stop
            )

        return BoundExpr(substr_fn, pa.string(), name)
    if name == "replace":
        a = args[0]

        def replace_fn(b):
            pat = args[1].fn(b).as_py()
            rep = args[2].fn(b).as_py()
            return pc.replace_substring(a.eval(b), pattern=pat, replacement=rep)

        return BoundExpr(replace_fn, pa.string(), name)
    if name == "like":
        a = args[0]

        def like_fn(b):
            pat = args[1].fn(b)
            return pc.match_like(a.eval(b), pat.as_py())

        return BoundExpr(like_fn, pa.bool_(), name)
    if name == "extract" or name == "date_part":
        part = expr.args[0].value if isinstance(expr.args[0], Literal) else None
        a = args[1]
        if part == "epoch":
            return BoundExpr(
                lambda b: pc.divide(
                    pc.cast(a.eval(b), pa.int64()), pa.scalar(1_000_000_000)
                ),
                pa.int64(),
                name,
            )
        if part not in _EXTRACT_FUNCS:
            raise SqlError(f"unsupported extract part {part!r}")
        f = _EXTRACT_FUNCS[part]
        return BoundExpr(lambda b: pc.cast(f(a.eval(b)), pa.int64()),
                         pa.int64(), name)
    if name == "date_trunc":
        unit = expr.args[0].value if isinstance(expr.args[0], Literal) else "day"
        a = args[1]
        return BoundExpr(
            lambda b: pc.floor_temporal(a.eval(b), unit=unit), a.dtype, name
        )
    if name == "to_timestamp":
        a = args[0]
        if pa.types.is_string(a.dtype):
            return BoundExpr(lambda b: pc.cast(a.eval(b), _NANOS), _NANOS, name)
        # numeric epoch seconds
        return BoundExpr(
            lambda b: pc.cast(
                pc.multiply(pc.cast(a.eval(b), pa.int64()),
                            pa.scalar(1_000_000_000)),
                _NANOS,
            ),
            _NANOS,
            name,
        )
    if name == "md5":
        a = args[0]

        def md5_fn(b):
            import hashlib

            return pa.array(
                [
                    hashlib.md5(str(v).encode()).hexdigest() if v is not None
                    else None
                    for v in a.eval(b).to_pylist()
                ],
                type=pa.string(),
            )

        return BoundExpr(md5_fn, pa.string(), name)
    if name == "array_element":
        a, idx = args[0], args[1]
        if not pa.types.is_list(a.dtype):
            raise SqlError("array_element requires a list operand")
        vt = a.dtype.value_type

        def elem_fn(b):
            i = idx.fn(b)
            i_v = i.as_py() if isinstance(i, pa.Scalar) else 1
            return pc.list_element(a.eval(b), i_v - 1)  # SQL is 1-indexed

        return BoundExpr(elem_fn, vt, name)
    if name == "cardinality":
        a = args[0]
        return BoundExpr(
            lambda b: pc.cast(pc.list_value_length(a.eval(b)), pa.int64()),
            pa.int64(),
            name,
        )
    # window TVFs leak here only if misused
    if name in ("tumble", "hop", "session"):
        raise SqlError(
            f"{name}() is a window function and may only appear in GROUP BY "
            "(and as a SELECT alias of that group)"
        )
    udf = udf_registry.get(name)
    if udf is not None:
        return udf.bind(args)
    raise SqlError(f"unknown function {name!r}")


# ---------------------------------------------------------------------------
# Compiled programs used by the stateless operators
# ---------------------------------------------------------------------------


def leaf_fields(t: pa.DataType) -> int:
    """Leaf arrays under a type: a struct counts its children, deeply."""
    if t.num_fields and pa.types.is_struct(t):
        return sum(leaf_fields(f.type) for f in t)
    return 1


class _ViewRule(NamedTuple):
    """How a projection's filtered view treats its input's columns."""

    # (column, *child path) of every struct child that is filtered alone
    alone: FrozenSet[tuple]
    # (column, *child path) the predicate says is not null in a kept row
    not_null: FrozenSet[tuple]
    column_leaves: Tuple[int, ...]  # leaf arrays under each input column


def _kept_not_null(predicate: Optional[BoundExpr]) -> FrozenSet[tuple]:
    """The (column, *child path) that a row kept by `predicate` holds a
    value in: what it asks `IS NOT NULL` of, alone or under ANDs (a null
    or false conjunct drops the row)."""
    out = set()

    def walk(e: Expr, scope: Scope) -> None:
        if isinstance(e, BinaryOp) and e.op.upper() == "AND":
            walk(e.left, scope)
            walk(e.right, scope)
        elif isinstance(e, IsNull) and e.negated:
            at = _column_path(e.operand, scope)
            if at is not None:  # and so is every struct above it
                key = (at[0], *at[1])
                out.update(key[:k] for k in range(1, len(key) + 1))

    if predicate is not None and predicate.source is not None:
        walk(*predicate.source)
    return frozenset(out)


def _view_rule(exprs: List[BoundExpr], schema: pa.Schema,
               predicate: Optional[BoundExpr] = None) -> _ViewRule:
    """Read from what the bound expressions say they read
    (`BoundExpr.reads`) and the schema they were bound against: the
    children read of a struct column are filtered alone where they hold
    fewer leaves than the struct and nothing reads the struct as a
    value; where they are all of it the struct is filtered whole, once
    (a validity merge a child costs more than one struct filter). A
    child below one that is read itself goes with that one. An
    expression that does not say reads whole columns through `column()`,
    which is always right. And from the predicate, which structs a kept
    row is sure to hold: a child of such a one needs no validity from
    above."""
    paths: Dict[int, set] = {}
    for e in exprs:
        for idx, path in e.reads or ():
            paths.setdefault(idx, set()).add(path)
    column_leaves = tuple(leaf_fields(f.type) for f in schema)
    alone = set()
    for idx, ps in paths.items():
        if () in ps:
            continue
        top = [p for p in ps
               if not any(p[:k] in ps for k in range(1, len(p)))]
        read = 0
        for p in top:
            t = schema.field(idx).type
            for f in p:
                t = t.field(f).type
            read += leaf_fields(t)
        if read < column_leaves[idx]:
            alone.update((idx, *p) for p in top)
    return _ViewRule(frozenset(alone), _kept_not_null(predicate),
                     column_leaves)


class _LazyFilteredBatch:
    """Duck-typed RecordBatch view whose columns are filtered ON DEMAND.

    CompiledProjection predicates used to filter the whole batch before
    projecting — paying the filter kernel for every column, including
    wide struct columns the projection never reads (nexmark batches
    carry person+auction+bid structs; q5/q1 read only `bid`). This view
    exposes just the surface bound expressions use (column(i)/num_rows/
    schema, and child(i, path) for a struct child below a column) and
    filters each accessed column once, lazily.

    It goes one level further for the struct children in `rule.alone`:
    such a child is taken from the unfiltered struct, null where an
    ancestor is, and filtered alone, so the struct's other children are
    never copied. Either way gives the same arrays; `leaves_filtered`
    counts the leaf arrays that went through the filter kernel."""

    __slots__ = ("_batch", "_mask", "_cols", "_flat", "_rule",
                 "leaves_filtered", "num_rows", "schema")

    def __init__(self, batch: pa.RecordBatch, mask, num_rows: int,
                 rule: Optional[_ViewRule] = None):
        self._batch = batch
        self._mask = mask
        self._cols = {}  # (column, *child path) -> the filtered array
        self._flat = {}  # (column, *child path) -> (its type, its children)
        self._rule = rule or _view_rule([], batch.schema)
        self.leaves_filtered = 0
        self.num_rows = num_rows
        self.schema = batch.schema

    def column(self, i: int):
        c = self._cols.get((i,))
        if c is None:
            c = self._cols[(i,)] = self._batch.column(i).filter(self._mask)
            self.leaves_filtered += self._rule.column_leaves[i]
        return c

    def _unfiltered(self, at: tuple, merged: bool = True):
        """The UNFILTERED array at (column, *child path); `merged`: null
        where the struct above it or an ancestor is (one `flatten()` a
        struct and call, whichever of its children are read). Below a
        struct that every kept row holds (`rule.not_null`) the merge is
        skipped: the filter drops every row it would mark, and a column
        without a validity buffer filters three times faster."""
        if len(at) == 1:
            return self._batch.column(at[0])
        if not merged or at[:-1] in self._rule.not_null:
            return self._unfiltered(at[:-1], merged=False).field(at[-1])
        flat = self._flat.get(at[:-1])
        if flat is None:
            struct = self._unfiltered(at[:-1])
            flat = self._flat[at[:-1]] = (struct.type, struct.flatten())
        return flat[1][flat[0].get_field_index(at[-1])]

    def child(self, i: int, path: Tuple[str, ...]):
        at = (i, *path)
        c = self._cols.get(at)
        if c is None:
            above = self._cols.get(at[:-1])
            if above is None and at in self._rule.alone:
                c = self._unfiltered(at).filter(self._mask)
                self.leaves_filtered += leaf_fields(c.type)
            else:
                if above is None:
                    above = (self.child(i, path[:-1]) if len(path) > 1
                             else self.column(i))
                # no nulls above: no validity to merge, so no kernel
                c = (pc.struct_field(above, at[-1]) if above.null_count
                     else above.field(at[-1]))
            self._cols[at] = c
        return c

    def book(self) -> None:
        """The count that says the rule engaged, once the columns are
        read: a `note` with no duration, `n` of the input's `padded` leaf
        arrays went through the filter kernel."""
        timeline.note("project.filter", 0.0, n=self.leaves_filtered,
                      padded=sum(self._rule.column_leaves))

    def __getattr__(self, name):
        # duck-typing guard: a BoundExpr reaching for any other
        # RecordBatch attribute would otherwise fail only on the
        # partially-filtered path with an anonymous error (zero-pass /
        # all-pass predicates never build this view)
        raise AttributeError(
            f"_LazyFilteredBatch (the lazy predicate-filtered RecordBatch "
            f"view) exposes only column()/child()/num_rows/schema, not "
            f"{name!r}; teach the view that attribute or filter eagerly in "
            f"CompiledProjection"
        )


class CompiledProjection:
    """Projection (+ optional pre-filter): the runtime form handed to
    ARROW_VALUE operators."""

    def __init__(self, exprs: List[BoundExpr], out_schema: pa.Schema,
                 predicate: Optional[BoundExpr] = None):
        self.exprs = exprs
        self.out_schema = out_schema
        self.predicate = predicate
        self._rule: Optional[_ViewRule] = None  # needs the input's schema

    def filtered(self, batch):
        """`batch` under the predicate: None where no row passes, `batch`
        itself where every row does (or nothing is asked), else the lazy
        view, for the caller to `book()` once its columns are read."""
        if self.predicate is None:
            return batch
        mask = self.predicate.eval(batch)
        if mask.null_count:
            mask = pc.fill_null(mask, False)
        kept = mask.true_count
        if kept == 0:
            return None
        if kept == batch.num_rows:
            return batch
        if self._rule is None:
            self._rule = _view_rule(self.exprs, batch.schema,
                                    self.predicate)
        return _LazyFilteredBatch(batch, mask, kept, self._rule)

    def __call__(self, batch: pa.RecordBatch) -> Optional[pa.RecordBatch]:
        rows = self.filtered(batch)
        if rows is None:
            return None
        arrays = []
        for e, f in zip(self.exprs, self.out_schema):
            arr = e.eval(rows)
            if not arr.type.equals(f.type):
                arr = _cast(arr, f.type)
            arrays.append(arr)
        if rows is not batch:
            rows.book()
        return pa.RecordBatch.from_arrays(arrays, schema=self.out_schema)

    @staticmethod
    def from_config(config: dict) -> "CompiledProjection":
        """Rebuild from a serialized config (cross-process path): exprs are
        re-bound from SQL text against the carried schema."""
        from .parser import parse_expr_text

        in_schema = config["in_schema"]
        scope = Scope.from_schema(
            in_schema.schema if hasattr(in_schema, "schema") else in_schema
        )
        exprs = [bind(parse_expr_text(s), scope) for s in config["exprs"]]
        pred = (
            bind(parse_expr_text(config["predicate"]), scope)
            if config.get("predicate")
            else None
        )
        out = config["out_schema"]
        return CompiledProjection(
            exprs, out.schema if hasattr(out, "schema") else out, pred
        )


class CompiledPredicate:
    def __init__(self, expr: BoundExpr):
        self.expr = expr

    def __call__(self, batch: pa.RecordBatch) -> Optional[pa.RecordBatch]:
        mask = self.expr.eval(batch)
        out = batch.filter(mask)
        return out if out.num_rows else None
