"""Required-field pruning at a source: send only what the statements read.

A source table often declares far more than a script reads (NEXmark's row
is three structs with 25 leaf fields; q7 reads three integer children of
`bid` and `bid`'s validity). Where the source's consumer is chained
behind it that costs nothing: the consumer's projection runs in the same
task and only its narrow output crosses an edge. Where the row itself
crosses an edge — the source fans out to several consumers, or chaining
is off, or the consumer runs at another parallelism — every column rides
along: serialized, measured, and fingerprinted by the conservation
ledger at both ends. `plan_query` sees that in the planned graph (a
source node that still ends at its watermark op) and plans the script
again with that table narrowed to the fields found here.

The analysis is a walk of the parsed statements, made before planning
because a `BoundExpr` is a closure that does not say what it read. It is
exact only where it can be, and keeps too much everywhere else:

* A table is narrowed only if EVERY reference to it is the sole FROM of
  a SELECT with explicit items (`direct_readers`). Such a SELECT binds
  its own expressions against the table and nothing else does, and its
  output schema does not depend on what the table carries beside them:
  every schema downstream of that first projection, so every state
  table, is what it was. A reference inside a JOIN (the sides pass every
  column through into join state), or under `*` / `tbl.*`, gives the
  whole table up.
* Inside a direct reader a column is kept whole unless every use of it
  is `col.child` or `col IS [NOT] NULL`: a bare column in a select list,
  a function or UDF argument, `unnest`, a comparison, a GROUP BY key
  keeps all of it. A struct read only through children keeps those
  children (each whole, whatever it nests) under the struct's own
  validity.
* `a.b` is ambiguous (qualified column or struct child, see
  `expressions._bind`): where both readings name something, both are
  kept.
* What the table's own DDL names — the event-time column, generated and
  metadata columns and every name a generated expression mentions — is
  kept whole, although those ops run before the projection.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Set, Tuple

import pyarrow as pa

from ..graph.logical import ChainedOp, OperatorName
from ..schema import StreamSchema, TIMESTAMP_FIELD
from .ast import (
    Column,
    Expr,
    FieldAccess,
    FuncCall,
    IsNull,
    Join,
    Relation,
    Select,
    Star,
    SubqueryRef,
    TableRef,
    expr_children,
)
from .expressions import BoundExpr, CompiledProjection, leaf_fields

# table name (lowercased) -> [(reading SELECT, the qualifier its scope
# gives the table's columns)]
Readers = Dict[str, List[Tuple[Select, Optional[str]]]]


def direct_readers(selects: Iterable[Select]) -> Tuple[Readers, Set[str]]:
    """Every reference to a table name in `selects` (statement bodies,
    view bodies; CTEs, subqueries and union arms are walked from them):
    the SELECTs that read a name as their sole FROM with explicit items,
    and the names read any other way, which are given up. A name may be
    a view or a CTE rather than a table: recording it as a reader only
    ever keeps more."""
    readers: Readers = {}
    given_up: Set[str] = set()

    def select(sel: Select) -> None:
        for _, body in getattr(sel, "ctes", []):
            select(body)
        for arm in sel.unions:
            select(arm)
        rel = sel.from_
        if not isinstance(rel, TableRef):
            relation(rel)
        elif any(isinstance(it.expr, Star) for it in sel.items):
            given_up.add(rel.name.lower())
        else:
            readers.setdefault(rel.name.lower(), []).append((sel, rel.alias))

    def relation(rel: Optional[Relation]) -> None:
        if isinstance(rel, TableRef):
            given_up.add(rel.name.lower())
        elif isinstance(rel, SubqueryRef):
            select(rel.query)
        elif isinstance(rel, Join):
            relation(rel.left)
            relation(rel.right)

    for sel in selects:
        select(sel)
    return readers, given_up


def _own_exprs(sel: Select) -> List[Expr]:
    """The expressions a SELECT binds against its FROM relation."""
    out = [it.expr for it in sel.items]
    out += [e for e in (sel.where, sel.having) if e is not None]
    out += list(sel.group_by)
    out += [e for e, _ in sel.order_by]
    return out


def kept_schema(
    schema: StreamSchema,
    table_name: str,
    readers: List[Tuple[Select, Optional[str]]],
    ddl_names: Iterable[str] = (),
) -> Optional[StreamSchema]:
    """`schema` narrowed to what `readers` read, fields in their declared
    order; None where nothing can be removed."""
    types = {f.name: f.type for f in schema.schema}
    whole: Set[str] = set(ddl_names)
    children: Dict[str, Set[str]] = {}  # touched columns -> children read

    def top(name: str, child: Optional[str], validity: bool) -> bool:
        if name not in types:
            return False  # a select alias, or the binder's error to raise
        if child is not None and pa.types.is_struct(types[name]):
            children.setdefault(name, set()).add(child)
        elif validity:
            children.setdefault(name, set())
        else:
            whole.add(name)
        return True

    def column(c: Column, qual: str, child: Optional[str] = None,
               validity: bool = False) -> None:
        if c.table is None:
            top(c.name, child, validity)
            return
        as_column = (
            c.table.lower() == qual.lower()
            and top(c.name, child, validity)
        )
        if not as_column or c.table in types:
            # `struct.child` (and, under a FieldAccess or IS NULL, what
            # hangs below that child: a kept child is kept whole)
            top(c.table, c.name, False)

    def walk(e: Expr, qual: str) -> None:
        if isinstance(e, Column):
            column(e, qual)
        elif isinstance(e, FieldAccess) and isinstance(e.base, Column):
            column(e.base, qual, child=e.field)
        elif isinstance(e, IsNull) and isinstance(e.operand, Column):
            column(e.operand, qual, validity=True)
        else:
            for c in expr_children(e):
                walk(c, qual)
            if isinstance(e, FuncCall) and e.over is not None:
                for p in e.over.partition_by:
                    walk(p, qual)
                for o, _ in e.over.order_by:
                    walk(o, qual)

    for sel, alias in readers:
        for e in _own_exprs(sel):
            walk(e, alias or table_name)

    fields: List[pa.Field] = []
    for f in schema.schema:
        if (
            f.name == TIMESTAMP_FIELD
            or f.name.startswith("__")  # engine columns: never a user's
            or f.name in whole
        ):
            fields.append(f)
        elif f.name in children:
            if not pa.types.is_struct(f.type):
                fields.append(f)
                continue
            read = [c for c in f.type if c.name in children[f.name]]
            # read for its validity alone: the first child stands in (a
            # struct of no fields has no Parquet form)
            read = read or list(f.type)[:1]
            fields.append(f.with_type(pa.struct(read)))
    kept = StreamSchema(pa.schema(fields))
    if leaf_count(kept.schema) == leaf_count(schema.schema):
        return None
    return kept


def leaf_count(schema: pa.Schema) -> int:
    """Leaf fields of a schema: a struct counts its children, deeply."""
    return sum(leaf_fields(f.type) for f in schema)


def prune_op(schema: StreamSchema, kept: StreamSchema) -> ChainedOp:
    """The stateless projection from `schema` to `kept` that ends a
    narrowed source's chain: a kept column passes through untouched, a
    narrowed struct is rebuilt from its kept children (zero-copy) and is
    null exactly where the source's was."""
    exprs: List[BoundExpr] = []
    for f in kept.schema:
        idx = schema.schema.names.index(f.name)
        if f.type.equals(schema.schema.field(idx).type):
            fn = (lambda i: lambda b: b.column(i))(idx)
        else:
            fn = (lambda i, t: lambda b: _narrow_struct(b.column(i), t))(
                idx, f.type
            )
        exprs.append(BoundExpr(fn, f.type, f.name))
    return ChainedOp(
        OperatorName.ARROW_VALUE,
        {
            "py_fn": CompiledProjection(exprs, kept.schema, None),
            "schema": kept,
            "name": "source_fields",
        },
        "source_fields",
    )


def _narrow_struct(col: pa.StructArray, to: pa.StructType) -> pa.StructArray:
    fields = list(to)
    arrays = [col.field(f.name) for f in fields]
    mask = col.is_null() if col.null_count else None
    return pa.StructArray.from_arrays(arrays, fields=fields, mask=mask)
