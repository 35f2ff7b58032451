"""SQL abstract syntax tree."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

# -- expressions ------------------------------------------------------------


@dataclasses.dataclass
class Expr:
    pass


def expr_children(e: Expr):
    """Immediate child expressions of an AST node, discovered generically
    through its dataclass fields (lists/tuples flattened) so walkers never
    miss a position — CASE branches, IN lists, BETWEEN bounds included."""

    def flatten(v):
        if isinstance(v, Expr):
            yield v
        elif isinstance(v, (list, tuple)):
            for item in v:
                yield from flatten(item)

    for f in dataclasses.fields(e):
        yield from flatten(getattr(e, f.name))


@dataclasses.dataclass
class Column(Expr):
    name: str
    table: Optional[str] = None  # qualifier

    def __str__(self):
        return f"{self.table}.{self.name}" if self.table else self.name


@dataclasses.dataclass
class FieldAccess(Expr):
    """struct.field access (e.g. window.start)."""

    base: Expr
    field: str

    def __str__(self):
        return f"{self.base}.{self.field}"


@dataclasses.dataclass
class Literal(Expr):
    value: Any  # python value; None for NULL

    def __str__(self):
        return repr(self.value)


@dataclasses.dataclass
class Interval(Expr):
    nanos: int

    def __str__(self):
        return f"INTERVAL {self.nanos}ns"


@dataclasses.dataclass
class BinaryOp(Expr):
    op: str  # + - * / % = != < <= > >= AND OR || ->> ->
    left: Expr
    right: Expr

    def __str__(self):
        return f"({self.left} {self.op} {self.right})"


@dataclasses.dataclass
class UnaryOp(Expr):
    op: str  # - NOT
    operand: Expr

    def __str__(self):
        return f"{self.op}({self.operand})"


@dataclasses.dataclass
class FuncCall(Expr):
    name: str  # lowercased
    args: List[Expr]
    distinct: bool = False
    star: bool = False  # count(*)
    # window-function OVER clause (None for plain calls)
    over: Optional["OverClause"] = None

    def __str__(self):
        inner = "*" if self.star else ", ".join(map(str, self.args))
        d = "DISTINCT " if self.distinct else ""
        return f"{self.name}({d}{inner})"


@dataclasses.dataclass
class OverClause:
    partition_by: List[Expr]
    order_by: List[Tuple[Expr, bool]]  # (expr, descending)


@dataclasses.dataclass
class Cast(Expr):
    operand: Expr
    type_name: str

    def __str__(self):
        return f"CAST({self.operand} AS {self.type_name})"


@dataclasses.dataclass
class Case(Expr):
    operand: Optional[Expr]
    branches: List[Tuple[Expr, Expr]]  # (when, then)
    else_: Optional[Expr]


@dataclasses.dataclass
class InList(Expr):
    operand: Expr
    items: List[Expr]
    negated: bool = False


@dataclasses.dataclass
class Between(Expr):
    operand: Expr
    low: Expr
    high: Expr
    negated: bool = False


@dataclasses.dataclass
class IsNull(Expr):
    operand: Expr
    negated: bool = False


@dataclasses.dataclass
class Star(Expr):
    table: Optional[str] = None  # t.* qualifier


# -- relations --------------------------------------------------------------


@dataclasses.dataclass
class Relation:
    pass


@dataclasses.dataclass
class TableRef(Relation):
    name: str
    alias: Optional[str] = None


@dataclasses.dataclass
class SubqueryRef(Relation):
    query: "Select"
    alias: Optional[str] = None


@dataclasses.dataclass
class Join(Relation):
    left: Relation
    right: Relation
    join_type: str  # inner | left | right | full
    condition: Optional[Expr]


@dataclasses.dataclass
class Unnest(Relation):
    expr: Expr
    alias: Optional[str] = None


# -- statements -------------------------------------------------------------


@dataclasses.dataclass
class SelectItem:
    expr: Expr
    alias: Optional[str] = None


@dataclasses.dataclass
class Select:
    items: List[SelectItem]
    from_: Optional[Relation]
    where: Optional[Expr] = None
    group_by: List[Expr] = dataclasses.field(default_factory=list)
    having: Optional[Expr] = None
    distinct: bool = False
    # UNION ALL chain: additional selects unioned onto this one
    unions: List["Select"] = dataclasses.field(default_factory=list)
    order_by: List[Tuple[Expr, bool]] = dataclasses.field(default_factory=list)
    limit: Optional[int] = None


@dataclasses.dataclass
class ColumnDef:
    name: str
    type_name: str
    nullable: bool = True
    # generated/virtual column expression (col AS (expr))
    generated: Optional[Expr] = None
    metadata_key: Optional[str] = None


@dataclasses.dataclass
class CreateTable:
    name: str
    columns: List[ColumnDef]
    options: Dict[str, str]  # WITH (...) connector options


@dataclasses.dataclass
class CreateView:
    name: str
    query: Select


@dataclasses.dataclass
class Insert:
    table: str
    query: Select


Statement = Any  # CreateTable | CreateView | Insert | Select
