"""SQL logical planner: statements -> LogicalGraph.

Capability parity with the reference's planner pipeline
(/root/reference/crates/arroyo-planner/src/lib.rs:789
parse_and_get_arrow_program + src/rewriters.rs + src/plan/*): CREATE TABLE
connector tables, views/CTEs, INSERT INTO sinks, source rewriting (event
time + watermark injection), projection/filter planning, window-TVF
aggregate detection (tumble/hop/session in GROUP BY, ordinals and aliases
resolved), window struct columns with .start/.end access, windowed
(instant) joins with residual predicates, expiring non-windowed joins,
unions, and sink wiring. Unsupported constructs raise SqlError with the
reference feature named, so gaps are visible rather than silent.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import pyarrow as pa

from ..graph.logical import (
    ChainedOp,
    EdgeType,
    LogicalGraph,
    LogicalNode,
    OperatorName,
)
from ..schema import StreamSchema, TIMESTAMP_FIELD, add_timestamp_field
from .ast import (
    BinaryOp,
    Column,
    CreateTable,
    CreateView,
    Expr,
    FieldAccess,
    FuncCall,
    Insert,
    Interval,
    Join,
    Literal,
    Relation,
    Select,
    SelectItem,
    Star,
    SubqueryRef,
    TableRef,
    Unnest,
    expr_children,
)
from .expressions import BoundExpr, CompiledProjection, Scope, bind
from .lexer import SqlError
from .parser import parse_statements
from .types import WINDOW_TYPE, sql_type_to_arrow

AGG_FUNCS = {
    "count", "sum", "min", "max", "avg", "mean",
    # variance family (one argument)
    "var", "var_samp", "var_pop", "variance", "stddev", "stddev_samp",
    "stddev_pop",
    # regression/covariance family: two arguments (y, x)
    "covar", "covar_pop", "covar_samp", "corr", "regr_slope",
    "regr_intercept", "regr_r2", "regr_avgx", "regr_avgy", "regr_count",
    "regr_sxx", "regr_syy", "regr_sxy",
    # boolean reductions
    "bool_and", "bool_or",
    # buffered builtins
    "median", "approx_median", "approx_distinct", "approx_percentile_cont",
    "approx_percentile_cont_with_weight", "bit_and", "bit_or", "bit_xor",
    "array_agg",
}
# canonical kind per alias (the rest map to themselves)
AGG_ALIASES = {"mean": "avg", "variance": "var", "covar": "covar_samp"}
# the variance/regression families decompose to pure add-reductions
# (Σx, Σx², Σxy, n), so they invert under retraction like count/sum/avg
from ..ops.aggregates import (  # noqa: E402
    REGR_KINDS as REGR_KINDS_SQL,
    VAR_KINDS as VAR_KINDS_SQL,
)
# two-argument aggregates: (y, x) / (value, weight)
TWO_ARG_AGGS = {
    "covar", "covar_pop", "covar_samp", "corr", "regr_slope",
    "regr_intercept", "regr_r2", "regr_avgx", "regr_avgy", "regr_count",
    "regr_sxx", "regr_syy", "regr_sxy",
    "approx_percentile_cont_with_weight",
}
# trailing literal parameters (not column inputs)
PARAM_AGGS = {"approx_percentile_cont": 1,
              "approx_percentile_cont_with_weight": 1}
WINDOW_TVFS = {"tumble", "hop", "session"}
DEFAULT_WATERMARK_DELAY = 1_000_000_000  # 1s, reference default


# ---------------------------------------------------------------------------
# Catalog
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TableDef:
    name: str
    fields: List[pa.Field]
    options: Dict[str, str]
    # col name -> connector metadata key (DDL `METADATA FROM 'key'`,
    # reference MetadataDef / SourceMetadataVisitor)
    metadata_fields: Dict[str, str] = dataclasses.field(default_factory=dict)
    # col name -> virtual-column expression (`GENERATED ALWAYS AS (expr)`)
    generated: Dict[str, Expr] = dataclasses.field(default_factory=dict)

    @property
    def connector(self) -> str:
        c = self.options.get("connector")
        if not c:
            raise SqlError(f"table {self.name} has no connector option")
        return c

    @property
    def is_memory(self) -> bool:
        """CREATE TABLE with no connector: an in-graph pass-through —
        INSERT INTO it defines the stream, reading it consumes that
        dataflow (reference memory/'virtual' tables, tables.rs)."""
        return "connector" not in self.options

    @property
    def table_type(self) -> str:
        # source | sink (some connectors imply one)
        return self.options.get("type", "")

    def schema(self) -> pa.Schema:
        return pa.schema(self.fields)


class SchemaProvider:
    """Table/view/UDF catalog (reference: ArroyoSchemaProvider, lib.rs:112)."""

    def __init__(self):
        self.tables: Dict[str, TableDef] = {}
        self.views: Dict[str, Select] = {}
        # bumped on every catalog mutation: cached subplans are keyed on
        # it so a multi-statement script redefining a table/view name
        # never reuses a plan bound to the old definition
        self.epoch = 0

    def add_table(self, t: TableDef):
        self.tables[t.name.lower()] = t
        self.epoch += 1

    def add_view(self, name: str, q: Select):
        self.views[name.lower()] = q
        self.epoch += 1

    def get_table(self, name: str) -> Optional[TableDef]:
        return self.tables.get(name.lower())

    def get_view(self, name: str) -> Optional[Select]:
        return self.views.get(name.lower())


# ---------------------------------------------------------------------------
# Window specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    kind: str  # tumbling | sliding | session
    width: int = 0  # nanos (tumbling/sliding)
    slide: int = 0
    gap: int = 0

    @staticmethod
    def from_call(call: FuncCall) -> "WindowSpec":
        def iv(e: Expr) -> int:
            if not isinstance(e, Interval):
                raise SqlError(
                    f"{call.name}() arguments must be INTERVAL literals"
                )
            return e.nanos

        if call.name == "tumble":
            if len(call.args) != 1:
                raise SqlError("tumble(width) takes one interval")
            return WindowSpec("tumbling", width=iv(call.args[0]))
        if call.name == "hop":
            if len(call.args) != 2:
                raise SqlError("hop(slide, width) takes two intervals")
            return WindowSpec(
                "sliding", slide=iv(call.args[0]), width=iv(call.args[1])
            )
        if len(call.args) != 1:
            raise SqlError("session(gap) takes one interval")
        return WindowSpec("session", gap=iv(call.args[0]))


# ---------------------------------------------------------------------------
# Relation plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RelOutput:
    node_id: int
    schema: StreamSchema  # includes _timestamp
    scope: Scope  # qualifier-aware name resolution over schema
    window: Optional[WindowSpec] = None  # set when rows are window outputs
    window_field: Optional[str] = None  # name of the window struct column
    updating: bool = False


class Planner:
    def __init__(self, provider: SchemaProvider, parallelism: int = 1,
                 narrowed: Optional[Dict[str, StreamSchema]] = None):
        self.provider = provider
        self.graph = LogicalGraph()
        self.parallelism = parallelism
        # source table (lowercased) -> the fields its readers read, where
        # its row would cross an edge whole (sql/pruning.py)
        self._narrowed = narrowed or {}
        self._source_cache: Dict[str, RelOutput] = {}
        self._select_plan_cache: Dict[tuple, RelOutput] = {}
        self._cache_epoch = getattr(provider, "epoch", 0)
        self._sink_nodes: Dict[str, dict] = {}
        self._memory_tables: Dict[str, RelOutput] = {}
        self._cte_stack: List[Dict[str, Select]] = []
        self._counter = 0

    # -- helpers ------------------------------------------------------------

    def _next_id(self) -> int:
        return self.graph.next_id()

    def _fresh(self, prefix: str) -> str:
        self._counter += 1
        return f"__{prefix}_{self._counter}"

    def _edge(self, src_node_id: int, dst_parallelism: int) -> EdgeType:
        """Forward when parallelism matches (chainable); otherwise an
        unkeyed shuffle (round-robin)."""
        if self.graph.nodes[src_node_id].parallelism == dst_parallelism:
            return EdgeType.FORWARD
        return EdgeType.SHUFFLE

    def _add_value_node(
        self,
        upstream: RelOutput,
        exprs: List[BoundExpr],
        names: List[str],
        predicate: Optional[BoundExpr],
        description: str,
        keep_timestamp_from: Optional[BoundExpr] = None,
    ) -> RelOutput:
        """Append a projection/filter node fed by `upstream` via a forward
        edge. `exprs` excludes _timestamp, which is passed through (or
        computed by keep_timestamp_from)."""
        # updating streams must keep retract/append ordering: the projection
        # runs at the upstream node's parallelism so the edge stays FORWARD
        # (an unkeyed shuffle would round-robin a flush's retract batch and
        # append batch onto different subtasks)
        node_par = (
            self.graph.nodes[upstream.node_id].parallelism
            if upstream.updating
            else self.parallelism
        )
        out_fields = [pa.field(n, e.dtype) for n, e in zip(names, exprs)]
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        ts_idx = upstream.schema.timestamp_index

        ts_expr = keep_timestamp_from or BoundExpr(
            lambda b: b.column(ts_idx), pa.timestamp("ns"), TIMESTAMP_FIELD
        )
        # updating streams carry __updating_meta through every projection
        from ..schema import UPDATING_META_FIELD, UPDATING_META_TYPE

        meta_idx = (
            upstream.schema.schema.names.index(UPDATING_META_FIELD)
            if UPDATING_META_FIELD in upstream.schema.schema.names
            else None
        )
        if meta_idx is not None and UPDATING_META_FIELD not in names:
            exprs = exprs + [
                BoundExpr(
                    (lambda j: lambda b: b.column(j))(meta_idx),
                    UPDATING_META_TYPE,
                    UPDATING_META_FIELD,
                )
            ]
            names = names + [UPDATING_META_FIELD]
            out_fields = [pa.field(n, e.dtype) for n, e in zip(names, exprs)]
            out_schema = StreamSchema(
                add_timestamp_field(pa.schema(out_fields))
            )
        prog = CompiledProjection(
            exprs + [ts_expr], out_schema.schema, predicate
        )
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.ARROW_VALUE,
                {"py_fn": prog, "schema": out_schema, "name": description},
                description,
                parallelism=node_par,
            )
        )
        self.graph.add_edge(
            upstream.node_id, node.node_id,
            self._edge(upstream.node_id, node_par), upstream.schema,
        )
        return RelOutput(
            node.node_id,
            out_schema,
            Scope.from_schema(out_schema.schema),
            window=upstream.window,
            window_field=_passthrough_window_field(upstream, names),
            updating=upstream.updating,
        )

    # -- entry points -------------------------------------------------------

    def plan_source_table(self, t: TableDef, alias: Optional[str]) -> RelOutput:
        cache_key = t.name.lower()
        if t.is_memory:
            rel = self._memory_tables.get(cache_key)
            if rel is None:
                raise SqlError(
                    f"memory table {t.name} is read before any INSERT INTO "
                    "it (statements plan in script order)"
                )
            return RelOutput(
                rel.node_id,
                rel.schema,
                Scope.from_schema(rel.schema.schema, alias or t.name),
                rel.window,
                rel.window_field,
                rel.updating,
            )
        if cache_key in self._source_cache:
            cached = self._source_cache[cache_key]
            return RelOutput(
                cached.node_id,
                cached.schema,
                Scope.from_schema(cached.schema.schema, alias or t.name),
                cached.window,
                cached.window_field,
                cached.updating,
            )
        from ..connectors import get_connector

        conn = get_connector(t.connector)
        options = conn.validate_options(
            {k: v for k, v in t.options.items()
             if k not in ("connector", "type", "format")},
            None,
        )
        event_time_field = t.options.get("event_time_field")
        watermark_delay = DEFAULT_WATERMARK_DELAY
        if "watermark_delay" in t.options:
            from .parser import parse_expr_text

            wd = parse_expr_text(f"interval '{t.options['watermark_delay']}'")
            watermark_delay = wd.nanos  # type: ignore[union-attr]
        elif "watermark_delay_nanos" in t.options:
            # set by the WATERMARK FOR column-DDL clause
            watermark_delay = int(t.options["watermark_delay_nanos"])

        if t.fields:
            source_schema = StreamSchema(
                add_timestamp_field(pa.schema(list(t.fields)))
            )
        else:
            # column-less CREATE TABLE: the connector defines the schema
            # (impulse, nexmark)
            fixed = conn.table_schema()
            if fixed is None:
                raise SqlError(
                    f"table {t.name} must declare columns (connector "
                    f"{t.connector} has no fixed schema)"
                )
            source_schema = fixed
        config = {
            "connector": t.connector,
            "schema": source_schema,
            "format": t.options.get("format"),
            "bad_data": t.options.get("bad_data", "fail"),
            "event_time_field": event_time_field,
            "proto_descriptor": _proto_descriptor(t),
            **options,
        }
        if t.metadata_fields:
            allowed = getattr(conn, "metadata_keys", ())
            for col, key in t.metadata_fields.items():
                if key not in allowed:
                    raise SqlError(
                        f"connector {t.connector} has no metadata key "
                        f"{key!r} (column {col}); available: "
                        f"{list(allowed) or 'none'}"
                    )
            config["metadata_fields"] = dict(t.metadata_fields)
        chain = [ChainedOp(OperatorName.CONNECTOR_SOURCE, config, t.name)]
        # virtual columns (GENERATED ALWAYS AS): computed right after
        # deserialization so event-time/watermark can reference them
        if t.generated:
            for col, gexpr in t.generated.items():
                for other in t.generated:
                    if _expr_references(gexpr, other):
                        what = (
                            "itself" if other == col
                            else f"generated column {other}"
                        )
                        raise SqlError(
                            f"generated column {col} references {what}; "
                            "generated columns may only reference payload "
                            "columns"
                        )
            scope = Scope.from_schema(source_schema.schema)
            gen_exprs: List[BoundExpr] = []
            for i, f in enumerate(source_schema.schema):
                if f.name == TIMESTAMP_FIELD:
                    continue
                if f.name in t.generated:
                    gen_exprs.append(bind(t.generated[f.name], scope))
                else:
                    gen_exprs.append(
                        BoundExpr(
                            (lambda j: lambda b: b.column(j))(i),
                            f.type, f.name,
                        )
                    )
            ts_i = source_schema.timestamp_index
            gen_exprs.append(
                BoundExpr(
                    (lambda j: lambda b: b.column(j))(ts_i),
                    pa.timestamp("ns"), TIMESTAMP_FIELD,
                )
            )
            chain.append(
                ChainedOp(
                    OperatorName.PROJECTION,
                    {
                        "py_fn": CompiledProjection(
                            gen_exprs, source_schema.schema, None
                        ),
                        "schema": source_schema,
                    },
                    "generated_columns",
                )
            )
        # event-time rewrite: _timestamp = event_time_field (reference
        # SourceRewriter, rewriters.rs)
        if event_time_field:
            scope = Scope.from_schema(source_schema.schema)
            et = bind(Column(event_time_field), scope)
            if not pa.types.is_timestamp(et.dtype):
                raise SqlError(
                    f"event_time_field {event_time_field} must be TIMESTAMP"
                )
            idxs = list(range(len(source_schema.schema) - 1))
            exprs = [
                BoundExpr(
                    (lambda i: lambda b: b.column(i))(i),
                    source_schema.schema.field(i).type,
                    source_schema.schema.field(i).name,
                )
                for i in idxs
            ]
            prog = CompiledProjection(exprs + [et], source_schema.schema, None)
            chain.append(
                ChainedOp(
                    OperatorName.PROJECTION,
                    {"py_fn": prog, "schema": source_schema},
                    "event_time",
                )
            )
        chain.append(
            ChainedOp(
                OperatorName.EXPRESSION_WATERMARK,
                {"interval_nanos": watermark_delay,
                 "idle_time": _opt_float(t.options.get("idle_time"))},
                "watermark",
            )
        )
        # required-field pruning: the chain ends in one stateless
        # projection to the fields the statements read, behind the ops
        # whose state a checkpoint names by position (source, watermark);
        # everything downstream binds against the narrowed schema
        kept = self._narrowed.get(cache_key)
        if kept is not None:
            from .pruning import prune_op

            chain.append(prune_op(source_schema, kept))
            source_schema = kept
        node = self.graph.add_node(
            LogicalNode(self._next_id(), t.name, chain, parallelism=1)
        )
        out = RelOutput(
            node.node_id,
            source_schema,
            Scope.from_schema(source_schema.schema, alias or t.name),
        )
        self._source_cache[cache_key] = out
        return out

    # -- relations ----------------------------------------------------------

    def plan_relation(self, rel: Relation) -> RelOutput:
        if isinstance(rel, TableRef):
            view = self._resolve_view(rel.name)
            if view is not None:
                out = self._plan_select_shared(view)
                return _requalify(out, rel.alias or rel.name)
            t = self.provider.get_table(rel.name)
            if t is None:
                raise SqlError(f"unknown table {rel.name}")
            return self.plan_source_table(t, rel.alias)
        if isinstance(rel, SubqueryRef):
            out = self._plan_select_shared(rel.query)
            return _requalify(out, rel.alias)
        if isinstance(rel, Join):
            return self.plan_join(rel)
        if isinstance(rel, Unnest):
            raise SqlError(
                "bare UNNEST in FROM has no input stream; use "
                "`FROM tbl CROSS JOIN UNNEST(tbl.col) AS x` or unnest(col) "
                "as a SELECT item"
            )
        raise SqlError(f"unsupported relation {rel!r}")

    def _plan_select_shared(self, sel: Select) -> RelOutput:
        """Common-subplan elimination: structurally identical subqueries,
        views and CTE bodies plan ONCE and fan out (nexmark q5's two hop
        branches share one aggregation instead of maintaining duplicate
        window state; the reference gets the same effect from DataFusion's
        CSE + its SourceRewriter source cache). AST dataclasses repr
        structurally, so the repr is the cache key; the CTE stack rides
        along since the same text can resolve differently per scope."""
        # the key must capture WHAT names resolve to, not just nesting
        # depth: same-text subqueries under different same-depth CTE
        # scopes (or across statements redefining a CTE) are different
        # plans
        # catalog epoch: a later statement redefining a table/view must
        # not reuse a plan bound to the old definition. Clearing (rather
        # than keying on epoch) also drops the now-unreachable entries.
        ep = getattr(self.provider, "epoch", 0)
        if ep != self._cache_epoch:
            self._select_plan_cache.clear()
            # source plans are keyed by bare table name: a redefined
            # table must re-plan, not reuse the stale source. Memory
            # tables stay — they are plan-local entities (INSERT INTO
            # targets created by earlier statements of THIS plan), not
            # catalog-backed, and dropping them would orphan references
            # from statements planned after a DDL epoch bump.
            self._source_cache.clear()
            self._cache_epoch = ep
        key = (
            repr(sel),
            tuple(
                tuple(sorted((n, repr(q)) for n, q in scope.items()))
                for scope in self._cte_stack
            ),
        )
        hit = self._select_plan_cache.get(key)
        if hit is not None:
            return hit
        out = self.plan_select(sel)
        self._select_plan_cache[key] = out
        return out

    def _resolve_view(self, name: str) -> Optional[Select]:
        for scope in reversed(self._cte_stack):
            if name.lower() in scope:
                return scope[name.lower()]
        return self.provider.get_view(name)

    # -- select -------------------------------------------------------------

    def plan_select(self, sel: Select) -> RelOutput:
        ctes = getattr(sel, "ctes", [])
        if ctes:
            self._cte_stack.append({n.lower(): q for n, q in ctes})
        try:
            out = self._plan_select_body(sel)
            for u in sel.unions:
                out = self._plan_union(out, self._plan_select_body(u))
            if sel.order_by or sel.limit is not None:
                raise SqlError(
                    "ORDER BY/LIMIT on unbounded streams is not supported "
                    "(use window functions for top-N)"
                )
            return out
        finally:
            if ctes:
                self._cte_stack.pop()

    def _plan_select_body(self, sel: Select) -> RelOutput:
        out = self._plan_select_body_inner(sel)
        if sel.distinct:
            out = self._add_distinct_node(out)
        return out

    def _add_distinct_node(self, out: RelOutput) -> RelOutput:
        """SELECT DISTINCT: a zero-aggregate updating aggregate keyed by
        every output column (the reference plans DISTINCT as an aggregation
        over all select items; the emitted stream is updating). Duplicate
        rows produce no state change, so only first occurrences emit; over
        an updating input the per-key live count retracts rows whose every
        contributing input was retracted."""
        from ..schema import UPDATING_META_FIELD, UPDATING_META_TYPE

        in_names = out.schema.schema.names
        key_cols = [
            i for i, n in enumerate(in_names)
            if n not in (TIMESTAMP_FIELD, UPDATING_META_FIELD)
        ]
        key_names = [in_names[i] for i in key_cols]
        for i in key_cols:
            t = out.schema.schema.field(i).type
            if pa.types.is_list(t) or pa.types.is_map(t):
                raise SqlError(
                    f"SELECT DISTINCT over {t} column "
                    f"{in_names[i]!r} is not supported (list/map values "
                    "cannot be grouping keys)"
                )
        out_fields = [
            pa.field(in_names[i], out.schema.schema.field(i).type)
            for i in key_cols
        ]
        out_fields.append(pa.field(UPDATING_META_FIELD, UPDATING_META_TYPE))
        schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        cfg: Dict = {"aggregates": [], "key_cols": key_cols,
                     "schema": schema}
        if out.updating:
            cfg["retractable"] = True
            cfg["meta_col"] = in_names.index(UPDATING_META_FIELD)
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.UPDATING_AGGREGATE,
                cfg,
                "distinct",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(
            out.node_id, node.node_id, EdgeType.SHUFFLE,
            out.schema.with_keys(key_names),
        )
        return RelOutput(
            node.node_id, schema, Scope.from_schema(schema.schema),
            updating=True,
        )

    def _plan_select_body_inner(self, sel: Select) -> RelOutput:
        if sel.from_ is None:
            raise SqlError("SELECT without FROM is not supported")
        upstream = self.plan_relation(sel.from_)
        where = bind(sel.where, upstream.scope) if sel.where is not None else None

        items = self._expand_stars(sel.items, upstream)
        has_window_fn = any(
            isinstance(it.expr, FuncCall) and it.expr.over is not None
            for it in items
        )
        if has_window_fn:
            return self._plan_window_function(sel, items, upstream, where)
        from ..udf import registry as udf_registry

        async_items = [
            it for it in items
            if isinstance(it.expr, FuncCall)
            and (u := udf_registry.get(it.expr.name)) is not None
            and u.is_async
        ]
        if async_items:
            return self._plan_async_udf(sel, items, async_items, upstream,
                                        where)
        unnest_items = [
            it for it in items
            if isinstance(it.expr, FuncCall) and it.expr.name == "unnest"
        ]
        if unnest_items or any(_contains_unnest(it.expr) for it in items):
            if not unnest_items:
                raise SqlError(
                    "unnest() must be a top-level SELECT item (wrap other "
                    "expressions around it in an outer query)"
                )
            return self._plan_unnest(sel, items, unnest_items, upstream,
                                     where)
        if sel.group_by or self._has_aggregate(items):
            return self._plan_aggregate(sel, items, upstream, where)
        # plain projection/filter
        exprs, names = self._bind_items(items, upstream.scope)
        return self._add_value_node(
            upstream, exprs, names, where, _describe_items(names)
        )

    def _expand_stars(
        self, items: List[SelectItem], upstream: RelOutput
    ) -> List[SelectItem]:
        out: List[SelectItem] = []
        for it in items:
            if isinstance(it.expr, Star):
                for c in upstream.scope.cols:
                    if c.name == TIMESTAMP_FIELD or c.name.startswith("__"):
                        continue
                    if it.expr.table and c.qualifier != it.expr.table:
                        continue
                    out.append(
                        SelectItem(Column(c.name, table=c.qualifier), c.name)
                    )
            else:
                out.append(it)
        return out

    def _bind_items(
        self, items: List[SelectItem], scope: Scope
    ) -> Tuple[List[BoundExpr], List[str]]:
        exprs, names = [], []
        for it in items:
            e = bind(it.expr, scope)
            exprs.append(e)
            names.append(it.alias or _default_name(it.expr, e))
        return exprs, _dedup(names)

    @staticmethod
    def _has_aggregate(items: List[SelectItem]) -> bool:
        return any(_find_aggregates(it.expr) for it in items)

    # -- aggregates ---------------------------------------------------------

    def _plan_aggregate(
        self,
        sel: Select,
        items: List[SelectItem],
        upstream: RelOutput,
        where: Optional[BoundExpr],
    ) -> RelOutput:
        # resolve group-by entries: ordinals and select-alias references
        group_exprs: List[Expr] = []
        window_spec: Optional[WindowSpec] = None
        window_alias: Optional[str] = None
        for g in sel.group_by:
            g = self._resolve_group_ref(g, items)
            if isinstance(g, FuncCall) and g.name in WINDOW_TVFS:
                if window_spec is not None:
                    raise SqlError("only one window function per GROUP BY")
                window_spec = WindowSpec.from_call(g)
                continue
            if isinstance(g, Column):
                # group by an alias of the window TVF select item
                hit = _find_item_by_alias(items, g.name)
                if hit is not None and isinstance(hit.expr, FuncCall) and (
                    hit.expr.name in WINDOW_TVFS
                ):
                    window_spec = WindowSpec.from_call(hit.expr)
                    window_alias = hit.alias
                    continue
            group_exprs.append(g)

        # select items referencing the window TVF directly
        for it in items:
            if isinstance(it.expr, FuncCall) and it.expr.name in WINDOW_TVFS:
                spec = WindowSpec.from_call(it.expr)
                if window_spec is None:
                    window_spec = spec
                elif spec != window_spec:
                    raise SqlError("conflicting window specifications")
                window_alias = it.alias or "window"

        # GROUP BY over a window struct COLUMN (aggregating an already-
        # windowed stream, e.g. nexmark q5's MaxBids): instant mode — rows
        # of one window share a _timestamp, so bins are exact timestamps
        key_bound = [bind(g, upstream.scope) for g in group_exprs]
        instant = window_spec is None and any(
            pa.types.is_struct(b.dtype) for b in key_bound
        )
        if window_spec is None and not instant:
            return self._plan_updating_aggregate(
                sel, items, upstream, where, group_exprs, key_bound
            )
        if upstream.updating:
            raise SqlError(
                "windowed aggregation over an updating (retracting) input "
                "is not yet supported"
            )

        key_names = _dedup([_default_name(g, b) for g, b in
                            zip(group_exprs, key_bound)])
        agg_calls, agg_inputs = _collect_aggregates(items, upstream.scope)
        wfield = None if instant else (window_alias or "window")
        agg_out, agg_out_names = self._windowed_agg_node(
            upstream, where, window_spec, key_bound, key_names,
            agg_calls, agg_inputs, wfield, instant,
        )
        out, _ = self._agg_post_projection(
            sel, items, agg_out, key_names, group_exprs, agg_calls,
            agg_out_names, wfield,
        )
        return out

    def _windowed_agg_node(
        self, upstream, where, window_spec, key_bound, key_names,
        agg_calls, agg_inputs, wfield: Optional[str], instant: bool,
    ) -> Tuple[RelOutput, List[str]]:
        """Pre-projection + window-aggregate node for one aggregate branch
        (shared by the plain windowed path and the mixed-distinct regular
        branch). Output schema: [keys..., agg outs..., wfield?]."""
        pre_exprs = list(key_bound)
        pre_names = list(key_names)
        agg_col_idx: List[List[int]] = []
        for bs in agg_inputs:
            idxs = []
            for b in bs:
                pre_exprs.append(b)
                idxs.append(len(pre_exprs) - 1)
                pre_names.append(self._fresh("agg_in"))
            agg_col_idx.append(idxs)
        pre = self._add_value_node(
            upstream, pre_exprs, pre_names, where, "agg_input"
        )

        # aggregate specs
        specs = []
        agg_out_names = []
        for call, col_idx in zip(agg_calls, agg_col_idx):
            specs.append(
                _make_spec(call, col_idx, pre_exprs, self._fresh("agg_out"))
            )
            agg_out_names.append(specs[-1]["name"])

        # window operator output schema: keys + aggs + window struct
        out_fields = [
            pa.field(n, pre.schema.schema.field(i).type)
            for i, n in enumerate(key_names)
        ]
        for spec, call in zip(specs, agg_calls):
            out_fields.append(pa.field(spec["name"], _agg_output_type(
                spec, call, pre.schema.schema)))
        if not instant:
            out_fields.append(pa.field(wfield, WINDOW_TYPE))
        agg_out_schema = StreamSchema(
            add_timestamp_field(pa.schema(out_fields))
        )

        window_config: Dict = {
            "aggregates": specs,
            "key_cols": list(range(len(key_names))),
            "schema": agg_out_schema,
        }
        # one group per bin: every grouping key IS a window struct (q5's
        # MAX-per-window stage) or there are none. Mesh hash ownership
        # would land each window's rows on one shard, so the window
        # operators run these salted (rows spread across shards, folded
        # at gather — parallel/sharded_state.SharedMeshSlotDirectory)
        if not key_bound or all(
            b.dtype == WINDOW_TYPE for b in key_bound
        ):
            window_config["mesh_salted"] = True
        if instant:
            op_name = OperatorName.TUMBLING_WINDOW_AGGREGATE
            window_config["width_nanos"] = 0
            description = "instant_window"
        else:
            op_name = {
                "tumbling": OperatorName.TUMBLING_WINDOW_AGGREGATE,
                "sliding": OperatorName.SLIDING_WINDOW_AGGREGATE,
                "session": OperatorName.SESSION_WINDOW_AGGREGATE,
            }[window_spec.kind]
            window_config["window_field"] = wfield
            description = f"{window_spec.kind}_window"
            if window_spec.kind == "tumbling":
                window_config["width_nanos"] = window_spec.width
            elif window_spec.kind == "sliding":
                window_config["width_nanos"] = window_spec.width
                window_config["slide_nanos"] = window_spec.slide
            else:
                window_config["gap_nanos"] = window_spec.gap

        # global (unkeyed) aggregates cannot shard: all rows of a window
        # must meet in one accumulator, so the node runs at parallelism 1
        # (keyed aggregates shard by group key)
        agg_par = self.parallelism if key_names else 1
        agg_node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                op_name,
                window_config,
                description,
                parallelism=agg_par,
            )
        )
        shuffle_schema = pre.schema.with_keys(key_names) if key_names else pre.schema
        self.graph.add_edge(
            pre.node_id, agg_node.node_id, EdgeType.SHUFFLE, shuffle_schema
        )
        out_window_field = wfield
        if instant:
            # the window struct key column carries the window downstream
            for i, b in enumerate(key_bound):
                if pa.types.is_struct(b.dtype):
                    out_window_field = key_names[i]
                    break
        agg_out = RelOutput(
            agg_node.node_id,
            agg_out_schema,
            Scope.from_schema(agg_out_schema.schema),
            window=window_spec if not instant else upstream.window,
            window_field=out_window_field,
        )
        return agg_out, agg_out_names

    def _agg_post_projection(
        self, sel, items, agg_out, key_names, group_exprs, agg_calls,
        call_names, wcol: Optional[str],
    ) -> Tuple[RelOutput, List[str]]:
        """Select-item/HAVING projection over an aggregate (or joined
        aggregate) output: aggregate calls map to their output columns,
        group expressions to key columns, window TVF refs to `wcol`
        (shared by the windowed, count-distinct and mixed-distinct paths)."""
        post_scope = _agg_post_scope(
            agg_out, key_names, group_exprs, agg_calls, call_names
        )
        having = (
            bind(
                _rewrite_group_refs(
                    _rewrite_aggregates(sel.having, agg_calls, call_names),
                    group_exprs, key_names,
                ),
                post_scope,
            )
            if sel.having is not None
            else None
        )
        post_exprs: List[BoundExpr] = []
        post_names: List[str] = []
        for it in items:
            rewritten = _rewrite_aggregates(it.expr, agg_calls, call_names)
            rewritten = _rewrite_group_refs(rewritten, group_exprs, key_names)
            if (
                isinstance(rewritten, FuncCall)
                and rewritten.name in WINDOW_TVFS
                and wcol is not None
            ):
                rewritten = Column(wcol)
            e = bind(rewritten, post_scope)
            post_exprs.append(e)
            post_names.append(it.alias or _default_name(it.expr, e))
        out = self._add_value_node(
            agg_out, post_exprs, _dedup(post_names), having,
            _describe_items(post_names),
        )
        return out, post_names

    def _restore_select_order(
        self, out: RelOutput, items, special_item, out_name: str,
        plain_items, plain_names, description: str,
        final_name: Optional[str] = None,
    ) -> RelOutput:
        """Final projection restoring the SELECT item order after an
        operator that appends one computed column (window fn / async udf /
        unnest). `out_name` is the (fresh, collision-free) internal column;
        `final_name` the user-facing name it takes in the output."""
        final_exprs, final_names = [], []
        for it in items:
            if it is special_item:
                final_exprs.append(bind(Column(out_name), out.scope))
                final_names.append(final_name or out_name)
            else:
                idx = plain_items.index(it)
                final_exprs.append(bind(Column(plain_names[idx]), out.scope))
                final_names.append(it.alias or plain_names[idx])
        return self._add_value_node(
            out, final_exprs, _dedup(final_names), None, description
        )

    def _plan_unnest(
        self, sel, items, unnest_items, upstream: RelOutput, where
    ) -> RelOutput:
        """unnest(list_col) explodes each row into one row per element
        (reference UnnestRewriter, rewriters.rs); other select items
        replicate across the exploded rows."""
        if len(unnest_items) != 1:
            raise SqlError("one unnest() per SELECT is supported")
        if upstream.updating:
            raise SqlError(
                "unnest() over an updating (retracting) input is not yet "
                "supported"
            )
        if sel.group_by or self._has_aggregate(items):
            raise SqlError(
                "unnest() cannot be combined with GROUP BY or aggregates "
                "in one SELECT; unnest in a subquery first"
            )
        for it in items:
            if it is unnest_items[0]:
                continue
            if _contains_unnest(it.expr):
                raise SqlError(
                    "unnest() must be a top-level SELECT item (wrap other "
                    "expressions around it in an outer query)"
                )
        call = unnest_items[0].expr
        if len(call.args) != 1:
            raise SqlError("unnest() takes one list-typed argument")
        list_expr = bind(call.args[0], upstream.scope)
        if not pa.types.is_list(list_expr.dtype):
            raise SqlError(
                f"unnest() requires a list argument, got {list_expr.dtype}"
            )
        display_name = unnest_items[0].alias or "unnest"
        # fresh internal name: a plain item aliased to the same name (e.g.
        # `SELECT id AS unnest, unnest(tags)`) must not collide in src_idx
        out_name = self._fresh("unnest")
        plain_items = [it for it in items if it is not unnest_items[0]]
        exprs, names = self._bind_items(plain_items, upstream.scope)
        exprs = exprs + [list_expr]
        names = _dedup(names + [self._fresh("list")])
        pre = self._add_value_node(
            upstream, exprs, names, where, "unnest_input"
        )
        list_idx = len(names) - 1
        value_type = list_expr.dtype.value_type
        out_fields = [
            pa.field(n, f.type)
            for n, f in zip(names[:-1], pre.schema.schema)
        ] + [pa.field(out_name, value_type)]
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        ts_idx = pre.schema.timestamp_index
        # static plan-time mapping: output field -> source column index
        # (-1 = the flattened values, -2 = timestamp)
        src_idx = [
            -1 if f.name == out_name
            else (-2 if f.name == TIMESTAMP_FIELD
                  else pre.schema.schema.names.index(f.name))
            for f in out_schema.schema
        ]

        def explode(batch):
            import pyarrow.compute as pc

            col = batch.column(list_idx)
            parents = pc.list_parent_indices(col)
            flat = pc.list_flatten(col)
            if len(flat) == 0:
                return None
            taken = batch.take(parents)
            arrays = [
                flat if i == -1
                else taken.column(ts_idx if i == -2 else i)
                for i in src_idx
            ]
            return pa.RecordBatch.from_arrays(
                arrays, schema=out_schema.schema
            )

        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.ARROW_VALUE,
                {"py_fn": explode, "schema": out_schema},
                "unnest",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(
            pre.node_id, node.node_id,
            self._edge(pre.node_id, self.parallelism), pre.schema,
        )
        out = RelOutput(
            node.node_id, out_schema, Scope.from_schema(out_schema.schema),
            window=upstream.window,
        )
        return self._restore_select_order(
            out, items, unnest_items[0], out_name, plain_items, names[:-1],
            "unnest_select", final_name=display_name,
        )

    def _plan_lateral_unnest(
        self, left: RelOutput, un: Unnest
    ) -> RelOutput:
        """FROM t CROSS JOIN UNNEST(t.col) AS x: one output row per list
        element, every left column replicated across the exploded rows."""
        if left.updating:
            raise SqlError(
                "UNNEST over an updating (retracting) input is not yet "
                "supported"
            )
        list_expr = bind(un.expr, left.scope)
        if not pa.types.is_list(list_expr.dtype):
            raise SqlError(
                f"UNNEST requires a list argument, got {list_expr.dtype}"
            )
        out_name = un.alias or "unnest"
        exprs, names = self._passthrough_exprs(left)
        exprs.append(list_expr)
        names = _dedup(names + [self._fresh("list")])
        pre = self._add_value_node(left, exprs, names, None, "unnest_input")
        list_idx = len(names) - 1
        value_type = list_expr.dtype.value_type
        out_fields = [
            pa.field(n, f.type)
            for n, f in zip(names[:-1], pre.schema.schema)
        ] + [pa.field(out_name, value_type)]
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        ts_idx = pre.schema.timestamp_index
        # positional mapping: passthrough cols, then the flattened values
        # (-1), then _timestamp (-2, appended last by add_timestamp_field)
        src_idx = list(range(list_idx)) + [-1, -2]

        def explode(batch):
            import pyarrow.compute as pc

            col = batch.column(list_idx)
            parents = pc.list_parent_indices(col)
            flat = pc.list_flatten(col)
            if len(flat) == 0:
                return None
            taken = batch.take(parents)
            arrays = [
                flat if i == -1
                else taken.column(ts_idx if i == -2 else i)
                for i in src_idx
            ]
            return pa.RecordBatch.from_arrays(
                arrays, schema=out_schema.schema
            )

        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.ARROW_VALUE,
                {"py_fn": explode, "schema": out_schema},
                "unnest",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(
            pre.node_id, node.node_id,
            self._edge(pre.node_id, self.parallelism), pre.schema,
        )
        return RelOutput(
            node.node_id, out_schema,
            self._requalified_scope(out_schema, left), window=left.window,
            window_field=_passthrough_window_field(left, names[:-1]),
        )

    def _plan_async_udf(
        self, sel, items, async_items, upstream: RelOutput, where
    ) -> RelOutput:
        """Async UDF select items plan as an AsyncUdf operator
        (reference async_udf.rs + planner AsyncUdf node): pre-project the
        plain items + the UDF's argument columns, run the async operator
        (which appends the result column), then restore SELECT order."""
        from ..udf import registry as udf_registry

        if len(async_items) != 1:
            raise SqlError("one async UDF per SELECT is supported")
        call = async_items[0].expr
        u = udf_registry.get(call.name)
        display_name = async_items[0].alias or call.name
        out_name = self._fresh("audf")  # internal; no alias collisions
        plain_items = [it for it in items if it is not async_items[0]]
        exprs, names = self._bind_items(plain_items, upstream.scope)
        arg_cols = []
        for a in call.args:
            exprs.append(bind(a, upstream.scope))
            names.append(self._fresh("aarg"))
            arg_cols.append(len(exprs) - 1)
        names = _dedup(names)
        pre = self._add_value_node(
            upstream, exprs, names, where, "async_udf_input"
        )
        out_fields = [
            pa.field(n, f.type)
            for n, f in zip(names, pre.schema.schema)
            if n != TIMESTAMP_FIELD
        ] + [pa.field(out_name, u.return_type)]
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.ASYNC_UDF,
                {
                    "udf": call.name,
                    "arg_cols": arg_cols,
                    "out_field": out_name,
                    "schema": out_schema,
                    "ordered": True,
                },
                f"async_{call.name}",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(
            pre.node_id, node.node_id,
            self._edge(pre.node_id, self.parallelism), pre.schema,
        )
        out = RelOutput(
            node.node_id, out_schema, Scope.from_schema(out_schema.schema),
            window=upstream.window,
        )
        return self._restore_select_order(
            out, items, async_items[0], out_name, plain_items, names,
            "async_udf_select", final_name=display_name,
        )

    def _plan_window_function(
        self, sel, items, upstream: RelOutput, where
    ) -> RelOutput:
        """SQL window functions (ROW_NUMBER/RANK/DENSE_RANK OVER
        (PARTITION BY ... ORDER BY ...)) evaluated per event-time window
        (reference plan/window_fn.rs + arrow/window_fn.rs)."""
        over_items = [
            it for it in items
            if isinstance(it.expr, FuncCall) and it.expr.over is not None
        ]
        if upstream.window is None:
            raise SqlError(
                "window functions require a windowed input (aggregate with "
                "tumble()/hop()/session() first)"
            )
        for it in over_items:
            if it.expr.name not in ("row_number", "rank", "dense_rank"):
                raise SqlError(
                    f"unsupported window function {it.expr.name}()"
                )
        # one WINDOW_FUNCTION operator per OVER item, chained; each stage
        # passes every upstream column through and appends its result
        # column, so later stages' PARTITION BY/ORDER BY still bind
        out = upstream
        pending_where = where
        out_cols: List[str] = []
        for it in over_items:
            out_name = self._fresh("wfn")  # internal; no alias collisions
            out = self._add_window_fn_stage(
                out, it.expr, pending_where, out_name
            )
            pending_where = None  # WHERE applies once, before the first
            out_cols.append(out_name)
        # final projection restoring SELECT item order
        final_exprs: List[BoundExpr] = []
        final_names: List[str] = []
        for it in items:
            hit = next(
                (i for i, o in enumerate(over_items) if o is it), None
            )
            if hit is not None:
                final_exprs.append(bind(Column(out_cols[hit]), out.scope))
                final_names.append(it.alias or it.expr.name)
            else:
                e = bind(it.expr, out.scope)
                final_exprs.append(e)
                final_names.append(it.alias or _default_name(it.expr, e))
        return self._add_value_node(
            out, final_exprs, _dedup(final_names), None, "window_fn_select"
        )

    def _passthrough_exprs(
        self, upstream: RelOutput
    ) -> Tuple[List[BoundExpr], List[str]]:
        """Pass every non-timestamp upstream column through by index
        (indices are stable, so qualified names stay remappable)."""
        exprs: List[BoundExpr] = []
        names: List[str] = []
        for i, f in enumerate(upstream.schema.schema):
            if f.name == TIMESTAMP_FIELD:
                continue
            exprs.append(
                BoundExpr(
                    (lambda j: lambda b: b.column(j))(i), f.type, f.name
                )
            )
            names.append(f.name)
        return exprs, names

    def _requalified_scope(
        self, schema: StreamSchema, upstream: RelOutput
    ) -> Scope:
        """Scope over `schema` that also resolves the upstream's qualified
        names — valid when `schema` starts with a pass-through of the
        upstream's non-timestamp columns in order."""
        ts_idx = upstream.schema.timestamp_index
        scope = Scope.from_schema(schema.schema)
        for c in upstream.scope.cols:
            if c.qualifier is not None and c.index != ts_idx:
                new_idx = c.index if c.index < ts_idx else c.index - 1
                scope.add(c.qualifier, c.name, new_idx, c.dtype)
        return scope

    def _add_window_fn_stage(
        self, upstream: RelOutput, call: FuncCall,
        where: Optional[BoundExpr], out_name: str,
    ) -> RelOutput:
        """One window-function operator: pass-through pre-projection (+
        fresh PARTITION BY/ORDER BY columns), then the WINDOW_FUNCTION
        node appending `out_name`."""
        exprs, names = self._passthrough_exprs(upstream)
        part_idx: List[int] = []
        for p in call.over.partition_by:
            # the window column partitions implicitly (rows bin by their
            # window's timestamp), so drop it from PARTITION BY
            b = bind(p, upstream.scope)
            if pa.types.is_struct(b.dtype):
                continue
            exprs.append(b)
            names.append(self._fresh("part"))
            part_idx.append(len(exprs) - 1)
        order_by: List[tuple] = []
        for o, desc in call.over.order_by:
            b = bind(o, upstream.scope)
            exprs.append(b)
            names.append(self._fresh("ord"))
            order_by.append((len(exprs) - 1, desc))
        names = _dedup(names)
        pre = self._add_value_node(
            upstream, exprs, names, where, "window_fn_input"
        )
        out_fields = [
            pa.field(n, f.type)
            for n, f in zip(names, pre.schema.schema)
            if n != TIMESTAMP_FIELD
        ] + [pa.field(out_name, pa.int64())]
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.WINDOW_FUNCTION,
                {
                    "fn": call.name,
                    "partition_cols": part_idx,
                    "order_by": [list(o) for o in order_by],
                    "schema": out_schema,
                    "out_field": out_name,
                },
                f"{call.name}_over",
                parallelism=1,  # bins must see all partitions' rows
            )
        )
        self.graph.add_edge(
            pre.node_id, node.node_id, self._edge(pre.node_id, 1), pre.schema
        )
        return RelOutput(
            node.node_id, out_schema,
            self._requalified_scope(out_schema, upstream),
            window=upstream.window, window_field=upstream.window_field
            if upstream.window_field in out_schema.names else None,
        )

    def _plan_updating_aggregate(
        self, sel, items, upstream, where, group_exprs, key_bound
    ) -> RelOutput:
        """Non-windowed GROUP BY: updating aggregate emitting retract/append
        pairs (reference incremental_aggregator.rs / plan/aggregate.rs
        UpdatingAggregateExtension)."""
        from ..schema import UPDATING_META_FIELD, UPDATING_META_TYPE

        key_names = _dedup(
            [_default_name(g, b) for g, b in zip(group_exprs, key_bound)]
        )
        agg_calls, agg_inputs = _collect_aggregates(items, upstream.scope)
        pre_exprs = list(key_bound)
        pre_names = list(key_names)
        agg_col_idx: List[List[int]] = []
        for bs in agg_inputs:
            idxs = []
            for b in bs:
                pre_exprs.append(b)
                pre_names.append(self._fresh("agg_in"))
                idxs.append(len(pre_exprs) - 1)
            agg_col_idx.append(idxs)
        pre = self._add_value_node(
            upstream, pre_exprs, pre_names, where, "agg_input"
        )
        specs = []
        agg_out_names = []
        for call, col_idx in zip(agg_calls, agg_col_idx):
            specs.append(
                _make_spec(call, col_idx, pre_exprs, self._fresh("agg_out"))
            )
            agg_out_names.append(specs[-1]["name"])
        if upstream.updating:
            # retraction-consuming aggregation: invertible aggregates
            # (add-reductions and multisets) apply retract rows with sign
            # -1; everything else (min/max/median/UDAF/...) switches to
            # raw-value replay through the signed multiset (reference
            # incremental_aggregator.rs raw-value replay, :77-90)
            invertible = ("count", "sum", "avg", "count_distinct",
                          "approx_distinct", *VAR_KINDS_SQL, *REGR_KINDS_SQL)
            for s in specs:
                if s["kind"] not in invertible and not s["distinct"]:
                    s["replay"] = True
        out_fields = [
            pa.field(n, pre.schema.schema.field(i).type)
            for i, n in enumerate(key_names)
        ]
        for spec, call in zip(specs, agg_calls):
            out_fields.append(
                pa.field(spec["name"],
                         _agg_output_type(spec, call, pre.schema.schema))
            )
        out_fields.append(pa.field(UPDATING_META_FIELD, UPDATING_META_TYPE))
        agg_out_schema = StreamSchema(
            add_timestamp_field(pa.schema(out_fields))
        )
        agg_par = self.parallelism if key_names else 1
        agg_config = {
            "aggregates": specs,
            "key_cols": list(range(len(key_names))),
            "schema": agg_out_schema,
        }
        if upstream.updating:
            agg_config["retractable"] = True
            agg_config["meta_col"] = pre.schema.schema.names.index(
                UPDATING_META_FIELD
            )
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.UPDATING_AGGREGATE,
                agg_config,
                "updating_aggregate",
                parallelism=agg_par,
            )
        )
        self.graph.add_edge(
            pre.node_id, node.node_id, EdgeType.SHUFFLE,
            pre.schema.with_keys(key_names) if key_names else pre.schema,
        )
        agg_out = RelOutput(
            node.node_id,
            agg_out_schema,
            Scope.from_schema(agg_out_schema.schema),
            updating=True,
        )
        post_scope = _agg_post_scope(
            agg_out, key_names, group_exprs, agg_calls, agg_out_names
        )
        having = (
            bind(
                _rewrite_group_refs(
                    _rewrite_aggregates(sel.having, agg_calls, agg_out_names),
                    group_exprs, key_names,
                ),
                post_scope,
            )
            if sel.having is not None
            else None
        )
        post_exprs: List[BoundExpr] = []
        post_names: List[str] = []
        for it in items:
            rewritten = _rewrite_aggregates(it.expr, agg_calls, agg_out_names)
            rewritten = _rewrite_group_refs(rewritten, group_exprs, key_names)
            e = bind(rewritten, post_scope)
            post_exprs.append(e)
            post_names.append(it.alias or _default_name(it.expr, e))
        return self._add_value_node(
            agg_out, post_exprs, _dedup(post_names), having,
            _describe_items(post_names),
        )

    def _resolve_group_ref(self, g: Expr, items: List[SelectItem]) -> Expr:
        if isinstance(g, Literal) and isinstance(g.value, int):
            idx = g.value - 1
            if idx < 0 or idx >= len(items):
                raise SqlError(f"GROUP BY ordinal {g.value} out of range")
            return items[idx].expr
        if isinstance(g, Column) and g.table is None:
            hit = _find_item_by_alias(items, g.name)
            if hit is not None and not isinstance(hit.expr, Column):
                return hit.expr
        return g

    # -- joins --------------------------------------------------------------

    def plan_join(self, rel: Join) -> RelOutput:
        # FROM tbl CROSS JOIN UNNEST(expr) AS x — lateral explode
        # (reference: DataFusion's LogicalPlan::Unnest via UnnestRewriter)
        if isinstance(rel.right, Unnest):
            if rel.condition is not None:
                raise SqlError("UNNEST join takes no ON condition")
            return self._plan_lateral_unnest(
                self.plan_relation(rel.left), rel.right
            )
        if isinstance(rel.left, Unnest):
            raise SqlError("UNNEST must be the right side of a CROSS JOIN")
        # lookup tables join via the LookupConnector path (reference:
        # LookupExtension + lookup_join.rs)
        if isinstance(rel.right, TableRef):
            t = self.provider.get_table(rel.right.name)
            if t is not None and t.table_type == "lookup":
                return self._plan_lookup_join(rel, t)
        left = self.plan_relation(rel.left)
        right = self.plan_relation(rel.right)
        if rel.condition is None:
            raise SqlError("JOIN requires an ON condition")
        merged_scope = left.scope.merge(
            right.scope, len(left.schema.schema)
        )
        equi, residual = _split_join_condition(rel.condition)
        if not equi:
            raise SqlError("JOIN requires at least one equality condition")
        left_keys: List[BoundExpr] = []
        right_keys: List[BoundExpr] = []
        for a, b in equi:
            sides = _classify_sides(a, b, left.scope, right.scope)
            if sides is None:
                raise SqlError(
                    f"join condition {a} = {b} must compare the two inputs"
                )
            le, re_ = sides
            left_keys.append(bind(le, left.scope))
            right_keys.append(bind(re_, right.scope))

        both_windowed = (
            left.window is not None
            and right.window is not None
            and left.window == right.window
        )
        if both_windowed and (left.updating or right.updating):
            raise SqlError(
                "windowed joins over updating inputs are not yet supported"
            )
        windowed = both_windowed

        # project each side to key columns + payload
        lpre, nkeys = self._join_side_projection(left, left_keys, "jl")
        rpre, _ = self._join_side_projection(right, right_keys, "jr")

        out_fields, left_names, right_names = _join_output_fields(
            lpre, rpre, nkeys
        )
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        config = {
            "n_keys": nkeys,
            "join_type": rel.join_type,
            "schema": out_schema,
            "left_fields": left_names,
            "right_fields": right_names,
            "left_schema": lpre.schema,
            "right_schema": rpre.schema,
        }
        if residual:
            if not windowed and rel.join_type != "inner":
                raise SqlError(
                    "non-equality conditions on updating outer joins are "
                    "not yet supported (they change match semantics)"
                )
            # inner joins filter joined rows symmetrically (appends and the
            # retracts that cancel them see the same predicate)
            config["residual_py"] = self._bind_residual(
                residual, out_schema, left, right, lpre, rpre, nkeys
            )
        if windowed:
            op = OperatorName.INSTANT_JOIN
            config["window"] = dataclasses.asdict(left.window)
        else:
            # non-windowed joins materialize both sides and emit retraction
            # deltas (reference: updating joins); output is an updating
            # stream requiring a debezium-capable sink
            op = OperatorName.JOIN
            config["mode"] = "updating"
            from ..schema import UPDATING_META_FIELD, UPDATING_META_TYPE

            out_fields = out_fields + [
                pa.field(UPDATING_META_FIELD, UPDATING_META_TYPE)
            ]
            out_schema = StreamSchema(
                add_timestamp_field(pa.schema(out_fields))
            )
            config["schema"] = out_schema
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(), op, config, f"{rel.join_type}_join",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(
            lpre.node_id, node.node_id, EdgeType.LEFT_JOIN,
            lpre.schema.with_keys(lpre.schema.names[:nkeys]),
        )
        self.graph.add_edge(
            rpre.node_id, node.node_id, EdgeType.RIGHT_JOIN,
            rpre.schema.with_keys(rpre.schema.names[:nkeys]),
        )
        scope = _join_output_scope(left, right, lpre, rpre, out_schema, nkeys)
        return RelOutput(
            node.node_id, out_schema, scope,
            window=left.window if windowed else None,
            window_field=None,
            updating=not windowed,
        )

    def _plan_lookup_join(self, rel: Join, t: TableDef) -> RelOutput:
        from ..connectors import get_connector

        left = self.plan_relation(rel.left)
        if rel.join_type not in ("inner", "left"):
            raise SqlError("lookup joins support INNER and LEFT JOIN")
        alias = rel.right.alias or rel.right.name
        right_fields = [f.name for f in t.fields]
        # condition must be stream_expr = lookup_key_column
        equi, residual = _split_join_condition(rel.condition)
        if len(equi) != 1 or residual:
            raise SqlError(
                "lookup joins require exactly one equality condition on the "
                "lookup table's key column"
            )
        a, b = equi[0]
        right_scope = Scope.from_schema(pa.schema(list(t.fields)), alias)
        sides = _classify_sides(a, b, left.scope, right_scope)
        if sides is None:
            raise SqlError("lookup join condition must compare the stream "
                           "with the lookup table")
        stream_expr, key_expr = sides
        lookup_key = t.options.get(
            "lookup_key", t.fields[0].name if t.fields else None
        )
        if not (
            isinstance(key_expr, Column) and key_expr.name == lookup_key
        ):
            raise SqlError(
                f"lookup joins must equate against {t.name}'s key column "
                f"{lookup_key!r} (got {key_expr})"
            )
        collisions = {f.name for f in t.fields} & {
            f.name for f in left.schema.schema if f.name != TIMESTAMP_FIELD
        }
        if collisions:
            raise SqlError(
                f"lookup table {t.name} fields collide with stream columns: "
                f"{sorted(collisions)} — alias or rename them"
            )
        conn = get_connector(t.connector)
        options = conn.validate_options(
            {k: v for k, v in t.options.items()
             if k not in ("connector", "type", "format")},
            None,
        )
        key_bound = bind(stream_expr, left.scope)
        exprs = [key_bound]
        names = ["__lookup_key"]
        for i, f in enumerate(left.schema.schema):
            if f.name == TIMESTAMP_FIELD:
                continue
            exprs.append(
                BoundExpr((lambda j: lambda bt: bt.column(j))(i), f.type,
                          f.name)
            )
            names.append(f.name)
        pre = self._add_value_node(left, exprs, _dedup(names), None, "lookup_in")
        out_fields = [
            f for f in pre.schema.schema
            if f.name not in (TIMESTAMP_FIELD, "__lookup_key")
        ] + [f for f in t.fields]
        out_schema = StreamSchema(add_timestamp_field(pa.schema(out_fields)))
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.LOOKUP_JOIN,
                {
                    "connector": t.connector,
                    "connector_config": options,
                    "key_col": 0,
                    "join_type": rel.join_type,
                    "right_fields": right_fields,
                    "schema": out_schema,
                },
                f"lookup_{t.name}",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(
            pre.node_id, node.node_id,
            self._edge(pre.node_id, self.parallelism), pre.schema,
        )
        scope = Scope.from_schema(out_schema.schema)
        for c in left.scope.cols:
            if c.qualifier and c.name in out_schema.names:
                scope.add(c.qualifier, c.name,
                          out_schema.names.index(c.name),
                          out_schema.schema.field(c.name).type)
        for f in t.fields:
            if f.name in out_schema.names:
                scope.add(alias, f.name, out_schema.names.index(f.name),
                          f.type)
        return RelOutput(node.node_id, out_schema, scope, window=left.window,
                         window_field=left.window_field)

    def _join_side_projection(
        self, side: RelOutput, keys: List[BoundExpr], tag: str
    ) -> Tuple[RelOutput, int]:
        """Key columns first, then all original columns. Struct keys (window
        structs) are exploded into child columns — Arrow's hash join does
        not take struct keys. Returns (projection, physical key count)."""
        import pyarrow.compute as pc

        exprs: List[BoundExpr] = []
        for k in keys:
            if pa.types.is_struct(k.dtype):
                for j in range(k.dtype.num_fields):
                    fname = k.dtype.field(j).name
                    exprs.append(
                        BoundExpr(
                            (lambda kk, fn: lambda b: pc.struct_field(
                                kk.eval(b), fn))(k, fname),
                            k.dtype.field(j).type,
                            fname,
                        )
                    )
            else:
                exprs.append(k)
        n_phys = len(exprs)
        names = [f"__key{i}" for i in range(n_phys)]
        for i, f in enumerate(side.schema.schema):
            if f.name == TIMESTAMP_FIELD:
                continue
            exprs.append(
                BoundExpr((lambda j: lambda b: b.column(j))(i), f.type, f.name)
            )
            names.append(f.name)
        return self._add_value_node(side, exprs, _dedup(names), None, tag), n_phys

    def _bind_residual(self, residual, out_schema, left, right, lpre, rpre,
                       nkeys):
        scope = _join_output_scope(left, right, lpre, rpre, out_schema, nkeys)
        from functools import reduce

        cond = reduce(lambda a, b: BinaryOp("AND", a, b), residual)
        bound = bind(cond, scope)

        def residual_fn(batch: pa.RecordBatch):
            return bound.eval(batch)

        return residual_fn

    # -- unions -------------------------------------------------------------

    def _plan_union(self, a: RelOutput, b: RelOutput) -> RelOutput:
        if len(a.schema.schema) != len(b.schema.schema):
            raise SqlError("UNION inputs must have the same number of columns")
        # align b's columns to a's schema (by position, cast types)
        exprs = []
        names = []
        for i, f in enumerate(a.schema.schema):
            if f.name == TIMESTAMP_FIELD:
                continue
            bf = b.schema.schema.field(i)
            be = BoundExpr(
                (lambda j: lambda bt: bt.column(j))(i), bf.type, f.name
            )
            if not bf.type.equals(f.type):
                from .expressions import _cast

                be = BoundExpr(
                    (lambda j, t: lambda bt: _cast(bt.column(j), t))(i, f.type),
                    f.type,
                    f.name,
                )
            exprs.append(be)
            names.append(f.name)
        b_aligned = self._add_value_node(b, exprs, names, None, "union_align")
        # merge node: identity op with two forward-ish edges (shuffle to
        # allow differing parallelism)
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.ARROW_VALUE,
                {"py_fn": lambda batch: batch, "schema": a.schema},
                "union",
                parallelism=self.parallelism,
            )
        )
        self.graph.add_edge(a.node_id, node.node_id, EdgeType.SHUFFLE, a.schema)
        self.graph.add_edge(
            b_aligned.node_id, node.node_id, EdgeType.SHUFFLE, b_aligned.schema
        )
        return RelOutput(
            node.node_id, a.schema, Scope.from_schema(a.schema.schema)
        )

    # -- sinks --------------------------------------------------------------

    def plan_insert(self, ins: Insert) -> Optional[int]:
        sink_table = self.provider.get_table(ins.table)
        if sink_table is None:
            raise SqlError(f"unknown sink table {ins.table}")
        out = self.plan_select(ins.query)
        if sink_table.is_memory:
            self._connect_memory(sink_table, out)
            return None
        return self._connect_sink(sink_table, out)

    def _connect_memory(self, t: TableDef, out: RelOutput):
        """INSERT INTO a memory (connector-less) table: positional-cast the
        select output to the declared columns and register the node as the
        table's readable stream."""
        if out.updating:
            raise SqlError(
                f"INSERT into memory table {t.name} from an updating "
                "(retracting) stream is not supported"
            )
        if t.name.lower() in self._memory_tables:
            raise SqlError(
                f"memory table {t.name} already has an INSERT; a single "
                "writer defines it"
            )
        declared = t.fields
        if not declared:
            raise SqlError(
                f"memory table {t.name} must declare its columns"
            )
        data_cols = [
            f for f in out.schema.schema if f.name != TIMESTAMP_FIELD
        ]
        if declared and len(declared) != len(data_cols):
            raise SqlError(
                f"memory table {t.name} declares {len(declared)} columns, "
                f"query produces {len(data_cols)}"
            )
        exprs, names = [], []
        for df, qf in zip(declared, data_cols):
            idx = out.schema.schema.names.index(qf.name)
            be = BoundExpr(
                (lambda j: lambda b: b.column(j))(idx), qf.type, df.name
            )
            if not qf.type.equals(df.type):
                from .expressions import _cast

                be = BoundExpr(
                    (lambda j, tt: lambda b: _cast(b.column(j), tt))(
                        idx, df.type
                    ),
                    df.type,
                    df.name,
                )
            exprs.append(be)
            names.append(df.name)
        rel = self._add_value_node(
            out, exprs, names, None, f"memory_{t.name}"
        )
        self._memory_tables[t.name.lower()] = rel

    def _connect_sink(self, t: TableDef, out: RelOutput) -> int:
        from ..connectors import get_connector

        conn = get_connector(t.connector)
        # cast/select columns to the declared sink schema by position
        from ..schema import UPDATING_META_FIELD

        if out.updating:
            # retract rows need an encoding; plain json/raw sinks would
            # silently serialize them as appends
            fmt = t.options.get("format")
            if fmt != "debezium_json" and t.connector not in (
                "vec", "preview", "blackhole"
            ):
                raise SqlError(
                    f"sink {t.name} receives an updating stream and must use "
                    "format = 'debezium_json' (or a debug sink)"
                )
        declared = t.fields
        data_cols = [
            f for f in out.schema.schema
            if f.name not in (TIMESTAMP_FIELD, UPDATING_META_FIELD)
        ]
        if declared and len(declared) != len(data_cols):
            raise SqlError(
                f"sink {t.name} expects {len(declared)} columns, query "
                f"produces {len(data_cols)}"
            )
        rel = out
        if declared:
            exprs = []
            names = []
            for i, (df, qf) in enumerate(zip(declared, data_cols)):
                idx = out.schema.schema.names.index(qf.name)
                be = BoundExpr(
                    (lambda j: lambda b: b.column(j))(idx), qf.type, df.name
                )
                if not qf.type.equals(df.type):
                    from .expressions import _cast

                    be = BoundExpr(
                        (lambda j, tt: lambda b: _cast(b.column(j), tt))(
                            idx, df.type
                        ),
                        df.type,
                        df.name,
                    )
                exprs.append(be)
                names.append(df.name)
            rel = self._add_value_node(out, exprs, names, None, "sink_cast")
        # several INSERT INTO statements targeting one sink table merge
        # into a single sink node with one in-edge per statement (the
        # reference's test_merge_sink.sql shape; barrier alignment across
        # the edges is the runner's normal multi-input path)
        existing = self._sink_nodes.get(t.name)
        if existing is not None:
            prev_schema, sink_par = existing["schema"], existing["par"]
            if not prev_schema.schema.equals(rel.schema.schema):
                raise SqlError(
                    f"INSERT statements into sink {t.name} produce "
                    "different schemas (mixing updating and append streams "
                    "into one sink is not supported)"
                )
            self.graph.add_edge(
                rel.node_id, existing["node"],
                self._edge(rel.node_id, sink_par), rel.schema,
            )
            return existing["node"]
        options = conn.validate_options(
            {k: v for k, v in t.options.items()
             if k not in ("connector", "type", "format")},
            None,
        )
        config = {
            "connector": t.connector,
            "schema": rel.schema,
            "format": t.options.get("format"),
            "proto_descriptor": _proto_descriptor(t),
            **options,
        }
        # sinks default to parallelism 1 (single_file/stdout write one
        # stream; scalable sinks opt in via the sink_parallelism option)
        sink_par = int(t.options.get("sink_parallelism", 1))
        node = self.graph.add_node(
            LogicalNode.single(
                self._next_id(),
                OperatorName.CONNECTOR_SINK,
                config,
                t.name,
                parallelism=sink_par,
            )
        )
        self.graph.add_edge(
            rel.node_id, node.node_id,
            self._edge(rel.node_id, sink_par), rel.schema,
        )
        self._sink_nodes[t.name] = {
            "node": node.node_id, "schema": rel.schema, "par": sink_par,
        }
        return node.node_id


# ---------------------------------------------------------------------------
# Aggregate helpers
# ---------------------------------------------------------------------------


def _is_aggregate_name(name: str) -> bool:
    if name in AGG_FUNCS:
        return True
    from ..udf.registry import get_udaf

    return get_udaf(name) is not None


def _proto_descriptor(t) -> Optional[dict]:
    """Load {'descriptor_set', 'message_name'} from the table's
    proto.descriptor_file / proto.message options when format='protobuf'
    (reference proto/schema resolution, arroyo-formats/src/proto)."""
    if t.options.get("format") not in ("protobuf", "proto"):
        return None
    path = t.options.get("proto.descriptor_file")
    msg = t.options.get("proto.message")
    if not path or not msg:
        raise SqlError(
            "format = 'protobuf' requires the proto.descriptor_file "
            "(compiled FileDescriptorSet from `protoc "
            "--descriptor_set_out`) and proto.message options"
        )
    if t.connector in ("single_file", "filesystem"):
        raise SqlError(
            "protobuf is message-framed binary and cannot ride "
            "newline-framed file connectors; use a message-based "
            "connector (e.g. kafka)"
        )
    if t.connector not in ("kafka", "confluent"):
        raise SqlError(
            f"format = 'protobuf' is wired to the kafka/confluent "
            f"connectors; {t.connector} does not carry a descriptor yet"
        )
    try:
        with open(path, "rb") as f:
            return {"descriptor_set": f.read(), "message_name": msg}
    except OSError as e:
        raise SqlError(f"cannot read proto.descriptor_file {path!r}: {e}")


def _find_aggregates(e: Expr) -> List[FuncCall]:
    out: List[FuncCall] = []

    def walk(x):
        if (
            isinstance(x, FuncCall)
            and _is_aggregate_name(x.name)
            and x.over is None
        ):
            out.append(x)
            return  # don't descend into agg args
        for c in expr_children(x):
            walk(c)

    walk(e)
    return out


def _agg_column_args(call: FuncCall) -> List[Expr]:
    """The column-input arguments of an aggregate call (trailing literal
    parameters like the percentile fraction excluded), arity-checked."""
    n_params = PARAM_AGGS.get(call.name, 0)
    col_args = call.args[: len(call.args) - n_params] if n_params else list(
        call.args
    )
    if call.name in TWO_ARG_AGGS:
        want = 2
    elif call.name not in AGG_FUNCS:
        from ..udf.registry import get_udaf

        u = get_udaf(call.name)
        want = min(len(u.arg_types), 2) if u is not None else 1
    else:
        want = 1
    if len(col_args) != want:
        raise SqlError(
            f"{call.name}() takes {want} column argument(s)"
            + (f" plus {n_params} literal parameter(s)" if n_params else "")
        )
    for p in call.args[len(col_args):]:
        if not isinstance(p, Literal):
            raise SqlError(
                f"{call.name}(): the trailing parameter must be a literal"
            )
    return col_args


def _collect_aggregates(items, scope):
    """Unique aggregate calls across select items + their bound column
    inputs (a list per call: [] for count(*), one entry for most, two for
    the regression family / weighted percentile)."""
    agg_calls: List[FuncCall] = []
    for it in items:
        for call in _find_aggregates(it.expr):
            if call not in agg_calls:
                agg_calls.append(call)
    agg_inputs: List[List[BoundExpr]] = []
    for call in agg_calls:
        if call.star or not call.args:
            if call.name != "count":
                raise SqlError(f"{call.name}() requires an argument")
            agg_inputs.append([])
            continue
        agg_inputs.append(
            [bind(a, scope) for a in _agg_column_args(call)]
        )
    return agg_calls, agg_inputs


def _rewrite_group_refs(
    e: Expr, group_exprs: List[Expr], key_names: List[str]
) -> Expr:
    """Replace subtrees structurally equal to a group-by expression with a
    reference to the aggregate's key output column."""
    if e is None:
        return None
    for g, name in zip(group_exprs, key_names):
        if e == g:
            return Column(name)
    if isinstance(e, BinaryOp):
        return BinaryOp(
            e.op,
            _rewrite_group_refs(e.left, group_exprs, key_names),
            _rewrite_group_refs(e.right, group_exprs, key_names),
        )
    if isinstance(e, FieldAccess):
        return FieldAccess(
            _rewrite_group_refs(e.base, group_exprs, key_names), e.field
        )
    if isinstance(e, FuncCall):
        return FuncCall(
            e.name,
            [_rewrite_group_refs(a, group_exprs, key_names) for a in e.args],
            e.distinct,
            e.star,
            e.over,
        )
    return e


def _rewrite_aggregates(
    e: Expr, calls: List[FuncCall], names: List[str]
) -> Expr:
    """Replace aggregate calls in an expression with references to the
    window operator's output columns."""
    if e is None:
        return None
    for call, name in zip(calls, names):
        if e == call:
            return Column(name)
    if isinstance(e, BinaryOp):
        return BinaryOp(
            e.op,
            _rewrite_aggregates(e.left, calls, names),
            _rewrite_aggregates(e.right, calls, names),
        )
    if isinstance(e, FieldAccess):
        return FieldAccess(_rewrite_aggregates(e.base, calls, names), e.field)
    if isinstance(e, FuncCall) and not (
        _is_aggregate_name(e.name) and e.over is None
    ):
        return FuncCall(
            e.name,
            [_rewrite_aggregates(a, calls, names) for a in e.args],
            e.distinct,
            e.star,
            e.over,
        )
    return e


def _make_spec(call: FuncCall, col_idx: list, pre_exprs, name: str) -> dict:
    from ..udf.registry import get_udaf

    kind = AGG_ALIASES.get(call.name, call.name)
    udaf = None
    if kind not in AGG_FUNCS and get_udaf(call.name) is not None:
        kind, udaf = "udaf", call.name
    distinct = False
    if call.distinct:
        if kind == "count":
            kind = "count_distinct"
        elif kind in ("sum", "avg", "min", "max") or kind == "udaf" or (
            kind in ("median", "approx_median", "array_agg")
        ):
            # dedupe through the value multiset, finalized per kind
            distinct = True
        else:
            raise SqlError(
                f"DISTINCT is not supported with {kind}()"
            )
    col = col_idx[0] if col_idx else None
    col2 = col_idx[1] if len(col_idx) > 1 else None
    param = None
    if call.name in PARAM_AGGS:
        lit = call.args[-1]
        param = float(lit.value)
        if not 0.0 <= param <= 1.0:
            raise SqlError(
                f"{call.name}(): percentile must be between 0 and 1"
            )
    is_float = (
        col is not None
        and pa.types.is_floating(pre_exprs[col].dtype)
    ) or kind == "avg"
    return {"kind": kind, "col": col, "name": name,
            "is_float": is_float, "udaf": udaf, "col2": col2,
            "param": param, "distinct": distinct}


def _agg_output_type(spec: dict, call: FuncCall, pre_schema: pa.Schema):
    from ..ops.aggregates import REGR_KINDS, VAR_KINDS

    kind = spec["kind"]
    if kind == "udaf":
        from ..udf.registry import get_udaf

        return get_udaf(spec["udaf"]).return_type
    if kind in ("count", "count_distinct", "approx_distinct",
                "bit_and", "bit_or", "bit_xor", "regr_count"):
        return pa.int64()
    if (
        kind == "avg"
        or kind in VAR_KINDS
        or kind in REGR_KINDS
        or kind in ("median", "approx_median", "approx_percentile_cont",
                    "approx_percentile_cont_with_weight")
    ):
        return pa.float64()
    if kind in ("bool_and", "bool_or"):
        return pa.bool_()
    col_t = pre_schema.field(spec["col"]).type
    if kind == "array_agg":
        return pa.list_(col_t)
    if kind == "sum":
        if pa.types.is_floating(col_t):
            return pa.float64()
        return pa.int64()
    return col_t  # min/max preserve type


def _agg_post_scope(agg_out, key_names, group_exprs, agg_calls, agg_names):
    """Scope over the window op output: group keys resolvable by their
    original names AND qualified forms."""
    scope = Scope.from_schema(agg_out.schema.schema)
    for i, g in enumerate(group_exprs):
        if isinstance(g, Column) and g.table is not None:
            scope.add(g.table, g.name, i, agg_out.schema.schema.field(i).type)
    return scope


# ---------------------------------------------------------------------------
# Join helpers
# ---------------------------------------------------------------------------


def _split_join_condition(cond: Expr):
    """AND-split into (equi pairs, residual exprs)."""
    conjuncts: List[Expr] = []

    def flat(e):
        if isinstance(e, BinaryOp) and e.op == "AND":
            flat(e.left)
            flat(e.right)
        else:
            conjuncts.append(e)

    flat(cond)
    equi, residual = [], []
    for c in conjuncts:
        if isinstance(c, BinaryOp) and c.op == "=":
            equi.append((c.left, c.right))
        else:
            residual.append(c)
    return equi, residual


def _side_of(e: Expr, scope: Scope) -> bool:
    """True if every column in e resolves in scope."""
    ok = True

    def walk(x):
        nonlocal ok
        if isinstance(x, Column):
            if scope.try_resolve(x.name, x.table) is None:
                ok = False
        elif isinstance(x, BinaryOp):
            walk(x.left)
            walk(x.right)
        elif isinstance(x, FieldAccess):
            walk(x.base)
        elif isinstance(x, FuncCall):
            for a in x.args:
                walk(a)
        elif hasattr(x, "operand"):
            walk(x.operand)

    walk(e)
    return ok


def _classify_sides(a: Expr, b: Expr, lscope: Scope, rscope: Scope):
    if _side_of(a, lscope) and _side_of(b, rscope):
        return a, b
    if _side_of(b, lscope) and _side_of(a, rscope):
        return b, a
    return None


def _join_output_fields(lpre: RelOutput, rpre: RelOutput, nkeys: int):
    """Left columns (keys + payload) then right payload; duplicate names get
    _right suffix. Input __updating_meta columns are consumed by the join
    itself (retraction routing), never forwarded. Returns
    (fields, left_names, right_names)."""
    from ..schema import UPDATING_META_FIELD

    fields: List[pa.Field] = []
    left_names: List[str] = []
    right_names: List[str] = []
    seen = set()
    for f in lpre.schema.schema:
        if f.name in (TIMESTAMP_FIELD, UPDATING_META_FIELD):
            continue
        fields.append(f)
        left_names.append(f.name)
        seen.add(f.name)
    for i, f in enumerate(rpre.schema.schema):
        if f.name in (TIMESTAMP_FIELD, UPDATING_META_FIELD) or i < nkeys:
            continue
        name = f.name
        while name in seen:
            name += "_right"
        seen.add(name)
        fields.append(pa.field(name, f.type))
        right_names.append(name)
    return fields, left_names, right_names


def _join_output_scope(left, right, lpre, rpre, out_schema, nkeys) -> Scope:
    scope = Scope.from_schema(out_schema.schema)
    # qualified access: left alias columns at their positions; right alias
    # payload after left block; right KEY columns resolve to the coalesced
    # left key positions
    from ..schema import UPDATING_META_FIELD

    left_quals = {c.qualifier for c in left.scope.cols if c.qualifier}
    right_quals = {c.qualifier for c in right.scope.cols if c.qualifier}
    n_left = len([
        f for f in lpre.schema.schema
        if f.name not in (TIMESTAMP_FIELD, UPDATING_META_FIELD)
    ])
    for q in left_quals:
        for c in left.scope.cols:
            if c.qualifier != q:
                continue
            hit = _find_field(out_schema, c.name)
            if hit is not None:
                scope.add(q, c.name, hit, out_schema.schema.field(hit).type)
    offset = n_left
    right_payload = [
        f for i, f in enumerate(rpre.schema.schema)
        if f.name not in (TIMESTAMP_FIELD, UPDATING_META_FIELD) and i >= nkeys
    ]
    for q in right_quals:
        for c in right.scope.cols:
            if c.qualifier != q:
                continue
            # payload position
            for j, f in enumerate(right_payload):
                if f.name == c.name or f.name == c.name + "_right":
                    idx = offset + j
                    scope.add(q, c.name, idx,
                              out_schema.schema.field(idx).type)
                    break
            else:
                # fall back to the coalesced left copy (join key)
                hit = _find_field(out_schema, c.name)
                if hit is not None:
                    scope.add(q, c.name, hit,
                              out_schema.schema.field(hit).type)
    return scope


def _find_field(schema: StreamSchema, name: str) -> Optional[int]:
    try:
        return schema.schema.names.index(name)
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# misc helpers
# ---------------------------------------------------------------------------


def _contains_unnest(e: Expr) -> bool:
    if isinstance(e, FuncCall) and e.name == "unnest":
        return True
    return any(_contains_unnest(c) for c in expr_children(e))


def _expr_references(e: Expr, col_name: str) -> bool:
    if isinstance(e, Column) and e.name.lower() == col_name.lower():
        return True
    return any(_expr_references(c, col_name) for c in expr_children(e))


def _find_item_by_alias(items: List[SelectItem], name: str):
    for it in items:
        if it.alias == name:
            return it
    return None


def _default_name(e: Expr, bound: BoundExpr) -> str:
    if isinstance(e, Column):
        return e.name
    if isinstance(e, FieldAccess):
        return e.field
    if isinstance(e, FuncCall):
        return e.name
    return bound.name


def _dedup(names: List[str]) -> List[str]:
    seen: Dict[str, int] = {}
    out = []
    for n in names:
        if n in seen:
            seen[n] += 1
            out.append(f"{n}_{seen[n]}")
        else:
            seen[n] = 0
            out.append(n)
    return out


def _describe_items(names: List[str]) -> str:
    s = ", ".join(names[:4])
    return f"select({s}{'...' if len(names) > 4 else ''})"


def _passthrough_window_field(upstream: RelOutput, names: List[str]):
    if upstream.window_field and upstream.window_field in names:
        return upstream.window_field
    return None


def _requalify(out: RelOutput, alias: Optional[str]) -> RelOutput:
    scope = Scope.from_schema(out.schema.schema, alias)
    return RelOutput(
        out.node_id, out.schema, scope, out.window, out.window_field,
        out.updating,
    )


def _opt_float(v):
    return float(v) if v is not None else None


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PlanResult:
    graph: LogicalGraph
    provider: SchemaProvider
    sink_nodes: List[int]
    # source table -> (leaf fields it hands its readers, leaf fields it
    # declares), `_timestamp` counted: fewer where plan_query narrowed the
    # table (sql/pruning.py)
    source_fields: Dict[str, Tuple[int, int]] = dataclasses.field(
        default_factory=dict
    )


def plan_query(
    sql: str,
    provider: Optional[SchemaProvider] = None,
    parallelism: int = 1,
    preview_results: Optional[list] = None,
) -> PlanResult:
    """Compile a SQL script (CREATE TABLE/VIEW + INSERT/SELECT statements)
    into a LogicalGraph (reference: parse_and_get_arrow_program)."""
    provider = provider or SchemaProvider()
    statements = parse_statements(sql)
    planner, sinks = _plan_script(
        statements, provider, parallelism, preview_results, {}
    )
    # required-field pruning engages from what that plan shows: a source
    # whose node still ends at its watermark op has no consumer chained
    # behind it, so its whole row crosses every out-edge (a fan-out, a
    # consumer at another parallelism, chaining off). Where the statements
    # read less than such a table declares, the script is planned again
    # with the table narrowed; every other plan is left op for op
    narrowed = _narrowable_sources(planner.graph, provider, statements)
    if narrowed:
        planner, sinks = _plan_script(
            statements, provider, parallelism, preview_results, narrowed
        )
    from .pruning import leaf_count

    fields = {}
    for node in planner.graph.nodes.values():
        if not node.is_source:
            continue
        declared = node.head.config["schema"]
        sent = narrowed.get(node.head.description.lower(), declared)
        fields[node.head.description] = (
            leaf_count(sent.schema), leaf_count(declared.schema)
        )
    return PlanResult(planner.graph, provider, sinks, fields)


def _narrowable_sources(
    graph: LogicalGraph, provider: SchemaProvider, statements
) -> Dict[str, StreamSchema]:
    """Source table (lowercased) -> the fields its readers read, for the
    tables of `graph` whose whole row crosses an edge and declares more."""
    wide = [
        n.head for n in graph.nodes.values()
        if n.is_source
        and n.chain[-1].operator is OperatorName.EXPRESSION_WATERMARK
    ]
    if not wide:
        return {}
    from .pruning import direct_readers, kept_schema

    readers, given_up = direct_readers(
        [st.query if isinstance(st, Insert) else st for st in statements
         if isinstance(st, (Insert, Select))]
        + list(provider.views.values())
    )
    narrowed: Dict[str, StreamSchema] = {}
    for head in wide:
        key = head.description.lower()
        if key in given_up or key not in readers:
            continue
        t = provider.get_table(key)
        # what the table's own DDL names stays whole
        ddl = [*t.generated, *t.metadata_fields]
        if t.options.get("event_time_field"):
            ddl.append(t.options["event_time_field"])
        for gexpr in t.generated.values():
            ddl.extend(_column_names(gexpr))
        kept = kept_schema(head.config["schema"], t.name, readers[key], ddl)
        if kept is not None:
            narrowed[key] = kept
    return narrowed


def _plan_script(
    statements, provider: SchemaProvider, parallelism: int,
    preview_results: Optional[list], narrowed: Dict[str, StreamSchema],
) -> Tuple[Planner, List[int]]:
    planner = Planner(provider, parallelism, narrowed)
    sinks: List[int] = []
    queries: List[Select] = []
    inserts: List[Insert] = []
    for st in statements:
        if isinstance(st, CreateTable):
            fields = [
                pa.field(c.name, sql_type_to_arrow(c.type_name), c.nullable)
                for c in st.columns
            ]
            provider.add_table(TableDef(
                st.name, fields, st.options,
                metadata_fields={
                    c.name: c.metadata_key for c in st.columns
                    if c.metadata_key
                },
                generated={
                    c.name: c.generated for c in st.columns
                    if c.generated is not None
                },
            ))
        elif isinstance(st, CreateView):
            provider.add_view(st.name, st.query)
        elif isinstance(st, Insert):
            inserts.append(st)
        elif isinstance(st, Select):
            queries.append(st)
    for ins in inserts:
        sink_id = planner.plan_insert(ins)
        if sink_id is not None:  # memory-table inserts have no sink node
            sinks.append(sink_id)
    for q in queries:
        out = planner.plan_select(q)
        # bare SELECT: attach a preview sink
        node = planner.graph.add_node(
            LogicalNode.single(
                planner._next_id(),
                OperatorName.CONNECTOR_SINK,
                {
                    "connector": "preview",
                    "results": preview_results if preview_results is not None
                    else [],
                    "schema": out.schema,
                },
                "preview",
            )
        )
        planner.graph.add_edge(
            out.node_id, node.node_id,
            planner._edge(out.node_id, 1), out.schema,
        )
        sinks.append(node.node_id)
    if not sinks:
        raise SqlError("query contains no INSERT or SELECT statement")
    # operator chaining at compile time, like the reference
    # (arroyo-planner/src/lib.rs:935-937 behind pipeline.chaining.enabled):
    # fused Forward chains execute in ONE subtask with direct calls, which
    # also guarantees they can never be scheduled onto different workers.
    # A source left unchained (a fan-out, chaining off) would ship full
    # pre-projection rows (e.g. nexmark structs) over its edges: plan_query
    # sees that here and narrows the table to the fields read
    from ..config import config as _config

    if _config().pipeline.chaining_enabled:
        from ..graph import ChainingOptimizer

        ChainingOptimizer().optimize(planner.graph)
    # segment fusion rides ON the chained nodes: maximal runs of
    # stateless value ops inside each chain become one FUSED_SEGMENT op
    # (one dispatch per batch); with engine.segment_fusion off the pass
    # instead annotates the members so the unfused A/B run counts its
    # per-operator dispatches into the same families
    from ..engine.segments import SegmentFusionPass

    SegmentFusionPass().optimize(planner.graph)
    return planner, sinks


def _column_names(e: Expr) -> List[str]:
    if isinstance(e, Column):
        return [e.name] + ([e.table] if e.table else [])
    return [n for c in expr_children(e) for n in _column_names(c)]
