"""_LazyFilteredBatch coverage (ADVICE round 5): every expression family
must evaluate correctly through a PARTIALLY-selective predicate — the
only path that builds the lazy filtered view (zero-pass and all-pass
predicates bypass it) — and an expression reaching for an unsupported
RecordBatch attribute must fail with a descriptive AttributeError naming
the view, not an anonymous duck-typing error."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from arroyo_tpu.sql.expressions import (
    CompiledProjection,
    Scope,
    _LazyFilteredBatch,
    bind,
)
from arroyo_tpu.sql.parser import parse_expr_text


def _batch(n=10):
    return pa.RecordBatch.from_arrays(
        [
            pa.array(np.arange(n, dtype=np.int64)),
            pa.array(np.arange(n, dtype=np.float64) * 1.5),
            pa.array([f"s{i}" for i in range(n)]),
            pa.array(
                np.arange(n, dtype=np.int64) * 1_000_000_000
            ).cast(pa.timestamp("ns")),
            pa.array([[i, i + 1] for i in range(n)],
                     type=pa.list_(pa.int64())),
        ],
        names=["a", "f", "s", "t", "l"],
    )


PREDICATE = "a % 2 = 0"  # partially selective: keeps half the rows

# one representative expression per family (arithmetic, comparison,
# boolean logic, CASE, CAST, null handling, math fn, string fns, LIKE,
# temporal extract/trunc, list ops)
FAMILY_EXPRS = [
    "a * 3 + 1",
    "f / 2.0 - a",
    "a >= 4",
    "a > 1 AND NOT (a = 6)",
    "CASE WHEN a < 4 THEN a ELSE -a END",
    "CAST(a AS DOUBLE) + 0.5",
    "coalesce(nullif(a, 2), -1)",
    "abs(a - 5)",
    "concat(s, '_x')",
    "upper(s)",
    "substr(s, 1, 1)",
    "s LIKE 's%'",
    "extract(second FROM t)",
    "date_trunc('minute', t)",
    "array_element(l, 1)",
    "cardinality(l)",
]


@pytest.mark.parametrize("expr_text", FAMILY_EXPRS)
def test_expression_families_through_partial_predicate(expr_text):
    batch = _batch()
    scope = Scope.from_schema(batch.schema)
    pred = bind(parse_expr_text(PREDICATE), scope)
    expr = bind(parse_expr_text(expr_text), scope)
    proj = CompiledProjection(
        [expr], pa.schema([pa.field("x", expr.dtype)]), predicate=pred
    )
    got = proj(batch)
    assert got is not None
    # reference: eager filter first, then evaluate (no lazy view)
    mask = pc.fill_null(pred.eval(batch), False)
    eager = batch.filter(mask)
    assert 0 < eager.num_rows < batch.num_rows, "predicate must be partial"
    want = expr.eval(eager)
    if not want.type.equals(got.column(0).type):
        want = want.cast(got.column(0).type)
    assert got.column(0).to_pylist() == want.to_pylist()
    assert got.num_rows == eager.num_rows


def test_lazy_view_names_itself_on_unsupported_attribute():
    batch = _batch()
    mask = pa.array(np.arange(batch.num_rows) % 2 == 0)
    view = _LazyFilteredBatch(batch, mask, 5)
    assert view.num_rows == 5
    assert view.column(0).to_pylist() == [0, 2, 4, 6, 8]
    with pytest.raises(AttributeError, match="_LazyFilteredBatch"):
        view.columns  # noqa: B018 - attribute probe is the assertion
    with pytest.raises(AttributeError, match="select"):
        view.select([0])


# -- a struct's child is filtered alone (ISSUE 43) ---------------------------
#
# Under a predicate, a struct child that an expression reads is taken
# first and filtered alone; the struct is filtered only where an
# expression reads it as a value, or where the expressions read every
# leaf of it. Whichever way, the output is the batch that filtering the
# WHOLE input and projecting afterwards gives: `RecordBatch.equals` and
# the conservation ledger's fingerprint.

N = 64
TS = pa.timestamp("ns")
PERSON = pa.struct([
    ("id", pa.int64()), ("name", pa.string()),
    ("email_address", pa.string()), ("credit_card", pa.string()),
    ("city", pa.string()), ("state", pa.string()), ("datetime", TS),
    ("extra", pa.string())])
AUCTION = pa.struct([
    ("id", pa.int64()), ("item_name", pa.string()),
    ("description", pa.string()), ("initial_bid", pa.int64()),
    ("reserve", pa.int64()), ("datetime", TS), ("expires", TS),
    ("seller", pa.int64()), ("category", pa.int64()),
    ("extra", pa.string())])
BID = pa.struct([
    ("auction", pa.int64()), ("bidder", pa.int64()), ("price", pa.int64()),
    ("channel", pa.string()), ("url", pa.string()), ("datetime", TS),
    ("extra", pa.string())])
RAW_LEAVES = 26  # NEXmark's 25 leaf fields and `_timestamp`


def _struct(t, rows, mask):
    """A struct column of type `t`: row i holds `rows(i)` and is null
    where `mask[i]`; a string child ending in "url" is null in every
    seventh row besides (a child's own nulls)."""
    kids = []
    for f in t:
        vals = [rows(i, f) for i in range(N)]
        kids.append(pa.array(vals, type=f.type))
    return pa.StructArray.from_arrays(kids, fields=list(t),
                                      mask=pa.array(mask))


def _cell(i, f):
    if f.name == "url" and i % 7 == 0:
        return None
    if pa.types.is_string(f.type):
        return f"{f.name}-{i % 5}"
    return i * 31 + len(f.name)  # ints and timestamps alike


def _nexmark(n_null_bid_every=8):
    """A raw NEXmark row as `benchmark/gen/nexmark.py` shapes it: three
    structs of which exactly one is set per event (1 person : 3 auctions
    : 46 bids in 50 there; every eighth row is no bid here), and the
    event time."""
    kind = np.array([0 if i % n_null_bid_every else (1 + i % 3 % 2)
                     for i in range(N)])  # 0 bid, 1 person, 2 auction
    return pa.RecordBatch.from_arrays(
        [_struct(PERSON, _cell, kind != 1),
         _struct(AUCTION, _cell, kind != 2),
         _struct(BID, _cell, kind != 0),
         pa.array(np.arange(N, dtype=np.int64) * 1000).cast(TS)],
        schema=pa.schema([
            pa.field("person", PERSON), pa.field("auction", AUCTION),
            pa.field("bid", BID), pa.field("_timestamp", TS, False)]))


META = pa.struct([
    ("origin", pa.struct([("host", pa.string()), ("port", pa.int64())])),
    ("seq", pa.int64())])


def _nested():
    """A struct in a struct with nulls at each level: `meta` null every
    fifth row, `meta.origin` every third, `meta.origin.port` every
    fourth."""
    origin = pa.StructArray.from_arrays(
        [pa.array([f"h{i}" for i in range(N)]),
         pa.array([None if i % 4 == 0 else i for i in range(N)],
                  type=pa.int64())],
        fields=list(META.field("origin").type),
        mask=pa.array([i % 3 == 0 for i in range(N)]))
    meta = pa.StructArray.from_arrays(
        [origin, pa.array(np.arange(N, dtype=np.int64))],
        fields=list(META), mask=pa.array([i % 5 == 0 for i in range(N)]))
    return pa.RecordBatch.from_arrays(
        [meta, pa.array(np.arange(N, dtype=np.int64))], names=["meta", "a"])


def _programs(batch, texts, predicate):
    """(the projection under `predicate`, the same one without it)."""
    scope = Scope.from_schema(batch.schema)
    exprs = [bind(parse_expr_text(t), scope) for t in texts]
    out = pa.schema([pa.field(f"c{i}", e.dtype) for i, e in enumerate(exprs)])
    pred = bind(parse_expr_text(predicate), scope)
    return (CompiledProjection(exprs, out, pred),
            CompiledProjection(exprs, out, None))


def _same(got, want):
    from arroyo_tpu.obs.audit import batch_fingerprint

    got.validate(full=True)
    assert got.schema.equals(want.schema)
    assert got.equals(want)
    assert batch_fingerprint(got) == batch_fingerprint(want)


def _filter_then_project(batch, texts, predicate):
    """The projection's output beside the reference's: the whole input
    filtered by the mask, then projected with no predicate. Also the
    view that the call built (None where it built none)."""
    with_pred, without = _programs(batch, texts, predicate)
    views = []
    built = with_pred.filtered

    def spy(b):
        v = built(b)
        if v is not None and v is not b:
            views.append(v)
        return v

    with_pred.filtered = spy
    got = with_pred(batch)
    mask = pc.fill_null(with_pred.predicate.eval(batch), False)
    eager = batch.filter(mask)
    want = without(eager) if eager.num_rows else None
    return got, want, (views[0] if views else None), with_pred


@pytest.mark.parametrize("expr_text", FAMILY_EXPRS)
def test_families_equal_filter_the_whole_batch_then_project(expr_text):
    got, want, view, _ = _filter_then_project(
        _batch(), [expr_text, "a"], PREDICATE)
    assert view is not None
    _same(got, want)


# (what the case holds, SELECT list, WHERE, the batch, the (column, *child
# path) the rule filters alone, leaf arrays through the filter kernel)
STRUCT_CASES = [
    ("q5's program: one int child of seven, null parents dropped",
     ["bid.auction", "_timestamp"], "bid IS NOT NULL", _nexmark,
     {(2, "auction")}, 2),
    ("null parents KEPT by the predicate: the child is null there",
     ["bid.auction", "_timestamp"], "_timestamp > 9000", _nexmark,
     {(2, "auction")}, 2),
    ("a child with nulls of its own, and a string",
     ["bid.url", "bid.channel"], "bid.price % 2 = 0", _nexmark,
     {(2, "url"), (2, "channel")}, 2),
    ("children of two structs, computed on",
     ["bid.price * 2 + auction.reserve", "upper(person.name)"],
     "_timestamp > 9000", _nexmark, {(2, "price"), (1, "reserve"), (0, "name")}, 3),
    ("a nested child",
     ["meta.origin.port", "meta.seq"], "a % 3 > 0", _nested,
     {(0, "origin", "port"), (0, "seq")}, 2),
    ("a nested struct as a value beside a sibling leaf: fewer than all",
     ["meta.origin"], "a % 3 > 0", _nested, {(0, "origin")}, 2),
    ("a nested child and the struct it lies in: counted once",
     ["meta.origin.host", "meta.origin", "a"], "a % 2 = 0", _nested,
     {(0, "origin")}, 3),
    ("the struct as a value beside one of its children: struct filter",
     ["bid", "bid.auction"], "_timestamp > 9000", _nexmark, set(), 7),
    ("every child read: one struct filter, the q7 2-0 side of the rule",
     ["meta.origin.host", "meta.origin.port", "meta.seq"], "a % 2 = 0",
     _nested, set(), 3),
    ("a child the predicate read too (it reads the raw batch)",
     ["bid.price"], "bid.price > 500", _nexmark, {(2, "price")}, 1),
    ("IS NOT NULL under an AND: the children need no validity from above",
     ["bid.url", "bid.auction"], "bid IS NOT NULL AND bid.price % 2 = 0",
     _nexmark, {(2, "url"), (2, "auction")}, 2),
    ("IS NOT NULL under an OR says nothing: null parents are kept",
     ["bid.url", "bid.auction"], "bid IS NOT NULL OR _timestamp > 40000",
     _nexmark, {(2, "url"), (2, "auction")}, 2),
    ("a nested struct IS NOT NULL: so is the one above it",
     ["meta.origin.port", "meta.seq"], "meta.origin IS NOT NULL AND a > 9",
     _nested, {(0, "origin", "port"), (0, "seq")}, 2),
    ("a batch that is a slice of a longer one",
     ["bid.url", "bid.auction", "_timestamp"], "bid IS NOT NULL",
     lambda: _nexmark().slice(5, 41), {(2, "url"), (2, "auction")}, 3),
    ("a slice, and null parents kept",
     ["meta.origin.port", "meta.seq"], "a % 3 > 0",
     lambda: _nested().slice(7, 30), {(0, "origin", "port"), (0, "seq")}, 2),
]


def test_what_the_predicate_says_a_kept_row_holds():
    from arroyo_tpu.sql.expressions import _kept_not_null

    scope = Scope.from_schema(_nested().schema)

    def says(text):
        return _kept_not_null(bind(parse_expr_text(text), scope))

    assert says("meta IS NOT NULL") == {(0,)}
    assert says("a > 1 AND meta.origin IS NOT NULL") == {
        (0,), (0, "origin")}
    assert says("meta IS NOT NULL OR a > 1") == frozenset()
    assert says("NOT (meta IS NOT NULL)") == frozenset()
    assert says("meta IS NULL") == frozenset()
    assert says("meta.seq > 3") == frozenset()
    assert _kept_not_null(None) == frozenset()


@pytest.mark.parametrize(
    "texts, predicate, make, alone, filtered",
    [c[1:] for c in STRUCT_CASES], ids=[c[0] for c in STRUCT_CASES])
def test_a_struct_child_is_filtered_alone_and_equals_filter_then_project(
        texts, predicate, make, alone, filtered):
    batch = make()
    got, want, view, proj = _filter_then_project(batch, texts, predicate)
    assert view is not None and 0 < got.num_rows < batch.num_rows
    _same(got, want)
    assert proj._rule.alone == alone
    assert view.leaves_filtered == filtered
    assert sum(proj._rule.column_leaves) == sum(
        1 for _ in _leaves(batch.schema))


def _leaves(schema):
    def walk(t):
        if pa.types.is_struct(t):
            for f in t:
                yield from walk(f.type)
        else:
            yield t

    for f in schema:
        yield from walk(f.type)


def test_the_raw_nexmark_row_has_26_leaf_arrays():
    assert sum(1 for _ in _leaves(_nexmark().schema)) == RAW_LEAVES


def test_null_parents_kept_by_the_predicate_give_null_children():
    batch = _nexmark()
    got, _, _, _ = _filter_then_project(
        batch, ["bid.auction", "bid.url"], "_timestamp > 9000")
    bid = batch.filter(pc.greater(
        batch.column(3).cast(pa.int64()), 9000)).column(2)
    assert 0 < bid.null_count < len(bid)
    assert got.column(0).is_null().equals(bid.is_null())
    # the child's own nulls and the parent's, merged
    assert got.column(1).null_count > bid.null_count
    assert got.column(1).to_pylist() == [
        None if r is None else r["url"] for r in bid.to_pylist()]


def test_the_same_child_read_twice_is_filtered_once():
    got, want, view, _ = _filter_then_project(
        _nexmark(), ["bid.auction", "bid.auction + 1", "bid.auction"],
        "bid IS NOT NULL")
    _same(got, want)
    assert view.leaves_filtered == 1
    assert got.column(0).equals(got.column(2))


@pytest.mark.parametrize("predicate, rows", [
    ("_timestamp >= 0", N),        # keeps every row: no view is built
    ("_timestamp < 0", 0),         # keeps none: no view, no batch
])
def test_a_predicate_that_keeps_all_rows_or_none_builds_no_view(
        predicate, rows):
    batch = _nexmark()
    got, want, view, proj = _filter_then_project(
        batch, ["bid.auction", "bid", "_timestamp"], predicate)
    assert view is None and proj._rule is None
    if rows:
        _same(got, want)
        assert got.num_rows == rows
    else:
        assert got is None and want is None


def test_a_hand_built_expression_reads_whole_columns_and_is_still_right():
    """A `BoundExpr` made from a closure does not say what it reads: it
    asks the view for `column(i)`, and a child of that struct asked for
    afterwards is served from the filtered struct, not filtered again."""
    from arroyo_tpu.sql.expressions import BoundExpr

    batch = _nexmark()
    scope = Scope.from_schema(batch.schema)
    exprs = [BoundExpr(lambda b: b.column(2), BID, "bid"),
             bind(parse_expr_text("bid.auction"), scope)]
    assert exprs[0].reads is None and exprs[1].reads == {(2, ("auction",))}
    out = pa.schema([pa.field("bid", BID), pa.field("auction", pa.int64())])
    pred = bind(parse_expr_text("_timestamp > 9000"), scope)
    proj = CompiledProjection(exprs, out, pred)
    got = proj(batch)
    eager = batch.filter(pc.fill_null(pred.eval(batch), False))
    _same(got, CompiledProjection(exprs, out, None)(eager))
    assert proj._rule.alone == {(2, "auction")}
    view = proj.filtered(batch)
    for e in exprs:
        e.eval(view)
    assert view.leaves_filtered == 7


def test_the_fused_view_tier_filters_children_alone_and_equals_unfused():
    """`engine/segments.py` `_run_host` composes stages through the same
    view: a stage's struct child under its predicate, a second stage
    over the first's output, against the two projections run one after
    the other over eagerly filtered batches."""
    from arroyo_tpu.engine.segments import FusedSegmentOperator
    from arroyo_tpu.obs import timeline

    batch = _nexmark()
    first, first_plain = _programs(
        batch, ["bid.auction", "bid.price", "bid.url", "_timestamp"],
        "_timestamp > 9000")
    mid = pa.RecordBatch.from_arrays(
        [pa.array([], type=f.type) for f in first.out_schema],
        schema=first.out_schema)
    second, second_plain = _programs(mid, ["c0 + c1", "c2"], "c1 % 2 = 0")
    op = FusedSegmentOperator(
        [{"config": {"py_fn": first}}, {"config": {"py_fn": second}}],
        None, "t")
    with timeline.phase("segment", job="fusedview", task="1-0"):
        got = op._run_host(batch)
    eager = batch.filter(pc.fill_null(first.predicate.eval(batch), False))
    step = first_plain(eager)
    step = step.filter(pc.fill_null(second.predicate.eval(step), False))
    _same(got, second_plain(step))
    assert first._rule.alone == {
        (2, "auction"), (2, "price"), (2, "url")}
    booked = timeline.phase_totals("fusedview")["project.filter"]
    # stage one: three of bid's children of the raw row's 26 (its
    # `_timestamp` is never asked for: stage two does not read it);
    # stage two: three of stage one's four columns
    assert (booked["count"], booked["n"], booked["padded"]) == (
        2, 3 + 3, RAW_LEAVES + 4)


def test_a_program_rebuilt_from_its_config_binds_the_same_rule():
    """The cross-process path re-binds the expressions' texts through the
    same `bind`, so it reads the same rule."""
    batch = _nexmark()
    texts = ["bid.auction", "_timestamp"]
    with_pred, without = _programs(batch, texts, "bid IS NOT NULL")
    rebuilt = CompiledProjection.from_config({
        "in_schema": batch.schema, "exprs": texts,
        "predicate": "bid IS NOT NULL", "out_schema": with_pred.out_schema})
    _same(rebuilt(batch), with_pred(batch))
    assert rebuilt._rule == with_pred._rule
    assert rebuilt._rule.alone == {(2, "auction")}


def test_what_a_bound_expression_reads_follows_the_binders_resolution():
    """`a.b` is the qualified column `b` of relation `a` first, and child
    `b` of a struct column `a` otherwise: `reads` says which it bound."""
    inner = pa.struct([("x", pa.int64()), ("y", pa.int64())])
    scope = Scope()
    scope.add("t", "s", 0, inner)      # t.s: a struct column of relation t
    scope.add("t", "x", 1, pa.int64())  # t.x: a plain column
    assert bind(parse_expr_text("t.x"), scope).reads == {(1, ())}
    assert bind(parse_expr_text("s.x"), scope).reads == {(0, ("x",))}
    assert bind(parse_expr_text("t.s.y"), scope).reads == {(0, ("y",))}
    assert bind(parse_expr_text("s"), scope).reads == {(0, ())}
    assert bind(parse_expr_text(
        "CASE WHEN s.y > x THEN s.x ELSE 0 END"), scope).reads == {
            (0, ("x",)), (0, ("y",)), (1, ())}
    assert bind(parse_expr_text("1 + 2"), scope).reads == frozenset()
