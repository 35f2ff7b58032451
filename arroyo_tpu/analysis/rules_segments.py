"""Segment-purity rule (fused segment runtime, engine/segments.py).

An operator class registered as fusable (`fusable = True`) may be fused
into a segment run that executes with ONE dispatch per batch and NO
per-operator checkpoint participation: the runner captures no state for
it and the segment does nothing at a barrier. A fusable
operator that quietly grows state (self._state...), reaches for the
state tables (ctx.table_manager / ctx.table(...)) or overrides the
checkpoint hooks would silently lose that state across recovery — its
writes would never ride a barrier. JAX004 makes that a lint failure
instead of a chaos-drill surprise.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from .core import FileContext, Finding, Rule, dotted_name, register

# hooks a stateless (fusable) operator must not implement: each one only
# exists to participate in checkpoint/2PC state capture
_FORBIDDEN_METHODS = {"handle_checkpoint", "handle_commit", "tables"}


def _is_fusable_class(cls: ast.ClassDef) -> bool:
    for stmt in cls.body:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = [t.id for t in stmt.targets if isinstance(t, ast.Name)]
        elif isinstance(stmt, ast.AnnAssign) and isinstance(
                stmt.target, ast.Name):
            targets = [stmt.target.id]
        else:
            continue
        if "fusable" in targets and isinstance(stmt.value, ast.Constant) \
                and stmt.value.value is True:
            return True
    return False


@register
class SegmentPurityRule(Rule):
    id = "JAX004"
    name = "segment-purity"
    description = (
        "an operator class registered as fusable (`fusable = True`) must "
        "stay stateless: no self._state* attributes, no "
        "ctx.table_manager / ctx.table(...) access, and no "
        "handle_checkpoint/handle_commit/tables overrides — a fused "
        "segment executes as one dispatch and takes no per-operator "
        "state capture at barriers, so hidden state would silently skip "
        "every checkpoint"
    )

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not _is_fusable_class(node):
                continue
            self._check_class(ctx, node, out)
        return out

    def _check_class(self, ctx: FileContext, cls: ast.ClassDef,
                     out: List[Finding]) -> None:
        for stmt in cls.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and stmt.name in _FORBIDDEN_METHODS:
                out.append(ctx.finding(
                    self, stmt,
                    f"fusable operator {cls.name} overrides {stmt.name}() — "
                    "checkpoint-hook state never survives inside a fused "
                    "segment",
                ))
        for node in ast.walk(cls):
            if isinstance(node, ast.Attribute):
                if node.attr.startswith("_state") or node.attr == "state":
                    if isinstance(node.value, ast.Name) \
                            and node.value.id == "self":
                        out.append(ctx.finding(
                            self, node,
                            f"fusable operator {cls.name} touches "
                            f"self.{node.attr} — hidden operator state "
                            "skips every barrier once fused",
                        ))
                elif node.attr == "table_manager":
                    out.append(ctx.finding(
                        self, node,
                        f"fusable operator {cls.name} reaches for "
                        ".table_manager — fused segments take no state "
                        "capture",
                    ))
            elif isinstance(node, ast.Call):
                name = dotted_name(node.func)
                if name in ("ctx.table", "context.table"):
                    out.append(ctx.finding(
                        self, node,
                        f"fusable operator {cls.name} opens a state table "
                        "via ctx.table() — fused segments take no state "
                        "capture",
                    ))
