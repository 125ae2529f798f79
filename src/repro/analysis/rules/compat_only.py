"""CB1xx — the compat-layer-only guardrail (ROADMAP, PR 1).

The JAX call sites whose spelling tends to drift live in
``src/repro/compat.py``; the rest of the tree never touches them
directly, so a future rename is a one-file change:

  * CB101: ``pltpu.CompilerParams`` (once named ``TPUCompilerParams``) —
    use ``compat.tpu_compiler_params``.
  * CB102: ``pl.pallas_call`` — every TPU call site goes through
    ``compat.pallas_call_tpu`` so ``dimension_semantics``/``interpret``
    handling stays centralized.
  * CB103: ``jax.shard_map`` / ``jax.experimental.shard_map`` — the
    location and the ``check_rep``/``check_vma`` kwarg have both moved;
    use ``compat.shard_map``.
  * CB104: ``axis_types=`` — mesh axis types are chosen in one place;
    use ``compat.make_mesh``.

``compat.py`` itself is exempt — it is the one place these spellings
are supposed to live.
"""
from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis.context import FileContext, dotted_name
from repro.analysis.findings import Finding
from repro.analysis.registry import rule


def _is_compat(ctx: FileContext) -> bool:
    return ctx.path.rsplit("/", 1)[-1] == "compat.py"


def _at(ctx: FileContext, node: ast.AST, code: str, message: str,
        hint: str) -> Finding:
    return Finding(path=ctx.path, line=node.lineno, col=node.col_offset + 1,
                   code=code, message=message, hint=hint)


@rule("CB101", "compat-compiler-params",
      "TPU compiler params are version-drifting; only compat.py names them")
def check_compiler_params(ctx: FileContext) -> Iterator[Finding]:
    if _is_compat(ctx):
        return
    for node in ctx.walk():
        if isinstance(node, ast.Attribute) and \
                node.attr.endswith("CompilerParams"):
            yield _at(ctx, node, "CB101",
                      f"direct {node.attr} use outside compat.py",
                      "build params via repro.compat.tpu_compiler_params")
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name.endswith("CompilerParams"):
                    yield _at(ctx, node, "CB101",
                              f"imports {alias.name} outside compat.py",
                              "build params via "
                              "repro.compat.tpu_compiler_params")


@rule("CB102", "compat-pallas-call",
      "pl.pallas_call call sites live behind compat.pallas_call_tpu")
def check_pallas_call(ctx: FileContext) -> Iterator[Finding]:
    if _is_compat(ctx):
        return
    for node in ctx.walk():
        if isinstance(node, ast.Attribute) and node.attr == "pallas_call":
            yield _at(ctx, node, "CB102",
                      "direct pl.pallas_call use outside compat.py",
                      "call repro.compat.pallas_call_tpu instead")
        if isinstance(node, ast.ImportFrom) and node.module and \
                "pallas" in node.module:
            for alias in node.names:
                if alias.name == "pallas_call":
                    yield _at(ctx, node, "CB102",
                              "imports pallas_call outside compat.py",
                              "call repro.compat.pallas_call_tpu instead")


@rule("CB103", "compat-shard-map",
      "shard_map's module path and check kwarg drift; use compat.shard_map")
def check_shard_map(ctx: FileContext) -> Iterator[Finding]:
    if _is_compat(ctx):
        return
    for node in ctx.walk():
        if isinstance(node, ast.Attribute) and node.attr == "shard_map" and \
                dotted_name(node) in ("jax.shard_map",
                                      "jax.experimental.shard_map"):
            yield _at(ctx, node, "CB103",
                      f"direct {dotted_name(node)} use outside compat.py",
                      "call repro.compat.shard_map instead")
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.startswith("jax") and (
                    "shard_map" in node.module
                    or any(a.name == "shard_map" for a in node.names)):
            yield _at(ctx, node, "CB103",
                      f"imports shard_map from {node.module} "
                      "outside compat.py",
                      "call repro.compat.shard_map instead")


@rule("CB104", "compat-axis-types",
      "mesh axis types are chosen in compat.make_mesh alone")
def check_axis_types(ctx: FileContext) -> Iterator[Finding]:
    if _is_compat(ctx):
        return
    for node in ctx.walk():
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "axis_types":
                    yield _at(ctx, node, "CB104",
                              "axis_types= kwarg outside compat.py",
                              "use repro.compat.make_mesh")
