"""The one home of the JAX call sites whose spelling tends to drift.

The repo targets the installed JAX (0.9.0) and no other version. These
shims keep each drift-prone call in one place, so a future rename or
signature change is a one-file edit (cblint's CB1xx rules keep every
other module off the direct spellings):

  * ``tpu_compiler_params``  — ``pltpu.CompilerParams``.
  * ``pallas_call_tpu``      — one entry point for every Pallas TPU call
    site; centralizes ``dimension_semantics``/``interpret`` handling so
    kernels never touch ``compiler_params`` directly.
  * ``make_mesh``            — ``jax.make_mesh`` with explicit ``Auto``
    axis types.
  * ``shard_map``            — ``jax.shard_map``.

Nothing here may import heavyweight repro modules; kernels and launch
code import *us*.
"""
from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from repro import errors

# ---------------------------------------------------------------------------
# Pallas compiler params
# ---------------------------------------------------------------------------

def tpu_compiler_params(
    *, dimension_semantics: Sequence[str] | None = None, **kwargs: Any
):
    """Build the Pallas TPU compiler-params object."""
    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


def pallas_call_tpu(
    kernel: Callable,
    *,
    out_shape,
    interpret: bool,
    grid=None,
    grid_spec=None,
    in_specs=None,
    out_specs=None,
    dimension_semantics: Sequence[str] | None = None,
    name: str | None = None,
    **kwargs: Any,
):
    """``pl.pallas_call`` with version-stable TPU compiler params.

    Exactly one of ``grid_spec`` (e.g. ``pltpu.PrefetchScalarGridSpec``)
    or the ``grid``/``in_specs``/``out_specs`` triple must be given —
    mirroring ``pl.pallas_call`` itself. Returns the callable to apply to
    the operands.

    ``interpret`` is deliberately required, here and on every kernel
    entry point: ``kernels.ops`` derives it from the backend (interpret
    off-TPU, compiled on TPU), and a silent default would make a TPU
    call site run the interpreter — slow with no error. Unsupplied
    grid/spec arguments are left to ``pl.pallas_call``'s own defaults
    rather than forwarded as ``None``.
    """
    call_kwargs: dict[str, Any] = dict(
        out_shape=out_shape,
        compiler_params=tpu_compiler_params(
            dimension_semantics=dimension_semantics
        ),
        interpret=interpret,
        name=name,
        **kwargs,
    )
    if grid_spec is not None:
        if grid is not None or in_specs is not None or out_specs is not None:
            raise errors.InvalidArgError("pass either grid_spec or grid/in_specs/out_specs")
        call_kwargs["grid_spec"] = grid_spec
    else:
        for key, value in (("grid", grid), ("in_specs", in_specs),
                           ("out_specs", out_specs)):
            if value is not None:
                call_kwargs[key] = value
    return pl.pallas_call(kernel, **call_kwargs)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def make_mesh(
    axis_shapes: Sequence[int], axis_names: Sequence[str], **kwargs: Any
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of ``Auto`` type."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_shapes), **kwargs
    )


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------


def shard_map(
    f: Callable,
    *,
    mesh: jax.sharding.Mesh,
    in_specs,
    out_specs,
    **kwargs: Any,
):
    """``jax.shard_map`` (supports ``functools.partial`` use)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         **kwargs)
