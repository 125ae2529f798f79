"""jit'd public entry points for the CB-SpMV / CB-SpMM kernels.

``cb_spmv(streams, x)`` runs the batched super-block execution engine:
each per-format stream becomes at most ONE ``pallas_call`` whose grid
covers every super-block group of that format (the paper's "segregated
per-format streams" replacement for intra-kernel branching — TPU cores
have no divergence mechanism, uniform kernels win), and all per-format
partials are combined by a SINGLE fused scatter-add into the ``(mb, B)``
result — one deterministic combine instead of three.

``streams`` may be either

  * ``SuperBlockStreams`` (from ``build_super_streams``) — blocks already
    packed into width-bucketed, load-balanced groups at preprocessing
    time; ``group_size`` is baked into the stream, or
  * ``SpMVStreams`` (from ``build_streams``) — the one-block-per-row
    layout. ``group_size=G`` then regroups it on the fly with pure
    reshapes (jit-safe, no host round-trip): G rows fuse into one grid
    step. On-the-fly regrouping keeps each format's global padding width
    (only the host-side packer can shrink it), but it already buys the
    batching win: 1/G as many grid steps, G times the payload per DMA.

``cb_spmm(stream, X)`` applies the same batched contract to the multi-RHS
tile stream: ``SuperTileStream`` (host-packed, nnz-balanced) or
``TileStream`` + ``group_size=`` (jit-side regroup), ONE ``pallas_call``
for the whole stream, one fused scatter-add, and a lane-aligned
activation tile width from ``spmm_block_n``.

``impl`` selects between the Pallas kernels ("pallas", interpret=True on
CPU; compiled Mosaic on TPU) and the pure-XLA reference ("reference",
kernels/ref.py) — the reference path is what the multi-pod dry-run lowers,
since Mosaic kernels cannot compile for the CPU stand-in devices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compile_cache  # noqa: F401 - counts cache hits/misses
from repro import errors, obs
from repro.core.streams import (
    LANE, SUBLANE, SpMVStreams, SuperBlockStreams, SuperTileStream,
    TileStream, even_group, spmm_block_n,
)

from . import cb_block_dense, cb_colagg, cb_coo, ref
from . import cb_spmm as _cb_spmm_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(arr: jax.Array, rows: int) -> jax.Array:
    """Zero-pad axis 0 to ``rows`` (ragged tails regroup as inert slots)."""
    pad = [(0, rows - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def _slot_brow(brow_blocks: jax.Array, width: int, groups: int) -> jax.Array:
    """Expand per-block rows to per-SUBLANE-slot rows (block-major lanes)."""
    per_block = width // SUBLANE
    if groups == 0 or per_block == 0:
        return jnp.zeros((groups, 0), jnp.int32)
    return jnp.repeat(brow_blocks.reshape(-1), per_block).reshape(groups, -1)


def _regroup(streams: SpMVStreams, G: int) -> SuperBlockStreams:
    """Fuse G one-block rows per super-block row with pure reshapes.

    Padding rows appended to ragged tails carry zero payload and brow 0,
    so they scatter-add exact zeros. The lane order of fused panel/coo
    rows is block-major (member g owns lanes [g*K, (g+1)*K)); since the
    flat stream's K is already a SUBLANE multiple, the per-slot brow
    arrays are just each block's row repeated over its K // SUBLANE
    slots. Each format uses its own evened member count.
    """
    B, mb = streams.block_size, streams.mb

    gd, Gd = even_group(streams.num_dense, G)
    d_tiles = _pad_rows(streams.dense_tiles, gd * Gd).reshape(gd, Gd * B, B)
    d_brow = _pad_rows(streams.dense_brow, gd * Gd).reshape(gd, Gd)
    d_xidx = _pad_rows(streams.dense_xidx, gd * Gd).reshape(gd, Gd, B)

    np_, Kp = streams.panel_vals.shape[0], streams.panel_vals.shape[2]
    gp, Gp = even_group(np_, G)
    p_vals = (
        _pad_rows(streams.panel_vals, gp * Gp)
        .reshape(gp, Gp, B, Kp)
        .transpose(0, 2, 1, 3)
        .reshape(gp, B, Gp * Kp)
    )
    p_xidx = _pad_rows(streams.panel_xidx, gp * Gp).reshape(gp, Gp * Kp)
    p_brow = _slot_brow(_pad_rows(streams.panel_brow, gp * Gp), Kp, gp)

    nc, Ep = streams.coo_codes.shape
    gc, Gc = even_group(nc, G)
    c_codes = _pad_rows(streams.coo_codes, gc * Gc).reshape(gc, Gc * Ep)
    c_vals = _pad_rows(streams.coo_vals, gc * Gc).reshape(gc, Gc * Ep)
    c_xidx = _pad_rows(streams.coo_xidx, gc * Gc).reshape(gc, Gc * Ep)
    c_brow = _slot_brow(_pad_rows(streams.coo_brow, gc * Gc), Ep, gc)

    return SuperBlockStreams(
        block_size=B, m=streams.m, n=streams.n, mb=mb,
        colagg_applied=streams.colagg_applied, group_size=G,
        dense_tiles=d_tiles, dense_brow=d_brow, dense_xidx=d_xidx,
        panel_vals=p_vals, panel_brow=p_brow, panel_xidx=p_xidx,
        coo_codes=c_codes, coo_vals=c_vals, coo_brow=c_brow,
        coo_xidx=c_xidx,
    )


def _gather(x: jax.Array, xidx: jax.Array, fmt: str) -> jax.Array:
    """``x[xidx]``, named ``cb_gather/<fmt>`` in the HLO's ``op_name``
    metadata so a profile finds the gather by name."""
    with jax.named_scope("cb_gather"), jax.named_scope(fmt):
        return x[xidx]


def _super_partials_pallas(s: SuperBlockStreams, x: jax.Array, interp: bool):
    """One pallas_call per present format -> [(partials (t, B), brow (t,))].

    Slot counts are positional: the kernels derive them from the payload
    widths (``W // SUBLANE`` for panel/coo, the brow shape for dense).
    """
    B = s.block_size
    parts = []
    if s.num_dense_groups:
        part = cb_block_dense.block_dense_spmv_batched(
            s.dense_tiles, _gather(x, s.dense_xidx, "dense"), interpret=interp
        )
        parts.append((part.reshape(-1, B), s.dense_brow.reshape(-1)))
    if s.num_panel_groups:
        part = cb_colagg.panel_spmv_batched(
            s.panel_vals, _gather(x, s.panel_xidx, "panel"), interpret=interp,
        )
        parts.append((part.reshape(-1, B), s.panel_brow.reshape(-1)))
    if s.num_coo_groups:
        part = cb_coo.coo_spmv_batched(
            s.coo_codes, s.coo_vals, _gather(x, s.coo_xidx, "coo"),
            block_size=B, interpret=interp,
        )
        parts.append((part.reshape(-1, B), s.coo_brow.reshape(-1)))
    return parts


def _resolve_plan(streams, plan, group_size):
    """Fold an autotune ``Plan`` into the effective ``group_size``.

    Duck-typed (any object with ``block_size``/``group_size``) so this
    module never imports the autotune package. The plan's block size
    must match the streams it is applied to; an explicit conflicting
    ``group_size`` is an error, matching the SuperBlockStreams contract.
    """
    if plan is None:
        return group_size
    if plan.block_size != streams.block_size:
        raise errors.InvalidArgError(
            f"plan was made for block_size={plan.block_size}; "
            f"streams carry block_size={streams.block_size}"
        )
    if group_size is not None and group_size != plan.group_size:
        raise errors.InvalidArgError(
            f"plan chose group_size={plan.group_size}; conflicting "
            f"explicit group_size={group_size}"
        )
    return plan.group_size


# ---------------------------------------------------------------------------
# Launch accounting (repro.obs): the numbers the cost model predicts,
# measured from the streams every call actually dispatches.
# ---------------------------------------------------------------------------

def spmv_launch_stats(
    streams: SpMVStreams | SuperBlockStreams, group_size: int | None = None
) -> dict:
    """Per-format grid steps / padded elements one ``cb_spmv`` call runs.

    Pure shape arithmetic (works on tracers): for a packed
    ``SuperBlockStreams`` the geometry is read off directly; for a flat
    ``SpMVStreams`` + ``group_size`` it replicates ``_regroup``'s
    ``even_group`` padding arithmetic without building anything — tested
    equal to the actually-regrouped stream. ``launches`` counts the
    ``pallas_call``s the batched engine issues: one per non-empty format.
    """
    B = streams.block_size
    if isinstance(streams, SuperBlockStreams):
        G = streams.group_size
        steps = {"dense": streams.num_dense_groups,
                 "panel": streams.num_panel_groups,
                 "coo": streams.num_coo_groups}
        padded = streams.padded_work()
    else:
        G = int(group_size or 1)
        gd, Gd = even_group(streams.num_dense, G)
        gp, Gp = even_group(streams.num_panel, G)
        gc, Gc = even_group(streams.num_coo, G)
        Kp = streams.panel_vals.shape[2]
        Ep = streams.coo_codes.shape[1]
        steps = {"dense": gd, "panel": gp, "coo": gc}
        padded = {"dense": gd * Gd * B * B, "panel": gp * B * Gp * Kp,
                  "coo": gc * Gc * Ep}
    steps = {k: int(v) for k, v in steps.items()}
    padded = {k: int(v) for k, v in padded.items()}
    return {
        "group_size": int(G),
        "steps": steps,
        "padded": padded,
        "launches": {k: int(steps[k] > 0) for k in steps},
        "steps_total": sum(steps.values()),
        "padded_total": sum(padded.values()),
    }


def spmm_launch_stats(
    stream: TileStream | SuperTileStream,
    group_size: int | None = None,
    *,
    n_cols: int | None = None,
    block_n: int = LANE,
) -> dict:
    """``cb_spmm``'s analogue of :func:`spmv_launch_stats`.

    ``steps`` is the full grid size ``tile_groups * n_tiles_of_X`` when
    the activation width is known (``n_cols``), else the weight-stream
    group count alone.
    """
    B = stream.block_size
    if isinstance(stream, SuperTileStream):
        G = stream.group_size
        gt, Gt = stream.num_groups, stream.slots
    else:
        G = int(group_size or 1)
        gt, Gt = even_group(stream.num_tiles, G)
    padded = int(gt * Gt * B * B)
    steps = int(gt)
    if n_cols is not None and gt:
        bn = spmm_block_n(int(n_cols), block_n)
        steps = gt * (-(-int(n_cols) // bn))
    return {
        "group_size": int(G),
        "steps": {"tiles": steps},
        "padded": {"tiles": padded},
        "launches": {"tiles": int(gt > 0)},
        "steps_total": steps,
        "padded_total": padded,
    }


def _record_call(entry: str, stats: dict, impl: str) -> None:
    """Emit one call's launch accounting to the default registry.

    Runs outside jitted code — under an outer ``jax.jit`` this is a
    trace-time side effect, so counts are per *logical* invocation.
    Only the Pallas engine dispatches kernels; reference calls count
    calls alone.
    """
    reg = obs.registry()
    reg.counter(f"repro.ops.{entry}.calls").inc(impl=impl)
    if impl != "pallas":
        return
    launches = reg.counter(f"repro.ops.{entry}.launches")
    steps = reg.counter(f"repro.ops.{entry}.steps")
    padded = reg.counter(f"repro.ops.{entry}.padded_elems")
    for fmt, n in stats["steps"].items():
        if n:
            launches.inc(stats["launches"][fmt], format=fmt)
            steps.inc(n, format=fmt)
            padded.inc(stats["padded"][fmt], format=fmt)
    reg.gauge(f"repro.ops.{entry}.group_size").set(stats["group_size"])


@functools.partial(
    jax.jit, static_argnames=("impl", "interpret", "group_size", "plan")
)
def _cb_spmv_jit(
    streams: SpMVStreams | SuperBlockStreams,
    x: jax.Array,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    group_size: int | None = None,
    plan=None,
) -> jax.Array:
    group_size = _resolve_plan(streams, plan, group_size)
    _check_group_size(streams, group_size)

    if impl == "reference":
        if isinstance(streams, SuperBlockStreams):
            return ref.super_spmv(streams, x)
        return ref.cb_spmv(streams, x)
    if impl != "pallas":
        raise errors.InvalidArgError(f"unknown impl {impl!r}")
    sup = (streams if isinstance(streams, SuperBlockStreams)
           else _regroup(streams, group_size or 1))
    interp = (not _on_tpu()) if interpret is None else interpret

    B, mb = sup.block_size, sup.mb
    y = _combine_into(jnp.zeros((mb, B), jnp.float32), sup, x, interp)
    return y.reshape(-1)[: sup.m]


def cb_spmv(
    streams: SpMVStreams | SuperBlockStreams,
    x: jax.Array,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    group_size: int | None = None,
    plan=None,
) -> jax.Array:
    """y = A @ x over the CB streams. x: (n,) -> y: (m,) float32.

    ``group_size`` (static) only applies to ``SpMVStreams`` input: blocks
    are fused G per grid step via ``_regroup``. ``SuperBlockStreams``
    carry their group size from the host-side packer; passing a
    conflicting value is an error. ``plan`` (static, an autotune
    ``Plan``) supplies the group size the planner chose — it must agree
    with both an explicit ``group_size`` and a packed stream's.

    ``impl="reference"`` stays an *independent* oracle: it consumes the
    stream layout as given (no regrouping), so batched Pallas results are
    always checked against math that never touched the batching code.

    The computation itself is the jitted ``_cb_spmv_jit``; this entry is
    a host-side shim that additionally records launch accounting
    (``repro.ops.spmv.*`` — see ``obs/README.md``) after a successful
    dispatch. Recording reads only static stream geometry, so results
    are bit-identical with obs enabled or disabled.
    """
    y = _cb_spmv_jit(streams, x, impl=impl, interpret=interpret,
                     group_size=group_size, plan=plan)
    if obs.is_enabled():
        g = group_size if group_size is not None else (
            plan.group_size if plan is not None else None)
        _record_call("spmv", spmv_launch_stats(streams, g), impl)
    return y


def _check_group_size(streams, group_size) -> None:
    """Shared argument contract of ``cb_spmv`` / ``cb_spmv_into``."""
    if group_size is not None and group_size < 1:
        raise errors.InvalidArgError(f"group_size must be >= 1, got {group_size}")
    if isinstance(streams, SuperBlockStreams):
        if group_size is not None and group_size != streams.group_size:
            raise errors.InvalidArgError(
                f"stream was packed with group_size={streams.group_size}; "
                f"cannot re-batch to {group_size} post hoc"
            )


def _combine_into(y2d, sup: SuperBlockStreams, x: jax.Array, interp: bool):
    """Scatter every format's partials into the (mb, B) accumulator."""
    parts = _super_partials_pallas(sup, x, interp)
    if parts:
        # ONE fused scatter-add over every format's per-slot partials.
        with jax.named_scope("cb_combine"):
            all_parts = jnp.concatenate([p for p, _ in parts], axis=0)
            all_brow = jnp.concatenate([b for _, b in parts], axis=0)
            y2d = y2d.at[all_brow].add(all_parts)
    return y2d


@functools.partial(
    jax.jit,
    static_argnames=("impl", "interpret", "group_size", "plan"),
    donate_argnums=(0,),
)
def _cb_spmv_into_jit(
    y_acc: jax.Array,
    streams: SpMVStreams | SuperBlockStreams,
    x: jax.Array,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    group_size: int | None = None,
    plan=None,
) -> jax.Array:
    group_size = _resolve_plan(streams, plan, group_size)
    _check_group_size(streams, group_size)
    if impl == "reference":
        return y_acc + _cb_spmv_jit(streams, x, impl="reference")
    if impl != "pallas":
        raise errors.InvalidArgError(f"unknown impl {impl!r}")
    sup = (streams if isinstance(streams, SuperBlockStreams)
           else _regroup(streams, group_size or 1))
    interp = (not _on_tpu()) if interpret is None else interpret
    B, mb = sup.block_size, sup.mb
    y2d = jnp.pad(
        y_acc.astype(jnp.float32), (0, mb * B - y_acc.shape[0])
    ).reshape(mb, B)
    y2d = _combine_into(y2d, sup, x, interp)
    return y2d.reshape(-1)[: sup.m]


def cb_spmv_into(
    y_acc: jax.Array,
    streams: SpMVStreams | SuperBlockStreams,
    x: jax.Array,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    group_size: int | None = None,
    plan=None,
) -> jax.Array:
    """``y_acc + A @ x`` with the ``(m,)`` accumulator **donated**.

    The iterative-solver pattern: the same ``y`` buffer is reused across
    thousands of matvecs, so the accumulator is donated (``donate_argnums``)
    and XLA aliases the output onto the caller's buffer instead of
    allocating a fresh one per iteration (a no-op where the backend lacks
    donation, e.g. CPU — then this is just fused accumulate-SpMV). The
    caller must not reuse ``y_acc`` after the call, per donation rules.

    Like :func:`cb_spmv`, the host-side shim records launch accounting
    (``repro.ops.spmv_into.*``) around the jitted computation.
    """
    y = _cb_spmv_into_jit(y_acc, streams, x, impl=impl, interpret=interpret,
                          group_size=group_size, plan=plan)
    if obs.is_enabled():
        g = group_size if group_size is not None else (
            plan.group_size if plan is not None else None)
        _record_call("spmv_into", spmv_launch_stats(streams, g), impl)
    return y


def _check_tile_group_size(stream, group_size) -> None:
    """``cb_spmm``'s group-size contract (mirrors ``_check_group_size``)."""
    if group_size is not None and group_size < 1:
        raise errors.InvalidArgError(f"group_size must be >= 1, got {group_size}")
    if isinstance(stream, SuperTileStream):
        if group_size is not None and group_size != stream.group_size:
            raise errors.InvalidArgError(
                f"tile stream was packed with group_size={stream.group_size};"
                f" cannot re-batch to {group_size} post hoc"
            )


def _regroup_tiles(ts: TileStream, G: int) -> SuperTileStream:
    """Fuse G one-tile rows per super-tile row with pure reshapes.

    The jit-safe analogue of ``build_super_tile_stream`` (no host round
    trip, no balancing): padding rows appended to ragged tails carry a
    zero tile and brow/bcol 0, so they DMA X block 0 and scatter-add
    exact zeros.
    """
    B = ts.block_size
    gt, Gt = even_group(ts.num_tiles, G)
    tiles = _pad_rows(ts.tiles, gt * Gt).reshape(gt, Gt * B, B)
    brow = _pad_rows(jnp.asarray(ts.brow), gt * Gt).reshape(gt, Gt)
    bcol = _pad_rows(jnp.asarray(ts.bcol), gt * Gt).reshape(gt, Gt)
    return SuperTileStream(
        block_size=B, m=ts.m, n=ts.n, mb=ts.mb, nb=ts.nb, group_size=G,
        tiles=tiles, brow=brow, bcol=bcol,
    )


@functools.partial(
    jax.jit,
    static_argnames=("impl", "interpret", "block_n", "group_size", "plan"),
)
def _cb_spmm_jit(
    stream: TileStream | SuperTileStream,
    X: jax.Array,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    block_n: int = LANE,
    group_size: int | None = None,
    plan=None,
) -> jax.Array:
    group_size = _resolve_plan(stream, plan, group_size)
    _check_tile_group_size(stream, group_size)
    if impl == "reference":
        if isinstance(stream, SuperTileStream):
            return ref.super_spmm(stream, X)
        return ref.cb_spmm(stream, X)
    if impl != "pallas":
        raise errors.InvalidArgError(f"unknown impl {impl!r}")
    sup = (stream if isinstance(stream, SuperTileStream)
           else _regroup_tiles(stream, group_size or 1))
    interp = (not _on_tpu()) if interpret is None else interpret

    B, mb, nb = sup.block_size, sup.mb, sup.nb
    n, N = X.shape
    bn = spmm_block_n(N, block_n)
    Npad = -(-N // bn) * bn
    Xp = jnp.pad(X, ((0, nb * B - n), (0, Npad - N)))
    Xb = Xp.reshape(nb, B, Npad)
    part = _cb_spmm_kernel.super_tile_spmm(
        sup.tiles, sup.bcol, Xb, block_n=bn, interpret=interp,
    )                                                  # (gt, Gt, B, Npad)
    Yb = jnp.zeros((mb, B, Npad), jnp.float32)
    Yb = Yb.at[sup.brow.reshape(-1)].add(part.reshape(-1, B, Npad))
    return Yb.reshape(mb * B, Npad)[: sup.m, :N]


def cb_spmm(
    stream: TileStream | SuperTileStream,
    X: jax.Array,
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    block_n: int = LANE,
    group_size: int | None = None,
    plan=None,
) -> jax.Array:
    """Y = A @ X over the block-dense tile stream. X: (n, N) -> Y: (m, N).

    Mirrors ``cb_spmv``'s batched contract: a ``SuperTileStream`` (from
    ``build_super_tile_stream``) carries its group size from the
    host-side nnz-balancing packer; a flat ``TileStream`` is regrouped
    on the fly with pure reshapes when ``group_size=G`` is passed
    (``G=None`` keeps one tile per grid step). Either way the whole
    stream is ONE ``pallas_call`` whose per-slot partials are combined
    by a single fused scatter-add over ``brow``.

    The activation tile width is ``spmm_block_n(N, block_n)`` — always a
    LANE multiple, with X zero-padded to match (the old
    ``min(block_n, max(8, N))`` policy emitted lane-misaligned widths
    that only interpret mode accepted). ``impl="reference"`` stays an
    independent oracle on the layout as given (no regrouping). ``plan``
    (static, an autotune ``Plan``) supplies the planner's group size,
    with the same conflict rules as ``cb_spmv``.

    The host-side shim records launch accounting (``repro.ops.spmm.*``)
    around the jitted computation, mirroring :func:`cb_spmv`.
    """
    Y = _cb_spmm_jit(stream, X, impl=impl, interpret=interpret,
                     block_n=block_n, group_size=group_size, plan=plan)
    if obs.is_enabled():
        g = group_size if group_size is not None else (
            plan.group_size if plan is not None else None)
        n_cols = int(X.shape[1]) if hasattr(X, "shape") else None
        _record_call(
            "spmm",
            spmm_launch_stats(stream, g, n_cols=n_cols, block_n=block_n),
            impl,
        )
    return Y
