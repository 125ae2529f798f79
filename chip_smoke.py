"""Smoke run of the CB-SpMV main path on a TPU, at deployment-sized inputs.

    python chip_smoke.py             # one chip: SpMV, CG, dense + SpMM, planner
    python chip_smoke.py --chips 4   # four chips: distributed SpMV only

Every phase builds through the public path (``CBMatrix.from_coo`` ->
stream builder -> ``device_put`` -> ``ops`` / ``CBLinearOperator``) with
the backend-derived ``interpret``, compares against scipy in float64, and
checks in the compiled HLO that each present format's Pallas kernel is a
``tpu_custom_call`` (so no phase ran the interpreter). The numbers printed
before the last line are smoke facts (set-up and first-call seconds, peak
device bytes), not benchmark results. The last line is one JSON object
naming the device; it is printed only when every phase passed. Without a
TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse as sp  # noqa: E402

from repro import compat, compile_cache  # noqa: E402
from repro.core import CBMatrix  # noqa: E402
from repro.core import distributed as dist  # noqa: E402
from repro.core.streams import (  # noqa: E402
    build_super_streams, super_tile_stream_from_cb,
)
from repro.data import matrices  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.solvers import CBLinearOperator, block_jacobi, cg  # noqa: E402

# Conformance bound: max |y - y_ref| <= REL_TOL * max |y_ref|.
REL_TOL = 1e-5
CG_TOL = 1e-6

KERNEL_OF_FORMAT = {
    "dense": "cb_block_dense_spmv_batched",
    "panel": "cb_colagg_panel_spmv_batched",
    "coo": "cb_coo_spmv_batched",
}
SPMM_KERNEL = "cb_super_tile_spmm"


class SmokeFailure(Exception):
    """A phase's result or compiled program is not what the chip owes."""


def tpu_kernel_names(hlo_text: str) -> set[str]:
    """Names of the Mosaic kernels (``tpu_custom_call``) in compiled HLO."""
    return set(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo_text))


def check_kernels(fn, *args, expect: set[str]) -> float:
    """Compile ``fn(*args)``, require every ``expect`` kernel in it, and
    return the seconds the lowering and compile took."""
    t0 = time.perf_counter()
    text = jax.jit(fn).lower(*args).compile().as_text()
    compile_s = time.perf_counter() - t0
    missing = expect - tpu_kernel_names(text)
    if missing:
        raise SmokeFailure(f"not compiled as tpu_custom_call: {sorted(missing)}")
    return compile_s


def present_kernels(steps: dict[str, int]) -> set[str]:
    """The SpMV kernels dispatched for these per-format grid steps."""
    return {KERNEL_OF_FORMAT[f] for f, n in steps.items() if n}


def rel_max_err(y, y_ref: np.ndarray) -> float:
    y = np.asarray(y, np.float64)
    return float(np.abs(y - y_ref).max() / np.abs(y_ref).max())


def require_close(what: str, y, y_ref: np.ndarray) -> float:
    err = rel_max_err(y, y_ref)
    if not err <= REL_TOL:
        raise SmokeFailure(f"{what}: relative max error {err:.3e} > {REL_TOL}")
    return err


def scipy_of(coo, shape) -> sp.csr_matrix:
    """The float64 reference of the matrix as stored (float32 values)."""
    r, c, v = coo
    v64 = np.asarray(v).astype(np.float32).astype(np.float64)
    return sp.csr_matrix((v64, (r, c)), shape=shape)


def peak_bytes(device=None) -> int | None:
    """``peak_bytes_in_use`` where the backend reports it (TPU does)."""
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def report(phase: str, **facts) -> None:
    """One line of smoke facts; numbers are printed unrounded."""
    body = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[{phase}] {body}", flush=True)


def two_calls(fn, *args, **kwargs):
    """``(result, first_s, second_s)``: the first call compiles, the
    second runs compiled; each ends with the result ready on the device."""
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return out, times[0], times[1]


def random_vector(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


# ---------------------------------------------------------------------------
# Matrices (the smoke's inputs, made from a seed)
# ---------------------------------------------------------------------------

def power_law_matrix(n: int = 2**18):
    """COO-heavy skewed graph: ~9.3M nnz at the default n."""
    return matrices.power_law(n, n, avg_deg=16, seed=0), (n, n)


def spd_banded_matrix(n: int = 2**20):
    """Panel-heavy SPD band: ~8.7M nnz at the default n."""
    return matrices.spd_banded(n, seed=0), (n, n)


def pruned_weight_matrix(m: int = 14336, n: int = 4096):
    """Block-pruned d_ff x d_model weight (granite-8b's MLP widths)."""
    coo = matrices.pruned_weight(m, n, block_size=16, block_sparsity=0.85,
                                 seed=0)
    return coo, (m, n)


# ---------------------------------------------------------------------------
# Phases (one chip)
# ---------------------------------------------------------------------------

def spmv_phase(coo, shape, *, name: str = "spmv"):
    """``ops.cb_spmv`` on packed super-streams vs scipy."""
    t0 = time.perf_counter()
    cb = CBMatrix.from_coo(*coo, shape, block_size=16)
    s = build_super_streams(cb).device_put()
    setup_s = time.perf_counter() - t0
    x = jnp.asarray(random_vector(shape[1], 1))
    y, first_s, second_s = two_calls(ops.cb_spmv, s, x)
    err = require_close(name, y, scipy_of(coo, shape) @ np.asarray(x, np.float64))
    steps = ops.spmv_launch_stats(s)["steps"]
    compile_s = check_kernels(ops.cb_spmv, s, x, expect=present_kernels(steps))
    report(name, matrix=f"{shape[0]}x{shape[1]}", nnz=cb.nnz,
           groups=steps, setup_s=setup_s, compile_s=compile_s,
           first_call_s=first_s, second_call_s=second_s, rel_max_err=err,
           peak_bytes_in_use=peak_bytes())


def cg_phase(coo, shape):
    """Block-Jacobi CG through ``CBLinearOperator``; residual checked on
    the host in float64."""
    t0 = time.perf_counter()
    cb = CBMatrix.from_coo(*coo, shape, block_size=16)
    op = jax.device_put(CBLinearOperator.from_cb(cb))
    M = block_jacobi(cb)
    setup_s = time.perf_counter() - t0
    A = scipy_of(coo, shape)
    x = jnp.asarray(random_vector(shape[1], 1))
    err = require_close("cg.matvec", op.matvec(x), A @ np.asarray(x, np.float64))
    b = jnp.asarray(random_vector(shape[0], 2))
    res, first_s, second_s = two_calls(cg, op, b, M, tol=CG_TOL)
    b64 = np.asarray(b, np.float64)
    true_rel = float(np.linalg.norm(b64 - A @ np.asarray(res.x, np.float64))
                     / np.linalg.norm(b64))
    if not (bool(res.converged) and true_rel <= CG_TOL):
        raise SmokeFailure(
            f"cg: converged={bool(res.converged)} after {int(res.iterations)} "
            f"iterations, host residual {true_rel:.3e} (tol {CG_TOL})")
    steps = ops.spmv_launch_stats(op.streams)["steps"]
    compile_s = check_kernels(lambda o, bb, m: cg(o, bb, m, tol=CG_TOL),
                              op, b, M, expect=present_kernels(steps))
    report("cg", matrix=f"{shape[0]}x{shape[1]}", nnz=cb.nnz,
           groups=steps, setup_s=setup_s,
           compile_s=compile_s, first_call_s=first_s, second_call_s=second_s,
           iterations=int(res.iterations), host_rel_residual=true_rel,
           matvec_rel_max_err=err, peak_bytes_in_use=peak_bytes())


def dense_spmm_phase(coo, shape, *, n_rhs: int = 256):
    """``ops.cb_spmv`` and ``ops.cb_spmm`` (n_rhs columns) on a pruned
    weight; the SpMV half goes through :func:`spmv_phase`."""
    spmv_phase(coo, shape, name="dense.spmv")
    t0 = time.perf_counter()
    cb = CBMatrix.from_coo(*coo, shape, block_size=16)
    st = jax.device_put(super_tile_stream_from_cb(cb))
    setup_s = time.perf_counter() - t0
    X = jnp.asarray(np.random.default_rng(3).standard_normal(
        (shape[1], n_rhs)).astype(np.float32))
    Y, first_s, second_s = two_calls(ops.cb_spmm, st, X)
    err = require_close("dense.spmm", Y,
                        scipy_of(coo, shape) @ np.asarray(X, np.float64))
    compile_s = check_kernels(ops.cb_spmm, st, X, expect={SPMM_KERNEL})
    report("dense.spmm", matrix=f"{shape[0]}x{shape[1]}", nnz=cb.nnz,
           tile_groups=st.num_groups, n_rhs=n_rhs, setup_s=setup_s,
           compile_s=compile_s, first_call_s=first_s, second_call_s=second_s,
           rel_max_err=err, peak_bytes_in_use=peak_bytes())


def planner_phase(coo, shape):
    """``CBLinearOperator.from_cb(plan="auto")``: the timed plan search on
    a TPU, then the planned operator's matvec vs scipy."""
    cb = CBMatrix.from_coo(*coo, shape, block_size=16)
    t0 = time.perf_counter()
    op = jax.device_put(CBLinearOperator.from_cb(cb, plan="auto"))
    setup_s = time.perf_counter() - t0
    x = jnp.asarray(random_vector(shape[1], 1))
    y, first_s, second_s = two_calls(op.matvec, x)
    err = require_close("planner", y,
                        scipy_of(coo, shape) @ np.asarray(x, np.float64))
    steps = ops.spmv_launch_stats(op.streams)["steps"]
    compile_s = check_kernels(lambda o, xx: o.matvec(xx), op, x,
                              expect=present_kernels(steps))
    p = op.plan
    report("planner", matrix=f"{shape[0]}x{shape[1]}", nnz=cb.nnz,
           groups=steps, mode=p.mode,
           block_size=p.block_size, group_size=p.group_size, colagg=p.colagg,
           plan_t_spmv_s=p.t_spmv, plan_and_build_s=setup_s,
           compile_s=compile_s, first_call_s=first_s, second_call_s=second_s,
           rel_max_err=err, peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------------
# Phase (four chips)
# ---------------------------------------------------------------------------

def distributed_phase(coo, shape, devices):
    """``distributed_spmv`` (pallas, psum_scatter) over a mesh of
    ``devices`` vs single-device ``ops.cb_spmv`` and scipy."""
    t0 = time.perf_counter()
    cb = CBMatrix.from_coo(*coo, shape, block_size=16)
    sharded = dist.shard_streams(cb, len(devices))
    setup_s = time.perf_counter() - t0
    stacked = sharded.streams
    nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(stacked))
    # packed groups (grid steps) per device, the same on every device
    groups = ops.spmv_launch_stats(
        jax.tree_util.tree_map(lambda a: a[0], stacked))["steps"]
    report("distributed.streams", matrix=f"{shape[0]}x{shape[1]}",
           nnz=cb.nnz, devices=len(devices), groups_per_device=groups,
           coo_width=stacked.coo_codes.shape[2], hbm_bytes_total=nbytes,
           hbm_bytes_per_device=nbytes // len(devices),
           device_nnz=sharded.device_nnz.tolist(), setup_s=setup_s)
    mesh = compat.make_mesh((len(devices),), ("model",), devices=devices)
    placed = sharded.device_put(mesh)
    x = jnp.asarray(random_vector(shape[1], 1))

    def run(streams, xx):
        return dist.distributed_spmv(
            dist.ShardedStreams(len(devices), streams, sharded.device_nnz),
            xx, mesh)

    y, first_s, second_s = two_calls(run, placed.streams, x)
    y_ref = scipy_of(coo, shape) @ np.asarray(x, np.float64)
    err = require_close("distributed", y, y_ref)
    compile_s = check_kernels(run, placed.streams, x,
                              expect=present_kernels(groups))
    y_one = ops.cb_spmv(build_super_streams(cb).device_put(), x)
    err_one = require_close("distributed.vs_single", y,
                            np.asarray(y_one, np.float64))
    report("distributed", compile_s=compile_s, first_call_s=first_s,
           second_call_s=second_s, rel_max_err=err,
           rel_max_err_vs_single=err_one,
           single_rel_max_err=rel_max_err(y_one, y_ref),
           peak_bytes_in_use=[peak_bytes(d) for d in devices])


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed SpMV phase on 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"compile cache: {compile_cache.enable()}", flush=True)

    if args.chips == 4:
        distributed_phase(*power_law_matrix(), devices[:4])
    else:
        coo, shape = power_law_matrix()
        spmv_phase(coo, shape)
        cg_phase(*spd_banded_matrix())
        dense_spmm_phase(*pruned_weight_matrix())
        planner_phase(coo, shape)

    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
