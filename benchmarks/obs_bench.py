"""Observability: instrumentation overhead + launch accounting.

Two claims, per corpus matrix:

  * **overhead** — instrumenting the engine must leave the guarded
    kernel-path timings untouched: ``t_enabled`` / ``t_disabled`` time
    the spmv_batch workload (a jitted ``ops.cb_spmv`` closure, freshly
    traced per side) with obs on and off, guarded as geomean
    t_enabled/t_disabled <= 1.05. Recording is a *trace-time* Python
    side effect, so the steady-state compiled path is identical by
    construction — the guard catches any future change that leaks
    recording (or a host sync) into the dispatch path. The eager
    per-call shim cost is µs-scale and reported as ``t_record_us``
    (informational, machine-dependent).
  * **accounting** — after one planned ``matvec``, ``metrics_present``
    asserts every required ``repro.ops.spmv.*`` key landed in the
    snapshot.

Determinism: planning is pinned to heuristic mode; only the ``t_*``
columns are machine-dependent (and guarded as a ratio).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.autotune import SearchSettings
from repro.core import CBMatrix
from repro.data import matrices
from repro.kernels import ops
from repro.solvers import CBLinearOperator

from ._timing import geomean, time_min

DETERMINISTIC = SearchSettings(mode="heuristic")

# Every snapshot produced by a planned pallas cb_spmv must carry these.
REQUIRED_METRICS = (
    "repro.ops.spmv.calls",
    "repro.ops.spmv.launches",
    "repro.ops.spmv.steps",
    "repro.ops.spmv.padded_elems",
)


def run(scale="small") -> list[dict]:
    rows_out = []
    was_enabled = obs.is_enabled()
    try:
        for spec, r, c, v, shape in matrices.corpus(scale):
            v32 = v.astype(np.float32)
            cb = CBMatrix.from_coo(r, c, v32, shape, block_size=16,
                                   val_dtype=np.float32)
            op = CBLinearOperator.from_cb(cb, plan="auto",
                                          plan_settings=DETERMINISTIC)
            x = jnp.asarray(
                np.random.default_rng(0).standard_normal(shape[1]),
                jnp.float32,
            )

            # -- accounting: one planned matvec, read the registry
            obs.configure(enabled=True)
            obs.reset()
            op.matvec(x).block_until_ready()
            snap = obs.snapshot()
            row = {
                "matrix": spec.name,
                "nnz": int(cb.nnz),
                "metrics_present": all(m in snap for m in REQUIRED_METRICS),
            }

            # -- overhead: the spmv_batch workload, obs on vs off. Fresh
            # jit closures per side force a retrace, so each side pays
            # (or skips) recording at trace time; the timed steady state
            # must be identical.
            streams = op.streams.device_put()
            kernel_on = jax.jit(lambda s, xx: ops.cb_spmv(s, xx))
            kernel_off = jax.jit(lambda s, xx: ops.cb_spmv(s, xx))
            row["t_enabled"] = time_min(kernel_on, streams, x)
            obs.configure(enabled=False)
            row["t_disabled"] = time_min(kernel_off, streams, x)
            obs.configure(enabled=True)
            row["overhead_ratio"] = row["t_enabled"] / row["t_disabled"]

            # eager per-call recording cost, µs (informational)
            t0 = time.perf_counter()
            reps = 50
            for _ in range(reps):
                ops.spmv_launch_stats(streams)
            row["t_record_us"] = (time.perf_counter() - t0) / reps * 1e6
            rows_out.append(row)
    finally:
        obs.configure(enabled=was_enabled)
    return rows_out


def main(scale="small"):
    rows = run(scale)
    print("matrix,nnz,t_on_ms,t_off_ms,overhead,t_record_us,metrics_ok")
    for r in rows:
        print(f"{r['matrix']},{r['nnz']},{r['t_enabled'] * 1e3:.2f},"
              f"{r['t_disabled'] * 1e3:.2f},{r['overhead_ratio']:.3f},"
              f"{r['t_record_us']:.1f},{int(r['metrics_present'])}")
    g_over = geomean([r["overhead_ratio"] for r in rows])
    print(f"GEOMEAN obs-on/obs-off: {g_over:.3f}x")
    return rows


if __name__ == "__main__":
    main()
