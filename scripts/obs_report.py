#!/usr/bin/env python
"""Run a tiny traced workload and render the obs subsystem's exports.

    PYTHONPATH=src python scripts/obs_report.py [--out PATH.trace.json]

Drives one ``robust_solve`` on an SPD corpus matrix plus a few serving
ticks on a toy model — both under the default tracer — then:

  * writes the spans as Chrome ``trace_event`` JSON (load the file in
    ``chrome://tracing`` / Perfetto);
  * prints a per-span-name summary table (count / total / self / mean /
    max);
  * prints the metrics snapshot's headline counters.

``main`` returns the payload dict (trace path, chrome trace object,
snapshot) so the tier-1 smoke test can validate the export schema
without re-parsing stdout.
"""
from __future__ import annotations

import argparse
import json
import sys


def _build_workload():
    """One robust_solve + a short serving run, all under obs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ModelConfig
    from repro.core.cb_matrix import CBMatrix
    from repro.data import matrices
    from repro.models.model import Model
    from repro.serving import Request, ServingEngine
    from repro.solvers import CBLinearOperator, robust_solve

    d = 96
    r, c, v = matrices.spd_banded(d, bandwidth=7, seed=3)
    cb = CBMatrix.from_coo(r, c, v.astype(np.float32), (d, d),
                           block_size=16, val_dtype=np.float32)
    op = CBLinearOperator.from_cb(cb, plan="auto")
    locality = _locality_stats(op, int(cb.nnz))
    b = jnp.asarray(
        np.random.default_rng(0).standard_normal(d).astype(np.float32))
    res = robust_solve(op, b, tol=1e-6, maxiter=300)

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=128,
                      attn_chunk=32, remat="none", dtype="float32")
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, slots=2, max_len=64)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=np.array([i + 1], np.int32),
                           max_new_tokens=2))
    eng.run_until_done(max_ticks=16)
    return res, eng, locality


def _locality_stats(op, nnz: int) -> dict:
    """Modeled cache traffic of the operator's planned super-streams."""
    from repro.obs import locality as loc

    return loc.stream_stats(loc.access_stream_super(op.streams), nnz=nnz)


def _counter_rows(snap: dict, name: str) -> list[tuple[str, float]]:
    entry = snap.get(name)
    if not entry:
        return []
    return [
        (",".join(f"{k}={v}" for k, v in sorted(s["labels"].items())) or "-",
         s["value"])
        for s in entry["series"]
    ]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="obs_demo.trace.json",
                    help="Chrome trace output path (default %(default)s)")
    args = ap.parse_args(argv)

    from repro import obs

    obs.configure(enabled=True)
    obs.reset()
    res, eng, locality = _build_workload()

    trace_path = obs.export_chrome_trace(args.out)
    trace = obs.chrome_trace()
    snap = obs.snapshot()

    print(f"solve: converged={res.converged} solver={res.solver} "
          f"attempts={len(res.attempts)}; "
          f"serving: ticks={eng.health()['ticks']} "
          f"completed={eng.health()['completed']}")
    print(f"\n[chrome trace: {trace_path} — "
          f"{len(trace['traceEvents'])} events]")

    print(f"\n{'span':<24}{'count':>7}{'total_ms':>10}{'self_ms':>9}"
          f"{'mean_ms':>9}{'max_ms':>9}")
    for row in obs.tracer().summary():
        print(f"{row['name']:<24}{row['count']:>7}"
              f"{row['total_s'] * 1e3:>10.2f}{row['self_s'] * 1e3:>9.2f}"
              f"{row['mean_s'] * 1e3:>9.2f}{row['max_s'] * 1e3:>9.2f}")

    print(f"\n{'metric / labels':<58}{'value':>10}")
    headline = (
        "repro.ops.spmv.calls",
        "repro.ops.spmv.launches",
        "repro.ops.spmv.steps",
        "repro.ops.spmv.padded_elems",
        "repro.solvers.traces",
        "repro.solvers.robust.attempts",
        "repro.solvers.robust.outcome",
        "repro.serving.ticks",
        "repro.serving.completed",
    )
    for name in headline:
        for labels, value in _counter_rows(snap, name):
            print(f"{name + '{' + labels + '}':<58}{value:>10g}")

    print("\nmodeled locality (planned super-streams, LRU line model):")
    print(f"  l1_hit={locality['l1_hit_rate']:.3f} "
          f"l2_hit={locality['l2_hit_rate']:.3f} "
          f"l1miss/nnz={locality['l1_misses_per_nnz']:.4f} "
          f"l2miss/nnz={locality['l2_misses_per_nnz']:.4f} "
          f"lines={locality['unique_lines']} "
          f"bytes_moved={locality['bytes_moved']} "
          f"AI={locality['arith_intensity']:.2f}")

    return {"trace_path": trace_path, "trace": trace, "snapshot": snap,
            "summary": obs.tracer().summary(), "locality": locality}


if __name__ == "__main__":
    main()
    sys.exit(0)
