"""Benchmark driver: one section per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--scale small|bench]
                                            [--only fig9,spmv_batch,...]
                                            [--json BENCH_spmv.json]

Sections, titles, runner modules, and guard schemas all live in ONE
place — ``benchmarks/registry.py`` — consumed here and by
``scripts/bench_guard.py``. ``--json`` writes every executed section's
row dicts (timings, bytes, padded-work ratios) to one machine-readable
file so the perf trajectory is tracked across PRs; the guard script
diffs such a file against the checked-in ``benchmarks/BENCH_spmv.json``
baseline.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from . import history
from .registry import SECTIONS, runner


def _jsonable(obj):
    """Coerce numpy scalars/arrays so ``json.dump`` accepts section rows."""
    import numpy as np

    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return _jsonable(obj.tolist())
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "bench"])
    ap.add_argument("--only", default=None,
                    help="comma list: " + ",".join(SECTIONS))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write executed sections' rows to PATH as JSON")
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.enable()

    chosen = args.only.split(",") if args.only else list(SECTIONS)
    unknown = [k for k in chosen if k not in SECTIONS]
    if unknown:
        ap.error(f"unknown sections {unknown}; known: {','.join(SECTIONS)}")

    results: dict[str, object] = {}
    for key in chosen:
        print(f"\n===== {SECTIONS[key].title} =====", flush=True)
        t0 = time.time()
        rows = runner(key)(args.scale)
        results[key] = _jsonable(rows)
        print(f"[{key} done in {time.time() - t0:.1f}s]", flush=True)

    if args.json:
        # attach the obs registry's view of everything the run recorded
        # (kernel-launch accounting, plan-cache rates, solver ladders) —
        # lazy import keeps the standalone guard script jax-free
        from repro import analysis, obs

        # lint health rides the same snapshot: repro.analysis.findings
        # gauges (per rule + total) so the JSON artifact records whether
        # the tree was invariant-clean when the numbers were taken.
        if os.path.isdir(os.path.join("src", "repro")):
            analysis.lint_paths(
                [os.path.join("src", "repro")],
                baseline_path=analysis.DEFAULT_BASELINE,
                record_obs=True,
            )

        payload = {
            "schema": "cb-spmv-bench/v1",
            "scale": args.scale,
            "git_sha": history.git_sha(),
            "sections": results,
            "metrics": obs.snapshot(),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"[wrote {args.json}]", flush=True)

        # every artifact run also extends the persistent trajectory
        # (benchmarks/history/history.jsonl, or $REPRO_BENCH_HISTORY)
        record = history.record_from_payload(
            payload, sha=payload["git_sha"])
        hist_path = history.append_record(record)
        print(f"[appended history record to {hist_path}]", flush=True)


if __name__ == "__main__":
    main()
