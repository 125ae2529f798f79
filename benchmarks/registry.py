"""Single data-driven registry of benchmark sections + their guard schemas.

One ``Section`` record per benchmark: the human title and runner module
consumed by ``benchmarks/run.py``, and the *declarative* guard schema
consumed by ``scripts/bench_guard.py`` — required row keys, per-row
minimum bounds, machine-independent timing-ratio pairs, keys that must
be ``True``, and geomean upper bounds between two row keys. PRs 2-4
each grew a copy-pasted per-section block in both files; new sections
now add exactly one record here.

This module is imported by the standalone guard script, so it must stay
dependency-free (no jax/numpy): runner modules are resolved lazily by
name via :func:`runner`.
"""
from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass(frozen=True)
class Section:
    """One benchmark section and its guard contract."""

    title: str
    module: str                  # dotted module with a ``main(scale)`` entry
    # -- guard schema (all optional; empty = section is not guarded) ------
    required_keys: tuple = ()    # every row must carry these, finite
    timing_pairs: tuple = ()     # (num, den): relative drift vs baseline
    require_true: tuple = ()     # row keys that must be exactly True
    min_values: tuple = ()       # (key, bound): row[key] >= bound
    geomean_max: tuple = ()      # (num, den, bound): geomean(num/den) <= bound

    @property
    def guarded(self) -> bool:
        return bool(self.required_keys)


_BATCH_KEYS = (
    "matrix", "nnz", "group_size", "steps_unbatched", "steps_batched",
    "padded_elems_unbatched", "padded_elems_batched",
    "padded_ratio_unbatched", "padded_ratio_batched",
    "t_unbatched", "t_batched",
)

SECTIONS: dict[str, Section] = {
    "fig9": Section("Fig. 9 — SpMV perf vs CSR/COO/BSR",
                    "benchmarks.fig9_perf"),
    "fig10": Section("Fig. 10 — cache hit-rate model",
                     "benchmarks.fig10_locality"),
    "fig11": Section("Fig. 11 — ablation CB-I/II/III",
                     "benchmarks.fig11_ablation"),
    "fig12": Section("Fig. 12 — storage + preprocessing",
                     "benchmarks.fig12_overhead"),
    "fig34": Section("Fig. 3/4 — distribution + balance",
                     "benchmarks.fig34_distribution"),
    "spmv_batch": Section(
        "Batched super-block engine vs unbatched",
        "benchmarks.spmv_batch",
        required_keys=_BATCH_KEYS,
        timing_pairs=(("t_batched", "t_unbatched"),
                      ("t_ref_batched", "t_ref_unbatched")),
    ),
    # the SpMM section mirrors spmv_batch's schema exactly (same batched-
    # engine claims: step shrink, padded weight stream, kernel-path timing)
    "spmm": Section(
        "Batched SpMM super-tile engine vs flat tile stream",
        "benchmarks.spmm_batch",
        required_keys=_BATCH_KEYS,
        timing_pairs=(("t_batched", "t_unbatched"),
                      ("t_ref_batched", "t_ref_unbatched")),
    ),
    "solvers": Section(
        "Iterative solvers vs scipy.sparse CPU reference",
        "benchmarks.solvers",
        required_keys=("matrix", "solver", "n", "nnz", "iters_to_tol",
                       "iters_ref", "converged", "t_per_iter",
                       "t_ref_per_iter"),
        timing_pairs=(("t_per_iter", "t_ref_per_iter"),),
        require_true=("converged",),
    ),
    "autotune": Section(
        "Autotuned plans vs default constants (cost model + cache)",
        "benchmarks.autotune_bench",
        required_keys=(
            "matrix", "nnz", "block_size_planned", "group_size_planned",
            "steps_default", "steps_planned",
            "predicted_padded_elems", "predicted_steps",
            "padded_elems_default", "padded_elems_planned",
            "plan_hit_rate",
        ),
        min_values=(("plan_hit_rate", 0.5),),
        # the acceptance bars: tuned plans never regress padded work, and
        # the padded work the built streams run stays inside a 2x
        # envelope of what the cost model predicted for the plan
        geomean_max=(("padded_elems_planned", "padded_elems_default", 1.0),
                     ("padded_elems_planned", "predicted_padded_elems", 2.0)),
    ),
    "dynamic": Section(
        "Dynamic sparsity: value churn via with_values vs rebuild",
        "benchmarks.dynamic_bench",
        required_keys=(
            "matrix", "nnz", "churn_steps", "t_update", "t_rebuild",
            "update_rebuild_ratio", "plan_hit_rate", "streams_match",
        ),
        timing_pairs=(("t_update", "t_rebuild"),),
        require_true=("streams_match",),
        # 15/16 churn steps must hit the structure-keyed plan cache
        min_values=(("plan_hit_rate", 0.9),),
        # the acceptance bar: payload rewrite at <= 1/4 of a full rebuild
        geomean_max=(("t_update", "t_rebuild", 0.25),),
    ),
    "obs": Section(
        "Observability: instrumentation overhead + launch accounting",
        "benchmarks.obs_bench",
        required_keys=(
            "matrix", "nnz", "t_enabled", "t_disabled", "overhead_ratio",
            "metrics_present",
        ),
        timing_pairs=(("t_enabled", "t_disabled"),),
        require_true=("metrics_present",),
        # the acceptance bar: recording costs <= 5% of the kernel path
        geomean_max=(("t_enabled", "t_disabled", 1.05),),
    ),
    "locality": Section(
        "Locality: modeled cache traffic, planned CB vs flat formats",
        "benchmarks.locality_bench",
        required_keys=(
            "matrix", "nnz", "block_size", "group_size",
            "accesses_cb", "unique_lines_cb",
            "bytes_moved_cb", "arith_intensity_cb",
            "l1_hit_cb", "l2_hit_cb",
            "l1_misses_per_nnz_cb", "l2_misses_per_nnz_cb",
            "l1_misses_per_nnz_csr", "l2_misses_per_nnz_csr",
            "l1_misses_per_nnz_bsr", "l2_misses_per_nnz_bsr",
            "l1_misses_per_nnz_tile", "l2_misses_per_nnz_tile",
            "l1_misses_per_nnz_baseline", "l2_misses_per_nnz_baseline",
        ),
        # the paper's Fig. 10 ordering claim on the real planned
        # pipeline: corpus geomean of CB misses/nnz over the
        # CSR/BSR/tile geomean, with margin (0.75 at both levels today)
        geomean_max=(
            ("l1_misses_per_nnz_cb", "l1_misses_per_nnz_baseline", 0.85),
            ("l2_misses_per_nnz_cb", "l2_misses_per_nnz_baseline", 0.85),
        ),
    ),
    "robustness": Section(
        "Fault injection: typed detection + solver fallback recovery",
        "benchmarks.robustness_bench",
        required_keys=("matrix", "case", "ok", "rate"),
        require_true=("ok",),
        # the acceptance bar: every injected fault detected (or tolerated
        # with a bit-correct result) and every seeded breakdown recovered
        min_values=(("rate", 1.0),),
    ),
}


def runner(name: str):
    """Resolve a section's ``main(scale)`` runner (lazy import)."""
    return importlib.import_module(SECTIONS[name].module).main
