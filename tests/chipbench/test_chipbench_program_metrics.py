"""The metrics read from the program's own spans and counters: present in
every traced tiny run, bounded by the host clock around the same calls,
and left out where the program records nothing of the kind."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import chipbench_faults  # noqa: E402
import chipbench_tiny  # noqa: E402
from chipbench_tiny import ROOT  # noqa: E402

from chipbench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = [m["name"] for m in BENCH["per_layer"]
                if m["source"] == "program_span"]
SEED = 2**31 + 11


def span_metrics_of(workload):
    return [m["name"] for m in BENCH["per_layer"]
            if m["source"] == "program_span"
            and workload in m.get("workloads", [workload])]


def check_program_metrics(metrics, workload):
    spans = span_metrics_of(workload)
    assert set(spans + ["setup.cache_misses"]) <= set(metrics)
    total = metrics["setup.from_coo_s"]["value"] + metrics["setup.pack_s"]["value"]
    assert sum(metrics[n]["value"] for n in spans) <= total
    misses = metrics["setup.cache_misses"]["value"]
    assert misses >= 0 and misses == int(misses)


@pytest.mark.parametrize("workload", ["g500-s19.spmv", "hpcg-64.cg50"])
def test_traced_run_reports_the_program_metrics(tmp_path, workload):
    from repro import compile_cache, obs

    obs.reset()
    r = harness.run_cell(workload, SEED, 0.3, True,
                         root=chipbench_tiny.tiny_root(tmp_path),
                         require_tpu=False, cache=False)
    check_program_metrics(r["metrics"], workload)
    assert (r["metrics"]["setup.cache_misses"]["value"]
            == compile_cache.counts["misses"])
    rows = {row["name"]: row for row in obs.tracer().summary()}
    subtree = sum(row["self_s"] for name, row in rows.items()
                  if name.startswith("cb.from_coo"))
    assert subtree == pytest.approx(rows["cb.from_coo"]["total_s"])
    assert subtree <= r["metrics"]["setup.from_coo_s"]["value"]


def test_four_device_traced_run_reports_the_program_metrics(tmp_path):
    code = (
        "import json, pathlib, sys\n"
        f"sys.path.insert(0, {str(pathlib.Path(__file__).parent)!r})\n"
        "import chipbench_tiny\n"
        "from chipbench import harness\n"
        f"root = chipbench_tiny.tiny_root(pathlib.Path({str(tmp_path)!r}))\n"
        f"r = harness.run_cell('g500-s19.spmv.4chip', {SEED}, 0.3, True,"
        " root=root, require_tpu=False, cache=False)\n"
        "print(json.dumps(r['metrics']))\n")
    env = {"JAX_PLATFORMS": "cpu", "PATH": "",
           "PYTHONPATH": f"{ROOT / 'src'}:{ROOT}",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    metrics = json.loads(out.stdout.strip().splitlines()[-1])
    check_program_metrics(metrics, "g500-s19.spmv.4chip")
    checks = chipbench_faults.load_checks(
        chipbench_faults.op_of("g500-s19.spmv.4chip"))
    assert set(checks.TRACED) <= set(metrics)


def test_program_metrics_read_nothing_where_the_program_records_nothing(
        monkeypatch):
    """A program without these spans and counts (an older checkout)
    leaves the metrics out instead of failing the run."""
    from repro import compile_cache, obs

    obs.reset()
    for name in SPAN_METRICS:
        reader = harness.load_module(ROOT / "chipbench" / "metrics" / f"{name}.py")
        assert reader.read(None) is None
    misses = harness.load_module(
        ROOT / "chipbench" / "metrics" / "setup.cache_misses.py")
    assert misses.read(None) == compile_cache.counts["misses"]
    monkeypatch.delattr(compile_cache, "counts")
    assert misses.read(None) is None


def test_precond_metric_reads_the_block_jacobi_span_in_the_cg_cell_only():
    """Only the CG cell builds block-Jacobi, so only it reads
    ``setup.precond_s``; the reader gives that span's self time."""
    from repro import obs
    from repro.core.cb_matrix import CBMatrix
    from repro.solvers import block_jacobi

    assert span_metrics_of("hpcg-64.cg50") == SPAN_METRICS
    assert "setup.precond_s" not in span_metrics_of("g500-s19.spmv")
    idx = np.arange(64)
    cb = CBMatrix.from_coo(idx, idx, np.full(64, 2.0), (64, 64), block_size=16)
    obs.reset()
    block_jacobi(cb)
    reader = harness.load_module(
        ROOT / "chipbench" / "metrics" / "setup.precond_s.py")
    (row,) = [row for row in obs.tracer().summary()
              if row["name"] == "cb.block_jacobi"]
    assert reader.read(None) == row["self_s"] > 0
