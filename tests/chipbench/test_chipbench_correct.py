"""What decides ``correct``: the lower-precision control and each fault a
cell can have must come out as not correct, on the whole run's path."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import chipbench_faults  # noqa: E402
import chipbench_tiny  # noqa: E402
from chipbench_tiny import ROOT  # noqa: E402

from chipbench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}

OPS = sorted({chipbench_faults.op_of(w) for w in CELLS})
CASES = [(w, f) for w in CELLS
         for f in chipbench_faults.fault_names(chipbench_faults.op_of(w))]


@pytest.mark.parametrize("op", OPS)
def test_every_op_has_checks(op):
    """Each operation a cell runs has its faults and traced metrics."""
    path = chipbench_faults.checks_path(op)
    assert path.is_file(), f"operation {op!r} has no checks file {path}"
    checks = chipbench_faults.load_checks(op)
    assert checks.FAULTS and all(callable(f) for f in checks.FAULTS.values())
    assert checks.TRACED


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_makes_the_run_incorrect(tmp_path, workload, fault):
    if CELLS[workload]["chips"] == 1:
        r = chipbench_faults.run(workload, fault, tmp_path)
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
        out = subprocess.run(
            [sys.executable, str(HERE / "chipbench_faults.py"), workload, fault],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is False and r["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("workload", [w for w in CELLS if CELLS[w]["chips"] == 1])
def test_unplanted_run_is_correct(tmp_path, workload):
    assert chipbench_faults.run(workload, None, tmp_path)["correct"] is True


@pytest.mark.parametrize("workload", list(CELLS))
def test_lower_precision_control_fails_the_limits(tmp_path, workload):
    """The reference in bfloat16, in the program's place, reads over the
    cell's limit on every input of the pool."""
    root = chipbench_tiny.tiny_root(tmp_path)
    spec = harness.load_cell(root, workload)
    gen, op = harness.load_module(spec["gen"]), harness.load_module(spec["op"])
    cfg, traffic = spec["config"], spec["traffic"]
    for seed in (1, 2, 3):
        r, c, v, shape = gen.generate(cfg, seed)
        data = harness.Data(r, c, v, shape, np.dtype(cfg["dtype"]))
        for inp in op.inputs(data, traffic, np.random.default_rng([seed, 1])):
            ref = op.reference(data, traffic, inp)
            ctrl = op.check(op.control(data, traffic, inp), ref)
            assert any(ctrl[n] > lim for n, lim in spec["limits"].items()), ctrl


def test_reference_cg_matches_a_float64_solve(tmp_path):
    """The CG reference with many iterations reaches scipy's solution."""
    import scipy.sparse.linalg as spla

    root = chipbench_tiny.tiny_root(tmp_path)
    spec = harness.load_cell(root, "hpcg-64.cg50")
    gen, op = harness.load_module(spec["gen"]), harness.load_module(spec["op"])
    r, c, v, shape = gen.generate(spec["config"], 0)
    data = harness.Data(r, c, v, shape, np.float32)
    b = op.inputs(data, spec["traffic"], np.random.default_rng(0))[0]
    x = op.reference(data, dict(spec["traffic"], maxiter=200), b)
    exact = spla.spsolve(data.csr64().tocsc(), b.astype(np.float64))
    np.testing.assert_allclose(x, exact, rtol=1e-9, atol=1e-9)
