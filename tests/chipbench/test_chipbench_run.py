"""Whole runs of tiny cells on the CPU: the result line, the refusals, and
a configuration, operation and cell added as files plus their entries."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import chipbench_faults  # noqa: E402
import chipbench_tiny  # noqa: E402
from chipbench_tiny import ROOT  # noqa: E402

from chipbench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {w["name"]: w for w in BENCH["workloads"]}
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
FOUR_CHIPS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
SEED = 2**31 + 5  # more than 32 signed bits hold


def cpu_env(devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def check_line(result, workload, trace, root=ROOT):
    """The result object as the contract shapes it."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    keys = list(result)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    dev = result["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bench[kind]
               if workload in m.get("workloads", [workload])}
    for name, m in result["metrics"].items():
        assert m["unit"] == allowed[name] and isinstance(m["value"], float)
    limits = json.loads(
        (root / "chipbench" / "cells" / f"{workload}.json").read_text())["limits"]
    assert set(result["checks"]) == set(limits)
    for c in result["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.fixture
def tiny(tmp_path):
    return chipbench_tiny.tiny_root(tmp_path)


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_and_prints_the_contract_line(tiny, workload, trace):
    r = harness.run_cell(workload, SEED, 0.3, bool(trace), root=tiny,
                         require_tpu=False, cache=False)
    check_line(r, workload, trace)
    if not trace:
        assert {"setup_s", "op_ms"} <= set(r["metrics"])
    else:
        checks = chipbench_faults.load_checks(chipbench_faults.op_of(workload))
        assert set(checks.TRACED) <= set(r["metrics"])
    json.dumps(r)


@pytest.mark.parametrize("workload", FOUR_CHIPS)
def test_tiny_four_device_cell_runs(tmp_path, workload):
    code = (
        "import json, pathlib, sys\n"
        f"sys.path.insert(0, {str(pathlib.Path(__file__).parent)!r})\n"
        "import chipbench_tiny\n"
        "from chipbench import harness\n"
        f"root = chipbench_tiny.tiny_root(pathlib.Path({str(tmp_path)!r}))\n"
        f"print(json.dumps(harness.run_cell({workload!r}, {SEED}, 0.3, False,"
        " root=root, require_tpu=False, cache=False)))\n")
    out = subprocess.run([sys.executable, "-c", code], env=cpu_env(4),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    check_line(r, workload, 0)
    assert r["device"]["count"] == 4


@pytest.mark.parametrize("workload", list(CELLS))
def test_same_seed_same_inputs_different_seed_different_inputs(tiny, workload):
    """The matrix is the configuration's data set, the same on every seed,
    so every run compiles the same programs; the seed draws the inputs."""
    import numpy as np

    spec = harness.load_cell(tiny, workload)
    gen, op = harness.load_module(spec["gen"]), harness.load_module(spec["op"])

    def draw(seed):
        r, c, v, shape = gen.generate(spec["config"], seed)
        data = harness.Data(r, c, v, shape, np.float32)
        return (r, c, v), op.inputs(data, spec["traffic"],
                                    np.random.default_rng([seed, 1]))

    (m1, x1), (m2, x2), (m3, x3) = draw(SEED), draw(SEED), draw(SEED + 1)
    assert all((a == b).all() for a, b in zip(m1, m3))
    assert all((a == b).all() for a, b in zip(x1, x2))
    assert not any((a == b).all() for a, b in zip(x1, x3))


def test_no_tpu_means_no_result():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", ONE_CHIP[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_fewer_chips_than_the_cell_asks_for_is_refused():
    with pytest.raises(harness.BenchError, match="chips"):
        harness.pick_devices(4, require_tpu=False)


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths
    cannot run the program, so it prints nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", ONE_CHIP[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_a_new_cell_is_files_plus_one_workloads_entry(tiny):
    """A new generator, configuration, traffic mix and cell, added without
    touching any file that exists."""
    before = {p: p.read_bytes() for p in tiny.rglob("*") if p.is_file()}
    (tiny / "chipbench" / "gen" / "uniform.py").write_text(
        "import numpy as np\n\n\n"
        "def generate(params, seed):\n"
        "    rng = np.random.default_rng(seed)\n"
        "    n = int(params['n'])\n"
        "    key = np.unique(rng.integers(0, n * n, int(params['nnz'])))\n"
        "    return key // n, key % n, rng.random(key.size) + 0.5, (n, n)\n")
    (tiny / "chipbench" / "configs" / "er-tiny.json").write_text(json.dumps(
        {"name": "er-tiny", "generator": "uniform", "n": 200, "nnz": 3000,
         "dtype": "float32", "published": {}, "reduced": {}, "assumed": []}))
    (tiny / "chipbench" / "traffic" / "spmv.big-sample.json").write_text(
        json.dumps({"op": "spmv", "block_size": 8, "inputs": 2, "sample": 3}))
    (tiny / "chipbench" / "cells" / "er-tiny.spmv.json").write_text(
        json.dumps({"limits": {"y_err": 1e-5}}))
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "er-tiny", "source": "https://example.org",
                             "file": "chipbench/configs/er-tiny.json",
                             "reduced": [], "why": "uniform control"})
    bench["workloads"].append({"name": "er-tiny.spmv", "config": "er-tiny",
                               "traffic": "spmv.big-sample", "chips": 1,
                               "why": "uniform control"})
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p: p.read_bytes() for p in before}
    assert all(after[p] == before[p] for p in before
               if p.name != "BENCHMARK.json")
    r = harness.run_cell("er-tiny.spmv", 3, 0.2, False, root=tiny,
                         require_tpu=False, cache=False)
    check_line(r, "er-tiny.spmv", 0, root=tiny)


# A configuration, generator, traffic mix, operation and cell, with their
# tiny sizes and checks, laid out as in the repository, and their entries.
EXAMPLE = pathlib.Path(__file__).resolve().parent / "example_cell"


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_config_has_tiny_sizes(config):
    """Each configuration has tiny sizes, and they name only its own keys."""
    path = chipbench_tiny.tiny_path(config)
    assert path.is_file(), f"configuration {config!r} has no tiny sizes file {path}"
    entry = {c["name"]: c for c in BENCH["configs"]}[config]
    keys = json.loads((ROOT / entry["file"]).read_text())
    assert set(json.loads(path.read_text())) <= set(keys)


def test_a_new_config_op_and_cell_need_no_edit(tmp_path):
    """On a copy of the benchmark and its tests, a new generator,
    configuration, traffic mix, operation and cell, with their tiny sizes
    and checks, join as new files plus one ``configs`` and one
    ``workloads`` entry: the tests collect them, the cell's tiny run is
    correct and each of its faults makes it not correct."""
    copy = tmp_path / "repo"
    copy.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", copy)
    for p in ("chipbench", "tests/chipbench"):
        shutil.copytree(ROOT / p, copy / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)

    def collected():
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "--collect-only", "-q",
             "-p", "no:cacheprovider", "tests/chipbench"],
            cwd=copy, env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
        return int(re.search(r"(\d+) tests? collected", out.stdout).group(1))

    before_count = collected()
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    for src in EXAMPLE.rglob("*"):
        if (src.is_file() and src.name != "entries.json"
                and "__pycache__" not in src.parts):
            dst = copy / src.relative_to(EXAMPLE)
            assert not dst.exists()
            shutil.copy(src, dst)
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    entries = json.loads((EXAMPLE / "entries.json").read_text())
    bench["configs"] += entries["configs"]
    bench["workloads"] += entries["workloads"]
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == b for p, b in before.items()
               if p.name != "BENCHMARK.json")
    assert collected() > before_count

    code = (
        "import json, pathlib, sys\n"
        f"sys.path.insert(0, {str(copy / 'tests' / 'chipbench')!r})\n"
        "import chipbench_faults\n"
        "faults = chipbench_faults.fault_names('spmv_copy')\n"
        "for i, fault in enumerate([None, *faults]):\n"
        f"    tmp = pathlib.Path({str(tmp_path)!r}) / str(i)\n"
        "    r = chipbench_faults.run('er-2k.spmv', fault, tmp)\n"
        "    print(json.dumps([fault, r['correct']]))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=copy, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    runs = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("[")]
    assert runs == [[None, True], ["scaled", False], ["half", False]]


def test_unknown_workload_is_refused(tiny):
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.load_cell(tiny, "nope.spmv")


def test_heartbeat_sees_a_host_pause():
    """A pause that holds the interpreter shows as the heartbeat's longest
    gap, and ``stop`` ends its thread."""
    import time

    beat = harness.Heartbeat(period=0.001)
    time.sleep(0.05)
    switch = sys.getswitchinterval()
    t0 = time.perf_counter()
    sys.setswitchinterval(1.0)
    try:
        end = t0 + 0.3
        while time.perf_counter() < end:  # holds the GIL: the host pauses
            pass
    finally:
        sys.setswitchinterval(switch)
    time.sleep(0.05)
    beat.stop()
    assert not beat._thread.is_alive()
    assert beat.longest >= 0.25
    assert t0 - 0.05 <= beat.at <= t0 + 0.1
