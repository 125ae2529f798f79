"""``chip_smoke.py`` off the chip: its phases at tiny sizes, its refusals.

The phases run here on the CPU, where ``ops`` derives interpret mode;
the compiled-kernel check, which only a TPU can pass, is replaced by a
recorder so the tests see which kernels each phase demands. ``main``
itself must refuse to run without a TPU.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def demanded(smoke, monkeypatch):
    """The kernel sets the phases require, in call order."""
    seen = []
    monkeypatch.setattr(smoke, "check_kernels",
                        lambda fn, *args, expect: seen.append(expect) or 0.0)
    return seen


def test_spmv_phase(smoke, demanded):
    coo, shape = smoke.power_law_matrix(512)
    smoke.spmv_phase(coo, shape)
    assert demanded == [{"cb_coo_spmv_batched", "cb_colagg_panel_spmv_batched"}]


def test_cg_phase(smoke, demanded):
    smoke.cg_phase(*smoke.spd_banded_matrix(1024))
    assert "cb_colagg_panel_spmv_batched" in demanded[0]


def test_dense_spmm_phase(smoke, demanded):
    smoke.dense_spmm_phase(*smoke.pruned_weight_matrix(256, 128), n_rhs=8)
    assert demanded == [{"cb_block_dense_spmv_batched"}, {"cb_super_tile_spmm"}]


def test_planner_phase(smoke, demanded):
    smoke.planner_phase(*smoke.power_law_matrix(512))
    assert len(demanded) == 1 and demanded[0]


def test_phase_fails_on_wrong_result(smoke, demanded, monkeypatch):
    """A result off by more than the conformance bound fails the phase."""
    real = smoke.ops.cb_spmv
    monkeypatch.setattr(smoke.ops, "cb_spmv",
                        lambda s, x, **kw: real(s, x, **kw) * (1 + 1e-4))
    with pytest.raises(smoke.SmokeFailure, match="relative max error"):
        smoke.spmv_phase(*smoke.power_law_matrix(512))


def test_distributed_phase_on_four_cpu_devices():
    code = f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", {str(ROOT / "chip_smoke.py")!r})
smoke = importlib.util.module_from_spec(spec); spec.loader.exec_module(smoke)
seen = []
smoke.check_kernels = lambda fn, *a, expect: seen.append(expect) or 0.0
smoke.distributed_phase(*smoke.power_law_matrix(512), jax.devices()[:4])
assert seen == [{{"cb_coo_spmv_batched", "cb_colagg_panel_spmv_batched"}}], seen
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_tpu_kernel_names_reads_custom_calls(smoke):
    hlo = (
        '  %cb_coo_spmv_batched.1 = f32[5,8,16]{2,1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", backend_config={}\n'
        '  %fusion.2 = f32[8] fusion(%b), kind=kLoop\n'
        '  %cb_super_tile_spmm = f32[1] custom-call(%c), '
        'custom_call_target="tpu_custom_call"\n'
        '  %other = f32[1] custom-call(%d), custom_call_target="Sharding"\n')
    assert smoke.tpu_kernel_names(hlo) == {"cb_coo_spmv_batched",
                                           "cb_super_tile_spmm"}


def test_main_refuses_without_tpu(smoke, capsys):
    cache_dir = jax.config.jax_compilation_cache_dir
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
    assert jax.config.jax_compilation_cache_dir == cache_dir


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
