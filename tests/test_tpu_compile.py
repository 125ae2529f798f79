"""AOT compiles for a described TPU v5e: what interpret mode cannot see.

Mosaic refuses layouts that the Pallas interpreter accepts (block shapes
whose last two dimensions are neither (8, 128)-aligned nor the whole
array, in-kernel lane splits, scalar-prefetch maps that overflow SMEM).
These tests compile every kernel and the whole SpMV path for a v5e chip
that is described, not attached, and require each kernel to come out as a
``tpu_custom_call``. Nothing runs, so they say nothing about results or
speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers must
all collect the same tests.
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import compat
from repro.core import CBMatrix
from repro.core import distributed as dist
from repro.core.streams import build_super_streams, group_size_for
from repro.data import matrices
from repro.kernels import cb_block_dense, cb_colagg, cb_coo, ops

# the package re-exports the ``cb_spmm`` entry point under the module's name
cb_spmm = importlib.import_module("repro.kernels.cb_spmm")

KERNEL_OF_FORMAT = {
    "dense": "cb_block_dense_spmv_batched",
    "panel": "cb_colagg_panel_spmv_batched",
    "coo": "cb_coo_spmv_batched",
}


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compile cache off: an
    entry written for a chip that is not attached cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        compilation_cache.reset_cache()
        if old_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(fn, *args) -> set[str]:
    """Names of the ``tpu_custom_call`` ops in the compiled program."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', text))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs_of(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _spec(np.shape(a), np.asarray(a).dtype, sharding), tree)


@pytest.mark.parametrize("B", [8, 16, 24])
def test_dense_kernel_compiles(one_chip, B):
    G, gd = group_size_for(B), 64
    fn = lambda t, x: cb_block_dense.block_dense_spmv_batched(  # noqa: E731
        t, x, interpret=False)
    got = _kernels(fn, _spec((gd, G * B, B), jnp.float32, one_chip),
                   _spec((gd, G, B), jnp.float32, one_chip))
    assert got == {"cb_block_dense_spmv_batched"}


@pytest.mark.parametrize("B", [8, 16, 24])
def test_panel_kernel_compiles(one_chip, B):
    gp, W = 64, 208
    fn = lambda p, x: cb_colagg.panel_spmv_batched(  # noqa: E731
        p, x, interpret=False)
    got = _kernels(fn, _spec((gp, B, W), jnp.float32, one_chip),
                   _spec((gp, W), jnp.float32, one_chip))
    assert got == {"cb_colagg_panel_spmv_batched"}


@pytest.mark.parametrize("B", [8, 16, 24])
def test_coo_kernel_compiles(one_chip, B):
    gc, W = 64, 272
    fn = lambda c, v, x: cb_coo.coo_spmv_batched(  # noqa: E731
        c, v, x, block_size=B, interpret=False)
    got = _kernels(fn, _spec((gc, W), jnp.int32, one_chip),
                   _spec((gc, W), jnp.float32, one_chip),
                   _spec((gc, W), jnp.float32, one_chip))
    assert got == {"cb_coo_spmv_batched"}


@pytest.mark.parametrize("B", [8, 16, 24])
def test_spmm_kernel_compiles(one_chip, B):
    # 2133 groups of 16 slots: the slot map of a 14336 x 4096 weight at
    # 85% block sparsity, which overflowed SMEM as a 2-D (gt, Gt) map.
    gt, Gt, nb, N = 2133, 16, 256, 256
    fn = lambda t, c, x: cb_spmm.super_tile_spmm(  # noqa: E731
        t, c, x, block_n=N, interpret=False)
    got = _kernels(fn, _spec((gt, Gt * B, B), jnp.float32, one_chip),
                   _spec((gt, Gt), jnp.int32, one_chip),
                   _spec((nb, B, N), jnp.float32, one_chip))
    assert got == {"cb_super_tile_spmm"}


@pytest.mark.parametrize("family", sorted(matrices.FAMILIES))
def test_spmv_path_compiles(one_chip, family):
    """The whole jitted SpMV (gathers, kernels, scatter-add combine) on
    real packed streams; every present format's kernel is compiled."""
    m = n = 1024
    r, c, v = matrices.FAMILIES[family](m, n, seed=0, **(
        {"density": 0.01} if family == "uniform" else {}))
    cb = CBMatrix.from_coo(r, c, v, (m, n), block_size=16)
    s = build_super_streams(cb)
    present = {KERNEL_OF_FORMAT[f] for f, g in (
        ("dense", s.num_dense_groups), ("panel", s.num_panel_groups),
        ("coo", s.num_coo_groups)) if g}
    assert present
    fn = lambda st, x: ops._cb_spmv_jit(st, x, interpret=False)  # noqa: E731
    got = _kernels(fn, _specs_of(s, one_chip),
                   _spec((n,), jnp.float32, one_chip))
    assert got == present


def test_distributed_spmv_compiles_on_four_chips(topo):
    """``distributed_spmv`` over a 4-chip mesh: kernels inside shard_map."""
    m = n = 1024
    r, c, v = matrices.power_law(m, n, seed=0)
    cb = CBMatrix.from_coo(r, c, v, (m, n), block_size=16)
    sharded = dist.shard_streams(cb, 4)
    mesh = compat.make_mesh((4,), ("model",), devices=topo.devices[:4])
    streams = _specs_of(sharded.streams, NamedSharding(mesh, P("model")))
    x = _spec((n,), jnp.float32, NamedSharding(mesh, P()))

    def fn(st, xx):
        return dist.distributed_spmv(
            dist.ShardedStreams(4, st, sharded.device_nnz), xx, mesh,
            interpret=False)

    assert "cb_coo_spmv_batched" in _kernels(fn, streams, x)
