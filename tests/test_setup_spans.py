"""Spans of the host set-up stages, named scopes on the x gather and the
combine, and the compile-cache counters (``repro.compile_cache``)."""
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from repro import compile_cache, obs
from repro.core import CBMatrix
from repro.core.distributed import shard_streams
from repro.core.streams import build_super_streams
from repro.data import matrices
from repro.solvers import block_jacobi

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.configure(enabled=True, clock=time.monotonic)
    obs.reset()
    yield
    obs.configure(enabled=True, clock=time.monotonic)
    obs.reset()


def _coo(d=160, seed=4):
    r, c, v = matrices.power_law(d, d, avg_deg=6, seed=seed)
    return r, c, v.astype(np.float32), (d, d)


def _cb():
    r, c, v, shape = _coo()
    return CBMatrix.from_coo(r, c, v, shape, block_size=16,
                             use_column_aggregation=True)


# Each set-up entry point: the spans it must emit, child -> parent.
ENTRY_POINTS = {
    "from_coo": (
        lambda cb: _cb(),
        {"cb.from_coo": None,
         "cb.from_coo.partition": "cb.from_coo",
         "cb.from_coo.colagg": "cb.from_coo",
         "cb.from_coo.formats": "cb.from_coo",
         "cb.from_coo.aggregate": "cb.from_coo",
         "cb.from_coo.balance": "cb.from_coo"}),
    "build_super_streams": (
        lambda cb: build_super_streams(cb, group_size=2),
        {"cb.build_super_streams": None,
         "cb.streams.collect": "cb.build_super_streams",
         "cb.streams.layout": "cb.build_super_streams",
         "cb.streams.balance": "cb.streams.layout"}),
    "shard_streams": (
        lambda cb: shard_streams(cb, 4),
        {"cb.shard_streams": None,
         "cb.shard.balance": "cb.shard_streams",
         "cb.shard.build": "cb.shard_streams",
         "cb.build_super_streams": "cb.shard.build",
         "cb.streams.collect": "cb.build_super_streams",
         "cb.streams.layout": "cb.build_super_streams",
         "cb.streams.balance": "cb.streams.layout",
         "cb.shard.stack": "cb.shard_streams"}),
    "block_jacobi": (
        lambda cb: block_jacobi(cb),
        {"cb.block_jacobi": None}),
}


@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
def test_entry_point_emits_its_spans_with_parent_links(entry):
    make, expected = ENTRY_POINTS[entry]
    cb = _cb() if entry != "from_coo" else None
    obs.reset()
    make(cb)
    recs = obs.tracer().records()
    by_id = {r.span_id: r for r in recs}
    assert {r.name for r in recs} == set(expected)
    for r in recs:
        parent = expected[r.name]
        if parent is None:
            assert r.parent is None and r.depth == 0
        else:
            assert by_id[r.parent].name == parent
            assert r.depth == by_id[r.parent].depth + 1
    # the two partitions of from_coo share one span name
    if entry == "from_coo":
        assert [r.name for r in recs].count("cb.from_coo.partition") == 2


def test_children_never_exceed_their_parent():
    cb = _cb()
    build_super_streams(cb, group_size=2)
    shard_streams(cb, 3)
    recs = obs.tracer().records()
    by_id = {r.span_id: r for r in recs}
    covered = {}
    for r in recs:
        if r.parent is not None:
            p = by_id[r.parent]
            assert p.start <= r.start
            assert r.start + r.duration <= p.start + p.duration
            covered[r.parent] = covered.get(r.parent, 0.0) + r.duration
    for pid, c in covered.items():
        assert c <= by_id[pid].duration
    rows = {row["name"]: row for row in obs.tracer().summary()}
    for row in rows.values():
        assert 0.0 <= row["self_s"] <= row["total_s"]
    # a root's subtree self times add up to the root
    subtree = [r for r in recs if r.name.startswith("cb.from_coo")]
    root = next(r for r in subtree if r.parent is None)
    assert sum(rows[n]["self_s"] for n in {r.name for r in subtree}) == \
        pytest.approx(root.duration, rel=1e-9, abs=1e-12)


def _leaves(tree):
    import jax

    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def test_obs_off_records_nothing_and_results_are_bit_identical():
    def build():
        cb = _cb()
        return (cb.packed, cb.blk_row_idx, cb.vp_per_blk,
                _leaves(build_super_streams(cb, group_size=2)),
                _leaves(shard_streams(cb, 2).streams),
                _leaves(block_jacobi(cb)))

    on = build()
    assert obs.tracer().records()
    obs.reset()
    obs.configure(enabled=False)
    off = build()
    assert obs.tracer().records() == ()
    flat_on = [np.asarray(a) for part in on for a in
               (part if isinstance(part, list) else [part])]
    flat_off = [np.asarray(a) for part in off for a in
                (part if isinstance(part, list) else [part])]
    assert len(flat_on) == len(flat_off)
    for a, b in zip(flat_on, flat_off):
        np.testing.assert_array_equal(a, b)


# -- named scopes -----------------------------------------------------------

SCOPE_SCRIPT = r"""
import contextlib, json, re, sys
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.core import CBMatrix
from repro.core import distributed as dist
from repro.core.streams import build_super_streams
from repro.data import matrices
from repro.kernels import ops
from repro.solvers import CBLinearOperator, block_jacobi, cg

d = 160
r, c, v = matrices.spd_banded(d, bandwidth=40, seed=5)
cb = CBMatrix.from_coo(r, c, v.astype(np.float32), (d, d), block_size=16)
x = jnp.ones(d, jnp.float32)
s = build_super_streams(cb, group_size=2)
op = CBLinearOperator.from_cb(cb)
M = block_jacobi(cb)
sh = dist.shard_streams(cb, 4)
mesh = compat.make_mesh((4,), ("model",), devices=jax.devices()[:4])

steps = {
    "cb_spmv": (lambda s, x: ops.cb_spmv(s, x), (s, x)),
    "cg": (lambda A, M, b: cg(A, b, M, tol=0.0, maxiter=3), (op, M, x)),
    "distributed_spmv": (
        lambda st, x: dist.distributed_spmv(
            dist.ShardedStreams(4, st, sh.device_nnz), x, mesh),
        (sh.streams, x)),
}

def strip(t):
    # op_name metadata, the module's name and its source-location tables
    t = re.sub(r",?\s*metadata=\{[^}]*\}", "", t)
    t = re.sub(r'^\d+ (\{|").*\n', "", t, flags=re.M)
    return re.sub(r"^HloModule [^\n]*", "HloModule", t, flags=re.M)

def compiled(name):
    jax.clear_caches()
    f, a = steps[name]
    return jax.jit(f).lower(*a).compile().as_text()

out = {}
scoped = {n: compiled(n) for n in steps}
jax.named_scope = lambda name: contextlib.nullcontext()
bare = {n: compiled(n) for n in steps}
for n, text in scoped.items():
    ops_of = {"gather": [], "scatter": []}
    for line in text.splitlines():
        m = re.search(r'= \S+ (gather|scatter)\(.*op_name="([^"]*)"', line)
        if m:
            ops_of[m.group(1)].append(m.group(2))
    out[n] = {"ops": ops_of, "same": strip(text) == strip(bare[n]),
              "bare_has_scope": "cb_gather" in bare[n]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def scoped_hlo():
    """Compiled HLO of the three benchmarked steps on 4 CPU devices, with
    the scopes and with ``jax.named_scope`` made a no-op."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SCOPE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


STEPS = ["cb_spmv", "cg", "distributed_spmv"]


@pytest.mark.parametrize("step", STEPS)
def test_gather_and_scatter_carry_their_scope(scoped_hlo, step):
    ops_of = scoped_hlo[step]["ops"]
    assert ops_of["gather"] and ops_of["scatter"]
    for name in ops_of["gather"]:
        assert "/cb_gather/" in name, name
        assert name.split("/cb_gather/")[1].split("/")[0] in (
            "coo", "panel", "dense")
    assert any("/cb_combine/" in name for name in ops_of["scatter"])


@pytest.mark.parametrize("step", STEPS)
def test_scopes_change_nothing_but_metadata(scoped_hlo, step):
    assert not scoped_hlo[step]["bare_has_scope"]
    assert scoped_hlo[step]["same"]


# -- compile-cache counters -------------------------------------------------

@pytest.mark.parametrize("obs_on", [True, False])
def test_compile_cache_counts_a_miss_then_a_hit(tmp_path, obs_on):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    obs.configure(enabled=obs_on)
    before = dict(compile_cache.counts)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        salt = float(obs_on) + 0.123  # a program no other test compiled

        def f(x):
            return jnp.sin(x) * salt + 1.0

        x = np.ones(7, np.float32)   # no compile of its own
        jax.jit(f)(x).block_until_ready()
        mid = dict(compile_cache.counts)
        jax.clear_caches()
        jax.jit(f)(x).block_until_ready()
        after = dict(compile_cache.counts)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()
    assert mid.get("misses", 0) - before.get("misses", 0) == 1
    assert mid.get("hits", 0) == before.get("hits", 0)
    assert after.get("hits", 0) - mid.get("hits", 0) == 1
    assert after.get("misses", 0) == mid.get("misses", 0)
    registry = (obs.counter("repro.compile_cache.misses").value(),
                obs.counter("repro.compile_cache.hits").value())
    assert registry == ((1, 1) if obs_on else (0, 0))
