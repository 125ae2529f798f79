"""Distribution tests that need >1 device run in a subprocess with
xla_force_host_platform_device_count (the main test process must keep the
default single CPU device — see the dry-run contract).
"""
import inspect
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=520,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def _mixed_power_law(m=256, n=256, seed=7):
    """A power-law matrix plus two full 16x16 blocks: dense-format blocks
    that land on some devices only, so the stacked shards differ per
    device in every format's group count or width."""
    import numpy as np

    from repro.data import matrices

    r, c, v = matrices.power_law(m, n, seed=seed)
    br, bc = (a.ravel() for a in np.meshgrid(np.arange(16), np.arange(16),
                                            indexing="ij"))
    r = np.concatenate([r, br + 32, br + 128])
    c = np.concatenate([c, bc + 200, bc + 64])
    v = np.concatenate([v, np.ones(2 * br.size, v.dtype)])
    _, keep = np.unique(r * n + c, return_index=True)
    return r[keep], c[keep], v[keep], (m, n)


def test_shard_streams_stacks_each_devices_packed_streams():
    """Every device's shard is ``build_super_streams`` of its own blocks,
    zero-padded to the largest device, and gathers no more lanes than the
    flat one-block-per-step layout would."""
    import numpy as np

    from repro.core import balance
    from repro.core import distributed as dist
    from repro.core.cb_matrix import CBMatrix
    from repro.core.streams import (SuperBlockStreams, build_streams,
                                    build_super_streams)

    cb = CBMatrix.from_coo(*_mixed_power_law(), block_size=16,
                           val_dtype=np.float32)
    D = 4
    sh = dist.shard_streams(cb, D)
    st = sh.streams
    assert isinstance(st, SuperBlockStreams)
    real = np.flatnonzero(cb.nnz_per_blk > 0)
    res = balance.device_load_balance(cb.nnz_per_blk[real], D)
    gs = res.group_size
    own, flat = [], []
    for d in range(D):
        slots = res.slots[d * gs : (d + 1) * gs]
        sub = dist._sub_matrix(cb, real[slots[slots >= 0]])
        own.append(build_super_streams(sub))
        flat.append(build_streams(sub))
    assert {s.group_size for s in own} == {st.group_size}
    # the shards differ per device, so the padding is exercised
    assert len({s.num_dense_groups for s in own}) > 1
    assert len({s.panel_vals.shape[-1] for s in own}) > 1
    for d, s in enumerate(own):
        for name, a in vars(s).items():
            if not isinstance(a, np.ndarray):
                continue
            got = np.asarray(getattr(st, name))[d]
            assert got.shape[0] == max(getattr(o, name).shape[0] for o in own)
            rest = got.copy()
            if a.shape[0]:  # an empty format sets no inner shape
                assert all(g >= w for g, w in zip(got.shape, a.shape)), name
                corner = tuple(map(slice, a.shape))
                np.testing.assert_array_equal(got[corner], a, err_msg=name)
                rest[corner] = 0
            assert not rest.any(), name  # padding is zeros alone
    # the stacked widths are the largest device's, not wider
    for name in ("dense_tiles", "panel_vals", "coo_codes"):
        full = [getattr(s, name) for s in own if getattr(s, name).shape[0]]
        assert getattr(st, name).shape[2:] == max(a.shape[1:] for a in full)
    # gathered lanes per device: packed layout against the flat one
    packed = sum(np.asarray(a)[0].size for a in
                 (st.dense_xidx, st.panel_xidx, st.coo_xidx))
    flat_lanes = (max(f.num_dense for f in flat) * 16
                  + max(f.num_panel for f in flat)
                  * max(f.panel_xidx.shape[1] for f in flat)
                  + max(f.num_coo for f in flat)
                  * max(f.coo_xidx.shape[1] for f in flat))
    assert packed <= flat_lanes, (packed, flat_lanes)


@pytest.mark.parametrize("impl", ["pallas", "reference"])
def test_distributed_spmv_4dev(impl):
    """Both engines against the dense oracle, under both combines, on a
    divisible and a ragged ``m`` and on shards that differ per device."""
    _run(textwrap.dedent(inspect.getsource(_mixed_power_law)) + f"""
import numpy as np, jax, jax.numpy as jnp
from repro import compat
from repro.core.cb_matrix import CBMatrix
from repro.core import distributed as dist
from repro.core.spmv_ref import dense_oracle
from repro.data import matrices

mesh = compat.make_mesh((4,), ("model",))
cases = [matrices.power_law(m, n, seed=7) + ((m, n),)
         for m, n in ((160, 160), (150, 144))]
cases.append(_mixed_power_law())
for r, c, v, (m, n) in cases:
    cb = CBMatrix.from_coo(r, c, v, (m, n), block_size=16,
                           val_dtype=np.float32)
    sh = dist.shard_streams(cb, 4)
    assert sh.load_imbalance < 1.2, sh.device_nnz
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    y0 = dense_oracle(r, c, v.astype(np.float32), (m, n), x)
    for combine in ("psum", "psum_scatter"):
        y = dist.distributed_spmv(sh, jnp.asarray(x), mesh, impl={impl!r},
                                  interpret=True, combine=combine)
        assert y.shape == (m,), (combine, y.shape)
        np.testing.assert_allclose(np.asarray(y), y0, rtol=3e-4, atol=3e-4)
print("OK")
""")


def test_distributed_spmv_combine_modes():
    """psum_scatter keeps an axis-divisible m's output sharded end to end;
    an unknown combine is refused."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core.cb_matrix import CBMatrix
from repro.core import distributed as dist
from repro.core.spmv_ref import dense_oracle
from repro.data import matrices

mesh = compat.make_mesh((4,), ("model",))
m = n = 160
r, c, v = matrices.power_law(m, n, seed=7)
cb = CBMatrix.from_coo(r, c, v, (m, n), block_size=16, val_dtype=np.float32)
sh = dist.shard_streams(cb, 4)
x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
y0 = dense_oracle(r, c, v.astype(np.float32), (m, n), x)
y = dist.distributed_spmv(sh, jnp.asarray(x), mesh, impl="reference")
np.testing.assert_allclose(np.asarray(y), y0, rtol=3e-4, atol=3e-4)
assert y.sharding.spec == P("model"), y.sharding
try:
    dist.distributed_spmv(sh, jnp.asarray(x), mesh, combine="bogus")
except ValueError:
    pass
else:
    raise AssertionError("bogus combine accepted")
print("OK")
""")


def test_sharded_train_step_matches_single_device():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import compat
from repro.configs.base import ModelConfig
from repro.models import Model, axis_rules, logical_to_sharding
from repro.models.sharding import sanitize_shardings
from repro.training import build_train_step, TrainState, OPTIMIZERS, warmup_cosine

cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=32,
                  num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=256,
                  attn_chunk=32, remat="none", dtype="float32")
model = Model(cfg)
opt = OPTIMIZERS["adamw"]()
lr = warmup_cosine(1e-3, 2, 100)
step = build_train_step(model, opt, lr)
params, axes = model.init(jax.random.PRNGKey(0))
state = TrainState.create(params, opt)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
batch = {"tokens": toks, "targets": toks}

# single-device result
s_plain, m_plain = jax.jit(step)(state, batch)

# sharded: data x model mesh
mesh = compat.make_mesh((2, 2), ("data", "model"))
with axis_rules(mesh):
    psh = sanitize_shardings(jax.eval_shape(lambda: params),
                             logical_to_sharding(axes, mesh), mesh)
    from repro.training.optimizer import AdamWState
    rep = NamedSharding(mesh, P())
    ssh = TrainState(step=rep, params=psh,
                     opt_state=AdamWState(mu=psh, nu=psh, count=rep),
                     ef_buffers=None)
    bsh = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    f = jax.jit(step, in_shardings=(ssh, bsh), out_shardings=(ssh, None))
    s_shard, m_shard = f(state, batch)

assert abs(float(m_plain["loss"]) - float(m_shard["loss"])) < 1e-4
for a, b in zip(jax.tree_util.tree_leaves(s_plain.params),
                jax.tree_util.tree_leaves(s_shard.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
print("OK")
""")


def test_compressed_cross_pod_sum():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.training.grad_compression import compressed_cross_pod_sum, init_ef_buffers

mesh = compat.make_mesh((2, 2), ("pod", "data"))
g_local = {"w": jnp.arange(8.0).reshape(2, 4) / 7.0}
ef = init_ef_buffers(g_local)

@partial(compat.shard_map, mesh=mesh, in_specs=(P(), P()),
         out_specs=(P(), P()), check_vma=False)
def run(g, e):
    s, ne = compressed_cross_pod_sum(g, e, axis_name="pod")
    return s, ne

summed, new_ef = run(g_local, ef)
# both pods contributed identical grads -> sum == 2x
np.testing.assert_allclose(np.asarray(summed["w"]), 2 * np.asarray(g_local["w"]),
                           rtol=0.02, atol=0.02)
print("OK")
""")


def test_pipeline_two_stages():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro import compat
from repro.runtime.pipeline import pipeline_forward

mesh = compat.make_mesh((2,), ("pod",))
# stage s applies ws[s]: y = x @ w
ws = jnp.stack([jnp.eye(8) * 2.0, jnp.eye(8) * 3.0])  # (S, 8, 8)

def stage_fn(w, h):
    return h @ w

run = pipeline_forward(stage_fn, mesh, axis="pod")
mbs = jnp.ones((4, 2, 8))   # 4 microbatches of (2, 8)
out = run(ws, mbs)
np.testing.assert_allclose(np.asarray(out), 6.0 * np.ones((4, 2, 8)), rtol=1e-5)
print("OK")
""", devices=2)
