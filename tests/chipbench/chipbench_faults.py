"""Faults planted under the timed path, and an entry point that runs a tiny cell
with one of them: ``python chipbench_faults.py <workload> <fault>`` prints
the result line (the four-device cell needs its own process, with
``--xla_force_host_platform_device_count=4``).

* ``altered``: the answer is altered where it is produced;
* ``half``: half of the stream groups are left out and the rest doubled,
  as a mean taken over the rest would;
* ``unchanged``: the solver returns its state (x0) unchanged;
* ``no_exchange``: the reduce-scatter between chips is left out, each
  chip keeping its own partial sums.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import chipbench_tiny  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _halve(s):
    """Streams whose later half of groups (or blocks) is zeroed and whose
    earlier half is doubled, in every format."""
    def cut(a):
        if a.shape[0] < 2:
            return a * 0
        h = a.shape[0] // 2
        return jnp.concatenate([a[:h] * 2, a[h:] * 0])

    return dataclasses.replace(s, coo_vals=cut(s.coo_vals),
                               panel_vals=cut(s.panel_vals),
                               dense_tiles=cut(s.dense_tiles))


@contextlib.contextmanager
def planted(fault: str):
    """Patch the program under the harness for the length of the block."""
    import repro.solvers
    from repro.kernels import ops

    saved = []

    def patch(obj, name, new):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    spmv, cg = ops.cb_spmv, repro.solvers.cg
    if fault == "altered":
        patch(ops, "cb_spmv", lambda s, x, **kw: spmv(s, x, **kw).at[0].add(1.0))
        patch(repro.solvers, "cg", lambda *a, **kw: dataclasses.replace(
            cg(*a, **kw), x=cg(*a, **kw).x.at[0].add(1.0)))
    elif fault == "half":
        patch(ops, "cb_spmv", lambda s, x, **kw: spmv(_halve(s), x, **kw))
    elif fault == "unchanged":
        patch(repro.solvers, "cg", lambda A, b, *a, **kw: dataclasses.replace(
            cg(A, b, *a, **kw), x=jnp.zeros_like(b)))
    elif fault == "no_exchange":
        def local(y, axis, scatter_dimension=0, tiled=True):
            n = y.shape[0] // jax.lax.axis_size(axis)
            return jax.lax.dynamic_slice_in_dim(y, jax.lax.axis_index(axis) * n, n)
        patch(jax.lax, "psum_scatter", local)
    else:
        raise ValueError(fault)
    jax.clear_caches()  # jitted callers must trace the patched code
    try:
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)
        jax.clear_caches()


def run(workload: str, fault: str | None, tmp) -> dict:
    from chipbench import harness

    root = chipbench_tiny.tiny_root(tmp)
    with planted(fault) if fault else contextlib.nullcontext():
        return harness.run_cell(workload, 2**31 + 11, 0.2, False, root=root,
                                require_tpu=False, cache=False)


if __name__ == "__main__":
    with tempfile.TemporaryDirectory() as tmp:
        fault = None if sys.argv[2] == "none" else sys.argv[2]
        print(json.dumps(run(sys.argv[1], fault, pathlib.Path(tmp))))
