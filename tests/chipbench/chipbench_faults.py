"""Faults planted under the timed path, and an entry point that runs a tiny cell
with one of them: ``python chipbench_faults.py <workload> <fault>`` prints
the result line (the four-device cell needs its own process, with
``--xla_force_host_platform_device_count=4``; ``none`` plants nothing).

What a tiny run of operation ``<op>`` (a traffic file's ``op``) is checked
for is the file ``tests/chipbench/op_checks/<op>.py``. It exposes

* ``FAULTS``: fault name -> a function ``plant(patch)`` that plants the
  fault through ``patch(obj, name, new)``, which sets ``obj.name = new``
  until the planted block ends. Each operation lists every fault its cells
  can have: the answer altered where it is produced, half of the work left
  out and the rest doubled, a solver's state returned unchanged, the
  exchange between chips left out;
* ``TRACED``: the per-layer metrics a traced tiny run of the operation
  reports.

A new operation adds its file there; no table in the tests names it.
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
from chipbench_tiny import HERE, ROOT  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def halve(s):
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


def op_of(workload: str) -> str:
    """The operation of ``workload``: its traffic file's ``op``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    path = ROOT / "chipbench" / "traffic" / f"{cell['traffic']}.json"
    return json.loads(path.read_text())["op"]


def checks_path(op: str) -> pathlib.Path:
    return HERE / "op_checks" / f"{op}.py"


def load_checks(op: str):
    """Operation ``op``'s ``FAULTS`` and ``TRACED``, from its file."""
    from chipbench import harness

    return harness.load_module(checks_path(op))


def fault_names(op: str) -> list[str]:
    """The faults ``op`` can have; none where its file is missing, which
    ``test_every_op_has_checks`` reports."""
    return list(load_checks(op).FAULTS) if checks_path(op).is_file() else []


@contextlib.contextmanager
def planted(fault: str, op: str):
    """Patch the program under the harness for the length of the block."""
    plant = load_checks(op).FAULTS[fault]
    saved = []

    def patch(obj, name, new):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    try:
        plant(patch)
        jax.clear_caches()  # jitted callers must trace the patched code
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)
        jax.clear_caches()


def run(workload: str, fault: str | None, tmp) -> dict:
    from chipbench import harness

    root = chipbench_tiny.tiny_root(tmp)
    with planted(fault, op_of(workload)) if fault else contextlib.nullcontext():
        return harness.run_cell(workload, 2**31 + 11, 0.2, False, root=root,
                                require_tpu=False, cache=False)


if __name__ == "__main__":
    with tempfile.TemporaryDirectory() as tmp:
        fault = None if sys.argv[2] == "none" else sys.argv[2]
        print(json.dumps(run(sys.argv[1], fault, pathlib.Path(tmp))))
