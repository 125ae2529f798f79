"""The lower-precision control of a cell, read on the chip at the cell's size.

    python3 chipbench/control.py --workload <name> --seeds 11,12,13

For each seed: generate the cell's matrix and input pool as a run would,
compute every input's answer with the operation's ``control`` (the
reference in bfloat16, on the default device) and print the numbers
``check`` compares, beside the cell's limits. The control has to read over
a limit on every seed; the smallest reading is the limit's upper end.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != _HERE]
for _p in (str(_HERE.parent / "src"), str(_HERE.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402


def readings(workload: str, seed: int, root=harness.ROOT) -> dict:
    spec = harness.load_cell(root, workload)
    gen, op = harness.load_module(spec["gen"]), harness.load_module(spec["op"])
    cfg, traffic = spec["config"], spec["traffic"]
    rows, cols, vals, shape = gen.generate(cfg, seed)
    data = harness.Data(rows, cols, vals, shape, np.dtype(cfg["dtype"]))
    worst = {}
    for inp in op.inputs(data, traffic, np.random.default_rng([seed, 1])):
        got = op.check(op.control(data, traffic, inp),
                       op.reference(data, traffic, inp))
        for k, v in got.items():
            worst[k] = min(worst.get(k, v), v)
    return {"seed": seed, "control": worst, "limits": spec["limits"],
            "fails": any(worst[k] > lim for k, lim in spec["limits"].items())}


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    d = jax.devices()[0]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = readings(args.workload, seed)
        r.update(workload=args.workload, seconds=time.perf_counter() - t0,
                 device={"platform": d.platform, "kind": d.device_kind})
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
