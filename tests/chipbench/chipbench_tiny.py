"""A copy of the benchmark at tiny sizes, for running cells on the CPU.

``tiny_root(tmp)`` copies ``chipbench/`` and a ``BENCHMARK.json`` whose
configurations are cut to a few hundred rows, so every cell runs end to
end here in a few seconds with the Pallas kernels interpreted.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {"g500-s19": {"scale": 8}, "hpcg-64": {"nx": 6, "ny": 6, "nz": 6}}


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark root under ``tmp`` whose configurations are tiny."""
    root = pathlib.Path(tmp) / "bench"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(TINY[c["name"]])
        path.write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
