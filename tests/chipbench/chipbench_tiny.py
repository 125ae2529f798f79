"""A copy of the benchmark at tiny sizes, for running cells on the CPU.

``tiny_root(tmp)`` copies ``chipbench/`` and ``BENCHMARK.json`` and cuts
every configuration to a few hundred rows, or a stack of layers to narrow
widths, so every cell runs end to end here in a few seconds with the
Pallas kernels interpreted.

The tiny sizes of configuration ``<config>`` are the file
``tests/chipbench/tiny/<config>.json``: a JSON object of the keys that
replace the configuration file's own (``{"scale": 8}``). A new
configuration adds its file there; no table in the tests names it.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def tiny_path(config: str) -> pathlib.Path:
    """The file of configuration ``config``'s tiny sizes."""
    return HERE / "tiny" / f"{config}.json"


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A benchmark root under ``tmp`` whose configurations are tiny."""
    root = pathlib.Path(tmp) / "bench"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        sizes = tiny_path(c["name"])
        if not sizes.is_file():
            # Its cells then fail on the missing file, by its path, and
            # never run at full size; the other cells run.
            c["file"] = str(sizes)
            continue
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(json.loads(sizes.read_text()))
        path.write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
