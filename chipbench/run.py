"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

(``python3 -m chipbench.run`` works too, from the root of the checkout.)
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import pathlib
import sys

_HERE = pathlib.Path(__file__).resolve().parent
_ROOT = _HERE.parent
# Run as a script, this directory would come first on the path and its
# modules (trace.py, ...) would shadow the standard library's.
sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve() != _HERE]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main())
