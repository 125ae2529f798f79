"""The ``spmv`` operation under a name of its own."""
from chipbench.ops.spmv import (check, control, floor, inputs,  # noqa: F401
                                reference, setup)
