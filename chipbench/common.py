"""Helpers the operation modules share: kernel names, errors, device puts."""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import numpy as np

# The Pallas kernel each stream format dispatches (named in the kernels'
# pallas_call), as chip_smoke.py checks them.
KERNEL_OF_FORMAT = {
    "dense": "cb_block_dense_spmv_batched",
    "panel": "cb_colagg_panel_spmv_batched",
    "coo": "cb_coo_spmv_batched",
}


def tpu_kernel_names(hlo_text: str) -> set[str]:
    """Names of the Mosaic kernels (``tpu_custom_call``) in compiled HLO."""
    return set(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo_text))


def kernels_of(steps: dict) -> set[str]:
    """The SpMV kernels dispatched for these per-format grid steps."""
    return {KERNEL_OF_FORMAT[f] for f, n in steps.items() if n}


def rel_max_err(y, y_ref: np.ndarray) -> float:
    """max |y - y_ref| / max |y_ref|, in float64; NaN anywhere reads inf."""
    y = np.asarray(y, np.float64)
    if y.shape != y_ref.shape or not np.all(np.isfinite(y)):
        return float("inf")
    return float(np.abs(y - y_ref).max() / np.abs(y_ref).max())


@dataclasses.dataclass
class Setup:
    """What an operation's set-up hands the harness.

    ``step(*args, inp)`` is the jitted timed call; ``put`` places one
    input of the pool where ``step`` expects it; ``output`` picks the
    array that is compared from what ``step`` returned.
    """

    step: Callable
    args: tuple
    put: Callable[[np.ndarray], Any]
    output: Callable[[Any], Any]
    grid_steps: int
    kernels: set[str]
