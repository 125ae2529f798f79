"""Faults and traced metrics of the ``cg`` operation (``chipbench/ops/cg.py``)."""
import dataclasses

import jax.numpy as jnp

from chipbench_faults import load_checks

spmv = load_checks("spmv")
TRACED = spmv.TRACED


def unchanged(patch):
    """The solver returns its state (x0 = 0) unchanged."""
    import repro.solvers

    cg = repro.solvers.cg
    patch(repro.solvers, "cg", lambda A, b, *a, **kw: dataclasses.replace(
        cg(A, b, *a, **kw), x=jnp.zeros_like(b)))


def altered(patch):
    """The solution altered where the solver produces it."""
    import repro.solvers

    cg = repro.solvers.cg
    patch(repro.solvers, "cg", lambda *a, **kw: dataclasses.replace(
        cg(*a, **kw), x=cg(*a, **kw).x.at[0].add(1.0)))


# ``half`` leaves out half of the operator's stream groups.
FAULTS = {"unchanged": unchanged, "altered": altered, "half": spmv.half}
