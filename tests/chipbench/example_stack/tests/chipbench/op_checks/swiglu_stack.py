"""Faults and traced metrics of the ``swiglu_stack`` operation."""

TRACED = {"setup.pack_s", "setup.compile_s"}


def no_activation(patch):
    """SiLU left out of every layer."""
    import jax

    patch(jax.nn, "silu", lambda g: g)


def scaled(patch):
    """Every product of the stack 0.1% too large."""
    import jax.numpy as jnp

    matmul = jnp.matmul
    patch(jnp, "matmul", lambda a, b, **kw: matmul(a, b, **kw) * 1.001)


FAULTS = {"no_activation": no_activation, "scaled": scaled}
