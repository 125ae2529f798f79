"""CB104 positive: axis_types= outside compat.make_mesh."""
import jax


def build_mesh(axis_type):
    return jax.make_mesh((1,), ("x",), axis_types=(axis_type,))
