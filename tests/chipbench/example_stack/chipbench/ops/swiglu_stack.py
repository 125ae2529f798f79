"""Tokens through a stack of block-pruned SwiGLU layers, in plain jax.numpy.

x <- x + down(silu(gate(x)) * up(x)) for each layer; each projection's
kept tiles are placed into its dense weight inside the timed call. The
operation reads the stack's widths from the data it is handed.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common

NAMES = ("gate", "up", "down")


def dense(xp, tiles, brow, bcol, shape):
    """The (in, out) weight whose ``block`` tiles sit at (brow, bcol)."""
    B = tiles.shape[-1]
    W = xp.zeros((shape[0] // B, shape[1] // B, B, B), tiles.dtype)
    if xp is np:
        W[brow, bcol] = tiles
    else:
        W = W.at[brow, bcol].set(tiles)
    return W.transpose(0, 2, 1, 3).reshape(shape)


def apply(stack, weights, x, xp, matmul, silu):
    for layer in range(stack.layers):
        W = {n: dense(xp, weights[n][layer], stack.brow[n][layer],
                      stack.bcol[n][layer], stack.shape(n)) for n in NAMES}
        g, u = matmul(x, W["gate"]), matmul(x, W["up"])
        x = x + matmul(silu(g) * u, W["down"])
    return x


def setup(data, traffic, devices, clock):
    dev = devices[0]
    with clock("pack"):
        weights = jax.block_until_ready(jax.device_put(data.tiles, dev))

    def step(weights, x):
        return apply(data, weights, x, jnp, lambda a, b: jnp.matmul(
            a, b, precision=jax.lax.Precision.HIGHEST), jax.nn.silu)

    return common.Setup(
        step=jax.jit(step), args=(weights,),
        put=lambda x: jax.device_put(jnp.asarray(x), dev),
        output=lambda y: y, grid_steps=None, kernels=set())


def inputs(data, traffic, rng):
    return [rng.standard_normal((int(traffic["tokens"]), data.d_model))
            .astype(np.float32) for _ in range(traffic["inputs"])]


def reference(data, traffic, inp):
    w = {n: t.astype(np.float64) for n, t in data.tiles.items()}
    return apply(data, w, inp.astype(np.float64), np, np.matmul,
                 lambda g: g / (1.0 + np.exp(-g)))


def control(data, traffic, inp):
    """bfloat16 weights and activations into every product, float32 sums."""
    def matmul(a, b):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    w = {n: jnp.asarray(t) for n, t in data.tiles.items()}
    x = apply(data, w, jnp.asarray(inp), jnp, matmul, jax.nn.silu)
    return np.asarray(x, np.float64)


def check(out, ref):
    return {"out_err": common.rel_max_err(out, ref)}


def floor(data, traffic):
    T = int(traffic["tokens"])
    weights = sum(t.size for t in data.tiles.values())
    return 4.0 * (weights + 2 * T * data.d_model), 2.0 * T * weights
