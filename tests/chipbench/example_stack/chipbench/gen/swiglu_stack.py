"""A stack of block-pruned SwiGLU feed-forward layers, handed over as tiles.

Each layer has three projections, gate and up (``d_model`` x ``d_ff``)
and down (``d_ff`` x ``d_model``), cut into ``block`` x ``block`` tiles
of which a share ``keep`` is kept. The weights are drawn from
``weight_seed``: one fixed stack, whatever the run's seed. There is no
COO: the operation receives the :class:`Stack`, which carries its widths.
"""
import dataclasses

import numpy as np


@dataclasses.dataclass
class Stack:
    """Per projection (``gate``, ``up``, ``down``): ``tiles[name]`` is
    (layers, kept, block, block), tile ``t`` of layer ``l`` sitting at
    block row ``brow[name][l, t]`` and block column ``bcol[name][l, t]``
    of the (in, out) weight."""

    d_model: int
    d_ff: int
    block: int
    layers: int
    tiles: dict
    brow: dict
    bcol: dict

    def shape(self, name):
        return ((self.d_ff, self.d_model) if name == "down"
                else (self.d_model, self.d_ff))

    def describe(self):
        kept = sum(t.shape[1] for t in self.tiles.values())
        return (f"{self.layers} SwiGLU layers, {self.d_model}x{self.d_ff}, "
                f"block {self.block}, {kept} tiles a layer")

    def arrays(self):
        return tuple(a for d in (self.tiles, self.brow, self.bcol)
                     for _, a in sorted(d.items()))


def generate(params, seed):
    rng = np.random.default_rng(params["weight_seed"])
    d, f, B, L = (int(params[k]) for k in ("d_model", "d_ff", "block", "layers"))
    tiles, brow, bcol = {}, {}, {}
    for name, (n_in, n_out) in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d))):
        nb_in, nb_out = n_in // B, n_out // B
        kept = max(1, round(params["keep"] * nb_in * nb_out))
        picks = np.stack([np.sort(rng.choice(nb_in * nb_out, kept, replace=False))
                          for _ in range(L)])
        brow[name], bcol[name] = (picks // nb_out).astype(np.int32), (picks % nb_out).astype(np.int32)
        scale = 1.0 / np.sqrt(params["keep"] * n_in)
        tiles[name] = (rng.standard_normal((L, kept, B, B)) * scale).astype(np.float32)
    return Stack(d, f, B, L, tiles, brow, bcol)
