"""Preconditioners extracted from the CB block structure (plan time).

The CB format already materializes the diagonal sub-blocks as tiles —
block-Jacobi preconditioning is therefore free structure reuse: walk the
blocks once at plan time, gather every entry whose *global* column lands
inside its own block-row's diagonal window, and invert the resulting
(B, B) diagonal blocks with numpy. The apply path is a single batched
(mb, B, B) x (mb, B) contraction — one fused einsum per iteration, no
gather/scatter, jit-native.

Rows whose diagonal block row is entirely zero get an identity row so the
block stays invertible (any nonsingular M is a valid preconditioner; for
those rows M acts as the identity).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.cb_matrix import CBMatrix


@dataclasses.dataclass
class IdentityPreconditioner:
    """M = I — the no-preconditioning baseline (still a pytree)."""

    def apply(self, r: jax.Array) -> jax.Array:
        return r


@dataclasses.dataclass
class JacobiPreconditioner:
    """M^-1 = diag(A)^-1 (point Jacobi)."""

    inv_diag: jax.Array  # (m,)

    def apply(self, r: jax.Array) -> jax.Array:
        return self.inv_diag * r


@dataclasses.dataclass
class BlockJacobiPreconditioner:
    """M^-1 = blockdiag(A)^-1 at the CB block size."""

    # -- static ----------------------------------------------------------
    m: int
    block_size: int
    # -- data -------------------------------------------------------------
    inv_blocks: jax.Array  # (mb, B, B)

    def apply(self, r: jax.Array) -> jax.Array:
        B = self.block_size
        mb = self.inv_blocks.shape[0]
        rp = jnp.pad(r, (0, mb * B - r.shape[0])).reshape(mb, B)
        y = jnp.einsum(
            "brc,bc->br", self.inv_blocks.astype(rp.dtype), rp
        )
        return y.reshape(-1)[: self.m]


jax.tree_util.register_dataclass(
    IdentityPreconditioner, data_fields=[], meta_fields=[]
)
jax.tree_util.register_dataclass(
    JacobiPreconditioner, data_fields=["inv_diag"], meta_fields=[]
)
jax.tree_util.register_dataclass(
    BlockJacobiPreconditioner,
    data_fields=["inv_blocks"],
    meta_fields=["m", "block_size"],
)


def _diag_blocks(cb: CBMatrix) -> np.ndarray:
    """Accumulate the (mb, B, B) block-diagonal of A from the CB blocks.

    Works in *global* column coordinates (via ``global_x_index``) so the
    extraction is correct whether or not column aggregation moved the
    diagonal entries into different compacted block columns.
    """
    B = cb.block_size
    m = cb.shape[0]
    mb = -(-m // B)
    D = np.zeros((mb, B, B), np.float64)
    for brow, bcol, _fmt, r, c, v in cb.iter_blocks():
        gc = cb.global_x_index(brow, bcol, c)
        lo = brow * B
        sel = (gc >= lo) & (gc < lo + B)
        if not np.any(sel):
            continue
        np.add.at(
            D,
            (np.full(int(sel.sum()), brow), r[sel], (gc[sel] - lo)),
            v[sel].astype(np.float64),
        )
    return D


def _jacobi_from_diag(D: np.ndarray, m: int) -> JacobiPreconditioner:
    diag = np.einsum("bii->bi", D).reshape(-1)[:m]
    inv = np.where(diag != 0.0, 1.0 / np.where(diag != 0.0, diag, 1.0), 1.0)
    return JacobiPreconditioner(inv_diag=jnp.asarray(inv, jnp.float32))


def _block_jacobi_from_diag(
    D: np.ndarray, m: int, block_size: int
) -> BlockJacobiPreconditioner:
    # Identity rows where the block row is entirely zero (incl. the ragged
    # padding rows of the last block) keep every block invertible.
    D = D.copy()
    dead = ~np.any(D != 0.0, axis=2)  # (mb, B)
    bidx, ridx = np.nonzero(dead)
    D[bidx, ridx, ridx] = 1.0
    try:
        inv = np.linalg.inv(D)
    except np.linalg.LinAlgError:
        inv = np.stack([np.linalg.pinv(blk) for blk in D])
    return BlockJacobiPreconditioner(
        m=m, block_size=block_size, inv_blocks=jnp.asarray(inv, jnp.float32)
    )


def jacobi(cb: CBMatrix) -> JacobiPreconditioner:
    """Point-Jacobi from the CB diagonal (zero diagonals act as identity)."""
    return _jacobi_from_diag(_diag_blocks(cb), cb.shape[0])


def block_jacobi(cb: CBMatrix) -> BlockJacobiPreconditioner:
    """Block-Jacobi from the materialized CB diagonal tiles."""
    with obs.span("cb.block_jacobi"):
        return _block_jacobi_from_diag(_diag_blocks(cb), cb.shape[0],
                                       cb.block_size)


# ---------------------------------------------------------------------------
# Dynamic-sparsity path: re-invert only the diagonal payloads.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class DiagScatter:
    """Pattern-derived map: canonical values -> (mb, B, B) block diagonal.

    Which canonical elements land in the block diagonal — and where — is
    pure structure, so it is recorded once (``diag_scatter``) and a value
    update only scatters fresh payloads and re-inverts: no CB block walk
    re-runs. ``jacobi``/``block_jacobi`` on the updated values are
    bit-identical to rebuilding the preconditioner from
    ``cb.update_values(vals)``.
    """

    m: int
    block_size: int
    mb: int
    val_dtype: np.dtype
    flat_idx: np.ndarray   # (k,) int64 — flat index into (mb, B, B)
    src: np.ndarray        # (k,) int64 — canonical value index

    def _diag(self, canonical_vals) -> np.ndarray:
        B = self.block_size
        vals = np.ascontiguousarray(canonical_vals, self.val_dtype)
        D = np.zeros((self.mb, B, B), np.float64)
        D.reshape(-1)[self.flat_idx] = vals[self.src].astype(np.float64)
        return D

    def jacobi(self, canonical_vals) -> JacobiPreconditioner:
        """Point-Jacobi for fresh canonical values (structure reused)."""
        return _jacobi_from_diag(self._diag(canonical_vals), self.m)

    def block_jacobi(self, canonical_vals) -> BlockJacobiPreconditioner:
        """Block-Jacobi for fresh canonical values (re-inversion only)."""
        return _block_jacobi_from_diag(self._diag(canonical_vals), self.m,
                                       self.block_size)


def diag_scatter(cb: CBMatrix) -> DiagScatter:
    """Record once which canonical elements feed the block diagonal.

    Derived straight from the value layout's global (row, col) keys —
    coordinates are unique after CB canonicalization, so the scatter is
    a plain assignment (no accumulation), matching ``_diag_blocks``'s
    ``np.add.at`` over unique positions exactly.
    """
    layout = cb.value_layout()
    B = cb.block_size
    m, n = cb.shape
    mb = -(-m // B)
    r_g = layout.keys // n
    c_g = layout.keys % n
    brow = r_g // B
    lo = brow * B
    sel = (c_g >= lo) & (c_g < lo + B)
    src = np.flatnonzero(sel)
    flat = ((brow[sel] * B + (r_g[sel] - lo[sel])) * B + (c_g[sel] - lo[sel]))
    return DiagScatter(
        m=m, block_size=B, mb=mb, val_dtype=np.dtype(cb.val_dtype),
        flat_idx=flat.astype(np.int64), src=src.astype(np.int64),
    )
