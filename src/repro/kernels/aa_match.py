"""Pallas TPU kernel: fused accumulating-automata string match (paper §3.1).

For every tuple i of a share-column the automaton of Table 3 computes

    out[i] = Π_{j<W} ( Σ_{α<A} col[i,j,α] · pat[j,α] )   (mod p)

i.e. W one-hot inner products chained by modular multiplication. The
sliding-window variant (suffix / substring predicates) runs a k-row pattern
tile at every offset m < M = W−k+1 of the word:

    out[i, m] = Π_{j<k} ( Σ_α col[i,m+j,α] · pat[j,α] )  (mod p)

and the exact match is its k = W, M = 1 case, so one kernel serves both.

All k·M inner products of a column tile are ONE contraction: the wrapper
places pattern row j at word position m+j of row j·M+m of a (k·M, W·A)
matrix, and the kernel contracts it against the (bn, W·A) column tile on the
MXU with the int8-limb algorithm of ``field.limb_contract``. The chain over
j is then k−1 elementwise mod-p products of (M, bn) slabs. Each column tile
is read once and only (M, bn) results are written.

Tiling: grid (B, n-tiles), tile axis innermost, so batch row b's pattern
matrix keeps the same block index while its tiles stream through and stays
resident in VMEM. VMEM at bn=512, W·A=828: column tile 1.7 MB (×2 buffers)
plus its int8 limbs 1.7 MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core.field import limb_contract, mulmod32
from .ss_matmul import ZERO, _round_up, interpret_default


def _dot_nt(x, y):
    """(R, K) · (bn, K)ᵀ -> (R, bn), int8 -> exact int32."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _slide_kernel(pat_ref, col_ref, o_ref, *, k: int, m: int):
    # one (b, i) grid cell: batch row b's placed pattern against its i-th
    # n-tile, all M windows at once
    v = limb_contract(pat_ref[0], col_ref[0], _dot_nt)     # (R, bn)
    acc = v[0:m]
    for j in range(1, k):                                  # N_{j+1} = N_j·v_j
        acc = mulmod32(acc, v[j * m:(j + 1) * m])
    o_ref[0] = acc


def _place(pat: jax.Array, w: int) -> jax.Array:
    """(B, k, A) pattern tiles -> (B, R, W·A): row j·M+m holds pattern row j
    at word position m+j, zeros elsewhere; R pads k·M to the int8 tile."""
    b, k, a = pat.shape
    m = w - k + 1
    pos = np.arange(w)
    at = (pos[None, None, :] ==
          np.arange(m)[None, :, None] + np.arange(k)[:, None, None])
    placed = jnp.where(jnp.asarray(at)[None, :, :, :, None],
                       pat[:, :, None, None, :], jnp.uint32(0))
    placed = placed.reshape(b, k * m, w * a)
    rows = _round_up(k * m, 32)
    return jnp.pad(placed, ((0, 0), (0, rows - k * m), (0, 0)))


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def aa_slide_batch_pallas(col: jax.Array, pat: jax.Array, *, bn: int = 512,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Stacked sliding-window AA match as a 2-D grid kernel.

    col: (B, n, W, A) uint32 shares; pat: (B, k, A) pattern tiles.
    Returns (B, n, M) raw window-chain products, M = W−k+1. The suffix
    terminator factor and the CONTAINS window count are linear
    post-processing outside the kernel, so one launch serves a whole
    suffix+substring group of the same k. ``interpret=None`` lets the
    platform decide.
    """
    b, n, w, a = col.shape
    k = pat.shape[-2]
    assert pat.shape == (b, k, a), (pat.shape, (b, k, a))
    assert 1 <= k <= w, (k, w)
    m = w - k + 1
    bn = min(bn, _round_up(max(n, 1), 128))
    n_pad = _round_up(max(n, 1), bn)
    col_p = jnp.pad(col.reshape(b, n, w * a), ((0, 0), (0, n_pad - n), (0, 0)))
    placed = _place(pat, w)
    out = pl.pallas_call(
        functools.partial(_slide_kernel, k=k, m=m),
        grid=(b, n_pad // bn),
        in_specs=[
            pl.BlockSpec((1,) + placed.shape[1:],
                         lambda bi, i: (bi, ZERO, ZERO)),
            pl.BlockSpec((1, bn, w * a), lambda bi, i: (bi, i, ZERO)),
        ],
        out_specs=pl.BlockSpec((1, m, bn), lambda bi, i: (bi, ZERO, i)),
        out_shape=jax.ShapeDtypeStruct((b, m, n_pad), jnp.uint32),
        interpret=interpret_default(interpret),
    )(placed, col_p)
    return jnp.swapaxes(out[:, :, :n], 1, 2)


def aa_match_batch_pallas(col: jax.Array, pat: jax.Array, *, bn: int = 512,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Stacked-predicate AA match: col (B, n, W, A), pat (B, W, A) -> (B, n).
    The exact match is the one-window (k = W) sliding match."""
    return aa_slide_batch_pallas(col, pat, bn=bn, interpret=interpret)[..., 0]
