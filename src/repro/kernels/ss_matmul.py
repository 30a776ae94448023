"""Pallas TPU kernel: mod-p matmul over F_p, p = 2³¹−1 (Mersenne-31).

The MXU cannot form 62-bit integer products, but it multiplies int8 exactly
into int32. So each 31-bit operand splits into four signed 8-bit limbs
(``field.digits8``) and a tile product becomes 16 int8 MXU dots, folded back
into [0, p) with Mersenne rotates (2³¹ ≡ 1) — ``field.limb_contract``, the
same algorithm ``field.matmul`` runs over whole arrays. (bm × bk) · (bk × bn)
blocks tile into VMEM with an explicit BlockSpec grid, accumulating mod p in
a VMEM scratch across the K grid axis (K is the innermost grid dimension, so
the scratch carries). A tile's int32 sums stay exact while bk ≤ 2¹⁶.

VMEM per grid cell at the defaults bm = bn = bk = 128: a/b tiles 64 KiB each,
their limbs 64 KiB, scratch + out 128 KiB — far below the scoped limit,
leaving room for double-buffered pipelining of the next a/b tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.field import LIMB_K_MAX, addmod32, limb_contract, mulmod32

#: int32 block index for ``BlockSpec`` index maps: a bare ``0`` becomes an
#: int64 under the process-wide x64 flag, which Mosaic cannot return.
ZERO = np.int32(0)


def interpret_default(interpret: Optional[bool]) -> bool:
    """``None`` -> the platform decides: compiled Mosaic on a TPU, the Pallas
    interpreter everywhere else (CPU/GPU have no Mosaic lowering)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _dot(x, y):
    return jnp.dot(x, y, preferred_element_type=jnp.int32)


def _ss_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """One (i, j, k) grid cell: acc += A[i,k] ·ₚ B[k,j]; emit at last k."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = addmod32(acc_ref[...],
                            limb_contract(a_ref[...], b_ref[...], _dot))

    @pl.when(pl.program_id(2) == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ss_matmul_pallas(a: jax.Array, b: jax.Array, *, bm: int = 128,
                     bn: int = 128, bk: int = 128,
                     interpret: Optional[bool] = None) -> jax.Array:
    """(M,K) @ (K,N) mod p. Pads to block multiples (zeros are absorbing).
    ``interpret=None`` lets the platform decide (:func:`interpret_default`).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert bk <= LIMB_K_MAX, bk
    if m == 0 or n == 0:        # empty fetch stack / empty relation slice
        return jnp.zeros((m, n), jnp.uint32)
    # int8 limb tiles are (32, 128)-native on the MXU path
    bm = min(bm, _round_up(max(m, 1), 32))
    bn = min(bn, _round_up(max(n, 1), 128))
    bk = min(bk, _round_up(max(k, 1), 128))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_ss_matmul_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.uint32)],
        interpret=interpret_default(interpret),
    )(a_p, b_p)
    return out[:m, :n]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# embedding fast path: tall-skinny contraction + fused share generation
# ---------------------------------------------------------------------------

#: heuristic gate for the tall-skinny tiling: M (tokens) is small enough to
#: keep resident as one block, K (vocab) dwarfs both other dims.
TALL_MAX_M = 256
TALL_MIN_K = 1024


def is_tall_skinny(m: int, k: int, n: int) -> bool:
    """Does (M,K)@(K,N) look like an embedding lookup? Small M = tokens,
    huge K = vocab, lane-sized N = model dim."""
    return m <= TALL_MAX_M and k >= TALL_MIN_K and k >= 8 * max(m, n)


def ss_matmul_tall_pallas(a: jax.Array, b: jax.Array, *,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Shape-tuned ``ss_matmul_pallas`` for the embedding contraction.

    The one-hot stack is tall-skinny: M = batch×seq tokens (tens to a few
    hundred rows), K = vocab (tens of thousands), N = D (≈128-lane model
    dim). The default square 128³ tiling walks K in 128-element steps —
    hundreds of grid cells whose (bm, bn) scratch round-trips dominate.
    Here the whole token block stays resident (bm covers M up to 256 rows)
    and K streams in 512-wide tiles, 4× fewer grid steps along the one
    huge axis; VMEM is still tiny (256·512·4 B = 512 KiB a-tile).
    """
    m, k = a.shape
    n = b.shape[1]
    bm = min(_round_up(max(m, 1), 32), TALL_MAX_M)
    bn = min(_round_up(max(n, 1), 128), 128)
    bk = min(_round_up(max(k, 1), 128), 512)
    return ss_matmul_pallas(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


def _share_onehot_kernel(tok_ref, a1_ref, o_ref, *, bm: int, bv: int):
    """One (cloud k, token tile i, vocab tile j) grid cell of the fused
    share generator: o[k, i, j] = onehot(tok_i)[j] + a1[i, j] · x_k mod p.

    The plaintext one-hot is never materialized in HBM — it exists only as
    an iota==token compare inside the kernel, fused with the degree-1
    polynomial evaluation at x_k = k+1.
    """
    kc = pl.program_id(0)
    j = pl.program_id(2)
    tok = tok_ref[...]                              # (bm, 1) int32
    a1 = a1_ref[...]                                # (bm, bv) uint32 < p
    v_ids = (jax.lax.broadcasted_iota(jnp.int32, (bm, bv), 1)
             + j * np.int32(bv))
    onehot = jnp.where(v_ids == tok, np.uint32(1), np.uint32(0))
    xk = (kc + 1).astype(jnp.uint32)                # eval point, < c+1 ≪ p
    o_ref[...] = addmod32(onehot, mulmod32(a1, xk))[None]


@functools.partial(jax.jit,
                   static_argnames=("n_shares", "bm", "bv", "interpret"))
def share_onehot_pallas(tokens: jax.Array, a1: jax.Array, *, n_shares: int,
                        bm: int = 64, bv: int = 512,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Fused degree-1 one-hot share generation.

    tokens: (M,) int32 token ids; a1: (M, V) uint32 per-token random
    coefficients (``core.queries.embed.token_coeffs``). Returns
    uint32 (n_shares, M, V) with share[k, i, v] = [v == tok_i] + a1[i,v]·x_k
    — bit-identical to the jnp reference program given the same a1.

    Padding: token rows pad with -1 (matches no vocab id ⇒ zero one-hot),
    coefficients pad with 0 ⇒ padded share cells are 0 and slice away.
    """
    (m,) = tokens.shape
    m2, v = a1.shape
    assert m == m2, (tokens.shape, a1.shape)
    bm = min(bm, _round_up(max(m, 1), 8))
    bv = min(bv, _round_up(max(v, 1), 128))
    mp, vp = _round_up(m, bm), _round_up(v, bv)
    tok_p = jnp.pad(tokens.astype(jnp.int32), (0, mp - m),
                    constant_values=-1).reshape(mp, 1)
    a1_p = jnp.pad(a1, ((0, mp - m), (0, vp - v)))
    out = pl.pallas_call(
        functools.partial(_share_onehot_kernel, bm=bm, bv=bv),
        grid=(n_shares, mp // bm, vp // bv),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda kc, i, j: (i, ZERO)),
            pl.BlockSpec((bm, bv), lambda kc, i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bv), lambda kc, i, j: (kc, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_shares, mp, vp), jnp.uint32),
        interpret=interpret_default(interpret),
    )(tok_p, a1_p)
    return out[:, :m, :v]
