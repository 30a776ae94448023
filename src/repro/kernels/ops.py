"""Public jit'd wrappers for the Pallas kernels.

Handle cloud-axis batching, and the join-oriented composite
``match_matrix``. Every kernel picks compiled Mosaic or the Pallas
interpreter from the platform (``ss_matmul.interpret_default``); a kernel
that fails to lower raises.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .aa_match import aa_match_batch_pallas, aa_slide_batch_pallas
from .ripple import ripple_carry_pallas, ripple_segment_pallas
from .ss_matmul import (is_tall_skinny, share_onehot_pallas, ss_matmul_pallas,
                        ss_matmul_tall_pallas)


@jax.jit
def ss_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched mod-p matmul. a: ([c,] M, K), b: ([c,] K, N) uint32.

    Tall-skinny operands (small M = tokens, huge K = vocab — the embedding
    contraction) route to the shape-tuned tiling; everything else takes the
    square 128³ tiles. Both are the same kernel body, so results are
    bit-identical either way.
    """
    def fn(x, y):
        if is_tall_skinny(x.shape[0], x.shape[1], y.shape[1]):
            return ss_matmul_tall_pallas(x, y)
        return ss_matmul_pallas(x, y)

    if a.ndim == 2 and b.ndim == 2:
        return fn(a, b)
    if a.ndim == 3 and b.ndim == 3:
        return jax.vmap(fn)(a, b)
    if a.ndim == 3 and b.ndim == 2:
        return jax.vmap(lambda x: fn(x, b))(a)
    raise ValueError(f"unsupported ranks: {a.shape} @ {b.shape}")


def share_onehot(tokens: jax.Array, a1: jax.Array, *,
                 n_shares: int) -> jax.Array:
    """Fused degree-1 one-hot share generation (embedding fast path):
    tokens (M,) int32 + per-token coefficients a1 (M, V) uint32 ->
    share tensor (n_shares, M, V), never materializing the one-hot."""
    return share_onehot_pallas(tokens, a1, n_shares=n_shares)


@jax.jit
def aa_match(col: jax.Array, pat: jax.Array) -> jax.Array:
    """Batched AA match. col: ([c,] n, W, A), pat: ([c,] W, A) -> ([c,] n).
    The cloud axis rides the kernel's batch grid axis."""
    if col.ndim == 3:
        return aa_match_batch_pallas(col[None], pat[None])[0]
    if col.ndim == 4:
        return aa_match_batch_pallas(col, pat)
    raise ValueError(f"unsupported rank: {col.shape}")


@jax.jit
def _aa_match_batch_grid(col: jax.Array, pat: jax.Array) -> jax.Array:
    c, b, n, w, a = col.shape
    out = aa_match_batch_pallas(col.reshape(c * b, n, w, a),
                                pat.reshape(c * b, w, a))
    return out.reshape(c, b, n)


def aa_match_batch(col: jax.Array, pat: jax.Array) -> jax.Array:
    """Stacked-predicate AA match: col (c, B, n, W, A), pat (c, B, W, A)
    -> (c, B, n). The cloud and batch axes fold into ONE 2-D grid
    ``pallas_call`` — a (c·B, n-tile) grid whose pattern tile stays
    resident in VMEM across a row's n-tiles — so the batched query engine
    really issues a single device dispatch per protocol round."""
    if col.ndim != 5:
        raise ValueError(f"unsupported rank: {col.shape}")
    c, b, _, w, a = col.shape
    if pat.shape != (c, b, w, a):
        raise ValueError(f"pattern shape {pat.shape} does not match "
                         f"column stack {col.shape}")
    return _aa_match_batch_grid(col, pat)


@jax.jit
def _aa_slide_batch_grid(cols: jax.Array, pats: jax.Array) -> jax.Array:
    c, b, n, w, a = cols.shape
    k = pats.shape[-2]
    out = aa_slide_batch_pallas(cols.reshape(c * b, n, w, a),
                                pats.reshape(c * b, k, a))
    return out.reshape(c, b, n, w - k + 1)


def aa_slide_batch(cols: jax.Array, pats: jax.Array) -> jax.Array:
    """Stacked sliding-window AA match: cols (c, B, n, W, A), pats
    (c, B, k, A) -> (c, B, n, M) raw window-chain products, M = W−k+1.
    Cloud and batch axes fold into one (c·B, n-tile) 2-D grid
    ``pallas_call``, the same kernel as ``aa_match_batch``."""
    if cols.ndim != 5 or pats.ndim != 4:
        raise ValueError(f"unsupported ranks: {cols.shape}, {pats.shape}")
    c, b, _, w, a = cols.shape
    k = pats.shape[-2]
    if (pats.shape[0], pats.shape[1], pats.shape[3]) != (c, b, a) \
            or not 1 <= k <= w:
        raise ValueError(f"pattern tile shape {pats.shape} does not match "
                         f"column stack {cols.shape}")
    return _aa_slide_batch_grid(cols, pats)


def ripple_carry(a: jax.Array, b: jax.Array, carry=None):
    """One fused SS-SUB bit step (Alg 6) over any share-plane shape.

    a, b: (...,) uint32 bit planes; carry: same shape or ``None`` for the
    LSB step. Returns ``(rb, carry')``. Flattens to one 1-D elementwise
    pallas dispatch regardless of how many queries are stacked."""
    shape = a.shape
    flat_a = a.reshape(-1)
    flat_b = b.reshape(-1)
    init = carry is None
    flat_c = (jnp.zeros_like(flat_a) if init
              else carry.reshape(-1))
    rb, co = ripple_carry_pallas(flat_a, flat_b, flat_c, init=init)
    return rb.reshape(shape), co.reshape(shape)


def ripple_segment(a: jax.Array, b: jax.Array, carry=None):
    """k chained SS-SUB bit steps (Alg 6) in ONE pallas dispatch.

    a, b: (..., k) uint32 bit planes (last axis = consecutive bit
    positions); carry: (...) or ``None`` when the chain starts at the LSB.
    Returns the final ``(rb, carry')`` after k steps, each shaped (...).
    The carry chains in registers inside the kernel, so a degree-reduction
    interval of k bits costs one launch instead of k."""
    shape = a.shape[:-1]
    k = a.shape[-1]
    flat_a = jnp.moveaxis(a.reshape(-1, k), -1, 0)     # (k, N)
    flat_b = jnp.moveaxis(b.reshape(-1, k), -1, 0)
    init = carry is None
    flat_c = (jnp.zeros(flat_a.shape[1:], flat_a.dtype) if init
              else carry.reshape(-1))
    rb, co = ripple_segment_pallas(flat_a, flat_b, flat_c, init=init)
    return rb.reshape(shape), co.reshape(shape)


@jax.jit
def match_matrix(col_x: jax.Array, col_y: jax.Array) -> jax.Array:
    """All-pairs word match (join §3.3.1 hotspot) via per-position ss_matmul.

    col_x: (c, nx, W, A), col_y: (c, ny, W, A) -> (c, nx, ny).
    """
    from ..core import field  # local import to avoid cycle
    c, nx, w, a = col_x.shape
    ny = col_y.shape[1]
    acc = None
    for j in range(w):
        pj = ss_matmul(col_x[:, :, j, :],
                       jnp.swapaxes(col_y[:, :, j, :], -1, -2))
        acc = pj if acc is None else field.mul(acc, pj)
    return acc


@jax.jit
def match_matrix_batch(col_x: jax.Array, col_y: jax.Array) -> jax.Array:
    """Stacked all-pairs match for a join group: col_x (c, B, nx, W, A),
    col_y (c, B, ny, W, A) -> (c, B, nx, ny). One vmapped composite over
    the group's B column pairs (each inner hop is the ss_matmul kernel), so
    equal-size right relations ride one dispatch like ``aa_match_batch``
    does for predicates."""
    if col_x.ndim != 5 or col_y.ndim != 5:
        raise ValueError(f"unsupported ranks: {col_x.shape}, {col_y.shape}")
    return jax.vmap(match_matrix, in_axes=1, out_axes=1)(col_x, col_y)


def as_backend():
    """Bundle these kernels as the ``"pallas"`` entry of the backend
    registry (``repro.api.backends``) — the query suite selects them with
    ``backend="pallas"``."""
    from ..api.backends import Backend  # local import to avoid cycle
    return Backend(name="pallas", aa_match=aa_match, ss_matmul=ss_matmul,
                   match_matrix=match_matrix, aa_match_batch=aa_match_batch,
                   ripple_carry=ripple_carry,
                   ripple_segment=ripple_segment,
                   match_matrix_batch=match_matrix_batch,
                   aa_slide_batch=aa_slide_batch,
                   share_onehot=share_onehot)
