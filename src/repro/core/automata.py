"""Accumulating Automata (AA) string matching on secret-shares (paper §3.1).

The automaton of Table 3 matches a length-``x`` pattern against a word by
chaining per-position one-hot inner products:

    v_j      = Σ_α SS[j, α] · p'[j, α]          (share-space, degree 2t)
    N_{j+1}  = N_j · v_j                         (degree accumulates)

``N_{x+1}`` is a share of 1 iff the word equals the pattern. Because padded
positions hold the terminator one-hot, equality is exact-word (the paper's
"John " fix). Everything here is per-cloud local — no cross-share traffic.

Two implementations, selected through the backend registry
(``repro.api.backends``):
  * ``backend="jnp"``    — reference, pure jnp (this file),
  * ``backend="pallas"`` — fused VMEM-tiled kernel (repro.kernels.ops).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import field
from .shamir import Shares

__all__ = ["match_words", "match_column", "count_column", "match_matrix",
           "slide_windows", "match_suffix", "window_count",
           "equality_indicator", "zero_indicator"]


def _inner_over_alphabet(col_vals: jax.Array, pat_vals: jax.Array) -> jax.Array:
    """v[..., j] = Σ_α col[..., j, α] · pat[..., j, α]  (mod p)."""
    return field.dot(col_vals, pat_vals, axis=-1)


def _chain(v: jax.Array) -> jax.Array:
    """N_{x+1} = Π_j v[..., j] via sequential chain (Table 3 order)."""
    w = v.shape[-1]
    acc = v[..., 0]
    for j in range(1, w):          # w is static & small; unrolled chain
        acc = field.mul(acc, v[..., j])
    return acc


def match_words(column: Shares, pattern: Shares) -> Shares:
    """Match pattern (c, W, A) against every word of column (c, n, W, A).

    Returns Shares (c, n): share of 1 where the word equals the pattern.
    Degree: 2·t·W for degree-t inputs.
    """
    col = column.values                            # (c, n, W, A)
    pat = pattern.values[:, None]                  # (c, 1, W, A)
    with jax.named_scope("match"):
        v = _inner_over_alphabet(col, jnp.broadcast_to(pat, col.shape))
        bits = _chain(v)
    out_degree = (column.degree + pattern.degree) * col.shape[-2]
    return Shares(bits, out_degree)


# alias used by query code: a "column" is (c, n, W, A)
match_column = match_words


def count_column(column: Shares, pattern: Shares) -> Shares:
    """§3.1 count: accumulate the AA output over all tuples.

    Faithful to Table 3's final accumulation step
    ``N_{x+1} += N_x · v_x`` across iterations: the per-tuple match bits are
    summed in share space, so the cloud never sees the count.
    """
    return match_words(column, pattern).sum(axis=0)


def match_matrix(col_x: Shares, col_y: Shares, *,
                 method: str = "chain") -> Shares:
    """All-pairs word match between two shared columns (join inner loop).

    col_x: (c, n_x, W, A), col_y: (c, n_y, W, A)
    Returns Shares (c, n_x, n_y) — share of 1 where word_i == word_j.

    method="chain" (paper-faithful, Table 3): per position a mod-p matmul
    over the alphabet axis, chained multiplicatively — W dot-sets.

    method="aggregate" (beyond-paper, §Perf): ONE dot over the flattened
    (W·A) axis gives P = #matching positions ∈ {0..W} (as a share); the
    equality indicator is the Lagrange basis polynomial
    ``1[P==W] = (Π_{j<W} (P−j)) / W!`` evaluated share-side — same output,
    same final degree (2tW), but 1 dot-set instead of W and a fusable
    elementwise chain (measured 12× fewer mod-p dots on the paper_db cell).
    """
    xv = col_x.values            # (c, nx, W, A)
    yv = col_y.values            # (c, ny, W, A)
    w = xv.shape[-2]
    out_degree = (col_x.degree + col_y.degree) * w
    with jax.named_scope("join"):
        if method == "aggregate":
            c, nx = xv.shape[0], xv.shape[1]
            ny = yv.shape[1]
            xf = xv.reshape(c, nx, -1)
            yf = yv.reshape(c, ny, -1)
            p_cnt = field.matmul(xf, jnp.swapaxes(yf, -1, -2))  # (c,nx,ny)
            return Shares(_equality_indicator(p_cnt, w), out_degree)
        acc = None
        for j in range(w):
            pj = field.matmul(xv[:, :, j, :],
                              jnp.swapaxes(yv[:, :, j, :], -1, -2))
            acc = pj if acc is None else field.mul(acc, pj)
    return Shares(acc, out_degree)


def _equality_indicator(p_cnt, w: int):
    """1[P == w] = Π_{j=0}^{w-1} (P − j) · (w!)⁻¹   (mod p)."""
    acc = None
    for j in range(w):
        term = field.sub(p_cnt, jnp.asarray(j, field.DTYPE))
        acc = term if acc is None else field.mul(acc, term)
    inv_wfact = _inv_factorial(w)
    return field.mul(acc, jnp.asarray(inv_wfact, field.DTYPE))


#: public raw-array form (shared with the backend registry's batched
#: aggregate match-matrix path). Input: P shares, static w; degree ×w.
equality_indicator = _equality_indicator


def zero_indicator(p_cnt, m: int):
    """1[P == 0] = Π_{j=1}^{m} (j − P) · (m!)⁻¹  over the domain {0..m}.

    The Lagrange basis polynomial at 0: a share-local (cloud-side)
    elementwise chain, degree ×m. Used by the CONTAINS matcher, whose
    window count P ∈ {0..M} may exceed 1 (repeated substrings)."""
    acc = None
    for j in range(1, m + 1):
        term = field.sub(jnp.asarray(j, field.DTYPE), p_cnt)
        acc = term if acc is None else field.mul(acc, term)
    return field.mul(acc, jnp.asarray(_inv_factorial(m), field.DTYPE))


# ---------------------------------------------------------------------------
# Sliding-window automata step (§3.1 general patterns)
# ---------------------------------------------------------------------------

def slide_windows(column: Shares, pattern: Shares) -> Shares:
    """Chain a k-position pattern tile at every window offset.

    column (c, n, W, A) × pattern (c, k, A) -> Shares (c, n, M) with
    M = W − k + 1: windows[..., o] is a share of 1 iff the word's
    characters at positions o..o+k−1 equal the tile. Degree (tc+tp)·k.
    Reference semantics of the ``aa_slide_batch`` backend op.
    """
    col = column.values                                  # (c, n, W, A)
    pat = pattern.values                                 # (c, k, A)
    k = pat.shape[-2]
    w = col.shape[-2]
    m = w - k + 1
    idx = jnp.arange(m)[:, None] + jnp.arange(k)[None, :]
    win = col[:, :, idx, :]                              # (c, n, M, k, A)
    v = field.dot(win, pat[:, None, None], axis=-1)      # (c, n, M, k)
    return Shares(_chain(v), (column.degree + pattern.degree) * k)


def match_suffix(column: Shares, pattern: Shares) -> Shares:
    """Suffix match bit: Σ_o windows[o] · term[o+k]  (term[W] ≡ 1).

    For a wildcard-free tile the windows are mutually exclusive (the tile's
    real characters cannot match padding, so a matching window must end
    exactly where the terminator run starts), hence the linear sum is the
    exact 0/1 match bit. Returns Shares (c, n), degree (tc+tp)·k + tc
    (the terminator factor; M = 1 skips it).
    """
    win = slide_windows(column, pattern)                 # (c, n, M)
    col = column.values
    k = pattern.values.shape[-2]
    m = col.shape[-2] - k + 1
    if m == 1:
        return Shares(win.values[..., 0], win.degree)
    term = col[:, :, k:, 0]                              # (c, n, M-1)
    ones = jnp.ones(term.shape[:-1] + (1,), field.DTYPE)
    termext = jnp.concatenate([term, ones], axis=-1)     # (c, n, M)
    bits = field.sum_(field.mul(win.values, termext), axis=-1)
    return Shares(bits, win.degree + column.degree)


def window_count(column: Shares, pattern: Shares) -> Shares:
    """P = Σ_o windows[o] — the CONTAINS window count (c, n), ∈ {0..M}
    secret-side for wildcard-free tiles. The match bit is
    ``1 − zero_indicator(P, M)`` after a degree-reduction re-share."""
    win = slide_windows(column, pattern)
    return Shares(field.sum_(win.values, axis=-1), win.degree)


def _inv_factorial(w: int) -> int:
    p = int(field.P)
    f = 1
    for j in range(2, w + 1):
        f = (f * j) % p
    return pow(f, p - 2, p)
