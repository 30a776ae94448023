"""Shamir secret-sharing over F_p (Mersenne-31) — vectorized, degree-tracked.

A secret ``s`` is hidden in a random degree-``t`` polynomial ``q`` with
``q(0) = s``; cloud ``k`` receives ``q(x_k)`` with distinct public evaluation
points ``x_k = k+1``. Every value of a secret-shared tensor uses an
*independent* polynomial (fresh randomness), which is the paper's defence
against frequency-count attacks (§2.1).

Share-space computation (the whole point of the paper):
  * ``shares(a) + shares(b)`` elementwise per cloud  -> shares of ``a+b``
    (degree unchanged),
  * ``shares(a) * shares(b)`` elementwise per cloud  -> shares of ``a*b``
    (degree adds),
so queries run obliviously at the clouds. ``Shares`` tracks the polynomial
degree statically; interpolation asserts ``n_shares >= degree+1``.

Degree reduction (§3.4 / [32]) is implemented honestly as a re-sharing
protocol round: each cloud re-shares its share with a fresh degree-``t``
polynomial and the new shares are combined with Lagrange weights. This is the
only operation that communicates across the cloud axis, and it is an explicit,
counted protocol round (see ``core.costs``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import field
from .field import P, DTYPE


# ---------------------------------------------------------------------------
# Shares pytree
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Shares:
    """Secret-shared tensor. ``values[k]`` lives at cloud ``k``.

    values: uint32[c, ...]  — axis 0 is the cloud/share axis.
    degree: static int      — polynomial degree of the sharing.
    """
    values: jax.Array
    degree: int = dataclasses.field(metadata=dict(static=True), default=1)

    @property
    def n_shares(self) -> int:
        return self.values.shape[0]

    @property
    def shape(self):
        return self.values.shape[1:]

    # -- share-space arithmetic (runs *per cloud*, no cross-cloud traffic) --
    def __add__(self, other: "Shares") -> "Shares":
        _check_compat(self, other)
        return Shares(field.add(self.values, other.values),
                      max(self.degree, other.degree))

    def __sub__(self, other: "Shares") -> "Shares":
        _check_compat(self, other)
        return Shares(field.sub(self.values, other.values),
                      max(self.degree, other.degree))

    def __mul__(self, other: "Shares") -> "Shares":
        _check_compat(self, other)
        return Shares(field.mul(self.values, other.values),
                      self.degree + other.degree)

    def add_public(self, const) -> "Shares":
        """Add a public constant (affects the free coefficient only)."""
        return Shares(field.add(self.values, field.to_field(const).astype(DTYPE)),
                      self.degree)

    def mul_public(self, const) -> "Shares":
        return Shares(field.mul(self.values, field.to_field(const).astype(DTYPE)),
                      self.degree)

    def neg(self) -> "Shares":
        return Shares(field.neg(self.values), self.degree)

    def sum(self, axis=None, keepdims: bool = False) -> "Shares":
        """Modular sum over secret-data axes (axis indexes self.shape)."""
        if axis is None:
            axes = tuple(range(1, self.values.ndim))
        elif isinstance(axis, int):
            axes = (_norm_axis(axis, self.values.ndim - 1) + 1,)
        else:
            axes = tuple(_norm_axis(a, self.values.ndim - 1) + 1 for a in axis)
        return Shares(field.sum_(self.values, axis=axes, keepdims=keepdims),
                      self.degree)

    def reshape(self, *shape) -> "Shares":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return Shares(self.values.reshape((self.n_shares,) + tuple(shape)),
                      self.degree)

    def __getitem__(self, idx) -> "Shares":
        """Index the *secret data* dims (cloud axis is preserved)."""
        if not isinstance(idx, tuple):
            idx = (idx,)
        return Shares(self.values[(slice(None),) + idx], self.degree)


def _norm_axis(a: int, ndim: int) -> int:
    return a + ndim if a < 0 else a


def _check_compat(a: Shares, b: Shares) -> None:
    if a.n_shares != b.n_shares:
        raise ValueError(f"share-count mismatch: {a.n_shares} vs {b.n_shares}")


# ---------------------------------------------------------------------------
# Share generation
# ---------------------------------------------------------------------------

def eval_points(n_shares: int) -> jax.Array:
    """Public evaluation points x_k = 1..c (never 0)."""
    return jnp.arange(1, n_shares + 1, dtype=DTYPE)


@functools.partial(jax.jit, static_argnames=("n_shares", "degree"))
def make_shares(key: jax.Array, secrets: jax.Array, *, n_shares: int,
                degree: int = 1) -> jax.Array:
    """Create ``n_shares`` Shamir shares of every element of ``secrets``.

    Every element gets an independent random polynomial (paper §2.1: multiple
    occurrences of a value must have different shares).

    Returns uint32[n_shares, *secrets.shape].
    """
    secrets = field.to_field(secrets).astype(DTYPE)
    coeffs = field.uniform(key, (degree,) + secrets.shape)      # a_1..a_t
    xs = eval_points(n_shares)                                   # (c,)
    # shares[k] = s + sum_t a_t * x_k^t  (Horner over t, vectorized over k)
    def horner(k_x):
        acc = jnp.zeros_like(secrets)
        for t in range(degree - 1, -1, -1):
            acc = field.add(field.mul(acc, jnp.broadcast_to(k_x, acc.shape)),
                            coeffs[t])
        return field.add(field.mul(acc, jnp.broadcast_to(k_x, acc.shape)),
                         secrets)
    return jax.vmap(horner)(xs)


def share(key: jax.Array, secrets, *, n_shares: int, degree: int = 1) -> Shares:
    secrets = jnp.asarray(secrets)
    return Shares(make_shares(key, secrets, n_shares=n_shares, degree=degree),
                  degree)


# ---------------------------------------------------------------------------
# Lagrange interpolation (the user-side "q_interpolate" of §2.2)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _lagrange_at_zero_np(points: tuple) -> np.ndarray:
    """λ_j = Π_{i≠j} x_i / (x_i − x_j) mod p, as numpy uint32 (host-side)."""
    p = int(P)
    xs = [int(x) for x in points]
    lams = []
    for j, xj in enumerate(xs):
        num, den = 1, 1
        for i, xi in enumerate(xs):
            if i == j:
                continue
            num = (num * xi) % p
            den = (den * (xi - xj)) % p
        lams.append((num * pow(den, p - 2, p)) % p)
    return np.asarray(lams, dtype=np.uint32)


def interpolate(shares: Shares, *, points: Optional[tuple] = None) -> jax.Array:
    """Reconstruct secrets from the first ``degree+1`` shares (or all).

    Uses exactly ``degree+1`` shares when available — the user contacts c′
    clouds, not all c (paper §2).
    """
    need = _shares_needed(shares)
    pts = points if points is not None else tuple(range(1, need + 1))
    return _open_at_zero(shares.values, tuple(int(x) for x in pts))


def interpolate_host(shares: Shares) -> np.ndarray:
    """``interpolate`` as the user runs it: the first ``degree+1`` shares
    are copied to the host and opened in numpy — no device program. Exact:
    each λ_j·v_j < 2⁶² is reduced before the ≤ c-term sum."""
    need = _shares_needed(shares)
    lam = _lagrange_at_zero_np(tuple(range(1, need + 1))).astype(np.uint64)
    vals = np.asarray(shares.values[:need]).astype(np.uint64)
    lam = lam.reshape((need,) + (1,) * (vals.ndim - 1))
    return ((lam * vals % P).sum(axis=0) % P).astype(np.uint32)


def _shares_needed(shares: Shares) -> int:
    need = shares.degree + 1
    if shares.n_shares < need:
        raise ValueError(
            f"need {need} shares to open a degree-{shares.degree} sharing, "
            f"have {shares.n_shares}")
    return need


@functools.partial(jax.jit, static_argnames=("pts",))
def _open_at_zero(values: jax.Array, pts: tuple) -> jax.Array:
    """Σ_j λ_j·values[j] over the first len(pts) shares: one program per
    shape instead of one per eager op."""
    vals = values[:len(pts)]
    lam = jnp.asarray(_lagrange_at_zero_np(pts))           # (c',)
    lam = lam.reshape((len(pts),) + (1,) * (vals.ndim - 1))
    return field.sum_(field.mul(vals, jnp.broadcast_to(lam, vals.shape)),
                      axis=0)


def verify_consistency(shares: Shares) -> jax.Array:
    """Berlekamp–Welch-style *detection* hook (paper §2.1 "Aside").

    With r = n_shares − (degree+1) redundant shares, an honest-but-wrong
    (or malicious) cloud is detected by checking that every share lies on the
    unique degree-``t`` polynomial through the first t+1 shares. Returns a
    boolean array (True = consistent) of the secret shape.
    """
    t1 = shares.degree + 1
    if shares.n_shares <= t1:
        return jnp.ones(shares.shape, dtype=bool)
    ok = jnp.ones(shares.shape, dtype=bool)
    base_pts = tuple(range(1, t1 + 1))
    for extra in range(t1, shares.n_shares):
        # interpolate *at x_extra* from the first t+1 shares
        xe = extra + 1
        lam = _lagrange_at(tuple(base_pts), xe)
        pred = field.sum_(
            field.mul(shares.values[:t1],
                      jnp.broadcast_to(
                          lam.reshape((t1,) + (1,) * (shares.values.ndim - 1)),
                          shares.values[:t1].shape)), axis=0)
        ok = ok & (pred == shares.values[extra])
    return ok


@functools.lru_cache(maxsize=256)
def _lagrange_at_np(points: tuple, x0: int) -> np.ndarray:
    p = int(P)
    xs = [int(x) for x in points]
    lams = []
    for j, xj in enumerate(xs):
        num, den = 1, 1
        for i, xi in enumerate(xs):
            if i == j:
                continue
            num = (num * (x0 - xi)) % p
            den = (den * (xj - xi)) % p
        lams.append((num * pow(den, p - 2, p)) % p)
    return np.asarray(lams, dtype=np.uint32)


def _lagrange_at(points: tuple, x0: int) -> jax.Array:
    return jnp.asarray(_lagrange_at_np(points, x0))


# ---------------------------------------------------------------------------
# Degree reduction (re-sharing; §3.4 / [32])
# ---------------------------------------------------------------------------

def reduce_degree(key: jax.Array, shares: Shares, *, target_degree: int = 1
                  ) -> Shares:
    """Re-share a high-degree sharing down to ``target_degree``.

    Protocol: cloud k re-shares its share s_k with a fresh degree-t polynomial
    (sub-shares [k -> j]); cloud j combines sub-shares with the Lagrange
    weights λ_k of the *high-degree* opening:  s'_j = Σ_k λ_k · sub_{k→j}.
    Correct because interpolation is linear. This crosses the cloud axis —
    it is the protocol's explicit communication round.
    """
    d = shares.degree
    c = shares.n_shares
    need = d + 1
    if c < need:
        raise ValueError(f"cannot reduce degree {d} with only {c} shares")
    return Shares(_reshare(key, shares.values, need=need,
                           target_degree=target_degree), target_degree)


@functools.partial(jax.jit, static_argnames=("need", "target_degree"))
def _reshare(key: jax.Array, values: jax.Array, *, need: int,
             target_degree: int) -> jax.Array:
    c = values.shape[0]
    lam = jnp.asarray(_lagrange_at_zero_np(tuple(range(1, need + 1))))
    with jax.named_scope("reshare"):
        # sub[k, j, ...] = share_{k -> j}
        sub = make_shares(key, values[:need], n_shares=c,
                          degree=target_degree)                 # (c, d+1, ...)
        lam_b = lam.reshape((1, need) + (1,) * (values.ndim - 1))
        return field.sum_(
            field.mul(sub, jnp.broadcast_to(lam_b, sub.shape)), axis=1)
