"""Finite-field arithmetic over F_p with p = 2**31 - 1 (Mersenne-31).

All secret-sharing math in this framework happens in this field. Elements are
stored as ``uint32`` in ``[0, p)``. Elementwise products are formed in
``uint64`` lanes and reduced with the Mersenne fold
``x -> (x & p) + (x >> 31)`` — two folds bring any 62-bit value below ``2p``,
one conditional subtract finishes: no integer division, no
Barrett/Montgomery constants.

Contractions (:func:`matmul`) never form a 64-bit dot — XLA:TPU has none.
They run on exact int8 limbs with int32 accumulation, and the same 32-bit-lane
helpers (``*32`` below) are what the Pallas kernels (``repro.kernels``) build
on; this module is the reference semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The field prime: Mersenne-31. Fits uint32; products fit uint64 (62 bits).
P = np.uint32(2**31 - 1)
P64 = np.uint64(2**31 - 1)
DTYPE = jnp.uint32

__all__ = [
    "P", "DTYPE", "to_field", "add", "sub", "neg", "mul", "pow_", "inv",
    "sum_", "dot", "matmul", "table_digits", "uniform", "from_signed",
]


@jax.jit
def to_field(x) -> jax.Array:
    """Cast integers (possibly negative / oversized) into canonical F_p form."""
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.signedinteger):
        x = jnp.asarray(x, jnp.int64) % jnp.int64(P)
    return _fold64(jnp.asarray(x, jnp.uint64))


def _fold64(x: jax.Array) -> jax.Array:
    """Mersenne fold of any uint64 value down to [0, p): the same result as
    ``x % p`` without a 64-bit division, which the TPU only emulates."""
    x = (x & P64) + (x >> np.uint64(31))          # < 2**34
    x = (x & P64) + (x >> np.uint64(31))          # < p + 9
    return x - jnp.where(x >= P64, P64, np.uint64(0))


@jax.jit
def add(a: jax.Array, b: jax.Array) -> jax.Array:
    s = a.astype(jnp.uint64) + b.astype(jnp.uint64)
    s = s - jnp.where(s >= P64, P64, np.uint64(0))
    return s.astype(DTYPE)


@jax.jit
def sub(a: jax.Array, b: jax.Array) -> jax.Array:
    a = a.astype(jnp.uint64)
    b = b.astype(jnp.uint64)
    return (a + jnp.where(a >= b, np.uint64(0), P64) - b).astype(DTYPE)


@jax.jit
def neg(a: jax.Array) -> jax.Array:
    a = a.astype(jnp.uint64)
    return jnp.where(a == 0, a, P64 - a).astype(DTYPE)


@jax.jit
def mul(a: jax.Array, b: jax.Array) -> jax.Array:
    prod = a.astype(jnp.uint64) * b.astype(jnp.uint64)   # < 2**62
    return _fold64(prod).astype(DTYPE)


@functools.partial(jax.jit, static_argnames=("axis", "keepdims"))
def sum_(x: jax.Array, axis=None, keepdims: bool = False) -> jax.Array:
    """Modular sum. Accumulates in uint64 (safe for up to 2**33 addends)."""
    acc = jnp.sum(x.astype(jnp.uint64), axis=axis, keepdims=keepdims)
    # acc < n * p <= 2**33 * 2**31 = 2**64 -> fold via % once (uint64 mod is
    # fine outside the hot path; hot paths use the Pallas kernels).
    return (acc % P64).astype(DTYPE)


@functools.partial(jax.jit, static_argnames=("axis",))
def dot(a: jax.Array, b: jax.Array, axis: int = -1) -> jax.Array:
    """Modular inner product along ``axis``."""
    prod = a.astype(jnp.uint64) * b.astype(jnp.uint64)
    prod = _fold64(prod)
    return sum_(prod, axis=axis)


# ---------------------------------------------------------------------------
# 32-bit-lane arithmetic: what the TPU's vector unit and MXU can do exactly.
# The Pallas kernels (``repro.kernels``) build on these; ``matmul`` below is
# the same limb algorithm over whole arrays.
# ---------------------------------------------------------------------------

#: Exact int8-limb contractions accumulate |Σ| ≤ K·2¹⁴ in int32; a
#: contraction chunk of 2¹⁶ keeps that ≤ 2³⁰, so the signed fold below
#: needs one conditional add.
LIMB_K_MAX = 1 << 16


def fold32(x: jax.Array) -> jax.Array:
    """uint32 -> [0, p): one Mersenne fold + conditional subtract."""
    x = (x & P) + (x >> np.uint32(31))                    # < p + 2
    return x - jnp.where(x >= P, P, np.uint32(0))


def addmod32(a: jax.Array, b: jax.Array) -> jax.Array:
    """(a + b) mod p for a, b < p. a+b < 2p < 2³², no wrap."""
    s = a + b
    return s - jnp.where(s >= P, P, np.uint32(0))


def mulmod32(x: jax.Array, y: jax.Array) -> jax.Array:
    """(x · y) mod p for x, y < p, entirely in 32-bit lanes (16-bit limbs)."""
    m16, m15 = np.uint32(0xFFFF), np.uint32(0x7FFF)
    x0, x1 = x & m16, x >> np.uint32(16)       # x1 < 2^15
    y0, y1 = y & m16, y >> np.uint32(16)
    lo = x0 * y0                     # < 2^32, exact in uint32
    mid = x1 * y0 + x0 * y1          # each < 2^31, sum < 2^32
    hi = x1 * y1                     # < 2^30
    # mid·2¹⁶ mod p: mid = mh·2¹⁵ + ml  ⇒  mh·2³¹ + ml·2¹⁶ ≡ mh + ml·2¹⁶
    t_mid = (mid >> np.uint32(15)) + ((mid & m15) << np.uint32(16))
    # lo mod p: lo = lh·2³¹ + ll ⇒ lh + ll
    t_lo = (lo >> np.uint32(31)) + (lo & P)
    # hi·2³² ≡ 2·hi
    t_hi = hi << np.uint32(1)
    return addmod32(addmod32(fold32(t_mid), fold32(t_lo)), fold32(t_hi))


def rotmod32(x: jax.Array, r: int) -> jax.Array:
    """x · 2^r mod p for x < p: a 31-bit rotate (2³¹ ≡ 1)."""
    r %= 31
    if r == 0:
        return x
    return ((x << np.uint32(r)) & P) | (x >> np.uint32(31 - r))


def digits8(x: jax.Array):
    """Four balanced int8 digits of x ∈ [0, p), viewed as the centred
    residue x' ∈ (−p/2, p/2): x ≡ Σ_i d_i·2^{8i} (mod p), d_i ∈ [−128, 128).
    Signed digits are what the MXU's int8 mode multiplies."""
    c = x.astype(jnp.int32)
    c = jnp.where(c > np.int32(2**30 - 1), c - np.int32(P), c)  # |c| < 2^30
    out = []
    for _ in range(3):
        d = ((c + np.int32(128)) & np.int32(255)) - np.int32(128)
        out.append(d.astype(jnp.int8))
        c = (c - d) >> np.int32(8)                   # exact: low byte is d
    out.append(c.astype(jnp.int8))                   # |c| ≤ 65
    return out


def _is_digits(b) -> bool:
    """Whether ``b`` is already split: the digits of :func:`digits8`."""
    return isinstance(b, (tuple, list))


def limb_contract(a: jax.Array, b, dot) -> jax.Array:
    """Σ_k a·b mod p as 16 exact int8 dots: ``dot(x, y)`` contracts two int8
    digit arrays into int32 (|Σ| ≤ K·2¹⁴, exact while K ≤ ``LIMB_K_MAX``).
    Digit pair (i, j) carries weight 2^{8(i+j)}, a 31-bit rotate mod p.
    ``b`` is a uint32 array or its four digits from :func:`digits8`."""
    ad = digits8(a)
    bd = b if _is_digits(b) else digits8(b)
    acc = None
    for i in range(4):
        for j in range(4):
            d = dot(ad[i], bd[j])
            t = jnp.where(d < 0, d + np.int32(P), d).astype(DTYPE)  # [0, p)
            t = rotmod32(t, 8 * (i + j))
            acc = t if acc is None else addmod32(acc, t)
    return acc


@jax.jit
def table_digits(x: jax.Array):
    """:func:`digits8` of ``x`` as one program of its own: the digits of an
    operand that :func:`matmul` contracts many times, made once and passed
    in its place."""
    return tuple(digits8(x))


@jax.jit
def matmul(a: jax.Array, b) -> jax.Array:
    """Modular matmul ``a @ b`` for 2-D (or batched) uint32 operands.

    Each operand splits into four signed 8-bit limbs (:func:`digits8`), so
    the mod-p matmul becomes 16 int8 dots that accumulate exactly in int32
    — the integer mode XLA:TPU runs on the MXU — recombined with Mersenne
    rotates. K streams in ``LIMB_K_MAX`` chunks summed mod p. The Pallas
    kernel (kernels/ss_matmul.py) is the same algorithm tiled for VMEM.
    Its operations sit under the name scope ``fetch``: the oblivious fetch
    and the embedding lookup, a fetch of one-hot rows, are its main users.

    ``b`` may come already split, as the four int8 digits that
    :func:`digits8` (or :func:`table_digits`) returns: then only ``a`` is
    split here, and the result is bit-identical. The pytree's structure
    picks the branch at trace time.
    """
    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.int32)

    k_dim = a.shape[-1]
    acc = None
    with jax.named_scope("fetch"):
        for k0 in range(0, max(k_dim, 1), LIMB_K_MAX):
            if _is_digits(b):
                bk = [d[..., k0:k0 + LIMB_K_MAX, :] for d in b]
            else:
                bk = b[..., k0:k0 + LIMB_K_MAX, :]
            part = limb_contract(a[..., k0:k0 + LIMB_K_MAX], bk, dot)
            acc = part if acc is None else addmod32(acc, part)
    return acc


def pow_(a: jax.Array, e: int) -> jax.Array:
    """a**e mod p by square-and-multiply (e is a static python int)."""
    e = int(e)
    result = jnp.full_like(a, 1)
    base = a
    while e > 0:
        if e & 1:
            result = mul(result, base)
        base = mul(base, base)
        e >>= 1
    return result


def inv(a: jax.Array) -> jax.Array:
    """Multiplicative inverse by Fermat: a**(p-2)."""
    return pow_(a, int(P) - 2)


@jax.jit
def from_signed(x: jax.Array) -> jax.Array:
    """Interpret field element as signed (for small +/- values around 0)."""
    x = x.astype(jnp.int64)
    half = jnp.int64(int(P) // 2)
    return jnp.where(x > half, x - jnp.int64(int(P)), x)


def uniform(key: jax.Array, shape) -> jax.Array:
    """Uniform field elements via rejection-free 62-bit sampling.

    Draws 64 random bits, keeps the low 62, reduces mod p. The bias is
    2**-31-scale (negligible, and irrelevant for tests).
    """
    bits = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, dtype=jnp.uint64), DTYPE)   # (..., 2)
    b_lo, b_hi = bits[..., 0], bits[..., 1]
    # (bits >> 2) % p without 64-bit arithmetic: bits >> 2 = hi·2³¹ + lo
    hi = b_hi >> np.uint32(1)
    lo = ((b_hi & np.uint32(1)) << np.uint32(30)) | (b_lo >> np.uint32(2))
    return addmod32(fold32(hi), fold32(lo))
