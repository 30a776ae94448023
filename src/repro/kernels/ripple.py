"""Pallas TPU kernels: fused SS-SUB ripple steps (paper §3.4, Alg 6).

One bit position of the two's-complement ripple subtract over secret-shared
bit planes. For every lane (one share of one query-direction of one tuple):

    aᵢ = 1 − Aᵢ                      (invert the subtrahend bit)
    x  = aᵢ ⊕ bᵢ = aᵢ + bᵢ − 2aᵢbᵢ
    c' = aᵢbᵢ + c·x                  (carry propagate/generate)
    rb = x + c − 2cx                 (result bit = x ⊕ c)

all mod p. The LSB step (``init=True``) instead computes the +1-absorbing
carry ``c = OR(1 − A₀, B₀)`` and ``rb = (1 − A₀) + B₀ − 2c`` (the
subtrahend bit is inverted there too).

Six fused elementwise mod-p ops per lane — unbatched, B queries would pay B
tiny dispatches per bit; the batched range engine stacks the whole query
batch (both subtraction directions of Eq. 2) into one (c·2B·n) plane and
issues :func:`ripple_carry_pallas` ONCE per bit-round. Purely a VPU
workload: same 16-bit-limb Mersenne-31 arithmetic as ss_matmul, 1-D grid
over flattened lanes, both outputs written in the same pass (the carry
never round-trips to HBM between the xor/propagate sub-steps).

:func:`ripple_segment_pallas` goes one step further: the k bit positions
*between* two degree-reduction boundaries chain inside ONE kernel — the
carry lives in registers across all k steps and only the final (rb, carry)
pair is written back, so a ``reduce_every=k`` range group pays ~t/k
dispatches instead of t. Layout is (k, N): bit position on the sublane
axis, flattened lanes on the 128-wide lane axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.field import P, addmod32, mulmod32
from .ss_matmul import ZERO, _round_up, interpret_default


def _submod(x: jax.Array, y: jax.Array) -> jax.Array:
    """(x − y) mod p for x, y < p, in 32-bit lanes."""
    return addmod32(x, jnp.where(y == 0, y, P - y))


def _ripple_kernel(a_ref, b_ref, c_ref, rb_ref, co_ref, *, init: bool):
    a = a_ref[...]
    b = b_ref[...]
    ai = _submod(jnp.ones_like(a), a)
    ab = mulmod32(ai, b)
    s = addmod32(ai, b)
    if init:
        carry = _submod(s, ab)
        rb = _submod(s, addmod32(carry, carry))
    else:
        carry_in = c_ref[...]
        x = _submod(s, addmod32(ab, ab))
        cx = mulmod32(carry_in, x)
        carry = addmod32(ab, cx)
        rb = _submod(addmod32(x, carry_in), addmod32(cx, cx))
    rb_ref[...] = rb
    co_ref[...] = carry


@functools.partial(jax.jit, static_argnames=("bn", "init", "interpret"))
def ripple_carry_pallas(a: jax.Array, b: jax.Array, carry: jax.Array, *,
                        bn: int = 4096, init: bool = False,
                        interpret: Optional[bool] = None):
    """a, b, carry: flat (N,) uint32 share planes -> (rb, carry') each (N,).

    ``init=True`` runs the LSB step (``carry`` is ignored but must be
    passed — zeros are fine — so both variants share one call signature).
    """
    n = a.shape[0]
    bn = min(bn, _round_up(max(n, 1), 8))
    n_pad = _round_up(max(n, 1), bn)
    pad = ((0, n_pad - n),)
    out = pl.pallas_call(
        functools.partial(_ripple_kernel, init=init),
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((bn,), lambda i: (i,))] * 3,
        out_specs=[pl.BlockSpec((bn,), lambda i: (i,))] * 2,
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.uint32)] * 2,
        interpret=interpret_default(interpret),
    )(jnp.pad(a, pad), jnp.pad(b, pad), jnp.pad(carry, pad))
    return out[0][:n], out[1][:n]


def _ripple_segment_kernel(a_ref, b_ref, c_ref, rb_ref, co_ref, *,
                           k: int, init: bool):
    """Chain k ripple bit steps; carry stays in registers between steps."""
    carry = c_ref[0, :]
    rb = carry
    for i in range(k):
        a = a_ref[i, :]
        b = b_ref[i, :]
        ai = _submod(jnp.ones_like(a), a)
        ab = mulmod32(ai, b)
        s = addmod32(ai, b)
        if init and i == 0:
            carry = _submod(s, ab)
            rb = _submod(s, addmod32(carry, carry))
        else:
            x = _submod(s, addmod32(ab, ab))
            cx = mulmod32(carry, x)
            rb = _submod(addmod32(x, carry), addmod32(cx, cx))
            carry = addmod32(ab, cx)
    rb_ref[0, :] = rb
    co_ref[0, :] = carry


@functools.partial(jax.jit, static_argnames=("bn", "init", "interpret"))
def ripple_segment_pallas(a: jax.Array, b: jax.Array, carry: jax.Array, *,
                          bn: int = 4096, init: bool = False,
                          interpret: Optional[bool] = None):
    """a, b: (k, N) bit planes (k = consecutive bit positions, N flattened
    lanes); carry: (N,) -> final ``(rb, carry')`` each (N,) after k chained
    steps in ONE kernel launch.

    ``init=True`` makes step 0 the LSB two's-complement step (``carry`` is
    ignored but must be passed — zeros are fine)."""
    k, n = a.shape
    bn = min(bn, _round_up(max(n, 1), 8))
    n_pad = _round_up(max(n, 1), bn)
    pad2 = ((0, 0), (0, n_pad - n))
    pad1 = ((0, n_pad - n),)
    out = pl.pallas_call(
        functools.partial(_ripple_segment_kernel, k=k, init=init),
        grid=(n_pad // bn,),
        in_specs=[pl.BlockSpec((k, bn), lambda i: (ZERO, i)),
                  pl.BlockSpec((k, bn), lambda i: (ZERO, i)),
                  pl.BlockSpec((1, bn), lambda i: (ZERO, i))],
        out_specs=[pl.BlockSpec((1, bn), lambda i: (ZERO, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.uint32)] * 2,
        interpret=interpret_default(interpret),
    )(jnp.pad(a, pad2), jnp.pad(b, pad2),
      jnp.pad(carry, pad1).reshape(1, n_pad))
    return out[0][0, :n], out[1][0, :n]
