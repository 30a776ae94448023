"""Backend registry: named implementations of the cloud-side hot ops.

A :class:`Backend` bundles the share-space hotspots every query is built
from:

  * ``aa_match``       — accumulating-automata word match (§3.1, Table 3),
  * ``ss_matmul``      — share-space mod-p matmul (the oblivious-fetch and
                         embedding-lookup hotspot),
  * ``match_matrix``   — all-pairs word match (the §3.3.1 join inner loop),
  * ``aa_match_batch`` — AA match over a *stack* of predicates, one per
                         batch row. This is the primitive the batched query
                         engine (``repro.core.queries.rounds``) issues once
                         per protocol round: B concurrent queries (or B
                         padded blocks of one tree-selection round) become a
                         single device dispatch instead of B.
  * ``ripple_carry``   — one bit position of the §3.4 SS-SUB ripple
                         (Algorithm 6) over a *stack* of subtractions:
                         given the bit-i share planes of A and B and the
                         incoming carry (``None`` selects the LSB
                         two's-complement step), returns ``(rb, carry')``.
                         The batched range engine issues it once per
                         bit-round for the whole query batch.
  * ``ripple_segment`` — k consecutive SS-SUB bit positions fused into one
                         dispatch: given ``(…, n, k)`` bit planes of A and
                         B and the incoming carry (``None`` = the chain
                         starts at the LSB step), returns the *final*
                         ``(rb, carry')`` after k steps. The range engine
                         issues one segment per degree-reduction boundary
                         (≈ t_bits/reduce_every dispatches) instead of one
                         ``ripple_carry`` per bit.
  * ``match_matrix_batch`` — all-pairs match over a stack of B column
                         pairs, ``(c, B, nx, W, A) × (c, B, ny, W, A) ->
                         (c, B, nx, ny)``: a join group's equal-size right
                         relations become ONE dispatch, mirroring what
                         ``aa_match_batch`` does for predicates.
  * ``aa_slide_batch`` — the sliding-window automata step over a stack of
                         B pattern tiles, ``(c, B, n, W, A) × (c, B, k, A)
                         -> (c, B, n, M)`` with M = W−k+1 raw window-chain
                         products: one dispatch per protocol round for a
                         whole group of suffix/substring predicates. The
                         suffix terminator factor and the CONTAINS window
                         count are linear post-processing at the round
                         engine, so one dispatch serves both kinds.

All operate on *raw* uint32 share arrays (cloud axis first where batched);
polynomial-degree bookkeeping stays at the query layer. Queries resolve a
backend by name via :func:`get_backend`; ``repro.api.QueryClient`` exposes
the choice as a constructor argument. Third parties can plug in alternatives
(a GPU kernel set) with :func:`register_backend`. A backend that omits
``aa_match_batch`` still works: :func:`batched_matcher` falls back to
``vmap`` over its ``aa_match``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

Array = jax.Array
_Op = Callable[[Array, Array], Array]
_RippleOp = Callable[[Array, Array, Optional[Array]], Tuple[Array, Array]]


@dataclasses.dataclass(frozen=True)
class Backend:
    """Named bundle of cloud-side primitives on raw uint32 share arrays.

    aa_match:       (c, n, W, A), (c, W, A)       -> (c, n)
    ss_matmul:      ([c,] M, K),  ([c,] K, N)     -> ([c,] M, N)
    match_matrix:   (c, nx, W, A), (c, ny, W, A)  -> (c, nx, ny)
    aa_match_batch: (c, B, n, W, A), (c, B, W, A) -> (c, B, n)
    ripple_carry:   (c, S, n), (c, S, n), carry|None -> (rb, carry')
    ripple_segment: (c, S, n, k), (c, S, n, k), carry|None -> (rb, carry')
    match_matrix_batch: (c, B, nx, W, A), (c, B, ny, W, A) -> (c, B, nx, ny)
    aa_slide_batch: (c, B, n, W, A), (c, B, k, A) -> (c, B, n, W-k+1)
    share_onehot:   tokens (M,) int32, a1 (M, V), n_shares= -> (c, M, V)
                    fused one-hot share generation (embedding fast path);
                    None falls back to the jnp reference program.
    """
    name: str
    aa_match: _Op
    ss_matmul: _Op
    match_matrix: _Op
    aa_match_batch: Optional[_Op] = None
    ripple_carry: Optional[_RippleOp] = None
    ripple_segment: Optional[_RippleOp] = None
    match_matrix_batch: Optional[_Op] = None
    aa_slide_batch: Optional[_Op] = None
    share_onehot: Optional[Callable[..., Array]] = None


def batched_matcher(backend: Backend) -> _Op:
    """The backend's batched AA match, or a vmap fallback over ``aa_match``.

    The fallback covers third-party backends whose ``aa_match`` is a
    traceable jax function.
    """
    if backend.aa_match_batch is not None:
        return backend.aa_match_batch
    return jax.vmap(backend.aa_match, in_axes=1, out_axes=1)


def ripple_stepper(backend: Backend) -> _RippleOp:
    """The backend's SS-SUB bit step, or the reference jnp implementation.

    Unlike the matcher there is no per-backend shape contract to adapt —
    the step is elementwise share arithmetic — so any backend without its
    own fused kernel transparently gets the jnp one.
    """
    if backend.ripple_carry is not None:
        return backend.ripple_carry
    return jnp_ripple_carry


def ripple_segmenter(backend: Backend) -> _RippleOp:
    """The backend's fused k-bit SS-SUB segment, or a per-bit fallback.

    The fallback steps the backend's own ``ripple_carry`` once per bit
    position — bit-identical output (the fused kernel runs the same six
    mod-p ops per lane), just k dispatches instead of one — so third-party
    backends keep working and counting/test backends still observe the
    per-bit op stream.
    """
    if backend.ripple_segment is not None:
        return backend.ripple_segment
    step = ripple_stepper(backend)

    def segment(a: Array, b: Array, carry: Optional[Array] = None):
        rb = None
        for i in range(a.shape[-1]):
            rb, carry = step(a[..., i], b[..., i], carry)
        return rb, carry

    return segment


def batched_match_matrix(backend: Backend) -> _Op:
    """The backend's stacked all-pairs matcher, or a vmap fallback.

    As with :func:`batched_matcher`, any traceable ``match_matrix`` gets
    the vmap for free.
    """
    if backend.match_matrix_batch is not None:
        return backend.match_matrix_batch
    return jax.vmap(backend.match_matrix, in_axes=1, out_axes=1)


def slide_matcher(backend: Backend) -> _Op:
    """The backend's batched sliding-window matcher, or the jnp reference.

    As with :func:`ripple_stepper`, the fallback is backend-agnostic: the
    op is pure share arithmetic on raw arrays, so any backend without its
    own fused kernel transparently gets the reference program.
    """
    if backend.aa_slide_batch is not None:
        return backend.aa_slide_batch
    return jnp_aa_slide


def aggregate_match_matrix(backend: Backend) -> _Op:
    """Batched all-pairs matcher in the AGGREGATE form (§3.1.2): ONE
    flattened (W·A) ``ss_matmul`` gives P = #matching positions per pair;
    the Lagrange equality indicator ``1[P==W]`` is a share-local
    elementwise chain. Same secrets and same final degree as the chain
    matcher — 1 dot-set instead of W — so the planner picks either per
    join group by launch count (``planner.choose_match_method``).
    """
    def run(bx: Array, by: Array) -> Array:
        from ..core import automata
        c, b, nx, w, a = bx.shape
        ny = by.shape[2]
        xf = bx.reshape(c * b, nx, w * a)
        yf = jnp.swapaxes(by.reshape(c * b, ny, w * a), -1, -2)
        p_cnt = backend.ss_matmul(xf, yf).reshape(c, b, nx, ny)
        return automata.equality_indicator(p_cnt, w)
    return run


def _make_jnp_slide():
    """Reference batched sliding-window chain (dot pattern row j against
    word positions j..j+M−1 of every tuple, chain the k rows — all under
    one jit; retraces per distinct (k, shape) group, which the round
    engine groups by anyway). Static slices, so no (…, M, k, A) window
    gather is ever materialized."""
    from ..core import field

    @jax.jit
    def aa_slide(cols: Array, pats: Array) -> Array:
        # cols (c, B, n, W, A), pats (c, B, k, A) -> (c, B, n, M)
        k = pats.shape[-2]
        m = cols.shape[-2] - k + 1
        acc = None
        with jax.named_scope("match"):
            for j in range(k):                       # k static: unrolled
                v = field.dot(cols[..., j:j + m, :],
                              pats[:, :, None, None, j, :], axis=-1)
                acc = v if acc is None else field.mul(acc, v)
        return acc

    return aa_slide


jnp_aa_slide: _Op = _make_jnp_slide()


def _make_jnp_ripple():
    """Reference fused ripple step (Algorithm 6 lines 1-4, one bit)."""
    from ..core import field

    @jax.jit
    def _init(a, b):
        # LSB handles the +1 of two's complement: carry = OR(1−a, b)
        ai = field.sub(jnp.ones_like(a), a)
        ab = field.mul(ai, b)
        s = field.add(ai, b)
        carry = field.sub(s, ab)
        rb = field.sub(s, field.add(carry, carry))
        return rb, carry

    @jax.jit
    def _step(a, b, carry):
        ai = field.sub(jnp.ones_like(a), a)
        ab = field.mul(ai, b)
        x = field.sub(field.add(ai, b), field.add(ab, ab))   # ai ⊕ b
        cx = field.mul(carry, x)
        new_carry = field.add(ab, cx)
        rb = field.sub(field.add(x, carry), field.add(cx, cx))
        return rb, new_carry

    def ripple_carry(a, b, carry=None):
        return _init(a, b) if carry is None else _step(a, b, carry)

    return ripple_carry


jnp_ripple_carry: _RippleOp = _make_jnp_ripple()


def _make_jnp_ripple_segment():
    """Reference fused k-bit segment: the per-bit chain under ONE jit, so a
    whole degree-reduction-free run of bits is a single device dispatch.
    The loop body is exactly :data:`jnp_ripple_carry`'s math, hence
    bit-identical to stepping (``carry=None`` starts at the LSB step)."""

    @jax.jit
    def ripple_segment(a, b, carry=None):
        rb = None
        for i in range(a.shape[-1]):
            rb, carry = jnp_ripple_carry(a[..., i], b[..., i], carry)
        return rb, carry

    return ripple_segment


jnp_ripple_segment: _RippleOp = _make_jnp_ripple_segment()


_REGISTRY: Dict[str, Backend] = {}

BackendLike = Union[str, Backend]


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(backend: BackendLike) -> Backend:
    """Resolve a backend name (a ``Backend`` instance passes through)."""
    if isinstance(backend, Backend):
        return backend
    _ensure_builtins()
    try:
        return _REGISTRY[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; available: "
                         f"{available_backends()}") from None


def available_backends() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def _ensure_builtins() -> None:
    """Register the pure-jnp and Pallas backends on first use (lazily, so
    importing the registry never imports the kernels: import-cycle safe)."""
    if "jnp" in _REGISTRY:
        return
    from ..core import automata, field
    from ..core.shamir import Shares
    from ..kernels import ops as kops

    def _raw(op):                       # Shares-level op -> raw-array op
        def run(a: Array, b: Array) -> Array:
            return op(Shares(a, 0), Shares(b, 0)).values
        return run

    aa_match = _raw(automata.match_words)

    match_matrix = _raw(automata.match_matrix)

    register_backend(Backend(
        "jnp",
        aa_match=aa_match,
        ss_matmul=field.matmul,
        match_matrix=match_matrix,
        aa_match_batch=jax.jit(jax.vmap(aa_match, in_axes=1, out_axes=1)),
        ripple_carry=jnp_ripple_carry,
        ripple_segment=jnp_ripple_segment,
        match_matrix_batch=jax.jit(jax.vmap(match_matrix, in_axes=1,
                                            out_axes=1)),
        aa_slide_batch=jnp_aa_slide))
    register_backend(kops.as_backend())
