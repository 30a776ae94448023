"""Unit + property tests for F_p (Mersenne-31) arithmetic."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import field

P = int(field.P)
elems = st.integers(min_value=0, max_value=P - 1)


def as_f(*xs):
    return [np.asarray(np.uint32(x)) for x in xs]


@settings(max_examples=200, deadline=None)
@given(elems, elems)
def test_add_matches_python(a, b):
    fa, fb = as_f(a, b)
    assert int(field.add(fa, fb)) == (a + b) % P


@settings(max_examples=200, deadline=None)
@given(elems, elems)
def test_mul_matches_python(a, b):
    fa, fb = as_f(a, b)
    assert int(field.mul(fa, fb)) == (a * b) % P


@settings(max_examples=200, deadline=None)
@given(elems, elems)
def test_sub_matches_python(a, b):
    fa, fb = as_f(a, b)
    assert int(field.sub(fa, fb)) == (a - b) % P


@settings(max_examples=50, deadline=None)
@given(elems.filter(lambda x: x != 0))
def test_inverse(a):
    fa, = as_f(a)
    assert int(field.mul(fa, field.inv(fa))) == 1


@settings(max_examples=50, deadline=None)
@given(elems, elems, elems)
def test_distributive(a, b, c):
    fa, fb, fc = as_f(a, b, c)
    lhs = field.mul(fa, field.add(fb, fc))
    rhs = field.add(field.mul(fa, fb), field.mul(fa, fc))
    assert int(lhs) == int(rhs)


def test_edge_values():
    # p-1 squared, 0, 1 — the overflow-critical corners
    for a in [0, 1, P - 1, P - 2, 2**30]:
        for b in [0, 1, P - 1, P - 2, 2**30]:
            fa, fb = as_f(a, b)
            assert int(field.mul(fa, fb)) == (a * b) % P
            assert int(field.add(fa, fb)) == (a + b) % P


def test_sum_long_axis():
    # accumulate 1e6 near-maximal values: uint64 accumulator must not wrap
    n = 1_000_000
    x = np.full((n,), P - 1, dtype=np.uint32)
    assert int(field.sum_(jax.numpy.asarray(x))) == ((P - 1) * n) % P


def test_matmul_matches_numpy_bigint():
    rng = np.random.default_rng(0)
    a = rng.integers(0, P, size=(7, 11), dtype=np.uint64)
    b = rng.integers(0, P, size=(11, 5), dtype=np.uint64)
    want = (a.astype(object) @ b.astype(object)) % P
    got = np.asarray(field.matmul(a.astype(np.uint32), b.astype(np.uint32)))
    assert np.array_equal(got.astype(object), want)


def test_uniform_in_range():
    x = np.asarray(field.uniform(jax.random.PRNGKey(0), (4096,)))
    assert x.max() < P
    # crude uniformity: mean within 2% of p/2
    assert abs(float(x.mean()) / (P / 2) - 1.0) < 0.02


def test_uniform_is_62_bit_draw_mod_p():
    # the uint32-lane reduction must equal the 62-bit draw reduced mod p
    key = jax.random.PRNGKey(7)
    bits = np.asarray(jax.random.bits(key, (3, 1000), dtype=np.uint64))
    want = (bits >> np.uint64(2)) % np.uint64(P)
    assert np.array_equal(np.asarray(field.uniform(key, (3, 1000))), want)


def test_to_field_folds_like_modulo():
    x = np.array([0, 1, P - 1, P, P + 1, 2**32 - 1, 2**62 - 1, 2**64 - 1,
                  P * 5], dtype=np.uint64)
    assert np.array_equal(np.asarray(field.to_field(x)), x % np.uint64(P))
    s = np.array([-1, -P, -P - 1, 0, 5], dtype=np.int64)
    assert np.array_equal(np.asarray(field.to_field(s)),
                          [(int(v) % P) for v in s])


@pytest.mark.parametrize("k,fill", [
    (field.LIMB_K_MAX, P - 1),            # one chunk at the exactness bound
    (field.LIMB_K_MAX + 3, P - 1),        # two chunks summed mod p
    (field.LIMB_K_MAX + 3, None),         # random operands across chunks
])
def test_matmul_limb_chunks_exact(k, fill):
    rng = np.random.default_rng(1)
    if fill is None:
        a = rng.integers(0, P, size=(2, k), dtype=np.uint64)
        b = rng.integers(0, P, size=(k, 3), dtype=np.uint64)
    else:
        a = np.full((2, k), fill, np.uint64)
        b = np.full((k, 3), fill, np.uint64)
    want = (a.astype(object) @ b.astype(object)) % P
    got = np.asarray(field.matmul(a.astype(np.uint32), b.astype(np.uint32)))
    assert np.array_equal(got.astype(object), want)


EDGES = np.array([0, 1, 2**30 - 1, 2**30, P - 2**30, P - 1], np.uint64)


def _with_edges(x):
    """``x`` with the digit split's edge residues spread through it."""
    flat = x.reshape(-1)
    flat[::7] = np.resize(EDGES, flat[::7].shape)
    return x


@pytest.mark.parametrize("split", ["digits8", "table_digits"])
@pytest.mark.parametrize("lead", [(), (3,)], ids=["2d", "batched"])
@pytest.mark.parametrize("k", [1, 300, field.LIMB_K_MAX + 37])
def test_matmul_presplit_operand_bit_identical(k, lead, split):
    # the right operand handed over as its int8 digits gives the same
    # residues as the uint32 operand, across a LIMB_K_MAX chunk boundary
    rng = np.random.default_rng(k + len(lead))
    a = _with_edges(rng.integers(0, P, size=lead + (5, k), dtype=np.uint64))
    b = _with_edges(rng.integers(0, P, size=lead + (k, 4), dtype=np.uint64))
    a, b = a.astype(np.uint32), b.astype(np.uint32)
    digits = getattr(field, split)(jax.numpy.asarray(b))
    got = np.asarray(field.matmul(a, digits))
    assert np.array_equal(got, np.asarray(field.matmul(a, b)))
    if k <= 300:
        want = (a.astype(object) @ b.astype(object)) % P
        assert np.array_equal(got.astype(object), want)


def test_digits8_recombine_to_residue():
    rng = np.random.default_rng(2)
    x = np.concatenate([EDGES, rng.integers(0, P, 4096, dtype=np.uint64)])
    digits = [np.asarray(d) for d in
              field.digits8(jax.numpy.asarray(x.astype(np.uint32)))]
    assert all(d.dtype == np.int8 for d in digits)
    total = sum(d.astype(object) * 2 ** (8 * i) for i, d in enumerate(digits))
    assert np.array_equal(total % P, x.astype(object))


def test_slide_chain_at_xla_cpu_fold_shape():
    # At this shape (27 clouds, 256 tuples, W=12, A=69, k=4) XLA:CPU
    # (jax 0.9.0) returned one wrong window product, at tuple 0 of one
    # cloud, when sum_ reduced its uint64 total by the Mersenne fold
    # instead of `%`. sum_ keeps `%` until that is understood; this pins the
    # program that showed it.
    from repro.api.backends import jnp_aa_slide
    rng = np.random.default_rng(0)
    cols = rng.integers(0, P, size=(27, 1, 256, 12, 69), dtype=np.uint32)
    pats = rng.integers(0, P, size=(27, 1, 4, 69), dtype=np.uint32)
    p64 = np.uint64(P)
    want = None
    for j in range(4):
        v = (cols[..., j:j + 9, :].astype(np.uint64)
             * pats[:, :, None, None, j, :] % p64).sum(-1) % p64
        want = v if want is None else want * v % p64
    assert np.array_equal(np.asarray(jnp_aa_slide(cols, pats)), want)
