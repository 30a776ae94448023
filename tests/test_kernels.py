"""Pallas kernel validation: shape sweeps vs pure-jnp oracles (exact match).

Field arithmetic is exact (no tolerance): any mismatch is a bug, so we use
array_equal, the strictest possible allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

P = 2**31 - 1
RNG = np.random.default_rng(42)


def rand_f(shape):
    return RNG.integers(0, P, size=shape, dtype=np.uint32)


# ---------------------------------------------------------------------------
# ss_matmul sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (8, 8, 8), (7, 13, 5), (128, 128, 128), (129, 127, 130),
    (3, 300, 2), (256, 64, 192), (37, 53, 29), (200, 1, 200),
])
def test_ss_matmul_shapes(m, k, n):
    a, b = rand_f((m, k)), rand_f((k, n))
    got = np.asarray(ops.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(ref.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, want)


def test_ss_matmul_extreme_values():
    """p-1 everywhere: worst case for limb overflow."""
    a = np.full((64, 96), P - 1, dtype=np.uint32)
    b = np.full((96, 64), P - 1, dtype=np.uint32)
    got = np.asarray(ops.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    want = (pow(P - 1, 2, P) * 96) % P
    assert np.all(got == want)


def test_ss_matmul_identity():
    n = 50
    eye = np.eye(n, dtype=np.uint32)
    x = rand_f((n, n))
    got = np.asarray(ops.ss_matmul(jnp.asarray(eye), jnp.asarray(x)))
    assert np.array_equal(got, x)


def test_ss_matmul_batched():
    a, b = rand_f((4, 17, 33)), rand_f((4, 33, 9))
    got = np.asarray(ops.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    for i in range(4):
        want = np.asarray(ref.ss_matmul(jnp.asarray(a[i]), jnp.asarray(b[i])))
        assert np.array_equal(got[i], want)


def test_ss_matmul_vs_bigint_oracle():
    """Double-check the jnp oracle itself against python bigints."""
    a, b = rand_f((9, 21)), rand_f((21, 6))
    want = (a.astype(object) @ b.astype(object)) % P
    got = np.asarray(ops.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got.astype(object), want)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 40), st.integers(1, 40), st.integers(1, 40))
def test_ss_matmul_property(m, k, n):
    a, b = rand_f((m, k)), rand_f((k, n))
    got = np.asarray(ops.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(ref.ss_matmul(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# aa_match sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,w,a", [
    (1, 1, 1), (8, 4, 16), (45, 6, 17), (512, 12, 69), (513, 8, 26),
    (100, 16, 128), (3, 2, 300),
])
def test_aa_match_shapes(n, w, a):
    col, pat = rand_f((n, w, a)), rand_f((w, a))
    got = np.asarray(ops.aa_match(jnp.asarray(col), jnp.asarray(pat)))
    want = np.asarray(ref.aa_match(jnp.asarray(col), jnp.asarray(pat)))
    assert np.array_equal(got, want)


def test_aa_match_onehot_semantics():
    """With real one-hots the kernel must return exact 0/1 matches."""
    from repro.core.encoding import Codec
    codec = Codec(word_length=6)
    col = codec.encode_column(["John", "Adam", "John", "Eve"])
    pat = codec.encode_word("John")
    got = np.asarray(ops.aa_match(jnp.asarray(col), jnp.asarray(pat)))
    assert got.tolist() == [1, 0, 1, 0]


def test_aa_match_batched_clouds():
    col, pat = rand_f((3, 20, 5, 11)), rand_f((3, 5, 11))
    got = np.asarray(ops.aa_match(jnp.asarray(col), jnp.asarray(pat)))
    for c in range(3):
        want = np.asarray(ref.aa_match(jnp.asarray(col[c]),
                                       jnp.asarray(pat[c])))
        assert np.array_equal(got[c], want)


# ---------------------------------------------------------------------------
# stacked-predicate batch kernel: 2-D grid == nested vmap of the jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c,b,n,w,a", [
    (1, 1, 1, 1, 1), (2, 3, 20, 5, 11), (3, 4, 45, 6, 17),
    (2, 2, 513, 4, 26), (4, 1, 37, 8, 26),
])
def test_aa_match_batch_grid_equals_vmap(c, b, n, w, a):
    col, pat = rand_f((c, b, n, w, a)), rand_f((c, b, w, a))
    got = np.asarray(ops.aa_match_batch(jnp.asarray(col), jnp.asarray(pat)))
    want = np.asarray(jax.vmap(jax.vmap(ref.aa_match))(jnp.asarray(col),
                                                       jnp.asarray(pat)))
    assert got.shape == (c, b, n)
    assert np.array_equal(got, want)


def test_aa_match_batch_grid_vs_ref_oracle():
    c, b = 2, 3
    col, pat = rand_f((c, b, 45, 5, 11)), rand_f((c, b, 5, 11))
    got = np.asarray(ops.aa_match_batch(jnp.asarray(col), jnp.asarray(pat)))
    for i in range(c):
        for j in range(b):
            want = np.asarray(ref.aa_match(jnp.asarray(col[i, j]),
                                           jnp.asarray(pat[i, j])))
            assert np.array_equal(got[i, j], want)


# ---------------------------------------------------------------------------
# SS-SUB ripple bit step: pallas kernel == jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1,), (7,), (3, 5, 40), (2, 6, 64)])
def test_ripple_carry_pallas_equals_jnp(shape):
    from repro.api.backends import jnp_ripple_carry
    a, b, carry = rand_f(shape), rand_f(shape), rand_f(shape)
    ja, jb, jc = jnp.asarray(a), jnp.asarray(b), jnp.asarray(carry)
    # LSB (init) step
    rb_p, co_p = ops.ripple_carry(ja, jb, None)
    rb_j, co_j = jnp_ripple_carry(ja, jb, None)
    assert np.array_equal(np.asarray(rb_p), np.asarray(rb_j))
    assert np.array_equal(np.asarray(co_p), np.asarray(co_j))
    # propagate step
    rb_p, co_p = ops.ripple_carry(ja, jb, jc)
    rb_j, co_j = jnp_ripple_carry(ja, jb, jc)
    assert np.array_equal(np.asarray(rb_p), np.asarray(rb_j))
    assert np.array_equal(np.asarray(co_p), np.asarray(co_j))


def test_ripple_carry_bigint_oracle():
    """One full ripple over both kernels must equal a python-int subtract
    sign on shares of real bit patterns (degree-0 'sharing' of the bits so
    the share-space math IS the plaintext math)."""
    t = 9
    for (x, bound) in [(12, 100), (255, 13), (5, 5), (-7, 3)]:
        xb = [(x >> i) & 1 if x >= 0 else ((x + (1 << t)) >> i) & 1
              for i in range(t)]
        bb = [(bound >> i) & 1 for i in range(t)]
        a_bits = jnp.asarray(np.asarray(xb, np.uint32)[None])   # A = x
        b_bits = jnp.asarray(np.asarray(bb, np.uint32)[None])   # B = bound
        rb, carry = ops.ripple_carry(a_bits[..., 0], b_bits[..., 0], None)
        for i in range(1, t):
            rb, carry = ops.ripple_carry(a_bits[..., i], b_bits[..., i],
                                         carry)
        want = 1 if (bound - x) < 0 else 0      # sign bit of B − A
        assert int(np.asarray(rb)[0]) == want, (x, bound)


# ---------------------------------------------------------------------------
# kernels wired into the query suite ≡ jnp implementation
# ---------------------------------------------------------------------------

def test_count_query_pallas_equals_jnp():
    from repro.core import outsource, Codec
    from repro.core.queries import count_query
    rows = [["a", "John"], ["b", "Eve"], ["c", "John"], ["d", "Dan"]]
    db = outsource(jax.random.PRNGKey(0), rows, codec=Codec(word_length=6),
                   n_shares=16)
    got_p, _ = count_query(jax.random.PRNGKey(1), db, 1, "John",
                           impl="pallas")
    got_j, _ = count_query(jax.random.PRNGKey(1), db, 1, "John", impl="jnp")
    assert got_p == got_j == 2


def test_select_fetch_pallas_equals_jnp():
    from repro.core import outsource, Codec
    from repro.core.queries import select_one_round
    rows = [["a", "x1"], ["b", "x2"], ["c", "x1"], ["d", "x3"]]
    db = outsource(jax.random.PRNGKey(2), rows, codec=Codec(word_length=6),
                   n_shares=16)
    rp, ap, _ = select_one_round(jax.random.PRNGKey(3), db, 1, "x1",
                                 impl="pallas")
    rj, aj, _ = select_one_round(jax.random.PRNGKey(3), db, 1, "x1",
                                 impl="jnp")
    assert rp == rj and ap == aj == [0, 2]


def test_pkfk_join_pallas_equals_jnp():
    from repro.core import outsource, Codec
    from repro.core.queries import pkfk_join
    codec = Codec(word_length=6)
    X = [["a1", "b1"], ["a2", "b2"]]
    Y = [["b1", "c1"], ["b2", "c2"], ["b2", "c3"]]
    dbX = outsource(jax.random.PRNGKey(4), X, codec=codec, n_shares=16)
    dbY = outsource(jax.random.PRNGKey(5), Y, codec=codec, n_shares=16)
    rp, _ = pkfk_join(dbX, dbY, 1, 0, impl="pallas")
    rj, _ = pkfk_join(dbX, dbY, 1, 0, impl="jnp")
    assert rp == rj
