"""The oblivious embedding fast path (§3.2.1 selection at serving scale).

Acceptance shape of the batched lookup engine:

* the fused path (ONE share program + ONE ``ss_matmul`` per shard) opens to
  EXACTLY the per-token ``private_lookup`` oracle — post-dequantize
  bit-identity, for S ∈ {1, 2, 4} shards across the Serial, Threaded and
  Mesh dispatchers (per-shard mod-p partial sums are exact, so S never
  shows in the opened values OR the ledgers);
* one ``EmbedLookup`` plan == one fused dispatch per shard, measured on the
  dataplane's own telemetry;
* the fixed-point codec round-trips exactly across the signed range and
  refuses (raises, never wraps) out-of-range tables;
* ``verify=True`` rides the OBSCURE-style redundant-share check: honest
  openings pass with a priced overhead, a tampered table share raises;
* two inline lookups never reuse a sharing key (the frequency-attack
  regression for the old hardcoded ``PRNGKey(0)``);
* the pallas fused share-generation kernel and the tall-skinny matmul
  tiling are bit-identical to the jnp reference programs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (EmbedLookup, MeshDispatcher, QueryClient,
                       ShardedRelation, ThreadedDispatcher,
                       estimate_embed_cost, get_backend)
from repro.core import shamir
from repro.core.costs import CostLedger
from repro.core.queries import embed as embed_q
from repro.models import private_embed as pe

V, D = 64, 16


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(5)
    return rng.uniform(-2.0, 2.0, (V, D)).astype(np.float32)


@pytest.fixture(scope="module")
def table_sh(table):
    return pe.setup_private_embed(jax.random.PRNGKey(5), table, n_shares=4)


def _client(table_sh, *, shards=1, dispatcher=None):
    client = QueryClient(key=3)
    client.attach(pe.as_embed_relation(table_sh), name="emb",
                  shards=shards, dispatcher=dispatcher)
    return client


def _oracle(table_sh, tokens):
    """Per-token reference: one private_lookup per id, same key stream as
    the batched engine (fold_in per position)."""
    outs = [np.asarray(pe.private_lookup(jax.random.fold_in(
        jax.random.PRNGKey(9), i), table_sh, jnp.asarray([t])))
        for i, t in enumerate(tokens)]
    return np.concatenate(outs)


# ---------------------------------------------------------------------------
# exactness: batched == per-token oracle == plain table row
# ---------------------------------------------------------------------------

def test_batched_matches_per_token_lookup(table, table_sh):
    toks = jnp.asarray([3, 3, 17, V - 1, 0], jnp.int32)
    got = pe.private_lookup_batched(jax.random.PRNGKey(1), table_sh, toks)
    want = np.stack([np.asarray(
        pe.private_lookup(jax.random.PRNGKey(2), table_sh,
                          jnp.asarray([t]))).reshape(D)
        for t in np.asarray(toks)])
    assert np.array_equal(np.asarray(got), want)      # sharing cancels
    # and both equal the quantized table rows exactly
    ref = embed_q.dequantize_from_field(
        embed_q.quantize_to_field(table))
    assert np.array_equal(np.asarray(got),
                          np.asarray(ref)[np.asarray(toks)])


def test_batched_keeps_token_shape(table_sh):
    toks = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    got = pe.private_lookup_batched(jax.random.PRNGKey(1), table_sh, toks)
    assert got.shape == (2, 3, D)


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("disp", ["serial", "threaded", "mesh"])
def test_engine_bit_identical_across_shards_and_dispatchers(
        table_sh, shards, disp):
    dispatcher = {"serial": None,
                  "threaded": ThreadedDispatcher(max_workers=2),
                  "mesh": MeshDispatcher()}[disp]
    client = _client(table_sh, shards=shards, dispatcher=dispatcher)
    tokens = tuple(int(t) for t in
                   np.random.default_rng(7).integers(0, V, 12))
    res = client.run(EmbedLookup(tokens=tokens), relation="emb")
    base = _client(table_sh).run(EmbedLookup(tokens=tokens),
                                 relation="emb")
    assert np.array_equal(np.asarray(res.embeddings),
                          np.asarray(base.embeddings))
    assert res.ledger == base.ledger          # S is execution policy only
    assert res.strategy == "embed"


def test_one_fused_dispatch_per_step_per_shard(table_sh):
    for shards in (1, 3):
        client = _client(table_sh, shards=shards,
                         dispatcher=MeshDispatcher())
        plane = client._entry("emb").dataplane
        client.run(EmbedLookup(tokens=(1, 2, 3)), relation="emb")
        placed = plane.stats.transfer_bytes
        d0 = plane.stats.dispatches
        client.run(EmbedLookup(tokens=(4, 5, 6, 7)), relation="emb")
        assert plane.stats.dispatches - d0 == shards
        assert plane.stats.transfer_bytes == placed   # device residency


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("disp", ["serial", "threaded", "mesh"])
def test_resident_digits_split_once_and_match_oracle(table_sh, shards, disp):
    # the jnp contraction reads the table's int8 digits, made once per
    # shard view; every lookup still opens to the per-token oracle
    dispatcher = {"serial": None,
                  "threaded": ThreadedDispatcher(max_workers=2),
                  "mesh": MeshDispatcher(strict_transfers=True)}[disp]
    client = _client(table_sh, shards=shards, dispatcher=dispatcher)
    plane = client._entry("emb").dataplane
    rng = np.random.default_rng(11)
    lookups, placed = 5, None
    for i in range(lookups):
        tokens = tuple(int(t) for t in rng.integers(0, V, 6))
        res = client.run(EmbedLookup(tokens=tokens), relation="emb")
        assert np.array_equal(np.asarray(res.embeddings),
                              _oracle(table_sh, tokens))
        if placed is None:
            placed = plane.stats.transfer_bytes
    assert plane.stats.table_splits == plane.n_shards == shards
    assert plane.stats.presplit_contractions == lookups * shards
    if disp == "mesh":
        assert plane.stats.transfer_bytes == placed   # device residency


def test_reattached_table_gets_fresh_digits(table, table_sh):
    other = pe.setup_private_embed(jax.random.PRNGKey(6), -table[::-1],
                                   n_shares=4)
    client = _client(table_sh, shards=2)
    tokens = (0, 5, V - 1)
    first = client.run(EmbedLookup(tokens=tokens), relation="emb")
    client.attach(pe.as_embed_relation(other), name="emb", shards=2)
    again = client.run(EmbedLookup(tokens=tokens), relation="emb")
    assert np.array_equal(np.asarray(first.embeddings),
                          _oracle(table_sh, tokens))
    assert np.array_equal(np.asarray(again.embeddings),
                          _oracle(other, tokens))
    assert not np.array_equal(np.asarray(first.embeddings),
                              np.asarray(again.embeddings))
    assert client._entry("emb").dataplane.stats.table_splits == 2


def test_placement_drops_digits_of_the_old_views(table, table_sh):
    # a plane that split its views and is then placed by a mesh
    # dispatcher (or has its db replaced) makes its digits anew
    other = pe.setup_private_embed(jax.random.PRNGKey(6), -table[::-1],
                                   n_shares=4)
    plane = ShardedRelation(pe.as_embed_relation(table_sh), shards=2)
    be = get_backend("jnp")

    def lookup(tokens):
        job = embed_q.EmbedJob(tokens=np.asarray(tokens),
                               key=jax.random.PRNGKey(4),
                               ledger=CostLedger())
        return embed_q.embed_phase(be, plane, [job])[0]

    tokens = (1, 2, 40)
    assert np.array_equal(lookup(tokens), _oracle(table_sh, tokens))
    plane.dispatcher = MeshDispatcher()
    lookup(tokens)                                # placed during this step
    assert np.array_equal(lookup(tokens), _oracle(table_sh, tokens))
    assert plane.stats.table_splits == 4          # before and after placing
    plane.db = pe.as_embed_relation(other)
    plane.clear_views()
    assert np.array_equal(lookup(tokens), _oracle(other, tokens))
    assert plane.stats.table_splits == 6
    assert plane.stats.presplit_contractions == 4 * 2


def test_pallas_backend_contracts_the_uint32_table(table_sh):
    pytest.importorskip("jax.experimental.pallas")
    client = QueryClient(key=3, backend="pallas")
    plane = client.attach(pe.as_embed_relation(table_sh), name="emb")
    tokens = (3, 9, V - 2)
    res = client.run(EmbedLookup(tokens=tokens), relation="emb")
    assert np.array_equal(np.asarray(res.embeddings),
                          _oracle(table_sh, tokens))
    assert plane.stats.table_splits == plane.stats.presplit_contractions == 0


def test_batch_of_jobs_fuses_and_matches_sequential(table_sh):
    plans = [EmbedLookup(tokens=(1, 2)), EmbedLookup(tokens=(3,)),
             EmbedLookup(tokens=(4, 5, 6))]
    bat_client = _client(table_sh, shards=2)
    plane = bat_client._entry("emb").dataplane
    d0 = plane.stats.dispatches
    bat = bat_client.run_batch(plans, relation="emb")
    assert plane.stats.dispatches - d0 == 2   # ALL jobs in S dispatches
    seq_client = _client(table_sh, shards=2)
    seq = [seq_client.run(p, relation="emb") for p in plans]
    for a, b in zip(seq, bat):
        assert np.array_equal(np.asarray(a.embeddings),
                              np.asarray(b.embeddings))
        assert a.ledger == b.ledger


def test_explain_matches_measured_ledger(table_sh):
    client = _client(table_sh)
    plan = EmbedLookup(tokens=tuple(range(9)), verify=True)
    exp = client.explain([plan], relation="emb")
    res = client.run(plan, relation="emb")
    (grp,) = exp.groups
    assert grp.estimate.bits == res.ledger.communication_bits
    assert grp.estimate.rounds == res.ledger.rounds


def test_estimate_embed_cost_shape():
    from repro.api import DBStats
    stats = DBStats(n=V, m=D, c=4, w=8, a=64, shards=2)
    est = estimate_embed_cost(stats, n_tokens=8)
    assert est.rounds == 1 and est.dispatches == 2
    assert est.bits == (4 * 8 * V + 4 * 8 * D) * 31
    ver = estimate_embed_cost(stats, n_tokens=8, verify=True)
    assert ver.rounds == 2 and ver.bits > est.bits


# ---------------------------------------------------------------------------
# plan validation
# ---------------------------------------------------------------------------

def test_embed_plan_validates_tokens():
    with pytest.raises(ValueError):
        EmbedLookup(tokens=())
    with pytest.raises(ValueError):
        EmbedLookup(tokens=(1, -2))
    assert EmbedLookup(tokens=[np.int64(3), 1]).tokens == (3, 1)


def test_engine_rejects_out_of_range_tokens(table_sh):
    client = _client(table_sh)
    with pytest.raises(ValueError, match="out of range"):
        client.run(EmbedLookup(tokens=(0, V)), relation="emb")


def test_engine_rejects_non_embedding_relation():
    from repro.core import outsource
    from repro.data import synthetic_relation
    db = outsource(jax.random.PRNGKey(0), synthetic_relation(8, seed=0),
                   n_shares=4, degree=1)
    client = QueryClient(db, key=1)
    with pytest.raises(ValueError, match="embedding relation"):
        client.run(EmbedLookup(tokens=(1,)))


# ---------------------------------------------------------------------------
# fixed-point codec: exact round-trip inside the range, refusal outside
# ---------------------------------------------------------------------------

def test_fixed_point_round_trip_at_signed_edges():
    scale = embed_q.QUANT_SCALE
    edges = np.asarray([0.0, 1.0 / scale, -1.0 / scale,
                        embed_q.QUANT_RANGE, -embed_q.QUANT_RANGE,
                        embed_q.QUANT_RANGE - 1.0 / scale,
                        -(embed_q.QUANT_RANGE - 1.0 / scale)],
                       np.float32)
    back = embed_q.dequantize_from_field(embed_q.quantize_to_field(edges))
    assert np.array_equal(np.asarray(back), edges)   # exact, not approx


def test_fixed_point_half_ulp_rounds_to_nearest():
    ulp = 1.0 / embed_q.QUANT_SCALE
    x = np.asarray([0.49999 * ulp, 1.50001 * ulp, -0.49999 * ulp],
                   np.float32)
    back = np.asarray(embed_q.dequantize_from_field(
        embed_q.quantize_to_field(x)))
    assert np.array_equal(back, np.asarray([0.0, 2 * ulp, 0.0], np.float32))


def test_fixed_point_error_bound_random():
    rng = np.random.default_rng(11)
    x = rng.uniform(-embed_q.QUANT_RANGE, embed_q.QUANT_RANGE,
                    1024).astype(np.float32)
    back = np.asarray(embed_q.dequantize_from_field(
        embed_q.quantize_to_field(x)))
    assert np.abs(back - x).max() <= 0.5 / embed_q.QUANT_SCALE + 1e-7


def test_overflow_guard_refuses_out_of_range_tables():
    for bad in (embed_q.QUANT_RANGE * 1.01, -embed_q.QUANT_RANGE * 1.01):
        with pytest.raises(ValueError, match="fixed-point range"):
            embed_q.quantize_to_field(np.asarray([0.0, bad], np.float32))
    with pytest.raises(ValueError, match="fixed-point range"):
        pe.setup_private_embed(jax.random.PRNGKey(0),
                               np.full((4, 4), 100.0, np.float32))


# ---------------------------------------------------------------------------
# verification (OBSCURE-style redundant shares)
# ---------------------------------------------------------------------------

def test_verify_passes_honest_and_prices_overhead(table_sh):
    client = _client(table_sh)
    base = client.run(EmbedLookup(tokens=(1, 2, 3)), relation="emb")
    ver = client.run(EmbedLookup(tokens=(1, 2, 3), verify=True),
                     relation="emb")
    assert np.array_equal(np.asarray(ver.embeddings),
                          np.asarray(base.embeddings))
    assert ver.ledger.rounds == base.ledger.rounds + 1
    assert ver.ledger.communication_bits > base.ledger.communication_bits


def test_verify_catches_tampered_table_share(table):
    table_sh = pe.setup_private_embed(jax.random.PRNGKey(5), table,
                                      n_shares=5)
    vals = np.asarray(table_sh.values).copy()
    vals[4, 7, 3] ^= 1                      # cloud 4 lies about one word
    bad = shamir.Shares(jnp.asarray(vals), table_sh.degree)
    client = _client(bad)
    with pytest.raises(embed_q.VerificationError):
        client.run(EmbedLookup(tokens=(7,), verify=True), relation="emb")
    # without verify the lie goes unnoticed — that's what the check buys
    client2 = _client(bad)
    client2.run(EmbedLookup(tokens=(7,)), relation="emb")


def test_batched_verify_flag(table_sh):
    got = pe.private_lookup_batched(jax.random.PRNGKey(1), table_sh,
                                    jnp.asarray([1, 2], jnp.int32),
                                    verify=True)
    want = pe.private_lookup_batched(jax.random.PRNGKey(1), table_sh,
                                     jnp.asarray([1, 2], jnp.int32))
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# satellite 1: inline lookups never reuse a sharing key
# ---------------------------------------------------------------------------

def test_inline_lookup_keys_never_repeat(table):
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=D,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=V,
                      dtype="float32", private_embed=True)
    params = {"embed": jnp.asarray(table)}
    k1 = pe._next_inline_key(params)
    k2 = pe._next_inline_key(params)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    # and the share tensors those keys produce differ (fresh polynomials)
    sh1 = embed_q.share_tokens(k1, jnp.asarray([3], jnp.int32),
                               vocab=V, n_shares=4)
    sh2 = embed_q.share_tokens(k2, jnp.asarray([3], jnp.int32),
                               vocab=V, n_shares=4)
    assert not np.array_equal(np.asarray(sh1.values),
                              np.asarray(sh2.values))
    # while the *opened* value is key-independent
    out1 = pe.private_lookup_inline(params, cfg, jnp.asarray([[3]]))
    out2 = pe.private_lookup_inline(params, cfg, jnp.asarray([[3]]))
    assert np.array_equal(np.asarray(out1), np.asarray(out2))


def test_inline_lookup_threads_explicit_key(table):
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=D,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=V,
                      dtype="float32", private_embed=True)
    params = {"embed": jnp.asarray(table)}
    out = pe.private_lookup_inline(params, cfg, jnp.asarray([[3, 5]]),
                                   key=jax.random.PRNGKey(42))
    ref = embed_q.dequantize_from_field(
        embed_q.quantize_to_field(jnp.asarray(table)))
    assert np.allclose(np.asarray(out), np.asarray(ref)[[3, 5]],
                       atol=1e-6)


# ---------------------------------------------------------------------------
# share generation: jnp program vs pallas fused kernel
# ---------------------------------------------------------------------------

def test_share_tokens_opens_to_onehot():
    key = jax.random.PRNGKey(8)
    toks = jnp.asarray([0, 5, V - 1], jnp.int32)
    sh = embed_q.share_tokens(key, toks, vocab=V, n_shares=4)
    assert sh.degree == 1 and sh.values.shape == (4, 3, V)
    opened = np.asarray(shamir.interpolate(sh))
    assert np.array_equal(opened, np.asarray(
        jax.nn.one_hot(toks, V, dtype=jnp.uint32)))


def test_share_tokens_rejects_empty():
    with pytest.raises(ValueError):
        embed_q.share_tokens(jax.random.PRNGKey(0), jnp.asarray([]),
                             vocab=V, n_shares=4)


def test_pallas_share_onehot_bit_identical():
    pytest.importorskip("jax.experimental.pallas")
    from repro.kernels.ss_matmul import share_onehot_pallas
    key = jax.random.PRNGKey(8)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 200, 70),
                       jnp.int32)
    a1 = embed_q.token_coeffs(key, toks, vocab=200)
    want = embed_q.share_tokens(key, toks, vocab=200, n_shares=4).values
    got = share_onehot_pallas(toks, a1, n_shares=4, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_pallas_backend_share_tokens_bit_identical():
    pytest.importorskip("jax.experimental.pallas")
    from repro.api.backends import get_backend
    key = jax.random.PRNGKey(8)
    toks = jnp.asarray([1, 2, 3, 4, 5], jnp.int32)
    jnp_sh = embed_q.share_tokens(key, toks, vocab=V, n_shares=4,
                                  be=get_backend("jnp"))
    pl_sh = embed_q.share_tokens(key, toks, vocab=V, n_shares=4,
                                 be=get_backend("pallas"))
    assert np.array_equal(np.asarray(jnp_sh.values),
                          np.asarray(pl_sh.values))


def test_tall_skinny_kernel_parity():
    pytest.importorskip("jax.experimental.pallas")
    from repro.core import field
    from repro.kernels.ss_matmul import is_tall_skinny, ss_matmul_tall_pallas
    assert is_tall_skinny(32, 2048, 64)
    assert not is_tall_skinny(512, 2048, 64)      # M too big
    assert not is_tall_skinny(32, 512, 64)        # K too small
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, field.P, (17, 1536), np.uint32))
    b = jnp.asarray(rng.integers(0, field.P, (1536, 40), np.uint32))
    got = ss_matmul_tall_pallas(a, b, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(field.matmul(a, b)))


def test_interpret_autodetect_default():
    from repro.kernels import ss_matmul as k
    # on anything but a real TPU the default must resolve to interpret
    a = jnp.zeros((8, 128), jnp.uint32)
    b = jnp.zeros((128, 8), jnp.uint32)
    out = k.ss_matmul_pallas(a, b)        # interpret=None — must not raise
    assert out.shape == (8, 8)


# ---------------------------------------------------------------------------
# serving: EmbedLookup routes through the multi-tenant QueryServer
# ---------------------------------------------------------------------------

def test_query_server_routes_embed_family(table_sh):
    from repro.core import outsource
    from repro.data import synthetic_relation
    from repro.launch.serve import QueryServer
    from repro.api import Count, Eq
    from repro.core import Codec
    rows = synthetic_relation(8, seed=0)
    db = outsource(jax.random.PRNGKey(0), rows, codec=Codec(word_length=8),
                   n_shares=20, degree=1)
    pat = rows[0][1]
    with QueryServer() as srv:
        srv.attach("emp", db)
        srv.attach("emb", pe.as_embed_relation(table_sh))
        r_emb = srv.submit(EmbedLookup(tokens=(2, 4)), relation="emb")
        r_cnt = srv.submit(Count(Eq(1, pat)), relation="emp")
        srv.pump(relation="emb")
        srv.pump(relation="emp")
        emb = r_emb.wait(timeout=30).result
        cnt = r_cnt.wait(timeout=30).result
    assert emb.embeddings.shape == (2, D)
    solo = _client(table_sh).run(EmbedLookup(tokens=(2, 4)),
                                 relation="emb")
    assert np.array_equal(np.asarray(emb.embeddings),
                          np.asarray(solo.embeddings))
    assert emb.ledger == solo.ledger          # tenant == solo, bit for bit
    assert cnt.count >= 1
    assert srv.stats.batches >= 2


def test_query_server_counts_splits_at_warmup_only(table_sh):
    # after a warm-up batch has split the table, a served window makes no
    # digits and every embed step contracts the resident ones
    from repro.launch.serve import QueryServer
    shards = 2
    with QueryServer(key=1) as srv:
        srv.attach("emb", pe.as_embed_relation(table_sh), shards=shards)
        srv.submit(EmbedLookup(tokens=(1,)), relation="emb")
        srv.pump(relation="emb")
        assert srv.stats.snapshot()["table_splits"] == shards
        srv.reset()
        for i in range(3):
            handles = [srv.submit(EmbedLookup(tokens=(i, 2 * i + 1)),
                                  relation="emb") for _ in range(2)]
            srv.pump(relation="emb")
            for h in handles:
                assert h.wait(timeout=30).result is not None
        snap = srv.stats.snapshot()
    rel = snap["relations"]["emb"]
    assert rel["batches"] == 3
    assert snap["table_splits"] == rel["table_splits"] == 0
    assert snap["presplit_contractions"] == rel["presplit_contractions"] \
        == 3 * shards
