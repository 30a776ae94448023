"""Program spans and named copy programs.

A batch served through ``QueryServer`` under the JAX profiler leaves the
program's spans on the trace's host plane: the scheduler's ``serve.park``
and ``serve.batch``, and inside each batch the planner's ``client.plan``,
the user's ``user.share``/``user.open`` and the clouds' ``cloud.<phase>``.
``snapshot()["span_s"]`` holds the same names. The round engine's copies
run as named programs, and tracing changes no result.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Count, EmbedLookup, Eq, QueryClient, Select
from repro.core import Codec, outsource
from repro.core.dataplane import ThreadedDispatcher
from repro.core.queries import rounds
from repro.data import synthetic_relation
from repro.launch.serve import QueryServer
from repro.models import private_embed as pe

BATCH_SPANS = ("client.plan", "user.share", "user.open", "cloud.match",
               "cloud.fetch", "cloud.embed")
SERVER_SPANS = ("serve.park", "serve.batch")


@pytest.fixture(scope="module")
def relations():
    rows = synthetic_relation(8, seed=0)
    db = outsource(jax.random.PRNGKey(0), rows, codec=Codec(word_length=8),
                   n_shares=20, degree=1)
    table = np.random.default_rng(5).uniform(-2, 2, (64, 16))
    table_sh = pe.setup_private_embed(jax.random.PRNGKey(5),
                                      table.astype(np.float32), n_shares=4)
    return rows, db, pe.as_embed_relation(table_sh)


def _serve(relations, trace_dir=None):
    """A count and a one-round select on one relation and an embedding
    lookup on another, through the scheduler; returns (results, span_s)."""
    rows, db, emb = relations
    pat = rows[0][1]
    srv = QueryServer(max_wait_ms=30)
    srv.attach("emp", db, key=1)
    srv.attach("emb", emb, key=2)
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        with srv:
            reqs = [srv.submit(Count(Eq(1, pat)), relation="emp"),
                    srv.submit(Select(Eq(1, pat), strategy="one_round"),
                               relation="emp"),
                    srv.submit(EmbedLookup(tokens=(2, 4, 9)),
                               relation="emb")]
            results = [r.wait(timeout=120).result for r in reqs]
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return results, srv.stats.snapshot()["span_s"]


def _host_events(trace_dir):
    """(thread line, name, start, end) of every host event of the trace."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(ln.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in data.planes if p.name.startswith("/host")
            for ln in p.lines for e in ln.events]


def test_spans_nest_under_the_batch_and_match_the_snapshot(relations,
                                                           tmp_path):
    _, span_s = _serve(relations, str(tmp_path))
    events = _host_events(str(tmp_path))
    names = {name for _, name, _, _ in events}
    for name in SERVER_SPANS + BATCH_SPANS:
        assert name in names, name
        assert span_s[name] > 0.0, name
    batches = [(line, lo, hi) for line, name, lo, hi in events
               if name == "serve.batch"]
    for line, name, lo, hi in events:
        if name in BATCH_SPANS:
            assert any(line == b_line and b_lo <= lo and hi <= b_hi
                       for b_line, b_lo, b_hi in batches), name


def test_tracing_changes_no_result(relations, tmp_path):
    plain, _ = _serve(relations)
    traced, _ = _serve(relations, str(tmp_path))
    for a, b in zip(plain, traced):
        assert a.ledger == b.ledger
        assert a.count == b.count
        assert a.rows == b.rows
        assert a.addresses == b.addresses
    assert np.array_equal(np.asarray(plain[2].embeddings),
                          np.asarray(traced[2].embeddings))


def test_a_fused_wave_charges_each_plane_an_equal_share(relations):
    rows, db, _ = relations
    plan = Select(Eq(1, rows[0][1]), strategy="one_round")
    pool = ThreadedDispatcher(max_workers=2)
    client = QueryClient()
    pa = client.attach(db, name="a", shards=2, key=1,
                       dispatcher=pool.handle())
    pb = client.attach(db, name="b", shards=2, key=2,
                       dispatcher=pool.handle())
    client.run_batch_multi([("a", [plan]), ("b", [plan])])
    pool.close()
    assert pa.stats.fused_steps == pb.stats.fused_steps == 1
    assert pa.stats.span_s["cloud.fetch"] > 0.0
    assert pa.stats.span_s["cloud.fetch"] == pb.stats.span_s["cloud.fetch"]


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


_REL = _u32(3, 8, 4, 6, 5)          # (c, n, m, W, A)
_COLS = _u32(3, 2, 8, 6, 5)         # (c, B, n, W, A)
_WIN = _u32(3, 2, 8, 3)             # (c, B, n, M)


@pytest.mark.parametrize("fn, args, kwargs", [
    (rounds.stack_columns, (_REL, (1, 1)), {"axis": 2}),
    (rounds.stack_columns, ((_u32(3, 8, 7), _u32(3, 8, 7)), (1, 0)),
     {"axis": 1}),
    (rounds.fetch_relayout, (_REL,), {}),
    (rounds.prefix_tile, (_COLS,), {"k": 2}),
    (rounds.window_bits, (_WIN, _COLS), {"suf": (0,), "con": (1,), "k": 4}),
    (rounds.stack_onehots, ((_u32(3, 2, 8), _u32(3, 5, 8)),), {}),
], ids=["stack_columns", "stack_columns_numeric", "fetch_relayout",
        "prefix_tile", "window_bits", "stack_onehots"])
def test_copy_programs_lower_under_their_names(fn, args, kwargs):
    text = fn.lower(*args, **kwargs).as_text()
    name = fn.__name__
    assert f"module @jit_{name} " in text


def test_stack_columns_matches_the_eager_stack():
    rel = jax.random.randint(jax.random.PRNGKey(0), (3, 8, 4, 6, 5), 0, 99,
                             jnp.int32).astype(jnp.uint32)
    one = rel[:, :, 2]
    want_bcast = jnp.broadcast_to(one[:, None], (3, 3) + one.shape[1:])
    got = rounds.stack_columns(rel, (2, 2, 2), axis=2)
    assert np.array_equal(np.asarray(got), np.asarray(want_bcast))
    want_gather = jnp.moveaxis(rel[:, :, np.asarray([3, 0, 3])], 2, 1)
    got = rounds.stack_columns(rel, (3, 0, 3), axis=2)
    assert np.array_equal(np.asarray(got), np.asarray(want_gather))
    cols = (rel[:, :, 0, :, 0], rel[:, :, 1, :, 0])
    got = rounds.stack_columns(cols, (1, 0, 1), axis=1)
    want = jnp.stack([cols[1], cols[0], cols[1]], axis=1)
    assert np.array_equal(np.asarray(got), np.asarray(want))
