#!/usr/bin/env python3
"""Bring-up smoke test: the query server end to end on a TPU.

    python3 chip_smoke.py              # one chip: relation + embedding phases
    python3 chip_smoke.py --chips 4    # four chips: mesh parity phase only

One process drives the chip through the entry points a user calls:
``QueryServer`` -> ``QueryClient.run_batch`` -> round engine -> backend ops
and Pallas kernels -> ``MeshDispatcher``. Every answer is checked against a
plain numpy reference over the cleartext rows.

* Relation phase (once per backend, ``jnp`` then ``pallas``): a seeded
  relation of 8,192 rows — four unary columns at the default codec
  (W=12, A=69), one of them also shared as a 14-bit two's-complement number —
  outsourced to 27 clouds at degree 1 and served by a ``QueryServer`` over a
  ``MeshDispatcher``: count, select (one_round / tree / one_tuple), LIKE
  prefix and substring, range count/select, PK/FK and equi joins, and
  SUM/AVG/MIN (one verified).
* Embedding phase (both backends): ``EmbedLookup`` of one 256-token decode
  step over a seeded table of gemma3-1b's embedding shape (262,144 x 1,152)
  shared to 4 clouds; the opened rows must equal the quantized table rows.
* ``--chips 4``: the relation plans (jnp backend) over 256 rows of the same
  widths on a four-chip mesh (data=4, then data=2 x model=2), rows and cost
  ledgers compared bit for bit with a ``SerialDispatcher`` client in the
  same process.

Earlier lines report each phase as JSON (result, compile and wall seconds,
peak device memory, relation size). The last line is
``{"ok": true, "device": {...}}`` and appears only when every phase passed;
the exit code is nonzero on any failure, and when no TPU is found.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` beside this file.
"""
import argparse
import gc
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))

N_SHARES = 27     # W=12 at degree 1: a fetch opens degree 25, verify +2
# 16,384 tuples do not fit 16 GB beside the Pallas relayout temporaries
RELATION_TUPLES = 8_192
# the four-chip phase compiles every program three times (serial client,
# two mesh layouts), and compile time grows with shape: widths stay real,
# the row count is cut to keep the call short
MESH_TUPLES = 256
SEED = 0
T_BITS = 14
V_MAX = (1 << (T_BITS - 2)) - 1   # SS-SUB range: differences fit t-1 bits
N_NAMES = 512                 # Name cardinality: ~n/512 rows per name
SYL = ["al", "be", "ga", "de", "ep", "ze", "et", "th", "io", "ka", "la",
       "mu", "nu", "xi", "om", "pi", "rh", "si", "ta", "up", "ph", "ch", "ps"]
VOCAB, D_MODEL, EMBED_SHARES, STEP_TOKENS = 262_144, 1_152, 4, 256


def log(**fields):
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# seeded data and the cleartext reference
# ---------------------------------------------------------------------------

def make_rows(n, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    vals = rng.integers(-V_MAX, V_MAX + 1, size=n)
    rows = []
    for i in range(n):
        j = int(perm[i])
        word = SYL[j % 23] + SYL[(j // 23) % 23] + SYL[(j // 529) % 23]
        rows.append([f"k{i:06d}", f"nm{j % N_NAMES}", word, str(vals[i])])
    return rows


def relation_plans(rows, right_pk, right_eq):
    """(label, plan, check) triples; ``check(result)`` compares the answer
    with the numpy reference over the cleartext rows."""
    import numpy as np
    from repro.api import (Aggregate, Between, Count, Eq, Join, Like, Padding,
                           RangeCount, RangeSelect, Select)
    ids = np.array([r[0] for r in rows])
    names = np.array([r[1] for r in rows])
    words = [r[2] for r in rows]
    vals = np.array([int(r[3]) for r in rows])

    def where(mask):
        idx = [int(i) for i in np.flatnonzero(mask)]
        return idx, [rows[i] for i in idx]

    def select_check(mask):
        idx, want = where(mask)
        return lambda r: r.addresses == idx and r.rows == want

    name_a, name_b, name_c = "nm3", "nm77", "nm200"
    lo, hi = int(np.sort(vals)[40]), int(np.sort(vals)[55])
    pk_by_id = {r[0]: r for r in rows}
    pk_want = [pk_by_id[y[0]] + y[1:] for y in right_pk[0]
               if y[0] in pk_by_id]
    eq_want = sorted(tuple(x + y[1:]) for y in right_eq[0] for x in rows
                     if x[1] == y[0])
    in_b = names == name_b
    return [
        ("count", Count(Eq("Name", name_a)),
         lambda r: r.count == int((names == name_a).sum())),
        ("select_one_round", Select(Eq("Name", name_b),
                                    strategy="one_round"),
         select_check(in_b)),
        ("select_tree", Select(Eq("Name", name_c), strategy="tree"),
         select_check(names == name_c)),
        ("select_one_tuple", Select(Eq("Id", str(ids[123])),
                                    strategy="one_tuple"),
         lambda r: r.rows == [rows[123]]),
        ("like_prefix", Count(Like("Word", "ka%")),
         lambda r: r.count == sum(w.startswith("ka") for w in words)),
        ("like_substring", Count(Like("Word", "%omth%")),
         lambda r: r.count == sum("omth" in w for w in words)),
        ("range_count", RangeCount(Between("Val", lo, hi), reduce_every=2),
         lambda r: r.count == int(((vals >= lo) & (vals <= hi)).sum())),
        ("range_select", RangeSelect(Between("Val", lo, hi), reduce_every=2),
         select_check((vals >= lo) & (vals <= hi))),
        ("join_pkfk", Join(right=right_pk[1], on=("Id", "Id"), kind="pkfk"),
         lambda r: r.rows == pk_want),
        ("join_equi", Join(right=right_eq[1], on=("Name", "Name"),
                           kind="equi", padding=Padding.fake_values(1)),
         lambda r: sorted(map(tuple, r.rows)) == eq_want),
        ("sum_verified", Aggregate("sum", "Val", where=Eq("Name", name_b),
                                   verify=True),
         lambda r: r.value == int(vals[in_b].sum())),
        ("avg", Aggregate("avg", "Val", where=Eq("Name", name_b)),
         lambda r: abs(r.value - float(vals[in_b].mean())) < 1e-9),
        ("min", Aggregate("min", "Val", reduce_every=2),
         lambda r: r.value == int(vals.min())),
    ]


def build_relation(n, seed):
    """Outsource the seeded relation and its two join partners."""
    import jax
    from repro.core import Codec, outsource
    codec = Codec()
    rows = make_rows(n, seed)
    db = outsource(jax.random.PRNGKey(seed), rows,
                   column_names=["Id", "Name", "Word", "Val"], codec=codec,
                   n_shares=N_SHARES, degree=1,
                   numeric_columns={3: T_BITS})
    # PK/FK partner: children of every 40th parent, plus dangling ones
    pk_rows = [[rows[(37 * i) % n][0] if i % 5 else f"x{i:06d}", f"t{i}"]
               for i in range(300)]
    # equi partner: mostly names absent on the left, a few present twice
    eq_rows = [[f"nm{N_NAMES + i}", f"g{i}"] for i in range(296)]
    eq_rows += [["nm3", "ga"], ["nm3", "gb"], ["nm9", "gc"], ["nm9", "gd"]]
    right_pk = outsource(jax.random.PRNGKey(seed + 1), pk_rows,
                         column_names=["Id", "Task"], codec=codec,
                         n_shares=N_SHARES, degree=1)
    right_eq = outsource(jax.random.PRNGKey(seed + 2), eq_rows,
                         column_names=["Name", "Tag"], codec=codec,
                         n_shares=N_SHARES, degree=1)
    return rows, db, (pk_rows, right_pk), (eq_rows, right_eq)


def relation_bytes(db):
    return int(db.relation.values.nbytes
               + sum(s.values.nbytes for s in db.numeric.values()))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class CompileClock:
    """Backend compile seconds and count, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.count = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_and_check(server, jobs, relation=None):
    """Submit every plan, wait, and return the labels that failed."""
    reqs = [(label, check, server.submit(plan, relation=relation))
            for label, plan, check in jobs]
    bad = []
    for label, check, req in reqs:
        req.wait()
        if req.error is not None:
            bad.append(f"{label}: {type(req.error).__name__}: {req.error}")
        elif not check(req.result):
            bad.append(f"{label}: answer differs from the reference")
    if server.stats.failed:
        bad.append(f"ServeStats.failed = {server.stats.failed}")
    return bad


def relation_phase(clock, n, seed):
    """Both backends over one outsourced relation, each behind its own
    server; the relation is released when this returns."""
    from repro.api import MeshDispatcher
    from repro.launch.serve import QueryServer
    t0 = time.perf_counter()
    rows, db, right_pk, right_eq = build_relation(n, seed)
    setup_s = time.perf_counter() - t0
    jobs = relation_plans(rows, right_pk, right_eq)
    ok_all = True
    for backend in ("jnp", "pallas"):
        t1, (c0, k0) = time.perf_counter(), clock.mark()
        server = QueryServer(db, key=seed, backend=backend,
                             dispatcher=MeshDispatcher(), max_wait_ms=50.0)
        with server:
            bad = serve_and_check(server, jobs)
        del server
        gc.collect()
        wall = time.perf_counter() - t1
        c1, k1 = clock.mark()
        log(phase="relation", backend=backend, ok=not bad, failures=bad,
            tuples=n, clouds=N_SHARES, relation_bytes=relation_bytes(db),
            requests=len(jobs), families=[j[0] for j in jobs],
            setup_s=setup_s, wall_s=wall, compile_s=c1 - c0,
            compiles=k1 - k0, peak_bytes_in_use=peak_bytes())
        ok_all &= not bad
    return ok_all


def embedding_phase(clock, seed):
    import jax
    import numpy as np
    from repro.api import EmbedLookup, MeshDispatcher
    from repro.core.queries import embed as embed_q
    from repro.launch.serve import QueryServer
    from repro.models import private_embed as pe
    t0 = time.perf_counter()
    k_tab, k_sh, k_tok = jax.random.split(jax.random.PRNGKey(seed), 3)
    table = jnp_clip_normal(k_tab, (VOCAB, D_MODEL))
    shares = pe.setup_private_embed(k_sh, table, n_shares=EMBED_SHARES)
    tokens = np.asarray(jax.random.randint(k_tok, (STEP_TOKENS,), 0, VOCAB))
    want = np.asarray(embed_q.dequantize_from_field(
        embed_q.quantize_to_field(table[tokens])))
    del table
    setup_s = time.perf_counter() - t0
    ok_all = True
    for backend in ("jnp", "pallas"):
        t1, (c0, k0) = time.perf_counter(), clock.mark()
        server = QueryServer(backend=backend)
        server.attach("emb", pe.as_embed_relation(shares),
                      dispatcher=MeshDispatcher())
        check = lambda r: np.array_equal(np.asarray(r.embeddings), want)
        with server:
            bad = serve_and_check(
                server, [("embed", EmbedLookup(tokens=tuple(tokens)), check)],
                relation="emb")
        del server
        gc.collect()
        wall = time.perf_counter() - t1
        c1, k1 = clock.mark()
        log(phase="embedding", backend=backend, ok=not bad, failures=bad,
            vocab=VOCAB, d_model=D_MODEL, clouds=EMBED_SHARES,
            tokens=STEP_TOKENS, table_bytes=int(shares.values.nbytes),
            setup_s=setup_s, wall_s=wall, compile_s=c1 - c0,
            compiles=k1 - k0, peak_bytes_in_use=peak_bytes())
        ok_all &= not bad
    return ok_all


def jnp_clip_normal(key, shape):
    """A seeded float table inside the fixed-point range, made on device."""
    import jax
    import jax.numpy as jnp
    return jnp.clip(jax.random.normal(key, shape, jnp.float32), -8.0, 8.0)


def mesh_phase(clock, n, seed):
    """Four chips: MeshDispatcher (data=4, then data=2 x model=2) against a
    SerialDispatcher client, bit for bit, on the relation plans. The jnp
    backend only: JAX refuses to partition a Mosaic kernel over several
    chips, and the Pallas ops have no shard_map wrapping yet."""
    import jax
    from repro.api import MeshDispatcher, QueryClient
    from repro.launch.mesh import make_dispatch_mesh
    rows, db, right_pk, right_eq = build_relation(n, seed)
    jobs = relation_plans(rows, right_pk, right_eq)
    plans = [p for _, p, _ in jobs]
    t0, (c0, k0) = time.perf_counter(), clock.mark()
    serial = QueryClient(db, key=seed)
    serial.attach(shards=4)
    ref = serial.run_batch(plans)
    bad = [label for (label, _, check), r in zip(jobs, ref) if not check(r)]
    for n_model in (1, 2):
        mesh = MeshDispatcher(make_dispatch_mesh(n_model))
        client = QueryClient(db, key=seed)
        client.attach(shards=4, dispatcher=mesh)
        got = client.run_batch(plans)
        for (label, _, _), a, b in zip(jobs, ref, got):
            same = (a.strategy == b.strategy and a.rows == b.rows
                    and a.addresses == b.addresses
                    and a.count == b.count and a.value == b.value
                    and a.ledger == b.ledger)
            if not same:
                bad.append(f"{label}: mesh data={4 // n_model} "
                           f"model={n_model} differs from serial")
        if not any("all-reduce" in t for t in mesh.hlo_texts().values()):
            bad.append(f"model={n_model}: no all-reduce in the reduce")
    wall = time.perf_counter() - t0
    c1, k1 = clock.mark()
    log(phase="mesh4", backend="jnp", ok=not bad, failures=bad,
        devices=len(jax.devices()), tuples=n, clouds=N_SHARES,
        relation_bytes=relation_bytes(db), requests=len(plans),
        wall_s=wall, compile_s=c1 - c0, compiles=k1 - k0,
        peak_bytes_in_use=peak_bytes())
    return not bad


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: relation + embedding phases on one chip; "
                         "4: the four-chip mesh parity phase only")
    args = ap.parse_args(argv)

    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro  # noqa: F401  (fails outside a checkout of this repo)
    warnings.simplefilter("error", RuntimeWarning)
    clock = CompileClock()

    if args.chips == 4:
        ok = mesh_phase(clock, MESH_TUPLES, SEED)
    else:
        ok = relation_phase(clock, RELATION_TUPLES, SEED)
        gc.collect()
        ok &= embedding_phase(clock, SEED)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
