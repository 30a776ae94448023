"""MeshDispatcher: device-resident SPMD execution of the sharded dataplane.

Anchor properties:

* **Transcript identity** — rows, opened values, addresses and per-query
  ``CostLedger``s through a ``MeshDispatcher`` are bit-identical to
  ``SerialDispatcher`` for S ∈ {1, 2, 4} across every query family
  (count / select / range / join / aggregate, ``verify=`` included). The
  shard count and the placement policy are both pure execution axes.
* **Device residency** — after the initial placement, zero host↔device
  share-buffer traffic inside ``run_batch``: strict mode runs every cloud
  step under ``jax.transfer_guard`` (device→host disallowed everywhere,
  both directions disallowed in the reduce), and the telemetry charges
  exactly the one-time placement, then stays at zero.
* **Seam transparency** — ``QueryClient.attach(dispatcher=...)`` and
  ``QueryServer`` tenants pick it up with no other code changes.

The SPMD psum path over real multiple devices (forced host platform,
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) runs in a
subprocess — tests/conftest.py pins this process to ONE device.
"""
import os
import subprocess
import sys

import jax
import pytest

from repro.api import (Aggregate, Between, Count, Eq, Join, MeshDispatcher,
                       Padding, QueryClient, RangeCount, RangeSelect, Select)
from repro.core import Codec, outsource
from repro.launch.mesh import (make_dispatch_mesh, make_host_mesh,
                               make_mesh)
from repro.launch.serve import QueryServer

CODEC = Codec(word_length=6)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def range_db():
    rows = [[f"id{i}", f"nm{i % 5}", str(500 + 137 * i)] for i in range(32)]
    db = outsource(jax.random.PRNGKey(19), rows,
                   column_names=["Id", "Name", "Val"], codec=CODEC,
                   n_shares=20, degree=1, numeric_columns={2: 14})
    return rows, db


@pytest.fixture(scope="module")
def child_db(range_db):
    rows, _ = range_db
    child = [[rows[i % len(rows)][0], f"t{i}"] for i in range(6)]
    return outsource(jax.random.PRNGKey(23), child,
                     column_names=["Id", "Task"], codec=CODEC,
                     n_shares=20, degree=1)


def _family_plans(child):
    return [
        Count(Eq("Name", "nm1")),
        Select(Eq("Name", "nm2"), strategy="one_round"),
        Select(Eq("Name", "nm3"), strategy="tree"),
        Select(Eq("Id", "id7"), strategy="one_tuple"),
        RangeCount(Between("Val", 500, 2000), reduce_every=2),
        RangeSelect(Between("Val", 900, 1800), reduce_every=2),
        Join(right=child, on=("Id", "Id"), kind="pkfk"),
        Join(right=child, on=("Id", "Id"), kind="equi",
             padding=Padding.fake_values(1)),
        Aggregate("sum", "Val", where=Eq("Name", "nm1"), verify=True),
        Aggregate("avg", "Val", where=Eq("Name", "nm2")),
        Aggregate("min", "Val", where=Eq("Name", "nm1"), reduce_every=2),
    ]


def _assert_results_equal(a, b):
    assert a.strategy == b.strategy
    assert a.rows == b.rows
    assert a.addresses == b.addresses
    assert a.count == b.count
    assert a.value == b.value
    assert a.ledger == b.ledger


# ---------------------------------------------------------------------------
# transcript identity (host mesh: the single-device degradation path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 4])
def test_mesh_parity_with_serial_all_families(range_db, child_db, shards):
    _, db = range_db
    plans = _family_plans(child_db)
    serial = QueryClient(db, key=7)
    serial.attach(shards=shards)
    ref = serial.run_batch(plans)

    client = QueryClient(db, key=7)
    mesh = MeshDispatcher(make_host_mesh(), strict_transfers=True)
    plane = client.attach(shards=shards, dispatcher=mesh)
    got = client.run_batch(plans)
    for a, b in zip(ref, got):
        _assert_results_equal(a, b)
    assert plane.stats.dispatches == plane.stats.steps * shards


def test_mesh_device_residency_placement_then_zero(range_db, child_db):
    """Transfer accounting: the first batch pays exactly the one-time
    placement of the share arrays; every later batch moves zero bytes.
    Strict mode (active here) additionally guards every cloud step, so an
    implicit transfer would raise, not just miscount."""
    _, db = range_db
    client = QueryClient(db, key=7)
    mesh = MeshDispatcher(make_host_mesh(), strict_transfers=True)
    plane = client.attach(shards=2, dispatcher=mesh)
    placed = db.relation.values.nbytes + sum(
        s.values.nbytes for s in db.numeric.values())
    plans = _family_plans(child_db)[:4]
    client.run_batch(plans)
    assert plane.stats.transfer_bytes == placed
    before = plane.stats.transfer_bytes
    client.run_batch(plans)
    assert plane.stats.transfer_bytes == before  # zero after placement
    assert plane.stats.span_s["cloud.match"] > 0.0
    assert plane.stats.span_s["cloud.fetch"] > 0.0
    assert plane.stats.steps > 0


def test_mesh_predicted_cost_report(range_db):
    _, db = range_db
    client = QueryClient(db, key=3)
    mesh = MeshDispatcher(make_host_mesh())
    client.attach(shards=2, dispatcher=mesh)
    client.run_batch([Count(Eq("Name", "nm1")),
                      Aggregate("sum", "Val")])
    cost = mesh.predicted_cost()
    assert cost["programs"] >= 1          # at least one compiled reduction
    assert cost["flops"] > 0
    assert cost["hbm_bytes"] > 0
    assert mesh.hlo_texts()               # texts retained for the bench


def test_query_server_tenant_gets_mesh_transparently(range_db):
    """A QueryServer tenant attached with a MeshDispatcher serves the same
    results as a serial tenant, and the serving snapshot now carries the
    cloud steps' span seconds and the placement-only transfer bytes."""
    _, db = range_db
    plans = [Count(Eq("Name", "nm1")), Count(Eq("Name", "nm2"))]

    solo = QueryServer()
    solo.attach("emp", db, key=5)
    with solo:
        ref = [solo.submit(p, relation="emp").wait().result for p in plans]

    server = QueryServer()
    mesh = MeshDispatcher(make_host_mesh())
    server.attach("emp", db, key=5, shards=2, dispatcher=mesh)
    with server:
        got = [server.submit(p, relation="emp").wait().result
               for p in plans]
    for a, b in zip(ref, got):
        _assert_results_equal(a, b)
    snap = server.stats.snapshot()["relations"]["emp"]
    assert snap["dispatches"] > 0
    assert snap["span_s"]["cloud.match"] > 0.0
    assert snap["transfer_bytes"] > 0     # the one-time placement
    # a second helping of traffic moves nothing new
    server2_stats = server.stats.snapshot()
    assert server2_stats["transfer_bytes"] == snap["transfer_bytes"]


def test_serial_dispatchers_also_record_time_and_bytes(range_db):
    """Satellite: the host paths record span seconds and staged bytes too
    — every shard partial round-trips through the host combine."""
    _, db = range_db
    client = QueryClient(db, key=7)
    plane = client.attach(shards=2)
    client.run_batch([Count(Eq("Name", "nm1"))])
    assert plane.stats.span_s["cloud.match"] > 0.0
    assert plane.stats.transfer_bytes > 0


# ---------------------------------------------------------------------------
# mesh construction seams (single-device side)
# ---------------------------------------------------------------------------

def test_host_and_elastic_mesh_shapes():
    hm = make_host_mesh()
    assert hm.axis_names == ("data", "model")
    assert dict(hm.shape) == {"data": 1, "model": 1}
    em = make_mesh((1, 1, 1), ("pod", "data", "model"))
    assert em.axis_names == ("pod", "data", "model")
    dm = make_dispatch_mesh()
    assert dm.axis_names == ("data", "model")
    assert dm.shape["data"] * dm.shape["model"] == jax.device_count()
    with pytest.raises(ValueError):
        make_dispatch_mesh(jax.device_count() + 1)


def test_share_spec_pins_cloud_and_tuple_axes():
    from jax.sharding import PartitionSpec as P
    from repro.sharding import share_spec
    mesh = make_host_mesh()
    # every axis divides a 1-sized mesh axis: cloud -> model, tuple -> data
    assert share_spec(mesh, (20, 32, 4, 3)) == P("model", ("data",))
    assert share_spec(mesh, (20,)) == P("model")


def test_mesh_dispatcher_requires_data_axis():
    with pytest.raises(ValueError):
        MeshDispatcher(make_mesh((1,), ("model",)))


# ---------------------------------------------------------------------------
# forced multi-device SPMD path (subprocess: needs its own XLA_FLAGS
# before jax import — this process is pinned to one device)
# ---------------------------------------------------------------------------

_FORCED_SCRIPT = r"""
import jax
assert jax.device_count() == 8, jax.device_count()
from jax.sharding import PartitionSpec as P
from repro.api import (Aggregate, Between, Count, Eq, MeshDispatcher,
                       QueryClient, RangeCount)
from repro.core import Codec, outsource
from repro.launch.mesh import make_dispatch_mesh, make_mesh
from repro.sharding import dp_axes, dp_size, model_size, share_spec

# -- construction: forced host platform, elastic shapes -------------------
dm = make_dispatch_mesh()
assert dict(dm.shape) == {"data": 8, "model": 1}, dm.shape
dm2 = make_dispatch_mesh(2)
assert dict(dm2.shape) == {"data": 4, "model": 2}, dm2.shape
mp = make_mesh((2, 2, 2), ("pod", "data", "model"))
assert dp_axes(mp) == ("pod", "data") and dp_size(mp) == 4
assert model_size(mp) == 2

# -- share_spec divisibility: non-divisible axes replicate ----------------
assert share_spec(dm2, (20, 32, 4, 3)) == P("model", ("data",))
assert share_spec(dm2, (21, 30, 4, 3)) == P(None, None)  # 21%2, 30%4

# -- SPMD parity: psum reduce across 4 data devices == serial -------------
CODEC = Codec(word_length=6)
rows = [[f"id{i}", f"nm{i % 4}", str(500 + 37 * i)] for i in range(16)]
db = outsource(jax.random.PRNGKey(11), rows,
               column_names=["Id", "Name", "Val"], codec=CODEC,
               n_shares=20, degree=1, numeric_columns={2: 14})
plans = [Count(Eq("Name", "nm1")),
         RangeCount(Between("Val", 500, 900), reduce_every=2),
         Aggregate("sum", "Val", where=Eq("Name", "nm2"), verify=True)]
serial = QueryClient(db, key=7); serial.attach(shards=4)
ref = serial.run_batch(plans)
client = QueryClient(db, key=7)
mesh = MeshDispatcher(dm2, strict_transfers=True)
client.attach(shards=4, dispatcher=mesh)
got = client.run_batch(plans)
for a, b in zip(ref, got):
    assert a.rows == b.rows and a.count == b.count and a.value == b.value
    assert a.ledger == b.ledger
# the reduction really is collective: psum lowers to all-reduce
texts = mesh.hlo_texts()
assert texts and any("all-reduce" in t for t in texts.values()), \
    sorted(texts)
assert mesh.predicted_cost()["collective_bytes"] > 0
print("FORCED-MESH-OK")
"""


@pytest.mark.slow
def test_forced_eight_device_spmd_parity():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _FORCED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "FORCED-MESH-OK" in proc.stdout
