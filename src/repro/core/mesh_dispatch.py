"""Device-resident mesh dispatcher: the cloud steps of a batch as SPMD.

Every host dispatcher in :mod:`repro.core.dataplane` (serial / thread pool /
MapReduce) runs one thunk per shard and reassembles the partials on the
host — correct, but the hardware never sees more than one shard-step at a
time and every reduce round-trips through Python. :class:`MeshDispatcher`
executes the same :class:`~repro.core.dataplane.DispatchSet` seam
device-resident:

* **Placement** — on first contact with a plane (``bind_plane``, called by
  ``QueryClient.attach`` and lazily from ``run_set``), the relation's share
  arrays are ``jax.device_put`` once onto a ``jax.make_mesh`` with the
  tuple axis pinned to the ``data`` mesh axis and the cloud axis (the c
  Shamir shares — independent non-communicating clouds) spread across
  ``model`` (``repro.sharding.share_spec``). Everything after that initial
  placement stays on device: shard views are jnp slices of the placed
  arrays, kernel dispatches consume and produce device buffers, and the
  reduce below never touches the host.
* **SPMD reduce** — a ``"sum"`` step's per-shard mod-p partials are stacked
  and lowered through ``shard_map``: each device sums its block as 16-bit
  halves in uint32, a ``psum`` along the data axes combines them, and a
  single final Mersenne fold recombines the halves. F_p addition is
  exact, so this is **bit-identical** to the host chain of ``field.add``
  for every shard count S below 2¹⁶ — the dataplane's standing
  transcript invariant. The stacked buffer is *donated* into the
  reduction (round-to-round re-shares reuse the storage; donation is a
  no-op on backends without buffer aliasing, e.g. CPU).
* **No blocking inside a batch** — ``run_set`` never calls
  ``block_until_ready``; jax async dispatch overlaps the next shard
  dispatch with the in-flight reduce, and synchronization happens only
  when the user-side protocol opens values at batch boundaries.
* **Predicted cost** — every distinct reduction program it compiles keeps
  its optimized HLO text; :meth:`predicted_cost` runs
  ``repro.launch.hlo_cost`` over them (FLOPs / HBM bytes / collective
  bytes), which the bench harness merges with the per-family kernel HLO
  into the gated ``mesh`` section of ``BENCH_queries.json``.

``strict_transfers=True`` wraps every cloud step in
``jax.transfer_guard("disallow")`` — any implicit host↔device copy inside a
round raises, which is how tests/test_mesh_dispatch.py *proves* the
device-residency invariant instead of asserting it by inspection.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import field
from .dataplane import Dispatcher, DispatchSet, ShardedRelation
from .engine import SecretSharedDB
from .shamir import Shares


class MeshDispatcher(Dispatcher):
    """Run a plane's cloud steps as one SPMD program per round on a mesh.

    Parameters
    ----------
    mesh:
        A ``("data", "model")`` (optionally ``("pod", "data", "model")``)
        mesh; defaults to ``repro.launch.mesh.make_dispatch_mesh()`` — all
        visible devices on the data axis. The single-device host mesh
        degrades to a correct (serial-speed) path, so the dispatcher is
        safe to construct anywhere.
    strict_transfers:
        Raise on any *implicit* host↔device transfer inside a cloud step
        (explicit placement via ``bind_plane`` is exempt). Used by tests to
        prove device residency.
    collect_hlo:
        Keep the optimized HLO text of every compiled reduction for
        :meth:`predicted_cost` (cheap: one text per distinct shape).
    """

    device_resident = True

    def __init__(self, mesh: Optional[Mesh] = None, *,
                 strict_transfers: bool = False, collect_hlo: bool = True):
        if mesh is None:
            from ..launch.mesh import make_dispatch_mesh
            mesh = make_dispatch_mesh()
        if "data" not in mesh.axis_names:
            raise ValueError(f"MeshDispatcher needs a 'data' axis, got "
                             f"{mesh.axis_names}")
        self.mesh = mesh
        self.strict_transfers = strict_transfers
        self.collect_hlo = collect_hlo
        self.data_axes: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in mesh.axis_names)
        self.data_size = 1
        for a in self.data_axes:
            self.data_size *= int(mesh.shape[a])
        self._sum_fns: Dict[Tuple[Tuple[int, ...], str], Any] = {}
        self._hlo_texts: Dict[str, str] = {}
        self._pending_transfer_bytes = 0

    # -- placement ----------------------------------------------------------
    def bind_plane(self, plane: ShardedRelation) -> None:
        """Device-put the plane's share arrays onto the mesh, once.

        Idempotent per (plane, dispatcher); re-binding after an attach
        re-shard is a fresh placement. The moved bytes are charged to the
        plane's next ``DispatchStats.record`` — after this, transfer bytes
        stay at zero (the residency invariant).
        """
        if getattr(plane, "_mesh_placed_by", None) is self:
            return
        from .. import sharding

        def put(shares: Shares) -> Shares:
            spec = sharding.share_spec(self.mesh, shares.values.shape)
            arr = jax.device_put(shares.values,
                                 NamedSharding(self.mesh, spec))
            self._pending_transfer_bytes += int(arr.nbytes)
            return Shares(arr, shares.degree)

        db = plane.db
        plane.db = SecretSharedDB(
            relation=put(db.relation), codec=db.codec,
            column_names=db.column_names,
            numeric={c: put(s) for c, s in db.numeric.items()},
            numeric_bits=dict(db.numeric_bits),
            base_degree=db.base_degree)
        plane.clear_views()
        plane._mesh_placed_by = self

    # -- the dispatch seam --------------------------------------------------
    def run_set(self, plane: ShardedRelation, ds: DispatchSet):
        self.bind_plane(plane)
        # strict mode: no device→host pull anywhere inside the cloud step
        # (partials must never stage through the host), and no transfer of
        # EITHER direction inside the reduce. Eager-mode kernel dispatch
        # uploads scalar slice indices (int64[] avals — bytes, not share
        # buffers), so blanket host→device disallow would false-positive
        # there; the share-buffer direction is enforced exactly instead by
        # the placement-only ``transfer_bytes`` accounting.
        d2h = (jax.transfer_guard_device_to_host("disallow")
               if self.strict_transfers else contextlib.nullcontext())
        with d2h:
            parts = [d.run() for d in ds.dispatches]
            both = (jax.transfer_guard("disallow") if self.strict_transfers
                    else contextlib.nullcontext())
            with both:
                if ds.reduce == "sum" and len(parts) > 1:
                    out = self._device_sum(parts)
                else:
                    out = ds.combine(parts)  # concat/list: already on device
        moved, self._pending_transfer_bytes = self._pending_transfer_bytes, 0
        plane.stats.record(len(ds.dispatches), transfer_bytes=moved)
        return out

    # -- SPMD mod-p reduction ----------------------------------------------
    def _device_sum(self, parts: List[jax.Array]):
        """psum the per-shard partials along the data axes, exactly mod p."""
        d = self.data_size
        pad = (-len(parts)) % d
        if pad:                       # 0 is the additive identity of F_p
            parts = list(parts) + [jnp.zeros_like(parts[0])] * pad
        stacked = jnp.stack(parts)
        return self._sum_fn(stacked.shape, str(stacked.dtype))(stacked)

    def _sum_fn(self, shape: Tuple[int, ...], dtype: str):
        key = (shape, dtype)
        fn = self._sum_fns.get(key)
        if fn is not None:
            return fn
        ndim = len(shape)
        in_spec = P(self.data_axes, *([None] * (ndim - 1)))
        out_spec = P(*([None] * (ndim - 1)))
        axes = self.data_axes

        def psum_fold(block):
            # Split each partial x < p into x = hi·2^16 + lo (hi < 2^15,
            # lo < 2^16) and sum the halves in uint32 — the widest integer
            # all-reduce the TPU lowers. Exact for fewer than 2^16 stacked
            # partials; ONE fold at the end == the field.add chain.
            u16 = jnp.uint32(0xFFFF)
            lo = jnp.sum(block & u16, axis=0, dtype=jnp.uint32)
            hi = jnp.sum(block >> jnp.uint32(16), axis=0, dtype=jnp.uint32)
            lo, hi = jax.lax.psum((lo, hi), axes)
            return field.addmod32(field.rotmod32(field.fold32(hi), 16),
                                  field.fold32(lo))

        mapped = shard_map(psum_fold, mesh=self.mesh,
                           in_specs=in_spec, out_specs=out_spec)
        # donate the stacked-partials buffer into the reduction: the
        # round-to-round re-share reuses its storage on aliasing backends
        donate = (0,) if jax.default_backend() != "cpu" else ()
        fn = jax.jit(mapped, donate_argnums=donate)
        if self.collect_hlo:
            lowered = fn.lower(
                jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)))
            name = f"sum/{'x'.join(map(str, shape))}/{dtype}"
            self._hlo_texts[name] = lowered.compile().as_text()
        self._sum_fns[key] = fn
        return fn

    # -- predicted cost -----------------------------------------------------
    def hlo_texts(self) -> Dict[str, str]:
        """Optimized HLO of every reduction program compiled so far."""
        return dict(self._hlo_texts)

    def predicted_cost(self) -> Dict[str, float]:
        """HLO-cost-model totals over the compiled reduction programs.

        Per-device numbers (the HLO is the SPMD-partitioned module);
        collective bytes are the psum traffic along the data axes.
        """
        from ..launch import hlo_cost   # lazy: core -> launch on demand
        total = hlo_cost.Cost()
        for text in self._hlo_texts.values():
            total += hlo_cost.analyze_text(text)
        return dict(flops=total.flops, hbm_bytes=total.hbm_bytes,
                    collective_bytes=total.collective_bytes,
                    programs=len(self._hlo_texts))
