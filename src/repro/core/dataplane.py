"""Sharded dataplane: tuple-axis partitioning of a secret-shared relation.

The paper states its efficiency claims per *query stream* (rounds and bits
between the user and c non-communicating clouds); how the cloud-side work is
*executed* is free as long as the transcript is unchanged. This module makes
that execution axis explicit: a :class:`ShardedRelation` partitions the share
arrays of a :class:`~repro.core.engine.SecretSharedDB` into S contiguous
tuple-axis shards (the same split MapReduce mappers use in the paper — every
shard holds whole share-columns of a tuple slice, so the non-communication
property is untouched), and the round engine emits one
:class:`ShardDispatch` per shard per cloud step instead of one monolithic
device call.

A :class:`DispatchSet` bundles the per-shard dispatches of one cloud step
together with the reduction that reassembles them:

  * ``"concat"`` — per-tuple outputs (match bits, match-matrix rows, ripple
    planes) concatenate along the tuple axis;
  * ``"sum"``    — partial mod-p sums (counts, one-hot fetch / matmul
    contractions over the tuple axis) combine additively. F_p addition is
    exact and associative, so the combined residues are **bit-identical** to
    the unsharded computation — user-side rounds, opened values and
    ``CostLedger`` totals never see the shard count.
  * ``"list"``   — raw per-shard results for callers that thread shard-local
    state themselves (the ripple carry chain).

Execution is a *placement policy*, not part of the protocol:
:class:`SerialDispatcher` runs shards inline (the S = 1 path is exactly the
pre-shard engine), :class:`ThreadedDispatcher` fans them out over a thread
pool (the async serving runtime), and
``repro.api.executor.MapReduceDispatcher`` places each shard dispatch as a
fault-tolerant MapReduce task.

Two multi-tenant refinements ride on the thread pool:

  * **Weighted fair quotas** — every :class:`PoolHandle` carries a
    ``weight``; dispatches submitted through a handle queue per handle and
    a deficit-round-robin picker admits them to the pool workers in
    weight-proportional order. A hot tenant flooding its handle degrades
    gracefully instead of starving its neighbours' shard dispatches behind
    a FIFO executor queue. Within one handle, dispatch order (and thus the
    shard-order combine) is unchanged — results stay bit-identical.
  * **Fused waves** — :func:`fused_execute` runs several planes' cloud
    steps as ONE dispatch wave when their dispatchers share a pool: all
    shard thunks enqueue together (each under its own handle, so quotas
    still apply) and each step combines in shard order as its futures
    resolve. Planes on serial / device-resident dispatchers execute
    unfused via their own ``run_set`` — transcripts never depend on
    whether a wave was fused.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp

from . import field
from .engine import SecretSharedDB
from .partition import split_bounds
from .shamir import Shares


def _tree_nbytes(part: Any) -> int:
    """Bytes of every array leaf in one shard result (tuples included)."""
    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(part))


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------

class Dispatcher:
    """Placement policy for one round's shard dispatches (serial default).

    Two seams, two levels of control:

    * :meth:`run_all` — run a list of opaque shard thunks; host dispatchers
      (serial / thread pool / MapReduce) override only this.
    * :meth:`run_set` — run one whole :class:`DispatchSet` against its
      :class:`ShardedRelation` and reduce it. The default implementation is
      ``run_all`` + host-side :meth:`DispatchSet.combine`; a device-resident
      dispatcher (``repro.core.mesh_dispatch.MeshDispatcher``) overrides it
      to keep the per-shard partials on device and reduce them there.

    ``device_resident`` tells the telemetry layer how to account transfer
    bytes: host dispatchers stage every shard partial through the combine
    (bytes = the parts), device-resident ones only pay the initial
    placement.
    """

    device_resident = False

    def run_all(self, thunks: Sequence[Callable[[], Any]]) -> List[Any]:
        return [t() for t in thunks]

    def run_set(self, plane: "ShardedRelation", ds: "DispatchSet"):
        """Execute + reduce one cloud step, recording telemetry."""
        parts = self.run_all([d.run for d in ds.dispatches])
        out = ds.combine(parts)
        plane.stats.record(len(ds.dispatches),
                           transfer_bytes=sum(_tree_nbytes(p)
                                              for p in parts))
        return out


SERIAL = Dispatcher()


#: deficit-round-robin serves one shard dispatch per unit of deficit;
#: weights below this floor still accumulate credit (no silent starvation).
_MIN_WEIGHT = 1e-6


class ThreadedDispatcher(Dispatcher):
    """Run shard dispatches concurrently on a shared thread pool.

    Share-space cloud steps are pure, so concurrent execution is safe; the
    combine step (concat / mod-p sum) happens on the caller's thread in
    shard order, keeping results bit-identical to serial execution.

    One pool can back many relations: :meth:`handle` returns a
    :class:`PoolHandle` — a per-relation view that delegates to this pool
    but whose ``close()`` only detaches the view. A multi-tenant server
    hands each attached relation its own handle, so the global fan-out
    stays bounded by ONE ``max_workers`` no matter how many dataplanes are
    attached, and detaching one tenant never kills its neighbours' pool.

    Handles are *weighted*: dispatches submitted through a handle are
    queued per handle and admitted to the pool workers by deficit round
    robin (:meth:`_pick_locked`) — each rotation visit tops a handle's
    deficit up by its weight and serves one queued shard dispatch per unit
    of deficit. Service is weight-proportional under contention, FIFO
    within a handle, and work-conserving (an idle pool never waits on a
    quota). Direct ``run_all`` calls on the dispatcher itself bypass the
    quota path — they are the single-tenant surface.
    """

    def __init__(self, max_workers: Optional[int] = None):
        # mirror ThreadPoolExecutor's default sizing — the cap doubles as
        # the DRR in-flight bound, so it must be a concrete number.
        self._cap = max_workers or min(32, (os.cpu_count() or 1) + 4)
        self._pool = ThreadPoolExecutor(max_workers=self._cap,
                                        thread_name_prefix="shard")
        self._closed = False
        self._dlock = threading.Lock()
        self._queues: Dict["PoolHandle", deque] = {}
        self._rr: deque = deque()           # handles with queued work
        self._deficits: Dict["PoolHandle", float] = {}
        self._granted: set = set()          # front handle already topped up
        self._inflight = 0

    def run_all(self, thunks: Sequence[Callable[[], Any]]) -> List[Any]:
        if self._closed or len(thunks) <= 1:
            return [t() for t in thunks]
        return list(self._pool.map(lambda t: t(), thunks))

    def handle(self, weight: float = 1.0) -> "PoolHandle":
        """A detachable per-relation view sharing this pool.

        ``weight`` sets the handle's deficit-round-robin share: under
        contention a weight-2 handle's shard dispatches are admitted twice
        as often as a weight-1 neighbour's.
        """
        return PoolHandle(self, weight=weight)

    # -- weighted fair admission (deficit round robin) ----------------------
    def enqueue(self, handle: "PoolHandle",
                thunks: Sequence[Callable[[], Any]]) -> List[Future]:
        """Queue thunks under ``handle``'s quota; returns their futures.

        Non-blocking: admission happens on whichever threads drive the
        queue (this caller now, pool workers as units finish).
        """
        futures = [Future() for _ in thunks]
        with self._dlock:
            q = self._queues.get(handle)
            if q is None:
                q = self._queues[handle] = deque()
                self._rr.append(handle)
            for t, f in zip(thunks, futures):
                q.append((t, f))
        self._drive()
        return futures

    def _pick_locked(self) -> Optional[Tuple[Callable[[], Any], Future]]:
        """Next admissible unit under DRR; caller holds ``_dlock``.

        The front handle's deficit is topped up by its weight once per
        rotation visit and spent one unit per served dispatch; when it runs
        dry (or drains) the rotation advances. Tiny weights merely take
        more rotations to accumulate a unit — they are never starved.
        """
        while self._rr:
            h = self._rr[0]
            q = self._queues.get(h)
            if not q:                       # drained: drop stale credit
                self._rr.popleft()
                self._queues.pop(h, None)
                self._deficits.pop(h, None)
                self._granted.discard(h)
                continue
            if h not in self._granted:
                self._granted.add(h)
                self._deficits[h] = (self._deficits.get(h, 0.0)
                                     + max(h.weight, _MIN_WEIGHT))
            if self._deficits[h] >= 1.0:
                self._deficits[h] -= 1.0
                unit = q.popleft()
                if not q:
                    self._rr.popleft()
                    self._queues.pop(h, None)
                    self._deficits.pop(h, None)
                    self._granted.discard(h)
                return unit
            self._granted.discard(h)        # spent: next visit re-grants
            self._rr.rotate(-1)
        return None

    def _drive(self) -> None:
        """Admit queued units while worker slots are free (cooperative:
        submitters and finishing workers both drive; no dedicated thread).
        """
        while True:
            with self._dlock:
                if not self._closed and self._inflight >= self._cap:
                    return
                unit = self._pick_locked()
                if unit is None:
                    return
                self._inflight += 1
                closed = self._closed
            if closed:
                self._run_unit(*unit)       # inline drain — never strand
            else:
                try:
                    self._pool.submit(self._run_unit, *unit)
                except RuntimeError:        # shut down mid-flight
                    self._run_unit(*unit)

    def _run_unit(self, thunk: Callable[[], Any], fut: Future) -> None:
        try:
            result = thunk()
        except BaseException as e:          # noqa: BLE001 — relayed to waiter
            fut.set_exception(e)
        else:
            fut.set_result(result)
        with self._dlock:
            self._inflight -= 1
        self._drive()

    def close(self) -> None:
        """Release the pool; later dispatches degrade to serial (correct,
        just unparallel) instead of raising on the shut-down executor.
        Units still queued under handle quotas drain inline so no waiter
        blocks forever."""
        self._closed = True
        self._pool.shutdown(wait=False)
        self._drive()


class PoolHandle(Dispatcher):
    """Per-relation view of a shared :class:`ThreadedDispatcher` pool.

    ``run_all`` submits through the pool's weighted fair queue (global
    worker bound, deficit-round-robin admission at this handle's
    ``weight``); ``close()`` detaches only this handle — subsequent
    dispatches through it run serial while the pool keeps serving its
    other handles.
    """

    def __init__(self, pool: ThreadedDispatcher, weight: float = 1.0):
        if weight <= 0:
            raise ValueError(f"PoolHandle weight must be > 0, got {weight}")
        self._shared_pool = pool
        self.weight = float(weight)
        self._detached = False

    def run_all(self, thunks: Sequence[Callable[[], Any]]) -> List[Any]:
        pool = self._shared_pool
        if self._detached or pool._closed or len(thunks) <= 1:
            return [t() for t in thunks]
        futures = pool.enqueue(self, list(thunks))
        return [f.result() for f in futures]

    def close(self) -> None:
        self._detached = True


# ---------------------------------------------------------------------------
# shards and dispatch descriptors
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Shard:
    """One contiguous tuple-axis slice [lo, hi) of the relation."""
    index: int
    lo: int
    hi: int

    @property
    def n_tuples(self) -> int:
        return self.hi - self.lo


@dataclasses.dataclass(frozen=True)
class ShardDispatch:
    """One shard's slice of a cloud step: a zero-argument device thunk."""
    shard: Shard
    run: Callable[[], Any]


@dataclasses.dataclass(frozen=True)
class DispatchSet:
    """All shards' dispatches for one cloud step + the reduction rule.

    ``phase`` names the protocol step for telemetry only: the step runs
    under the span ``cloud.<phase>`` (``match``, ``fetch``, ``embed``,
    ``ripple``, ``join``; ``step`` where its caller named none).
    """
    dispatches: Tuple[ShardDispatch, ...]
    reduce: str = "concat"          # "concat" | "sum" | "list"
    axis: int = -1                  # concat axis
    phase: str = "step"

    def combine(self, parts: List[Any]):
        if self.reduce == "list":
            return parts
        if len(parts) == 1:
            return parts[0]
        if self.reduce == "concat":
            return jnp.concatenate(parts, axis=self.axis)
        if self.reduce == "sum":
            acc = parts[0]
            for p in parts[1:]:
                acc = field.add(acc, p)
            return acc
        raise ValueError(f"unknown reduce mode {self.reduce!r}")


@contextlib.contextmanager
def span(sink, name: str):
    """One program span: ``name`` on the profiler's host plane and its
    host seconds added to ``sink.span_s[name]``.

    The span opens a ``jax.profiler.TraceAnnotation``, so a traced run
    sees it on the same clock as the device's operations; with no
    profiler attached that costs well under a microsecond. ``sink`` is a
    plane's :class:`DispatchStats` or the server's ``ServeStats`` (both
    expose ``add_span``), or None to trace only. A span never waits on
    the device: the seconds are host wall time, so a span that ends in a
    host copy includes the wait for the bytes.
    """
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if sink is not None:
            sink.add_span(name, time.perf_counter() - t0)


@dataclasses.dataclass
class DispatchStats:
    """Execution-side telemetry (never part of the protocol transcript).

    ``transfer_bytes`` accumulates staged bytes: for host dispatchers,
    every shard partial that round-trips through the combine; for a
    device-resident dispatcher, only the initial host→device placement
    (zero afterwards — the device-residency invariant, asserted in
    tests/test_mesh_dispatch.py). ``span_s`` accumulates the host seconds
    of every :func:`span` charged to this plane, by name: ``cloud.<phase>``
    for cloud steps (submission plus the combine; jax dispatch is
    asynchronous, so this is not device time), ``client.plan``,
    ``user.share`` and ``user.open`` for the user's side of a batch.
    ``table_splits`` counts the shard tables split into resident int8
    digits (:meth:`ShardedRelation.table_digits`), ``presplit_contractions``
    the shard contractions that read such digits. ``reshares`` counts the
    protocol's re-sharing rounds (``shamir.reduce_degree`` of a range or
    tournament carry, a tournament's mask or winners, a window match), and
    ``ripple_segments`` the SS-SUB ripple segments dispatched (one per
    segment of a range group or of a tournament level, whatever the shard
    count).
    """
    dispatches: int = 0             # shard dispatches executed
    steps: int = 0                  # cloud steps (DispatchSets) executed
    fused_steps: int = 0            # steps executed inside a fused wave
    transfer_bytes: int = 0         # staged bytes (see above)
    table_splits: int = 0           # resident digit sets made
    presplit_contractions: int = 0  # contractions over resident digits
    reshares: int = 0               # re-sharing rounds (degree reduction)
    ripple_segments: int = 0        # SS-SUB ripple segments dispatched
    span_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def record(self, n_dispatches: int, transfer_bytes: int = 0,
               fused: bool = False) -> None:
        self.dispatches += n_dispatches
        self.steps += 1
        if fused:
            # the step ran inside a cross-plane fused_execute wave
            self.fused_steps += 1
        self.transfer_bytes += transfer_bytes

    def add_span(self, name: str, seconds: float) -> None:
        self.span_s[name] = self.span_s.get(name, 0.0) + seconds

    def copy(self) -> "DispatchStats":
        """A snapshot whose ``span_s`` later spans leave unchanged."""
        return dataclasses.replace(self, span_s=dict(self.span_s))


# ---------------------------------------------------------------------------
# the sharded relation
# ---------------------------------------------------------------------------

class ShardedRelation:
    """Tuple-axis partitioned view of one outsourced relation.

    ``shards=S`` splits [0, n) with the shared :func:`split_bounds` rule
    (the same rounding MapReduce input splits and tree blocks use), so every
    shard is a contiguous ``ceil(n/S)``-ish block. ``view(i)`` materializes
    shard i as a regular :class:`SecretSharedDB` slice (relation + binary
    columns), cheap jnp views over the parent arrays. The attached
    ``dispatcher`` decides *where* shard dispatches run; swapping it never
    changes results.
    """

    def __init__(self, db: SecretSharedDB, shards: int = 1,
                 dispatcher: Optional[Dispatcher] = None):
        if isinstance(db, ShardedRelation):        # re-shard an existing plane
            db = db.db
        self.db = db
        # ``split_bounds`` clamps the shard count to n and never returns an
        # empty range, so ``shards > n_tuples`` degrades to one shard per
        # tuple — a DispatchSet must never carry a zero-width shard (an
        # empty slice would emit degenerate device dispatches and a
        # zero-row concat block). Guarded here and regression-tested for
        # n=1, S=4 in tests/test_dataplane.py.
        bounds = split_bounds(0, db.n_tuples, max(1, shards))
        assert all(lo < hi for lo, hi in bounds), "empty shard bounds"
        self.shards: List[Shard] = [Shard(i, lo, hi)
                                    for i, (lo, hi) in enumerate(bounds)]
        self.dispatcher = dispatcher or SERIAL
        self.stats = DispatchStats()
        self._views: dict = {}
        self._digits: dict = {}

    # -- SecretSharedDB delegation (user-side code reads relation metadata
    # off the plane without caring about the shard count) -------------------
    @property
    def relation(self):
        return self.db.relation

    @property
    def codec(self):
        return self.db.codec

    @property
    def column_names(self):
        return self.db.column_names

    @property
    def numeric(self):
        return self.db.numeric

    @property
    def numeric_bits(self):
        return self.db.numeric_bits

    @property
    def base_degree(self) -> int:
        return self.db.base_degree

    @property
    def n_shares(self) -> int:
        return self.db.n_shares

    @property
    def n_tuples(self) -> int:
        return self.db.n_tuples

    @property
    def n_attrs(self) -> int:
        return self.db.n_attrs

    def column(self, col: int):
        return self.db.column(col)

    def col_index(self, name: str) -> int:
        return self.db.col_index(name)

    # -- structure ----------------------------------------------------------
    @property
    def n_shards(self) -> int:
        return len(self.shards)

    @property
    def max_shard_rows(self) -> int:
        return max((s.n_tuples for s in self.shards), default=0)

    def view(self, index: int) -> SecretSharedDB:
        """Shard ``index`` as a sliced SecretSharedDB (cached)."""
        sh = self.shards[index]
        if sh.lo == 0 and sh.hi == self.db.n_tuples:
            return self.db
        if index not in self._views:
            db = self.db
            self._views[index] = SecretSharedDB(
                relation=Shares(db.relation.values[:, sh.lo:sh.hi],
                                db.relation.degree),
                codec=db.codec,
                column_names=db.column_names,
                numeric={c: Shares(s.values[:, sh.lo:sh.hi], s.degree)
                         for c, s in db.numeric.items()},
                numeric_bits=dict(db.numeric_bits),
                base_degree=db.base_degree)
        return self._views[index]

    def table_digits(self, index: int) -> Tuple[jax.Array, ...]:
        """Shard ``index``'s share tensor as its four int8 digits
        (``field.table_digits``), made on the device once per view and
        kept beside it: what ``field.matmul`` reads in place of a table
        it would split on every call."""
        digits = self._digits.get(index)
        if digits is None:
            digits = field.table_digits(self.view(index).relation.values)
            self._digits[index] = digits
            self.stats.table_splits += 1
        return digits

    def clear_views(self) -> None:
        """Drop the cached shard views and their digits, after ``db`` has
        been replaced (a placement)."""
        self._views.clear()
        self._digits.clear()

    # -- dispatch -----------------------------------------------------------
    def dispatch_set(self, build: Callable[[SecretSharedDB, Shard], Any],
                     *, reduce: str = "concat", axis: int = -1,
                     phase: str = "step") -> DispatchSet:
        """One cloud step: a per-shard dispatch descriptor per shard."""
        return DispatchSet(tuple(
            ShardDispatch(sh, functools.partial(build, self.view(sh.index),
                                                sh))
            for sh in self.shards), reduce=reduce, axis=axis, phase=phase)

    def execute(self, ds: DispatchSet):
        """Run one step through the placement policy and reduce it."""
        with span(self.stats, f"cloud.{ds.phase}"):
            return self.dispatcher.run_set(self, ds)

    def run_concat(self, build, *, axis: int = -1, phase: str = "step"):
        return self.execute(self.dispatch_set(build, reduce="concat",
                                              axis=axis, phase=phase))

    def run_sum(self, build, *, phase: str = "step"):
        return self.execute(self.dispatch_set(build, reduce="sum",
                                              phase=phase))

    def run_list(self, build, *, phase: str = "step") -> List[Any]:
        return self.execute(self.dispatch_set(build, reduce="list",
                                              phase=phase))


RelationLike = Union[SecretSharedDB, ShardedRelation]


def _fusion_pool(plane: "ShardedRelation") -> Optional[ThreadedDispatcher]:
    """The shared thread pool a plane's cloud steps can fuse into, if any.

    Planes whose dispatchers resolve to the SAME live pool form one fusion
    domain; serial, detached, closed, and device-resident dispatchers fuse
    with nobody (their ``run_set`` may carry placement invariants — e.g.
    the mesh transfer guard — that a pooled wave must not bypass).
    """
    disp = plane.dispatcher
    if isinstance(disp, PoolHandle):
        if disp._detached or disp._shared_pool._closed:
            return None
        return disp._shared_pool
    if isinstance(disp, ThreadedDispatcher) and not disp._closed:
        return disp
    return None


def fused_execute(pairs: Sequence[Tuple["ShardedRelation", DispatchSet]]
                  ) -> List[Any]:
    """Execute one cloud step per (plane, set) pair, fusing shared pools.

    Steps whose planes share a live :class:`ThreadedDispatcher` run as ONE
    dispatch wave: every plane's shard thunks enqueue together — each under
    its own :class:`PoolHandle`, so weighted fair quotas still arbitrate —
    and each step's partials combine in shard order as they resolve.
    Everything else (serial, mesh, detached) executes through its own
    ``run_set``, unfused. Results come back in ``pairs`` order and are
    bit-identical to executing each step alone: fusion changes only *when*
    shard thunks are admitted, never their inputs or combine order.
    """
    results: List[Any] = [None] * len(pairs)
    groups: Dict[ThreadedDispatcher, List[int]] = {}
    for i, (plane, _) in enumerate(pairs):
        pool = _fusion_pool(plane)
        if pool is None:
            plane_, ds = pairs[i]
            results[i] = plane_.execute(ds)
        else:
            groups.setdefault(pool, []).append(i)
    for pool, idxs in groups.items():
        if len(idxs) == 1:
            plane, ds = pairs[idxs[0]]
            results[idxs[0]] = plane.execute(ds)
            continue
        # one span for the whole wave; each plane is charged an equal
        # share of its seconds, so the planes' spans sum to the wall spent
        name = f"cloud.{pairs[idxs[0]][1].phase}"
        t0 = time.perf_counter()
        with span(None, name):
            waves: List[Tuple[int, List[Future]]] = []
            for i in idxs:
                plane, ds = pairs[i]
                disp = plane.dispatcher
                handle = (disp if isinstance(disp, PoolHandle)
                          else pool.handle())       # transient, weight 1
                waves.append((i, pool.enqueue(
                    handle, [d.run for d in ds.dispatches])))
            for i, futs in waves:
                plane, ds = pairs[i]
                parts = [f.result() for f in futs]
                out = ds.combine(parts)
                plane.stats.record(len(ds.dispatches),
                                   transfer_bytes=sum(_tree_nbytes(p)
                                                      for p in parts),
                                   fused=True)
                results[i] = out
        share = (time.perf_counter() - t0) / len(idxs)
        for i in idxs:
            pairs[i][0].stats.add_span(name, share)
    return results


def as_dataplane(rel: RelationLike) -> ShardedRelation:
    """Normalize: a plain db becomes its own single-shard dataplane (the
    S = 1 slice is the whole relation, so the sharded path is *the* path)."""
    if isinstance(rel, ShardedRelation):
        return rel
    return ShardedRelation(rel, shards=1)
