"""Fault-tolerant MapReduce runtime — the paper's execution substrate.

The paper runs its oblivious queries as MapReduce jobs: a *master* assigns
map tasks over input splits and reduce tasks over keyed groups; the original
MapReduce fault model (Dean & Ghemawat, OSDI'04) re-executes lost tasks and
launches **speculative backup tasks** for stragglers. This module implements
that master faithfully:

  * worker pool with heartbeats; a worker that misses its lease deadline is
    declared dead and its in-flight task re-queued;
  * injected fault hooks (``fail_prob``, ``slow_factor``) so tests can kill
    workers and create stragglers deterministically;
  * speculative execution: when ≥ ``spec_threshold`` of tasks have finished,
    backup copies of the stragglers are issued; first result wins
    (map tasks are pure/idempotent — share-space programs have no side
    effects, so duplicate execution is safe);
  * wave-based elasticity: workers may be added/removed between waves.

At cluster scale each "worker" is a TPU host driving a jitted shard program;
here workers are threads driving the same jitted functions on CPU — the
scheduling logic is identical and is what the tests exercise.

Its caller is the sharded-dataplane placement policy
(``repro.core.dataplane.MapReduceDispatcher``): the round engine emits one
dispatch per tuple-axis shard of a ``ShardedRelation``, and each shard
dispatch becomes one map task here, inheriting re-execution and speculative
backups. ``splits`` is any sequence of task payloads; the dispatcher's are
zero-argument thunks.
"""
from __future__ import annotations

import dataclasses
import queue
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence


@dataclasses.dataclass
class TaskResult:
    task_id: int
    value: Any
    worker: int
    attempt: int
    duration_s: float


@dataclasses.dataclass
class _Attempt:
    task_id: int
    attempt: int
    worker: int
    started: float
    deadline: float


class WorkerPool:
    """Threads with injected failures/slowness, heartbeat-observable."""

    def __init__(self, n_workers: int, *, fail_prob: float = 0.0,
                 slow_workers: Optional[Dict[int, float]] = None,
                 dead_workers: Optional[set] = None, seed: int = 0):
        self.n = n_workers
        self.fail_prob = fail_prob
        self.slow = slow_workers or {}
        self.dead = dead_workers or set()
        self.rng = random.Random(seed)


class MapReduceRunner:
    """run(map_fn, splits, reduce_fn) with re-execution + backup tasks."""

    def __init__(self, pool: WorkerPool, *, lease_s: float = 2.0,
                 spec_threshold: float = 0.75, max_attempts: int = 4,
                 poll_s: float = 0.01):
        self.pool = pool
        self.lease_s = lease_s
        self.spec_threshold = spec_threshold
        self.max_attempts = max_attempts
        self.poll_s = poll_s
        # telemetry the tests assert on
        self.reexecutions = 0
        self.speculative_launched = 0
        self.worker_deaths = 0

    # -- internals ----------------------------------------------------------
    def _exec(self, map_fn, splits, task_id: int, attempt: int, worker: int,
              out_q: "queue.Queue"):
        t0 = time.time()
        slow = self.pool.slow.get(worker, 0.0)
        if slow:
            time.sleep(slow)
        if worker in self.pool.dead:
            return  # silent death: no result, no heartbeat -> lease expiry
        if self.pool.rng.random() < self.pool.fail_prob:
            return  # crashed mid-task
        try:
            value = map_fn(splits[task_id])
        except Exception as e:  # noqa: BLE001 — surfaced via queue
            out_q.put(("error", task_id, attempt, worker, e))
            return
        out_q.put(("ok", TaskResult(task_id, value, worker, attempt,
                                    time.time() - t0)))

    def run(self, map_fn: Callable[[Any], Any], splits: Sequence[Any],
            reduce_fn: Optional[Callable[[List[Any]], Any]] = None) -> Any:
        n = len(splits)
        results: Dict[int, TaskResult] = {}
        attempts: Dict[int, int] = {i: 0 for i in range(n)}
        inflight: List[_Attempt] = []
        out_q: "queue.Queue" = queue.Queue()
        pending = list(range(n))
        next_worker = [0]

        def launch(task_id: int):
            w = next_worker[0] % self.pool.n
            next_worker[0] += 1
            attempts[task_id] += 1
            att = attempts[task_id]
            if att > self.max_attempts:
                raise RuntimeError(f"task {task_id} exceeded max attempts")
            rec = _Attempt(task_id, att, w, time.time(),
                           time.time() + self.lease_s)
            inflight.append(rec)
            th = threading.Thread(
                target=self._exec, args=(map_fn, splits, task_id, att, w,
                                         out_q), daemon=True)
            th.start()

        while pending:
            launch(pending.pop(0))

        spec_done = False
        while len(results) < n:
            # drain ALL queued results this iteration: with many splits,
            # taking one per poll would add up to poll_s latency per
            # completed task.
            ready = []
            try:
                ready.append(out_q.get(timeout=self.poll_s))
                while True:
                    ready.append(out_q.get_nowait())
            except queue.Empty:
                pass
            for kind, *payload in ready:
                if kind == "ok":
                    res: TaskResult = payload[0]
                    if res.task_id not in results:   # first result wins
                        results[res.task_id] = res
                    inflight[:] = [a for a in inflight
                                   if a.task_id != res.task_id]
                else:
                    _, task_id, attempt, worker, err = (kind, *payload)
                    raise err
            now = time.time()
            # lease expiry -> declare worker dead, re-execute
            expired = [a for a in inflight if a.deadline < now
                       and a.task_id not in results]
            for a in expired:
                inflight.remove(a)
                self.worker_deaths += 1
                self.reexecutions += 1
                launch(a.task_id)
            # speculative backups for stragglers
            if (not spec_done
                    and len(results) >= self.spec_threshold * n):
                stragglers = {a.task_id for a in inflight
                              if a.task_id not in results}
                for t in stragglers:
                    self.speculative_launched += 1
                    launch(t)
                spec_done = True
        ordered = [results[i].value for i in range(n)]
        return reduce_fn(ordered) if reduce_fn else ordered
