"""Concurrent control-plane scheduler: queued admission + worker pool.

The paper's control loop (§IV-D) processes one task at a time; real PNN
serving is many-client, so this module turns the orchestrator's
match → admit → invoke → validate path into a sustained-throughput pipeline:

- a bounded task queue gives explicit backpressure (a full queue blocks the
  producer instead of growing without bound);
- a worker pool keeps many tasks in flight so every substrate's
  ``max_concurrent`` budget stays saturated instead of serializing behind a
  single control loop;
- per-task deadlines bound both queue wait and substrate admission
  (tasks whose deadline lapses while queued are rejected without ever
  touching a substrate);
- results are futures, so clients can pipeline (``submit_async``), batch
  (``submit_many``) or quiesce (``drain``).

``Orchestrator.submit`` remains the one-shot synchronous path; both go
through ``Orchestrator.execute``, so scheduling changes placement *timing*
but never placement *semantics*.
"""
from __future__ import annotations

import queue
import threading
from collections import deque
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple, Union

from repro.core.errors import ErrorCode
from repro.core.invocation import InvocationResult
from repro.core.orchestrator import Orchestrator, OrchestrationTrace
from repro.core.simclock import Clock, SYSTEM_CLOCK
from repro.core.tasks import TaskRequest

_STOP = object()


class SchedulerClosed(RuntimeError):
    pass


class ControlPlaneScheduler:
    """Bounded-queue, worker-pool front end over an :class:`Orchestrator`.

    Usage::

        with ControlPlaneScheduler(orch, workers=16) as sched:
            futs = [sched.submit_async(t) for t in tasks]
            results = [f.result() for f in futs]

    or batched: ``results = sched.submit_many(tasks)``.
    """

    def __init__(self, orchestrator: Orchestrator, workers: int = 8,
                 queue_size: int = 256,
                 default_deadline_s: Optional[float] = None,
                 health_tick_interval_s: float = 0.05,
                 clock: Optional[Clock] = None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        self.orchestrator = orchestrator
        self.workers = workers
        self.default_deadline_s = default_deadline_s
        # injectable time source: defaults to the orchestrator's clock so
        # scheduler deadlines and the orchestrator's admission deadlines
        # share one timebase (virtual under the scenario simulator)
        self.clock: Clock = clock or getattr(orchestrator, "clock",
                                             SYSTEM_CLOCK)
        # background probe cadence for the health manager (0 disables):
        # cooled-down breakers half-open on the tick, not only when a task
        # happens to rank the resource
        self.health_tick_interval_s = health_tick_interval_s
        self._health_stop = threading.Event()
        self._health_thread: Optional[threading.Thread] = None  # guarded_by: _lock
        self._queue: "queue.Queue" = queue.Queue(maxsize=queue_size)
        self._threads: List[threading.Thread] = []              # guarded_by: _lock
        self._started = False                                   # guarded_by: _lock
        self._closed = False                                    # guarded_by: _lock
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        # notified whenever a worker takes an item off the bounded queue —
        # producers blocked on a full queue park here instead of polling
        self._space = threading.Condition(self._lock)
        self._pending = 0   # guarded_by: _lock — queued + in-flight tasks
        self._stats_lock = threading.Lock()
        self._status_counts: Dict[str, int] = {}    # guarded_by: _stats_lock
        self._per_resource: Dict[str, int] = {}     # guarded_by: _stats_lock
        # recent completion timestamps: the observed DRAIN RATE for
        # retry_after_s (end-to-end latencies include queue wait, which
        # would inflate a backoff hint exactly when the queue is busy)
        self._done_times: "deque[float]" = deque(maxlen=32)  # guarded_by: _stats_lock

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "ControlPlaneScheduler":
        with self._lock:
            if self._closed:
                raise SchedulerClosed("scheduler already shut down")
            if self._started:
                return self
            self._started = True
            for i in range(self.workers):
                t = threading.Thread(target=self._worker, daemon=True,
                                     name=f"phys-mcp-worker-{i}")
                t.start()
                self._threads.append(t)
            if (self.health_tick_interval_s
                    and getattr(self.orchestrator, "health", None) is not None):
                self._health_thread = threading.Thread(
                    target=self._health_probe_loop, daemon=True,
                    name="phys-mcp-health-ticker")
                self._health_thread.start()
        return self

    def __enter__(self) -> "ControlPlaneScheduler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(wait=exc == (None, None, None))

    def shutdown(self, wait: bool = True) -> None:
        # setting _closed under the lock before any sentinel is enqueued
        # guarantees no real task can land behind a sentinel: submit_async
        # re-checks _closed under this same lock right before its put
        with self._lock:
            if self._closed:
                return
            self._closed = True
            started = self._started
            threads = list(self._threads)
            # snapshot under the lock: start() writes _health_thread while
            # holding _lock, so an unlocked read below could miss it
            health_thread = self._health_thread
        self._health_stop.set()
        with self._lock:
            # wake producers parked on queue space so they observe _closed
            self._space.notify_all()
        if started:
            for _ in range(self.workers):
                self._queue.put((_STOP, None, None))
            if wait:
                for t in threads:
                    t.join()
                if health_thread is not None:
                    health_thread.join()

    def _health_probe_loop(self) -> None:
        """Background probe ticks: periodically promote cooled-down OPEN
        breakers to PROBATION so re-admission does not depend on task
        arrival timing.  Exceptions never kill the ticker.  The wait goes
        through the injected clock, so a virtual-clock deployment ticks in
        virtual time."""
        health = self.orchestrator.health
        while not self.clock.wait_event(self._health_stop,
                                        self.health_tick_interval_s):
            try:
                health.tick()
            except Exception:              # noqa: BLE001 — keep ticking
                pass

    # -- submission -----------------------------------------------------------
    def submit_async(self, task: TaskRequest,
                     deadline_s: Optional[float] = None
                     ) -> "Future[Tuple[InvocationResult, OrchestrationTrace]]":
        """Enqueue one task; returns a future resolving to the same
        ``(result, trace)`` pair ``Orchestrator.submit`` gives.  Blocks for
        queue space when the bounded queue is full (backpressure)."""
        self.start()                 # raises SchedulerClosed when shut down
        fut: Future = Future()
        # only an EXPLICIT deadline (per-call or scheduler default) rejects
        # tasks that lapse while queued; a task's latency_budget_ms stays the
        # soft signal it is on the serial path (Orchestrator.execute pins it
        # to bound admission blocking identically in both modes)
        budget = deadline_s if deadline_s is not None \
            else self.default_deadline_s
        clock = self.clock
        deadline = (clock.monotonic() + budget) if budget is not None else None
        # closed-check + enqueue are atomic w.r.t. shutdown(), so a task is
        # either rejected here or is guaranteed to sit ahead of the stop
        # sentinels.  A full queue parks the producer on the _space
        # condition (workers notify after every dequeue, shutdown notifies
        # all), so backpressure costs no polling: the producer wakes the
        # moment a slot frees instead of rediscovering it up to 10ms late.
        with self._lock:
            while True:
                if self._closed:
                    raise SchedulerClosed("scheduler already shut down")
                try:
                    self._queue.put_nowait((task, fut, deadline))
                except queue.Full:
                    clock.wait_for(
                        self._space,
                        lambda: self._closed or not self._queue.full())
                else:
                    self._pending += 1
                    break
        return fut

    def submit_many(self, tasks: Sequence[TaskRequest],
                    deadline_s: Optional[float] = None, wait: bool = True
                    ) -> Union[List[Tuple[InvocationResult, OrchestrationTrace]],
                               List[Future]]:
        """Enqueue a batch.  With ``wait=True`` (default) blocks until every
        task resolved and returns ``(result, trace)`` pairs in submission
        order; with ``wait=False`` returns the unresolved futures instead."""
        futs = [self.submit_async(t, deadline_s=deadline_s) for t in tasks]
        if not wait:
            return futs
        return [f.result() for f in futs]

    def submit_speculative(self, task: TaskRequest,
                           deadline_s: Optional[float] = None
                           ) -> Tuple[Optional[InvocationResult], Future]:
        """Speculate mode: a VALID executable twin answers immediately; the
        real execution is enqueued for asynchronous confirmation.

        Returns ``(speculative_result, confirmation_future)``.  When a twin
        could speculate, the future resolves to ``(real_result, trace,
        verdict)`` where the verdict records confirmed / divergence /
        retro_invalidated — a beyond-tolerance mismatch retro-invalidates
        the twin (its next ``valid()`` fails until an explicit re-sync).
        When no valid twin exists the speculative result is None and the
        future is the plain ``submit_async`` future resolving to
        ``(result, trace)``.
        """
        self.start()
        orch = self.orchestrator
        spec = orch.twin_exec.speculate(task, orch.matcher)
        # the confirmation run must execute on real hardware: strip the twin
        # mode (clone() un-aliases the metadata dict) from the enqueued copy
        confirm_task = task.clone(twin_mode=None) \
            if hasattr(task, "clone") else task
        real_fut = self.submit_async(confirm_task, deadline_s=deadline_s)
        if spec is None:
            return None, real_fut
        twin_result, rid = spec
        confirm_fut: Future = Future()

        def _confirm(f: Future) -> None:
            try:
                real_result, trace = f.result()
            except BaseException as e:          # noqa: BLE001 — via future
                confirm_fut.set_exception(e)
                return
            verdict = orch.twin_exec.confirm_speculation(
                task, rid, twin_result, real_result)
            confirm_fut.set_result((real_result, trace, verdict))

        real_fut.add_done_callback(_confirm)
        return twin_result, confirm_fut

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every enqueued task has resolved (or timeout).
        Returns True when the scheduler is fully quiesced."""
        clock = self.clock
        end = None if timeout is None else clock.monotonic() + timeout
        with self._idle:
            while self._pending > 0:
                remaining = None if end is None else end - clock.monotonic()
                if remaining is not None and remaining <= 0:
                    return False
                clock.wait_for(self._idle, lambda: self._pending == 0,
                               timeout=remaining)
        return True

    # -- worker loop ----------------------------------------------------------
    def _worker(self) -> None:
        while True:
            task, fut, deadline = self._queue.get()
            with self._lock:
                self._space.notify()       # one queue slot freed
            if task is _STOP:
                return
            try:
                if not fut.set_running_or_notify_cancel():
                    continue
                if deadline is not None and self.clock.monotonic() > deadline:
                    # queue saturation endpoint: an opted-in task whose
                    # deadline lapsed while queued is served by a valid twin
                    # instead of rejected (same funnel as the orchestrator's)
                    try:
                        result, trace = self.orchestrator._reject_or_twin(
                            task, OrchestrationTrace(task.task_id),
                            "deadline exceeded while queued",
                            code=ErrorCode.DEADLINE)
                    except BaseException as e:  # noqa: BLE001 — via future
                        fut.set_exception(e)
                        self._account(None)
                        continue
                    fut.set_result((result, trace))
                    self._account(result)
                    continue
                try:
                    result, trace = self.orchestrator.execute(
                        task, deadline=deadline)
                except BaseException as e:   # noqa: BLE001 — surfaced via future
                    fut.set_exception(e)
                    self._account(None)
                    continue
                fut.set_result((result, trace))
                self._account(result)
            finally:
                with self._idle:
                    self._pending -= 1
                    self._idle.notify_all()

    def _account(self, result: Optional[InvocationResult]) -> None:
        now = self.clock.monotonic()
        with self._stats_lock:
            status = result.status if result is not None else "error"
            self._status_counts[status] = \
                self._status_counts.get(status, 0) + 1
            if result is not None and result.resource_id:
                self._per_resource[result.resource_id] = \
                    self._per_resource.get(result.resource_id, 0) + 1
            self._done_times.append(now)

    # -- observability --------------------------------------------------------
    def stats(self) -> Dict:
        """Live counters: tasks resolved, queued + in flight, status mix and
        per-substrate placement."""
        with self._stats_lock:
            counts = dict(self._status_counts)
            per_resource = dict(self._per_resource)
        return {
            "done": sum(counts.values()),
            "pending": self.pending,
            "statuses": counts,
            "per_resource": per_resource,
        }

    @property
    def pending(self) -> int:
        with self._lock:
            return self._pending

    #: retry_after_s clamps: never tell a client "retry immediately" into a
    #: saturated queue, never park it for more than this many seconds
    MIN_RETRY_AFTER_S = 0.05
    MAX_RETRY_AFTER_S = 5.0

    def retry_after_s(self) -> float:
        """Informed-backoff hint for QUEUE_SATURATED rejections: how long
        until this plane has likely worked off its current backlog, from
        the OBSERVED recent drain rate (completions per second across the
        worker pool — enqueue-to-resolve latencies would double-count the
        queue wait the backlog already represents).  Clamped so clients
        neither hammer nor stall."""
        with self._lock:
            backlog = self._pending
        with self._stats_lock:
            times = list(self._done_times)
        if len(times) >= 2 and times[-1] > times[0]:
            drain_per_s = (len(times) - 1) / (times[-1] - times[0])
            est = backlog / drain_per_s
        else:
            # no drain history yet: assume fast tasks, stay near the floor
            est = backlog * 0.01
        return round(min(self.MAX_RETRY_AFTER_S,
                         max(self.MIN_RETRY_AFTER_S, est)), 3)
