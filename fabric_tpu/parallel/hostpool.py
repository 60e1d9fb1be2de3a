"""Host staging worker pool: sharding the per-block HOST pipeline
across cores.

PR 3 made the device lane mesh-parallel, but the host side of every
1000-tx block (envelope parse, per-signature admission + Montgomery
batch inversion + residue dgemm, device-path preprocessing) stayed a
single thread feeding a now-parallel device — the classic host-bound
input pipeline every accelerator stack solves with a worker pool ahead
of the device (tf.data prefetch workers; the batched-ECDSA GPU
literature's CPU staging pools).  This module is that pool, shaped for
this repo's staging work:

* threads by DEFAULT — the hot loops are numpy dgemms, ``hashlib``,
  the native C pre-parser, and ``int.to_bytes`` batches, all of which
  release the GIL, so threads scale on the very loops that matter
  without pickling block-sized arrays across process boundaries;
* an optional PROCESS mode behind the ``mode`` knob for workloads that
  really are Python-bound — tasks submitted there must be picklable
  top-level functions (the validator keeps its bound-method fan-out on
  threads and says so);
* slice helpers that shard a batch's lane axis at bucket boundaries
  (multiples of ``align``) into per-worker contiguous ranges, so the
  per-shard outputs CONCATENATE back bit-trivially — every staged lane
  is lane-independent, which is what pins pooled ≡ serial the same way
  sharded ≡ single-device is pinned on the mesh;
* per-task telemetry: ``host_stage_pool_seconds{stage,worker}`` rides
  the process metrics registry so the pool's occupancy is observable
  next to the validator stage histograms.

The knob (nodeconfig ``host_stage_workers``) resolves exactly like
``mesh_devices``: 0 = off (serial staging — the safe default, CPU-only
hosts pay nothing), -1 = one worker per core, n = n workers; a
resolution below 2 returns None because a 1-worker pool is only queue
overhead.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor


def clamp_workers(n: int, cores: int | None = None) -> int:
    """The ONE resize-clamp rule (shared by ``set_workers`` and the
    validator's post-swap size prediction, so the two can never
    drift): a pool runs at least 2 workers and at most the core
    count — dropping below 2 is a close, not a resize."""
    if cores is None:
        cores = os.cpu_count() or 1
    return max(2, min(int(n), max(2, cores)))


def _pool_hist():
    from fabric_tpu.ops_metrics import global_registry

    return global_registry().histogram(
        "host_stage_pool_seconds",
        "host staging pool task time (s) by stage and worker",
        buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                 0.1, 0.25, 1.0, float("inf")),
    )


def _pool_tracer():
    from fabric_tpu.observe import global_tracer

    return global_tracer()


def _label_task_error(e: BaseException, stage: str, worker: str) -> None:
    """Attach the failing stage/worker to a task exception in place —
    the TYPE is preserved (callers catch specific exceptions) and the
    first string arg gains a ``[host pool stage=… worker=…]`` suffix so
    logs name the slot.  Idempotent across re-submission layers."""
    if getattr(e, "fab_stage", None) is not None:
        return
    try:
        e.fab_stage = stage
        e.fab_worker = worker
        if e.args and isinstance(e.args[0], str):
            e.args = (
                f"{e.args[0]} [host pool stage={stage} worker={worker}]",
            ) + e.args[1:]
    except Exception as label_err:
        # frozen/slots exception types: labels are best-effort — the
        # original error still propagates unlabeled
        import logging

        logging.getLogger("fabric_tpu.hostpool").debug(
            "could not label task error: %s", label_err
        )


class HostStagePool:
    """Persistent staging worker pool (see module docstring).

    Construct via :func:`resolve_host_pool`; the pool is created once
    per validator and reused for every block — worker spin-up must not
    ride the per-block critical path.
    """

    def __init__(self, workers: int, mode: str = "thread"):
        if workers < 2:
            raise ValueError("HostStagePool needs >= 2 workers "
                             "(resolve_host_pool returns None below that)")
        if mode not in ("thread", "process"):
            raise ValueError(f"host pool mode {mode!r}: "
                             "expected 'thread' or 'process'")
        self.workers = int(workers)
        self.mode = mode
        if mode == "process":
            import multiprocessing as mp

            # spawn, not fork: this process is multithreaded the
            # moment jax loads, and forking a threaded process can
            # deadlock the child in a held allocator/runtime lock
            self._ex = ProcessPoolExecutor(
                self.workers, mp_context=mp.get_context("spawn")
            )
        else:
            self._ex = ThreadPoolExecutor(
                self.workers, thread_name_prefix="fabtpu-hoststage"
            )
        self._hist = _pool_hist()
        self._trc = _pool_tracer()
        # recent per-task durations for the bench's host_stage
        # sub-breakdown (p50 per shard) — bounded, lock-guarded
        self._durs: deque = deque(maxlen=1024)
        self._lock = threading.Lock()
        self._tasks = 0
        # runtime resize (the autopilot's host_stage_workers
        # actuator): set_workers latches a target; the swap happens at
        # a TASK BOUNDARY — the next submit that finds the pool idle
        # (no in-flight tasks) drains the old executor and rebuilds.
        # ``_active`` counts in-flight tasks; both are guarded by the
        # same lock as the telemetry so a submitter can never hand a
        # task to an executor mid-teardown.
        self._active = 0
        self._pending_workers: int | None = None

    # -- submission --------------------------------------------------------

    def _observe(self, stage: str, worker: str, dt: float) -> None:
        self._hist.observe(dt, stage=stage, worker=worker)
        with self._lock:
            self._durs.append(dt)
            self._tasks += 1

    def _timed(self, fn, stage: str, parent):
        """Wrap ``fn`` to observe its duration from INSIDE the worker
        (thread mode) so the worker label names the executing slot.
        ``parent`` is the SUBMITTING thread's current tracer span,
        captured at submit time — the worker adopts it so its task
        span lands in the right block tree (the explicit cross-thread
        handoff; thread-locals do not follow executor tasks).

        A task exception is ANNOTATED with the failing stage/worker
        before it propagates (``fab_stage``/``fab_worker`` attributes
        plus a message suffix): by the time the ordered ``map`` gather
        re-raises it on the submitting thread, the executing slot is
        long gone — without the labels a one-in-N shard failure is
        undebuggable.  The ``hostpool.task`` fault-injection point
        fires here so a chaos plan can kill exactly one worker task."""
        trc = self._trc

        def run(*args, **kwargs):
            from fabric_tpu import faults as _faults

            name = threading.current_thread().name
            worker = name.rsplit("_", 1)[-1] if "_" in name else name
            t0 = time.perf_counter()
            try:
                with trc.span(stage, parent=parent, worker=worker):
                    _faults.fire("hostpool.task", stage=stage)
                    return fn(*args, **kwargs)
            except BaseException as e:
                _label_task_error(e, stage, worker)
                raise
            finally:
                self._observe(stage, worker, time.perf_counter() - t0)
        return run

    # -- runtime resize (autopilot actuator) -------------------------------

    def set_workers(self, n: int) -> None:
        """Request a new worker count, applied drain-and-rebuild at
        the next task boundary: the first ``submit`` that finds the
        pool IDLE swaps in a fresh executor (the old one, empty, shuts
        down instantly).  In-flight tasks always finish on the
        executor that started them — a resize can never strand or
        interleave a shard.  ``n`` clamps via :func:`clamp_workers`
        (a pool below 2 workers is not a pool; dropping to 0 is a
        close, not a resize)."""
        n = clamp_workers(n)
        with self._lock:
            self._pending_workers = None if n == self.workers else n

    def _maybe_resize_locked(self):
        """Caller holds the lock.  Returns the executor a new task
        must be submitted to (post-swap when a pending resize applies
        at this idle boundary)."""
        n = self._pending_workers
        if n is None or self._active > 0:
            return self._ex
        self._pending_workers = None
        old = self._ex
        if self.mode == "process":
            import multiprocessing as mp

            self._ex = ProcessPoolExecutor(
                n, mp_context=mp.get_context("spawn")
            )
        else:
            self._ex = ThreadPoolExecutor(
                n, thread_name_prefix="fabtpu-hoststage"
            )
        self.workers = n
        # idle by the _active==0 guard: shutdown returns immediately
        old.shutdown(wait=False)
        return self._ex

    def _task_done(self, _fut) -> None:
        with self._lock:
            self._active -= 1

    def submit(self, fn, *args, stage: str = "task", **kwargs):
        """Submit one task; returns a Future.  Thread mode times the
        task inside its worker; process mode times submit→done in the
        parent (the child's registry is not this process's)."""
        with self._lock:
            ex = self._maybe_resize_locked()
            # counted BEFORE the lock releases: a concurrent resize
            # check can never see the pool idle while this task is on
            # its way to ``ex``
            self._active += 1
        try:
            if self.mode == "process":
                t0 = time.perf_counter()
                fut = ex.submit(fn, *args, **kwargs)
                fut.add_done_callback(
                    lambda f: self._observe(stage, "proc",
                                            time.perf_counter() - t0)
                )
            else:
                fut = ex.submit(
                    self._timed(fn, stage, self._trc.current()),
                    *args, **kwargs
                )
        except BaseException:
            with self._lock:
                self._active -= 1
            raise
        fut.add_done_callback(self._task_done)
        return fut

    def map(self, fn, items, stage: str = "task") -> list:
        """Ordered parallel map: fan every item out, gather in order.
        The FIRST task exception (submission order) propagates at the
        gather with the failing stage/worker labels attached — never a
        wedged gather, never a silently dropped shard; the remaining
        futures still run to completion (staging tasks are short and
        side-effect-free)."""
        futs = [self.submit(fn, it, stage=stage) for it in items]
        out = []
        for f in futs:
            try:
                out.append(f.result())
            except BaseException as e:
                # thread mode labeled inside the worker; process mode
                # (exception pickled back from the child) labels here
                _label_task_error(e, stage, "proc")
                raise
        return out

    # -- lane-axis sharding ------------------------------------------------

    def slice_bounds(self, n: int, align: int = 1) -> list[tuple[int, int]]:
        """Split [0, n) into ≤ ``workers`` contiguous ranges whose
        boundaries are multiples of ``align`` (bucket boundaries —
        MIN_BUCKET for signature columns), so each worker stages a
        self-contained slab and concatenation needs no re-bucketing.
        The tail range absorbs the remainder."""
        if n <= 0:
            return []
        per = -(-n // self.workers)
        per = -(-per // align) * align  # round the stride UP to align
        out = []
        lo = 0
        while lo < n:
            hi = min(n, lo + per)
            out.append((lo, hi))
            lo = hi
        return out

    def map_slices(self, n: int, fn, stage: str = "task",
                   align: int = 1) -> list:
        """``fn(lo, hi)`` over :meth:`slice_bounds`, ordered results."""
        return self.map(lambda b: fn(*b), self.slice_bounds(n, align),
                        stage=stage)

    # -- introspection / lifecycle ----------------------------------------

    def stats(self) -> dict:
        """Pool occupancy summary for bench extras: worker count and
        the p50 of recent per-task (per-shard) durations in ms."""
        with self._lock:
            durs = sorted(self._durs)
            tasks = self._tasks
            pending = self._pending_workers
        p50 = durs[len(durs) // 2] if durs else 0.0
        return {
            "workers": self.workers,
            "mode": self.mode,
            "tasks": tasks,
            "per_shard_p50_ms": round(p50 * 1000.0, 3),
            **({"pending_workers": pending} if pending is not None
               else {}),
        }

    def shutdown(self) -> None:
        self._ex.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.shutdown()
        return False


def resolve_host_pool(workers: int) -> HostStagePool | None:
    """Production knob → pool (the nodeconfig ``host_stage_workers``
    knob; mirrors parallel.mesh.resolve_mesh):

    0  = staging pool off (serial host staging — the safe default);
    -1 = one worker per core;
    n  = n workers (clamped to the core count; below 2 → None, a
         1-worker pool is queue overhead with no parallelism).
    """
    if workers == 0:
        return None
    cores = os.cpu_count() or 1
    n = cores if workers < 0 else min(workers, cores)
    if n < 2:
        return None
    return HostStagePool(n)
