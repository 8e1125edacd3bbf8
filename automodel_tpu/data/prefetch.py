"""Overlapped input pipeline: host prefetch threads + device double-buffering.

The reference hides input cost behind torch DataLoader worker processes and
CUDA-stream H2D copies. The TPU-native train loop had neither: every optimizer
step serially paid ``next(it)`` (host collation/packing), ``stack_batches``,
and a blocking ``jax.device_put`` before the device did any work — the
``data_wait`` goodput bucket was pure dead time. This module overlaps all
three with device compute:

- :class:`HostPrefetcher` — one background thread owns the ``StepScheduler``
  iterator and runs collation + ``stack_batches`` off the critical path into a
  bounded FIFO queue. Single-producer/single-consumer, so batch order is
  exactly the synchronous order. Worker exceptions and end-of-data propagate
  to the consumer at the position they occurred.
- :class:`DevicePrefetcher` — keeps ``device_depth`` stacks already
  ``device_put`` to the batch ``NamedSharding``. JAX dispatch is asynchronous,
  so issuing the transfer for step k+1 while step k executes makes the H2D
  copy free; the consumer only ever blocks on a *true* stall (host collation
  slower than the device).
- :class:`InputPipeline` — the facade the recipes hold. ``prefetch.enabled:
  false`` degrades to the exact synchronous fetch path (same code shape, no
  threads), which is also the determinism reference for tests.

One batch in hand: the train loop takes step N+1's batch with ``get(ahead=True)``
right after it has dispatched step N, on its own thread, while the device works
(``recipes/llm/train_ft.py`` ``_run_step_loop``), and calls ``consume(item)`` when
it starts that step. So on the synchronous path too the live scheduler and
dataloader stand one step past what the loop has consumed, and on the threaded
path a batch handed out is not yet a batch consumed.

Checkpoint-exact resume, one rule for both paths: ``(step_scheduler,
dataloader)`` state is snapshotted *at the yield point of each item* (by the
worker, or inline). The pipeline tracks the snapshot of the last item the
training loop actually **consumed**; ``client_states()`` hands that snapshot to
the checkpointer instead of the live objects (which the look-ahead has advanced
by one step, the worker by up to ``host_depth + device_depth`` more). Restoring
it replays every in-flight-but-unconsumed batch in order, the one in hand
included — resume is bit-identical. A rollback drops the batch in hand with the
pipeline.

Shutdown: ``close()`` is idempotent and never deadlocks on a full queue — the
worker checks a stop event around every blocking put. The recipes close the
pipeline before an in-process rollback restores scheduler/dataloader state
(the worker must stop mutating them first) and on every exit from a train
pass (done / preempted / exception).
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
from typing import Any, Callable, Iterator

logger = logging.getLogger(__name__)

__all__ = ["PrefetchConfig", "StepBatch", "HostPrefetcher", "DevicePrefetcher",
           "InputPipeline"]


@dataclasses.dataclass
class PrefetchConfig:
    """The ``dataloader.prefetch`` YAML section.

    .. code-block:: yaml

        dataloader:
          prefetch:
            enabled: true
            host_depth: 2     # stacked batches buffered on host
            device_depth: 2   # stacks already device_put (double-buffering)
    """

    enabled: bool = False
    host_depth: int = 2
    device_depth: int = 2

    def __post_init__(self):
        if self.host_depth < 1:
            raise ValueError(f"prefetch.host_depth must be >= 1, got {self.host_depth}")
        if self.device_depth < 1:
            raise ValueError(f"prefetch.device_depth must be >= 1, got {self.device_depth}")

    @classmethod
    def from_config(cls, raw: Any) -> "PrefetchConfig":
        if raw is None:
            return cls()
        if hasattr(raw, "to_dict"):
            raw = raw.to_dict()
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in dict(raw).items() if k in known})


@dataclasses.dataclass
class StepBatch:
    """One optimizer step's input plus the state needed to resume *before* it
    was consumed. ``client_state`` holds post-yield ``state_dict()`` snapshots
    of the scheduler/dataloader: restore them and the NEXT produced item is
    step+1 — everything later in the pipeline replays."""

    step: int
    epoch: int
    stack: Any
    client_state: dict[str, Any]


class _End:
    """Queue sentinel: the scheduler iterator is exhausted."""


class _Error:
    """Queue sentinel: the worker raised; re-raise at the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


_END = _End()
_NOT_READY = object()  # get_nowait(): nothing buffered yet (worker still busy)


def _iter_source(scheduler: Any) -> Iterator[list]:
    """The scheduler's step iterator for a pipeline, threaded or not."""
    if callable(getattr(scheduler, "batches", None)):
        # collective_sigterm=False: the fetch issues no multi-host collective
        # (off the main thread it would race the loop's own; on it, ahead of a
        # running step, it would queue behind the device). It stops on the local
        # flag and the main loop owns the agreed decision
        return scheduler.batches(collective_sigterm=False)
    return iter(scheduler)


def _snapshot_states(scheduler: Any, dataloader: Any) -> dict[str, Any]:
    """state_dict snapshots of the two objects the prefetch worker mutates."""
    snap: dict[str, Any] = {}
    if hasattr(scheduler, "state_dict"):
        snap["step_scheduler"] = dict(scheduler.state_dict())
    if hasattr(dataloader, "state_dict"):
        snap["dataloader"] = dict(dataloader.state_dict())
    return snap


class HostPrefetcher:
    """Background-thread producer of :class:`StepBatch` items.

    The worker owns the scheduler iterator exclusively — scheduler and
    dataloader state is only ever mutated from the worker thread while the
    prefetcher is live. SIGTERM inside the worker is checked against the
    *local* flag only (no collectives off the main thread); the training loop
    performs the pod-agreed check per consumed step.
    """

    def __init__(
        self,
        scheduler: Any,
        dataloader: Any,
        stack_fn: Callable[[list], Any],
        depth: int = 2,
        name: str = "host-prefetch",
    ):
        self.scheduler = scheduler
        self.dataloader = dataloader
        self.stack_fn = stack_fn
        self._q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- worker side
    def _iter_source(self) -> Iterator[list]:
        return _iter_source(self.scheduler)

    def _snapshot(self) -> dict[str, Any]:
        return _snapshot_states(self.scheduler, self.dataloader)

    def _put(self, item: Any) -> bool:
        """Bounded put that can always be interrupted by close()."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self) -> None:
        try:
            for batches in self._iter_source():
                # the scheduler just advanced to this item's step: snapshot the
                # post-yield state BEFORE stacking so the pair (stack, state)
                # is consistent even if stack_fn raises later
                step = int(getattr(self.scheduler, "step", 0))
                epoch = int(getattr(self.scheduler, "epoch", 0))
                state = self._snapshot()
                stack = self.stack_fn(batches)
                if not self._put(StepBatch(step, epoch, stack, state)):
                    return  # closed mid-flight
                if self._stop.is_set():
                    return
            self._put(_END)
        except BaseException as exc:  # noqa: BLE001 — re-raised at the consumer
            if not self._stop.is_set():
                self._put(_Error(exc))

    # ----------------------------------------------------------- consumer side
    def _resolve(self, item: Any) -> Any:
        if item is _END:
            self._q.put(_END)  # stay terminal for later calls (capacity >= 1 here)
            return None
        if isinstance(item, _Error):
            self._q.put(item)
            raise item.exc
        return item

    def get(self) -> StepBatch | None:
        """Next item in order; None at end-of-data; re-raises worker errors."""
        while True:
            try:
                return self._resolve(self._q.get(timeout=0.1))
            except queue.Empty:
                if not self._thread.is_alive():
                    # the worker may have enqueued its final item(s) and exited
                    # in the window between the timeout and the liveness check;
                    # it is dead now, so one non-blocking drain is race-free
                    try:
                        return self._resolve(self._q.get_nowait())
                    except queue.Empty:
                        # truly empty: end-of-data (close() raced the worker,
                        # or it was killed without a sentinel)
                        return None

    def get_nowait(self) -> Any:
        """Non-blocking: a StepBatch, None (end), or _NOT_READY."""
        try:
            return self._resolve(self._q.get_nowait())
        except queue.Empty:
            return _NOT_READY

    @property
    def ready(self) -> int:
        return self._q.qsize()

    def close(self, join_timeout_s: float = 5.0) -> None:
        """Stop the worker and drain the queue. Idempotent, deadlock-free:
        draining frees the worker from any blocking put, and the put loop
        re-checks the stop event every 50ms."""
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                if not self._thread.is_alive():
                    break
                self._thread.join(timeout=0.05)
                if self._thread.is_alive():
                    continue
                break
        self._thread.join(timeout=join_timeout_s)
        if self._thread.is_alive():  # pragma: no cover — daemon thread backstop
            logger.warning("host prefetch worker did not exit within %.1fs",
                           join_timeout_s)


class DevicePrefetcher:
    """Keep ``depth`` stacks already in flight to the device.

    ``put_fn`` (the recipe's ``_device_put_stack``) issues asynchronous H2D
    transfers to the batch NamedSharding; keeping ``depth`` >= 2 items inside
    means step k+1's transfer overlaps step k's compute. Runs entirely on the
    consumer thread — only the host stacking sits behind a thread.
    """

    def __init__(self, source: HostPrefetcher, put_fn: Callable[[Any], Any],
                 depth: int = 2):
        self.source = source
        self.put_fn = put_fn
        self.depth = max(int(depth), 1)
        self._buf: list[StepBatch] = []
        self._exhausted = False
        self._pending_error: BaseException | None = None

    def _transfer(self, item: StepBatch) -> StepBatch:
        return dataclasses.replace(item, stack=self.put_fn(item.stack))

    def _top_up(self) -> None:
        """Issue transfers for every host-ready stack, without blocking. Errors
        — from the source worker AND from ``put_fn`` itself — are deferred
        until the already-transferred items are consumed, so the exception
        surfaces at the same batch position as the sync path."""
        while len(self._buf) < self.depth and not self._exhausted and self._pending_error is None:
            try:
                item = self.source.get_nowait()
            except BaseException as exc:  # noqa: BLE001
                self._pending_error = exc
                return
            if item is _NOT_READY:
                return
            if item is None:
                self._exhausted = True
                return
            try:
                self._buf.append(self._transfer(item))
            except BaseException as exc:  # noqa: BLE001 — device_put for batch
                # k+n must not outrank the buffered good batches k..k+n-1
                self._pending_error = exc
                return

    def get(self) -> StepBatch | None:
        if not self._buf:
            if self._pending_error is not None:
                exc, self._pending_error = self._pending_error, None
                raise exc
            if self._exhausted:
                return None
            item = self.source.get()  # true stall: blocks on the host worker
            if item is None:
                self._exhausted = True
                return None
            self._buf.append(self._transfer(item))
        self._top_up()  # issue k+1.. transfers before handing back k
        out = self._buf.pop(0)
        self._top_up()
        return out

    @property
    def ready(self) -> int:
        return len(self._buf)


class InputPipeline:
    """What a recipe's train pass holds: one ``get()`` per optimizer step.

    Prefetch off -> inline fetch/stack/put (the exact pre-pipeline code path,
    minus zero threads); prefetch on -> HostPrefetcher + DevicePrefetcher.
    Either way, ``get()`` returns :class:`StepBatch` or None at end-of-data,
    and ``client_states()`` returns what the checkpointer should persist for
    scheduler/dataloader so resume replays in-flight batches exactly.
    ``get(ahead=True)`` hands a batch out without counting it consumed; the
    loop says ``consume(item)`` when it starts that step.
    """

    def __init__(
        self,
        scheduler: Any,
        dataloader: Any,
        stack_fn: Callable[[list], Any],
        put_fn: Callable[[Any], Any],
        config: PrefetchConfig | None = None,
    ):
        self.config = config or PrefetchConfig()
        self.scheduler = scheduler
        self.dataloader = dataloader
        self.stack_fn = stack_fn
        self.put_fn = put_fn
        self._consumed_state: dict[str, Any] | None = None
        self._in_hand = False  # a batch handed out ahead and not yet consumed
        self._closed = False
        self._host: HostPrefetcher | None = None
        self._device: DevicePrefetcher | None = None
        self._sync_it: Iterator[list] | None = None
        # snapshot BEFORE anything advances the live objects (the worker thread
        # starts at once): until the first batch is consumed, this is the
        # position a checkpoint must persist (client_states falls back to it)
        self._initial_state = _snapshot_states(scheduler, dataloader)
        if self.config.enabled:
            self._host = HostPrefetcher(
                scheduler, dataloader, stack_fn, depth=self.config.host_depth
            )
            self._device = DevicePrefetcher(
                self._host, put_fn, depth=self.config.device_depth
            )
        else:
            self._sync_it = _iter_source(scheduler)

    @property
    def prefetching(self) -> bool:
        return self._device is not None

    def get(self, ahead: bool = False) -> StepBatch | None:
        """The next step's batch, on the device; None at end-of-data. With
        ``ahead`` it is in the caller's hand and not consumed until
        ``consume(item)``: a checkpoint in between persists the position
        before it."""
        if self._device is not None:
            item = self._device.get()
        else:
            batches = next(self._sync_it, None)
            if batches is None:
                return None
            # the live objects stand just past this item: its post-yield
            # snapshot, as the worker takes it on the threaded path
            item = StepBatch(
                step=int(getattr(self.scheduler, "step", 0)),
                epoch=int(getattr(self.scheduler, "epoch", 0)),
                stack=self.put_fn(self.stack_fn(batches)),
                client_state=_snapshot_states(self.scheduler, self.dataloader),
            )
        if item is not None:
            self._in_hand = True
            if not ahead:
                self.consume(item)
        return item

    def consume(self, item: StepBatch) -> None:
        """The loop starts ``item``'s step: its snapshot is the consumed position."""
        self._consumed_state = item.client_state
        self._in_hand = False

    def truncated_by_local_sigterm(self) -> bool:
        """End-of-stream that does NOT mean end of data.

        The prefetch worker iterates with ``collective_sigterm=False`` — it
        stops on this host's LOCAL flag (collectives are banned off the main
        thread), so this host's stream can end while data remains and the pod
        has not agreed to preempt. Treating that as "done" would desync the
        per-step collectives: the other hosts keep stepping and their agreed
        check waits for a partner that has moved on to teardown. True here
        tells the train loop to rebuild the pipeline from the live scheduler
        position (exactly the last consumed step — the worker stops right
        after the item the consumer drained) and keep the step rhythm until
        the pod-agreed check fires.

        Never true of the synchronous path: its stream can only stop on the
        flag behind a batch the loop is running (the look-ahead), and that
        step's own agreed check then preempts the pod at that step.
        """
        if not self.prefetching:
            return False
        if getattr(self.scheduler, "done", True):
            return False  # genuine end of data: every host's stream ends here
        return bool(getattr(self.scheduler, "sigterm_local", False))

    def ready_depth(self) -> int:
        """Stacks buffered ahead of the consumer (host queue + device ring) —
        0 means the next step will block on the host: a true input stall."""
        if not self.prefetching:
            return 0
        return (self._host.ready if self._host else 0) + (
            self._device.ready if self._device else 0
        )

    def client_states(self) -> dict[str, Any]:
        """Checkpoint overrides for the live scheduler/dataloader objects.

        Prefetching, or synchronous with a batch in hand: the snapshot
        attached to the last consumed item (the live objects are a step ahead,
        and up to host_depth+device_depth more under the worker); before the
        first item is consumed, the construction-time snapshot — the worker
        starts advancing the live objects immediately, so even a save issued
        before the first ``get()`` must see the pre-worker position.
        Synchronous with nothing in hand: empty — the live objects are exactly
        the consumed state.
        """
        if not self.prefetching and not self._in_hand:
            return {}
        if self._consumed_state is None:
            return dict(self._initial_state)
        return dict(self._consumed_state)

    def close(self) -> None:
        """Stop the worker and drop buffers. Must run before anything restores
        scheduler/dataloader state (rollback) — the worker mutates both."""
        if self._closed:
            return
        self._closed = True
        if self._host is not None:
            self._host.close()
        self._device = None
        self._host = None
