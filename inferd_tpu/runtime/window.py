"""First-arrival-flushes micro-batch window (thread-safe, executor-agnostic).

Shared by the continuous-batching executor (runtime/batch_executor.py) and
the in-mesh pipelined executor (runtime/mesh_executor.py): decode requests
from concurrent sessions that arrive within a short window run as ONE
device step. The first arriving thread becomes the flusher — it waits
`window_s` for co-arrivals (skipped when none are possible), then calls the
executor's `run_batch` callback with every pending entry; co-arrived
threads block on their entry until the flusher distributes results.

The executor's `run_batch(entries)` must:
  * acquire its own device lock (the batcher holds no locks while calling);
  * set `entry.result` for each entry it serves;
errors raised by run_batch are propagated to every entry in the batch.

`invalidate(pred, error)` lets session teardown fail-fast entries that are
still waiting in the window (never started), so a freed lane/slot can be
reused without a stale write racing its new owner.

Two opt-in modes power STAGE-level continuous batching (runtime/node +
runtime/stage_batch — see docs/SERVING.md):
  * `swap_in_run`: the flusher passes run_batch an EMPTY list and the
    callback pulls the batch itself via `drain_pending()` once it holds
    the device — entries arriving mid-step join the next step instead of
    fragmenting into mini-batches queued on the device lock;
  * `gang_target`: the window wait ends early once every live idle
    session's entry is pending, which merges phase-offset session
    cohorts into one lockstep co-batch and lets the window be sized
    generously without charging steady-state latency.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, List, Optional  # noqa: F401

from inferd_tpu.obs import trace as tracelib
from inferd_tpu.utils import lockwatch


class Entry:
    __slots__ = ("payload", "event", "result", "error",
                 "t_submit", "t_taken", "ctx")

    def __init__(self, payload: Any):
        self.payload = payload
        self.event = threading.Event()
        self.result: Any = None
        self.error: Optional[Exception] = None
        # the arrival-window wait: submit() entry -> a flush takes the
        # entry into its batch (the `batch_wait` span and the
        # queue_wait_ms_sum counter); `ctx` is the submitter's span
        # context, so the flusher can parent what it waits for on the
        # entry's behalf (`lock_wait`) to the entry's own `compute`
        self.t_submit = tracelib.now()
        self.t_taken: Optional[float] = None
        self.ctx = tracelib.current()


class WindowedBatcher:
    def __init__(
        self,
        window_s: float,
        run_batch: Callable[[List[Entry]], None],
        co_possible: Callable[[], bool],
        wait_timeout_s: float = 120.0,
        swap_in_run: bool = False,
        gang_target: Optional[Callable[[], int]] = None,
    ):
        self.window_s = window_s
        self._run_batch = run_batch
        self._co_possible = co_possible
        self._wait_timeout_s = wait_timeout_s
        # gang formation (optional): the flusher's window wait ends EARLY
        # once `gang_target()` entries are pending — and, more importantly,
        # the window is allowed to be sized at a whole loop iteration
        # without costing that much per step. Without it, sessions whose
        # token loops happen to be phase-offset (e.g. staggered by their
        # prefills) form persistent co-batching COHORTS that a short fixed
        # window can never merge: each cohort's coalesced reply resyncs
        # only its own members. Waiting for the full gang once merges the
        # cohorts, and the merged gang then stays in lockstep, so the
        # steady-state wait collapses to the arrival jitter.
        self._gang_target = gang_target
        # swap_in_run=True: the flusher does NOT take the pending list at
        # wake-up; run_batch is called with an empty list and pulls the
        # batch itself via drain_pending() once it holds the device. This
        # is the CONTINUOUS-batching mode: entries that arrive while the
        # previous device step is still running keep accumulating until
        # the device actually frees, so batch size tracks device occupancy
        # instead of arrival phase (a wake-up swap fragments them into a
        # convoy of mini-batches queued on the device lock). The callback
        # owns every drained entry: result/error AND event delivery.
        self._swap_in_run = swap_in_run
        self._mu = lockwatch.make_lock("window")
        self._pending: List[Entry] = []
        self._flusher_active = False
        self.n_steps = 0  # flushed batches
        self.n_served = 0  # entries served across those batches
        self.queue_waits = 0  # entries a flush took into a batch
        self.queue_wait_ms_sum = 0.0  # their summed arrival-window waits
        # the node's span recorder (obs.trace.SpanRecorder), handed to
        # the executor that owns this batcher; None records no span
        self.tracer: Optional[tracelib.SpanRecorder] = None
        # optional flight-recorder hook (the node wires its journal's
        # emit): a flusher that never completes within the wait timeout
        # is a wedged device step — the single worst windowing failure —
        # and must leave a typed `window.stall` event, not just a raised
        # TimeoutError that the client may swallow in a retry loop
        self.on_event: Optional[Callable[..., Any]] = None

    def _stall(self, where: str) -> None:
        from inferd_tpu.obs.events import emit_safely

        emit_safely(
            self.on_event, "window.stall", where=where,
            timeout_s=self._wait_timeout_s,
        )

    def _take(self) -> List[Entry]:
        """Swap the pending list out (under self._mu) and stamp the end
        of each live entry's arrival-window wait."""
        batch, self._pending = self._pending, []
        now = tracelib.now()
        for e in batch:
            if e.error is None:
                e.t_taken = now
                self.queue_waits += 1
                self.queue_wait_ms_sum += (now - e.t_submit) * 1e3
        return batch

    def submit(self, payload: Any) -> Any:
        entry = Entry(payload)
        with self._mu:
            self._pending.append(entry)
            i_flush = not self._flusher_active
            if i_flush:
                self._flusher_active = True
            wait = self._co_possible()
        try:
            return self._serve(entry, i_flush, wait)
        finally:
            if self.tracer is not None and entry.t_taken is not None:
                self.tracer.record_span(
                    "batch_wait", "batch_wait", entry.t_submit, entry.t_taken,
                    parent=entry.ctx, attrs={"flusher": int(i_flush)},
                )

    def _serve(self, entry: Entry, i_flush: bool, wait: bool) -> Any:
        if not i_flush:
            entry.event.wait(timeout=self._wait_timeout_s)
            if entry.error is not None:
                raise entry.error
            if not entry.event.is_set():
                self._stall("co_arrival")
                raise TimeoutError("batched decode flusher never completed")
            return entry.result

        if wait:
            if self._gang_target is None:
                time.sleep(self.window_s)
            else:
                # bounded gang wait: poll until every live idle session's
                # step is pending or the window cap elapses
                deadline = time.monotonic() + self.window_s
                while True:
                    if entry.event.is_set():
                        break  # our entry was invalidated mid-wait
                    want = self._gang_target()
                    with self._mu:
                        have = len(self._pending)
                    if want and have >= want:
                        break
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    time.sleep(min(0.0005, left))
        if self._swap_in_run:
            # release the flusher slot BEFORE running: a co-arrival during
            # our device step becomes the next flusher and queues on the
            # device lock, draining everything that accumulated meanwhile
            with self._mu:
                self._flusher_active = False
            try:
                self._run_batch([])
            except Exception as exc:
                # entries the callback never drained would hang their
                # submitters: fail whatever is still pending, plus our own
                # entry if the callback died before delivering it
                for e in self.drain_pending():
                    e.error = exc
                    e.event.set()
                if not entry.event.is_set():
                    entry.error = entry.error or exc
                    entry.event.set()
            entry.event.wait(timeout=self._wait_timeout_s)
            if entry.error is not None:
                raise entry.error
            if not entry.event.is_set():
                self._stall("swap_in_run")
                raise TimeoutError("batched decode flusher never completed")
            return entry.result
        with self._mu:
            batch = self._take()
            self._flusher_active = False
        # entries invalidated between swap and here already have error set;
        # run the rest
        live = [e for e in batch if e.error is None]
        try:
            if live:
                self._run_batch(live)
                self.n_steps += 1
                self.n_served += len(live)
        except Exception as exc:
            for e in live:
                e.error = exc
                e.event.set()
            raise
        for e in live:
            e.event.set()
        if entry not in batch:
            # a concurrent flusher's drain_pending() absorbed this entry
            # into ITS device step before we could swap — wait for that
            # step to deliver, exactly like a non-flusher co-arrival
            entry.event.wait(timeout=self._wait_timeout_s)
            if not entry.event.is_set():
                self._stall("absorbed")
                raise TimeoutError("batched decode flusher never completed")
        if entry.error is not None:
            raise entry.error
        return entry.result

    def stats(self) -> dict:
        """Coalescing effectiveness counters (shared by both executors)."""
        return {
            "batched_steps": self.n_steps,
            "batched_tokens": self.n_served,
            "mean_batch": round(self.n_served / self.n_steps, 3)
            if self.n_steps
            else 0.0,
            # arrival-window wait (submit -> taken into a batch): the mean
            # is sum / count for an operator without /spans
            "queue_waits": self.queue_waits,
            "queue_wait_ms_sum": round(self.queue_wait_ms_sum, 3),
        }

    def drain_pending(self) -> List[Entry]:
        """Atomically take every live entry still waiting in the window.

        For CONTINUOUS batching: a flusher that has just acquired the
        device absorbs the entries that arrived while the previous step
        was still running (they would otherwise form a lagging
        under-filled window — arrival phase, not load, would set the
        batch size). The caller owns the drained entries end to end: it
        must set each one's result/error AND `event` when its step
        completes (the flush loop only signals entries of its own swap);
        a flusher whose own entry was drained waits on its event like any
        co-arrival."""
        with self._mu:
            batch = self._take()
        live = [e for e in batch if e.error is None]
        if live:
            self.n_steps += 1
            self.n_served += len(live)
        return live

    def invalidate(self, pred: Callable[[Any], bool], error: Exception) -> None:
        """Fail-fast waiting entries whose payload matches `pred` (they have
        not started executing — entries already swapped into a running
        flush are the executor's responsibility via its in-flight
        accounting)."""
        with self._mu:
            still = []
            for e in self._pending:
                if pred(e.payload):
                    e.error = error
                    e.event.set()
                else:
                    still.append(e)
            self._pending[:] = still
