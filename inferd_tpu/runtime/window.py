"""First-arrival-flushes micro-batch window (thread-safe, executor-agnostic).

Decode requests from concurrent sessions run as ONE device step. The first
arriving thread becomes the flusher: it waits for co-arrivals (skipped when
none are possible), then the executor's `run_batch` callback runs every
pending entry; co-arrived threads block on their entry until the flusher
distributes results.

The executor's `run_batch(entries)` must:
  * acquire its own device lock (the batcher holds no locks while calling);
  * set `entry.result` for each entry it serves;
errors raised by run_batch are propagated to every entry in the batch.

`invalidate(pred, error)` lets session teardown fail-fast entries that are
still waiting in the window (never started), so a freed lane/slot can be
reused without a stale write racing its new owner.

Three modes, and who runs each (docs/SERVING.md):
  * FORMATION (`swap_in_run` + `expect`): both serving executors, keyed by
    lane (runtime/batch_executor.py) or by mesh slot
    (runtime/mesh_executor.py). The batcher watches whom its steps serve.
    A flusher first waits out a step that is still running, then, the
    device free and no lock held, for the entry of every session served
    by the last step or the one before it, or until a cap since the
    device freed runs out; only then does the callback take the device
    and pull the batch itself via `drain_pending()`. The flusher keeps its
    slot until that drain, so no second flusher ever queues on the device
    lock behind it, and entries arriving mid-step join the next step.
    The cap is derived: three times a running mean of a served session's
    result -> next submit, never above the last measured step; `window_s`
    is only that mean's start value. Each drain ends a TURN (the device
    freed -> the drain): counted in `stats()` where a session was owed
    the step, and recorded as a parentless `turn` span by the flusher
    once its step is delivered (docs/OBSERVABILITY.md "Between two
    steps"); `stamp_out` is where each served entry's `deliver` begins.
    An entry of this mode need not have a thread waiting on it
    (`submit_nowait`: a caller that must not block, the node's event
    loop): it joins the same queue and the same formation, and the drain
    that answers it hands it, with every other such entry it answered, to
    the submitter's callback in ONE call. Where such an entry finds no
    flusher, a thread the window owns becomes one (`_own`); one that is
    not answered within the wait timeout is failed by the window's
    watcher (`_watch`) as a blocked thread would have failed itself.
  * `swap_in_run` + `gang_target`: `--stage-lanes` (the window lives on
    the node, runtime/node.py `_attach_window` + runtime/stage_batch.py).
    The flusher polls until `gang_target()` entries are pending or
    `window_s` runs out, gives up its slot and calls run_batch with an
    EMPTY list; the callback drains once it holds the device.
  * the plain wake-up swap (neither): the speculative windows only
    (runtime/spec_serving.py). The flusher sleeps `window_s` and takes
    what is pending then.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional  # noqa: F401

from inferd_tpu.obs import trace as tracelib
from inferd_tpu.utils import lockwatch

log = logging.getLogger(__name__)


class Entry:
    __slots__ = ("payload", "event", "result", "error",
                 "t_submit", "t_lock", "t_taken", "t_out", "ctx",
                 "hand", "t_handed", "waiter", "i_flush")

    def __init__(self, payload: Any, hand: Optional[Callable[[list], None]] = None):
        self.payload = payload
        # a thread sleeps on `event`; an entry submitted without one
        # (`submit_nowait`) names `hand` instead: what the window calls,
        # once, with all the entries of that submitter it answered
        # together. `t_handed` is when (None: not yet); `waiter` is the
        # submitter's own (what its callback finds its way back by)
        self.event = threading.Event() if hand is None else None
        self.hand = hand
        self.t_handed: Optional[float] = None
        self.waiter: Any = None
        # this entry's submit found no flusher and made one: its own thread,
        # or the window's on its behalf (the `flusher` of its `batch_wait`)
        self.i_flush = False
        self.result: Any = None
        self.error: Optional[Exception] = None
        # the arrival-window wait: submit() entry -> a flush takes the
        # entry into its batch (the `batch_wait` span and the
        # queue_wait_ms_sum counter); `ctx` is the submitter's span
        # context, so the flusher can parent what it waits for on the
        # entry's behalf (`lock_wait`) to the entry's own `compute`
        self.t_submit = tracelib.now()
        # formation only: submit -> t_lock is the part of the wait during
        # which the device was held by a step that did not serve this
        # entry (`lock_wait`), t_lock -> t_taken the rest (`batch_wait`)
        self.t_lock: Optional[float] = None
        self.t_taken: Optional[float] = None
        # when the copy_out of the step that served this entry returned
        # (`stamp_out`): the t0 of the entry's `deliver` span
        self.t_out: Optional[float] = None
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
        expect: Optional[Callable[[Any], Hashable]] = None,
        kind: str = "decode",
    ):
        if expect is not None and not swap_in_run:
            raise ValueError("expect needs swap_in_run (the callback drains)")
        self.window_s = window_s
        # what a formed step is called on its entries' `lock_wait` spans:
        # "decode", or "block" for a model generated by blocks
        self.kind = kind
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
        # formation (see the module docstring): `expect(payload)` names the
        # session an entry belongs to. Everything below is guarded by
        # self._mu; self._cv wakes a forming flusher on a submit, a step's
        # end and an invalidation.
        self._expect = expect
        self._mu = lockwatch.make_lock("window")
        self._cv = threading.Condition(self._mu)
        self._pending: List[Entry] = []
        self._flusher_active = False
        self._running = False  # a drained step has not returned yet
        self._ticket: Optional[list] = None  # the forming flusher's drain slot
        self._t_drain = 0.0  # when the last batch was taken
        self._t_freed = 0.0  # when the last drained step returned
        self._t_formed = 0.0  # when the last formation wait ended
        # session -> its last served payload, for the last step and the
        # one before it: the sessions a formation waits for
        self._served: List[Dict[Hashable, Any]] = [{}, {}]
        # entries without a thread (`submit_nowait`), until they are handed
        # back, oldest first; the formation such an entry found without a
        # flusher is `_owed` to the window's own thread (its `wait`), which
        # sleeps on `_own_cv`; the window's two threads (`_own`, `_watch`)
        # are started with the first such entry and stay
        self._threadless: Dict[Entry, None] = {}
        self._owed: Optional[tuple] = None
        self._own_cv = threading.Condition(self._mu)
        self._own_started = False
        self._delivered: Dict[Hashable, float] = {}  # session -> result out
        self._turn_s = window_s  # running mean: result out -> next submit
        self._step_s: Optional[float] = None  # the last measured step
        # formation: a callback whose drain does not wait out the step it
        # dispatched (runtime/batch_executor.py keeps one step ahead of its
        # sessions) leaves the step's own time here before it returns; it
        # is taken once, in place of drain -> return
        self.step_hint: Optional[float] = None
        self.gang_full = 0  # formations that ended with every expected entry
        self.gang_timeout = 0  # ... because the cap ran out
        self.empty_drains = 0  # drains that found no live entry: no step
        self.n_steps = 0  # flushed batches
        self.n_served = 0  # entries served across those batches
        self.queue_waits = 0  # entries a flush took into a batch
        self.queue_wait_ms_sum = 0.0  # their summed arrival-window waits
        # formation, the stretch between two steps (docs/OBSERVABILITY.md
        # "Between two steps"): a TURN runs from a step's return to the
        # next drain, counted where the formation was owed a session's
        # entry (the `turn` span's own stamps); a SESSION's turn from its
        # result out to its next submit, before `_returned` clamps it
        self.turns = 0
        self.turn_ms_sum = 0.0
        self.session_turns = 0
        self.session_turn_ms_sum = 0.0
        self._formed = ("solo", 0)  # how the last formation ended, whom it was owed
        # a step dispatched ahead of its sessions (runtime/step_ahead.py),
        # as whoever came for it first found it: still running (that
        # thread waited for its end) or done; the hops that rode such a
        # step and waited for it in their own thread, and for how long
        self.steps_waited = 0
        self.steps_found_done = 0
        self.rides = 0
        self.ride_ms_sum = 0.0
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
        now = self._t_drain = tracelib.now()
        for e in batch:
            if e.error is None:
                e.t_taken = now
                waited_from = e.t_submit
                if self._expect is not None:
                    # the device was not this entry's to have while the
                    # step it arrived under still ran, and from the end
                    # of the formation wait until the callback got the
                    # lock (a prefill had cut in); the rest was spent
                    # waiting for the expected sessions
                    held = max(0.0, self._t_freed - e.t_submit) + (
                        now - max(e.t_submit, self._t_formed)
                    )
                    waited_from = e.t_lock = e.t_submit + min(
                        held, now - e.t_submit
                    )
                self.queue_waits += 1
                self.queue_wait_ms_sum += (now - waited_from) * 1e3
        return batch

    def submit(self, payload: Any) -> Any:
        entry = Entry(payload)
        with self._mu:
            self._pending.append(entry)
            i_flush = entry.i_flush = not self._flusher_active
            if i_flush:
                self._flusher_active = True
            wait = self._co_possible()
            if self._expect is not None:
                self._returned(entry)
        try:
            return self._serve(entry, i_flush, wait)
        finally:
            self.record_waits(entry)
            if self.tracer is not None and entry.t_out is not None:
                # handed up to whoever records this call's `compute`
                tracelib.mark("t_out", entry.t_out)

    def record_waits(self, entry: Entry) -> None:
        """The `lock_wait` and `batch_wait` spans of an entry a drain took,
        under the span that was current at its submit: by the submitter,
        once the entry is answered (`submit` itself; whoever called
        `submit_nowait`, when the entry is handed back)."""
        if self.tracer is None or entry.t_taken is None:
            return
        t_wait = entry.t_submit
        if entry.t_lock is not None:
            t_wait = entry.t_lock
            self.tracer.record_span(
                "lock_wait", "lock_wait", entry.t_submit, t_wait,
                parent=entry.ctx, attrs={"kind": self.kind},
            )
        self.tracer.record_span(
            "batch_wait", "batch_wait", t_wait, entry.t_taken,
            parent=entry.ctx, attrs={"flusher": int(entry.i_flush)},
        )

    def _await(self, entry: Entry, where: str) -> Any:
        """Block until another thread's step delivers `entry`."""
        entry.event.wait(timeout=self._wait_timeout_s)
        if entry.error is not None:
            raise entry.error
        if not entry.event.is_set():
            self._stall(where)
            raise TimeoutError("batched decode flusher never completed")
        return entry.result

    def _serve(self, entry: Entry, i_flush: bool, wait: bool) -> Any:
        if not i_flush:
            return self._await(entry, "co_arrival")
        if self._expect is not None:
            return self._flush_formed(entry, wait)

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
            return self._await(entry, "swap_in_run")
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
            return self._await(entry, "absorbed")
        if entry.error is not None:
            raise entry.error
        return entry.result

    # -- entries without a thread (formation only) ---------------------------

    def submit_nowait(self, payload: Any, hand: Callable[[List[Entry]], None]) -> Entry:
        """`submit` for a caller that must not block: the entry joins the
        queue and the formation as any other and this returns at once.
        When the entry has its result or its error (`entry.result`,
        `entry.error`: what `submit` would have returned or raised) the
        window calls `hand` with a list that holds it: ONE call for all the
        entries of this `hand` that one drain answered (or one
        invalidation, one failure, one sweep of the watcher), from the
        thread that answered them, `t_handed` stamped on each. The spans of
        its wait are the caller's to record then (`record_waits`)."""
        if self._expect is None:
            raise ValueError("submit_nowait needs a formation (expect)")
        entry = Entry(payload, hand)
        with self._mu:
            self._pending.append(entry)
            self._threadless[entry] = None
            if not self._own_started:
                self._own_started = True
                for name, target in (("window-flush", self._own), ("window-watch", self._watch)):
                    threading.Thread(target=target, name=name, daemon=True).start()
            if not self._flusher_active:
                # nobody forms: the window's own thread does, on this
                # entry's behalf (one wake-up a formation, whoever else
                # arrives joins it)
                self._flusher_active = entry.i_flush = True
                self._owed = (self._co_possible(), entry.ctx)
                self._own_cv.notify()
            self._returned(entry)
        return entry

    def _own(self) -> None:
        """The window's own flusher: sleeps until a formation is owed to it
        (`submit_nowait` found none active) and runs it as a submitting
        thread would (`_flush_formed`)."""
        while True:
            with self._mu:
                while self._owed is None:
                    self._own_cv.wait()
                (wait, ctx), self._owed = self._owed, None
            # what the flush stamps (a step's `device` and `copy_out`) hangs
            # where it would had the entry's submitter flushed itself: under
            # the span that was current at that submit
            token = tracelib.set_current(ctx)
            try:
                self._flush_formed(None, wait)
            except Exception:  # the entries have their own errors by now
                log.exception("window: the flush of a formation failed")
            finally:
                tracelib.reset_current(token)

    def _watch(self) -> None:
        """What a blocked thread does for itself (`_await`), for the entries
        that have none: one not answered `wait_timeout_s` after its submit
        gets the same TimeoutError, leaves the same `window.stall` event,
        and is handed back. Sleeps until the oldest such entry's limit."""
        while True:
            with self._mu:
                now = tracelib.now()
                late, nap = [], self._wait_timeout_s
                for e in self._threadless:  # a few: at most one a session
                    left = e.t_submit + self._wait_timeout_s - now
                    if left <= 0:
                        late.append(e)
                    else:
                        nap = min(nap, left)
                handed = self._mark_handed(
                    late, TimeoutError("batched decode flusher never completed")
                )
            for mine in handed.values():
                for _ in mine:
                    self._stall("co_arrival")
            self._hand(handed)
            time.sleep(max(nap, 0.005))

    def _mark_handed(self, entries, error: Optional[Exception] = None):
        """Under self._mu: of `entries`, those without a thread that nobody
        has handed back yet, by their `hand`, stamped as handed now (an
        entry is handed once, whoever comes first: its drain, an
        invalidation, the watcher); `error` is theirs if they had none."""
        handed: Dict[Any, List[Entry]] = {}
        now = tracelib.now()
        for e in entries:
            if e.hand is not None and e.t_handed is None:
                e.t_handed = now
                if error is not None and e.error is None:
                    e.error = error
                self._threadless.pop(e, None)
                handed.setdefault(e.hand, []).append(e)
        return handed

    @staticmethod
    def _hand(handed) -> None:
        """ONE call of each submitter's `hand`, with all of its entries."""
        for hand, mine in handed.items():
            try:
                hand(mine)
            except Exception:  # a submitter's fault is not the step's
                log.exception("window: handing %d entries back failed", len(mine))

    # -- formation (expect=...) ---------------------------------------------

    def _returned(self, entry: Entry) -> None:
        """A submit under self._mu: feed the turn estimate with this
        session's result-out -> submit, and wake a forming flusher."""
        out = self._delivered.pop(self._expect(entry.payload), None)
        if out is not None:
            turn = max(0.0, entry.t_submit - out)
            self.session_turns += 1
            self.session_turn_ms_sum += turn * 1e3
            if self._step_s is not None:
                # the cap never passes a step, so neither need a sample
                turn = min(turn, self._step_s)
            # quick to rise, slow to fall: the cap has to cover the
            # slowest of the sessions it waits for, not the typical one
            gain = 0.5 if turn > self._turn_s else 0.05
            self._turn_s += gain * (turn - self._turn_s)
        self._cv.notify_all()

    def _cap_s(self) -> float:
        """The longest a formation waits once the device is free: a few
        turns of a served session, never more than a step — beyond that
        two alternating cohorts would serve more than lockstep does."""
        cap = 3.0 * self._turn_s
        return cap if self._step_s is None else min(cap, self._step_s)

    def _form(self, wait: bool) -> None:
        """The flusher's wait before it asks for the device (under
        self._mu through self._cv, no other lock held): a step still
        running is waited out whatever it takes; then, the device free,
        the entries of the sessions the last two steps served, until
        `_cap_s` since the device freed."""
        stall_at = time.monotonic() + self._wait_timeout_s
        while self._running:
            left = stall_at - time.monotonic()
            if left <= 0:
                self._stall("formation")
                raise TimeoutError("batched decode step never completed")
            self._cv.wait(timeout=left)
        how = "solo"
        owed = len({k for served in self._served for k in served})
        while wait:
            here = {
                self._expect(e.payload) for e in self._pending if e.error is None
            }
            if not here:
                break  # every waiting entry was invalidated
            missing = [
                k for served in self._served for k in served if k not in here
            ]
            if not missing:
                self.gang_full += 1
                how = "full"
                break
            left = self._t_freed + self._cap_s() - tracelib.now()
            if left <= 0:
                # whoever is not back by now is not waited for again
                # until a step has served it
                self.gang_timeout += 1
                how = "timeout"
                for served in self._served:
                    for k in missing:
                        served.pop(k, None)
                break
            self._cv.wait(timeout=left)
        self._t_formed = tracelib.now()
        self._formed = (how, owed)

    def _flush_formed(self, entry: Optional[Entry], wait: bool) -> Any:
        """Form, let the callback take the device and drain, then note
        whom the step served and when, and deliver."""
        # drain_pending() leaves the step's entries and the turn it ended
        ticket: list = [None, None]
        failed: Optional[Exception] = None
        try:
            with self._cv:
                self._ticket = ticket
                self._form(wait)
            self._run_batch([])
        except Exception as exc:
            failed = exc
        now = tracelib.now()
        with self._cv:
            drained = self._ticket is not ticket
            if drained:
                batch = ticket[0]
                self._running = False
                self._t_freed = now
            else:
                # the callback never drained: whatever is pending has no
                # flusher any more and would hang its submitter
                self._ticket = None
                self._flusher_active = False
                failed = failed or RuntimeError("run_batch never drained")
                batch = [e for e in self._take() if e.error is None]
            served = {}
            for e in batch:
                if e.error is None and e.result is None:
                    e.error = failed or RuntimeError("entry was not served")
                if e.error is None:
                    served[self._expect(e.payload)] = e.payload
            hint, self.step_hint = self.step_hint, None
            if served:
                self._step_s = now - self._t_drain if hint is None else hint
                self._served = [served, self._served[0]]
                self._delivered.update(dict.fromkeys(served, now))
            self._cv.notify_all()
        # each entry has its result or its error: the threads that sleep on
        # theirs are woken first, as ever; the entries without one go back
        # to their submitters once the `turn` span is recorded (such an
        # entry may end its request the moment it has its answer, and the
        # step's spans are whole by then, as they were when the flusher's
        # own hop could not end before this returned)
        threadless = False
        for e in batch:
            if e.hand is None:
                e.event.set()
            else:
                threadless = True
        if drained and ticket[1] is not None:
            self._record_turn(batch, *ticket[1])
        if threadless:
            with self._mu:
                handed = self._mark_handed(batch)
            self._hand(handed)
        # the window's own thread (`_own`) has no entry to wait for
        return None if entry is None else self._await(entry, "formation")

    def _record_turn(
        self, batch: List[Entry], t_freed: float, t_drain: float,
        t_formed: float, how: str, owed: int,
    ) -> None:
        """The `turn` span of the stretch the drain of `batch` ended (the
        flusher, once its step is delivered): parentless, one a step."""
        if self.tracer is None or not tracelib.enabled():
            return
        last = max(batch, key=lambda e: e.t_submit)
        self.tracer.record_span(
            "turn", "turn", t_freed, t_drain, keep=True,  # once a step
            attrs={
                "kind": self.kind, "cobatch": len(batch), "expected": owed,
                "how": how,
                "formed_ms": round((t_formed - t_freed) * 1e3, 3),
                "first_ms": round(
                    (min(e.t_submit for e in batch) - t_freed) * 1e3, 3
                ),
                "last": last.ctx.span_id if last.ctx is not None else None,
            },
        )

    def stamp_out(self, entries: List[Entry]) -> None:
        """The flusher, as its step's copy_out returns: ONE stamp for
        every entry the step served, where each one's `deliver` span
        starts. Nothing without a recorder or with INFERD_TRACE=0."""
        if self.tracer is not None and tracelib.enabled():
            t_out = tracelib.now()
            for e in entries:
                e.t_out = t_out

    def delivered(self, key: Hashable, rode_s: float) -> None:
        """The result of session `key` is out NOW, handed over by its
        caller and not by a drain's own delivery (an entry the callback
        answered with a step still running, which the submitter waited for
        itself, `rode_s` seconds): where its next submit's turn is counted
        from."""
        with self._mu:
            self._delivered[key] = tracelib.now()
            self.rides += 1
            self.ride_ms_sum += rode_s * 1e3

    def step_seen(self, waited: bool) -> None:
        """Whoever came first for a step dispatched ahead had to wait for
        its end, or found it done."""
        with self._mu:
            if waited:
                self.steps_waited += 1
            else:
                self.steps_found_done += 1

    def unexpect(self, pred: Callable[[Any], bool]) -> None:
        """Stop waiting for the sessions whose last served payload matches
        `pred` (ended, evicted, or inside a prefill): the next step that
        serves one expects it again."""
        with self._cv:
            self._unexpect(pred)

    def _unexpect(self, pred: Callable[[Any], bool]) -> None:
        for served in self._served:
            for k in [k for k, payload in served.items() if pred(payload)]:
                del served[k]
                self._delivered.pop(k, None)
        self._cv.notify_all()

    def stats(self) -> dict:
        """Coalescing effectiveness counters (shared by both executors)."""
        formation = {} if self._expect is None else {
            "gang_full": self.gang_full,
            "gang_timeout": self.gang_timeout,
            "empty_drains": self.empty_drains,
            # between two steps: the device free and a session owed a
            # step (sum / count: the mean turn; against batched_steps and
            # the uptime, the share of time the chip waited on the host),
            # a session's own result -> next submit, and the estimate the
            # formation's cap is made of
            "turns": self.turns,
            "turn_ms_sum": round(self.turn_ms_sum, 3),
            "session_turns": self.session_turns,
            "session_turn_ms_sum": round(self.session_turn_ms_sum, 3),
            "turn_est_ms": round(self._turn_s * 1e3, 3),
            # steps run ahead of their sessions: who saw them end, and the
            # hops that waited for their own (runtime/step_ahead.py)
            "steps_waited": self.steps_waited,
            "steps_found_done": self.steps_found_done,
            "rides": self.rides,
            "ride_ms_sum": round(self.ride_ms_sum, 3),
        }
        return {
            **formation,
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
            if self._ticket is not None:
                # formation: the flusher's slot goes with its drain, so
                # whoever submits from here on forms the NEXT step and
                # first waits this one out
                self._ticket[0] = live
                if live and self._t_freed:
                    # the turn this drain ends: from the last step's
                    # return; _flush_formed records its span once this
                    # step is delivered
                    how, owed = self._formed
                    if owed:
                        self.turns += 1
                        self.turn_ms_sum += (self._t_drain - self._t_freed) * 1e3
                    self._ticket[1] = (
                        self._t_freed, self._t_drain, self._t_formed, how, owed
                    )
                self._ticket = None
                self._flusher_active = False
                self._running = True
                if not live:
                    self.empty_drains += 1
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
            still, failed = [], []
            for e in self._pending:
                if pred(e.payload):
                    e.error = error
                    failed.append(e)
                    if e.hand is None:
                        e.event.set()
                else:
                    still.append(e)
            self._pending[:] = still
            handed = self._mark_handed(failed)
            if self._expect is not None:
                self._unexpect(pred)
        self._hand(handed)
