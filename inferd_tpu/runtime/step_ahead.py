"""One step ahead of the sessions (docs/SERVING.md "One step ahead"): what
the two whole-model executors share of it.

Both run the row of a session's NEXT hop before that hop arrives, where the
hop before it promised one (`ahead` / `eos` in a decode hop's ask,
runtime/executor.parse_decode_ask), fed by the token and key the step before
left on the device in its one packed array (core.sampling.pack_rows /
ahead_rows). One definition here of: a dispatched step and how it is waited
for, copied out and stamped (`_Step`, `StepAhead._finish`); a row run ahead
and the test by which a hop claims it (`_Ahead`, `_Step.fed`,
`StepAhead._claim`); the promises of the riders (`_take_carry`). Each
executor keeps its own dispatch, its own `_runs_ahead` (where its lengths
live and what room a row needs) and its own `_forget` (how a dropped row is
repaired): the dense lanes (runtime/batch_executor.py) hold their lengths on
the host and write a row beyond the lane's length, the mesh's lengths
(runtime/mesh_executor.py) advance inside the pass.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

import numpy as np

from inferd_tpu.core import sampling as samplib
from inferd_tpu.obs import trace as tracelib


class _Step:
    """One dispatched decode program (a step of the dense lanes, a pass of
    the mesh) and what it ran for. `finish` (StepAhead._finish: any thread,
    once) waits for it, copies its one packed array out and fills `toks` /
    `keys` / `replies` / `rows` (`out`); until then only the device holds
    what it chose."""

    __slots__ = (
        "packed", "logits", "top_n", "asks", "plain", "lanes", "prev",
        "program", "t_call", "t0", "t_done", "t_out", "lock", "done", "error",
        "toks", "keys", "replies", "rows", "released", "behind",
    )

    def __init__(self, packed, logits, top_n, asks, plain, lanes, prev, program):
        self.packed, self.logits, self.top_n = packed, logits, top_n
        self.asks = asks  # {lane: SampleAsk} of the rows that chose a token
        self.plain = plain  # lanes answered with their logits row
        self.lanes = lanes  # every row run for a session
        self.prev = prev  # the step dispatched before: `device` starts at its end
        self.program = program
        # the steps dispatched before this one that the device had not
        # ended when this one was called (a rider's `ride` span says so)
        self.behind, at = 0, prev
        while at is not None and not (at.done or at.packed.is_ready()):
            self.behind, at = self.behind + 1, at.prev
        self.t_call = tracelib.now()
        self.t0 = self.t_done = self.t_out = 0.0
        self.lock = threading.Lock()
        self.done = False
        # set once the next drain has run the rows this step's riders
        # promised (`_carry`); the steps of one drain share it
        self.released = threading.Event()
        self.error: Optional[Exception] = None
        self.toks = self.keys = self.replies = self.rows = None

    def reply(self, lane: int):
        """What the hop of `lane` is answered with, once finished: the
        token's reply, or the logits row of a hop that asked for none."""
        return self.replies[lane] if lane in self.replies else self.rows[lane]

    def span(self) -> Dict[str, Any]:
        """The attributes of the step's `device` span."""
        return {"kind": "decode", "tokens": len(self.lanes),
                "cobatch": len(self.lanes), "program": self.program}

    def fed(self, lane: int, tok, ask, ran) -> bool:
        """Whether a hop of `lane` that carries `tok` under `ask` is the one
        a row was run for under `ran`, from what this step (finished) chose:
        its token and its key, the same sampling and log-probabilities."""
        return (
            ask.sampling == ran.sampling and ask.want == ran.want
            and self.toks[lane] == tok and self.keys[lane] == ask.key.tolist()
        )

    def ends(self, lane: int, eos: int) -> bool:
        """Whether what this step (finished) chose for `lane` ends its
        generation."""
        return self.toks[lane] == eos

    def out(self, host: np.ndarray, ex) -> None:
        """Read the step's packed array, on the host now (`_finish`)."""
        routes = getattr(ex.engine, "routes", False)  # the mesh's pass returns no routing
        self.replies, routed = samplib.row_replies(
            host, self.top_n, self.asks, ex.cfg.num_experts_per_tok if routes else 0,
        )
        self.toks = host[:, 0].tolist()
        self.keys = host[:, 1:3].view(np.uint32).tolist()
        if routed is not None and self.lanes:
            ex._count_routing(routed[:, self.lanes], ex.engine.lanes)


class Admissions:
    """Lanes (the mesh: slots) bound to new sessions, and for how long each
    had stood free: what the `lane` span of a session's first call says
    (`bound` returns its new, evicted, vacant_ms; `last` is the last bind's)
    and /stats `executor` counts (`admissions`, `lane_vacant_ms_sum`). Under
    the lock of the table that hands the lanes out."""

    KNOWN = (0, 0, None)  # the session had its lane already

    def __init__(self):
        self.count = 0
        self.vacant_ms_sum = 0.0
        self._freed: Dict[int, float] = {}  # lane -> when it was given back
        self.last = self.KNOWN

    def freed(self, lane: int) -> None:
        self._freed[lane] = tracelib.now()

    def bound(self, lane: int, evicted: bool):
        """`lane` goes to a new session (a lane never used has stood free
        for no known time)."""
        freed = self._freed.pop(lane, None)
        vacant = None if freed is None else round((tracelib.now() - freed) * 1e3, 3)
        self.count += 1
        self.vacant_ms_sum += vacant or 0.0
        self.last = (1, int(evicted), vacant)
        return self.last

    def stats(self) -> Dict[str, Any]:
        return {"admissions": self.count,
                "lane_vacant_ms_sum": round(self.vacant_ms_sum, 3)}


class _Ahead:
    """A row run for a lane before its hop arrived (under the executor's
    lock of its session table): the position it was written at (the lane's
    length) and how many positions it covers (1; a block), the step whose
    output row was its input (`src`: the token and key its hop must carry),
    the step that ran it (`step`: what answers the hop) and the ask it was
    run under."""

    __slots__ = ("pos", "width", "src", "step", "ask")

    def __init__(self, pos: int, width: int, src: _Step, ask):
        self.pos, self.width, self.src, self.step, self.ask = pos, width, src, None, ask


class StepAhead:
    """What an executor that keeps a step ahead of its sessions does the
    same way whichever it is. It brings: `_ahead` {lane: _Ahead}, the rows
    run before their hops, unclaimed; `_carry` {lane: (the step its waiting
    hop rode, the hop's ask)} where that ask promised a hop to follow;
    `_last_step`, the last step dispatched (the only one whose output a
    drain may find on the device alone); `_forget(lane)` and
    `_runs_ahead(lane, ask, src)`; `_batcher`, `tracer`, `cfg`. The records
    are read and written under the executor's lock of its session table,
    the dispatching ones under its device's."""

    def _lane_span(self, t_in: Optional[float], lane: int, bound) -> None:
        """The `lane` span of a session's first call (`t_in`: `process`
        entered, None for any later call), once its lane is bound (`bound`:
        what `Admissions.bound` said, or `KNOWN`) and the table's lock
        released."""
        if t_in is not None and self.tracer is not None:
            new, evicted, vacant = bound
            self.tracer.record_span(
                "lane", "lane", t_in, tracelib.now(), parent=tracelib.current(),
                attrs={"lane": lane, "new": new, "evicted": evicted, "vacant_ms": vacant},
            )

    def _claim(self, lane: int, pos: int, tok, ask) -> bool:
        """Whether the call at `pos` of `lane` is the hop a row was run
        ahead for: a one-token hop at the row's position, with the token
        and key the step before chose and the ask the row was run under (a
        block hop: nothing known of its block, the key the step before
        left: `_Step.fed`).
        Anything else drops the row and is served as if none had been run;
        on a lane with a recurrent state, which the row has already moved,
        it is refused as a replay of such a lane is, and the row stays for
        the hop it was run for (a restart at 0 resets the state: dropped)."""
        rec = self._ahead.get(lane)
        if rec is None:
            return False
        src = rec.src
        if (
            ask is not None and src.done and src.error is None and rec.pos == pos
            and src.fed(lane, tok, ask, rec.ask)
        ):
            return True
        if self.cfg.has_state_layers and pos:
            raise ValueError(
                f"lane {lane}: a step already ran position {rec.pos} for the hop "
                f"this session promised (`ahead`); {self.cfg.name} holds a recurrent "
                "state, which does not roll back: send that hop, or restart the "
                "session at 0"
            )
        self._forget(lane)
        return False

    def _take_carry(self, conts, here=()) -> None:
        """The promises of the riders since the last drain: the next row of
        every such lane that is not back (`here`: the lanes of this drain's
        entries) goes into `conts` where `_runs_ahead` lets it."""
        carried, self._carry = self._carry, {}
        for lane, (src, ask) in carried.items():
            if lane not in here and self._runs_ahead(lane, ask, src):
                conts[lane] = (src, ask)

    def _wait_out(self, step: _Step) -> None:
        """A drain waits for `step` (`_finish`) and leaves the window the
        step's own time: what the drain waited for is only what was left of
        it (runtime/window.py `step_hint`)."""
        self._finish(step)
        if step.error is None:
            self._batcher.step_hint = step.t_done - step.t0

    def _fed_by(self, conts):
        """Where the rows of `conts` take what the step before left for them
        (under the device's lock, before a dispatch): the last step
        dispatched is the only one whose output the device may still hold
        alone; an older one is finished here."""
        last = self._last_step
        for src, _ in conts.values():
            if not src.done and src is not last:
                self._finish(src)
        return last

    def _recorded(self, made: Dict[int, _Ahead], step: Optional[_Step]) -> None:
        """The dispatch of the rows recorded in `made` returned `step`, or
        failed (None): the records go."""
        for lane, rec in made.items():
            if step is not None:
                rec.step = step
            elif self._ahead.get(lane) is rec:
                del self._ahead[lane]

    def _ridden(self, step: _Step, lane: int):
        """A hop rode `step`, which other sessions' drains do not wait for:
        its own thread waits for it (under no lock of the executor), and
        the window expects the lane again once a drain has answered it.
        Returns what the hop is answered with. The whole of it is the
        hop's `ride` span and a ride of /stats `executor` (`rides`,
        `ride_ms_sum`)."""
        t_ride = tracelib.now()
        with tracelib.region(self.tracer, "ride", behind=step.behind):
            self._batcher.unexpect(lambda p, _lane=lane: p[0] == _lane)
            self._finish(step)
            if step.error is not None:
                raise step.error
            tracelib.mark("t_out", step.t_out)  # `deliver` starts (runtime/window.py submit)
            if lane in self._carry:
                # the next drain runs this session's next row ahead if its hop
                # is not back before it: give that drain a step's time to come
                # (a session that returned at once would ride again, and again)
                step.released.wait(min(0.1, step.t_done - step.t0))
            self._batcher.delivered(lane, rode_s=tracelib.now() - t_ride)
        return step.reply(lane)

    def _finish(self, step: _Step) -> None:
        """Wait for `step`, copy its one packed array out and keep what the
        host reads of it on the step (`_Step.out`): once, by whoever needs
        it first (the next drain's flusher under the device's lock; a
        rider's own thread under no lock). Its `device` span runs from its
        dispatch, or from the end of the step before it where it queued
        behind that one, to the moment this thread learnt it was done, and
        says which that was (`waited`; /stats `executor` `steps_waited`,
        `steps_found_done`): 1 where this thread waited for the step, so
        the span's end is the step's to a wake-up; 0 where the step was
        done when the thread came (a turn longer than the step: the next
        drain), so the span overstates the step and the chip has been free
        since some earlier moment. Once a step: both spans are kept."""
        with step.lock:
            if step.done:
                return
            prev, step.prev = step.prev, None
            if prev is not None:
                self._finish(prev)
            try:
                waited = not step.packed.is_ready()
                step.packed.block_until_ready()
                step.t_done = tracelib.now()
                step.t0 = max(step.t_call, prev.t_done if prev is not None else 0.0)
                self._batcher.step_seen(waited)
                if self.tracer is not None and tracelib.enabled():
                    self.tracer.record_span(
                        "device", "device", step.t0, step.t_done, parent=tracelib.current(),
                        attrs=dict(step.span(), waited=int(waited)), keep=True,
                    )
                with tracelib.region(self.tracer, "copy_out", keep=True) as at:
                    host = np.asarray(step.packed)
                    step.rows, moved = samplib.logits_out(step.logits, step.plain)
                    at["bytes"] = host.nbytes + moved
                step.t_out = tracelib.now()
                step.out(host, self)
            except Exception as exc:
                step.error = exc
                step.t_done = step.t_done or tracelib.now()
            finally:
                # `packed` stays: a drain that found the step unfinished may
                # still be about to feed its next step from it
                step.logits = None
                step.done = True
