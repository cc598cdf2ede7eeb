"""Trace/span context and the per-process span recorder.

The north-star metric is p50 inter-stage hop latency, but per-node
counters cannot say where one slow token spent its time — queue vs
compute vs relay vs rescue vs handoff. This module gives every request a
`trace_id` and every timed interval a span:

  * the context rides the wire envelope as a `trace` key next to
    `session_id`/`task_id` (runtime/node.handle_forward) and as the
    `X-Inferd-Trace` HTTP header on /generate;
  * spans are recorded HOST-SIDE only (never inside jit — this module
    imports no jax) into a bounded thread-safe ring buffer, one per
    process, with a JSONL exporter per node;
  * recording is cheap enough to stay always-on (Dapper's core design
    choice): one dict append under a lock, with the cumulative recording
    cost tracked in `overhead_ms` so perf/gate.check_span_overhead can
    prove the <1%-of-compute budget holds in the field.

Phase vocabulary (the `phase` tag): `queue`, `compute`, `wire`, `relay`,
`rescue`, `handoff`, `sample`, `window` (a decode step's co-batching
wait in the stage arrival window, runtime/node._run_stage_window) for
timed request phases, plus the structural umbrellas `client` (a
client's whole generate call) and `server` (a node's whole handler).
Inside an executor call (`compute`) the executors stamp four child
phases through `region`/`holding`: `batch_wait` (the arrival window of
runtime/window.py), `lock_wait` (the executor's device lock), `device`
(jitted call -> block_until_ready) and `copy_out` (device -> host).
Between two steps (docs/OBSERVABILITY.md "Between two steps"): `deliver`
(the flusher's `copy_out` returned -> the entry's worker is back from the
executor), `resume` (-> its coroutine runs again on the event loop),
`emit` (the generation loop's `on_token` callback) and, once a step and
parentless, `turn` (runtime/window.py: the device freed -> the next
drain).
A request's admission and release (docs/OBSERVABILITY.md "A request's
admission"), once a request: `accept` (/generate arrived -> the generation
loop's `generate` opens), `open` (-> the first chunk's `step`), `lane`
(the executor binds the session's lane or slot), `ride` (the first decode
hop's own wait for the step it rode) and `close` (the loop gives the
session back).

What the ring may forget: a span is KEPT (`keep`) or sampled. Kept spans
live in a ring of their own (`KEPT_CAP`), which a window of the
benchmark does not fill: the roots of a request, every span of its
admission (they inherit `keep` from their parent: `SpanContext.keep`,
never on the wire), the spans that are once a step (`device`, `copy_out`,
`turn`) and, while a profiler capture runs (`annotating`), every span.
The spans that are once a HOP stay in the sampled ring, which holds a
few seconds of them at a few thousand hops a second.
Disabled-by-config tracing
(INFERD_TRACE=0, read per call) records nothing and leaves the wire
envelope byte-identical to the untraced format.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import random
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, NamedTuple, Optional

PHASES = (
    "queue", "compute", "wire", "relay", "rescue", "handoff", "sample",
    "window",
    "batch_wait", "lock_wait", "device", "copy_out",
    "deliver", "resume", "emit", "turn",
    "accept", "open", "lane", "ride", "close",
    "client", "server",
)

#: The ring of KEPT spans (SpanRecorder): sized from the benchmark's cell
#: with the highest span rate, `g4hm-many-chat`, over a 45 s window: 36-60
#: steps a second x 3 spans (`device`, `copy_out`, `turn`) is 5-8 thousand,
#: 4 requests a second x (5 + 9 a prefill chunk + 13 for the first decode
#: hop) is 5-7 thousand, and a profiler capture's 4 s at 12 thousand spans
#: a second, all kept, is 48 thousand.
KEPT_CAP = 65536

#: HTTP header carrying "<trace_id>-<span_id>" (the /generate surface).
TRACE_HEADER = "X-Inferd-Trace"

#: Envelope key carrying {"id": trace_id, "span": parent_span_id}.
WIRE_KEY = "trace"


def enabled() -> bool:
    """Always-on by default; INFERD_TRACE=0 disables. Read per call so
    tests (and an operator's kill switch) toggle without reimports."""
    return os.environ.get("INFERD_TRACE", "1").lower() not in (
        "0", "off", "false", "no",
    )


# Process clock anchor: every span/event timestamp is the wall-clock
# epoch captured ONCE at import plus a perf_counter delta. time.time()
# at each stamp would let an NTP step mid-span yield a NEGATIVE duration
# that poisons merge breakdowns; perf_counter is monotonic, so durations
# are non-negative by construction and all of one process's stamps share
# one consistent clock (cross-process skew stays merge.clock_offsets'
# job, exactly as before).
_EPOCH_WALL = time.time()
_EPOCH_PERF = time.perf_counter()


def now() -> float:
    """Anchored wall-clock epoch seconds — the ONE stamp source for span
    and event timestamps in this process."""
    return _EPOCH_WALL + (time.perf_counter() - _EPOCH_PERF)


# Span and trace ids: 64 random bits from a generator of this process's
# own, seeded from the system's entropy at import (and again in a forked
# child). An id needs no secrecy, and `uuid.uuid4()` is an `os.urandom`
# system call an id: the call gives up the GIL, so every span recorded on
# a node's event loop handed the loop's thread to whichever worker was
# awake and waited to get it back: 94 us a span against 5 (PERF.md section 6,
# PR 40), 4 % of `q4b-sat-chat`'s tokens a second.
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)


def new_id() -> str:
    return "%016x" % _ids.getrandbits(64)


class SpanContext(NamedTuple):
    """The propagated half of a span: enough to parent remote children.
    `keep` (this process only: neither the wire nor the header carries it)
    is what a child recorded without a `keep` of its own inherits. A tuple:
    a hop makes seven of them, and a frozen dataclass takes twice as long
    to make."""

    trace_id: str
    span_id: str
    keep: bool = False

    def child(self) -> "SpanContext":
        """The context of a new span under this one: its trace, its `keep`."""
        return SpanContext(self.trace_id, new_id(), self.keep)

    def to_wire(self) -> Dict[str, str]:
        return {"id": self.trace_id, "span": self.span_id}

    def to_header(self) -> str:
        return f"{self.trace_id}-{self.span_id}"

    @staticmethod
    def from_wire(obj: Any) -> Optional["SpanContext"]:
        if not isinstance(obj, dict):
            return None
        tid, sid = obj.get("id"), obj.get("span")
        if not isinstance(tid, str) or not isinstance(sid, str):
            return None
        return SpanContext(tid, sid)

    @staticmethod
    def from_header(value: Optional[str]) -> Optional["SpanContext"]:
        if not value or "-" not in value:
            return None
        tid, _, sid = value.partition("-")
        if not tid or not sid:
            return None
        return SpanContext(tid, sid)


_current: "contextvars.ContextVar[Optional[SpanContext]]" = contextvars.ContextVar(
    "inferd_trace_ctx", default=None
)


def current() -> Optional[SpanContext]:
    return _current.get()


def set_current(ctx: Optional[SpanContext]):
    """Returns a token for reset_current (task-local via contextvars)."""
    return _current.set(ctx)


def reset_current(token) -> None:
    _current.reset(token)


def adopt(ctx: Optional[SpanContext]) -> Optional[SpanContext]:
    """`ctx` as an envelope or a header gave it, with what only this
    process knows of that span (`keep`): where it names the CURRENT span
    (a hop the generation loop made as a call, not over a socket), that
    one."""
    cur = _current.get()
    if ctx is not None and cur is not None and cur.span_id == ctx.span_id:
        return cur
    return ctx


# Stamps a callee hands up to the owner of the span it runs under: the
# owner `open_marks()` a dict in its own thread or task, whatever runs
# beneath `mark(name, t)`s into it (a no-op where nobody opened one), and
# the owner reads it once the call is back. `deliver`'s t0 travels this
# way from the window's submit to Node._timed_process. A stamp the OWNER
# put there travels the other way (`marked`): `accept`'s t0, from the
# /generate handler down to the generation loop that records the span.
_marks: "contextvars.ContextVar[Optional[Dict[str, float]]]" = contextvars.ContextVar(
    "inferd_trace_marks", default=None
)


def open_marks():
    """(the dict, a token for close_marks)."""
    marks: Dict[str, float] = {}
    return marks, _marks.set(marks)


def close_marks(token) -> None:
    _marks.reset(token)


def mark(name: str, t: float) -> None:
    marks = _marks.get()
    if marks is not None:
        marks[name] = t


def marked(name: str) -> Optional[float]:
    marks = _marks.get()
    return None if marks is None else marks.get(name)


def wire_ctx() -> Optional[Dict[str, str]]:
    """The envelope `trace` value for the current context, or None when
    tracing is off / no context is active — callers must OMIT the key
    then, so a disabled config leaves the envelope byte-identical."""
    ctx = current()
    if ctx is None or not enabled():
        return None
    return ctx.to_wire()


def attach_wire(env: Dict[str, Any]) -> Dict[str, Any]:
    """Attach the current context to a wire envelope under WIRE_KEY, or
    leave the envelope UNTOUCHED (no key at all) when tracing is off or
    no context is active. The single enforcement point of the
    byte-identical-when-disabled invariant for every client."""
    ctx = wire_ctx()
    if ctx is not None:
        env[WIRE_KEY] = ctx
    return env


def nearest_rank_quantile(sorted_values, q: float) -> float:
    """Nearest-rank quantile over an ascending list — the ONE estimator
    merge.hop_summary (the CLI's swarm-wide numbers) uses."""
    idx = min(len(sorted_values) - 1, max(0, int(q * len(sorted_values) + 0.5) - 1))
    return sorted_values[idx]


def header_ctx() -> Optional[Dict[str, str]]:
    """{TRACE_HEADER: ...} for the current context, or None."""
    ctx = current()
    if ctx is None or not enabled():
        return None
    return {TRACE_HEADER: ctx.to_header()}


@contextmanager
def region(recorder: Optional["SpanRecorder"], name: str, parents=None, keep=None, **attrs):
    """Time the block as one span `name` (phase = name) on `recorder`, a
    child of the CURRENT context — or one span per context in `parents`
    (a flusher stamping a wait it served for every entry of its batch).
    Yields the attrs dict, so the block can add what it only knows at its
    end (`bytes`). `keep` as `SpanRecorder.record_span` takes it (None: the
    parent's). While the recorder is `annotating` (a profiler capture
    is running) the block is also the profiler-trace annotation
    `inferd.<name>`. Does nothing without a recorder or with
    INFERD_TRACE=0."""
    if recorder is None or not enabled():
        yield attrs
        return
    ann = _annotation(recorder, name)
    t0 = now()
    try:
        yield attrs
    finally:
        t1 = now()
        if ann is not None:
            ann.__exit__(None, None, None)
        for parent in (current(),) if parents is None else parents:
            recorder.record_span(
                name, name, t0, t1, parent=parent, attrs=dict(attrs) or None, keep=keep
            )


def _annotation(recorder: "SpanRecorder", name: str):
    """The entered profiler annotation `inferd.<name>` while a capture runs
    on `recorder`, else None (and jax is not imported)."""
    if not recorder.annotating:
        return None
    import jax

    ann = jax.profiler.TraceAnnotation("inferd." + name)
    ann.__enter__()
    return ann


@contextmanager
def holding(lock, recorder: Optional["SpanRecorder"], **attrs):
    """`with lock:` whose wait is the span `lock_wait` (see `region`),
    recorded when the lock is RELEASED so that it can say how long the
    block held it: `held_ms`, acquired -> released."""
    if recorder is None or not enabled():
        with lock:
            yield
        return
    ann = _annotation(recorder, "lock_wait")
    t0 = now()
    lock.acquire()
    t1 = now()
    if ann is not None:
        ann.__exit__(None, None, None)
    try:
        yield
    finally:
        lock.release()
        attrs["held_ms"] = round((now() - t1) * 1e3, 3)
        recorder.record_span("lock_wait", "lock_wait", t0, t1, parent=current(), attrs=attrs)


#: one span as its JSONL line (an encoder made once: `json.dumps` with
#: separators of its own makes one a call)
_encode = json.JSONEncoder(separators=(",", ":")).encode


class SpanRecorder:
    """Two bounded thread-safe span rings for one process/service.

    `service` names the recorder in every span (a node_id like
    "10.0.0.2:6050", or "client"); the merge CLI uses it as the clock
    domain for skew correction. A span goes to the ring of KEPT spans
    (`KEPT_CAP`; the module docstring says which are) or to the sampled
    ring (`cap`); each drops its OLDEST spans on overflow (`kept_dropped`,
    `dropped` count them): tracing must never grow RSS unboundedly on a
    long-lived node. Every reader gets both as one list in t0 order.
    """

    def __init__(self, service: str, cap: int = 8192):
        self.service = service
        self._lock = threading.Lock()
        self._buf: "deque[Dict[str, Any]]" = deque(maxlen=max(16, cap))
        self._kept: "deque[Dict[str, Any]]" = deque(maxlen=KEPT_CAP)
        self.dropped = 0
        self.kept_dropped = 0
        self.count = 0
        self.kept = 0  # of `count`, those recorded into the kept ring
        self.overhead_ms = 0.0
        self._flushed = [0, 0]  # high-water marks for flush_jsonl: sampled, kept
        # True while a jax.profiler capture runs in this process
        # (utils.profiling.Profiler sets it): `region`s then also enter a
        # jax.profiler.TraceAnnotation, so the written trace shows them
        # above the device's operations on the profiler's own clock, and
        # every span is kept: the capture's stretch is whole.
        # Otherwise no annotation object is made and jax is not imported.
        self.annotating = False

    # ------------------------------------------------------------ recording

    def record_span(
        self,
        name: str,
        phase: str,
        t0: float,
        t1: float,
        *,
        parent: Optional[SpanContext] = None,
        ctx: Optional[SpanContext] = None,
        attrs: Optional[Dict[str, Any]] = None,
        keep: Optional[bool] = None,
    ) -> Optional[SpanContext]:
        """Record a finished [t0, t1] span (wall-clock epoch seconds).

        `ctx` pre-allocates the span's own (trace, span) ids — used when
        the id already rode an envelope to remote children before the
        span finished. Otherwise the span joins `parent`'s trace (or
        starts a fresh trace when parentless). `keep` says which ring
        holds it; None means `ctx`'s where one is given, else the
        parent's. Returns the span's context, or None when tracing is
        disabled."""
        if not enabled():
            return None
        r0 = time.perf_counter()
        if keep is None:
            keep = ctx.keep if ctx is not None else parent is not None and parent.keep
        if ctx is None:
            tid = parent.trace_id if parent is not None else new_id()
            ctx = SpanContext(tid, new_id(), keep)
        span = {
            "trace": ctx.trace_id,
            "span": ctx.span_id,
            "parent": parent.span_id if parent is not None else None,
            "name": name,
            "phase": phase,
            "service": self.service,
            "t0": t0,
            "t1": t1,
        }
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            if keep or self.annotating:
                if len(self._kept) == self._kept.maxlen:
                    self.kept_dropped += 1
                self._kept.append(span)
                self.kept += 1
            else:
                if len(self._buf) == self._buf.maxlen:
                    self.dropped += 1
                self._buf.append(span)
            self.count += 1
            self.overhead_ms += (time.perf_counter() - r0) * 1e3
        return ctx

    @contextmanager
    def span(
        self,
        name: str,
        phase: str,
        *,
        parent: Optional[SpanContext] = None,
        attrs: Optional[Dict[str, Any]] = None,
        keep: Optional[bool] = None,
    ):
        """Context manager: times the block, records the span, and makes
        it the CURRENT context inside (children — local blocks, wire
        envelopes, HTTP headers — parent to it automatically, and inherit
        its `keep`; None: its own parent's). A no-op yielding None when
        tracing is disabled."""
        if not enabled():
            yield None
            return
        p = parent if parent is not None else current()
        if keep is None:
            keep = p is not None and p.keep
        ctx = SpanContext(p.trace_id if p is not None else new_id(), new_id(), keep)
        token = _current.set(ctx)
        t0 = now()
        try:
            yield ctx
        finally:
            _current.reset(token)
            self.record_span(
                name, phase, t0, now(), parent=p, ctx=ctx, attrs=attrs
            )

    # ------------------------------------------------------------ reading

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf) + len(self._kept)

    @staticmethod
    def _in_order(sampled, kept) -> List[Dict[str, Any]]:
        """Both rings as one list by t0 (each is in the order its spans
        ENDED, so nearly sorted already)."""
        return sorted(itertools.chain(sampled, kept), key=lambda s: s["t0"])

    def spans(self) -> List[Dict[str, Any]]:
        """Point-in-time copy of both rings (non-draining)."""
        with self._lock:
            rings = list(self._buf), list(self._kept)
        return self._in_order(*rings)

    def drain(self) -> List[Dict[str, Any]]:
        with self._lock:
            rings = list(self._buf), list(self._kept)
            self._buf.clear()
            self._kept.clear()
        return self._in_order(*rings)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "service": self.service,
                "buffered": len(self._buf) + len(self._kept),
                "recorded": self.count,
                "dropped": self.dropped,
                "kept": self.kept,
                "kept_dropped": self.kept_dropped,
                "overhead_ms": round(self.overhead_ms, 3),
            }

    # ------------------------------------------------------------ export

    def jsonl_lines(self, spans: Optional[Iterable[Dict[str, Any]]] = None):
        """`spans` (default: both rings, in t0 order) a line each."""
        return map(_encode, self.spans() if spans is None else spans)

    def dump_jsonl(self, path: str, drain: bool = True) -> int:
        """Append the buffer (draining it by default) to a JSONL file;
        returns the number of spans written. The per-node span file the
        merge CLI consumes."""
        spans = self.drain() if drain else self.spans()
        return self._append_jsonl(path, spans)

    def flush_jsonl(self, path: str) -> int:
        """Append only the spans recorded since the last flush, WITHOUT
        draining the rings — the periodic exporter's mode: /spans and the
        gossiped hop quantiles keep seeing the live buffer, while the
        JSONL file still receives every span exactly once (ring overflow
        between flushes loses the dropped spans, counted in `dropped` /
        `kept_dropped`)."""
        with self._lock:
            new = []
            counts = (self.count - self.kept, self.kept)
            for i, ring in enumerate((self._buf, self._kept)):
                n_new = min(len(ring), max(0, counts[i] - self._flushed[i]))
                new.append(list(ring)[len(ring) - n_new:] if n_new else [])
                self._flushed[i] = counts[i]
        return self._append_jsonl(path, self._in_order(*new))

    def _append_jsonl(self, path: str, spans: List[Dict[str, Any]]) -> int:
        if not spans:
            return 0
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            for line in self.jsonl_lines(spans):
                f.write(line + "\n")
        return len(spans)
