"""Runtime lock-order sanitizer, fair device lock, and loop-stall detector.

The static side of the concurrency plane (analysis/concurrency, rules
J007-J011) proves properties of the acquisition orders the SOURCE admits;
this module watches the orders that actually HAPPEN — the TSan-style
dynamic half that catches what lexical analysis cannot (cross-function
nesting, callback-driven acquisition, orders that only occur under a
specific interleaving):

  * `LOCK_ORDER` is the committed canonical acquisition order for the
    repo's named locks. It is THE single source of truth — the static
    J007 rule imports it, so the lint and the sanitizer can never
    disagree about which nesting is an inversion.
  * `make_lock(name)` is the constructor seam the runtime threads its
    named locks through (executor device lock / `_mu`, the adapter
    registry, the standby store, the arrival window). Disabled — the
    default outside tests — it returns a plain `threading.Lock` and
    costs NOTHING. Watching (INFERD_LOCKWATCH env,
    or `instrument()`), it returns an order-recording `WatchedLock`
    proxy that keeps a per-thread stack of held ranks and, on a BLOCKING
    acquisition that violates `LOCK_ORDER`, raises `LockOrderError`
    (strict mode: the tier-1 suite) or journals ONE `lock.inversion`
    event per (held, acquiring) pair (production mode, events-gated).
    Non-blocking acquires (`blocking=False`) are exempt: a try-acquire
    cannot participate in a deadlock cycle.
  * `FairDeviceLock` is a ticketed (FIFO) mutex for the device lock:
    `threading.Lock` wakes waiters in no defined order and a releasing
    thread can immediately re-acquire, which is exactly the
    chunked-prefill starvation the executors' explicit
    `time.sleep(0.0005)` yield worked around. Ticket grant order makes
    the handoff deterministic, so the yield is skipped when the device
    lock is fair (see `is_fair`).
  * `LoopStallDetector` measures asyncio scheduling drift: an
    `asyncio.sleep(interval)` that returns `> stall_ms` late means some
    handler blocked the event loop that long; each stall journals a
    `loop.stall` event. Wired suite-wide by tests/conftest.py (kill
    switch INFERD_LOCKWATCH=0) and into the node's telemetry tick.

The checking cost is accumulated in `stats()['overhead_ms']` and
budgeted by perf.gate.check_span_overhead under the same <=1%-of-compute
bar as the rest of the telemetry plane (the node exports it as the
`lockwatch.overhead_ms` gauge). Pure stdlib — no jax import.
"""

from __future__ import annotations

import asyncio
import os
import threading
import time
from typing import Any, Callable, List, Optional

#: Committed canonical acquisition order (outermost first). An
#: acquisition is an inversion iff the acquiring lock's rank is LOWER
#: than the highest rank already held by the same thread. Leaf
#: registries (metrics, events) are ranked but not runtime-watched —
#: they are too hot for per-acquire bookkeeping; the static J007 rule
#: still checks their lexical nesting.
LOCK_ORDER = (
    "dev",       # executor device lock (serializes device steps)
    "mu",        # executor session/lane bookkeeping
    "registry",  # AdapterRegistry._mu (slot + refcount state)
    "repl",      # StandbyStore._mu (shadow KV for peers)
    "window",    # WindowedBatcher._mu (arrival-window entries)
    "metrics",   # utils.metrics Metrics/Histogram._lock
    "events",    # obs.events EventJournal._lock
)
LOCK_RANK = {name: i for i, name in enumerate(LOCK_ORDER)}


class LockOrderError(RuntimeError):
    """A blocking acquisition contradicted LOCK_ORDER (strict mode)."""


_tls = threading.local()


class _State:
    def __init__(self) -> None:
        self.enabled = False
        self.strict = False
        self.on_event: Optional[Callable[..., Any]] = None


_state = _State()
_seen_pairs: set = set()  # (held, acquiring) pairs already journaled
_stats_lock = threading.Lock()
_stats = {"checks": 0, "inversions": 0, "overhead_ms": 0.0}


def _env() -> str:
    return os.environ.get("INFERD_LOCKWATCH", "").strip().lower()


def watching() -> bool:
    """Is lock watching on? INFERD_LOCKWATCH=0 is an absolute kill
    switch; any other non-empty value (or a prior `instrument()` call)
    enables. Read at `make_lock` time — construction decides proxy vs
    plain lock, so the disabled path costs nothing per acquire."""
    env = _env()
    if env in ("0", "off", "false", "no"):
        return False
    return _state.enabled or bool(env)


def strict() -> bool:
    """Raise on inversion instead of journaling (the test-suite mode:
    INFERD_LOCKWATCH=strict, or instrument(strict=True))."""
    return _state.strict or _env() == "strict"


def instrument(
    journal: Optional[Callable[..., Any]] = None,
    strict: bool = False,
) -> None:
    """Enable watching process-wide. `journal` is an
    EventJournal.emit-shaped hook for `lock.inversion` events (ignored
    in strict mode, where an inversion raises). Call BEFORE the locks
    you want watched are constructed — `make_lock` decides at
    construction time."""
    _state.enabled = True
    _state.strict = bool(strict)
    if journal is not None:
        _state.on_event = journal


def set_journal(journal: Optional[Callable[..., Any]]) -> None:
    """Late-bind the inversion journal (the node builds its EventJournal
    after its executor's locks exist)."""
    _state.on_event = journal


def reset() -> None:
    """Test hook: drop instrumented state and counters."""
    _state.enabled = False
    _state.strict = False
    _state.on_event = None
    _seen_pairs.clear()
    with _stats_lock:
        _stats.update({"checks": 0, "inversions": 0, "overhead_ms": 0.0})


def stats() -> dict:
    with _stats_lock:
        return dict(_stats)


def held_stack() -> List[str]:
    """Names of watched locks the CALLING thread currently holds,
    acquisition order (diagnostics/tests)."""
    return [name for _rank, name in getattr(_tls, "stack", [])]


def _emit(etype: str, **fields: Any) -> None:
    """Journal through the late-bound hook; never raises (emit_safely
    semantics — observability must not add a failure mode)."""
    hook = _state.on_event
    if hook is None:
        return
    try:
        hook(etype, **fields)
    except Exception:
        pass


class WatchedLock:
    """Order-recording proxy around a Lock-shaped object.

    Mirrors the `threading.Lock` surface the runtime uses (`acquire`,
    `release`, `locked`, context manager). The held-rank stack is
    per-thread (threading.local), so checking is lock-free; the check
    itself is O(held locks) — 2-3 in practice.
    """

    __slots__ = ("name", "rank", "_lock")

    def __init__(self, name: str, rank: int, lock: Any = None):
        self.name = name
        self.rank = rank
        self._lock = lock if lock is not None else threading.Lock()

    # -- checking ----------------------------------------------------------

    def _check(self) -> None:
        t0 = time.perf_counter()
        stack = getattr(_tls, "stack", None)
        if stack:
            worst_rank, worst_name = max(stack)
            if self.rank < worst_rank:
                self._violation(worst_name)
        with _stats_lock:
            _stats["checks"] += 1
            _stats["overhead_ms"] += (time.perf_counter() - t0) * 1e3

    def _violation(self, held_name: str) -> None:
        msg = (
            f"lock-order inversion: acquiring '{self.name}' "
            f"(rank {self.rank}) while holding '{held_name}' "
            f"(rank {LOCK_RANK[held_name]}) — canonical order is "
            f"{' -> '.join(LOCK_ORDER)}"
        )
        with _stats_lock:
            _stats["inversions"] += 1
        if strict():
            raise LockOrderError(msg)
        pair = (held_name, self.name)
        if pair in _seen_pairs:
            return
        _seen_pairs.add(pair)
        _emit(
            "lock.inversion",
            held=held_name,
            acquiring=self.name,
            thread=threading.current_thread().name,
        )

    # -- Lock surface ------------------------------------------------------

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if blocking:
            # try-acquires can't deadlock; only blocking waits are checked
            self._check()
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append((self.rank, self.name))
        return ok

    def release(self) -> None:
        self._lock.release()
        stack = getattr(_tls, "stack", None)
        if stack:
            entry = (self.rank, self.name)
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] == entry:
                    del stack[i]
                    break

    def locked(self) -> bool:
        return self._lock.locked()

    def __enter__(self) -> "WatchedLock":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()


class FairDeviceLock:
    """Ticketed FIFO mutex.

    `threading.Lock` makes no fairness promise: a thread that releases
    and immediately re-acquires (the chunked-prefill loop) can win the
    race against waiters forever — the executors' inter-chunk
    `time.sleep(0.0005)` yield exists solely to break that. Tickets make
    grant order ARRIVAL order: the flusher that started waiting during
    chunk K runs before chunk K+1, deterministically, no yield needed.
    Same `acquire(blocking, timeout)`/`release()`/`locked()` surface as
    threading.Lock so WatchedLock and the executors treat both alike.
    """

    def __init__(self) -> None:
        self._cv = threading.Condition(threading.Lock())
        self._next = 0     # next ticket to hand out
        self._serving = 0  # ticket currently holding the lock
        self._abandoned: set = set()  # timed-out tickets to skip

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        with self._cv:
            if not blocking:
                if self._serving == self._next:
                    self._next += 1  # free: our ticket is served at once
                    return True
                return False
            ticket = self._next
            self._next += 1
            deadline = (
                None if timeout is None or timeout < 0
                else time.monotonic() + timeout
            )
            while self._serving != ticket:
                remaining = (
                    None if deadline is None
                    else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    self._abandoned.add(ticket)
                    self._skip_abandoned()
                    return False
                self._cv.wait(remaining)
            return True

    def _skip_abandoned(self) -> None:
        # caller holds _cv; advance past tickets whose waiters gave up
        while self._serving in self._abandoned:
            self._abandoned.discard(self._serving)
            self._serving += 1
        self._cv.notify_all()

    def release(self) -> None:
        with self._cv:
            self._serving += 1
            self._skip_abandoned()

    def locked(self) -> bool:
        with self._cv:
            return self._serving != self._next

    def __enter__(self) -> "FairDeviceLock":
        self.acquire()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.release()


def make_lock(name: str, fair: bool = False) -> Any:
    """The ONE construction seam for the runtime's named locks.

    `name` must be in LOCK_ORDER (unknown names get a plain lock — a
    new named lock must be ranked before it can be watched). `fair`
    swaps the underlying mutex for a FairDeviceLock (the device lock's
    INFERD_FAIR_DEVLOCK option)."""
    base: Any = FairDeviceLock() if fair else threading.Lock()
    if not watching():
        return base
    rank = LOCK_RANK.get(name)
    if rank is None:
        return base
    return WatchedLock(name, rank, base)


def is_fair(lock: Any) -> bool:
    """Is this (possibly watch-wrapped) lock a FairDeviceLock? The
    chunked-prefill yield site consults this: with FIFO handoff the
    anti-starvation sleep is dead weight."""
    inner = getattr(lock, "_lock", lock)
    return isinstance(inner, FairDeviceLock)


def fair_devlock_enabled() -> bool:
    """INFERD_FAIR_DEVLOCK=1 opts the executors' device lock into the
    ticketed mutex (default off: the yield-based workaround is proven
    and the ticket lock's condition-variable handoff costs ~2x a bare
    Lock per uncontended acquire — noise next to a device step, but not
    next to nothing)."""
    return os.environ.get("INFERD_FAIR_DEVLOCK", "").strip().lower() in (
        "1", "on", "true", "yes",
    )


class LoopStallDetector:
    """Event-loop stall watchdog: journals `loop.stall` when a handler
    blocks the asyncio loop longer than `stall_ms`.

    Implementation is scheduling drift: an `asyncio.sleep(interval)`
    that returns late by more than the threshold means the loop spent
    that long unable to run ready callbacks — i.e. some handler did
    blocking work inline instead of hopping to an executor thread
    (J009's dynamic twin). Start from INSIDE the target loop."""

    def __init__(
        self,
        stall_ms: float = 50.0,
        interval_ms: float = 20.0,
        on_event: Optional[Callable[..., Any]] = None,
    ):
        self.stall_ms = float(stall_ms)
        self.interval_ms = float(interval_ms)
        self.on_event = on_event
        self.stalls: List[float] = []  # observed stall durations (ms)
        self._task: Optional[asyncio.Task] = None

    def start(self) -> "LoopStallDetector":
        self._task = asyncio.get_running_loop().create_task(self._run())
        return self

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None

    def _emit(self, etype: str, **fields: Any) -> None:
        hook = self.on_event or _state.on_event
        if hook is None:
            return
        try:
            hook(etype, **fields)
        except Exception:
            pass

    async def _run(self) -> None:
        interval = self.interval_ms / 1e3
        while True:
            t0 = time.perf_counter()
            await asyncio.sleep(interval)
            drift_ms = (time.perf_counter() - t0 - interval) * 1e3
            if drift_ms > self.stall_ms:
                self.stalls.append(drift_ms)
                self._emit("loop.stall", blocked_ms=round(drift_ms, 1))
