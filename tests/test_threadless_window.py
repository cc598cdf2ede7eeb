"""Entries without a thread (runtime/window.py `submit_nowait`): a caller
that must not block (the node's event loop) joins the same queue and the
same formation as a blocked thread, and the drain that answers its entries
hands them back in ONE call; whatever would have failed a blocked thread
fails such an entry with the same error. Threads and a fake device, no jax
(tests/test_lane_window.py's)."""

import threading
import time

import pytest

from inferd_tpu.obs import trace as tracelib
from inferd_tpu.runtime.window import WindowedBatcher
from test_lane_window import FakeLanes, _join, _until


class Hands:
    """A submitter's callback: every call's entries, and which thread made it."""

    def __init__(self):
        self.calls, self.threads = [], []

    def __call__(self, entries):
        self.calls.append(list(entries))
        self.threads.append(threading.current_thread().name)

    def sids(self):
        return [sorted(e.payload[0] for e in call) for call in self.calls]


def _submit_held(lanes, hands, sids):
    """One thread-less entry of each session, pending while somebody else
    (a prefill) holds the device: ONE drain takes them all."""
    with lanes.dev:
        entries = [lanes.batcher.submit_nowait((s, 0), hands) for s in sids]
        _until(lambda: lanes.batcher._ticket is not None)  # the window's own flusher forms
    return entries


@pytest.mark.parametrize("n", [1, 4, 32])
def test_a_drain_hands_all_its_entries_back_in_one_call(n):
    lanes, hands = FakeLanes(step_s=0.0), Hands()
    sids = [f"s{i}" for i in range(n)]
    entries = _submit_held(lanes, hands, sids)
    _until(lambda: hands.calls)
    assert hands.sids() == [sorted(sids)] and lanes.steps == [sorted(sids)]
    assert hands.threads == ["window-flush"]  # nobody else was there to flush
    stamps = {e.t_handed for e in entries}
    assert len(stamps) == 1 and None not in stamps  # one stamp a hand-over
    for e, sid in zip(entries, sids):
        assert e.error is None and e.result == ("ok", (sid, 0))
        assert e.t_submit <= e.t_taken <= e.t_handed
    assert not lanes.batcher._threadless


def test_entries_of_both_kinds_share_one_drain_whoever_flushes():
    lanes, hands = FakeLanes(step_s=0.0), Hands()
    with lanes.dev:
        first = lanes.batcher.submit_nowait(("a", 0), hands)  # the window's thread forms
        blocked = lanes.session("b", 1, end=False)
        _until(lambda: len(lanes.batcher._pending) == 2)
    _join([blocked])
    _until(lambda: hands.calls)
    assert lanes.steps == [["a", "b"]] and hands.sids() == [["a"]]
    assert first.result == ("ok", ("a", 0))
    # the other way round: a blocked thread is the flusher, and hands over
    with lanes.dev:
        blocked = lanes.session("b", 1, end=False)
        _until(lambda: lanes.batcher._ticket is not None)
        late = lanes.batcher.submit_nowait(("a", 1), hands)
    _join([blocked])
    _until(lambda: len(hands.calls) == 2)
    assert lanes.steps[-1] == ["a", "b"] and late.result == ("ok", ("a", 1))
    assert hands.threads[0] == "window-flush" and hands.threads[1] != "window-flush"


def test_two_submitters_get_one_call_each():
    lanes, one, two = FakeLanes(step_s=0.0), Hands(), Hands()
    with lanes.dev:
        for i in range(3):
            lanes.batcher.submit_nowait((f"a{i}", 0), one)
            lanes.batcher.submit_nowait((f"b{i}", 0), two)
    _until(lambda: one.calls and two.calls)
    assert one.sids() == [["a0", "a1", "a2"]] and two.sids() == [["b0", "b1", "b2"]]


def test_a_closed_loop_without_threads_forms_full_steps():
    """Sessions that submit again from their hand-over, as the node's
    coroutines do: every step but the first serves them all, and the
    window's own thread is woken once a formation."""
    lanes, n, rounds = FakeLanes(step_s=0.01), 6, 8
    done = threading.Event()
    left = {"hops": n * rounds}

    def hand(entries):
        for e in entries:
            sid, i = e.payload
            assert e.error is None and e.result == ("ok", (sid, i))
            left["hops"] -= 1
            if i + 1 < rounds:
                lanes.batcher.submit_nowait((sid, i + 1), hand)
        if not left["hops"]:
            done.set()

    with lanes.dev:
        for s in range(n):
            lanes.batcher.submit_nowait((f"s{s}", 0), hand)
    assert done.wait(timeout=30)
    assert lanes.steps == [sorted(f"s{s}" for s in range(n))] * rounds
    stats = lanes.batcher.stats()
    assert stats["gang_full"] >= rounds - 1 and stats["gang_timeout"] == 0
    assert stats["session_turns"] == n * (rounds - 1)


# -- whatever fails a blocked thread fails the entry, with the same error ------


def _blocked_error(lanes, sid):
    """What a blocked thread's submit raises, once it has."""
    got = []

    def run():
        try:
            lanes.batcher.submit((sid, 0))
        except Exception as exc:
            got.append(exc)

    t = threading.Thread(target=run)
    t.start()
    return t, got


def test_an_invalidation_hands_the_entry_back_with_its_error():
    lanes, hands = FakeLanes(step_s=0.0), Hands()
    with lanes.dev:
        gone = lanes.batcher.submit_nowait(("a", 0), hands)
        stays = lanes.batcher.submit_nowait(("b", 0), hands)
        t, got = _blocked_error(lanes, "a")
        _until(lambda: len(lanes.batcher._pending) == 3)
        lanes.end("a")
        _join([t])
        assert hands.sids() == [["a"]]  # at once, by the thread that invalidated
    _until(lambda: len(hands.calls) == 2)
    assert type(gone.error) is type(got[0]) is ValueError and str(gone.error) == str(got[0])
    assert gone.result is None and stays.result == ("ok", ("b", 0))
    assert lanes.steps == [["b"]]


def test_a_raising_step_fails_its_entries_and_the_next_step_runs():
    lanes, hands = FakeLanes(step_s=0.0), Hands()
    boom = RuntimeError("device fell over")
    run = lanes.run

    def failing(entries):
        with lanes.dev:
            lanes.batcher.drain_pending()
            raise boom

    lanes.batcher._run_batch = failing
    with lanes.dev:
        a = lanes.batcher.submit_nowait(("a", 0), hands)
        t, got = _blocked_error(lanes, "b")
        _until(lambda: len(lanes.batcher._pending) == 2)
    _join([t])
    _until(lambda: hands.calls)
    assert a.error is boom and got == [boom] and a.t_handed is not None
    lanes.batcher._run_batch = run
    again = lanes.batcher.submit_nowait(("a", 1), hands)
    _until(lambda: len(hands.calls) == 2)
    assert again.error is None and again.result == ("ok", ("a", 1))


def test_a_callback_that_never_drains_fails_what_was_pending():
    hands = Hands()
    batcher = WindowedBatcher(
        0.005, lambda entries: None, co_possible=lambda: False,
        swap_in_run=True, expect=lambda p: p[0],
    )
    a = batcher.submit_nowait(("a", 0), hands)
    _until(lambda: hands.calls)
    assert isinstance(a.error, RuntimeError) and "never drained" in str(a.error)
    assert not batcher._flusher_active and not batcher._pending


@pytest.mark.parametrize("flusher", ["own", "thread"])
def test_the_stall_timeout_fails_the_entry_and_leaves_the_event(flusher):
    """A step that never ends: the entry in it gets what a thread blocked
    beside it gets, a TimeoutError and a `window.stall` event, from the
    window's watcher (whichever thread is the wedged flusher)."""
    lanes, hands, events = FakeLanes(step_s=0.0), Hands(), []
    lanes.gate = threading.Event()
    lanes.batcher._wait_timeout_s = 0.3
    lanes.batcher.on_event = lambda etype, **f: events.append((etype, f))
    wedged = None
    with lanes.dev:
        if flusher == "thread":
            wedged, _ = _blocked_error(lanes, "b")  # it flushes, and sits in the step
            _until(lambda: lanes.batcher._ticket is not None)
        a = lanes.batcher.submit_nowait(("a", 0), hands)
        beside, got = _blocked_error(lanes, "c")
        _until(lambda: len(lanes.batcher._pending) == (3 if wedged else 2))
    t0 = time.monotonic()
    _until(lambda: hands.calls)
    assert 0.2 < time.monotonic() - t0 < 5.0
    _join([beside])
    assert isinstance(a.error, TimeoutError) and a.t_handed is not None
    assert type(got[0]) is TimeoutError and str(got[0]) == str(a.error)
    stalls = [f for etype, f in events if etype == "window.stall"]
    assert stalls == [{"where": "co_arrival", "timeout_s": 0.3}] * 2  # the thread's, the entry's
    lanes.gate.set()  # the step ends after all: nobody is handed twice
    _until(lambda: not lanes.batcher._running)
    if wedged is not None:
        _join([wedged])
    time.sleep(0.05)
    assert hands.sids() == [["a"]]


def test_a_formation_that_waits_out_a_wedged_step_stalls_as_before():
    lanes, hands, events = FakeLanes(step_s=0.0), Hands(), []
    lanes.gate = threading.Event()
    lanes.batcher._wait_timeout_s = 0.3
    lanes.batcher.on_event = lambda etype, **f: events.append(f.get("where"))
    first = lanes.batcher.submit_nowait(("a", 0), hands)
    _until(lambda: lanes.steps == [["a"]])  # a's step holds the device for good
    second = lanes.batcher.submit_nowait(("b", 0), hands)
    _until(lambda: len(hands.calls) >= 2 or (first.t_handed and second.t_handed))
    assert isinstance(first.error, TimeoutError) and isinstance(second.error, TimeoutError)
    assert "co_arrival" in events
    lanes.gate.set()


def test_submit_nowait_is_a_formations():
    plain = WindowedBatcher(0.001, lambda entries: None, co_possible=lambda: True)
    with pytest.raises(ValueError, match="formation"):
        plain.submit_nowait(("a", 0), lambda entries: None)


def test_the_spans_of_its_wait_are_the_submitters_to_record():
    """`record_waits` on the entry handed back: the two spans `submit`
    records for a blocked thread, under the span current at the submit."""
    lanes, hands = FakeLanes(step_s=0.0), Hands()
    rec = lanes.batcher.tracer = tracelib.SpanRecorder("t")
    parent = tracelib.SpanContext("trace", "compute-span")
    token = tracelib.set_current(parent)
    try:
        with lanes.dev:
            e = lanes.batcher.submit_nowait(("a", 0), hands)
            time.sleep(0.02)
    finally:
        tracelib.reset_current(token)
    _until(lambda: hands.calls)
    assert not [s for s in rec.spans() if s["name"] in ("lock_wait", "batch_wait")]
    lanes.batcher.record_waits(e)
    waits = {s["name"]: s for s in rec.spans() if s["name"] in ("lock_wait", "batch_wait")}
    assert set(waits) == {"lock_wait", "batch_wait"}
    assert all(s["parent"] == "compute-span" and s["trace"] == "trace" for s in waits.values())
    assert waits["lock_wait"]["t0"] == e.t_submit and waits["lock_wait"]["t1"] == e.t_lock
    assert waits["batch_wait"]["t0"] == e.t_lock and waits["batch_wait"]["t1"] == e.t_taken
    assert waits["batch_wait"]["attrs"] == {"flusher": 1}  # the window's thread flushed for it


def test_the_windows_own_threads_are_started_once_and_stay():
    lanes, hands = FakeLanes(step_s=0.0), Hands()
    mine = lambda: sorted(  # noqa: E731
        t.name for t in threading.enumerate() if getattr(t, "_target", None) in (
            lanes.batcher._own, lanes.batcher._watch))
    assert mine() == []  # a window of blocked threads alone has none
    for step in range(3):  # three formations, each owed to the window's thread
        lanes.batcher.submit_nowait(("a", step), hands)
        _until(lambda: len(hands.calls) == step + 1)
        assert mine() == ["window-flush", "window-watch"]
