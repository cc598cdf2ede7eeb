"""Batch formation of the lane executor's window (runtime/window.py,
`expect=`): the batch is drained when the callback holds the device, and
the flusher first waits for the sessions the last two steps served. Threads
and a fake device (a lock and a sleep), no jax.

Sessions here are closed loops like a client's token loop: submit, get the
result, think for a moment, submit again. Where a test reads the size of a
step, steps are long against a turn (as on the chip: 37 ms against 4), and
a few steps may miss a member to the scheduler of a loaded test machine."""

import threading
import time

import pytest

from inferd_tpu.obs import trace as tracelib
from inferd_tpu.runtime.window import WindowedBatcher


class FakeLanes:
    """What BatchedExecutor is to the batcher: a device lock, a callback
    that drains under it, and a program that takes `step_s`."""

    def __init__(self, step_s, turn0_s=0.005, co_possible=lambda: True):
        self.step_s = step_s
        self.dev = threading.Lock()
        self.steps = []  # the sessions of every program run, in order
        self.gate = None  # an Event a test holds a step on
        self.batcher = WindowedBatcher(
            turn0_s, self.run, co_possible=co_possible,
            swap_in_run=True, expect=lambda payload: payload[0],
        )

    def run(self, entries):
        assert entries == []
        with self.dev:
            batch = self.batcher.drain_pending()
            if not batch:
                return
            self.steps.append(sorted(e.payload[0] for e in batch))
            if self.gate is not None:
                assert self.gate.wait(timeout=30)
            time.sleep(self.step_s)
            for e in batch:
                e.result = ("ok", e.payload)

    def end(self, sid):
        self.batcher.invalidate(lambda p: p[0] == sid, ValueError(f"{sid} ended"))

    def session(self, sid, tokens, think_s=0.0, start_s=0.0, end=True, out=None):
        """A closed-loop client; returns its (started) thread."""

        def loop():
            time.sleep(start_s)
            for i in range(tokens):
                got = self.batcher.submit((sid, i))
                assert got == ("ok", (sid, i))
                if out is not None:
                    out.append(time.monotonic())
                time.sleep(think_s)
            if end:
                self.end(sid)

        t = threading.Thread(target=loop)
        t.start()
        return t


def _one_step(lanes, sids):
    """One entry of each session, served by ONE step: they arrive while
    somebody else (a prefill) has the device."""
    with lanes.dev:
        threads = [lanes.session(s, 1, end=False) for s in sids]
        _until(lambda: len(lanes.batcher._pending) == len(sids))
    _join(threads)
    assert lanes.steps[-1] == sorted(sids)


def _join(threads):
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()


def _until(cond, timeout=30.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end
        time.sleep(0.001)


def test_arrivals_during_a_step_are_one_batch_of_the_next():
    lanes = FakeLanes(step_s=0.0)
    lanes.gate = threading.Event()
    a = lanes.session("a", 2, end=False)
    _until(lambda: lanes.steps == [["a"]])  # a's first step holds the device
    others = [lanes.session(s, 1, end=False) for s in "bcd"]
    _until(lambda: len(lanes.batcher._pending) == 3)
    lanes.gate.set()
    _join([a] + others)
    # b, c and d waited the step out, then for a, whom it had just served
    assert lanes.steps == [["a"], ["a", "b", "c", "d"]]
    st = lanes.batcher.stats()
    assert (st["batched_steps"], st["batched_tokens"]) == (2, 5)
    # both formations were complete: nobody to wait for, then only a
    assert (st["gang_full"], st["gang_timeout"], st["empty_drains"]) == (2, 0, 0)


def test_two_cohorts_half_a_step_apart_become_one_and_stay_one():
    lanes = FakeLanes(step_s=0.05, turn0_s=0.02)
    tokens = 12
    threads = [lanes.session(s, tokens, think_s=0.005) for s in "abc"]
    threads += [lanes.session(s, tokens, think_s=0.005, start_s=0.025) for s in "de"]
    _join(threads)
    assert sum(len(s) for s in lanes.steps) == 5 * tokens
    # two cohorts taking turns would need 2 * tokens steps; lockstep needs
    # `tokens` and a few to get there and to run out
    assert len(lanes.steps) <= tokens + 4
    assert len(lanes.steps[2]) == 5 or len(lanes.steps[3]) == 5
    full = [len(s) == 5 for s in lanes.steps[3:tokens - 1]]
    assert sum(full) >= len(full) - 1, lanes.steps
    assert lanes.batcher.stats()["mean_batch"] >= 4.0


def test_a_session_that_never_returns_costs_one_cap_and_is_not_expected_again():
    lanes = FakeLanes(step_s=0.05, turn0_s=0.02)
    done = []
    gone = lanes.session("gone", 3, think_s=0.002, end=False)  # no end_session
    stays = lanes.session("stays", 9, think_s=0.002, out=done)
    _join([gone, stays])
    st = lanes.batcher.stats()
    assert st["gang_timeout"] == 1
    assert lanes.steps[-5:] == [["stays"]] * 5
    # the cap is at most a step, so the one token that waited for `gone`
    # took at most two steps; the tokens after it pay nothing
    gaps = [b - a for a, b in zip(done, done[1:])]
    assert max(gaps) < 0.05 * 2 + 0.04
    assert sorted(gaps)[len(gaps) // 2] < 0.05 + 0.03
    assert lanes.batcher._cap_s() <= lanes.batcher._step_s


def test_an_ended_session_is_dropped_from_the_expectation_at_once():
    lanes = FakeLanes(step_s=0.3, turn0_s=1.0)  # the cap is a whole step
    _one_step(lanes, ["ends", "stays"])
    t0 = time.monotonic()
    again = lanes.session("stays", 1, end=False)  # now waits for `ends`
    _until(lambda: len(lanes.batcher._pending) == 1)
    time.sleep(0.02)
    assert lanes.steps[-1] != ["stays"]
    lanes.end("ends")
    _join([again])
    assert lanes.steps[-1] == ["stays"]
    assert time.monotonic() - t0 < 0.3 + 0.02 + 0.15  # not step + cap
    assert lanes.batcher.stats()["gang_timeout"] == 0
    # and from now on nobody waits for it
    t0 = time.monotonic()
    _join([lanes.session("stays", 1, end=False)])
    assert time.monotonic() - t0 < 0.3 + 0.15


def test_a_flusher_whose_entries_are_gone_runs_and_counts_no_step():
    lanes = FakeLanes(step_s=0.0)
    lanes.gate = threading.Event()
    first = lanes.session("first", 1, end=False)
    _until(lambda: lanes.steps == [["first"]])
    got = {}

    def late():
        try:
            got["r"] = lanes.batcher.submit(("late", 0))
        except Exception as e:
            got["r"] = e

    t = threading.Thread(target=late)
    t.start()  # the flusher of the next step, waiting this one out
    _until(lambda: len(lanes.batcher._pending) == 1)
    lanes.end("late")
    lanes.gate.set()
    _join([first, t])
    assert isinstance(got["r"], ValueError)
    assert lanes.steps == [["first"]]
    st = lanes.batcher.stats()
    assert (st["batched_steps"], st["batched_tokens"]) == (1, 1)
    assert st["empty_drains"] == 1
    # the slot is free again: the next entry is served
    _join([lanes.session("next", 1)])
    assert lanes.steps[-1] == ["next"]


@pytest.mark.parametrize("co_possible", [False, True])
def test_a_solo_session_never_waits(co_possible):
    """Alone on the node (`co_possible` false) or alone in decode (the
    other sessions prefilling or idle: nobody was served, nobody is
    expected), an entry goes to the device at once."""
    lanes = FakeLanes(step_s=0.01, turn0_s=5.0, co_possible=lambda: co_possible)
    t0 = time.monotonic()
    _join([lanes.session("solo", 5, end=False)])
    assert time.monotonic() - t0 < 1.0  # the start value of a turn is 5 s
    assert lanes.steps == [["solo"]] * 5
    st = lanes.batcher.stats()
    assert st["gang_timeout"] == 0 and st["gang_full"] == (5 if co_possible else 0)


def test_a_prefilling_session_is_not_waited_for():
    lanes = FakeLanes(step_s=0.3, turn0_s=1.0)
    _one_step(lanes, "ab")
    lanes.batcher.unexpect(lambda p: p[0] == "b")  # b starts a prefill
    t0 = time.monotonic()
    _join([lanes.session("a", 1, end=False)])
    assert time.monotonic() - t0 < 0.3 + 0.15 and lanes.steps[-1] == ["a"]
    # its first decode entry joins whatever step is next: a, served a
    # moment ago, is waited for
    b = lanes.session("b", 1, end=False)
    time.sleep(0.05)
    _join([b, lanes.session("a", 1, end=False)])
    assert lanes.steps[-1] == ["a", "b"]


def test_the_cap_follows_the_turns_it_sees_and_never_passes_a_step():
    lanes = FakeLanes(step_s=0.02, turn0_s=0.1)
    b = lanes.batcher
    assert b._cap_s() == pytest.approx(0.3)  # nothing measured yet: three start values
    _join([lanes.session(s, 30, think_s=0.002) for s in "ab"])
    assert b._step_s == pytest.approx(0.02, abs=0.015)
    assert b._cap_s() <= b._step_s
    # from 100 ms toward what a turn takes, a twentieth of the way a sample
    assert 0.001 < b._turn_s < 0.02
    _join([lanes.session("slow", 1, end=False)])
    before, step = b._turn_s, b._step_s
    time.sleep(0.1)  # a slow turn: counted as a step at most, and half of it at once
    _join([lanes.session("slow", 1, end=False)])
    assert b._turn_s == pytest.approx(before + 0.5 * (step - before))


def test_a_prefill_cutting_in_delays_the_step_and_joins_late_arrivals():
    """The formation wait holds no lock: someone else (a prefill) can take
    the device meanwhile, and what arrives until the flusher gets it rides
    the same step."""
    lanes = FakeLanes(step_s=0.0)
    lanes.dev.acquire()  # a prefill has the device
    a = lanes.session("a", 1, end=False)
    _until(lambda: len(lanes.batcher._pending) == 1)
    time.sleep(0.01)  # a's formation is over (nobody to wait for)
    b = lanes.session("b", 1, end=False)
    _until(lambda: len(lanes.batcher._pending) == 2)
    lanes.dev.release()
    _join([a, b])
    assert lanes.steps == [["a", "b"]]


def test_lock_wait_and_batch_wait_split_an_entrys_wait():
    """lock_wait: the device was held by a step that does not serve the
    entry; batch_wait: the rest of submit -> taken. They add up to the
    whole wait and the counter sums the second."""
    rec = tracelib.SpanRecorder("w")
    lanes = FakeLanes(step_s=0.0, turn0_s=0.1)  # cap 0.3 s until a step is measured
    lanes.batcher.tracer = rec
    lanes.gate = threading.Event()
    a = lanes.session("a", 1, end=False)
    _until(lambda: lanes.steps == [["a"]])
    t_b = tracelib.now()
    b = lanes.session("b", 1, end=False)  # arrives under a's step
    _until(lambda: len(lanes.batcher._pending) == 1)
    time.sleep(0.05)
    lanes.gate.set()  # a's step ends 50 ms on; b now waits for a
    _until(lambda: lanes.batcher._t_freed > 0)
    t_free = lanes.batcher._t_freed
    time.sleep(0.03)
    a2 = lanes.session("a", 1, end=False)
    _join([a, b, a2])
    assert lanes.steps == [["a"], ["a", "b"]]
    spans = rec.spans()
    locks = sorted((s for s in spans if s["name"] == "lock_wait"), key=lambda s: s["t0"])
    waits = sorted((s for s in spans if s["name"] == "batch_wait"), key=lambda s: s["t0"])
    assert len(locks) == 3 and all(s["attrs"] == {"kind": "decode"} for s in locks)
    # a's first entry and its second waited for nothing and nobody
    for i in (0, 2):
        assert locks[i]["t1"] - locks[i]["t0"] < 0.01
        assert waits[i]["t1"] - waits[i]["t0"] < 0.01
    # b: submit -> a's step returned, then -> taken with a's next entry
    lock_b, wait_b = locks[1], waits[1]
    assert [w["attrs"]["flusher"] for w in waits] == [1, 1, 0]
    assert lock_b["t0"] == pytest.approx(t_b, abs=0.01)
    assert lock_b["t1"] == pytest.approx(t_free, abs=0.005)
    assert wait_b["t0"] == lock_b["t1"]
    assert 0.03 <= wait_b["t1"] - wait_b["t0"] < 0.2
    st = lanes.batcher.stats()
    assert st["queue_waits"] == 3
    assert st["queue_wait_ms_sum"] == pytest.approx(
        sum((w["t1"] - w["t0"]) * 1e3 for w in waits), abs=0.01)


def test_a_failing_step_fails_its_entries_and_frees_the_window():
    lanes = FakeLanes(step_s=0.0)
    boom = RuntimeError("device fell over")

    def run(entries):
        with lanes.dev:
            batch = lanes.batcher.drain_pending()
            if any(e.payload[0] == "bad" for e in batch):
                raise boom
            for e in batch:
                e.result = ("ok", e.payload)

    lanes.batcher._run_batch = run
    with pytest.raises(RuntimeError, match="fell over"):
        lanes.batcher.submit(("bad", 0))
    assert lanes.batcher.submit(("good", 0)) == ("ok", ("good", 0))
    # a callback that dies before it drains leaves no entry hanging either
    lanes.batcher._run_batch = lambda entries: (_ for _ in ()).throw(boom)
    with pytest.raises(RuntimeError, match="fell over"):
        lanes.batcher.submit(("bad", 1))
    lanes.batcher._run_batch = run
    assert lanes.batcher.submit(("good", 1)) == ("ok", ("good", 1))


def test_expect_needs_the_callback_to_drain():
    with pytest.raises(ValueError, match="swap_in_run"):
        WindowedBatcher(0.003, lambda e: None, co_possible=lambda: True,
                        expect=lambda p: p[0])


def test_many_sessions_with_a_short_switch_interval_lose_no_entry():
    """More threads than cores, sessions that come, end and come back under
    other names: every entry is served once, by one step, to its own
    submitter, and the counters are token-true."""
    import sys

    lanes = FakeLanes(step_s=0.0005, turn0_s=0.001)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            lanes.session(f"s{i}", 40 + i, think_s=0.0002 * (i % 3), end=i % 2 == 0)
            for i in range(24)
        ]
        _join(threads)
    finally:
        sys.setswitchinterval(old)
    total = sum(40 + i for i in range(24))
    assert sum(len(s) for s in lanes.steps) == total
    assert all(len(set(s)) == len(s) for s in lanes.steps)  # one entry a session a step
    st = lanes.batcher.stats()
    assert st["batched_tokens"] == total == st["queue_waits"]
    assert st["batched_steps"] == len(lanes.steps)
    assert not lanes.batcher._pending and not lanes.batcher._flusher_active
