"""utils.lockwatch: the dynamic half of the concurrency plane.

Covers the order-recording lock proxies (strict raise vs journal mode),
the ticketed FairDeviceLock's starvation bound, the event-loop stall
detector (J009's runtime twin, seeded via the chaos `block_ms` fault),
and the <=1%-of-compute overhead budget perf.gate holds the sanitizer
to. tests/conftest.py instruments strict mode suite-wide; the fixture
here isolates each test's state and restores the suite's.
"""

import asyncio
import threading
import time

import pytest

from inferd_tpu.utils import lockwatch
from inferd_tpu.utils.chaos import Chaos
from inferd_tpu.utils.lockwatch import (
    LOCK_ORDER,
    FairDeviceLock,
    LockOrderError,
    LoopStallDetector,
    WatchedLock,
)

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)


@pytest.fixture
def lw(monkeypatch):
    """Pristine lockwatch state; restores the suite's strict instrument
    (conftest) afterwards."""
    monkeypatch.delenv("INFERD_LOCKWATCH", raising=False)
    prev = (
        lockwatch._state.enabled,
        lockwatch._state.strict,
        lockwatch._state.on_event,
    )
    lockwatch.reset()
    yield lockwatch
    lockwatch.reset()
    (
        lockwatch._state.enabled,
        lockwatch._state.strict,
        lockwatch._state.on_event,
    ) = prev


# ------------------------------------------------------ construction seam


def test_make_lock_plain_when_disabled(lw):
    lock = lw.make_lock("dev")
    assert not isinstance(lock, WatchedLock)
    lock.acquire()
    lock.release()
    assert lw.stats()["checks"] == 0  # disabled = zero bookkeeping


def test_make_lock_watched_when_instrumented(lw):
    lw.instrument()
    assert isinstance(lw.make_lock("dev"), WatchedLock)
    # an unranked name cannot be order-checked: plain lock, no guessing
    assert not isinstance(lw.make_lock("not_a_ranked_lock"), WatchedLock)


def test_env_kill_switch_beats_instrument(lw, monkeypatch):
    lw.instrument(strict=True)
    monkeypatch.setenv("INFERD_LOCKWATCH", "0")
    assert not lw.watching()
    assert not isinstance(lw.make_lock("dev"), WatchedLock)


# ----------------------------------------------------- inversion checking


def test_canonical_order_passes_strict(lw):
    lw.instrument(strict=True)
    locks = [lw.make_lock(n) for n in LOCK_ORDER]
    for lock in locks:
        lock.acquire()
    assert lw.held_stack() == list(LOCK_ORDER)
    for lock in reversed(locks):
        lock.release()
    assert lw.held_stack() == []
    assert lw.stats()["inversions"] == 0


def test_inversion_raises_in_strict_mode(lw):
    # the seeded inversion's DYNAMIC catch (its static twin is
    # test_analysis.test_j007_inversion_fires)
    lw.instrument(strict=True)
    dev, mu = lw.make_lock("dev"), lw.make_lock("mu")
    with mu:
        with pytest.raises(LockOrderError, match="canonical order"):
            dev.acquire()
    # the refused acquire left no phantom entry behind
    assert lw.held_stack() == []
    with dev:
        with mu:
            pass  # same pair, canonical direction: fine


def test_inversion_journals_once_per_pair(lw):
    events = []
    lw.instrument(journal=lambda et, **kw: events.append((et, kw)))
    dev, mu = lw.make_lock("dev"), lw.make_lock("mu")
    for _ in range(3):
        with mu:
            with dev:
                pass
    assert lw.stats()["inversions"] == 3
    assert len(events) == 1  # deduped per (held, acquiring) pair
    et, kw = events[0]
    assert et == "lock.inversion"
    assert kw["held"] == "mu" and kw["acquiring"] == "dev"


def test_try_acquire_is_exempt(lw):
    lw.instrument(strict=True)
    dev, mu = lw.make_lock("dev"), lw.make_lock("mu")
    with mu:
        # a try-acquire cannot participate in a deadlock cycle
        assert dev.acquire(blocking=False)
        dev.release()
    assert lw.stats()["inversions"] == 0


def test_journal_hook_failure_is_swallowed(lw):
    def bad_hook(et, **kw):
        raise RuntimeError("observability must not add failure modes")

    lw.instrument(journal=bad_hook)
    dev, mu = lw.make_lock("dev"), lw.make_lock("mu")
    with mu:
        with dev:
            pass  # no raise: the hook error is contained


# ------------------------------------------------------- FairDeviceLock


def test_fair_lock_release_cannot_barge_past_waiter(lw):
    """The chunked-prefill starvation shape, deterministically: once a
    flusher is queued, the releasing chunk loop CANNOT re-acquire ahead
    of it (threading.Lock makes no such promise — that race is why the
    executors' inter-chunk sleep existed)."""
    lock = FairDeviceLock()
    assert lock.acquire()
    got = threading.Event()

    def flusher():
        lock.acquire()
        got.set()
        lock.release()

    t = threading.Thread(target=flusher)
    t.start()
    while lock._next < 2:  # flusher's ticket is queued
        time.sleep(0.001)
    lock.release()
    # the ticket at the head of the queue is the flusher's, not ours
    assert lock.acquire(blocking=False) is False
    assert got.wait(2.0)
    t.join()
    assert lock.acquire(blocking=False)  # queue drained: ours again
    lock.release()


def test_fair_lock_flusher_not_starved_under_chunk_loop(lw):
    """Contention test: a decode flusher arriving mid-prefill is served
    within ONE further chunk — the FIFO bound the yield-based
    workaround could only approximate."""
    lock = FairDeviceLock()
    chunks_done = 0
    flusher_done = threading.Event()
    granted_after = None

    def chunk_loop():
        nonlocal chunks_done
        for _ in range(2000):
            with lock:
                time.sleep(0.0002)  # one chunk dispatch
            chunks_done += 1
            if flusher_done.is_set():
                return

    def flusher():
        nonlocal granted_after
        queued_at = chunks_done
        with lock:
            granted_after = chunks_done - queued_at
        flusher_done.set()

    ct = threading.Thread(target=chunk_loop)
    ct.start()
    while chunks_done < 3:
        time.sleep(0.001)
    ft = threading.Thread(target=flusher)
    ft.start()
    assert flusher_done.wait(5.0), "flusher starved behind the chunk loop"
    ct.join()
    ft.join()
    # at most the in-flight chunk plus the one that queued ahead of us
    assert granted_after is not None and granted_after <= 2


def test_fair_lock_timeout_abandons_ticket(lw):
    lock = FairDeviceLock()
    lock.acquire()
    t0 = time.perf_counter()
    assert lock.acquire(timeout=0.05) is False
    assert time.perf_counter() - t0 < 1.0
    lock.release()
    # the abandoned ticket must not wedge the grant chain
    assert lock.acquire(blocking=False)
    lock.release()
    assert not lock.locked()


def test_fair_devlock_composes_with_watching(lw, monkeypatch):
    lw.instrument(strict=True)
    lock = lw.make_lock("dev", fair=True)
    assert isinstance(lock, WatchedLock)
    assert lw.is_fair(lock)  # the chunk-yield site sees through the proxy
    assert not lw.is_fair(lw.make_lock("dev"))
    with lock:
        assert lw.held_stack() == ["dev"]
    monkeypatch.setenv("INFERD_FAIR_DEVLOCK", "1")
    assert lw.fair_devlock_enabled()
    monkeypatch.delenv("INFERD_FAIR_DEVLOCK")
    assert not lw.fair_devlock_enabled()


# --------------------------------------------------- loop-stall detector


async def test_stall_detector_catches_blocking_sleep(lw):
    # the seeded blocking-async handler's DYNAMIC catch (static twin:
    # test_analysis.test_j009_sync_lock_in_async_handler)
    events = []
    det = LoopStallDetector(
        stall_ms=50.0, interval_ms=10.0,
        on_event=lambda et, **kw: events.append((et, kw)),
    ).start()
    await asyncio.sleep(0.03)
    time.sleep(0.12)  # jaxlint: disable=J005 -- the seeded loop stall this test exists to catch
    await asyncio.sleep(0.05)
    det.stop()
    assert det.stalls and max(det.stalls) >= 50.0
    et, kw = events[0]
    assert et == "loop.stall" and kw["blocked_ms"] >= 50.0


async def test_stall_detector_quiet_loop_stays_silent(lw):
    det = LoopStallDetector(stall_ms=50.0, interval_ms=10.0).start()
    for _ in range(5):
        await asyncio.sleep(0.02)  # yielding work never stalls the loop
    det.stop()
    assert det.stalls == []


async def test_chaos_block_ms_is_detectable(lw):
    """utils.chaos `block_ms` holds the event loop synchronously — the
    injectable J009 violation — and the detector sees it."""
    chaos = Chaos.parse("block_ms=120")
    assert chaos.block_ms == 120.0
    det = LoopStallDetector(stall_ms=50.0, interval_ms=10.0).start()
    await asyncio.sleep(0.03)
    await chaos.before_forward()
    await asyncio.sleep(0.05)
    det.stop()
    assert det.stalls and max(det.stalls) >= 50.0


async def test_chaos_delay_ms_yields_no_stall(lw):
    # the async twin fault must NOT trip the detector: it awaits
    chaos = Chaos.parse("delay_ms=120")
    det = LoopStallDetector(stall_ms=50.0, interval_ms=10.0).start()
    await asyncio.sleep(0.03)
    await chaos.before_forward()
    await asyncio.sleep(0.05)
    det.stop()
    assert det.stalls == []


# ------------------------------------------------------- overhead budget


def test_overhead_within_gate_budget(lw):
    from inferd_tpu.perf import gate as gatelib

    lw.instrument()
    lock = lw.make_lock("dev")
    n = 20000
    for _ in range(n):
        lock.acquire()
        lock.release()
    ov = lw.stats()["overhead_ms"]
    assert lw.stats()["checks"] == n
    # perf.gate's bar: sanitizer cost <= 1% of compute. One check per
    # device step against a conservative 1 ms step means the per-check
    # cost must stay under 10 us.
    per_check_ms = ov / n
    assert per_check_ms < 0.01, f"{per_check_ms * 1e3:.2f}us per check"
    stats = {
        "gauges": {"lockwatch.overhead_ms": ov},
        "counters": {},
        "histograms": {"stage.compute_ms": {"count": n, "mean_ms": 1.0}},
    }
    assert gatelib.check_span_overhead(stats) == []
    # and the gate actually watches the gauge: blow the budget, it fires
    stats["gauges"]["lockwatch.overhead_ms"] = 0.02 * n * 1.0
    found = gatelib.check_span_overhead(stats)
    assert any("lock-order-sanitizer" in f.message for f in found)


def test_suite_runs_instrumented_with_zero_inversions():
    """tier-1's standing invariant: conftest instruments strict mode
    suite-wide (unless INFERD_LOCKWATCH=0), so by the time this test
    runs, every executor/node lock constructed by earlier tests was
    order-checked — and nothing raised or journaled an inversion."""
    import os

    if os.environ.get("INFERD_LOCKWATCH", "").strip().lower() in (
        "0", "off", "false", "no"
    ):
        pytest.skip("lockwatch killed via INFERD_LOCKWATCH")
    assert lockwatch.watching() and lockwatch.strict()
    assert lockwatch.stats()["inversions"] == 0


# ------------------------------------- the order as it stands, both halves


def test_every_named_lock_is_ranked_and_the_package_nests_none_backwards():
    """The lint's half: the node's capture lock left the order with its
    one other holder, so no name the lint resolves and no lock the
    runtime constructs may still point outside `LOCK_ORDER` (an unranked
    name is silently unwatched), and J007 over the package finds nothing
    with no baseline to lean on."""
    import pathlib
    import re

    from inferd_tpu.analysis import concurrency
    from inferd_tpu.analysis.engine import check_paths
    from inferd_tpu.analysis.rules import ALL_RULES

    assert "capture" not in LOCK_ORDER
    resolved = set(concurrency._ATTR_DEFAULT.values()) | set(concurrency._CLASS_ATTR.values())
    assert resolved <= set(LOCK_ORDER)
    pkg = pathlib.Path(lockwatch.__file__).resolve().parents[1]
    built = set()
    for f in pkg.rglob("*.py"):
        if f.name != "lockwatch.py":
            built |= set(re.findall(r"make_lock\(\s*\"(\w+)\"", f.read_text()))
    assert built and built <= set(LOCK_ORDER)
    j007 = [r for r in ALL_RULES if r.id == "J007"]
    assert len(j007) == 1
    assert check_paths([str(pkg)], rules=j007, rel_to=str(pkg.parent)) == []


@pytest.mark.asyncio
async def test_profile_window_under_traffic_takes_no_lock_out_of_order(tmp_path):
    """The sanitizer's half, strict as the suite runs it: a lane node
    serves two generations side by side inside an open /profile window
    (30 s, so no host is too slow for it; the shutdown closes it). Every
    named lock it took was watched; an acquisition against the order
    would have failed its request."""
    import os

    import aiohttp
    import jax

    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.config import TINY, SamplingConfig
    from inferd_tpu.control.dht import SwarmDHT
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, split_and_save
    from inferd_tpu.runtime import wire
    from inferd_tpu.runtime.node import Node, NodeInfo

    if os.environ.get("INFERD_LOCKWATCH", "").strip().lower() in (
        "0", "off", "false", "no"
    ):
        pytest.skip("lockwatch killed via INFERD_LOCKWATCH")
    assert lockwatch.watching() and lockwatch.strict()
    host, port = "127.0.0.1", PORTS.http()
    split_and_save(qwen3.init_params(TINY, jax.random.PRNGKey(0)), TINY,
                   Manifest.even_split("tiny", 1), str(tmp_path / "parts"))
    info = NodeInfo(name="lw", host=host, port=port, stage=0, num_stages=1,
                    capacity=8, model_name="tiny")
    dht = SwarmDHT(info.node_id, PORTS.gossip(), bootstrap=[], host=host,
                   gossip_period_s=0.05, ttl_s=5.0)
    node = Node(info, TINY, str(tmp_path / "parts"), dht, backend="qwen3", max_len=64,
                rebalance_period_s=600.0, batch_lanes=2, enable_profiling=True)
    node.profiler.base_dir = str(tmp_path / "profiles")
    before = lockwatch.stats()
    await node.start()
    try:
        ex = node.executor
        assert isinstance(ex._dev_lock, WatchedLock) and isinstance(ex._mu, WatchedLock)
        async with aiohttp.ClientSession() as http:
            body = wire.pack({"action": "window", "seconds": 30.0, "capture_id": "lw"})
            async with http.post(f"http://{host}:{port}/profile", data=body) as r:
                assert r.status == 200
        assert node.profiler.active_dir is not None  # the window is open
        async with SwarmClient([(host, port)], sampling=SamplingConfig(temperature=0.0)) as c:
            out = await asyncio.gather(*(
                c.generate_server_side_stream(p, lambda t: None, 12)
                for p in ([3, 7, 11, 19], [5, 13, 17, 41])
            ))
        assert all(len(o) == 12 for o in out)
        assert node.profiler.active_dir is not None  # and stayed open throughout
    finally:
        await node.stop()
    assert node.profiler.active_dir is None
    after = lockwatch.stats()
    assert after["checks"] > before["checks"]
    assert after["inversions"] == before["inversions"]
