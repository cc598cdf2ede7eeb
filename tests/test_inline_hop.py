"""A decode hop that holds no thread (runtime/node.py `_process_hop`,
runtime/batch_executor.py `begin_hop` / `end_hop`): admitted on the event
loop, handed to the window, answered by the drain that served it.

Through the stock node on CPU, eight sessions side by side: what a session
streams is what the pooled path streams, line for line (the pooled path is
forced through the executor's capability: `begin_hop` taken away); a hop on
the new path leaves every span the readers of the host turn read
(benchmark/turns.py), with the right parents and abutting parts; the
scheduler counts it as it counts a pooled one; a hop that rides a step
waits on a worker and the loop stays free meanwhile; a session that ends,
is evicted or exported in the middle of a hop frees its lane once."""

import asyncio
import contextlib
import importlib.util
import json
import os
import sys
import threading
import time

import aiohttp
import jax
import pytest

from inferd_tpu.config import TINY
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.models import qwen3
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.batch_executor import BatchedExecutor
from inferd_tpu.runtime.node import Node, NodeInfo
from test_step_ahead import Session

HERE = os.path.dirname(os.path.abspath(__file__))
from conftest import port_block  # noqa: E402

PORTS, HOST = port_block(__file__), "127.0.0.1"
SESSIONS, NEW, TOP = 8, 10, 3
GREEDY = {"temperature": 0.0, "top_k": 0, "top_p": 1.0}
SAMPLED = {"temperature": 0.9, "top_k": 12, "top_p": 1.0}
LIMIT_S = 300


def _prompt(i):
    return [3 + i, 7 + 2 * i, 11 + i, 19, 23 + 3 * i, 29]


@pytest.fixture(scope="module")
def parts_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("inline-hop")
    split_and_save(qwen3.init_params(TINY, jax.random.PRNGKey(0)), TINY,
                   Manifest.even_split("tiny", 1), str(d))
    return str(d)


async def _node(idx, parts_dir, lanes=SESSIONS):
    info = NodeInfo(name=f"ih{idx}", host=HOST, port=PORTS.http(idx), stage=0,
                    num_stages=1, capacity=8, model_name="tiny")
    dht = SwarmDHT(info.node_id, PORTS.gossip(idx), bootstrap=[], host=HOST,
                   gossip_period_s=0.05, ttl_s=5.0)
    node = Node(info, TINY, parts_dir, dht, backend="qwen3", max_len=64,
                rebalance_period_s=600.0, batch_lanes=lanes)
    await node.start()
    for _ in range(4800):  # the warm-up compiles what the hops run
        if any(e["type"].startswith("executor.warmup_") for e in node.journal.events()):
            return node
        await asyncio.sleep(0.05)
    raise TimeoutError("no warm-up")


async def _stream(http, port, i, sampling):
    """One streamed /generate: its lines as the socket gave them."""
    body = {"prompt_ids": _prompt(i), "max_new_tokens": NEW, "stream": True, "seed": 100 + i,
            "sampling": sampling, "logprobs": True, "top_logprobs": TOP}
    async with http.post(f"http://{HOST}:{port}/generate", data=wire.pack(body)) as r:
        assert r.status == 200
        return [json.loads(raw) async for raw in r.content]


async def _round(node, sampling):
    async with aiohttp.ClientSession() as http:
        return await asyncio.gather(*(
            _stream(http, node.info.port, i, sampling) for i in range(SESSIONS)))


async def _serve(parts_dir):
    node = await _node(0, parts_dir)
    ex = node.executor
    try:
        run = {"stats": [ex.stats()]}
        for how in ("inline", "pooled"):
            if how == "pooled":
                ex.begin_hop = None  # the capability taken away: every hop takes a worker
            t0 = tracelib.now()
            for name, sampling in (("greedy", GREEDY), ("sampled", SAMPLED)):
                run[how, name] = await _round(node, sampling)
            run["stats"].append(ex.stats())
            run[how, "window"] = (t0, tracelib.now())
        run["spans"] = node.tracer.spans()
        return run
    finally:
        await node.stop()


@pytest.fixture(scope="module")
def served(parts_dir):
    return asyncio.run(asyncio.wait_for(_serve(parts_dir), LIMIT_S))


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
def test_the_stream_is_the_pooled_paths_line_for_line(served, sampling):
    inline, pooled = served["inline", sampling], served["pooled", sampling]
    for mine, theirs in zip(inline, pooled):
        assert [set(line) for line in mine] == [{"t", "lp", "top"}] * NEW + [
            {"done", "ids", "logprobs", "top_logprobs"}]
        assert mine == theirs  # tokens, log-probabilities, top lists, the last line
    assert len({tuple(lines[-1]["ids"]) for lines in inline}) > 1  # eight prompts, not one stream


def test_the_counters_say_which_way_the_hops_went(served):
    s0, s1, s2 = served["stats"]
    hops = 2 * SESSIONS * (NEW - 1)  # two rounds; a request's first token is its prefill's
    inline, pooled = s1["hops_inline"] - s0["hops_inline"], s1["hops_pooled"] - s0["hops_pooled"]
    assert inline + pooled == hops
    # a request's first hop may ride the step it arrived under, and waits on a worker
    assert pooled <= 2 * SESSIONS and inline >= hops - 2 * SESSIONS
    assert pooled == s1["rides"] - s0["rides"]
    assert s2["hops_inline"] == s1["hops_inline"]
    assert s2["hops_pooled"] - s1["hops_pooled"] == hops


def _reader(metric):
    bench = os.path.join(os.path.dirname(HERE), "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            metric.replace(".", "_"), os.path.join(bench, "layer_metrics", f"{metric}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        import turns
    finally:
        sys.path.remove(bench)
    return mod.read, turns


@pytest.mark.parametrize("how", ["inline", "pooled"])
def test_a_hop_leaves_every_span_of_the_host_turn(served, how):
    """benchmark/turns.py's hop: `step` <- `forward` <- `queue`, `resume`,
    `compute` <- `lock_wait` / `batch_wait`, `deliver`; the parts of a
    session's way from one step to the next abut; the instrument's own check
    (`window.turn_unaccounted_ms_p50`) reads 0 on either path."""
    read, turns = _reader("window.turn_unaccounted_ms_p50")
    w0, w1 = served[how, "window"]
    run = {"spans": served["spans"], "wall0": w0, "wall1": w1}
    hops = turns.hops(run)
    assert len(hops) == 2 * SESSIONS * (NEW - 1)  # none lacks a span
    for h in hops:
        c, f = h["compute"], h["forward"]
        assert h["queue"]["parent"] == h["resume"]["parent"] == c["parent"] == f["span"]
        assert h["deliver"]["parent"] == c["span"] and f["parent"] == h["step"]["span"]
        assert f["t0"] <= h["queue"]["t0"] <= h["queue"]["t1"] == c["t0"] <= h["submit"]
        assert h["deliver"]["t0"] <= h["deliver"]["t1"] == c["t1"] == h["resume"]["t0"]
        assert h["resume"]["t1"] <= f["t1"] <= h["step"]["t1"]
        assert c["attrs"]["ms"] == pytest.approx((c["t1"] - c["t0"]) * 1e3, abs=2e-3)
    chains, _ = turns.last_chains(run)
    assert len(chains) > NEW
    for _turn, parts in chains:
        assert [p[0] for p in parts] == [
            "deliver", "resume", "reply", "between", "enter", "queue", "admit", "wait"]
        for a, b in zip(parts, parts[1:]):
            assert a[2] == b[1] and a[1] <= a[2]
    assert read(run) == pytest.approx(0.0, abs=1e-6)
    if how == "inline":  # the hand-over to the window, not to a pool: microseconds
        queues = sorted(h["queue"]["t1"] - h["queue"]["t0"] for h in hops)
        assert queues[len(queues) // 2] < 0.5e-3


# -- on the node's loop, hop by hop ---------------------------------------------


def _hops(ex, n, **kw):
    """`n` sessions of `ex`, prefilled, each about to make its first decode hop."""
    return [Session(ex, f"s{i}", prompt=_prompt(i), new=NEW, **kw) for i in range(n)]


@contextlib.asynccontextmanager
async def _device_held(ex):
    """The device is somebody else's (a prefill's): no drain runs inside."""
    assert ex._dev_lock.acquire(timeout=30)
    try:
        yield
    finally:
        ex._dev_lock.release()


async def _pending(ex, n):
    for _ in range(5000):
        if len(ex._batcher._pending) == n:
            return
        await asyncio.sleep(0.001)
    raise TimeoutError(f"{len(ex._batcher._pending)} entries pending, not {n}")


async def _hop(node, s, tin=None):
    (result, ms, w0, w1, ctx), t_res = await node._process_hop(
        node.executor, s.sid, s.payload(), tin)
    s.pos += 1
    s.out.append(int(result["tokens"][0][0]))
    s.chain = {"key": result["key"]}
    return result


@pytest.mark.asyncio
async def test_the_scheduler_counts_an_inline_hop_as_a_pooled_one(parts_dir):
    """`inflight` holds the hop from its admission to its answer and `ticks`
    moves by two, whichever way the hop goes (tests/test_load_tick.py: what
    a peer reads of this node's load is built from them)."""
    node = await _node(1, parts_dir, lanes=4)
    ex, sched = node.executor, node.scheduler
    try:
        sessions = await asyncio.get_running_loop().run_in_executor(None, _hops, ex, 3)
        for s in sessions:  # each one's first hop rides: after it the rows run ahead
            await _hop(node, s)
        for take_away in (False, True):
            if take_away:
                ex.begin_hop = None
            ticks, pooled, inline = sched.ticks, ex.hops_pooled, ex.hops_inline
            async with _device_held(ex):  # no drain: the hops wait in the window
                tasks = [asyncio.ensure_future(_hop(node, s)) for s in sessions]
                await _pending(ex, 3)
                assert sched.inflight == 3 and sched.ticks == ticks + 3
            await asyncio.gather(*tasks)
            assert sched.inflight == 0 and sched.ticks == ticks + 6
            moved = (ex.hops_inline - inline, ex.hops_pooled - pooled)
            assert moved == ((0, 3) if take_away else (3, 0))
        # a hop that raises leaves the count as it found it, either way
        with pytest.raises(ValueError, match="out-of-order"):
            await node._process_hop(ex, "s0", sessions[0].payload(pos=40), None)
        del ex.begin_hop
        with pytest.raises(ValueError, match="out-of-order"):
            await node._process_hop(ex, "s1", sessions[1].payload(pos=40), None)
        assert sched.inflight == 0 and ex._inflight == {}
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_riders_wait_is_a_workers_and_the_loop_stays_free(parts_dir):
    """A session's first hop is answered with a step to ride: `end_hop`
    says so on the loop, and the wait runs on the pool. While it is held
    there the loop completes another session's hop."""
    node = await _node(2, parts_dir, lanes=4)
    ex = node.executor
    try:
        old, new = await asyncio.get_running_loop().run_in_executor(None, _hops, ex, 2)
        await _hop(node, old)
        await _hop(node, old)  # `old` is ahead: its hops are claimed, inline
        ridden, held, threads = ex._ridden, threading.Event(), []

        def slow_ride(step, lane):
            threads.append(threading.current_thread().name)
            assert held.wait(timeout=60)
            return ridden(step, lane)

        ex._ridden = slow_ride
        pooled, inline = ex.hops_pooled, ex.hops_inline
        async with _device_held(ex):  # one drain takes both: `old` claims its row, `new` rides
            ahead = asyncio.ensure_future(_hop(node, old))
            rider = asyncio.ensure_future(_hop(node, new))
            await _pending(ex, 2)
        await asyncio.wait_for(ahead, 30)
        for _ in range(5000):
            if threads:
                break
            await asyncio.sleep(0.001)
        assert threads and threads[0].startswith("stage") and not rider.done()
        for _ in range(3):
            await asyncio.wait_for(_hop(node, old), 30)  # the loop is free, the window too
        assert not rider.done() and ex.hops_inline - inline == 4
        held.set()
        await asyncio.wait_for(rider, 30)
        assert ex.hops_pooled - pooled == 1
        # the streams are those of two sessions served alone
        alone = await asyncio.get_running_loop().run_in_executor(
            None, lambda: [Session(ex, f"a{i}", prompt=_prompt(i), new=len(s.out)).run().out
                           for i, s in enumerate((old, new))])
        assert alone == [old.out, new.out]
    finally:
        held.set()
        await node.stop()


@pytest.mark.asyncio
async def test_a_hop_whose_caller_went_away_is_ended_by_a_worker(parts_dir):
    """The hop's coroutine is cancelled while its entry waits in the window
    (the caller hung up): the entry is answered all the same, and the hop
    is ended then as `process` would have ended it: the session leaves
    flight, the scheduler's count returns, the lane serves on."""
    node = await _node(3, parts_dir, lanes=2)
    ex, sched = node.executor, node.scheduler
    try:
        (s,) = await asyncio.get_running_loop().run_in_executor(None, _hops, ex, 1)
        await _hop(node, s)
        async with _device_held(ex):
            task = asyncio.ensure_future(node._process_hop(ex, s.sid, s.payload(), None))
            await _pending(ex, 1)
            assert ex._inflight == {s.sid: 1} and sched.inflight == 1
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            assert sched.inflight == 0 and ex._inflight == {s.sid: 1}  # the lane is still the hop's
        for _ in range(5000):
            if not ex._inflight:
                break
            await asyncio.sleep(0.001)
        assert ex._inflight == {} and not ex._batcher._threadless
        # the answer nobody took moved the lane on by a token: the session restarts, and serves
        again = await asyncio.get_running_loop().run_in_executor(
            None, lambda: Session(ex, s.sid, prompt=_prompt(0), new=4).run().out)
        assert len(again) == 4
    finally:
        await node.stop()


# -- a session that goes in the middle of a hop (the executor alone) -------------


@pytest.fixture(scope="module")
def params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


def _begun(ex, s):
    """`s`'s next hop, begun as the loop begins it; what is handed back."""
    handed = []
    hop = ex.begin_hop(s.sid, s.payload(), handed.extend)
    assert hop is not None and ex._inflight == {s.sid: 1}
    return hop, handed


def _wait(cond, timeout=30.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end
        time.sleep(0.001)


@pytest.mark.parametrize("how", ["ended", "evicted", "exported"])
def test_a_session_that_goes_mid_hop_frees_its_lane_once(params, how):
    ex = BatchedExecutor(TINY, params, lanes=2, max_len=64)
    s = Session(ex, "s", new=NEW).run(2)
    lane = ex._sessions["s"]
    with ex._dev_lock:  # the hop waits in the window
        hop, handed = _begun(ex, s)
        if how == "ended":
            ex.end_session("s")
        elif how == "evicted":
            with ex._mu:
                ex._drop("s")  # what `_admit_lane` does to its victim
    if how == "exported":
        _wait(lambda: handed)
        assert ex.export_sessions(only="s")  # reads the lane: the row run ahead is dropped
        assert ex.end_hop(hop, block=False)["tokens"]
        ex.end_session("s")
    else:
        # invalidated in the window: handed back at once, with the error a
        # blocked thread gets; the lane waits for the hop to end
        assert handed == [hop.entry]
        assert ex._dying == {lane: "s"} and lane not in ex.engine.free
        with pytest.raises(ValueError, match="ended mid-request"):
            ex.end_hop(hop, block=False)
    assert ex._inflight == {} and ex._dying == {}
    assert sorted(ex.engine.free) == [0, 1] and "s" not in ex._sessions
    # and the lanes serve again
    assert Session(ex, "t", new=4).run().out == Session(ex, "u", new=4).run().out


def test_begin_hop_offers_only_what_the_loop_may_run(params):
    ex = BatchedExecutor(TINY, params, lanes=2, max_len=64)
    s = Session(ex, "s", new=NEW).run(1)
    never = lambda entries: pytest.fail("nothing was submitted")  # noqa: E731
    prefill = {"tokens": [[1, 2, 3]], "start_pos": 0, "real_len": 3}
    raw = {k: v for k, v in s.payload().items() if k != "sampling"}  # answered with logits: rides
    for payload in (prefill, raw, {**s.payload(), "decode_steps": 4},
                    {**s.payload(), "adapter": "ten0"}):
        assert ex.begin_hop("s", payload, never) is None
    assert ex._inflight == {} and ex.hops_inline == ex.hops_pooled - 1 == 0
    # the session table held for long (an export reads a lane under it): a worker's hop
    ex._mu.acquire()
    try:
        t0 = time.monotonic()
        assert ex.begin_hop("s", s.payload(), never) is None
        assert time.monotonic() - t0 < 0.5
    finally:
        ex._mu.release()
    # what `process` refuses, `begin_hop` refuses with the same error
    for bad, err in (({**s.payload(), "start_pos": 40}, ValueError),
                     ({**s.payload(), "start_pos": 64}, BufferError)):
        with pytest.raises(err) as loop_side:
            ex.begin_hop("s", bad, never)
        with pytest.raises(err) as thread_side:
            ex.process("s", bad)
        assert str(loop_side.value) == str(thread_side.value)
    assert ex._inflight == {}
    s.run()  # and the session goes on
    assert len(s.out) == NEW


def test_a_paged_or_speculating_executor_offers_nothing(params):
    paged = BatchedExecutor(TINY, params, lanes=2, max_len=64, block_size=16, kv_blocks=16)
    s = Session(paged, "s", new=4).run(1)
    assert paged.begin_hop("s", s.payload(), lambda entries: None) is None
    assert s.run().out and paged.hops_inline == 0 and paged.hops_pooled == 3
