"""The node's load tick: a hop entering or leaving the scheduler moves
`scheduler.inflight` and nothing else, and the gossip record is rebuilt
when somebody is about to READ it (a gossip send, a local replica pick),
not when the count moves. Held here: hops build no record; a send carries
the load at that send; a local reader sees the exact `inflight`; an urgent
announce still gossips at once; a node with every optional plane off
gossips the bytes it always did; a hop that raises leaves no load behind."""

import asyncio
import importlib.util
import json
import os
import sys
import threading

import msgpack
import pytest

from inferd_tpu.config import TINY
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.control.path_finder import min_load_node
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.node import Node, NodeInfo, TaskScheduler

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)
HERE = os.path.dirname(os.path.abspath(__file__))


def _mk_node(idx, *, bootstrap=(), gossip_period_s=600.0):
    """A one-stage counter node whose own clocks never fire inside a test
    (gossip and telemetry periods of ten minutes): every record build a
    test counts is one the test asked for."""
    info = NodeInfo(
        name=f"n{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=0, num_stages=1, capacity=4, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx), bootstrap=list(bootstrap),
        host="127.0.0.1", gossip_period_s=gossip_period_s, ttl_s=30.0,
    )
    node = Node(info, TINY, "", dht, backend="counter", max_len=64,
                rebalance_period_s=600.0)
    node.tsdb_period_s = 600.0
    # a counter node's pool has two workers: room to park a burst in
    node.scheduler.shutdown()
    node.scheduler = TaskScheduler(workers=8)
    return node


class _Held:
    """K hops parked inside the scheduler's pool while the block runs."""

    def __init__(self, node, k):
        self.node, self.k = node, k
        self._go = threading.Event()
        self._in = threading.Semaphore(0)
        self._tasks = []

    def _park(self):
        self._in.release()
        self._go.wait(10)
        return "done"

    async def __aenter__(self):
        self._tasks = [
            asyncio.ensure_future(self.node.scheduler.run(self._park))
            for _ in range(self.k)
        ]
        loop = asyncio.get_running_loop()
        for _ in range(self.k):  # every worker is really inside its hop
            assert await loop.run_in_executor(None, self._in.acquire, True, 10)
        return self

    async def __aexit__(self, *exc):
        self._go.set()
        assert await asyncio.gather(*self._tasks) == ["done"] * self.k


def _sent(node):
    """Capture the records the node's gossip puts on the wire (a HELLO
    carries none): [(frame, addr)]."""
    frames = []

    def send(data, addr):
        frame = msgpack.unpackb(data, raw=False)
        if "recs" in frame:
            frames.append((frame, tuple(addr)))

    node.dht._send_raw = send
    return frames


def _own(frame, node):
    (rec,) = [r for r in frame["recs"] if r["owner"] == node.info.node_id]
    return rec


# ------------------------------------------------------------ the scheduler


@pytest.mark.asyncio
async def test_run_stamped_keeps_inflight_exact_and_calls_nobody():
    sched = TaskScheduler(workers=4)
    try:
        assert (sched.inflight, sched.ticks) == (0, 0)
        seen = []
        out, t = await sched.run_stamped(lambda: seen.append(sched.inflight) or 7)
        assert out == 7 and t > 0 and seen == [1]
        assert (sched.inflight, sched.ticks) == (0, 2)
        assert await sched.run(lambda a, b: a + b, 2, 3) == 5
        assert (sched.inflight, sched.ticks) == (0, 4)
        assert not hasattr(sched, "_lock") and not hasattr(sched, "_on_load_change")
    finally:
        sched.shutdown()


@pytest.mark.asyncio
@pytest.mark.parametrize("how", ["run", "run_stamped"])
async def test_inflight_returns_to_zero_after_a_hop_that_raises(how):
    sched = TaskScheduler(workers=2)

    def boom():
        raise KeyError("lane")

    try:
        with pytest.raises(KeyError):
            await getattr(sched, how)(boom)
        assert (sched.inflight, sched.ticks) == (0, 2)
    finally:
        sched.shutdown()


# ------------------------------------------------------------------ the node


@pytest.mark.asyncio
async def test_hops_build_no_record_until_a_reader_asks():
    node = _mk_node(0)
    await node.start()
    try:
        nid = node.info.node_id
        builds = node._record_builds
        version = node.dht._records[nid].version
        for i in range(20):
            assert await node.scheduler.run(lambda: i) == i
        out = await node._serve_local(
            "/forward", {"stage": 0, "session_id": "s", "payload": {}})
        assert out["result_for_user"]["state"] == 1
        assert node.scheduler.ticks >= 42
        # 21 hops, 42 load changes: no record built, none replaced
        assert node._record_builds == builds
        assert node.dht._records[nid].version == version
        # the first reader pays for one build; the next finds it fresh
        assert node.dht.get_stage(0)[nid]["load"] == 0
        assert node._record_builds == builds + 1
        node.dht.get_stage(0), node.dht.get_all(1), node.dht.alive_records()
        assert node._record_builds == builds + 1
        # another stage's view never holds the own record: no build for it
        await node.scheduler.run(lambda: 0)
        assert node.dht.get_stage(1) == {}
        assert node._record_builds == builds + 1
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_gossip_send_carries_the_load_at_that_send():
    node = _mk_node(1, bootstrap=[("127.0.0.1", PORTS.gossip(99))])
    await node.start()
    try:
        frames = _sent(node)
        async with _Held(node, 3):
            assert node.scheduler.inflight == 3
            builds = node._record_builds
            node.dht.gossip_tick()
            assert frames and all(
                _own(f, node)["value"]["load"] == 3 for f, _ in frames
            )
            # one build serves everything the tick sends
            assert node._record_builds == builds + 1
            # the HELLO answer and the anti-entropy reply are sends too
            async with _Held(node, 2):
                del frames[:]
                node.dht._on_message(
                    {"t": "hello", "from": "x:1", "port": PORTS.gossip(98)},
                    ("127.0.0.1", PORTS.gossip(98)))
                assert _own(frames[-1][0], node)["value"]["load"] == 5
        del frames[:]
        node.dht._on_message(
            {"t": "state", "from": "x:1", "recs": [], "reply": True},
            ("127.0.0.1", PORTS.gossip(98)))
        assert _own(frames[-1][0], node)["value"]["load"] == 0
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_versions_rise_with_each_load_a_peer_is_sent():
    node = _mk_node(2, bootstrap=[("127.0.0.1", PORTS.gossip(99))])
    await node.start()
    try:
        frames = _sent(node)
        seen = []
        for k in (1, 2, 0):
            async with _Held(node, k):
                node.dht.gossip_tick()
                rec = _own(frames[-1][0], node)
                seen.append((rec["version"], rec["value"]["load"]))
        assert [load for _, load in seen] == [1, 2, 0]
        assert seen[0][0] < seen[1][0] < seen[2][0]
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_local_replica_pick_sees_the_exact_inflight_mid_burst():
    a = _mk_node(3, gossip_period_s=0.05)
    b = _mk_node(4, bootstrap=[("127.0.0.1", PORTS.gossip(3))], gossip_period_s=0.05)
    await a.start()
    await b.start()
    try:
        for _ in range(100):
            if len(a.dht.get_stage(0)) == 2:
                break
            await asyncio.sleep(0.05)
        ida, idb = a.info.node_id, b.info.node_id
        async with _Held(a, 3):
            view = a.dht.get_stage(0)
            assert view[ida]["load"] == a.scheduler.inflight == 3
            assert min_load_node(view)[0] == idb
            assert [n for n, _ in a.path_finder.find_ranked(0)] == [idb, ida]
            assert (await a.path_finder.find_best_node(0))[0] == idb
            # the planner and the rebalancer read get_all: exact there too
            assert a.dht.get_all(1)[0][ida]["load"] == 3
            async with _Held(a, 1):
                assert a.dht.get_stage(0)[ida]["load"] == 4
            assert a.dht.get_stage(0)[ida]["load"] == 3
            # and the peer has it within a gossip period, as it had before
            for _ in range(100):
                if b.dht.get_stage(0).get(ida, {}).get("load") == 3:
                    break
                await asyncio.sleep(0.05)
            assert b.dht.get_stage(0)[ida]["load"] == 3
        assert a.dht.get_stage(0)[ida]["load"] == 0
    finally:
        await a.stop()
        await b.stop()


@pytest.mark.asyncio
async def test_an_urgent_announce_still_gossips_at_once():
    node = _mk_node(5, bootstrap=[("127.0.0.1", PORTS.gossip(99))])
    await node.start()
    try:
        frames = _sent(node)
        async with _Held(node, 2):
            builds = node._record_builds
            node._draining = True
            node.announce()  # as /drain, a migration, a session import do
            assert len(frames) == 1  # sent inside the call, to the one target
            frame, addr = frames[0]
            assert addr == ("127.0.0.1", PORTS.gossip(99)) and frame["t"] == "gossip"
            value = _own(frame, node)["value"]
            assert value["draining"] == 1 and value["load"] == 2
            # the send read the record the call had just built: ONE build
            assert node._record_builds == builds + 1
        # a non-urgent announce sends nothing
        node.announce(urgent=False)
        assert len(frames) == 1
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_session_that_ends_leaves_the_advert_at_the_next_read():
    node = _mk_node(6)
    await node.start()
    try:
        nid = node.info.node_id
        node.dht.get_stage(0)
        builds = node._record_builds
        node._record_moved()  # what /end_session and a standby's advert do
        assert node._record_builds == builds  # nothing built for it yet
        node.dht.get_stage(0)
        assert node._record_builds == builds + 1
        resp = await node._serve_local("/end_session", {"session_id": "nobody"})
        assert wire.unpack(resp.body) == {"ok": True}
        assert node._record_builds == builds + 1
        assert nid in node.dht.get_all(1)[0]
        assert node._record_builds == builds + 2
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_read_neither_creates_the_record_nor_revives_a_tombstone():
    node = _mk_node(7)
    nid = node.info.node_id
    # before the node's first announce (start()): reads build nothing
    assert node.dht.get_all(1) == {0: {}}
    assert node._record_builds == 0 and nid not in node.dht._records
    await node.start()
    try:
        await node.scheduler.run(lambda: 0)
        node.dht.withdraw()
        builds = node._record_builds
        assert node.dht.get_stage(0) == {}
        node.dht.gossip_tick()
        assert node._record_builds == builds
        assert node.dht._records[nid].value == {"_tombstone": True}
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_disabled_planes_record_is_the_bytes_it_always_was(monkeypatch):
    """No standby, no adapters, no paged pool, events off: the record is
    the seven keys of the first swarm slice, in their order, and `svc_ms`
    once a hop has run. (The same assertions pass on the parent of the PR
    that took the load tick off the hop: checked there when this was
    written.)"""
    monkeypatch.setenv("INFERD_EVENTS", "0")
    node = _mk_node(8)
    await node.start()
    try:
        nid = node.info.node_id
        want = {
            "name": "n8", "stage": 0, "load": 0, "cap": 4,
            "host": "127.0.0.1", "port": PORTS.http(8), "model": "tiny",
        }
        value = node.dht.get_stage(0)[nid]
        assert msgpack.packb(value, use_bin_type=True) == msgpack.packb(
            want, use_bin_type=True)
        async with _Held(node, 2):
            value = node.dht.get_stage(0)[nid]
            assert msgpack.packb(value, use_bin_type=True) == msgpack.packb(
                dict(want, load=2), use_bin_type=True)
        await node._serve_local(
            "/forward", {"stage": 0, "session_id": "s", "payload": {}})
        value = node.dht.get_stage(0)[nid]
        assert list(value) == list(want) + ["svc_ms"]
        assert {k: value[k] for k in want} == want
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_hops_task_id_is_made_without_a_system_call(monkeypatch):
    """uuid4 reads the kernel's random source, and on the loop thread every
    such call hands the GIL to a pool worker: neither the node (an envelope
    that brings no id) nor the client's envelope (the LocalClient's hops)
    asks the kernel."""
    import os as oslib
    import uuid

    from inferd_tpu.client.swarm_client import SwarmClient

    node = _mk_node(10)
    await node.start()
    try:
        def refuse(*a, **k):
            raise AssertionError("a system call for an id, a hop")

        monkeypatch.setattr(uuid, "uuid4", refuse)
        monkeypatch.setattr(oslib, "urandom", refuse)
        seen = set()
        for _ in range(3):
            out = await node._serve_local(
                "/forward", {"stage": 0, "session_id": "s", "payload": {}})
            seen.add(out["task_id"])
            env = SwarmClient([("h", 1)])._forward_env("s", [1, 2], 0)
            seen.add(env["task_id"])
        assert len(seen) == 6 and all(
            len(t) == 16 and int(t, 16) >= 0 for t in seen)
        # an id the envelope brings is echoed as it came
        out = await node._serve_local(
            "/forward", {"stage": 0, "session_id": "s", "task_id": "mine", "payload": {}})
        assert out["task_id"] == "mine"
    finally:
        await node.stop()


# ------------------------------------------------------ /stats and the reader


def _reader(metric):
    bench = os.path.join(os.path.dirname(HERE), "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            metric.replace(".", "_"),
            os.path.join(bench, "layer_metrics", f"{metric}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    return mod.read


@pytest.mark.asyncio
async def test_stats_count_builds_against_ticks():
    node = _mk_node(9)
    await node.start()
    try:
        before = json.loads((await node.handle_stats(None)).body)["announce"]
        for _ in range(10):
            await node.scheduler.run(lambda: 0)
        after = json.loads((await node.handle_stats(None)).body)
        assert after["announce"]["ticks"] == before["ticks"] + 20
        # /stats reads the record (its `dht` view): that read built one
        assert after["announce"]["builds"] == before["builds"] + 1
        assert after["dht"]["0"][node.info.node_id]["load"] == 0
    finally:
        await node.stop()


@pytest.mark.parametrize(
    "stats0, stats1, want",
    [
        # a record a second against 33 steps a second
        ({"announce": {"builds": 4}, "executor": {"batched_steps": 100}},
         {"announce": {"builds": 64}, "executor": {"batched_steps": 1600}}, 0.04),
        # the parent has no counter: nothing to read, and no raise
        ({"executor": {"batched_steps": 100}},
         {"executor": {"batched_steps": 1600}}, None),
        # no step in the window
        ({"announce": {"builds": 4}, "executor": {"batched_steps": 100}},
         {"announce": {"builds": 9}, "executor": {"batched_steps": 100}}, None),
        ({"announce": {"builds": 4}}, {"announce": {"builds": 9}}, None),
    ],
)
def test_record_builds_per_step_reader(stats0, stats1, want):
    got = _reader("node.record_builds_per_step")({"stats0": stats0, "stats1": stats1})
    assert got == want if want is None else got == pytest.approx(want)
