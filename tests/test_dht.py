"""Swarm store tests: gossip propagation on loopback UDP, owner-only write
merges (the B6 race fix), TTL expiry of dead nodes, tombstone withdrawal."""

import asyncio

import pytest

from inferd_tpu.control.dht import SwarmDHT

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)

def _mk(node_id, port, bootstrap=None, ttl=5.0, period=0.05):
    return SwarmDHT(
        node_id, port, bootstrap=bootstrap or [], ttl_s=ttl,
        gossip_period_s=period, host="127.0.0.1",
    )


async def _wait_for(cond, timeout=5.0, interval=0.05):
    loop = asyncio.get_event_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return True
        await asyncio.sleep(interval)
    return False


@pytest.mark.asyncio
async def test_gossip_propagation_three_nodes():
    ports = [PORTS.gossip(1), PORTS.gossip(2), PORTS.gossip(3)]
    a = _mk("a", ports[0])
    b = _mk("b", ports[1], bootstrap=[("127.0.0.1", ports[0])])
    c = _mk("c", ports[2], bootstrap=[("127.0.0.1", ports[0])])
    await a.start(); await b.start(); await c.start()
    try:
        a.announce({"stage": 0, "load": 0, "cap": 1})
        b.announce({"stage": 1, "load": 2, "cap": 1})
        c.announce({"stage": 1, "load": 0, "cap": 1})
        ok = await _wait_for(
            lambda: len(a.get_stage(1)) == 2
            and len(b.get_stage(0)) == 1
            and len(c.get_stage(0)) == 1
        )
        assert ok, "gossip did not converge"
        assert a.get_stage(1)["b"]["load"] == 2
        allmap = c.get_all(3)
        assert set(allmap.keys()) == {0, 1, 2} and allmap[2] == {}
    finally:
        await a.stop(); await b.stop(); await c.stop()


@pytest.mark.asyncio
async def test_owner_only_writes_no_clobber():
    """Concurrent announces from different nodes can never clobber each
    other (the reference's shared-record RMW race, SURVEY B6)."""
    a = _mk("a", PORTS.gossip(11))
    b = _mk("b", PORTS.gossip(12), bootstrap=[("127.0.0.1", PORTS.gossip(11))])
    await a.start(); await b.start()
    try:
        for i in range(20):  # interleaved rapid announces
            a.announce({"stage": 0, "load": i, "cap": 1})
            b.announce({"stage": 0, "load": 100 + i, "cap": 1})
        ok = await _wait_for(
            lambda: a.get_stage(0).get("b", {}).get("load") == 119
            and b.get_stage(0).get("a", {}).get("load") == 19
        )
        assert ok
        assert set(a.get_stage(0)) == {"a", "b"}
    finally:
        await a.stop(); await b.stop()


@pytest.mark.asyncio
async def test_ttl_expires_dead_node():
    a = _mk("a", PORTS.gossip(21), ttl=0.6)
    b = _mk("b", PORTS.gossip(22), bootstrap=[("127.0.0.1", PORTS.gossip(21))], ttl=0.6)
    await a.start(); await b.start()
    a.announce({"stage": 0, "load": 0, "cap": 1})
    b.announce({"stage": 1, "load": 0, "cap": 1})
    assert await _wait_for(lambda: len(a.get_stage(1)) == 1)
    await b.stop()  # b dies silently (no tombstone)
    try:
        assert await _wait_for(lambda: len(a.get_stage(1)) == 0, timeout=3.0)
    finally:
        await a.stop()


@pytest.mark.asyncio
async def test_withdraw_tombstone():
    a = _mk("a", PORTS.gossip(31))
    b = _mk("b", PORTS.gossip(32), bootstrap=[("127.0.0.1", PORTS.gossip(31))])
    await a.start(); await b.start()
    a.announce({"stage": 0, "load": 0, "cap": 1})
    b.announce({"stage": 1, "load": 0, "cap": 1})
    assert await _wait_for(lambda: len(a.get_stage(1)) == 1)
    b.withdraw()
    try:
        assert await _wait_for(lambda: len(a.get_stage(1)) == 0, timeout=3.0)
    finally:
        await a.stop(); await b.stop()


@pytest.mark.asyncio
async def test_late_joiner_bootstrap_state():
    a = _mk("a", PORTS.gossip(41))
    await a.start()
    a.announce({"stage": 0, "load": 3, "cap": 2})
    late = _mk("late", PORTS.gossip(42), bootstrap=[("127.0.0.1", PORTS.gossip(41))])
    await late.start()
    try:
        assert await _wait_for(lambda: late.get_stage(0).get("a", {}).get("load") == 3)
    finally:
        await a.stop(); await late.stop()


@pytest.mark.asyncio
async def test_bootstrap_retry_when_seed_starts_late():
    """A node whose initial HELLO is lost (seed not yet up) must keep
    retrying bootstrap and converge once the seed appears (the reference's
    Kademlia bootstrap retry, kademlia_client.py:25-37)."""
    base = 19450
    late = SwarmDHT(
        "late", base + 1, bootstrap=[("127.0.0.1", base)], host="127.0.0.1",
        gossip_period_s=0.05, ttl_s=5.0,
    )
    await late.start()  # hello goes nowhere: seed port not bound yet
    late.announce({"stage": 0, "load": 0, "cap": 1, "name": "late"})
    await asyncio.sleep(0.3)
    seed = SwarmDHT("seed", base, host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0)
    await seed.start()
    seed.announce({"stage": 1, "load": 0, "cap": 1, "name": "seed"})
    try:
        for _ in range(100):
            if late.get_stage(1) and seed.get_stage(0):
                break
            await asyncio.sleep(0.05)
        assert late.get_stage(1), "late node never learned the seed's record"
        assert seed.get_stage(0), "seed never learned the late node's record"
    finally:
        await late.stop()
        await seed.stop()


@pytest.mark.asyncio
async def test_mixed_version_gossip_windowed_and_outlier_keys():
    """PR 7 wire compat (mirroring the PR 4 multi-envelope pattern): a
    NEW node's record carries `outlier`, `svc_p99_ms`, and the windowed
    hop quantiles; an OLD peer must relay and store them untouched (the
    gossip store is schema-agnostic), and an old-style record LACKING
    them must coexist in the same stage map without defaults being
    invented for it."""
    new = _mk("new", PORTS.gossip(51))
    old = _mk("old", PORTS.gossip(52), bootstrap=[("127.0.0.1", PORTS.gossip(51))])
    obs = _mk("obs", PORTS.gossip(53), bootstrap=[("127.0.0.1", PORTS.gossip(51))])
    await new.start(); await old.start(); await obs.start()
    try:
        new.announce({
            "stage": 0, "load": 1, "cap": 4,
            # PR 7 keys + a future key nobody knows yet
            "hop_p50_ms": 4.5, "hop_p99_ms": 22.0, "svc_p99_ms": 9.0,
            "outlier": 1, "sloth_factor_v9": {"nested": True},
        })
        old.announce({"stage": 0, "load": 0, "cap": 4})  # pre-PR record
        ok = await _wait_for(lambda: len(obs.get_stage(0)) == 2)
        assert ok, "gossip did not converge"
        stage = obs.get_stage(0)
        # the new keys arrive bit-true through the old-agnostic store
        assert stage["new"]["outlier"] == 1
        assert stage["new"]["svc_p99_ms"] == 9.0
        assert stage["new"]["hop_p99_ms"] == 22.0
        assert stage["new"]["sloth_factor_v9"] == {"nested": True}
        # the old record gained nothing it never announced
        for key in ("outlier", "svc_p99_ms", "hop_p50_ms", "hop_p99_ms"):
            assert key not in stage["old"]
    finally:
        await new.stop(); await old.stop(); await obs.stop()
