"""D*-Lite chain routing WIRED into serving (the reference's signature gap:
its dstar/ module was never imported by routing — path_finder.py:22,36 TODO,
client.py:131-138 dead stub). Covered here:

  * SwarmChainPlanner unit behavior: incremental replans (update_edge +
    bounded compute, proven by expansion counts on a wide graph), node
    death as an INF cost update, rebuild only on genuinely new nodes,
    agent advance restricting replans to the remaining stages;
  * node-side wiring: a new session entering the swarm gets a planned
    whole-chain route that relays follow (route.planned / route.followed
    metrics), falling back to per-hop picks when planning fails;
  * client-side wiring (RoutedChainClient): a mid-first-pass load spike on
    the replica planned for a LATER stage replans the remaining hops
    incrementally and the pass lands on the better replica — token-exact
    vs the single-process engine; an empty stage raises NoNodeForStage.
"""

import asyncio

import numpy as np
import pytest

from inferd_tpu.client.routed_client import RoutedChainClient
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.control.dstar import START, SwarmChainPlanner, node_cost
from inferd_tpu.control.path_finder import NoNodeForStage
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime.node import Node, NodeInfo

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)

GREEDY = SamplingConfig(temperature=0.0)


# ----------------------------------------------------------------- planner


def _snap(loads):
    """{stage: {node_id: value}} from {stage: {node_id: load}}."""
    return {
        s: {nid: {"load": load, "cap": 4} for nid, load in m.items()}
        for s, m in loads.items()
    }


def test_node_cost_svc_ms_term():
    base = node_cost({"load": 2, "cap": 4})
    assert base == 1.0 + 0.5
    # 100 ms of announced service time weighs like one extra hop
    assert node_cost({"load": 2, "cap": 4, "svc_ms": 100.0}) == pytest.approx(base + 1.0)
    # nodes that don't announce svc_ms stay comparable (no term)
    assert node_cost({"load": 0, "cap": 1}) == 1.0


def test_planner_initial_chain_and_stats():
    p = SwarmChainPlanner(
        _snap({0: {"a0": 0}, 1: {"b0": 0, "b1": 2}, 2: {"c0": 1, "c1": 0}}), 0, 3
    )
    assert [n for _, n, _ in p.chain()] == ["a0", "b0", "c1"]
    assert p.stats["builds"] == 1 and p.stats["expansions_build"] > 0


def test_planner_incremental_replan_cheaper_than_build():
    """On a wide graph, a single-node cost change replans with FAR fewer
    expansions than the initial solve — the incremental property that is
    D*-Lite's entire reason to exist over re-running Dijkstra."""
    stages, width = 6, 8
    loads = {s: {f"n{s}_{i}": (i % 3) for i in range(width)} for s in range(stages)}
    p = SwarmChainPlanner(_snap(loads), 0, stages)
    chain0 = [n for _, n, _ in p.chain()]
    build_exp = p.stats["expansions_build"]
    # spike the load on the planned stage-3 replica
    loads[3][chain0[3]] = 50
    assert p.refresh(_snap(loads))
    chain1 = [n for _, n, _ in p.chain()]
    assert chain1[3] != chain0[3]
    assert p.stats["builds"] == 1  # no rebuild: pure cost update
    assert p.stats["expansions_replan"] < build_exp / 2, p.stats


def test_planner_death_and_flap_are_cost_updates():
    loads = {0: {"a0": 0}, 1: {"b0": 0, "b1": 1}}
    p = SwarmChainPlanner(_snap(loads), 0, 2)
    assert [n for _, n, _ in p.chain()] == ["a0", "b0"]
    # b0 TTLs out -> INF edges -> survivor routes; no rebuild
    p.refresh(_snap({0: {"a0": 0}, 1: {"b1": 1}}))
    assert [n for _, n, _ in p.chain()] == ["a0", "b1"]
    assert p.stats["builds"] == 1
    # b0 flaps back -> cost update again, still no rebuild
    p.refresh(_snap(loads))
    assert [n for _, n, _ in p.chain()] == ["a0", "b0"]
    assert p.stats["builds"] == 1
    # a genuinely NEW node on a live stage is SPLICED in incrementally
    # (join = D*-Lite increment, not a rebuild) and is immediately
    # routable when it wins on cost
    loads[1]["b9"] = -5  # cheapest stage-1 replica by far
    p.refresh(_snap(loads))
    assert p.stats["builds"] == 1 and p.stats["node_adds"] == 1
    assert [n for _, n, _ in p.chain()] == ["a0", "b9"]


def test_planner_kill_node_is_incremental_and_empty_stage_rebuilds():
    """kill_node folds an observed peer death into the plan without a
    refresh (the runtime's peer.dead hook); a node resurrecting a stage
    that was EMPTY at build time is the one topology change that still
    rebuilds (the layered graph never reached GOAL through it)."""
    loads = {0: {"a0": 0}, 1: {"b0": 0, "b1": 1}, 2: {"c0": 0}}
    p = SwarmChainPlanner(_snap(loads), 0, 3)
    assert [n for _, n, _ in p.chain()] == ["a0", "b0", "c0"]
    build_exp = p.stats["expansions_build"]
    assert p.kill_node("b0") is True
    assert [n for _, n, _ in p.chain()] == ["a0", "b1", "c0"]
    assert p.stats["builds"] == 1 and p.stats["kills"] == 1
    assert p.stats["expansions_replan"] < max(2, build_exp)
    # killing something unknown (or already dead) is a no-op
    assert p.kill_node("b0") is False
    assert p.kill_node("zz") is False
    # empty-at-build stage: no chain; a join there rebuilds and routes
    p2 = SwarmChainPlanner(_snap({0: {"a0": 0}, 1: {}}), 0, 2)
    with pytest.raises(NoNodeForStage):
        p2.chain()
    p2.refresh(_snap({0: {"a0": 0}, 1: {"b0": 0}}))
    assert p2.stats["builds"] == 2
    assert [n for _, n, _ in p2.chain()] == ["a0", "b0"]


def test_node_cost_hop_p99_term():
    """The gossiped trailing-window relay p99 is a live edge-weight term:
    HOP_P99_NORM_MS milliseconds of tail latency weigh like one extra
    hop, and records without the key stay comparable (no term)."""
    from inferd_tpu.control.dstar import HOP_P99_NORM_MS

    base = node_cost({"load": 2, "cap": 4})
    assert node_cost(
        {"load": 2, "cap": 4, "hop_p99_ms": HOP_P99_NORM_MS}
    ) == pytest.approx(base + 1.0)
    # composes with (does not replace) the svc_ms EWMA term
    assert node_cost(
        {"load": 2, "cap": 4, "svc_ms": 100.0, "hop_p99_ms": 2 * HOP_P99_NORM_MS}
    ) == pytest.approx(base + 3.0)


def test_planner_advance_limits_replans_to_remaining_stages():
    loads = {0: {"a0": 0, "a1": 1}, 1: {"b0": 0, "b1": 1}, 2: {"c0": 0, "c1": 1}}
    p = SwarmChainPlanner(_snap(loads), 0, 3)
    p.advance(0, "a0")
    assert [s for s, _, _ in p.chain()] == [1, 2]
    # a committed-stage cost change is ignored entirely
    loads[0]["a0"] = 99
    assert not p.refresh(_snap(loads))
    # a remaining-stage spike replans
    loads[1]["b0"] = 99
    assert p.refresh(_snap(loads))
    assert [n for _, n, _ in p.chain()] == ["b1", "c0"]


def test_planner_empty_stage_raises():
    p = SwarmChainPlanner(_snap({0: {"a0": 0}, 1: {"b0": 0}}), 0, 2)
    p.refresh(_snap({0: {"a0": 0}, 1: {}}))
    with pytest.raises(NoNodeForStage):
        p.chain()
    with pytest.raises(NoNodeForStage):
        SwarmChainPlanner(_snap({0: {}, 1: {"b0": 0}}), 0, 2).chain()


# ------------------------------------------------------------- swarm e2e


@pytest.fixture(scope="module")
def tiny_params():
    import jax

    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_parts(tmp_path_factory, tiny_params):
    parts = tmp_path_factory.mktemp("parts_router")
    split_and_save(tiny_params, TINY, Manifest.even_split("tiny", 2), str(parts))
    return str(parts)


def _mk_node(idx, stage, num_stages, *, parts, capacity=4):
    info = NodeInfo(
        name=f"r{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=stage, num_stages=num_stages, capacity=capacity,
        model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx),
        bootstrap=[("127.0.0.1", PORTS.gossip())] if idx else [],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0,
    )


async def _start_all(nodes):
    for n in nodes:
        await n.start()

    async def converged():
        for n in nodes:
            m = n.dht.get_all(n.info.num_stages)
            if any(not m[s] for s in range(n.info.num_stages)):
                return False
        return True

    for _ in range(100):
        if await converged():
            return
        await asyncio.sleep(0.05)
    raise TimeoutError("swarm did not converge")


PROMPT = [3, 7, 11, 19, 5]


@pytest.mark.asyncio
async def test_relay_follows_planned_route(tiny_params, tiny_parts):
    """A new session entering the swarm gets a D*-Lite whole-chain route;
    the relay follows it to the LOW-cost stage-1 replica (not round-robin,
    not accidental) and the tokens match the single-process engine."""
    # engine reference FIRST: its jit compile blocks the shared event loop
    # for seconds, which would stall every in-process gossip loop and TTL
    # out the records mid-test
    engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    want = engine.generate(PROMPT, max_new_tokens=6)
    nodes = [
        _mk_node(0, 0, 2, parts=tiny_parts),
        _mk_node(1, 1, 2, parts=tiny_parts),
        _mk_node(2, 1, 2, parts=tiny_parts),
    ]
    try:
        await _start_all(nodes)
        # skew the stage-1 replicas: make nodes[1] expensive so the planner
        # must choose nodes[2] (min-load would too — the point here is that
        # the route is PLANNED once and followed, metrics prove the path)
        nodes[1]._svc_ewma = 500.0
        nodes[1].announce()
        for _ in range(40):
            v = nodes[0].dht.get_stage(1).get(nodes[1].info.node_id, {})
            if v.get("svc_ms"):
                break
            await asyncio.sleep(0.05)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http())], sampling=GREEDY, prefill_chunk=4
        ) as c:
            got = await c.generate_ids(PROMPT, max_new_tokens=6)
        assert got == want
        m = nodes[0].metrics.snapshot()
        assert m["counters"].get("route.planned", 0) >= 1
        assert m["counters"].get("route.followed", 0) >= 1
        stats = nodes[0].path_finder.planner.stats
        assert stats["builds"] >= 1
        # the cheap replica served every relayed chunk; the expensive one
        # stayed idle — the planned route, not round-robin, carried traffic
        m1 = nodes[1].metrics.snapshot()["counters"]
        m2 = nodes[2].metrics.snapshot()["counters"]
        assert m2.get("forward.requests", 0) > 0
        assert m1.get("forward.requests", 0) == 0
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_entry_plan_failure_falls_back_to_per_hop(tiny_parts):
    """With no stage-1 replica in view, planning fails (route.plan_failed)
    and the request degrades to the existing per-hop pick path (which
    surfaces 503 after its own retries) — never an unhandled error."""
    node = _mk_node(0, 0, 2, parts=tiny_parts)
    try:
        await node.start()
        assert node._plan_route(1) is None
        assert node.metrics.snapshot()["counters"].get("route.plan_failed") == 1
    finally:
        await node.stop()


# ------------------------------------------------------- routed client e2e


@pytest.mark.asyncio
async def test_routed_client_mid_pass_spike_replans(tiny_params, tiny_parts):
    """The verdict's e2e: while the first pass sits between stage 0 and
    stage 1, a load spike hits the replica the planner chose for stage 1;
    the client replans INCREMENTALLY (no rebuild, bounded expansions) and
    the pass lands on the other replica — token-exact vs the engine."""
    # engine reference FIRST (see test_relay_follows_planned_route: the jit
    # compile must not stall the in-process gossip loops mid-test)
    engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    want = engine.generate(PROMPT, max_new_tokens=5)
    nodes = [
        _mk_node(0, 0, 2, parts=tiny_parts),
        _mk_node(1, 1, 2, parts=tiny_parts),
        _mk_node(2, 1, 2, parts=tiny_parts),
    ]
    spiked_id = nodes[1].info.node_id
    try:
        await _start_all(nodes)
        # make nodes[1] the initial stage-1 choice (cheaper than nodes[2])
        nodes[2]._svc_ewma = 50.0
        nodes[2].announce()

        obs = SwarmDHT(
            "router-client", PORTS.gossip(99),
            bootstrap=[("127.0.0.1", PORTS.gossip())],
            host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
        )
        await obs.start()
        for _ in range(100):
            snap = obs.get_all(2)
            if all(snap[s] for s in range(2)) and (
                snap[1].get(nodes[2].info.node_id, {}).get("svc_ms")
            ):
                break
            await asyncio.sleep(0.05)
        else:
            raise TimeoutError("observer never converged")

        stats_seen = {}

        async def spike(session_id, completed_stage):
            if completed_stage != 0 or stats_seen.get("spiked"):
                return
            stats_seen["spiked"] = True
            # the planned stage-1 replica becomes very expensive while the
            # pass is in flight between stage 0 and stage 1
            nodes[1]._svc_ewma = 5000.0
            nodes[1].announce()
            for _ in range(100):
                v = obs.get_stage(1).get(spiked_id, {})
                if v.get("svc_ms", 0) > 1000:
                    return
                await asyncio.sleep(0.05)
            raise TimeoutError("spike never reached the observer view")

        async with RoutedChainClient(
            obs, 2, sampling=GREEDY, prefill_chunk=4
        ) as c:
            c.hop_hook = spike

            # capture planner stats before the client freezes the plan
            orig_step = c._step

            async def step_and_snap(session_id, tokens, start_pos):
                out = await orig_step(session_id, tokens, start_pos)
                st = c.planner_stats(session_id)
                if st is not None:
                    stats_seen["stats"] = st
                plan = c._plans.get(session_id)
                if plan is not None and plan.committed:
                    stats_seen["chain"] = [nid for nid, _ in plan.chain]
                return out

            c._step = step_and_snap
            got = await c.generate_ids(PROMPT, max_new_tokens=5)

        assert got == want
        assert stats_seen["spiked"]
        # the pass landed on the OTHER replica for stage 1
        assert stats_seen["chain"][1] == nodes[2].info.node_id
        st = stats_seen["stats"]
        assert st["builds"] == 1, st  # replans were incremental, no rebuild
        assert st["cost_updates"] >= 1, st
        assert st["expansions_replan"] > 0, st
        await obs.stop()
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_routed_client_empty_stage_raises(tiny_parts):
    """Planner's stage view empty -> retryable 503 (code no_chain): the
    generation gets its session retries (a gossip blip heals), and a
    PERSISTENTLY empty stage surfaces the error cleanly after them."""
    from inferd_tpu.client.base import ServerError

    node = _mk_node(0, 0, 2, parts=tiny_parts)  # no stage-1 node at all
    try:
        await node.start()
        obs = SwarmDHT(
            "router-client-2", PORTS.gossip(98),
            bootstrap=[("127.0.0.1", PORTS.gossip())],
            host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
        )
        await obs.start()
        for _ in range(100):
            if obs.get_all(2)[0]:
                break
            await asyncio.sleep(0.05)
        async with RoutedChainClient(obs, 2, sampling=GREEDY) as c:
            with pytest.raises(ServerError) as ei:
                await c.generate_ids(
                    PROMPT, max_new_tokens=3,
                    session_retries=1, retry_delay_s=0.05,
                )
            assert ei.value.code == "no_chain" and ei.value.retryable
        await obs.stop()
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_routed_client_mid_session_failover_via_gossip(
    tiny_params, tiny_parts
):
    """VERDICT r03 item 5: a COMMITTED-chain replica dies mid-decode. The
    routed client consults the gossip session-location adverts it already
    merges (the `sess` hashes — the same records the swarm relay's rescue
    uses), repairs the chain to the replica holding the handed-off KV, and
    completes token-exact with ZERO session restarts (we drive _step
    directly, so a restart would be impossible — any unrescued failure
    raises instead)."""
    from inferd_tpu.control.dht import sess_hash

    engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    want = engine.generate(PROMPT, max_new_tokens=6)
    nodes = [
        _mk_node(0, 0, 2, parts=tiny_parts),
        _mk_node(1, 0, 2, parts=tiny_parts),
        _mk_node(2, 1, 2, parts=tiny_parts),
    ]
    obs = None
    try:
        await _start_all(nodes)
        obs = SwarmDHT(
            "router-failover-client", PORTS.gossip(98),
            bootstrap=[("127.0.0.1", PORTS.gossip())],
            host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
        )
        await obs.start()
        for _ in range(100):
            snap = obs.get_all(2)
            if all(snap[s] for s in range(2)):
                break
            await asyncio.sleep(0.05)
        else:
            raise TimeoutError("observer never converged")

        async with RoutedChainClient(obs, 2, sampling=GREEDY) as c:
            sid = "routed-failover"
            logits = await c._step(sid, PROMPT, 0)
            toks = [int(np.argmax(logits))]
            pos = len(PROMPT)
            for _ in range(2):
                logits = await c._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            plan = c._plans[sid]
            assert plan.committed
            victim_id = plan.chain[0][0]
            victim = next(n for n in nodes[:2] if n.info.node_id == victim_id)
            survivor = next(n for n in nodes[:2] if n is not victim)
            # graceful death: drains + hands the session KV to the survivor
            await victim.stop()
            assert sid in survivor.executor.sessions
            # the survivor's session advert must reach the CLIENT's view
            for _ in range(100):
                v = obs.get_stage(0).get(survivor.info.node_id, {})
                if sess_hash(sid) in (v.get("sess") or ()):
                    break
                await asyncio.sleep(0.05)
            else:
                raise TimeoutError("session advert never reached the client")

            for _ in range(3):  # hop to the dead node -> rescued, repaired
                logits = await c._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            assert c._plans[sid].chain[0][0] == survivor.info.node_id
            await c._end_session(sid)
        assert toks == want
        nodes.remove(victim)
        if obs is not None:
            await obs.stop()
            obs = None
    finally:
        for n in nodes:
            await n.stop()
        if obs is not None:
            await obs.stop()
