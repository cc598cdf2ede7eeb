"""Continuous-batching node serving (runtime/batch_executor.py): concurrent
SwarmClient generations against ONE batched node must each match solo-engine
output exactly, with decode steps actually coalescing; plus session eviction
and restart semantics."""

import asyncio

import jax
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime.node import Node, NodeInfo

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)


@pytest.fixture(scope="module")
def whole_parts(tmp_path_factory):
    parts = tmp_path_factory.mktemp("whole")
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 1)
    split_and_save(params, TINY, manifest, str(parts))
    return str(parts), params


def _mk_batched_node(idx, parts, lanes=4):
    info = NodeInfo(
        name=f"bn{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=0, num_stages=1, capacity=8, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx), bootstrap=[],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, batch_lanes=lanes,
    )


@pytest.mark.asyncio
async def test_concurrent_generations_match_solo(whole_parts):
    parts, params = whole_parts
    node = _mk_batched_node(0, parts)
    await node.start()
    try:
        prompts = [[3, 7, 11], [2, 5, 13, 17], [23, 29], [31, 37, 41, 43, 47]]
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        want = [engine.generate(p, max_new_tokens=8, seed=0) for p in prompts]

        async def one(p):
            async with SwarmClient([("127.0.0.1", PORTS.http())], sampling=sc) as c:
                return await c.generate_ids(p, max_new_tokens=8)

        got = await asyncio.gather(*(one(p) for p in prompts))
        assert list(got) == want
    finally:
        await node.stop()


def test_closed_loops_ride_the_same_device_steps(whole_parts):
    """Five closed-loop sessions (a client's token loop each: one decode
    step, sample, the next) ride the same device steps: the batch forms
    when the device frees and waits for the sessions just served
    (runtime/window.py, formation), so after the first few steps every
    step serves all five — and each session still decodes exactly what it
    decodes alone. Driven directly through process() in threads, so the
    closed loops are the only timing there is; the tiny model's step is
    stretched to 20 ms, a step's length against a host turn as on a chip
    (where a loaded test machine's scheduler cannot pass for a client
    that went away). Programs are counted against hops over 47 hops a
    session: 3.5 rows a step is what a loaded machine still shows, and an
    executor that does not batch shows 1.0, one that pairs 2.0."""
    import threading
    import time

    import numpy as np

    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    parts, params = whole_parts
    ex = BatchedExecutor(TINY, params, lanes=6, max_len=64)  # five and a warm-up
    prompts = {
        "s0": [3, 7, 11], "s1": [2, 5, 13, 17], "s2": [23, 29],
        "s3": [31, 37, 41, 43, 47], "s4": [53, 59, 61],
    }
    new = 48
    engine = Engine(TINY, params, max_len=64,
                    sampling_cfg=SamplingConfig(temperature=0.0))
    want = {s: engine.generate(p, max_new_tokens=new, seed=0)
            for s, p in prompts.items()}

    got = {}
    for s, p in prompts.items():
        r = ex.process(s, {"tokens": [p], "start_pos": 0, "real_len": len(p)})
        got[s] = [int(np.argmax(r["logits"][0]))]
    # one decode step of one session, so that no loop below waits for XLA
    ex.process("warm", {"tokens": [[1, 2]], "start_pos": 0, "real_len": 2})
    ex.process("warm", {"tokens": [[3]], "start_pos": 2, "real_len": 1})
    ex.end_session("warm")
    program = ex.engine._decode_logits

    programs = []

    def slow_step(*args, **kwargs):
        programs.append(1)
        time.sleep(0.02)
        return program(*args, **kwargs)

    ex.engine._decode_logits = slow_step
    before = ex.stats()
    barrier = threading.Barrier(len(prompts))

    def loop(s):
        barrier.wait()
        for i in range(new - 1):
            r = ex.process(s, {"tokens": [[got[s][-1]]],
                               "start_pos": len(prompts[s]) + i, "real_len": 1})
            assert r["logits"].shape == (1, TINY.vocab_size)
            got[s].append(int(np.argmax(r["logits"][0])))
        ex.end_session(s)

    threads = [threading.Thread(target=loop, args=(s,)) for s in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert got == want
    st = ex.stats()
    tokens = st["batched_tokens"] - before["batched_tokens"]
    steps = st["batched_steps"] - before["batched_steps"]
    assert tokens == 5 * (new - 1)  # token-true: one count a decoded token
    assert steps == len(programs)  # a step the stats count is a program that ran
    assert tokens / len(programs) >= 3.5, (tokens, steps, st)
    assert st["gang_full"] >= steps - st["gang_timeout"] - 1
    assert st["empty_drains"] == 0


@pytest.mark.asyncio
async def test_lane_eviction_and_restart(whole_parts):
    """More sessions than lanes: LRU eviction frees lanes; an evicted
    session resuming mid-stream gets a clean session_state error and the
    client restarts transparently."""
    parts, params = whole_parts
    node = _mk_batched_node(2, parts, lanes=2)
    await node.start()
    try:
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        prompts = [[3, 7, 11], [2, 5, 13], [23, 29, 31], [37, 41, 43]]
        want = [engine.generate(p, max_new_tokens=6, seed=0) for p in prompts]

        async def one(p):
            async with SwarmClient([("127.0.0.1", PORTS.http(2))], sampling=sc) as c:
                # capacity backpressure (503 busy) retries the whole
                # generation; under full-suite load the in-flight ones
                # finish slowly, so give the retry loop more headroom than
                # the default 2 attempts
                return await c.generate_ids(p, max_new_tokens=6, session_retries=6)

        got = await asyncio.gather(*(one(p) for p in prompts))
        assert list(got) == want
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_quantized_batched_node_matches_quantized_engine(whole_parts):
    """--quant int8 + --batch-lanes compose: concurrent generations against
    a quantized batched node equal the solo engine on the SAME quantized
    params (greedy)."""
    from inferd_tpu.ops import quant

    parts, params = whole_parts
    info = NodeInfo(
        name="bq0", host="127.0.0.1", port=PORTS.http(40),
        stage=0, num_stages=1, capacity=8, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(40), bootstrap=[],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
    )
    node = Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, batch_lanes=3, quant="int8",
    )
    await node.start()
    try:
        qparams = quant.quantize_params(
            params, tie_word_embeddings=TINY.tie_word_embeddings
        )
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, qparams, max_len=64, sampling_cfg=sc)
        prompts = [[3, 7, 11], [2, 5, 13, 17], [23, 29]]
        want = [engine.generate(p, max_new_tokens=6, seed=0) for p in prompts]

        async def one(p):
            async with SwarmClient([("127.0.0.1", PORTS.http(40))], sampling=sc) as c:
                return await c.generate_ids(p, max_new_tokens=6)

        got = await asyncio.gather(*(one(p) for p in prompts))
        assert list(got) == want
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_int4_node_matches_int4_engine(whole_parts):
    """--quant int4 serves end to end: the node's group-wise int4 stage
    generates exactly what a solo engine over the SAME int4 params does
    (greedy) — the serving wiring (executor quantize hook, stage load,
    tied-head shadow) composes with the new format."""
    from inferd_tpu.ops import quant

    parts, params = whole_parts
    info = NodeInfo(
        name="i4", host="127.0.0.1", port=PORTS.http(41),
        stage=0, num_stages=1, capacity=8, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(41), bootstrap=[],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
    )
    node = Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, quant="int4",
    )
    await node.start()
    try:
        qparams = quant.apply_quant_mode(
            "int4", params, tie_word_embeddings=TINY.tie_word_embeddings
        )
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, qparams, max_len=64, sampling_cfg=sc)
        prompt = [3, 7, 11, 19]
        want = engine.generate(prompt, max_new_tokens=6)
        async with SwarmClient([("127.0.0.1", PORTS.http(41))], sampling=sc) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == want
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_chain_client_against_batched_node(whole_parts):
    """ChainClient (fixed hub-and-spoke, reference rpc_client.py topology)
    drives a 1-stage batched node identically to the swarm client."""
    from inferd_tpu.client.chain_client import ChainClient

    parts, params = whole_parts
    node = _mk_batched_node(5, parts)
    await node.start()
    try:
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        prompt = [3, 7, 11, 19]
        want = engine.generate(prompt, max_new_tokens=6, seed=0)
        async with ChainClient([("127.0.0.1", PORTS.http(5))], sampling=sc) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == want
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_batched_replica_graceful_death_failover(whole_parts):
    """Two --batch-lanes replicas: the serving one STOPS mid-generation,
    hands its lane KV to the survivor, and the client (failing over on the
    dead entry) completes token-exact with session_retries=0 — the
    zero-restart failover story on the continuous-batching path."""
    parts, params = whole_parts
    nodes = []
    for i in range(2):
        info = NodeInfo(
            name=f"gf{i}", host="127.0.0.1", port=PORTS.http(30 + i),
            stage=0, num_stages=1, capacity=8, model_name="tiny",
        )
        dht = SwarmDHT(
            info.node_id, PORTS.gossip(30 + i),
            bootstrap=[] if i == 0 else [("127.0.0.1", PORTS.gossip(30))],
            host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
        )
        nodes.append(Node(
            info, TINY, parts, dht, backend="qwen3", max_len=64,
            rebalance_period_s=600.0, batch_lanes=2,
        ))
    for n in nodes:
        await n.start()
    for _ in range(100):
        if all(len(n.dht.get_stage(0)) == 2 for n in nodes):
            break
        await asyncio.sleep(0.05)
    stopped = []
    try:
        engine = Engine(TINY, params, max_len=64,
                        sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19, 5]
        # long enough that the poll below finds the session mid-generation
        # (the warm-up has compiled the decode step: a hop takes a few ms)
        want = engine.generate(prompt, max_new_tokens=48)

        killed = {}

        async def kill_serving_entry():
            for _ in range(12000):
                for n in nodes:
                    if len(n.executor.sessions):
                        await n.stop()
                        stopped.append(n)
                        killed["node"] = n
                        return
                await asyncio.sleep(0.005)

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(30)), ("127.0.0.1", PORTS.http(31))],
            sampling=SamplingConfig(temperature=0.0), timeout_s=60.0,
        ) as c:
            task = asyncio.create_task(kill_serving_entry())
            got = await c.generate_ids(prompt, max_new_tokens=48,
                                       session_retries=0)
            await task
        assert killed.get("node") is not None
        assert got == want
        survivor = [n for n in nodes if n is not killed["node"]][0]
        m = survivor.metrics.snapshot()["counters"]
        assert m.get("sessions.imported", 0) >= 1
    finally:
        for n in nodes:
            if n not in stopped:
                await n.stop()
