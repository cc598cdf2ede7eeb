"""Lane-batched speculative SERVING (runtime/node.py + batch_executor):
concurrent /generate requests on a --batch-lanes --spec-draft-layers node
must all speculate (no shedding to the regular loop), stay greedy-exact
with the solo engine, coalesce rounds, stream accepted runs, and coexist
with regular /forward sessions on the same lanes. Round-5 scope (VERDICT
r04 #1a/c)."""

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


async def _start(node):
    """Start + wait for the spec warmup (it briefly holds a lane; tests
    that immediately saturate all lanes would otherwise race it)."""
    await node.start()
    t = getattr(node, "_spec_prebuild_task", None)
    if t is not None:
        await t
    return node


@pytest.fixture(scope="module")
def whole_parts(tmp_path_factory):
    parts = tmp_path_factory.mktemp("whole")
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 1)
    split_and_save(params, TINY, manifest, str(parts))
    return str(parts), params


def _mk_node(idx, parts, lanes=4, draft_layers=2, k=3):
    info = NodeInfo(
        name=f"sbn{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=0, num_stages=1, capacity=8, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx), bootstrap=[],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, batch_lanes=lanes,
        spec_draft_layers=draft_layers, spec_k=k,
    )


@pytest.mark.asyncio
async def test_concurrent_generate_all_speculative_greedy_exact(whole_parts):
    """Every one of 3 concurrent greedy /generate requests takes the lane
    fast path (speculative: true in each reply — the round-4 build would
    shed all but one to the regular loop) and each stream is token-exact
    with the solo engine."""
    parts, params = whole_parts
    node = _mk_node(0, parts)
    await _start(node)
    try:
        prompts = [[3, 7, 11], [2, 5, 13, 17], [23, 29]]
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        want = [engine.generate(p, max_new_tokens=10) for p in prompts]

        async def one(p):
            async with SwarmClient([("127.0.0.1", PORTS.http())], sampling=sc) as c:
                return await c.generate_server_side(
                    p, max_new_tokens=10, return_payload=True
                )

        payloads = await asyncio.gather(*(one(p) for p in prompts))
        got = [p["ids"] for p in payloads]
        assert got == want
        assert all(p.get("speculative") for p in payloads), payloads
        st = node.executor.stats()
        assert st["spec_sessions"] == 0  # all closed
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_rounds_coalesce_across_sessions(whole_parts):
    """With a long window and simultaneous requests, at least one spec
    round must serve >1 session (the whole point of lane batching)."""
    parts, params = whole_parts
    node = _mk_node(1, parts)
    # widen the spec window BEFORE start: the warmup prebuild constructs
    # the greedy runner's batcher with whatever window is set then
    node.executor._spec_window_s = 0.2
    await _start(node)
    try:
        prompts = [[3, 7, 11], [2, 5, 13, 17], [23, 29], [5, 6]]
        sc = SamplingConfig(temperature=0.0)

        async def one(p):
            async with SwarmClient([("127.0.0.1", PORTS.http(1))], sampling=sc) as c:
                return await c.generate_server_side(p, max_new_tokens=10)

        await asyncio.gather(*(one(p) for p in prompts))
        st = node.executor.stats()
        assert st["spec_rounds"] > 0
        assert st["spec_round_sessions"] > st["spec_rounds"], st
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_streaming_speculative(whole_parts):
    """stream=true on a spec-enabled batched node emits accepted runs as
    ndjson {"t": ...} lines and finishes with speculative metadata; the
    streamed ids equal the solo greedy stream."""
    import json as jsonlib

    import aiohttp

    parts, params = whole_parts
    node = _mk_node(2, parts)
    await _start(node)
    try:
        from inferd_tpu.runtime import wire

        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        prompt = [3, 7, 11]
        want = engine.generate(prompt, max_new_tokens=10)

        async with aiohttp.ClientSession() as http:
            async with http.post(
                f"http://127.0.0.1:{PORTS.http(2)}/generate",
                data=wire.pack({
                    "prompt_ids": prompt, "max_new_tokens": 10,
                    "sampling": {"temperature": 0.0}, "stream": True,
                }),
            ) as r:
                assert r.status == 200
                lines = [
                    jsonlib.loads(l) for l in (await r.read()).splitlines()
                ]
        toks = [l["t"] for l in lines if "t" in l]
        done = lines[-1]
        assert done.get("done") and done["ids"] == want
        assert toks == want
        assert done.get("speculative") is True
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_spec_and_regular_sessions_interleave(whole_parts):
    """A regular client-side-sampling /forward session decoding WHILE spec
    generations run on sibling lanes keeps its exact stream (no KV
    corruption from verify-chunk garbage writes)."""
    parts, params = whole_parts
    node = _mk_node(3, parts)
    await _start(node)
    try:
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        reg_prompt = [9, 8, 7, 6]
        want_reg = engine.generate(reg_prompt, max_new_tokens=12)
        want_spec = engine.generate([3, 7, 11], max_new_tokens=12)

        async def regular():
            async with SwarmClient(
                [("127.0.0.1", PORTS.http(3))], sampling=sc
            ) as c:
                return await c.generate_ids(reg_prompt, max_new_tokens=12)

        async def spec():
            async with SwarmClient(
                [("127.0.0.1", PORTS.http(3))], sampling=sc
            ) as c:
                return await c.generate_server_side([3, 7, 11], max_new_tokens=12)

        got_reg, got_spec = await asyncio.gather(regular(), spec())
        assert got_reg == want_reg
        assert got_spec == want_spec
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_sampled_spec_serving_deterministic_per_seed(whole_parts):
    """Sampled lane speculation: tokens flow, the reply carries accept
    stats, and a repeated (prompt, seed) request on the same engine is
    deterministic (single in-flight request; the seed contract for
    CONCURRENT sampled requests is documented weaker)."""
    parts, params = whole_parts
    node = _mk_node(4, parts)
    await _start(node)
    try:
        sc = SamplingConfig(temperature=0.9, top_k=10, top_p=0.95)

        async def one():
            async with SwarmClient(
                [("127.0.0.1", PORTS.http(4))], sampling=sc
            ) as c:
                return await c.generate_server_side(
                    [3, 7, 11], max_new_tokens=12, seed=5,
                    return_payload=True,
                )

        p1 = await one()
        p2 = await one()
        assert p1["speculative"] and p2["speculative"]
        assert len(p1["ids"]) == 12
        assert p1["ids"] == p2["ids"]
        assert 0.0 <= p1["spec_accept_rate"] <= 1.0
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_capacity_cap_and_fallback(whole_parts):
    """A prompt+budget over the spec-capped capacity declines the fast
    path and the regular loop surfaces the ordinary overflow contract."""
    parts, params = whole_parts
    node = _mk_node(5, parts)
    await _start(node)
    try:
        # cap = 64 - (3+1) = 60; 50-token prompt + 20 new > 60 -> 409 from
        # the regular path (process() caps admissions at 60 too)
        from inferd_tpu.client.base import ServerError

        sc = SamplingConfig(temperature=0.0)
        async with SwarmClient([("127.0.0.1", PORTS.http(5))], sampling=sc) as c:
            with pytest.raises(ServerError):
                await c.generate_server_side(
                    list(range(1, 51)), max_new_tokens=20
                )
            # well within cap: serves speculatively
            p = await c.generate_server_side(
                [3, 7, 11], max_new_tokens=8, return_payload=True
            )
            assert p.get("speculative") is True
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_streaming_solo_spec_node(whole_parts):
    """SOLO (stage-executor) spec nodes stream too (round 5: the round-4
    build excluded stream=true from the fast path entirely): accepted
    runs arrive as {"t"} lines, the done line carries speculative
    metadata, and the ids equal the solo engine's greedy stream."""
    import json as jsonlib

    import aiohttp

    from inferd_tpu.runtime import wire

    parts, params = whole_parts
    # no batch_lanes: the stage executor hosts the whole 1-stage model
    info_port = PORTS.http(30)
    from inferd_tpu.runtime.node import Node, NodeInfo

    info = NodeInfo(
        name="solo-spec", host="127.0.0.1", port=info_port,
        stage=0, num_stages=1, capacity=8, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(30), bootstrap=[],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
    )
    node = Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, spec_draft_layers=2, spec_k=3,
    )
    await _start(node)
    try:
        assert not getattr(node.executor, "spec_enabled", lambda: False)()
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        prompt = [3, 7, 11]
        want = engine.generate(prompt, max_new_tokens=10)
        async with aiohttp.ClientSession() as http:
            async with http.post(
                f"http://127.0.0.1:{info_port}/generate",
                data=wire.pack({
                    "prompt_ids": prompt, "max_new_tokens": 10,
                    "sampling": {"temperature": 0.0}, "stream": True,
                }),
            ) as r:
                assert r.status == 200
                lines = [
                    jsonlib.loads(l) for l in (await r.read()).splitlines()
                ]
        toks = [l["t"] for l in lines if "t" in l]
        done = lines[-1]
        assert done.get("done") and done["ids"] == want
        assert toks == want
        assert done.get("speculative") is True
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_forward_overflow_at_spec_cap(whole_parts):
    """While speculation is enabled, a REGULAR /forward admission past
    max_len-(k+1) must 409 (the verify-chunk headroom contract applies to
    every lane, not just speculating ones)."""
    from inferd_tpu.client.base import ServerError

    parts, params = whole_parts
    node = _mk_node(6, parts)  # max_len=64, k=3 -> cap 60
    await _start(node)
    try:
        sc = SamplingConfig(temperature=0.0)
        async with SwarmClient([("127.0.0.1", PORTS.http(6))], sampling=sc) as c:
            with pytest.raises(ServerError) as ei:
                # 59-token prompt + 2 new: the second decode step would
                # write past cap=60
                await c.generate_ids(list(range(1, 60)), max_new_tokens=3)
            assert ei.value.status == 409
            # within cap: fine
            out = await c.generate_ids([3, 7, 11], max_new_tokens=4)
            assert len(out) == 4
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_pinned_prefix_composes_with_spec(whole_parts):
    """pin_prefix_len > 0 no longer excludes the speculative fast path:
    the spec session FORKS the shared pin (prefix KV reused, only the
    suffix prefills) and the stream stays greedy-exact. Covers both the
    suffix case and the prompt==prefix case (pin logits seed the first
    token)."""
    parts, params = whole_parts
    node = _mk_node(7, parts)
    await _start(node)
    try:
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        prefix = [3, 7, 11, 13]
        full = prefix + [2, 5]
        want_full = engine.generate(full, max_new_tokens=10)
        want_pfx = engine.generate(prefix, max_new_tokens=10)

        async with SwarmClient([("127.0.0.1", PORTS.http(7))], sampling=sc) as c:
            p1 = await c.generate_server_side(
                full, max_new_tokens=10, pin_prefix_len=len(prefix),
                return_payload=True,
            )
            # prompt == pinned prefix: first token comes from the pin's
            # stored logits, the rest from spec rounds
            p2 = await c.generate_server_side(
                prefix, max_new_tokens=10, pin_prefix_len=len(prefix),
                return_payload=True,
            )
        assert p1["ids"] == want_full
        assert p2["ids"] == want_pfx
        assert p1.get("speculative") and p2.get("speculative"), (p1, p2)
        snap = node.metrics.snapshot()
        assert snap["counters"]["generate.speculative_pinned"] == 2
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_greedy_logprobs_ride_the_lane_spec_path(whole_parts):
    """Greedy logprob/top-N requests take the lane fast path too (round 5:
    previously shed to the regular loop on batched nodes): the reply is
    speculative AND its logprob trail matches the regular loop's engine-
    computed values."""
    import math

    parts, params = whole_parts
    node = _mk_node(8, parts)
    await _start(node)
    try:
        sc = SamplingConfig(temperature=0.0)
        prompt = [3, 7, 11]
        # reference trail from the solo engine (the regular loop's source)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        want_lps = []
        want = engine.generate(
            prompt, max_new_tokens=10, logprob_sink=want_lps
        )
        async with SwarmClient([("127.0.0.1", PORTS.http(8))], sampling=sc) as c:
            lps = []
            tops = []
            p = await c.generate_server_side(
                prompt, max_new_tokens=10, logprob_sink=lps,
                top_logprobs=4, top_sink=tops, return_payload=True,
            )
        assert p["ids"] == want
        assert p.get("speculative") is True, p
        assert len(lps) == len(want) == len(tops)
        for a, b in zip(lps, want_lps):
            assert math.isfinite(a) and abs(a - b) < 1e-3, (a, b)
        for ti, tl in tops:
            assert len(ti) == 4 and len(tl) == 4
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_spec_serving_mixed_load_soak(whole_parts):
    """Concurrency soak over the round-5 serving surface: 12 requests —
    greedy spec, sampled spec, logprob spec, pinned spec, streamed spec,
    and regular client-side-sampling sessions — race on a 4-lane node.
    Every greedy reply must be EXACT vs the solo engine regardless of
    which path served it (CapacityError fallbacks to the regular loop are
    legal and equally exact); nothing may deadlock or leak sessions."""
    import json as jsonlib

    import aiohttp

    from inferd_tpu.runtime import wire

    parts, params = whole_parts
    node = _mk_node(9, parts)
    await _start(node)
    try:
        sc = SamplingConfig(temperature=0.0)
        engine = Engine(TINY, params, max_len=64, sampling_cfg=sc)
        prompts = [[3 + i, 7, 11 + i] for i in range(6)]
        want = {tuple(p): engine.generate(p, max_new_tokens=8)
                for p in prompts}
        prefix = [3, 7, 11, 13]
        want_pin = engine.generate(prefix + [9], max_new_tokens=8)

        entry = [("127.0.0.1", PORTS.http(9))]

        async def retry503(fn):
            # 503 = documented retryable backpressure (all lanes busy with
            # in-flight requests); a real client backs off and retries
            from inferd_tpu.client.base import ServerError

            for attempt in range(12):
                try:
                    return await fn()
                except ServerError as e:
                    # the client contract: retryable = transient
                    # backpressure (503) or a session whose lane was
                    # evicted under thrash (409 session_state) — restart
                    if not e.retryable:
                        raise
                    await asyncio.sleep(0.3 * (attempt + 1))
            raise AssertionError("backpressure never cleared")

        async def greedy_spec(p):
            async with SwarmClient(entry, sampling=sc) as c:
                out = await retry503(
                    lambda: c.generate_server_side(p, max_new_tokens=8)
                )
            assert out == want[tuple(p)], (p, out)

        async def lp_spec(p):
            async with SwarmClient(entry, sampling=sc) as c:
                lps = []
                out = await retry503(lambda: c.generate_server_side(
                    p, max_new_tokens=8, logprob_sink=lps
                ))
            assert out == want[tuple(p)]
            assert len(lps) == len(out)

        async def pinned_spec():
            async with SwarmClient(entry, sampling=sc) as c:
                out = await retry503(lambda: c.generate_server_side(
                    prefix + [9], max_new_tokens=8,
                    pin_prefix_len=len(prefix),
                ))
            assert out == want_pin

        async def sampled_spec(seed):
            s2 = SamplingConfig(temperature=0.9, top_k=10, top_p=0.95)
            async with SwarmClient(entry, sampling=s2) as c:
                out = await retry503(lambda: c.generate_server_side(
                    [5, 6, 7], max_new_tokens=8, seed=seed
                ))
            assert len(out) == 8

        async def streamed_spec(p):
            # same backpressure contract as the wire clients: a terminal
            # {"error": ...503...} line means retry the whole request
            for attempt in range(12):
                async with aiohttp.ClientSession() as http:
                    async with http.post(
                        f"http://127.0.0.1:{PORTS.http(9)}/generate",
                        data=wire.pack({
                            "prompt_ids": p, "max_new_tokens": 8,
                            "sampling": {"temperature": 0.0}, "stream": True,
                        }),
                    ) as r:
                        lines = [jsonlib.loads(l)
                                 for l in (await r.read()).splitlines()]
                done = lines[-1]
                if done.get("done"):
                    break
                err = str(done.get("error", ""))
                # transient classes only: busy lanes (503) or a session
                # evicted under thrash (409 session_state)
                assert "503" in err or "409" in err, done
                await asyncio.sleep(0.3 * (attempt + 1))
            assert done.get("done") and done["ids"] == want[tuple(p)]

        async def regular(p):
            async with SwarmClient(entry, sampling=sc) as c:
                # under 12-sessions-on-4-lanes thrash a regular session can
                # be LRU-evicted repeatedly (each eviction is a correct,
                # retryable 409 session_state); give the restart loop room
                out = await c.generate_ids(
                    p, max_new_tokens=8, session_retries=10,
                    retry_delay_s=0.3,
                )
            assert out == want[tuple(p)]

        await asyncio.gather(
            greedy_spec(prompts[0]), greedy_spec(prompts[1]),
            lp_spec(prompts[2]), pinned_spec(),
            sampled_spec(1), sampled_spec(2),
            streamed_spec(prompts[3]), streamed_spec(prompts[4]),
            regular(prompts[5]), regular(prompts[0]),
            greedy_spec(prompts[2]), lp_spec(prompts[1]),
        )
        # nothing leaked: every spec session closed, lanes recycled
        st = node.executor.stats()
        assert st["spec_sessions"] == 0, st
    finally:
        await node.stop()
