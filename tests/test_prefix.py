# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Prefix caching: engine pin_prefix, executor fork_session, and the
client-driven distributed session fork (swarm relay + chain hub-and-spoke).

The reference has no prefix reuse at all — every generation re-prefills its
full prompt (/root/reference/models/qwen3/client/client.py:217-236). Here a
pinned prefix's per-stage KV is forked stage-locally into each new session
(inner stages never see tokens, so the client — which does — drives it)."""

import asyncio

import jax
import numpy as np
import pytest

from inferd_tpu.client.chain_client import ChainClient
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import (
    Manifest,
    StageSpec,
    extract_stage_params,
    split_and_save,
)
from inferd_tpu.runtime.executor import Qwen3StageExecutor
from inferd_tpu.runtime.node import Node, NodeInfo

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)

PREFIX = [3, 7, 11, 19, 5, 2, 17, 13]
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


# ---------------------------------------------------------------- engine


def test_engine_pin_parity(tiny_params):
    """Pinned-prefix generation == cold generation, token for token."""
    cold = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    warm = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    warm.pin_prefix(PREFIX)
    for tail in ([4, 9], [8], [6, 1, 2, 12]):
        prompt = PREFIX + tail
        assert warm.generate(prompt, 5) == cold.generate(prompt, 5)


def test_engine_pin_exact_prompt(tiny_params):
    """Prompt == pinned prefix exactly: first token comes from the stored
    pin logits, no prefill at all."""
    cold = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    warm = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    warm.pin_prefix(PREFIX)
    assert warm.generate(PREFIX, 5) == cold.generate(PREFIX, 5)


def test_engine_pin_reusable_and_lru(tiny_params):
    """A pin survives repeated reuse (donation must never eat the snapshot)
    and the pin store is LRU-capped."""
    eng = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    eng.pin_prefix(PREFIX)
    first = eng.generate(PREFIX + [4], 4)
    for _ in range(2):
        assert eng.generate(PREFIX + [4], 4) == first
    eng.max_pins = 2
    for i in range(3):
        eng.pin_prefix([10 + i, 20 + i])
    assert len(eng._pins) == 2
    # evicted pin falls back to the cold path, still correct
    cold = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    assert eng.generate(PREFIX + [4], 4) == cold.generate(PREFIX + [4], 4)


def test_engine_non_matching_prompt_unaffected(tiny_params):
    cold = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    warm = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
    warm.pin_prefix(PREFIX)
    prompt = [9, 9, 9]  # does not start with the pin
    assert warm.generate(prompt, 5) == cold.generate(prompt, 5)


# -------------------------------------------------------------- executor


def test_executor_fork_parity(tiny_params):
    """Fork at a prefix + prefill the tail == fresh full prefill."""
    cfg = TINY
    spec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    ex = Qwen3StageExecutor(
        cfg, spec, extract_stage_params(tiny_params, cfg, spec), max_len=64
    )
    tail = [4, 9, 6]
    # parent: prefill the prefix (then decode a bit — fork must still take
    # only the first prefix_len slots)
    out_p = ex.process("parent", {"tokens": np.asarray([PREFIX]), "start_pos": 0})
    ex.process(
        "parent",
        {"tokens": np.asarray([[int(np.argmax(out_p["logits"][0]))]]),
         "start_pos": len(PREFIX)},
    )
    assert ex.fork_session("child", "parent", len(PREFIX))
    out_c = ex.process(
        "child",
        {"tokens": np.asarray([tail]), "start_pos": len(PREFIX),
         "real_len": len(tail)},
    )
    out_f = ex.process(
        "fresh", {"tokens": np.asarray([PREFIX + tail]), "start_pos": 0}
    )
    np.testing.assert_allclose(
        out_c["logits"], out_f["logits"], rtol=2e-5, atol=2e-5
    )


def test_executor_fork_misses(tiny_params):
    cfg = TINY
    spec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    ex = Qwen3StageExecutor(
        cfg, spec, extract_stage_params(tiny_params, cfg, spec), max_len=64
    )
    assert not ex.fork_session("c", "nope", 4)  # unknown parent
    ex.process("p", {"tokens": np.asarray([[1, 2]]), "start_pos": 0})
    assert not ex.fork_session("c", "p", 5)  # parent shorter than prefix
    assert not ex.fork_session("c", "p", 0)  # degenerate


# ------------------------------------------- batched / mesh executors


def test_batched_executor_fork_parity(tiny_params):
    """Lane fork on the continuous-batching executor == fresh prefill."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(TINY, tiny_params, lanes=4, max_len=64)
    tail = [4, 9, 6]
    ex.process("parent", {"tokens": np.asarray([PREFIX]), "start_pos": 0})
    assert ex.fork_session("child", "parent", len(PREFIX))
    out_c = ex.process(
        "child",
        {"tokens": np.asarray([tail]), "start_pos": len(PREFIX),
         "real_len": len(tail)},
    )
    out_f = ex.process(
        "fresh", {"tokens": np.asarray([PREFIX + tail]), "start_pos": 0}
    )
    np.testing.assert_allclose(
        out_c["logits"], out_f["logits"], rtol=2e-5, atol=2e-5
    )
    # decode continues on the forked lane
    tok = int(np.argmax(out_c["logits"][0]))
    out_d = ex.process(
        "child",
        {"tokens": np.asarray([[tok]]), "start_pos": len(PREFIX) + len(tail)},
    )
    assert out_d["logits"].shape == out_f["logits"].shape
    assert not ex.fork_session("c2", "ghost", 3)


def test_batched_executor_fork_protects_parent(tiny_params):
    """With every lane taken, forking must not LRU-evict the parent to make
    room for its own child."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(TINY, tiny_params, lanes=2, max_len=64)
    ex.process("parent", {"tokens": np.asarray([PREFIX]), "start_pos": 0})
    ex.process("other", {"tokens": np.asarray([[1, 2]]), "start_pos": 0})
    assert ex.fork_session("child", "parent", len(PREFIX))  # evicts "other"
    assert "parent" in ex
    assert "child" in ex


def test_mesh_executor_fork_parity(tiny_params):
    """Slot fork on the in-mesh pipelined executor == fresh prefill (the
    copy is shard-local per pp rank)."""
    import jax

    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    ex = MeshExecutor(
        TINY, tiny_params, MeshPlan(pp=2), num_slots=4, max_len=64,
        devices=jax.devices()[:2],
    )
    tail = [4, 9, 6]
    ex.process("parent", {"tokens": np.asarray([PREFIX]), "start_pos": 0})
    assert ex.fork_session("child", "parent", len(PREFIX))
    out_c = ex.process(
        "child",
        {"tokens": np.asarray([tail]), "start_pos": len(PREFIX),
         "real_len": len(tail)},
    )
    out_f = ex.process(
        "fresh", {"tokens": np.asarray([PREFIX + tail]), "start_pos": 0}
    )
    np.testing.assert_allclose(
        out_c["logits"], out_f["logits"], rtol=2e-5, atol=2e-5
    )
    assert not ex.fork_session("c2", "ghost", 3)


# ------------------------------------------------------------------ swarm


def _mk_node(idx, stage, num_stages, *, parts, bootstrap_idx):
    info = NodeInfo(
        name=f"px{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=stage, num_stages=num_stages, capacity=4, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx),
        bootstrap=[("127.0.0.1", PORTS.gossip(bootstrap_idx))]
        if idx != bootstrap_idx else [],
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


@pytest.fixture(scope="module")
def tiny_parts(tmp_path_factory, tiny_params):
    parts = tmp_path_factory.mktemp("parts_prefix")
    split_and_save(tiny_params, TINY, Manifest.even_split("tiny", 2), str(parts))
    return str(parts)


@pytest.mark.asyncio
async def test_swarm_fork_e2e(tiny_parts, tiny_params):
    """Pinned client over a 2-stage swarm: token parity with the engine,
    forks actually taken on both stages, and prefix tokens prefilled once."""
    nodes = [
        _mk_node(i, i, 2, parts=tiny_parts, bootstrap_idx=0) for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        tails = ([4, 9], [8, 6, 1])
        expected = [engine.generate(PREFIX + list(t), 5) for t in tails]
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(0))], sampling=GREEDY, prefill_chunk=4
        ) as c:
            await c.pin_prefix(PREFIX)
            got = [await c.generate_ids(PREFIX + list(t), 5) for t in tails]
        assert got == expected
        for n in nodes:
            snap = n.metrics.snapshot()
            assert snap["counters"].get("fork.ok", 0) >= len(tails)
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_swarm_fork_fallback_after_parent_eviction(tiny_parts, tiny_params):
    """Ending the pinned session behind the client's back: generation still
    succeeds via the full-prefill fallback and the stale pin is dropped."""
    nodes = [
        _mk_node(10 + i, i, 2, parts=tiny_parts, bootstrap_idx=10)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        prompt = PREFIX + [4, 9]
        expected = engine.generate(prompt, 5)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(10))], sampling=GREEDY
        ) as c:
            await c.pin_prefix(PREFIX)
            parent_sid, _ = c._pins[tuple(PREFIX)]
            await c._end_session(parent_sid)  # simulate server-side eviction
            got = await c.generate_ids(prompt, 5)
            assert got == expected
            assert tuple(PREFIX) not in c._pins
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_server_side_generate(tiny_parts, tiny_params):
    """/generate: the node runs the token loop against itself — one round
    trip returns what the client-side loop returns, greedy and pinned."""
    nodes = [
        _mk_node(30 + i, i, 2, parts=tiny_parts, bootstrap_idx=30)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        prompt = PREFIX + [4, 9]
        expected = engine.generate(prompt, 5)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(30))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            got = await c.generate_server_side(prompt, max_new_tokens=5)
            assert got == expected
            # pinned variant: the node pins the prefix and forks it
            got2 = await c.generate_server_side(
                prompt, max_new_tokens=5, pin_prefix_len=len(PREFIX)
            )
            assert got2 == expected
            got3 = await c.generate_server_side(
                prompt, max_new_tokens=5, pin_prefix_len=len(PREFIX)
            )
            assert got3 == expected
        # the second pinned call forked the node-held pin on both stages
        assert any(
            n.metrics.snapshot()["counters"].get("fork.ok", 0) >= 1 for n in nodes
        )
        # entering at the WRONG node still works (relay to stage 0)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(31))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            got = await c.generate_server_side(prompt, max_new_tokens=5)
        assert got == expected
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_server_side_generate_logprobs(tiny_parts, tiny_params):
    """/generate with logprobs=true returns per-token model log-
    probabilities that match re-scoring the emitted sequence with the
    single-process model (log-softmax of the raw logits at each step)."""
    import jax.numpy as jnp
    import numpy as np

    from inferd_tpu.models import qwen3

    nodes = [
        _mk_node(90 + i, i, 2, parts=tiny_parts, bootstrap_idx=90)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        prompt = [3, 7, 11, 5]
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(90))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            lps: list = []
            tops: list = []
            ids = await c.generate_server_side(
                prompt, max_new_tokens=5, logprob_sink=lps,
                top_logprobs=3, top_sink=tops,
            )
        assert len(lps) == len(ids) == len(tops) == 5
        # engine parity: same greedy tokens, same logprobs, same top-3
        eng = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        elps: list = []
        etops: list = []
        eids = eng.generate(
            prompt, max_new_tokens=5, logprob_sink=elps, top_n=3,
            top_sink=etops,
        )
        assert ids == eids
        np.testing.assert_allclose(lps, elps, atol=1e-3, rtol=1e-4)
        for (ti, tl), (ei, el) in zip(tops, etops):
            assert list(ti) == list(ei)
            np.testing.assert_allclose(tl, el, atol=1e-3, rtol=1e-4)
        # re-score: full forward over prompt + emitted ids; the logprob of
        # ids[i] is log_softmax(logits at position len(prompt)-1+i)[ids[i]]
        toks = jnp.asarray([prompt + ids[:-1]], jnp.int32)
        logits, _, _ = qwen3.forward(tiny_params, TINY, toks)
        lsm = np.asarray(
            logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        )
        for i, (t, lp) in enumerate(zip(ids, lps)):
            want = float(lsm[0, len(prompt) - 1 + i, t])
            assert abs(lp - want) < 1e-3, f"token {i}: {lp} vs {want}"
            assert lp <= 0.0
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_server_side_generate_stream(tiny_parts, tiny_params):
    """Streaming /generate: tokens arrive one ndjson line at a time and
    match both the final ids and the engine."""
    nodes = [
        _mk_node(40 + i, i, 2, parts=tiny_parts, bootstrap_idx=40)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        prompt = PREFIX + [4, 9]
        expected = engine.generate(prompt, 5)
        streamed = []
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(40))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            got = await c.generate_server_side_stream(
                prompt, streamed.append, max_new_tokens=5
            )
        assert got == expected
        assert streamed == expected  # every token arrived incrementally
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_server_side_generate_concurrent_sampling(tiny_parts, tiny_params):
    """Two concurrent /generate requests with DIFFERENT sampling configs:
    the node's shared self-client must not let one request's sampling bleed
    into the other (per-call sampling pass-through)."""
    nodes = [
        _mk_node(60 + i, i, 2, parts=tiny_parts, bootstrap_idx=60)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine_g = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        hot = SamplingConfig(temperature=0.9, top_k=5, top_p=0.9)
        prompt = PREFIX + [4, 9]
        expected_greedy = engine_g.generate(prompt, 6)

        from inferd_tpu.client.base import sample_np

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(60))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            pairs = await asyncio.gather(
                c.generate_server_side(prompt, max_new_tokens=6, seed=0),
                c.generate_server_side(
                    prompt, max_new_tokens=6, seed=3, sampling=hot
                ),
                c.generate_server_side(prompt, max_new_tokens=6, seed=0),
            )
        greedy1, sampled, greedy2 = pairs
        assert greedy1 == expected_greedy == greedy2
        # the hot request sampled from ITS config: reproduce via the client
        # sampler over a locally-driven session would need logits; instead
        # assert determinism of the hot path itself (same seed -> same out)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(60))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            sampled2 = await c.generate_server_side(
                prompt, max_new_tokens=6, seed=3, sampling=hot
            )
        assert sampled == sampled2
    finally:
        for n in nodes:
            await n.stop()


@pytest.mark.asyncio
async def test_speculative_server_side_generate(tiny_params):
    """--spec-draft-layers: greedy /generate takes the self-drafting
    propose/verify path and stays token-exact with the plain engine."""
    from inferd_tpu.parallel.stages import Manifest, split_and_save
    import tempfile

    work = tempfile.mkdtemp(prefix="prefix_spec_")
    split_and_save(tiny_params, TINY, Manifest.even_split("tiny", 1), work)
    info = NodeInfo(
        name="sp0", host="127.0.0.1", port=PORTS.http(70),
        stage=0, num_stages=1, capacity=4, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(70), bootstrap=[], host="127.0.0.1",
        gossip_period_s=0.05, ttl_s=1.5,
    )
    node = Node(
        info, TINY, work, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, spec_draft_layers=2, spec_k=3,
    )
    await node.start()
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        prompt = [3, 7, 11, 19, 5]
        expected = engine.generate(prompt, 8)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(70))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            resp = await c._post(
                "/generate",
                {"prompt_ids": prompt, "max_new_tokens": 8,
                 "sampling": {"temperature": 0.0}},
            )
        assert resp["speculative"] is True
        assert 0.0 <= resp["draft_acceptance"] <= 1.0
        assert [int(t) for t in resp["ids"]] == expected
        assert node.metrics.snapshot()["counters"].get("generate.speculative", 0) >= 1
        # logprobs + top-N ride the speculative path (the verify chunk's
        # TARGET logits) and match the plain engine exactly
        elps: list = []
        etops: list = []
        engine.generate(
            prompt, 8, logprob_sink=elps, top_n=3, top_sink=etops
        )
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(70))], sampling=GREEDY, timeout_s=60.0
        ) as c:
            resp_lp = await c._post(
                "/generate",
                {"prompt_ids": prompt, "max_new_tokens": 8,
                 "logprobs": True, "top_logprobs": 3,
                 "sampling": {"temperature": 0.0}},
            )
        assert resp_lp["speculative"] is True
        np.testing.assert_allclose(resp_lp["logprobs"], elps, atol=1e-3, rtol=1e-4)
        for (ti, tl), (ei, el) in zip(resp_lp["top_logprobs"], etops):
            assert [int(x) for x in ti] == list(ei)
            np.testing.assert_allclose(tl, el, atol=1e-3, rtol=1e-4)
        # sampled requests take the rejection-sampled speculative engine
        # (one engine per sampling config, LRU-capped) — the response says
        # so and carries the acceptance rate; /stats accumulates the
        # production counters
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(70))], sampling=GREEDY, timeout_s=120.0
        ) as c:
            resp2 = await c._post(
                "/generate",
                {"prompt_ids": prompt, "max_new_tokens": 4, "seed": 1,
                 "sampling": {"temperature": 0.8}},
            )
        assert resp2["speculative"] is True
        assert 0.0 <= resp2["spec_accept_rate"] <= 1.0
        assert len(resp2["ids"]) == 4
        snap = node.metrics.snapshot()["counters"]
        assert snap.get("spec.proposed", 0) > 0
        assert snap.get("spec.accepted", 0) <= snap.get("spec.proposed", 0)
        # sampled + logprobs falls back to the regular loop (the rejection
        # step has no per-token logprob trail)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(70))], sampling=GREEDY, timeout_s=120.0
        ) as c:
            resp3 = await c._post(
                "/generate",
                {"prompt_ids": prompt, "max_new_tokens": 4, "seed": 1,
                 "logprobs": True, "sampling": {"temperature": 0.8}},
            )
        assert "speculative" not in resp3
        assert len(resp3["logprobs"]) == len(resp3["ids"])
    finally:
        await node.stop()


@pytest.mark.asyncio
@pytest.mark.slow
async def test_speculative_sampled_distribution_over_http(tiny_params):
    """Sampled /generate through the speculative path is DISTRIBUTED as
    target-only warped sampling (the rejection scheme's guarantee, pinned
    end-to-end through the HTTP surface): the empirical first-token
    distribution over many seeds matches the target's warped probabilities
    in total variation, and a fixed seed is deterministic. (The rejection
    step's own exactness is pinned at the engine level by
    test_speculative.test_sampled_distribution_matches_target; this
    asserts the serving wiring — the per-request sampling config must
    reach the engine's warp.)"""
    from inferd_tpu.parallel.stages import Manifest, split_and_save
    from inferd_tpu.core import sampling as samplib
    import jax
    import jax.numpy as jnp
    import tempfile

    work = tempfile.mkdtemp(prefix="prefix_spec_tv_")
    split_and_save(tiny_params, TINY, Manifest.even_split("tiny", 1), work)
    info = NodeInfo(
        name="sptv0", host="127.0.0.1", port=PORTS.http(71),
        stage=0, num_stages=1, capacity=4, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(71), bootstrap=[], host="127.0.0.1",
        gossip_period_s=0.05, ttl_s=1.5,
    )
    node = Node(
        info, TINY, work, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, spec_draft_layers=2, spec_k=3,
    )
    await node.start()
    try:
        prompt = [3, 17, 42, 9]
        temp, top_k, top_p = 1.2, 5, 0.9
        # the target's warped next-token distribution after the prompt
        logits, _, _ = qwen3.forward(
            tiny_params, TINY, jnp.asarray([prompt], jnp.int32)
        )
        want = np.asarray(
            jax.nn.softmax(
                samplib.warped_logits(
                    logits[:, len(prompt) - 1], temp, top_k, top_p
                )
            )
        )[0]

        counts = np.zeros(TINY.vocab_size)
        trials = 250
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(71))], sampling=GREEDY, timeout_s=120.0
        ) as c:
            for seed in range(trials):
                r = await c._post(
                    "/generate",
                    {"prompt_ids": prompt, "max_new_tokens": 1, "seed": seed,
                     "sampling": {"temperature": temp, "top_k": top_k,
                                  "top_p": top_p}},
                )
                assert r["speculative"] is True
                counts[int(r["ids"][0])] += 1
            tv = 0.5 * np.abs(counts / trials - want).sum()
            assert tv < 0.12, f"TV distance {tv}"

            # fixed seed => identical stream (deterministic replay)
            a = await c._post(
                "/generate",
                {"prompt_ids": prompt, "max_new_tokens": 6, "seed": 7,
                 "sampling": {"temperature": temp, "top_k": top_k,
                              "top_p": top_p}},
            )
            b = await c._post(
                "/generate",
                {"prompt_ids": prompt, "max_new_tokens": 6, "seed": 7,
                 "sampling": {"temperature": temp, "top_k": top_k,
                              "top_p": top_p}},
            )
            assert a["ids"] == b["ids"] and len(a["ids"]) == 6
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_batched_node_fork_e2e(tiny_params):
    """Pinned client against a --batch-lanes node: the fork lands in a
    lane (BatchedEngine.fork_lane) and generations match the engine."""
    from inferd_tpu.parallel.stages import Manifest, split_and_save
    import tempfile

    work = tempfile.mkdtemp(prefix="prefix_batch_")
    split_and_save(tiny_params, TINY, Manifest.even_split("tiny", 1), work)
    info = NodeInfo(
        name="pb0", host="127.0.0.1", port=PORTS.http(50),
        stage=0, num_stages=1, capacity=4, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(50), bootstrap=[], host="127.0.0.1",
        gossip_period_s=0.05, ttl_s=1.5,
    )
    node = Node(
        info, TINY, work, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, batch_lanes=3,
    )
    await node.start()
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        prompt = PREFIX + [4, 9]
        expected = engine.generate(prompt, 5)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(50))], sampling=GREEDY
        ) as c:
            await c.pin_prefix(PREFIX)
            got = [await c.generate_ids(prompt, 5) for _ in range(2)]
        assert got == [expected, expected]
        assert node.metrics.snapshot()["counters"].get("fork.ok", 0) >= 2
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_chain_fork_e2e(tiny_parts, tiny_params):
    """ChainClient (hub-and-spoke, relay=False) forks every stage directly."""
    nodes = [
        _mk_node(20 + i, i, 2, parts=tiny_parts, bootstrap_idx=20)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, tiny_params, max_len=64, sampling_cfg=GREEDY)
        prompt = PREFIX + [4, 9]
        expected = engine.generate(prompt, 5)
        async with ChainClient(
            [("127.0.0.1", PORTS.http(20)), ("127.0.0.1", PORTS.http(21))], sampling=GREEDY
        ) as c:
            await c.pin_prefix(PREFIX)
            got = await c.generate_ids(prompt, 5)
        assert got == expected
        for n in nodes:
            snap = n.metrics.snapshot()
            assert snap["counters"].get("fork.ok", 0) >= 1
    finally:
        for n in nodes:
            await n.stop()
