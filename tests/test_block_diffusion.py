"""Generation by diffusion over blocks on the lane path at `tiny-sdar`: the
block-causal mask, BatchedEngine._block_step (denoising passes and the
commit in one dispatch), the `block` call of the lane executor, the client
loop and /generate. Seeded random weights, float32 on both sides; the plain
reference is the benchmark's own, `benchmark/references/sdar.py`, loaded
from its file."""

import argparse
import asyncio
import dataclasses
import importlib.util
import json
import os
import time

import aiohttp
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.client.base import GenerationClient
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import SamplingConfig, get_config
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.batch_executor import BatchedExecutor
from inferd_tpu.runtime.node import Node, NodeInfo

CFG = get_config("tiny-sdar")
CONFIDENT = dataclasses.replace(CFG, remask="low_confidence")
BLK = CFG.block_length
GREEDY = SamplingConfig(temperature=0.0)
from conftest import port_block  # noqa: E402

PORTS, HOST = port_block(__file__), "127.0.0.1"


def _load_reference():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "references", "sdar.py")
    spec = importlib.util.spec_from_file_location("sdar_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()
# what the reference reads of a configuration's file, for the tiny preset
REF_CONFIG = {
    "num_hidden_layers": CFG.num_layers, "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.num_kv_heads, "head_dim": CFG.head_dim,
    "rms_norm_eps": CFG.rms_norm_eps, "rope_theta": CFG.rope_theta,
    "num_experts": CFG.num_experts, "num_experts_per_tok": CFG.num_experts_per_tok,
    "norm_topk_prob": CFG.norm_topk_prob, "tie_word_embeddings": CFG.tie_word_embeddings,
    "block_length": BLK, "denoising_steps": CFG.denoising_steps,
    "mask_token_id": CFG.mask_token_id,
}


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the projections scaled up so that rows differ by far
    more than float32 rounding from one state of a block to the next."""
    p = qwen3.init_params(CFG, jax.random.PRNGKey(34))
    grow = lambda tree: {k: v * 6 if v.ndim > 2 else v for k, v in tree.items()}  # noqa: E731
    return dict(p, layers=grow(p["layers"]), lm_head=p["lm_head"] * 6)


def prompt_of(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CFG.vocab_size - 1, n)]


class Direct(GenerationClient):
    """The generation loop over one executor, its hops plain calls; keeps
    every block reply (the pass that made each place known)."""

    def __init__(self, ex, prefill_chunk=8):
        super().__init__(sampling=GREEDY, prefill_chunk=prefill_chunk)
        self.ex, self._block, self.replies, self.calls = ex, ex.cfg.block_length, [], []

    async def _forward(self, session_id, tokens, start_pos, **extra):
        payload = {"tokens": np.asarray([tokens], np.int32), "start_pos": start_pos,
                   "real_len": len(tokens), **extra}
        res = await asyncio.to_thread(self.ex.process, session_id, payload)
        if "block" in extra:
            self.replies.append(res)
            self.calls.append(extra["block"])
        return res

    async def _end_session(self, session_id):
        self.ex.end_session(session_id)


def generate(ex, prompt, new, top_n=0, eos=None, **kw):
    """(tokens, top lists, the pass that made each token known)."""
    async def go():
        c = Direct(ex, **kw)
        tops = [] if top_n else None
        out = await c._generate_once(list(prompt), new, eos, 0, GREEDY, None, None, top_n, tops)
        head = len(prompt) % BLK
        order = [o for i, r in enumerate(c.replies) for o in r["order"][head if i == 0 else 0:]]
        return out, tops, order[: len(out)]

    return asyncio.run(go())


# ---------------------------------------------------------------------------
# (e) the mask
# ---------------------------------------------------------------------------


def test_block_length_1_is_the_causal_mask():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.integers(0, 40, (3, 6)))
    kpos = jnp.asarray(rng.integers(0, 40, (3, 12)))
    valid = jnp.asarray(rng.integers(1, 13, (3,)))
    causal = get_config("tiny-moe")
    assert qwen3.visible_until(causal, q) is q  # nothing traced: the programs are the parent's
    want = np.asarray(qwen3._causal_mask(12, valid, kpos, q))
    got = np.asarray(qwen3._causal_mask(12, valid, kpos, qwen3.visible_until(CFG, q)))
    by_hand = (np.arange(12)[None, None, :] < np.asarray(valid)[:, None, None]) & (
        np.asarray(kpos)[:, None, :] // BLK <= np.asarray(q)[:, :, None] // BLK)
    assert (got == by_hand).all() and (got >= want).all() and (got != want).any()


# ---------------------------------------------------------------------------
# (a) prefill in chunks + block steps against the reference, both orders
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("left", range(BLK))
@pytest.mark.parametrize("cfg", [CFG, CONFIDENT], ids=["sequential", "low_confidence"])
def test_lane_path_equals_the_reference(params, cfg, left):
    """A prompt of 20 + `left` tokens (three prefill chunks of at most 8, a
    first block opened by `left` prompt tokens) and an answer that ends on a
    block's end. The reference is handed the order the program reports."""
    ex = BatchedExecutor(cfg, params, lanes=2, max_len=64)
    prompt, new = prompt_of(20 + left, seed=left), 12 - left
    toks, tops, order = generate(ex, prompt, new, top_n=3)
    assert len(toks) == new and len(tops) == new
    if cfg is CFG:
        assert order == REF.leftmost_order(len(prompt), new, BLK, CFG.denoising_steps)
        ref = REF.logprobs(params, prompt + toks[:-1], new, REF_CONFIG)
    else:
        # the answer ends its block, so one more (unknown) token opens the
        # next block and the reference is given every token the order needs
        ref = REF.logprobs(params, prompt + toks, new + 1, REF_CONFIG, order=order + [0])[:new]
    for j, (ids, lps) in enumerate(tops):
        assert toks[j] == ids[0] == int(ref[j].argmax())
        np.testing.assert_allclose(lps, ref[j][ids], atol=2e-4)
    # a pass makes at most B / steps places known, and every place is made known
    assert set(order) <= set(range(CFG.denoising_steps))
    st = ex.stats()
    blocks = -(-(left + new) // BLK)
    assert st["diffusion"] == {
        "block_steps": blocks, "lane_passes": 3 * blocks, "tokens": BLK * blocks - left,
        "rows": 3 * BLK * blocks, "hops": blocks}
    # the first block hop rode, every later one found its block run; none in vain
    assert st["ahead_claimed"] == st["ahead_rows"] == blocks - 1 and st["ahead_dropped"] == 0
    assert st["moe"]["steps"] == 3 * blocks
    assert st["moe"]["assignments"] == 3 * blocks * BLK * CFG.num_layers * CFG.num_experts_per_tok


def test_the_confidence_order_is_not_the_leftmost(params):
    ex = BatchedExecutor(CONFIDENT, params, lanes=2, max_len=64)
    orders = [generate(ex, prompt_of(8, seed=s), 16)[2] for s in range(4)]
    assert any(o != REF.leftmost_order(8, 16, BLK, CFG.denoising_steps) for o in orders)


# ---------------------------------------------------------------------------
# (b) lanes in different blocks and one in prefill ride one step
# ---------------------------------------------------------------------------


def test_cobatched_lanes_get_what_they_get_alone(params):
    jobs = [(prompt_of(9 + 4 * i + i % 3, seed=10 + i), 24) for i in range(4)]
    alone = [generate(BatchedExecutor(CFG, params, lanes=4, max_len=128), p, n)[0]
             for p, n in jobs]
    ex = BatchedExecutor(CFG, params, lanes=4, max_len=128, window_ms=20.0)
    step = ex.engine._block_step

    def slow_step(*a, **kw):  # a block step outlasts the stagger: the sessions overlap
        time.sleep(0.03)
        return step(*a, **kw)

    ex.engine._block_step = slow_step

    async def together():
        async def one(p, n, delay):
            await asyncio.sleep(delay)  # staggered: a prefill cuts into the others' blocks
            c = Direct(ex)
            return await c._generate_once(list(p), n, None, 0, GREEDY, None, None, 0, None)

        return await asyncio.gather(*(one(p, n, 0.05 * i) for i, (p, n) in enumerate(jobs)))

    assert asyncio.run(together()) == alone
    st = ex.stats()
    assert st["batched_tokens"] > st["batched_steps"]  # some step carried several lanes
    assert st["diffusion"]["block_steps"] == st["batched_steps"]
    assert st["diffusion"]["lane_passes"] == 3 * st["batched_tokens"]


# ---------------------------------------------------------------------------
# (c) exactly what was asked; (d) the mask token's id is a token like any
# ---------------------------------------------------------------------------


def test_max_new_tokens_and_eos_cut_inside_a_block(params):
    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)
    prompt = prompt_of(10, seed=3)
    full = generate(ex, prompt, 12)[0]
    for n in (1, 5, 7):
        assert generate(ex, prompt, n)[0] == full[:n]
    stop = next(i for i in range(2, 12) if full[i] not in full[:i] and i % BLK != BLK - 1)
    assert generate(ex, prompt, 12, eos=full[stop])[0] == full[: stop + 1]


@pytest.mark.parametrize("n_prompt,new", [(10, 1), (10, 2), (10, 3), (8, 9), (11, 13), (3, 6)])
def test_the_loop_promises_exactly_the_block_hops_that_follow(params, n_prompt, new):
    """`ahead` of a block hop: the hops after it unless `eos` ends the answer,
    from `max_new_tokens`, what is out and the block (an answer that ends
    inside a block, on its end, in the first one; a prompt shorter than a
    block): the executor runs those blocks ahead and none past them."""
    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)

    async def go():
        c = Direct(ex)
        out = await c._generate_once(prompt_of(n_prompt, seed=2), new, 7, 0, GREEDY, None, None, 0, None)
        return out, c.calls

    out, calls = asyncio.run(go())
    hops = -(-(n_prompt % BLK + new) // BLK)
    assert 7 not in out and len(out) == new  # the stream ran to its budget
    assert [c["ahead"] for c in calls] == list(range(hops - 1, -1, -1))
    assert all(c["eos"] == 7 for c in calls) and calls[0]["known"] == n_prompt % BLK
    st = ex.stats()
    assert st["diffusion"]["hops"] == st["diffusion"]["block_steps"] == hops
    assert st["ahead_claimed"] == st["ahead_rows"] == hops - 1 and st["ahead_dropped"] == 0


def test_the_mask_tokens_id_is_served_as_any_token(params):
    """Places are masked by the `known` array: a prompt that holds the mask
    token's id, and an answer forced to hold it, change nothing but the
    embeddings that are read."""
    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)
    prompt = prompt_of(10, seed=4)
    prompt[3] = prompt[9] = CFG.mask_token_id  # one in a whole block, one opening the first
    toks, tops, _ = generate(ex, prompt, 8, top_n=2)
    ref = REF.logprobs(params, prompt + toks[:-1], 8, REF_CONFIG)
    for j, (ids, lps) in enumerate(tops):
        np.testing.assert_allclose(lps, ref[j][ids], atol=2e-4)
    # an answer that holds the id: its column of the head is the third
    # token's, half again as large, so it wins wherever that token won
    head = params["lm_head"]
    forced = dict(params, lm_head=head.at[:, CFG.mask_token_id].set(1.5 * head[:, toks[2]]))
    ex = BatchedExecutor(CFG, forced, lanes=2, max_len=64)
    toks, tops, _ = generate(ex, prompt, 8, top_n=2)
    assert CFG.mask_token_id in toks
    ref = REF.logprobs(forced, prompt + toks[:-1], 8, REF_CONFIG)
    for j, (ids, lps) in enumerate(tops):
        assert toks[j] == int(ref[j].argmax())
        np.testing.assert_allclose(lps, ref[j][ids], atol=2e-3, rtol=1e-4)


# ---------------------------------------------------------------------------
# the executor's contract: kinds, replay, overflow, sampling
# ---------------------------------------------------------------------------


def _block(start, known=0, toks=None, **more):
    return {"tokens": [toks or [0] * BLK], "start_pos": start, "real_len": BLK,
            "block": {"known": known, **more}}


def test_calls_that_are_no_whole_block_are_refused(params):
    ex = BatchedExecutor(CFG, params, lanes=2, max_len=16)
    ex.process("s", {"tokens": [[1] * 8], "start_pos": 0, "real_len": 8, "want_logits": False})
    for bad in ({"tokens": [[1]], "start_pos": 8, "real_len": 1},  # a decode step
                {"tokens": [[1, 2]], "start_pos": 8, "real_len": 2},  # half a block
                _block(6), dict(_block(8), real_len=2)):
        with pytest.raises(ValueError, match="generated by blocks of 4"):
            ex.process("s", bad)
    with pytest.raises(ValueError, match="known 4 outside"):
        ex.process("s", _block(8, known=4))
    first = ex.process("s", _block(8, top_logprobs=2))
    # a replayed block call rolls the whole block back and gives the same
    assert ex.process("s", _block(8, top_logprobs=2)) == first
    assert ex.engine.lengths[ex._sessions["s"]] == 12
    ex.process("s", _block(12))
    with pytest.raises(BufferError, match="KV overflow"):
        ex.process("s", _block(16))
    with pytest.raises(ValueError, match="not generated by blocks"):
        BatchedExecutor(get_config("tiny-moe"), qwen3.init_params(
            get_config("tiny-moe"), jax.random.PRNGKey(0)), lanes=2, max_len=16).process(
                "s", _block(0))
    assert ex.fork_session("child", "s", 8) is False  # no fork of such a model yet


def test_sampling_runs_on_the_device_under_the_sessions_key(params):
    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)
    warm = {"temperature": 1.5, "top_k": 0, "top_p": 1.0, "min_p": 0.0}

    def run(seed):
        ex.process("s", {"tokens": [[5] * 4], "start_pos": 0, "real_len": 4, "want_logits": False})
        a = ex.process("s", _block(4, sampling=warm, seed=seed))
        b = ex.process("s", _block(8, sampling=warm, key=a["key"]))
        return a["tokens"], b["tokens"], b["key"]

    assert run(1) == run(1) and run(1) != run(2)
    greedy = ex.process("s", _block(12))
    assert greedy["key"] == [0, 0] or len(greedy["key"]) == 2  # greedy reads no key


# ---------------------------------------------------------------------------
# (f) run_node's refusals
# ---------------------------------------------------------------------------

REFUSED = {
    "mesh": (["--mesh", "pp=2"], "--mesh"),
    "stage-lanes": (["--stage-lanes", "2"], "--stage-lanes"),
    "paged-kv": (["--batch-lanes", "2", "--paged-kv", "16"], "--paged-kv"),
    "spec": (["--batch-lanes", "2", "--spec-draft-layers", "1"], "--spec-draft-layers"),
    "lora": (["--batch-lanes", "2", "--lora", "/nowhere"], "--lora"),
    "adapters": (["--batch-lanes", "2", "--adapters", "/nowhere"], "--adapters"),
    "standby-repl": (["--batch-lanes", "2", "--standby-repl"], "--standby-repl"),
    "no-lanes": ([], "serving without --batch-lanes"),
    "stages": (["--num-stages", "2"], "a manifest of several stages"),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_every_path_but_the_lanes(path, tmp_path):
    from inferd_tpu.tools import run_node

    flags, names = REFUSED[path]
    args = run_node.build_parser().parse_args(
        ["--model", "tiny-sdar", "--parts", str(tmp_path), "--device", "cpu", *flags])
    with pytest.raises(SystemExit, match="tiny-sdar cannot be served with") as e:
        asyncio.run(run_node._run(args))
    assert names in str(e.value)


def test_run_node_lets_the_lane_path_through():
    from inferd_tpu.tools import run_node

    args = argparse.Namespace(
        mesh="", stage_lanes=0, paged_kv=0, quant="int8", spec_draft_layers=0, lora="",
        adapters="", standby_repl=False, backend="qwen3", batch_lanes=4)
    run_node.check_servable(CFG, args)  # --quant and --kv-dtype stay open


@pytest.mark.parametrize("control", ["int8", "float8_e4m3fn"])
def test_the_8_bit_controls_serve_blocks(params, control):
    from inferd_tpu.ops import quant

    cfg, p = CFG, params
    if control == "int8":
        p = quant.apply_quant_mode("int8", params)
    else:
        cfg = dataclasses.replace(CFG, kv_dtype=control)
    toks, tops, _ = generate(BatchedExecutor(cfg, p, lanes=2, max_len=64), prompt_of(10), 8, top_n=2)
    ref = REF.logprobs(params, prompt_of(10) + toks[:-1], 8, REF_CONFIG)
    worst = max(abs(lp - ref[j][i]) for j, (ids, lps) in enumerate(tops) for i, lp in zip(ids, lps))
    assert 2e-4 < worst < 1.0  # served, and a precision below the float32 program


# ---------------------------------------------------------------------------
# /generate and an outside client on a node
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def parts(tmp_path_factory, params):
    d = tmp_path_factory.mktemp("sdar-parts")
    split_and_save(params, CFG, Manifest.even_split("tiny-sdar", 1), str(d))
    return str(d)


@pytest.mark.asyncio
async def test_generate_streams_blocks_and_tells_a_client_of_them(parts, params):
    info = NodeInfo(name="bd0", host=HOST, port=PORTS.http(), stage=0, num_stages=1, capacity=8,
                    model_name="tiny-sdar")
    dht = SwarmDHT(info.node_id, PORTS.gossip(), bootstrap=[], host=HOST,
                   gossip_period_s=0.05, ttl_s=5.0)
    node = Node(info, CFG, parts, dht, backend="qwen3", max_len=64, batch_lanes=4,
                rebalance_period_s=600.0)
    await node.start()
    prompt, new = prompt_of(10, seed=5), 7
    try:
        for _ in range(200):  # the warm-up: a whole block in, a step for each top-n variant
            if any(e["type"].startswith("executor.warmup_") for e in node.journal.events()):
                break
            await asyncio.sleep(0.05)
        assert any(e["type"] == "executor.warmup_ok" for e in node.journal.events())
        # the warm-up sent each top-n width's hop with a promise and the hop it
        # promised: the block step fed from the host and from the device, and
        # the program that hands the key over, are compiled
        from inferd_tpu.core import sampling as samplib
        warm = node.executor.stats()
        assert warm["ahead_claimed"] == warm["ahead_rows"] == 2 and warm["diffusion"]["hops"] == 4
        programs = (node.executor.engine._block_step, samplib.ahead_block_keys)
        compiled = [f._cache_size() for f in programs]
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://{HOST}:{PORTS.http()}/stats") as r:
                stats = await r.json()
            assert stats["model"] == {"name": "tiny-sdar", "block_length": BLK}
            body = {"prompt_ids": prompt, "max_new_tokens": new, "stream": True, "logprobs": True,
                    "top_logprobs": 3, "sampling": {"temperature": 0.0, "top_k": 0, "top_p": 1.0}}
            lines = []
            async with http.post(f"http://{HOST}:{PORTS.http()}/generate", data=wire.pack(body)) as r:
                assert r.status == 200
                async for raw in r.content:
                    lines.append(json.loads(raw))
            async with http.post(f"http://{HOST}:{PORTS.http()}/generate", data=wire.pack(
                    dict(body, pin_prefix_len=4))) as r:
                assert r.status == 400 and b"pinned prefix" in await r.read()
        toks = [m["t"] for m in lines if "t" in m]
        assert lines[-1]["done"] and lines[-1]["ids"] == toks and len(toks) == new
        ref = REF.logprobs(params, prompt + toks[:-1], new, REF_CONFIG)
        for j, m in enumerate(m for m in lines if "t" in m):
            ids, lps = m["top"]
            assert m["t"] == ids[0] and abs(m["lp"] - lps[0]) < 1e-6
            np.testing.assert_allclose(lps, ref[j][ids], atol=2e-4)
        async with SwarmClient([(HOST, PORTS.http())], sampling=GREEDY) as c:
            assert await c.generate_ids(prompt, new) == toks  # it asked /stats for the block
            assert c._block == BLK
            with pytest.raises(ValueError, match="pinned prefix"):
                await c.pin_prefix(prompt[:4])
        assert [f._cache_size() for f in programs] == compiled  # nothing compiled since
        ran = node.executor.stats()
        hops = -(-(len(prompt) % BLK + new) // BLK)
        assert ran["diffusion"]["hops"] - warm["diffusion"]["hops"] == 2 * hops
        assert ran["ahead_claimed"] - warm["ahead_claimed"] == 2 * (hops - 1)
        assert ran["ahead_dropped"] == 0
        kinds = {(s["name"], (s.get("attrs") or {}).get("kind")) for s in node.tracer.spans()}
        assert {("compute", "block"), ("compute", "prefill"), ("device", "block"),
                ("lock_wait", "block")} <= kinds
        dev = next(s for s in node.tracer.spans()
                   if s["name"] == "device" and s["attrs"]["kind"] == "block")
        assert dev["attrs"]["passes"] == 3 and dev["attrs"]["rows"] == 3 * BLK * dev["attrs"]["cobatch"]
    finally:
        await node.stop()
