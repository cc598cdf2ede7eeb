"""A decode hop is answered with its token (docs/SERVING.md "A decode hop's
ask"): the generation loop asks every decode hop for its token, the decode
step of a whole-model executor (lanes, paged lanes, the mesh) chooses it
after the head under that hop's OWN sampling (core.sampling.sample_rows),
and one small array leaves the device. What is held here:

- the row sampler against core.sampling.sample, row by row, one call for a
  mix of configs; what it covers; the packing there and back;
- one parser for every call that carries a sampling ask;
- through /generate on a lane node, a mesh node and a latent-attention
  node: the greedy stream, its log-probabilities and top-8 are those of the
  loop sampling from the logits reply; a seeded sampled generation repeats
  itself, also across a mid-generation restart; the step's copy-out stays
  under 1 KB a lane; the counters of /stats `executor`;
- two lanes with two sampling configs and one raw /forward ride ONE
  dispatch and compile nothing new; a row outside the device form falls
  back to its logits and is counted; a stage executor's logits reply is
  still sampled by the loop."""

import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.client.base import logprob_np, top_logprobs_np
from inferd_tpu.client.chain_client import ChainClient
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig, get_config
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core import sampling as samplib
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import executor as execlib
from inferd_tpu.runtime.node import Node, NodeInfo
from inferd_tpu.utils import retry as retrylib

from conftest import port_block  # noqa: E402

PORTS, HOST = port_block(__file__), "127.0.0.1"
GREEDY = SamplingConfig(temperature=0.0)
SAMPLED = SamplingConfig(temperature=0.8, top_k=20, top_p=0.95)
PROMPT = [3, 7, 11, 19, 23, 29, 31, 37]
NEW, TOP = 10, 8
LIMIT_S = 300  # a topology's whole service, compiles included

# -- the row sampler -----------------------------------------------------------

CONFIGS = {
    "greedy": (0.0, 0, 1.0, 0.0),
    "greedy_with_filters": (0.0, 20, 0.9, 0.0),  # greedy reads no filter
    "temperature_only": (0.8, 0, 1.0, 0.0),
    "default_0.6_20_0.95": (0.6, 20, 0.95, 0.0),
    "min_p": (0.7, 0, 1.0, 0.1),
    "top_k_and_min_p": (1.0, 5, 1.0, 0.05),
    "top_k_at_the_width": (1.3, samplib.ROW_CANDIDATES, 0.9, 0.0),
}


@pytest.fixture(scope="module")
def rows():
    """Every config on eight rows of one [L, V] step, sampled in ONE call."""
    rng = np.random.default_rng(0)
    per = 8
    logits = jnp.asarray(rng.normal(size=(per * len(CONFIGS), 512)) * 2, jnp.float32)
    ask = samplib.RowAsk.greedy(len(logits))
    for i in range(len(logits)):
        ask.put(i, list(CONFIGS.values())[i // per], execlib.root_key(i))
    fn = jax.jit(samplib.sample_rows)
    w = ask.warp
    tok, keys = fn(logits, ask.keys, w[:, 0], w[:, 1].astype(np.int32), w[:, 2], w[:, 3])
    return {"logits": logits, "tok": np.asarray(tok), "keys": np.asarray(keys),
            "per": per, "compiled": fn._cache_size()}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_a_row_draws_what_sample_draws_under_its_own_config(rows, name):
    cfg, at = CONFIGS[name], list(CONFIGS).index(name) * rows["per"]
    want_tok, want_keys = [], []
    for i in range(at, at + rows["per"]):
        key = jax.random.PRNGKey(i)
        nxt, sub = jax.random.split(key) if cfg[0] > 0 else (key, key)
        want_tok.append(samplib.sample(rows["logits"][i][None], sub, *cfg)[0])
        want_keys.append(nxt)  # greedy: as it went in
    assert rows["tok"][at:at + rows["per"]].tolist() == np.asarray(jnp.stack(want_tok)).tolist()
    assert np.array_equal(rows["keys"][at:at + rows["per"]], np.asarray(jnp.stack(want_keys)))
    assert rows["compiled"] == 1  # the mix of seven configs was one program


@pytest.mark.parametrize("cfg, covered", [
    ((0.0, 0, 1.0, 0.0), True),
    ((0.0, 500, 0.5, 0.0), True),  # greedy reads no filter
    ((0.6, 20, 0.95, 0.0), True),
    ((0.9, samplib.ROW_CANDIDATES, 1.0, 0.2), True),
    ((0.9, 0, 1.0, 0.2), True),  # temperature and min-p over the whole row
    ((0.9, 0, 0.9, 0.0), False),  # top-p alone sorts the whole row
    ((0.9, samplib.ROW_CANDIDATES + 1, 1.0, 0.0), False),
])
def test_what_the_row_sampler_covers(cfg, covered):
    assert samplib.rows_cover(*cfg) is covered
    ask = execlib.parse_decode_ask({"sampling": dict(zip(
        ("temperature", "top_k", "top_p", "min_p"), cfg))})
    assert (ask is not None) is covered


@pytest.mark.parametrize("top_n, experts", [(0, False), (8, False), (0, True), (64, True)])
def test_a_step_packs_what_the_host_reads_into_one_array(top_n, experts):
    rng = np.random.default_rng(1)
    lanes, layers, k = 5, 3, 2
    logits = jnp.asarray(rng.normal(size=(lanes, 128)), jnp.float32)
    routed = jnp.asarray(rng.integers(0, 9, (layers, lanes, k)), jnp.int32) if experts else None
    ask = samplib.RowAsk.greedy(lanes)
    ask.put(2, (0.7, 10, 0.9, 0.0), np.asarray(jax.random.PRNGKey(7)))
    packed = np.asarray(samplib.choose_rows(logits, ask, top_n, routed))
    assert packed.dtype == np.int32 and packed.shape == (
        lanes, 3 + (1 + 2 * top_n if top_n else 0) + (layers * k if experts else 0))
    tok, keys, lp, ti, tl, back = samplib.unpack_rows(packed, top_n, k if experts else 0)
    w = ask.warp
    want_tok, want_keys = samplib.sample_rows(
        logits, ask.keys, w[:, 0], w[:, 1].astype(np.int32), w[:, 2], w[:, 3])
    assert np.array_equal(tok, want_tok) and np.array_equal(keys, want_keys)
    assert keys.dtype == np.uint32
    if top_n:
        want = samplib.logprob_topn(logits, want_tok, top_n)
        assert np.array_equal(lp, want[0]) and np.array_equal(ti, want[1])
        assert np.array_equal(tl, want[2]) and lp.dtype == tl.dtype == np.float32
    else:
        assert lp is None and ti is None and tl is None
    assert back is None if not experts else np.array_equal(back, routed)


def test_one_parser_reads_every_sampling_ask():
    ask = {"sampling": {"temperature": 0.7, "top_k": 12, "top_p": 0.9, "min_p": 0.05},
           "key": [5, 9], "logprobs": True}
    read = execlib.parse_ask(ask)
    assert read.sampling == (0.7, 12, 0.9, 0.05) and read.want == 1 and read.top_n == 8
    assert read.key.dtype == np.uint32 and read.key.tolist() == [5, 9]
    assert execlib.parse_kstep({"decode_steps": 2, **ask}, 8)["sampling"] == read.sampling
    assert execlib.parse_block({"block": {"known": 0, **ask}}, 4).sampling == read.sampling
    assert execlib.parse_decode_ask(ask).sampling == read.sampling
    assert execlib.parse_decode_ask({"tokens": [[1]]}) is None  # a raw /forward
    seeded = execlib.parse_ask({"seed": 3})
    assert seeded.sampling == (0.0, 0, 1.0, 0.0) and seeded.want == 0
    seeds = (0, 3, 2**31 - 1, -5)  # root_key is PRNGKey, made on the host
    assert np.array_equal(np.stack([execlib.root_key(s) for s in seeds]),
                          np.asarray(jnp.stack([jax.random.PRNGKey(s) for s in seeds])))
    assert np.array_equal(seeded.key, execlib.root_key(3))
    # more top log-probabilities than the widest variant: the logits reply
    assert execlib.parse_decode_ask({"sampling": {}, "top_logprobs": 65}) is None
    assert execlib.parse_ask({"top_logprobs": 9}).top_n == 64
    with pytest.raises(ValueError, match="min_p"):
        execlib.parse_ask({"sampling": {"temperature": 1.0, "min_p": 1.0}})


# -- through /generate -----------------------------------------------------------

TOPOLOGIES = {
    "lanes": (0, "tiny", {"batch_lanes": 3}),
    "mesh": (1, "tiny", {"mesh_plan": MeshPlan(pp=2), "mesh_slots": 3}),
    "latent": (2, "tiny-dsv2", {"batch_lanes": 3}),
}


def _cfg(model):
    return TINY if model == "tiny" else get_config(model)


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    out = {}
    for model in ("tiny", "tiny-dsv2"):
        d = tmp_path_factory.mktemp(f"ds-{model}")
        split_and_save(qwen3.init_params(_cfg(model), jax.random.PRNGKey(0)), _cfg(model),
                       Manifest.even_split(model, 1), str(d))
        out[model] = str(d)
    return out


def _node(idx, model, parts_dir, **kw):
    info = NodeInfo(name=f"ds{idx}", host=HOST, port=PORTS.http(idx), stage=0,
                    num_stages=1, capacity=8, model_name=model)
    dht = SwarmDHT(info.node_id, PORTS.gossip(idx), bootstrap=[], host=HOST,
                   gossip_period_s=0.05, ttl_s=5.0)
    return Node(info, _cfg(model), parts_dir, dht, backend="qwen3", max_len=64,
                rebalance_period_s=600.0, **kw)


async def _warm(node):
    await node.start()
    for _ in range(4800):  # the warm-up compiles what the hops run
        if any(e["type"].startswith("executor.warmup_") for e in node.journal.events()):
            return
        await asyncio.sleep(0.05)
    raise TimeoutError("no warm-up")


def _compiles(node):
    """Compiles of the decode step so far (a prompt's first chunk compiles
    its own bucket of the prefill program: not this file's)."""
    steps = ("_decode_logits", "_decode_logits_paged", "_step_raw_multi")
    return sum(e["type"] == "compile.begin" and e["attrs"]["name"].endswith(steps)
               for e in node.journal.events())


async def _generate(node, sampling, seed=0):
    lps, tops = [], []
    async with SwarmClient([(HOST, node.info.port)], sampling=sampling) as c:
        ids = await c.generate_server_side(
            PROMPT, NEW, seed=seed, logprob_sink=lps, top_logprobs=TOP, top_sink=tops)
    return {"ids": ids, "lps": lps, "tops": tops}


async def _serve(idx, model, kw, parts_dir):
    node = _node(idx, model, parts_dir, **kw)
    await _warm(node)
    ex, process = node.executor, node.executor.process
    backoff = retrylib.backoff_delay
    try:
        run = {"warm_compiles": _compiles(node), "stats0": ex.stats()}
        run["device"] = await _generate(node, GREEDY)
        run["stats1"] = ex.stats()
        run["spans"] = node.tracer.spans()

        def without_ask(session_id, payload):  # the loop forced onto the logits reply
            return process(session_id, {k: v for k, v in payload.items() if k != "sampling"})

        ex.process = without_ask
        ex.begin_hop = None  # no hop goes round `process` (the lanes' form for the loop)
        run["loop"] = await _generate(node, GREEDY)
        run["stats2"] = ex.stats()
        ex.process = process
        del ex.begin_hop
        run["sampled"] = [await _generate(node, SAMPLED, seed=11) for _ in range(2)]
        run["other_seed"] = await _generate(node, SAMPLED, seed=12)
        calls = []

        def failing_once(session_id, payload):
            calls.append(session_id)
            if len(calls) == 5:  # the prefill and three decode hops went through
                raise RuntimeError("injected compute failure")
            return process(session_id, payload)

        ex.process = failing_once
        ex.begin_hop = None
        retrylib.backoff_delay = lambda *a, **k: 0.0
        run["restarted"] = await _generate(node, SAMPLED, seed=11)
        run["sessions_tried"] = len(set(calls))
        ex.process = process
        del ex.begin_hop
        run["compiles"] = _compiles(node)
        run["stats3"] = ex.stats()
        return run
    finally:
        retrylib.backoff_delay = backoff
        await node.stop()


@pytest.fixture(scope="module")
def served(parts, devices8):
    cache = {}

    def of(topology):
        if topology not in cache:
            idx, model, kw = TOPOLOGIES[topology]
            cache[topology] = asyncio.run(asyncio.wait_for(
                _serve(idx, model, kw, parts[model]), LIMIT_S))
        return cache[topology]

    return of


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_the_greedy_stream_is_the_one_the_loop_samples_from_logits(served, topology):
    run = served(topology)
    dev, loop = run["device"], run["loop"]
    assert dev["ids"] == loop["ids"] and len(dev["ids"]) == NEW
    # the device path answered every decode hop with a token, the forced
    # one none: the first token is the prefill row's in both
    d1 = {k: run["stats1"][k] - run["stats0"][k] for k in ("sampled_rows", "logit_rows")}
    d2 = {k: run["stats2"][k] - run["stats1"][k] for k in ("sampled_rows", "logit_rows")}
    assert d1 == {"sampled_rows": NEW - 1, "logit_rows": 0}
    assert d2 == {"sampled_rows": 0, "logit_rows": NEW - 1}


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_log_probabilities_and_top_8_are_those_of_the_row(served, topology):
    """float32 log-softmax on the device against logprob_np /
    top_logprobs_np (float64 from the float32 row) in the loop."""
    run = served(topology)
    dev, loop = run["device"], run["loop"]
    assert len(dev["lps"]) == len(dev["tops"]) == NEW
    np.testing.assert_allclose(dev["lps"], loop["lps"], atol=1e-4)
    for (ids_d, lps_d), (ids_l, lps_l) in zip(dev["tops"], loop["tops"]):
        assert len(ids_d) == TOP and ids_d == ids_l
        np.testing.assert_allclose(lps_d, lps_l, atol=1e-4)
        assert lps_d == sorted(lps_d, reverse=True)
    for tok, lp, (ids, lps) in zip(dev["ids"], dev["lps"], dev["tops"]):
        assert ids[0] == tok and abs(lps[0] - lp) < 1e-6  # greedy: the top one


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_a_seeded_sampled_generation_repeats_itself(served, topology):
    run = served(topology)
    first, again = run["sampled"]
    assert first == again and len(first["ids"]) == NEW
    assert run["other_seed"]["ids"] != first["ids"]  # the seed is read
    assert first["ids"] != run["device"]["ids"]  # and it is no argmax


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_a_restarted_generation_re_emits_the_same_tokens(served, topology):
    """A retryable failure after three decode hops: the re-run carries the
    same seed, hence the same key chain."""
    run = served(topology)
    assert run["sessions_tried"] == 2
    assert run["restarted"] == run["sampled"][0]


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_a_step_of_asks_copies_out_under_1_kb_a_lane(served, topology):
    run = served(topology)
    by_id = {s["span"]: s for s in run["spans"]}
    began = min(s["t0"] for s in run["spans"] if s["name"] == "generate")
    mine = [s for s in run["spans"] if s["name"] == "copy_out" and s["t0"] >= began
            and by_id[s["parent"]]["attrs"].get("kind") == "decode"]
    assert len(mine) == NEW - 1
    # the loop still closes every token's timeline with a `sample` span
    # (obs.merge counts tokens by them): the first is numpy's, the rest say
    # where the token was chosen
    samples = [s for s in run["spans"] if s["name"] == "sample" and s["t0"] >= began
               and s["trace"] == by_id[mine[0]["parent"]]["trace"]]
    assert [(s.get("attrs") or {}).get("on") for s in sorted(samples, key=lambda s: s["t0"])] \
        == [None] + ["device"] * (NEW - 1)
    lanes = 3
    assert all(0 < s["attrs"]["bytes"] < 1024 * lanes for s in mine)
    # with the top-8: token, key, own log-probability, 8 ids, 8 values (and
    # the routed experts of a model that has them)
    assert all(s["attrs"]["bytes"] >= lanes * 4 * (3 + 1 + 2 * TOP) for s in mine)


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_served_generations_compile_nothing_after_the_warm_up(served, topology):
    """Greedy with top-8, the logits reply, a sampling config never seen,
    a restart: the warm-up compiled every variant they ran."""
    run = served(topology)
    # without and with top-8; on the lanes each counted once more where its
    # tokens and keys come from the device (a step run ahead of its hop): the
    # watch counts a new entry of the jit's call cache, the program is the
    # same executable and nothing is compiled for it. On the mesh such an
    # entry WOULD be a compile (inputs that lie on the mesh are another
    # program's), so every pass is fed one way (PipelinedEngine.dispatch_slots)
    assert run["compiles"] == run["warm_compiles"] == (2 if topology == "mesh" else 4)
    if topology == "latent":  # the experts rode the step's one array
        assert run["stats3"]["moe"]["steps"] > run["stats0"]["moe"]["steps"]


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_every_executor_runs_a_step_ahead_and_every_row_is_claimed(served, topology):
    """/generate promises its hops (`ahead`): the lane executor and the mesh
    executor ran every hop's row but the first before the hop arrived, and
    none in vain (no `eos` is sent)."""
    run = served(topology)
    d = {k: run["stats1"][k] - run["stats0"][k]
         for k in ("ahead_rows", "ahead_claimed", "ahead_dropped")}
    assert d == {"ahead_rows": NEW - 2, "ahead_claimed": NEW - 2, "ahead_dropped": 0}
    # the loop forced onto the logits reply promised nothing
    assert run["stats2"]["ahead_rows"] == run["stats1"]["ahead_rows"]
    end = run["stats3"]
    assert end["ahead_rows"] == end["ahead_claimed"] + end["ahead_dropped"]
    assert end["ahead_dropped"] <= 1  # the generation that was made to fail mid-way


# -- one dispatch a drain ----------------------------------------------------------


def _co_arrive(ex, lock, calls):
    """Every call of `calls` ({sid: payload}) submitted while `lock` keeps
    the flusher from the device; one drain takes them all."""
    out = {}

    def one(sid, payload):
        out[sid] = ex.process(sid, payload)

    threads = [threading.Thread(target=one, args=c) for c in calls.items()]
    lock.acquire()
    try:
        for t in threads:
            t.start()
        for _ in range(20000):
            if len(ex._batcher._pending) == len(calls):
                break
            time.sleep(0.001)
        time.sleep(0.05)
    finally:
        lock.release()
    for t in threads:
        t.join(timeout=120)
    assert len(out) == len(calls)
    return out


@pytest.mark.asyncio
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
async def test_any_mix_of_asks_rides_one_dispatch_and_compiles_nothing(parts, paged):
    """Two lanes with two sampling configs (one with log-probabilities) and
    one raw /forward without an ask, pending in one drain: ONE step, no
    compile, a token each for the two, its logits row for the third."""
    kw = {"paged_block_size": 8, "kv_blocks": 24} if paged else {}
    node = _node(10 + paged, "tiny", parts["tiny"], batch_lanes=3, **kw)
    await _warm(node)
    try:
        ex = node.executor
        assert (ex.pool is not None) is paged
        firsts = {  # the executor's prefill reply is a host array
            sid: ex.process(sid, {"tokens": [[3 + i, 7, 11]], "start_pos": 0, "real_len": 3})
            for i, sid in enumerate(("a", "b", "raw"))
        }
        firsts = {sid: int(r["logits"][0].argmax()) for sid, r in firsts.items()}
        asks = {
            "a": {"sampling": {"temperature": 0.9, "top_k": 7, "top_p": 0.8}, "seed": 4},
            "b": {"sampling": {"temperature": 1.7, "min_p": 0.02}, "key": [1, 2],
                  "top_logprobs": 3},
            "raw": {},
        }
        before, compiled = ex.stats(), _compiles(node)
        out = await asyncio.to_thread(_co_arrive, ex, ex._dev_lock, {
            sid: {"tokens": [[firsts[sid]]], "start_pos": 3, "real_len": 1, **ask}
            for sid, ask in asks.items()
        })
        after = ex.stats()
        assert after["batched_steps"] - before["batched_steps"] == 1
        assert after["batched_tokens"] - before["batched_tokens"] == 3
        assert after["sampled_rows"] - before["sampled_rows"] == 2
        assert after["logit_rows"] - before["logit_rows"] == 1
        assert _compiles(node) == compiled
        row = np.asarray(out["raw"]["logits"])
        assert row.shape == (1, TINY.vocab_size) and "tokens" not in out["raw"]
        for sid in ("a", "b"):
            assert "logits" not in out[sid] and len(out[sid]["tokens"][0]) == 1
            assert len(out[sid]["key"]) == 2 and out[sid]["real_len"] == 1
        assert "logprobs" not in out["a"]
        assert len(out["b"]["top_ids"][0]) == len(out["b"]["top_lps"][0]) == 3
        # b's key chain: split([1, 2])[0]; a's is rooted at its seed
        assert out["b"]["key"] == np.asarray(
            jax.random.split(jnp.asarray([1, 2], jnp.uint32))[0]).tolist()
        assert out["a"]["key"] == np.asarray(
            jax.random.split(jax.random.PRNGKey(4))[0]).tolist()
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_a_row_outside_the_device_form_falls_back_to_its_logits(parts):
    """top-p with no top-k, and more top log-probabilities than the widest
    variant: the hop is answered with logits, the loop samples as before
    (numpy's generator), the row is counted in `logit_rows`."""
    node = _node(12, "tiny", parts["tiny"], batch_lanes=2)
    await _warm(node)
    try:
        ex = node.executor
        before = ex.stats()
        wide = SamplingConfig(temperature=0.9, top_k=0, top_p=0.9)
        async with SwarmClient([(HOST, node.info.port)], sampling=wide) as c:
            got = await c.generate_server_side(PROMPT, NEW, seed=5)
            outside = await c.generate_ids(PROMPT, NEW, seed=5)  # over HTTP: the same loop
        assert got == outside and len(got) == NEW
        mid = ex.stats()
        assert mid["logit_rows"] - before["logit_rows"] == 2 * (NEW - 1)
        assert mid["sampled_rows"] == before["sampled_rows"]
        async with SwarmClient([(HOST, node.info.port)], sampling=GREEDY) as c:
            tops = []
            ids = await c.generate_ids(PROMPT, 4, top_n=65, top_sink=tops)
        assert len(ids) == 4 and all(len(t[0]) == 65 for t in tops)
        after = ex.stats()
        assert after["logit_rows"] - mid["logit_rows"] == 3
        assert after["sampled_rows"] == mid["sampled_rows"]
    finally:
        await node.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
async def test_a_stage_executors_logits_reply_is_still_sampled_by_the_loop(parts, sampling):
    """A solo stage executor reads no ask and answers with logits: the loop
    that asked (SwarmClient) emits what the one that cannot ask
    (ChainClient) emits, log-probabilities included."""
    node = _node(13 + (sampling is SAMPLED), "tiny", parts["tiny"])
    await _warm(node)
    try:
        assert not hasattr(node.executor, "sampled_rows")  # it chooses no tokens
        lps_a, lps_b = [], []
        async with SwarmClient([(HOST, node.info.port)], sampling=sampling) as c:
            asked = await c.generate_ids(PROMPT, NEW, seed=9, logprob_sink=lps_a)
        async with ChainClient([(HOST, node.info.port)], sampling=sampling) as c:
            plain = await c.generate_ids(PROMPT, NEW, seed=9, logprob_sink=lps_b)
        assert asked == plain and len(asked) == NEW
        assert lps_a == lps_b
        row = np.zeros(8); row[3] = 5.0
        assert abs(logprob_np(row, 3) - top_logprobs_np(row, 1)[1][0]) < 1e-12
    finally:
        await node.stop()
