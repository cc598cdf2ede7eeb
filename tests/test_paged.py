"""Paged KV block pool: token-exactness vs the dense path, CoW shared-
prefix caching, chunked prefill, and the block-pool allocator itself
(core/cache.BlockPool, ops/attention block-table path, both batched
executors' --paged-kv mode). The correctness bar everywhere is the dense
layout: the same tokens exactly, the same logits to float32 rounding
(assert_same_logits)."""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from inferd_tpu.config import PRESETS
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core.cache import BlockPool, KVCache, PagedKVCache, grow
from inferd_tpu.models import qwen3

TINY = PRESETS["tiny"]


def assert_same_logits(a, b):
    """Dense vs paged logits: the same math over a gathered, position-
    contiguous view. XLA fuses a gathered operand differently from a dense
    slab, so float32 logits agree to rounding — not to the bit, as they
    happened to under an older XLA:CPU — while the tokens they select must
    agree exactly. Tolerance: 100 float32 ulps of these O(1) logits."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.array_equal(a.argmax(-1), b.argmax(-1))
    np.testing.assert_allclose(a, b, rtol=1.2e-5, atol=1.2e-5)


@pytest.fixture(scope="module")
def tiny_params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def whole_stage(tiny_params):
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params

    manifest = Manifest.even_split("tiny", 1)
    spec = list(manifest.stage_specs())[0]
    return spec, extract_stage_params(tiny_params, TINY, spec)


# ---------------------------------------------------------------------------
# block_keys: the shared-prefix identity
# ---------------------------------------------------------------------------


def test_block_keys_chain_identity():
    a = prefixlib.block_keys(list(range(40)), 16)
    b = prefixlib.block_keys(list(range(40)) + [99], 16)
    assert len(a) == 2 and len(b) == 2
    assert a == b  # same full blocks -> same keys (tail token is partial)
    c = prefixlib.block_keys([7] + list(range(1, 40)), 16)
    # first block differs -> EVERY key differs (chained, not per-block)
    assert c[0] != a[0] and c[1] != a[1]
    d = prefixlib.block_keys(list(range(16)) , 16)
    assert d == a[:1]


def test_block_keys_partial_blocks_get_no_key():
    assert prefixlib.block_keys([1, 2, 3], 16) == []
    assert len(prefixlib.block_keys(list(range(16)), 16)) == 1


# ---------------------------------------------------------------------------
# BlockPool allocator
# ---------------------------------------------------------------------------


def test_block_pool_alloc_release_refcount():
    pool = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=64,
                     block_size=16)
    pool.ensure(0, 40, owner="session a, lane 0")
    assert pool.lane_blocks[0] == 3 and pool.blocks_used == 3
    pool.release_lane(0)
    assert pool.blocks_used == 0 and pool.lane_blocks[0] == 0
    # exhaustion carries the owner identity in the BufferError
    small = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=64,
                      block_size=16, num_blocks=3)  # scratch + 2
    small.ensure(0, 32, owner="session a, lane 0")
    with pytest.raises(BufferError, match="session b, lane 1"):
        small.ensure(1, 32, owner="session b, lane 1")


def test_block_pool_prefix_index_map_register_evict():
    pool = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=64,
                     block_size=16, num_blocks=5)
    keys = prefixlib.block_keys(list(range(32)), 16)
    pool.ensure(0, 32, owner="a")
    assert pool.register_prefix(0, keys) == 2
    pool.release_lane(0)
    # blocks survive teardown through the index's own references
    assert pool.blocks_used == 2
    cov = pool.map_prefix(1, keys)
    assert cov == 32 and pool.lane_shared[1] == 2
    assert pool.cow_shared == 2
    pool.release_lane(1)
    # unpinned entries evict LRU when space is needed
    pool.ensure(0, 64, owner="a")  # needs all 4 usable blocks
    assert pool.prefix_evictions == 2 and pool.blocks_used == 4


def test_block_pool_pinned_entries_never_evicted():
    pool = BlockPool(TINY, TINY.num_layers, lanes=1, max_len=64,
                     block_size=16, num_blocks=4)
    keys = prefixlib.block_keys(list(range(16)), 16)
    pool.ensure(0, 16, owner="a")
    pool.register_prefix(0, keys)
    assert pool.pin(keys) == 1 and pool.pins_resident == 1
    pool.release_lane(0)
    with pytest.raises(BufferError):
        pool.ensure(0, 64, owner="session x, lane 0")  # pin holds 1 of 3
    pool.unpin(keys)
    pool.ensure(0, 48, owner="a")  # now evictable
    assert pool.pins_resident == 0


def test_block_pool_cow_split_queues_copy_and_holds_src():
    pool = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=64,
                     block_size=16)
    keys = prefixlib.block_keys(list(range(32)), 16)
    pool.ensure(0, 32, owner="a")
    pool.register_prefix(0, keys)
    pool.release_lane(0)
    pool.map_prefix(1, keys)
    pool.make_writable(1, 20, owner="b")  # split block 1 only
    assert pool.cow_splits == 1 and pool.lane_shared[1] == 1
    src_before = pool.blocks_used
    # a release between queue and drain must NOT recycle the copy source
    pairs = pool.drain_copies()
    assert len(pairs) == 1
    assert pool.blocks_used <= src_before


def test_block_pool_rejects_sliding_window_models():
    with pytest.raises(ValueError, match="uniform-layout"):
        BlockPool(PRESETS["tiny-gemma2"], 4, lanes=1, max_len=64,
                  block_size=16)


# ---------------------------------------------------------------------------
# ops-level: block-table attention path vs dense
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
def test_decode_gqa_block_table_exact(pool_dtype):
    """decode_gqa through a (shuffled) block table equals decode_gqa over
    the equivalent dense buffer — including compressed-KV storage (the
    gather preserves the narrow dtype; the upcast stays downstream)."""
    from inferd_tpu.ops import attention as ops

    rng = np.random.RandomState(0)
    b, nkv, g, d, bs, mb = 2, 2, 2, 8, 4, 4
    t = mb * bs
    nb = 1 + b * mb
    pool_k = rng.randn(nb, bs, nkv, d).astype(np.float32)
    pool_v = rng.randn(nb, bs, nkv, d).astype(np.float32)
    # deliberately non-contiguous chains
    table = np.array([[3, 1, 7, 5], [2, 8, 4, 6]], np.int32)
    kd = pool_k[table].reshape(b, t, nkv, d)
    vd = pool_v[table].reshape(b, t, nkv, d)
    q = jnp.asarray(rng.randn(b, 1, nkv * g, d), jnp.float32)
    qpos = jnp.asarray([[9], [11]], jnp.int32)
    valid = jnp.asarray([10, 12], jnp.int32)
    dense = ops.decode_gqa(
        q, jnp.asarray(kd, pool_dtype), jnp.asarray(vd, pool_dtype),
        qpos, valid,
    )
    paged = ops.decode_gqa(
        q, jnp.asarray(pool_k, pool_dtype), jnp.asarray(pool_v, pool_dtype),
        qpos, valid, block_table=jnp.asarray(table),
    )
    assert jnp.array_equal(dense, paged)


def test_gqa_attention_block_table_prefill_exact(tiny_params):
    """The S>1 path (prefill chunks) gathers through the table too."""
    rng = np.random.RandomState(1)
    b, s, nkv, g, d, bs, mb = 1, 5, 2, 2, 8, 4, 3
    nb = 1 + b * mb
    pool_k = rng.randn(nb, bs, nkv, d).astype(np.float32)
    pool_v = rng.randn(nb, bs, nkv, d).astype(np.float32)
    table = np.array([[2, 3, 1]], np.int32)
    kd = pool_k[table].reshape(b, mb * bs, nkv, d)
    vd = pool_v[table].reshape(b, mb * bs, nkv, d)
    q = jnp.asarray(rng.randn(b, s, nkv * g, d), jnp.float32)
    qpos = jnp.asarray([[4, 5, 6, 7, 8]], jnp.int32)
    dense = qwen3.gqa_attention(q, jnp.asarray(kd), jnp.asarray(vd), qpos,
                                jnp.int32(9))
    paged = qwen3.gqa_attention(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v), qpos, jnp.int32(9),
        block_table=jnp.asarray(table),
    )
    assert jnp.array_equal(dense, paged)


# ---------------------------------------------------------------------------
# model-level: paged forward_cached / decode_k vs dense
# ---------------------------------------------------------------------------


def _prefill_both(params, pool, toks):
    import dataclasses

    b, n = toks.shape
    dense = KVCache.create(TINY, TINY.num_layers, b, pool.max_blocks *
                           pool.block_size, ring=False)
    for lane in range(b):
        pool.ensure(lane, n, owner=f"lane {lane}")
    paged = dataclasses.replace(pool.cache, table=pool.device_table())
    pos = jnp.broadcast_to(jnp.arange(n), (b, n))
    ld, dc, _ = qwen3.forward_cached(params, TINY, jnp.asarray(toks), pos,
                                  dense, jnp.int32(0), real_end=jnp.int32(n))
    lp, pc, _ = qwen3.forward_cached(params, TINY, jnp.asarray(toks), pos,
                                  paged, jnp.int32(0), real_end=jnp.int32(n))
    assert_same_logits(ld, lp)
    return ld, dc, pc


def test_forward_cached_paged_parity_prefill_decode(tiny_params):
    import dataclasses

    pool = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=96,
                     block_size=16)
    toks = np.array([[3, 7, 11, 19, 23, 5, 2, 9, 14, 6],
                     [4, 8, 12, 20, 24, 6, 3, 10, 15, 7]], np.int32)
    n = toks.shape[1]
    logits, dc, pc = _prefill_both(tiny_params, pool, toks)
    lens = np.full((2,), n, np.int32)
    cur = n  # host-side frontier (python int: no per-step device read)
    tok = jnp.argmax(logits[:, n - 1], -1).astype(jnp.int32)
    for _ in range(4):
        for lane in range(2):
            pool.ensure(lane, cur + 1, owner=f"lane {lane}")
        cur += 1
        pc = dataclasses.replace(pc, table=pool.device_table())
        ld, dc, _ = qwen3.forward_cached(
            tiny_params, TINY, tok[:, None], jnp.asarray(lens)[:, None],
            dc, jnp.asarray(lens), real_end=jnp.asarray(lens) + 1,
        )
        lp, pc, _ = qwen3.forward_cached(
            tiny_params, TINY, tok[:, None], jnp.asarray(lens)[:, None],
            pc, jnp.asarray(lens), real_end=jnp.asarray(lens) + 1,
            write_mask=jnp.ones((2,), bool),
        )
        assert_same_logits(ld, lp)
        tok = jnp.argmax(ld[:, 0], -1).astype(jnp.int32)
        lens += 1


def test_decode_k_paged_parity_with_eos(tiny_params):
    """The K-step fused inner loop over a paged cache: same tokens, same
    early-eos n_new as the dense cache."""
    import dataclasses

    pool = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=96,
                     block_size=16)
    serve = qwen3.make_decode_k_serve(TINY)
    toks = np.array([list(range(3, 23)), list(range(4, 24))], np.int32)
    n = toks.shape[1]
    logits, dc, pc = _prefill_both(tiny_params, pool, toks)
    tok = jnp.argmax(logits[:, n - 1], -1).astype(jnp.int32)
    lens = jnp.full((2,), n, jnp.int32)
    act = jnp.ones((2,), bool)
    keys = jnp.zeros((2, 2), jnp.uint32)
    # eos = whatever greedy emits first for row 0 -> row 0 stops after 1
    eos = jnp.asarray([int(tok[0]) if True else -1, -1], jnp.int32)
    K = 6
    for lane in range(2):
        pool.ensure(lane, n + K, owner=f"lane {lane}")
    pc = dataclasses.replace(pc, table=pool.device_table())
    dc2, seq_d, n_d, _ = serve(tiny_params, dc, tok, lens, act, keys, eos,
                               K, 0.0, 0, 1.0, 0.0)
    pc2, seq_p, n_p, _ = serve(tiny_params, pc, tok, lens, act, keys, eos,
                               K, 0.0, 0, 1.0, 0.0)
    assert jnp.array_equal(seq_d, seq_p)
    assert jnp.array_equal(n_d, n_p)


# ---------------------------------------------------------------------------
# executor parity: both batched executors, dense vs paged
# ---------------------------------------------------------------------------


def _mk_stage(whole_stage, **kw):
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    spec, sp = whole_stage
    return BatchedStageExecutor(TINY, spec, sp, lanes=4, max_len=128, **kw)


def _mk_batch(tiny_params, **kw):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    return BatchedExecutor(TINY, tiny_params, lanes=4, max_len=128, **kw)


def _drive(ex, sid, prompt, steps, kstep=None, sampling=None, eos=None,
           seed=0):
    """Greedy (or K-step payload) stream through an executor's process()
    surface; returns the emitted ids."""
    r = ex.process(sid, {"tokens": [prompt], "start_pos": 0,
                         "real_len": len(prompt)})
    out = [int(np.argmax(r["logits"][0]))]
    pos = len(prompt)
    while len(out) < steps + 1:
        payload = {"tokens": [[out[-1]]], "start_pos": pos, "real_len": 1}
        if kstep:
            payload["decode_steps"] = min(kstep, steps + 1 - len(out))
            payload["seed"] = seed
            if sampling:
                payload["sampling"] = sampling
            if eos is not None:
                payload["eos"] = eos
            r = ex.process(sid, payload)
            toks = r["tokens"][0]
            out.extend(int(t) for t in toks)
            pos += r["real_len"]
            if eos is not None and out and out[-1] == eos:
                break
            if r["real_len"] == 0:
                break
        else:
            r = ex.process(sid, payload)
            out.append(int(np.argmax(r["logits"][0])))
            pos += 1
    return out


@pytest.mark.parametrize("flavor", ["stage", "batch"])
def test_executor_paged_parity_greedy(flavor, whole_stage, tiny_params):
    mk = (lambda **kw: _mk_stage(whole_stage, **kw)) if flavor == "stage" \
        else (lambda **kw: _mk_batch(tiny_params, **kw))
    dense, paged = mk(), mk(block_size=16, prefill_chunk=8)
    prompt = list(range(3, 3 + 20))
    a = _drive(dense, "s", prompt, 6)
    b = _drive(paged, "s", prompt, 6)
    assert a == b


@pytest.mark.parametrize("flavor", ["stage", "batch"])
def test_executor_paged_parity_kstep_sampled_and_eos(flavor, whole_stage,
                                                     tiny_params):
    """K-step fused decode with on-device SAMPLING and an eos stop:
    paged == dense, token for token, committed-length for committed-
    length."""
    mk = (lambda **kw: _mk_stage(whole_stage, **kw)) if flavor == "stage" \
        else (lambda **kw: _mk_batch(tiny_params, **kw))
    dense, paged = mk(), mk(block_size=16)
    prompt = list(range(3, 3 + 20))
    sampling = {"temperature": 0.8, "top_k": 5}
    a = _drive(dense, "s", prompt, 8, kstep=4, sampling=sampling, seed=11)
    b = _drive(paged, "s", prompt, 8, kstep=4, sampling=sampling, seed=11)
    assert a == b
    # eos mid-window: stop after the first emitted token repeats
    eos = a[0]
    c = _drive(dense, "e", prompt, 8, kstep=4, eos=eos)
    d = _drive(paged, "e", prompt, 8, kstep=4, eos=eos)
    assert c == d


@pytest.mark.parametrize("flavor", ["stage", "batch"])
def test_executor_paged_replay_rollback_parity(flavor, whole_stage,
                                               tiny_params):
    """A replayed decode step (client re-sent after a lost response)
    rolls the paged frontier back and recomputes the same token."""
    mk = (lambda **kw: _mk_stage(whole_stage, **kw)) if flavor == "stage" \
        else (lambda **kw: _mk_batch(tiny_params, **kw))
    paged = mk(block_size=16)
    prompt = list(range(3, 3 + 20))
    out = _drive(paged, "s", prompt, 5)
    # replay the step that produced out[3]: frontier rolls back
    pos = len(prompt) + 2
    r = paged.process("s", {"tokens": [[out[2]]], "start_pos": pos,
                            "real_len": 1})
    assert int(np.argmax(r["logits"][0])) == out[3]


def test_shared_prefix_skips_prefill_compute(whole_stage):
    """THE acceptance assertion: a session admitted against a pinned
    shared prefix performs zero prefill compute for the shared region —
    the prefill-token counter moves only by the unshared remainder."""
    ex = _mk_stage(whole_stage, block_size=16)
    prefix = list(range(3, 3 + 64))
    assert ex.pin_prefix(prefix) == 64
    prompt = prefix + [99, 98, 97]
    before = ex.stats()["prefill_tokens"]
    hits0 = ex.stats()["paged"]["prefix_hit_tokens"]
    out = _drive(ex, "s", prompt, 4)
    moved = ex.stats()["prefill_tokens"] - before
    assert moved == len(prompt) - 64  # zero FLOPs for the pinned region
    assert ex.stats()["paged"]["prefix_hit_tokens"] - hits0 == 64
    # and the stream equals a dense run of the same prompt
    dense = _mk_stage(whole_stage)
    assert out == _drive(dense, "s", prompt, 4)


def test_cow_divergence_does_not_corrupt_sharers(whole_stage):
    """Two sessions share pinned-prefix blocks; one REWRITES inside the
    shared region (divergent replay). CoW must split its blocks so the
    other session's stream stays exact."""
    ex = _mk_stage(whole_stage, block_size=16)
    dense = _mk_stage(whole_stage)
    prefix = list(range(3, 3 + 32))
    ex.pin_prefix(prefix)
    prompt = prefix + [77, 76]
    a1 = _drive(ex, "a", prompt, 2)
    _b1 = _drive(ex, "b", prompt, 2)
    # session b diverges: replay a prefill chunk INSIDE the shared region
    # with different tokens
    alt = [50, 51, 52, 53]
    pos = 8
    rb = ex.process("b", {"tokens": [alt], "start_pos": pos, "real_len": 4})
    assert ex.stats()["paged"]["cow_splits"] >= 1
    # session a (and the pin) keep decoding the ORIGINAL stream
    ra = ex.process("a", {"tokens": [[a1[-1]]],
                          "start_pos": len(prompt) + 2, "real_len": 1})
    _drive(dense, "a", prompt, 2)
    rd = dense.process("a", {"tokens": [[a1[-1]]],
                             "start_pos": len(prompt) + 2, "real_len": 1})
    assert_same_logits(ra["logits"], rd["logits"])
    # and b's rewritten stream equals a dense executor given the same
    # divergent history
    dense_b = _mk_stage(whole_stage)
    dense_b.process("b", {"tokens": [prompt], "start_pos": 0,
                          "real_len": len(prompt)})
    rdb = dense_b.process("b", {"tokens": [alt], "start_pos": pos,
                                "real_len": 4})
    assert_same_logits(rb["logits"], rdb["logits"])


def test_cow_protects_registered_blocks_from_rollback(whole_stage):
    """Review regression: a lane that PUBLISHED its own blocks into the
    prefix index (register_prefix — lane_shared stays 0) must still CoW-
    split them on a divergent rollback rewrite; an in-place rewrite would
    corrupt the index for every future session."""
    ex = _mk_stage(whole_stage, block_size=16)
    dense = _mk_stage(whole_stage)
    prompt = list(range(3, 3 + 34))
    a = _drive(ex, "a", prompt, 2)  # registers blocks 0-1 on a COLD index
    assert _drive(dense, "a", prompt, 2) == a
    # divergent replay INSIDE the registered region (not a mapped prefix:
    # lane_shared is 0 for the registering lane)
    alt = [60, 61, 62, 63]
    ex.process("a", {"tokens": [alt], "start_pos": 18, "real_len": 4})
    assert ex.stats()["paged"]["cow_splits"] >= 1
    ex.end_session("a")
    # a NEW session with the ORIGINAL prompt maps the indexed blocks —
    # they must still hold the ORIGINAL KV
    b = _drive(ex, "b", prompt, 2)
    assert b == a


def test_cow_protects_fork_parent_blocks_from_rollback(tiny_params):
    """Review regression sibling: a fork PARENT's blocks are shared with
    the child (refcount) without the parent's lane_shared moving — a
    parent rollback rewrite must split, not scribble on the child."""
    ex = _mk_batch(tiny_params, block_size=16)
    dense = _mk_batch(tiny_params)
    prompt = list(range(3, 3 + 20))
    a = _drive(ex, "parent", prompt, 3)
    assert _drive(dense, "parent", prompt, 3) == a
    assert ex.fork_session("child", "parent", 16)
    assert dense.fork_session("child", "parent", 16)
    # parent diverges INSIDE the forked region
    alt = [70, 71, 72]
    ex.process("parent", {"tokens": [alt], "start_pos": 8, "real_len": 3})
    dense.process("parent", {"tokens": [alt], "start_pos": 8, "real_len": 3})
    # the child continues from the ORIGINAL prefix, unaffected
    tail = prompt[16:] + [88]
    rp = ex.process("child", {"tokens": [tail], "start_pos": 16,
                              "real_len": len(tail)})
    rd = dense.process("child", {"tokens": [tail], "start_pos": 16,
                                 "real_len": len(tail)})
    assert_same_logits(rp["logits"], rd["logits"])


def test_export_after_fork_before_dispatch(tiny_params):
    """Review regression: exporting a session whose CoW copies are still
    QUEUED (forked, no dispatch yet) must apply them first — otherwise
    the snapshot ships uninitialized blocks."""
    src = _mk_batch(tiny_params, block_size=16)
    dense = _mk_batch(tiny_params)
    prompt = list(range(3, 3 + 20))
    a = _drive(src, "parent", prompt, 3)
    assert _drive(dense, "parent", prompt, 3) == a
    assert src.fork_session("child", "parent", 18)  # partial tail queued
    exp = dict(src.export_sessions(only="child"))  # NO dispatch ran
    dst = _mk_batch(tiny_params, block_size=16)
    assert dst.import_session("child", exp["child"])
    assert dense.fork_session("child", "parent", 18)
    tail = prompt[18:] + [88]
    r1 = dst.process("child", {"tokens": [tail], "start_pos": 18,
                               "real_len": len(tail)})
    r2 = dense.process("child", {"tokens": [tail], "start_pos": 18,
                                 "real_len": len(tail)})
    assert_same_logits(r1["logits"], r2["logits"])


def test_paged_cobatch_mixed_lanes_parity(whole_stage):
    """Co-batched decode windows over paged lanes at mixed positions:
    every stream equals its dense co-batched sibling."""
    dense = _mk_stage(whole_stage)
    paged = _mk_stage(whole_stage, block_size=16)
    prompts = {"x": list(range(3, 3 + 18)), "y": [5, 2, 8],
               "z": list(range(9, 9 + 33))}
    state_d, state_p = {}, {}
    for ex, state in ((dense, state_d), (paged, state_p)):
        for sid, p in prompts.items():
            r = ex.process(sid, {"tokens": [p], "start_pos": 0,
                                 "real_len": len(p)})
            state[sid] = {"pos": len(p),
                          "out": [int(np.argmax(r["logits"][0]))]}
    for _ in range(4):
        for ex, state in ((dense, state_d), (paged, state_p)):
            items = [
                (sid, {"tokens": [[state[sid]["out"][-1]]],
                       "start_pos": state[sid]["pos"], "real_len": 1})
                for sid in prompts
            ]
            outs = ex.process_batch(items)
            for (sid, _), o in zip(items, outs):
                assert not isinstance(o, Exception), o
                state[sid]["out"].append(int(np.argmax(o["logits"][0])))
                state[sid]["pos"] += 1
    for sid in prompts:
        assert state_d[sid]["out"] == state_p[sid]["out"], sid


def test_paged_pool_exhaustion_is_per_item_and_carries_identity(whole_stage):
    """A lane that cannot extend its chain fails ALONE (per-item), with
    the session/lane identity in the error; its co-batch survives."""
    ex = _mk_stage(whole_stage, block_size=16, kv_blocks=5)  # tight pool
    a = list(range(3, 3 + 30))  # 2 blocks + partial
    b = list(range(40, 40 + 30))
    ex.process("a", {"tokens": [a], "start_pos": 0, "real_len": len(a)})
    ex.process("b", {"tokens": [b], "start_pos": 0, "real_len": len(b)})
    # both at 30 positions = 2 blocks each: the 4-block pool is full.
    # a's 2-step request still fits its tail block; b's 8-step request
    # needs a third block and must fail ALONE
    outs = ex.process_batch([
        ("a", {"tokens": [[1]], "start_pos": 30, "real_len": 1,
               "decode_steps": 2}),
        ("b", {"tokens": [[1]], "start_pos": 30, "real_len": 1,
               "decode_steps": 8}),
    ])
    errs = [o for o in outs if isinstance(o, Exception)]
    oks = [o for o in outs if not isinstance(o, Exception)]
    assert len(errs) == 1 and len(oks) == 1
    assert "block pool exhausted" in str(errs[0])
    assert "lane" in str(errs[0]) and "session" in str(errs[0])


def test_paged_fork_and_export_import_roundtrip(tiny_params):
    """fork_session maps blocks CoW-style; export/import speak the dense
    handoff schema so paged and dense replicas interchange sessions."""
    src = _mk_batch(tiny_params, block_size=16)
    dense = _mk_batch(tiny_params)
    prompt = list(range(3, 3 + 20))
    a = _drive(src, "parent", prompt, 3)
    b = _drive(dense, "parent", prompt, 3)
    assert a == b
    assert src.fork_session("child", "parent", 18)
    assert dense.fork_session("child", "parent", 18)
    tail = prompt[18:] + [88]
    rp = src.process("child", {"tokens": [tail], "start_pos": 18,
                               "real_len": len(tail)})
    rd = dense.process("child", {"tokens": [tail], "start_pos": 18,
                                 "real_len": len(tail)})
    assert_same_logits(rp["logits"], rd["logits"])
    # export from paged, import into a FRESH paged executor, keep decoding
    exp = dict(src.export_sessions(only="parent"))
    dst = _mk_batch(tiny_params, block_size=16)
    assert dst.import_session("parent", exp["parent"])
    pos = len(prompt) + 3
    r1 = dst.process("parent", {"tokens": [[a[-1]]], "start_pos": pos,
                                "real_len": 1})
    r2 = dense.process("parent", {"tokens": [[b[-1]]], "start_pos": pos,
                                  "real_len": 1})
    assert_same_logits(r1["logits"], r2["logits"])


def test_paged_rejects_spec_and_library_loop(tiny_params):
    ex = _mk_batch(tiny_params, block_size=16)
    with pytest.raises(ValueError, match="paged"):
        ex.enable_spec(2, 4)
    with pytest.raises(RuntimeError, match="dense-only"):
        ex.engine.admit([1, 2, 3])


def test_block_pool_gauges_surface(whole_stage):
    from inferd_tpu.obs import devtel

    ex = _mk_stage(whole_stage, block_size=16)
    ex.pin_prefix(list(range(3, 3 + 32)))
    g = devtel.block_pool_gauges(ex)
    assert g["pins.resident"] == 2.0  # 32 tokens / 16-token blocks
    assert g["kv.blocks_used"] >= 2.0
    assert g["kv.blocks_free"] > 0.0
    dense = _mk_stage(whole_stage)
    assert devtel.block_pool_gauges(dense) == {}


# ---------------------------------------------------------------------------
# chunked prefill: a long admission must not stall co-batched decoders
# ---------------------------------------------------------------------------


def test_chunked_prefill_interleaves_with_decode_windows(whole_stage):
    """Mixed prefill+decode load (the WindowedBatcher satellite): a long
    chunked prefill runs WHILE 8 lanes keep decoding through the node-
    style window — decode steps complete during the prefill (no head-of-
    line blocking) and the window.stall hook never fires."""
    from inferd_tpu.runtime.window import WindowedBatcher

    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    spec, sp = whole_stage
    ex = BatchedStageExecutor(TINY, spec, sp, lanes=6, max_len=384,
                              block_size=16, prefill_chunk=8)
    stalls = []
    ex.on_event = lambda etype, **attrs: stalls.append(etype) if \
        etype == "window.stall" else None

    def run_batch(entries):
        assert entries == []
        drained = ex.window.drain_pending()
        outs = ex.process_batch([(e.payload[0], e.payload[1])
                                 for e in drained])
        for e, o in zip(drained, outs):
            if isinstance(o, Exception):
                e.error = o
            else:
                e.result = o
            e.event.set()

    # window budget: the configured bound a decode lane may wait
    window_s = 0.005
    ex.window = WindowedBatcher(
        window_s, run_batch, co_possible=ex.co_possible, swap_in_run=True,
        wait_timeout_s=30.0,
    )
    ex.window.on_event = ex.on_event

    # warm the chunked-prefill jit (bucket-8 chunks) so the measured
    # prefill is dispatch-paced, not one long compile
    ex.process("warm", {"tokens": [list(range(5, 5 + 24))], "start_pos": 0,
                        "real_len": 24})
    ex.end_session("warm")

    n_dec = 3
    prompts = {f"d{i}": [3 + i, 7, 11, 19] for i in range(n_dec)}
    state = {}
    for sid, p in prompts.items():
        r = ex.process(sid, {"tokens": [p], "start_pos": 0,
                             "real_len": len(p)})
        state[sid] = {"pos": len(p), "tok": int(np.argmax(r["logits"][0]))}

    done_ts = {sid: [] for sid in prompts}

    def one_step(sid):
        st = state[sid]
        r = ex.window.submit((sid, {
            "tokens": [[st["tok"]]], "start_pos": st["pos"],
            "real_len": 1,
        }))
        st["tok"] = int(np.argmax(r["logits"][0]))
        st["pos"] += 1
        done_ts[sid].append(time.monotonic())

    # warm the co-batched decode dispatch OUTSIDE the measured window
    warm_threads = [threading.Thread(target=one_step, args=(sid,))
                    for sid in prompts]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join(timeout=60)
    for sid in done_ts:
        done_ts[sid].clear()

    long_prompt = list(range(5, 5 + 240))  # 30 chunks of 8
    span = {}

    def prefill():
        span["t0"] = time.monotonic()
        ex.process("long", {"tokens": [long_prompt], "start_pos": 0,
                            "real_len": len(long_prompt)})
        span["t1"] = time.monotonic()

    def decoder(sid):
        for _ in range(30):
            one_step(sid)

    tds = [threading.Thread(target=decoder, args=(sid,)) for sid in prompts]
    for t in tds:
        t.start()
    # let the decode cadence establish, then admit the long prompt
    time.sleep(0.03)
    tp = threading.Thread(target=prefill)
    tp.start()
    tp.join(timeout=60)
    for t in tds:
        t.join(timeout=60)
    assert stalls == []  # the window.stall hook stayed silent
    # decode steps really interleaved INTO the prefill window
    during = [
        ts for sid in prompts for ts in done_ts[sid]
        if span["t0"] <= ts <= span["t1"]
    ]
    assert during, "no decode step completed while the prefill ran"
    # and the long session is correct: its next decode matches a dense run
    dense = BatchedStageExecutor(TINY, spec, sp, lanes=2, max_len=384)
    r1 = ex.process("long", {"tokens": [[1]],
                             "start_pos": len(long_prompt), "real_len": 1})
    dense.process("long", {"tokens": [long_prompt], "start_pos": 0,
                           "real_len": len(long_prompt)})
    r2 = dense.process("long", {"tokens": [[1]],
                                "start_pos": len(long_prompt),
                                "real_len": 1})
    # chunked prefill is TOKEN-exact, not bit-exact, vs a one-dispatch
    # prefill (XLA reduces a [1, 8, H] chunk program differently than a
    # [1, 256, H] one): same argmax, logits within float tolerance
    assert np.argmax(r1["logits"][0]) == np.argmax(r2["logits"][0])
    assert np.allclose(r1["logits"], r2["logits"], atol=1e-4)


# ---------------------------------------------------------------------------
# cache.grow(): grow-then-decode token exactness (satellite)
# ---------------------------------------------------------------------------


def _decode_tokens(forward, cache, logits, n, steps):
    toks = [int(np.argmax(np.asarray(logits)[0, n - 1]))]
    lens = n
    for _ in range(steps):
        l, cache, _ = forward(jnp.asarray([[toks[-1]]], jnp.int32),
                              jnp.asarray([[lens]], jnp.int32), cache, jnp.int32(lens))
        toks.append(int(np.argmax(np.asarray(l)[0, 0])))  # jaxlint: disable=J003 -- per-token decode loop: one boundary sync per emitted token is the pattern under test
        lens += 1
    return toks, cache


@pytest.mark.parametrize("preset", ["tiny", "tiny-gemma2"])
def test_grow_then_decode_token_exact(preset):
    """grow() to a larger bucket mid-stream changes NOTHING about the
    decoded tokens — uniform AND sliding-window (ring) layouts."""
    cfg = PRESETS[preset]
    params = qwen3.init_params(cfg, jax.random.PRNGKey(1))
    prompt = np.asarray([list(range(3, 3 + 12))], np.int32)
    n = prompt.shape[1]
    pos = jnp.broadcast_to(jnp.arange(n), (1, n))

    @jax.jit  # one program a chunk width and bucket, where op-by-op dispatch paid a step
    def forward(toks, pos, cache, at):
        return qwen3.forward_cached(params, cfg, toks, pos, cache, at, real_end=at + toks.shape[1])

    small = KVCache.create(cfg, cfg.num_layers, 1, 32)
    big = KVCache.create(cfg, cfg.num_layers, 1, 64)
    ls, cs, _ = forward(jnp.asarray(prompt), pos, small, jnp.int32(0))
    lb, cb, _ = forward(jnp.asarray(prompt), pos, big, jnp.int32(0))
    toks_small, cs = _decode_tokens(forward, cs, ls, n, 8)
    # grow mid-stream, decode past the old 32-slot bucket
    cs = grow(cs, 64)
    assert cs.max_len == 64
    toks_big, cb = _decode_tokens(forward, cb, lb, n, 8)
    assert toks_small == toks_big
    # continue decoding in the grown cache vs the always-big cache
    lens = n + 8
    tok = toks_big[-1]
    for _ in range(16):
        l1, cs, _ = forward(jnp.asarray([[tok]], jnp.int32), jnp.asarray([[lens]], jnp.int32),
                            cs, jnp.int32(lens))
        l2, cb, _ = forward(jnp.asarray([[tok]], jnp.int32), jnp.asarray([[lens]], jnp.int32),
                            cb, jnp.int32(lens))
        t1 = int(np.argmax(np.asarray(l1)[0, 0]))  # jaxlint: disable=J003 -- per-token parity loop: the grown-vs-big comparison IS per step
        t2 = int(np.argmax(np.asarray(l2)[0, 0]))  # jaxlint: disable=J003 -- same per-step comparison
        assert t1 == t2
        tok = t1
        lens += 1


def test_grow_is_noop_at_or_below_current_size():
    cache = KVCache.create(TINY, TINY.num_layers, 1, 32)
    assert grow(cache, 32) is cache
    assert grow(cache, 16) is cache


def test_ensure_room_carries_owner_identity():
    cache = KVCache.create(TINY, TINY.num_layers, 1, 16)
    with pytest.raises(BufferError, match="session s7, lane 3"):
        cache.ensure_room(32, owner="session s7, lane 3")
    cache.ensure_room(8)  # fits: no raise


# ---------------------------------------------------------------------------
# Engine.max_pins satellite
# ---------------------------------------------------------------------------


def test_engine_max_pins_parameter_and_gauge(tiny_params):
    from inferd_tpu.core.generate import Engine

    eng = Engine(TINY, tiny_params, max_len=64, max_pins=2)
    assert eng.pins_resident == 0
    eng.pin_prefix([3, 7])
    eng.pin_prefix([4, 8])
    assert eng.pins_resident == 2
    eng.pin_prefix([5, 9])  # LRU caps at max_pins
    assert eng.pins_resident == 2
    assert eng._longest_pin([3, 7, 1]) is None  # [3,7] was LRU-evicted
    assert eng._longest_pin([5, 9, 1]) == (5, 9)
    with pytest.raises(ValueError):
        Engine(TINY, tiny_params, max_len=64, max_pins=0)
