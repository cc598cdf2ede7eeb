"""Sequence-parallel SERVING (round 5, VERDICT r04 #3): --mesh pp=N,sp=M
shards a long prompt's prefill across sp ranks with ring attention, gathers
the K/V into the decode cache, and decodes on the standard pass —
token-exact with the unsharded engine. The reference's prefill is a
full-sequence forward on one machine with O(seq^2) eager attention
(qwen3_server_module.py:67-89); SURVEY §7 names sequence sharding the
idiomatic TPU extension axis."""

import asyncio

import jax
import numpy as np
import pytest

from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.generate import Engine, bucket_len
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.infer import PipelinedEngine

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def target():
    return TINY, qwen3.init_params(TINY, jax.random.PRNGKey(0))


def _long_prompt(n, seed=0):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(3, TINY.vocab_size - 1, size=n)]


def _decode(eng, slot, first_logits, pos, steps):
    toks = [int(np.argmax(first_logits[0]))]
    while len(toks) < steps:
        lg = eng.step_slot(
            slot, np.asarray([[toks[-1]]], np.int32), 1, False, start_pos=pos
        )
        pos += 1
        toks.append(int(np.argmax(lg[0])))
    return toks


def test_pp2_sp2_long_prefill_token_exact(target, devices8):
    """70-token prompt (non-power-of-two, > one sp block) prefis sharded
    over sp; prefill logits match the solo engine bit-for-bit-ish and the
    decoded stream is token-exact."""
    cfg, params = target
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, sp=2), devices8[:4])
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=2, batch=1,
                          max_len=128)
    assert eng.sp_active
    prompt = _long_prompt(70)
    solo = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY)
    want = solo.generate(prompt, max_new_tokens=8)

    logits = eng.sp_prefill_slot(0, np.asarray([prompt], np.int32), len(prompt))
    # prefill logits equal the unsharded forward's last-token logits
    toks128 = np.zeros((1, bucket_len(len(prompt))), np.int32)
    toks128[0, : len(prompt)] = prompt
    ref_logits, _, _ = qwen3.forward(params, cfg, jax.numpy.asarray(toks128))
    np.testing.assert_allclose(
        np.asarray(logits[0]),
        np.asarray(ref_logits[0, len(prompt) - 1], np.float32),
        rtol=2e-4, atol=2e-4,
    )
    got = _decode(eng, 0, logits, len(prompt), 8)
    assert got == want


def test_pp2_sp2_tp2_composes(target, devices8):
    """sp composes with tp inside the same mesh (pp2 x sp2 x tp2 = 8
    virtual devices): still token-exact."""
    cfg, params = target
    mesh = meshlib.make_mesh(
        meshlib.MeshPlan(pp=2, sp=2, tp=2), devices8[:8]
    )
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=2, batch=1,
                          max_len=128)
    prompt = _long_prompt(40, seed=3)
    solo = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY)
    want = solo.generate(prompt, max_new_tokens=6)
    logits = eng.sp_prefill_slot(0, np.asarray([prompt], np.int32), len(prompt))
    got = _decode(eng, 0, logits, len(prompt), 6)
    assert got == want


def test_sp_per_chip_memory_is_sharded(target, devices8):
    """MEASURED per-chip bytes: the prompt block each chip holds is S/sp,
    and the adopted KV cache holds L/pp layers per chip (replicated over
    sp) — the memory contract behind the sp win (each chip's prefill
    activations scale with its block, not the full sequence)."""
    cfg, params = target
    sp, pp = 2, 2
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=pp, sp=sp), devices8[:4])
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=2, batch=1,
                          max_len=128)
    prompt = _long_prompt(64, seed=4)
    eng.sp_prefill_slot(0, np.asarray([prompt], np.int32), len(prompt))
    # KV cache: layer axis sharded over pp, replicated over sp
    shard = eng.caches.k.addressable_shards[0]
    assert shard.data.shape[0] == cfg.num_layers // pp
    total_bytes = eng.caches.k.size * eng.caches.k.dtype.itemsize
    per_chip = shard.data.size * shard.data.dtype.itemsize
    assert per_chip == total_bytes // pp  # sp replicates, pp shards
    # the sp-sharded prompt: each chip's block is S/sp tokens
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(
        np.zeros((1, 64), np.int32), NamedSharding(mesh, P(None, "sp"))
    )
    assert x.addressable_shards[0].data.shape == (1, 64 // sp)


def test_sp_with_quantized_params(target, devices8):
    """int8-quantized params serve through the sp prefill (the tp-path
    projections contract via qdot)."""
    from inferd_tpu.ops import quant

    cfg, params = target
    qparams = quant.apply_quant_mode(
        "int8", params, tie_word_embeddings=cfg.tie_word_embeddings
    )
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, sp=2), devices8[:4])
    eng = PipelinedEngine(cfg, qparams, mesh, num_microbatches=2, batch=1,
                          max_len=128)
    prompt = _long_prompt(40, seed=5)
    want = Engine(cfg, qparams, max_len=128, sampling_cfg=GREEDY).generate(
        prompt, max_new_tokens=6
    )
    logits = eng.sp_prefill_slot(0, np.asarray([prompt], np.int32), len(prompt))
    got = _decode(eng, 0, logits, len(prompt), 6)
    assert got == want


@pytest.mark.asyncio
async def test_mesh_node_sp_serving_e2e(target, devices8):
    """A --mesh pp=2,sp=2 node serves a long-prompt generation through the
    stock SwarmClient, token-exact with the solo engine (the sp prefill
    rides /forward's first chunk transparently)."""
    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.control.dht import SwarmDHT
    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.parallel.stages import Manifest, split_and_save
    from inferd_tpu.runtime.node import Node, NodeInfo

    cfg, params = target
    base = 18950
    import tempfile

    with tempfile.TemporaryDirectory() as parts:
        split_and_save(params, cfg, Manifest.even_split("tiny", 1), parts)
        info = NodeInfo(
            name="spn0", host="127.0.0.1", port=base, stage=0,
            num_stages=1, model_name="tiny",
        )
        dht = SwarmDHT(
            info.node_id, base + 100, bootstrap=[], host="127.0.0.1",
            gossip_period_s=0.05, ttl_s=5.0,
        )
        node = Node(
            info, cfg, parts, dht, backend="qwen3", max_len=128,
            rebalance_period_s=600.0, mesh_plan=MeshPlan(pp=2, sp=2),
            mesh_slots=2,
        )
        await node.start()
        try:
            assert node.executor.engine.sp_active
            prompt = _long_prompt(70, seed=6)
            want = Engine(
                cfg, params, max_len=128, sampling_cfg=GREEDY
            ).generate(prompt, max_new_tokens=8)
            async with SwarmClient(
                [("127.0.0.1", base)], sampling=GREEDY
            ) as c:
                got = await c.generate_ids(prompt, max_new_tokens=8)
            assert got == want
        finally:
            await node.stop()
