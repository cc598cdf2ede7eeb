# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""In-mesh speculative decoding (parallel.infer.MeshSpecRunner): the draft
layers replicate on every rank and the verify chunk rides the ppermute
pipeline — one SPMD program per round. Greedy parity vs the solo engine on
pp and pp x tp virtual meshes; sampled rounds flow. Round-5 scope (VERDICT
r04 #1b)."""

import jax
import numpy as np
import pytest

from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.generate import Engine, bucket_len
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.infer import MeshSpecRunner, PipelinedEngine


@pytest.fixture(scope="module")
def target():
    return TINY, qwen3.init_params(TINY, jax.random.PRNGKey(0))


def _drive(eng, runner, prompts, max_new, seed=0):
    """Lockstep driver over slots (the serving driver lives in the mesh
    executor; this mirrors core.spec_batch.generate_lanes)."""
    MB, K = eng.mb, runner.k
    sampled = runner.sampling.temperature > 0.0
    dlens = [0] * MB
    outs, tlens, chains = {}, {}, {}
    for slot, p in enumerate(prompts):
        n = len(p)
        logits = eng.step_slot(slot, np.asarray([p], np.int32), n, reset=True)
        b = min(bucket_len(n), eng.max_len)
        padded = np.zeros((1, b), np.int32)
        padded[0, :n] = p
        runner.draft_prefill(padded, slot, 0, n)
        dlens[slot] = n
        tlens[slot] = n
        key = jax.random.PRNGKey(seed + slot)
        key, sub = jax.random.split(key)
        if sampled:
            outs[slot] = [runner.first_token(logits[0], sub)]
        else:
            outs[slot] = [int(np.argmax(logits[0]))]
        chains[slot] = key
    live = set(outs)
    while live:
        for s in list(live):
            if len(outs[s]) >= max_new or tlens[s] + K + 1 > eng.max_len:
                live.discard(s)
        if not live:
            break
        active = np.zeros(MB, bool)
        last = np.zeros(MB, np.int32)
        catch = np.zeros(MB, np.int32)
        cm = np.zeros(MB, bool)
        keys = np.zeros((MB, 2), np.uint32)
        for s in live:
            active[s] = True
            last[s] = outs[s][-1]
            if dlens[s] < tlens[s]:
                catch[s] = outs[s][-2]
                cm[s] = True
            if sampled:
                chains[s], sub = jax.random.split(chains[s])
                keys[s] = np.asarray(sub)
        toks, n_new = runner.run_round(
            last, catch, cm, np.asarray(dlens, np.int32), active,
            keys if sampled else None,
        )
        for s in live:
            n = int(n_new[s])
            old = tlens[s]
            tlens[s] = old + n
            dlens[s] = old + min(n, K)
            for t in toks[s][:n].tolist():
                outs[s].append(int(t))
                if len(outs[s]) >= max_new:
                    break
    return [outs[s][:max_new] for s in range(len(prompts))]


def test_pp2_greedy_parity(target, devices8):
    cfg, params = target
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2), devices8[:2])
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=4, batch=1,
                          max_len=64)
    eng.enable_spec(2, 3, params)
    runner = MeshSpecRunner(eng)
    solo = Engine(cfg, params, max_len=64,
                  sampling_cfg=SamplingConfig(temperature=0.0))
    prompts = [[3, 7, 11], [2, 5, 13, 17]]
    want = [solo.generate(p, max_new_tokens=12) for p in prompts]
    got = _drive(eng, runner, prompts, 12)
    assert got == want


def test_pp2_tp2_greedy_parity(target, devices8):
    """Speculation composes with tensor parallelism inside the same SPMD
    program: draft replicated over pp x tp, verify sharded both ways."""
    cfg, params = target
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, tp=2), devices8[:4])
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=2, batch=1,
                          max_len=64)
    eng.enable_spec(2, 3, params)
    runner = MeshSpecRunner(eng)
    solo = Engine(cfg, params, max_len=64,
                  sampling_cfg=SamplingConfig(temperature=0.0))
    prompts = [[3, 7, 11]]
    want = [solo.generate(p, max_new_tokens=10) for p in prompts]
    got = _drive(eng, runner, prompts, 10)
    assert got == want


def test_pp2_sampled_rounds_flow(target, devices8):
    """Sampled rejection rounds on the mesh: tokens flow and full
    acceptance holds when draft == target layers would — here just check
    length/liveness and determinism per seed."""
    cfg, params = target
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2), devices8[:2])
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=2, batch=1,
                          max_len=64)
    eng.enable_spec(2, 3, params)
    sc = SamplingConfig(temperature=0.9, top_k=10, top_p=0.95)
    runner = MeshSpecRunner(eng, sc)
    got1 = _drive(eng, runner, [[3, 7, 11]], 10, seed=5)
    got2 = _drive(eng, runner, [[3, 7, 11]], 10, seed=5)
    assert len(got1[0]) == 10
    assert got1 == got2


def test_ring_kv_mesh_spec_exactness(devices8):
    """Speculation composes with the ring-KV mesh layout: a Gemma-2-style
    sliding-window model on pp=2 (split ring caches) speculates
    token-exact — the verify chunk's rollback stays inside the ring
    margin and the draft's own sliding layers ring too."""
    from inferd_tpu.config import TINY_GEMMA2

    cfg = TINY_GEMMA2
    params = qwen3.init_params(cfg, jax.random.PRNGKey(31))
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2), devices8[:2])
    eng = PipelinedEngine(cfg, params, mesh, num_microbatches=2, batch=1,
                          max_len=64)
    assert eng.ring_active  # the split ring layout engages for gemma2 pp=2
    eng.enable_spec(2, 3, params)
    runner = MeshSpecRunner(eng)
    solo = Engine(cfg, params, max_len=64,
                  sampling_cfg=SamplingConfig(temperature=0.0))
    prompt = [3, 17, 42, 9, 8, 1, 5, 12, 2]  # walks past window 8
    want = [solo.generate(prompt, max_new_tokens=12)]
    got = _drive(eng, runner, [prompt], 12)
    assert got == want
