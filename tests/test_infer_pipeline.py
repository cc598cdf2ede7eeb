# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""In-mesh pipelined inference tests: the microbatched pp decode must match
the single-process engine token for token, across pipeline depths and
microbatch counts (including MB > PP and MB < PP bubble regimes), with
greedy AND temperature sampling, ragged prompts, EOS stop, and slot refill
(more sequences than slots)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import TINY, TINY_GEMMA2, TINY_QWEN2, SamplingConfig
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.infer import PipelinedEngine

GREEDY = SamplingConfig(temperature=0.0)


def make_engine(cfg, pp, mb, devices8, batch=1, max_len=32, sampling=GREEDY):
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=pp), devices8[:pp])
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=mb, batch=batch,
        max_len=max_len, sampling_cfg=sampling,
    )
    return eng, params


@pytest.mark.parametrize(
    "cfg,pp,mb",
    [
        (TINY, 2, 1),   # minimal: bubble-dominated
        (TINY, 2, 3),   # MB > PP: interleaving exercised
        (TINY, 4, 2),   # MB < PP
        (TINY_QWEN2, 2, 2),
        # gemma2 at pp=4: one layer per rank, so every rank's TRACED
        # layer_offset picks a different point in the sliding/global
        # alternation; decode walks past the window of 8
        (TINY_GEMMA2, 4, 2),
    ],
    ids=["pp2-mb1", "pp2-mb3", "pp4-mb2", "qwen2-pp2-mb2", "gemma2-pp4-mb2"],
)
def test_pipelined_decode_matches_engine(cfg, pp, mb, devices8):
    eng, params = make_engine(cfg, pp, mb, devices8)
    batch, prompt_len, steps = 1, 5, 6
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (mb, batch, prompt_len), 0, cfg.vocab_size, dtype=jnp.int32
    )
    got = np.asarray(eng.generate_array(prompts, max_new_tokens=steps))

    single = Engine(cfg, params, max_len=32, sampling_cfg=GREEDY)
    for m in range(mb):
        expected = single.generate(list(np.asarray(prompts[m, 0])), max_new_tokens=steps)
        assert got[m, 0].tolist() == expected, f"microbatch {m}"


@pytest.mark.parametrize(
    "cfg,pp,tp,mb",
    [
        (TINY, 2, 2, 2),       # pp x tp serving
        (TINY, 1, 2, 2),       # tp-only (pp=1 pipeline degenerates cleanly)
        ("moe", 2, 2, 1),      # MoE: experts shard over tp, psum combine
    ],
    ids=["pp2-tp2", "tp-only", "moe-pp2-tp2"],
)
def test_tp_pipelined_decode_matches_engine(cfg, pp, tp, mb, devices8):
    """Tensor-parallel serving: the cached decoder blocks run on head/expert
    shards with Megatron psums (models/qwen3.decoder_layer tp_axis) and must
    match the single-process engine token for token."""
    from inferd_tpu.config import TINY_MOE

    cfg = TINY_MOE if cfg == "moe" else cfg
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=pp, tp=tp), devices8[: pp * tp])
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=mb, batch=1,
        max_len=32, sampling_cfg=GREEDY,
    )
    batch, prompt_len, steps = 1, 5, 6
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (mb, batch, prompt_len), 0, cfg.vocab_size, dtype=jnp.int32
    )
    got = np.asarray(eng.generate_array(prompts, max_new_tokens=steps))

    single = Engine(cfg, params, max_len=32, sampling_cfg=GREEDY)
    for m in range(mb):
        expected = single.generate(list(np.asarray(prompts[m, 0])), max_new_tokens=steps)
        assert got[m, 0].tolist() == expected, f"microbatch {m}"


@pytest.mark.parametrize(
    "pp,tp,ep",
    [(2, 1, 2), (1, 2, 2)],
    ids=["pp2-ep2", "tp2-ep2"],
)
def test_ep_pipelined_moe_decode_matches_engine(pp, tp, ep, devices8):
    """Expert-parallel serving (BASELINE config 5's axis): expert weights
    shard over the ep (x tp) mesh axes, attention/KV replicate over ep, and
    the combine psums — token parity with the single-process engine."""
    from inferd_tpu.config import TINY_MOE

    cfg = TINY_MOE
    mesh = meshlib.make_mesh(
        meshlib.MeshPlan(pp=pp, tp=tp, ep=ep), devices8[: pp * tp * ep]
    )
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=1, batch=1,
        max_len=32, sampling_cfg=GREEDY,
    )
    prompt = [5, 2, 9, 13, 4]
    prompts = jnp.asarray([[prompt]], jnp.int32)
    got = np.asarray(eng.generate_array(prompts, max_new_tokens=6))

    single = Engine(cfg, params, max_len=32, sampling_cfg=GREEDY)
    assert got[0, 0].tolist() == single.generate(prompt, max_new_tokens=6)


def test_gpt_oss_pipelined_tp_ep_matches_engine(devices8):
    """GPT-OSS over a pp2 x tp2 x ep2 serving mesh: sinks shard with the
    q heads over tp, expert biases + clamped GLU shard over (ep, tp), the
    topk-then-softmax router replicates — token parity with the engine."""
    from inferd_tpu.config import TINY_GPT_OSS

    cfg = TINY_GPT_OSS
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, tp=2, ep=2), devices8)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(21))
    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=1, batch=1,
        max_len=32, sampling_cfg=GREEDY,
    )
    prompt = [5, 2, 9, 13, 4, 7, 11, 3, 8]  # + 6 new > window of 8
    prompts = jnp.asarray([[prompt]], jnp.int32)
    got = np.asarray(eng.generate_array(prompts, max_new_tokens=6))

    single = Engine(cfg, params, max_len=32, sampling_cfg=GREEDY)
    assert got[0, 0].tolist() == single.generate(prompt, max_new_tokens=6)


def test_ep_rejects_dense(devices8):
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=1, tp=1, ep=2), devices8[:2])
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="dense has no experts"):
        PipelinedEngine(TINY, params, mesh, num_microbatches=1, max_len=32)


def test_tp_rejects_indivisible_heads(devices8):
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=1, tp=4), devices8[:4])
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not divisible by tp"):
        PipelinedEngine(TINY, params, mesh, num_microbatches=1, max_len=32)


def test_tp_moe_quant_decode_matches_quant_engine(devices8):
    """--quant int8 composes with tp MoE serving: QuantWeight expert
    weights flow through moe_mlp_sharded's qeinsum path (a plain einsum
    cannot consume them) and match the quantized single-process engine."""
    from inferd_tpu.config import TINY_MOE
    from inferd_tpu.ops import quant

    cfg = TINY_MOE
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quant.apply_quant_mode(
        "int8", params, tie_word_embeddings=cfg.tie_word_embeddings
    )
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, tp=2), devices8[:4])
    eng = PipelinedEngine(
        cfg, qparams, mesh, num_microbatches=1, batch=1,
        max_len=32, sampling_cfg=GREEDY,
    )
    prompt = [5, 2, 9, 13]
    prompts = jnp.asarray([[prompt]], jnp.int32)
    got = np.asarray(eng.generate_array(prompts, max_new_tokens=5))

    single = Engine(cfg, qparams, max_len=32, sampling_cfg=GREEDY)
    assert got[0, 0].tolist() == single.generate(prompt, max_new_tokens=5)


def test_sampled_ragged_refill_matches_engine(devices8):
    """The round-2 'real engine' bar (VERDICT item 4): temperature>0, mixed
    prompt lengths, more sequences than slots (forces refill) — every
    sequence must match Engine.generate(prompt, seed=seed+i) exactly."""
    sampling = SamplingConfig(temperature=0.6, top_k=20, top_p=0.95)
    eng, params = make_engine(TINY, 2, 2, devices8, sampling=sampling)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, TINY.vocab_size, size=n)) for n in (3, 7, 4, 5, 6)]
    steps, seed = 8, 11

    got = eng.generate(prompts, max_new_tokens=steps, seed=seed)

    single = Engine(TINY, params, max_len=32, sampling_cfg=sampling)
    for i, p in enumerate(prompts):
        expected = single.generate(p, max_new_tokens=steps, seed=seed + i)
        assert got[i] == expected, f"sequence {i}"


def test_eos_stop_matches_engine(devices8):
    eng, params = make_engine(TINY, 2, 2, devices8)
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, TINY.vocab_size, size=n)) for n in (4, 6, 5)]
    single = Engine(TINY, params, max_len=32, sampling_cfg=GREEDY)

    # pick an EOS that actually fires mid-generation for sequence 0
    ref = single.generate(prompts[0], max_new_tokens=8)
    eos = ref[3]

    got = eng.generate(prompts, max_new_tokens=8, eos_token_id=eos)
    for i, p in enumerate(prompts):
        expected = single.generate(p, max_new_tokens=8, eos_token_id=eos)
        assert got[i] == expected, f"sequence {i}"
    assert got[0][-1] == eos and len(got[0]) <= 8


def test_multi_lane_slots_group_equal_lengths(devices8):
    """batch>1: lanes of one slot share a cache length, so sequences are
    grouped by prompt length; odd-sized groups pad with a dummy lane."""
    eng, params = make_engine(TINY, 2, 2, devices8, batch=2)
    rng = np.random.RandomState(5)
    lens = [4, 4, 6, 6, 4]  # two full groups + one odd group
    prompts = [list(rng.randint(0, TINY.vocab_size, size=n)) for n in lens]

    got = eng.generate(prompts, max_new_tokens=5)

    single = Engine(TINY, params, max_len=32, sampling_cfg=GREEDY)
    for i, p in enumerate(prompts):
        expected = single.generate(p, max_new_tokens=5)
        assert got[i] == expected, f"sequence {i}"


def test_caches_persist_across_generate_calls(devices8):
    eng, params = make_engine(TINY, 2, 2, devices8)
    p = [list(range(1, 6))]
    first = eng.generate(p, max_new_tokens=4)
    again = eng.generate(p, max_new_tokens=4)
    assert first == again  # slot reuse must fully reset per-slot state


def test_pipelined_rejects_indivisible_layers(devices8):
    plan = meshlib.MeshPlan(pp=3)
    mesh = meshlib.make_mesh(plan, devices8[:3])
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))  # 4 layers, pp=3
    with pytest.raises(ValueError, match="not divisible"):
        PipelinedEngine(TINY, params, mesh, num_microbatches=1)


def test_generate_guards(devices8):
    eng, _ = make_engine(TINY, 2, 1, devices8, max_len=8)
    assert eng.generate([[1, 2, 3]], max_new_tokens=0) == [[]]
    with pytest.raises(BufferError, match="exceeds max_len"):
        eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=4)  # 5 + 4 > 8
    with pytest.raises(ValueError, match="empty"):
        eng.generate([[]], max_new_tokens=2)


def test_elastic_reshard_carries_live_session(devices8):
    """Elastic reshard (BASELINE config 4's correctness half): a live
    session served on a pp=2 mesh is EXPORTED (layer axis reassembled
    across ranks), imported into a pp=4 engine — a genuinely different
    layer split — and keeps decoding token-exact vs the solo engine."""
    eng1, params = make_engine(TINY, pp=2, mb=2, devices8=devices8)
    want = Engine(TINY, params, max_len=32, sampling_cfg=GREEDY).generate(
        [3, 7, 11, 19, 5], max_new_tokens=6
    )
    prompt = [3, 7, 11, 19, 5]
    logits = eng1.step_slot(0, np.asarray([prompt]), len(prompt), reset=True)
    toks = [int(np.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(2):
        logits = eng1.step_slot(0, np.asarray([[toks[-1]]]), 1, False, start_pos=pos)
        pos += 1
        toks.append(int(np.argmax(logits[0])))
    k, v, ln, _, _ = eng1.export_slot(0)
    assert ln == pos

    mesh2 = meshlib.make_mesh(meshlib.MeshPlan(pp=4), devices8[:4])
    eng2 = PipelinedEngine(
        TINY, params, mesh2, num_microbatches=2, batch=1, max_len=32,
        sampling_cfg=GREEDY,
    )
    eng2.import_slot(1, k, v, ln)
    for _ in range(3):
        logits = eng2.step_slot(1, np.asarray([[toks[-1]]]), 1, False, start_pos=pos)
        pos += 1
        toks.append(int(np.argmax(logits[0])))
    assert toks == want

    # shape validation: wrong head count is refused
    with pytest.raises(ValueError, match="does not match"):
        eng2.import_slot(0, k[:, :, :, :1], v[:, :, :, :1], ln)
    with pytest.raises(BufferError):
        eng2.import_slot(0, k, v, 999)


@pytest.mark.parametrize(
    "cfg,pp,ring",
    [
        (TINY, 4, False),         # uniform dense lanes, three bubble ticks a stage
        (TINY_GEMMA2, 2, True),   # ring-split: sliding layers in O(window) rings
        (TINY_GEMMA2, 4, False),  # uniform under a traced layer offset (mask-only windows)
    ],
    ids=["uniform-pp4", "ring-pp2", "gemma2-uniform-pp4"],
)
def test_rows_pass_matches_the_per_slot_pass_and_masks_its_writes(cfg, pp, ring, devices8):
    """The serving decode pass carries its slots as rows (step_slots): over
    slots at ragged lengths, with a free slot, one full at max_len and one
    that sits passes out, several passes in a row give each active slot the
    logits of the per-slot pass (step_slot, one slot at a time), advance
    the active slots' lengths only, and leave an inactive slot's keys and
    values bit for bit as they were: a stage that wrote a row in a tick
    other than its own would undo its own tick's write, or scribble on a
    slot that is mid-prefill, free or full."""
    mb, max_len = 6, 32
    rows, _ = make_engine(cfg, pp, mb, devices8, max_len=max_len)
    solo, _ = make_engine(cfg, pp, mb, devices8, max_len=max_len)
    assert rows.ring_active == ring == solo.ring_active
    rng = np.random.default_rng(7)
    # slot 4 stays free; slot 5 is full: its prompt fills the whole buffer
    prompt_len = {0: 3, 1: 9, 2: 17, 3: 5, 5: max_len}
    nxt, lens = {}, dict(prompt_len)
    for slot, n in prompt_len.items():
        prompt = rng.integers(0, cfg.vocab_size, (1, n), dtype=np.int32)
        got = rows.step_slot(slot, prompt, n, reset=True)
        want = solo.step_slot(slot, prompt, n, reset=True)
        np.testing.assert_array_equal(got, want)  # the same program on both
        nxt[slot] = int(np.argmax(want[0]))

    def kv_of(eng, slot):
        c = eng.caches
        return [np.asarray(a[:, slot]) for a in (c.k, c.v, c.k_loc, c.v_loc) if a is not None]

    # slot 3 sits the first two passes out (mid-stream, inactive), slot 0 the third
    for active in ([0, 1, 2], [0, 1, 2], [1, 2, 3], [0, 1, 2, 3], [0, 3]):
        idle = [s for s in range(mb) if s not in active]
        before = {s: kv_of(rows, s) for s in idle}
        out = rows.step_slots({s: nxt[s] for s in active})
        assert sorted(out) == active
        for s in active:
            want = solo.step_slot(s, np.asarray([[nxt[s]]], np.int32), 1, False,
                                  start_pos=lens[s])[0]
            np.testing.assert_allclose(out[s], want, rtol=2e-4, atol=2e-4)
            assert int(np.argmax(out[s])) == int(np.argmax(want))
            nxt[s], lens[s] = int(np.argmax(want)), lens[s] + 1
        assert [rows.slot_length(s) for s in range(mb)] == [lens.get(s, 0) for s in range(mb)]
        for s in idle:
            for was, now in zip(before[s], kv_of(rows, s)):
                np.testing.assert_array_equal(was, now)
    # what the passes wrote is what the per-slot passes wrote, everywhere
    for s in range(mb):
        for a, b in zip(kv_of(rows, s), kv_of(solo, s)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
