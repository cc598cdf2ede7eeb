"""Continuous batching (core.batch.BatchedEngine): per-lane token parity
with solo Engine runs (greedy and sampled PRNG-chain parity), ragged lane
fills, lane refill from the queue, and EOS/capacity handling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3


@pytest.fixture(scope="module")
def setup():
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    return TINY, params


PROMPTS = [
    [3, 7, 11],
    [2, 5, 13, 17, 19],
    [23, 29],
    [31, 37, 41, 43, 47, 53, 59],
    [61, 67, 71, 3],
]


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
def test_lanes_match_solo_engine(setup, temperature):
    """Every sequence from the batched engine must equal a solo Engine run
    with the same per-sequence seed — ragged prompts decode together but
    never numerically interact."""
    cfg, params = setup
    sc = SamplingConfig(temperature=temperature, top_k=8, top_p=0.9)
    eng = BatchedEngine(cfg, params, lanes=3, max_len=64, sampling_cfg=sc)
    got = eng.generate_all(PROMPTS, max_new_tokens=10, seed=5)

    solo = Engine(cfg, params, max_len=64, sampling_cfg=sc)
    for i, p in enumerate(PROMPTS):
        want = solo.generate(p, max_new_tokens=10, seed=5 + i)
        assert got[i] == want, f"lane for prompt {i} diverged"


def test_refill_more_prompts_than_lanes(setup):
    """Queue longer than lanes: freed lanes must refill until drained."""
    cfg, params = setup
    sc = SamplingConfig(temperature=0.0)
    eng = BatchedEngine(cfg, params, lanes=2, max_len=64, sampling_cfg=sc)
    got = eng.generate_all(PROMPTS, max_new_tokens=6, seed=0)
    assert len(got) == len(PROMPTS)
    assert all(len(g) == 6 for g in got)
    assert len(eng.free) == 2  # all lanes returned


def test_eos_frees_lane(setup):
    cfg, params = setup
    sc = SamplingConfig(temperature=0.0)
    solo = Engine(cfg, params, max_len=64, sampling_cfg=sc)
    ref = solo.generate(PROMPTS[0], max_new_tokens=12, seed=0)
    eos = ref[4]
    want = solo.generate(PROMPTS[0], max_new_tokens=12, eos_token_id=eos, seed=0)

    eng = BatchedEngine(cfg, params, lanes=2, max_len=64, sampling_cfg=sc)
    got = eng.generate_all([PROMPTS[0]], max_new_tokens=12, eos_token_id=eos, seed=0)
    assert got[0] == want
    assert len(eng.free) == 2


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "sampled"])
def test_chunked_decode_matches_per_step(setup, temperature):
    """chunk>1 fuses decode steps into one scan dispatch; tokens must be
    bit-identical to the per-step path (and hence to solo Engine runs) —
    including lanes that finish mid-chunk and per-lane PRNG chains that
    continue across chunk boundaries."""
    cfg, params = setup
    sc = SamplingConfig(temperature=temperature, top_k=8, top_p=0.9)
    eng = BatchedEngine(cfg, params, lanes=3, max_len=64, sampling_cfg=sc)
    got = eng.generate_all(PROMPTS, max_new_tokens=10, seed=5, chunk=4)
    assert len(eng.free) == 3

    solo = Engine(cfg, params, max_len=64, sampling_cfg=sc)
    for i, p in enumerate(PROMPTS):
        want = solo.generate(p, max_new_tokens=10, seed=5 + i)
        assert got[i] == want, f"chunked lane for prompt {i} diverged"


def test_chunked_eos_mid_chunk(setup):
    """A lane hitting EOS inside a fused chunk truncates there and frees."""
    cfg, params = setup
    sc = SamplingConfig(temperature=0.0)
    solo = Engine(cfg, params, max_len=64, sampling_cfg=sc)
    ref = solo.generate(PROMPTS[0], max_new_tokens=12, seed=0)
    eos = ref[4]
    want = solo.generate(PROMPTS[0], max_new_tokens=12, eos_token_id=eos, seed=0)

    eng = BatchedEngine(cfg, params, lanes=2, max_len=64, sampling_cfg=sc)
    got = eng.generate_all(
        PROMPTS, max_new_tokens=12, eos_token_id=eos, seed=0, chunk=8
    )
    assert got[0] == want
    assert len(eng.free) == 2
    # every other lane matches its solo run with the same EOS
    for i, p in enumerate(PROMPTS[1:], start=1):
        assert got[i] == solo.generate(p, max_new_tokens=12, eos_token_id=eos, seed=i)


def test_chunked_max_len_boundary(setup):
    """Chunks cap at KV headroom; lanes at the cache cap release exactly
    where the per-step path releases them."""
    cfg, params = setup
    sc = SamplingConfig(temperature=0.0)
    eng1 = BatchedEngine(cfg, params, lanes=2, max_len=16, sampling_cfg=sc)
    want = eng1.generate_all(PROMPTS, max_new_tokens=40, seed=0)
    eng2 = BatchedEngine(cfg, params, lanes=2, max_len=16, sampling_cfg=sc)
    got = eng2.generate_all(PROMPTS, max_new_tokens=40, seed=0, chunk=8)
    assert got == want
    assert len(eng2.free) == 2


def test_lanes_match_solo_engine_sliding_window():
    """Continuous batching on a sliding-window model (tiny-gptoss): lanes at
    RAGGED fill levels exercise the per-row [B] branch of the windowed KV
    read (_windowed_slice vmapped slices) — every lane must still equal its
    solo engine run past the window."""
    from inferd_tpu.config import TINY_GPT_OSS

    cfg = TINY_GPT_OSS
    params = qwen3.init_params(cfg, jax.random.PRNGKey(23))
    sc = SamplingConfig(temperature=0.0)
    eng = BatchedEngine(cfg, params, lanes=3, max_len=64, sampling_cfg=sc)
    got = eng.generate_all(PROMPTS, max_new_tokens=12, seed=7)  # past window 8

    solo = Engine(cfg, params, max_len=64, sampling_cfg=sc)
    for i, p in enumerate(PROMPTS):
        want = solo.generate(p, max_new_tokens=12, seed=7 + i)
        assert got[i] == want, f"lane for prompt {i} diverged"


def test_admit_capacity_guard(setup):
    cfg, params = setup
    eng = BatchedEngine(cfg, params, lanes=1, max_len=64)
    eng.admit([1, 2, 3])
    with pytest.raises(RuntimeError, match="free lanes"):
        eng.admit([4, 5])
    with pytest.raises(BufferError):
        BatchedEngine(cfg, params, lanes=1, max_len=8).admit(list(range(8)))


# ---------------------------------------------------------------------------
# the programs are a function of the configuration (core.batch.lane_programs)
# ---------------------------------------------------------------------------

PROGRAMS = ("_prefill_lane", "_decode_all", "_decode_scan", "_decode_k_serve", "_decode_logits", "_prefill_lane_logits",
            "_block_step", "_decode_logits_paged", "_prefill_lane_logits_paged", "_copy_blocks",
            "_fork_lane")

# one tiny preset behind each layout the benchmark's cells serve
LAYOUTS = {
    "heads": ("tiny", dict(head_dim=128), {}),
    "rows": ("tiny", {}, {}),
    "latent": ("tiny-dsv2", {}, {}),
    "ring": ("tiny-afmoe", {}, {}),
    "state": ("tiny-granite-h", {}, {}),
    "gated_delta": ("tiny-qwen3-next", {}, {}),
    "folded_state": ("tiny-olmo-hybrid", {}, {}),
    "stream": ("tiny-xing4", {}, {}),
    "single_sublayers": ("tiny-nemotron-h", {}, {}),
    "block": ("tiny-sdar", {}, {}),
    "paged": ("tiny", {}, dict(block_size=16, kv_blocks=12)),
}


def _compiled(eng):
    """Entries in the compile cache of each of the engine's programs."""
    return {name: getattr(eng, name)._cache_size() for name in PROGRAMS}


def _serve(ex, width):
    """A prefill chunk and one decode hop (a model generated by blocks: one
    block hop) through the executor; the tokens chosen."""
    n = 2 * width
    first = ex.process("s", {"tokens": [list(range(3, 3 + n))], "start_pos": 0, "real_len": n})
    hop = {"tokens": [[int(np.argmax(first["logits"][0]))] * width], "start_pos": n, "real_len": width}
    if width > 1:
        hop["block"] = {"known": 0}
    else:
        hop["sampling"] = {"temperature": 0.0}
    return [int(np.argmax(first["logits"][0]))] + list(ex.process("s", hop)["tokens"][0])


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_second_engine_of_a_configuration_compiles_nothing(layout):
    import dataclasses

    from inferd_tpu.config import get_config
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    model, over, kw = LAYOUTS[layout]
    cfg = dataclasses.replace(get_config(model), **over)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    width = cfg.block_length if cfg.is_block_diffusion else 1
    first = BatchedExecutor(cfg, params, lanes=2, max_len=64, **kw)
    want = _serve(first, width)
    second = BatchedExecutor(cfg, params, lanes=2, max_len=64, **kw)
    held = _compiled(second.engine)
    assert sum(held.values()) >= 2  # the first one's prefill and its step
    assert _serve(second, width) == want
    assert _compiled(second.engine) == held


@pytest.mark.parametrize("what", ["sampling", "lanes", "paged", "config", "switch"])
def test_engines_that_differ_share_no_program(setup, what, monkeypatch):
    """The key holds what the bodies read: the sampling config (baked into
    the library loop's programs), the lane count (`L` of the packed rows),
    paged or dense (`routes`), the model, the process-wide switches."""
    import dataclasses

    cfg, params = setup
    base = dict(lanes=3, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
    other = {
        "sampling": dict(base, sampling_cfg=SamplingConfig(temperature=0.9)),
        "lanes": dict(base, lanes=4),
        "paged": dict(base, block_size=16),
        "config": base,
        "switch": base,
    }[what]
    a = BatchedEngine(cfg, params, **base)
    same = BatchedEngine(cfg, params, **dict(base, max_len=128))  # `max_len` arrives with the cache
    if what == "switch":  # read when the model is TRACED: a kernel forced on (core.batch.traced_switches)
        from inferd_tpu.ops import quant

        monkeypatch.setattr(quant, "FORCE_QUANT_KERNEL", True)
    b = BatchedEngine(dataclasses.replace(cfg, rope_theta=5e5) if what == "config" else cfg, params, **other)
    for name in PROGRAMS:
        assert getattr(a, name) is getattr(same, name)
        assert getattr(a, name) is not getattr(b, name)


def test_an_evicted_entry_takes_nothing_from_a_living_engine(setup):
    from inferd_tpu.core import batch as batchlib

    cfg, params = setup
    sc = SamplingConfig(temperature=0.0)
    eng = BatchedEngine(cfg, params, lanes=2, max_len=64, sampling_cfg=sc)
    want = eng.generate_all(PROMPTS[:2], max_new_tokens=4)
    held = _compiled(eng)
    for lanes in range(100, 100 + batchlib.PROGRAM_SETS):  # closures only: nothing is traced
        batchlib.lane_programs(cfg, sc, lanes, False)
    assert batchlib.lane_programs.cache_info().currsize == batchlib.PROGRAM_SETS
    assert batchlib.lane_programs(cfg, sc, 2, False).decode_scan is not eng._decode_scan  # evicted, made anew
    assert eng.generate_all(PROMPTS[:2], max_new_tokens=4) == want
    assert _compiled(eng) == held  # the engine's own references: still compiled


def test_a_program_set_on_an_instance_shadows_its_own_only(setup):
    cfg, params = setup
    a = BatchedEngine(cfg, params, lanes=2, max_len=64)
    b = BatchedEngine(cfg, params, lanes=2, max_len=64)
    real = a._decode_logits

    def slow_step(*args, **kw):
        return real(*args, **kw)

    a._decode_logits = slow_step  # as a test or a node's compile watch does
    assert b._decode_logits is real
    assert BatchedEngine(cfg, params, lanes=2, max_len=64)._decode_logits is real
