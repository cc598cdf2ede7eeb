"""Feature-composition matrix: every engine must produce its reference
output under every storage/compute variant — quantized weights (dequant /
w8a8 / Pallas kernel) x compressed KV (fp8) x engines (plain, batched,
speculative). Features that each pass alone but corrupt state when
composed are a classic integration failure mode; this pins the grid."""

import dataclasses

import jax
import pytest

from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.generate import Engine
from inferd_tpu.core.speculative import SpeculativeEngine
from inferd_tpu.models import qwen3
from inferd_tpu.ops import quant

VARIANTS = [
    ("bf16", "none", "model"),
    ("int8", "int8", "model"),
    ("w8a8", "w8a8", "model"),
    ("kernel", "int8-kernel", "model"),
    ("fp8kv", "none", "float8_e4m3fn"),
    ("int8+fp8kv", "int8", "float8_e4m3fn"),
]

GREEDY = SamplingConfig(temperature=0.0)
PROMPTS = [[3, 7, 11], [2, 5, 13, 17]]


@pytest.fixture(scope="module")
def base_params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


def _setup(base_params, quant_flag, kv_dtype):
    cfg = TINY if kv_dtype == "model" else dataclasses.replace(TINY, kv_dtype=kv_dtype)
    params = quant.apply_quant_mode(
        quant_flag, base_params, tie_word_embeddings=cfg.tie_word_embeddings
    )
    return cfg, params


@pytest.mark.parametrize("name,quant_flag,kv_dtype", VARIANTS,
                         ids=[v[0] for v in VARIANTS])
def test_engines_agree_under_variant(base_params, name, quant_flag, kv_dtype):
    cfg, params = _setup(base_params, quant_flag, kv_dtype)
    try:
        solo = Engine(cfg, params, max_len=64, sampling_cfg=GREEDY)
        want = [solo.generate(p, max_new_tokens=6, seed=0) for p in PROMPTS]

        batched = BatchedEngine(cfg, params, lanes=2, max_len=64, sampling_cfg=GREEDY)
        got_b = batched.generate_all(PROMPTS, max_new_tokens=6, seed=0)
        assert got_b == want, f"batched diverged under {name}"

        spec = SpeculativeEngine(cfg, params, cfg, params, k=3, max_len=64)
        got_s, _ = spec.generate(PROMPTS[0], max_new_tokens=6)
        assert got_s == want[0], f"speculative diverged under {name}"
    finally:
        quant.QDOT_MODE = "dequant"  # module default for other tests


@pytest.mark.parametrize("name,quant_flag,kv_dtype", [
    ("int8", "int8", "model"),
    ("fp8kv", "none", "float8_e4m3fn"),
    ("int8+fp8kv", "int8", "float8_e4m3fn"),
], ids=["int8", "fp8kv", "int8+fp8kv"])
def test_pipelined_engine_agrees_under_variant(base_params, name, quant_flag, kv_dtype):
    """The in-mesh pp pipeline under the same variants: sharded QuantWeight
    placement + compressed sharded caches must not perturb tokens."""
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.infer import PipelinedEngine

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 devices")
    cfg, params = _setup(base_params, quant_flag, kv_dtype)
    try:
        solo = Engine(cfg, params, max_len=64, sampling_cfg=GREEDY)
        want = [solo.generate(p, max_new_tokens=6, seed=0) for p in PROMPTS]

        mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2), devs[:2])
        eng = PipelinedEngine(
            cfg, params, mesh, num_microbatches=2, batch=1, max_len=64,
            sampling_cfg=GREEDY,
        )
        got = eng.generate(PROMPTS, max_new_tokens=6)
        assert got == want, f"pipelined diverged under {name}"
    finally:
        quant.QDOT_MODE = "dequant"


def test_pipelined_pp_tp_maximal_composition(base_params):
    """The maximal serving stack in one program: pp x tp mesh x int8
    weights x fp8 KV. Sharded QuantWeight leaves (q + scale specs), a
    tp-sharded compressed cache, Megatron psums, and ppermute hops must
    compose to the exact tokens of the solo engine under the same
    quant/kv variant."""
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.infer import PipelinedEngine

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    cfg, params = _setup(base_params, "int8", "float8_e4m3fn")
    try:
        solo = Engine(cfg, params, max_len=64, sampling_cfg=GREEDY)
        want = [solo.generate(p, max_new_tokens=6, seed=0) for p in PROMPTS]

        mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, tp=2), devs[:4])
        eng = PipelinedEngine(
            cfg, params, mesh, num_microbatches=2, batch=1, max_len=64,
            sampling_cfg=GREEDY,
        )
        got = eng.generate(PROMPTS, max_new_tokens=6)
        assert got == want, "pp x tp x int8 x fp8kv diverged"
    finally:
        quant.QDOT_MODE = "dequant"


@pytest.mark.parametrize("name,quant_flag,kv_dtype", VARIANTS,
                         ids=[v[0] for v in VARIANTS])
def test_lane_spec_agrees_under_variant(base_params, name, quant_flag, kv_dtype):
    """Round 5: the LANE-batched speculative engine joins the grid — its
    greedy stream must equal the solo engine under every weight/KV storage
    variant (the verify chunk writes through the same compressed cache the
    regular steps use)."""
    from inferd_tpu.core.spec_batch import (
        LaneSpecRunner, generate_lanes, make_draft_cache,
    )

    cfg, params = _setup(base_params, quant_flag, kv_dtype)
    try:
        solo = Engine(cfg, params, max_len=64, sampling_cfg=GREEDY)
        want = [solo.generate(p, max_new_tokens=6, seed=0) for p in PROMPTS]

        engine = BatchedEngine(cfg, params, lanes=2, max_len=64,
                               sampling_cfg=GREEDY)
        runner = LaneSpecRunner(cfg, cfg, k=3)
        dcache = make_draft_cache(cfg, 2, 64)
        got, _, _ = generate_lanes(
            engine, runner, params, params, dcache, PROMPTS,
            max_new_tokens=6,
        )
        assert got == want, f"lane spec diverged under {name}"
    finally:
        quant.QDOT_MODE = "dequant"
