"""The chip's compiler, asked without the chip: every Pallas kernel in ops/
and one whole serving step, compiled for a DESCRIBED v5e at the published
widths of Qwen3-0.6B and Qwen3-8B.

Interpret mode (tests/test_kernels.py) checks what a kernel computes; it
cannot see what Mosaic refuses — a block whose last two dimensions break the
(8, 128) tiling, a shift the v5e does not lower, a kernel over the VMEM
budget. These compiles cost about two seconds each and no chip time. Nothing
here runs: a compile that passes says nothing about results or speed.

The topology is described inside a module-scoped fixture and nowhere else
(never at import, in a skipif or a parametrize argument): only one process
may load the TPU library, and every xdist worker imports every test file.
All of these tests live in this one file for the same reason — a second file
could land on another worker, where the fixture would skip in silence.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from inferd_tpu.config import get_config
from inferd_tpu.ops import attention as att
from inferd_tpu.ops import lora as lora_ops
from inferd_tpu.ops import qmatmul, quant

BF16 = jnp.bfloat16
FP8 = jnp.float8_e4m3fn

# (Nq, Nkv, D, hidden, intermediate) at published widths
WIDTHS = {
    "qwen3-0.6b": (16, 8, 128, 1024, 3072),
    "qwen3-8b": (32, 8, 128, 4096, 12288),
}
VOCAB = 151936
LANES = 8  # run_node --batch-lanes 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


@pytest.fixture()
def expert_kernel(monkeypatch):
    """The routed layer's grouped product as the chip runs it: off a TPU it
    asks for the Pallas interpreter (models.qwen3.grouped_expert_ffn), which
    would compile, for the described chip, loops that are not its kernel."""
    from inferd_tpu.models import qwen3

    monkeypatch.setattr(qwen3, "is_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    """Shapes of an abstract pytree, placed on the described chip."""
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _compile(fn, *args):
    """Lower + compile for the described chip; returns the compiled text.
    Raises whatever the chip's compiler would raise."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"


# ---------------------------------------------------------------------------
# attention kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("kv_dtype", [BF16, FP8], ids=["bf16kv", "fp8kv"])
@pytest.mark.parametrize("stream", [True, False], ids=["stream", "resident"])
@pytest.mark.parametrize("s,t", [(2048, 4096), (1, 4096)],
                         ids=["prefill", "decode"])
def test_flash_gqa_compiles(one_chip, no_compile_cache, model, kv_dtype,
                            stream, s, t):
    nq, nkv, d, _h, _i = WIDTHS[model]
    q = _sds((1, s, nq, d), BF16, one_chip)
    k = _sds((1, t, nkv, d), kv_dtype, one_chip)
    start = _sds((1,), jnp.int32, one_chip)

    def fn(q, k, v, q_start, kv_len):
        return att.flash_gqa(q, k, v, q_start, kv_len, stream=stream)

    _assert_kernel(_compile(fn, q, k, k, start, start))


@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("kv_dtype", [BF16, FP8], ids=["bf16kv", "fp8kv"])
def test_paged_decode_gqa_compiles(one_chip, no_compile_cache, model,
                                   kv_dtype):
    """run_node --batch-lanes 8 --paged-kv 32 at max_len 4096: 128-block
    chains over a fully provisioned pool."""
    nq, nkv, d, _h, _i = WIDTHS[model]
    bs, mb = 32, 4096 // 32
    q = _sds((LANES, 1, nq, d), BF16, one_chip)
    pool = _sds((1 + LANES * mb, bs, nkv, d), kv_dtype, one_chip)
    table = _sds((LANES, mb), jnp.int32, one_chip)
    qpos = _sds((LANES, 1), jnp.int32, one_chip)
    kv_len = _sds((LANES,), jnp.int32, one_chip)

    def fn(q, kp, vp, tbl, qpos, kv_len):
        return att.paged_decode_gqa(q, kp, vp, tbl, qpos, kv_len)

    _assert_kernel(_compile(fn, q, pool, pool, table, qpos, kv_len))


# ---------------------------------------------------------------------------
# dequant-fused decode matmuls
# ---------------------------------------------------------------------------


def _matmul_shapes():
    """(M, K, N) of the decode matmuls: the fused q/k/v, the MLP up and down
    projections and the vocabulary head, at one lane and at eight."""
    out = []
    for name, (nq, nkv, d, h, i) in sorted(WIDTHS.items()):
        out += [
            pytest.param(1, h, (nq + 2 * nkv) * d, id=f"{name}-qkv-m1"),
            pytest.param(LANES, h, i, id=f"{name}-up-m8"),
            pytest.param(LANES, i, h, id=f"{name}-down-m8"),
            pytest.param(LANES, h, VOCAB, id=f"{name}-head-m8"),
        ]
    return out


@pytest.mark.parametrize("m,k,n", _matmul_shapes())
def test_w8a16_matmul_compiles(one_chip, no_compile_cache, m, k, n):
    x = _sds((m, k), BF16, one_chip)
    q = _sds((k, n), jnp.int8, one_chip)
    scale = _sds((n,), jnp.float32, one_chip)
    _assert_kernel(_compile(qmatmul.w8a16_matmul, x, q, scale))


@pytest.mark.parametrize("scheme", ["dequant", "grouped"])
@pytest.mark.parametrize("m,k,n", _matmul_shapes())
def test_w4a16_matvec_compiles(one_chip, no_compile_cache, scheme, m, k, n):
    w = jax.eval_shape(
        quant.quantize_int4, jax.ShapeDtypeStruct((k, n), jnp.float32)
    )
    assert w.packed
    x = _sds((m, k), BF16, one_chip)

    def fn(x, w):
        return qmatmul.w4a16_matvec(x, w, scheme=scheme)

    _assert_kernel(_compile(fn, x, _on(w, one_chip)))


# ---------------------------------------------------------------------------
# fused LoRA lane-delta
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_fused_lane_delta_compiles(one_chip, no_compile_cache, model):
    """Eight lanes, rank 16, the widest projection pair of each model."""
    _nq, _nkv, _d, h, i = WIDTHS[model]
    slots, layers, r = 5, 4, 16
    x = _sds((LANES, 1, h), BF16, one_chip)
    a = _sds((slots, layers, h, r), BF16, one_chip)
    b = _sds((slots, layers, r, i), BF16, one_chip)
    scale = _sds((slots,), jnp.float32, one_chip)
    ids = _sds((LANES,), jnp.int32, one_chip)
    layer = _sds((), jnp.int32, one_chip)
    _assert_kernel(_compile(lora_ops.fused_lane_delta, x, a, b, scale, ids, layer))


# ---------------------------------------------------------------------------
# one whole serving step, as run_node --batch-lanes 8 builds it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_size", [0, 32], ids=["dense", "paged32"])
def test_batched_decode_step_compiles(one_chip, no_compile_cache, block_size):
    """The jitted decode step of core.batch.BatchedEngine — the program a
    --batch-lanes 8 node runs per token — for all 28 layers of Qwen3-0.6B
    over a 4096-token cache, dense and --paged-kv 32. The engine is built
    over a 64-token cache (its jits close over nothing the cache length
    changes) and lowered with the serving shapes."""
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache, PagedKVCache
    from inferd_tpu.models import qwen3

    cfg = get_config("qwen3-0.6b")
    max_len = 4096
    params = jax.eval_shape(
        lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))
    )
    eng = BatchedEngine(
        cfg, None, lanes=LANES, max_len=64, block_size=block_size
    )
    toks = _sds((LANES,), jnp.int32, one_chip)
    if block_size:
        mb = max_len // block_size
        pool = (cfg.num_layers, 1 + LANES * mb, block_size,
                cfg.num_kv_heads, cfg.head_dim)
        small = eng.cache
        cache = PagedKVCache(
            k=_sds(pool, small.k.dtype, one_chip),
            v=_sds(pool, small.v.dtype, one_chip),
            table=_sds((LANES, mb), jnp.int32, one_chip),
            length=_sds(small.length.shape, small.length.dtype, one_chip),
        )
        active = _sds((LANES,), jnp.bool_, one_chip)
        compiled = eng._decode_logits_paged.lower(
            _on(params, one_chip), cache, toks, toks, active
        ).compile()
    else:
        cache = _on(
            jax.eval_shape(
                lambda: KVCache.create(cfg, cfg.num_layers, LANES, max_len)
            ),
            one_chip,
        )
        compiled = eng._decode_logits.lower(
            _on(params, one_chip), cache, toks, toks
        ).compile()
    mem = compiled.memory_analysis()
    # weights + cache + logits of this one program fit a 16 GB chip, and
    # the donated cache (lanes or pool, 3.76 GB) is written where it lies:
    # 0.001 / 0.07 GB of temporaries, no second copy of it
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert mem.temp_size_in_bytes < 0.2e9
    assert mem.alias_size_in_bytes > 3.7e9


@pytest.mark.parametrize("lanes", [5, 8])
def test_q4b_decode_step_writes_the_donated_cache_where_it_lies(one_chip, no_compile_cache, lanes):
    """The decode step of `--model qwen3-4b --batch-lanes <lanes> --max-len
    4096` (5: the cell q4b-sat-chat). The layer scan carries the lanes and a
    layer writes its rows into them (models/qwen3.forward_layers): the
    program holds no second copy of the lanes (3.02 GB at 5 lanes; with one,
    8 lanes do not fit), every byte of the donated cache is aliased to the
    output, and no `copy` makes an array of the cache's shape. At 8 lanes
    the arguments and temporaries fit the chip's 15.75 GB."""
    import re

    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config("qwen3-4b")
    params = _on(jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    eng = BatchedEngine(cfg, None, lanes=lanes, max_len=64)
    cache = jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, lanes, 4096))
    toks = _sds((lanes,), jnp.int32, one_chip)
    step = eng._decode_logits.lower(params, _on(cache, one_chip), toks, toks).compile()
    mem = step.memory_analysis()
    assert mem.temp_size_in_bytes < 1e9
    assert mem.alias_size_in_bytes >= cache.k.size * 2 * 2  # K and V, two bytes a value
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    lanes_shape = f"bf16[{cfg.num_layers},{lanes},4096,{cfg.num_kv_heads},{cfg.head_dim}]"
    copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", step.as_text())
    assert copies and lanes_shape not in copies, [c for c in copies if c == lanes_shape]


def test_q4b_step_and_chunk_read_their_slabs_by_prefix_and_copy_none(one_chip, no_compile_cache):
    """The two programs of the cells `q4b-sat-chat` / `q4b-long-prompt` at their
    own shapes (5 lanes of 4096 slots; a 512-token chunk): a layer reads its
    slab through ONE conditional of eight branches (models/qwen3._lanes_read),
    each slicing [layer, :, :rung] out of the stack where it lies. Held: the
    slices are there at every rung; no branch copies the stack to the dot's
    layout (the first form did: 1.51 GB of temporaries a step, a `copy` of
    `bf16[36,5,4096,8,128]{4,2,3,1,0}` in every branch); the step has no
    temporary of a slab's size (42 MB a layer; 0.002 GB in all) and the chunk
    none beyond the lane it is handed (0.91 GB, the parent's); the donated
    cache is aliased whole (3.02 GB)."""
    import re

    from inferd_tpu.core import sampling as samplib
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config("qwen3-4b")
    lanes, max_len = 5, 4096
    stack = jax.ShapeDtypeStruct((cfg.num_layers, lanes, max_len, cfg.num_kv_heads, cfg.head_dim), BF16)
    rungs = qwen3.read_rungs(cfg, (stack, stack), 1, lanes, False)
    assert rungs == tuple(range(512, 4097, 512))
    params = _on(jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    eng = BatchedEngine(cfg, None, lanes=lanes, max_len=64)
    cache = jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, lanes, max_len))
    toks = _sds((lanes,), jnp.int32, one_chip)
    ask = samplib.RowAsk(_sds((lanes, 2), jnp.uint32, one_chip), _sds((lanes, 4), jnp.float32, one_chip))
    i32 = _sds((), jnp.int32, one_chip)
    step = eng._decode_logits.lower(params, _on(cache, one_chip), toks, toks, ask=ask, top_n=0).compile()
    chunk = eng._prefill_lane_logits.lower(
        params, _on(cache, one_chip), _sds((1, 512), jnp.int32, one_chip), i32, i32, i32).compile()
    slab = lanes * max_len * cfg.num_kv_heads * cfg.head_dim * 2
    for program, rows, temp in ((step, lanes, slab), (chunk, 1, 0.95e9)):
        mem, text = program.memory_analysis(), program.as_text()
        assert mem.temp_size_in_bytes < temp
        assert mem.alias_size_in_bytes >= cache.k.size * 2 * 2
        for rung in rungs:
            assert f"dynamic_slice_sizes={{1,{rows},{rung},{cfg.num_kv_heads},{cfg.head_dim}}}" in text
        stack = f"bf16[{cfg.num_layers},{rows},{max_len},{cfg.num_kv_heads},{cfg.head_dim}]"
        copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", text)
        assert stack not in copies


def test_dsv2l_lane_programs_compile_and_decode_expands_no_head_over_the_cache(
    one_chip, no_compile_cache, expert_kernel
):
    """The two programs a `--model deepseek-v2-lite-8l --batch-lanes 16
    --max-len 4096` node runs, at the published widths: both fit the chip
    with the weights (9.19 GB) and the 16 lanes of latents, and the decode
    program is absorbed — of the arrays it holds over the cache's 4096
    slots none has a head axis of 192 or 128 values (a key or a value per
    head); the cache itself is [layers, 16, 4096, 512] and [.., 64]."""
    import re

    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config("deepseek-v2-lite-8l")
    lanes, max_len = 16, 4096
    params = _on(jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    eng = BatchedEngine(cfg, None, lanes=lanes, max_len=64)
    cache = _on(jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, lanes, max_len)),
                one_chip)
    toks = _sds((lanes,), jnp.int32, one_chip)
    decode = eng._decode_logits.lower(params, cache, toks, toks).compile()
    mem = decode.memory_analysis()
    assert 9.7e9 < mem.argument_size_in_bytes < 9.9e9  # weights + 0.60 GB of latents
    # the latents are written where they lie: 0.14 GB of temporaries, no
    # second copy of the 0.60 GB of lanes
    assert mem.temp_size_in_bytes < 0.25e9
    assert mem.alias_size_in_bytes > 0.6e9
    # over any rung of the lanes (PR 56: eighths of 4096), as over the lanes themselves
    rungs = "|".join(str(r) for r in range(512, 4097, 512))
    over_cache = set(re.findall(rf"(?:bf16|f32)\[(?:[0-9]+,)*(?:{rungs})(?:,[0-9]+)*\]", decode.as_text()))
    assert "bf16[8,16,4096,512]" in over_cache and "bf16[8,16,4096,64]" in over_cache
    per_head = [s for s in over_cache  # [lanes, slots, heads, a head's width], or heads before slots
                if re.search(r"\b16,[0-9]+,16,(192|128)\]|\b16,16,[0-9]+,(192|128)\]", s)]
    assert not per_head, per_head
    i32 = _sds((), jnp.int32, one_chip)
    chunk = _sds((1, 512), jnp.int32, one_chip)
    prefill = eng._prefill_lane_logits.lower(params, cache, chunk, i32, i32, i32).compile()
    assert prefill.memory_analysis().temp_size_in_bytes < 0.25e9  # 0.18 GB (0.15 with the lane read whole)
    # both read a lane by its prefix: the step's temporaries are what they were (0.14 GB)
    assert mem.temp_size_in_bytes < 0.15e9
    _assert_latent_lanes_read_by_prefix(cfg, decode, prefill, 8, lanes, max_len, r_copies=2)


@pytest.mark.parametrize("model, lanes", [("qwen3-4b", 5), ("deepseek-v2-lite-8l", 16)],
                         ids=["q4b-sat-chat", "dsv2l-long-chat"])
@pytest.mark.parametrize("top_n", [0, 8])
def test_decode_step_with_its_sampler_still_writes_the_cache_in_place(
    one_chip, no_compile_cache, expert_kernel, model, lanes, top_n
):
    """The decode step the cells run since the step chooses the tokens
    (`_decode_logits` with an ask: core.sampling.choose_rows after the head,
    per-lane sampling traced; `top_n` 8: the probe's variant), against the
    same program without an ask (the parent's, byte for byte): the donated
    cache is aliased to the output to the same byte, and the sampler's
    temporaries are a megabyte (0.68 -> 1.65 MB at qwen3-4b, 135.9 -> 136.9
    MB at deepseek-v2-lite-8l: the candidates' `top_k` and the packed rows),
    under 1 % of the parent's and 2 MB."""
    from inferd_tpu.core import sampling as samplib
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config(model)
    params = _on(jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    eng = BatchedEngine(cfg, None, lanes=lanes, max_len=64)
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, lanes, 4096))
    cache = _on(shapes, one_chip)
    toks = _sds((lanes,), jnp.int32, one_chip)
    ask = samplib.RowAsk(_sds((lanes, 2), jnp.uint32, one_chip),
                         _sds((lanes, 4), jnp.float32, one_chip))
    parent = eng._decode_logits.lower(params, cache, toks, toks).compile().memory_analysis()
    step = eng._decode_logits.lower(params, cache, toks, toks, ask=ask, top_n=top_n).compile()
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes == parent.alias_size_in_bytes
    assert mem.alias_size_in_bytes >= (shapes.k.size + shapes.v.size) * 2  # two bytes a value
    assert mem.temp_size_in_bytes <= parent.temp_size_in_bytes * 1.01 + 2e6
    # the step hands the host ONE int32 array beside the logits that stay
    packed = 3 + (1 + 2 * top_n if top_n else 0)
    if cfg.is_moe:
        packed += (cfg.num_layers - cfg.first_k_dense_replace) * cfg.num_experts_per_tok
    assert f"s32[{lanes},{packed}]" in step.as_text()


def test_sdar_block_program_compiles_at_16_lanes(one_chip, no_compile_cache, expert_kernel):
    """The two programs a `--model sdar-30b-a3b-7l --batch-lanes 16 --max-len
    4096` node runs, at the published widths: the block step (two denoising
    passes and the commit over [16, 4] rows in one dispatch, with and
    without the top log-probabilities) and a 512-token prefill chunk fit the
    chip with the weights (9.97 GB) and 16 lanes x 4096 of keys and values
    (0.94 GB); the numbers are the ones in the configuration's `deployment`
    (benchmark/configs/sdar-30b-a3b-1chip.json)."""
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config("sdar-30b-a3b-7l")
    lanes, max_len, blk = 16, 4096, cfg.block_length
    params = _on(jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    eng = BatchedEngine(cfg, None, lanes=lanes, max_len=64)
    cache = _on(jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, lanes, max_len)),
                one_chip)
    toks = _sds((lanes, blk), jnp.int32, one_chip)
    known = _sds((lanes, blk), jnp.bool_, one_chip)
    lens = _sds((lanes,), jnp.int32, one_chip)
    live = _sds((lanes,), jnp.bool_, one_chip)
    keys = _sds((lanes, 2), jnp.uint32, one_chip)
    for top_n in (0, 8):
        step = eng._block_step.lower(params, cache, toks, known, lens, live, keys,
                                     top_n=top_n).compile()
        mem = step.memory_analysis()
        assert 10.8e9 < mem.argument_size_in_bytes < 11.0e9  # 9.97 GB of weights + 0.94 of cache
        # the cache is donated and its lanes are written where they lie, in
        # all three passes: 0.15-0.19 GB of temporaries, no copy of the
        # 0.94 GB of lanes
        assert mem.temp_size_in_bytes < 0.3e9
        assert mem.alias_size_in_bytes > 0.9e9
    i32 = _sds((), jnp.int32, one_chip)
    chunk = _sds((1, 512), jnp.int32, one_chip)
    prefill = eng._prefill_lane_logits.lower(params, cache, chunk, i32, i32, i32).compile()
    assert prefill.memory_analysis().temp_size_in_bytes < 0.4e9  # 0.25 GB


def _lane_programs(cfg, lanes, max_len, one_chip, active):
    """(cache shapes, the compiled decode step with its sampler, the compiled
    512-token prefill chunk) of a `--batch-lanes` node, for the described chip."""
    from inferd_tpu.core import sampling as samplib
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    params = _on(jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    eng = BatchedEngine(cfg, None, lanes=lanes, max_len=64)
    shapes = jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, lanes, max_len))
    cache = _on(shapes, one_chip)
    toks = _sds((lanes,), jnp.int32, one_chip)
    ask = samplib.RowAsk(_sds((lanes, 2), jnp.uint32, one_chip),
                         _sds((lanes, 4), jnp.float32, one_chip))
    step = eng._decode_logits.lower(
        params, cache, toks, toks, ask=ask, top_n=8,
        active=_sds((lanes,), jnp.bool_, one_chip) if active else None,
    ).compile()
    i32 = _sds((), jnp.int32, one_chip)
    chunk = _sds((1, 512), jnp.int32, one_chip)
    prefill = eng._prefill_lane_logits.lower(params, cache, chunk, i32, i32, i32).compile()
    return shapes, step, prefill


def _assert_latent_lanes_read_by_prefix(cfg, step, prefill, layers, lanes, max_len, r_copies):
    """What models/qwen3._lanes_read promises of a latent cell's two programs
    at the cell's own shapes: the conditional with a branch a rung (a slice
    [1, rows, rung, R] and [1, rows, rung, Dr] of each stack at every eighth
    of the lane), no copy of the latents' stack anywhere (all 16 lanes' in the
    step, the one lane's in the chunk: the parent's chunk made two of its
    lane's), and of the roped keys' stack (64 columns: the chip lays it
    slots-minor, its rows are re-laid for the scatter and back) `r_copies` of
    all lanes' in the step at most, and two of the one lane's in the chunk:
    once in, once out, none a branch."""
    import re

    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    rungs = tuple(range(max_len // 8, max_len + 1, max_len // 8))
    for program, rows, most in ((step, lanes, r_copies), (prefill, 1, 2)):
        text = program.as_text()
        assert " conditional(" in text
        for rung in rungs:
            assert f"dynamic_slice_sizes={{1,{rows},{rung},{r}}}" in text
            assert f"dynamic_slice_sizes={{1,{rows},{rung},{dr}}}" in text
        copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", text)
        assert f"bf16[{layers},{rows},{max_len},{r}]" not in copies
        assert copies.count(f"bf16[{layers},{rows},{max_len},{dr}]") <= most


def _made_whole(text, *shapes):
    """The `copy` and `dynamic-slice` fusion operations of a compiled text
    whose result has one of the shapes (a regular expression each): the
    operations that MAKE an array of a cache's, a stack's or a slab's size
    (a `dynamic-slice` fused into the dot that reads it makes none)."""
    import re

    made = re.findall(r"^ *(?:ROOT )?(%\S+) = (\S+?)\{[^ ]* (copy|fusion)\(", text, re.M)
    return [
        (name, shape) for name, shape, op in made
        if (op == "copy" or "dynamic-slice" in name) and any(re.match(s, shape) for s in shapes)
    ]


def test_granite_hybrid_lane_programs_compile_and_the_state_is_updated_where_it_lies(
    one_chip, no_compile_cache
):
    """The two programs a `--model granite-4.0-h-micro --batch-lanes 32
    --max-len 4096` node runs, at the published widths and depth: 6.38 GB of
    weights, 2.45 GB of recurrent state and columns, 1.07 GB of keys and
    values. The decode step (with its sampler and the lanes' `active` mask,
    as the executor calls it) aliases the whole donated cache to its output
    and holds no second copy of the state stack `f32[36,32,64,64,128]` among
    its temporaries, nor of a period's weights (the two weight stacks are
    read where they lie: folded into the scan's inputs, nine layers of them
    were copied out a period, 5.8 GB of temporaries). Nor of the four
    attention layers' keys and values: 8 kv heads of 64 are ONE row of 512 a
    token (core.cache.rows_layout), written and read where the stack lies:
    under 0.3 GB of temporaries (0.007; as `[4,32,4096,8,64]` the stacks
    were re-laid T-minor around the layer loop and each slab again inside
    it: 2.19 GB, PR 42), no `copy` and no `dynamic-slice` fusion that makes
    a stack `bf16[4,32,4096,...]` or a layer's slab `bf16[(1,)32,4096,...]`.
    A 512-token prefill chunk likewise: no copy of the stack or of the
    lane's view of it. The numbers are the configuration's `deployment`."""
    import re

    cfg = get_config("granite-4.0-h-micro")
    shapes, step, prefill = _lane_programs(cfg, 32, 4096, one_chip, active=True)
    assert shapes.k.shape == (4, 32, 4096, 512)
    mem = step.memory_analysis()
    assert 9.85e9 < mem.argument_size_in_bytes < 9.95e9  # 6.38 GB of weights + 3.52 of cache
    assert mem.alias_size_in_bytes >= shapes.nbytes  # state, columns, keys and values: all in place
    assert mem.temp_size_in_bytes < 0.3e9
    text = step.as_text()
    copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", text)
    assert copies and not [c for c in copies if c.startswith("f32[36,32,64,64,128]")]
    assert not [c for c in copies if re.match(r"bf16\[(36|4,9|9),(2048|4096|8192),", c)]
    kv = (r"bf16\[4,32,4096,", r"bf16\[1,32,4096,", r"bf16\[32,4096,\d+[,\]]")
    assert _made_whole(text, *kv) == []
    pm = prefill.memory_analysis()
    assert pm.temp_size_in_bytes < 0.5e9 and pm.alias_size_in_bytes >= shapes.nbytes
    assert not re.findall(r"= bf16\[4,(?:32|1),4096,[^ ]* copy\(", prefill.as_text())


def test_trinity_share_lane_programs_compile_with_rings_and_the_cache_in_place(
    one_chip, no_compile_cache, expert_kernel
):
    """The two programs a `--model trinity-large-ep8-5l --batch-lanes 16
    --max-len 16384` node runs, at the published widths: 8.64 GB of weights
    (32 held experts a sparse layer under a 256-wide router), four windowed
    layers as rings of 4 160 slots and one full layer's slab of 16 384:
    2.164 GB of cache, where full-length slabs for all five would be 5.37.
    The decode step (with its sampler, as the executor calls it) aliases the
    whole donated cache and holds under 0.6 GB of temporaries (0.43: the
    windowed layers' `concatenate` of ring and fresh row, 0.27 GB, is the
    largest of them), so nothing of the slab's 1.07 GB or of the rings' 1.09
    is made a second time: the re-laying `copy` of a slab or a ring in the
    compiled text sits INSIDE the fusion of the dot that reads it. A
    512-token prefill chunk: 0.96
    GB of temporaries (every token through the 32 held experts). The numbers
    are the configuration's `deployment`."""
    cfg = get_config("trinity-large-ep8-5l")
    shapes, step, prefill = _lane_programs(cfg, 16, 16384, one_chip, active=False)
    assert shapes.k.shape == (1, 16, 16384, 8, 128) and shapes.k_loc.shape == (4, 16, 4160, 8, 128)
    assert shapes.nbytes == 2_164_260_864
    mem = step.memory_analysis()
    assert 10.79e9 < mem.argument_size_in_bytes < 10.83e9  # 8.644 GB of weights + 2.164 of cache
    assert mem.alias_size_in_bytes >= shapes.nbytes
    assert mem.temp_size_in_bytes < 0.6e9
    pm = prefill.memory_analysis()
    assert pm.alias_size_in_bytes >= shapes.nbytes and pm.temp_size_in_bytes < 1.3e9
    assert mem.argument_size_in_bytes + pm.temp_size_in_bytes < 15.75e9 * 0.8


def test_qwen3_next_share_lane_programs_compile_with_state_and_rows_in_place(
    one_chip, no_compile_cache, expert_kernel
):
    """The two programs a `--model qwen3-next-80b-ep4-8l --batch-lanes 16
    --max-len 32768` node runs, at the published widths: 7.33 GB of weights
    (128 held experts a layer under a 512-wide router, in BOTH weight
    stacks), six layers' float32 delta-rule state `f32[6,16,32,128,128]` and
    columns, two full layers' keys and values as ONE row of 512 a token: 2.354
    GB of cache. The decode step (with its sampler and the lanes' `active`
    mask, as the executor calls it) aliases the whole donated cache and holds
    under 0.1 GB of temporaries (0.013): no second copy of the state stack,
    of a slab or of a layer's experts. As `[.., 2, 256]` the slabs compiled
    unpadded (tiles of T(2,128)) but each was copied T-minor before its dot:
    0.54 GB of temporaries (core.cache.rows_layout). A 512-token prefill chunk:
    0.85 GB of temporaries. The numbers are the configuration's `deployment`."""
    import re

    cfg = get_config("qwen3-next-80b-ep4-8l")
    shapes, step, prefill = _lane_programs(cfg, 16, 32768, one_chip, active=True)
    assert shapes.k.shape == (2, 16, 32768, 512) and shapes.s.shape == (6, 16, 32, 128, 128)
    assert shapes.nbytes == 2_353_528_832 and shapes.state_bytes == 16 * 12_877_824
    mem = step.memory_analysis()
    assert 9.68e9 < mem.argument_size_in_bytes < 9.70e9  # 7.335 GB of weights + 2.354 of cache
    assert mem.alias_size_in_bytes >= shapes.nbytes
    assert mem.temp_size_in_bytes < 0.1e9
    text = step.as_text()
    copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", text)
    assert not [c for c in copies if c.startswith(("f32[6,16,32,128,128]", "bf16[6,128,", "bf16[2,128,"))]
    assert _made_whole(text, r"bf16\[2,16,32768,", r"bf16\[1,16,32768,", r"bf16\[16,32768,\d+[,\]]") == []
    pm = prefill.memory_analysis()
    assert pm.alias_size_in_bytes >= shapes.nbytes and pm.temp_size_in_bytes < 1.1e9
    assert mem.argument_size_in_bytes + pm.temp_size_in_bytes < 15.75e9 * 0.8


def test_olmo_hybrid_lane_programs_compile_with_the_state_unpadded_and_the_rows_in_place(
    one_chip, no_compile_cache
):
    """The two programs a `--model olmo-hybrid-7b-16l --batch-lanes 16
    --max-len 4096` node runs, at the published widths: 8.20 GB of weights,
    twelve layers' float32 delta-rule state HELD two heads side by side,
    `f32[12,16,15,96,384]` in whole tiles (as `[.., 30, 96, 192]` every row of
    192 pads to 256, a third more), four full layers' keys and values as ONE
    row of 3 840 a token (as `[.., 30, 128]` the head axis pads to 32 and the
    decode step re-laid both stacks whole: 2 x 2.0 GB of temporaries, 15.93 GB
    of 15.75, refused): 4.464 GB of cache. The decode step (with its sampler
    and the lanes' `active` mask, as the executor calls it) aliases the whole
    donated cache and holds under 0.1 GB of temporaries (0.007): no copy of
    the state stack (the update as `gated_delta_mixer` writes it compiled to
    three fusions a layer and a copy of the stack around each: 24 x 0.57 GB a
    step), of a slab or of a weight stack; each linear layer reads the stack
    in ONE reduce fusion (S^T k and S^T q together) and writes it in ONE
    update where it lies. A 512-token prefill chunk: 0.65 GB of temporaries.
    The numbers are the configuration's `deployment`."""
    import re

    cfg = get_config("olmo-hybrid-7b-16l")
    shapes, step, prefill = _lane_programs(cfg, 16, 4096, one_chip, active=True)
    assert shapes.k.shape == (4, 16, 4096, 3840) and shapes.s.shape == (12, 16, 15, 96, 384)
    assert shapes.nbytes == 4_464_476_160 and shapes.state_bytes == 16 * 27_371_520
    mem = step.memory_analysis()
    assert 12.65e9 < mem.argument_size_in_bytes < 12.69e9  # 8.202 GB of weights + 4.464 of cache
    assert mem.alias_size_in_bytes >= shapes.nbytes
    assert mem.temp_size_in_bytes < 0.1e9
    text = step.as_text()
    # the state as it is held, in whole (8, 128) tiles: nothing padded
    assert re.search(r"f32\[12,16,15,96,384\]\{4,3,2,1,0:T\(8,128\)\} parameter", text)
    assert re.search(r"bf16\[4,16,4096,3840\]\{3,2,1,0:T\(8,128\)\(2,1\)\} parameter", text)
    copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", text)
    assert not [c for c in copies
                if c.startswith(("f32[12,16,15,", "f32[16,15,96,", "bf16[12,3840,", "bf16[4,3840,",
                                 "bf16[12,11008,", "bf16[4,11008,"))]
    assert _made_whole(text, r"bf16\[4,16,4096,", r"bf16\[1,16,\d+,3840", r"bf16\[16,\d{3,4},3840\]") == []
    # the fused computations handed the state stack: a period's three linear
    # layers, ONE that reads it and ONE that writes it where it lies each
    takes = re.findall(r"^%\S+ \([^)]*f32\[12,16,15,96,384\][^)]*\) -> (\S+)", text, re.M)
    assert len(takes) == 6 and sum(t.startswith("f32[12,16,15,96,384]") for t in takes) == 3, takes
    pm = prefill.memory_analysis()
    assert pm.alias_size_in_bytes >= shapes.nbytes and pm.temp_size_in_bytes < 0.9e9
    assert not re.findall(r"= bf16\[4,(?:16|1),4096,[^ ]* copy\(", prefill.as_text())
    assert mem.argument_size_in_bytes + pm.temp_size_in_bytes < 15.75e9 * 0.86


def test_xing4_lane_programs_compile_with_the_stream_inside_and_the_latents_in_place(
    one_chip, no_compile_cache, expert_kernel
):
    """The two programs a `--model xing4.0-29b-a4b-6l --batch-lanes 16
    --max-len 16384` node runs, at the published widths: 9.585 GB of weights
    (one dense and five sparse layers, all 64 experts, the whole vocabulary)
    and 16 lanes of 16 384 latent slots, 1.812 GB (6 x 1 152 B a token). The
    decode step (with its sampler, as the executor calls it) aliases the whole
    donated cache, holds 0.43 GB of temporaries and is absorbed: nothing per
    head over the 16 384 slots. A 512-token chunk attends the whole lane in
    the expanded form: 1.33 GB of temporaries (the float32 scores
    [1, 32, 512, 16384] are 1.07 of them), so weights, lanes and a chunk are
    12.73 GB of the chip's 15.75. The residual stream stays inside both
    programs: no argument or result is four hidden states wide, and the
    Sinkhorn rounds are a loop of four trips. The numbers are the
    configuration's `deployment`."""
    import re

    cfg = get_config("xing4.0-29b-a4b-6l")
    shapes, step, prefill = _lane_programs(cfg, 16, 16384, one_chip, active=False)
    assert shapes.k.shape == (6, 16, 16384, 512) and shapes.v.shape == (6, 16, 16384, 64)
    assert shapes.nbytes == 1_811_939_328
    mem = step.memory_analysis()
    assert 11.39e9 < mem.argument_size_in_bytes < 11.41e9  # 9.585 GB of weights + 1.812 of latents
    assert mem.alias_size_in_bytes >= shapes.nbytes
    assert mem.temp_size_in_bytes < 0.44e9  # 0.415 (0.429 with the lanes read whole)
    pm = prefill.memory_analysis()
    assert pm.alias_size_in_bytes >= shapes.nbytes
    assert pm.temp_size_in_bytes < 1.45e9  # 1.40, the top branch's (1.335 with the lane read whole)
    assert mem.argument_size_in_bytes + pm.temp_size_in_bytes < 15.75e9 * 0.85
    text = step.as_text()
    # over any rung of the lanes (PR 56: eighths of 16 384), as over the lanes themselves
    rungs = "|".join(str(r) for r in range(2048, 16385, 2048))
    over_cache = set(re.findall(rf"(?:bf16|f32)\[(?:[0-9]+,)*(?:{rungs})(?:,[0-9]+)*\]", text))
    assert "bf16[6,16,16384,512]" in over_cache and "bf16[6,16,16384,64]" in over_cache
    per_head = [s for s in over_cache
                if re.search(rf",(?:{rungs}),32,(192|128)\]|,32,(?:{rungs}),(192|128)\]", s)]
    assert not per_head, per_head
    _assert_latent_lanes_read_by_prefix(cfg, step, prefill, 6, 16, 16384, r_copies=2)
    signature = text[text.index("ENTRY"):].split("\n")[0]  # arguments and result
    assert "bf16[4,16,1,3584]" not in signature and "bf16[4,16,3584]" not in signature
    assert "hc_sinkhorn" in text and "while" in text


def test_nemotron_h_share_lane_programs_compile_with_three_stacks_and_the_cache_in_place(
    one_chip, no_compile_cache, expert_kernel
):
    """The two programs a `--model nemotron-3-super-120b-ep4-11l --batch-lanes
    32 --max-len 4096` node runs, at the published widths: 9.30 GB of weights
    in three stacks by kind of sublayer (5 Mamba-2, 1 attention, 5 LatentMoE of
    128 held experts under a 512-wide router), five float32 states
    `f32[5,32,128,64,128]` and their columns, ONE attention sublayer's keys
    and values as one row of 256 a token: 0.815 GB of cache. The decode step
    (with its sampler and the lanes' `active` mask, as the executor calls it)
    aliases the whole donated cache (the states updated where they lie) and
    holds under 0.1 GB of temporaries (0.019): no second copy of the state
    stack, of the slab or of a layer's experts (the grouped product finds
    them in the stack: Mosaic kernels in both programs). As `[.., 2, 128]`
    the slab compiled unpadded (T(2,128)) but the prefix a step reads was
    copied T-minor before its dot, twice a step (core.cache.rows_layout). A
    512-token prefill chunk: 0.23 GB of temporaries. The numbers are the
    configuration's `deployment`."""
    import re

    cfg = get_config("nemotron-3-super-120b-ep4-11l")
    shapes, step, prefill = _lane_programs(cfg, 32, 4096, one_chip, active=True)
    assert shapes.k.shape == (1, 32, 4096, 256) and shapes.s.shape == (5, 32, 128, 64, 128)
    assert shapes.nbytes == 815_136_768 and shapes.state_bytes == 32 * 21_278_720
    mem = step.memory_analysis()
    assert 10.10e9 < mem.argument_size_in_bytes < 10.12e9  # 9.296 GB of weights + 0.815 of cache
    assert mem.alias_size_in_bytes >= shapes.nbytes
    assert mem.temp_size_in_bytes < 0.1e9
    text = step.as_text()
    _assert_kernel(text)
    assert re.search(r"f32\[5,32,128,64,128\]\{4,3,2,1,0:T\(8,128\)\} parameter", text)
    copies = re.findall(r"= (\S+?)\{[^ ]* copy\(", text)
    assert not [c for c in copies
                if c.startswith(("f32[5,32,128,64,128]", "f32[32,128,64,128]", "bf16[5,128,", "bf16[128,1024,",
                                 "bf16[128,2688,"))]
    assert _made_whole(text, r"bf16\[1,32,4096,", r"bf16\[32,4096,\d+[,\]]") == []
    pm = prefill.memory_analysis()
    _assert_kernel(prefill.as_text())
    assert pm.alias_size_in_bytes >= shapes.nbytes and pm.temp_size_in_bytes < 0.4e9
    assert mem.argument_size_in_bytes + pm.temp_size_in_bytes < 15.75e9 * 0.8


def test_llama32_1b_lanes_keep_their_rows_where_they_lie(one_chip, no_compile_cache):
    """The other public model of this head size (8 kv heads of 64, 16
    layers), as `--model llama3.2-1b --batch-lanes 32 --max-len 4096` would
    run it: the decode step aliases its 2.1 GB cache and makes no copy of a
    stack `bf16[16,32,4096,512]` or of a layer's slab (as `[.., 8, 64]` it
    compiled to the same re-laying copies as granite's, two of its whole
    cache; PERF.md section 7), the prefill chunk none of the stack."""
    import re

    cfg = get_config("llama3.2-1b")
    shapes, step, prefill = _lane_programs(cfg, 32, 4096, one_chip, active=False)
    assert shapes.k.shape == (16, 32, 4096, 512)
    mem = step.memory_analysis()
    assert mem.alias_size_in_bytes >= shapes.nbytes
    assert mem.temp_size_in_bytes < 0.3e9
    kv = (r"bf16\[16,32,4096,", r"bf16\[1,32,4096,", r"bf16\[32,4096,\d+[,\]]")
    assert _made_whole(step.as_text(), *kv) == []
    assert prefill.memory_analysis().alias_size_in_bytes >= shapes.nbytes
    assert not re.findall(r"= bf16\[16,(?:32|1),4096,[^ ]* copy\(", prefill.as_text())


# ---------------------------------------------------------------------------
# the --mesh pipeline's decode pass, as run_node --mesh pp=4 --mesh-slots 8 builds it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sampled", [False, True], ids=["logits", "tokens"])
def test_q8b_pp4_decode_pass_carries_its_slots_as_rows_in_place(
    topo, no_compile_cache, monkeypatch, sampled
):
    """`PipelinedEngine._step_raw_multi` of `--model qwen3-8b --mesh pp=4
    --mesh-slots 8 --max-len 4096` (the cell q8b-pp4-sat-chat) for the
    described 2x2: a loop of four ticks that carries a stage's two stacks
    with the slots as rows and writes them where they lie. No `copy` (nor
    any other operation but the loops' own updates) makes an array of a
    stack's shape, both donated stacks are aliased to the output, and the
    program fits a chip: 7.17 GB of arguments a device. Its 0.454 GB of
    temporaries are the q, k and v projection weights, which this
    compiler re-lays once a pass (302 + 75.5 + 75.5 MB); nothing of the
    cache's size is among them (a slot's view of a stage is 75.5 MB, the
    rows' slab of one layer 67 MB). The engine is built over the
    described devices with its parameters and caches as shapes: placing
    arrays there is what the test has to keep it from. `tokens`: the pass
    as the cell runs it since it chooses its slots' tokens after the head
    (an ask a slot, the probe's top-8 variant): the same stacks in place,
    the same two collectives, one s32[8, 20] more for the host."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from inferd_tpu.core import sampling as samplib
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel import infer, mesh as meshlib

    cfg = get_config("qwen3-8b")
    slots, max_len, pp = 8, 4096, 4
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=pp), topo.devices)

    def on_mesh(shape, dtype, spec):
        return _sds(shape, dtype, NamedSharding(mesh, spec))

    def shard_shapes(params, cfg, mesh, layer_axis=None):
        specs = meshlib.param_specs_for(params, cfg, layer_axis)
        return jax.tree.map(lambda a, s: on_mesh(a.shape, a.dtype, s), params, specs,
                            is_leaf=lambda x: isinstance(x, P))

    def cache_shapes(cfg, mesh, mb, batch, max_len, ring=None):
        kv = on_mesh((cfg.num_layers, mb, batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                     cfg.kv_jnp_dtype, infer.cache_spec(mesh))
        return infer.PipelinedCaches(k=kv, v=kv, lengths=on_mesh((mb,), jnp.int32, P()))

    monkeypatch.setattr(meshlib, "shard_params", shard_shapes)
    monkeypatch.setattr(infer, "make_caches", cache_shapes)
    params = jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0)))
    eng = infer.PipelinedEngine(cfg, params, mesh, num_microbatches=slots, max_len=max_len)
    ask = {"ask": samplib.RowAsk(on_mesh((slots, 2), jnp.uint32, P()),
                                 on_mesh((slots, 4), jnp.float32, P())),
           "top_n": 8} if sampled else {}
    step = eng._step_raw_multi.lower(
        eng.params, eng.caches, on_mesh((slots,), jnp.int32, P()),
        on_mesh((slots,), jnp.bool_, P()), **ask,
    ).compile()
    mem = step.memory_analysis()
    stack_bytes = cfg.num_layers // pp * slots * max_len * cfg.num_kv_heads * cfg.head_dim * 2
    assert mem.alias_size_in_bytes >= 2 * stack_bytes  # K and V, a stage's share
    assert 7.1e9 < mem.argument_size_in_bytes < 7.3e9
    assert mem.temp_size_in_bytes < 0.5e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    text = step.as_text()
    stack = re.escape(f"bf16[{cfg.num_layers // pp},{slots},{max_len},{cfg.num_kv_heads},{cfg.head_dim}]")
    made = set(re.findall(rf"= {stack}\S* ([\w\-]+)\(", text))
    # the stack appears as the loops' carry and their in-place row updates only
    assert made and made <= {"parameter", "get-tuple-element", "bitcast", "dynamic-update-slice",
                             "fusion", "while"}, made
    assert not re.search(rf"= {stack}\S* (copy|select)\(", text)
    # one hop a tick inside the loop, the last rank's hidden state once after it
    assert len(re.findall(r" collective-permute-start\(", text)) == 1
    assert len(re.findall(r" all-reduce(-start)?\(", text)) == 1
    assert (f"s32[{slots},20]" in text) is sampled
