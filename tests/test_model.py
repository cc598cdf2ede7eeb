# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Model-correctness tests: shapes, cache/cacheless consistency, stage
splitting, and golden-logits parity against HF transformers — the test the
reference never had (SURVEY.md §4: no model-correctness tests there)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import TINY, TINY_MOE, ModelConfig
from inferd_tpu.models import qwen3
from inferd_tpu.models.loader import params_from_hf_state_dict


@pytest.fixture(scope="module")
def tiny_params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


def test_forward_shapes(tiny_params):
    tokens = jnp.array([[1, 2, 3, 4, 5]])
    logits, _, _ = qwen3.forward(tiny_params, TINY, tokens)
    assert logits.shape == (1, 5, TINY.vocab_size)
    assert logits.dtype == jnp.float32


def test_moe_forward_shapes():
    params = qwen3.init_params(TINY_MOE, jax.random.PRNGKey(0))
    tokens = jnp.array([[1, 2, 3]])
    logits, _, _ = qwen3.forward(params, TINY_MOE, tokens)
    assert logits.shape == (1, 3, TINY_MOE.vocab_size)
    assert np.all(np.isfinite(logits))


def test_cache_matches_cacheless(tiny_params):
    """Prefill+decode through a preallocated KV buffer must produce the same
    logits as a cache-free full-sequence forward."""
    cfg = TINY
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 7), 0, cfg.vocab_size)
    full_logits, _, _ = qwen3.forward(tiny_params, cfg, tokens)

    max_len = 16
    k = jnp.zeros((cfg.num_layers, 1, max_len, cfg.num_kv_heads, cfg.head_dim), cfg.jnp_dtype)
    v = jnp.zeros_like(k)

    # prefill first 4 tokens
    pos = jnp.arange(4)[None, :]
    logits_p, k, v = qwen3.forward(
        tiny_params, cfg, tokens[:, :4], pos, k, v, jnp.int32(0)
    )
    np.testing.assert_allclose(logits_p, full_logits[:, :4], rtol=1e-4, atol=1e-4)

    # decode tokens 4..6 one at a time
    for t in range(4, 7):
        pos = jnp.array([[t]])
        logits_d, k, v = qwen3.forward(
            tiny_params, cfg, tokens[:, t : t + 1], pos, k, v, jnp.int32(t)
        )
        np.testing.assert_allclose(
            logits_d[:, 0], full_logits[:, t], rtol=1e-4, atol=1e-4
        )


def test_cacheless_offset_positions_stay_causal(tiny_params):
    """A cache-free forward over a chunk with offset absolute positions must
    still be causal: token i's output can't depend on tokens > i."""
    cfg = TINY
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab_size)
    positions = 10 + jnp.arange(6)[None, :]
    hidden = qwen3.embed(tiny_params, tokens, cfg)
    out_full, _, _ = qwen3.forward_layers(tiny_params["layers"], cfg, hidden, positions)

    # perturb the last token; earlier outputs must be unchanged
    tokens2 = tokens.at[0, -1].set((int(tokens[0, -1]) + 1) % cfg.vocab_size)
    hidden2 = qwen3.embed(tiny_params, tokens2, cfg)
    out2, _, _ = qwen3.forward_layers(tiny_params["layers"], cfg, hidden2, positions)
    np.testing.assert_allclose(
        np.asarray(out_full[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-5, atol=1e-5
    )


def test_stage_split_matches_full(tiny_params):
    """Running layers as two sliced stages == running the full stack."""
    cfg = TINY
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(5), tokens.shape)
    hidden = qwen3.embed(tiny_params, tokens, cfg)
    full, _, _ = qwen3.forward_layers(tiny_params["layers"], cfg, hidden, positions)

    s0 = qwen3.slice_layers(tiny_params["layers"], 0, 2)
    s1 = qwen3.slice_layers(tiny_params["layers"], 2, 4)
    h, _, _ = qwen3.forward_layers(s0, cfg, hidden, positions)
    h, _, _ = qwen3.forward_layers(s1, cfg, h, positions)
    np.testing.assert_allclose(np.asarray(h), np.asarray(full), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_golden_parity_vs_hf(moe):
    """Logits parity vs HF transformers Qwen3 on a randomly-initialized tiny
    config (offline — no downloads). Covers RMSNorm/RoPE/GQA-with-qk-norm/
    SwiGLU(/MoE routing) numerics end to end."""
    torch = pytest.importorskip("torch")
    import transformers

    if moe:
        hf_cfg = transformers.Qwen3MoeConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=512, rope_theta=1e6,
            tie_word_embeddings=True, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, norm_topk_prob=True, decoder_sparse_step=1,
            mlp_only_layers=[],
        )
        hf_model = transformers.Qwen3MoeForCausalLM(hf_cfg)
        cfg = ModelConfig(
            name="tiny-moe-parity", vocab_size=256, hidden_size=64,
            intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, max_position_embeddings=512, dtype="float32",
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        )
    else:
        hf_cfg = transformers.Qwen3Config(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, max_position_embeddings=512, rope_theta=1e6,
            tie_word_embeddings=True,
        )
        hf_model = transformers.Qwen3ForCausalLM(hf_cfg)
        cfg = ModelConfig(
            name="tiny-parity", vocab_size=256, hidden_size=64,
            intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, max_position_embeddings=512, dtype="float32",
        )

    hf_model.eval()
    params = params_from_hf_state_dict(cfg, hf_model.state_dict())

    tokens_np = np.array([[3, 17, 42, 99, 7, 250]], dtype=np.int64)
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()

    logits, _, _ = qwen3.forward(params, cfg, jnp.asarray(tokens_np))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-4, atol=2e-4)


def test_qwen2_golden_parity_vs_hf():
    """Logits parity vs HF transformers Qwen2 (no q/k-norm, attention bias
    — the reference swarm path's model family, petals/inferd.yaml:1)."""
    torch = pytest.importorskip("torch")
    import transformers

    hf_cfg = transformers.Qwen2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512, rope_theta=1e6, tie_word_embeddings=True,
    )
    hf_model = transformers.Qwen2ForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="tiny-qwen2-parity", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512, dtype="float32",
        qk_norm=False, attn_bias=True,
    )
    hf_model.eval()
    # biases must actually be exercised: HF inits them to zero, so nudge
    with torch.no_grad():
        for layer in hf_model.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj, layer.self_attn.v_proj):
                proj.bias.normal_(0.0, 0.1)
    params = params_from_hf_state_dict(cfg, hf_model.state_dict())

    tokens_np = np.array([[3, 17, 42, 99, 7, 250]], dtype=np.int64)
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()
    logits, _, _ = qwen3.forward(params, cfg, jnp.asarray(tokens_np))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-4, atol=2e-4)


def test_qwen2_cache_matches_cacheless():
    """KV-cached decode == full recompute for the qwen2 variant."""
    from inferd_tpu.config import TINY_QWEN2
    from inferd_tpu.core.cache import KVCache

    cfg = TINY_QWEN2
    params = qwen3.init_params(cfg, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, cfg.vocab_size, dtype=jnp.int32)
    cache = KVCache.create(cfg, cfg.num_layers, 1, 16)
    logits, k, v = qwen3.forward(params, cfg, toks, k_cache=cache.k, v_cache=cache.v, cache_write_pos=cache.length)
    cache = KVCache(k=k, v=v, length=cache.length + 6)
    nxt = jnp.argmax(logits[:, -1], -1)[:, None]
    cached = []
    full = toks
    for _ in range(4):
        cached.append(int(nxt[0, 0]))
        logits, k, v = qwen3.forward(params, cfg, nxt, k_cache=cache.k, v_cache=cache.v, cache_write_pos=cache.length)
        cache = KVCache(k=k, v=v, length=cache.length + 1)
        nxt = jnp.argmax(logits[:, -1], -1)[:, None]
    uncached = []
    for _ in range(4):
        logits, _, _ = qwen3.forward(params, cfg, full)
        t = jnp.argmax(logits[:, -1], -1)[:, None]
        uncached.append(int(t[0, 0]))
        full = jnp.concatenate([full, t], axis=1)
    assert cached == uncached


def test_llama_golden_parity_vs_hf():
    """Logits parity vs HF transformers Llama (no q/k-norm, no attention
    bias, llama3 frequency-dependent RoPE scaling — the Llama-3.1+ family,
    added scope beyond the reference's Qwen2/Qwen3)."""
    torch = pytest.importorskip("torch")
    import transformers

    hf_cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=5e5,
        tie_word_embeddings=True, attention_bias=False, mlp_bias=False,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0, "original_max_position_embeddings": 128,
        },
    )
    hf_model = transformers.LlamaForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="tiny-llama-parity", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=5e5,
        dtype="float32", qk_norm=False, attn_bias=False,
        rope_scaling="llama3", rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0, rope_high_freq_factor=4.0,
        rope_original_max_position=128,
    )
    hf_model.eval()
    params = params_from_hf_state_dict(cfg, hf_model.state_dict())

    # positions past rope_original_max_position exercise the scaled bands
    tokens_np = np.array([[3, 17, 42, 99, 7, 250] * 24], dtype=np.int64)  # S=144
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()
    logits, _, _ = qwen3.forward(params, cfg, jnp.asarray(tokens_np))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-4, atol=2e-4)


def test_llama_cache_matches_cacheless():
    """KV-cached decode == full recompute for the llama variant (exercises
    the scaled-rope path through the cache plumbing)."""
    from inferd_tpu.config import TINY_LLAMA
    from inferd_tpu.core.cache import KVCache

    cfg = TINY_LLAMA
    params = qwen3.init_params(cfg, jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 10), 0, cfg.vocab_size, jnp.int32)

    full_logits, _, _ = qwen3.forward(params, cfg, toks)

    cache = KVCache.create(cfg, cfg.num_layers, 1, 32, ring=False)
    logits_p, nk, nv = qwen3.forward(params, cfg, toks[:, :6], None, cache.k, cache.v, jnp.int32(0))
    cache = KVCache(k=nk, v=nv, length=jnp.int32(6))
    outs = [logits_p[:, -1]]
    for i in range(6, 10):
        logits_i, nk, nv = qwen3.forward(
            params, cfg, toks[:, i : i + 1], None, cache.k, cache.v, cache.length
        )
        cache = KVCache(k=nk, v=nv, length=cache.length + 1)
        outs.append(logits_i[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_logits[:, 5:10]), rtol=2e-4, atol=2e-4
    )


def test_mixtral_golden_parity_vs_hf():
    """Logits parity vs HF transformers Mixtral — Llama-like attention with
    the block_sparse_moe naming (w1/w3/w2) mapped by the loader; routing is
    the same softmax-all -> top-k -> renormalize as Qwen3-MoE."""
    torch = pytest.importorskip("torch")
    import transformers

    hf_cfg = transformers.MixtralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=1e6,
        tie_word_embeddings=False, num_local_experts=8, num_experts_per_tok=2,
        sliding_window=None, attn_implementation="eager",
    )
    hf_model = transformers.MixtralForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="tiny-mixtral-parity", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=1e6,
        rms_norm_eps=1e-5,  # Mixtral's default (Qwen uses 1e-6)
        dtype="float32", qk_norm=False, attn_bias=False,
        tie_word_embeddings=False, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=128, norm_topk_prob=True,
    )
    hf_model.eval()
    params = params_from_hf_state_dict(cfg, hf_model.state_dict())

    tokens_np = np.array([[3, 17, 42, 99, 7, 250]], dtype=np.int64)
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()
    logits, _, _ = qwen3.forward(params, cfg, jnp.asarray(tokens_np))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-4, atol=2e-4)


def test_gpt_oss_golden_parity_vs_hf():
    """Logits parity vs HF transformers GptOss — the full recipe: attention
    sinks, q/k/v/o biases, YaRN rope scaling, sliding window on even
    layers, topk-then-softmax routing, and biased clamped-GLU experts
    (alpha=1.702, limit=7). S=24 > window=8 so the local/global alternation
    and the sink's effect on long contexts are both exercised."""
    torch = pytest.importorskip("torch")
    import transformers

    hf_cfg = transformers.GptOssConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=150000.0,
        tie_word_embeddings=False, num_local_experts=8, num_experts_per_tok=2,
        sliding_window=8, attention_bias=True, rms_norm_eps=1e-5,
        rope_scaling={
            "rope_type": "yarn", "factor": 32.0, "beta_fast": 32.0,
            "beta_slow": 1.0, "truncate": False,
            "original_max_position_embeddings": 64,
        },
        attn_implementation="eager",
    )
    hf_model = transformers.GptOssForCausalLM(hf_cfg)
    # sinks/biases init to zero or empty: randomize so they're exercised
    with torch.no_grad():
        for layer in hf_model.model.layers:
            layer.self_attn.sinks.normal_(0.0, 1.0)
            layer.self_attn.o_proj.bias.normal_(0.0, 0.1)
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.normal_(0.0, 0.1)
            layer.mlp.router.bias.normal_(0.0, 0.1)
            layer.mlp.experts.gate_up_proj_bias.normal_(0.0, 0.1)
            layer.mlp.experts.down_proj_bias.normal_(0.0, 0.1)
    hf_model.eval()
    cfg = ModelConfig(
        name="tiny-gptoss-parity", vocab_size=256, hidden_size=64,
        intermediate_size=32, num_layers=4, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=150000.0,
        rms_norm_eps=1e-5, dtype="float32", qk_norm=False,
        attn_bias=True, o_bias=True, attn_sinks=True, sliding_window=8,
        tie_word_embeddings=False,
        rope_scaling="yarn", rope_scaling_factor=32.0,
        rope_original_max_position=64, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_truncate=False,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
        moe_router_mode="topk_softmax", router_bias=True, moe_bias=True,
        swiglu_limit=7.0,
    )
    params = params_from_hf_state_dict(cfg, hf_model.state_dict())

    tokens_np = np.array([[3, 17, 42, 99, 7, 250] * 4], dtype=np.int64)  # S=24
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()
    logits, _, _ = qwen3.forward(params, cfg, jnp.asarray(tokens_np))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=3e-4, atol=3e-4)


def test_gpt_oss_cache_matches_cacheless():
    """KV-cached decode == full recompute for the gpt-oss variant (sinks +
    sliding window + yarn through the cache plumbing)."""
    from inferd_tpu.config import TINY_GPT_OSS
    from inferd_tpu.core.cache import KVCache

    cfg = TINY_GPT_OSS
    params = qwen3.init_params(cfg, jax.random.PRNGKey(12))
    toks = jax.random.randint(jax.random.PRNGKey(13), (1, 14), 0, cfg.vocab_size, jnp.int32)

    full_logits, _, _ = qwen3.forward(params, cfg, toks)

    cache = KVCache.create(cfg, cfg.num_layers, 1, 32, ring=False)
    logits_p, nk, nv = qwen3.forward(params, cfg, toks[:, :6], None, cache.k, cache.v, jnp.int32(0))
    cache = KVCache(k=nk, v=nv, length=jnp.int32(6))
    outs = [logits_p[:, -1]]
    for i in range(6, 14):  # decode walks past the window of 8
        logits_i, nk, nv = qwen3.forward(
            params, cfg, toks[:, i : i + 1], None, cache.k, cache.v, cache.length
        )
        cache = KVCache(k=nk, v=nv, length=cache.length + 1)
        outs.append(logits_i[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_logits[:, 5:14]), rtol=2e-4, atol=2e-4
    )


def test_mxfp4_dequant_matches_transformers():
    """loader.dequant_mxfp4 == transformers' convert_moe_packed_tensors on
    random packed tensors (the official GPT-OSS checkpoint storage)."""
    torch = pytest.importorskip("torch")
    from transformers.integrations.mxfp4 import convert_moe_packed_tensors

    from inferd_tpu.models.loader import dequant_mxfp4

    rng = np.random.RandomState(0)
    blocks = rng.randint(0, 256, size=(3, 8, 2, 16), dtype=np.uint8)
    scales = rng.randint(118, 136, size=(3, 8, 2), dtype=np.uint8)
    want = (
        convert_moe_packed_tensors(
            torch.from_numpy(blocks), torch.from_numpy(scales),
            dtype=torch.float32,
        )
        .float()
        .numpy()
    )
    got = dequant_mxfp4(blocks, scales)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_gpt_oss_mxfp4_state_dict_loads():
    """A state dict with *_blocks/*_scales expert tensors (the official
    GPT-OSS storage) loads to the same params as its dequantized-dense
    equivalent."""
    from inferd_tpu.config import TINY_GPT_OSS
    from inferd_tpu.models.loader import dequant_mxfp4

    cfg = TINY_GPT_OSS  # H=64, D=32: gate_up rows=64 packs [G=2, B=16]
    rng = np.random.RandomState(1)
    base = qwen3.init_params(cfg, jax.random.PRNGKey(0))

    def common(i):
        sd = {}
        L = cfg.num_layers
        sd[f"model.layers.{i}.input_layernorm.weight"] = np.asarray(base["layers"]["input_norm"][i])
        sd[f"model.layers.{i}.post_attention_layernorm.weight"] = np.asarray(base["layers"]["post_norm"][i])
        for nm in ("q", "k", "v", "o"):
            sd[f"model.layers.{i}.self_attn.{nm}_proj.weight"] = np.asarray(
                base["layers"][f"{nm}_proj"][i]
            ).T
        for nm in ("q", "k", "v"):
            sd[f"model.layers.{i}.self_attn.{nm}_proj.bias"] = np.asarray(base["layers"][f"{nm}_bias"][i])
        sd[f"model.layers.{i}.self_attn.o_proj.bias"] = np.asarray(base["layers"]["o_bias"][i])
        sd[f"model.layers.{i}.self_attn.sinks"] = np.asarray(base["layers"]["sinks"][i])
        sd[f"model.layers.{i}.mlp.router.weight"] = np.asarray(base["layers"]["router"][i]).T
        sd[f"model.layers.{i}.mlp.router.bias"] = np.asarray(base["layers"]["router_bias"][i])
        sd[f"model.layers.{i}.mlp.experts.gate_up_proj_bias"] = rng.normal(
            0, 0.1, (cfg.num_experts, 2 * cfg.moe_intermediate_size)
        ).astype(np.float32)
        sd[f"model.layers.{i}.mlp.experts.down_proj_bias"] = rng.normal(
            0, 0.1, (cfg.num_experts, cfg.hidden_size)
        ).astype(np.float32)
        return sd

    sd_packed, sd_dense = {}, {}
    E, H, D = cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size
    for i in range(cfg.num_layers):
        c = common(i)
        sd_packed.update(c)
        sd_dense.update(c)
        gu_blocks = rng.randint(0, 256, (E, 2 * D, H // 32, 16), dtype=np.uint8)
        gu_scales = rng.randint(120, 130, (E, 2 * D, H // 32), dtype=np.uint8)
        dn_blocks = rng.randint(0, 256, (E, H, D // 32, 16), dtype=np.uint8)
        dn_scales = rng.randint(120, 130, (E, H, D // 32), dtype=np.uint8)
        pre = f"model.layers.{i}.mlp.experts."
        sd_packed[pre + "gate_up_proj_blocks"] = gu_blocks
        sd_packed[pre + "gate_up_proj_scales"] = gu_scales
        sd_packed[pre + "down_proj_blocks"] = dn_blocks
        sd_packed[pre + "down_proj_scales"] = dn_scales
        sd_dense[pre + "gate_up_proj"] = dequant_mxfp4(gu_blocks, gu_scales)
        sd_dense[pre + "down_proj"] = dequant_mxfp4(dn_blocks, dn_scales)
    for sd in (sd_packed, sd_dense):
        sd["model.embed_tokens.weight"] = np.asarray(base["embed"])
        sd["model.norm.weight"] = np.asarray(base["final_norm"])
        sd["lm_head.weight"] = np.asarray(base["lm_head"]).T

    pa = params_from_hf_state_dict(cfg, sd_packed)
    pb = params_from_hf_state_dict(cfg, sd_dense)
    for path, leaf in jax.tree_util.tree_leaves_with_path(pa):
        other = dict(jax.tree_util.tree_leaves_with_path(pb))[path]
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(other))
    logits, _, _ = qwen3.forward(pa, cfg, jnp.asarray([[3, 7, 11]], jnp.int32))
    assert np.all(np.isfinite(np.asarray(logits)))


def test_gemma2_golden_parity_vs_hf():
    """Logits parity vs HF transformers Gemma2 — the architecturally most
    distinct family in the zoo: sandwich norms, (1+w) RMSNorm, GeGLU,
    scaled embeddings, attn/final logit softcapping, query_pre_attn_scalar
    score scale, and sliding-window attention on even layers. The sequence
    (S=24) exceeds the window (8) so the local/global alternation is
    actually exercised."""
    torch = pytest.importorskip("torch")
    import transformers

    hf_cfg = transformers.Gemma2Config(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=1e4,
        tie_word_embeddings=True, query_pre_attn_scalar=32.0,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        sliding_window=8, hidden_activation="gelu_pytorch_tanh",
        attn_implementation="eager",
    )
    hf_model = transformers.Gemma2ForCausalLM(hf_cfg)
    cfg = ModelConfig(
        name="tiny-gemma2-parity", vocab_size=256, hidden_size=64,
        intermediate_size=128, num_layers=4, num_heads=4, num_kv_heads=2,
        head_dim=16, max_position_embeddings=512, rope_theta=1e4,
        dtype="float32", qk_norm=False, attn_bias=False,
        norm_placement="both", rms_norm_plus_one=True, hidden_act="gelu_tanh",
        scale_embedding=True, attn_logit_softcap=50.0,
        final_logit_softcap=30.0, query_pre_attn_scalar=32.0,
        sliding_window=8,
    )
    hf_model.eval()
    params = params_from_hf_state_dict(cfg, hf_model.state_dict())

    tokens_np = np.array([[3, 17, 42, 99, 7, 250] * 4], dtype=np.int64)  # S=24 > window
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()
    logits, _, _ = qwen3.forward(params, cfg, jnp.asarray(tokens_np))
    np.testing.assert_allclose(np.asarray(logits), hf_logits, rtol=2e-4, atol=2e-4)


def test_gemma2_cache_matches_cacheless():
    """KV-cached decode == full recompute for the gemma2 variant — the
    sliding-window mask must produce identical logits whether the window
    is applied over a padded cache buffer or the exact prefix."""
    from inferd_tpu.config import TINY_GEMMA2
    from inferd_tpu.core.cache import KVCache

    cfg = TINY_GEMMA2
    params = qwen3.init_params(cfg, jax.random.PRNGKey(5))
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 14), 0, cfg.vocab_size, jnp.int32)

    full_logits, _, _ = qwen3.forward(params, cfg, toks)

    cache = KVCache.create(cfg, cfg.num_layers, 1, 32, ring=False)
    logits_p, nk, nv = qwen3.forward(params, cfg, toks[:, :6], None, cache.k, cache.v, jnp.int32(0))
    cache = KVCache(k=nk, v=nv, length=jnp.int32(6))
    outs = [logits_p[:, -1]]
    for i in range(6, 14):  # decode walks well past the window of 8
        logits_i, nk, nv = qwen3.forward(
            params, cfg, toks[:, i : i + 1], None, cache.k, cache.v, cache.length
        )
        cache = KVCache(k=nk, v=nv, length=cache.length + 1)
        outs.append(logits_i[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_logits[:, 5:14]), rtol=2e-4, atol=2e-4
    )


def test_gemma2_stage_split_matches_full():
    """Stage slices of a sliding-window model must pass layer_offset so the
    even/odd local-global pattern follows GLOBAL layer indices; a wrong
    offset flips window assignment on stage 1 and diverges."""
    from inferd_tpu.config import TINY_GEMMA2

    cfg = TINY_GEMMA2
    params = qwen3.init_params(cfg, jax.random.PRNGKey(7))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 12), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(12), tokens.shape)
    hidden = qwen3.embed(params, tokens, cfg)
    full, _, _ = qwen3.forward_layers(params["layers"], cfg, hidden, positions)

    s0 = qwen3.slice_layers(params["layers"], 0, 3)
    s1 = qwen3.slice_layers(params["layers"], 3, 4)
    h, _, _ = qwen3.forward_layers(s0, cfg, hidden, positions, layer_offset=0)
    h, _, _ = qwen3.forward_layers(s1, cfg, h, positions, layer_offset=3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(full), rtol=1e-5, atol=1e-5)

    # sanity: the WRONG offset must not match (odd split => patterns differ)
    h_bad, _, _ = qwen3.forward_layers(s1, cfg, h * 0 + hidden, positions, layer_offset=0)
    h_good, _, _ = qwen3.forward_layers(s1, cfg, h * 0 + hidden, positions, layer_offset=3)
    assert not np.allclose(np.asarray(h_bad), np.asarray(h_good))


@pytest.mark.parametrize("family", ["gemma2", "gptoss"])
def test_windowed_read_fast_path_matches_uniform(family):
    """A static layer offset (a period of the scan is a (sliding, global)
    pair; static window -> KV read narrowed to a window-covering slice)
    must produce bit-comparable logits AND identical cache writes to a
    traced one (every layer its own period, traced window, full-buffer
    mask-only read) — prefill chunk and decode steps."""
    from inferd_tpu.config import TINY_GEMMA2, TINY_GPT_OSS
    from inferd_tpu.core.cache import KVCache

    cfg = TINY_GEMMA2 if family == "gemma2" else TINY_GPT_OSS
    params = qwen3.init_params(cfg, jax.random.PRNGKey(17))
    toks = jax.random.randint(jax.random.PRNGKey(18), (2, 6), 0, cfg.vocab_size, jnp.int32)

    def run(layer_offset):
        # static int offset 0 -> static windows; traced offset -> mask-only
        # (ring=False: this test pins the UNIFORM-layout windowed-READ fast
        # path; ring STORAGE has its own suite, tests/test_ringkv.py)
        cache = KVCache.create(cfg, cfg.num_layers, 2, 32, ring=False)
        pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
        hidden = qwen3.embed(params, toks, cfg)
        h, cache, _ = qwen3.forward_layers_cached(
            params["layers"], cfg, hidden, pos, cache, jnp.int32(0),
            layer_offset=layer_offset,
        )
        outs = [qwen3.unembed(params, cfg, h)]
        length = jnp.int32(6)
        tok = jnp.argmax(outs[0][:, -1], -1)[:, None]
        for i in range(6, 14):  # decode walks past the window of 8
            pos = jnp.full((2, 1), i, jnp.int32)
            hidden = qwen3.embed(params, tok, cfg)
            h, cache, _ = qwen3.forward_layers_cached(
                params["layers"], cfg, hidden, pos, cache, length,
                layer_offset=layer_offset,
            )
            length = length + 1
            outs.append(qwen3.unembed(params, cfg, h))
            tok = jnp.argmax(outs[-1][:, -1], -1)[:, None]
        return jnp.concatenate(outs, axis=1), cache.k, cache.v

    # both jitted: layer_offset a static closure int (read fast path) vs a
    # traced argument (mask-only) — same compilation regime otherwise
    fast_logits, fast_k, fast_v = jax.jit(lambda: run(0))()
    uni_logits, uni_k, uni_v = jax.jit(run)(jnp.int32(0))
    np.testing.assert_allclose(
        np.asarray(fast_logits), np.asarray(uni_logits), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(fast_k), np.asarray(uni_k), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(fast_v), np.asarray(uni_v), rtol=1e-6, atol=1e-6
    )


def test_windowed_slice_fuzz():
    """Randomized shapes/fills: attention over the window-covering slice ==
    attention over the full buffer with the window mask, for scalar and
    per-row ends, prefill chunks and decode steps, tiny and buffer-sized
    windows (the invariant the windowed-read fast path rests on)."""
    from inferd_tpu.models.qwen3 import _windowed_slice, gqa_attention

    rng = np.random.RandomState(41)
    for trial in range(12):
        b = int(rng.randint(1, 3))
        t = int(rng.choice([16, 24, 48]))
        s = int(rng.choice([1, 1, 4]))
        window = int(rng.choice([2, 8, t]))
        nq, nkv, d = 4, 2, 8
        kq = jax.random.PRNGKey(trial)
        q = jax.random.normal(kq, (b, s, nq, d))
        kbuf = jax.random.normal(jax.random.fold_in(kq, 1), (b, t, nkv, d))
        vbuf = jax.random.normal(jax.random.fold_in(kq, 2), (b, t, nkv, d))
        per_row = bool(rng.randint(0, 2))
        if per_row:
            end_np = rng.randint(s, t + 1, size=b)
            end = jnp.asarray(end_np, jnp.int32)
            qpos = end[:, None] - s + jnp.arange(s)[None, :]
        else:
            end_np = int(rng.randint(s, t + 1))
            end = jnp.int32(end_np)
            qpos = end - s + jnp.broadcast_to(jnp.arange(s), (b, s))

        ref = gqa_attention(
            q, kbuf, vbuf, qpos, end, window=jnp.int32(window)
        )
        k_att, v_att, kvpos, valid = _windowed_slice(kbuf, vbuf, end, window, s)
        got = gqa_attention(
            q, k_att, v_att, qpos, valid,
            kv_positions=kvpos, window=jnp.int32(window),
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"trial {trial}: b={b} t={t} s={s} w={window} "
                    f"per_row={per_row} end={end_np}",
        )


def test_fp8_kv_cache_close_to_full_recompute():
    """cfg.kv_dtype=float8_e4m3fn: cached decode logits must track the
    cache-free forward within fp8 storage noise (the narrow dtype only
    touches KV storage — weights/activations stay in cfg.dtype)."""
    from inferd_tpu.config import TINY
    from inferd_tpu.core.cache import KVCache

    cfg = dataclasses.replace(TINY, kv_dtype="float8_e4m3fn")
    assert str(cfg.kv_jnp_dtype) == "float8_e4m3fn"
    params = qwen3.init_params(cfg, jax.random.PRNGKey(6))
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 10), 0, cfg.vocab_size, jnp.int32)

    full_logits, _, _ = qwen3.forward(params, cfg, toks)

    cache = KVCache.create(cfg, cfg.num_layers, 1, 32, ring=False)
    assert cache.k.dtype == jnp.float8_e4m3fn
    logits_p, nk, nv = qwen3.forward(
        params, cfg, toks[:, :6], None, cache.k, cache.v, jnp.int32(0)
    )
    cache = KVCache(k=nk, v=nv, length=jnp.int32(6))
    outs = [logits_p[:, -1]]
    for i in range(6, 10):
        logits_i, nk, nv = qwen3.forward(
            params, cfg, toks[:, i : i + 1], None, cache.k, cache.v, cache.length
        )
        cache = KVCache(k=nk, v=nv, length=cache.length + 1)
        outs.append(logits_i[:, 0])
    got = np.asarray(jnp.stack(outs, axis=1), np.float32)
    want = np.asarray(full_logits[:, 5:10], np.float32)
    # fp8 (e4m3 ~ 2 decimal digits) perturbs but must stay well correlated
    cos = (got * want).sum() / (np.linalg.norm(got) * np.linalg.norm(want) + 1e-9)
    assert cos > 0.99, cos


def test_fp8_kv_engine_generates():
    from inferd_tpu.config import TINY
    from inferd_tpu.core.generate import Engine

    cfg = dataclasses.replace(TINY, kv_dtype="float8_e4m3fn")
    params = qwen3.init_params(cfg, jax.random.PRNGKey(6))
    eng = Engine(cfg, params, max_len=64)
    out = eng.generate([3, 5, 7], max_new_tokens=8, seed=0)
    assert len(out) == 8 and all(0 <= t < cfg.vocab_size for t in out)


def test_fp8_kv_write_saturates_no_nan():
    """An out-of-e4m3-range V value must saturate on cache write, not
    become NaN (e4m3fn maps overflow to NaN, which would permanently
    poison the session's cache)."""
    from inferd_tpu.models.qwen3 import _to_cache_dtype

    big = jnp.asarray([[1e4, -1e4, 0.5]], jnp.float32)
    out = _to_cache_dtype(big, jnp.float8_e4m3fn)
    f = np.asarray(out, np.float32)
    assert not np.isnan(f).any()
    assert f[0, 0] > 400 and f[0, 1] < -400


# ---------------------------------------------------------------------------
# every cache layout through the one layer scan
# ---------------------------------------------------------------------------

# layout -> (preset, stage [start, end) or None for the whole model, the cache
# the seam is given, per-row write positions in decode)
# "tiny-wide": the tiny preset with heads as wide as a tile, so its dense lanes
# keep a head axis (DenseEntry); the tiny presets' 16-wide heads are stored as
# rows (RowEntry, core.cache.rows_layout)
LAYOUTS = {
    "none": ("tiny", None, None, False),
    "dense-scalar": ("tiny-wide", None, "lanes", False),
    "dense-per-row": ("tiny-wide", None, "lanes", True),
    "rows-scalar": ("tiny", None, "lanes", False),
    "rows-per-row": ("tiny", None, "lanes", True),
    "ring-even-offset": ("tiny-gemma2", None, "lanes", True),
    "ring-odd-offset-odd-length": ("tiny-gptoss", (1, 4), "lanes", True),
    "paged": ("tiny", None, "paged", True),
    "latent-after-a-dense-group": ("tiny-dsv2", None, "lanes", True),
}


def _scan_lengths(jaxpr):
    """The length of every lax.scan in a jaxpr, nested ones included, but
    those inside the grouped expert product (megablox `gmm` lays out its tile
    visits with a binary search, which is a scan of a few steps)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        if eqn.params.get("name") == "gmm":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_lengths(sub)
    return out


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_every_layout_runs_through_the_one_scan(layout):
    """For each layout of core/cache: cache -> stacked entries -> cache is
    the identity; the cached forward holds exactly ONE scan per layer group,
    of length (layers - head - tail) / period; and prefill then decode
    through the seam (forward_cached for a whole model, forward_layers_cached
    for a stage) equals the cache-free forward. Two rows at ragged fills
    (6 and 4 real tokens, the shorter padded to the bucket) except where the
    write position is one scalar."""
    from inferd_tpu.config import get_config
    from inferd_tpu.core import cache as cachelib

    preset, stage, kind, per_row = LAYOUTS[layout]
    if preset == "tiny-wide":
        cfg = dataclasses.replace(get_config("tiny"), name=preset, head_dim=128)
    else:
        cfg = get_config(preset)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(23))
    if stage is None:  # (layers, global index of the first) for each layer group
        stacks = qwen3.layer_groups(params)
        offs = np.cumsum([0] + [qwen3._stack_len(g) for g in stacks])
        groups = [(g, int(o)) for g, o in zip(stacks, offs)]
    else:
        groups = [(qwen3.slice_layers(params["layers"], *stage), stage[0])]
    n_layers = sum(qwen3._stack_len(g) for g, _ in groups)

    def free(tokens):  # the cache-free forward over whole sequences
        h = qwen3.embed(params, tokens, cfg)
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        for layers, off in groups:
            h, ents, _ = qwen3.forward_layers(layers, cfg, h, pos, layer_offset=off)
            assert ents == ()
        return qwen3.unembed(params, cfg, h)

    def cached(tokens, pos, cache, write_pos, real_end):
        if stage is None:
            return qwen3.forward_cached(params, cfg, tokens, pos, cache, write_pos, real_end)[:2]
        h, nc, _ = qwen3.forward_layers_cached(
            groups[0][0], cfg, qwen3.embed(params, tokens, cfg), pos, cache,
            write_pos, real_end, layer_offset=stage[0],
        )
        return qwen3.unembed(params, cfg, h), nc

    # one scan per group, as long as its whole periods
    period = len(cfg.layer_pattern)
    want_scans = [(qwen3._stack_len(g) - (-off % period)) // period for g, off in groups]
    toks = jax.random.randint(jax.random.PRNGKey(24), (2, 9), 0, cfg.vocab_size, jnp.int32)
    if kind is None:
        assert _scan_lengths(jax.make_jaxpr(free)(toks).jaxpr) == want_scans
        # against the layers run one by one, outside any scan
        h = qwen3.embed(params, toks, cfg)
        pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
        cos, sin = qwen3.rope_cos_sin(pos, cfg.rope_dim, cfg.rope_theta, cfg)
        for i in range(n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            h, entry, _ = qwen3.decoder_layer(lp, cfg, h, cos, sin, pos)
            assert entry is None
        np.testing.assert_allclose(
            free(toks), qwen3.unembed(params, cfg, h), rtol=1e-4, atol=1e-4
        )
        return

    lens = [6, 4] if per_row else [6, 6]  # real tokens a row after prefill
    if kind == "paged":
        cache = cachelib.PagedKVCache.create(cfg, n_layers, 2, 32, block_size=8)
        table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)  # block 0 is scratch
        cache = dataclasses.replace(cache, table=table)
        want_types = [cachelib.PagedEntry]
    else:
        cache = cachelib.KVCache.create(
            cfg, n_layers, 2, 32, layer_offset=0 if stage is None else stage[0]
        )
        want_types = (
            [cachelib.LatentEntry] if cfg.is_mla
            else [cachelib.RingEntry, cachelib.DenseEntry] if cfg.sliding_window
            else [cachelib.RowEntry] if cfg.head_dim < 128
            else [cachelib.DenseEntry]
        )
        assert cache.layout(cfg) == {
            cachelib.LatentEntry: "latent", cachelib.RowEntry: "rows"
        }.get(want_types[-1], "heads")
    entries = cache.entries(cfg)
    assert [type(e) for e in entries] == want_types
    back = cache.with_entries(entries)
    assert jax.tree.structure(back) == jax.tree.structure(cache)
    assert all(a is b for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(cache)))

    # row r's whole sequence: its prefill tokens, then its decoded ones
    seqs = [np.concatenate([toks[r, : lens[r]], toks[r, 6:9]]) for r in range(2)]
    # compiled (a program a shape) where op-by-op dispatch paid every call;
    # the jaxpr that is counted below is the function's own
    run, whole = jax.jit(cached), jax.jit(free)
    want = [np.asarray(whole(jnp.asarray(s)[None]))[0] for s in seqs]
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    logits, cache = run(toks[:, :6], pos, cache, jnp.int32(0), jnp.asarray(lens))
    for r in range(2):
        np.testing.assert_allclose(
            logits[r, : lens[r]], want[r][: lens[r]], rtol=1e-4, atol=1e-4
        )
    for i in range(3):
        at = jnp.asarray(lens) + i if per_row else jnp.int32(6 + i)
        pos = jnp.broadcast_to(jnp.asarray(lens)[:, None] + i, (2, 1))
        step = lambda c, f=cached: f(toks[:, 6 + i : 7 + i], pos, c, at, at + 1)
        if i == 0:
            assert _scan_lengths(jax.make_jaxpr(step)(cache).jaxpr) == want_scans
        logits, cache = step(cache, run)
        for r in range(2):
            np.testing.assert_allclose(
                logits[r, 0], want[r][lens[r] + i], rtol=1e-4, atol=1e-4
            )


# (preset, stage (start, end) or None = the whole model, storage, write_pos
# per row, a write_mask, a traced layer offset)
IN_PLACE = {
    "dense-scalar": ("tiny", None, "lanes", False, False, False),
    "dense-per-row": ("tiny", None, "lanes", True, False, False),
    "latent-two-layer-groups": ("tiny-dsv2", None, "lanes", True, False, False),
    "ring-by-kind-off-both-period-boundaries": ("tiny-gptoss", (1, 4), "lanes", True, False, False),
    "paged-write-mask": ("tiny", None, "paged", True, True, False),
    "pp-rank-traced-offset": ("tiny-gemma2", (2, 4), "uniform", True, False, True),
}


@pytest.mark.parametrize("layout", list(IN_PLACE))
def test_the_carried_cache_equals_slabs_threaded_layer_by_layer(layout):
    """The one scan carries the stacked entries and a layer writes its rows
    where the stack lies. For every layout the logits and the resulting
    cache of a prefill chunk and three decode steps equal, bit for bit,
    those of this test's own threading in plain jnp: each layer's slab is
    taken out of its stack (a stack of ONE), the layer runs over it, the
    slab is put back; the layer's place in its stack is counted here, not
    taken from forward_layers. Rows the mask leaves out write nothing.
    (Against the cache-free forward, to a tolerance: the test above.)"""
    from inferd_tpu.config import get_config
    from inferd_tpu.core import cache as cachelib

    preset, stage, kind, per_row, masked, traced = IN_PLACE[layout]
    cfg = get_config(preset)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(31))
    if stage is None:
        stacks = qwen3.layer_groups(params)
        offs = np.cumsum([0] + [qwen3._stack_len(g) for g in stacks])
        groups = [(g, int(o)) for g, o in zip(stacks, offs)]
    else:
        groups = [(qwen3.slice_layers(params["layers"], *stage), stage[0])]
    n_layers = sum(qwen3._stack_len(g) for g, _ in groups)
    first = groups[0][1]
    if kind == "paged":
        cache = cachelib.PagedKVCache.create(cfg, n_layers, 2, 32, block_size=8)
        cache = dataclasses.replace(cache, table=jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4))
    else:
        cache = cachelib.KVCache.create(
            cfg, n_layers, 2, 32, layer_offset=first, ring=None if kind == "lanes" else False
        )
    # a cache that is not all zeros: what a layer must leave alone shows
    cache = jax.tree.map(
        lambda a: (jnp.arange(a.size, dtype=jnp.float32).reshape(a.shape) % 7 / 8).astype(a.dtype)
        if a.ndim > 2 else a, cache,
    )

    def carried(h, pos, cache, write_pos, real_end, mask, offset):
        topi = None
        for layers, off in groups:
            h, cache, chosen = qwen3.forward_layers_cached(
                layers, cfg, h, pos, cache, write_pos, real_end,
                layer_offset=off - first + offset, cache_offset=off - first, write_mask=mask,
            )
            topi = chosen if chosen is not None else topi
        return h, cache, topi

    def threaded(h, pos, cache, write_pos, real_end, mask, offset):
        entries = list(cache.entries(cfg))
        ctx = cache.ctx(write_pos, real_end, mask)
        cos, sin = qwen3.rope_cos_sin(pos, cfg.rope_dim, cfg.rope_theta, cfg)
        kinds = cfg.layer_pattern
        seen, at_all = {}, 0
        for layers, off in groups:
            n = qwen3._stack_len(layers)
            wins = qwen3.layer_windows(cfg, n, off - first + offset) if traced else None
            for i in range(n):
                s = (offset + off - first + i) % len(kinds) if len(entries) > 1 else 0
                at = seen[s] = seen.get(s, -1) + 1
                if traced:
                    win = wins[i]
                else:
                    sliding = kinds[(offset + off - first + i) % len(kinds)] == "sliding"
                    win = int(cfg.sliding_window) if sliding else None
                one = jax.tree.map(lambda a: a[at : at + 1], entries[s])
                h, new, _ = qwen3.decoder_layer(
                    jax.tree.map(lambda a: a[i], layers), cfg, h, cos, sin, pos, one, 0, ctx, win
                )
                entries[s] = jax.tree.map(lambda a, b: a.at[at].set(b[0]), entries[s], new)
                at_all += 1
        assert at_all == n_layers
        return h, cache.with_entries(tuple(entries)), None

    # a pp rank's offset is no python int: every layer is its own period
    offset = jnp.int32(first) if traced else first
    toks = jax.random.randint(jax.random.PRNGKey(32), (2, 9), 0, cfg.vocab_size, jnp.int32)
    lens = jnp.asarray([6, 4] if per_row else [6, 6])
    mask = jnp.asarray([True, False]) if masked else None
    steps = [(toks[:, :6], jnp.broadcast_to(jnp.arange(6), (2, 6)), jnp.int32(0), lens)]
    for i in range(3):
        at = lens + i if per_row else jnp.int32(6 + i)
        steps.append((toks[:, 6 + i : 7 + i], jnp.broadcast_to(lens[:, None] + i, (2, 1)), at, at + 1))
    got_c = want_c = cache
    for tokens, pos, write_pos, real_end in steps:
        h = qwen3.embed(params, tokens, cfg)
        # operation by operation on both sides (the scan too): a compiled
        # body may fuse, and round, otherwise than the same operations alone
        with jax.disable_jit():
            got_h, got_c, topi = carried(h, pos, got_c, write_pos, real_end, mask, offset)
            want_h, want_c, _ = threaded(h, pos, want_c, write_pos, real_end, mask, offset)
        np.testing.assert_array_equal(
            qwen3.unembed(params, cfg, got_h), qwen3.unembed(params, cfg, want_h)
        )
        assert jax.tree.structure(got_c) == jax.tree.structure(want_c)
        for a, b in zip(jax.tree.leaves(got_c), jax.tree.leaves(want_c)):
            np.testing.assert_array_equal(a, b)
        if cfg.is_moe:  # every layer with a router reports, in layer order
            assert topi.shape[0] == n_layers - cfg.num_dense_layers
    # the steps wrote something, and only where they should
    changed = [not np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got_c), jax.tree.leaves(cache)) if a.ndim > 2]
    assert all(changed)
    if masked:  # row 1 wrote nothing: its blocks (5..8) hold what they held
        np.testing.assert_array_equal(got_c.k[:, 5:], cache.k[:, 5:])
        np.testing.assert_array_equal(got_c.v[:, 5:], cache.v[:, 5:])


# ---------------------------------------------------------------------------
# the programs the benchmark's cells run, held to their recorded text
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lowered_programs():
    from tests import lowered_programs as lp

    return lp.digests(lp.texts())


def _program_names():
    from tests import lowered_programs as lp

    return lp.NAMES


@pytest.mark.parametrize("name", _program_names())
def test_a_cells_program_lowers_to_its_recorded_text(lowered_programs, name):
    """The ten programs of the five older cells (tests/lowered_programs.py)
    lower byte for byte to the recorded text: a model's new fields, absent by
    default, trace nothing into another model's program. Recorded at PR 42
    from the parent (4f27965); at PR 43 the seven per-head ones were recorded
    anew with the tiny presets' heads as wide as the cells' (128), from the
    parent (787d7c0) under the same script, and PR 43's tree lowered them to
    the same bytes: a head as wide as a tile keeps its layout. Granite's two
    lane programs are PR 43's own (one row a token). PR 46 recorded the
    seven of `dsv2l.*`, `sdar.*` and `trinl.*` anew on purpose (the routed
    layer multiplies grouped by expert, and a layer finds its experts in the
    stack where it lies); the seven without an expert kept the parent's
    bytes. PR 48 added the two of `q3n.*` (a second recurrence and state
    layers that route) and left the fourteen digests as they were. PR 49 left
    the sixteen as they were (a 64-slot slab is under the floor of the read
    by prefix, models/qwen3.read_rungs) and added `q4b.*.t1024`, the step and
    the prefill over lanes of 1024 slots, where that rule engages. PR 51 left
    the eighteen as they were (a third norm placement, a flat q/k norm, beta
    to 2, a state held heads side by side and a two-pass update: each behind
    a field that is absent by default) and added the two of `olmoh.*`. PR 55
    left the twenty as they were (the residual's read / join pair is the plain
    add where `hc_mult` is unset, and a compressed query sits behind
    `q_lora_rank`) and added the two of `xing.*`. PR 56 left the twenty-two
    as they were (a 64-slot latent lane is under the floor too, and the dense
    callers of `_lanes_read` hand it what they did) and added `dsv2l.*.t1024`
    and `xing.*.t1024`, where a latent lane is read by its prefix. PR 57 left
    the twenty-six as they were (a layer's sublayers are optional by what its
    stack holds, and every held stack holds both; an expert without a gate and
    a latent sit behind `ffn_gated` and `moe_latent_size`; the row tile is told
    the router's width, which is the held count at every tiny preset) and
    added the two of `nem3s.*`. A PR that
    changes one of them on purpose runs `python tests/lowered_programs.py
    --record` and says so."""
    import json

    from tests import lowered_programs as lp

    with open(lp.DIGESTS) as f:
        recorded = json.load(f)
    assert lowered_programs[name] == recorded[name], (
        f"{name} no longer lowers to the recorded text: diff `python tests/lowered_programs.py "
        "<dir>` of this tree against the parent's")
