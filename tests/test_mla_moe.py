"""DeepSeek-V2 on the lane path at `tiny-dsv2`: latent attention with a
latent cache and absorbed decode, shared and routed experts behind a leading
dense layer. Seeded random weights, float32; the plain reference below is
this file's own (expanded attention, experts by a loop over the chosen)."""

import argparse
import asyncio
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import PRESETS, get_config, yarn_mscale
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import BlockPool, KVCache
from inferd_tpu.models import qwen3

CFG = get_config("tiny-dsv2")


@pytest.fixture(scope="module")
def params():
    return qwen3.init_params(CFG, jax.random.PRNGKey(7))


# ---------------------------------------------------------------------------
# the plain reference: one full pass, no cache, from the published equations
# ---------------------------------------------------------------------------


def _norm(x, w, eps):
    return x * (1.0 / np.sqrt((x * x).mean(-1, keepdims=True) + eps)) * w


def _yarn_inv_freq(cfg):
    dim, base, factor = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling_factor
    orig = cfg.rope_original_max_position
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    keep = 1.0 - ramp
    return (1.0 / (factor * pos_freqs)) * (1 - keep) + (1.0 / pos_freqs) * keep


def _rope(x, pos, inv_freq, mul):  # x [S, N, D] half-split layout
    ang = pos[:, None] * inv_freq[None]
    cos = np.cos(np.concatenate([ang, ang], -1))[:, None] * mul
    sin = np.sin(np.concatenate([ang, ang], -1))[:, None] * mul
    x1, x2 = np.split(x, 2, -1)
    return x * cos + np.concatenate([-x2, x1], -1) * sin


def _swiglu(x, g, u, d):
    a = x @ g
    return (a / (1 + np.exp(-a)) * (x @ u)) @ d


def reference_logits(cfg, params, ids):
    """One sequence, float64 numpy -> [S, V] logits."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    s = len(ids)
    n, dn, dr, dv, r = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    pos = np.arange(s, dtype=np.float64)
    inv_freq = _yarn_inv_freq(cfg)
    mul = yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    scale = (dn + dr) ** -0.5 * yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) ** 2
    h = p["embed"][np.asarray(ids)]
    stacks = [p["dense_layers"], p["layers"]]
    for stack in stacks:
        for i in range(stack["q_proj"].shape[0]):
            lp = {k: v[i] for k, v in stack.items()}
            x = _norm(h, lp["input_norm"], cfg.rms_norm_eps)
            q = (x @ lp["q_proj"]).reshape(s, n, dn + dr)
            q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], pos, inv_freq, mul)
            kva = x @ lp["kv_a_proj"]
            c = _norm(kva[:, :r], lp["kv_a_norm"], cfg.rms_norm_eps)
            k_pe = _rope(kva[:, None, r:], pos, inv_freq, mul)[:, 0]
            kv = (c @ lp["kv_b_proj"]).reshape(s, n, dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]
            out = np.zeros((s, n, dv))
            for head in range(n):
                sc = (q_nope[:, head] @ k_nope[:, head].T + q_pe[:, head] @ k_pe.T) * scale
                sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
                pr = np.exp(sc - sc.max(-1, keepdims=True))
                out[:, head] = (pr / pr.sum(-1, keepdims=True)) @ v[:, head]
            h = h + out.reshape(s, n * dv) @ lp["o_proj"]
            x = _norm(h, lp["post_norm"], cfg.rms_norm_eps)
            if "router" not in lp:
                h = h + _swiglu(x, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
                continue
            logits = x @ lp["router"]
            g = np.exp(logits - logits.max(-1, keepdims=True))
            g = g / g.sum(-1, keepdims=True)
            y = _swiglu(x, lp["shared_gate_proj"], lp["shared_up_proj"], lp["shared_down_proj"])
            chosen = np.argsort(-g, axis=-1, kind="stable")[:, : cfg.num_experts_per_tok]
            for t in range(s):
                for e in chosen[t]:
                    y[t] += g[t, e] * cfg.routed_scaling_factor * _swiglu(
                        x[t], lp["gate_proj"][e], lp["up_proj"][e], lp["down_proj"][e])
            h = h + y
    x = _norm(h, p["final_norm"], cfg.rms_norm_eps)
    return x @ p["lm_head"]


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(0, CFG.vocab_size, size=n).tolist()


# ---------------------------------------------------------------------------
# (a) no-cache forward, (b) lanes: chunked prefill + ragged decode
# ---------------------------------------------------------------------------


def test_forward_matches_the_plain_reference(params):
    ids = _ids(40)
    got, _, _ = qwen3.forward(params, CFG, jnp.asarray([ids]))
    want = reference_logits(CFG, params, ids)
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-5)


def test_lanes_prefill_in_two_chunks_then_decode_ragged(params):
    """Three lanes at ragged lengths: each prompt ingested in two chunks
    (the second attends to the first's cached latents), then 8 decode
    steps of all lanes together, teacher-forced; every row against the
    reference's ONE full pass over that lane's tokens."""
    eng = BatchedEngine(CFG, params, lanes=4, max_len=64)
    seqs = {0: _ids(29, 1), 2: _ids(17, 2), 3: _ids(38, 3)}
    new = 8
    want = {lane: reference_logits(CFG, params, ids) for lane, ids in seqs.items()}
    lengths = [0] * 4
    for lane, ids in seqs.items():
        n0 = len(ids) - new
        cut = n0 // 2
        for start, end in ((0, cut), (cut, n0)):
            chunk = np.zeros((1, 32), np.int32)
            chunk[0, : end - start] = ids[start:end]
            eng.cache, logits = eng._prefill_lane_logits(
                eng.params, eng.cache, jnp.asarray(chunk), jnp.int32(lane),
                jnp.int32(start), jnp.int32(end - start))
        np.testing.assert_allclose(logits, want[lane][n0 - 1], atol=2e-5)
        lengths[lane] = n0
    for step in range(new):
        toks = [0] * 4
        for lane, ids in seqs.items():
            toks[lane] = ids[lengths[lane]]
        eng.cache, logits, routed = eng._decode_logits(
            eng.params, eng.cache, jnp.asarray(toks, jnp.int32), jnp.asarray(lengths, jnp.int32))
        assert routed.shape == (3, 4, CFG.num_experts_per_tok)
        for lane in seqs:
            np.testing.assert_allclose(logits[lane], want[lane][lengths[lane]], atol=2e-5)
            lengths[lane] += 1


def test_fork_copies_a_lane_of_latents(params):
    eng = BatchedEngine(CFG, params, lanes=2, max_len=32)
    ids = _ids(16, 4)
    chunk = jnp.asarray([ids])
    eng.cache, _ = eng._prefill_lane_logits(
        eng.params, eng.cache, chunk, jnp.int32(0), jnp.int32(0), jnp.int32(16))
    eng.fork_lane(0, 1, 16)
    np.testing.assert_array_equal(np.asarray(eng.cache.k[:, 1, :16]), np.asarray(eng.cache.k[:, 0, :16]))
    np.testing.assert_array_equal(np.asarray(eng.cache.v[:, 1, :16]), np.asarray(eng.cache.v[:, 0, :16]))


# ---------------------------------------------------------------------------
# (c) absorbed == expanded from the same latents
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s", [1, 5])
def test_absorbed_attention_equals_expanded(s):
    rng = np.random.RandomState(5)
    b, t, n = 2, 24, CFG.num_heads
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q_nope, q_pe = f(b, s, n, CFG.qk_nope_head_dim), f(b, s, n, CFG.qk_rope_head_dim)
    c, k_pe = f(b, t, CFG.kv_lora_rank), f(b, t, CFG.qk_rope_head_dim)
    w = f(CFG.kv_lora_rank, n * (CFG.qk_nope_head_dim + CFG.v_head_dim)) * 0.2
    valid = jnp.asarray([20, 11])
    q_pos = (valid - s)[:, None] + jnp.arange(s)[None]
    both = [qwen3.mla_attend(CFG, q_nope, q_pe, c, k_pe, w, q_pos, valid, absorbed=a)
            for a in (False, True)]
    assert both[0].shape == (b, s, n * CFG.v_head_dim)
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(both[1]), atol=2e-5)


# ---------------------------------------------------------------------------
# (d) what the cache allocates
# ---------------------------------------------------------------------------


def test_the_cache_holds_a_latent_and_one_rope_key_a_token():
    cache = jax.eval_shape(lambda: KVCache.create(CFG, CFG.num_layers, 3, 16))
    assert cache.k.shape == (CFG.num_layers, 3, 16, CFG.kv_lora_rank)
    assert cache.v.shape == (CFG.num_layers, 3, 16, CFG.qk_rope_head_dim)
    assert cache.k_loc is None and cache.max_len == 16 and cache.batch == 3
    full = get_config("deepseek-v2-lite-8l")
    big = jax.eval_shape(lambda: KVCache.create(full, full.num_layers, 16, 4096))
    per_token = (big.k.size + big.v.size) * 2 // (16 * 4096)
    assert per_token == 8 * 576 * 2 == 9216  # never 8 * 16 * (192 + 128) * 2 = 81 920
    assert big.k.dtype == jnp.bfloat16
    fp8 = dataclasses.replace(full, kv_dtype="float8_e4m3fn")
    assert jax.eval_shape(lambda: KVCache.create(fp8, 8, 1, 8)).k.dtype == jnp.float8_e4m3fn


def test_stats_report_the_allocated_cache_and_the_routing(params):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(CFG, params, lanes=2, max_len=32)
    try:
        st = ex.stats()
        per_token = CFG.num_layers * (CFG.kv_lora_rank + CFG.qk_rope_head_dim) * 4
        assert st["kv_bytes_per_token"] == per_token
        assert st["kv_cache_bytes"] == per_token * 2 * 32
        assert st["moe"]["assignments"] == 0
        ids = _ids(9, 6)
        r = ex.process("a", {"tokens": [ids], "start_pos": 0, "real_len": 9})
        tok = int(np.argmax(r["logits"][0]))
        want = reference_logits(CFG, params, ids + [tok])
        np.testing.assert_allclose(r["logits"][0], want[8], atol=2e-5)
        r = ex.process("a", {"tokens": [[tok]], "start_pos": 9, "real_len": 1})
        np.testing.assert_allclose(r["logits"][0], want[9], atol=2e-5)
        st = ex.stats()
        sparse = CFG.num_layers - CFG.num_dense_layers
        chose = sparse * CFG.num_experts_per_tok  # one row: all distinct
        assert st["moe"] == {
            "experts": CFG.num_experts, "steps": 1, "assignments": chose, "experts_touched": chose,
            "assignments_hottest": sparse,
            # every expert is held here (PR 44: a rank's share would hold fewer)
            "experts_held": CFG.num_experts, "assignments_here": chose, "experts_touched_here": chose}
        with pytest.raises(ValueError, match="tiny-dsv2"):
            ex.export_sessions()
        with pytest.raises(ValueError, match="tiny-dsv2"):
            ex.export_session_delta("a", 0)
        with pytest.raises(ValueError, match="tiny-dsv2"):
            ex.import_session("b", {})
    finally:
        ex.end_session("a")
    dense = BatchedExecutor(get_config("tiny"), qwen3.init_params(get_config("tiny"), jax.random.PRNGKey(0)),
                            lanes=2, max_len=16)
    assert "moe" not in dense.stats()


# ---------------------------------------------------------------------------
# (e) mscale, scale and the YaRN frequencies, by hand for factor 40
# ---------------------------------------------------------------------------


def test_yarn_numbers_for_factor_40():
    full = get_config("deepseek-v2-lite")
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40.0) + 1.0) == pytest.approx(1.26081, abs=1e-5)
    assert full.attn_scale == pytest.approx(192 ** -0.5 * 1.58963, rel=1e-5)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # dims under `low` keep their frequency, dims over `high` are divided by 40:
    # low = floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10, high = ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = 23
    pos = jnp.asarray([[1.0]])
    cos, sin = qwen3.rope_cos_sin(pos, 64, 10_000.0, full)
    ang = np.arctan2(np.asarray(sin[0, 0, :32], np.float64), np.asarray(cos[0, 0, :32], np.float64))
    base = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(ang[:11], base[:11], rtol=1e-5)
    np.testing.assert_allclose(ang[23:], base[23:] / 40.0, rtol=1e-4)
    i = 16  # inside the ramp: (16 - 10) / 13 of the way from kept to divided
    mix = (i - 10) / 13
    np.testing.assert_allclose(ang[i], base[i] * ((1 - mix) + mix / 40.0), rtol=1e-4)
    # cos and sin carry mscale / mscale_all_dim = 1.0 here, not 1.26
    assert float(cos[0, 0, 0] ** 2 + sin[0, 0, 0] ** 2) == pytest.approx(1.0, abs=1e-5)
    np.testing.assert_allclose(_yarn_inv_freq(full), ang, rtol=1e-4)


# ---------------------------------------------------------------------------
# (f) routing, the shared expert, the dense layer
# ---------------------------------------------------------------------------


def test_routing_is_not_renormalised_and_the_shared_expert_is_added_once(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.asarray(np.random.RandomState(8).randn(1, 6, CFG.hidden_size), jnp.float32)
    out, chosen = qwen3.moe_mlp_routed(lp, CFG, x)
    logits = np.asarray(x[0] @ lp["router"], np.float64)
    g = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    topw, topi = qwen3.route_topk(CFG, jnp.asarray(logits, jnp.float32))
    assert np.all(np.asarray(topw).sum(-1) < 0.9)  # the top 2 of 8: their own values, not scaled to 1
    np.testing.assert_allclose(np.asarray(topw), np.take_along_axis(g, np.asarray(topi), -1), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(chosen[0]), np.asarray(topi))
    scaled = dataclasses.replace(CFG, routed_scaling_factor=2.5)
    np.testing.assert_allclose(
        np.asarray(qwen3.route_topk(scaled, jnp.asarray(logits, jnp.float32))[0]),
        2.5 * np.asarray(topw), rtol=1e-6)
    no_shared = dataclasses.replace(CFG, n_shared_experts=0)
    routed_only, _ = qwen3.moe_mlp_routed(lp, no_shared, x)
    shared = qwen3.swiglu_mlp(
        {k: lp[f"shared_{k}"] for k in ("gate_proj", "up_proj", "down_proj")}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(routed_only + shared), atol=1e-6)


def test_layer_zero_is_dense_and_the_rest_are_sparse(params):
    assert "router" not in params["dense_layers"] and "shared_up_proj" not in params["dense_layers"]
    assert params["dense_layers"]["gate_proj"].shape == (1, CFG.hidden_size, CFG.intermediate_size)
    assert params["layers"]["gate_proj"].shape == (
        3, CFG.num_experts, CFG.hidden_size, CFG.moe_intermediate_size)
    assert params["layers"]["shared_gate_proj"].shape == (3, CFG.hidden_size, 2 * CFG.moe_intermediate_size)
    assert "k_proj" not in params["layers"] and "v_proj" not in params["layers"]
    assert params["layers"]["kv_a_proj"].shape == (3, CFG.hidden_size, CFG.kv_lora_rank + CFG.qk_rope_head_dim)
    assert [qwen3._stack_len(g) for g in qwen3.layer_groups(params)] == [1, 3]


# ---------------------------------------------------------------------------
# (g) every path that cannot serve it refuses by name
# ---------------------------------------------------------------------------

REFUSED = {
    "paged-kv": ["--batch-lanes", "2", "--paged-kv", "16"],
    "mesh": ["--mesh", "pp=2"],
    "stage-lanes": ["--stage-lanes", "2"],
    "quant": ["--batch-lanes", "2", "--quant", "int8"],
    "spec": ["--batch-lanes", "2", "--spec-draft-layers", "1"],
    "lora": ["--batch-lanes", "2", "--lora", "/nowhere"],
    "adapters": ["--batch-lanes", "2", "--adapters", "/nowhere"],
    "standby-repl": ["--batch-lanes", "2", "--standby-repl"],
    "no-lanes": [],
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_by_name(path, tmp_path):
    from inferd_tpu.tools import run_node

    args = run_node.build_parser().parse_args(
        ["--model", "tiny-dsv2", "--parts", str(tmp_path), "--device", "cpu", *REFUSED[path]])
    with pytest.raises(SystemExit, match="tiny-dsv2 cannot be served with"):
        asyncio.run(run_node._run(args))


def test_run_node_lets_the_lane_path_through():
    from inferd_tpu.tools import run_node

    args = argparse.Namespace(
        mesh="", stage_lanes=0, paged_kv=0, quant="none", spec_draft_layers=0, lora="",
        adapters="", standby_repl=False, backend="qwen3", batch_lanes=4)
    run_node.check_servable(CFG, args)
    args.batch_lanes = 0
    run_node.check_servable(get_config("tiny-moe"), args)  # other models: nothing to refuse


def test_lower_layers_refuse_by_name_too(params):
    with pytest.raises(ValueError, match="tiny-dsv2"):
        BlockPool(CFG, CFG.num_layers, 2, 32, block_size=16)
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params

    with pytest.raises(ValueError, match="tiny-dsv2"):
        extract_stage_params(params, CFG, Manifest.even_split("tiny-dsv2", 2).stage_spec(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.zeros((1, 2, CFG.hidden_size), jnp.float32)
    pos = jnp.arange(2)[None]
    cos, sin = qwen3.rope_cos_sin(pos, CFG.rope_dim, CFG.rope_theta, CFG)
    with pytest.raises(ValueError, match="tiny-dsv2"):
        qwen3.decoder_layer(lp, CFG, x, cos, sin, pos, None, None, None, tp_axis="tp")


# ---------------------------------------------------------------------------
# checkpoint round trip; the published preset
# ---------------------------------------------------------------------------


def test_the_checkpoint_carries_both_groups(params, tmp_path):
    from inferd_tpu.parallel.stages import Manifest, load_stage_checkpoint, split_and_save

    paths = split_and_save(params, CFG, Manifest.even_split("tiny-dsv2", 1), str(tmp_path))
    loaded, spec, name = load_stage_checkpoint(paths[0])
    assert name == "tiny-dsv2" and spec.num_stages == 1
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_the_published_preset_and_its_cut():
    full, cut = PRESETS["deepseek-v2-lite"], PRESETS["deepseek-v2-lite-8l"]
    assert dataclasses.replace(cut, name=full.name, num_layers=27) == full
    assert (cut.num_layers, cut.num_dense_layers) == (8, 1)
    shapes = jax.eval_shape(lambda: qwen3.init_params(cut, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert round(count / 1e6) == 4594  # 4.59 G parameters, 9.19 GB in bf16


# ---------------------------------------------------------------------------
# HF `deepseek_v2` names -> the grouped tree
# ---------------------------------------------------------------------------


def _interleaved(x):  # HF's rope over adjacent pairs: (x0, y0), (x1, y1), ...
    return np.stack([-x[..., 1::2], x[..., 0::2]], axis=-1).reshape(x.shape)


def test_loader_maps_hf_names_and_de_interleaves_the_rope_dimensions(params):
    from inferd_tpu.models.loader import params_from_hf_state_dict

    rng = np.random.RandomState(9)
    cfg = CFG
    h, n, dn, dr, dv, r = (cfg.hidden_size, cfg.num_heads, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
    mi, e = cfg.moe_intermediate_size, cfg.num_experts
    f = lambda *shape: (rng.randn(*shape) * 0.05).astype(np.float32)
    sd = {"model.embed_tokens.weight": f(cfg.vocab_size, h), "model.norm.weight": f(h),
          "lm_head.weight": f(cfg.vocab_size, h)}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        sd.update({
            pre + "input_layernorm.weight": f(h), pre + "post_attention_layernorm.weight": f(h),
            pre + "self_attn.q_proj.weight": f(n * (dn + dr), h),
            pre + "self_attn.kv_a_proj_with_mqa.weight": f(r + dr, h),
            pre + "self_attn.kv_a_layernorm.weight": f(r),
            pre + "self_attn.kv_b_proj.weight": f(n * (dn + dv), r),
            pre + "self_attn.o_proj.weight": f(h, n * dv),
        })
        if i == 0:
            sd.update({pre + "mlp.gate_proj.weight": f(cfg.intermediate_size, h),
                       pre + "mlp.up_proj.weight": f(cfg.intermediate_size, h),
                       pre + "mlp.down_proj.weight": f(h, cfg.intermediate_size)})
            continue
        sd[pre + "mlp.gate.weight"] = f(e, h)
        for who, width in [(f"experts.{j}", mi) for j in range(e)] + [("shared_experts", 2 * mi)]:
            sd.update({pre + f"mlp.{who}.gate_proj.weight": f(width, h),
                       pre + f"mlp.{who}.up_proj.weight": f(width, h),
                       pre + f"mlp.{who}.down_proj.weight": f(h, width)})
    got = params_from_hf_state_dict(cfg, sd)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)))
    np.testing.assert_array_equal(
        np.asarray(got["layers"]["gate_proj"][1, 3]), sd["model.layers.2.mlp.experts.3.gate_proj.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(got["layers"]["shared_down_proj"][0]), sd["model.layers.1.mlp.shared_experts.down_proj.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(got["dense_layers"]["up_proj"][0]), sd["model.layers.0.mlp.up_proj.weight"].T)
    np.testing.assert_array_equal(np.asarray(got["layers"]["router"][2]), sd["model.layers.3.mlp.gate.weight"].T)
    # the rope dimensions: HF turns interleaved pairs of ITS columns, the
    # program turns halves of the permuted ones; the dot products agree
    x = rng.randn(h).astype(np.float32)
    ang = rng.rand(dr // 2).astype(np.float32)
    q_hf = (sd["model.layers.1.self_attn.q_proj.weight"] @ x).reshape(n, dn + dr)[:, dn:]
    k_hf = (sd["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"] @ x)[r:]
    cos, sin = np.repeat(np.cos(ang), 2), np.repeat(np.sin(ang), 2)
    q_hf, k_hf = q_hf * cos + _interleaved(q_hf) * sin, k_hf * cos + _interleaved(k_hf) * sin
    q_us = (x @ np.asarray(got["layers"]["q_proj"][0])).reshape(n, dn + dr)[:, dn:]
    k_us = (x @ np.asarray(got["layers"]["kv_a_proj"][0]))[r:]
    cos2, sin2 = np.tile(np.cos(ang), 2), np.tile(np.sin(ang), 2)
    rot = lambda v: np.concatenate([-v[..., dr // 2:], v[..., : dr // 2]], -1)
    q_us, k_us = q_us * cos2 + rot(q_us) * sin2, k_us * cos2 + rot(k_us) * sin2
    np.testing.assert_allclose(q_us @ k_us, q_hf @ k_hf, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(  # the no-rope columns are untouched
        (x @ np.asarray(got["layers"]["q_proj"][0])).reshape(n, dn + dr)[:, :dn],
        (sd["model.layers.1.self_attn.q_proj.weight"] @ x).reshape(n, dn + dr)[:, :dn], rtol=1e-5)
