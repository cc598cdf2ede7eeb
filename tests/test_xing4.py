# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values
"""The Xing4.0 layer on the lane path at `tiny-xing4`: a residual STREAM of
four hidden states under manifold-constrained hyper-connections (mHC: each
sublayer reads one mix of them, writes back by another, and the stream is
mixed by a doubly-stochastic matrix made by twenty Sinkhorn rounds), latent
attention WITH query compression, a dense layer and then sigmoid-routed
experts beside a shared one. Seeded random weights, float32 at `highest`.
The float32 full forward the program is held to is the benchmark's own plain
reference (`benchmark/references/xing4.py`: expanded attention, a Python loop
over the chosen experts and over the Sinkhorn rounds, no cache, independent
of `models/qwen3.py`), loaded here by its file. One engine serves every test
that needs lanes."""

import argparse
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import get_config
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import KVCache
from inferd_tpu.models import qwen3

CFG = get_config("tiny-xing4")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 both sides, matmuls at `highest`: the two differ by the order of
# float32 additions (the program applies the stream's norm to 24 products, the
# reference to 256 values; absorbed decode sums over latents, the reference
# over heads' keys), some 1e-6 on log-probabilities of size 5
TOL = 2e-5
WRONG = 1e-3  # a mistake in the mathematics moves the log-probabilities by far more
LANES = 3
N = CFG.hc_mult


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    """Drawn away from init's flat spots, where a mistake would hide: the
    norms get a weight that is not 1, a_res is 6 (H~res has deviation 14, so
    some entries pass the clamp at 30 and Hres is far from uniform), the
    selection bias is as wide as the scores differ, the mixer's output wider."""
    p = qwen3.init_params(CFG, jax.random.PRNGKey(7))
    key = jax.random.PRNGKey(8)
    for group in ("dense_layers", "layers"):
        g = dict(p[group])
        for i, name in enumerate(sorted(g)):
            if name.endswith("_norm"):
                g[name] = g[name] + 0.3 * jax.random.normal(jax.random.fold_in(key, i), g[name].shape)
        for sub in qwen3.STREAM_SUBLAYERS:
            g[f"hc_{sub}_scale"] = g[f"hc_{sub}_scale"].at[:, 2].set(6.0)
        g["o_proj"] = g["o_proj"] * 6.0
        if "router_select_bias" in g:
            g["router_select_bias"] = 0.3 * jax.random.normal(key, g["router_select_bias"].shape)
        p[group] = g
    p["final_norm"] = p["final_norm"] + 0.3 * jax.random.normal(key, p["final_norm"].shape)
    return p


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "xing4_reference", os.path.join(REPO, "benchmark", "references", "xing4.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg):
    """The keys the benchmark's reference reads, as the configuration's file names them."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.rope_scaling_factor,
            "original_max_position_embeddings": cfg.rope_original_max_position,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale, "mscale_all_dim": cfg.rope_mscale_all_dim},
        "num_hidden_layers": cfg.num_layers, "first_k_dense_replace": cfg.first_k_dense_replace,
        "n_routed_experts": cfg.num_experts, "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling_factor,
        "n_shared_experts": cfg.n_shared_experts,
        "hc_mult": cfg.hc_mult, "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters, "hc_eps": cfg.hc_eps,
        "mhc_h_res_clamp_min": -cfg.hc_res_clamp, "mhc_h_res_clamp_max": cfg.hc_res_clamp,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "tie_word_embeddings": False, "hidden_act": "silu", "attention_bias": False,
        "moe_layer_freq": 1,
    }


def _ids(n, seed=3):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))


@pytest.fixture(scope="module")
def eng(params):
    return BatchedEngine(CFG, params, lanes=LANES, max_len=64)


@pytest.fixture(scope="module")
def want(params, reference):
    """30 tokens and the reference's log-probabilities at every position."""
    ids = _ids(30)
    return ids, reference.logprobs(jax.tree.map(np.asarray, params), np.asarray(ids), 30,
                                   published(CFG))


def _prefill(eng, lane, ids, start=0, bucket=None):
    b = bucket or len(ids)
    padded = np.zeros((1, b), np.int32)
    padded[0, : len(ids)] = ids
    eng.cache, logits = eng._prefill_lane_logits(
        eng.params, eng.cache, jnp.asarray(padded), jnp.int32(lane), jnp.int32(start),
        jnp.int32(len(ids)))
    return np.asarray(logits)


def _decode(eng, toks, lens):
    eng.cache, last, _ = eng._decode_logits(
        eng.params, eng.cache, jnp.asarray(toks, jnp.int32), jnp.asarray(lens, jnp.int32))
    return np.asarray(last)


def _stream(seed, b=2, s=5, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (N, b, s, CFG.hidden_size), jnp.float32)
    return x * scale


def _layer(params, group="layers", i=0):
    return jax.tree.map(lambda a: a[i], params[group])


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------


def test_prefill_in_three_chunks_then_eight_decode_steps_equal_one_pass_of_the_reference(
        eng, want):
    """Through the lanes' latent cache: three chunks (the last padded to its
    bucket) in expanded attention, then eight absorbed decode steps, lane 1 of
    three; every row against the reference's ONE full forward."""
    ids, lp = want
    _prefill(eng, 1, ids[:8], 0)
    _prefill(eng, 1, ids[8:16], 8)
    rows = [_prefill(eng, 1, ids[16:22], 16, bucket=8)]
    for j, t in enumerate(ids[22:30]):
        rows.append(_decode(eng, [0, t, 0], [0, 22 + j, 0])[1])
    assert np.abs(_logp(np.stack(rows)) - lp[21:30]).max() < TOL


def test_the_cache_free_forward_equals_the_reference_at_every_position(params, want):
    ids, lp = want
    full, _, _ = qwen3.forward(params, CFG, jnp.asarray([ids]))
    assert np.abs(_logp(full[0]) - lp).max() < TOL


def test_absorbed_equals_expanded_with_the_compressed_query(params):
    """One layer's attention over a cache of 12 tokens, the 13th as a decode
    row (absorbed) and as the last row of a 13-token chunk (expanded)."""
    lp = _layer(params)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 13, CFG.hidden_size), jnp.float32)
    pos = jnp.arange(13)[None]
    cos, sin = qwen3.rope_cos_sin(pos, CFG.rope_dim, CFG.rope_theta, CFG)
    expanded, _ = qwen3._mla_attend_update(lp, CFG, x, cos, sin, pos, None, None, None)
    cache = KVCache.create(CFG, 1, 1, 16)
    (entry,) = cache.entries(CFG)
    _, entry = qwen3._mla_attend_update(
        lp, CFG, x[:, :12], cos[:, :12], sin[:, :12], pos[:, :12], entry, 0, cache.ctx(jnp.int32(0)))
    absorbed, _ = qwen3._mla_attend_update(
        lp, CFG, x[:, 12:], cos[:, 12:], sin[:, 12:], pos[:, 12:], entry, 0, cache.ctx(jnp.int32(12)))
    assert "q_a_proj" in lp and "q_proj" not in lp
    np.testing.assert_allclose(np.asarray(absorbed[0, 0]), np.asarray(expanded[0, 12]),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# the stream's maps
# ---------------------------------------------------------------------------


def _res(maps):
    return np.asarray(jnp.stack([jnp.stack(row) for row in maps.res]))  # [n, n, B, S]


@pytest.mark.parametrize("sub", qwen3.STREAM_SUBLAYERS)
def test_after_twenty_rounds_every_row_and_column_of_hres_sums_to_one(sub):
    """At the seeded draw the configuration's file describes (a_res 0.2, the
    diagonal raised by 1), over two thousand tokens' streams."""
    lp = _layer(qwen3.init_params(CFG, jax.random.PRNGKey(55)))
    maps = qwen3.stream_maps(lp, CFG, _stream(4, b=4, s=512, scale=3.0), sub)
    res = _res(maps)
    assert res.min() > 0
    assert np.abs(res.sum(axis=0) - 1).max() < 1e-4 and np.abs(res.sum(axis=1) - 1).max() < 1e-4
    pre, post = np.asarray(jnp.stack(maps.pre)), np.asarray(jnp.stack(maps.post))
    assert 0 < pre.min() and pre.max() < 1 and 0 < post.min() and post.max() < 2
    assert pre.std() > 0.1 and post.std() > 0.2  # alive: they differ from token to token
    assert res[0, 1].std() > 0.03 and 0.3 < res[0, 0].mean() < 0.6


def test_an_h_res_of_ten_thousand_stays_finite_under_the_clamp(params):
    lp = dict(_layer(params))
    lp["hc_attn_scale"] = jnp.asarray([0.5, 0.5, 1e4], jnp.float32)
    res = _res(qwen3.stream_maps(lp, CFG, _stream(5), "attn"))
    assert np.isfinite(res).all() and np.abs(res.sum(axis=1) - 1).max() < 1e-4
    loose = dataclasses.replace(CFG, hc_res_clamp=1e9)  # without it exp overflows
    assert not np.isfinite(_res(qwen3.stream_maps(lp, loose, _stream(5), "attn"))).all()


def test_sinkhorn_is_column_then_row_and_a_trip_of_the_loop_changes_nothing():
    m = np.random.default_rng(0).uniform(0.1, 3.0, (N, N, 7)).astype(np.float32)
    want = m.copy()
    for _ in range(20):
        want = want / (want.sum(axis=0, keepdims=True) + 1e-6)
        want = want / (want.sum(axis=1, keepdims=True) + 1e-6)
    rows = [[jnp.asarray(m[i, j]) for j in range(N)] for i in range(N)]
    got = np.asarray(jnp.stack([jnp.stack(r) for r in qwen3.sinkhorn(rows, 20, 1e-6)]))  # four trips
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert np.abs(got.sum(axis=1) - 1).max() < 1e-5
    with pytest.raises(AssertionError):  # a trip is SINKHORN_TRIP rounds, whole
        qwen3.sinkhorn(rows, 7, 1e-6)


def test_identity_maps_reduce_a_layer_to_the_plain_pre_norm_block(params):
    """Hres = I, Hpre one-hot and Hpost matching: hidden state 0 goes through
    the layer as through the plain block of the same weights (the join pair
    against `hidden + y`), and the other three are left as they were."""
    big = 40.0
    onehot = jnp.where(jnp.arange(N) == 0, big, -big)
    bias = jnp.concatenate([onehot, jnp.where(jnp.arange(N) == 0, 0.0, -big),
                            (big * (2 * jnp.eye(N) - 1)).reshape(-1)])
    lp = dict(_layer(params))
    for sub in qwen3.STREAM_SUBLAYERS:
        lp[f"hc_{sub}_scale"] = jnp.zeros((3,), jnp.float32)
        lp[f"hc_{sub}_bias"] = bias.astype(jnp.float32)
    stream = _stream(6, b=1, s=9)
    pos = jnp.arange(9)[None]
    cos, sin = qwen3.rope_cos_sin(pos, CFG.rope_dim, CFG.rope_theta, CFG)
    out, _, _ = qwen3.decoder_layer(lp, CFG, stream, cos, sin, pos)
    plain_cfg = dataclasses.replace(CFG, hc_mult=0)
    plain, _, _ = qwen3.decoder_layer(lp, plain_cfg, stream[0], cos, sin, pos)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(plain), rtol=0, atol=2e-5)
    # forty divisions by a sum that is the entry itself but for its last bits
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(stream[1:]), rtol=1e-5, atol=1e-6)


def test_without_hc_mult_the_join_is_the_plain_add_bit_for_bit_and_traces_no_stream():
    cfg = get_config("tiny-dsv2")
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.hidden_size), jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 3, cfg.hidden_size), jnp.float32)
    x, maps = qwen3.stream_read({}, cfg, h, "attn")
    assert x is h and maps is None
    got = qwen3.stream_join(h, y, None)
    assert got.dtype == h.dtype and np.array_equal(np.asarray(got), np.asarray(h + y.astype(h.dtype)))
    assert str(jax.make_jaxpr(lambda h, y: qwen3.stream_join(h, y, None))(h, y)) == str(
        jax.make_jaxpr(lambda h, y: h + y.astype(h.dtype))(h, y))
    toks = jnp.zeros((1, 4), jnp.int32)

    def text(c):
        p = jax.eval_shape(lambda: qwen3.init_params(c, jax.random.PRNGKey(0)))
        return jax.make_jaxpr(lambda p: qwen3.forward(p, c, toks)[0])(p).pretty_print(
            name_stack=True)

    plain, stream = text(cfg), text(CFG)
    assert "hc_" not in plain and "mla_q_lora" not in plain
    for scope in ("hc_map", "hc_sinkhorn", "hc_read", "hc_join", "mla_q_lora"):
        assert scope in stream, scope


MISTAKES = ["row_then_column", "no_clamp", "hpost_without_the_two", "hres_transposed",
            "norm_over_c", "selection_bias_in_the_weights"]


@pytest.mark.parametrize("mistake", MISTAKES)
def test_each_mistake_in_the_mathematics_fails_parity(params, want, reference, mistake,
                                                      monkeypatch):
    """What the tolerance is worth: the reference with ONE term of the
    equations wrong is far outside it."""
    ids, lp = want
    config = published(CFG)
    sums = jnp.sum
    def wrong_maps(xs, p, c, sub, rows_first=False, per_state=False, post_mul=2.0,
                   transposed=False):
        n, s, width = xs.shape
        flat = jnp.transpose(xs, (1, 0, 2)).reshape(s, n * width)
        if per_state:  # each hidden state normed over its own C values
            normed = jnp.transpose(
                xs * jax.lax.rsqrt(jnp.mean(xs * xs, -1, keepdims=True) + c["rms_norm_eps"]),
                (1, 0, 2)).reshape(s, n * width)
        else:
            normed = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + c["rms_norm_eps"])
        h = normed @ p[f"hc_{sub}_proj"].reshape(-1, n * width).T
        a_pre, a_post, a_res = p[f"hc_{sub}_scale"]
        b = p[f"hc_{sub}_bias"]
        pre = jax.nn.sigmoid(a_pre * h[:, :n] + b[:n])
        post = post_mul * jax.nn.sigmoid(a_post * h[:, n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip(a_res * h[:, 2 * n:].reshape(s, n, n) + b[2 * n:].reshape(n, n),
                             c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]))
        for _ in range(c["hc_sinkhorn_iters"]):
            for axis in ((2, 1) if rows_first else (1, 2)):
                m = m / (sums(m, axis=axis, keepdims=True) + c["hc_eps"])
        return pre, post, jnp.swapaxes(m, 1, 2) if transposed else m

    if mistake == "row_then_column":
        monkeypatch.setattr(reference, "stream_maps",
                            lambda *a: wrong_maps(*a, rows_first=True))
    elif mistake == "no_clamp":
        config = {**config, "mhc_h_res_clamp_min": -1e9, "mhc_h_res_clamp_max": 1e9}
    elif mistake == "hpost_without_the_two":
        monkeypatch.setattr(reference, "stream_maps", lambda *a: wrong_maps(*a, post_mul=1.0))
    elif mistake == "norm_over_c":
        monkeypatch.setattr(reference, "stream_maps", lambda *a: wrong_maps(*a, per_state=True))
    elif mistake == "selection_bias_in_the_weights":
        def route(a, p, c):
            g = jax.nn.sigmoid(a @ p["router"]) + p["router_select_bias"]
            w, chosen = jax.lax.top_k(g, c["num_experts_per_tok"])
            w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
            return jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], chosen].set(w)

        monkeypatch.setattr(reference, "route", route)
    elif mistake == "hres_transposed":  # X' = Hres^T X: new state m takes COLUMN m
        monkeypatch.setattr(reference, "stream_maps", lambda *a: wrong_maps(*a, transposed=True))
    got = reference.logprobs(jax.tree.map(np.asarray, params), np.asarray(ids), 30, config)
    assert not np.abs(got - lp).max() < WRONG  # `no_clamp` overflows: not a number is wrong too


def test_a_mean_in_place_of_the_sum_out_is_the_same_model_up_to_the_norms_epsilon(params, want):
    """The seventh mistake one could name is none: the final RMSNorm is free
    of scale, so a mean of the four hidden states in place of their sum moves
    the log-probabilities only through rms_norm_eps (1e-6 beside a mean square
    of order one). The program sums, as the configuration's file says."""
    ids, lp = want
    stream = qwen3.embed(params, jnp.asarray([ids]), CFG)
    assert stream.shape == (N, 1, 30, CFG.hidden_size)
    np.testing.assert_array_equal(np.asarray(stream[0]), np.asarray(stream[N - 1]))
    offset = 0
    for layers in qwen3.layer_groups(params):
        stream, _, _ = qwen3.forward_layers(layers, CFG, stream, jnp.arange(30)[None],
                                            layer_offset=offset)
        offset += qwen3._stack_len(layers)
    plain = dataclasses.replace(CFG, hc_mult=0)
    summed = qwen3.unembed(params, plain, jnp.sum(stream, axis=0))
    mean = qwen3.unembed(params, plain, jnp.mean(stream, axis=0))
    assert np.abs(_logp(summed[0]) - lp).max() < TOL
    assert np.abs(_logp(mean[0]) - _logp(summed[0])).max() < 1e-4


# ---------------------------------------------------------------------------
# the preset, what is refused, the counters, the published names
# ---------------------------------------------------------------------------


def test_the_served_preset_is_the_cut_of_the_published_one_and_its_cache_the_arithmetic():
    """`xing4.0-29b-a4b-6l` is one of the two dense layers and five sparse
    ones of the published 40 at every width; its parameters and its cache at
    16 lanes x 16 384 are the configuration's `deployment` (shapes only)."""
    whole, cut = get_config("xing4.0-29b-a4b"), get_config("xing4.0-29b-a4b-6l")
    assert dataclasses.replace(cut, name=whole.name, num_layers=40, first_k_dense_replace=2) == whole
    assert (cut.hc_mult, cut.hc_sinkhorn_iters, cut.hc_eps, cut.hc_res_clamp, cut.q_lora_rank) == (
        4, 20, 1e-6, 30.0, 768)
    assert abs(cut.attn_scale - 192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9
    shapes = jax.eval_shape(lambda: qwen3.init_params(cut, jax.random.PRNGKey(0)))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["dense_layers"]) == 128_196_918
    assert count(shapes["layers"]) == 5 * 744_989_046
    assert count(shapes) == 4_792_669_828
    per_sublayer = sum(count(shapes["layers"][f"hc_attn_{k}"]) for k in ("proj", "bias", "scale"))
    assert per_sublayer == 5 * (14_336 * 24 + 27)
    assert shapes["layers"]["q_b_proj"].shape == (5, 768, 32 * 192)
    cache = jax.eval_shape(lambda: KVCache.create(cut, cut.num_layers, 16, 16384))
    assert cache.nbytes == 16 * 16384 * 6 * 1152 == 1_811_939_328


def test_the_seeded_draw_scales_the_routed_down_projections_and_nothing_else():
    """`seeded_routed_scale` is read by the seeded draw alone: the routed
    experts' down-projections are the plain draw times it (0.125 is a power
    of two: exact in any dtype), every other leaf is the plain draw's, and
    the served presets carry it while `tiny-xing4` draws at 1."""
    assert get_config("xing4.0-29b-a4b-6l").seeded_routed_scale == 0.125
    assert CFG.seeded_routed_scale == get_config("tiny-dsv2").seeded_routed_scale == 1.0
    scaled = dataclasses.replace(CFG, seeded_routed_scale=0.125)
    plain = qwen3.init_params(CFG, jax.random.PRNGKey(55))
    drawn = qwen3.init_params(scaled, jax.random.PRNGKey(55))
    moved = [path for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(plain), jax.tree_util.tree_leaves_with_path(drawn))
        if not np.array_equal(np.asarray(a), np.asarray(b))]
    assert [jax.tree_util.keystr(path) for path in moved] == ["['layers']['down_proj']"]
    np.testing.assert_array_equal(np.asarray(drawn["layers"]["down_proj"]),
                                  0.125 * np.asarray(plain["layers"]["down_proj"]))
    np.testing.assert_array_equal(np.asarray(drawn["layers"]["shared_down_proj"]),
                                  np.asarray(plain["layers"]["shared_down_proj"]))


@pytest.mark.parametrize("bad,said", [
    (dict(hc_mult=1), "hc_mult >= 2"), (dict(hc_sinkhorn_iters=0), "Sinkhorn round"),
    (dict(kv_lora_rank=0, qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0), "q_lora_rank"),
])
def test_a_config_that_contradicts_itself_is_refused(bad, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CFG, **bad)


REFUSED = {
    "mesh": (dict(mesh="pp=2"), "a tick hands the next rank one hidden state"),
    "stage-lanes": (dict(stage_lanes=2), "take and give one hidden state a token"),
    "relay": (dict(), "a relay's hop carries one hidden state"),
    "spec": (dict(spec_draft_layers=1), "a draft over the first layers"),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_every_path_that_hands_on_one_hidden_state(path):
    """In the latent table that is there: a stream of four hidden states
    cannot cross a mesh tick, a stage's lanes, a relay's hop or a self-draft,
    a sentence each; the lane path with --kv-dtype stays open, and a latent
    model without a stream is told none of them."""
    from inferd_tpu.tools import run_node

    base = dict(mesh="", stage_lanes=0, paged_kv=0, quant="none", spec_draft_layers=0, lora="",
                adapters="", standby_repl=False, backend="qwen3", batch_lanes=16)
    cfg = get_config("xing4.0-29b-a4b-6l")
    run_node.check_servable(cfg, argparse.Namespace(**base))
    flags, sentence = REFUSED[path]
    stages = 2 if path == "relay" else 1
    with pytest.raises(SystemExit, match="xing4.0-29b-a4b-6l cannot be served with") as e:
        run_node.check_servable(cfg, argparse.Namespace(**{**base, **flags}), num_stages=stages)
    assert sentence in str(e.value)
    try:
        run_node.check_servable(get_config("deepseek-v2-lite-8l"),
                                argparse.Namespace(**{**base, **flags}), num_stages=stages)
    except SystemExit as other:
        assert sentence not in str(other) and "hidden state" not in str(other)


def test_the_executor_counts_the_stream_and_the_latents(params):
    """/stats `executor`: `stream_bytes_per_token` is n x hidden x the
    activation's bytes, `kv_layout` latent, the `moe.*` counters move; a model
    without a stream reports no such key."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)
    ex.process("a", {"tokens": [_ids(8)], "start_pos": 0, "real_len": 8})
    ex.process("a", {"tokens": [[5]], "start_pos": 8, "real_len": 1})
    st = ex.stats()
    assert st["stream_bytes_per_token"] == 4 * 64 * 4
    assert st["kv_layout"] == "latent" and st["kv_bytes_per_token"] == 4 * (32 + 8) * 4
    assert st["moe"]["steps"] >= 1 and st["moe"]["experts"] == 8
    served = get_config("xing4.0-29b-a4b-6l")
    assert served.hc_mult * served.hidden_size * served.jnp_dtype.itemsize == 28_672
    plain = get_config("tiny-dsv2")
    other = BatchedExecutor(plain, qwen3.init_params(plain, jax.random.PRNGKey(0)), lanes=2,
                            max_len=64)
    assert "stream_bytes_per_token" not in other.stats()


def _hf_state_dict(params, cfg):
    """The tree under the names models/loader.py reads, rope columns interleaved
    as a DeepSeek checkpoint stores them."""
    host = jax.tree.map(np.asarray, params)
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    pairs = np.argsort(np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)]))
    sd = {"model.embed_tokens.weight": host["embed"], "model.norm.weight": host["final_norm"],
          "lm_head.weight": host["lm_head"].T,
          "model.mtp.0.enorm.weight": np.ones(cfg.hidden_size, np.float32)}  # not read
    nd = cfg.num_dense_layers
    for i in range(cfg.num_layers):
        stack, at = (host["dense_layers"], i) if i < nd else (host["layers"], i - nd)
        lp = {k: v[at] for k, v in stack.items()}
        pre = f"model.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = lp["input_norm"]
        sd[f"{pre}.post_attention_layernorm.weight"] = lp["post_norm"]
        q = lp["q_b_proj"].reshape(cfg.q_lora_rank, cfg.num_heads, dn + dr)
        q = np.concatenate([q[..., :dn], q[..., dn:][..., pairs]], -1).reshape(cfg.q_lora_rank, -1)
        kv_a = np.concatenate([lp["kv_a_proj"][:, :r], lp["kv_a_proj"][:, r:][:, pairs]], -1)
        for theirs, ours in (("q_a_proj", lp["q_a_proj"]), ("q_b_proj", q),
                             ("kv_a_proj_with_mqa", kv_a), ("kv_b_proj", lp["kv_b_proj"]),
                             ("o_proj", lp["o_proj"])):
            sd[f"{pre}.self_attn.{theirs}.weight"] = ours.T
        sd[f"{pre}.self_attn.q_a_layernorm.weight"] = lp["q_a_norm"]
        sd[f"{pre}.self_attn.kv_a_layernorm.weight"] = lp["kv_a_norm"]
        for sub in qwen3.STREAM_SUBLAYERS:
            sd[f"{pre}.hc_{sub}.weight"] = lp[f"hc_{sub}_proj"].reshape(cfg.hc_maps, -1)
            sd[f"{pre}.hc_{sub}.bias"] = lp[f"hc_{sub}_bias"]
            sd[f"{pre}.hc_{sub}.scale"] = lp[f"hc_{sub}_scale"]
        if i < nd:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                sd[f"{pre}.mlp.{proj}.weight"] = lp[proj].T
            continue
        sd[f"{pre}.mlp.gate.weight"] = lp["router"].T
        sd[f"{pre}.mlp.gate.e_score_correction_bias"] = lp["router_select_bias"]
        for proj in ("gate_proj", "up_proj", "down_proj"):
            sd[f"{pre}.mlp.shared_experts.{proj}.weight"] = lp[f"shared_{proj}"].T
            for e in range(cfg.num_experts):
                sd[f"{pre}.mlp.experts.{e}.{proj}.weight"] = lp[proj][e].T
    return sd


def test_the_published_names_round_trip_and_the_checkpoint_carries_the_stream(params, tmp_path):
    from inferd_tpu.models.loader import params_from_hf_state_dict
    from inferd_tpu.parallel.stages import Manifest, load_stage_checkpoint, split_and_save

    sd = _hf_state_dict(params, CFG)
    assert sd["model.layers.1.hc_ffn.weight"].shape == (24, 4 * 64)
    assert not any(k.endswith("self_attn.q_proj.weight") for k in sd)
    back = params_from_hf_state_dict(CFG, sd)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    paths = split_and_save(params, CFG, Manifest.even_split("tiny-xing4", 1), str(tmp_path))
    held, _spec, name = load_stage_checkpoint(paths[0])
    assert name == "tiny-xing4" and set(held) == set(params)
    for group in ("dense_layers", "layers"):
        assert set(held[group]) == set(params[group])
