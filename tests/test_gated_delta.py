# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values
"""The Qwen3-Next layer on the lane path at `tiny-qwen3-next`: two periods of
three Gated-DeltaNet layers to one gated full-attention layer whose rope turns
a quarter of a head, every layer with 16 softmax-routed experts (top 4) beside
a gated shared one; a second recurrence in the one state cache, state layers
that route, and one chip's SHARE of the experts. Seeded random weights,
float32 at `highest`. The float32 full forward the program is held to is the
benchmark's own plain reference (`benchmark/references/qwen3-next.py`: the
delta rule as a sequential scan over tokens, one forward pass, no cache,
independent of `models/qwen3.py`), loaded here by its file."""

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
from inferd_tpu.core.cache import KVCache, RowEntry, StateEntry
from inferd_tpu.models import qwen3

CFG = get_config("tiny-qwen3-next")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 both sides, matmuls at `highest`: the two differ by the order of a
# few hundred float32 additions (the chunked form solves a tile at once, the
# reference goes token by token), some 1e-6 on log-probabilities of size 5
TOL = 5e-6
WRONG = 1e-3  # a mistake in the mathematics moves the log-probabilities by far more


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    """Drawn away from init's flat spots, where a mistake would hide: the
    zero-centred norms get a weight (1 + w differs from w), the gates' inputs
    and the values are wider (a sigmoid at 0 forgives much)."""
    p = qwen3.init_params(CFG, jax.random.PRNGKey(7))
    key = jax.random.PRNGKey(8)
    for group in ("layers", "state_layers"):
        g = dict(p[group])
        for i, name in enumerate(sorted(g)):
            if name.endswith("_norm"):
                g[name] = g[name] + 0.3 * jax.random.normal(jax.random.fold_in(key, i), g[name].shape)
        for name in ("attn_gate_proj", "v_proj", "o_proj", "ba_proj", "shared_expert_gate"):
            if name in g:
                g[name] = g[name] * 6.0
        p[group] = g
    p["final_norm"] = p["final_norm"] + 0.3 * jax.random.normal(key, p["final_norm"].shape)
    return p


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_reference", os.path.join(REPO, "benchmark", "references", "qwen3-next.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg):
    """The keys the benchmark's reference reads, as the configuration's file names them."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "full_attention_interval": cfg.full_attention_interval,
        "layer_kinds": cfg.layer_type_names, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta, "partial_rotary_factor": cfg.partial_rotary_factor,
        "linear_num_key_heads": cfg.linear_key_heads, "linear_num_value_heads": cfg.linear_value_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim, "linear_conv_kernel_dim": cfg.linear_conv,
        "num_experts": cfg.num_experts, "router_experts": cfg.router_width,
        "expert_offset": cfg.expert_offset, "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob, "decoder_sparse_step": 1, "mlp_only_layers": [],
        "tie_word_embeddings": False,
        "shared_expert_intermediate_size": cfg.shared_expert_intermediate_size,
    }


def _ids(n, seed=3):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))


def _prefill(eng, lane, ids, start=0, bucket=None):
    """One chunk through the serving program, padded to `bucket`."""
    b = bucket or len(ids)
    padded = np.zeros((1, b), np.int32)
    padded[0, : len(ids)] = ids
    eng.cache, logits = eng._prefill_lane_logits(
        eng.params, eng.cache, jnp.asarray(padded), jnp.int32(lane), jnp.int32(start),
        jnp.int32(len(ids)))
    return np.asarray(logits)


def _decode(eng, toks, lens, active):
    eng.cache, logits, routed = eng._decode_logits(
        eng.params, eng.cache, np.asarray(toks, np.int32), np.asarray(lens, np.int32),
        active=np.asarray(active, bool))
    return np.asarray(logits), routed


# ---------------------------------------------------------------------------
# the recurrence: two forms of one function
# ---------------------------------------------------------------------------


def _sequential(q, k, v, g, beta, s0):
    """The delta rule a token at a time, float64."""
    state = s0.astype(np.float64)
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        state = np.exp(g[:, t])[..., None, None] * state
        u = beta[:, t][..., None] * (v[:, t] - np.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., None] * u[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    return out, state


@pytest.mark.parametrize("tile", [8, 24, 6])
def test_the_chunked_form_equals_the_token_by_token_recurrence(tile):
    """A chunk of 24 positions entered with a state that is not zero, in
    tiles that divide it (8: three tiles; 6: four) and as one tile (what a
    chunk its tile does not divide runs as), against the recurrence a token
    at a time; the last third padding (g = 0, beta = 0), which the state
    passes."""
    rng = np.random.default_rng(0)
    b, s, h, dk, dv = 2, 24, 3, 5, 6
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(rng.normal(size=(b, s, h, dk))).astype(np.float32) / np.sqrt(dk).astype(np.float32)
    k = unit(rng.normal(size=(b, s, h, dk))).astype(np.float32)
    v = rng.normal(size=(b, s, h, dv)).astype(np.float32)
    g = -rng.uniform(0.01, 2.0, size=(b, s, h)).astype(np.float32)
    beta = rng.uniform(0.05, 0.95, size=(b, s, h)).astype(np.float32)
    g[1, 16:], beta[1, 16:] = 0.0, 0.0
    s0 = rng.normal(size=(b, h, dk, dv)).astype(np.float32)
    want, state = _sequential(q, k, v, g, beta, s0)
    got, s_out = qwen3.gated_delta_chunked(*map(jnp.asarray, (q, k, v, g, beta, s0)), tile=tile)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_out), state, atol=2e-5)
    _, at16 = _sequential(q[1:, :16], k[1:, :16], v[1:, :16], g[1:, :16], beta[1:, :16], s0[1:])
    np.testing.assert_allclose(np.asarray(s_out)[1], at16[0], atol=2e-5)  # the padding moved nothing


def test_a_decay_too_long_for_float32_overflows_nothing():
    """Only differences of the cumulated log decay with i >= j are
    exponentiated: a tile whose decay sums to -400 stays finite."""
    rng = np.random.default_rng(1)
    b, s, h, d = 1, 16, 2, 4
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32) for _ in range(3))
    g = jnp.full((b, s, h), -25.0, jnp.float32)
    beta = jnp.full((b, s, h), 0.5, jnp.float32)
    o, s_out = qwen3.gated_delta_chunked(q, k, v, g, beta, jnp.ones((b, h, d, d), jnp.float32), 16)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s_out)).all()


# ---------------------------------------------------------------------------
# the model on the lanes against one pass of the reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def want(params, reference):
    ids = _ids(37)
    return ids, reference.logprobs(params, ids, len(ids), published(CFG))  # row t: after token t


def test_prefill_in_two_chunks_then_eight_decode_steps_equal_one_pass_of_the_reference(
        params, want):
    """20 tokens padded to 32 (tiles of 8 divide it), then 9 padded to a
    bucket of 12 (they do not: one tile), then 8 tokens one at a time through
    the state and the cache, on lane 1 of 3; and the cache-free forward is
    the same function. Logits, not tokens."""
    ids, lp = want
    eng = BatchedEngine(CFG, params, lanes=3, max_len=64)
    assert isinstance(eng.cache.entries(CFG)[0], StateEntry)
    assert isinstance(eng.cache.entries(CFG)[1], RowEntry)  # 2 kv heads of 32: one row a token
    np.testing.assert_allclose(_logp(_prefill(eng, 1, ids[:20], 0, 32)), lp[19], atol=TOL)
    np.testing.assert_allclose(_logp(_prefill(eng, 1, ids[20:29], 20, 12)), lp[28], atol=TOL)
    for t in range(29, 37):
        got, routed = _decode(eng, [0, ids[t], 0], [0, t, 0], [False, True, False])
        np.testing.assert_allclose(_logp(got[1]), lp[t], atol=TOL)
    assert np.asarray(routed).shape == (CFG.num_layers, 3, CFG.num_experts_per_tok)  # all 8 routers
    full, _, _ = qwen3.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(_logp(full[0]), lp, atol=TOL)


@pytest.mark.parametrize("mistake", ["norm_plus_one", "no_shared_gate", "rope_everywhere",
                                     "flat_beta", "key_head_map"])
def test_each_mistake_in_the_mathematics_fails_parity(params, want, mistake):
    """What the tolerance is worth: the program with ONE term of the
    equations wrong is far outside it."""
    ids, lp = want
    cfg, p = CFG, params
    state = params["state_layers"]
    if mistake == "norm_plus_one":  # every norm scaling by w, not 1 + w
        cfg = dataclasses.replace(CFG, rms_norm_plus_one=False)
    elif mistake == "no_shared_gate":
        p = {**params, **{g: {k: v for k, v in params[g].items() if k != "shared_expert_gate"}
                          for g in ("layers", "state_layers")}}
    elif mistake == "rope_everywhere":
        cfg = dataclasses.replace(CFG, partial_rotary_factor=1.0)
    elif mistake == "flat_beta":  # beta = 1/2 whatever the token
        p = {**params, "state_layers": {**state, "ba_proj": state["ba_proj"].at[..., :4].set(0.0)}}
    elif mistake == "key_head_map":  # value head h reading key head h % 2, not h // 2
        w = state["in_proj"]
        swap = lambda part: part.reshape(*part.shape[:-1], 2, 2, 16)[..., ::-1, :, :].reshape(part.shape)  # noqa: E731
        p = {**params, "state_layers": {
            **state, "in_proj": jnp.concatenate([w[..., :64], swap(w[..., 64:128]), w[..., 128:]], -1)}}
    full, _, _ = qwen3.forward(p, cfg, jnp.asarray([ids]))
    assert np.abs(_logp(full[0]) - lp).max() > WRONG


def test_a_padded_position_and_a_masked_row_leave_state_and_columns_bit_unchanged(params):
    """A decode step a lane sits out (write_mask False) and a chunk that is
    ALL padding (real_end == write_pos) leave its state, its kept columns and
    its keys bit for bit; the other lanes move."""
    eng = BatchedEngine(CFG, params, lanes=3, max_len=64)
    for lane in range(3):
        _prefill(eng, lane, _ids(7 + lane, seed=lane), 0, 16)
    before = jax.tree.map(np.asarray, eng.cache)
    assert float(np.abs(before.s).max()) > 1e-3  # a state there is
    _decode(eng, [5, 6, 7], [7, 8, 9], [True, False, True])
    after = jax.tree.map(np.asarray, eng.cache)
    for name in ("s", "conv", "k", "v"):
        old, new = getattr(before, name), getattr(after, name)
        np.testing.assert_array_equal(new[:, 1], old[:, 1])
        assert not np.array_equal(new[:, 0], old[:, 0]) and not np.array_equal(new[:, 2], old[:, 2])
    # a chunk of nothing but padding: the state passes it (g = 0, beta = 0), the columns stay
    layer = jax.tree.map(lambda a: a[0], params["state_layers"])
    entry = StateEntry(s=jnp.asarray(after.s), conv=jnp.asarray(after.conv))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 8, CFG.hidden_size), jnp.float32)
    ctx = KVCache.ctx(jnp.asarray([8, 8, 10]), jnp.asarray([8, 8, 10]))
    _, passed = qwen3.gated_delta_mixer(layer, CFG, x, entry, 0, ctx)
    np.testing.assert_array_equal(np.asarray(passed.s), after.s)
    np.testing.assert_array_equal(np.asarray(passed.conv), after.conv)


def test_a_padded_chunk_and_an_unpadded_one_leave_the_same_state(params):
    ids = _ids(11, seed=5)
    eng = BatchedEngine(CFG, params, lanes=2, max_len=64)
    a = _prefill(eng, 0, ids, 0, 11)
    b = _prefill(eng, 1, ids, 0, 32)
    np.testing.assert_allclose(a, b, atol=TOL)
    c = eng.cache
    np.testing.assert_allclose(np.asarray(c.s[:, 0]), np.asarray(c.s[:, 1]), atol=TOL)
    np.testing.assert_allclose(np.asarray(c.conv[:, 0]), np.asarray(c.conv[:, 1]), atol=TOL)


def test_a_chunk_at_position_zero_starts_from_zeros_whatever_the_lane_held(params):
    eng = BatchedEngine(CFG, params, lanes=2, max_len=64)
    ids = _ids(9, seed=8)
    first = _prefill(eng, 0, ids, 0, 16)
    _prefill(eng, 0, _ids(5, seed=9), 9, 16)  # the lane moves on
    again = _prefill(eng, 0, ids, 0, 16)  # a new session on the same lane
    np.testing.assert_array_equal(first, again)


@pytest.mark.parametrize("head_dim, turned", [(256, 64), (32, 8)])
def test_partial_rope_turns_the_first_dimensions_and_leaves_the_rest(head_dim, turned):
    cfg = dataclasses.replace(CFG, head_dim=head_dim)
    assert cfg.rope_dim == turned
    pos = jnp.arange(5)[None] + 3
    cos, sin = qwen3.rope_cos_sin(pos, cfg.rope_dim, cfg.rope_theta, cfg)
    assert cos.shape == (1, 5, turned)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, head_dim), jnp.float32)
    y = qwen3.apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(y[..., turned:]), np.asarray(x[..., turned:]))
    # the turned part: pair (d, d + turned / 2) by position / theta^(2d / turned)
    ang = np.asarray(pos, np.float64)[0, :, None] / cfg.rope_theta ** (np.arange(0, turned, 2) / turned)
    a, b = np.asarray(x[0, :, :, : turned // 2]), np.asarray(x[0, :, :, turned // 2: turned])
    c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
    np.testing.assert_allclose(np.asarray(y[0, ..., :turned]),
                               np.concatenate([a * c - b * s, b * c + a * s], -1), atol=1e-5)
    assert float(jnp.abs(y[..., :turned] - x[..., :turned]).max()) > 0.1


# ---------------------------------------------------------------------------
# one chip's share of the experts, in a layer of either stack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stack", ["layers", "state_layers"])
@pytest.mark.parametrize("held", [4, 8])
def test_the_shares_routed_parts_and_the_gated_shared_expert_once_add_up_to_the_whole_layer(
        params, stack, held):
    """The guide's share test: the routed parts of all the shares (four of 4
    experts, two of 8, of 16) plus what every chip computes alike (the gated
    shared expert), counted ONCE, are the uncut layer; every share chooses
    the same experts; a share alone is NOT the layer."""
    lp = jax.tree.map(lambda a: a[1], params[stack])
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 24, CFG.hidden_size), jnp.float32)
    xt = x.reshape(48, -1)
    whole, topi = qwen3.moe_mlp_routed(lp, CFG, x)
    shared = qwen3.swiglu_mlp({k: lp[f"shared_{k}"] for k in ("gate_proj", "up_proj", "down_proj")}, xt)
    shared = (shared * jax.nn.sigmoid(xt @ lp["shared_expert_gate"])[:, None]).reshape(x.shape)
    cut = lambda o: {k: (v[o: o + held] if k in ("gate_proj", "up_proj", "down_proj") else v)  # noqa: E731
                     for k, v in lp.items()}
    parts = []
    for offset in range(0, 16, held):
        part, chose = qwen3.moe_routed_part(cut(offset), CFG, xt, offset)
        np.testing.assert_array_equal(np.asarray(chose), np.asarray(topi).reshape(48, -1))
        parts.append(part.reshape(x.shape))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole), atol=TOL)
    assert float(jnp.abs(parts[0] + shared - whole).max()) > WRONG


def test_a_share_served_from_the_lanes_is_the_reference_over_the_held_experts(params, reference):
    """Experts 8..11 of 16 under the whole router, in both weight stacks:
    prefill, then decode through the executor, and the counters that say
    which part of the routing fell here, over all eight routers."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg = dataclasses.replace(CFG, name="tiny-q3n-share", num_experts=4, router_experts=16,
                              expert_offset=8)
    cut = lambda g: {k: (v[:, 8:12] if k in ("gate_proj", "up_proj", "down_proj") else v)  # noqa: E731
                     for k, v in g.items()}
    p = {**params, "layers": cut(params["layers"]), "state_layers": cut(params["state_layers"])}
    ids = _ids(21, seed=4)
    lp = reference.logprobs(p, ids, len(ids), published(cfg))
    ex = BatchedExecutor(cfg, p, lanes=2, max_len=64)
    out = ex.process("s", {"tokens": [ids[:16]], "start_pos": 0, "real_len": 16})
    np.testing.assert_allclose(_logp(out["logits"][0]), lp[15], atol=TOL)
    for t in range(16, 21):
        out = ex.process("s", {"tokens": [[ids[t]]], "start_pos": t, "real_len": 1})
        np.testing.assert_allclose(_logp(out["logits"][0]), lp[t], atol=TOL)
    stats = ex.stats()
    moe = stats["moe"]
    assert moe["experts"] == 16 and moe["experts_held"] == 4 and moe["steps"] == 5
    assert moe["assignments"] == 5 * CFG.num_layers * CFG.num_experts_per_tok  # eight routers a step
    assert 0 < moe["assignments_here"] < moe["assignments"]
    assert 0 < moe["experts_touched_here"] <= moe["experts_touched"]
    assert moe["rows_multiplied"] >= moe["assignments_here"]
    per_layer = 4 * 4 * 16 * 16 + 3 * (2 * 2 * 16 + 4 * 16) * 4  # f32 state; f32 columns at tiny
    assert stats["state_bytes_per_session"] == 6 * per_layer
    assert stats["state_bytes"] == 2 * 6 * per_layer and stats["kv_layout"] == "rows"
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 4  # two full layers, k and v, float32


# ---------------------------------------------------------------------------
# the presets, the cache's arithmetic, what is refused, the checkpoint
# ---------------------------------------------------------------------------


def test_the_served_preset_is_the_cut_of_the_published_one_and_its_cache_the_arithmetic():
    pub, cut = get_config("qwen3-next-80b-a3b"), get_config("qwen3-next-80b-ep4-8l")
    assert (pub.num_layers, pub.num_experts, pub.vocab_size) == (48, 512, 151936)
    assert (cut.num_layers, cut.num_experts, cut.router_width, cut.vocab_size) == (8, 128, 512, 37984)
    assert cut.layer_type_names == ["delta", "delta", "delta", "attention"] * 2
    assert cut.rope_dim == 64 and cut.full_attention_interval == 4
    widths = [f.name for f in dataclasses.fields(pub)
              if f.name not in ("name", "num_layers", "num_experts", "router_experts", "vocab_size")]
    assert all(getattr(pub, w) == getattr(cut, w) for w in widths)
    shapes = jax.eval_shape(lambda: qwen3.init_params(cut, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 3_667_251_328
    assert shapes["state_layers"]["router"].shape == (6, 2048, 512)
    assert shapes["state_layers"]["gate_proj"].shape == (6, 128, 2048, 512)
    assert shapes["state_layers"]["in_proj"].shape == (6, 2048, 12288)
    assert shapes["layers"]["q_proj"].shape == shapes["layers"]["attn_gate_proj"].shape == (2, 2048, 4096)
    c = jax.eval_shape(lambda: KVCache.create(cut, cut.num_layers, 16, 32768))
    # 2 kv heads of 256 are ONE row of 512 a token (core.cache.rows_layout: a head wider
    # than a tile among fewer heads than a tile has sublanes)
    assert c.k.shape == (2, 16, 32768, 512) and c.s.shape == (6, 16, 32, 128, 128)
    assert c.s.dtype == jnp.float32 and c.conv.shape == (6, 16, 3, 8192)
    assert c.state_bytes == 16 * 12_877_824 and c.nbytes == 2_353_528_832


def test_the_rows_rule_leaves_every_other_cells_layout_as_it_was():
    from inferd_tpu.core.cache import rows_layout

    assert rows_layout(get_config("qwen3-next-80b-ep4-8l"))  # 2 x 256
    assert rows_layout(get_config("granite-4.0-h-micro"))  # 8 x 64: narrower than a tile
    for held in ("qwen3-4b", "qwen3-8b", "sdar-30b-a3b-7l", "trinity-large-ep8-5l", "deepseek-v2-lite-8l"):
        assert not rows_layout(get_config(held)), held


@pytest.mark.parametrize("bad", [
    dict(layer_types=("delta", "mamba", "attention", "delta")), dict(linear_key_heads=3),
    dict(linear_value_head_dim=0), dict(sliding_window=8), dict(first_k_dense_replace=1),
    dict(num_layers=6)])
def test_a_config_that_contradicts_itself_is_refused(bad):
    with pytest.raises(ValueError, match="tiny-qwen3-next"):
        dataclasses.replace(CFG, **bad)


def test_a_state_layer_beside_experts_is_no_longer_refused():
    cfg = dataclasses.replace(get_config("tiny-granite-h"), name="tiny-granite-moe", num_layers=4,
                              num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32)
    p = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    assert "router" in p["state_layers"] and "router" in p["layers"]
    logits, _, _ = qwen3.forward(p, cfg, jnp.asarray([_ids(12)]))
    assert np.isfinite(np.asarray(logits)).all()


REFUSED = {
    "mesh": dict(mesh="pp=2"), "stage-lanes": dict(stage_lanes=2), "paged-kv": dict(paged_kv=16),
    "spec": dict(spec_draft_layers=1), "lora": dict(lora="x"),
    "adapters": dict(adapters="a"), "standby": dict(standby_repl=True), "no lanes": dict(batch_lanes=0),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_every_other_path_by_the_tables_it_had(path):
    """No table of this model's own: `has_state_layers`, `attn_gate` and
    `router_experts` select the refusals; --kv-dtype and --quant stay open."""
    from inferd_tpu.tools import run_node

    base = dict(mesh="", stage_lanes=0, paged_kv=0, quant="none", spec_draft_layers=0, lora="",
                adapters="", standby_repl=False, backend="qwen3", batch_lanes=16)
    cfg = get_config("qwen3-next-80b-ep4-8l")
    run_node.check_servable(cfg, argparse.Namespace(**base))  # the lane path, --kv-dtype open
    run_node.check_servable(cfg, argparse.Namespace(**{**base, "quant": "int8"}))
    with pytest.raises(SystemExit, match="qwen3-next-80b-ep4-8l cannot be served with"):
        run_node.check_servable(cfg, argparse.Namespace(**{**base, **REFUSED[path]}))
    with pytest.raises(SystemExit, match="several stages"):
        run_node.check_servable(cfg, argparse.Namespace(**base), num_stages=2)


def test_what_needs_a_snapshot_of_the_state_is_refused_below_too(params):
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    with pytest.raises(ValueError, match="tiny-qwen3-next"):
        extract_stage_params(params, CFG, Manifest.even_split("tiny-qwen3-next", 2).stage_spec(0))
    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)
    ex.process("a", {"tokens": [_ids(8)], "start_pos": 0, "real_len": 8})
    with pytest.raises(ValueError, match="recurrent state"):
        ex.process("a", {"tokens": [_ids(2)], "start_pos": 4, "real_len": 2})  # a replay
    with pytest.raises(ValueError, match="recurrent state"):
        ex.pin_prefix([1, 2, 3])


def test_quant_int8_reaches_both_stacks_the_experts_and_the_shared_expert(params):
    """The 8-bit control of `correct`: every projection of both weight
    stacks, the held experts and (since PR 48) the shared expert are
    quantized; router, `ba_proj` and the vectors are not. It is the same
    model at another precision, and a quantised expert weight takes the dense
    product (`routed_row_tile`)."""
    from inferd_tpu.ops import quant

    q = quant.apply_quant_mode("int8", params, tie_word_embeddings=False)
    try:
        for group in ("layers", "state_layers"):
            for name in ("gate_proj", "up_proj", "down_proj", "shared_gate_proj", "shared_up_proj",
                         "shared_down_proj"):
                assert isinstance(q[group][name], quant.QuantWeight), (group, name)
            assert not isinstance(q[group]["router"], quant.QuantWeight)
        assert isinstance(q["state_layers"]["in_proj"], quant.QuantWeight)
        assert not isinstance(q["state_layers"]["ba_proj"], quant.QuantWeight)
        ids = jnp.asarray([_ids(20, seed=6)])
        sound, _, _ = qwen3.forward(params, CFG, ids)
        got, _, _ = qwen3.forward(q, CFG, ids)
        assert 1e-4 < float(np.abs(_logp(got) - _logp(sound)).max()) < 0.1  # another precision, the same model
        assert qwen3.routed_row_tile(q["state_layers"]["gate_proj"], 2, 4, 16) == 0  # the dense product
        assert qwen3.routed_row_tile(params["state_layers"]["gate_proj"], 2, 4, 16) == 16
    finally:
        quant.QDOT_MODE = "dequant"


# ---------------------------------------------------------------------------
# the published names
# ---------------------------------------------------------------------------


def _hf_state_dict(params, cfg):
    """The tiny preset's weights under the names and in the layouts a
    `qwen3_next` checkpoint has them: [out, in], the fused projections
    interleaved by key-head group, q_proj by head as [query | gate]."""
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    hk, hv, dk, dv = (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
    r, kd, h = hv // hk, hk * dk, cfg.hidden_size
    sd = {"model.embed_tokens.weight": f(params["embed"]), "model.norm.weight": f(params["final_norm"]),
          "lm_head.weight": f(params["lm_head"]).T}
    seen = {"attention": 0, "delta": 0}
    for i, kind in enumerate(cfg.layer_type_names):
        stack = params["layers" if kind == "attention" else "state_layers"]
        p = {k: f(v[seen[kind]]) for k, v in stack.items()}
        seen[kind] += 1
        pre = f"model.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = p["input_norm"]
        sd[f"{pre}.post_attention_layernorm.weight"] = p["post_norm"]
        sd[f"{pre}.mlp.gate.weight"] = p["router"].T
        sd[f"{pre}.mlp.shared_expert_gate.weight"] = p["shared_expert_gate"][None]
        for proj in ("gate_proj", "up_proj", "down_proj"):
            for e in range(cfg.num_experts):
                sd[f"{pre}.mlp.experts.{e}.{proj}.weight"] = p[proj][e].T
            sd[f"{pre}.mlp.shared_expert.{proj}.weight"] = p[f"shared_{proj}"].T
        if kind == "attention":
            q = p["q_proj"].reshape(h, cfg.num_heads, 1, cfg.head_dim)
            g = p["attn_gate_proj"].reshape(h, cfg.num_heads, 1, cfg.head_dim)
            sd[f"{pre}.self_attn.q_proj.weight"] = np.concatenate([q, g], 2).reshape(h, -1).T
            for proj in ("k_proj", "v_proj", "o_proj"):
                sd[f"{pre}.self_attn.{proj}.weight"] = p[proj].T
            sd[f"{pre}.self_attn.q_norm.weight"] = p["q_norm"]
            sd[f"{pre}.self_attn.k_norm.weight"] = p["k_norm"]
            continue
        w = p["in_proj"]
        parts = [w[:, :kd].reshape(h, hk, dk), w[:, kd:2 * kd].reshape(h, hk, dk),
                 w[:, 2 * kd:2 * kd + hv * dv].reshape(h, hk, r * dv),
                 w[:, 2 * kd + hv * dv:].reshape(h, hk, r * dv)]
        sd[f"{pre}.linear_attn.in_proj_qkvz.weight"] = np.concatenate(parts, 2).reshape(h, -1).T
        ba = p["ba_proj"]
        sd[f"{pre}.linear_attn.in_proj_ba.weight"] = np.concatenate(
            [ba[:, :hv].reshape(h, hk, r), ba[:, hv:].reshape(h, hk, r)], 2).reshape(h, -1).T
        sd[f"{pre}.linear_attn.conv1d.weight"] = p["conv_w"].T[:, None, :]
        sd[f"{pre}.linear_attn.dt_bias"] = p["dt_bias"]
        sd[f"{pre}.linear_attn.A_log"] = p["A_log"]
        sd[f"{pre}.linear_attn.norm.weight"] = p["gate_norm"]
        sd[f"{pre}.linear_attn.out_proj.weight"] = p["out_proj"].T
    return sd


def test_loader_takes_the_published_layouts_apart_and_reads_a_share(params):
    from inferd_tpu.models.loader import params_from_hf_state_dict

    sd = _hf_state_dict(params, CFG)
    assert sd["model.layers.0.linear_attn.in_proj_qkvz.weight"].shape == (2 * 32 + 2 * 64, 64)
    assert sd["model.layers.3.self_attn.q_proj.weight"].shape == (2 * 4 * 32, 64)
    back = params_from_hf_state_dict(CFG, sd)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the interleaving is real: the fused rows are NOT the de-interleaved ones in order
    assert not np.array_equal(sd["model.layers.0.linear_attn.in_proj_qkvz.weight"].T,
                              np.asarray(params["state_layers"]["in_proj"][0]))
    share = dataclasses.replace(CFG, num_experts=4, router_experts=16, expert_offset=8, vocab_size=100)
    part = params_from_hf_state_dict(share, sd)
    np.testing.assert_array_equal(np.asarray(part["state_layers"]["up_proj"]),
                                  np.asarray(params["state_layers"]["up_proj"][:, 8:12]))
    assert part["layers"]["router"].shape == (2, 64, 16) and part["embed"].shape == (100, 64)
    assert part["lm_head"].shape == (64, 100)


def test_the_checkpoint_carries_both_stacks_with_their_experts(params, tmp_path):
    from inferd_tpu.parallel.stages import Manifest, load_stage_checkpoint, split_and_save

    paths = split_and_save(params, CFG, Manifest.even_split("tiny-qwen3-next", 1), str(tmp_path))
    back, _spec, name = load_stage_checkpoint(paths[0])
    assert name == "tiny-qwen3-next" and set(back) == set(params)
    for group in ("layers", "state_layers"):
        assert set(back[group]) == set(params[group])
        np.testing.assert_array_equal(np.asarray(back[group]["gate_proj"]),
                                      np.asarray(params[group]["gate_proj"]))
