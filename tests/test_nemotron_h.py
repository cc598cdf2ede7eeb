# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values
"""The Nemotron-H layers on the lane path at `tiny-nemotron-h`: seven layers
that are ONE sublayer each, M E M * E M E: Mamba-2 in four groups, a GQA layer
that rotates nothing, and sigmoid-routed experts (4 held of 16, top 4) that
work in a latent of 32 under a hidden state of 64 beside a full-width shared
expert, every feed-forward an ungated squared ReLU; three weight stacks by
kind of sublayer and one chip's SHARE of the experts. Seeded random weights,
float32 at `highest`. The float32 full forward the program is held to is the
benchmark's own plain reference (`benchmark/references/nemotron-h.py`: the
recurrence as a sequential scan over tokens, one forward pass, no cache,
independent of `models/qwen3.py`), loaded here by its file."""

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import get_config, pattern_kinds
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import KVCache, rows_layout
from inferd_tpu.models import qwen3

CFG = get_config("tiny-nemotron-h")
UNCUT = dataclasses.replace(CFG, name="tiny-nemotron-h-whole", num_experts=16, router_experts=0)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STACKS = ("layers", "state_layers", "ffn_layers")
# float32 both sides, matmuls at `highest`: the two differ by the order of a
# few hundred float32 additions (the chunked form solves a tile at once, the
# reference goes token by token), some 1e-6 on log-probabilities of size 5
TOL = 5e-6
WRONG = 1e-3  # a mistake in the mathematics moves the log-probabilities by far more


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _drawn(cfg, seed=7):
    """Drawn away from init's flat spots, where a mistake would hide: every
    norm gets a weight that is not one."""
    p = qwen3.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    for group in STACKS:
        g = dict(p[group])
        for i, name in enumerate(sorted(g)):
            if name.endswith("_norm"):
                g[name] = g[name] + 0.3 * jax.random.normal(jax.random.fold_in(key, i), g[name].shape)
        p[group] = g
    p["final_norm"] = p["final_norm"] + 0.3 * jax.random.normal(key, p["final_norm"].shape)
    return p


@pytest.fixture(scope="module")
def whole():
    """All 16 experts of every E layer."""
    return _drawn(UNCUT)


def _share(whole, offset, held=4):
    cut = {k: (v[:, offset: offset + held] if k in ("up_proj", "down_proj") else v)
           for k, v in whole["ffn_layers"].items()}
    return {**whole, "ffn_layers": cut}


@pytest.fixture(scope="module")
def params(whole):
    """Experts 0..3 of the 16, what `tiny-nemotron-h` holds."""
    return _share(whole, 0)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference", os.path.join(REPO, "benchmark", "references", "nemotron-h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg):
    """The keys the benchmark's reference reads, as the configuration's file names them."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "hybrid_override_pattern": cfg.hybrid_override_pattern,
        "layer_norm_epsilon": cfg.rms_norm_eps, "mamba_num_heads": cfg.mamba_heads,
        "mamba_head_dim": cfg.mamba_head_dim, "ssm_state_size": cfg.mamba_state,
        "n_groups": cfg.mamba_groups, "conv_kernel": cfg.mamba_conv,
        "n_routed_experts": cfg.num_experts, "router_experts": cfg.router_width,
        "expert_offset": cfg.expert_offset, "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling_factor,
        "moe_latent_size": cfg.moe_latent_size,
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
# the model against the plain reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("offset", [0, 8])
def test_the_cache_free_forward_is_the_reference(whole, reference, offset):
    cfg = dataclasses.replace(CFG, expert_offset=offset)
    p = _share(whole, offset)
    ids = _ids(24)
    logits, _, _ = qwen3.forward(p, cfg, jnp.asarray([ids]))
    np.testing.assert_allclose(
        _logp(logits[0]), reference.logprobs(p, ids, len(ids), published(cfg)), atol=TOL)


@pytest.mark.parametrize("second, bucket", [(7, 16), (16, 16), (3, 8)])
def test_the_lane_path_prefill_in_two_chunks_then_decode_is_the_reference(
        params, reference, second, bucket):
    """A prompt in two chunks (the second padded to its bucket: what must not
    move the state), then eight decode steps through the states and the slab
    on lane 1 of three, the others idle, against ONE forward pass."""
    ids = _ids(16 + second + 8, seed=second)
    lp = reference.logprobs(params, ids, len(ids), published(CFG))
    eng = BatchedEngine(CFG, params, lanes=3, max_len=64)
    _prefill(eng, 1, ids[:16])
    n = 16 + second
    got = _prefill(eng, 1, ids[16:n], 16, bucket)
    np.testing.assert_allclose(_logp(got).reshape(-1), lp[n - 1], atol=TOL)
    for t in range(n, n + 8):
        logits, routed = _decode(eng, [0, ids[t], 0], [0, t, 0], [False, True, False])
        np.testing.assert_allclose(_logp(logits[1]), lp[t], atol=TOL)
    assert np.asarray(routed).shape == (3, 3, 4)  # the three E layers' choices, no mixer's
    assert not np.asarray(eng.cache.s[:, 0]).any() and not np.asarray(eng.cache.s[:, 2]).any()


def test_a_share_served_by_the_executor_is_the_reference_and_its_counters_read_true(
        whole, reference):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg = dataclasses.replace(CFG, name="tiny-nem-share", expert_offset=8)
    p = _share(whole, 8)
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
    assert moe["experts"] == 16 and moe["experts_held"] == 4 and moe["latent_size"] == 32
    assert moe["steps"] == 5 and moe["assignments"] == 5 * 3 * 4  # three routers a step, top 4
    assert 0 < moe["assignments_here"] < moe["assignments"]
    assert moe["rows_multiplied"] >= moe["assignments_here"]
    per_layer = 8 * 16 * 16 * 4 + 3 * 256 * 4  # float32 state; three float32 columns at tiny
    assert stats["state_bytes_per_session"] == 3 * per_layer
    assert stats["kv_bytes_per_token"] == 2 * 2 * 16 * 4  # ONE attention layer, k and v, float32
    assert stats["kv_layout"] == "rows"
    with pytest.raises(ValueError, match="recurrent state"):
        ex.process("s", {"tokens": [_ids(2)], "start_pos": 4, "real_len": 2})  # a replay


# ---------------------------------------------------------------------------
# one chip's share of the experts, and the latent around them
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("held", [4, 8])
def test_the_shares_routed_parts_and_what_every_chip_computes_alike_add_up_to_the_whole_layer(
        whole, reference, layer, held):
    """The guide's share test: the parts of the routed result that all the
    shares give (four of 4 experts, two of 8, of the router's 16; each through
    the latent and back), plus the shared expert counted ONCE, are what the
    UNCUT reference gives for the whole layer; every share chooses the same
    experts; a share alone is NOT the layer."""
    lp = jax.tree.map(lambda a: a[layer], whole["ffn_layers"])
    lp["down_proj"] = lp["down_proj"] * 30.0  # as drawn the routed part is a hundredth of the shared
    h = jax.random.normal(jax.random.PRNGKey(11), (48, CFG.hidden_size), jnp.float32)
    want = reference.experts(h, lp, published(UNCUT))
    shared = qwen3.act_fn(CFG)(h @ lp["shared_up_proj"]) @ lp["shared_down_proj"]
    cut = lambda o: {k: (v[o: o + held] if k in ("up_proj", "down_proj") else v)  # noqa: E731
                     for k, v in lp.items()}
    parts, chosen = zip(*(qwen3.moe_routed_part(cut(o), UNCUT, h, o) for o in range(0, 16, held)))
    for chose in chosen[1:]:
        np.testing.assert_array_equal(np.asarray(chose), np.asarray(chosen[0]))
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(want), atol=TOL)
    assert float(jnp.abs(parts[0] + shared - want).max()) > WRONG
    # the program's own whole layer says the same
    got, _ = qwen3.moe_mlp_routed(lp, UNCUT, h[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("rows, tile", [(4, 16), (40, 0)])
def test_an_ungated_expert_multiplies_two_matrices_in_either_arm(whole, rows, tile):
    """The grouped product (a token meets the experts it chose) and the dense
    one (every row through every held expert) are one function; neither has
    a gate to multiply by."""
    lp = jax.tree.map(lambda a: a[1], whole["ffn_layers"])
    assert "gate_proj" not in lp and "shared_gate_proj" not in lp
    assert qwen3.expert_row_tile(rows, 4, 16) == tile
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, CFG.hidden_size), jnp.float32)
    got, topi = qwen3.moe_routed_part(lp, UNCUT, h)
    topw, _ = qwen3.route_topk(UNCUT, qwen3.router_logits(lp, UNCUT, h), lp["router_select_bias"])
    u = h @ lp["latent_in_proj"]
    every = jnp.einsum("tei,eil->tel", qwen3.act_fn(CFG)(jnp.einsum("tl,eli->tei", u, lp["up_proj"])),
                       lp["down_proj"])
    picked = jnp.take_along_axis(every, topi[..., None], axis=1)
    want = jnp.sum(picked * topw[..., None], axis=1) @ lp["latent_out_proj"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("rows, k, held, width, tile", [
    (32, 22, 128, 512, 16),  # the cell's decode step: 1.4 expected pairs a held expert
    (32, 22, 128, 0, 0),  # the same rows over a router no wider than the held: every expert met
    (512, 22, 128, 512, 128),  # its prefill chunk
    (16, 10, 128, 512, 16), (16, 4, 32, 256, 16),  # the two held shares' decode steps: as before
    (512, 10, 128, 512, 128), (512, 4, 32, 256, 128),  # and their chunks
    (64, 8, 128, 0, 0), (16, 6, 64, 0, 16),  # the whole routers of sdar and dsv2l / xing: as before
])
def test_the_row_tile_is_told_the_shares_expected_pairs(rows, k, held, width, tile):
    assert qwen3.expert_row_tile(rows, k, held, width) == tile
    counts = np.zeros(held, np.int64)
    counts[:3] = (5, 0, 20)
    want = rows * held if not tile else tile * (1 + (2 if tile == 16 else 1))
    assert qwen3.expert_rows_multiplied(counts, tile, rows) == want


# ---------------------------------------------------------------------------
# a layer is ONE sublayer: what its stack holds, and nothing in the other's place
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["mamba", "attention", "moe"])
def test_a_layer_computes_its_one_sublayer_and_no_product_of_the_other(params, kind):
    """A mixer's stack holds no feed-forward and the experts' stack no mixer,
    in the parameter tree and in the traced layer: the M before the * has no
    expert, MLP or latent product behind it (no zero weight stands in)."""
    stack = {"mamba": "state_layers", "attention": "layers", "moe": "ffn_layers"}[kind]
    lp = jax.tree.map(lambda a: a[0], params[stack])
    ffn = {"router", "up_proj", "down_proj", "shared_up_proj", "shared_down_proj",
           "latent_in_proj", "latent_out_proj", "post_norm"}
    mixer = {"in_proj", "out_proj", "conv_w", "q_proj", "k_proj", "v_proj", "o_proj", "input_norm"}
    assert not set(lp) & (mixer if kind == "moe" else ffn)
    assert "gate_proj" not in lp
    x = jnp.ones((2, 1, CFG.hidden_size), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda lp, x: qwen3.decoder_layer(lp, CFG, x, None, None, jnp.zeros((2, 1), jnp.int32))[0]
    )(lp, x)
    dots = [tuple(v.aval.shape for v in e.invars) for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "dot_general"]
    # the feed-forward's widths at this preset: experts of 48, a shared expert of 96
    wide = [s for s in dots if any(d in (48, 96) for shape in s for d in shape)]
    assert dots and bool(wide) == (kind == "moe")
    if kind == "mamba":  # a decode row: in and out, the recurrence elementwise
        assert len(dots) == 2
    out, entry, topi = qwen3.decoder_layer(lp, CFG, x, None, None, jnp.zeros((2, 1), jnp.int32))
    assert out.shape == x.shape and entry is None and (topi is not None) == (kind == "moe")


def test_the_three_stacks_are_the_pattern_by_kind_and_the_cache_holds_what_mixes():
    shapes = jax.eval_shape(lambda: qwen3.init_params(CFG, jax.random.PRNGKey(0)))
    assert {g: jax.tree.leaves(shapes[g])[0].shape[0] for g in STACKS} == {
        "layers": 1, "state_layers": 3, "ffn_layers": 3}
    assert CFG.sublayer_counts == {"mamba": 3, "moe": 3, "attention": 1}
    c = KVCache.create(CFG, CFG.num_layers, 2, 64)
    assert c.k.shape == (1, 2, 64, 32) and c.s.shape == (3, 2, 8, 16, 16) and c.conv.shape == (3, 2, 3, 256)
    state, nothing, rows = c.entries(CFG)  # in the order the pattern first meets the kinds
    assert nothing is None and state.s is c.s and rows.k is c.k
    assert c.with_entries((state, None, rows)).s is c.s


# ---------------------------------------------------------------------------
# Mamba-2 with several groups
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("groups, chunk", [(8, 16), (8, 1), (2, 24)])
def test_mamba2_in_groups_equals_a_loop_over_its_heads(groups, chunk):
    """16 heads of 8 in `groups` groups (B and C shared by a group's heads, the
    gated norm over each group's channels), a chunk entered with a state that
    is not zero (chunk 1: the decode row; 24: one tile its tile of 8 divides;
    16: two), against the recurrence a head and a token at a time in float64."""
    cfg = dataclasses.replace(CFG, mamba_heads=16, mamba_head_dim=8, mamba_groups=groups)
    lp = jax.tree.map(lambda a: a[0], qwen3.init_state_layer_params(cfg, jax.random.PRNGKey(2), 1))
    lp["gate_norm"] = lp["gate_norm"] + 0.3 * jax.random.normal(jax.random.PRNGKey(3), lp["gate_norm"].shape)
    heads, p, n, k = 16, 8, cfg.mamba_state, cfg.mamba_conv
    di, cd = heads * p, cfg.mamba_conv_dim
    x = jax.random.normal(jax.random.PRNGKey(4), (1, chunk, cfg.hidden_size), jnp.float32)
    cache = KVCache.create(cfg, cfg.num_layers, 1, 64)
    s0 = jax.random.normal(jax.random.PRNGKey(5), (1, heads, p, n), jnp.float32)
    kept0 = jax.random.normal(jax.random.PRNGKey(6), (1, k - 1, cd), jnp.float32)
    entry = cache.entries(cfg)[0]
    entry = dataclasses.replace(entry, s=entry.s.at[0].set(s0), conv=entry.conv.at[0].set(kept0))
    out, new = qwen3.mamba_mixer(lp, cfg, x, entry, 0, KVCache.ctx(jnp.asarray([5])))
    # the loop, float64
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    proj = f(x[0]) @ f(lp["in_proj"])
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    full = np.concatenate([f(kept0[0]), xbc])
    conv = sum(full[i:i + chunk] * f(lp["conv_w"])[i] for i in range(k)) + f(lp["conv_b"])
    xbc = conv / (1 + np.exp(-conv))
    step = np.log1p(np.exp(dt + f(lp["dt_bias"])))
    a = -np.exp(f(lp["A_log"]))
    y, state = np.zeros((chunk, di)), f(s0[0]).copy()
    for h in range(heads):
        g = h // (heads // groups)
        xs = xbc[:, h * p:(h + 1) * p]
        bm, cm = xbc[:, di + g * n: di + (g + 1) * n], xbc[:, di + (groups + g) * n: di + (groups + g + 1) * n]
        for t in range(chunk):
            state[h] = np.exp(step[t, h] * a[h]) * state[h] + step[t, h] * np.outer(xs[t], bm[t])
            y[t, h * p:(h + 1) * p] = state[h] @ cm[t] + f(lp["D"])[h] * xs[t]
    y = (y * z / (1 + np.exp(-z))).reshape(chunk, groups, di // groups)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    want = (y.reshape(chunk, di) * f(lp["gate_norm"])) @ f(lp["out_proj"])
    np.testing.assert_allclose(np.asarray(out[0]), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new.s[0, 0]), state, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new.conv[0, 0]), full[chunk:], atol=1e-6)


# ---------------------------------------------------------------------------
# the presets, the cache's arithmetic, what is refused, the checkpoint
# ---------------------------------------------------------------------------


def test_the_served_preset_is_the_cut_of_the_published_one_and_its_cache_the_arithmetic():
    pub, cut = get_config("nemotron-3-super-120b-a12b"), get_config("nemotron-3-super-120b-ep4-11l")
    assert (pub.num_layers, pub.num_experts, pub.vocab_size) == (88, 512, 131072)
    assert pub.sublayer_counts == {"mamba": 40, "moe": 40, "attention": 8}
    assert (cut.num_layers, cut.num_experts, cut.router_width, cut.vocab_size) == (11, 128, 512, 32768)
    assert cut.hybrid_override_pattern == "MEMEMEM*EME" == pub.hybrid_override_pattern[:11]
    assert cut.sublayer_counts == {"mamba": 5, "moe": 5, "attention": 1}  # the published 40 : 40 : 8
    widths = [f.name for f in dataclasses.fields(pub) if f.name not in (
        "name", "num_layers", "layer_types", "num_experts", "router_experts", "vocab_size")]
    assert all(getattr(pub, w) == getattr(cut, w) for w in widths)
    shapes = jax.eval_shape(lambda: qwen3.init_params(cut, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 4_648_163_712
    assert shapes["ffn_layers"]["up_proj"].shape == (5, 128, 1024, 2688)
    assert shapes["ffn_layers"]["down_proj"].shape == (5, 128, 2688, 1024)
    assert shapes["ffn_layers"]["router"].shape == (5, 4096, 512)
    assert shapes["ffn_layers"]["shared_up_proj"].shape == (5, 4096, 5376)
    assert shapes["ffn_layers"]["latent_in_proj"].shape == (5, 4096, 1024)
    assert shapes["state_layers"]["in_proj"].shape == (5, 4096, 18560)
    assert shapes["layers"]["k_proj"].shape == (1, 4096, 256)
    c = jax.eval_shape(lambda: KVCache.create(cut, cut.num_layers, 32, 4096))
    # 2 kv heads of 128 are ONE row of 256 a token (core.cache.rows_layout)
    assert c.k.shape == (1, 32, 4096, 256) and c.s.shape == (5, 32, 128, 64, 128)
    assert c.s.dtype == jnp.float32 and c.conv.shape == (5, 32, 3, 10240)
    assert c.state_bytes == 32 * 21_278_720 and c.nbytes - c.state_bytes == 32 * 4096 * 1024


def test_the_rows_rule_leaves_every_held_cells_layout_as_it_was():
    assert rows_layout(get_config("nemotron-3-super-120b-ep4-11l"))  # 2 x 128 beside state layers
    for name, rows in (("granite-4.0-h-micro", True), ("qwen3-next-80b-ep4-8l", True),
                       ("olmo-hybrid-7b-16l", True), ("qwen3-4b", False), ("sdar-30b-a3b-7l", False),
                       ("trinity-large-ep8-5l", False), ("qwen2-1.5b", False)):
        assert rows_layout(get_config(name)) == rows, name


def test_the_files_published_widths_are_the_presets():
    """`preset_check` of the configuration's file over every published width,
    as benchmark/run.py makes it before it serves anything."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    try:
        import run as harness
    finally:
        sys.path.pop(0)
    path = os.path.join(REPO, "benchmark", "configs", "nemotron-3-super-120b-ep4-1chip.json")
    with open(path) as f:
        config = json.load(f)
    harness.check_preset(config, config["reduced"], get_config(config["preset"]))
    for width in ("hidden_size", "moe_intermediate_size", "moe_latent_size", "head_dim",
                  "moe_shared_expert_intermediate_size", "mamba_head_dim", "ssm_state_size",
                  "mamba_num_heads", "n_groups", "num_experts_per_tok", "num_attention_heads",
                  "num_key_value_heads", "routed_scaling_factor"):
        assert width in config["preset_check"], width
    assert config["published"]["hybrid_override_pattern"][:11] == config["hybrid_override_pattern"]


@pytest.mark.parametrize("bad, said", [
    (dict(layer_types=pattern_kinds("EMM*EME")), "a mixer first"),
    (dict(norm_placement="after"), "one sublayer each"),
    (dict(hc_mult=4), "one sublayer each"),
    (dict(num_experts=0, router_experts=0), "one sublayer each"),
    (dict(num_layers=6), "divides num_layers"),
    (dict(hidden_act="relu"), "unknown hidden_act"),
    (dict(shared_expert_gate=True), "ungated"),
    (dict(mamba_groups=3), "whole groups"),
])
def test_a_config_that_contradicts_itself_is_refused_in_words(bad, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CFG, **bad)


def test_a_pattern_with_a_kind_that_does_not_run_is_refused_in_words():
    with pytest.raises(ValueError, match="not runnable"):
        pattern_kinds("ME-M*")
    with pytest.raises(ValueError, match="latent"):
        dataclasses.replace(get_config("tiny"), moe_latent_size=32)


REFUSED = {
    "mesh": (dict(mesh="pp=2"), "stacks of single sublayers are not sharded"),
    "stage-lanes": (dict(stage_lanes=2), "not a stack a kind of sublayer"),
    "paged-kv": (dict(paged_kv=16), "one sublayer in eleven"),
    "spec": (dict(spec_draft_layers=1), "first layers of ONE stack"),
    "lora": (dict(lora="x"), "--lora"), "adapters": (dict(adapters="a"), "--adapters"),
    "standby": (dict(standby_repl=True), "--standby-repl"),
    "no lanes": (dict(batch_lanes=0), "without --batch-lanes"),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_every_other_path_in_words(path):
    """What cuts a model between layers counts a layer as a mixer and its
    feed-forward: refused by `single_sublayer` with that reason; the rest by
    the tables `has_state_layers` and `router_experts` had; --kv-dtype and
    --quant stay open."""
    from inferd_tpu.tools import run_node

    base = dict(mesh="", stage_lanes=0, paged_kv=0, quant="none", spec_draft_layers=0, lora="",
                adapters="", standby_repl=False, backend="qwen3", batch_lanes=32)
    cfg = get_config("nemotron-3-super-120b-ep4-11l")
    run_node.check_servable(cfg, argparse.Namespace(**base))  # the lane path
    run_node.check_servable(cfg, argparse.Namespace(**{**base, "quant": "int8"}))
    change, said = REFUSED[path]
    with pytest.raises(SystemExit, match="nemotron-3-super-120b-ep4-11l cannot be served with") as e:
        run_node.check_servable(cfg, argparse.Namespace(**{**base, **change}))
    assert said in str(e.value)
    with pytest.raises(SystemExit, match="three stacks of sublayers are kept whole"):
        run_node.check_servable(cfg, argparse.Namespace(**base), num_stages=2)


def test_a_stage_slice_of_the_three_stacks_is_refused_below_too(params):
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params

    with pytest.raises(ValueError, match="ffn_layers"):
        extract_stage_params(params, CFG, Manifest.even_split("tiny-nemotron-h", 2).stage_spec(0))
    one = extract_stage_params(params, CFG, Manifest.even_split("tiny-nemotron-h", 1).stage_spec(0))
    assert set(STACKS) <= set(one)


def test_quant_int8_reaches_the_three_stacks_the_experts_and_the_latent(params):
    """The 8-bit control of `correct`: every projection of the three stacks,
    the held experts, the shared expert and both latent projections; the
    router and the vectors are not. The same model at another precision; a
    quantised expert weight takes the dense product."""
    from inferd_tpu.ops import quant

    q = quant.apply_quant_mode("int8", params, tie_word_embeddings=False)
    try:
        for name in ("up_proj", "down_proj", "shared_up_proj", "shared_down_proj",
                     "latent_in_proj", "latent_out_proj"):
            assert isinstance(q["ffn_layers"][name], quant.QuantWeight), name
        assert not isinstance(q["ffn_layers"]["router"], quant.QuantWeight)
        assert isinstance(q["state_layers"]["in_proj"], quant.QuantWeight)
        assert isinstance(q["layers"]["q_proj"], quant.QuantWeight)
        ids = jnp.asarray([_ids(20, seed=6)])
        sound, _, _ = qwen3.forward(params, CFG, ids)
        got, _, _ = qwen3.forward(q, CFG, ids)
        assert 1e-4 < float(np.abs(_logp(got) - _logp(sound)).max()) < 0.1
        assert qwen3.routed_row_tile(qwen3.routed_weight(q), 2, 4, 4, 16) == 0  # the dense product
        assert qwen3.routed_row_tile(qwen3.routed_weight(params), 2, 4, 4, 16) == 16
    finally:
        quant.QDOT_MODE = "dequant"


# ---------------------------------------------------------------------------
# the published names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("offset", [0, 8])
def test_loader_maps_the_published_names_and_takes_the_share(whole, offset):
    """A synthetic `nemotron_h` state dict of the WHOLE model (16 experts, 256
    rows of vocabulary) read by a preset that holds experts offset..offset+4
    and the first 192 rows: one norm and one mixer a published layer."""
    from inferd_tpu.models.loader import params_from_hf_state_dict

    cfg = dataclasses.replace(CFG, expert_offset=offset, vocab_size=192)
    host = jax.tree.map(np.asarray, whole)
    sd = {"backbone.embeddings.weight": host["embed"], "backbone.norm_f.weight": host["final_norm"],
          "lm_head.weight": host["lm_head"].T}
    seen = dict.fromkeys(STACKS, 0)
    of_kind = {"attention": "layers", "mamba": "state_layers", "moe": "ffn_layers"}
    for i, kind in enumerate(cfg.layer_type_names):
        lp = {k: v[seen[of_kind[kind]]] for k, v in host[of_kind[kind]].items()}
        seen[of_kind[kind]] += 1
        pre = f"backbone.layers.{i}"
        sd[f"{pre}.norm.weight"] = lp["post_norm" if kind == "moe" else "input_norm"]
        if kind == "attention":
            for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
                sd[f"{pre}.mixer.{proj}.weight"] = lp[proj].T
        elif kind == "mamba":
            sd[f"{pre}.mixer.in_proj.weight"] = lp["in_proj"].T
            sd[f"{pre}.mixer.out_proj.weight"] = lp["out_proj"].T
            sd[f"{pre}.mixer.conv1d.weight"] = lp["conv_w"].T[:, None, :]  # [channels, 1, K]
            sd[f"{pre}.mixer.conv1d.bias"] = lp["conv_b"]
            sd[f"{pre}.mixer.norm.weight"] = lp["gate_norm"]
            for name in ("dt_bias", "A_log", "D"):
                sd[f"{pre}.mixer.{name}"] = lp[name]
        else:
            sd[f"{pre}.mixer.gate.weight"] = lp["router"].T
            sd[f"{pre}.mixer.gate.e_score_correction_bias"] = lp["router_select_bias"]
            for e in range(16):
                sd[f"{pre}.mixer.experts.{e}.up_proj.weight"] = lp["up_proj"][e].T
                sd[f"{pre}.mixer.experts.{e}.down_proj.weight"] = lp["down_proj"][e].T
            sd[f"{pre}.mixer.shared_experts.up_proj.weight"] = lp["shared_up_proj"].T
            sd[f"{pre}.mixer.shared_experts.down_proj.weight"] = lp["shared_down_proj"].T
            sd[f"{pre}.mixer.fc1_latent_proj.weight"] = lp["latent_in_proj"].T
            sd[f"{pre}.mixer.fc2_latent_proj.weight"] = lp["latent_out_proj"].T
    loaded = params_from_hf_state_dict(cfg, sd)
    want = _share(whole, offset)
    want = {**want, "embed": want["embed"][:192], "lm_head": want["lm_head"][:, :192]}
    assert jax.tree.structure(loaded) == jax.tree.structure(want)
    for got, exp in zip(jax.tree.leaves(loaded), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    assert loaded["ffn_layers"]["router_select_bias"].dtype == jnp.float32
