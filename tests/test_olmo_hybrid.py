# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values
"""The Olmo-Hybrid layer on the lane path at `tiny-olmo-hybrid`: two periods of
three Gated-DeltaNet layers (keys of 96, values of 192, the PUBLISHED head
sizes: what is off the tile is the point; beta in (0, 2)) to one full-attention
layer without rope whose q/k norm runs over the whole projection, a dense MLP
in every layer, and NO norm on a sublayer's input: one on its output. Seeded
random weights, float32 at `highest`. The float32 full forward the program is
held to is the benchmark's own plain reference
(`benchmark/references/olmo-hybrid.py`: the delta rule as a sequential scan
over tokens, one forward pass, no cache, independent of `models/qwen3.py`),
loaded here by its file. One engine serves every test that needs lanes."""

import argparse
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import get_config
from inferd_tpu.core import cache as cachelib
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import KVCache, RowEntry, StateEntry
from inferd_tpu.models import qwen3

CFG = get_config("tiny-olmo-hybrid")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 both sides, matmuls at `highest`: the two differ by the order of a
# few hundred float32 additions (the chunked form solves a tile at once, a
# decode row reads S^T q before the update where the reference reads it after),
# some 1e-5 on log-probabilities of size 5
TOL = 3e-5
WRONG = 1e-3  # a mistake in the mathematics moves the log-probabilities by far more
LANES = 3


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    """Drawn away from init's flat spots, where a mistake would hide: the
    norms get a weight that is not 1, b is wide enough that beta = 2
    sigmoid(b) reaches both ends of (0, 2), the values are wider."""
    p = qwen3.init_params(CFG, jax.random.PRNGKey(7))
    key = jax.random.PRNGKey(8)
    for group in ("layers", "state_layers"):
        g = dict(p[group])
        for i, name in enumerate(sorted(g)):
            if name.endswith("_norm"):
                g[name] = g[name] + 0.3 * jax.random.normal(jax.random.fold_in(key, i), g[name].shape)
        for name in ("v_proj", "o_proj", "ba_proj"):
            if name in g:
                g[name] = g[name] * 6.0
        p[group] = g
    p["final_norm"] = p["final_norm"] + 0.3 * jax.random.normal(key, p["final_norm"].shape)
    return p


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "olmo_hybrid_reference", os.path.join(REPO, "benchmark", "references", "olmo-hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg):
    """The keys the benchmark's reference reads, as the configuration's file names them."""
    names = {"attention": "full_attention", "delta": "linear_attention"}
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.rms_norm_eps,
        "layer_types": [names[k] for k in cfg.layer_type_names] * 2,  # the file lists more than are served
        "layer_kinds": cfg.layer_type_names,
        "linear_num_key_heads": cfg.linear_key_heads, "linear_num_value_heads": cfg.linear_value_heads,
        "linear_key_head_dim": cfg.linear_key_head_dim,
        "linear_value_head_dim": cfg.linear_value_head_dim, "linear_conv_kernel_dim": cfg.linear_conv,
        "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval,
        "tie_word_embeddings": False, "hidden_act": "silu", "attention_bias": False,
        "rope_parameters": {"rope_theta": None},
    }


def _ids(n, seed=3):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))


@pytest.fixture(scope="module")
def eng(params):
    """ONE engine of three lanes for the module: its two programs compile
    once a bucket; a test starts its sessions at position 0, which is how a
    lane forgets what it held."""
    return BatchedEngine(CFG, params, lanes=LANES, max_len=64)


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
    eng.cache, logits, _routed = eng._decode_logits(
        eng.params, eng.cache, np.asarray(toks, np.int32), np.asarray(lens, np.int32),
        active=np.asarray(active, bool))
    return np.asarray(logits)


# ---------------------------------------------------------------------------
# the recurrence at beta up to 2: two forms of one function, and a third
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


def _draw(rng, b, s, h, dk, dv, beta_lo, beta_hi):
    unit = lambda a: a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(rng.normal(size=(b, s, h, dk))).astype(np.float32) / np.sqrt(dk).astype(np.float32)
    k = unit(rng.normal(size=(b, s, h, dk))).astype(np.float32)
    v = rng.normal(size=(b, s, h, dv)).astype(np.float32)
    g = -rng.uniform(0.01, 2.0, size=(b, s, h)).astype(np.float32)
    beta = rng.uniform(beta_lo, beta_hi, size=(b, s, h)).astype(np.float32)
    s0 = rng.normal(size=(b, h, dk, dv)).astype(np.float32)
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("tile", [8, 24])
def test_the_chunked_form_equals_the_recurrence_at_beta_near_two(tile):
    """beta in (1.8, 1.999): I - beta k k^T turns a key's direction nearly
    round, and the tile's unit-triangular system has off-diagonal entries up
    to 2 where beta under 1 keeps them under 1. 24 positions entered with a
    state that is not zero, in three tiles and in one, the last third of one
    row padding (g = 0, beta = 0): float32 rounding of a float64 recurrence,
    no more (the slow decays, g from -0.01, are where it would grow)."""
    rng = np.random.default_rng(0)
    q, k, v, g, beta, s0 = _draw(rng, 2, 24, 3, 12, 24, 1.8, 1.999)
    g[1, 16:], beta[1, 16:] = 0.0, 0.0
    want, state = _sequential(q, k, v, g, beta, s0)
    got, s_out = qwen3.gated_delta_chunked(*map(jnp.asarray, (q, k, v, g, beta, s0)), tile=tile)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)
    np.testing.assert_allclose(np.asarray(s_out), state, atol=5e-5)
    _, at16 = _sequential(q[1:, :16], k[1:, :16], v[1:, :16], g[1:, :16], beta[1:, :16], s0[1:])
    np.testing.assert_allclose(np.asarray(s_out)[1], at16[0], atol=5e-5)  # the padding moved nothing


@pytest.mark.parametrize("fold", [1, 2, 4])
def test_the_update_over_heads_held_side_by_side_is_the_update_as_written(fold):
    """One token over a state held with `fold` heads beside each other
    (core.cache.state_held_shape) against the recurrence over [H, Dk, Dv]:
    the same o, and the same state laid head by head."""
    rng = np.random.default_rng(2)
    b, h, dk, dv = 2, 4, 6, 10
    q, k, v, g, beta, s0 = _draw(rng, b, 1, h, dk, dv, 0.05, 1.95)
    want, state = _sequential(q, k, v, g, beta, s0)
    held = cachelib.state_heads_beside(s0, fold)
    o, s_new = qwen3._delta_update_folded(
        *(jnp.asarray(a[:, 0]) for a in (q, k, v, g, beta)), jnp.asarray(held), fold)
    np.testing.assert_allclose(np.asarray(o), want[:, 0], atol=1e-5)
    np.testing.assert_allclose(cachelib.state_heads_apart(np.asarray(s_new), fold), state, atol=1e-5)
    assert held.shape == (b, h // fold, dk, fold * dv)
    for i in range(fold):  # head g * fold + i lies in columns [i * Dv, (i + 1) * Dv) of row-block g
        np.testing.assert_array_equal(held[:, :, :, i * dv:(i + 1) * dv], s0[:, i::fold])


def test_the_state_is_held_unpadded_and_counted_as_held():
    """Values of 192 are one and a half lane tiles: two heads side by side
    are three. The state cache holds [heads / 2, 96, 384]; the counter says
    the buffers' bytes; every other state model holds what it computes."""
    assert cachelib.state_fold(CFG) == 2 and cachelib.state_held_shape(CFG) == (2, 96, 384)
    full = get_config("olmo-hybrid-7b-16l")
    assert cachelib.state_held_shape(full) == (15, 96, 384) and full.state_shape == (30, 96, 192)
    for other in ("qwen3-next-80b-ep4-8l", "granite-4.0-h-micro", "tiny-qwen3-next", "tiny-granite-h"):
        cfg = get_config(other)
        assert cachelib.state_fold(cfg) == 1 and cachelib.state_held_shape(cfg) == cfg.state_shape
    odd = dataclasses.replace(full, linear_key_heads=15, linear_value_heads=15)
    assert cachelib.state_fold(odd) == 1  # heads that do not pair stay as they are computed


# ---------------------------------------------------------------------------
# the model on the lanes against one pass of the reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def want(params, reference):
    ids = _ids(37)
    return ids, reference.logprobs(params, ids, len(ids), published(CFG))  # row t: after token t


def test_prefill_in_two_chunks_then_eight_decode_steps_equal_one_pass_of_the_reference(
        eng, params, want):
    """20 tokens padded to 32 (tiles of 8 divide it), then 9 padded to a
    bucket of 12 (they do not: one tile), then 8 tokens one at a time through
    the state as it is held and the rows, on lane 1 of 3 with lanes 0 and 2
    idle; and the cache-free forward is the same function. Logits, not
    tokens."""
    ids, lp = want
    assert isinstance(eng.cache.entries(CFG)[0], StateEntry)
    assert isinstance(eng.cache.entries(CFG)[1], RowEntry)
    assert eng.cache.s.shape == (6, LANES, 2, 96, 384)
    np.testing.assert_allclose(_logp(_prefill(eng, 1, ids[:20], 0, 32)), lp[19], atol=TOL)
    np.testing.assert_allclose(_logp(_prefill(eng, 1, ids[20:29], 20, 12)), lp[28], atol=TOL)
    for t in range(29, 37):
        got = _decode(eng, [0, ids[t], 0], [0, t, 0], [False, True, False])
        np.testing.assert_allclose(_logp(got[1]), lp[t], atol=TOL)
    full, _, _ = qwen3.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(_logp(full[0]), lp, atol=TOL)


def test_ragged_lanes_a_masked_row_and_position_zero(eng, params, reference):
    """Three sessions of 7, 8 and 9 tokens decode together at their own
    depths and each equals its own pass of the reference; a step that lane 1
    sits out leaves its state, columns and rows bit for bit while the others
    move; a new session on a used lane starts from zeros."""
    seqs = [_ids(12 + lane, seed=10 + lane) for lane in range(LANES)]
    lps = [reference.logprobs(params, s, len(s), published(CFG)) for s in seqs]
    first = [_prefill(eng, lane, seqs[lane][: 7 + lane], 0, 16) for lane in range(LANES)]
    for lane in range(LANES):
        np.testing.assert_allclose(_logp(first[lane]), lps[lane][6 + lane], atol=TOL)
    lens = [7, 8, 9]
    got = _decode(eng, [s[n] for s, n in zip(seqs, lens)], lens, [True] * LANES)
    for lane in range(LANES):
        np.testing.assert_allclose(_logp(got[lane]), lps[lane][lens[lane]], atol=TOL)
    lens = [8, 9, 10]
    before = jax.tree.map(np.asarray, eng.cache)
    assert float(np.abs(before.s).max()) > 1e-3  # a state there is
    got = _decode(eng, [s[n] for s, n in zip(seqs, lens)], lens, [True, False, True])
    after = jax.tree.map(np.asarray, eng.cache)
    for name in ("s", "conv", "k", "v"):
        old, new = getattr(before, name), getattr(after, name)
        np.testing.assert_array_equal(new[:, 1], old[:, 1])
        assert not np.array_equal(new[:, 0], old[:, 0]) and not np.array_equal(new[:, 2], old[:, 2])
    np.testing.assert_allclose(_logp(got[2]), lps[2][10], atol=TOL)
    again = _prefill(eng, 0, seqs[0][:7], 0, 16)  # a new session where another was
    np.testing.assert_array_equal(again, first[0])


def _norms_left_out(monkeypatch):
    """The program with a sublayer's OUTPUT norm left out: rms_norm handed
    one of a layer's two output norms gives its input back."""
    real_layer, real_norm, skip = qwen3.decoder_layer, qwen3.rms_norm, []

    def layer(lp, *a, **kw):
        skip[:] = [lp["post_norm"], lp["post_ffn_norm"]]
        return real_layer(lp, *a, **kw)

    def norm(x, weight, *a, **kw):
        return x if any(weight is w for w in skip) else real_norm(x, weight, *a, **kw)

    monkeypatch.setattr(qwen3, "decoder_layer", layer)
    monkeypatch.setattr(qwen3, "rms_norm", norm)


@pytest.mark.parametrize("mistake", ["beta_not_doubled", "pre_norm", "no_output_norm",
                                     "per_head_qk_norm", "rope_applied", "state_swapped"])
def test_each_mistake_in_the_mathematics_fails_parity(eng, params, want, mistake, monkeypatch):
    """What the tolerance is worth: the program with ONE term of the
    equations wrong is far outside it."""
    ids, lp = want
    cfg, p = CFG, params
    if mistake == "state_swapped":  # the state a chunk left, read with Dk and Dv swapped
        _prefill(eng, 1, ids[:20], 0, 32)
        heads = cachelib.state_heads_apart(np.asarray(eng.cache.s), 2)  # [L, B, 4, 96, 192]
        swapped = np.swapaxes(heads, -1, -2).reshape(heads.shape)
        eng.cache = dataclasses.replace(
            eng.cache, s=jnp.asarray(cachelib.state_heads_beside(swapped, 2)))
        got = _decode(eng, [0, ids[20], 0], [0, 20, 0], [False, True, False])
        assert np.abs(_logp(got[1]) - lp[20]).max() > WRONG
        return
    if mistake == "beta_not_doubled":
        cfg = dataclasses.replace(CFG, linear_allow_neg_eigval=False)
    elif mistake == "pre_norm":  # the same weights norming each sublayer's INPUT
        cfg = dataclasses.replace(CFG, norm_placement="before")
        p = {**params, **{g: {**{k: v for k, v in params[g].items() if k != "post_ffn_norm"},
                              "input_norm": params[g]["post_norm"],
                              "post_norm": params[g]["post_ffn_norm"]}
                          for g in ("layers", "state_layers")}}
    elif mistake == "no_output_norm":
        _norms_left_out(monkeypatch)
    elif mistake == "per_head_qk_norm":  # Qwen3's norm of head_dim, each head
        cfg = dataclasses.replace(CFG, qk_norm_flat=False)
        att = params["layers"]
        p = {**params, "layers": {**att, "q_norm": att["q_norm"][:, : CFG.head_dim],
                                  "k_norm": att["k_norm"][:, : CFG.head_dim]}}
    elif mistake == "rope_applied":
        cfg = dataclasses.replace(CFG, position_embedding="rope")
    full, _, _ = qwen3.forward(p, cfg, jnp.asarray([ids]))
    assert np.abs(_logp(full[0]) - lp).max() > WRONG


# ---------------------------------------------------------------------------
# the preset, the rules on shapes, what is refused
# ---------------------------------------------------------------------------


def test_the_served_preset_is_the_cut_of_the_published_one_and_its_cache_the_arithmetic():
    """`olmo-hybrid-7b-16l` is layers 0-15 of the published 32 at every
    width; its parameters and its cache at 16 lanes x 4096 are the
    configuration's `deployment` (shapes only: nothing is allocated)."""
    whole, cut = get_config("olmo-hybrid-7b"), get_config("olmo-hybrid-7b-16l")
    assert dataclasses.replace(cut, name=whole.name, num_layers=32) == whole
    assert cut.layer_type_names == ["delta", "delta", "delta", "attention"] * 4
    assert (cut.norm_placement, cut.qk_norm_kind, cut.position_embedding) == ("after", "flat", "nope")
    shapes = jax.eval_shape(lambda: qwen3.init_params(cut, jax.random.PRNGKey(0)))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["state_layers"]) == 12 * 215_570_172
    assert count(shapes["layers"]) == 4 * 185_809_920
    assert count(shapes) == 4_100_788_944
    assert "input_norm" not in shapes["layers"] and "input_norm" not in shapes["state_layers"]
    assert shapes["layers"]["q_norm"].shape == (4, 3840)
    cache = jax.eval_shape(lambda: KVCache.create(cut, cut.num_layers, 16, 4096))
    assert cache.k.shape == (4, 16, 4096, 3840) and cache.s.shape == (12, 16, 15, 96, 384)
    assert cache.state_bytes == 16 * 27_371_520 and cache.nbytes == 4_464_476_160


def test_the_rows_rule_takes_thirty_heads_of_a_tile_and_leaves_every_held_cell_as_it_was():
    from inferd_tpu.core.cache import rows_layout

    assert rows_layout(get_config("olmo-hybrid-7b-16l"))  # 30 x 128: the head axis would pad to 32
    assert rows_layout(get_config("qwen3-next-80b-ep4-8l")) and rows_layout(get_config("granite-4.0-h-micro"))
    for held in ("qwen3-4b", "qwen3-8b", "sdar-30b-a3b-7l", "trinity-large-ep8-5l", "deepseek-v2-lite-8l",
                 "llama3.1-8b", "qwen2-7b", "gemma2-27b", "qwen3-32b"):
        assert not rows_layout(get_config(held)), held


@pytest.mark.parametrize("bad, said", [
    (dict(norm_placement="both"), "ONE norm a sublayer"), (dict(norm_placement="between"), "norm_placement"),
    (dict(qk_norm=False), "qk_norm_flat"), (dict(num_layers=6), "layer_types"),
    (dict(linear_value_heads=6), "whole")])
def test_a_config_that_contradicts_itself_is_refused(bad, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CFG, **bad)


REFUSED = {
    "mesh": dict(mesh="pp=2"), "stage-lanes": dict(stage_lanes=2), "paged-kv": dict(paged_kv=16),
    "spec": dict(spec_draft_layers=1), "lora": dict(lora="x"),
    "adapters": dict(adapters="a"), "standby": dict(standby_repl=True), "no lanes": dict(batch_lanes=0),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_what_has_state_layers_refuses(path):
    """No table of this model's own: `has_state_layers` selects the
    refusals; --kv-dtype and --quant stay open."""
    from inferd_tpu.tools import run_node

    base = dict(mesh="", stage_lanes=0, paged_kv=0, quant="none", spec_draft_layers=0, lora="",
                adapters="", standby_repl=False, backend="qwen3", batch_lanes=16)
    cfg = get_config("olmo-hybrid-7b-16l")
    run_node.check_servable(cfg, argparse.Namespace(**base))
    run_node.check_servable(cfg, argparse.Namespace(**{**base, "quant": "int8"}))
    with pytest.raises(SystemExit, match="olmo-hybrid-7b-16l cannot be served with"):
        run_node.check_servable(cfg, argparse.Namespace(**{**base, **REFUSED[path]}))
    with pytest.raises(SystemExit, match="several stages"):
        run_node.check_servable(cfg, argparse.Namespace(**base), num_stages=2)


def test_quant_int8_reaches_both_stacks_and_the_head(params):
    """The 8-bit control of `correct`: every projection of both weight
    stacks, both MLPs and the untied head are quantized; `ba_proj`, the taps
    and the vectors are not. The same model at another precision."""
    from inferd_tpu.ops import quant

    q = quant.apply_quant_mode("int8", params, tie_word_embeddings=False)
    try:
        for group, names in (("layers", ("q_proj", "k_proj", "v_proj", "o_proj")),
                             ("state_layers", ("in_proj", "out_proj"))):
            for name in names + ("gate_proj", "up_proj", "down_proj"):
                assert isinstance(q[group][name], quant.QuantWeight), (group, name)
        assert isinstance(q["lm_head"], quant.QuantWeight)
        for name in ("ba_proj", "conv_w", "gate_norm", "post_norm"):
            assert not isinstance(q["state_layers"][name], quant.QuantWeight), name
        ids = jnp.asarray([_ids(20, seed=6)])
        sound, _, _ = qwen3.forward(params, CFG, ids)
        got, _, _ = qwen3.forward(q, CFG, ids)
        assert 1e-4 < float(np.abs(_logp(got) - _logp(sound)).max()) < 0.5  # another precision, the same model
    finally:
        quant.QDOT_MODE = "dequant"


def test_the_executor_counts_the_bytes_it_holds(params):
    """/stats `executor`: `state_bytes_per_session` is the buffers' bytes a
    lane (the state as it is HELD, the columns), `kv_layout` rows, and a
    session streams through prefill and decode."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(CFG, params, lanes=2, max_len=64)
    ex.process("a", {"tokens": [_ids(8)], "start_pos": 0, "real_len": 8})
    ex.process("a", {"tokens": [[5]], "start_pos": 8, "real_len": 1})
    st = ex.stats()
    held = (ex.engine.cache.s.nbytes + ex.engine.cache.conv.nbytes) // 2
    assert st["state_bytes_per_session"] == held == 6 * (4 * 96 * 192 * 4 + 3 * 1536 * 4)
    assert st["kv_layout"] == "rows" and st["kv_bytes_per_token"] == 2 * 2 * 64 * 4
    with pytest.raises(ValueError, match="recurrent state"):
        ex.process("a", {"tokens": [_ids(2)], "start_pos": 4, "real_len": 2})  # a replay


# ---------------------------------------------------------------------------
# the published names
# ---------------------------------------------------------------------------


def _hf_state_dict(params, cfg):
    """The tiny preset's weights under the names and in the layouts an
    `olmo_hybrid` checkpoint has them: [out, in], the linear layer's
    projections apart."""
    f = lambda a: np.asarray(a, np.float32)  # noqa: E731
    kd, vd = cfg.linear_key_dim, cfg.linear_value_dim
    hv = cfg.linear_value_heads
    sd = {"model.embed_tokens.weight": f(params["embed"]), "model.norm.weight": f(params["final_norm"]),
          "lm_head.weight": f(params["lm_head"]).T}
    seen = {"attention": 0, "delta": 0}
    for i, kind in enumerate(cfg.layer_type_names):
        stack = params["layers" if kind == "attention" else "state_layers"]
        p = {k: f(v[seen[kind]]) for k, v in stack.items()}
        seen[kind] += 1
        pre = f"model.layers.{i}"
        sd[f"{pre}.post_attention_layernorm.weight"] = p["post_norm"]
        sd[f"{pre}.post_feedforward_layernorm.weight"] = p["post_ffn_norm"]
        for proj in ("gate_proj", "up_proj", "down_proj"):
            sd[f"{pre}.mlp.{proj}.weight"] = p[proj].T
        if kind == "attention":
            for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
                sd[f"{pre}.self_attn.{proj}.weight"] = p[proj].T
            sd[f"{pre}.self_attn.q_norm.weight"] = p["q_norm"]
            sd[f"{pre}.self_attn.k_norm.weight"] = p["k_norm"]
            continue
        m, w, ba = f"{pre}.linear_attn", p["in_proj"], p["ba_proj"]
        cuts = {"q_proj": (0, kd), "k_proj": (kd, 2 * kd), "v_proj": (2 * kd, 2 * kd + vd),
                "g_proj": (2 * kd + vd, 2 * kd + 2 * vd)}
        for name, (lo, hi) in cuts.items():
            sd[f"{m}.{name}.weight"] = w[:, lo:hi].T
        sd[f"{m}.b_proj.weight"], sd[f"{m}.a_proj.weight"] = ba[:, :hv].T, ba[:, hv:].T
        sd[f"{m}.conv1d.weight"] = p["conv_w"].T[:, None, :]
        sd[f"{m}.dt_bias"], sd[f"{m}.A_log"] = p["dt_bias"], p["A_log"]
        sd[f"{m}.o_norm.weight"] = p["gate_norm"]
        sd[f"{m}.o_proj.weight"] = p["out_proj"].T
    return sd


def test_the_olmo_hybrid_names_round_trip_and_the_checkpoint_carries_both_stacks(params, tmp_path):
    from inferd_tpu.models.loader import params_from_hf_state_dict
    from inferd_tpu.parallel.stages import Manifest, load_stage_checkpoint, split_and_save

    sd = _hf_state_dict(params, CFG)
    assert sd["model.layers.0.linear_attn.q_proj.weight"].shape == (4 * 96, 64)
    assert sd["model.layers.0.linear_attn.g_proj.weight"].shape == (4 * 192, 64)
    assert sd["model.layers.3.self_attn.q_norm.weight"].shape == (64,)  # over the whole projection
    assert not any("input_layernorm" in k for k in sd)
    back = params_from_hf_state_dict(CFG, sd)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    paths = split_and_save(params, CFG, Manifest.even_split("tiny-olmo-hybrid", 1), str(tmp_path))
    held, _spec, name = load_stage_checkpoint(paths[0])
    assert name == "tiny-olmo-hybrid" and set(held) == set(params)
    for group in ("layers", "state_layers"):
        assert set(held[group]) == set(params[group])
