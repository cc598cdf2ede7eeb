# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values
"""The afmoe layer (Trinity) on the lane path at `tiny-afmoe`: a dense layer,
then two periods of three windowed layers (window 8, rope) to one full layer
(no rope), a gated attention output, sandwich norms, sigmoid routing over 16
experts (top 2, a selection bias) beside a shared one; and one chip's SHARE
of the experts. Seeded random weights, float32 at `highest`; the forward
they are held to is written here from the equations, with a switch for each
of four mistakes that must NOT pass; the benchmark's own reference
(`benchmark/references/afmoe.py`) is held to the same."""

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
from inferd_tpu.core.cache import KVCache, RingEntry
from inferd_tpu.models import qwen3

CFG = get_config("tiny-afmoe")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF_KINDS = {"sliding": "sliding_attention", "global": "full_attention"}
TOL = 5e-6  # float32 both sides at `highest`: the order of a few hundred additions
WRONG = 1e-3  # a mistake in the mathematics moves the log-probabilities by far more


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    """Drawn wider than init's 0.02 where a flat function would hide a
    mistake: the gate's input and the values (a gate of sigmoid(0) = 1/2 and
    a softmax over equal values forgive much)."""
    p = qwen3.init_params(CFG, jax.random.PRNGKey(7))
    for group in ("dense_layers", "layers"):
        g = dict(p[group])
        for name in ("attn_gate_proj", "v_proj", "o_proj"):
            g[name] = g[name] * 6.0
        p[group] = g
    return p


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "afmoe_reference", os.path.join(REPO, "benchmark", "references", "afmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg):
    """The keys the benchmark's reference reads, as the configuration's file names them."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "num_dense_layers": cfg.num_dense_layers,
        "layer_types": [HF_KINDS[k] for k in cfg.layer_type_names],
        "sliding_window": cfg.sliding_window, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps, "num_experts": cfg.num_experts,
        "router_experts": cfg.router_width, "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.num_experts_per_tok, "route_norm": cfg.norm_topk_prob,
        "route_scale": cfg.routed_scaling_factor, "score_func": "sigmoid", "mup_enabled": True,
        "tie_word_embeddings": False,
    }


# ---------------------------------------------------------------------------
# the equations, in numpy-like jnp, one sequence, no cache
# ---------------------------------------------------------------------------


def _norm(x, w, eps=CFG.rms_norm_eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, _, d = x.shape
    ang = jnp.arange(s)[:, None] / theta ** (jnp.arange(0, d, 2) / d)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def equations(params, cfg, ids, mistake=None):
    """Log-probabilities [S, V] of one sequence. `mistake`: None, or one of
    "rope_on_full", "no_gate", "bias_weighs", "no_window"."""
    f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    s, nq, nkv, d = len(ids), cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    x = f(params["embed"])[jnp.asarray(ids)] * cfg.hidden_size ** 0.5
    nd = cfg.num_dense_layers
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]  # p - j
    for i, kind in enumerate(cfg.layer_type_names):
        stack, at = (params["dense_layers"], i) if i < nd else (params["layers"], i - nd)
        p = {k: f(v[at]) for k, v in stack.items()}
        a = _norm(x, p["input_norm"])
        q = _norm((a @ p["q_proj"]).reshape(s, nq, d), p["q_norm"])
        k = _norm((a @ p["k_proj"]).reshape(s, nkv, d), p["k_norm"])
        v = (a @ p["v_proj"]).reshape(s, nkv, d)
        windowed = kind == "sliding"
        if windowed or mistake == "rope_on_full":
            q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        seen = ahead >= 0
        if windowed and mistake != "no_window":
            seen &= ahead < cfg.sliding_window
        k, v = jnp.repeat(k, nq // nkv, 1), jnp.repeat(v, nq // nkv, 1)
        scores = jnp.where(seen[None], jnp.einsum("qnd,knd->nqk", q, k) * d ** -0.5, -jnp.inf)
        out = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v).reshape(s, nq * d)
        if mistake != "no_gate":
            out = out * jax.nn.sigmoid(a @ p["attn_gate_proj"])
        x = x + _norm(out @ p["o_proj"], p["post_norm"])
        a = _norm(x, p["pre_ffn_norm"])
        mlp = lambda g, u, dn: (jax.nn.silu(a @ g) * (a @ u)) @ dn  # noqa: E731
        if i < nd:
            y = mlp(p["gate_proj"], p["up_proj"], p["down_proj"])
        else:
            score = jax.nn.sigmoid(a @ p["router"])
            biased = score + p["router_select_bias"]
            _, chosen = jax.lax.top_k(biased, cfg.num_experts_per_tok)
            w = jnp.take_along_axis(biased if mistake == "bias_weighs" else score, chosen, 1)
            w = w / (w.sum(1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
            y = mlp(p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"])
            for e in range(p["gate_proj"].shape[0]):  # the experts HELD: offset + e of the router's
                mine = jnp.where(chosen == cfg.expert_offset + e, w, 0.0).sum(1)
                y = y + mine[:, None] * mlp(p["gate_proj"][e], p["up_proj"][e], p["down_proj"][e])
        x = x + _norm(y, p["post_ffn_norm"])
    return np.asarray(jax.nn.log_softmax(_norm(x, f(params["final_norm"])) @ f(params["lm_head"]), -1))


def _ids(n, seed=3):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, n)]


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))


def _prefill(eng, lane, ids, start=0, bucket=None):
    b = bucket or len(ids)
    padded = np.zeros((1, b), np.int32)
    padded[0, : len(ids)] = ids
    eng.cache, logits = eng._prefill_lane_logits(
        eng.params, eng.cache, jnp.asarray(padded), jnp.int32(lane), jnp.int32(start),
        jnp.int32(len(ids)))
    return np.asarray(logits)


def _decode(eng, toks, lens):
    eng.cache, logits, chosen = eng._decode_logits(
        eng.params, eng.cache, np.asarray(toks, np.int32), np.asarray(lens, np.int32))
    return np.asarray(logits), np.asarray(chosen)


IDS = _ids(116)  # past the ring's 80 slots: the windowed layers wrap


@pytest.fixture(scope="module")
def want(params):
    return equations(params, CFG, IDS)


# ---------------------------------------------------------------------------
# the served path against the equations
# ---------------------------------------------------------------------------


def test_the_cache_free_forward_and_the_benchmarks_reference_are_the_equations(
        params, reference, want):
    full, _, _ = qwen3.forward(params, CFG, jnp.asarray([IDS]))
    np.testing.assert_allclose(_logp(full[0]), want, atol=TOL)
    ref = reference.logprobs(params, IDS, len(IDS), published(CFG))
    np.testing.assert_allclose(ref, want, atol=TOL)
    assert np.abs(want).max() > 1.0  # the logits are not flat


@pytest.mark.parametrize("mistake", ["rope_on_full", "no_gate", "bias_weighs", "no_window"])
def test_each_mistake_in_the_mathematics_fails_parity(params, want, mistake):
    """Rope put on the full layers, the gate dropped, the selection bias
    added to the weights, the window ignored: each is another function, by
    far more than the tolerance, and over many positions."""
    wrong = equations(params, CFG, IDS, mistake)
    diff = np.abs(wrong - want).max(axis=1)  # by position
    assert np.median(diff[16:]) > WRONG, (mistake, float(np.median(diff)))
    full, _, _ = qwen3.forward(params, CFG, jnp.asarray([IDS]))
    assert np.abs(_logp(full[0]) - wrong).max() > 100 * TOL


def test_two_chunks_then_ragged_decode_past_a_wrapped_ring_with_a_masked_row(params, want):
    """Lane 1 of 3: 64 tokens, then 36 padded to a bucket of 64 (100 tokens:
    the ring of 80 slots has wrapped), then 16 tokens one at a time while
    lane 0 decodes another, shorter session and lane 2 is idle (its row is
    computed and thrown away). Every row against ONE pass of the equations."""
    eng = BatchedEngine(CFG, params, lanes=3, max_len=128)
    assert eng.cache.k_loc.shape == (7, 3, 80, 2, 16) and eng.cache.k.shape[0] == 2
    assert isinstance(eng.cache.entries(CFG)[0], RingEntry) and len(eng.cache.entries(CFG)) == 2
    np.testing.assert_allclose(_logp(_prefill(eng, 1, IDS[:64], 0)), want[63], atol=TOL)
    np.testing.assert_allclose(_logp(_prefill(eng, 1, IDS[64:100], 64, 64)), want[99], atol=TOL)
    other = _ids(30, seed=5)
    want_other = equations(params, CFG, other)
    np.testing.assert_allclose(_logp(_prefill(eng, 0, other[:20], 0, 32)), want_other[19], atol=TOL)
    for step in range(16):
        t, u = 100 + step, 20 + min(step, 9)
        toks, lens = [other[u], IDS[t], 0], [u, t, 0]
        got, chosen = _decode(eng, toks, lens)
        np.testing.assert_allclose(_logp(got[1]), want[t], atol=TOL)
        if step < 10:
            np.testing.assert_allclose(_logp(got[0]), want_other[u], atol=TOL)
        assert chosen.shape == (8, 3, 2) and chosen.max() < CFG.router_width


def test_k_step_decode_fork_and_handoff_run_the_model_unchanged(params, want):
    """`decode_steps` (models/qwen3.decode_k, rows under `write_mask`), a
    fork at the prompt's end and an export / import into another executor,
    each continued greedily against the equations' argmax."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(CFG, params, lanes=3, max_len=128)
    n = 90
    r = ex.process("k", {"tokens": [IDS[:n]], "start_pos": 0, "real_len": n})
    np.testing.assert_allclose(_logp(r["logits"][0]), want[n - 1], atol=TOL)
    assert ex.fork_session("child", "k", n)
    payload = dict(ex.export_sessions())["k"]
    assert "k_loc" in payload
    peer = BatchedExecutor(CFG, params, lanes=2, max_len=128)
    assert peer.import_session("k", payload)
    step = {"tokens": [[IDS[n]]], "start_pos": n, "real_len": 1}
    for who, where in (("child", ex), ("k", peer)):
        got = where.process(who, dict(step))
        np.testing.assert_allclose(_logp(got["logits"][0]), want[n], atol=TOL)
    r = ex.process("k", {**step, "decode_steps": 4, "sampling": {"temperature": 0.0}})
    seq = IDS[: n + 1] + r["tokens"][0]
    again = equations(params, CFG, seq[:-1])
    assert [int(row.argmax()) for row in again[n:]] == r["tokens"][0]
    st = ex.stats()
    assert st["kv"]["window"] == 8 and st["kv"]["ring_bytes_per_session"] == 7 * 80 * 2 * 2 * 16 * 4
    # the full layers' slabs are under the floor of the read by prefix: read whole
    assert st["kv"]["slots_read"] == st["kv"]["slots_held"] > 0
    assert st["moe"]["experts"] == 16 and st["moe"]["experts_held"] == 16
    assert st["moe"]["assignments_here"] == st["moe"]["assignments"] > 0


def test_a_grown_cache_keeps_its_rings(params, want):
    cache = KVCache.create(CFG, CFG.num_layers, 1, 64)
    logits, cache, _ = qwen3.forward_cached(params, CFG, jnp.asarray([IDS[:60]]), None, cache, jnp.int32(0))
    grown = cachelib.grow(cache, 128)
    assert grown.max_len == 128 and grown.k_loc is cache.k_loc
    logits, _, _ = qwen3.forward_cached(
        params, CFG, jnp.asarray([IDS[60:100]]), None, grown, jnp.int32(60))
    np.testing.assert_allclose(_logp(logits[0]), want[60:100], atol=TOL)


@pytest.mark.parametrize("dtype, kv_dtype, low, high", [
    ("bfloat16", "model", 1e-3, 0.2), ("bfloat16", "float8_e4m3fn", 1e-3, 0.4)])
def test_bf16_and_the_control_dtype_run_the_same_model(params, want, dtype, kv_dtype, low, high):
    """The cell's precision and its control (`--kv-dtype float8_e4m3fn`:
    rings and slab in 8 bits) serve the model: near the float32 equations,
    not equal to them."""
    cfg = dataclasses.replace(CFG, dtype=dtype, kv_dtype=kv_dtype)
    cast = jax.tree.map(lambda a: a if a.dtype == jnp.float32 and a.ndim == 2 and a.shape[-1] == 16
                        else a.astype(jnp.bfloat16), params)
    assert cast["layers"]["router_select_bias"].dtype == jnp.float32
    eng = BatchedEngine(cfg, cast, lanes=2, max_len=128)
    assert eng.cache.k_loc.dtype == (jnp.bfloat16 if kv_dtype == "model" else jnp.float8_e4m3fn)
    _prefill(eng, 0, IDS[:64], 0)
    got = _logp(_prefill(eng, 0, IDS[64:100], 64, 64))
    top = np.argsort(want[99])[-8:]
    diff = float(np.abs(got[top] - want[99][top]).mean())
    assert low < diff < high, diff


# ---------------------------------------------------------------------------
# one chip's share of the experts
# ---------------------------------------------------------------------------


def _share(params, cfg, offset, held):
    """The preset and the weights of the chip that holds experts offset .. offset + held."""
    sh = dataclasses.replace(cfg, num_experts=held, router_experts=cfg.router_width,
                             expert_offset=offset)
    layers = {k: (v[:, offset: offset + held] if k in ("gate_proj", "up_proj", "down_proj") else v)
              for k, v in params["layers"].items()}
    return sh, {**params, "layers": layers}


@pytest.fixture(scope="module")
def one_layer(params):
    lp = jax.tree.map(lambda a: a[2], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 24, CFG.hidden_size), jnp.float32)
    whole, topi = qwen3.moe_mlp_routed(lp, CFG, x)
    shared = qwen3.swiglu_mlp({k: lp[f"shared_{k}"] for k in ("gate_proj", "up_proj", "down_proj")},
                              x.reshape(48, -1)).reshape(x.shape)
    return lp, x, whole, topi, shared


@pytest.mark.parametrize("case", ["eight-shares", "two-halves", "sharded-2", "held-only"])
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up_to_the_whole_layer(
        one_layer, case):
    """The guide's share test: at the tiny size the routed parts of all
    shares (offsets 0, 2, .. of 16 experts) plus what every chip computes
    alike (the shared expert), counted ONCE, are the uncut layer; every share
    chooses the same experts; `moe_mlp_sharded` on a 2-device mesh is the
    same sum by its psum; and a share alone is NOT the layer."""
    lp, x, whole, topi, shared = one_layer
    xt = x.reshape(48, -1)
    cut = lambda o, h: {k: (v[o: o + h] if k in ("gate_proj", "up_proj", "down_proj") else v)  # noqa: E731
                        for k, v in lp.items()}
    if case == "sharded-2":
        from jax.sharding import Mesh, PartitionSpec as P

        from inferd_tpu.parallel import tp as tplib

        bare = dataclasses.replace(CFG, n_shared_experts=0)  # the sharded layer has no shared expert
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("ep",))
        specs = {k: P("ep") if k in ("gate_proj", "up_proj", "down_proj") else P() for k in lp}
        out = jax.jit(jax.shard_map(
            lambda p, h: tplib.moe_mlp_sharded(p, bare, h, ("ep",)), mesh=mesh,
            in_specs=(specs, P()), out_specs=P(), check_vma=False))(lp, x)
        np.testing.assert_allclose(np.asarray(out + shared), np.asarray(whole), atol=TOL)
        return
    held = {"eight-shares": 2, "two-halves": 8, "held-only": 2}[case]
    parts = []
    for offset in range(0, 16, held):
        part, chose = qwen3.moe_routed_part(cut(offset, held), CFG, xt, offset)
        np.testing.assert_array_equal(np.asarray(chose), np.asarray(topi).reshape(48, -1))
        parts.append(part.reshape(x.shape))
    if case == "held-only":
        assert float(jnp.abs(parts[0] + shared - whole).max()) > WRONG
        return
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole), atol=TOL)


def test_a_share_served_from_the_lanes_is_the_equations_over_the_held_experts(params, reference):
    """Experts 8..11 of 16 under the whole router: prefill, then decode, and
    the counters that say which part of the routing fell here."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    sh, ps = _share(params, CFG, 8, 4)
    ids = _ids(40, seed=9)
    want = equations(ps, sh, ids)
    np.testing.assert_allclose(reference.logprobs(ps, ids, 40, published(sh)), want, atol=TOL)
    assert np.abs(want - equations(params, CFG, ids)).max() > WRONG  # not the whole model
    ex = BatchedExecutor(sh, ps, lanes=2, max_len=64)
    r = ex.process("s", {"tokens": [ids[:30]], "start_pos": 0, "real_len": 30})
    np.testing.assert_allclose(_logp(r["logits"][0]), want[29], atol=TOL)
    for t in range(30, 40):
        r = ex.process("s", {"tokens": [[ids[t]]], "start_pos": t, "real_len": 1,
                             "sampling": {"temperature": 0.0}})
    moe = ex.stats()["moe"]
    assert moe["experts"] == 16 and moe["experts_held"] == 4
    assert moe["assignments"] == 10 * 8 * 2 and 0 < moe["assignments_here"] < moe["assignments"]
    assert 0 < moe["experts_touched_here"] <= moe["experts_touched"]


# ---------------------------------------------------------------------------
# the presets, the bytes, what is refused, the published names
# ---------------------------------------------------------------------------


def test_the_served_preset_is_the_cut_of_the_published_one():
    pub, cut = get_config("trinity-large-preview"), get_config("trinity-large-ep8-5l")
    assert pub.layer_type_names == (["sliding"] * 3 + ["global"]) * 15
    assert (pub.num_layers, pub.num_dense_layers, pub.num_experts, pub.vocab_size) == (60, 6, 256, 200192)
    assert cut.layer_type_names == ["sliding", "sliding", "sliding", "global", "sliding"]
    assert (cut.num_layers, cut.num_dense_layers, cut.num_experts, cut.router_width,
            cut.expert_offset, cut.vocab_size) == (5, 1, 32, 256, 0, 25024)
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_heads", "num_kv_heads",
              "head_dim", "num_experts_per_tok", "sliding_window", "n_shared_experts", "rope_theta",
              "routed_scaling_factor", "nope_kinds", "attn_gate", "moe_router_mode", "layer_types")
    assert all(getattr(pub, w) == getattr(cut, w) for w in widths)
    shapes = jax.eval_shape(lambda: qwen3.init_params(cut, jax.random.PRNGKey(0)))
    leaves = jax.tree.leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves if a.dtype == jnp.bfloat16) == 4_321_902_848
    assert shapes["layers"]["router"].shape == (4, 3072, 256)
    assert shapes["layers"]["router_select_bias"].dtype == jnp.float32
    c = jax.eval_shape(lambda: KVCache.create(cut, cut.num_layers, 16, 16384))
    assert c.k.shape == (1, 16, 16384, 8, 128) and c.k_loc.shape == (4, 16, 4160, 8, 128)
    assert c.nbytes == 2_164_260_864
    flat = jax.eval_shape(lambda: KVCache.create(cut, cut.num_layers, 16, 16384, ring=False))
    assert flat.nbytes == 5 * 16 * 16384 * 4096


@pytest.mark.parametrize("bad", [
    dict(layer_types=("sliding", "full")), dict(nope_kinds=("mamba",)), dict(sliding_window=0),
    dict(moe_router_mode="sigmoid"), dict(router_experts=8), dict(router_experts=32, expert_offset=20)])
def test_a_config_that_contradicts_itself_is_refused(bad):
    with pytest.raises(ValueError, match="tiny-afmoe"):
        dataclasses.replace(CFG, **bad)


REFUSED = {
    "mesh": dict(mesh="pp=2"), "stage-lanes": dict(stage_lanes=2), "paged-kv": dict(paged_kv=16),
    "spec": dict(spec_draft_layers=1), "adapters": dict(adapters="a"), "lora": dict(lora="x"),
    "standby": dict(standby_repl=True), "no lanes": dict(batch_lanes=0),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_every_other_path_by_what_the_config_observes(path):
    from inferd_tpu.tools import run_node

    base = dict(mesh="", stage_lanes=0, paged_kv=0, quant="none", spec_draft_layers=0, lora="",
                adapters="", standby_repl=False, backend="qwen3", batch_lanes=16)
    run_node.check_servable(CFG, argparse.Namespace(**base))  # the lane path, --kv-dtype open
    renamed = dataclasses.replace(CFG, name="something-else", first_k_dense_replace=0)
    with pytest.raises(SystemExit, match="something-else cannot be served with"):
        run_node.check_servable(renamed, argparse.Namespace(**{**base, **REFUSED[path]}))
    with pytest.raises(SystemExit, match="several stages"):
        run_node.check_servable(renamed, argparse.Namespace(**base), num_stages=2)
    # --quant: refused for the leading dense group alone (PR 48: the shared expert has a
    # quantized form), so open without one and refused, by the group's table, with it
    run_node.check_servable(renamed, argparse.Namespace(**{**base, "quant": "int8"}))
    with pytest.raises(SystemExit, match="--quant"):
        run_node.check_servable(CFG, argparse.Namespace(**{**base, "quant": "int8"}))


def test_a_traced_layer_offset_and_a_split_are_refused_below_too(params):
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params

    with pytest.raises(ValueError, match="tiny-afmoe"):
        extract_stage_params(params, CFG, Manifest.even_split("tiny-afmoe", 3).stage_spec(0))
    x = jnp.zeros((1, 2, CFG.hidden_size), jnp.float32)
    with pytest.raises(ValueError, match="knows no kind"):
        jax.jit(lambda off: qwen3.forward_layers(params["layers"], CFG, x, jnp.arange(2)[None],
                                                 layer_offset=off))(jnp.int32(1))


def test_loader_maps_the_published_names_and_reads_a_share(params):
    from inferd_tpu.models.loader import params_from_hf_state_dict

    host = jax.tree.map(np.asarray, params)
    sd = {"model.embed_tokens.weight": host["embed"], "model.norm.weight": host["final_norm"],
          "lm_head.weight": host["lm_head"].T}
    nd = CFG.num_dense_layers
    for i in range(CFG.num_layers):
        stack, at = (host["dense_layers"], i) if i < nd else (host["layers"], i - nd)
        lp = {k: v[at] for k, v in stack.items()}
        pre = f"model.layers.{i}"
        for ours, theirs in (("input_norm", "input_layernorm"), ("post_norm", "post_attention_layernorm"),
                             ("pre_ffn_norm", "pre_mlp_layernorm"), ("post_ffn_norm", "post_mlp_layernorm"),
                             ("q_norm", "self_attn.q_norm"), ("k_norm", "self_attn.k_norm")):
            sd[f"{pre}.{theirs}.weight"] = lp[ours]
        for ours, theirs in (("q_proj", "q_proj"), ("k_proj", "k_proj"), ("v_proj", "v_proj"),
                             ("o_proj", "o_proj"), ("attn_gate_proj", "gate_proj")):
            sd[f"{pre}.self_attn.{theirs}.weight"] = lp[ours].T
        if i < nd:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                sd[f"{pre}.mlp.{proj}.weight"] = lp[proj].T
            continue
        sd[f"{pre}.mlp.router.gate.weight"] = lp["router"].T
        sd[f"{pre}.mlp.expert_bias"] = lp["router_select_bias"]
        for proj in ("gate_proj", "up_proj", "down_proj"):
            sd[f"{pre}.mlp.shared_experts.{proj}.weight"] = lp[f"shared_{proj}"].T
            for e in range(CFG.num_experts):
                sd[f"{pre}.mlp.experts.{e}.{proj}.weight"] = lp[proj][e].T
    loaded = jax.tree.map(np.asarray, params_from_hf_state_dict(CFG, sd))
    assert jax.tree.structure(loaded) == jax.tree.structure(host)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(host)):
        np.testing.assert_array_equal(a, b)
    sh, ps = _share(params, CFG, 4, 4)
    sh = dataclasses.replace(sh, vocab_size=64)
    part = params_from_hf_state_dict(sh, sd)
    np.testing.assert_array_equal(np.asarray(part["layers"]["up_proj"]), np.asarray(ps["layers"]["up_proj"]))
    assert part["layers"]["router"].shape[-1] == 16 and part["embed"].shape[0] == 64
    assert part["lm_head"].shape == (CFG.hidden_size, 64)


def test_the_checkpoint_carries_both_groups_and_the_float32_bias(params, tmp_path):
    from inferd_tpu.parallel.stages import Manifest, load_stage_checkpoint, split_and_save

    paths = split_and_save(params, CFG, Manifest.even_split("tiny-afmoe", 1), str(tmp_path))
    loaded, spec, name = load_stage_checkpoint(paths[0])
    assert name == "tiny-afmoe" and spec.num_stages == 1
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    assert np.asarray(loaded["layers"]["router_select_bias"]).dtype == np.float32
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
