"""Granite-4.0-H on the lane path at `tiny-granite-h`: Mamba-2 layers beside
attention without positions (one period `m m A m`, two periods), a recurrent
state as one more cache entry. Seeded random weights, float32 at `highest`;
the plain reference is the benchmark's own (`benchmark/references/
granite-hybrid.py`: a sequential scan over tokens, one forward pass)."""

import argparse
import asyncio
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import PRESETS, get_config
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import BlockPool, KVCache, StateEntry
from inferd_tpu.models import qwen3

CFG = get_config("tiny-granite-h")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 both sides, matmuls at `highest`: the two differ by the order of a
# few hundred float32 additions (the chunked form sums a tile at once, the
# reference token by token), some 1e-7 on logits of size 0.2
TOL = 2e-6


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return qwen3.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "granite_hybrid", os.path.join(REPO, "benchmark", "references", "granite-hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg):
    """The keys the reference reads, as a published config names them."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "num_hidden_layers": cfg.num_layers,
        "layer_types": cfg.layer_type_names, "rms_norm_eps": cfg.rms_norm_eps,
        "attention_multiplier": cfg.attn_scale, "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier, "logits_scaling": cfg.logits_scaling,
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.mamba_state, "mamba_n_groups": cfg.mamba_groups,
        "mamba_d_conv": cfg.mamba_conv, "tie_word_embeddings": cfg.tie_word_embeddings,
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
    eng.cache, logits, _ = eng._decode_logits(
        eng.params, eng.cache, np.asarray(toks, np.int32), np.asarray(lens, np.int32),
        active=np.asarray(active, bool))
    return np.asarray(logits)


# ---------------------------------------------------------------------------
# the recurrence: two forms of one function
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("groups", [1, 2])
def test_the_chunked_form_equals_the_token_by_token_recurrence_across_tiles(groups):
    """A chunk of 24 positions in tiles of 8, entered with a state that is
    not zero, against the recurrence run a token at a time."""
    rng = np.random.default_rng(0)
    b, s, hg, p, n = 2, 24, 4 // groups, 5, 6
    x = rng.normal(size=(b, s, groups, hg, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(b, s, groups, hg)))).astype(np.float32)
    a = -rng.uniform(0.1, 1.0, size=(groups, hg)).astype(np.float32)
    bm = rng.normal(size=(b, s, groups, n)).astype(np.float32)
    cm = rng.normal(size=(b, s, groups, n)).astype(np.float32)
    s0 = rng.normal(size=(b, groups, hg, p, n)).astype(np.float32)
    y, s_out = qwen3.ssm_chunked(*map(jnp.asarray, (x, dt, a, bm, cm, s0)), tile=8)
    state, want = s0.astype(np.float64), np.zeros((b, s, groups, hg, p))
    for t in range(s):
        decay = np.exp(dt[:, t] * a)[..., None, None]
        state = decay * state + (dt[:, t, ..., None] * x[:, t])[..., None] * bm[:, t, :, None, None, :]
        want[:, t] = (state * cm[:, t, :, None, None, :]).sum(-1)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_out), state, atol=2e-5)
    one_tile, _ = qwen3.ssm_chunked(*map(jnp.asarray, (x, dt, a, bm, cm, s0)), tile=24)
    np.testing.assert_allclose(np.asarray(one_tile), want, atol=2e-5)


def test_prefill_in_two_chunks_then_eight_decode_steps_equal_one_pass_of_the_reference(
        params, reference):
    """20 tokens, then 9 padded to a bucket of 16, then 8 tokens one at a
    time through the state and the cache, on lane 1 of 3."""
    ids = _ids(37)
    want = reference.logprobs(params, ids, len(ids), published(CFG))  # row t: after token t
    eng = BatchedEngine(CFG, params, lanes=3, max_len=64)
    np.testing.assert_allclose(_logp(_prefill(eng, 1, ids[:20], 0, 32)), want[19], atol=TOL)
    np.testing.assert_allclose(_logp(_prefill(eng, 1, ids[20:29], 20, 16)), want[28], atol=TOL)
    for t in range(29, 37):
        toks, lens = [0, ids[t], 0], [0, t, 0]
        got = _decode(eng, toks, lens, [False, True, False])[1]
        np.testing.assert_allclose(_logp(got), want[t], atol=TOL)
    # and the cache-free forward is the same function
    full, _, _ = qwen3.forward(params, CFG, jnp.asarray([ids]))
    np.testing.assert_allclose(_logp(full[0]), want, atol=TOL)


def test_a_padded_chunk_and_an_unpadded_one_leave_the_same_state(params):
    ids = _ids(11, seed=5)
    eng = BatchedEngine(CFG, params, lanes=2, max_len=64)
    a = _prefill(eng, 0, ids, 0, 11)
    b = _prefill(eng, 1, ids, 0, 32)
    np.testing.assert_allclose(a, b, atol=TOL)
    c = eng.cache
    # the padding's rows of the convolution are not kept, its steps are d = 0
    np.testing.assert_allclose(np.asarray(c.s[:, 0]), np.asarray(c.s[:, 1]), atol=TOL)
    np.testing.assert_allclose(np.asarray(c.conv[:, 0]), np.asarray(c.conv[:, 1]), atol=TOL)
    assert float(jnp.abs(c.s[:, 0]).max()) > 1e-3  # and a state there is


def test_an_inactive_lane_keeps_its_state_bit_for_bit(params):
    eng = BatchedEngine(CFG, params, lanes=3, max_len=64)
    for lane in range(3):
        _prefill(eng, lane, _ids(7 + lane, seed=lane), 0, 16)
    before = jax.tree.map(np.asarray, eng.cache)
    _decode(eng, [5, 6, 7], [7, 8, 9], [True, False, True])
    after = jax.tree.map(np.asarray, eng.cache)
    for name in ("s", "conv"):
        old, new = getattr(before, name), getattr(after, name)
        np.testing.assert_array_equal(new[:, 1], old[:, 1])
        assert not np.array_equal(new[:, 0], old[:, 0]) and not np.array_equal(new[:, 2], old[:, 2])
    np.testing.assert_array_equal(after.k[:, 1], before.k[:, 1])


def test_two_lanes_at_different_lengths_equal_each_alone(params):
    a, b = _ids(13, seed=1), _ids(6, seed=2)

    def run(lanes, prompts):
        eng = BatchedEngine(CFG, params, lanes=lanes, max_len=64)
        for lane, ids in prompts.items():
            _prefill(eng, lane, ids, 0, 16)
        out = []
        lens = {lane: len(ids) for lane, ids in prompts.items()}
        for step in range(3):
            toks = [step + 1 if lane in prompts else 0 for lane in range(lanes)]
            got = _decode(eng, toks, [lens.get(lane, 0) for lane in range(lanes)],
                          [lane in prompts for lane in range(lanes)])
            out.append({lane: got[lane] for lane in prompts})
            lens = {lane: n + 1 for lane, n in lens.items()}
        return out

    both, only_a, only_b = run(2, {0: a, 1: b}), run(2, {0: a}), run(2, {1: b})
    for step in range(3):
        np.testing.assert_allclose(both[step][0], only_a[step][0], atol=TOL)
        np.testing.assert_allclose(both[step][1], only_b[step][1], atol=TOL)


def test_a_chunk_at_position_zero_starts_from_zeros_whatever_the_lane_held(params):
    eng = BatchedEngine(CFG, params, lanes=2, max_len=64)
    ids = _ids(9, seed=8)
    first = _prefill(eng, 0, ids, 0, 16)
    _prefill(eng, 0, _ids(5, seed=9), 9, 16)  # the lane moves on
    again = _prefill(eng, 0, ids, 0, 16)  # a new session on the same lane
    np.testing.assert_array_equal(first, again)


# ---------------------------------------------------------------------------
# the executor: what a recurrent state cannot do is refused, by sentence
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def executor(params):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    return BatchedExecutor(CFG, params, lanes=3, max_len=64)


def test_a_replay_into_the_middle_is_refused_before_the_frontier_moves(executor, params, reference):
    ids = _ids(12, seed=4)
    want = reference.logprobs(params, ids + [1, 2], 14, published(CFG))
    r = executor.process("a", {"tokens": [ids], "start_pos": 0, "real_len": 12})
    np.testing.assert_allclose(_logp(r["logits"][0]), want[11], atol=TOL)
    state = np.asarray(executor.engine.cache.s)
    with pytest.raises(ValueError, match="does not roll\\s+back; restart the session at 0"):
        executor.process("a", {"tokens": [ids[6:]], "start_pos": 6, "real_len": 6})
    assert executor.session_lengths()["a"] == 12
    np.testing.assert_array_equal(np.asarray(executor.engine.cache.s), state)
    r = executor.process("a", {"tokens": [[1]], "start_pos": 12, "real_len": 1})  # goes on
    np.testing.assert_allclose(_logp(r["logits"][0]), want[12], atol=TOL)
    r = executor.process("a", {"tokens": [ids], "start_pos": 0, "real_len": 12})  # restart at 0
    np.testing.assert_allclose(_logp(r["logits"][0]), want[11], atol=TOL)
    executor.end_session("a")


def test_stats_keep_the_state_apart_from_what_grows_with_tokens(executor):
    st = executor.stats()
    la, lm = CFG.layers_of("attention"), CFG.layers_of("mamba")
    per_session = lm * (CFG.mamba_heads * CFG.mamba_head_dim * CFG.mamba_state * 4
                        + (CFG.mamba_conv - 1) * CFG.mamba_conv_dim * 4)  # float32 model
    assert st["state_bytes_per_session"] == per_session
    assert st["state_bytes"] == 3 * per_session
    assert st["kv_bytes_per_token"] == la * 2 * CFG.num_kv_heads * CFG.head_dim * 4
    assert st["kv_cache_bytes"] == st["kv_bytes_per_token"] * 3 * 64
    dense = get_config("tiny")
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    other = BatchedExecutor(dense, qwen3.init_params(dense, jax.random.PRNGKey(0)), 2, 16).stats()
    assert "state_bytes" not in other and "state_bytes_per_session" not in other


@pytest.mark.parametrize("what", ["fork", "engine-fork", "pin", "export", "delta", "import", "paged"])
def test_what_needs_a_snapshot_of_state_is_refused(executor, what):
    executor.process("p", {"tokens": [_ids(8)], "start_pos": 0, "real_len": 8})
    try:
        if what == "fork":  # its refusal is False: the caller prefills
            assert executor.fork_session("child", "p", 4) is False
            return
        with pytest.raises(ValueError, match="tiny-granite-h"):
            {"engine-fork": lambda: executor.engine.fork_lane(0, 1, 4),
             "pin": lambda: executor.pin_prefix([1, 2, 3]),
             "export": executor.export_sessions,
             "delta": lambda: executor.export_session_delta("p", 0),
             "import": lambda: executor.import_session("q", {}),
             "paged": lambda: BlockPool(CFG, CFG.num_layers, 2, 32, block_size=16)}[what]()
    finally:
        executor.end_session("p")


def test_k_step_decode_runs_the_model_unchanged(executor, params, reference):
    """`decode_steps`: K tokens a dispatch through models/qwen3.decode_k,
    whose rows carry `write_mask`; greedy, against the reference's argmax."""
    ids = _ids(10, seed=6)
    r = executor.process("k", {"tokens": [ids], "start_pos": 0, "real_len": 10})
    tok = int(np.argmax(r["logits"][0]))
    r = executor.process("k", {"tokens": [[tok]], "start_pos": 10, "real_len": 1,
                               "decode_steps": 4, "sampling": {"temperature": 0.0}})
    got = r["tokens"][0]
    assert len(got) == 4
    want = reference.logprobs(params, ids + [tok] + got[:-1], 5, published(CFG))
    assert [int(row.argmax()) for row in want] == [tok] + got
    executor.end_session("k")


def test_quant_int8_runs_the_model_and_reaches_the_state_layers(params):
    from inferd_tpu.ops import quant

    q = quant.apply_quant_mode("int8", params, tie_word_embeddings=True)
    try:
        assert isinstance(q["state_layers"]["in_proj"], quant.QuantWeight)
        assert isinstance(q["state_layers"]["out_proj"], quant.QuantWeight)
        assert isinstance(q["layers"]["q_proj"], quant.QuantWeight)
        ids = jnp.asarray([_ids(12)])
        sound, _, _ = qwen3.forward(params, CFG, ids)
        got, _, _ = qwen3.forward(q, CFG, ids)
        diff = float(jnp.abs(_logp(got) - _logp(sound)).max())
        assert 1e-6 < diff < 5e-2  # another precision, the same model
    finally:
        quant.QDOT_MODE = "dequant"


# ---------------------------------------------------------------------------
# the bytes, by arithmetic
# ---------------------------------------------------------------------------


def test_the_cache_of_the_published_preset_is_the_arithmetic():
    cfg = get_config("granite-4.0-h-micro")
    c = jax.eval_shape(lambda: KVCache.create(cfg, cfg.num_layers, 32, 4096))
    assert c.s.shape == (36, 32, 64, 64, 128) and c.s.dtype == jnp.float32
    assert c.conv.shape == (36, 32, 3, 4352) and c.conv.dtype == jnp.bfloat16
    # 8 kv heads of 64, narrower than a tile: ONE row of 512 a token (core.cache.rows_layout)
    assert c.k.shape == c.v.shape == (4, 32, 4096, 512) and c.k.dtype == jnp.bfloat16
    assert c.layout(cfg) == "rows"
    per_session = 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    assert per_session == 76_437_504  # 75.5 MB of state + 0.94 MB of columns
    assert c.state_bytes == 32 * per_session
    assert c.nbytes == c.state_bytes + 32 * 4096 * 8192  # 8 192 B a token in four layers
    assert round(c.nbytes / 1e9, 2) == 3.52
    kinds = [type(e) for e in c.entries(cfg)]
    assert kinds == [StateEntry, type(c.entries(cfg)[1])] and len(kinds) == 2  # a stack a kind
    back = c.with_entries(c.entries(cfg))
    assert back.s is c.s and back.conv is c.conv and back.k is c.k
    kv8 = dataclasses.replace(cfg, kv_dtype="float8_e4m3fn")  # --kv-dtype: k and v only
    c8 = jax.eval_shape(lambda: KVCache.create(kv8, kv8.num_layers, 32, 4096))
    assert c8.k.dtype == jnp.float8_e4m3fn and c8.s.dtype == jnp.float32
    assert c8.conv.dtype == jnp.bfloat16


def test_the_published_preset_is_the_published_config():
    cfg = PRESETS["granite-4.0-h-micro"]
    assert cfg.layer_type_names.count("mamba") == 36
    assert [i for i, k in enumerate(cfg.layer_type_names) if k == "attention"] == [5, 15, 25, 35]
    assert cfg.attn_scale == 0.015625 and cfg.position_embedding == "nope"
    assert (cfg.mamba_inner, cfg.mamba_conv_dim) == (4096, 4352)
    shapes = jax.eval_shape(lambda: qwen3.init_params(cfg, jax.random.PRNGKey(0)))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2_048  # 3.191 G
    assert shapes["state_layers"]["in_proj"].shape == (36, 2048, 8512)
    held = dataclasses.replace(cfg, state_dtype="bfloat16")  # a lower precision of the state alone
    c16 = jax.eval_shape(lambda: KVCache.create(held, held.num_layers, 2, 64))
    assert c16.s.dtype == jnp.bfloat16 and c16.k.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="one period"):
        dataclasses.replace(cfg, num_layers=39)


# ---------------------------------------------------------------------------
# no scalar is silently 1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("field, other", [
    ("embedding_multiplier", 6.0), ("residual_multiplier", 0.5), ("logits_scaling", 4.0),
    ("query_pre_attn_scalar", 16.0), ("position_embedding", "rope"),
])
def test_each_scalar_moves_the_logits(params, field, other):
    # queries and keys drawn at 0.02 give scores near 0 and a flat softmax,
    # which neither a scale nor a rotation moves: sharpen them for this test
    sharp = dict(params, layers=dict(
        params["layers"], q_proj=params["layers"]["q_proj"] * 15, k_proj=params["layers"]["k_proj"] * 15))
    ids = jnp.asarray([_ids(12)])
    base, _, _ = qwen3.forward(sharp, CFG, ids)
    moved, _, _ = qwen3.forward(sharp, dataclasses.replace(CFG, **{field: other}), ids)
    assert float(jnp.abs(moved - base).max()) > 5e-4, field  # on logits of size 0.06


def test_the_seeded_recurrence_is_neither_dead_nor_saturated(params):
    """The draw of init_state_layer_params: a step's decay exp(d A) at the
    drawn dt_bias spreads over about 0.5-0.999."""
    sl = params["state_layers"]
    step = np.log1p(np.exp(np.asarray(sl["dt_bias"], np.float64)))
    decay = np.exp(-step * np.exp(np.asarray(sl["A_log"], np.float64)))
    assert 0.55 < decay.min() < 0.9 and 0.99 < decay.max() < 1.0
    assert (step > 0.009).all() and (step < 0.51).all()


# ---------------------------------------------------------------------------
# run_node: one case a refused path
# ---------------------------------------------------------------------------

REFUSED = {
    "mesh": (["--mesh", "pp=2"], "--mesh"),
    "stage-lanes": (["--stage-lanes", "2"], "--stage-lanes"),
    "paged-kv": (["--batch-lanes", "2", "--paged-kv", "16"], "--paged-kv"),
    "spec": (["--batch-lanes", "2", "--spec-draft-layers", "1"], "--spec-draft-layers"),
    "lora": (["--batch-lanes", "2", "--lora", "/nowhere"], "--lora"),
    "adapters": (["--batch-lanes", "2", "--adapters", "/nowhere"], "--adapters"),
    "standby-repl": (["--batch-lanes", "2", "--standby-repl"], "--standby-repl"),
    "no-lanes": ([], "serving without --batch-lanes"),
    "stages": (["--num-stages", "2"], "serving without --batch-lanes"),
}


@pytest.mark.parametrize("path", sorted(REFUSED))
def test_run_node_refuses_by_has_state_layers(path, tmp_path):
    from inferd_tpu.tools import run_node

    flags, names = REFUSED[path]
    args = run_node.build_parser().parse_args(
        ["--model", "tiny-granite-h", "--parts", str(tmp_path), "--device", "cpu", *flags])
    with pytest.raises(SystemExit, match="tiny-granite-h cannot be served with") as e:
        asyncio.run(run_node._run(args))
    assert names in str(e.value)


def test_run_node_refuses_a_manifest_of_several_stages_and_lets_the_lane_path_through():
    from inferd_tpu.tools import run_node

    args = argparse.Namespace(
        mesh="", stage_lanes=0, paged_kv=0, quant="int8", spec_draft_layers=0, lora="",
        adapters="", standby_repl=False, backend="qwen3", batch_lanes=32)
    run_node.check_servable(CFG, args)  # --quant and --kv-dtype stay open
    with pytest.raises(SystemExit, match="a manifest of several stages"):
        run_node.check_servable(CFG, args, num_stages=2)
    renamed = dataclasses.replace(CFG, name="something-else")  # by what it has, not by name
    with pytest.raises(SystemExit, match="something-else cannot be served with --paged-kv"):
        run_node.check_servable(renamed, argparse.Namespace(**{**vars(args), "paged_kv": 16}))
    args.batch_lanes = 0
    run_node.check_servable(get_config("tiny"), args)  # other models: nothing to refuse


def test_lower_layers_refuse_too(params):
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params

    with pytest.raises(ValueError, match="tiny-granite-h"):
        extract_stage_params(params, CFG, Manifest.even_split("tiny-granite-h", 2).stage_spec(0))
    x = jnp.zeros((1, 2, CFG.hidden_size), jnp.float32)
    with pytest.raises(ValueError, match="whole periods"):  # a stage's stack alone
        qwen3.forward_layers(params["layers"], CFG, x, jnp.arange(2)[None])
    lp = jax.tree.map(lambda a: a[0], params["state_layers"])
    with pytest.raises(ValueError, match="tiny-granite-h"):
        qwen3.decoder_layer(lp, CFG, x, None, None, jnp.arange(2)[None], tp_axis="tp")


# ---------------------------------------------------------------------------
# weights: the checkpoint and the published names
# ---------------------------------------------------------------------------


def test_the_checkpoint_carries_both_stacks(params, tmp_path):
    from inferd_tpu.parallel.stages import Manifest, load_stage_checkpoint, split_and_save

    paths = split_and_save(params, CFG, Manifest.even_split("tiny-granite-h", 1), str(tmp_path))
    loaded, spec, name = load_stage_checkpoint(paths[0])
    assert name == "tiny-granite-h" and spec.num_stages == 1
    assert sorted(loaded) == ["embed", "final_norm", "layers", "state_layers"]
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)


def test_loader_maps_the_published_names(params):
    from inferd_tpu.models.loader import params_from_hf_state_dict

    host = jax.tree.map(np.asarray, params)  # one transfer, then numpy
    sd = {"model.embed_tokens.weight": host["embed"], "model.norm.weight": host["final_norm"]}
    seen = {"attention": 0, "mamba": 0}
    for i, kind in enumerate(CFG.layer_type_names):
        stack = host["layers" if kind == "attention" else "state_layers"]
        lp = {k: v[seen[kind]] for k, v in stack.items()}
        seen[kind] += 1
        pre = f"model.layers.{i}"
        sd[f"{pre}.input_layernorm.weight"] = lp["input_norm"]
        sd[f"{pre}.post_attention_layernorm.weight"] = lp["post_norm"]
        sd[f"{pre}.shared_mlp.input_linear.weight"] = np.concatenate(
            [lp["gate_proj"].T, lp["up_proj"].T])
        sd[f"{pre}.shared_mlp.output_linear.weight"] = lp["down_proj"].T
        if kind == "attention":
            for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
                sd[f"{pre}.self_attn.{proj}.weight"] = lp[proj].T
            continue
        sd[f"{pre}.mamba.in_proj.weight"] = lp["in_proj"].T
        sd[f"{pre}.mamba.out_proj.weight"] = lp["out_proj"].T
        sd[f"{pre}.mamba.conv1d.weight"] = lp["conv_w"].T[:, None, :]  # [channels, 1, K]
        sd[f"{pre}.mamba.conv1d.bias"] = lp["conv_b"]
        sd[f"{pre}.mamba.norm.weight"] = lp["gate_norm"]
        for name in ("dt_bias", "A_log", "D"):
            sd[f"{pre}.mamba.{name}"] = lp[name]
    loaded = jax.tree.map(np.asarray, params_from_hf_state_dict(CFG, sd))
    assert jax.tree.structure(loaded) == jax.tree.structure(host)
    jax.tree.map(np.testing.assert_array_equal, loaded, host)
