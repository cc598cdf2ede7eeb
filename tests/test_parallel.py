# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Mesh-parallel correctness: ring attention, TP/EP layer parity vs the
single-device model, and the full pipelined train step (all five axes)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from inferd_tpu.config import TINY, TINY_GEMMA2, TINY_GPT_OSS, TINY_MOE, TINY_QWEN2
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.ring import ring_gqa_attention
from inferd_tpu.parallel.tp import sharded_forward_layers
from inferd_tpu.parallel.train import make_train_step


def _mesh(dp=1, pp=1, sp=1, tp=1, ep=1):
    plan = meshlib.MeshPlan(dp=dp, pp=pp, sp=sp, tp=tp, ep=ep)
    return plan, meshlib.make_mesh(plan)


def test_ring_attention_matches_full():
    b, s, nq, nkv, d = 2, 16, 4, 2, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, nq, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, nkv, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    ref = qwen3.gqa_attention(q, k, v, positions, jnp.int32(s), kv_positions=positions)

    plan, mesh = _mesh(sp=4)

    def f(q, k, v, pos):
        return ring_gqa_attention(q, k, v, pos, pos, "sp")

    out = jax.jit(
        jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_window_softcap_scale_matches_full():
    """Ring attention with the Gemma-2 recipe (sliding window + logit
    softcap + query_pre_attn_scalar scale) == full-sequence gqa_attention —
    the round-2 sp-axis capability cliff (tp.py raised NotImplementedError
    for these configs), lifted."""
    b, s, nq, nkv, d = 2, 16, 4, 2, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(kq, (b, s, nq, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, nkv, d), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    scale, softcap, window = 1.0 / 5.6, 30.0, 6

    ref = qwen3.gqa_attention(
        q, k, v, positions, jnp.int32(s), kv_positions=positions,
        scale=scale, softcap=softcap, window=jnp.int32(window),
    )
    plan, mesh = _mesh(sp=4)

    def f(q, k, v, pos):
        return ring_gqa_attention(
            q, k, v, pos, pos, "sp",
            scale=scale, softcap=softcap, window=jnp.int32(window),
        )

    out = jax.jit(
        jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_sinks_matches_full():
    """GPT-OSS attention sinks join the ring's online softmax exactly once,
    at finalize — parity with the closed-form full-sequence path."""
    b, s, nq, nkv, d = 1, 16, 4, 2, 8
    kq, kk, kv, ks = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(kq, (b, s, nq, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, nkv, d), jnp.float32)
    # large positive sink on one head makes the denominator term decisive
    sinks = jax.random.normal(ks, (nq,), jnp.float32).at[1].set(4.0)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    ref = qwen3.gqa_attention(
        q, k, v, positions, jnp.int32(s), kv_positions=positions, sinks=sinks,
    )
    plan, mesh = _mesh(sp=4)

    def f(q, k, v, pos):
        return ring_gqa_attention(q, k, v, pos, pos, "sp", sinks=sinks)

    out = jax.jit(
        jax.shard_map(
            f, mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
    )(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "cfg", [TINY, TINY_MOE, TINY_GEMMA2, TINY_GPT_OSS],
    ids=["dense", "moe", "gemma2", "gptoss"],
)
def test_sharded_layers_match_single_device(cfg):
    b, s = 2, 16
    key = jax.random.PRNGKey(1)
    layers = qwen3.init_layer_params(cfg, key)
    hidden = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.hidden_size), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    ref, _, _ = qwen3.forward_layers(layers, cfg, hidden, positions)

    plan, mesh = _mesh(sp=2, tp=2, ep=2 if cfg.is_moe else 1)
    lspecs = meshlib.layer_param_specs(cfg)

    def f(layers_local, h, pos):
        return sharded_forward_layers(layers_local, cfg, h, pos, "tp", "sp")

    out = jax.jit(
        jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(lspecs, P(None, "sp", None), P(None, "sp")),
            out_specs=P(None, "sp", None),
            check_vma=False,
        )
    )(layers, hidden, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize(
    "cfg,plan_kw",
    [
        (TINY, dict(dp=2, pp=2, tp=2)),
        (TINY_MOE, dict(pp=2, sp=2, tp=2)),
    ],
    ids=["dense-dp-pp-tp", "moe-pp-sp-tp"],
)
def test_train_step_loss_decreases(cfg, plan_kw):
    plan, mesh = _mesh(**plan_kw)
    meshlib.check_divisibility(cfg, plan)
    step = make_train_step(cfg, mesh, plan, learning_rate=5e-2)

    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    mb, batch, seq = 2, 2 * plan.dp, 8 * plan.sp
    data = jax.random.randint(
        jax.random.PRNGKey(3), (mb, batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )
    tokens, targets = data[..., :-1], data[..., 1:]

    losses = []
    for _ in range(4):
        params, loss = step(params, tokens, targets)
        losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize(
    "cfg,plan_kw",
    [
        (TINY, dict(dp=2)),
        (TINY, dict(pp=2)),
        (TINY, dict(sp=2)),
        (TINY, dict(tp=2)),
        (TINY_MOE, dict(ep=2)),
        (TINY_QWEN2, dict(tp=2)),
        (TINY, dict(dp=2, pp=2, tp=2)),
        (TINY_MOE, dict(pp=2, sp=2, ep=2)),
        # the round-2 sp-axis capability cliff, lifted: sliding windows,
        # softcaps, sinks, and non-head_dim scales train with sp > 1
        (TINY_GEMMA2, dict(sp=2, tp=2)),
        (TINY_GPT_OSS, dict(sp=2, ep=2)),
    ],
    ids=["dp2", "pp2", "sp2", "tp2", "ep2", "qwen2-tp2", "dense-8dev",
         "moe-8dev", "gemma2-sp2tp2", "gptoss-sp2ep2"],
)
def test_train_step_matches_single_device(cfg, plan_kw):
    """One train step on a multi-device plan must produce the SAME updated
    params as the single-device plan — catches gradient mis-scaling (e.g.
    effective lr silently growing with device count) and wrong grad sync."""
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    mb, batch, seq = 2, 4, 16
    data = jax.random.randint(
        jax.random.PRNGKey(5), (mb, batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )
    tokens, targets = data[..., :-1], data[..., 1:]

    plan1, mesh1 = _mesh()
    ref_params, ref_loss = make_train_step(cfg, mesh1, plan1, learning_rate=1e-2)(
        params, tokens, targets
    )

    plan, mesh = _mesh(**plan_kw)
    got_params, got_loss = make_train_step(cfg, mesh, plan, learning_rate=1e-2)(
        params, tokens, targets
    )

    np.testing.assert_allclose(float(got_loss), float(ref_loss), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(ref_params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_params))
    for path, ref_leaf in flat_ref:
        got_leaf = flat_got[path]
        np.testing.assert_allclose(
            np.asarray(got_leaf, np.float32),
            np.asarray(ref_leaf, np.float32),
            atol=2e-5,
            rtol=2e-5,
            err_msg=f"param {jax.tree_util.keystr(path)} diverged under plan {plan_kw}",
        )


def test_load_balance_loss_matches_hf():
    """tp.load_balance_loss == transformers' load_balancing_loss_func on the
    same router logits (the Switch-style aux the MoE training step adds)."""
    torch = pytest.importorskip("torch")
    from transformers.models.mixtral.modeling_mixtral import (
        load_balancing_loss_func,
    )

    from inferd_tpu.parallel.tp import load_balance_loss

    E, K, T = 8, 2, 64
    logits = np.random.RandomState(0).normal(size=(T, E)).astype(np.float32)
    want = float(
        load_balancing_loss_func((torch.from_numpy(logits),), E, K)
    )
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    _, topi = jax.lax.top_k(probs, K)
    got = float(load_balance_loss(probs, topi, E))
    assert got == pytest.approx(want, rel=1e-5)


@pytest.mark.parametrize(
    "plan_kw",
    [dict(ep=2), dict(pp=2, ep=2), dict(pp=2, sp=2, tp=2), dict(dp=2, ep=2)],
    ids=["ep2", "pp2-ep2", "pp2-sp2-tp2", "dp2-ep2"],
)
def test_moe_aux_loss_matches_single_device(plan_kw):
    """The load-balancing aux term must be invariant to the mesh plan: same
    loss and same updated params as the 1-device plan (pins the 1/(ep*tp)
    per-rank scaling against the router's grad-sync psum, the GPipe
    bubble-tick masking, and the report-side psum)."""
    cfg = TINY_MOE
    params = qwen3.init_params(cfg, jax.random.PRNGKey(1))
    mb, batch, seq = 2, 4, 16
    data = jax.random.randint(
        jax.random.PRNGKey(6), (mb, batch, seq + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )
    tokens, targets = data[..., :-1], data[..., 1:]
    kw = dict(learning_rate=1e-2, moe_aux_coef=0.01)

    plan1, mesh1 = _mesh()
    ref_params, ref_loss = make_train_step(cfg, mesh1, plan1, **kw)(
        params, tokens, targets
    )
    # the aux term must actually move the objective
    _, base_loss = make_train_step(cfg, mesh1, plan1, learning_rate=1e-2)(
        params, tokens, targets
    )
    assert float(ref_loss) != pytest.approx(float(base_loss), rel=1e-6)

    plan, mesh = _mesh(**plan_kw)
    got_params, got_loss = make_train_step(cfg, mesh, plan, **kw)(
        params, tokens, targets
    )
    np.testing.assert_allclose(float(got_loss), float(ref_loss), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves_with_path(ref_params)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_params))
    for path, ref_leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(flat_got[path], np.float32),
            np.asarray(ref_leaf, np.float32),
            atol=2e-5, rtol=2e-5,
            err_msg=f"param {jax.tree_util.keystr(path)} diverged under {plan_kw}",
        )


def test_pipeline_forward_matches_single_device():
    """The GPipe schedule must compute exactly the plain stacked forward."""
    cfg = TINY
    plan, mesh = _mesh(pp=2)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    mb, b, s = 3, 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(4), (mb, b, s), 0, cfg.vocab_size, dtype=jnp.int32)

    # reference: plain forward per microbatch
    ref = []
    for i in range(mb):
        logits, _, _ = qwen3.forward(params, cfg, tokens[i])
        ref.append(logits)
    ref = jnp.stack(ref)

    from inferd_tpu.parallel.train import _pipeline_forward, _unembed_local

    pspecs = meshlib.model_param_specs(cfg, layer_axis="pp")

    def f(p, toks):
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        out = _pipeline_forward(p, cfg, toks, positions, None)
        out = jax.lax.psum(out, "pp")  # valid only on last rank; others zero
        return _unembed_local(p, cfg, out.reshape(mb * b, s, -1)).reshape(mb, b, s, -1)

    got = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(pspecs, P()), out_specs=P(), check_vma=False
        )
    )(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_grad_clip_and_schedule_match_single_device(devices8):
    """grad_clip_norm + warmup/cosine schedule over a sharded mesh must
    equal the same update computed on one device (the global-norm psum per
    shard axis has to reconstruct the exact full-tree norm)."""
    from inferd_tpu.parallel.train import init_train_state, make_train_step

    cfg = TINY
    key = jax.random.PRNGKey(0)
    params = qwen3.init_params(cfg, key)
    mb, b, s = 2, 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (mb, b, s), 0, cfg.vocab_size, jnp.int32)
    tgts = jax.random.randint(jax.random.PRNGKey(2), (mb, b, s), 0, cfg.vocab_size, jnp.int32)

    kw = dict(
        learning_rate=3e-2, optimizer="adam",
        grad_clip_norm=0.5, warmup_steps=3, decay_steps=10,
    )
    plan1 = meshlib.MeshPlan()
    mesh1 = meshlib.make_mesh(plan1, jax.devices()[:1])
    step1 = make_train_step(cfg, mesh1, plan1, **kw)
    st1 = step1.init_state(meshlib.shard_params(params, cfg, mesh1))
    plan8 = meshlib.MeshPlan(dp=2, pp=2, tp=2)
    mesh8 = meshlib.make_mesh(plan8, devices8)
    step8 = make_train_step(cfg, mesh8, plan8, **kw)
    st8 = step8.init_state(
        meshlib.shard_params(params, cfg, mesh8, layer_axis="pp")
    )

    for i in range(3):  # cross warmup into decay; clip engages on step 1
        st1, loss1 = step1(st1, toks, tgts)
        st8, loss8 = step8(st8, toks, tgts)
        np.testing.assert_allclose(float(loss1), float(loss8), rtol=2e-4, atol=2e-4)
    for a, b_ in zip(jax.tree.leaves(st1.params), jax.tree.leaves(st8.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32), rtol=3e-3, atol=3e-3
        )
