"""Tensor- and expert-parallel decoder forward with explicit collectives.

The shard_map compute path: every function here runs *per-rank* inside
`jax.shard_map` over the mesh of inferd_tpu.parallel.mesh, with Megatron-style
sharding — column-parallel q/k/v/gate/up (output dim sharded over `tp`, so
attention heads and MLP hidden are local), row-parallel o/down (input dim
sharded, partial products `psum`'d over `tp`). MoE experts are sharded over
the combined ('ep','tp') axes: each rank runs the routed layer over the experts
it holds (grouped by expert, models.qwen3.moe_routed_part) and a psum combines.
Sequence parallelism composes orthogonally: when `sp_axis` is given the
sequence axis is sharded and attention runs as ring attention
(inferd_tpu.parallel.ring).

This is new TPU-native capability relative to the reference, which has no
tensor/expert/sequence parallelism at all (SURVEY §2.1) — its only axis is
the inter-node pipeline. The math (RMSNorm, RoPE, GQA with q/k norm, SwiGLU,
softmax-top-k routing) is shared with the single-device model in
inferd_tpu.models.qwen3; parity is tested in tests/test_parallel.py.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from inferd_tpu.config import ModelConfig
from inferd_tpu.ops.quant import qdot
from inferd_tpu.models.qwen3 import (
    act_fn,
    apply_rope,
    gqa_attention,
    layer_windows,
    experts_held,
    moe_routed_part,
    router_logits,
    rms_norm,
    rope_cos_sin,
)
from inferd_tpu.parallel.ring import ring_gqa_attention

Params = Dict[str, Any]


def _psum(x: jax.Array, axes) -> jax.Array:
    for ax in axes:
        x = lax.psum(x, ax)
    return x


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_replicated(x: jax.Array, axes: Tuple[str, ...]) -> jax.Array:
    """Megatron's `g` operator: psum forward, identity backward.

    Under shard_map with check_vma=False, JAX cannot prove a psum's
    cotangent is replicated, so it transposes psum to psum — multiplying a
    replicated cotangent by the axis size (verified: grads through a plain
    lax.psum come out N_axis× too large). Everything consuming these
    combined partial products (residual stream, loss) IS replicated across
    the axis in this Megatron layout, so the correct transpose is identity
    per rank. Use for every in-forward partial-sum combine (attention
    out-proj, MLP down-proj, MoE expert combine).
    """
    return _psum(x, axes)


def _psum_replicated_fwd(x, axes):
    return _psum(x, axes), None


def _psum_replicated_bwd(axes, _, g):
    return (g,)


psum_replicated.defvjp(_psum_replicated_fwd, _psum_replicated_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def enter_sharded(x: jax.Array, axes: Tuple[str, ...]) -> jax.Array:
    """Megatron's `f` operator: identity forward, psum backward.

    Marks the boundary where a replicated activation enters `axes`-sharded
    compute. In per-rank AD (shard_map) the activation's cotangent at this
    point is only the local shard's partial contribution; the backward psum
    restores the full cotangent on every rank, so upstream REPLICATED
    params get complete, rank-identical gradients with no post-hoc sync
    (post-hoc psum over-counts any gradient path that bypasses the sharded
    region — e.g. embeddings reach the loss through the residual stream
    without touching a tp-sharded matmul).
    """
    return x


def _enter_sharded_fwd(x, axes):
    return x, None


def _enter_sharded_bwd(axes, _, g):
    return (_psum(g, axes),)


enter_sharded.defvjp(_enter_sharded_fwd, _enter_sharded_bwd)


def _route_fractions(probs: jax.Array, topi: jax.Array, num_experts: int):
    """(f [K, E] fraction of tokens routed per k-slot, P [E] mean router
    prob) over the LOCAL tokens — the two means the load-balance loss
    multiplies."""
    one_hot = jax.nn.one_hot(topi, num_experts, dtype=jnp.float32)  # [T, K, E]
    return jnp.mean(one_hot, axis=0), jnp.mean(probs, axis=0)


def load_balance_loss(probs: jax.Array, topi: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style router load-balancing loss, matching HF's
    load_balancing_loss_func exactly (tests pin it): E * sum_e f[k,e]*P[e],
    where f is the per-k-slot fraction of tokens routed to e and P the mean
    router probability. probs [T, E] float32, topi [T, K]."""
    f, p = _route_fractions(probs, topi, num_experts)
    return num_experts * jnp.sum(f * p[None, :])


def moe_mlp_sharded(
    lp: Params,
    cfg: ModelConfig,
    x: jax.Array,  # [B, S, H]
    expert_axes: Tuple[str, ...] = ("ep", "tp"),
    return_aux: bool = False,
    aux_token_axes: Tuple[str, ...] = (),
) -> jax.Array:
    """Expert-parallel MoE: router is replicated, expert weights hold only
    the local expert slice; each rank computes its local experts' (masked)
    contribution and the outputs psum-combine over the expert axes.

    return_aux: also return the load-balancing loss for this block, SCALED
    by 1/prod(expert_axes sizes). The router's gradient sync
    (mesh.grad_sync_axes) psums over the expert axes because every routed
    path holds a partial contribution — but the aux term is computed
    identically on every (ep, tp) rank (its inputs sit before the expert
    shard), so an unscaled aux would over-count by the axis product after
    that psum. The scaling makes per-rank partials sum to the true value
    for both the loss report and the gradient.

    aux_token_axes: mesh axes the TOKENS are sharded over (dp, sp). The
    loss multiplies two token-means (f * P), so per-shard products differ
    from the global product — the route fractions psum-combine over these
    axes first (psum_replicated: identity backward, each rank's cotangent
    reaches only its own shard's mean), making the aux objective exactly
    the single-device value regardless of the mesh plan."""
    b, s, h = x.shape
    xt = x.reshape(b * s, h)
    # every path from here (router AND experts) is sharded over expert_axes
    xt = enter_sharded(xt, tuple(expert_axes))
    e_local = experts_held(lp["gate_proj"])
    rank = jnp.int32(0)
    stride = 1
    for ax in reversed(expert_axes):
        rank = rank + lax.axis_index(ax) * stride
        stride *= lax.axis_size(ax)
    # the one routed layer (models.qwen3.moe_routed_part: routing modes,
    # silu or GPT-OSS clamped GLU with biases, grouped by expert, or dense
    # for a QuantWeight under --quant) over the LOCAL expert slice, told
    # where in the router's width it lies
    out, topi = moe_routed_part(lp, cfg, xt, rank * e_local)
    out = psum_replicated(out, tuple(expert_axes))
    if return_aux:
        # the aux always uses softmax-over-all probabilities (the HF
        # load-balancing formula), independent of the routing mode
        probs = jax.nn.softmax(router_logits(lp, cfg, xt), axis=-1)
        f, p = _route_fractions(probs, topi, cfg.num_experts)
        n_shards = 1.0
        for ax in aux_token_axes:
            n_shards *= lax.axis_size(ax)
        f = psum_replicated(f / n_shards, tuple(aux_token_axes))
        p = psum_replicated(p / n_shards, tuple(aux_token_axes))
        denom = 1.0
        for ax in expert_axes:
            denom *= lax.axis_size(ax)
        aux = cfg.num_experts * jnp.sum(f * p[None, :]) / denom
        return out.reshape(b, s, h), aux
    return out.reshape(b, s, h)


def sharded_decoder_layer(
    lp: Params,
    cfg: ModelConfig,
    hidden: jax.Array,  # [B, S_local, H]
    cos: jax.Array,
    sin: jax.Array,
    positions: jax.Array,  # [B, S_local] absolute positions of local tokens
    tp_axis: str = "tp",
    sp_axis: Optional[str] = None,
    window: Optional[jax.Array] = None,  # sliding window (traced; <=0 = global)
    with_aux: bool = False,  # also return the MoE load-balance aux loss
    aux_token_axes: Tuple[str, ...] = (),  # token-sharding axes (see moe_mlp_sharded)
    return_kv: bool = False,  # also return this block's (roped) K/V
) -> jax.Array:
    """One decoder block on local head/expert shards, full-sequence (no KV
    cache — the training / prefill regime). Two psums per block (attention
    out-proj and MLP down-proj), the Megatron minimum.

    with_aux: return (hidden, aux) where aux is this block's (scaled)
    router load-balancing loss — 0.0 for dense configs.
    return_kv: additionally return (k, v) [B, S_local, Nkv_local, D] —
    post-rope, exactly what the cached serving path stores — so a
    sequence-parallel PREFILL can populate the decode KV cache
    (parallel.infer.make_sp_prefill_pass)."""
    b, s, _ = hidden.shape
    d = cfg.head_dim
    p1 = cfg.rms_norm_plus_one
    nq_local = lp["q_proj"].shape[-1] // d
    nkv_local = lp["k_proj"].shape[-1] // d

    x = rms_norm(hidden, lp["input_norm"], cfg.rms_norm_eps, p1)
    x = enter_sharded(x, (tp_axis,))  # q/k/v are column-parallel over tp
    q = qdot(x, lp["q_proj"])  # qdot: plain arrays fall through to @,
    k = qdot(x, lp["k_proj"])  # quantized leaves contract natively — the
    v = qdot(x, lp["v_proj"])  # sp/tp path serves --quant params too
    if cfg.attn_bias:  # Qwen2: bias shards follow the column-parallel output
        q = q + lp["q_bias"]
        k = k + lp["k_bias"]
        v = v + lp["v_bias"]
    q = q.reshape(b, s, nq_local, d)
    k = k.reshape(b, s, nkv_local, d)
    v = v.reshape(b, s, nkv_local, d)
    if cfg.qk_norm:  # Qwen3
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if sp_axis is not None:
        attn = ring_gqa_attention(
            q, k, v, positions, positions, sp_axis,
            scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap, window=window,
            sinks=lp["sinks"] if cfg.attn_sinks else None,
        )
    else:
        attn = gqa_attention(
            q, k, v, positions, jnp.int32(s), kv_positions=positions,
            scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap, window=window,
            sinks=lp["sinks"] if cfg.attn_sinks else None,
        )

    attn_out = psum_replicated(qdot(attn, lp["o_proj"]), (tp_axis,))
    if cfg.o_bias:  # replicated bias joins AFTER the partial-sum combine
        attn_out = attn_out + lp["o_bias"]
    if cfg.norm_placement == "both":  # Gemma: post-norm the sublayer output pre-residual
        attn_out = rms_norm(attn_out, lp["post_norm"], cfg.rms_norm_eps, p1)
    hidden = hidden + attn_out.astype(hidden.dtype)

    pre_ffn = lp["pre_ffn_norm"] if cfg.norm_placement == "both" else lp["post_norm"]
    x = rms_norm(hidden, pre_ffn, cfg.rms_norm_eps, p1)
    aux = jnp.float32(0.0)
    if cfg.is_moe:
        if with_aux:
            mlp_out, aux = moe_mlp_sharded(
                lp, cfg, x, ("ep", tp_axis), return_aux=True,
                aux_token_axes=aux_token_axes,
            )
        else:
            mlp_out = moe_mlp_sharded(lp, cfg, x, ("ep", tp_axis))
    else:
        x = enter_sharded(x, (tp_axis,))  # gate/up are column-parallel over tp
        gate = act_fn(cfg)(qdot(x, lp["gate_proj"]))
        up = qdot(x, lp["up_proj"])
        mlp_out = psum_replicated(qdot(gate * up, lp["down_proj"]), (tp_axis,))
    if cfg.norm_placement == "both":
        mlp_out = rms_norm(mlp_out, lp["post_ffn_norm"], cfg.rms_norm_eps, p1)
    out = hidden + mlp_out.astype(hidden.dtype)
    if return_kv:
        return (out, (k, v), aux) if with_aux else (out, (k, v))
    return (out, aux) if with_aux else out


def sharded_forward_layers(
    local_layers: Params,  # stacked [L_local, ...] leaves (this rank's slice)
    cfg: ModelConfig,
    hidden: jax.Array,
    positions: jax.Array,
    tp_axis: str = "tp",
    sp_axis: Optional[str] = None,
    layer_offset=0,  # global index of local_layers[0] (sliding-window pattern)
    with_aux: bool = False,  # also return summed MoE load-balance aux loss
    aux_token_axes: Tuple[str, ...] = (),  # token-sharding axes (see moe_mlp_sharded)
    return_kv: bool = False,  # also return stacked per-layer (roped) K/V
) -> jax.Array:
    """Scan this rank's decoder-layer slice (one compiled body).

    with_aux: return (hidden, aux) where aux sums each layer's (scaled)
    router load-balancing loss over this rank's slice.
    return_kv: return (hidden, (k, v)) with k/v stacked per layer
    [L_local, B, S_local, Nkv_local, D] — the sp-prefill cache feed."""
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, cfg)
    n_local = jax.tree.leaves(local_layers)[0].shape[0]
    wins = layer_windows(cfg, n_local, layer_offset)

    if return_kv:
        if with_aux:
            # no caller needs KV + aux together yet; silently dropping the
            # aux would be worse than refusing
            raise NotImplementedError("return_kv does not compose with with_aux")

        def body_kv(h, xs):
            lp, w = xs
            h, kv = sharded_decoder_layer(
                lp, cfg, h, cos, sin, positions, tp_axis, sp_axis,
                window=w, return_kv=True,
            )
            return h, kv

        hidden, (ks, vs) = lax.scan(body_kv, hidden, (local_layers, wins))
        return hidden, (ks, vs)

    if with_aux:

        def body_aux(carry, xs):
            h, acc = carry
            lp, w = xs
            h, aux = sharded_decoder_layer(
                lp, cfg, h, cos, sin, positions, tp_axis, sp_axis,
                window=w, with_aux=True, aux_token_axes=aux_token_axes,
            )
            return (h, acc + aux), None

        (hidden, aux), _ = lax.scan(
            body_aux, (hidden, jnp.float32(0.0)), (local_layers, wins)
        )
        return hidden, aux

    def body(h, xs):
        lp, w = xs
        return sharded_decoder_layer(
            lp, cfg, h, cos, sin, positions, tp_axis, sp_axis, window=w
        ), None

    hidden, _ = lax.scan(body, hidden, (local_layers, wins))
    return hidden
