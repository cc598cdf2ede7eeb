"""Pure-JAX Qwen3 decoder — the framework's L0 model compute.

Capability parity with the reference's from-scratch torch blocks
(/root/reference/models/qwen3/server/qwen3_server_module.py:14-206 — RMSNorm,
SwiGLU MLP, RoPE, GQA with per-head q/k RMSNorm, pre-norm residual decoder
layer) re-designed TPU-first rather than translated:

  * params are a pytree of arrays, with all decoder layers STACKED on a
    leading axis — the layer loop is a `lax.scan` (one compiled layer body,
    fast XLA compile) and a pipeline stage is a slice `layers[a:b]` of the
    stacked pytree (stage partitioning is an array slice, not a class
    hierarchy like the reference's FirstStage/StageInner/LastStage,
    split_model.py:13-70).
  * weights are stored [in, out] so the hot matmuls are plain `x @ W`
    feeding the MXU; norms/softmax/RoPE run in float32, matmuls in bf16.
  * attention takes a preallocated KV buffer + length (functional cache,
    replaces the server-side mutable DynamicCache at
    qwen3_server_module.py:220,253) so jit sees static shapes.

Every function is pure; nothing here touches the network or the filesystem.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas.ops.tpu.megablox import gmm

from inferd_tpu.config import FFN_KINDS, STATE_KINDS, ModelConfig, yarn_mscale
from inferd_tpu.core import cache as cachelib
from inferd_tpu.ops import attention as attention_ops
from inferd_tpu.ops import lora as lora_ops
from inferd_tpu.ops.quant import Int4Weight, QuantWeight, qdot, qeinsum
from inferd_tpu.utils.platform import is_tpu

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _init_ffn_params(cfg: ModelConfig, w, n: int, dense: bool, ks, key) -> Params:
    """A layer stack's feed-forward leaves: the dense MLP, or (a model with
    experts, not `dense`) the router, the held experts and the shared one.
    `w(key, *shape)` draws a stacked projection, `ks` four keys (router, gate,
    up, down); what else is drawn folds `key`. An ungated feed-forward
    (cfg.ffn_gated False) has no `gate_proj`; experts that work in a latent
    (cfg.moe_latent_size) are that wide, between `latent_in_proj` and
    `latent_out_proj`."""
    h, i, dt = cfg.hidden_size, cfg.intermediate_size, cfg.jnp_dtype
    if not (cfg.is_moe and not dense):
        p = {"up_proj": w(ks[2], h, i), "down_proj": w(ks[3], i, h)}
        if cfg.ffn_gated:
            p["gate_proj"] = w(ks[1], h, i)
        return p
    p = {}
    e, mi = cfg.num_experts, cfg.moe_intermediate_size  # the experts HELD here
    he = cfg.moe_latent_size or h  # what an expert reads and writes
    p["router"] = w(ks[0], h, cfg.router_width)
    if cfg.moe_router_mode == "sigmoid_topk":
        # drawn so that the router's logits have unit variance whatever
        # the width (the scores spread over (0, 1)), and a selection bias
        # small and non-zero: of 256 experts' top 4 it changes the choice
        # of about half the tokens and leaves the load near even (a
        # deviation of 0.1 sends a third of the rows to one expert)
        p["router"] = (p["router"].astype(jnp.float32) * (50.0 / math.sqrt(h))).astype(dt)
        p["router_select_bias"] = 0.01 * jax.random.normal(
            jax.random.fold_in(key, 14), (n, cfg.router_width), dtype=jnp.float32)
    if cfg.ffn_gated:
        p["gate_proj"] = w(ks[1], e, he, mi)
    p["up_proj"] = w(ks[2], e, he, mi)
    p["down_proj"] = w(ks[3], e, mi, he)
    if cfg.seeded_routed_scale != 1.0:  # an expert that comes or goes at a near-tie moves little
        p["down_proj"] = (
            p["down_proj"].astype(jnp.float32) * cfg.seeded_routed_scale).astype(dt)
    if cfg.moe_latent_size:
        p["latent_in_proj"] = w(jax.random.fold_in(key, 19), h, he)
        p["latent_out_proj"] = w(jax.random.fold_in(key, 20), he, h)
    if cfg.router_bias:
        p["router_bias"] = jnp.zeros((n, e), dtype=dt)
    if cfg.moe_bias:
        p["gate_bias"] = jnp.zeros((n, e, mi), dtype=dt)
        p["up_bias"] = jnp.zeros((n, e, mi), dtype=dt)
        p["down_bias"] = jnp.zeros((n, e, h), dtype=dt)
    if cfg.n_shared_experts:
        si = cfg.n_shared_experts * mi
        if cfg.ffn_gated:
            p["shared_gate_proj"] = w(jax.random.fold_in(key, 10), h, si)
        p["shared_up_proj"] = w(jax.random.fold_in(key, 11), h, si)
        p["shared_down_proj"] = w(jax.random.fold_in(key, 12), si, h)
        if cfg.shared_expert_gate:  # Qwen3-Next: the shared expert's own gate, a vector
            p["shared_expert_gate"] = w(jax.random.fold_in(key, 15), h)
    return p


def _sublayer_norms(cfg: ModelConfig, norm: jax.Array, sublayer: str) -> Params:
    """A layer stack's RMSNorms around its two sublayers, each drawn as
    `norm`, by cfg.norm_placement. "before": `input_norm`, and `post_norm`
    before the feed-forward. "both": `post_norm` stands on the MIXER's output,
    and the feed-forward has `pre_ffn_norm` and `post_ffn_norm`. "after":
    `post_norm` on the mixer's output, `post_ffn_norm` on the
    feed-forward's, and no other. A stack of layers that are ONE `sublayer`
    ("mixer" or "ffn": cfg.single_sublayer, placement "before") has that
    sublayer's norm alone, under the name it has in a layer of two."""
    if cfg.single_sublayer:
        return {"input_norm" if sublayer == "mixer" else "post_norm": norm}
    names = {"before": ("input_norm", "post_norm"),
             "both": ("input_norm", "post_norm", "pre_ffn_norm", "post_ffn_norm"),
             "after": ("post_norm", "post_ffn_norm")}[cfg.norm_placement]
    return {name: norm for name in names}


def init_layer_params(
    cfg: ModelConfig, key: jax.Array, num_layers: Optional[int] = None,
    dense: bool = False,
) -> Params:
    """Stacked decoder-layer params: every leaf has leading dim `num_layers`.
    `dense` gives a model with experts its leading dense-MLP layers."""
    n = cfg.num_layers if num_layers is None else num_layers
    h, q, kv, d = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.head_dim
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 8)

    def w(k, *shape):
        return (jax.random.normal(k, (n, *shape), dtype=jnp.float32) * 0.02).astype(dt)

    # (1+w)-style norms (Gemma) store zero-centered weights: init to 0
    norm1 = jnp.zeros if cfg.rms_norm_plus_one else jnp.ones

    p = {
        # Gemma: around the MLP too; Olmo: outputs only
        **_sublayer_norms(cfg, norm1((n, h), dtype=dt), "mixer"),
        "k_proj": w(ks[1], h, kv),
        "v_proj": w(ks[2], h, kv),
        "o_proj": w(ks[3], q, h),
    }
    if cfg.q_lora_rank:  # latent attention's queries compressed too: two projections around a norm
        p["q_a_proj"] = w(jax.random.fold_in(key, 16), h, cfg.q_lora_rank)
        p["q_a_norm"] = jnp.ones((n, cfg.q_lora_rank), dtype=dt)
        p["q_b_proj"] = w(jax.random.fold_in(key, 17), cfg.q_lora_rank, q)
    else:
        p["q_proj"] = w(ks[0], h, q)
    if cfg.qk_norm:  # Qwen3's per-head q/k RMSNorm; Olmo's over the whole projection
        p["q_norm"] = norm1((n, q if cfg.qk_norm_flat else d), dtype=dt)
        p["k_norm"] = norm1((n, kv if cfg.qk_norm_flat else d), dtype=dt)
    if cfg.attn_bias:  # Qwen2's q/k/v projection biases
        p["q_bias"] = jnp.zeros((n, q), dtype=dt)
        p["k_bias"] = jnp.zeros((n, kv), dtype=dt)
        p["v_bias"] = jnp.zeros((n, kv), dtype=dt)
    if cfg.o_bias:  # GPT-OSS: bias on the output projection too
        p["o_bias"] = jnp.zeros((n, h), dtype=dt)
    if cfg.attn_sinks:  # GPT-OSS: per-q-head sink logits
        p["sinks"] = jnp.zeros((n, cfg.num_heads), dtype=dt)
    if cfg.attn_gate:  # afmoe: the attention output's gate, from the layer's input
        p["attn_gate_proj"] = w(jax.random.fold_in(key, 13), h, q)
    if cfg.is_mla:  # latent attention: no k/v projections per head
        del p["k_proj"], p["v_proj"]
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        heads_out = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
        p["kv_a_proj"] = w(jax.random.fold_in(key, 8), h, r + dr)
        p["kv_a_norm"] = jnp.ones((n, r), dtype=dt)
        p["kv_b_proj"] = w(jax.random.fold_in(key, 9), r, heads_out)
        p["o_proj"] = w(ks[3], cfg.num_heads * cfg.v_head_dim, h)
    if cfg.hc_mult:
        p.update(_init_stream_params(cfg, n, jax.random.fold_in(key, 18)))
    if not cfg.single_sublayer:  # else the mixer is the layer: no feed-forward drawn
        p.update(_init_ffn_params(cfg, w, n, dense, ks[4:8], key))
    return p


def init_ffn_layer_params(cfg: ModelConfig, key: jax.Array, num_layers: int) -> Params:
    """Stacked layers that are a feed-forward and no mixer (cfg.single_sublayer:
    the "moe" kind), `post_norm` the norm on their input: every leaf has
    leading dim `num_layers`."""
    n, dt = num_layers, cfg.jnp_dtype
    norm1 = jnp.zeros if cfg.rms_norm_plus_one else jnp.ones

    def w(k, *shape):
        return (jax.random.normal(k, (n, *shape), dtype=jnp.float32) * 0.02).astype(dt)

    return {**_sublayer_norms(cfg, norm1((n, cfg.hidden_size), dtype=dt), "ffn"),
            **_init_ffn_params(cfg, w, n, False, jax.random.split(key, 4), key)}


# a layer's two sublayers, as the stream's maps name them
STREAM_SUBLAYERS = ("attn", "ffn")


def _init_stream_params(cfg: ModelConfig, n: int, key: jax.Array) -> Params:
    """A layer stack's hyper-connection maps, one set a sublayer (`hc_attn_*`
    before the mixer, `hc_ffn_*` before the feed-forward): `proj`
    [n, m (2 + m), m, H] in the model's dtype, its rows Hpre (m) | Hpost (m) |
    Hres (m x m, row after row), each over the stream of m = cfg.hc_mult
    hidden states, one after the other; `bias` [n, m (2 + m)] and `scale` [n, 3] (a_pre, a_post,
    a_res) in float32. Drawn so that the maps are neither dead nor saturated
    at any width, and so that twenty Sinkhorn rounds reach a doubly-stochastic
    Hres: the projection normal with deviation 2.4 / sqrt(m H) (0.02 at 4 x
    3584), so that what it makes of the unit-RMS stream has deviation 2.4;
    a_pre = a_post = 0.5 (the sigmoids' arguments spread over +- 1.2, Hpre
    over about 0.1-0.9); a_res = 0.2 and the biases 0 but Hres's diagonal at
    1: Hres keeps about 0.46 of a hidden state where it is and trades the
    rest, differently a token (an entry off the diagonal 0.18 +- 0.07), and
    its columns sum to one within 2e-5 (at a_res 0.5 and a diagonal of 2 one
    token in a thousand was 4e-3 off after twenty rounds)."""
    m, wide = cfg.hc_mult, cfg.hc_mult * cfg.hidden_size
    bias = jnp.concatenate([jnp.zeros((2 * m,)), jnp.eye(m).reshape(-1)])
    out = {}
    for j, sub in enumerate(STREAM_SUBLAYERS):
        proj = jax.random.normal(
            jax.random.fold_in(key, j), (n, cfg.hc_maps, m, cfg.hidden_size), jnp.float32)
        out[f"hc_{sub}_proj"] = (proj * (2.4 / math.sqrt(wide))).astype(cfg.jnp_dtype)
        out[f"hc_{sub}_bias"] = jnp.broadcast_to(bias, (n, cfg.hc_maps)).astype(jnp.float32)
        out[f"hc_{sub}_scale"] = jnp.broadcast_to(
            jnp.asarray([0.5, 0.5, 0.2], jnp.float32), (n, 3))
    return out


def init_state_layer_params(cfg: ModelConfig, key: jax.Array, num_layers: int) -> Params:
    """Stacked state layers of cfg.state_kind (mamba_mixer or
    gated_delta_mixer) with their feed-forward: every leaf has leading dim
    `num_layers`. The projections are drawn as every other one (normal,
    0.02). What steers the recurrence is drawn so that it is neither dead nor
    saturated: a target step d log-uniform in [0.01, 0.5] with dt_bias its
    inverse softplus, A = -exp(A_log) with exp(A_log) uniform in [0.1, 1], so
    the decay exp(d A) of a step spreads over about 0.5-0.999 and the state
    weighs about what the newest token does; Mamba-2's D uniform in
    [0.5, 1.5]; the convolution's taps normal with deviation 0.3. Where beta
    may pass 1 (cfg.linear_allow_neg_eigval) the columns of `b` are drawn
    with deviation 0.5 / sqrt(hidden): b is then about half the residual
    stream's RMS wide, and beta = 2 sigmoid(b) spreads over (0, 2) where a
    deviation of 0.02 would leave every head at 1 +- 0.03."""
    n, h = num_layers, cfg.hidden_size
    delta = cfg.state_kind == "delta"
    heads = cfg.linear_value_heads if delta else cfg.mamba_heads
    dt = cfg.jnp_dtype
    ks = jax.random.split(key, 10)
    norm1 = jnp.zeros if cfg.rms_norm_plus_one else jnp.ones

    def w(k, *shape, std=0.02):
        return (jax.random.normal(k, (n, *shape), dtype=jnp.float32) * std).astype(dt)

    def uniform(k, lo, hi):
        return jax.random.uniform(k, (n, heads), jnp.float32, lo, hi)

    step = jnp.exp(uniform(ks[4], math.log(0.01), math.log(0.5)))
    p = {
        **_sublayer_norms(cfg, norm1((n, h), dtype=dt), "mixer"),
        "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),  # softplus^-1(step)
        "A_log": jnp.log(uniform(ks[5], 0.1, 1.0)).astype(dt),
    }
    if delta:
        cd, dv = cfg.linear_conv_dim, cfg.linear_value_dim
        p.update(
            in_proj=w(ks[0], h, cd + dv),  # [q | k | v | z], each head after head
            ba_proj=w(ks[2], h, 2 * heads),  # [b | a], a value head each
            conv_w=w(ks[1], cfg.linear_conv, cd, std=0.3),  # no bias
            gate_norm=jnp.ones((n, cfg.linear_value_head_dim), dtype=dt),  # scales by w
            out_proj=w(ks[3], dv, h),
        )
        if cfg.linear_allow_neg_eigval:
            wide = w(jax.random.fold_in(ks[2], 1), h, heads, std=0.5 / math.sqrt(h))
            p["ba_proj"] = p["ba_proj"].at[..., :heads].set(wide)
    else:
        di, cd = cfg.mamba_inner, cfg.mamba_conv_dim
        p.update(
            in_proj=w(ks[0], h, di + cd + heads),  # [z | xBC | dt], in that order
            conv_w=w(ks[1], cfg.mamba_conv, cd, std=0.3),  # tap i meets the input K-1-i back
            conv_b=w(ks[2], cd),
            D=uniform(ks[6], 0.5, 1.5).astype(dt),
            gate_norm=jnp.ones((n, di), dtype=dt),
            out_proj=w(ks[3], di, h),
        )
    if not cfg.single_sublayer:
        p.update(_init_ffn_params(cfg, w, n, False, ks[6:10], key))
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Full-model params: embed + stacked layers + final norm (+ lm_head)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    dt = cfg.jnp_dtype
    norm1 = jnp.zeros if cfg.rms_norm_plus_one else jnp.ones
    n_state = cfg.layers_of(cfg.state_kind) if cfg.has_state_layers else 0
    n_ffn = sum(cfg.layers_of(kind) for kind in FFN_KINDS)  # layers that are a feed-forward alone
    params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.hidden_size), dtype=jnp.float32) * 0.02).astype(dt),
        "layers": init_layer_params(
            cfg, k_layers, cfg.num_layers - cfg.num_dense_layers - n_state - n_ffn),
        "final_norm": norm1((cfg.hidden_size,), dtype=dt),
    }
    if n_state:  # the state kind's stack, beside the attention kind's `layers`
        params["state_layers"] = init_state_layer_params(
            cfg, jax.random.fold_in(k_layers, 2), n_state)
    if n_ffn:  # and the stack of the layers that are experts alone
        params["ffn_layers"] = init_ffn_layer_params(
            cfg, jax.random.fold_in(k_layers, 3), n_ffn)
    if cfg.num_dense_layers:  # a leading group with leaves of its own
        params["dense_layers"] = init_layer_params(
            cfg, jax.random.fold_in(k_layers, 1), cfg.num_dense_layers, dense=True
        )
    if not cfg.tie_word_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.hidden_size, cfg.vocab_size), dtype=jnp.float32) * 0.02
        ).astype(dt)
    return params


# ---------------------------------------------------------------------------
# Blocks (reference: qwen3_server_module.py:14-89 — rebuilt, not translated)
# ---------------------------------------------------------------------------


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, plus_one: bool = False
) -> jax.Array:
    """RMSNorm computed in float32, result cast back to x.dtype.

    plus_one: Gemma-style zero-centered scale — the effective weight is
    (1 + w), with w stored near zero (matches HF Gemma2RMSNorm)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:
        w = 1.0 + w
    return (out * w).astype(x.dtype)


def act_fn(cfg: ModelConfig):
    """MLP gate activation: SiLU (Qwen/Llama), tanh-approx GeLU (Gemma —
    torch's gelu_pytorch_tanh) or the squared ReLU (Nemotron-H)."""
    if cfg.hidden_act == "gelu_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    if cfg.hidden_act == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    return jax.nn.silu


def rope_cos_sin(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    cfg: Optional[ModelConfig] = None,
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for rotary embedding, float32.

    positions: [B, S] absolute positions. Returns cos/sin [B, S, head_dim]
    in the duplicated-halves layout (emb = concat(freqs, freqs)).

    With cfg.rope_scaling == "llama3" (Llama-3.1+ long-context scheme,
    matching HF's rope_utils): frequency bands whose wavelength exceeds
    `rope_original_max_position / low_freq_factor` are slowed by
    `rope_scaling_factor`, bands shorter than `.. / high_freq_factor` are
    untouched, with a smooth interpolation ramp between.

    With "yarn" (GPT-OSS; matches HF _compute_yarn_parameters): NTK-by-
    parts — each band blends its original frequency with the
    factor-interpolated one via a linear ramp between the beta_fast and
    beta_slow rotation counts over the pretraining window, and cos/sin are
    multiplied by the attention temperature factor (0.1*ln(factor)+1).
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    attn_factor = 1.0
    if cfg is not None and cfg.rope_scaling == "yarn":
        dim = head_dim
        orig = float(cfg.rope_original_max_position)

        def corr_dim(rot: float) -> float:
            return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(theta))

        low = corr_dim(cfg.rope_beta_fast)
        high = corr_dim(cfg.rope_beta_slow)
        if cfg.rope_truncate:
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0.0), min(high, dim - 1.0)
        if low == high:
            high += 0.001
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
        )
        extrap_factor = 1.0 - ramp  # 1 where the band keeps its frequency
        inv_freq = (
            (inv_freq / cfg.rope_scaling_factor) * (1.0 - extrap_factor)
            + inv_freq * extrap_factor
        )
        if cfg.rope_mscale_all_dim:  # DeepSeek: the pair's ratio; the rest scales the softmax
            attn_factor = yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale) / yarn_mscale(
                cfg.rope_scaling_factor, cfg.rope_mscale_all_dim
            )
        else:
            attn_factor = cfg.rope_attention_factor or yarn_mscale(cfg.rope_scaling_factor)
    if cfg is not None and cfg.rope_scaling == "llama3":
        wavelen = 2.0 * jnp.pi / inv_freq
        low_len = cfg.rope_original_max_position / cfg.rope_low_freq_factor
        high_len = cfg.rope_original_max_position / cfg.rope_high_freq_factor
        smooth = (
            cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor
        ) / (cfg.rope_high_freq_factor - cfg.rope_low_freq_factor)
        scaled = jnp.where(
            wavelen > low_len,
            inv_freq / cfg.rope_scaling_factor,  # long wavelengths: slow down
            jnp.where(
                wavelen < high_len,
                inv_freq,  # short wavelengths: keep
                (1 - smooth) * inv_freq / cfg.rope_scaling_factor + smooth * inv_freq,
            ),
        )
        inv_freq = scaled
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, S, D/2]
    emb = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(emb) * attn_factor, jnp.sin(emb) * attn_factor


def _to_cache_dtype(x: jax.Array, dtype) -> jax.Array:
    """Cast a K/V chunk to the cache's storage dtype, SATURATING for
    narrow float types: e4m3fn has no inf, so values past +-448 would
    become NaN and permanently poison the session's cache (V is raw
    v_proj output with no norm — LLM activations do have outliers)."""
    if x.dtype == dtype:
        return x
    if jnp.issubdtype(dtype, jnp.floating):
        lim = float(jnp.finfo(dtype).max)
        x = jnp.clip(x.astype(jnp.float32), -lim, lim)
    return x.astype(dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, N, D]; cos/sin: [B, S, R] float32, R <= D: the first R
    dimensions of a head are turned (cfg.rope_dim), the rest pass."""
    r = cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate([apply_rope(x[..., :r], cos, sin), x[..., r:]], axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    xf = x.astype(jnp.float32)
    return (xf * c + _rotate_half(xf) * s).astype(x.dtype)


def gqa_attention(
    q: jax.Array,  # [B, S, Nq, D]
    k: jax.Array,  # [B, T, Nkv, D]
    v: jax.Array,  # [B, T, Nkv, D]
    q_positions: jax.Array,  # [B, S] absolute position of each query
    kv_valid_len: jax.Array,  # scalar or [B]: kv slots < this are populated
    kv_positions: Optional[jax.Array] = None,  # [B, T] or [T]: absolute position per slot
    scale: Optional[float] = None,  # score scale; default head_dim**-0.5
    softcap: float = 0.0,  # Gemma-2 logit softcapping: cap*tanh(x/cap)
    window: Optional[jax.Array] = None,  # sliding window (traced scalar; <=0 = global)
    sinks: Optional[jax.Array] = None,  # [Nq] per-head sink logits (GPT-OSS)
    block_table: Optional[jax.Array] = None,  # [B, MB] paged-KV table —
    #   k/v are then block POOLS [NB, bs, Nkv, D] gathered through it
) -> jax.Array:
    """Grouped-query attention with causal masking over a (possibly oversized)
    KV buffer. Slot j attends iff j < kv_valid_len AND its absolute position
    <= the query's absolute position. By default slot index == absolute
    position (the cache layout); pass kv_positions when slots hold an
    offset chunk (cache-free stage forward mid-sequence).

    With `block_table`, k/v are paged block pools read through the table
    (ops.attention.gather_block_kv) — the gathered view is position-
    contiguous, so the math below is bit-identical to the dense layout.

    `window` additionally restricts to positions within (qpos - window, qpos]
    when > 0 — a traced scalar so a per-layer window array can ride a
    lax.scan over stacked layers (Gemma-2's alternating local/global
    attention) with ONE compiled layer body.

    Softmax in float32; matmuls in input dtype (MXU-friendly).
    """
    b, s, nq, d = q.shape
    if s == 1:
        # decode fast path (ops.attention.decode_gqa): same math with the
        # query axis dropped from every intermediate and the compressed-KV
        # upcast dequant-fused into the contractions' operand stream
        return attention_ops.decode_gqa(
            q, k, v, q_positions, kv_valid_len, kv_positions=kv_positions,
            scale=scale, softcap=softcap, window=window, sinks=sinks,
            block_table=block_table,
        )
    if block_table is not None:
        k, v = attention_ops.gather_block_kv(k, v, block_table)
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    if k.dtype != q.dtype:  # compressed KV storage: upcast at the read
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    qh = q.reshape(b, s, nkv, g, d)
    # scores: [B, Nkv, G, S, T]
    scores = jnp.einsum("bsngd,btnd->bngst", qh, k).astype(jnp.float32)
    scores = scores * (float(scale) if scale is not None else 1.0 / math.sqrt(d))
    scores = attention_ops.apply_softcap(scores, softcap)

    slots = jnp.arange(t)
    valid = jnp.asarray(kv_valid_len)
    if valid.ndim == 0:
        valid = valid[None]
    kpos = slots if kv_positions is None else kv_positions
    if kpos.ndim == 1:
        kpos = kpos[None, :]
    mask = (slots[None, None, :] < valid[:, None, None]) & (
        kpos[:, None, :] <= q_positions[:, :, None]
    )  # [B, S, T]
    mask = attention_ops.apply_window_mask(mask, kpos, q_positions, window)
    scores = jnp.where(mask[:, None, None, :, :], scores, jnp.float32(-1e30))
    if sinks is not None:
        # GPT-OSS attention sinks: a per-q-head learned logit joins the
        # softmax denominator (a virtual always-attendable slot whose value
        # is dropped) — exact closed form, no concat/column-drop needed
        sk = sinks.astype(jnp.float32).reshape(nkv, g)[None, :, :, None, None]
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sk)
        p = jnp.exp(scores - m)
        denom = jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sk - m)
        probs = (p / denom).astype(q.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngst,btnd->bsngd", probs, v)
    return out.reshape(b, s, nq * d)


def swiglu_mlp(
    p: Params, x: jax.Array, act=jax.nn.silu, lane_adapters=None
) -> jax.Array:
    """Gated feed-forward: SwiGLU (reference: qwen3_server_module.py:28-40)
    or GeGLU when `act` is the tanh-approx GeLU (Gemma). `lane_adapters`
    (multi-tenant registry — ops.lora.apply_lane_delta) adds each lane's
    per-projection LoRA delta BEFORE the activation, matching where a
    merged adapter's weights would act."""
    gate = None  # an ungated feed-forward (cfg.ffn_gated False) is down(act(up(x)))
    if "gate_proj" in p:
        gate = act(lora_ops.apply_lane_delta(
            qdot(x, p["gate_proj"]), x, "gate_proj", lane_adapters
        ))
    up = lora_ops.apply_lane_delta(
        qdot(x, p["up_proj"]), x, "up_proj", lane_adapters
    )
    h = act(up) if gate is None else gate * up
    return lora_ops.apply_lane_delta(
        qdot(h, p["down_proj"]), h, "down_proj", lane_adapters
    )


def route_topk(
    cfg: ModelConfig, router_logits: jax.Array, select_bias: Optional[jax.Array] = None
) -> Tuple[jax.Array, jax.Array]:
    """Router -> (top-k weights [T, K] f32, top-k indices [T, K]) — the
    single source of the HF-exact routing modes (moe_routed_part):
      softmax_topk (Qwen3-MoE / Mixtral): probabilities over ALL experts,
        top-k selected, optionally renormalized;
      topk_softmax (GPT-OSS): top-k over the raw LOGITS, softmax over just
        the k selected values;
      sigmoid_topk (afmoe): a sigmoid score per expert; the top k of
        score + `select_bias` [E] f32, which chooses and never weighs; the
        weights are the chosen experts' scores, optionally over their sum.
    """
    k = cfg.num_experts_per_tok
    if cfg.moe_router_mode == "topk_softmax":
        topv, topi = jax.lax.top_k(router_logits, k)
        topw = jax.nn.softmax(topv, axis=-1)
    elif cfg.moe_router_mode == "sigmoid_topk":
        scores = jax.nn.sigmoid(router_logits)
        _, topi = jax.lax.top_k(scores + select_bias, k)
        topw = jnp.take_along_axis(scores, topi, axis=-1)
        if cfg.norm_topk_prob:
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(router_logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        if cfg.norm_topk_prob:
            topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        topw = topw * cfg.routed_scaling_factor
    return topw, topi


class ExpertStack(NamedTuple):
    """One layer's expert weights where they lie: the whole stack [L, E, K, N]
    and the layer's index in it. forward_layers hands the routed layer its
    experts this way, because the grouped product is a kernel: a layer's
    [E, K, N] taken out of the stack for it would be COPIED out (three times
    a layer's experts, read and written, a step), where the kernel can find
    expert e of layer l as group l * E + e of the stack as it is."""

    stack: jax.Array
    layer: Any  # a python int or a traced scalar


def experts_held(w) -> int:
    """How many experts a routed layer's weight `w` holds."""
    return w.stack.shape[1] if isinstance(w, ExpertStack) else w.shape[0]


def _expert_slab(w):
    """The layer's own [E, K, N] (the dense product reads it as a view)."""
    return _slab(w.stack, w.layer) if isinstance(w, ExpertStack) else w


def _expert_rhs(w) -> jax.Array:
    """[G, K, N] for the grouped product: everything `w` lies in, an expert a
    group (an ExpertStack's layer l holds groups l * E .. l * E + E)."""
    return w.stack.reshape(-1, *w.stack.shape[2:]) if isinstance(w, ExpertStack) else w


# What the grouped product is told to keep of one expert's [K, N] in fast
# memory at a time, in elements: 3072 x 1024 (6 MiB of bf16, twice for the
# next block in flight) was the largest that fit beside a 128-row tile on a
# v5e (my chip run, PR 46: PERF.md section 6)
_EXPERT_BLOCK = 3 * 2**20


def expert_row_tile(rows: int, k: int, held: int, width: int = 0) -> int:
    """The row tile of the routed layer's expert product for `rows` tokens
    that each chose `k` experts of a router `width` wide (0: `held`), over
    the `held` experts that are here: how many of the sorted
    (token, expert) pairs one visit of an expert multiplies. 0 = the dense
    product, every row through every held expert. From static shapes alone,
    so that the program and its counter (`moe.rows_multiplied`,
    expert_rows_multiplied) agree and nothing recompiles with the routing.
    A share of a wider router expects rows x k x held / width pairs here, and
    it is those that leave held experts untouched or do not.

    Measured alone on a v5e (PR 46, PERF.md section 6; ms a layer, dense
    against grouped): under 3 pairs a held expert the rows leave experts
    untouched, the grouped product does not read those and any tile costs the
    same, so the smallest keeps the padding low (64 experts of 2048 x 1408,
    top 6: 8 rows 1.49 / 0.86, 16 rows 1.50 / 1.29, 32 rows 1.51 / 1.48). From
    3 pairs an expert on every expert is touched; up to 128 rows the dense
    product is still bound by the weights it reads once, and is as fast or
    faster (128 experts of 2048 x 768, top 8: 64 rows 1.67 / 1.69 at a tile
    of 128 and 1.82 at 16; 128 rows 1.65 / 1.76; the 64 experts: 64 rows
    1.52 / 1.56, 128 rows 1.56 / 1.66). Past that it is bound by operations it
    multiplies by zero (256 rows 1.98 / 1.91 and 1.77 / 1.78; 512 rows
    3.78 / 2.18, 3.38 / 2.06, 5.27 / 2.75 for 32 held experts of 3072 x 3072),
    and a tile of 64 or 128 feeds the MXU."""
    if rows * k < 3 * (width or held):  # under 3 expected pairs a held expert
        return 16
    return 0 if rows <= 128 else 128


def routed_row_tile(w, rows: int, k: int, held: int, width: int = 0) -> int:
    """expert_row_tile for a routed layer whose expert weight is `w` (or the
    stack it lies in): 0, the dense product, for a quantised weight, which
    the grouped kernel does not take."""
    if isinstance(w, (QuantWeight, Int4Weight)):
        return 0
    return expert_row_tile(rows, k, held, width)


def routed_weight(params: Params):
    """An expert weight of the model's routed layers (their up-projection,
    which every flavour has), in whichever stack holds the routers."""
    return next(params[g]["up_proj"] for g in ("ffn_layers", "layers", "state_layers")
                if "router" in params.get(g, ()))


def expert_rows_multiplied(counts: np.ndarray, tile: int, rows: int) -> int:
    """Rows the expert product of one layer multiplied, padding included, for
    `counts` [held] pairs on each held expert (in expert order, as the sort
    lays them) under expert_row_tile's `tile`: a visit of an expert
    multiplies a whole tile, and an expert visits every tile its pairs lie
    in. The dense product (tile 0) runs each of the `rows` through every
    held expert."""
    if not tile:
        return rows * len(counts)
    ends = np.cumsum(counts)
    visits = -(-ends // tile) - (ends - counts) // tile
    return int(visits[counts > 0].sum()) * tile


def _glu(cfg: ModelConfig, gate: jax.Array, up: jax.Array) -> jax.Array:
    """The experts' activation, two flavors: plain SwiGLU (Qwen3-MoE/Mixtral)
    and GPT-OSS's clamped GLU: gate clamped above at `swiglu_limit`, up
    clamped to +-limit, glu = gate*sigmoid(1.702*gate), output (up+1)*glu."""
    if cfg.swiglu_limit > 0:
        lim = cfg.swiglu_limit
        gate = jnp.minimum(gate, lim)
        up = jnp.clip(up, -lim, lim)
        return (up + 1.0) * (gate * jax.nn.sigmoid(1.702 * gate))
    return jax.nn.silu(gate) * up


def _expert_act(cfg: ModelConfig, gate: Optional[jax.Array], up: jax.Array) -> jax.Array:
    """What an expert's down-projection reads: _glu of the two products, or
    for an ungated expert (cfg.ffn_gated False: TWO matrices, no product
    against a gate, `gate` None) the activation of `up` alone."""
    return act_fn(cfg)(up) if gate is None else _glu(cfg, gate, up)


def expert_ffn(p: Params, cfg: ModelConfig, xt: jax.Array) -> jax.Array:
    """The dense expert product: [T, H] -> [T, E, H], every token through
    every held expert (the caller's combine weights zero what it did not
    choose); biases where the model has them (GPT-OSS). The arm of
    moe_routed_part for a quantised weight and for the few rows that touch
    every expert anyway (expert_row_tile)."""
    gated = "gate_proj" in p
    gate = qeinsum("th,ehi->tei", xt, _expert_slab(p["gate_proj"])) if gated else None
    up = qeinsum("th,ehi->tei", xt, _expert_slab(p["up_proj"]))
    if cfg.moe_bias:
        gate = gate + p["gate_bias"][None]
        up = up + p["up_bias"][None]
    expert_out = qeinsum(
        "tei,eih->teh", _expert_act(cfg, gate, up), _expert_slab(p["down_proj"]))
    if cfg.moe_bias:
        expert_out = expert_out + p["down_bias"][None]
    return expert_out


def _expert_tiles(tile: int, k: int, n: int) -> Tuple[int, int, int]:
    """gmm's (rows, contraction, columns) tiles: the contraction whole where
    it can be, so that consecutive row tiles of one expert find its block
    still there, and as many columns as _EXPERT_BLOCK then leaves."""
    tk = min(k, 4096)
    return tile, tk, min(n, max(128, _EXPERT_BLOCK // tk // 128 * 128))


def grouped_expert_ffn(
    p: Params, cfg: ModelConfig, xt: jax.Array, topw: jax.Array, local: jax.Array,
    here: jax.Array, tile: int,
) -> jax.Array:
    """The grouped expert product: tokens xt [T, H], each with K chosen
    experts `local` [T, K] (ids among the held ones where `here`) of weights
    `topw` [T, K] f32 -> their weighted sum [T, H]. The T x K pairs are
    sorted by expert (those not held here behind the last group, in none),
    each expert multiplies its own rows once (megablox gmm: a visit takes a
    tile of `tile` rows, an expert nobody chose is not read), products
    accumulate in float32, and a row's K results are weighted and summed in
    float32 and rounded once."""
    t, k = local.shape
    w = p["up_proj"]
    held = experts_held(w)
    n = t * k
    m = -(-n // tile) * tile  # whole tiles: the padding belongs to no group
    group = jnp.where(here, local, held).reshape(n)
    if m != n:
        group = jnp.concatenate([group, jnp.full((m - n,), held, group.dtype)])
    order = jnp.argsort(group)  # the pairs (row-major: pair j is of row j // k) by expert
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    if isinstance(w, ExpertStack):  # every group of the stack but this layer's is empty
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w.stack.shape[0] * held,), jnp.int32), sizes,
            (jnp.asarray(w.layer, jnp.int32) * held,))
    mm = lambda lhs, weight: gmm(  # noqa: E731
        lhs, weight, sizes, jnp.float32, _expert_tiles(tile, *weight.shape[1:]),
        interpret=not is_tpu(),
    )
    # each sorted pair's expert; `held`: none. The kernel neither reads nor
    # writes such a row, so what stands there (and in its gradient) is masked
    of = group[order]
    grouped = (of < held)[:, None]
    of = jnp.minimum(of, held - 1)
    xs = jnp.where(grouped, xt[jnp.minimum(order // k, t - 1)], 0)  # [m, H]
    gate = None
    if "gate_proj" in p:
        gate = jnp.where(grouped, mm(xs, _expert_rhs(p["gate_proj"])), 0.0)
    up = jnp.where(grouped, mm(xs, _expert_rhs(w)), 0.0)
    if cfg.moe_bias:
        gate = gate + p["gate_bias"][of]
        up = up + p["up_bias"][of]
    out = mm(_expert_act(cfg, gate, up).astype(xt.dtype), _expert_rhs(p["down_proj"]))
    if cfg.moe_bias:
        out = out + p["down_bias"][of]
    # back to the rows: pair j lies at place back[j] of the sorted order; what
    # lies behind the last group was never written
    back = jnp.zeros((m,), jnp.int32).at[order].set(jnp.arange(m, dtype=jnp.int32))[:n]
    out = jnp.where(here[..., None], out[back].reshape(t, k, -1), 0.0)
    return jnp.sum(out * topw[..., None], axis=1).astype(xt.dtype)


def router_logits(p: Params, cfg: ModelConfig, xt: jax.Array) -> jax.Array:
    """Tokens xt [T, H] -> the router's float32 logits [T, E] over its whole width."""
    logits = (xt @ p["router"]).astype(jnp.float32)
    if cfg.router_bias:
        logits = logits + p["router_bias"].astype(jnp.float32)
    return logits


def moe_routed_part(
    p: Params, cfg: ModelConfig, xt: jax.Array, offset=0
) -> Tuple[jax.Array, jax.Array]:
    """THE routed layer, told which experts it holds: tokens xt [T, H] are
    routed over the router's whole width (p["router"]: every routed expert
    of the model), and the experts whose weights are here (experts_held of
    them, the router's outputs `offset` .. offset + that many) give their
    part of the result -> (that part [T, H], the experts each token chose
    [T, K], ids over the router's width, the same in every share).

    Every expert here (moe_mlp_routed) is the whole layer; a rank of an
    expert-parallel mesh passes its offset and sums the parts
    (parallel/tp.moe_mlp_sharded); one chip serving a rank's share
    (cfg.router_experts) computes its part and nothing stands in for the
    others. A token meets only the experts it chose (grouped_expert_ffn),
    but where expert_row_tile says the rows touch every held expert anyway,
    and for a quantised weight, which the grouped kernel does not take:
    there every token visits each held expert (expert_ffn) and the combine
    weights zero what it did not choose."""
    with jax.named_scope("moe_route"):
        topw, topi = route_topk(cfg, router_logits(p, cfg, xt), p.get("router_select_bias"))
    w = p["up_proj"]
    held = experts_held(w)
    local = topi - offset
    here = (local >= 0) & (local < held)
    tile = routed_row_tile(w, *topi.shape, held, cfg.router_width)
    if "latent_in_proj" in p:  # LatentMoE: the router read the full width, the experts do not
        with jax.named_scope("moe_latent_in"):
            xt = qdot(xt, p["latent_in_proj"])
    with jax.named_scope("moe_experts"):
        if tile:
            out = grouped_expert_ffn(p, cfg, xt, topw, local, here, tile)
        else:
            t = xt.shape[0]
            comb = (  # the combine weights [T, held] f32; column `held`: chosen, not held here
                jnp.zeros((t, held + 1), jnp.float32)
                .at[jnp.arange(t)[:, None], jnp.where(here, local, held)]
                .add(topw)[:, :held]
            )
            expert_out = expert_ffn(p, cfg, xt)
            out = jnp.einsum("teh,te->th", expert_out, comb.astype(expert_out.dtype))
    if "latent_out_proj" in p:  # ONE projection back, after the combine
        with jax.named_scope("moe_latent_out"):
            out = qdot(out, p["latent_out_proj"])
    return out, topi


def moe_mlp_routed(p: Params, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Mixture-of-experts feed-forward (routing modes in `route_topk`, expert
    flavors in `_glu`) -> (output [B, S, H], chosen experts [B, S, K]):
    moe_routed_part over the experts this device holds, from
    cfg.expert_offset (0 and all of them, but for a rank's share served on
    one chip). With `n_shared_experts` one always-on SwiGLU is added to the
    routed output, once, outside the share, multiplied by its own gate
    where the layer has one (`shared_expert_gate`).
    """
    b, s, h = x.shape
    xt = x.reshape(b * s, h)
    out, topi = moe_routed_part(p, cfg, xt, cfg.expert_offset)
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            shared = {k: p[f"shared_{k}"] for k in ("gate_proj", "up_proj", "down_proj")
                      if f"shared_{k}" in p}
            shared_out = swiglu_mlp(shared, xt, jax.nn.silu if cfg.ffn_gated else act_fn(cfg))
            if "shared_expert_gate" in p:  # Qwen3-Next: sigmoid(x . w) on the shared expert
                with jax.named_scope("moe_shared_gate"):
                    gate = jax.nn.sigmoid(jnp.einsum(
                        "th,h->t", xt, p["shared_expert_gate"],
                        preferred_element_type=jnp.float32))
                    shared_out = (shared_out.astype(jnp.float32) * gate[:, None]).astype(
                        shared_out.dtype)
            out = out + shared_out
    return out.reshape(b, s, h), topi.reshape(b, s, -1)


def moe_mlp(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    return moe_mlp_routed(p, cfg, x)[0]


def _attend(
    cfg: ModelConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_len: jax.Array,
    kv_positions: Optional[jax.Array] = None,
    window: Optional[jax.Array] = None,
    sinks: Optional[jax.Array] = None,
    flash: Optional[bool] = None,
) -> jax.Array:
    """Hot-op dispatch (the single site for prefill AND cached decode):
    Pallas flash kernel when enabled for this buffer size (`flash` None; a
    caller that hands a prefix of its buffer decides by the buffer), XLA
    gqa_attention otherwise. Positions from forward_layers/forward are contiguous per batch
    row (start + arange) — the flash kernel's layout contract; kv slot j holds
    position kv_positions[:, 0] + j (or j when kv_positions is None).
    Scattered-position callers must use gqa_attention directly.

    Gemma-2 features (logit softcapping, non-head_dim score scale, sliding
    window) pass straight through to both paths — the kernels implement
    them natively (window bounds their kv-block loop, so local layers do
    O(window) work), so long-context Gemma keeps the streaming kernel's
    memory safety instead of falling back to score materialization.
    Attention sinks (GPT-OSS) fold into the kernels' online-softmax
    denominator at finalize — the full sink+window+softcap recipe rides
    either path."""
    if flash is None:
        flash = attention_ops.flash_enabled(
            cfg, k.shape[1], compressed_kv=k.dtype != q.dtype,
            q_len=q.shape[1], batch=q.shape[0],
        )
    if flash:
        kv_start = kv_positions[:, 0] if kv_positions is not None else 0
        return attention_ops.flash_gqa(
            q, k, v,
            q_start=q_positions[:, 0], kv_len=kv_len, kv_start=kv_start,
            interpret=attention_ops.flash_interpret(cfg),
            scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
            window=window, sinks=sinks,
        )
    return gqa_attention(
        q, k, v, q_positions, kv_len, kv_positions=kv_positions,
        scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap, window=window,
        sinks=sinks,
    )


def _windowed_slice(new_k, new_v, end, window: int, s: int):
    """Static-length KV slice covering every slot a query in this chunk can
    attend under a STATIC sliding window: [max(0, end - L), end) with
    L = min(T, round16(window + S)) — window + S is what covers the OLDEST
    query's window start (that query sits S-1 slots before `end`, and its
    window reaches window-1 slots further back), rounded up to a multiple
    of 16 for tiling. This is the windowed-read optimization: a sliding layer's
    attention reads O(window) KV from HBM instead of the whole buffer
    (storage stays full-length — only the read narrows). Returns
    (k, v, kv_positions [B, L], valid_len) with absolute positions;
    `end` is scalar or per-row [B] (continuous batching)."""
    b, t = new_k.shape[0], new_k.shape[1]
    ls = min(t, (window + s + 15) // 16 * 16)
    if jnp.ndim(end) == 1:
        start = jnp.maximum(0, end - ls)  # [B]
        sl = jax.vmap(
            lambda buf, st: jax.lax.dynamic_slice_in_dim(buf, st, ls, axis=0)
        )
        k_att = sl(new_k, start)
        v_att = sl(new_v, start)
        kvpos = start[:, None] + jnp.arange(ls)[None, :]
        return k_att, v_att, kvpos, end - start
    start = jnp.maximum(0, end - ls)
    k_att = jax.lax.dynamic_slice_in_dim(new_k, start, ls, axis=1)
    v_att = jax.lax.dynamic_slice_in_dim(new_v, start, ls, axis=1)
    kvpos = jnp.broadcast_to(start + jnp.arange(ls), (b, ls))
    return k_att, v_att, kvpos, end - start


# causal mask sentinel: never attendable. A PYTHON int, not jnp.int32:
# a module-level device constant would initialize a jax backend at
# IMPORT time — before any CLI can pin its platform, and claiming the chip
# for whichever process merely imported the package
_FAR_FUTURE = 1 << 30


def _ring_attend_update(
    cfg, q, k_new, v_new, q_positions, k_rings, v_rings, at, write_pos, real_end,
    window: int, sinks, write_mask=None,
):
    """Sliding-layer attention + update over an O(window) RING buffer.

    Storage invariant: position p lives at ring slot p % R until position
    p + R overwrites it (R = core.cache.ring_slots >= round16(window) +
    RING_MARGIN). The chunk's own K/V never round-trips through the ring
    for its own queries — attention reads concat(ring-before-write, fresh
    chunk), so chunks of ANY length are exact (a chunk longer than the
    ring would otherwise overwrite positions its own later queries need).

    Slot positions are derived, not stored: slot j is attributed position
    p_f(j) = the largest p < write_pos with p % R == j (never-written slots
    get a far-future sentinel the causal mask kills). A slot whose data is
    actually NEWER than its attributed position (speculative rollback wrote
    ahead then reset `length`; a fork truncated the parent's stream) is
    attributed p_f = p_actual - R, and p_actual - R is inside a query's
    window only when p_actual > q + (R - window) — i.e. only when the
    stream ran more than RING_MARGIN positions past the reset point, which
    rollback depth (spec chunk <= RING_MARGIN) and the fork-margin check
    (runtime executors) both forbid. Within those bounds stale data is
    STRUCTURALLY outside every window: no flags, no zeroing.

    The update scatters only the chunk's LAST min(S, R) real rows (unique
    slots by construction); rows at positions >= real_end (bucket padding)
    and the rows of a batch row whose `write_mask` [B] is False scatter to
    index R, which `mode="drop"` discards.

    The rings are layer `at` of the sliding layers' stacks
    [Ll, B, R, Nkv, D]: read as a view of the stack before the write, and
    the kept rows scattered into the stack where it lies.

    write_pos/real_end: scalar or per-batch-row [B]. Returns
    (attn [B, S, Nq*D], new_k_rings, new_v_rings).
    """
    b, s = q.shape[0], q.shape[1]
    k_ring, v_ring = _slab(k_rings, at), _slab(v_rings, at)  # [B, R, Nkv, D]
    r = k_ring.shape[1]
    per_row = jnp.ndim(write_pos) == 1
    wp = write_pos if per_row else jnp.broadcast_to(jnp.asarray(write_pos), (b,))
    re = real_end if jnp.ndim(real_end) == 1 else jnp.broadcast_to(
        jnp.asarray(real_end), (b,)
    )

    # -- attend: ring (positions < write_pos) + fresh chunk -----------------
    j = jnp.arange(r)[None, :]  # [1, R]
    pf = wp[:, None] - 1 - ((wp[:, None] - 1 - j) % r)  # [B, R]
    pf = jnp.where(pf < 0, _FAR_FUTURE, pf)
    fresh_pos = wp[:, None] + jnp.arange(s)[None, :]  # [B, S] (incl. padding)
    # padded fresh rows hold garbage K at positions >= real_end; queries at
    # real positions exclude them causally, but mark them far-future anyway
    # so even same-position padding can never be attended
    fresh_pos = jnp.where(fresh_pos < re[:, None], fresh_pos, _FAR_FUTURE)
    k_cat = jnp.concatenate([k_ring.astype(q.dtype), k_new], axis=1)
    v_cat = jnp.concatenate([v_ring.astype(q.dtype), v_new], axis=1)
    attn = gqa_attention(
        q, k_cat, v_cat, q_positions, jnp.int32(r + s),
        kv_positions=jnp.concatenate([pf, fresh_pos], axis=1),
        scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
        window=jnp.int32(window), sinks=sinks,
    )

    # -- update: scatter the last min(S, R) real rows into their slots ------
    pos = wp[:, None] + jnp.arange(s)[None, :]  # [B, S]
    keep = (pos < re[:, None]) & (pos >= re[:, None] - r)
    if write_mask is not None:
        keep &= write_mask[:, None]
    slot = jnp.where(keep, pos % r, r)  # r = out of bounds -> dropped
    kc = _to_cache_dtype(k_new, k_ring.dtype)
    vc = _to_cache_dtype(v_new, v_ring.dtype)
    rows = jnp.arange(b)[:, None]
    upd = lambda rings, ch: rings.at[at, rows, slot].set(ch, mode="drop")
    return attn, upd(k_rings, kc), upd(v_rings, vc)


def _mask_only(window):
    """A window that only masks: a STATIC int narrows the READ on dense
    lanes alone (_attend_update_lanes); every other layout takes it traced."""
    return None if window is None else jnp.asarray(window, jnp.int32)


def _slab(stack, at):
    """Layer `at` of a stacked cache array, as a view of the stack."""
    return jax.lax.dynamic_index_in_dim(stack, at, 0, keepdims=False)


def _lanes_write(stack, at, chunk, write_pos, write_mask=None):
    """A chunk [B, S, ...] written into layer `at` of stacked dense lanes
    [L, B, T, ...] at `write_pos`: a scalar, or [B] per row (continuous
    batching: lanes at ragged fill levels advance in one step: one scatter
    of B windows [S, ...] at (at, b, write_pos[b]), its starts clamped as
    dynamic_update_slice clamps them). Only the chunk's rows are written:
    under donation the stack is updated where it lies. With `write_mask`
    [B] bool a False row writes nothing: its window starts past the end of
    its lane and the scatter drops it whole (as it then drops, not clamps,
    a window the caller let run past the end)."""
    chunk = _to_cache_dtype(chunk, stack.dtype)
    if write_mask is None and jnp.ndim(write_pos) == 0:
        return jax.lax.dynamic_update_slice(
            stack, chunk[None], (at, 0, write_pos) + (0,) * (stack.ndim - 3)
        )
    b = chunk.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(write_pos, jnp.int32), (b,))
    mode = jax.lax.GatherScatterMode.CLIP
    if write_mask is not None:
        pos = jnp.where(write_mask, pos, stack.shape[2])
        mode = jax.lax.GatherScatterMode.FILL_OR_DROP
    starts = jnp.stack([jnp.full_like(rows, at), rows, pos], axis=-1)
    return jax.lax.scatter(
        stack, starts, chunk,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(1, chunk.ndim)),
            inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2),
        ),
        indices_are_sorted=True, unique_indices=True, mode=mode,
    )


def _attend_chunk(cfg, q, k, v, q_positions, entry, at, ctx, window, sinks):
    """No cache: the chunk attends to itself (prefill-style parity)."""
    attn = _attend(
        cfg, q, k, v, q_positions, jnp.int32(q.shape[1]),
        kv_positions=q_positions, window=_mask_only(window), sinks=sinks,
    )
    return attn, None


# How finely a dense lane's slab is read by its prefix: eighths of its length
# (at 4096 slots a rung is one 512-token prefill chunk; over a 2816-token
# prompt eighths read 44 % of the slots where powers of two read 56 %), each a
# whole number of 128-slot tiles, so a slab under 1024 slots is read whole
_READ_RUNGS = 8
_READ_TILE = 128
# The longest slice worth making before the dots (a pinned read, read_pinned):
# the chip's compiler keeps one of 64 MiB in fast memory and makes a longer one
# in HBM (described-v5e compile of `trinl-window-docs`' step: the rung of 2048
# slots, 67 108 864 B, is placed in S(1), the one of 4096 is not), and a slice
# made in HBM is read, written and read again (my chip run, PR 49: two slices
# of 470 MB took 0.17 s of a capture each before their dots, where the parent's
# program spends 0.093 on each whole slab of 537 MB)
_READ_SLICE_BYTES = 64 * 2**20


def read_rungs(
    cfg: ModelConfig, stacks, q_len: int, batch: int, compressed_kv: bool
) -> Tuple[int, ...]:
    """The static lengths a full layer's slab of dense lanes may be read to by
    `q_len` queries a row over `batch` rows, the last of them the whole slab.
    `stacks`: the two stacked arrays [L, B, T, ...] the read is taken from (or
    their shapes: keys and values per head or one row a token, a latent
    cache's latents and roped keys), which say what the config cannot: the
    slab's length and what a slot of each holds; `compressed_kv`: they are
    stored narrower than the activations. From static shapes alone, so the
    program (_lanes_read) and its counter (`kv.slots_read`,
    runtime/batch_executor) agree and nothing recompiles with the lengths.
    One rung, the slab, where a rung would not be a whole number of tiles, and
    where the Pallas kernel is chosen (by the slab's length: its block loop is
    bounded by the valid length already). Where a slice is made before the
    dots (read_pinned), no rung at which it is over _READ_SLICE_BYTES."""
    t = stacks[0].shape[2]
    step = read_step(t)
    if not step or attention_ops.flash_enabled(
            cfg, t, compressed_kv=compressed_kv, q_len=q_len, batch=batch):
        return (t,)
    # what a slot of a pinned stack holds: its trailing widths in its own dtype
    pinned = [math.prod(stack.shape[3:]) * jnp.dtype(stack.dtype).itemsize
              for stack in stacks if read_pinned(stack, q_len)]
    longest = _READ_SLICE_BYTES // (batch * max(pinned)) if pinned else t
    return tuple(r for r in range(step, t, step) if r <= longest) + (t,)


def read_step(t: int) -> int:
    """What two neighbouring rungs of a `t`-slot slab's ladder lie apart, 0
    where it has none: a rung would not be a whole number of tiles."""
    return 0 if t % (_READ_RUNGS * _READ_TILE) else t // _READ_RUNGS


def read_pinned(stack, q_len: int) -> bool:
    """Whether a rung's slice of `stack` [L, B, T, ...] keeps the stack's own
    row-major layout, and so is made before the dots (_lanes_read). Where a
    dot asks another layout of its keys (heads before slots of a stack with a
    head axis; slots innermost for a chunk's weighted sum over rows, and for
    every product with a row narrower than a tile's 128 lanes: a latent
    cache's roped keys) that layout otherwise runs back through the slice to
    the branch's parameter, and every branch copies the WHOLE stack
    (described-v5e compiles: 1.51 GB of temporaries in `q4b-sat-chat`'s step
    where pinned has 0.002; a `copy` of `bf16[4,1,4096,512]{2,3,1,0}` in
    granite's chunk; sixteen of `bf16[6,16,16384,64]{2,3,1,0}` in
    `xing-latent-docs`' step, 0.500 GB where pinned has 0.415). A decode step
    over rows as wide as a tile takes them as they lie, and unpinned its slice
    fuses into the dots."""
    return stack.ndim == 5 or q_len > 1 or stack.shape[-1] < cachelib.TILE_LANES


def read_rung(longest, rungs: Tuple[int, ...]):
    """Index in `rungs` (read_rungs) of the shortest that covers `longest`
    valid slots (the last where none does): a Python int on the host, a
    traced int32 in a program."""
    xp = jnp if isinstance(longest, jax.Array) else np
    return xp.sum(longest > xp.asarray(rungs[:-1], xp.int32))


def _lanes_read(cfg, k_stack, v_stack, at, ctx, q, window, attend):
    """What the chunk of queries `q` [B, S, ...] reads of layer `at` of the
    stacked dense-lane slabs [L, B, T, ...] (any layout: T is axis 2; keys and
    values, or a latent cache's latents and roped keys),
    handed to `attend(k, v, kv_positions, valid length, window, flash)` -> its
    result; `flash`: whether the Pallas kernel is chosen, by the length of the
    buffer the read is taken from (the slab's, or the window's slice). A STATIC
    int window narrows the read to a window-covering slice (_windowed_slice,
    the sliding-layer read fast path). A traced window (or None) masks only,
    and the slab is read to the shortest of read_rungs that covers the
    LONGEST row's valid length (a row whose ctx.write_mask is False left out:
    nobody reads what it computes), chosen here, in the program: one branch a
    rung, each slicing [at, :, :rung] out of the STACK (a layer's slab taken
    out before the conditional would be the copy of it this rule is there to
    spare) and attending at that length. Slot index stays absolute position and
    the valid length is what it was, so a slot of the rung beyond it is masked
    as it was in the whole slab: the same mathematics over fewer masked slots.
    The kernel bounds its own loop by the valid length: where it is chosen,
    as where the slab is one rung, the slab is read as a view of the stack.
    A slice that read_pinned names keeps the stack's row-major layout."""
    s, t = q.shape[1], k_stack.shape[2]
    shapes = dict(compressed_kv=k_stack.dtype != q.dtype, q_len=s, batch=q.shape[0])
    windowed = isinstance(window, int) and window > 0
    rungs = read_rungs(cfg, (k_stack, v_stack), **shapes)
    by_prefix = not windowed and len(rungs) > 1
    if not by_prefix:
        k_slab, v_slab = _slab(k_stack, at), _slab(v_stack, at)
    end = ctx.write_pos + s
    if cfg.is_block_diffusion and ctx.real_end is not None:
        # a query sees to the end of its block, so the bucket's padding is
        # kept out by the valid length (causality does it elsewhere)
        end = ctx.real_end
    if windowed:
        k_att, v_att, kvpos, valid = _windowed_slice(k_slab, v_slab, end, window, s)
        return attend(k_att, v_att, kvpos, valid, jnp.int32(window),
                      attention_ops.flash_enabled(cfg, k_att.shape[1], **shapes))
    if not by_prefix:
        return attend(k_slab, v_slab, None, end, window,
                      attention_ops.flash_enabled(cfg, t, **shapes))
    live = end if ctx.write_mask is None else jnp.where(ctx.write_mask, end, 0)

    def to(rung):
        def read(ks, vs):
            head = lambda stack: jax.lax.dynamic_slice(
                stack, (at,) + (0,) * (stack.ndim - 1),
                (1, stack.shape[1], rung) + stack.shape[3:])[0]
            k_att, v_att = head(ks), head(vs)
            row_major = Layout(major_to_minor=tuple(range(k_att.ndim)))
            if read_pinned(ks, s):
                k_att = with_layout_constraint(k_att, row_major)
            if read_pinned(vs, s):
                v_att = with_layout_constraint(v_att, row_major)
            return attend(k_att, v_att, None, end, window, False)
        return read

    with jax.named_scope("prefix_read"):
        return jax.lax.switch(
            read_rung(jnp.max(live), rungs), [to(r) for r in rungs], k_stack, v_stack)


def _attend_update_lanes(cfg, q, k, v, q_positions, entry, at, ctx, window, sinks):
    """Dense lanes: write at ctx.write_pos, attend over the layer's slab
    (what of it: _lanes_read); attention masks per row through the valid
    length where write_pos is per row."""
    new = cachelib.DenseEntry(
        k=_lanes_write(entry.k, at, k, ctx.write_pos, ctx.write_mask),
        v=_lanes_write(entry.v, at, v, ctx.write_pos, ctx.write_mask),
    )
    attend = lambda k_att, v_att, kvpos, valid, win, flash: _attend(
        cfg, q, k_att, v_att, q_positions, valid, kv_positions=kvpos, window=win, sinks=sinks,
        flash=flash)
    return _lanes_read(cfg, new.k, new.v, at, ctx, q, window, attend), new


def _rows_query(q, nkv: int):
    """The block-diagonal query of row-stored keys: head i of kv group n
    (q [B, S, Nq, D]) laid into an Nkv * D wide row that is zero outside
    columns [n*D, (n+1)*D), so ONE contraction over the row gives each head
    the score of its own kv head (every product added has a zero factor)."""
    b, s, nq, d = q.shape
    own = jnp.eye(nkv, dtype=bool)[:, None, :, None]  # [n, 1, m, 1]
    qh = q.reshape(b, s, nkv, nq // nkv, 1, d)
    return jnp.where(own, qh, jnp.zeros((), q.dtype)).reshape(b, s, nq, nkv * d)


def _rows_own(out, nq: int, nkv: int):
    """What a head keeps of the weighted sum of value ROWS: out
    [B, S, Nq * Nkv * D] -> its own kv head's D columns, [B, S, Nq * D]."""
    b, s, _ = out.shape
    o = out.reshape(b, s, nkv, nq // nkv, nkv, -1)
    return jnp.stack([o[:, :, n, :, n] for n in range(nkv)], axis=2).reshape(b, s, -1)


def _attend_update_rows(cfg, q, k, v, q_positions, entry, at, ctx, window, sinks):
    """Dense lanes of a head narrower than a tile (core.cache.RowEntry):
    the chunk's keys (values) of all kv heads written as one row a token,
    and attention as ONE kv "head" the row's width against the
    block-diagonal query, for a decode step and a chunk alike: the stack is
    written and read where it lies, in one layout (taken apart into heads it
    is re-laid whole around the layer loop). Same mask, scale (the REAL
    head's), softmax and window as _attend_update_lanes. The flash kernel
    wants heads: where it is chosen the layer's slab is viewed as heads."""
    b, s, nq, d = q.shape
    nkv = k.shape[2]
    new = cachelib.RowEntry(
        k=_lanes_write(entry.k, at, k.reshape(b, s, nkv * d), ctx.write_pos, ctx.write_mask),
        v=_lanes_write(entry.v, at, v.reshape(b, s, nkv * d), ctx.write_pos, ctx.write_mask),
    )
    # a chunk over rows of heads as wide as a tile attends head by head: the
    # block-diagonal query costs Nkv times the products, nothing beside a
    # step's read of the slab, a chunk's whole budget at 30 kv heads
    by_head = s > 1 and d == cachelib.TILE_LANES

    def attend(new_k, new_v, kvpos, end, win, flash):  # [B, T or less, Nkv * D]
        if flash or by_head:
            heads = lambda a: a.reshape(*a.shape[:2], nkv, d)
            return _attend(
                cfg, q, heads(new_k), heads(new_v), q_positions, end,
                kv_positions=kvpos, window=win, sinks=sinks, flash=flash,
            )
        out = gqa_attention(
            _rows_query(q, nkv), new_k[:, :, None], new_v[:, :, None], q_positions, end,
            kv_positions=kvpos, scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
            window=win, sinks=sinks,
        )
        return _rows_own(out, nq, nkv)

    return _lanes_read(cfg, new.k, new.v, at, ctx, q, window, attend), new


def _attend_update_ring(cfg, q, k, v, q_positions, entry, at, ctx, window, sinks):
    """A sliding layer's O(window) ring (_ring_attend_update); the window
    is the entry's own."""
    real_end = ctx.write_pos + q.shape[1] if ctx.real_end is None else ctx.real_end
    attn, nk, nv = _ring_attend_update(
        cfg, q, k, v, q_positions, entry.k, entry.v, at, ctx.write_pos, real_end,
        entry.window, sinks, ctx.write_mask,
    )
    return attn, cachelib.RingEntry(k=nk, v=nv, window=entry.window)


def _attend_update_paged(cfg, q, k, v, q_positions, entry, at, ctx, window, sinks):
    """Paged pool: scatter the chunk's K/V through the block table, then
    attend over the table-gathered view. Write target for row b, chunk
    offset i at absolute position p = wp[b] + i is slot
    (at, table[b, p // bs], p % bs) of the stacked pools; rows past
    real_end (bucket padding) and rows with write_mask False scatter to
    block index NB, which mode="drop" discards — on dense lanes garbage
    writes were lane-private and safe, here a dropped write is the ONLY
    safe garbage (blocks are shared property). Windows stay mask-only:
    paged storage is one layout for every layer by construction
    (core.cache)."""
    b, s = q.shape[0], q.shape[1]
    nb_, bs_ = entry.k.shape[1], entry.k.shape[2]
    wp = jnp.asarray(ctx.write_pos)
    col = lambda a: a[:, None] if a.ndim == 1 else jnp.broadcast_to(a, (b, 1))
    pos = col(wp) + jnp.arange(s)[None, :]  # [B, S]
    real_end = ctx.write_pos + s if ctx.real_end is None else ctx.real_end
    ok = pos < col(jnp.asarray(real_end))
    if ctx.write_mask is not None:
        ok &= ctx.write_mask[:, None]
    chain = jnp.clip(pos // bs_, 0, ctx.table.shape[1] - 1)
    blk = jnp.take_along_axis(ctx.table, chain, axis=1)  # [B, S]
    blk = jnp.where(ok, blk, nb_)  # NB = out of range -> dropped
    off = pos % bs_
    new = cachelib.PagedEntry(
        k=entry.k.at[at, blk, off].set(_to_cache_dtype(k, entry.k.dtype), mode="drop"),
        v=entry.v.at[at, blk, off].set(_to_cache_dtype(v, entry.v.dtype), mode="drop"),
    )
    attn = gqa_attention(
        q, _slab(new.k, at), _slab(new.v, at), q_positions, ctx.write_pos + s,
        scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
        window=_mask_only(window), sinks=sinks, block_table=ctx.table,
    )
    return attn, new


# the write-then-read of each cache layout (core.cache owns the layouts):
# (cfg, q, k, v, q_positions, entry, at, ctx, window, sinks) -> (attn, entry')
# where `entry` is the layers' STACKED entries and `at` this layer's index
# in them: the chunk's rows are written into the stack, the layer's slab
# is read as a view of it, and the whole stack comes back
_ATTEND_UPDATE = {
    type(None): _attend_chunk,
    cachelib.DenseEntry: _attend_update_lanes,
    cachelib.RowEntry: _attend_update_rows,
    cachelib.RingEntry: _attend_update_ring,
    cachelib.PagedEntry: _attend_update_paged,
}


def _gqa_attend_update(lp, cfg, x, cos, sin, q_positions, entry, at, ctx, window, adapters):
    """Per-head q/k/v from the layer's input `x` (normed where the block norms
    it), the chunk's keys and values written at layer `at` of the stacked
    entries in whichever layout they have, and attention over that layer ->
    (attn [B, S, Nq*D], entry')."""
    b, s, _h = x.shape
    d = cfg.head_dim
    q = lora_ops.apply_lane_delta(qdot(x, lp["q_proj"]), x, "q_proj", adapters)
    k = lora_ops.apply_lane_delta(qdot(x, lp["k_proj"]), x, "k_proj", adapters)
    v = lora_ops.apply_lane_delta(qdot(x, lp["v_proj"]), x, "v_proj", adapters)
    if cfg.attn_bias:  # Qwen2 family
        q = q + lp["q_bias"]
        k = k + lp["k_bias"]
        v = v + lp["v_bias"]
    if cfg.qk_norm_flat:  # Olmo: one norm over the whole projection, before the split
        with jax.named_scope("qk_norm_flat"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.rms_norm_plus_one)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.rms_norm_plus_one)
    q = q.reshape(b, s, q.shape[-1] // d, d)
    k = k.reshape(b, s, k.shape[-1] // d, d)
    v = v.reshape(b, s, v.shape[-1] // d, d)
    if cfg.qk_norm and not cfg.qk_norm_flat:  # Qwen3 signature feature
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps, cfg.rms_norm_plus_one)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps, cfg.rms_norm_plus_one)
    if cos is not None:  # None: a model without position embedding
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    sinks = lp["sinks"] if cfg.attn_sinks else None
    # RoPE above took the true positions; the mask of every layout below
    # compares a slot's position with the last one the query sees
    q_positions = visible_until(cfg, q_positions)
    attn, entry = _ATTEND_UPDATE[type(entry)](
        cfg, q, k, v, q_positions, entry, at, ctx, window, sinks)
    if cfg.attn_gate:  # afmoe: each head's output gated from the layer's input
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(qdot(x, lp["attn_gate_proj"]).astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate).astype(attn.dtype)
    return attn, entry


def visible_until(cfg: ModelConfig, q_positions: jax.Array) -> jax.Array:
    """The last position a query at `q_positions` attends to: its own for a
    causal decoder, the last of its block for a model generated by blocks
    (bidirectional inside a block, causal across: slot j is seen iff
    j // B <= p // B). Every mask is `slot position <= this` under the valid
    length, so with block_length 1 nothing is traced and the programs are
    the causal ones."""
    b = cfg.block_length
    if b == 1:
        return q_positions
    return (q_positions // b + 1) * b - 1


def _causal_mask(t: int, kv_valid_len, kv_positions, q_positions) -> jax.Array:
    """[B, S, T]: slot j is attended iff j < kv_valid_len and its absolute
    position (slot index where `kv_positions` is None) <= the query's."""
    slots = jnp.arange(t)
    valid = jnp.asarray(kv_valid_len)
    if valid.ndim == 0:
        valid = valid[None]
    kpos = slots if kv_positions is None else kv_positions
    if kpos.ndim == 1:
        kpos = kpos[None, :]
    return (slots[None, None, :] < valid[:, None, None]) & (
        kpos[:, None, :] <= q_positions[:, :, None]
    )


def mla_attend(
    cfg: ModelConfig,
    q_nope: jax.Array,  # [B, S, N, Dn]
    q_pe: jax.Array,  # [B, S, N, Dr], roped
    c: jax.Array,  # [B, T, R] normed latents (a cache buffer or the chunk's own)
    k_pe: jax.Array,  # [B, T, Dr] the roped key all heads share
    w_kvb: jax.Array,  # [R, N * (Dn + Dv)]
    q_positions: jax.Array,  # [B, S]
    kv_valid_len,  # scalar or [B]
    kv_positions: Optional[jax.Array] = None,
    absorbed: bool = False,
    sum_step: int = 0,
) -> jax.Array:
    """Latent attention -> [B, S, N * Dv]; softmax in float32 (with
    `sum_step` its sum is taken `sum_step` slots at a time: _softmax_by_steps).

    Expanded: keys and values per head are made from the latents
    (k_nope_i, v_i = c W_kvb,i) and attended as ordinary heads. Absorbed:
    W_kvb's key half is folded into the query and its value half applied
    after the weighted sum of LATENTS, so nothing per head exists over T:
    score_i = (q_nope_i W_UK,i^T) . c + q_pe_i . k_pe. The two are the same
    mathematics; decode (S == 1) runs absorbed over the cache."""
    b, s, n, dn = q_nope.shape
    dv = cfg.v_head_dim
    if c.dtype != q_nope.dtype:  # compressed cache storage: upcast at the read
        c, k_pe = c.astype(q_nope.dtype), k_pe.astype(q_nope.dtype)
    w = w_kvb.reshape(w_kvb.shape[0], n, dn + dv)
    w_uk, w_uv = w[..., :dn], w[..., dn:]
    mask = _causal_mask(c.shape[1], kv_valid_len, kv_positions, q_positions)
    rope_scores = jnp.einsum("bsnd,btd->bnst", q_pe, k_pe)
    if absorbed:
        q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, w_uk)
        scores = jnp.einsum("bsnr,btr->bnst", q_lat, c)
    else:
        k_nope = jnp.einsum("btr,rnd->btnd", c, w_uk)
        v = jnp.einsum("btr,rnd->btnd", c, w_uv)
        scores = jnp.einsum("bsnd,btnd->bnst", q_nope, k_nope)
    scores = (scores.astype(jnp.float32) + rope_scores.astype(jnp.float32)) * cfg.attn_scale
    scores = jnp.where(mask[:, None], scores, jnp.float32(-1e30))
    probs = _softmax_by_steps(scores, sum_step) if sum_step else jax.nn.softmax(scores, axis=-1)
    probs = probs.astype(q_nope.dtype)
    if absorbed:
        o_lat = jnp.einsum("bnst,btr->bsnr", probs, c)
        out = jnp.einsum("bsnr,rnd->bsnd", o_lat, w_uv)
    else:
        out = jnp.einsum("bnst,btnd->bsnd", probs, v)
    return out.reshape(b, s, n * dv)


def _softmax_by_steps(scores: jax.Array, step: int) -> jax.Array:
    """jax.nn.softmax over the last axis (a whole number of `step`s), its SUM
    taken step by step and the steps' sums added in slot order. A step masked
    whole adds an exact zero, so a row gives the same bits at every length
    that covers its valid slots: where several rows share one read, whose
    length the LONGEST sets (_lanes_read), a session's tokens must not turn on
    who else is on the chip. One reduction over the axis splits it as its
    length suggests (my chip run, PR 56: the sums of `xing-latent-docs`' step
    over 6144 and over 8192, 12288 or 16384 slots differ in their last bits;
    the scores and both products over the slots do not), and the cell's
    16-lane probe then answered other tokens beside sessions than alone."""
    e = jnp.exp(scores - scores.max(-1, keepdims=True))
    sums = e.reshape(*e.shape[:-1], -1, step).sum(-1)
    total = sums[..., 0]
    for i in range(1, sums.shape[-1]):
        total = total + sums[..., i]
    return e / total[..., None]


def _mla_attend_update(lp, cfg, x, cos, sin, q_positions, entry, at, ctx):
    """Latent attention's side of decoder_layer: queries per head (from x by
    one projection, or with cfg.q_lora_rank by two around a norm), ONE
    latent and ONE roped key per token written at layer `at` of the stacked
    entries (core.cache.LatentEntry; None = no cache, the chunk attends to
    itself), attention over that layer's lanes (what of them: _lanes_read;
    absorbed for one query a row, expanded for a chunk) ->
    (attn [B, S, N * Dv], entry')."""
    b, s, _h = x.shape
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    if "q_a_proj" in lp:  # cfg.q_lora_rank: the queries through a latent of their own
        with jax.named_scope("mla_q_lora"):
            c_q = rms_norm(x @ lp["q_a_proj"], lp["q_a_norm"], cfg.rms_norm_eps)
            q = c_q @ lp["q_b_proj"]
    else:
        q = qdot(x, lp["q_proj"])
    q = q.reshape(b, s, -1, dn + dr)
    q_nope, q_pe = q[..., :dn], apply_rope(q[..., dn:], cos, sin)
    kv_a = x @ lp["kv_a_proj"]
    c = rms_norm(kv_a[..., :r], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = apply_rope(kv_a[..., None, r:], cos, sin)[:, :, 0]
    if entry is None:
        with jax.named_scope("mla_attend"):
            attn = mla_attend(
                cfg, q_nope, q_pe, c, k_pe, lp["kv_b_proj"], q_positions,
                jnp.int32(s), kv_positions=q_positions,
            )
        return attn, None
    new = cachelib.LatentEntry(
        c=_lanes_write(entry.c, at, c, ctx.write_pos, ctx.write_mask),
        r=_lanes_write(entry.r, at, k_pe, ctx.write_pos, ctx.write_mask),
    )
    # rows that share a read: the longest sets its length, no row's bits may turn on it
    sum_step = read_step(entry.c.shape[2]) if b > 1 else 0
    attend = lambda c_att, r_att, kvpos, valid, win, flash: mla_attend(
        cfg, q_nope, q_pe, c_att, r_att, lp["kv_b_proj"], q_positions, valid,
        kv_positions=kvpos, absorbed=s == 1, sum_step=sum_step)
    with jax.named_scope("mla_attend"):
        return _lanes_read(cfg, new.c, new.r, at, ctx, q_nope, None, attend), new


# ---------------------------------------------------------------------------
# State layers in attention's place (cfg.layer_types): Mamba-2 and the gated
# delta rule. One cache entry (core.cache.StateEntry), one convolution, one set
# of rules for padding, write_mask and position 0; two recurrences
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST  # float32 operands stay float32 on the MXU


def _state_enter(cfg: ModelConfig, entry, at, ctx, b: int, s: int, dtype):
    """What a state layer's chunk of `s` positions enters with -> (the state
    [B, *cfg.state_shape] f32 (a decode row of a state held with heads side by
    side, core.cache.state_fold: as it is held), the convolution's kept inputs
    [B, K-1, C] in `dtype`, how many of the chunk's positions are real [B], and the stored
    state and inputs of layer `at` as they lie, None without a cache). A
    row written at position 0 enters with zeros whatever its lane held: that
    is how a session starts; with no `entry` every row does."""
    f32 = jnp.float32
    if entry is None:
        return (jnp.zeros((b, *cfg.state_shape), f32),
                jnp.zeros((b, *cfg.state_conv_shape), dtype),
                jnp.full((b,), s, jnp.int32), None, None)
    row = lambda v: jnp.broadcast_to(jnp.asarray(v, jnp.int32), (b,))  # noqa: E731
    start = row(ctx.write_pos)
    real = jnp.clip(row(start + s if ctx.real_end is None else ctx.real_end) - start, 0, s)
    fresh = (start == 0)[:, None, None]
    s_old, kept_old = _slab(entry.s, at), _slab(entry.conv, at)
    s_in = jnp.where(fresh[..., None], 0.0, s_old.astype(f32))
    kept = jnp.where(fresh, 0, kept_old).astype(dtype)
    if s > 1:  # a chunk computes head by head: heads held side by side are taken apart
        s_in = cachelib.state_heads_apart(s_in, cachelib.state_fold(cfg))
    return s_in, kept, real, s_old, kept_old


def _state_leave(entry, at, ctx, s_new, kept, s_old, kept_old):
    """The stacked StateEntry with layer `at` holding `s_new` and `kept`; a
    row whose ctx.write_mask is False keeps what it had, by a select."""
    # a chunk's state comes head by head: laid as it is held (core.cache.state_fold)
    s_new = cachelib.state_heads_beside(s_new, s_new.shape[1] // s_old.shape[1])
    s_new = s_new.astype(entry.s.dtype)
    kept = kept.astype(entry.conv.dtype)
    if ctx.write_mask is not None:
        s_new = jnp.where(ctx.write_mask[:, None, None, None], s_new, s_old)
        kept = jnp.where(ctx.write_mask[:, None, None], kept, kept_old)
    put = jax.lax.dynamic_update_index_in_dim
    return cachelib.StateEntry(s=put(entry.s, s_new, at, 0), conv=put(entry.conv, kept, at, 0))


def _causal_conv(kept, x, taps, bias, real):
    """The depthwise causal convolution of a state layer and its SiLU:
    x [B, S, C] behind the K-1 inputs `kept` [B, K-1, C] that came before it,
    taps [K, C] (tap i meets the input K-1-i back), `bias` [C] or None ->
    (silu(conv) [B, S, C] f32, the K-1 inputs before the first padding
    position, `real` [B] positions into the chunk)."""
    f32 = jnp.float32
    s, k = x.shape[1], taps.shape[0]
    full = jnp.concatenate([kept, x], axis=1)  # [B, K-1 + S, C]
    w = taps.astype(f32)
    bias = None if bias is None else bias.astype(f32)
    acc = sum(full[:, i:i + s].astype(f32) * w[i] for i in range(k))
    if bias is not None:
        acc = bias + acc
    out = jax.nn.silu(acc)
    kept = full[:, 1:] if s == 1 else jax.vmap(
        lambda f, r: jax.lax.dynamic_slice_in_dim(f, r, k - 1, axis=0))(full, real)
    return out, kept


def ssm_chunked(x, dt, a, bm, cm, s_in, tile: int):
    """The chunked (SSD) form of  S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t,
    y_t = S_t C_t  over a chunk, float32 throughout.

    x [B, S, G, Hg, P], dt [B, S, G, Hg] (0 where a position is padding: the
    state passes it unchanged), a [G, Hg] (negative), bm / cm [B, S, G, N]
    (the heads of a group share them), s_in [B, G, Hg, P, N] the state the
    chunk enters with -> (y [B, S, G, Hg, P], the state it leaves).

    The chunk is cut into tiles of `tile` positions (it has to divide S).
    Inside a tile position i reads position j <= i through the decay between
    them (a [tile, tile] matrix a head, as attention without a softmax); a
    tile's own contribution to the state and the state it entered with go
    from tile to tile through a sequential scan over the few tiles."""
    b, s, g, hg, p = x.shape
    c = s // tile
    t5 = lambda v: v.reshape(b, c, tile, *v.shape[2:])  # noqa: E731
    x, dt, bm, cm = t5(x), t5(dt), t5(bm), t5(cm)
    acum = jnp.cumsum(dt * a, axis=2)  # [b, c, q, g, h]: log decay from the tile's start
    dtx = dt[..., None] * x  # [b, c, q, g, h, p]
    # inside a tile
    diff = acum[:, :, :, None] - acum[:, :, None, :]  # [b, c, i, j, g, h]
    seen = (jnp.arange(tile)[:, None] >= jnp.arange(tile)[None, :])[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(seen, diff, -jnp.inf))
    cb = jnp.einsum("bcign,bcjgn->bcijg", cm, bm, precision=_HI)
    y = jnp.einsum("bcijgh,bcjghp->bcighp", cb[..., None] * decay, dtx, precision=_HI)
    # each tile's own contribution to the state at its end, and its whole decay
    to_end = jnp.exp(acum[:, :, -1:] - acum)  # [b, c, q, g, h]
    own = jnp.einsum("bcjghp,bcjgn->bcghpn", to_end[..., None] * dtx, bm, precision=_HI)
    whole = jnp.exp(acum[:, :, -1])  # [b, c, g, h]

    def carry(state, xs):
        own_c, whole_c = xs
        return state * whole_c[..., None, None] + own_c, state

    s_out, entered = jax.lax.scan(
        carry, s_in, (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    entered = jnp.moveaxis(entered, 0, 1)  # [b, c, g, h, p, n]: the state each tile enters with
    y = y + jnp.einsum("bcign,bcghpn->bcighp", cm, entered, precision=_HI) * jnp.exp(acum)[..., None]
    return y.reshape(b, s, g, hg, p), s_out


def mamba_mixer(lp: Params, cfg: ModelConfig, x: jax.Array, entry, at, ctx):
    """A Mamba-2 block over the normed input x [B, S, H] -> (out [B, S, H],
    entry'). `entry` is the state layers' STACKED core.cache.StateEntry and
    `at` this layer's index in it (None: no cache, the chunk starts from
    zeros and nothing is kept).

        [z | xBC | dt] = x W_in
        xBC_t = silu(sum_i w_conv[i] xBC_{t-(K-1)+i} + b_conv)      causal, depthwise
        [x_t | B_t | C_t] = xBC_t;  d_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        S_t = exp(d_t A) S_{t-1} + d_t x_t (x) B_t;   y_t = S_t C_t + D x_t
        out = RMSNorm(y_t silu(z_t); w_norm) W_out        the norm over each group

    One recurrence in two forms: S == 1 (a decode row) is one multiply-add
    over the state; a longer chunk runs `ssm_chunked`, tiled by
    cfg.mamba_chunk_size where that divides it. The state is float32 while
    it is computed and is held in cfg.state_dtype between steps.

    What must not move a session's state: a position at or past
    ctx.real_end (bucket padding) has d_t = 0, so the state passes it
    bit-unchanged, and the convolution's kept inputs are the last K-1
    BEFORE real_end; a row whose ctx.write_mask is False keeps its old state
    and inputs by a select. A row written at position 0 enters with zeros
    whatever its lane held: that is how a session starts."""
    f32 = jnp.float32
    b, s, _ = x.shape
    heads, p, n, g = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state, cfg.mamba_groups
    di, cd, hg = cfg.mamba_inner, cfg.mamba_conv_dim, cfg.mamba_heads // cfg.mamba_groups
    proj = qdot(x, lp["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:di + cd], proj[..., di + cd:]
    s_in, kept, real, s_old, kept_old = _state_enter(cfg, entry, at, ctx, b, s, xbc.dtype)
    with jax.named_scope("ssm_conv"):
        xbc, kept = _causal_conv(kept, xbc, lp["conv_w"], lp["conv_b"], real)
    xs = xbc[..., :di].reshape(b, s, g, hg, p)
    bm = xbc[..., di:di + g * n].reshape(b, s, g, n)
    cm = xbc[..., di + g * n:].reshape(b, s, g, n)
    step = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
    step = jnp.where((jnp.arange(s)[None, :] < real[:, None])[..., None], step, 0.0)
    step = step.reshape(b, s, g, hg)
    a = -jnp.exp(lp["A_log"].astype(f32)).reshape(g, hg)
    s_in = s_in.reshape(b, g, hg, p, n)
    if s == 1:
        with jax.named_scope("ssm_update"):
            d1, x1 = step[:, 0], xs[:, 0]
            s_new = (s_in * jnp.exp(d1 * a)[..., None, None]
                     + (d1[..., None] * x1)[..., None] * bm[:, 0, :, None, None, :])
            y = jnp.sum(s_new * cm[:, 0, :, None, None, :], axis=-1)[:, None]
    else:
        with jax.named_scope("ssm_scan"):
            q = cfg.mamba_chunk_size
            y, s_new = ssm_chunked(xs, step, a, bm, cm, s_in, q if s % q == 0 else s)
    y = y + lp["D"].astype(f32).reshape(g, hg)[:, :, None] * xs
    with jax.named_scope("ssm_gate_norm"):  # the norm is over each group's channels
        y = (y.reshape(b, s, di) * jax.nn.silu(z.astype(f32))).reshape(b, s, g, di // g)
        y = rms_norm(y, lp["gate_norm"].reshape(g, di // g), cfg.rms_norm_eps)
        y = y.reshape(b, s, di).astype(x.dtype)
    out = qdot(y, lp["out_proj"])
    if entry is None:
        return out, None
    return out, _state_leave(
        entry, at, ctx, s_new.reshape(b, heads, p, n), kept, s_old, kept_old)


def gated_delta_chunked(q, k, v, g, beta, s_in, tile: int):
    """The chunked (WY / UT) form of the gated delta rule over a chunk,
    float32 throughout:

        S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;  o_t = S_t^T q_t

    q, k [B, S, H, Dk] (normalised, q scaled), v [B, S, H, Dv], g (<= 0; 0
    with beta 0 where a position is padding: the state passes it unchanged)
    and beta [B, S, H], s_in [B, H, Dk, Dv] -> (o [B, S, H, Dv], the state the
    chunk leaves).

    The chunk is cut into tiles of `tile` positions (it has to divide S).
    With G_t the log decay cumulated from the tile's start and S0 the state
    the tile enters with, the tile's u solve ONE unit lower-triangular system

        (I + L) U = beta V - (beta K exp(G)) S0,   L[i, j] = beta_i exp(G_i - G_j) k_i . k_j  (j < i)

    whose two right-hand sides do not depend on S0, so every tile's solve
    runs at once; what depends on S0 goes from tile to tile through a
    sequential scan over the few tiles. Only DIFFERENCES of the cumulated
    log decay with i >= j are exponentiated (and G itself, <= 0), as
    ssm_chunked does: nothing overflows however long the decay."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = s // tile
    t5 = lambda a: jnp.moveaxis(a.reshape(b, c, tile, *a.shape[2:]), 3, 2)  # noqa: E731
    q, k, v, g, beta = t5(q), t5(k), t5(v), t5(g), t5(beta)  # [b, c, h, tile(, d)]
    gc = jnp.cumsum(g, axis=-1)
    at = jnp.arange(tile)
    seen = at[:, None] >= at[None, :]
    decay = jnp.exp(jnp.where(seen, gc[..., :, None] - gc[..., None, :], -jnp.inf))  # [.., i, j]
    kb = k * beta[..., None]
    lower = jnp.where(at[:, None] > at[None, :],
                      jnp.einsum("bchid,bchjd->bchij", kb, k, precision=_HI) * decay, 0.0)
    rhs = jnp.concatenate([v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        lower + jnp.eye(tile, dtype=lower.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u_own, k_dec = sol[..., :dv], sol[..., dv:]  # U with S0 = 0, and what S0 takes off it
    qk = jnp.einsum("bchid,bchjd->bchij", q, k, precision=_HI) * decay  # j <= i
    q_dec = q * jnp.exp(gc)[..., None]
    k_end = k * jnp.exp(gc[..., -1:] - gc)[..., None]  # each key's decay to the tile's end
    whole = jnp.exp(gc[..., -1])  # [b, c, h]

    def carry(state, xs):
        u_c, kd_c, qk_c, qd_c, ke_c, whole_c = xs
        u = u_c - jnp.einsum("bhtk,bhkv->bhtv", kd_c, state, precision=_HI)
        o = (jnp.einsum("bhtk,bhkv->bhtv", qd_c, state, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk_c, u, precision=_HI))
        state = (state * whole_c[..., None, None]
                 + jnp.einsum("bhtk,bhtv->bhkv", ke_c, u, precision=_HI))
        return state, o

    s_out, o = jax.lax.scan(
        carry, s_in, tuple(jnp.moveaxis(a, 1, 0) for a in (u_own, k_dec, qk, q_dec, k_end, whole)))
    o = jnp.moveaxis(o, (0, 1, 2, 3), (1, 0, 3, 2))  # [c, b, h, t, v] -> [b, c, t, h, v]
    return o.reshape(b, s, h, dv), s_out


def _delta_update_folded(q, k, v, g, beta, state, fold: int):
    """One token of the gated delta rule over a state held with `fold` heads
    side by side (core.cache.state_held_shape): q, k [B, H, Dk], v [B, H, Dv],
    g and beta [B, H], state [B, H / fold, Dk, fold * Dv] f32 -> (o [B, H, Dv],
    the state after the token, as it is held). The update as
    gated_delta_mixer writes it, in TWO passes over the state where that reads
    it three times: the first reads the OLD state alone, S^T k and S^T q at
    once; the second writes S_t = exp(g) S + k u^T where it lies; and

        o_t = S_t^T q_t = exp(g_t) S_{t-1}^T q_t + (k_t . q_t) u_t

    needs no read of the new state. One reader before one writer: nothing of
    the stack is copied around the update (as written, the described-v5e
    compile of Dk 96, Dv 192 split it into three fusions of which the second
    rewrites the stack while the third still reads it, and copied the whole
    stack around each)."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    grp = h // fold
    col = jnp.arange(fold * dv) // dv  # the head of a group that a column belongs to

    def per_head(x):  # [B, H] -> [B, H / fold, fold * Dv]: a head's scalar over its columns
        return jnp.repeat(x.reshape(b, grp, fold), dv, axis=-1)

    def over_columns(x):  # [B, H, Dk] -> [B, H / fold, Dk, fold * Dv]: a head's vector down them
        parts = x.reshape(b, grp, fold, dk)
        out = parts[:, :, fold - 1, :, None]
        for i in reversed(range(fold - 1)):
            out = jnp.where(col <= i, parts[:, :, i, :, None], out)
        return out

    decay, kc, qc = per_head(jnp.exp(g)), over_columns(k), over_columns(q)
    sk = jnp.sum(state * kc, axis=-2) * decay  # S'^T k, S' = exp(g) S
    sq = jnp.sum(state * qc, axis=-2) * decay
    u = per_head(beta) * (v.reshape(b, grp, fold * dv) - sk)
    s_new = state * decay[:, :, None, :] + kc * u[:, :, None, :]
    o = sq + per_head(jnp.sum(k * q, axis=-1)) * u
    return o.reshape(b, h, dv), s_new


def _l2norm(u: jax.Array) -> jax.Array:
    return u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)


def gated_delta_mixer(lp: Params, cfg: ModelConfig, x: jax.Array, entry, at, ctx):
    """A Gated-DeltaNet block (the Qwen3-Next and Olmo-Hybrid families) over
    the layer's input x [B, S, H] (normed where the block norms it) -> (out
    [B, S, H], entry'); `entry`, `at` and `ctx` as mamba_mixer takes them, and
    the same rules for padding, write_mask and position 0 (_state_enter,
    _causal_conv, _state_leave).

        [q | k | v | z] = x W_in;  [b | a] = x W_ba
        [q|k|v]_t = silu(sum_i w_conv[i] [q|k|v]_{t-(K-1)+i})        causal, depthwise, no bias
        value head h reads key head h // (value heads / key heads)
        q = l2norm(q) / sqrt(Dk);  k = l2norm(k);  beta_t = sigmoid(b_t)
                                  (cfg.linear_allow_neg_eigval: 2 sigmoid(b_t))
        g_t = -exp(A_log) softplus(a_t + dt_bias)
        S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;  o_t = S_t^T q_t
        out = (RMSNorm(o_t; w_norm) silu(z_t)) W_out      per head: the norm first, then the gate

    One recurrence in two forms: S == 1 (a decode row) is the update as
    written; a longer chunk runs `gated_delta_chunked`, tiled by
    cfg.linear_chunk_size where that divides it. The state [B, value heads,
    Dk, Dv] is float32 while it is computed and is held in cfg.state_dtype
    between steps; where it is held with heads side by side
    (core.cache.state_fold: a value size that would pad its tiles) a decode row
    updates it as it is held (_delta_update_folded) and a chunk takes the heads
    apart and lays them back. A padding position has g_t = 0 and beta_t = 0."""
    f32 = jnp.float32
    b, s, _ = x.shape
    hk, hv, dk, dv = (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
    kd, cd = cfg.linear_key_dim, cfg.linear_conv_dim
    proj = qdot(x, lp["in_proj"])
    qkv, z = proj[..., :cd], proj[..., cd:]
    ba = qdot(x, lp["ba_proj"]).astype(f32)
    s_in, kept, real, s_old, kept_old = _state_enter(cfg, entry, at, ctx, b, s, qkv.dtype)
    with jax.named_scope("gdn_conv"):
        qkv, kept = _causal_conv(kept, qkv, lp["conv_w"], None, real)
    heads = lambda u, n, d: jnp.repeat(u.reshape(b, s, n, d), hv // n, axis=2)  # noqa: E731
    q = _l2norm(heads(qkv[..., :kd], hk, dk)) * dk ** -0.5
    k = _l2norm(heads(qkv[..., kd:2 * kd], hk, dk))
    v = qkv[..., 2 * kd:].reshape(b, s, hv, dv)
    valid = (jnp.arange(s)[None, :] < real[:, None])[..., None]
    beta = jax.nn.sigmoid(ba[..., :hv])
    if cfg.linear_allow_neg_eigval:  # Olmo-Hybrid: I - beta k k^T may turn a key's direction round
        beta = 2.0 * beta
    beta = jnp.where(valid, beta, 0.0)
    g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + lp["dt_bias"].astype(f32))
    g = jnp.where(valid, g, 0.0)
    fold = cachelib.state_fold(cfg) if entry is not None else 1
    if s == 1 and fold > 1:
        with jax.named_scope("gdn_update"):
            o, s_new = _delta_update_folded(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s_in, fold)
            o = o[:, None]
    elif s == 1:
        with jax.named_scope("gdn_update"):
            q1, k1, v1, g1, b1 = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
            s_new = s_in * jnp.exp(g1)[..., None, None]
            u = b1[..., None] * (v1 - jnp.sum(s_new * k1[..., None], axis=-2))
            s_new = s_new + k1[..., None] * u[..., None, :]
            o = jnp.sum(s_new * q1[..., None], axis=-2)[:, None]
    else:
        with jax.named_scope("gdn_scan"):
            t = cfg.linear_chunk_size
            o, s_new = gated_delta_chunked(q, k, v, g, beta, s_in, t if s % t == 0 else s)
    with jax.named_scope("gdn_gate_norm"):  # per head; this one norm scales by w
        y = rms_norm(o, lp["gate_norm"], cfg.rms_norm_eps)
        y = (y * jax.nn.silu(z.astype(f32).reshape(b, s, hv, dv))).reshape(b, s, hv * dv)
    out = qdot(y.astype(x.dtype), lp["out_proj"])
    if entry is None:
        return out, None
    return out, _state_leave(entry, at, ctx, s_new, kept, s_old, kept_old)


# ---------------------------------------------------------------------------
# The residual path: one hidden state and the plain add, or (cfg.hc_mult) a
# stream of hidden states under manifold-constrained hyper-connections. ONE
# pair of functions stands between decoder_layer and either
# ---------------------------------------------------------------------------


SINKHORN_TRIP = 5  # rounds a trip of the loop: a trip costs the device a few microseconds,
#   an unrolled round costs the compiler some eighty operations a sublayer


def sinkhorn(m, iters: int, eps: float):
    """n rows of n positive arrays of one shape (a matrix a token, its
    entries apart) -> the same, doubly stochastic: `iters` rounds of column
    normalisation (every column divided by its sum over the rows + eps), then
    row normalisation. float32. Every entry is an array of its own, so a
    round is elementwise work between equal shapes and the rounds of a trip
    fuse: no slice, no reduction, no axis of n for the compiler to lay out.
    The rounds run as a loop of trips of SINKHORN_TRIP rounds each."""
    n = len(m)
    assert iters % SINKHORN_TRIP == 0, (iters, SINKHORN_TRIP)

    def trip(_, m):
        for _ in range(SINKHORN_TRIP):
            cols = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
            m = [[m[i][j] / cols[j] for j in range(n)] for i in range(n)]
            rows = [sum(m[i]) + eps for i in range(n)]
            m = tuple(tuple(m[i][j] / rows[i] for j in range(n)) for i in range(n))
        return m

    m = tuple(map(tuple, m))  # the loop's carry keeps ONE structure
    return jax.lax.fori_loop(0, iters // SINKHORN_TRIP, trip, m)


class StreamMaps(NamedTuple):
    """A sublayer's three maps over the stream, a token each: float32 arrays
    [B, S], one an entry."""

    pre: tuple  # n: Hpre, what the sublayer reads of each hidden state
    post: tuple  # n: Hpost, what each takes of the sublayer's output
    res: tuple  # n rows of n: Hres, doubly stochastic; stream' = Hres stream


def stream_maps(lp: Params, cfg: ModelConfig, stream: jax.Array, sub: str) -> StreamMaps:
    """The maps of sublayer `sub` (STREAM_SUBLAYERS) from the stream
    [n, B, S, H] itself: x' = RMSNorm over all n H values of a token (no
    weight), H~ = scale * (x' proj) + bias, Hpre = sigmoid, Hpost = 2 sigmoid,
    Hres = Sinkhorn(exp(clip(H~res))). All of it in float32 whatever the
    stream is carried in; the norm's factor multiplies the 24 products, not
    the 14 336 values."""
    n, f32 = cfg.hc_mult, jnp.float32
    with jax.named_scope("hc_map"):
        xf = stream.astype(f32)
        raw = jnp.einsum("nbsh,knh->kbs", xf, lp[f"hc_{sub}_proj"].astype(f32), precision=_HI)
        ms = jnp.sum(xf * xf, axis=(0, 3)) / (n * cfg.hidden_size)  # [B, S]
        scale, bias = lp[f"hc_{sub}_scale"], lp[f"hc_{sub}_bias"]
        a_of = [0] * n + [1] * n + [2] * (n * n)  # which of a_pre, a_post, a_res a row takes
        inv = jax.lax.rsqrt(ms + cfg.rms_norm_eps)
        h = [raw[k] * inv * scale[a_of[k]] + bias[k] for k in range(cfg.hc_maps)]
        pre = tuple(jax.nn.sigmoid(x) for x in h[:n])
        post = tuple(2.0 * jax.nn.sigmoid(x) for x in h[n : 2 * n])
    with jax.named_scope("hc_sinkhorn"):
        lim = cfg.hc_res_clamp
        m = [[jnp.exp(jnp.clip(h[2 * n + i * n + j], -lim, lim)) for j in range(n)]
             for i in range(n)]
        res = sinkhorn(m, cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return StreamMaps(pre, post, res)


def stream_read(lp: Params, cfg: ModelConfig, hidden: jax.Array, sub: str):
    """What sublayer `sub` reads of the residual -> (x [B, S, H], maps).
    One hidden state: itself, and no maps. A stream [n, B, S, H]: Hpre's mix
    of its n hidden states, and the maps that stream_join writes back by."""
    if not cfg.hc_mult:
        return hidden, None
    maps = stream_maps(lp, cfg, hidden, sub)
    with jax.named_scope("hc_read"):
        x = sum(maps.pre[i][..., None] * hidden[i].astype(jnp.float32)
                for i in range(cfg.hc_mult))
    return x.astype(hidden.dtype), maps


def stream_join(hidden: jax.Array, y: jax.Array, maps: Optional[StreamMaps]) -> jax.Array:
    """A sublayer's output y [B, S, H] joins the residual: hidden + y, or for
    a stream Hres stream + Hpost^T y (float32, carried on in the stream's
    dtype)."""
    if maps is None:
        return hidden + y.astype(hidden.dtype)
    with jax.named_scope("hc_join"):
        n, yf = hidden.shape[0], y.astype(jnp.float32)
        rows = [hidden[i].astype(jnp.float32) for i in range(n)]
        return jnp.stack([
            sum(maps.res[m][i][..., None] * rows[i] for i in range(n))
            + maps.post[m][..., None] * yf
            for m in range(n)
        ]).astype(hidden.dtype)


def decoder_layer(
    lp: Params,
    cfg: ModelConfig,
    hidden: jax.Array,  # [B, S, H]; a stream of cfg.hc_mult of them [n, B, S, H]
    cos: jax.Array,
    sin: jax.Array,
    q_positions: jax.Array,  # [B, S]
    entry=None,  # the STACKED cache entries this layer's lives in (core.cache:
    #   dense lanes, latent, ring or paged pool, the kv axis a tp rank's
    #   local heads); None = no cache, the chunk attends to itself
    at=None,  # this layer's index in `entry`: a python int or a traced scalar
    ctx=None,  # core.cache.CacheCtx: where the chunk is written, the same
    #   for every layer
    window=None,  # this layer's sliding window: None (global), a STATIC
    #   int (a sliding layer whose place in the stack is known) or a traced
    #   scalar (mask-only; <= 0 = global)
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    adapters=None,  # this layer's per-lane LoRA slice (multi-tenant
    #   registry): {"layers": {target: (a [B, in, r], b [B, r, out])},
    #   "scale": [B] f32} — slot-0 (base) lanes carry zero A/B and apply
    #   nothing (ops.lora.apply_lane_delta)
):
    """One residual decoder block: a mixer, then a feed-forward (of a model
    whose layers are one sublayer each, whichever of the two the layer's stack
    holds), two
    independent choices, each sublayer with its RMSNorm where
    cfg.norm_placement puts it: on its input (the pre-norm block), on its
    input and its output (Gemma's sandwich), or on its output alone (Olmo:
    h = x + Norm(Mixer(x)), y = h + Norm(MLP(h)), both reading the residual
    stream as it is). What a sublayer reads of the residual and how its output
    joins it is stream_read / stream_join's: the plain add, or with
    cfg.hc_mult the hyper-connection maps over a stream of hidden states. The mixer is GQA + per-head q/k RMSNorm (the Qwen3
    signature feature — reference qwen3_server_module.py:123-124; Olmo's norm
    over the whole projection), latent attention (cfg.is_mla), or for a layer
    of the state kind's stack a Mamba-2 block or the gated delta rule; the
    feed-forward is the dense MLP or routed experts beside a shared one, by
    what the layer's stack holds.

    Returns (hidden', entry', chosen experts [B, S, K] or None for a dense
    MLP); entry' is the whole stack with this layer's rows of the chunk
    written into it. With no entry the layer runs cache-free over the full
    sequence (prefill-style parity testing).

    Shard-polymorphic: head counts come from the projection widths, not the
    config, so the same code runs full-width (single device / pp stage) or
    on a tensor-parallel head shard inside shard_map — pass `tp_axis` there
    and the block psums its two row-parallel outputs (attention o_proj and
    the MLP down-proj, the Megatron minimum; tp.sharded_decoder_layer is
    the cache-free training sibling). The entry then holds this rank's
    local heads only. `ep_axis` (MoE only) additionally shards the expert
    axis: attention replicates across ep ranks (its weights and KV carry no
    ep spec, mesh.layer_param_specs) while each rank computes its local
    experts' contribution and the combine psums over (ep, tp).

    Caller contract: ctx.write_pos + S must be <= a dense entry's length T.
    dynamic_update_slice clamps out-of-range starts (it would silently
    overwrite the newest slots), so overflow must be prevented host-side —
    the runtime's session registry enforces this before dispatch
    (inferd_tpu.core.cache.KVCache.ensure_room).
    """
    p1 = cfg.rms_norm_plus_one
    # Granite's residual multiplier: each sublayer's output is scaled before
    # it joins the residual (1.0: absent, nothing traced)
    scaled = (lambda y: y) if cfg.residual_multiplier == 1.0 else (
        lambda y: y * cfg.residual_multiplier)

    def out_norm(y, w):  # a sublayer's output, before it joins the residual
        with jax.named_scope("out_norm"):
            return rms_norm(y, w, cfg.rms_norm_eps, p1)

    # a layer of a model whose layers are ONE sublayer each (cfg.single_sublayer)
    # is what its stack holds: a mixer and nothing after it, or a
    # feed-forward and no mixer before it; no product stands in for the other
    if "in_proj" in lp or "o_proj" in lp:
        x, maps = stream_read(lp, cfg, hidden, "attn")
        if cfg.norm_before:
            x = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps, p1)
        if "in_proj" in lp:  # a state layer: its stack holds no q / k / v
            if tp_axis or ep_axis or adapters is not None or not isinstance(
                    entry, (type(None), cachelib.StateEntry)):
                raise ValueError(
                    f"{cfg.name}: a state-space layer runs whole on its device over a "
                    "StateEntry (no tensor/expert parallel shard, no adapter)"
                )
            mixer = gated_delta_mixer if "ba_proj" in lp else mamba_mixer
            attn_out, entry = mixer(lp, cfg, x, entry, at, ctx)
        else:
            if cfg.is_mla:
                if (tp_axis or ep_axis or window is not None or adapters is not None
                        or not isinstance(entry, (type(None), cachelib.LatentEntry))):
                    raise ValueError(
                        f"{cfg.name}: latent attention runs on the dense lane layout only "
                        "(no tensor/expert parallel shard, paged pool, ring, window or adapter)"
                    )
                attn, entry = _mla_attend_update(lp, cfg, x, cos, sin, q_positions, entry, at, ctx)
            else:
                attn, entry = _gqa_attend_update(
                    lp, cfg, x, cos, sin, q_positions, entry, at, ctx, window, adapters
                )

            attn_out = lora_ops.apply_lane_delta(
                qdot(attn, lp["o_proj"]), attn, "o_proj", adapters
            )
            if tp_axis is not None:  # row-parallel o_proj: partial sums per rank
                attn_out = jax.lax.psum(attn_out, tp_axis)
            if cfg.o_bias:  # replicated bias joins AFTER the partial-sum combine
                attn_out = attn_out + lp["o_bias"]
        if cfg.norm_after:  # Gemma, Olmo: the mixer's output normed pre-residual
            attn_out = out_norm(attn_out, lp["post_norm"])
        hidden = stream_join(hidden, scaled(attn_out), maps)
    if "down_proj" not in lp:
        return hidden, entry, None

    # the feed-forward: dense, or routed experts beside a shared one, whatever
    # the mixer above was
    x, maps = stream_read(lp, cfg, hidden, "ffn")
    if cfg.norm_before:
        pre_ffn = lp["pre_ffn_norm"] if cfg.norm_after else lp["post_norm"]
        x = rms_norm(x, pre_ffn, cfg.rms_norm_eps, p1)
    expert_axes = tuple(a for a in (ep_axis, tp_axis) if a is not None)
    topi = None
    if cfg.is_moe and "router" in lp:  # a leading dense layer has no router
        if adapters is not None:
            raise ValueError(
                "the adapter registry targets dense decoder projections — "
                "MoE expert adapters are unsupported (merge_adapter "
                "rejects them for the same reason)"
            )
        if expert_axes:
            if cfg.n_shared_experts:
                raise ValueError(
                    f"{cfg.name}: the sharded expert layer has no shared expert"
                )
            # expert weights shard over (ep, tp) on the EXPERT axis
            # (mesh.layer_param_specs); local dispatch + psum combine
            from inferd_tpu.parallel import tp as tplib  # lazy: tp imports us

            mlp_out = tplib.moe_mlp_sharded(lp, cfg, x, expert_axes)
        else:
            mlp_out, topi = moe_mlp_routed(lp, cfg, x)
    else:
        mlp_out = swiglu_mlp(lp, x, act_fn(cfg), lane_adapters=adapters)
        if tp_axis is not None:  # row-parallel down-proj
            mlp_out = jax.lax.psum(mlp_out, tp_axis)
    if cfg.norm_after:
        mlp_out = out_norm(mlp_out, lp["post_ffn_norm"])
    return stream_join(hidden, scaled(mlp_out), maps), entry, topi


# ---------------------------------------------------------------------------
# Stage / model forward
# ---------------------------------------------------------------------------


def slice_layers(layers: Params, start: int, end: int) -> Params:
    """Stage partition = a slice of the stacked layer pytree, [start, end)."""
    return jax.tree.map(lambda a: a[start:end], layers)


def layer_windows(cfg: ModelConfig, n_layers: int, layer_offset) -> Optional[jax.Array]:
    """Per-layer sliding windows [n_layers] int32 (0 = global), or None when
    the config has no sliding window. GLOBAL layer index (layer_offset + i)
    selects the kind from cfg.layer_pattern, so a pipeline stage's slice
    applies the same windows the full model would. layer_offset may be a
    traced scalar (pp rank inside shard_map): the windows then only mask."""
    if not cfg.sliding_window:
        return None
    kinds = cfg.layer_pattern
    per_kind = jnp.asarray(
        [cfg.sliding_window if kind == "sliding" else 0 for kind in kinds], jnp.int32
    )
    idx = jnp.asarray(layer_offset, jnp.int32) + jnp.arange(n_layers, dtype=jnp.int32)
    return per_kind[idx % len(kinds)]


def _stack_len(layers: Params) -> int:
    return jax.tree.leaves(layers)[0].shape[0]


def layer_groups(params: Params) -> list:
    """The model's homogeneous layer stacks in order: the leading dense
    group of a model with experts (`dense_layers`, if it has one), then
    `layers`. Every other model is the one group it always was."""
    head = [params["dense_layers"]] if "dense_layers" in params else []
    return head + [params["layers"]]


def forward_layers(
    layers: Params,
    cfg: ModelConfig,
    hidden: jax.Array,  # [B, S, H]
    positions: jax.Array,  # [B, S]
    entries: tuple = (),  # the stack's cache entries stacked over layers
    #   (core.cache `entries()`): one stack per kind of cfg.layer_pattern, or
    #   one stack for every layer in layer order; () = no cache
    ctx=None,  # core.cache.CacheCtx, layer-invariant
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    layer_offset=0,  # global index of layers[0] (the layer pattern)
    cache_offset: int = 0,  # the layer of `entries` that layers[0] writes: a
    #   model whose layers come in groups hands every group the whole cache
    adapters=None,  # multi-tenant LoRA pools + per-lane ids (the ops.lora
    #   pool pytree: {"a", "b", "scale", "ids"}); gathered ONCE here, the
    #   per-layer slices ride the scan like the cache entries
    state_layers: Optional[Params] = None,  # a model with state layers: the
    #   state kind's stack, `layers` then being the attention kind's; the two
    #   ride the scan by kind, as a cache split by kind does
    ffn_layers: Optional[Params] = None,  # and, of a model whose layers are one
    #   sublayer each, the stack of those that are a feed-forward alone
):
    """Run a stack of decoder layers via ONE lax.scan over periods of
    cfg.layer_pattern -> (hidden, entries', chosen experts [L, B, S, K] or
    None for a stack without routers).

    The scan carries the hidden states AND the stacked cache entries — one
    compiled body per period regardless of stage depth. A layer writes only
    its chunk's rows into the carried stack, at (its own index, row,
    write_pos), and reads its slab as a view of that stack: no layer's slab
    is taken out, rewritten and stacked again, so a donated cache is updated
    where it lies. `tp_axis`/`ep_axis` (inside shard_map only) run each
    block on its tensor-/expert-parallel shard — see decoder_layer.

    With a Python-int `layer_offset` every layer's kind is static: a
    sliding layer's window is a static int, so on dense lanes its attention
    reads only a window-covering KV slice from HBM (_windowed_slice), and a
    ring entry is met by the layer it belongs to. A stack that does not
    start or end on a period boundary (odd offset, odd length) unrolls the
    layers before the first and after the last whole period through the
    same body, writing into the same stacks. With a traced offset (a pp
    rank inside shard_map) the kinds are unknown until run time: every
    layer is its own period and the windows ride the scan as a traced,
    mask-only input.
    """
    cos = sin = None
    if cfg.position_embedding == "rope":
        cos, sin = rope_cos_sin(positions, cfg.rope_dim, cfg.rope_theta, cfg)
    n = _stack_len(layers)
    split = state_layers is not None  # a weight stack per kind
    if split:
        n += _stack_len(state_layers) + (_stack_len(ffn_layers) if ffn_layers else 0)

    # multi-tenant LoRA: one per-lane gather of the stacked pools, then
    # the layer-leading slices ride the scan as ordinary xs (None = no
    # adapters = the scan traces exactly as without them). When the
    # fused kernel is measured faster (ops.lora.fused_delta_enabled), the
    # gather never happens: the stacked pools close over the scan body
    # (layer-invariant, like the paged block table), only the int32 layer
    # index rides the xs, and fused_lane_delta picks each lane's slot
    # in-kernel at every projection.
    ad_per = ad_scale = None
    fused_ad = adapters is not None and lora_ops.fused_delta_enabled()
    if fused_ad:
        ad_per = jnp.arange(n, dtype=jnp.int32)
    elif adapters is not None:
        ad_per, ad_scale = lora_ops.gather_lanes(adapters)

    def _ad(ad_sl):
        if ad_sl is None:
            return None
        if fused_ad:
            return {"pools": adapters, "layer": ad_sl}
        return {"layers": ad_sl, "scale": ad_scale}

    # a routed layer's expert weights do not ride the scan: a layer finds its
    # experts in the stack where it lies (ExpertStack), as it finds its cache
    # entry, so the grouped product's kernel is handed no copy of them
    def without_experts(stack):  # -> (the stack's other leaves, its expert weights)
        held = {
            name: stack[name] for name in ("gate_proj", "up_proj", "down_proj")
            if "router" in stack and isinstance(stack.get(name), jax.Array)
        }
        return {name: a for name, a in stack.items() if name not in held}, held

    experts = {}
    if not split:
        rest, experts = without_experts(layers)
        if experts:
            layers = rest

    static = isinstance(layer_offset, int)
    kinds = cfg.layer_pattern if static else (None,)
    period = len(kinds)
    head = min(-layer_offset % period, n) if period > 1 else 0
    nper, tail = divmod(n - head, period)
    per_layer = (layers, None if static else layer_windows(cfg, n, layer_offset), ad_per)
    uniq = tuple(dict.fromkeys(kinds))  # the kinds, in the order a period first meets them
    by_kind = len(entries) == len(uniq) > 1  # a stack per kind; else ONE, in layer order
    if cfg.nope_kinds and not static:
        raise ValueError(
            f"{cfg.name}: which layers carry no rope is known by their kind, "
            "and a traced layer offset (a pp rank) knows no kind"
        )
    if cfg.has_state_layers and not (
            split and static and head == tail == 0 and adapters is None):
        raise ValueError(
            f"{cfg.name}: a model with state-space layers runs whole periods of its two "
            "weight stacks from a static offset (one stage, no adapter)"
        )
    if split:  # a weight stack per kind, and beside each its expert weights
        stack_of = lambda kind: (  # noqa: E731
            state_layers if kind in STATE_KINDS else ffn_layers if kind in FFN_KINDS else layers)
        stacks, experts_of = zip(*(without_experts(stack_of(kind)) for kind in uniq))
        per_layer = (stacks, None, None)

    def place(j):  # of place j in a period: (its kind's stack, its rank among the
        #   period's layers of that kind, how many of them a period has)
        return uniq.index(kinds[j]), kinds[:j].count(kinds[j]), kinds.count(kinds[j])

    def home(i, p=0):  # (stack, index in it) of the entry of layer i + p periods
        m = cache_offset + i
        if not by_kind:
            return 0, m + p * period
        s, rank, count = place((layer_offset + i) % period)
        return s, m // period * count + rank + (p if count == 1 else p * count)

    def layer(h, ents, i, per_i, p=0):
        lp, win, ad_sl = per_i
        if experts:
            lp = {**lp, **{name: ExpertStack(a, i + p * period) for name, a in experts.items()}}
        rope = (cos, sin)
        if static:
            kind = kinds[(layer_offset + i) % period]
            win = int(cfg.sliding_window) if kind == "sliding" else None
            if kind in cfg.nope_kinds:  # this kind of layer carries no rotation
                rope = (None, None)
        s, at = home(i, p) if ents else (0, None)
        h, stack, topi = decoder_layer(
            lp, cfg, h, *rope, positions, ents[s] if ents else None, at, ctx, win,
            tp_axis, ep_axis, _ad(ad_sl),
        )
        if ents:
            ents = ents[:s] + (stack,) + ents[s + 1 :]
        return h, ents, topi

    # a period of one layer is the layer: no reshape enters its program
    def fold(tree, lo, count):  # leaves [n, ...] -> `count` periods [count, period, ...]
        if period == 1:
            return tree
        if split:  # nothing rides as xs: a layer reads its weights where they lie
            return None
        return jax.tree.map(
            lambda a: a[lo : lo + count * period].reshape(count, period, *a.shape[1:]), tree
        )

    def pick(tree, j, p=0):  # one period's leaves [period, ...] -> layer j's
        if split:
            # a stack per kind: layer j of period p is a view of its kind's
            # stack, as its cache entry is (a period's weights folded into
            # the scan's inputs would be copied out, nine layers at a time)
            s, rank, count = place(j)
            lp = jax.tree.map(lambda a: _slab(a, p * count + rank), per_layer[0][s])
            lp.update(
                {name: ExpertStack(a, p * count + rank) for name, a in experts_of[s].items()})
            return lp, None, None
        return tree if period == 1 else jax.tree.map(lambda a: a[j], tree)

    def pack(vals):  # the period's layers' values -> leaves [period, ...]; of layers
        #   with a router and layers without, [the period's routers, ...]
        if period == 1:
            return vals[0]
        vals = [v for v in vals if v is not None]
        return jax.tree.map(lambda *a: jnp.stack(a), *vals) if vals else None

    chosen = []

    def single(h, ents, i):  # a layer outside the whole periods, through the same body
        h, ents, topi = layer(h, ents, i, jax.tree.map(lambda a: a[i], per_layer))
        chosen.append(None if topi is None else topi[None])
        return h, ents

    for i in range(head):
        hidden, entries = single(hidden, entries, i)
    if nper:
        def body(carry, xs):
            h, ents = carry
            per_p, p = xs
            tops = []
            for j in range(period):
                h, ents, topi = layer(h, ents, head + j, pick(per_p, j, p), p)
                tops.append(topi)
            return (h, ents), pack(tops)

        periods = jnp.arange(nper, dtype=jnp.int32) if entries or split or experts else None
        (hidden, entries), tops = jax.lax.scan(
            body, (hidden, entries), (fold(per_layer, head, nper), periods)
        )
        if period > 1:  # [nper, period, ...] -> [nper * period, ...]
            tops = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), tops)
        chosen.append(tops)
    for i in range(n - tail, n):
        hidden, entries = single(hidden, entries, i)

    chosen = [t for t in chosen if t is not None]
    if len(chosen) > 1:  # routers outside the whole periods too
        chosen = [jnp.concatenate(chosen, axis=0)]
    return hidden, entries, chosen[0] if chosen else None


def forward_layers_cached(
    layers: Params,
    cfg: ModelConfig,
    hidden: jax.Array,
    positions: jax.Array,
    cache,  # core.cache.KVCache (ring-split, uniform or latent) or PagedKVCache
    cache_write_pos,  # slot where the chunk's keys and values go: scalar, or [B] per row
    real_end=None,  # scalar or [B]: first bucket-padding position (ring and
    #   paged layouts; default cache_write_pos + S)
    layer_offset=0,
    cache_offset: int = 0,  # the cache's layer that layers[0] writes (a
    #   model in groups hands each group the whole cache and its first layer)
    write_mask=None,  # [B] bool: rows whose KV writes commit; False rows
    #   compute but write NOTHING, in any layout — a non-participating
    #   co-batch lane must never scribble on a block another lane or a
    #   shared prefix may own, and the mesh decode pass must leave an
    #   inactive slot's rows as they are (parallel/infer._rows_pass); None
    #   = every row writes
    adapters=None,  # multi-tenant LoRA pool pytree + per-lane ids
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    state_layers: Optional[Params] = None,  # the state kind's stack (forward_layers)
    ffn_layers: Optional[Params] = None,  # the stack of layers that are a feed-forward alone
):
    """THE cached stage/model forward: every layout of core.cache (dense
    lanes, latent, ring-split, paged pool) goes through here and through
    the one scan of forward_layers. Returns (hidden, new cache with the
    INPUT length — the caller advances it —, chosen experts [L, B, S, K] or
    None for a stack without routers)."""
    hidden, entries, topi = forward_layers(
        layers, cfg, hidden, positions, cache.entries(cfg),
        cache.ctx(cache_write_pos, real_end, write_mask),
        tp_axis, ep_axis, layer_offset, cache_offset, adapters, state_layers, ffn_layers,
    )
    return hidden, cache.with_entries(entries), topi


def forward_cached(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S]
    positions: Optional[jax.Array],
    cache,  # core.cache.KVCache or PagedKVCache
    cache_write_pos,
    real_end=None,
    write_mask=None,  # [B] bool: rows whose KV writes commit (None = all)
    adapters=None,  # multi-tenant LoRA pool pytree + per-lane ids
):
    """Whole-model cached forward -> (logits [B, S, V], new cache with
    the INPUT length — the caller advances it —, the experts each row chose
    in each sparse layer [Ls, B, S, K] int32, or None for a model without
    routers). A model with leading dense layers runs its groups one after
    the other, each writing its own layers of the one cache."""
    if positions is None:
        start = cache_write_pos
        if jnp.ndim(start) == 1:
            start = start[:, None]
        positions = start + jnp.broadcast_to(
            jnp.arange(tokens.shape[1]), tokens.shape
        )
    hidden = embed(params, tokens, cfg)
    topi, offset = None, 0
    for layers in layer_groups(params):
        hidden, cache, chosen = forward_layers_cached(
            layers, cfg, hidden, positions, cache, cache_write_pos,
            real_end, layer_offset=offset, cache_offset=offset,
            write_mask=write_mask, adapters=adapters,
            state_layers=params.get("state_layers"), ffn_layers=params.get("ffn_layers"),
        )
        if chosen is not None:
            topi = chosen  # the one group with routers
        offset += _stack_len(layers)
    return unembed(params, cfg, hidden), cache, topi


def decode_k(
    params: Params,
    cfg: ModelConfig,
    toks: jax.Array,  # [B] int32: each row's last emitted token
    cache,  # core.cache.KVCache with batch B
    lengths: jax.Array,  # [B] int32 per-row KV fill (next write position)
    active: jax.Array,  # [B] bool: rows that advance this window
    keys: jax.Array,  # [B, 2] uint32 per-row PRNG keys (chained split/step)
    k: int,  # STATIC: fused decode steps per dispatch
    temperature: float = 0.0,  # STATIC sampling params (greedy/temperature
    top_k: int = 0,  #   fast path: passthrough_filters skips every
    top_p: float = 1.0,  #   full-vocab filter op — core.sampling)
    min_p: float = 0.0,
    eos: Optional[jax.Array] = None,  # [B] or scalar int32; < 0 disables
    top_n: int = 0,  # STATIC
    want_lp: bool = False,  # STATIC
    adapters=None,  # multi-tenant LoRA pool pytree + per-lane ids (scan-
    #   invariant: the pools and ids close over the body; every fused
    #   step serves each lane its own adapter)
):
    """K fused decode steps in ONE compiled graph — THE multi-step decode
    inner loop shared by the solo stage executor (runtime/executor), the
    whole-model batched executor (runtime/batch_executor via
    core.batch.BatchedEngine), and the stage-batch executor
    (runtime/stage_batch). Sampling (greedy argmax or the
    temperature/top-k/top-p chain) and every KV write stay on device; the
    host syncs ONCE per K tokens instead of once per token, which is what
    amortizes the per-dispatch host overhead (ROADMAP S1).

    Per-row semantics (the core/batch lane invariants, unchanged):
      * positions/masking come from `lengths`, not cache.length — inactive
        rows compute garbage at their frozen frontier and write nothing
        (`write_mask`);
      * `lengths` advances only for rows active at step entry; `n_new`
        counts exactly those advances;
      * with `eos` >= 0, a row DEACTIVATES the step after it emits its
        stop token (the eos token itself is emitted and counted), so a
        stop mid-window costs only the window tail — token-exact with the
        K=1 loop, no host fallback;
      * sampled rows chain `key, sub = split(key)` per step — the same
        schedule as the per-step path, so tokens are bit-identical to K
        single-step dispatches with the same starting keys. Keys split
        every step for every row (deactivated rows too — their emitted
        tokens are discarded with the tail, and a stopped row's key is
        never used again), matching the pre-existing batched scan.

    NOT jitted here: callers wrap it in their own jit with the cache
    donated (donation-clean carry — the KV update runs in place on device
    instead of copying the whole buffer per step).

    Returns (cache, seq [k, B], n_new [B], keys' [B, 2], lps [k, B],
    top_ids [k, B, top_n], top_lps [k, B, top_n]).
    """
    from inferd_tpu.core import sampling as samplib

    b = toks.shape[0]
    eos_arr = (
        None if eos is None
        else jnp.broadcast_to(jnp.asarray(eos, jnp.int32), (b,))
    )

    def body(carry, _):
        cache, toks, lengths, act, keys, n_new = carry
        pos = lengths[:, None]  # [B, 1] absolute per row
        logits, nc, _ = forward_cached(
            params, cfg, toks[:, None], pos, cache, lengths,
            real_end=lengths + 1,
            # a frozen row's tail-step garbage write is DROPPED, not
            # parked at its frontier slot — a paged pool's blocks are
            # shared property (on dense lanes nothing would read it)
            write_mask=act,
            adapters=adapters,
        )
        last = logits[:, 0]  # [B, V]
        if temperature == 0.0:
            ntok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            nkeys = keys
        else:
            pairs = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
            nkeys, subs = pairs[:, 0], pairs[:, 1]
            ntok = jax.vmap(
                lambda l, kk: samplib.sample(
                    l[None], kk, temperature, top_k, top_p, min_p
                )[0]
            )(last, subs).astype(jnp.int32)
        # frozen rows re-emit their token and write nothing real
        ntok = jnp.where(act, ntok, toks)
        lp, ti, tl = (
            samplib.logprob_topn(last, ntok, top_n) if want_lp
            else (jnp.zeros((b,), jnp.float32),
                  jnp.zeros((b, 0), jnp.int32),
                  jnp.zeros((b, 0), jnp.float32))
        )
        nlen = lengths + act.astype(jnp.int32)
        n_new = n_new + act.astype(jnp.int32)
        nact = act if eos_arr is None else (
            act & ((eos_arr < 0) | (ntok != eos_arr))
        )
        return (nc, ntok, nlen, nact, nkeys, n_new), (ntok, lp, ti, tl)

    init = (cache, toks, lengths, active, keys, jnp.zeros((b,), jnp.int32))
    (cache, _, _, _, keys, n_new), (seq, lps, tis, tls) = jax.lax.scan(
        body, init, None, length=k
    )
    return cache, seq, n_new, keys, lps, tis, tls


def make_decode_k_serve(cfg: ModelConfig):
    """The SERVING jit over decode_k — ONE definition shared by
    core.batch.BatchedEngine (`_decode_k_serve`) and the stage-batch
    executor (runtime/stage_batch `_decode_k_all`), so the
    runtime.executor.fuse_kstep_group dispatch contract
    (params, cache, toks, lengths, active, keys, eos, k, t, tk, tp, mp)
    -> (cache, seq [k, L], n_new [L], keys' [L, 2]) cannot drift between
    the two co-batch executors.

    Sampling params ride per-request (static per compile) instead of a
    baked SamplingConfig, and per-lane `eos` [L] deactivates a lane
    in-graph the step after it emits its stop token (the tail writes
    garbage at the frozen frontier — the core/batch invariant; the
    lane's next real step overwrites it).

    Static sampling is a deliberate tradeoff: every distinct
    (k, temperature, top_k, top_p, min_p) tuple compiles its own
    variant, so an adversarial client cycling sampling configs can grow
    the jit cache. The greedy default shares ONE graph whose passthrough
    filters skip every full-vocab op, and real serving traffic clusters
    on a handful of configs; making the params dynamic would put the
    full filter chain in every graph and tax the common case to bound
    the pathological one. K itself is already quantized by the budget
    clamp."""
    from functools import partial

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("k", "temperature", "top_k", "top_p",
                              "min_p"))
    def _decode_k_serve(params, cache, toks, lengths, active, keys, eos,
                        k: int, temperature: float, top_k: int,
                        top_p: float, min_p: float, ads=None):
        cache, seq, n_new, keys, _lps, _tis, _tls = decode_k(
            params, cfg, toks, cache, lengths, active, keys, k,
            temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p, eos=eos, adapters=ads,
        )
        return cache, seq, n_new, keys

    return _decode_k_serve


def embed(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    e = params["embed"][tokens]
    if cfg.scale_embedding:
        # Gemma: scale by sqrt(H), normalizer rounded to the activation
        # dtype first (matches HF's torch.tensor(h**0.5, dtype=...))
        e = e * jnp.asarray(math.sqrt(cfg.hidden_size), e.dtype)
    if cfg.embedding_multiplier != 1.0:  # Granite
        e = e * jnp.asarray(cfg.embedding_multiplier, e.dtype)
    if cfg.hc_mult:  # the residual stream: the embedding enters as n copies [n, B, S, H]
        e = jnp.broadcast_to(e, (cfg.hc_mult, *e.shape))
    return e


def unembed(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """Final norm + LM head -> float32 logits (+ Gemma final softcapping);
    a residual stream's hidden states are summed first."""
    if cfg.hc_mult:
        hidden = jnp.sum(hidden.astype(jnp.float32), axis=0).astype(hidden.dtype)
    x = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps, cfg.rms_norm_plus_one)
    if cfg.tie_word_embeddings:
        if "lm_head_q" in params:  # quantized shadow of embed.T (ops.quant)
            z = qdot(x, params["lm_head_q"]).astype(jnp.float32)
        else:
            z = (x @ params["embed"].T).astype(jnp.float32)
    else:
        z = qdot(x, params["lm_head"]).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:  # Granite DIVIDES its logits
        z = z / cfg.logits_scaling
    return attention_ops.apply_softcap(z, cfg.final_logit_softcap)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, S]
    positions: Optional[jax.Array] = None,
    k_cache: Optional[jax.Array] = None,  # a uniform KVCache's buffers
    v_cache: Optional[jax.Array] = None,
    cache_write_pos: Optional[jax.Array] = None,
):
    """Whole-model forward -> (logits [B, S, V], new_k, new_v); cache-free
    (the one scan with no entry) unless the buffers of a uniform
    core.cache.KVCache are passed, which forward_cached then serves.

    When `positions` is omitted it is derived from `cache_write_pos` (or 0),
    so cached decode steps get correct RoPE angles and causal masking.
    """
    if k_cache is not None:
        cache = cachelib.KVCache(k=k_cache, v=v_cache, length=cache_write_pos)
        logits, nc, _ = forward_cached(params, cfg, tokens, positions, cache, cache_write_pos)
        return logits, nc.k, nc.v
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    hidden = embed(params, tokens, cfg)
    offset = 0
    for layers in layer_groups(params):
        hidden, _, _ = forward_layers(
            layers, cfg, hidden, positions, layer_offset=offset,
            state_layers=params.get("state_layers"), ffn_layers=params.get("ffn_layers"))
        offset += _stack_len(layers)
    return unembed(params, cfg, hidden), None, None
