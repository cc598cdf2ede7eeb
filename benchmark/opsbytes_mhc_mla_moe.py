"""Operations and bytes a step NEEDS of a model whose residual is a STREAM
of hidden states under manifold-constrained hyper-connections (mHC), around
latent attention with compressed queries (DeepSeek-V3's MLA) and sigmoid-
routed + shared experts behind leading dense layers, from the configuration's
published sizes (the keys of a `xing4_0` config.json). A sibling of
`opsbytes_mla_moe.py`; `opsbytes.least_time_s` and `peaks.json` serve both.

"Needs" is what the algorithm needs: every weight the step touches read
once, of the routed experts only those some token of the step was routed to,
the compressed query's TWO products, the latent and the rope key of the
tokens that are live (decode in the absorbed form: nothing per head over the
cache's length; prefill in the expanded form, every token expanded once), the
operations of the experts each token chose, and around EVERY sublayer the
stream of `hc_mult` hidden states read once and written once in the
activation dtype and the products of the maps' projection (hc_mult x hidden
inputs, hc_mult (2 + hc_mult) outputs a token). The Sinkhorn rounds over
hc_mult^2 numbers a token are counted as nothing: what they cost is latency,
and it counts against the program, as does whatever else it reads or
computes beyond this."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(c: dict) -> dict:
    h, n = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r, rq, m = c["kv_lora_rank"], c["q_lora_rank"], c["hc_mult"]
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    expert = 3 * h * c["moe_intermediate_size"]
    maps = m * (2 + m)
    return {
        "layers": c["num_hidden_layers"], "dense_layers": dense,
        "sparse_layers": c["num_hidden_layers"] - dense,
        # one layer's mixer projections: q_a, q_b, kv_a (latent + rope key),
        # kv_b, o; also a token's multiply-accumulates through them in either
        # form (absorbed decode folds kv_b's halves into the query and the
        # output: the same count as expanding one token)
        "mixer_macs": h * rq + rq * n * (dn + dr) + h * (r + dr) + r * n * (dn + dv) + n * dv * h,
        "mixer_norms": rq + r,
        "layer_norms": 2 * h,
        # ONE sublayer's maps: the projection, the biases, a_pre / a_post / a_res
        "stream_map_macs": m * h * maps,
        "stream_map_params": m * h * maps + maps + 3,
        "dense_mlp": 3 * h * c["intermediate_size"],
        "expert": expert,
        "shared": c["n_shared_experts"] * expert,
        "router": h * c["n_routed_experts"],
        "router_bias": c["n_routed_experts"],
        "embed": h * c["vocab_size"],
        "head": h * c["vocab_size"],
        "bytes_per_param": DTYPE_BYTES[c["torch_dtype"]],
        "cache_bytes_per_token": c["num_hidden_layers"] * (r + dr) * DTYPE_BYTES[c["torch_dtype"]],
        # the stream read once and written once around each of a layer's two sublayers
        "stream_bytes_per_token": c["num_hidden_layers"] * 2 * 2 * m * h
        * DTYPE_BYTES[c["torch_dtype"]],
    }


def layer_params(s: dict, sparse: bool, experts: float) -> float:
    """Parameters of one layer with `experts` of its routed experts."""
    own = s["mixer_macs"] + s["mixer_norms"] + s["layer_norms"] + 2 * s["stream_map_params"]
    if not sparse:
        return own + s["dense_mlp"]
    return own + s["router"] + s["router_bias"] + s["shared"] + experts * s["expert"]


def held_params(c: dict) -> float:
    """Every parameter the configuration holds (its `deployment` names it)."""
    s = sizes(c)
    return (s["dense_layers"] * layer_params(s, False, 0)
            + s["sparse_layers"] * layer_params(s, True, c["n_routed_experts"])
            + s["embed"] + s["head"] + c["hidden_size"])


def token_macs(c: dict, s: dict) -> float:
    """Multiply-accumulates of one token through every layer's projections,
    its two sets of maps and its feed-forward (its own chosen experts only),
    without the head."""
    sparse = s["router"] + s["shared"] + c["num_experts_per_tok"] * s["expert"]
    return (s["layers"] * (s["mixer_macs"] + 2 * s["stream_map_macs"])
            + s["dense_layers"] * s["dense_mlp"] + s["sparse_layers"] * sparse)


def step_weights(s: dict, experts_touched: float) -> float:
    """Parameters a step reads: all but the embedding table, of the routed
    experts `experts_touched` (summed over the sparse layers)."""
    return (s["dense_layers"] * layer_params(s, False, 0)
            + s["sparse_layers"] * layer_params(s, True, 0)
            + experts_touched * s["expert"] + s["head"])


def decode_step(c: dict, batch_tokens: float, live_kv_tokens: float,
                experts_touched: float) -> dict:
    """One decode step that advances `batch_tokens` sessions holding
    `live_kv_tokens` tokens of context between them, its tokens routed to
    `experts_touched` distinct experts summed over the sparse layers."""
    s = sizes(c)
    n, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    # absorbed: a score is a dot over r + dr, the weighted sum one over r
    attn = 2 * s["layers"] * n * (2 * r + dr) * live_kv_tokens
    return {
        "flops": 2 * (token_macs(c, s) + s["head"]) * batch_tokens + attn,
        "bytes": step_weights(s, experts_touched) * s["bytes_per_param"]
        + s["cache_bytes_per_token"] * live_kv_tokens
        + s["stream_bytes_per_token"] * batch_tokens,
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every layer over every
    token with the experts each token chose, causal attention in the
    expanded form over its causal half, the head at the last position only;
    every weight once (of the experts those its tokens' choices can reach)."""
    s = sizes(c)
    n, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    attn = 2 * s["layers"] * n * (dn + dr + dv) * prompt_tokens * prompt_tokens / 2
    experts = min(c["n_routed_experts"], prompt_tokens * c["num_experts_per_tok"])
    return {
        "flops": 2 * token_macs(c, s) * prompt_tokens + 2 * s["head"] + attn,
        "bytes": step_weights(s, s["sparse_layers"] * experts) * s["bytes_per_param"]
        + (s["cache_bytes_per_token"] + s["stream_bytes_per_token"]) * prompt_tokens,
    }
