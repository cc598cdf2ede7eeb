"""Operations and bytes a step NEEDS of a model with latent attention (MLA)
and routed + shared experts behind leading dense layers, from the
configuration's published sizes (the keys of a HF `deepseek_v2`
config.json). The sibling of `opsbytes.py`, whose arithmetic is dense;
`opsbytes.least_time_s` and `peaks.json` serve both.

"Needs" is what the algorithm needs: every weight the step touches read
once, of the routed experts only those some token of the step was routed to
(not all of them, which a dispatch of every token to every expert reads),
the latent and the rope key of the tokens that are live, the operations of
the experts each token chose (not of all), decode in the absorbed form
(nothing per head over the cache's length), prefill in the expanded form.
What the program reads or computes beyond that lowers its roofline share, as
it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(c: dict) -> dict:
    h, n = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv, r = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"]
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    expert = 3 * h * c["moe_intermediate_size"]
    return {
        "layers": c["num_hidden_layers"], "dense_layers": dense,
        "sparse_layers": c["num_hidden_layers"] - dense,
        # one layer's attention weights: q, kv_a (latent + rope key), kv_b, o;
        # also a token's multiply-accumulates through them in either form
        # (absorbed decode folds kv_b's halves into the query, n dn r, and
        # the output, n r dv: the same count as expanding one token)
        "attn_params": h * n * (dn + dr) + h * (r + dr) + r * n * (dn + dv) + n * dv * h,
        "dense_mlp": 3 * h * c["intermediate_size"],
        "expert": expert,
        "shared": c["n_shared_experts"] * expert,
        "router": h * c["n_routed_experts"],
        "head": h * c["vocab_size"],
        "bytes_per_param": DTYPE_BYTES[c["torch_dtype"]],
        "cache_bytes_per_token": c["num_hidden_layers"] * (r + dr) * DTYPE_BYTES[c["torch_dtype"]],
    }


def token_macs(c: dict, s: dict) -> float:
    """Multiply-accumulates of one token through every layer's projections
    and feed-forward (its own chosen experts only), without the head."""
    sparse = s["router"] + s["shared"] + c["num_experts_per_tok"] * s["expert"]
    return (s["layers"] * s["attn_params"] + s["dense_layers"] * s["dense_mlp"]
            + s["sparse_layers"] * sparse)


def decode_step(c: dict, batch_tokens: float, live_kv_tokens: float,
                experts_touched: float) -> dict:
    """One decode step that advances `batch_tokens` sessions holding
    `live_kv_tokens` tokens of context between them, its tokens routed to
    `experts_touched` distinct experts summed over the sparse layers."""
    s = sizes(c)
    n, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    # absorbed: a score is a dot over r + dr, the weighted sum one over r
    attn = 2 * s["layers"] * n * (2 * r + dr) * live_kv_tokens
    weights = (s["layers"] * s["attn_params"] + s["dense_layers"] * s["dense_mlp"]
               + s["sparse_layers"] * (s["router"] + s["shared"])
               + experts_touched * s["expert"] + s["head"])
    return {
        "flops": 2 * (token_macs(c, s) + s["head"]) * batch_tokens + attn,
        "bytes": weights * s["bytes_per_param"] + s["cache_bytes_per_token"] * live_kv_tokens,
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every layer over every
    token with the experts each token chose, causal attention in the
    expanded form, the head at the last position only; every weight once
    (a prompt of this size reaches every expert)."""
    s = sizes(c)
    n, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    attn = 2 * s["layers"] * n * (dn + dr + dv) * prompt_tokens * prompt_tokens / 2
    experts = min(c["n_routed_experts"], prompt_tokens * c["num_experts_per_tok"])
    weights = (s["layers"] * s["attn_params"] + s["dense_layers"] * s["dense_mlp"]
               + s["sparse_layers"] * (s["router"] + s["shared"] + experts * s["expert"])
               + s["head"])
    return {
        "flops": 2 * token_macs(c, s) * prompt_tokens + 2 * s["head"] + attn,
        "bytes": weights * s["bytes_per_param"] + s["cache_bytes_per_token"] * prompt_tokens,
    }
