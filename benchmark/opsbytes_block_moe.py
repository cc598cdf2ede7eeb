"""Operations and bytes a step NEEDS of a model generated by blocks whose
layers are grouped-query attention and routed experts (no shared expert, no
dense layer), from the configuration's published sizes (the keys of a HF
`sdar_moe` / `qwen3_moe` config.json) and the file's `block_length` and
`denoising_steps`. A sibling of `opsbytes.py` and `opsbytes_mla_moe.py`;
`opsbytes.least_time_s` and `peaks.json` serve all three.

"Needs" is what the algorithm needs. A block step is `denoising_steps` + 1
passes, and a pass depends on the tokens the pass before chose, so each
pass reads what it touches once: the attention weights and the routers, of
the experts those some live row of the pass was routed to (not all of them,
which a dispatch of every row to every expert reads), the keys and values
of the tokens that are live, and (a denoising pass only: the commit's
logits are never read) the head. Operations are those of each row's own
chosen experts. What the program reads or computes beyond that lowers its
roofline share, as it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(c: dict) -> dict:
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    width = DTYPE_BYTES[c["torch_dtype"]]
    return {
        "layers": c["num_hidden_layers"], "q": q,
        "attn_params": h * q + 2 * h * kv + q * h,
        "router": h * c["num_experts"],
        "expert": 3 * h * c["moe_intermediate_size"],
        "head": h * c["vocab_size"],
        "bytes_per_param": width,
        "kv_bytes_per_token": 2 * c["num_hidden_layers"] * kv * width,
    }


def row_macs(c: dict, s: dict) -> float:
    """Multiply-accumulates of one row through every layer's projections,
    router and its own chosen experts, without the head."""
    return s["layers"] * (s["attn_params"] + s["router"]
                          + c["num_experts_per_tok"] * s["expert"])


def block_step(c: dict, lanes: float, live_kv_tokens: float, experts_touched: float) -> dict:
    """One block step of `lanes` live lanes holding `live_kv_tokens` tokens
    of context between them: every pass runs lanes x block_length rows that
    were routed to `experts_touched` distinct experts summed over the layers."""
    s = sizes(c)
    rows = lanes * c["block_length"]
    denoising, passes = c["denoising_steps"], c["denoising_steps"] + 1
    attn = 4 * s["layers"] * s["q"] * c["block_length"] * live_kv_tokens  # q.k and p.v
    per_pass = (s["layers"] * (s["attn_params"] + s["router"]) + experts_touched * s["expert"])
    return {
        "flops": passes * (2 * row_macs(c, s) * rows + attn) + denoising * 2 * s["head"] * rows,
        "bytes": (passes * per_pass + denoising * s["head"]) * s["bytes_per_param"]
        + passes * s["kv_bytes_per_token"] * live_kv_tokens,
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """The whole blocks of one prompt of `prompt_tokens` tokens: every layer
    over every token with the experts each token chose, block-causal
    attention (half the square, the blocks' own corners beside it), no head
    (a prompt's logits are not read); every weight once (a prompt of this
    size reaches every expert)."""
    s = sizes(c)
    n = prompt_tokens // c["block_length"] * c["block_length"]
    attn = 4 * s["layers"] * s["q"] * n * (n + c["block_length"]) / 2
    experts = min(c["num_experts"], n * c["num_experts_per_tok"])
    weights = s["layers"] * (s["attn_params"] + s["router"] + experts * s["expert"])
    return {
        "flops": 2 * row_macs(c, s) * n + attn,
        "bytes": weights * s["bytes_per_param"] + s["kv_bytes_per_token"] * n,
    }
