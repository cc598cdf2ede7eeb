"""Operations and bytes a step NEEDS of a model whose attention is windowed
in some layers and full in others (`layer_types`, `sliding_window`), its
output gated, and whose feed-forward is routed experts beside a shared one
behind leading dense layers, of which this chip HOLDS a share (`num_experts`
of the router's `router_experts`); from the configuration's published sizes
(the keys of a HF `afmoe` config.json and the file's share). A sibling of
`opsbytes_mla_moe.py`; `opsbytes.least_time_s` and `peaks.json` serve both.

"Needs" is what the algorithm needs: every weight the step touches read
once, of the HELD experts only those some token of the step chose (not all
of them, which a dispatch of every token to every held expert reads); keys
and values of the last min(context, window) tokens in a windowed layer and
of the whole context in a full one; the operations of the held experts each
token chose (not of all). What the program reads or computes beyond that
lowers its roofline share, as it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes(c: dict) -> dict:
    h, nq, nkv, d = (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
    layers, dense = c["num_hidden_layers"], min(c["num_dense_layers"], c["num_hidden_layers"])
    kinds = c["layer_types"][:layers]
    expert = 3 * h * c["moe_intermediate_size"]
    per = DTYPE_BYTES[c["torch_dtype"]]
    return {
        "layers": layers, "dense_layers": dense, "sparse_layers": layers - dense,
        "windowed_layers": kinds.count("sliding_attention"),
        "full_layers": kinds.count("full_attention"),
        # q, k, v, the output's gate, o, the two head norms; and the four norms of a layer
        "attn_params": h * nq * d + 2 * h * nkv * d + h * nq * d + nq * d * h + 2 * d,
        "norm_params": 4 * h,
        "dense_mlp": 3 * h * c["intermediate_size"],
        "expert": expert,
        "shared": c["num_shared_experts"] * expert,
        "router": h * c["router_experts"],
        "held": c["num_experts"],
        "embed_head": 2 * h * c["vocab_size"] + h,  # the table, the untied head, the final norm
        "head": h * c["vocab_size"],
        "q": nq * d,
        "bytes_per_param": per,
        "kv_bytes_per_token_layer": 2 * nkv * d * per,
        "window": c["sliding_window"],
    }


def weight_params(s: dict) -> int:
    """Every parameter the chip holds."""
    layer = s["attn_params"] + s["norm_params"]
    return (s["layers"] * layer + s["dense_layers"] * s["dense_mlp"]
            + s["sparse_layers"] * (s["router"] + s["shared"] + s["held"] * s["expert"])
            + s["embed_head"])


def ring_bytes_per_session(c: dict, margin: int = 64) -> int:
    """What a session's windowed layers hold whatever its length: a ring of
    the window rounded up to 16, and `margin` slots, a layer."""
    s = sizes(c)
    slots = (s["window"] + 15) // 16 * 16 + margin
    return s["windowed_layers"] * slots * s["kv_bytes_per_token_layer"]


def visible_tokens(s: dict, contexts) -> float:
    """Keys a decode step reads, summed over layers and sessions: the last
    `window` of a session's tokens in a windowed layer, all in a full one."""
    return float(sum(s["windowed_layers"] * min(t, s["window"]) + s["full_layers"] * t
                     for t in contexts))


def token_macs(s: dict, held_chosen: float) -> float:
    """Multiply-accumulates of one token through every layer's projections
    and feed-forward, `held_chosen` held experts a sparse layer; no head."""
    return (s["layers"] * s["attn_params"] + s["dense_layers"] * s["dense_mlp"]
            + s["sparse_layers"] * (s["router"] + s["shared"] + held_chosen * s["expert"]))


def decode_step(c: dict, contexts, held_touched: float, held_assignments: float) -> dict:
    """One decode step that advances len(contexts) sessions of those many
    tokens each, its rows having chosen `held_assignments` held experts in
    all (rows x chosen held experts, summed over the sparse layers), those
    being `held_touched` distinct ones (summed over the sparse layers)."""
    s = sizes(c)
    rows = len(contexts)
    seen = visible_tokens(s, contexts)
    weights = (s["layers"] * (s["attn_params"] + s["norm_params"])
               + s["dense_layers"] * s["dense_mlp"]
               + s["sparse_layers"] * (s["router"] + s["shared"])
               + held_touched * s["expert"] + s["head"])  # the table's rows: a few KB
    return {
        "flops": 2 * (token_macs(s, 0.0) + s["head"]) * rows
        + 2 * s["expert"] * held_assignments + 4 * s["q"] * seen,  # q.k and p.v, 2 flops a MAC
        "bytes": weights * s["bytes_per_param"] + s["kv_bytes_per_token_layer"] * seen,
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every layer over every
    token, each token through the held experts it chose (under an even
    router `num_experts_per_tok x held / router_experts` of them a layer:
    the harness does not see a prompt's routes); causal attention, within
    the window in the windowed layers; the head at the last position only;
    every held weight once (a prompt of a thousand tokens reaches every held
    expert); the prompt's keys and values written."""
    s = sizes(c)
    t, w = prompt_tokens, s["window"]
    pairs_full = t * t / 2
    pairs_windowed = pairs_full if t <= w else w * w / 2 + (t - w) * w
    attn = 4 * s["q"] * (s["full_layers"] * pairs_full + s["windowed_layers"] * pairs_windowed)
    held_chosen = c["num_experts_per_tok"] * s["held"] / c["router_experts"]
    return {
        "flops": 2 * token_macs(s, held_chosen) * t + 2 * s["head"] + attn,
        "bytes": weight_params(s) * s["bytes_per_param"]
        + s["layers"] * s["kv_bytes_per_token_layer"] * t,
    }
