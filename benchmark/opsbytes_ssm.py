"""Operations and bytes a step NEEDS of a model whose layers are, by
`layer_types`, Mamba-2 blocks or grouped-query attention, each with a dense
SwiGLU MLP (no experts), from the configuration's published sizes (the keys
of a HF `granitemoehybrid` config.json) and the file's `state_dtype`. A
sibling of `opsbytes.py`; `opsbytes.least_time_s` and `peaks.json` serve it.

"Needs" is what the algorithm needs. A decode step reads every weight once
(the tied table once, as the head), and for every live lane READS AND WRITES
each Mamba layer's recurrent state in `state_dtype` and the convolution's
kept columns, whatever the context; only the attention layers read keys and
values, of the tokens that are live. A prompt reads every weight once and
each state once, runs the projections, the MLP and the chunked form of the
recurrence over its REAL tokens, the causal half of the attention layers'
square, and the head once. What the program reads or computes beyond that
(a second pass over the state, bucket padding, the weights again for a
second chunk) lowers its roofline share, as it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_FLOPS = 5  # a state element a token: decay, d x B, add, times C, the sum over N


def sizes(c: dict) -> dict:
    h, nq, nkv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    d = h // nq
    q, kv = nq * d, nkv * d
    heads, hd, n, g, k = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                          c["mamba_n_groups"], c["mamba_d_conv"])
    inner = heads * hd
    conv = inner + 2 * g * n
    width = DTYPE_BYTES[c["torch_dtype"]]
    mlp = 3 * h * c["intermediate_size"]
    kinds = list(c["layer_types"])
    return {
        "attn_layers": kinds.count("attention"), "mamba_layers": kinds.count("mamba"),
        "q": q, "heads": heads, "hd": hd, "n": n, "g": g, "conv": conv, "taps": k,
        # multiply-accumulates a token through one layer's matrices
        "attn_matmul": h * q + 2 * h * kv + q * h + mlp,
        "mamba_matmul": h * (inner + conv + heads) + inner * h + mlp,
        # what a layer holds beside its matrices: norms; the convolution, dt_bias, A_log, D
        "attn_small": 2 * h,
        "mamba_small": 2 * h + conv * k + conv + 3 * heads + inner,
        "head": h * c["vocab_size"], "final_norm": h,
        "bytes_per_param": width,
        "kv_bytes_per_token_layer": 2 * kv * width,
        "state_bytes_layer": heads * hd * n * DTYPE_BYTES[c["state_dtype"]]
        + (k - 1) * conv * width,
    }


def weight_bytes(s: dict) -> float:
    params = (s["attn_layers"] * (s["attn_matmul"] + s["attn_small"])
              + s["mamba_layers"] * (s["mamba_matmul"] + s["mamba_small"])
              + s["head"] + s["final_norm"])
    return params * s["bytes_per_param"]


def state_bytes_per_session(c: dict) -> int:
    s = sizes(c)
    return s["mamba_layers"] * s["state_bytes_layer"]


def decode_step(c: dict, lanes: float, live_kv_tokens: float) -> dict:
    """One decode step that advances `lanes` sessions holding
    `live_kv_tokens` tokens of context between them."""
    s = sizes(c)
    matmul = s["attn_layers"] * s["attn_matmul"] + s["mamba_layers"] * s["mamba_matmul"] + s["head"]
    one_token = s["mamba_layers"] * (
        STATE_FLOPS * s["heads"] * s["hd"] * s["n"] + 2 * s["taps"] * s["conv"])
    attn = 4 * s["attn_layers"] * s["q"] * live_kv_tokens  # q.k and p.v, 2 flops a MAC
    return {
        "flops": (2 * matmul + one_token) * lanes + attn,
        "bytes": weight_bytes(s)
        + 2 * lanes * s["mamba_layers"] * s["state_bytes_layer"]  # read and written
        + s["attn_layers"] * s["kv_bytes_per_token_layer"] * live_kv_tokens,
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens."""
    s = sizes(c)
    t = prompt_tokens
    body = s["attn_layers"] * s["attn_matmul"] + s["mamba_layers"] * s["mamba_matmul"]
    tile = min(c["mamba_chunk_size"], t)
    # the chunked form a token: inside its tile the causal half of C.B (a group) and of
    # the decayed mix over positions (a head), then the tile's state out and in
    chunked = s["mamba_layers"] * t * (
        tile * (s["g"] * s["n"] + s["heads"] * s["hd"])
        + 4 * s["heads"] * s["hd"] * s["n"] + 2 * s["taps"] * s["conv"])
    attn = 4 * s["attn_layers"] * s["q"] * t * t / 2
    return {
        "flops": 2 * body * t + 2 * s["head"] + chunked + attn,
        "bytes": weight_bytes(s) + 2 * s["mamba_layers"] * s["state_bytes_layer"]
        + s["attn_layers"] * s["kv_bytes_per_token_layer"] * t,
    }
