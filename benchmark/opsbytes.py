"""Operations and bytes a step needs, from the configuration's published
sizes (the keys of a HF config.json) — the benchmark's own copy of the
arithmetic in `inferd_tpu/perf/roofline.py`, kept where no later PR can
change it. "Needs" is what the algorithm needs: weights read once, the keys
and values of the tokens that are live, operations on real tokens. What the
program reads or computes beyond that (whole dense lanes, bucket padding)
lowers its roofline share, as it should.

`least_time_s` is the larger of operations over peak FLOP/s and bytes over
peak bytes/s of ONE chip of the kind named; an unknown kind is an error."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def sizes(c: dict) -> dict:
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * c["intermediate_size"]
    head = h * c["vocab_size"]
    return {
        "layers": c["num_hidden_layers"], "q": q, "kv": kv,
        "matmul_params": c["num_hidden_layers"] * per_layer + head,
        # tied: the table is read once as the head, and a few rows as embedding
        "weight_params": c["num_hidden_layers"] * per_layer + head,
        "bytes_per_param": DTYPE_BYTES[c["torch_dtype"]],
        "kv_bytes_per_token": 2 * c["num_hidden_layers"] * kv * DTYPE_BYTES[c["torch_dtype"]],
    }


def decode_step(c: dict, batch_tokens: float, live_kv_tokens: float) -> dict:
    """One decode step that advances `batch_tokens` sessions holding
    `live_kv_tokens` tokens of context between them."""
    s = sizes(c)
    attn = 4 * s["layers"] * s["q"] * live_kv_tokens  # q.k and p.v, 2 flops a MAC
    return {
        "flops": 2 * s["matmul_params"] * batch_tokens + attn,
        "bytes": s["weight_params"] * s["bytes_per_param"]
        + s["kv_bytes_per_token"] * live_kv_tokens,
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every layer over every
    token, causal attention, the head at the last position only."""
    s = sizes(c)
    body = s["matmul_params"] - c["hidden_size"] * c["vocab_size"]
    attn = 4 * s["layers"] * s["q"] * prompt_tokens * prompt_tokens / 2
    return {
        "flops": 2 * body * prompt_tokens + 2 * c["hidden_size"] * c["vocab_size"] + attn,
        "bytes": s["weight_params"] * s["bytes_per_param"]
        + s["kv_bytes_per_token"] * prompt_tokens,
    }


def least_time_s(work: dict, device_kind: str) -> dict:
    p = peaks(device_kind)
    by_flops = work["flops"] / p["bf16_flops_per_s"]
    by_bytes = work["bytes"] / p["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
