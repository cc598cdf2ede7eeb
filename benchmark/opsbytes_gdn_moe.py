"""Operations and bytes a step NEEDS of a model whose mixer is a gated delta
rule (Gated DeltaNet) in most layers and gated full attention in every
`full_attention_interval`-th, and whose feed-forward is, in every layer,
routed experts beside a gated shared one, of which this chip HOLDS a share
(`num_experts` of the router's `router_experts`); from the configuration's
published sizes (the keys of a HF `qwen3_next` config.json and the file's
share). A sibling of `opsbytes_swa_moe.py` and `opsbytes_ssm.py`;
`opsbytes.least_time_s` and `peaks.json` serve all.

"Needs" is what the algorithm needs: every weight the step touches read
once, of the HELD experts only those some token of the step chose; each live
session's recurrent state and kept columns read and written once a linear
layer; keys and values of the whole context in the full layers only; the
operations of the held experts each token chose; the recurrence over real
tokens. What the program reads or computes beyond that (a slab to its last
slot, bucket padding, a tile's padding rows) lowers its roofline share, as
it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4  # the recurrent state is float32 between steps
TILE = 64  # positions the chunked recurrence solves at once (the family's kernels' chunk)


def sizes(c: dict) -> dict:
    h, nq, nkv, d = (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv, taps = c["linear_key_head_dim"], c["linear_value_head_dim"], c["linear_conv_kernel_dim"]
    layers = c["num_hidden_layers"]
    full = sum((i + 1) % c["full_attention_interval"] == 0 for i in range(layers))
    kd, vd = hk * dk, hv * dv
    channels = 2 * kd + vd  # through the convolution: q, k, v
    per = DTYPE_BYTES[c["torch_dtype"]]
    return {
        "layers": layers, "full_layers": full, "linear_layers": layers - full,
        # q | k | v | z, b | a, the taps, dt_bias and A_log, the gated norm, the output
        "linear_mixer": h * (channels + vd) + h * 2 * hv + taps * channels + 2 * hv + dv + vd * h,
        # q and its gate, k, v, o, the two head norms
        "full_mixer": 2 * h * nq * d + 2 * h * nkv * d + nq * d * h + 2 * d,
        "norm_params": 2 * h,
        "router": h * c["router_experts"],
        "shared": 3 * h * c["shared_expert_intermediate_size"] + h,  # and its gate's vector
        "expert": 3 * h * c["moe_intermediate_size"],
        "held": c["num_experts"],
        "embed_head": 2 * h * c["vocab_size"] + h,  # the table, the untied head, the final norm
        "head": h * c["vocab_size"],
        "q": nq * d,
        "bytes_per_param": per,
        "kv_bytes_per_token_layer": 2 * nkv * d * per,
        # a session's state and kept columns in ONE linear layer
        "state_bytes_layer": hv * dk * dv * STATE_BYTES + (taps - 1) * channels * per,
        # the recurrence, a token and linear layer: the decay, S'k, the update and the
        # read-out over a [Dk, Dv] state a value head (7 operations an element)
        "update_flops": 7 * hv * dk * dv,
        # the chunked form, a token and linear layer at TILE positions a solve: k k^T,
        # q k^T, the unit-triangular solve of both right-hand sides, and four products
        # with the state or the tile's u
        "scan_flops": hv * (4 * TILE * dk + TILE * (dk + dv) + 6 * dk * dv + 2 * TILE * dv),
    }


def weight_params(s: dict) -> int:
    """Every parameter the chip holds."""
    return (s["linear_layers"] * s["linear_mixer"] + s["full_layers"] * s["full_mixer"]
            + s["layers"] * (s["norm_params"] + s["router"] + s["shared"]
                             + s["held"] * s["expert"])
            + s["embed_head"])


def state_bytes_per_session(c: dict) -> int:
    """What a session holds whatever its length: a float32 state and the
    convolution's kept columns in every linear layer."""
    s = sizes(c)
    return s["linear_layers"] * s["state_bytes_layer"]


def token_macs(s: dict, held_chosen: float) -> float:
    """Multiply-accumulates of one token through every layer's projections
    and feed-forward, `held_chosen` held experts a layer; no head, no
    recurrence, no attention scores."""
    return (s["linear_layers"] * s["linear_mixer"] + s["full_layers"] * s["full_mixer"]
            + s["layers"] * (s["router"] + s["shared"] + held_chosen * s["expert"]))


def decode_step(c: dict, contexts, held_touched: float, held_assignments: float) -> dict:
    """One decode step that advances len(contexts) sessions of those many
    tokens each, its rows having chosen `held_assignments` held experts in
    all (rows x chosen held experts, summed over the layers), those being
    `held_touched` distinct ones (summed over the layers)."""
    s = sizes(c)
    rows = len(contexts)
    seen = float(sum(contexts)) * s["full_layers"]  # keys a step reads, over layers and sessions
    weights = (s["linear_layers"] * s["linear_mixer"] + s["full_layers"] * s["full_mixer"]
               + s["layers"] * (s["norm_params"] + s["router"] + s["shared"])
               + held_touched * s["expert"] + s["head"])  # the table's rows: a few KB
    return {
        "flops": 2 * (token_macs(s, 0.0) + s["head"]) * rows
        + 2 * s["expert"] * held_assignments + 4 * s["q"] * seen
        + rows * s["linear_layers"] * s["update_flops"],
        "bytes": weights * s["bytes_per_param"] + s["kv_bytes_per_token_layer"] * seen
        + 2 * rows * s["linear_layers"] * s["state_bytes_layer"],  # read and written once
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every layer over every
    token, each token through the held experts it chose (under an even
    router `num_experts_per_tok x held / router_experts` of them a layer:
    the harness does not see a prompt's routes); the chunked recurrence in
    the linear layers, causal attention in the full ones; the head at the
    last position only; every held weight once (a prompt of a thousand
    tokens reaches every held expert); the state read and written once, the
    prompt's keys and values written."""
    s = sizes(c)
    t = prompt_tokens
    held_chosen = c["num_experts_per_tok"] * s["held"] / c["router_experts"]
    return {
        "flops": 2 * token_macs(s, held_chosen) * t + 2 * s["head"]
        + 4 * s["q"] * s["full_layers"] * t * t / 2
        + s["linear_layers"] * s["scan_flops"] * t,
        "bytes": weight_params(s) * s["bytes_per_param"]
        + s["full_layers"] * s["kv_bytes_per_token_layer"] * t
        + 2 * s["linear_layers"] * s["state_bytes_layer"],
    }
