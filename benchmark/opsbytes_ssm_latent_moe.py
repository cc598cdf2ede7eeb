"""Operations and bytes a step NEEDS of a model whose layers are ONE sublayer
each, by `hybrid_override_pattern` a Mamba-2 block (M), grouped-query
attention (*) or routed experts that work in a latent beside a full-width
shared expert (E), every feed-forward ungated (two matrices), of which this
chip HOLDS a share of the experts (`n_routed_experts` of the router's
`router_experts`); from the configuration's published sizes (the keys of a HF
`nemotron_h` config.json, the file's share and its `state_dtype`). A sibling
of `opsbytes_ssm.py` and `opsbytes_gdn_moe.py`; `opsbytes.least_time_s` and
`peaks.json` serve all.

"Needs" is what the algorithm needs, by kind of sublayer. M: its weights
once; each live session's recurrent state and kept columns read and written
once; the one-token recurrence (a chunk: the chunked form over real tokens).
*: its weights once; keys and values of the whole context. E: the router, both
latent projections and the shared expert once; of the HELD experts only those
some row of the step chose (TOUCHED: from the step's routing counts), two
matrices each; the operations of the held experts each row chose. What the
program reads or computes beyond that (every held expert where few were
touched, a tile's padding rows, a slab to its last slot, bucket padding)
lowers its roofline share, as it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_FLOPS = 5  # a state element a token: decay, d x B, add, times C, the sum over N


def sizes(c: dict) -> dict:
    h, nq, nkv, d = (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
    heads, hd, n, g, k = (c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"],
                          c["n_groups"], c["conv_kernel"])
    inner = heads * hd
    conv = inner + 2 * g * n  # channels through the convolution: x, then B and C of every group
    latent, mi = c["moe_latent_size"], c["moe_intermediate_size"]
    per = DTYPE_BYTES[c["torch_dtype"]]
    pattern = c["hybrid_override_pattern"]
    return {
        "m_layers": pattern.count("M"), "a_layers": pattern.count("*"),
        "e_layers": pattern.count("E"),
        "q": nq * d, "heads": heads, "hd": hd, "n": n, "g": g, "conv": conv, "taps": k,
        # a sublayer's matrices (multiply-accumulates a token) and what else it holds
        "m_matmul": h * (inner + conv + heads) + inner * h,  # [z | xBC | dt], the output
        "m_small": h + conv * k + conv + 3 * heads + inner,  # norm; taps, bias, dt_bias/A_log/D, gate norm
        "a_matmul": 2 * h * nq * d + 2 * h * nkv * d,  # q and o, k and v
        "a_small": h,
        # an E sublayer outside its routed experts: router, latent in and out, the shared expert
        "e_matmul": h * c["router_experts"] + 2 * h * latent
        + 2 * h * c["moe_shared_expert_intermediate_size"],
        "e_small": h + 2 * c["router_experts"],  # norm; the float32 selection bias as two bf16
        "expert": 2 * latent * mi,  # TWO matrices: up and down, no gate
        "held": c["n_routed_experts"],
        "head": h * c["vocab_size"], "embed_final": h * c["vocab_size"] + h,
        "bytes_per_param": per,
        "kv_bytes_per_token_layer": 2 * nkv * d * per,
        "state_bytes_layer": heads * hd * n * DTYPE_BYTES[c["state_dtype"]]
        + (k - 1) * conv * per,
    }


def weight_params(s: dict) -> int:
    """Every parameter the chip holds."""
    return (s["m_layers"] * (s["m_matmul"] + s["m_small"])
            + s["a_layers"] * (s["a_matmul"] + s["a_small"])
            + s["e_layers"] * (s["e_matmul"] + s["e_small"] + s["held"] * s["expert"])
            + s["head"] + s["embed_final"])


def state_bytes_per_session(c: dict) -> int:
    """What a session holds whatever its length: a state and the convolution's
    kept columns in every M sublayer."""
    s = sizes(c)
    return s["m_layers"] * s["state_bytes_layer"]


def token_macs(s: dict, held_chosen: float) -> float:
    """Multiply-accumulates of one token through every sublayer's matrices,
    `held_chosen` held experts an E sublayer; no head, no recurrence, no scores."""
    return (s["m_layers"] * s["m_matmul"] + s["a_layers"] * s["a_matmul"]
            + s["e_layers"] * (s["e_matmul"] + held_chosen * s["expert"]))


def decode_step(c: dict, contexts, held_touched: float, held_assignments: float) -> dict:
    """One decode step that advances len(contexts) sessions of those many
    tokens each, its rows having chosen `held_assignments` held experts in all
    (summed over the E sublayers), those being `held_touched` distinct ones
    (summed over the E sublayers)."""
    s = sizes(c)
    rows = len(contexts)
    seen = float(sum(contexts)) * s["a_layers"]  # keys a step reads, over layers and sessions
    weights = (s["m_layers"] * (s["m_matmul"] + s["m_small"])
               + s["a_layers"] * (s["a_matmul"] + s["a_small"])
               + s["e_layers"] * (s["e_matmul"] + s["e_small"])
               + held_touched * s["expert"] + s["head"])  # the table's rows: a few KB
    one_token = s["m_layers"] * (
        STATE_FLOPS * s["heads"] * s["hd"] * s["n"] + 2 * s["taps"] * s["conv"])
    return {
        "flops": (2 * (token_macs(s, 0.0) + s["head"]) + one_token) * rows
        + 2 * s["expert"] * held_assignments + 4 * s["q"] * seen,
        "bytes": weights * s["bytes_per_param"] + s["kv_bytes_per_token_layer"] * seen
        + 2 * rows * s["m_layers"] * s["state_bytes_layer"],  # read and written once
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every sublayer over every
    token, each token through the held experts it chose (under an even router
    `num_experts_per_tok x held / router_experts` of them an E sublayer: the
    harness does not see a prompt's routes); the chunked recurrence in the M
    sublayers, causal attention in the * ones; the head at the last position
    only; every held weight once; the state read and written once, the
    prompt's keys and values written."""
    s = sizes(c)
    t = prompt_tokens
    held_chosen = c["num_experts_per_tok"] * s["held"] / c["router_experts"]
    tile = min(c["chunk_size"], t)
    # the chunked form a token: inside its tile the causal half of C.B (a group) and of
    # the decayed mix over positions (a head), then the tile's state out and in
    chunked = s["m_layers"] * t * (
        tile * (s["g"] * s["n"] + s["heads"] * s["hd"])
        + 4 * s["heads"] * s["hd"] * s["n"] + 2 * s["taps"] * s["conv"])
    return {
        "flops": 2 * token_macs(s, held_chosen) * t + 2 * s["head"]
        + 4 * s["q"] * s["a_layers"] * t * t / 2 + chunked,
        "bytes": (weight_params(s) - s["embed_final"]) * s["bytes_per_param"]
        + s["a_layers"] * s["kv_bytes_per_token_layer"] * t
        + 2 * s["m_layers"] * s["state_bytes_layer"],
    }
