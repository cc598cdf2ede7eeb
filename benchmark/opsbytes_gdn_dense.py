"""Operations and bytes a step NEEDS of a model whose mixer is a gated delta
rule (Gated DeltaNet) in the layers `layer_types` calls `linear_attention`
and full softmax attention in those it calls `full_attention`, and whose
feed-forward is a dense SwiGLU MLP in every layer (no experts); from the
configuration's published sizes (the keys of a HF `olmo_hybrid` config.json,
`head_dim` where the file gives it, else hidden_size / num_attention_heads). A
sibling of `opsbytes_gdn_moe.py` and `opsbytes_ssm.py`;
`opsbytes.least_time_s` and `peaks.json` serve all.

"Needs" is what the algorithm needs: every weight the step touches read once
(the untied head once; of the table a few rows); each live session's recurrent
state and kept columns read and written once a linear layer AT THE BYTES OF
THEIR UNPADDED SHAPES (heads x Dk x Dv float32, (taps - 1) x channels in the
weights' dtype); keys and values of the LIVE tokens in the full layers only;
the recurrence over real tokens. What the program reads or computes beyond
that (a slab read past its live part, a state held or moved in padded tiles,
bucket padding, the weights again for a second chunk) lowers its roofline
share, as it should."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
STATE_BYTES = 4  # the recurrent state is float32 between steps
TILE = 64  # positions the chunked recurrence solves at once (the family's kernels' chunk)


def sizes(c: dict) -> dict:
    h, nq, nkv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or h // nq
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv, taps = c["linear_key_head_dim"], c["linear_value_head_dim"], c["linear_conv_kernel_dim"]
    layers = c["num_hidden_layers"]
    kinds = list(c["layer_types"])[:layers]  # the first `layers` of the published list
    full = kinds.count("full_attention")
    kd, vd = hk * dk, hv * dv
    channels = 2 * kd + vd  # through the convolution: q, k, v
    per = DTYPE_BYTES[c["torch_dtype"]]
    return {
        "layers": layers, "full_layers": full, "linear_layers": layers - full,
        # q | k | v | z, b | a, the taps, dt_bias and A_log, the gated norm, the output
        "linear_mixer": h * (channels + vd) + h * 2 * hv + taps * channels + 2 * hv + dv + vd * h,
        # q, k, v, o, the two norms over the whole projection
        "full_mixer": h * nq * d + 2 * h * nkv * d + nq * d * h + nq * d + nkv * d,
        "norm_params": 2 * h,  # one on each sublayer's output
        "mlp": 3 * h * c["intermediate_size"],
        "embed_head": 2 * h * c["vocab_size"] + h,  # the table, the untied head, the final norm
        "head": h * c["vocab_size"],
        "q": nq * d,
        "bytes_per_param": per,
        "kv_bytes_per_token_layer": 2 * nkv * d * per,
        # a session's state and kept columns in ONE linear layer, unpadded
        "state_bytes_layer": hv * dk * dv * STATE_BYTES + (taps - 1) * channels * per,
        # the recurrence, a token and linear layer: the decay, S'k, the update and the
        # read-out over a [Dk, Dv] state a value head (7 operations an element)
        "update_flops": 7 * hv * dk * dv,
        # the chunked form, a token and linear layer at TILE positions a solve: k k^T,
        # q k^T, the unit-triangular solve of both right-hand sides, and four products
        # with the state or the tile's u
        "scan_flops": hv * (4 * TILE * dk + TILE * (dk + dv) + 6 * dk * dv + 2 * TILE * dv),
    }


def layer_params(s: dict) -> int:
    """The parameters of all the layers: mixers, MLPs and norms."""
    return (s["linear_layers"] * s["linear_mixer"] + s["full_layers"] * s["full_mixer"]
            + s["layers"] * (s["norm_params"] + s["mlp"]))


def weight_params(s: dict) -> int:
    """Every parameter the chip holds."""
    return layer_params(s) + s["embed_head"]


def state_bytes_per_session(c: dict) -> int:
    """What a session holds whatever its length: a float32 state and the
    convolution's kept columns in every linear layer."""
    s = sizes(c)
    return s["linear_layers"] * s["state_bytes_layer"]


def decode_step(c: dict, contexts) -> dict:
    """One decode step that advances len(contexts) sessions of those many
    tokens each."""
    s = sizes(c)
    rows = len(contexts)
    seen = float(sum(contexts)) * s["full_layers"]  # keys a step reads, over layers and sessions
    weights = layer_params(s) + s["head"]  # the table's rows: a few KB
    return {
        "flops": 2 * weights * rows + 4 * s["q"] * seen
        + rows * s["linear_layers"] * s["update_flops"],
        "bytes": weights * s["bytes_per_param"] + s["kv_bytes_per_token_layer"] * seen
        + 2 * rows * s["linear_layers"] * s["state_bytes_layer"],  # read and written once
    }


def prefill(c: dict, prompt_tokens: float) -> dict:
    """One prompt of `prompt_tokens` real tokens: every layer over every
    token; the chunked recurrence in the linear layers, causal attention in
    the full ones; the head at the last position only; every weight once;
    the state read and written once, the prompt's keys and values written."""
    s = sizes(c)
    t = prompt_tokens
    return {
        "flops": 2 * layer_params(s) * t + 2 * s["head"]
        + 4 * s["q"] * s["full_layers"] * t * t / 2
        + s["linear_layers"] * s["scan_flops"] * t,
        "bytes": (layer_params(s) + s["head"]) * s["bytes_per_param"]
        + s["full_layers"] * s["kv_bytes_per_token_layer"] * t
        + 2 * s["linear_layers"] * s["state_bytes_layer"],
    }
