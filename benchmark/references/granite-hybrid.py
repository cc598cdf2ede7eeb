#!/usr/bin/env python3
"""The plain reference of Granite-4.0-H (`model_type: granitemoehybrid` with
`num_local_experts` 0): a pre-norm decoder whose mixer is, by `layer_types`,
a Mamba-2 block or grouped-query attention WITHOUT any position embedding,
a SwiGLU MLP in every layer and four scalars; in straightforward jax.numpy,
float32, `default_matmul_precision("highest")`, no cache, no kernel, no
sampling, written from the equations below, independent of
`inferd_tpu/models/qwen3.py` and of the other references. Of the program it
uses only `parallel.stages.load_stage_checkpoint`, to read the file the
node serves. Every size comes from `--config`.

    x = embedding_multiplier * E[tokens]
    per layer i (kind layer_types[i]):
      x = x + residual_multiplier * mixer(RMSNorm(x; w_in))
      x = x + residual_multiplier * (silu(a Wg) * (a Wu)) Wd,  a = RMSNorm(x; w_post)
    attention:  q = a Wq, k = a Wk, v = a Wv;  NO rotation, no per-head norm
      s[p, j] = q_p k_j * attention_multiplier,  j <= p;  out = softmax(s) v Wo
    mamba:  [z | xBC | dt] = a W_in          (mamba_n_heads x mamba_d_head = d_inner)
      xBC_t = silu(sum_{i<K} w_conv[i] xBC_{t-(K-1)+i} + b_conv)     zeros before t = 0
      [x_t | B_t | C_t] = xBC_t;  d_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
      S_t[h] = exp(d_t[h] A[h]) S_{t-1}[h] + d_t[h] x_t[h] (outer) B_t[group of h],  S_{-1} = 0
      y_t[h] = S_t[h] C_t[group of h] + D[h] x_t[h]
      out = RMSNorm(y_t * silu(z_t); w_norm, over each group's channels) W_out
    logits = RMSNorm(x; w_final) E^T / logits_scaling

The recurrence runs as ONE sequential `lax.scan` over the tokens of the
whole sequence, prompt and continuation together: no chunked form, no state
handed from a call to the next, nothing kept between tokens but S.

Departures from the published code (transformers' `granitemoehybrid`):
the recurrence is the sequential one, not the chunked (SSD) kernels that
`mamba_chunk_size` tiles (the same function of its inputs); everything is
float32 where the published path keeps bf16 activations and float32 only
inside the softplus, the decay and the gated norm; the MLP's fused
`shared_mlp.input_linear` is read as the two halves the program's checkpoint
stores (gate, up); `time_step_limit` is the family's default (0, inf), so
d_t is not clamped; the convolution's taps are stored [K, channels].

Output: `[M, V]` float32, M = 1 + len(continue), row j the log-softmax at
position len(prompt) - 1 + j. `logprobs` takes one sequence or several of
one length (`control.py`). The weights are the checkpoint's bf16 values read
as float32.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEQUENCES = 4  # sequences through a layer at a time (attention scores are [heads, S, S] each)


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def attention(a, p, c):
    """One sequence's normed input [S, H] -> causal attention without positions."""
    import jax
    import jax.numpy as jnp

    s = a.shape[0]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // nq
    q = (a @ p["q_proj"]).reshape(s, nkv, nq // nkv, d)  # query heads grouped over their key head
    k = (a @ p["k_proj"]).reshape(s, nkv, d)
    v = (a @ p["v_proj"]).reshape(s, nkv, d)
    scores = jnp.einsum("qngd,knd->ngqk", q, k) * c["attention_multiplier"]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(s, nq * d) @ p["o_proj"]


def mamba(a, p, c):
    """One sequence's normed input [S, H] -> the Mamba-2 block, token by token."""
    import jax
    import jax.numpy as jnp

    s = a.shape[0]
    heads, hd, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    g, k = c["mamba_n_groups"], c["mamba_d_conv"]
    inner = heads * hd
    channels = inner + 2 * g * n
    proj = a @ p["in_proj"]
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + channels], proj[:, inner + channels:]
    before = jnp.concatenate([jnp.zeros((k - 1, channels), xbc.dtype), xbc])
    xbc = jax.nn.silu(sum(before[i:i + s] * p["conv_w"][i] for i in range(k)) + p["conv_b"])
    x = xbc[:, :inner].reshape(s, heads, hd)
    of_head = jnp.arange(heads) // (heads // g)  # the group whose B and C a head reads
    b = xbc[:, inner:inner + g * n].reshape(s, g, n)[:, of_head]
    cc = xbc[:, inner + g * n:].reshape(s, g, n)[:, of_head]
    d = jax.nn.softplus(dt + p["dt_bias"])  # [S, heads]
    neg = -jnp.exp(p["A_log"])

    def token(state, now):
        x_t, b_t, c_t, d_t = now
        state = (jnp.exp(d_t * neg)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, hd, n), jnp.float32), (x, b, cc, d))
    y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c["rms_norm_eps"])
    return (y.reshape(s, inner) * p["gate_norm"]) @ p["out_proj"]


def layer(x, p, c, mixer):
    """One sequence [S, H] through one layer of either kind."""
    import jax

    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    x = x + r * mixer(rms_norm(x, p["input_norm"], eps), p, c)
    a = rms_norm(x, p["post_norm"], eps)
    return x + r * ((jax.nn.silu(a @ p["gate_proj"]) * (a @ p["up_proj"])) @ p["down_proj"])


def logprobs(params, tokens, rows, config):
    """Log-probabilities [rows, V] of the `rows` tokens that follow the
    prompt `tokens[: len - rows + 1]`, the first `rows - 1` of them being the
    rest of `tokens` [S]; of tokens [N, S], sequences that do not see each
    other, [N, rows, V]. ONE forward pass over each whole sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = config
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    tokens = np.asarray(tokens)
    seqs = np.atleast_2d(tokens)
    kinds = list(c["layer_types"])
    if len(kinds) != c["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(kinds)} layers of {c['num_hidden_layers']}")
    stacks = {"attention": params["layers"], "mamba": params["state_layers"]}
    held = {k: np.asarray(v["input_norm"]).shape[0] for k, v in stacks.items()}
    if held != {k: kinds.count(k) for k in stacks}:
        raise ValueError(f"the checkpoint holds {held} layers, the file lists {kinds}")
    run = {k: jax.jit(jax.vmap(lambda x, p, m=m: layer(x, p, c, m), in_axes=(0, None)))
           for k, m in (("attention", attention), ("mamba", mamba))}
    with jax.default_matmul_precision("highest"):
        x = c["embedding_multiplier"] * f32(np.asarray(params["embed"])[seqs])
        seen = {"attention": 0, "mamba": 0}
        for kind in kinds:
            p = {k: f32(np.asarray(v)[seen[kind]]) for k, v in stacks[kind].items()}
            seen[kind] += 1
            x = jnp.concatenate([run[kind](x[j: j + SEQUENCES], p)
                                 for j in range(0, len(seqs), SEQUENCES)])
        hid = rms_norm(x[:, seqs.shape[1] - rows:], f32(params["final_norm"]), c["rms_norm_eps"])
        head = f32(params["embed"]).T if c["tie_word_embeddings"] else f32(params["lm_head"])
        lp = np.asarray(jax.nn.log_softmax(hid @ head / c["logits_scaling"], axis=-1))
        return lp if tokens.ndim == 2 else lp[0]


def ids(text: str):
    return [int(t) for t in text.split(",") if t]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ckpt", required=True)
    ap.add_argument("--model", required=True, help="the program's preset; no size is read from it")
    ap.add_argument("--config", required=True, help="the configuration's file: every size")
    ap.add_argument("--device", required=True, choices=["tpu", "cpu"])
    ap.add_argument("--prompt-ids", required=True)
    ap.add_argument("--continue-ids", default="", help="the tokens that follow, but the last")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = args.device
    sys.path.insert(0, REPO)
    import jax
    import numpy as np

    if jax.devices()[0].platform != args.device:
        print(f"asked for {args.device}, JAX gave {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    with open(args.config) as f:
        config = json.load(f)
    params, _spec, _name = load_stage_checkpoint(args.ckpt)
    more = ids(args.continue_ids)
    lp = logprobs(params, ids(args.prompt_ids) + more, 1 + len(more), config)
    if not np.isfinite(lp).all():
        print("the reference's log-probabilities are not finite", file=sys.stderr)
        return 3
    np.save(args.out, lp.astype(np.float32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
