#!/usr/bin/env python3
"""The plain reference of the Nemotron-H family with LatentMoE (`model_type:
nemotron_h`, Nemotron 3 Super): a pre-norm decoder whose every layer is ONE
sublayer, by `hybrid_override_pattern` a Mamba-2 block (M), grouped-query
attention WITHOUT any position embedding (*), or sigmoid-routed experts that
work in a latent narrower than the hidden state beside a full-width shared
expert (E), every feed-forward an UNGATED squared ReLU; in straightforward
jax.numpy, float32, `default_matmul_precision("highest")`, no cache, no
kernel, no sampling, written from the equations below, independent of
`inferd_tpu/models/qwen3.py` and of the other references. Of the program it
uses only `parallel.stages.load_stage_checkpoint`, to read the file the node
serves. Every size comes from `--config`.

    N(x; w) = x / sqrt(mean(x^2) + layer_norm_epsilon) * w
    x = E[tokens]
    per layer l (kind hybrid_override_pattern[l]):  x = x + F_l(N(x; w_l))
    M:  [z | xBC | dt] = h W_in          (mamba_num_heads x mamba_head_dim = d_inner)
      xBC_t = silu(sum_{i<K} w_conv[i] xBC_{t-(K-1)+i} + b_conv)     zeros before t = 0
      [x_t | B_t | C_t] = xBC_t;  d_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
      S_t[a] = exp(d_t[a] A[a]) S_{t-1}[a] + d_t[a] x_t[a] (outer) B_t[group of a],  S_{-1} = 0
      y_t[a] = S_t[a] C_t[group of a] + D[a] x_t[a]
      F = N(y_t * silu(z_t); w_norm, over each of the n_groups groups' channels) W_out
    *:  q = h W_q (num_attention_heads x head_dim), k, v = h W_k, h W_v
      (num_key_value_heads x head_dim); NO rotation, no head norm
      s[p, j] = q_p k_j / sqrt(head_dim),  j <= p;  F = softmax(s) v W_o
    E:  s = sigmoid(h W_r) over ALL the router's experts
      chosen = the num_experts_per_tok largest of s + b   (b chooses, never weighs)
      w_e = routed_scaling_factor * s_e / (sum over chosen of s + 1e-20)   (norm_topk_prob)
      u = h W_in  (hidden -> moe_latent_size)
      r = sum over chosen e HELD HERE of w_e relu(u U_e)^2 D_e
      F = r W_out (latent -> hidden) + relu(h U_s)^2 D_s
    logits = N(x; w_f) W_head

The share (the configuration's `deployment`): the checkpoint holds
`n_routed_experts` experts of each E layer, the router's outputs
`expert_offset` .. `expert_offset + n_routed_experts` of its `router_experts`;
a token's chosen experts that are not among them add nothing, here as in the
program, and the partial result goes on to the next layer. The shared expert
and both latent projections are whole. The vocabulary is the checkpoint's
slice, and the logits are over it.

The recurrence runs as ONE sequential `lax.scan` over the tokens of the whole
sequence, prompt and continuation together: no chunked form, no state handed
from a call to the next. The experts run one after the other, every token
through each (the weight of an expert a token did not choose is zero).

Departures from the published code (transformers' `nemotron_h`):
the recurrence is the sequential one, not the chunked (SSD) kernels that
`chunk_size` tiles (the same function of its inputs); everything is float32
where the published path keeps bf16 activations; `time_step_limit` is the
family's default (0, inf), so d_t is not clamped (`time_step_min` / `_max` /
`_floor` only draw dt_bias at initialisation); the multi-token-prediction
module is left out (the model's own logits do not depend on it); the
convolution's taps are stored [K, channels].

Output: `[M, V]` float32, M = 1 + len(continue), row j the log-softmax at
position len(prompt) - 1 + j. `logprobs` takes one sequence or several of
one length (`control.py`). The weights are the checkpoint's bf16 values read
as float32; the selection bias is float32 as stored.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEQUENCES = 4  # sequences through a layer at a time
EXPERTS = 16  # held experts multiplied at a time (each [tokens, width] of float32)
STACKS = {"M": "state_layers", "*": "layers", "E": "ffn_layers"}


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def attention(h, p, c):
    """One sequence's normed input [S, H] -> causal attention without positions."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    nq, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = (h @ p["q_proj"]).reshape(s, nkv, nq // nkv, d)  # query heads grouped over their key head
    k = (h @ p["k_proj"]).reshape(s, nkv, d)
    v = (h @ p["v_proj"]).reshape(s, nkv, d)
    scores = jnp.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, axis=-1), v)
    return o.reshape(s, nq * d) @ p["o_proj"]


def mamba(h, p, c):
    """One sequence's normed input [S, H] -> the Mamba-2 block, token by token."""
    import jax
    import jax.numpy as jnp

    s = h.shape[0]
    heads, hd, n = c["mamba_num_heads"], c["mamba_head_dim"], c["ssm_state_size"]
    g, k = c["n_groups"], c["conv_kernel"]
    inner = heads * hd
    channels = inner + 2 * g * n
    proj = h @ p["in_proj"]
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
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c["layer_norm_epsilon"])
    return (y.reshape(s, inner) * p["gate_norm"]) @ p["out_proj"]


def experts(h, p, c):
    """One sequence's normed input [S, H] -> the held experts' part of the
    routed result through the latent, plus the shared expert."""
    import jax
    import jax.numpy as jnp

    k, held, lo = c["num_experts_per_tok"], c["n_routed_experts"], c["expert_offset"]
    s = jax.nn.sigmoid(h @ p["router"])  # [S, router_experts]
    _, chosen = jax.lax.top_k(s + p["router_select_bias"], k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    # each held expert's weight a token: zero where the token did not choose it
    weight = jnp.zeros((h.shape[0], c["router_experts"]), jnp.float32)
    weight = weight.at[jnp.arange(h.shape[0])[:, None], chosen].add(w)[:, lo:lo + held]
    u = h @ p["latent_in_proj"]
    r = jnp.zeros_like(u)
    for e0 in range(0, held, EXPERTS):
        part = jnp.einsum("sem,eml->sel",
                          relu2(jnp.einsum("sl,elm->sem", u, p["up_proj"][e0:e0 + EXPERTS])),
                          p["down_proj"][e0:e0 + EXPERTS])
        r = r + jnp.einsum("sel,se->sl", part, weight[:, e0:e0 + EXPERTS])
    return r @ p["latent_out_proj"] + relu2(h @ p["shared_up_proj"]) @ p["shared_down_proj"]


MIXERS = {"M": (mamba, "input_norm"), "*": (attention, "input_norm"), "E": (experts, "post_norm")}


def layer(x, p, c, letter):
    """One sequence [S, H] through one layer: ONE sublayer behind its norm."""
    f, norm = MIXERS[letter]
    return x + f(rms_norm(x, p[norm], c["layer_norm_epsilon"]), p, c)


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
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"] or set(pattern) - set(STACKS):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} names {len(pattern)} layers of "
            f"{c['num_hidden_layers']}, each M, E or *")
    held = {letter: len(jax.tree.leaves(params[stack])[0]) for letter, stack in STACKS.items()}
    if held != {letter: pattern.count(letter) for letter in STACKS}:
        raise ValueError(f"the checkpoint holds {held} layers, the file's pattern is {pattern}")
    run = {letter: jax.jit(jax.vmap(lambda x, p, m=letter: layer(x, p, c, m), in_axes=(0, None)))
           for letter in STACKS}
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[seqs])
        seen = dict.fromkeys(STACKS, 0)
        for letter in pattern:
            p = {k: f32(np.asarray(v)[seen[letter]]) for k, v in params[STACKS[letter]].items()}
            seen[letter] += 1
            x = jnp.concatenate([run[letter](x[j: j + SEQUENCES], p)
                                 for j in range(0, len(seqs), SEQUENCES)])
        hid = rms_norm(x[:, seqs.shape[1] - rows:], f32(params["final_norm"]),
                       c["layer_norm_epsilon"])
        lp = np.asarray(jax.nn.log_softmax(hid @ f32(params["lm_head"]), axis=-1))
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
