#!/usr/bin/env python3
"""The plain reference of the Qwen3-Next family (`model_type: qwen3_next`): a
pre-norm decoder whose mixer is a Gated-DeltaNet block in three layers of four
and gated softmax attention in the fourth (`full_attention_interval`), and
whose feed-forward is, in every layer, softmax-routed experts beside one gated
shared expert; in straightforward jax.numpy, float32,
`default_matmul_precision("highest")`, no cache, no kernel, no chunked form,
no sampling, written from the equations below (those of HF
`modeling_qwen3_next.py`), independent of `inferd_tpu/models/qwen3.py` and of
the other references. Of the program it uses only
`parallel.stages.load_stage_checkpoint`, to read the file the node serves.
Every size comes from `--config`.

    norm(x; w) = x rsqrt(mean x^2 + eps) (1 + w)         every RMSNorm but the gated one
    x = E[tokens]
    per layer i (full attention iff (i + 1) % full_attention_interval == 0):
      x = x + mixer(norm(x; w_in));  x = x + experts(norm(x; w_post))
    linear (Gated DeltaNet), Hk key heads and Hv value heads of Dk / Dv:
      [q | k | v | z] = a W_in;  [b | a'] = a W_ba
      [q|k|v]_t = silu(sum_{i<K} w_conv[i] [q|k|v]_{t-(K-1)+i})       zeros before t = 0, no bias
      value head h reads key head h // (Hv / Hk)
      l2(u) = u rsqrt(sum u^2 + 1e-6);  q = l2(q) / sqrt(Dk);  k = l2(k)
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a'_t + dt_bias)
      S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;  S_{-1} = 0
      o_t = S_t^T q_t;  y_t = o_t rsqrt(mean o_t^2 + eps) w_norm * silu(z_t)    per head, norm FIRST
      out = y W_out
    full:  q = norm_head(a Wq; w_q), gate = a Wg, k = norm_head(a Wk; w_k), v = a Wv
      the first head_dim x partial_rotary_factor dimensions of q and k rotated
      (theta, halves split), the rest pass; causal softmax at 1 / sqrt(head_dim)
      out = (attn * sigmoid(gate)) Wo
    experts(a):  p = softmax(a Wr) over ALL the router's experts
      chosen = the num_experts_per_tok largest;  w = p[chosen] / sum p[chosen]   (norm_topk_prob)
      y = sigmoid(a . w_sg) shared(a) + sum over chosen e HELD HERE of w_e E_e(a)
    logits = norm(x; w_final) W_head

The recurrence runs as ONE sequential `lax.scan` over the tokens of the whole
sequence, prompt and continuation together: no chunked form, no state handed
from a call to the next, nothing kept between tokens but S.

The share (the configuration's `deployment`): the checkpoint holds
`num_experts` experts of each layer, the router's outputs `expert_offset` ..
`expert_offset + num_experts` of its `router_experts`; a token's chosen experts
that are not among them add nothing, here as in the program, and the partial
result goes on to the next layer. The vocabulary is the checkpoint's slice, and
the logits are over it.

Departures from the published code: the delta rule is the sequential
recurrence, not the chunked kernels (the same function of its inputs);
everything is float32 where the published path keeps bf16 activations; the
fused `in_proj_qkvz` / `in_proj_ba` (interleaved by key-head group) and
`q_proj` (query and gate interleaved by head) are read de-interleaved, as the
program's checkpoint stores them; the multi-token-prediction module is not
run (generation does not run it).

Attention runs in blocks of BLOCK queries so that a probe of some thousands of
tokens fits; the experts one after the other, every token through each (the
weight of an expert a token did not choose is zero). ONE forward pass over
each whole sequence.

Output: `[M, V]` float32, M = 1 + len(continue), row j the log-softmax at
position len(prompt) - 1 + j. `logprobs` takes one sequence or several of one
length (`control.py`). The weights are the checkpoint's bf16 values read as
float32.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BLOCK = 512  # queries attended at a time (scores are [heads, BLOCK, keys])


def norm(x, w, eps):
    """RMSNorm scaling by 1 + w: every norm of the model but the gated one."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rotate(x, theta, turned):
    """x [S, heads, D] at positions 0..S-1: of the first `turned` dimensions,
    pair (d, d + turned / 2) turned by position / theta^(2d / turned)."""
    import jax.numpy as jnp

    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, turned, 2, dtype=jnp.float32) / turned))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # [S, turned / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : turned // 2], x[..., turned // 2: turned], x[..., turned:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(a, p, c):
    """One sequence's normed input [S, H] -> gated attention [S, H]."""
    import jax
    import jax.numpy as jnp

    s = a.shape[0]
    nq, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, turned = c["rms_norm_eps"], int(d * c["partial_rotary_factor"])
    q = norm((a @ p["q_proj"]).reshape(s, nq, d), p["q_norm"], eps)
    k = norm((a @ p["k_proj"]).reshape(s, nkv, d), p["k_norm"], eps)
    v = (a @ p["v_proj"]).reshape(s, nkv, d)
    q, k = rotate(q, c["rope_theta"], turned), rotate(k, c["rope_theta"], turned)
    q = q.reshape(s, nkv, nq // nkv, d)  # query heads grouped over their key head
    outs = []
    for lo in range(0, s, BLOCK):
        hi = min(s, lo + BLOCK)
        scores = jnp.einsum("qngd,knd->ngqk", q[lo:hi], k[:hi]) * d ** -0.5
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("ngqk,knd->qngd", probs, v[:hi]).reshape(hi - lo, nq * d))
    return (jnp.concatenate(outs) * jax.nn.sigmoid(a @ p["attn_gate_proj"])) @ p["o_proj"]


def delta_net(a, p, c):
    """One sequence's normed input [S, H] -> the Gated-DeltaNet block, token by token."""
    import jax
    import jax.numpy as jnp

    s = a.shape[0]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv, taps = c["linear_key_head_dim"], c["linear_value_head_dim"], c["linear_conv_kernel_dim"]
    kd, vd = hk * dk, hv * dv
    proj = a @ p["in_proj"]
    qkv, z = proj[:, : 2 * kd + vd], proj[:, 2 * kd + vd:]
    ba = a @ p["ba_proj"]
    before = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(before[i:i + s] * p["conv_w"][i] for i in range(taps)))
    of_head = jnp.arange(hv) // (hv // hk)  # the key head a value head reads
    l2 = lambda u: u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(qkv[:, :kd].reshape(s, hk, dk))[:, of_head] * dk ** -0.5
    k = l2(qkv[:, kd: 2 * kd].reshape(s, hk, dk))[:, of_head]
    v = qkv[:, 2 * kd:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])

    def token(state, now):  # state [Hv, Dk, Dv]
        q_t, k_t, v_t, g_t, beta_t = now
        state = jnp.exp(g_t)[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + c["rms_norm_eps"])
    y = o * p["gate_norm"] * jax.nn.silu(z.reshape(s, hv, dv))
    return y.reshape(s, vd) @ p["out_proj"]


def swiglu(a, gate, up, down):
    import jax

    return (jax.nn.silu(a @ gate) * (a @ up)) @ down


def experts(a, p, c):
    """The gated shared expert + the held experts' part of the routed sum, [S, H]."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(a @ p["router"], axis=-1)  # [S, every routed expert of the model]
    if probs.shape[1] != c["router_experts"]:
        raise ValueError(f"the router is {probs.shape[1]} wide, the file says {c['router_experts']}")
    w, chosen = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    held = p["gate_proj"].shape[0]
    if held != c["num_experts"]:
        raise ValueError(f"the checkpoint holds {held} experts a layer, the file says {c['num_experts']}")

    def one(y, e):  # expert `e` of the held ones is the router's output offset + e
        mine = jnp.sum(jnp.where(chosen == c["expert_offset"] + e, w, 0.0), axis=1)
        return y + mine[:, None] * swiglu(a, p["gate_proj"][e], p["up_proj"][e], p["down_proj"][e]), None

    shared = swiglu(a, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"])
    y = jax.nn.sigmoid(a @ p["shared_expert_gate"])[:, None] * shared
    return jax.lax.scan(one, y, jnp.arange(held))[0]


def layer(x, p, c, full):
    """One sequence [S, H] through one layer of either kind."""
    eps = c["rms_norm_eps"]
    x = x + (attention if full else delta_net)(norm(x, p["input_norm"], eps), p, c)
    return x + experts(norm(x, p["post_norm"], eps), p, c)


def logprobs(params, tokens, rows, config):
    """Log-probabilities [rows, V] of the `rows` tokens that follow the
    prompt `tokens[: len - rows + 1]`, the first `rows - 1` of them being the
    rest of `tokens` [S]; of tokens [N, S], sequences that do not see each
    other, [N, rows, V]. ONE forward pass over each whole sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = dict(config)
    c.setdefault("expert_offset", 0)
    c.setdefault("router_experts", c["num_experts"])
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    tokens = np.asarray(tokens)
    seqs = np.atleast_2d(tokens)
    n = c["num_hidden_layers"]
    full = [(i + 1) % c["full_attention_interval"] == 0 for i in range(n)]
    served = [k == "attention" for k in c.get("layer_kinds", [])]  # the file's own reading
    if served and served != full:
        raise ValueError(f"layer_kinds {c['layer_kinds']} is not the interval's list {full}")
    if c["decoder_sparse_step"] != 1 or c["mlp_only_layers"] or c["tie_word_embeddings"]:
        raise ValueError("this reference is of the all-sparse, untied qwen3_next")
    if c["shared_expert_intermediate_size"] != np.asarray(params["layers"]["shared_up_proj"]).shape[-1]:
        raise ValueError("the shared expert's width is not the file's")
    stacks = {True: params["layers"], False: params["state_layers"]}
    held = {k: int(np.asarray(v["input_norm"]).shape[0]) for k, v in stacks.items()}
    if held != {True: sum(full), False: n - sum(full)}:
        raise ValueError(f"the checkpoint holds {held} full / linear layers, the file lists {full}")
    run = jax.jit(lambda x, p, kind: layer(x, p, c, kind), static_argnums=2)
    with jax.default_matmul_precision("highest"):
        embed = np.asarray(params["embed"])
        xs = [f32(embed[s]) for s in seqs]
        seen = {True: 0, False: 0}
        for kind in full:  # a layer's weights at a time
            p = {k: f32(np.asarray(v)[seen[kind]]) for k, v in stacks[kind].items()}
            seen[kind] += 1
            xs = [run(x, p, kind) for x in xs]
        hid = norm(jnp.stack(xs)[:, seqs.shape[1] - rows:], f32(params["final_norm"]),
                   c["rms_norm_eps"])
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
