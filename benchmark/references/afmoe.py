#!/usr/bin/env python3
"""The plain reference of the afmoe family (`model_type: afmoe`, Arcee's
Trinity): a decoder with sandwich norms whose attention is windowed with rope
or full without, by `layer_types`, its output gated from the layer's input,
and whose feed-forward is dense in the leading layers and then sigmoid-routed
experts beside a shared one; in straightforward jax.numpy, float32,
`default_matmul_precision("highest")`, no cache, no kernel, no sampling,
written from the equations below (those of HF `modeling_afmoe.py`),
independent of `inferd_tpu/models/qwen3.py` and of the other references. Of
the program it uses only `parallel.stages.load_stage_checkpoint`, to read the
file the node serves. Every size comes from `--config`.

    x = sqrt(hidden_size) * E[tokens]                       (mup_enabled)
    per layer i (kind layer_types[i]):
      x = x + RMSNorm(attn(RMSNorm(x; w_in)); w_post_attn)
      x = x + RMSNorm(ffn(RMSNorm(x; w_pre_mlp)); w_post_mlp)
    attn(a):  q = RMSNorm_head(a Wq; w_q), k = RMSNorm_head(a Wk; w_k), v = a Wv
      sliding_attention: q, k rotated (theta, every dimension, halves
        split); position p sees j with 0 <= p - j < sliding_window
      full_attention: NO rotation; p sees every j <= p
      out = (softmax(q k^T / sqrt(head_dim)) v * sigmoid(a Wg)) Wo
    ffn, i < num_dense_layers:  (silu(a Wgate) * (a Wup)) Wdown
    ffn, otherwise:  s = sigmoid(a Wr) over ALL the router's experts
      chosen = the num_experts_per_tok largest of s + expert_bias
      w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale      (route_norm)
      y = shared(a) + sum over chosen e HELD HERE of w_e E_e(a)
    logits = RMSNorm(x; w_final) W_head

The share (the configuration's `deployment`): the checkpoint holds
`num_experts` experts of each sparse layer, the router's outputs
`expert_offset` .. `expert_offset + num_experts` of its `router_experts`; a
token's chosen experts that are not among them add nothing, here as in the
program, and the partial result goes on to the next layer. The vocabulary is
the checkpoint's slice, and the logits are over it.

Attention runs in blocks of BLOCK queries so that a probe of some thousands
of tokens fits; the experts one after the other, every token through each
(the weight of an expert a token did not choose is zero). ONE forward pass
over each whole sequence.

Output: `[M, V]` float32, M = 1 + len(continue), row j the log-softmax at
position len(prompt) - 1 + j. `logprobs` takes one sequence or several of
one length (`control.py`). The weights are the checkpoint's bf16 values read
as float32; `expert_bias` is float32 as stored.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BLOCK = 512  # queries attended at a time (scores are [heads, BLOCK, keys])
KINDS = {"sliding_attention": True, "full_attention": False}  # kind -> windowed with rope


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta):
    """x [S, heads, D] at positions 0..S-1: dimension pair (d, d + D/2) turned
    by position / theta^(2d / D)."""
    import jax.numpy as jnp

    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(a, p, c, windowed):
    """One sequence's normed input [S, H] -> gated attention [S, H]."""
    import jax
    import jax.numpy as jnp

    s = a.shape[0]
    nq, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, window = c["rms_norm_eps"], c["sliding_window"]
    q = rms_norm((a @ p["q_proj"]).reshape(s, nq, d), p["q_norm"], eps)
    k = rms_norm((a @ p["k_proj"]).reshape(s, nkv, d), p["k_norm"], eps)
    v = (a @ p["v_proj"]).reshape(s, nkv, d)
    if windowed:
        q, k = rotate(q, c["rope_theta"]), rotate(k, c["rope_theta"])
    q = q.reshape(s, nkv, nq // nkv, d)  # query heads grouped over their key head
    outs = []
    for lo in range(0, s, BLOCK):
        hi = min(s, lo + BLOCK)
        first = max(0, lo - window + 1) if windowed else 0  # the oldest key the block sees
        scores = jnp.einsum("qngd,knd->ngqk", q[lo:hi], k[first:hi]) * d ** -0.5
        ahead = jnp.arange(lo, hi)[:, None] - jnp.arange(first, hi)[None, :]  # p - j
        seen = (ahead >= 0) & (ahead < window) if windowed else ahead >= 0
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("ngqk,knd->qngd", probs, v[first:hi]).reshape(hi - lo, nq * d))
    out = jnp.concatenate(outs) * jax.nn.sigmoid(a @ p["attn_gate_proj"])
    return out @ p["o_proj"]


def swiglu(a, gate, up, down):
    import jax

    return (jax.nn.silu(a @ gate) * (a @ up)) @ down


def experts(a, p, c):
    """shared(a) + the held experts' part of the routed sum, [S, H]."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(a @ p["router"])  # [S, every routed expert of the model]
    if scores.shape[1] != c["router_experts"]:
        raise ValueError(f"the router is {scores.shape[1]} wide, the file says {c['router_experts']}")
    _, chosen = jax.lax.top_k(scores + p["router_select_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if c["route_norm"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = w * c["route_scale"]
    held = p["gate_proj"].shape[0]
    if held != c["num_experts"]:
        raise ValueError(f"the checkpoint holds {held} experts a layer, the file says {c['num_experts']}")

    def one(y, e):  # expert `e` of the held ones is the router's output offset + e
        mine = jnp.sum(jnp.where(chosen == c["expert_offset"] + e, w, 0.0), axis=1)
        return y + mine[:, None] * swiglu(a, p["gate_proj"][e], p["up_proj"][e], p["down_proj"][e]), None

    y = swiglu(a, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"])
    return jax.lax.scan(one, y, jnp.arange(held))[0]


def layer(x, p, c, windowed):
    """One sequence [S, H] through one layer; a layer with a router is sparse."""
    eps = c["rms_norm_eps"]
    x = x + rms_norm(attention(rms_norm(x, p["input_norm"], eps), p, c, windowed), p["post_norm"], eps)
    a = rms_norm(x, p["pre_ffn_norm"], eps)
    y = experts(a, p, c) if "router" in p else swiglu(a, p["gate_proj"], p["up_proj"], p["down_proj"])
    return x + rms_norm(y, p["post_ffn_norm"], eps)


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
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    tokens = np.asarray(tokens)
    seqs = np.atleast_2d(tokens)
    n, dense = c["num_hidden_layers"], c["num_dense_layers"]
    kinds = [KINDS[k] for k in c["layer_types"][:n]]
    if len(kinds) != n:
        raise ValueError(f"layer_types names {len(kinds)} layers of {n}")
    served = [k == "sliding" for k in c.get("layer_kinds", [])]  # the file's own reading of the list
    if served and served != kinds:
        raise ValueError(f"layer_kinds {c['layer_kinds']} is not layer_types[:{n}]")
    if not (c["score_func"] == "sigmoid" and c["mup_enabled"] and not c["tie_word_embeddings"]):
        raise ValueError("this reference is of the sigmoid-routed, mup-scaled, untied afmoe")
    stacks = [(params["dense_layers"], i) for i in range(dense)] + [
        (params["layers"], i) for i in range(n - dense)]
    held = [int(np.asarray(s["input_norm"]).shape[0]) for s in (params["dense_layers"], params["layers"])]
    if held != [dense, n - dense]:
        raise ValueError(f"the checkpoint holds {held} dense and sparse layers, the file {dense} of {n}")
    run = jax.jit(lambda x, p, windowed: layer(x, p, c, windowed), static_argnums=2)
    with jax.default_matmul_precision("highest"):
        embed = np.asarray(params["embed"])
        xs = [c["hidden_size"] ** 0.5 * f32(embed[s]) for s in seqs]
        for (stack, i), windowed in zip(stacks, kinds):  # a layer's weights at a time
            p = {k: f32(np.asarray(v)[i]) for k, v in stack.items()}
            xs = [run(x, p, windowed) for x in xs]
        hid = rms_norm(jnp.stack(xs)[:, seqs.shape[1] - rows:], f32(params["final_norm"]),
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
