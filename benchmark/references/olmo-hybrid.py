#!/usr/bin/env python3
"""The plain reference of the Olmo-Hybrid family (`model_type: olmo_hybrid`):
a decoder with NO norm on a sublayer's input and one on its output, whose mixer
is a Gated-DeltaNet block in three layers of four and full softmax attention
without any rotation in the fourth (`layer_types`), and whose feed-forward is a
dense SwiGLU MLP in every layer; in straightforward jax.numpy, float32,
`default_matmul_precision("highest")`, no cache, no kernel, no chunked form,
no sampling, written from the equations below, independent of
`inferd_tpu/models/qwen3.py` and of the other references. Of the program it
uses only `parallel.stages.load_stage_checkpoint`, to read the file the node
serves. Every size comes from `--config`.

    norm(x; w) = x rsqrt(mean x^2 + eps) w                    every RMSNorm
    x = E[tokens]
    per layer i (its kind from layer_types[i]):
      h = x + norm(mixer(x); w_a);  x = h + norm(mlp(h); w_f)       mixer and mlp read x, h UN-normed
    linear_attention (Gated DeltaNet), H heads, keys of Dk, values of Dv:
      [q | k | v | z] = x W_in;  [b | a] = x W_ba
      [q|k|v]_t = silu(sum_{i<K} w_conv[i] [q|k|v]_{t-(K-1)+i})       zeros before t = 0, no bias
      l2(u) = u rsqrt(sum u^2 + 1e-6);  q = l2(q) / sqrt(Dk);  k = l2(k)
      beta_t = 2 sigmoid(b_t)  (linear_allow_neg_eigval; else sigmoid(b_t))
      g_t = -exp(A_log) softplus(a_t + dt_bias)
      S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;  S_{-1} = 0
      o_t = S_t^T q_t;  y_t = o_t rsqrt(mean o_t^2 + eps) w_norm * silu(z_t)    per head, norm FIRST
      out = y W_out
    full_attention:  q = norm(x Wq; w_q), k = norm(x Wk; w_k)   each norm over the WHOLE projection
      v = x Wv;  split into heads of head_dim;  NO rotation
      causal softmax at 1 / sqrt(head_dim);  out = attn Wo
    mlp(h) = (silu(h W_gate) * (h W_up)) W_down
    logits = norm(x; w_final) W_head

The recurrence runs as ONE sequential `lax.scan` over the tokens of the whole
sequence, prompt and continuation together: no chunked form, no state handed
from a call to the next, nothing kept between tokens but S.

Departures from the published code: the delta rule is the sequential
recurrence, not the chunked kernels (the same function of its inputs);
everything is float32 where the published path keeps bf16 activations; the
linear layer's separate q, k, v, g and b, a projections are read side by side
(`in_proj`, `ba_proj`), as the program's checkpoint stores them.

Attention runs in blocks of BLOCK queries so that a probe of some thousands of
tokens fits. ONE forward pass over each whole sequence.

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
    """RMSNorm over the last axis, scaling by w."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def attention(x, p, c):
    """One sequence's residual stream [S, H] -> full attention without rotation [S, H]."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    nq, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    d, eps = c["head_dim"], c["rms_norm_eps"]
    q = norm(x @ p["q_proj"], p["q_norm"], eps).reshape(s, nkv, nq // nkv, d)
    k = norm(x @ p["k_proj"], p["k_norm"], eps).reshape(s, nkv, d)
    v = (x @ p["v_proj"]).reshape(s, nkv, d)
    outs = []
    for lo in range(0, s, BLOCK):
        hi = min(s, lo + BLOCK)
        scores = jnp.einsum("qngd,knd->ngqk", q[lo:hi], k[:hi]) * d ** -0.5
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("ngqk,knd->qngd", probs, v[:hi]).reshape(hi - lo, nq * d))
    return jnp.concatenate(outs) @ p["o_proj"]


def delta_net(x, p, c):
    """One sequence's residual stream [S, H] -> the Gated-DeltaNet block, token by token."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv, taps = c["linear_key_head_dim"], c["linear_value_head_dim"], c["linear_conv_kernel_dim"]
    kd, vd = hk * dk, hv * dv
    proj = x @ p["in_proj"]
    qkv, z = proj[:, : 2 * kd + vd], proj[:, 2 * kd + vd:]
    ba = x @ p["ba_proj"]
    before = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), qkv.dtype), qkv])
    qkv = jax.nn.silu(sum(before[i:i + s] * p["conv_w"][i] for i in range(taps)))
    of_head = jnp.arange(hv) // (hv // hk)  # the key head a value head reads
    l2 = lambda u: u * jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = l2(qkv[:, :kd].reshape(s, hk, dk))[:, of_head] * dk ** -0.5
    k = l2(qkv[:, kd: 2 * kd].reshape(s, hk, dk))[:, of_head]
    v = qkv[:, 2 * kd:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv]) * (2.0 if c["linear_allow_neg_eigval"] else 1.0)
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


def layer(x, p, c, full):
    """One sequence [S, H] through one layer of either kind: norms on the OUTPUTS."""
    import jax

    eps = c["rms_norm_eps"]
    h = x + norm((attention if full else delta_net)(x, p, c), p["post_norm"], eps)
    mlp = (jax.nn.silu(h @ p["gate_proj"]) * (h @ p["up_proj"])) @ p["down_proj"]
    return h + norm(mlp, p["post_ffn_norm"], eps)


def logprobs(params, tokens, rows, config):
    """Log-probabilities [rows, V] of the `rows` tokens that follow the
    prompt `tokens[: len - rows + 1]`, the first `rows - 1` of them being the
    rest of `tokens` [S]; of tokens [N, S], sequences that do not see each
    other, [N, rows, V]. ONE forward pass over each whole sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = dict(config)
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    tokens = np.asarray(tokens)
    seqs = np.atleast_2d(tokens)
    n = c["num_hidden_layers"]
    c.setdefault("head_dim", c["hidden_size"] // c["num_attention_heads"])
    names = {"linear_attention": False, "full_attention": True}
    full = [names[k] for k in c["layer_types"][:n]]  # the first n of the published list
    served = [k == "attention" for k in c.get("layer_kinds", [])]  # the file's own reading
    if served and served != full:
        raise ValueError(f"layer_kinds {c['layer_kinds']} is not the published list's start {full}")
    if c["tie_word_embeddings"] or c["hidden_act"] != "silu" or c.get("attention_bias"):
        raise ValueError("this reference is of the untied, bias-free, SiLU olmo_hybrid")
    if any(v is not None for v in (c.get("rope_parameters") or {}).values()):
        raise ValueError("this reference rotates nothing; the file names a rope")
    stacks = {True: params["layers"], False: params["state_layers"]}
    held = {k: int(np.asarray(v["post_norm"]).shape[0]) for k, v in stacks.items()}
    if held != {True: sum(full), False: n - sum(full)}:
        raise ValueError(f"the checkpoint holds {held} full / linear layers, the file lists {full}")
    if any("input_norm" in v for v in stacks.values()):
        raise ValueError("the checkpoint has input norms; this family has none")
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
