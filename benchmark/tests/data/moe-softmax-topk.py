#!/usr/bin/env python3
"""Plain reference of a decoder whose feed-forward is a softmax-top-k
mixture of experts, written from Qwen3-MoE's published equations (HF
`modeling_qwen3_moe.py`): float32, `default_matmul_precision("highest")`,
no cache, no kernel, layers streamed, one full forward pass over `prompt +
continue`. It stands alone: of the program it uses only
`parallel.stages.load_stage_checkpoint` (run.py puts the checkout on
`PYTHONPATH`), of the benchmark nothing. Every
size comes from `--config`. Output `[M, V]` float32 log-probabilities, row j
at position len(prompt) - 1 + j (the contract at the top of run.py).

    x = E[tokens]
    per layer:
      a = RMSNorm(x; w_in);  q,k,v = a Wq, a Wk, a Wv
      q,k = RoPE(RMSNorm over each head), rotate-half; GQA: query head h
            reads key/value head h // (heads / kv_heads); causal softmax
      x = x + o Wo;  m = RMSNorm(x; w_post)
      g = softmax(m W_r)                 over all E experts
      T = the k experts of largest g;    w_e = g_e / sum_{e in T} g_e  (norm_topk_prob)
      x = x + sum_{e in T} w_e * (silu(m Wg_e) * (m Wu_e)) Wd_e
    logits = RMSNorm(x[-M:]; w_final) @ (E^T if tied else W_head)
"""

import argparse
import json
import os
import sys

import numpy as np


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    import jax.numpy as jnp

    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] / (
        theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    return x * cos + jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1) * sin


def attention(x, p, c):
    import jax
    import jax.numpy as jnp

    s, heads, kv, d = x.shape[0], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_parameters"]["rope_theta"]
    a = rms_norm(x, p["input_norm"], eps)
    q = rope(rms_norm((a @ p["q_proj"]).reshape(s, heads, d), p["q_norm"], eps), theta)
    k = rope(rms_norm((a @ p["k_proj"]).reshape(s, kv, d), p["k_norm"], eps), theta)
    v = (a @ p["v_proj"]).reshape(s, kv, d)
    k, v = jnp.repeat(k, heads // kv, axis=1), jnp.repeat(v, heads // kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + o.reshape(s, heads * d) @ p["o_proj"]


def experts(x, p, c):
    import jax
    import jax.numpy as jnp

    m = rms_norm(x, p["post_norm"], c["rms_norm_eps"])
    g = jax.nn.softmax(m @ p["router"], axis=-1)
    top_g, top_e = jax.lax.top_k(g, c["num_experts_per_tok"])
    w = top_g / jnp.sum(top_g, axis=-1, keepdims=True) if c["norm_topk_prob"] else top_g
    y = jnp.zeros_like(m)
    for e in range(c["num_experts"]):  # expert by expert; a token that did not choose it weighs 0
        w_e = jnp.sum(jnp.where(top_e == e, w, 0.0), axis=-1, keepdims=True)
        y = y + w_e * ((jax.nn.silu(m @ p["gate_proj"][e]) * (m @ p["up_proj"][e]))
                       @ p["down_proj"][e])
    return x + y


def logprobs(params, tokens, rows, c):
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[np.asarray(tokens)])
        for i in range(c["num_hidden_layers"]):
            p = {k: f32(np.asarray(v)[i]) for k, v in params["layers"].items()}
            x = experts(attention(x, p, c), p, c)
        h = rms_norm(x[-rows:], f32(params["final_norm"]), c["rms_norm_eps"])
        head = f32(params["embed"]).T if c["tie_word_embeddings"] else f32(params["lm_head"])
        return np.asarray(jax.nn.log_softmax(h @ head, axis=-1))


def main() -> int:
    ap = argparse.ArgumentParser()
    for name in ("--ckpt", "--model", "--config", "--device", "--prompt-ids", "--out"):
        ap.add_argument(name, required=True)
    ap.add_argument("--continue-ids", default="")
    args = ap.parse_args()
    os.environ["JAX_PLATFORMS"] = args.device
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    with open(args.config) as f:
        config = json.load(f)
    params, _spec, _name = load_stage_checkpoint(args.ckpt)
    ids = lambda text: [int(t) for t in text.split(",") if t]  # noqa: E731
    more = ids(args.continue_ids)
    lp = logprobs(params, ids(args.prompt_ids) + more, 1 + len(more), config)
    if not np.isfinite(lp).all():
        return 3
    np.save(args.out, lp.astype(np.float32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
