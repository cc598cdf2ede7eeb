#!/usr/bin/env python3
"""The plain reference of Xing4.0 (`model_type: xing4_0`): DeepSeek-V3's
layer (latent attention with compressed queries, sigmoid-routed experts
beside a shared one) under manifold-constrained hyper-connections (mHC,
arXiv:2512.24880, on hyper-connections, arXiv:2409.19606). Its forward pass
in straightforward jax.numpy, float32, `default_matmul_precision("highest")`,
no cache, no kernel, no batching, written from the published config's keys
and the two papers, independent of `inferd_tpu/models/qwen3.py` and of every
other reference. Of the program it uses only
`parallel.stages.load_stage_checkpoint`, to read the file the node serves.
Every size comes from `--config`.

    X = n copies of E[tokens]                      the stream, [n, S, C], n = hc_mult
    per layer, around EACH sublayer F (the mixer, then the feed-forward; each
    has its own P, a, b):
      x'    = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     over all n C values, no weight
      H~pre = a_pre (P_pre x') + b_pre             1 x n
      H~post= a_post (P_post x') + b_post          1 x n
      H~res = a_res mat(P_res x') + b_res          n x n, row after row
      Hpre  = sigmoid(H~pre);  Hpost = 2 sigmoid(H~post)
      M     = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
      hc_sinkhorn_iters times:  M = M / (its columns' sums + hc_eps)
                                M = M / (its rows' sums + hc_eps)
      X     = M X + Hpost^T F(RMSNorm(Hpre X; w))
    the mixer F (latent attention, EXPANDED: keys and values per head):
      c_q = RMSNorm(a W_qa; w_qa);  q = c_q W_qb, heads of qk_nope + qk_rope; q_pe = RoPE(q[.., nope:])
      [c_raw ; k_pe_raw] = a W_kva;  c = RMSNorm(c_raw; w_kva);  k_pe = RoPE(k_pe_raw), one key for all heads
      [k_nope_i ; v_i] = c W_kvb,i
      s_i = (q_nope_i k_nope_i^T + q_pe_i k_pe^T) * scale + causal
      scale = (qk_nope + qk_rope)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1
      F = concat_i(softmax(s_i) v_i) W_o
    the feed-forward F: layer < first_k_dense_replace: SwiGLU(a; intermediate_size); else
      g = sigmoid(a W_r); the top num_experts_per_tok of g + e_score_correction_bias
      (n_group 1: no group limit); weights those g (NOT g + bias), divided by their sum
      + 1e-20 if norm_topk_prob, times routed_scaling_factor;
      F = sum_chosen w_e SwiGLU_e(a) + SwiGLU_shared(a)
    logits = RMSNorm(sum of X's n rows; w_final) @ W_head

RoPE is YaRN over the qk_rope dimensions (HF `DeepseekV3YarnRotaryEmbedding`):
per pair the published frequency, or that divided by `factor`, blended by a
linear ramp between the pairs that turn `beta_fast` and `beta_slow` times in
`original_max_position_embeddings`; cos and sin times
yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim) (1 here).

Experts: a Python loop over the experts that any token chose; each multiplies
every token and is weighed by what the token gave it (0 where it was not
chosen). The Sinkhorn rounds are a Python loop. Attention is computed a block
of queries at a time, so that a probe of some thousands of tokens fits.

One full forward pass over `prompt + continue` (teacher forcing; nothing is
sampled, nothing cached), one sequence at a time, a layer's weights at a
time. Output: `[M, V]` float32 log-probabilities, M = 1 + len(continue), row
j at position len(prompt) - 1 + j. `logprobs` takes one sequence or several
of one length, each on its own (`control.py`).

What the published description leaves open, and how it is read here, is
listed under `assumed` in the configuration's file. The multi-token-
prediction module (`num_nextn_predict_layers`) is not part of this forward
pass. The weights are the checkpoint's bf16 values read as float32.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BLOCK = 512  # queries a block of attention


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, rs):
    import numpy as np

    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / max(high - low, 0.001), 0, 1)
    freq = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    return (freq / factor) * ramp + freq * (1 - ramp)


def rope(x, inv_freq, mul):
    """x [S, heads, D], positions 0..S-1, the rotate-half convention."""
    import jax.numpy as jnp

    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * mul
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * mul
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def swiglu(a, gate, up, down):
    import jax

    return (jax.nn.silu(a @ gate) * (a @ up)) @ down


def stream_maps(xs, p, c, sub):
    """The stream [n, S, C] -> Hpre [S, n], Hpost [S, n], Hres [S, n, n] of
    sublayer `sub` ("attn" or "ffn")."""
    import jax
    import jax.numpy as jnp

    n, s, width = xs.shape
    flat = jnp.transpose(xs, (1, 0, 2)).reshape(s, n * width)  # vec(X), a token a row
    normed = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + c["rms_norm_eps"])
    h = normed @ p[f"hc_{sub}_proj"].reshape(-1, n * width).T  # [S, n + n + n n]
    a_pre, a_post, a_res = p[f"hc_{sub}_scale"]
    b = p[f"hc_{sub}_bias"]
    pre = jax.nn.sigmoid(a_pre * h[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a_post * h[:, n:2 * n] + b[n:2 * n])
    res = a_res * h[:, 2 * n:].reshape(s, n, n) + b[2 * n:].reshape(n, n)
    m = jnp.exp(jnp.clip(res, c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]))
    for _ in range(c["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + c["hc_eps"])  # every column by its sum
        m = m / (jnp.sum(m, axis=2, keepdims=True) + c["hc_eps"])  # every row by its sum
    return pre, post, m


def around(xs, p, c, sub, norm, f):
    """X' = Hres X + Hpost^T f(RMSNorm(Hpre X; norm))."""
    import jax.numpy as jnp

    pre, post, res = stream_maps(xs, p, c, sub)
    y = f(rms_norm(jnp.einsum("sn,nsc->sc", pre, xs), norm, c["rms_norm_eps"]))
    return jnp.einsum("smn,nsc->msc", res, xs) + jnp.einsum("sm,sc->msc", post, y)


def attention(a, p, c, inv_freq):
    """One sequence's normed input [S, C] -> latent attention's output [S, C]."""
    import jax
    import jax.numpy as jnp

    s = a.shape[0]
    heads, dn, dr = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, r, eps = c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"]
    rs = c["rope_scaling"]
    mul = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    c_q = rms_norm(a @ p["q_a_proj"], p["q_a_norm"], eps)
    if c_q.shape[1] != c["q_lora_rank"]:
        raise ValueError(f"the query latent is {c_q.shape[1]} wide, the file says {c['q_lora_rank']}")
    q = (c_q @ p["q_b_proj"]).reshape(s, heads, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], inv_freq, mul)
    kva = a @ p["kv_a_proj"]
    latent = rms_norm(kva[:, :r], p["kv_a_norm"], eps)
    k_pe = rope(kva[:, None, r:], inv_freq, mul)[:, 0]
    kv = (latent @ p["kv_b_proj"]).reshape(s, heads, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    outs = []
    for lo in range(0, s, BLOCK):
        hi = min(s, lo + BLOCK)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[lo:hi], k_nope[:hi])
                  + jnp.einsum("qhd,kd->hqk", q_pe[lo:hi], k_pe[:hi])) * scale
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:hi]).reshape(hi - lo, heads * dv))
    return jnp.concatenate(outs) @ p["o_proj"]


def route(a, p, c):
    """[S, C] -> the weight each token gives each routed expert, [S, E]: 0
    where it did not choose it."""
    import jax
    import jax.numpy as jnp

    g = jax.nn.sigmoid(a @ p["router"])
    if g.shape[1] != c["n_routed_experts"]:
        raise ValueError(f"the router is {g.shape[1]} wide, the file says {c['n_routed_experts']}")
    _, chosen = jax.lax.top_k(g + p["router_select_bias"], c["num_experts_per_tok"])
    w = jnp.take_along_axis(g, chosen, axis=1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = w * c["routed_scaling_factor"]
    return jnp.zeros_like(g).at[jnp.arange(g.shape[0])[:, None], chosen].set(w)


def experts(a, p, c, jit_swiglu):
    """shared(a) + sum over each token's chosen experts, [S, C]."""
    import numpy as np

    weights = route(a, p, c)
    y = jit_swiglu(a, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"]) \
        if c["n_shared_experts"] else 0.0
    for e in np.flatnonzero(np.asarray(weights).any(axis=0)):  # the experts some token chose
        y = y + weights[:, e, None] * jit_swiglu(
            a, p["gate_proj"][e], p["up_proj"][e], p["down_proj"][e])
    return y


def check(c):
    """This reference is of ONE family: refuse a file it does not describe."""
    want = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
            "tie_word_embeddings": False, "hidden_act": "silu", "attention_bias": False,
            "moe_layer_freq": 1}
    odd = {k: c.get(k) for k, v in want.items() if c.get(k) != v}
    if odd or c["rope_scaling"]["type"] != "yarn" or c["hc_mult"] < 2:
        raise ValueError(f"this reference is of the sigmoid-routed, yarn-roped, mHC xing4_0: {odd}")


def logprobs(params, tokens, rows, config):
    """Log-probabilities [rows, V] at the last `rows` positions of `tokens`
    [S]; of tokens [N, S], sequences that do not see each other,
    [N, rows, V]. ONE forward pass over each whole sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c, eps = config, config["rms_norm_eps"]
    check(c)
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    inv_freq = yarn_inv_freq(c["qk_rope_head_dim"], c["rope_theta"], c["rope_scaling"])
    tokens = np.asarray(tokens)
    seqs = np.atleast_2d(tokens)
    n_layers, dense = c["num_hidden_layers"], min(c["first_k_dense_replace"], c["num_hidden_layers"])
    stacks = [(params.get("dense_layers"), i) for i in range(dense)] + [
        (params["layers"], i) for i in range(n_layers - dense)]
    held = [int(np.asarray(s["input_norm"]).shape[0]) if s else 0
            for s in (params.get("dense_layers"), params["layers"])]
    if held != [dense, n_layers - dense]:
        raise ValueError(f"the checkpoint holds {held} dense and sparse layers, the file "
                         f"{dense} of {n_layers}")
    mixer = jax.jit(lambda xs, p: around(
        xs, p, c, "attn", p["input_norm"], lambda a: attention(a, p, c, inv_freq)))
    dense_ffn = jax.jit(lambda xs, p: around(
        xs, p, c, "ffn", p["post_norm"],
        lambda a: swiglu(a, p["gate_proj"], p["up_proj"], p["down_proj"])))
    jit_swiglu = jax.jit(swiglu)
    with jax.default_matmul_precision("highest"):
        embed = np.asarray(params["embed"])
        xs = [jnp.broadcast_to(f32(embed[s]), (c["hc_mult"], len(s), embed.shape[1])) for s in seqs]
        for stack, i in stacks:  # a layer's weights at a time
            p = {k: f32(np.asarray(v)[i]) for k, v in stack.items()}
            xs = [mixer(x, p) for x in xs]
            if "router" in p:
                xs = [around(x, p, c, "ffn", p["post_norm"],
                             lambda a: experts(a, p, c, jit_swiglu)) for x in xs]
            else:
                xs = [dense_ffn(x, p) for x in xs]
        hid = jnp.stack([jnp.sum(x[:, seqs.shape[1] - rows:], axis=0) for x in xs])
        hid = rms_norm(hid, f32(params["final_norm"]), eps)
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
