#!/usr/bin/env python3
"""The plain reference of DeepSeek-V2 (`model_type: deepseek_v2`, no query
compression): its forward pass in straightforward jax.numpy, float32,
`default_matmul_precision("highest")`, no cache, no kernel, written from the
published config's keys and HF `modeling_deepseek.py`, independent of
`inferd_tpu/models/qwen3.py` and of `benchmark/reference.py`. Of the program
it uses only `parallel.stages.load_stage_checkpoint`, to read the file the
node serves. Every size comes from `--config`.

    x = E[tokens]
    per layer (attention in its EXPANDED form: keys and values per head):
      a  = RMSNorm(x; w_in)
      q  = a Wq, heads of qk_nope + qk_rope; q_pe = RoPE(q[.., nope:])
      [c_raw ; k_pe_raw] = a Wkva           kv_lora_rank + qk_rope
      c  = RMSNorm(c_raw; w_kva); k_pe = RoPE(k_pe_raw), one key for all heads
      [k_nope_i ; v_i] = c Wkvb,i           qk_nope + v_head per head i
      s_i = (q_nope_i k_nope_i^T + q_pe_i k_pe^T) * scale + causal
      scale = (qk_nope + qk_rope)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1
      x  = x + concat_i(softmax(s_i) v_i) Wo
      m  = RMSNorm(x; w_post)
      layer < first_k_dense_replace:  x = x + SwiGLU(m; intermediate_size)
      else: g = softmax(m Wg) over the routed experts; the top
            num_experts_per_tok by g (greedy; n_group 1), weights those
            values of g (divided by their sum only if norm_topk_prob) times
            routed_scaling_factor;
            x = x + sum_chosen w_e SwiGLU_e(m) + SwiGLU_shared(m)
    logits = RMSNorm(x[-M:]; w_final) @ W_head

RoPE is YaRN over the qk_rope dimensions (HF `DeepseekV2YarnRotaryEmbedding`):
per pair of dimensions the frequency is the published one, or that divided by
`factor`, blended by a linear ramp between the pairs that turn `beta_fast`
and `beta_slow` times in `original_max_position_embeddings`; cos and sin are
multiplied by yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim).

Experts: each (token, chosen expert) pair is computed once and nothing is
computed for an expert a token did not choose. The pairs are grouped by
expert on the host (an index table [experts, capacity], padded), the rows
gathered, one batched SwiGLU over the experts, the results scattered back
weighted: the published sum over the chosen, not the program's dispatch of
every token to every expert.

One full forward pass over `prompt + continue` (teacher forcing; nothing is
sampled, nothing cached). Layers stream through the device one at a time.
Output: `[M, V]` float32 log-probabilities, M = 1 + len(continue), row j at
position len(prompt) - 1 + j. `logprobs` takes one sequence or several of one
length, each on its own (`control.py`).

Departures from the published model: the checkpoint stores each rope pair
interleaved and HF de-interleaves at run time; here (as in the program, whose
loader permutes on load) the stored layout is half-split and RoPE is the
rotate-half form. With the seeded random weights of a benchmark run the two
are the same model. The weights are the checkpoint's bf16 values read as
float32.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


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


def swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(x, p, c, inv_freq):
    """One sequence [S, H] -> x + attention, expanded keys and values."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    heads, dn, dr = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    dv, r, eps = c["v_head_dim"], c["kv_lora_rank"], c["rms_norm_eps"]
    rs = c["rope_scaling"]
    mul = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    a = rms_norm(x, p["input_norm"], eps)
    q = (a @ p["q_proj"]).reshape(s, heads, dn + dr)
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], inv_freq, mul)
    kva = a @ p["kv_a_proj"]
    latent = rms_norm(kva[:, :r], p["kv_a_norm"], eps)
    k_pe = rope(kva[:, None, r:], inv_freq, mul)[:, 0]
    kv = (latent @ p["kv_b_proj"]).reshape(s, heads, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return x + o.reshape(s, heads * dv) @ p["o_proj"]


def route(m, p, c):
    """[T, H] -> (chosen experts [T, K], their weights [T, K])."""
    import jax
    import jax.numpy as jnp

    g = jax.nn.softmax(m @ p["router"], axis=-1)
    w, chosen = jax.lax.top_k(g, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w * c["routed_scaling_factor"]


def grouped(pairs, p):
    """rows [E, C, H] of the tokens each expert was chosen by -> [E, C, H]."""
    import jax
    import jax.numpy as jnp

    act = jax.nn.silu(jnp.einsum("ech,ehi->eci", pairs, p["gate_proj"]))
    return jnp.einsum("eci,eih->ech", act * jnp.einsum("ech,ehi->eci", pairs, p["up_proj"]),
                      p["down_proj"])


def experts(m, p, c):
    """[T, H] -> sum over each token's chosen experts + the shared expert."""
    import jax.numpy as jnp
    import numpy as np

    t, n_e = m.shape[0], c["n_routed_experts"]
    chosen, w = (np.asarray(a) for a in route(m, p, c))
    counts = np.bincount(chosen.ravel(), minlength=n_e)
    cap = int(-(-max(int(counts.max()), 1) // 64) * 64)
    rows = np.full((n_e, cap), t, np.int32)  # t: a row of zeros past the last token
    wts = np.zeros((n_e, cap), np.float32)
    fill = np.zeros(n_e, np.int64)
    for tok in range(t):
        for e, weight in zip(chosen[tok], w[tok]):
            rows[e, fill[e]], wts[e, fill[e]] = tok, weight
            fill[e] += 1
    padded = jnp.concatenate([m, jnp.zeros((1, m.shape[1]), m.dtype)])
    out = grouped(padded[jnp.asarray(rows)], p) * jnp.asarray(wts)[..., None]
    y = jnp.zeros_like(padded).at[jnp.asarray(rows)].add(out)[:t]
    if c["n_shared_experts"]:
        y = y + swiglu(m, p["shared_gate_proj"], p["shared_up_proj"], p["shared_down_proj"])
    return y


def logprobs(params, tokens, rows, config):
    """Log-probabilities [rows, V] at the last `rows` positions of `tokens`
    [S]; of tokens [B, S], sequences that do not see each other, [B, rows, V]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c, eps = config, config["rms_norm_eps"]
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    inv_freq = yarn_inv_freq(c["qk_rope_head_dim"], c["rope_theta"], c["rope_scaling"])
    attend = jax.jit(jax.vmap(lambda x, p: attention(x, p, c, inv_freq), in_axes=(0, None)))
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[np.atleast_2d(tokens)])
        b, s, h = x.shape
        stacks = [params[k] for k in ("dense_layers", "layers") if k in params]
        layer = 0
        for stack in stacks:
            for i in range(np.asarray(stack["input_norm"]).shape[0]):
                p = {k: f32(np.asarray(v)[i]) for k, v in stack.items()}
                x = attend(x, p)
                m = rms_norm(x, p["post_norm"], eps).reshape(b * s, h)
                if layer < c["first_k_dense_replace"]:
                    y = swiglu(m, p["gate_proj"], p["up_proj"], p["down_proj"])
                else:
                    y = experts(m, p, c)
                x = x + y.reshape(b, s, h)
                layer += 1
        if layer != c["num_hidden_layers"]:
            raise ValueError(f"the checkpoint holds {layer} layers, the file says "
                             f"{c['num_hidden_layers']}")
        hid = rms_norm(x[:, -rows:], f32(params["final_norm"]), eps)
        head = f32(params["embed"]).T if c["tie_word_embeddings"] else f32(params["lm_head"])
        lp = np.asarray(jax.nn.log_softmax(hid @ head, axis=-1))
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
