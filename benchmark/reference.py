#!/usr/bin/env python3
"""The plain reference: Qwen3's forward pass in straightforward jax.numpy,
float32, `default_matmul_precision("highest")`, no cache, no kernel, no
batching, written from the published equations (Qwen3 technical report;
HF `modeling_qwen3.py`) and independent of `inferd_tpu/models/qwen3.py`.
Of the program it uses only `parallel.stages.load_stage_checkpoint`, to
read the file the node serves. Every size comes from `--config`, the
configuration's file of published keys (`num_attention_heads`,
`num_key_value_heads`, `head_dim`, `rms_norm_eps`, `rope_theta`,
`tie_word_embeddings`), which run.py has tied to the program's preset.

    x   = E[tokens]
    per layer:
      a = RMSNorm(x; w_in)                    y * rsqrt(mean(y^2) + eps) * w
      q,k,v = a Wq, a Wk, a Wv                heads of 128
      q,k = RMSNorm over each head (w_q, w_k); RoPE(theta), rotate-half form
      o   = softmax(q k^T / sqrt(128) + causal) v, query head h reading
            key/value head h // (heads / kv_heads)
      x   = x + o Wo
      m   = RMSNorm(x; w_post)
      x   = x + (silu(m Wg) * (m Wu)) Wd
    logits = RMSNorm(x[-M:]; w_final) @ (E^T if tied else W_head)

One full forward pass over `prompt + continue` (teacher forcing: the
continuation is given, nothing is sampled, nothing is cached). Layers
stream through one device one at a time, so a model that does not fit a
chip in float32 (or at all) still has a reference. Output: `[M, V]` float32
log-probabilities as .npy, M = 1 + len(continue), row j at position
len(prompt) - 1 + j: what follows the prompt, then what follows each token
of the continuation but the last.

A reference of another architecture is a file `references/<name>.py` of its
own, with the same arguments and output, that takes nothing from this one.
`logprobs` takes one sequence or several of one length, each on its own
(`control.py` reads a dozen probes in one pass over the weights).

Departure from the published model: none in the equations; the weights are
the seeded random bf16 values of the checkpoint, read as float32.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [S, heads, D], positions 0..S-1, the rotate-half convention."""
    import jax.numpy as jnp

    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def layer(x, p, heads, kv_heads, d, eps, theta):
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    a = rms_norm(x, p["input_norm"], eps)
    q = (a @ p["q_proj"]).reshape(s, heads, d)
    k = (a @ p["k_proj"]).reshape(s, kv_heads, d)
    v = (a @ p["v_proj"]).reshape(s, kv_heads, d)
    q = rope(rms_norm(q, p["q_norm"], eps), theta)
    k = rope(rms_norm(k, p["k_norm"], eps), theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + o.reshape(s, heads * d) @ p["o_proj"]
    m = rms_norm(x, p["post_norm"], eps)
    return x + (jax.nn.silu(m @ p["gate_proj"]) * (m @ p["up_proj"])) @ p["down_proj"]


def logprobs(params, tokens, rows, config):
    """Log-probabilities [rows, V] at the last `rows` positions of `tokens`
    [S]; of tokens [B, S], sequences that do not see each other, [B, rows, V]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    d, eps, theta = config["head_dim"], config["rms_norm_eps"], config["rope_theta"]
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    step = jax.jit(jax.vmap(layer, in_axes=(0,) + (None,) * 6), static_argnums=(2, 3, 4, 5, 6))
    tokens = np.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[np.atleast_2d(tokens)])
        n_layers = np.asarray(params["layers"]["input_norm"]).shape[0]
        for i in range(n_layers):
            p = {k: f32(np.asarray(v)[i]) for k, v in params["layers"].items()}
            x = step(x, p, heads, kv_heads, d, eps, theta)
        h = rms_norm(x[:, -rows:], f32(params["final_norm"]), eps)
        tied = config["tie_word_embeddings"]
        head = f32(params["embed"]).T if tied else f32(params["lm_head"])
        lp = np.asarray(jax.nn.log_softmax(h @ head, axis=-1))
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
