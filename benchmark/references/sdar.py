#!/usr/bin/env python3
"""The plain reference of SDAR-MoE (`model_type: sdar_moe`): the Qwen3-MoE
decoder layer under a block-causal mask, generated block by block, in
straightforward jax.numpy, float32, `default_matmul_precision("highest")`,
no cache, no kernel, no sampling, written from the equations below,
independent of `inferd_tpu/models/qwen3.py` and of the other references. Of
the program it uses only `parallel.stages.load_stage_checkpoint`, to read
the file the node serves. Every size comes from `--config`.

With B the block length, positions p, blk(p) = p // B:

    x  = E[tokens]
    per layer:
      a = RMSNorm(x; w_in)
      q = RMSNorm_head(a Wq; w_q), k = RMSNorm_head(a Wk; w_k), v = a Wv
      q, k = RoPE(q, k; theta, the TRUE position p)    rotate-half, no scaling
      s[p, j] = q_p k_j / sqrt(head_dim);  j visible to p  iff  blk(j) <= blk(p)
      x = x + softmax(s) v Wo                 grouped: num_key_value_heads keys
      m = RMSNorm(x; w_post); g = softmax(m Wg) over num_experts
      the top num_experts_per_tok by g, weights g_e / their sum (norm_topk_prob)
      x = x + sum_chosen w_e SwiGLU_e(m)      no shared expert, no dense layer
    logits = RMSNorm(x; w_final) W_head;  the row at p speaks of the token AT p

Generation, as the program serves it (`block_length`, `denoising_steps`,
`mask_token_id` of the file): the prompt's whole blocks are given; the
P mod B tokens left open the first generated block. A block starts with its
unknown places holding the mask token; each of `denoising_steps` passes
picks a token at every masked place and makes B / steps of them known (the
leftmost: the `sequential` order); a last pass over the known block writes
its keys and values.

**What is computed, and the departure from "one forward pass".** For P
prompt tokens and M answered tokens the reference lays out every (block,
pass) STATE that made one of the M known: the sequence up to and with that
block, the places known before that pass holding the node's tokens, the
others the mask token (what lies beyond the block is invisible under the
mask and holds the mask token). All states run as ONE batch of full forward
passes, and row j is the log-softmax at token j's place in the state of the
pass that made it known. Under the leftmost order that state holds only
tokens before j, so the M-th token itself is never needed (the harness does
not give it). The commit pass yields no row: it is held to the reference
through every later block, whose states see the earlier blocks' true tokens.

Experts: each (token, chosen expert) pair is computed once, nothing for an
expert a token did not choose: the pairs are grouped by expert on the host
(an index table [experts, capacity], padded), gathered, one batched SwiGLU a
group of experts, scattered back weighted; tokens go through in chunks and
experts in groups, so that the gathered rows fit whatever the routing.

Output: `[M, V]` float32, M = 1 + len(continue). `logprobs` takes one
sequence or several of one length (`control.py`), and, for the CPU tests of
the `low_confidence` order, the pass that made each answered token known
(`order`; the default is the leftmost order's). The weights are the
checkpoint's bf16 values read as float32. Departures from the published
script: a place is masked by position, never by comparing ids with the mask
token; the rope layout is half-split, as the program's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ATTEND_STATES = 8  # sequences through attention at a time (scores are [heads, S, S] each)
EXPERT_TOKENS = 4096  # tokens through the experts at a time,
EXPERT_GROUP = 16  # and experts at a time: the gathered rows are at most [16, 4096, H]


def rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [S, heads, D] at positions 0..S-1, the rotate-half convention."""
    import jax.numpy as jnp

    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


def attention(x, p, c):
    """One sequence [S, H] -> x + attention under the block-causal mask."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    nq, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps, blk = c["rms_norm_eps"], c["block_length"]
    a = rms_norm(x, p["input_norm"], eps)
    q = rope(rms_norm((a @ p["q_proj"]).reshape(s, nq, d), p["q_norm"], eps), c["rope_theta"])
    k = rope(rms_norm((a @ p["k_proj"]).reshape(s, nkv, d), p["k_norm"], eps), c["rope_theta"])
    v = (a @ p["v_proj"]).reshape(s, nkv, d)
    q = q.reshape(s, nkv, nq // nkv, d)  # query heads grouped over their key head
    scores = jnp.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(jnp.float32(d))
    block = jnp.arange(s) // blk
    scores = jnp.where((block[None, :] <= block[:, None])[None, None], scores, -jnp.inf)
    o = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, axis=-1), v)
    return x + o.reshape(s, nq * d) @ p["o_proj"]


def route(m, p, c):
    """[T, H] -> (chosen experts [T, K], their weights [T, K])."""
    import jax
    import jax.numpy as jnp

    g = jax.nn.softmax(m @ p["router"], axis=-1)
    w, chosen = jax.lax.top_k(g, c["num_experts_per_tok"])
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w


def grouped(pairs, p):
    """rows [E, C, H] of the tokens each expert was chosen by -> [E, C, H]."""
    import jax
    import jax.numpy as jnp

    act = jax.nn.silu(jnp.einsum("ech,ehi->eci", pairs, p["gate_proj"]))
    return jnp.einsum("eci,eih->ech", act * jnp.einsum("ech,ehi->eci", pairs, p["up_proj"]),
                      p["down_proj"])


def experts(m, p, c):
    """[T, H] -> the weighted sum over each token's chosen experts."""
    import jax.numpy as jnp
    import numpy as np

    t, n_e = m.shape[0], c["num_experts"]
    chosen, w = (np.asarray(a) for a in route(m, p, c))
    flat = chosen.ravel()
    by_expert = np.argsort(flat, kind="stable")
    counts = np.bincount(flat, minlength=n_e)
    cap = max(64, 1 << (int(counts.max()) - 1).bit_length())
    slot = np.arange(flat.size) - np.repeat(np.cumsum(counts) - counts, counts)
    rows = np.full((n_e, cap), t, np.int32)  # t: a row of zeros past the last token
    wts = np.zeros((n_e, cap), np.float32)
    rows[flat[by_expert], slot] = by_expert // chosen.shape[1]
    wts[flat[by_expert], slot] = w.ravel()[by_expert]
    padded = jnp.concatenate([m, jnp.zeros((1, m.shape[1]), m.dtype)])
    y = jnp.zeros_like(padded)
    for e in range(0, n_e, EXPERT_GROUP):  # random weights route unevenly: a few experts take most rows
        group = slice(e, e + EXPERT_GROUP)
        width = max(64, 1 << (int(counts[group].max()) - 1).bit_length())  # few shapes to compile
        at = jnp.asarray(rows[group, :width])
        mine = {k: p[k][group] for k in ("gate_proj", "up_proj", "down_proj")}
        y = y.at[at].add(grouped(padded[at], mine) * jnp.asarray(wts[group, :width])[..., None])
    return y[:t]


def leftmost_order(p_len, rows, blk, steps):
    """The pass that makes each answered token known when every pass takes
    the leftmost B / steps masked places: the r-th masked place of a block
    (the prompt's leftovers fill the first places of the first one) is
    known after pass r // (B / steps)."""
    out = []
    for pos in range(p_len, p_len + rows):
        filled = p_len % blk if pos // blk == p_len // blk else 0
        out.append((pos % blk - filled) // (blk // steps))
    return out


def states_of(seq, rows, c, order=None):
    """The (block, pass) states of one sequence `seq` (prompt + answered
    tokens but the last) that made its `rows` answered tokens known:
    (states [n, S'], where [rows] = (state, position) of each token's row)."""
    import numpy as np

    blk, steps, mask = c["block_length"], c["denoising_steps"], c["mask_token_id"]
    seq = [int(t) for t in seq]
    p_len = len(seq) - (rows - 1)
    if p_len < 1:
        raise ValueError(f"{len(seq)} tokens hold no prompt before {rows - 1} answered ones")
    total = -(-(p_len + rows) // blk) * blk
    if order is None:
        order = leftmost_order(p_len, rows, blk, steps)
    states, index, where = [], {}, []
    for j in range(rows):
        pos, at = p_len + j, int(order[j])
        b = pos // blk
        if (b, at) not in index:
            state = seq[: b * blk] + [mask] * (total - b * blk)
            for q in range(b * blk, min((b + 1) * blk, p_len + rows)):
                if q < p_len or int(order[q - p_len]) < at:
                    if q >= len(seq):
                        raise ValueError("the order needs the last answered token, which is not given")
                    state[q] = seq[q]
            index[(b, at)] = len(states)
            states.append(state)
        where.append((index[(b, at)], pos))
    return np.asarray(states, np.int64), where


def logprobs(params, tokens, rows, config, order=None):
    """Log-probabilities [rows, V] of the `rows` tokens answered to the
    prompt `tokens[: len - rows + 1]`, the first `rows - 1` of them being
    the rest of `tokens` [S]; of tokens [N, S], sequences that do not see
    each other, [N, rows, V]. `order` ([rows] or [N, rows]): the pass that
    made each answered token known, where it is not the leftmost order's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    c, eps = config, config["rms_norm_eps"]
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    tokens = np.asarray(tokens)
    seqs = np.atleast_2d(tokens)
    orders = [None] * len(seqs) if order is None else np.atleast_2d(np.asarray(order))
    laid = [states_of(s, rows, c, o) for s, o in zip(seqs, orders)]
    first = np.cumsum([0] + [len(st) for st, _ in laid])
    states = np.concatenate([st for st, _ in laid])
    where = np.asarray([(first[i] + s, pos) for i, (_, w) in enumerate(laid) for s, pos in w])
    attend = jax.jit(jax.vmap(lambda x, p: attention(x, p, c), in_axes=(0, None)))
    with jax.default_matmul_precision("highest"):
        x = f32(np.asarray(params["embed"])[states])
        b, s, h = x.shape
        stack = params["layers"]
        depth = np.asarray(stack["input_norm"]).shape[0]
        if depth != c["num_hidden_layers"]:
            raise ValueError(f"the checkpoint holds {depth} layers, the file says "
                             f"{c['num_hidden_layers']}")
        for i in range(depth):
            p = {k: f32(np.asarray(v)[i]) for k, v in stack.items()}
            x = jnp.concatenate([attend(x[j: j + ATTEND_STATES], p)
                                 for j in range(0, b, ATTEND_STATES)])
            m = rms_norm(x, p["post_norm"], eps).reshape(b * s, h)
            y = jnp.concatenate([experts(m[j: j + EXPERT_TOKENS], p, c)
                                 for j in range(0, b * s, EXPERT_TOKENS)])
            x = x + y.reshape(b, s, h)
        hid = rms_norm(x[where[:, 0], where[:, 1]], f32(params["final_norm"]), eps)
        head = f32(params["embed"]).T if c["tie_word_embeddings"] else f32(params["lm_head"])
        lp = np.asarray(jax.nn.log_softmax(hid @ head, axis=-1)).reshape(len(seqs), rows, -1)
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
