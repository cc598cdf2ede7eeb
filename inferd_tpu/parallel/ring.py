"""Ring attention: exact causal GQA over a sequence-sharded mesh axis.

Long-context sequence/context parallelism — absent from the reference
(SURVEY §5 "Long-context: ABSENT"; its eager attention materializes the full
[S, S] score matrix, /root/reference/models/qwen3/server/qwen3_server_module.py:67-89)
— built TPU-first: each `sp` rank holds one sequence block of Q and one of
K/V; K/V blocks rotate around the ring via `lax.ppermute` (ICI
neighbor-to-neighbor traffic, fully overlappable) while each rank streams
blocks through an online-softmax accumulator (the flash-attention recurrence,
so nothing bigger than [S_local, S_local] is ever materialized).

The full model-zoo attention recipe is native: `scale` (Gemma-2's
query_pre_attn_scalar), `softcap` (tanh logit capping, applied to scaled
scores BEFORE masking — the gqa_attention order), `window` (sliding-window
masking; a traced scalar so per-layer windows ride the layer scan), and
`sinks` (GPT-OSS per-q-head sink logits, folded into the online-softmax
denominator at FINALIZE exactly like the flash kernels: rescale by
max(m, sink), add exp(sink - m') — the sink joins the softmax once,
globally, no matter how many ring hops contributed). Every block still
rotates all the way around (one SPMD program; windows mask rather than
skip hops — the skip would save compute, not the ppermute, and is left
for a profile-driven pass).

Must run inside `jax.shard_map` with `axis` a mesh axis name. Exactness is
tested against full-sequence attention in tests/test_parallel.py, including
windowed+softcapped and sinks configs.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from inferd_tpu.ops.attention import NEG_INF as NEG  # shared masking sentinel
from inferd_tpu.ops.attention import apply_softcap, apply_window_mask


def ring_gqa_attention(
    q: jax.Array,  # [B, S, Nq, D] — local sequence block of queries
    k: jax.Array,  # [B, T, Nkv, D] — local sequence block of keys
    v: jax.Array,  # [B, T, Nkv, D]
    q_positions: jax.Array,  # [B, S] absolute positions of local queries
    kv_positions: jax.Array,  # [B, T] absolute positions of local keys
    axis: str,
    scale: Optional[float] = None,  # score scale; default head_dim**-0.5
    softcap: float = 0.0,  # Gemma-2 logit softcapping: cap*tanh(x/cap)
    window: Optional[jax.Array] = None,  # sliding window (traced; <=0 = global)
    sinks: Optional[jax.Array] = None,  # [Nq] per-q-head sink logits (GPT-OSS)
) -> jax.Array:
    """Exact causal attention over the ring; returns [B, S, Nq*D]."""
    sp = lax.axis_size(axis)
    b, s, nq, d = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qh = q.reshape(b, s, nkv, g, d)
    sc = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    m0 = jnp.full((b, nkv, g, s), NEG)
    l0 = jnp.zeros((b, nkv, g, s), jnp.float32)
    acc0 = jnp.zeros((b, nkv, g, s, d), jnp.float32)

    def block(carry, _):
        kb, vb, kpos, m, l, acc = carry
        scores = jnp.einsum("bsngd,btnd->bngst", qh, kb).astype(jnp.float32) * sc
        scores = apply_softcap(scores, softcap)
        mask = kpos[:, None, :] <= q_positions[:, :, None]  # [B, S, T]
        mask = apply_window_mask(mask, kpos, q_positions, window)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG)
        bm = jnp.max(scores, axis=-1)  # [B, Nkv, G, S]
        new_m = jnp.maximum(m, bm)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])
        # fully-masked block: every p entry is exp(NEG - new_m) ~ 0 already
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bngst,btnd->bngsd", p.astype(vb.dtype), vb).astype(jnp.float32)
        acc = acc * corr[..., None] + pv
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        kpos = lax.ppermute(kpos, axis, perm)
        return (kb, vb, kpos, new_m, l, acc), None

    (_, _, _, m, l, acc), _ = lax.scan(block, (k, v, kv_positions, m0, l0, acc0), None, length=sp)
    if sinks is not None:
        # the sink is a single always-attendable virtual slot: join it once
        # at finalize (its value contributes nothing to acc)
        sk = sinks.astype(jnp.float32).reshape(nkv, g)[None, :, :, None]  # [1,Nkv,G,1]
        m_f = jnp.maximum(m, sk)
        corr = jnp.exp(m - m_f)
        l = l * corr + jnp.exp(sk - m_f)
        acc = acc * corr[..., None]
    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, Nkv, G, S, D]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, s, nq * d)
    return out.astype(q.dtype)
