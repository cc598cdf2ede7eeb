"""Token sampling: temperature / top-k / top-p, fully jittable.

Capability parity with the reference's HF LogitsProcessor chain
(/root/reference/models/qwen3/client/client.py:95-120 — TemperatureLogitsWarper,
TopKLogitsWarper, TopPLogitsWarper + multinomial), re-implemented as a single
pure function on logits so it fuses into the jitted decode step instead of
running on host between steps.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import SamplingConfig
from inferd_tpu.ops.attention import NEG_INF  # shared masking sentinel


def top_k_filter(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k highest logits per row, others -> -inf. k<=0 disables."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def top_p_filter(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest set of tokens whose cumulative
    probability reaches p (HF semantics: the token that crosses the
    threshold is kept). p>=1 disables."""
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # A sorted position is kept iff the cumulative mass *before* it is < p.
    keep_sorted = (cum - probs) < p
    # Threshold logit = smallest kept logit; everything below is dropped.
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < thresh, NEG_INF, logits)


def min_p_filter(logits: jax.Array, min_p: float) -> jax.Array:
    """min-p filtering (HF MinPLogitsWarper): drop tokens whose probability
    is below min_p * the max probability. Denominator-free logit form —
    keep iff l >= l_max + ln(min_p) — so it composes EXACTLY on a top-k
    candidate row too (probability ratios don't see the softmax Z).
    min_p <= 0 disables; min_p >= 1 would silently mask EVERY token
    (even the max fails l >= l_max + ln(min_p)) and degrade the draw to
    uniform noise over the vocab — rejected loudly (HF parity)."""
    if min_p <= 0.0:
        return logits
    if min_p >= 1.0:
        raise ValueError(f"min_p must be in [0, 1), got {min_p}")
    lmax = jnp.max(logits, axis=-1, keepdims=True)
    return jnp.where(logits < lmax + math.log(min_p), NEG_INF, logits)


def passthrough_filters(top_k: int, top_p: float, min_p: float, vocab: int) -> bool:
    """True when the warp chain is the identity — greedy or
    temperature-only configs (no active top-k / top-p / min-p). These are
    static Python values (jit static closure), so the check costs nothing
    traced and lets the samplers skip building ANY full-vocab filter ops
    (sort/cumsum/scatter over V=151936 — a suspected decode-step cost,
    VERDICT r05 item 1)."""
    return (top_k <= 0 or top_k >= vocab) and top_p >= 1.0 and min_p <= 0.0


def warped_logits(
    logits: jax.Array, temperature: float, top_k: int, top_p: float,
    min_p: float = 0.0,
) -> jax.Array:
    """The fully-warped (temperature + top-k + top-p filtered) logits whose
    softmax is the distribution `sample` draws from. Exposed for consumers
    that need the distribution itself, e.g. speculative decoding's
    accept/residual computation.

    temperature == 0 is the greedy point mass: NEG_INF everywhere except
    the argmax index (`sample`'s argmax semantics exactly; ties break to
    the first index like argmax). The old division-by-zero produced
    +/-inf logits whose softmax was NaN.

    When top-k is active this avoids the full-vocab sort (measured ~3.6 ms
    per row at V=152K on v5e): filter the k sorted candidates, then scatter
    them back into a -inf row — one top_k pass plus a k-element scatter.
    Greedy/temperature-only configs skip the filter chain entirely
    (passthrough_filters).
    """
    if temperature == 0.0:
        best = jnp.argmax(logits, axis=-1, keepdims=True)
        out = jnp.full_like(logits, NEG_INF)
        return jnp.put_along_axis(
            out, best, jnp.zeros_like(best, logits.dtype), axis=-1,
            inplace=False,
        )
    logits = logits / jnp.float32(temperature)
    if passthrough_filters(top_k, top_p, min_p, logits.shape[-1]):
        return logits  # temperature-only: no filter op touches the row
    if 0 < top_k < logits.shape[-1]:
        vals, idx = jax.lax.top_k(logits, top_k)  # [.., k] sorted desc
        vals = min_p_filter(top_p_filter(vals, top_p), min_p)
        out = jnp.full_like(logits, NEG_INF)
        return jnp.put_along_axis(out, idx, vals, axis=-1, inplace=False)
    logits = top_k_filter(logits, top_k)
    return min_p_filter(top_p_filter(logits, top_p), min_p)


def sample(
    logits: jax.Array,  # [B, V] float32
    key: jax.Array,
    temperature: float = 0.6,
    top_k: int = 20,
    top_p: float = 0.95,
    min_p: float = 0.0,
) -> jax.Array:
    """Sample next token ids [B]. temperature == 0 -> greedy argmax.

    When top-k is active, top-p filtering and the categorical draw run over
    the k candidates only: `lax.top_k` already returns them sorted, so the
    full-vocab sort and full-vocab gumbel draw (V=152K for Qwen3 — measured
    ~3.6 ms/step on v5e, half the decode step) collapse to O(k) work. The
    result is distribution-identical to filtering the full row: tokens
    outside the top-k are -inf under both schemes.
    """
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.float32(temperature)
    if passthrough_filters(top_k, top_p, min_p, logits.shape[-1]):
        # temperature-only fast path: one categorical draw, no filter op
        # ever materializes over the vocab (HF parity: every warper in the
        # chain is the identity for this config — asserted by test)
        return jax.random.categorical(key, logits, axis=-1)
    if 0 < top_k < logits.shape[-1]:
        vals, idx = jax.lax.top_k(logits, top_k)  # [B, k], sorted descending
        vals = min_p_filter(top_p_filter(vals, top_p), min_p)  # O(k) row
        choice = jax.random.categorical(key, vals, axis=-1)  # [B] in [0, k)
        return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    logits = min_p_filter(top_p_filter(logits, top_p), min_p)
    return jax.random.categorical(key, logits, axis=-1)


def sample_cfg(logits: jax.Array, key: jax.Array, cfg: Optional[SamplingConfig]) -> jax.Array:
    c = cfg or SamplingConfig()
    return sample(logits, key, c.temperature, c.top_k, c.top_p, c.min_p)


def warped_probs(logits: jax.Array, cfg: SamplingConfig) -> jax.Array:
    """softmax(warped_logits): the exact distribution `sample` draws from
    at temperature > 0 — the ONE definition both speculative rejection
    schemes (core.speculative, core.spec_batch) accept/residual against, so
    a warp-pipeline change can never make them diverge."""
    return jax.nn.softmax(
        warped_logits(logits, cfg.temperature, cfg.top_k, cfg.top_p, cfg.min_p),
        axis=-1,
    )


def logprob_topn(
    logits: jax.Array,  # [B, V]
    tok: jax.Array,  # [B] the emitted token
    n: int,  # static top-N count; 0 -> empty top arrays
):
    """Model log-probabilities from the RAW logits (log-softmax — the
    standard serving-API meaning, not the warped sampler distribution):
    (lp_of_tok [B] f32, top_ids [B, n] i32, top_lps [B, n] f32, descending).
    Device-side so engines can report logprobs without shipping a [B, V]
    row to the host per step."""
    lf = logits.astype(jnp.float32)
    lps = lf - jax.nn.logsumexp(lf, axis=-1, keepdims=True)
    lp_tok = jnp.take_along_axis(lps, tok[:, None].astype(jnp.int32), axis=-1)[:, 0]
    if n <= 0:
        b = logits.shape[0]
        return lp_tok, jnp.zeros((b, 0), jnp.int32), jnp.zeros((b, 0), jnp.float32)
    top_lps, top_ids = jax.lax.top_k(lps, n)
    return lp_tok, top_ids.astype(jnp.int32), top_lps


# -- per-row sampling inside a decode step ------------------------------------
#
# The serving decode programs (core.batch `_decode_logits`, parallel.infer
# `_step_raw_multi`) choose every row's token after the head, under that
# row's OWN sampling parameters: traced arrays, so a step is one dispatch
# whatever mix its rows ask and a config never seen before compiles nothing.

#: candidates a row with top-k keeps (the static width of `lax.top_k`);
#: `SamplingConfig`'s default 0.6 / 20 / 0.95 fits
ROW_CANDIDATES = 64


def rows_cover(temperature: float, top_k: int, top_p: float, min_p: float) -> bool:
    """Whether `sample_rows` draws from this config's distribution: greedy;
    a top-k within ROW_CANDIDATES (then top-p and min-p over the sorted
    candidates); or no top-k and no top-p (temperature and min-p over the
    whole row). Top-p alone needs the whole row sorted and a wider top-k
    more candidates: the caller samples those rows from their logits."""
    if temperature == 0.0 or 0 < top_k <= ROW_CANDIDATES:
        return True
    return top_k <= 0 and top_p >= 1.0


def sample_rows(
    logits: jax.Array,  # [L, V] float32
    keys: jax.Array,  # [L, 2] uint32: each row's PRNG chain
    temperature: jax.Array,  # [L] float32; 0 = greedy
    top_k: jax.Array,  # [L] int32; <= 0 = none
    top_p: jax.Array,  # [L] float32; >= 1 = none
    min_p: jax.Array,  # [L] float32; <= 0 = none
):
    """One token a row, each under its own config (see `rows_cover`), the
    warp chain of `sample` in its order: temperature, top-k, top-p, min-p,
    a categorical draw. Returns (tokens [L] int32, keys' [L, 2]): a sampled
    row draws under `split(key)[1]` and hands back `split(key)[0]`, a
    greedy row's key comes back as it went in.

    Greedy rows do not pay for the others: the candidates (`lax.top_k` at
    ROW_CANDIDATES) and the whole-row draw each sit under a `lax.cond` on
    "some row needs me", so a step of greedy rows runs one argmax."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    hot = temperature > 0.0
    narrow = top_k > 0

    def draw():
        pairs = jax.vmap(jax.random.split)(keys)  # [L, 2, 2]
        subs = pairs[:, 1]
        t = jnp.where(hot, temperature, 1.0)[:, None]
        # min-p in its logit form (min_p_filter): keep iff l >= l_max + ln(min_p)
        floor = jnp.where(
            min_p > 0.0, jnp.log(jnp.maximum(min_p, 1e-30)), -jnp.inf
        )[:, None]

        def among_candidates():
            vals, idx = jax.lax.top_k(logits, ROW_CANDIDATES)  # sorted descending
            vals = vals / t
            rank = jnp.arange(ROW_CANDIDATES)[None, :]
            vals = jnp.where(rank < top_k[:, None], vals, NEG_INF)
            # top_p_filter over a sorted row: kept iff the mass before it is < p
            probs = jax.nn.softmax(vals, axis=-1)
            kept = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
            thresh = jnp.min(jnp.where(kept, vals, jnp.inf), axis=-1, keepdims=True)
            vals = jnp.where(vals < thresh, NEG_INF, vals)
            vals = jnp.where(vals < vals[:, :1] + floor, NEG_INF, vals)
            choice = jax.vmap(jax.random.categorical)(subs, vals)
            return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

        def over_the_row():
            vals = logits / t
            lmax = jnp.max(vals, axis=-1, keepdims=True)
            vals = jnp.where(vals < lmax + floor, NEG_INF, vals)
            return jax.vmap(jax.random.categorical)(subs, vals).astype(jnp.int32)

        few = jax.lax.cond(jnp.any(hot & narrow), among_candidates, lambda: greedy)
        whole = jax.lax.cond(jnp.any(hot & ~narrow), over_the_row, lambda: greedy)
        tok = jnp.where(hot, jnp.where(narrow, few, whole), greedy)
        return tok, jnp.where(hot[:, None], pairs[:, 0], keys)

    return jax.lax.cond(jnp.any(hot), draw, lambda: (greedy, keys))


class RowAsk(NamedTuple):
    """What the rows of one decode step ask of `sample_rows`: two arrays
    over the rows (a pytree: it goes into the jitted step as is, two small
    transfers a step)."""

    keys: jax.Array  # [L, 2] uint32: each row's PRNG chain
    warp: jax.Array  # [L, 4] float32: temperature, top_k, top_p, min_p

    @classmethod
    def greedy(cls, rows: int) -> "RowAsk":
        """Host arrays for `rows` rows that ask nothing: greedy, zero keys.
        The caller writes the asking rows' own values in (`put`)."""
        warp = np.zeros((rows, 4), np.float32)
        warp[:, 2] = 1.0
        return cls(np.zeros((rows, 2), np.uint32), warp)

    def put(self, row: int, sampling, key) -> None:
        """Write one row's (temperature, top_k, top_p, min_p) and key."""
        self.keys[row] = key
        self.warp[row] = sampling

    @classmethod
    def of(cls, rows: int, asks) -> "tuple[RowAsk, int]":
        """The host arrays of a step of `rows` rows from `asks`, {row: an
        ask with `.sampling`, `.key`, `.top_n`
        (runtime/executor.SampleAsk)}, and the widest top-n any asked: the
        step's static log-probability width. Rows that asked nothing are
        greedy rows nobody reads."""
        ask = cls.greedy(rows)
        for row, want in asks.items():
            ask.put(row, want.sampling, want.key)
        return ask, max((want.top_n for want in asks.values()), default=0)


def choose_rows(logits: jax.Array, ask: RowAsk, top_n: int = 0, routed=None) -> jax.Array:
    """The tail of a serving decode step, after the head: every row's token
    under its own ask (`sample_rows`), with `top_n` > 0 (static) its log-
    probability and top-n under the UNWARPED logits (`logprob_topn`), and
    the experts the step routed to, packed for one transfer (`pack_rows`)."""
    w = ask.warp
    tok, keys = sample_rows(
        logits, ask.keys, w[:, 0], w[:, 1].astype(jnp.int32), w[:, 2], w[:, 3]
    )
    lp = ti = tl = None
    if top_n:
        lp, ti, tl = logprob_topn(logits, tok, top_n)
    return pack_rows(tok, keys, lp, ti, tl, routed)


@jax.jit
def logits_row(logits: jax.Array, row) -> jax.Array:
    """One row of a step's logits (the row is traced: one program for
    every row)."""
    return jax.lax.dynamic_index_in_dim(logits, row, 0, keepdims=False)


def logits_out(logits: jax.Array, rows):
    """The float32 logits of the hops a step answers with logits, copied to
    the host: ({row: [V]} or the whole [L, V], indexed by row either way;
    the bytes that moved). One such hop: its [V] floats leave the device,
    not the step's [L, V]; several: the whole array in ONE transfer (a
    transfer costs the same whatever its size); none: nothing."""
    if not rows:
        return {}, 0
    if len(rows) == 1:
        out = np.asarray(logits_row(logits, rows[0]), np.float32)
        return {rows[0]: out}, out.nbytes
    out = np.asarray(logits, np.float32)
    return out, out.nbytes


def row_replies(packed, top_n: int, asks, k: int = 0):
    """What a step answers its asking hops with, from the ONE array it
    handed the host (`pack_rows`): ({row: {"tokens": [[id]], "key": [2],
    and where the row asked for log-probabilities "logprobs": [lp],
    "top_ids": [[n]], "top_lps": [[n]]}} for the rows of `asks`, as plain
    lists; the experts [sparse layers, L, K] | None)."""
    tok, keys, lps, tis, tls, routed = unpack_rows(packed, top_n, k)
    tok, keys = tok.tolist(), keys.tolist()
    if top_n:
        lps, tis, tls = lps.tolist(), tis.tolist(), tls.tolist()
    out = {}
    for row, want in asks.items():
        out[row] = {"tokens": [[tok[row]]], "key": keys[row]}
        if want.want:
            out[row].update(
                logprobs=[lps[row]], top_ids=[tis[row][:want.want]],
                top_lps=[tls[row][:want.want]],
            )
    return out, routed


def pack_bits(*arrays: jax.Array) -> jax.Array:
    """Arrays of one leading axis [L, ...] and 32-bit elements as ONE int32
    array [L, W], each flattened a row and laid side by side, floats and
    unsigned as their bits: what a step hands the host leaves the device in
    one transfer (a transfer costs the same whatever its size, so five
    small ones cost five times one). `unpack_bits` is the inverse."""
    cols = [
        (a if a.dtype == jnp.int32 else jax.lax.bitcast_convert_type(a, jnp.int32))
        .reshape(a.shape[0], -1)
        for a in arrays
    ]
    return jnp.concatenate(cols, axis=1)


def unpack_bits(packed, *parts):
    """`pack_bits` undone on the host, over the numpy array the transfer
    made: one array a part, each part (dtype, the shape after the leading
    axis); the last part's shape may hold one -1 (it takes what is left)."""
    out, at = [], 0
    for dtype, tail in parts:
        n = packed.shape[1] - at if -1 in tail else int(np.prod(tail, dtype=int))
        out.append(packed[:, at:at + n].view(dtype).reshape(len(packed), *tail))
        at += n
    return out


def pack_rows(tok, keys, lp=None, top_ids=None, top_lps=None, routed=None) -> jax.Array:
    """What a decode step hands the host (`pack_bits`): the token, the
    row's next key, then with log-probabilities the token's own, the top
    ids and theirs, then the experts the row chose, [sparse layers, L, K]
    with the rows brought to the front."""
    parts = [tok.astype(jnp.int32), keys]
    if lp is not None:
        parts += [lp, top_ids, top_lps]
    if routed is not None:
        parts.append(jnp.moveaxis(routed, 1, 0))
    return pack_bits(*parts)


@jax.jit
def ahead_rows(packed: jax.Array, toks, keys, ahead):
    """The inputs of a decode step that runs ahead of its hops: for the
    rows of `ahead` [L] bool the token and the next key the step before
    left ON THE DEVICE (`pack_rows`' first three columns of its `packed`),
    for every other row the host's `toks` [L] int32 and `keys` [L, 2]
    uint32. What comes back has the shapes and dtypes of the host arrays
    and goes into the same compiled step; one small program a width of
    `packed` (the log-probability variants)."""
    last = jax.lax.bitcast_convert_type(packed[:, 1:3], jnp.uint32)
    return (jnp.where(ahead, packed[:, 0], toks),
            jnp.where(ahead[:, None], last, keys))


@partial(jax.jit, static_argnames=("block",))
def ahead_block_keys(packed: jax.Array, keys, ahead, block: int):
    """`ahead_rows` for a block step that runs ahead of its hops (a model
    generated by blocks of `block`): nothing of the next block is known, so
    all a row of `ahead` [L] bool takes from the step before is the key it
    left ON THE DEVICE: in its `packed` (`pack_bits` of tokens [L, block],
    passes [L, block], keys [L, 2], ...) the two columns after the first
    2 x `block`. Every other row keeps the host's `keys` [L, 2] uint32, whose
    shape and dtype come back; one small program a width of `packed`."""
    last = jax.lax.bitcast_convert_type(packed[:, 2 * block:2 * block + 2], jnp.uint32)
    return jnp.where(ahead[:, None], last, keys)


def unpack_rows(packed, top_n: int, k: int = 0):
    """`pack_rows` undone on the host: (tokens [L], keys [L, 2] uint32,
    lp [L] | None, top ids [L, n] | None, top log-probabilities [L, n] |
    None, experts [sparse layers, L, K] | None). `top_n` 0 = the step
    computed no log-probabilities; `k` > 0 = it returned the experts each
    row chose, `k` a layer."""
    parts = [(np.int32, ()), (np.uint32, (2,))]
    if top_n:
        parts += [(np.float32, ()), (np.int32, (top_n,)), (np.float32, (top_n,))]
    if k:
        parts.append((np.int32, (-1, k)))
    out = unpack_bits(packed, *parts)
    tok, keys = out[:2]
    lp, ti, tl = out[2:5] if top_n else (None, None, None)
    return tok, keys, lp, ti, tl, np.moveaxis(out[-1], 0, 1) if k else None
