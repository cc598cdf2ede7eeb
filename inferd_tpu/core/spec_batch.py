"""Lane-batched speculative decoding: every speculating session advances a
whole accepted run per device round, and concurrent sessions' rounds
COALESCE into one dispatch.

core.speculative.SpeculativeEngine drives ONE sequence (B=1) with its own
private caches — serving it requires a lock, so concurrent requests shed to
the regular loop (round-4 verdict: "speculation never composes with
concurrency"). This module is the composition: the draft scan, the target
verify chunk, and the accept frontier all run ONCE over the continuous-
batching engine's lanes (core.batch.BatchedEngine), so N speculating
sessions cost one draft scan + one verify forward per round — the target
weights are read once per round for ALL of them, stacking the speculative
win (fewer target reads per token) on top of the batching win (one read
serves every lane).

Reference anchor: the strictly one-token-per-pass decode this exists to
beat (/root/reference/models/qwen3/client/client.py:244-266).

Design (shares core.speculative's round invariant, per lane):
  * the TARGET cache is the BatchedEngine's own lane cache — a speculating
    lane is an ordinary engine lane (the regular decode flusher skips it;
    it skips regular lanes), so speculation and plain continuous batching
    interleave freely on one device;
  * the DRAFT cache is a second lane-indexed KVCache over the draft
    config's layers (layer-truncated self-draft by construction, so it is
    small); lanes not speculating this round compute garbage at their
    frontier which is never attributed (the same static-shape trick as
    BatchedEngine._decode_all — see the aliasing argument in core/cache);
  * one jitted round: [catch-up draft step] -> K-step draft scan ->
    (K+1)-token target verify with PER-LANE positions -> per-lane accept
    frontier. Host mirrors advance per lane by its own n_new;
  * greedy rounds emit each lane's target-greedy tokens EXACTLY (the
    classic guarantee, per lane); sampled rounds run the standard
    per-lane rejection scheme — each lane's emitted stream is distributed
    exactly as target-only sampling under its own PRNG chain (per-lane
    keys: a lane's draws never depend on which other lanes co-batched).

Rollback is free exactly as in the solo engine: verify writes K+1 slots at
the lane frontier, and the lane length simply advances by the accepted
count — stale slots are overwritten by the lane's own next round. Ring-KV
models bound the depth by RING_MARGIN (checked at construction).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import ModelConfig, SamplingConfig
from inferd_tpu.core import sampling as samplib
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import KVCache, RING_MARGIN

Params = Any

# Static top-N width every speculative runner's greedy logprob trail
# compiles with — THE one definition (the node's /generate gate, the solo
# engine, and both lane/mesh runners all read it; a per-site copy could
# silently desync the gate from the computed width).
SPEC_TOP_N = 8


@partial(jax.jit, static_argnames=("top_n",))
def row_logprob(logits, tok, top_n: int):
    """TARGET logprob + top-N alternatives of one emitted token from its
    raw logits row (prefill first tokens and tail steps — the same math
    as the verify-chunk trail). Shared by both runners."""
    lp, ti, tls = samplib.logprob_topn(
        logits[None], jnp.asarray([tok], jnp.int32), top_n
    )
    return lp[0], ti[0], tls[0]


def chunk_logprob_trail(tl, greedy, k: int, top_n: int, want_lp: bool):
    """Per-position logprob trail over a verify chunk: tl [L, K+1, V]
    logits, greedy [L, K+1] emitted tokens -> (lp [L, K+1], top_ids
    [L, K+1, N], top_lps [L, K+1, N]); zero-width placeholders when
    want_lp is False (static — the fast path never pays the full-vocab
    log-softmax). Shared by the lane and mesh greedy rounds."""
    L = greedy.shape[0]
    if want_lp:
        lp, ti, tls = samplib.logprob_topn(
            tl.reshape(L * (k + 1), -1), greedy.reshape(L * (k + 1)), top_n
        )
        return (
            lp.reshape(L, k + 1),
            ti.reshape(L, k + 1, -1),
            tls.reshape(L, k + 1, -1),
        )
    return (
        jnp.zeros((L, k + 1), jnp.float32),
        jnp.zeros((L, k + 1, 0), jnp.int32),
        jnp.zeros((L, k + 1, 0), jnp.float32),
    )


def spec_key(sampling: SamplingConfig):
    """(cache key, normalized config) for per-sampling-config speculative
    engines/runners. Greedy ignores the warp parameters entirely —
    normalize so greedy clients with different top-k/p defaults share ONE
    compiled engine (used by both the solo-engine LRU in runtime/node.py
    and the lane-runner LRU in runtime/batch_executor.py)."""
    import dataclasses as _dc

    if sampling.temperature == 0.0:
        return (0.0, 0, 1.0, 0.0), _dc.replace(
            sampling, temperature=0.0, top_k=0, top_p=1.0, min_p=0.0
        )
    return (
        (sampling.temperature, sampling.top_k, sampling.top_p,
         sampling.min_p),
        sampling,
    )


def make_draft_cache(
    draft_cfg: ModelConfig, lanes: int, max_len: int
) -> KVCache:
    """Lane-indexed draft KV cache (one draft lane per engine lane,
    shared by every sampling-config runner — a lane belongs to exactly one
    session at a time, so runners never contend for draft rows)."""
    return KVCache.create(draft_cfg, draft_cfg.num_layers, lanes, max_len)


# ---------------------------------------------------------------------------
# Round building blocks — shared by the lane rounds below and the in-mesh
# pipelined rounds (parallel.infer): the draft scan, full-accept catch-up,
# and accept-frontier math are identical whether the TARGET verify is a flat
# forward or a ppermute pipeline pass. All are traced inside the caller's
# jit; `L` below is lanes or microbatch slots interchangeably.
# ---------------------------------------------------------------------------


def draft_step(dp, dcfg: ModelConfig, dcache: KVCache, toks, dlens, advance):
    """One draft step over all lanes ([L] toks at per-lane positions);
    only `advance` lanes count. Non-advancing lanes write garbage at their
    frontier — never attributed (overwritten by their own next real
    write)."""
    from inferd_tpu.models import qwen3

    lg, nc, _ = qwen3.forward_cached(
        dp, dcfg, toks[:, None], dlens[:, None], dcache, dlens,
        real_end=dlens + 1,
    )
    return lg[:, 0], nc, dlens + advance.astype(jnp.int32)


def catch_up(dp, dcfg: ModelConfig, dcache: KVCache, catch, catch_mask, dlens):
    """Lanes one token behind after a fully-accepted round ingest it first
    (skipped entirely when no lane needs it). Returns (dcache',
    post-catchup draft lengths)."""
    def do_catch(dc):
        _, nc, _ = draft_step(dp, dcfg, dc, catch, dlens, catch_mask)
        return nc

    dcache = jax.lax.cond(jnp.any(catch_mask), do_catch, lambda dc: dc, dcache)
    return dcache, dlens + catch_mask.astype(jnp.int32)


def draft_scan(dp, dcfg: ModelConfig, dcache: KVCache, last, dlens, active,
               k: int, sc: SamplingConfig, draft_keys=None):
    """K greedy (draft_keys None) or warped-sampled draft steps for every
    active lane. Returns (dcache', drafts [L, K], dprobs [L, K, V] — zeros
    row placeholder when greedy). draft_keys [K, L, 2]."""
    sampled = draft_keys is not None

    def body(carry, keys_t):
        tok, dc, dl = carry
        lg, dc, dl = draft_step(dp, dcfg, dc, tok, dl, active)
        if sampled:
            wl = samplib.warped_logits(
                lg, sc.temperature, sc.top_k, sc.top_p, sc.min_p
            )  # [L, V]
            ntok = jax.vmap(
                lambda row, kk: jax.random.categorical(kk, row)
            )(wl, keys_t).astype(jnp.int32)
            probs = jax.nn.softmax(wl, axis=-1)
        else:
            ntok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            probs = ()
        ntok = jnp.where(active, ntok, tok).astype(jnp.int32)
        return (ntok, dc, dl), (ntok, probs)

    xs = draft_keys if sampled else jnp.zeros((k, 1), jnp.uint32)
    (_, dcache, _), (drafts, dprobs) = jax.lax.scan(
        body, (last, dcache, dlens), xs
    )
    d = drafts.T  # [L, K]
    if sampled:
        dprobs = jnp.transpose(dprobs, (1, 0, 2))  # [L, K, V]
    else:
        dprobs = None
    return dcache, d, dprobs


def greedy_accept(d, greedy, active, k: int):
    """Per-lane greedy accept frontier: d [L, K] drafts, greedy [L, K+1]
    the target's greedy chunk continuation. Returns (toks [L, K+1], n_new
    [L]) — lane l emits toks[l, :n_new[l]], exactly its target-greedy
    stream."""
    acc = jnp.cumprod((d == greedy[:, :k]).astype(jnp.int32), axis=1)
    m = jnp.sum(acc, axis=1)
    return greedy, jnp.where(active, m + 1, 0)


def rejection_accept(d, dprobs, tprobs, active, akeys, rskeys, k: int):
    """Per-lane rejection accept (Leviathan/Chen): d [L, K] draft tokens,
    dprobs [L, K, V] their draw distributions, tprobs [L, K+1, V] the
    target's warped distributions over the verify chunk. Returns (toks
    [L, K+1], n_new [L]); the emitted stream per lane is distributed
    exactly as target-only warped sampling."""
    L = d.shape[0]
    q_d = jnp.take_along_axis(tprobs[:, :k], d[..., None], axis=-1)[..., 0]
    p_d = jnp.take_along_axis(dprobs, d[..., None], axis=-1)[..., 0]
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(akeys)
    # STRICT <: u can be exactly 0 and `0 * p <= 0` would accept a
    # zero-target-probability token (core.speculative's edge)
    ok = u * p_d < q_d
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    m = jnp.sum(acc, axis=1)  # [L]
    n_new = jnp.where(active, m + 1, 0)

    resid = jnp.maximum(tprobs[:, :k] - dprobs, 0.0)
    rmass = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(
        rmass > 1e-9, resid / jnp.maximum(rmass, 1e-30), tprobs[:, :k]
    )
    corr = jnp.concatenate([resid, tprobs[:, k:]], axis=1)  # [L, K+1, V]
    corr_m = jnp.take_along_axis(corr, m[:, None, None], axis=1)[:, 0]
    extra = jax.vmap(
        lambda row, kk: jax.random.categorical(
            kk,
            jnp.where(row > 0, jnp.log(jnp.maximum(row, 1e-38)), -jnp.inf),
        )
    )(corr_m, rskeys).astype(jnp.int32)
    toks = jnp.concatenate([d, jnp.zeros((L, 1), jnp.int32)], axis=1)
    toks = jnp.where(
        jnp.arange(k + 1)[None, :] == m[:, None], extra[:, None], toks
    )
    return toks, n_new


def split_round_keys(keys, k: int):
    """Per-lane round key [L, 2] -> (draft_keys [K, L, 2], accept keys
    [L, 2], resample keys [L, 2]) — a lane's draws never depend on which
    other lanes co-batched."""
    all_keys = jax.vmap(lambda kk: jax.random.split(kk, k + 2))(keys)
    return (
        jnp.transpose(all_keys[:, :k], (1, 0, 2)),
        all_keys[:, k],
        all_keys[:, k + 1],
    )


def check_ring_margin(cfg: ModelConfig, draft_cfg: ModelConfig, k: int):
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError("target/draft vocab mismatch")
    if (cfg.sliding_window or draft_cfg.sliding_window) and (
        k + 1 > RING_MARGIN
    ):
        raise ValueError(
            f"speculative k={k} exceeds the sliding-window ring margin "
            f"({RING_MARGIN - 1} max for ring-KV models)"
        )


class LaneSpecRunner:
    """Jitted speculative rounds for ONE sampling config over a
    BatchedEngine's lanes.

    Stateless over device buffers: the target cache lives in the engine,
    the draft cache is passed through every call (the executor owns both
    and serializes device steps under its lock). Warp parameters are baked
    into the jits — the serving layer caches one runner per sampling
    config, exactly like the solo engine LRU (runtime/node.py)."""

    def __init__(
        self,
        cfg: ModelConfig,
        draft_cfg: ModelConfig,
        k: int,
        sampling: Optional[SamplingConfig] = None,
    ):
        check_ring_margin(cfg, draft_cfg, k)
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.k = k
        self.top_n = SPEC_TOP_N
        self.sampling = sampling or SamplingConfig(temperature=0.0)
        sc = self.sampling
        K = k
        TOPN = self.top_n
        from inferd_tpu.models import qwen3

        from inferd_tpu.core.cache import lane_slice, lane_write

        @partial(jax.jit, donate_argnames=("dcache",))
        def _draft_prefill(dp, dcache: KVCache, tokens, lane, start, n):
            """Ingest one lane's prompt chunk into the draft cache (no
            logits consumer — the first draft proposal starts from the
            target's first emitted token)."""
            lc = lane_slice(dcache, lane)
            _, nc, _ = qwen3.forward_cached(
                dp, draft_cfg, tokens, None, lc, start, real_end=start + n
            )
            return lane_write(dcache, lane, nc)

        def _verify(tp, tcache, last, d, tlens):
            """Target verify: the whole [L, K+1] chunk in one flat forward
            at per-lane positions (the mesh sibling verifies through the
            ppermute pipeline pass instead — parallel.infer)."""
            chunk = jnp.concatenate([last[:, None], d], axis=1)  # [L, K+1]
            pos = tlens[:, None] + jnp.arange(K + 1)[None, :]
            return qwen3.forward_cached(
                tp, cfg, chunk, pos, tcache, tlens, real_end=tlens + K + 1
            )[:2]

        @partial(jax.jit, donate_argnames=("tcache", "dcache"),
                 static_argnames=("want_lp",))
        def _spec_round_greedy(tp, dp, tcache: KVCache, dcache: KVCache,
                               last, catch, catch_mask, tlens, dlens, active,
                               want_lp: bool = False):
            """One greedy round for every active lane. Returns (toks
            [L, K+1], n_new [L], tcache', dcache', lp [L, K+1], top_ids
            [L, K+1, N], top_lps [L, K+1, N]): lane l emits
            toks[l, :n_new[l]] — its target-greedy continuation exactly.
            want_lp (static — the no-logprob fast path never pays the
            full-vocab log-softmax) fills the TARGET model's logprob of
            each emitted token + its top-N alternatives from the verify
            chunk's logits, identical to the solo engine's trail."""
            dcache, dl0 = catch_up(dp, draft_cfg, dcache, catch, catch_mask, dlens)
            dcache, d, _ = draft_scan(
                dp, draft_cfg, dcache, last, dl0, active, K, sc
            )
            tl, tcache = _verify(tp, tcache, last, d, tlens)
            greedy = jnp.argmax(tl, axis=-1).astype(jnp.int32)  # [L, K+1]
            toks, n_new = greedy_accept(d, greedy, active, K)
            lp, ti, tls = chunk_logprob_trail(tl, greedy, K, TOPN, want_lp)
            return toks, n_new, tcache, dcache, lp, ti, tls

        @partial(jax.jit, donate_argnames=("tcache", "dcache"))
        def _spec_round_sampled(tp, dp, tcache: KVCache, dcache: KVCache,
                                last, catch, catch_mask, tlens, dlens,
                                active, keys):
            """One rejection-sampled round (Leviathan/Chen scheme, per
            lane). keys [L, 2]: each lane's round key — draws are vmapped
            per lane so a lane's stream never depends on co-batched lanes.
            Returns (toks [L, K+1], n_new [L], tcache', dcache')."""
            draft_keys, akeys, rskeys = split_round_keys(keys, K)
            dcache, dl0 = catch_up(dp, draft_cfg, dcache, catch, catch_mask, dlens)
            dcache, d, dprobs = draft_scan(
                dp, draft_cfg, dcache, last, dl0, active, K, sc, draft_keys
            )
            tl, tcache = _verify(tp, tcache, last, d, tlens)
            tprobs = samplib.warped_probs(tl, sc)  # [L, K+1, V]
            toks, n_new = rejection_accept(
                d, dprobs, tprobs, active, akeys, rskeys, K
            )
            return toks, n_new, tcache, dcache

        @jax.jit
        def _first_token(logits, key):
            """Sample/argmax the post-prefill first token the way the solo
            engines do (greedy: argmax; sampled: one warped draw)."""
            row = logits[None]
            if sc.temperature == 0.0:
                return jnp.argmax(row, axis=-1)[0].astype(jnp.int32)
            return samplib.sample(
                row, key, sc.temperature, sc.top_k, sc.top_p, sc.min_p
            )[0].astype(jnp.int32)

        self._draft_prefill = _draft_prefill
        self._spec_round_greedy = _spec_round_greedy
        self._spec_round_sampled = _spec_round_sampled
        self._first_token_fn = _first_token

    # -- host-facing surface (the executor holds the device lock) -----------

    def draft_prefill(
        self, dparams: Params, dcache: KVCache, tokens: np.ndarray,
        lane: int, start: int, n: int,
    ) -> KVCache:
        return self._draft_prefill(
            dparams, dcache, jnp.asarray(tokens, jnp.int32),
            jnp.int32(lane), jnp.int32(start), jnp.int32(n),
        )

    def first_token(self, logits: np.ndarray, key) -> int:
        return int(self._first_token_fn(jnp.asarray(logits), key))

    def row_lp(self, logits: np.ndarray, tok: int):
        """(logprob, top_ids list, top_lps list) of `tok` under `logits`."""
        lp, ti, tls = row_logprob(jnp.asarray(logits), int(tok), self.top_n)
        return float(lp), np.asarray(ti).tolist(), np.asarray(tls).tolist()

    def run_round(
        self,
        params: Params,
        dparams: Params,
        engine: BatchedEngine,
        dcache: KVCache,
        last: np.ndarray,  # [L] int32
        catch: np.ndarray,  # [L] int32
        catch_mask: np.ndarray,  # [L] bool
        dlens: np.ndarray,  # [L] int32 (pre-catchup draft lengths)
        active: np.ndarray,  # [L] bool
        keys: Optional[np.ndarray] = None,  # [L, 2] uint32 (sampled only)
        want_lp: bool = False,
    ) -> tuple:
        """One coalesced speculative round over `engine`'s lanes. Mutates
        engine.cache (target) in place-functionally; returns (toks
        [L, K+1], n_new [L], new draft cache) — plus (lp, top_ids,
        top_lps) per chunk position when want_lp (greedy only). Host
        bookkeeping (lengths, catch-up state) is the caller's.

        Headroom contract: the verify chunk writes K+1 rows at EVERY
        lane's frontier (inactive lanes' rows are garbage, never
        attributed) — so every lane, speculating or not, must have K+1
        free slots, else the per-lane dynamic_update_slice CLAMPS and
        silently overwrites that lane's newest valid KV
        (models/qwen3.decoder_layer caller contract). Checked here against
        the host mirrors; the serving layer avoids ever tripping it by
        capping ALL admissions at max_len - (k+1) while speculation is
        enabled (runtime/batch_executor)."""
        worst = max(engine.lengths)
        if worst + self.k + 1 > engine.max_len:
            raise BufferError(
                f"spec round needs k+1={self.k + 1} free slots on every "
                f"lane; a lane is at {worst}/{engine.max_len}"
            )
        tlens = jnp.asarray(engine.lengths, jnp.int32)
        args = (
            params, dparams, engine.cache, dcache,
            jnp.asarray(last, jnp.int32), jnp.asarray(catch, jnp.int32),
            jnp.asarray(catch_mask, bool), tlens,
            jnp.asarray(dlens, jnp.int32), jnp.asarray(active, bool),
        )
        lp = ti = tls = None
        if self.sampling.temperature == 0.0:
            toks, n_new, tcache, dcache, lp, ti, tls = self._spec_round_greedy(
                *args, want_lp=want_lp
            )
        else:
            if want_lp:
                raise ValueError(
                    "speculative logprobs are greedy-only (the sampled "
                    "rejection round has no per-token logprob trail)"
                )
            if keys is None:
                raise ValueError("sampled rounds need per-lane keys")
            toks, n_new, tcache, dcache = self._spec_round_sampled(
                *args, jnp.asarray(keys, jnp.uint32)
            )
        engine.cache = tcache
        if want_lp:
            return (
                np.asarray(toks), np.asarray(n_new), dcache,
                np.asarray(lp), np.asarray(ti), np.asarray(tls),
            )
        return np.asarray(toks), np.asarray(n_new), dcache


def generate_lanes(
    engine: BatchedEngine,
    runner: LaneSpecRunner,
    params: Params,
    dparams: Params,
    dcache: KVCache,
    prompts,
    max_new_tokens: int,
    eos_token_id: Optional[int] = None,
    seed: int = 0,
):
    """Drive several prompts to completion with every lane speculating in
    LOCKSTEP (the test/bench driver; serving drives rounds through the
    batched executor's window instead). Returns (results, dcache,
    accept_rate): results[i] is prompt i's emitted tokens — greedy rounds
    are token-exact with the solo Engine; sampled rounds follow per-lane
    PRNG chains seeded PRNGKey(seed + i)."""
    from inferd_tpu.core.generate import bucket_len

    K, L = runner.k, engine.lanes
    if len(prompts) > len(engine.free):
        raise RuntimeError(f"{len(prompts)} prompts > {len(engine.free)} free lanes")
    sampled = runner.sampling.temperature > 0.0

    lanes, outs, keys_chain = [], {}, {}
    dlens = [0] * L
    for i, p in enumerate(prompts):
        lane = engine.free.pop()
        lanes.append(lane)
        n = len(p)
        b = min(bucket_len(n), engine.max_len)
        padded = np.zeros((1, b), np.int32)
        padded[0, :n] = np.asarray(p, np.int32)
        engine.cache, logits = engine._prefill_lane_logits(
            engine.params, engine.cache, jnp.asarray(padded),
            jnp.int32(lane), jnp.int32(0), jnp.int32(n),
        )
        engine.lengths[lane] = n
        dcache = runner.draft_prefill(dparams, dcache, padded, lane, 0, n)
        dlens[lane] = n
        key = jax.random.PRNGKey(seed + i)
        key, sub = jax.random.split(key)
        if sampled:
            first = runner.first_token(np.asarray(logits), sub)
        else:
            first = int(np.argmax(np.asarray(logits)))
        outs[lane] = [first]
        keys_chain[lane] = key

    live = set(lanes)
    drafted = accepted = 0
    while live:
        for lane in list(live):
            if (
                len(outs[lane]) >= max_new_tokens
                or (eos_token_id is not None and outs[lane][-1] == eos_token_id)
                or engine.lengths[lane] + K + 1 > engine.max_len
            ):
                live.discard(lane)
        if not live:
            break
        active = np.zeros((L,), bool)
        last = np.zeros((L,), np.int32)
        catch = np.zeros((L,), np.int32)
        catch_mask = np.zeros((L,), bool)
        keys = np.zeros((L, 2), np.uint32)
        for lane in live:
            active[lane] = True
            last[lane] = outs[lane][-1]
            if dlens[lane] < engine.lengths[lane]:  # full-accept catch-up
                catch[lane] = outs[lane][-2]
                catch_mask[lane] = True
            if sampled:
                keys_chain[lane], sub = jax.random.split(keys_chain[lane])
                keys[lane] = np.asarray(sub)
        toks, n_new, dcache = runner.run_round(
            params, dparams, engine, dcache, last, catch, catch_mask,
            np.asarray(dlens, np.int32), active,
            keys if sampled else None,
        )
        for lane in live:
            n = int(n_new[lane])
            old = engine.lengths[lane]
            engine.lengths[lane] = old + n
            dlens[lane] = old + min(n, K)
            drafted += K
            accepted += n - 1
            for t in toks[lane, :n].tolist():
                outs[lane].append(int(t))
                if (
                    eos_token_id is not None and t == eos_token_id
                ) or len(outs[lane]) >= max_new_tokens:
                    break
    results = [outs[lane][:max_new_tokens] for lane in lanes]
    for lane in lanes:
        engine.release(lane)
    return results, dcache, accepted / max(drafted, 1)
