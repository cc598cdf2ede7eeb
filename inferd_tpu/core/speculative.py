"""Speculative decoding: a draft model proposes K tokens, the target model
verifies them in ONE chunked forward, and every accepted token costs the
target a fraction of a sequential decode step.

Added TPU-first scope beyond the reference (whose decode is strictly one
token per pipeline pass — /root/reference/models/qwen3/client/client.py:
244-266): bs=1 decode is HBM-bound on target weight reads, and verification
reads the target weights once per chunk instead of once per token, so with
acceptance rate a the target-read cost per emitted token drops toward
1/(1 + a*K) of sequential decode.

Design notes (what makes this cheap here):
  * the functional KV cache (core.cache.KVCache) masks validity by
    `length`, and chunk writes land at `length` — so REJECTION ROLLBACK IS
    FREE: keep the returned buffers, reset `length` to the accepted
    frontier, and stale slots are overwritten by the next chunk;
  * draft-scan + chunk-verify + accept-frontier run as ONE jitted step
    (lax arithmetic, no host sync inside); the host loop advances a whole
    accepted run per dispatch — fewer dispatches than per-token decode,
    which also matters on high-latency interconnects;
  * greedy mode reproduces the target's greedy decode EXACTLY, token for
    token, regardless of draft quality (the classic guarantee) — that
    exactness is the test;
  * sampled mode (temperature > 0) uses the standard rejection scheme over
    the warped (temperature/top-k/top-p) distributions: the emitted stream
    is DISTRIBUTED exactly as target-only sampling — pinned by a
    total-variation test against the target's warped probabilities.

Round invariant (B = 1):
  - both caches hold KV for the emitted stream x_0..x_{n-1}
  - x_n = `last_tok` is emitted but in NEITHER cache
  - the draft scan's first step ingests x_n, then drafts d_1..d_K
  - the target verifies chunk [x_n, d_1..d_K] in one forward; greedy[i] is
    its next token after chunk[:i+1], so d_{i+1} is accepted iff it equals
    greedy[i] and all earlier drafts were accepted
  - with m accepted drafts the round emits greedy[0..m] (m+1 tokens); the
    new pending token is greedy[m], and both caches roll forward exactly
    m+1 slots (the draft wrote only K slots, so on full acceptance it is
    one token behind and the next round's host loop ingests that token).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import ModelConfig, SamplingConfig
from inferd_tpu.core import sampling as samplib
from inferd_tpu.core.cache import KVCache
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.core.spec_batch import SPEC_TOP_N
from inferd_tpu.models import qwen3

Params = Any


def self_draft(
    cfg: ModelConfig, params: Any, draft_layers: int
) -> Tuple[ModelConfig, Any]:
    """Layer-truncated SELF-draft: the target's own first `draft_layers`
    layers propose (no second checkpoint read). One definition shared by
    the local CLI (tools/generate) and the node's speculative /generate."""
    if not 0 < draft_layers < cfg.num_layers:
        raise ValueError(
            f"draft_layers must be in (0, {cfg.num_layers}), got {draft_layers}"
        )
    dcfg = cfg.with_layers(draft_layers)
    dparams = dict(params)
    dparams["layers"] = qwen3.slice_layers(params["layers"], 0, draft_layers)
    return dcfg, dparams


class SpeculativeEngine:
    """Greedy speculative decoding with a small draft model.

    Both models must share the tokenizer/vocab (e.g. qwen3-0.6b drafting
    for qwen3-8b). Decode state is two KV caches; rollback = length reset.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        draft_cfg: ModelConfig,
        draft_params: Params,
        k: int = 4,
        max_len: int = 2048,
        sampling_cfg: Optional[SamplingConfig] = None,
        top_n: int = SPEC_TOP_N,
    ):
        if cfg.vocab_size != draft_cfg.vocab_size:
            raise ValueError(
                f"target/draft vocab mismatch: {cfg.vocab_size} vs "
                f"{draft_cfg.vocab_size} (they must share a tokenizer)"
            )
        from inferd_tpu.core.cache import RING_MARGIN

        if (cfg.sliding_window or draft_cfg.sliding_window) and k + 1 > RING_MARGIN:
            # ring KV safety: rejection rollback may reset length by up to
            # the verify-chunk depth, and stale ring slots stay outside
            # every window only while that depth is under the ring margin
            raise ValueError(
                f"speculative k={k} exceeds the sliding-window ring margin "
                f"({RING_MARGIN - 1} max for ring-KV models)"
            )
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.params = params
        self.draft_params = draft_params
        self.k = k
        self.max_len = max_len
        self.sampling = sampling_cfg or SamplingConfig(temperature=0.0)

        self.top_n = top_n
        tcfg, dcfg, K = cfg, draft_cfg, k
        TOPN = top_n
        sc = self.sampling

        def _warped_probs(logits):  # [.., V] f32 -> the sampled distribution
            return samplib.warped_probs(logits, sc)

        @partial(jax.jit, donate_argnames=("tc", "dc"),
                 static_argnames=("want_lp",))
        def _prefill(tp, dp, tokens, n, tc: KVCache, dc: KVCache, key,
                     want_lp: bool = False):
            """Prefill BOTH models on the prompt; returns the target's next
            token (greedy, or sampled when temperature > 0) + caches."""
            tl, tc, _ = qwen3.forward_cached(tp, tcfg, tokens, None, tc, jnp.int32(0), real_end=n)
            _, dc, _ = qwen3.forward_cached(dp, dcfg, tokens, None, dc, jnp.int32(0), real_end=n)
            tc = dataclasses.replace(tc, length=n)
            dc = dataclasses.replace(dc, length=n)
            last = tl[jnp.arange(tokens.shape[0]), n - 1]
            if sc.temperature == 0.0:
                tok = jnp.argmax(last, axis=-1)
            else:
                tok = samplib.sample(last, key, sc.temperature, sc.top_k, sc.top_p, sc.min_p)
            tok = tok.astype(jnp.int32)
            # want_lp static: the plain greedy fast path never pays the
            # full-vocab log-softmax (each variant compiles separately)
            lp, ti, tls = (
                samplib.logprob_topn(last, tok, TOPN) if want_lp
                else (jnp.zeros((1,), jnp.float32),
                      jnp.zeros((1, 0), jnp.int32), jnp.zeros((1, 0), jnp.float32))
            )
            return tok, tc, dc, lp, ti, tls

        @partial(jax.jit, donate_argnames=("dc",))
        def _draft_ingest(dp, tok, dc: KVCache):
            """Cache catch-up: feed one already-emitted token through the
            draft (used after a fully-accepted round)."""
            _, nc, _ = qwen3.forward_cached(dp, dcfg, tok[:, None], None, dc, dc.length)
            return dataclasses.replace(nc, length=dc.length + 1)

        @partial(jax.jit, donate_argnames=("tc", "dc"),
                 static_argnames=("want_lp",))
        def _spec_step(tp, dp, last_tok, tc: KVCache, dc: KVCache,
                       want_lp: bool = False):
            """One speculative round (see module docstring invariant).

            Returns (toks [K+1], n_new in [1, K+1], tc', dc'): toks[:n_new]
            are the emitted target-greedy tokens."""
            n = tc.length

            # -- draft: ingest x_n then K-1 self-fed greedy steps -----------
            def draft_body(carry, _):
                tok, c = carry
                lg, nc, _ = qwen3.forward_cached(
                    dp, dcfg, tok[:, None], None, c, c.length
                )
                c = dataclasses.replace(nc, length=c.length + 1)
                ntok = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                return (ntok, c), ntok

            (_, dc2), drafts = jax.lax.scan(
                draft_body, (last_tok, dc), None, length=K
            )  # drafts [K, B]: d_1..d_K; dc2.length == n + K

            # -- target: verify the whole chunk in one forward --------------
            chunk = jnp.concatenate([last_tok[None], drafts], axis=0).T  # [B, K+1]
            tl, tc2, _ = qwen3.forward_cached(tp, tcfg, chunk, None, tc, n)
            greedy = jnp.argmax(tl, axis=-1).astype(jnp.int32)  # [B, K+1]

            # -- target logprobs for the whole chunk: the TARGET model's
            # log-softmax at every verify position (the serving-API logprob
            # of each emitted token g[i]; positions past the accept frontier
            # are discarded host-side)
            lp_all, ti_all, tl_all = (
                samplib.logprob_topn(tl[0], greedy[0], TOPN) if want_lp
                else (jnp.zeros((K + 1,), jnp.float32),
                      jnp.zeros((K + 1, 0), jnp.int32),
                      jnp.zeros((K + 1, 0), jnp.float32))
            )  # [K+1], [K+1, N], [K+1, N]

            # -- accept frontier (B = 1) ------------------------------------
            d = drafts[:, 0]  # [K]
            g = greedy[0]  # [K+1]
            acc = jnp.cumprod((d == g[:K]).astype(jnp.int32))  # 1..1 0..0
            m = jnp.sum(acc)  # accepted draft count in [0, K]
            n_new = m + 1  # + the target's own correction/extension token

            # -- roll both caches to the accepted frontier (ring-safe: the
            # rollback depth is <= K < cache.RING_MARGIN, so stale ring
            # slots stay structurally outside every window)
            tc = dataclasses.replace(tc2, length=n + n_new)
            # draft slots n..n+K-1 hold [x_n, d_1..d_{K-1}]; the accepted
            # stream prefix occupies n..n+m, so the draft is exactly at the
            # frontier for m < K and one token behind for m == K
            dc2 = dataclasses.replace(dc2, length=n + jnp.minimum(n_new, K))
            return g, n_new, tc, dc2, lp_all, ti_all, tl_all

        @partial(jax.jit, donate_argnames=("tc", "dc"))
        def _spec_step_sampled(tp, dp, last_tok, tc: KVCache, dc: KVCache, rkey):
            """One sampled speculative round (standard rejection scheme,
            Leviathan et al. / Chen et al.): draft token d_i ~ p_i is
            accepted with prob min(1, q_i(d_i)/p_i(d_i)); the first
            rejection resamples from the residual norm(max(q_i - p_i, 0));
            full acceptance samples the target's extra position. The
            emitted stream is distributed EXACTLY as target-only sampling
            over the warped (temperature/top-k/top-p) distribution."""
            n = tc.length
            keys = jax.random.split(rkey, K + 2)
            draft_keys, akey, rskey = keys[:K], keys[K], keys[K + 1]

            def draft_body(carry, key):
                tok, c = carry
                lg, nc, _ = qwen3.forward_cached(
                    dp, dcfg, tok[:, None], None, c, c.length
                )
                c = dataclasses.replace(nc, length=c.length + 1)
                wl = samplib.warped_logits(
                    lg[:, 0], sc.temperature, sc.top_k, sc.top_p, sc.min_p
                )  # [B, V]
                # categorical over the warped logits directly: the draw is
                # from exactly softmax(wl) — the same p the accept ratio
                # and residual use (no smoothing mismatch)
                ntok = jax.random.categorical(key, wl, axis=-1).astype(jnp.int32)
                return (ntok, c), (ntok, jax.nn.softmax(wl, axis=-1)[0])

            (_, dc2), (drafts, dprobs) = jax.lax.scan(
                draft_body, (last_tok, dc), draft_keys
            )  # drafts [K, B]; dprobs [K, V]

            chunk = jnp.concatenate([last_tok[None], drafts], axis=0).T  # [B, K+1]
            tl, tc2, _ = qwen3.forward_cached(tp, tcfg, chunk, None, tc, n)
            tprobs = _warped_probs(tl[0])  # [K+1, V]

            d = drafts[:, 0]  # [K]
            idx = jnp.arange(K)
            q_d = tprobs[idx, d]  # q_i(d_i)
            p_d = dprobs[idx, d]  # p_i(d_i) > 0 (d_i was sampled from p_i)
            u = jax.random.uniform(akey, (K,))
            # STRICT: u in [0,1) can be exactly 0, and `0 * p <= 0` would
            # accept a token with zero target probability; `<` rejects both
            # the q_d == 0 and p_d == 0 edges, matching min(1, q/p)
            ok = u * p_d < q_d  # accept wp min(1, q/p)
            acc = jnp.cumprod(ok.astype(jnp.int32))
            m = jnp.sum(acc)  # accepted draft count
            n_new = m + 1

            # correction distribution at the frontier: residual for m < K,
            # the target's extra position for m == K
            resid = jnp.maximum(tprobs[:K] - dprobs, 0.0)  # [K, V]
            rmass = jnp.sum(resid, axis=-1, keepdims=True)
            # q <= p everywhere can only happen when q == p; guard the
            # normalization and fall back to q itself
            resid = jnp.where(rmass > 1e-9, resid / jnp.maximum(rmass, 1e-30), tprobs[:K])
            corr = jnp.concatenate([resid, tprobs[K:]], axis=0)  # [K+1, V]
            corr_m = corr[m]
            extra = jax.random.categorical(
                rskey,
                jnp.where(corr_m > 0, jnp.log(jnp.maximum(corr_m, 1e-38)), -jnp.inf),
                axis=-1,
            ).astype(jnp.int32)

            toks = jnp.concatenate([d, jnp.zeros((1,), jnp.int32)]).at[m].set(extra)

            tc = dataclasses.replace(tc2, length=n + n_new)
            dc2 = dataclasses.replace(dc2, length=n + jnp.minimum(n_new, K))
            return toks, n_new, tc, dc2

        self._prefill = _prefill
        self._spec_step = _spec_step
        self._spec_step_sampled = _spec_step_sampled
        self._draft_ingest = _draft_ingest

    def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        logprob_sink: Optional[List[float]] = None,
        top_sink: Optional[List] = None,
    ) -> Tuple[List[int], float]:
        """Generation; returns (tokens, draft_acceptance_rate). See
        generate_with_stats for the raw proposed/accepted counts (the
        serving layer's cumulative metrics need counts, not a rate — and
        returning them keeps the handoff atomic under concurrent
        generates on one cached engine; mutable instance attributes would
        race)."""
        out, rate, _, _ = self.generate_with_stats(
            prompt_ids, max_new_tokens, eos_token_id, seed,
            logprob_sink, top_sink,
        )
        return out, rate

    def generate_with_stats(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        logprob_sink: Optional[List[float]] = None,
        top_sink: Optional[List] = None,
        on_tokens=None,
    ) -> Tuple[List[int], float, int, int]:
        """Generation; returns (tokens, draft_acceptance_rate, drafted,
        accepted). `on_tokens` (optional sync callable) receives each
        ACCEPTED RUN (a list of token ids) the moment its round lands —
        the streaming hook; called from the caller's thread.

        temperature == 0 (default): token-exact with core.generate.Engine
        greedy decode on the target. temperature > 0: rejection-sampled —
        the output stream is DISTRIBUTED exactly as target-only sampling
        (not token-identical to any particular Engine key schedule).

        `logprob_sink`/`top_sink` (greedy mode only — the rejection-sampled
        step has no per-token logprob trail) collect the TARGET model's
        log-probability of each emitted token + its top-`self.top_n`
        alternatives, straight from the verify chunk's logits — identical
        to what a plain Engine run reports for the same tokens.
        """
        want_lp = logprob_sink is not None or top_sink is not None
        if want_lp and self.sampling.temperature > 0.0:
            raise ValueError(
                "speculative logprobs are greedy-only (the sampled "
                "rejection step has no per-token logprob trail)"
            )
        if max_new_tokens <= 0:
            # match Engine.generate: no prefill, no emission — a streamed
            # max_new_tokens=0 must not produce a phantom token line
            if logprob_sink is not None:
                logprob_sink.clear()
            if top_sink is not None:
                top_sink.clear()
            return [], 0.0, 0, 0
        if logprob_sink is not None:
            logprob_sink.clear()
        if top_sink is not None:
            top_sink.clear()

        def record(lp, ti, tl):
            if logprob_sink is not None:
                logprob_sink.append(float(lp))
            if top_sink is not None:
                top_sink.append(
                    (np.asarray(ti).tolist(), np.asarray(tl).tolist())
                )

        n = len(prompt_ids)
        b = bucket_len(n)
        tokens = jnp.asarray([list(prompt_ids) + [0] * (b - n)], jnp.int32)
        tc = KVCache.create(self.cfg, self.cfg.num_layers, 1, self.max_len)
        dc = KVCache.create(self.draft_cfg, self.draft_cfg.num_layers, 1, self.max_len)
        key, sub = jax.random.split(jax.random.PRNGKey(seed))
        tok, tc, dc, plp, pti, ptl = self._prefill(
            self.params, self.draft_params, tokens, jnp.int32(n), tc, dc, sub,
            want_lp,
        )
        sampled = self.sampling.temperature > 0.0

        out: List[int] = [int(tok[0])]
        if want_lp:
            record(plp[0], pti[0], ptl[0])
        if on_tokens is not None:
            on_tokens(out[:1])
        drafted = accepted = 0
        while len(out) < max_new_tokens and (
            eos_token_id is None or out[-1] != eos_token_id
        ):
            if int(tc.length) + self.k + 1 > self.max_len:
                break  # KV budget: a whole verify chunk must fit
            if int(dc.length) < int(tc.length):  # catch-up after full accept
                dc = self._draft_ingest(
                    self.draft_params, jnp.asarray([out[-2]], jnp.int32), dc
                )
            if sampled:
                key, sub = jax.random.split(key)
                toks, n_new, tc, dc = self._spec_step_sampled(
                    self.params, self.draft_params, tok, tc, dc, sub
                )
                lps = tis = tls = None
            else:
                toks, n_new, tc, dc, lps, tis, tls = self._spec_step(
                    self.params, self.draft_params, tok, tc, dc, want_lp
                )
            n_new = int(n_new)
            drafted += self.k
            accepted += n_new - 1
            run: List[int] = []
            for j, t in enumerate(np.asarray(toks[:n_new]).tolist()):
                out.append(int(t))
                run.append(int(t))
                if want_lp:
                    record(lps[j], tis[j], tls[j])
                if (eos_token_id is not None and t == eos_token_id) or len(
                    out
                ) >= max_new_tokens:
                    break
            if on_tokens is not None and run:
                on_tokens(run)
            tok = jnp.asarray([out[-1]], jnp.int32)
        if logprob_sink is not None:
            del logprob_sink[max_new_tokens:]
        if top_sink is not None:
            del top_sink[max_new_tokens:]
        return (
            out[:max_new_tokens], accepted / max(drafted, 1), drafted, accepted
        )
