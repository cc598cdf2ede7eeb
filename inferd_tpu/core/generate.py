"""Single-process generation engine: jitted prefill + decode over a
functional KV cache.

Capability parity with the reference's client-side generation loop
(/root/reference/models/qwen3/client/client.py:204-287 — chat-template
prefill, per-token decode with absolute positions, server-held KV, sampling,
EOS/max-length stop), redesigned for XLA:

  * prompt lengths are padded to power-of-two buckets so each bucket
    compiles once (dynamic shapes would recompile every prompt length);
  * decode is one fused jit step: forward + temperature/top-k/top-p sample
    on-device, so the host loop only syncs one int per token;
  * `generate_scan` runs the whole decode as a `lax.scan` — a single
    dispatch for fixed-length generation, the TPU-friendly benchmark path.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import ModelConfig, SamplingConfig
from inferd_tpu.core.cache import KVCache, grow
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core import sampling as samplib
from inferd_tpu.models import qwen3


def bucket_len(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


class Engine:
    """Owns params + jitted step functions for one model on one device/mesh."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        max_len: int = 2048,
        sampling_cfg: Optional[SamplingConfig] = None,
        ring_kv: Optional[bool] = None,
        max_pins: int = 4,
    ):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.sampling = sampling_cfg or SamplingConfig()
        # ring_kv=None auto-enables O(window) ring storage for sliding-
        # window models (core.cache); False forces the classic uniform
        # full-length layout (comparison/compat path)
        self.ring_kv = ring_kv

        # cache buffers are donated: each step's KV update reuses the input
        # buffers in place on TPU instead of allocating a fresh [L,B,T,n,d]
        # pair per token (callers always rebind to the returned cache)
        @partial(jax.jit, donate_argnames=("cache",))
        def _prefill(params, tokens, prompt_len, cache: KVCache):
            # tokens are padded to a bucket; positions run 0..S-1. Slots past
            # prompt_len hold garbage but are never attended: cache.length is
            # reset to prompt_len and decode overwrites them sequentially
            # (rings drop padded rows at write time via real_end).
            logits, nc, _ = qwen3.forward_cached(
                params, cfg, tokens, None, cache, jnp.int32(0),
                real_end=prompt_len,
            )
            cache = dataclasses.replace(nc, length=prompt_len)
            last = logits[jnp.arange(tokens.shape[0]), prompt_len - 1]
            return last, cache

        @partial(jax.jit, donate_argnames=("cache",))
        def _prefill_at(params, tokens, start_pos, real_len, cache: KVCache):
            # prefill a chunk at an arbitrary offset (prefix-cache reuse:
            # the first start_pos positions are already in the cache)
            b, s = tokens.shape
            pos = start_pos + jnp.broadcast_to(jnp.arange(s), (b, s))
            logits, nc, _ = qwen3.forward_cached(
                params, cfg, tokens, pos, cache, cache.length,
                real_end=cache.length + real_len,
            )
            cache = dataclasses.replace(nc, length=cache.length + real_len)
            last = logits[jnp.arange(b), real_len - 1]
            return last, cache

        @partial(jax.jit, donate_argnames=("cache",))
        def _decode(params, tok, cache: KVCache, key):
            pos = jnp.broadcast_to(cache.length, (tok.shape[0], 1))
            logits, nc, _ = qwen3.forward_cached(
                params, cfg, tok, pos, cache, cache.length,
                real_end=cache.length + 1,
            )
            cache = dataclasses.replace(nc, length=cache.length + 1)
            next_tok = samplib.sample(
                logits[:, 0],
                key,
                self.sampling.temperature,
                self.sampling.top_k,
                self.sampling.top_p,
                self.sampling.min_p,
            )
            return next_tok, cache

        @partial(jax.jit, donate_argnames=("cache",), static_argnames=("top_n",))
        def _decode_lp(params, tok, cache: KVCache, key, top_n: int):
            # logprob-reporting decode: samples IDENTICALLY to _decode (same
            # key, same warper chain) and additionally returns the emitted
            # token's model log-probability + top-N alternatives, computed
            # on device (no [B, V] host transfer per step)
            pos = jnp.broadcast_to(cache.length, (tok.shape[0], 1))
            logits, nc, _ = qwen3.forward_cached(
                params, cfg, tok, pos, cache, cache.length,
                real_end=cache.length + 1,
            )
            cache = dataclasses.replace(nc, length=cache.length + 1)
            row = logits[:, 0]
            next_tok = samplib.sample(
                row, key,
                self.sampling.temperature, self.sampling.top_k,
                self.sampling.top_p, self.sampling.min_p,
            )
            lp, top_ids, top_lps = samplib.logprob_topn(row, next_tok, top_n)
            return next_tok, cache, lp, top_ids, top_lps

        @partial(
            jax.jit, donate_argnames=("cache",),
            static_argnames=("s", "top_n", "want_lp"),
        )
        def _decode_chunk(params, tok, cache: KVCache, key, s: int,
                          top_n: int = 0, want_lp: bool = False):
            """`s` fused decode steps in ONE dispatch (the solo-engine
            analogue of BatchedEngine.decode_chunk): the in-graph key chain
            splits exactly like the host loop, so tokens are bit-identical
            to `s` calls of _decode. Returns (seq [s, B], cache, key',
            lps [s, B], top_ids [s, B, n], top_lps [s, B, n])."""

            def body(carry, _):
                tok, cache, key = carry
                key, sub = jax.random.split(key)
                pos = jnp.broadcast_to(cache.length, (tok.shape[0], 1))
                logits, nc, _ = qwen3.forward_cached(
                    params, cfg, tok, pos, cache, cache.length,
                    real_end=cache.length + 1,
                )
                cache = dataclasses.replace(nc, length=cache.length + 1)
                row = logits[:, 0]
                ntok = samplib.sample(
                    row, sub,
                    self.sampling.temperature, self.sampling.top_k,
                    self.sampling.top_p, self.sampling.min_p,
                )
                b = row.shape[0]
                lp, ti, tl = (
                    samplib.logprob_topn(row, ntok, top_n) if want_lp
                    else (jnp.zeros((b,), jnp.float32),
                          jnp.zeros((b, 0), jnp.int32),
                          jnp.zeros((b, 0), jnp.float32))
                )
                return (ntok[:, None], cache, key), (ntok, lp, ti, tl)

            (tok, cache, key), (seq, lps, tis, tls) = jax.lax.scan(
                body, (tok, cache, key), None, length=s
            )
            return seq, cache, key, lps, tis, tls

        @partial(jax.jit, static_argnames=("max_len",))
        def _run_scan(params, tokens, prompt_len, step_keys, eos, max_len):
            # jit caches by (token shape, steps via step_keys shape, max_len)
            # — repeated benchmark calls with the same shapes reuse the
            # compiled executable.
            b = tokens.shape[0]
            logits, c = _prefill(
                params, tokens, prompt_len,
                KVCache.create(cfg, cfg.num_layers, b, max_len, ring=self.ring_kv),
            )
            tok = samplib.sample(
                logits, step_keys[0],
                self.sampling.temperature, self.sampling.top_k,
                self.sampling.top_p, self.sampling.min_p,
            )
            done = tok == eos

            def body(carry, step_key):
                tok, c, done = carry
                ntok, c = _decode(params, tok[:, None], c, step_key)
                ntok = jnp.where(done, tok, ntok)
                done = done | (ntok == eos)
                return (ntok, c, done), ntok

            (_, _, _), toks = jax.lax.scan(body, (tok, c, done), step_keys[1:])
            return jnp.concatenate([tok[:, None], toks.T], axis=1)

        self._prefill = _prefill
        self._prefill_at = _prefill_at
        self._decode = _decode
        self._decode_lp = _decode_lp
        self._decode_chunk = _decode_chunk
        self._run_scan = _run_scan
        # prefix cache: pinned prompt prefix -> (KV snapshot, last logits).
        # The serving-path analogue is session forking (runtime.executor
        # fork_session); here the snapshot lives in this process.
        self._pins: "OrderedDict[Tuple[int, ...], Tuple[KVCache, jax.Array]]" = (
            OrderedDict()
        )
        # LRU cap on pinned prefix snapshots — a constructor parameter
        # (CLI: tools/generate --max-pins) because each pin holds a whole
        # KV snapshot: prefix-cache pressure is a capacity decision, not a
        # constant
        if max_pins < 1:
            raise ValueError(f"max_pins must be >= 1, got {max_pins}")
        self.max_pins = max_pins

    @property
    def pins_resident(self) -> int:
        """Pinned prefix snapshots currently held — exported as the
        `pins.resident` gauge wherever an Engine serves behind /metrics."""
        return len(self._pins)

    def new_cache(self, batch: int, max_len: Optional[int] = None) -> KVCache:
        return KVCache.create(
            self.cfg, self.cfg.num_layers, batch, max_len or self.max_len,
            ring=self.ring_kv,
        )

    # -- prefix caching ------------------------------------------------------

    def pin_prefix(self, prefix_ids: Sequence[int]) -> None:
        """Prefill `prefix_ids` once and keep the KV snapshot; later
        `generate()` calls whose prompt starts with these ids reuse it
        instead of recomputing the prefix (the classic shared-system-prompt
        serving win). Snapshots are LRU-capped at `max_pins`."""
        ids = prefixlib.normalize_ids(prefix_ids)
        if ids in self._pins:
            self._pins.move_to_end(ids)
            return
        cache = KVCache.create(
            self.cfg, self.cfg.num_layers, 1, bucket_len(len(ids)),
            ring=self.ring_kv,
        )
        logits, cache = self.prefill(list(ids), cache)
        self._pins[ids] = (cache, logits)
        while len(self._pins) > self.max_pins:
            self._pins.popitem(last=False)

    def unpin_prefix(self, prefix_ids: Sequence[int]) -> None:
        self._pins.pop(tuple(int(t) for t in prefix_ids), None)

    def _longest_pin(self, prompt_ids: Sequence[int]):
        return prefixlib.longest_prefix_match(self._pins, prompt_ids)

    def _cache_from_pin(self, pinned: KVCache) -> KVCache:
        """Session cache seeded from a pinned snapshot. EVERY leaf a fresh
        buffer (rings and length included): the decode/prefill jits donate
        their cache argument, and any leaf shared with the pin would be
        destroyed on first reuse."""
        target = max(self.max_len, pinned.max_len)
        ln = jnp.copy(pinned.length)
        kl = None if pinned.k_loc is None else jnp.copy(pinned.k_loc)
        vl = None if pinned.v_loc is None else jnp.copy(pinned.v_loc)
        if pinned.max_len < target:
            g = grow(pinned, target)  # pad writes into fresh k/v buffers
            return KVCache(k=g.k, v=g.v, length=ln, k_loc=kl, v_loc=vl)
        return KVCache(
            k=jnp.copy(pinned.k), v=jnp.copy(pinned.v), length=ln,
            k_loc=kl, v_loc=vl,
        )

    def prefill(self, prompt_ids: Sequence[int], cache: KVCache) -> Tuple[jax.Array, KVCache]:
        """Pad to bucket, run prefill; returns (last-token logits [B,V], cache)."""
        n = len(prompt_ids)
        cache.ensure_room(n)
        b = min(bucket_len(n), cache.max_len)
        padded = list(prompt_ids) + [0] * (b - n)
        tokens = jnp.asarray([padded], dtype=jnp.int32)
        return self._prefill(self.params, tokens, jnp.int32(n), cache)

    def generate(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: Optional[int] = None,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        logprob_sink: Optional[List[float]] = None,
        top_n: int = 0,
        top_sink: Optional[List[Tuple[List[int], List[float]]]] = None,
        chunk: int = 1,
    ) -> List[int]:
        """Host-loop generation with EOS stop. Returns new token ids.

        `logprob_sink` (optional list, cleared) collects each emitted
        token's model log-probability (log-softmax of the RAW logits);
        `top_sink` with `top_n > 0` additionally collects the top-N
        (ids, logprobs) alternatives per step — the serving-API logprob
        surface, computed on device. Tokens are bit-identical with or
        without the sinks (same sampler, same key schedule).

        `chunk` > 1 fuses up to that many decode steps per dispatch (one
        compiled scan instead of N host round trips — the solo analogue of
        BatchedEngine's fused decode; removes the per-step host dispatch
        and sync). Tokens are bit-identical to chunk=1: the
        in-graph key chain equals the host loop's, and an EOS mid-chunk
        just discards the chunk's tail (bounded waste, like the batched
        engine)."""
        if len(prompt_ids) == 0:
            raise ValueError("prompt_ids must be non-empty")
        steps = self.sampling.max_new_tokens if max_new_tokens is None else max_new_tokens
        if steps <= 0:
            return []
        pin = self._longest_pin(prompt_ids)
        if pin is not None:
            pcache, plogits = self._pins[pin]
            self._pins.move_to_end(pin)
            cache = self._cache_from_pin(pcache)
            rest = list(prompt_ids[len(pin):])
            if rest:
                cache.ensure_room(len(rest))
                b = min(bucket_len(len(rest)), cache.max_len - len(pin))
                tokens = jnp.asarray([rest + [0] * (b - len(rest))], jnp.int32)
                logits, cache = self._prefill_at(
                    self.params, tokens, jnp.int32(len(pin)),
                    jnp.int32(len(rest)), cache,
                )
            else:
                logits = plogits
        else:
            cache = self.new_cache(batch=1)
            logits, cache = self.prefill(prompt_ids, cache)
        want_lp = logprob_sink is not None or top_sink is not None
        if logprob_sink is not None:
            logprob_sink.clear()
        if top_sink is not None:
            top_sink.clear()

        def append(lp, ti, tl):
            # single sink-append path for the prefill and decode steps
            if logprob_sink is not None:
                logprob_sink.append(float(lp[0]))
            if top_sink is not None:
                top_sink.append(
                    (np.asarray(ti[0]).tolist(), np.asarray(tl[0]).tolist())
                )

        def record(row_logits, tok_arr):
            # host-side for the prefill step (its [B, V] logits are already
            # on the host path); decode steps use the device-side jit
            append(*samplib.logprob_topn(
                jnp.asarray(row_logits), jnp.asarray(tok_arr), top_n
            ))

        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tok = samplib.sample(
            logits, sub, self.sampling.temperature, self.sampling.top_k,
            self.sampling.top_p, self.sampling.min_p,
        )
        if want_lp:
            record(logits, tok)
        out = [int(tok[0])]
        if eos_token_id is not None and out[-1] == eos_token_id:
            return out
        while len(out) < steps:
            room = self.max_len - int(cache.length)
            s = min(chunk, steps - len(out), max(room, 1))
            if s > 1:
                s = 1 << (s.bit_length() - 1)  # pow2: bounded compile set
            if s > 1:
                # no ensure_room: s <= room by construction above, and the
                # check would cost a blocking device read per chunk — the
                # RTT this path exists to amortize
                seq, cache, key, lps_a, tis_a, tls_a = self._decode_chunk(
                    self.params, tok[:, None], cache, key, s, top_n, want_lp,
                )
                # ONE transfer for everything the host loop reads — a
                # per-token fetch would reintroduce the RTTs the chunk
                # exists to amortize
                seq_np, lps_a, tis_a, tls_a = jax.device_get(
                    (seq, lps_a, tis_a, tls_a)
                )
                done = False
                for j in range(s):
                    t = int(seq_np[j, 0])
                    out.append(t)
                    if want_lp:
                        append(lps_a[j], tis_a[j], tls_a[j])
                    if (eos_token_id is not None and t == eos_token_id) or (
                        len(out) >= steps
                    ):
                        done = True
                        break
                if done:
                    break
                tok = jnp.asarray(seq_np[-1])
                continue
            cache.ensure_room(1)
            key, sub = jax.random.split(key)
            if want_lp:
                tok, cache, lp, ti, tl = self._decode_lp(
                    self.params, tok[:, None], cache, sub, top_n
                )
                append(lp, ti, tl)
            else:
                tok, cache = self._decode(self.params, tok[:, None], cache, sub)
            t = int(tok[0])
            out.append(t)
            if eos_token_id is not None and t == eos_token_id:
                break
        return out

    def generate_scan(
        self,
        prompt_tokens: jax.Array,  # [B, S] already padded/bucketed
        prompt_len: int,
        steps: int,
        seed: int = 0,
        eos_token_id: Optional[int] = None,
    ) -> jax.Array:
        """Fully-jitted fixed-length generation (decode loop as lax.scan).

        One XLA dispatch for the whole generation — the benchmark path.
        After EOS (if given) a sequence keeps emitting pad-like tokens but is
        marked done; returns [B, steps] generated ids.
        """
        max_len = bucket_len(prompt_tokens.shape[1] + steps)

        # Key schedule identical to the host loop (`generate`): chained
        # key, sub = split(key) per step — so both paths sample the same
        # tokens for the same seed.
        key = jax.random.PRNGKey(seed)
        subs = []
        for _ in range(steps):
            key, sub = jax.random.split(key)
            subs.append(sub)
        step_keys = jnp.stack(subs)

        eos = jnp.int32(-1 if eos_token_id is None else eos_token_id)
        return self._run_scan(
            self.params, prompt_tokens, jnp.int32(prompt_len), step_keys, eos, max_len
        )


def generate_text(
    engine: Engine,
    tokenizer,
    prompt: str,
    max_new_tokens: int = 64,
    seed: int = 0,
    chat: bool = True,
) -> str:
    """Convenience end-to-end text generation (reference client.py:204-287)."""
    if chat:
        ids = tokenizer.apply_chat_template(
            [{"role": "user", "content": prompt}], add_generation_prompt=True
        )
    else:
        ids = tokenizer.encode(prompt)
    out = engine.generate(ids, max_new_tokens, eos_token_id=tokenizer.eos_token_id, seed=seed)
    return tokenizer.decode(out)
