"""Continuous batching: N session lanes decode in ONE jitted step.

Single-sequence decode is HBM-bound on weight reads, so a chip serving
several sessions one-at-a-time (the reference's regime — every request is a
lone pipeline pass, /root/reference/petals/send_message.py:27-49) wastes
almost all of its arithmetic: the same 1.19 GB of weights is re-read per
session per token. Batching the decode step across live sessions reads the
weights ONCE per step for all of them — aggregate tok/s scales nearly
linearly with lanes until the MXU saturates (measured upstream: bs=32 on a
v5e-1 is >10x bs=1 aggregate for Qwen3-0.6B shapes).

Design:
  * one KV cache with batch == lanes; each lane is one session's cache row;
  * PREFILL is per-lane (batch-1 chunked forward writing that lane's cache
    rows via dynamic_update_slice on the batch axis) — ragged prompt
    lengths never pad against each other;
  * DECODE is one fused step over all lanes: forward + sample + EOS mask;
    inactive lanes run but their cache length pins to 0 writes are masked
    by per-lane positions (they compute garbage that is never read — the
    XLA-friendly alternative to dynamic batch shapes);
  * a lane frees on EOS/length and refills from the queue (continuous
    batching a la Orca/vLLM, redesigned for static shapes).

This is the single-chip sibling of parallel.infer.PipelinedEngine (which
spreads ONE model over a pp mesh with microbatch slots); here the model is
whole on one device and the batch axis carries the concurrency.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import ModelConfig, SamplingConfig
from inferd_tpu.core import sampling as samplib
from inferd_tpu.core.cache import BlockPool, KVCache, PagedKVCache
from inferd_tpu.core.cache import lane_slice as _lane_slice
from inferd_tpu.core.cache import lane_write as _lane_write
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.models import qwen3
from inferd_tpu.ops import attention, lora, quant

Params = Any


class LanePrograms(NamedTuple):
    """The jitted programs of a lane engine, by name (lane_programs)."""

    prefill_lane: Any
    decode_all: Any
    decode_scan: Any
    decode_k_serve: Any
    decode_logits: Any
    prefill_lane_logits: Any
    block_step: Any
    decode_logits_paged: Any
    prefill_lane_logits_paged: Any
    copy_blocks: Any
    fork_lane: Any


#: configurations whose programs a process keeps for its next engine. A node
#: builds one engine (one entry); a test worker meets a few tiny presets a
#: file. An engine holds its own references: eviction takes nothing from it.
PROGRAM_SETS = 8


def traced_switches() -> tuple:
    """The process-wide switches the model reads WHEN IT IS TRACED (a kernel
    forced on or off, the quantized product's form), as they stand: a
    program traced under one setting is not the program of another, so they
    are part of lane_programs' key. A node sets them before it builds its
    engine and never again; a bench leg that flips one between two
    executors (bench.py `kernels`) gets two sets."""
    return (attention.FORCE_FLASH, attention.FORCE_PAGED_KERNEL, lora.FORCE_LORA_KERNEL,
            quant.FORCE_QUANT_KERNEL, quant.QDOT_MODE, quant.INT4_MODE)


@lru_cache(maxsize=PROGRAM_SETS)
def lane_programs(cfg: ModelConfig, sc: SamplingConfig, lanes: int,
                  paged: bool, switches: tuple = ()) -> LanePrograms:
    """The programs of BatchedEngine(cfg, lanes=lanes, sampling_cfg=sc),
    dense or `paged`: everything a body reads besides its arguments is one
    of these (`switches`: traced_switches(), read inside the model), so
    every engine of a configuration runs ONE set, compiled once a shape
    (jax.jit caches by the function object). Weights, caches and `max_len`
    arrive as arguments."""
    L = lanes
    B = cfg.block_length
    routes = cfg.is_moe and not paged

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("s", "top_n", "want_lp"))
    def _prefill_lane(params, cache: KVCache, tokens, lane, n, key, s: int,
                      top_n: int = 0, want_lp: bool = False):
        """Chunk-prefill ONE lane: tokens [1, s] (bucketed), write this
        lane's cache rows, return the sampled/greedy next token (+ its
        model logprob and top-N alternatives)."""
        lc = _lane_slice(cache, lane)
        logits, nc, _ = qwen3.forward_cached(
            params, cfg, tokens, None, lc, jnp.int32(0), real_end=n
        )
        cache = _lane_write(cache, lane, nc)
        last = logits[0, n - 1][None]
        if sc.temperature == 0.0:
            tok = jnp.argmax(last, axis=-1)
        else:
            tok = samplib.sample(last, key, sc.temperature, sc.top_k, sc.top_p, sc.min_p)
        tok = tok.astype(jnp.int32)
        # want_lp static: the no-logprob fast path never pays the
        # full-vocab log-softmax (each variant compiles separately)
        lp, ti, tl = (
            samplib.logprob_topn(last, tok, top_n) if want_lp
            else (jnp.zeros((1,), jnp.float32),
                  jnp.zeros((1, 0), jnp.int32), jnp.zeros((1, 0), jnp.float32))
        )
        return cache, tok, lp, ti, tl

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("top_n", "want_lp"))
    def _decode_all(params, cache: KVCache, toks, lengths, active, keys,
                    top_n: int = 0, want_lp: bool = False):
        """One batched decode step over all lanes.

        toks [L]; lengths [L] (per-lane KV fill); active [L] bool.
        Per-lane positions make each lane attend to exactly its own
        prefix; inactive lanes compute at position 0 and are ignored.
        """
        pos = lengths[:, None]  # [L, 1] absolute position per lane
        logits, nc, _ = qwen3.forward_cached(
            params, cfg, toks[:, None], pos, cache, lengths,
            real_end=lengths + 1,
        )
        cache = nc
        last = logits[:, 0]  # [L, V]
        if sc.temperature == 0.0:
            ntok = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            ntok = jax.vmap(
                lambda l, kk: samplib.sample(
                    l[None], kk, sc.temperature, sc.top_k, sc.top_p, sc.min_p
                )[0]
            )(last, keys).astype(jnp.int32)
        # inactive lanes keep their token and write nothing real (their
        # lengths stay 0-advanced host-side; device rows hold garbage)
        ntok = jnp.where(active, ntok, toks)
        lp, ti, tl = (
            samplib.logprob_topn(last, ntok, top_n) if want_lp
            else (jnp.zeros((L,), jnp.float32),
                  jnp.zeros((L, 0), jnp.int32), jnp.zeros((L, 0), jnp.float32))
        )
        return cache, ntok, lp, ti, tl

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("s", "top_n", "want_lp"))
    def _decode_scan(params, cache: KVCache, toks, lengths, active, keys, s: int,
                     top_n: int = 0, want_lp: bool = False):
        """`s` fused decode steps over all lanes in ONE dispatch.

        Serial over tokens by data dependency; per-lane PRNG chains
        split exactly like the per-step path, so the emitted tokens
        are bit-identical to `s` calls of _decode_all. This
        turns s host dispatches and syncs into one —
        the device-rate path for throughput serving and the batched
        bench. The scan body is the SHARED multi-step inner loop
        (models/qwen3.decode_k — one definition for the solo, batched,
        and stage-batch executors); the engine bakes its sampling
        config and runs with no in-graph stop (lanes finish host-side,
        the generate_all contract). Returns
        (cache, seq [s, L], final keys [L, 2], lps, tis, tls)."""
        cache, seq, _n_new, keys, lps, tis, tls = qwen3.decode_k(
            params, cfg, toks, cache, lengths, active, keys, s,
            temperature=sc.temperature, top_k=sc.top_k, top_p=sc.top_p,
            min_p=sc.min_p, top_n=top_n, want_lp=want_lp,
        )
        return cache, seq, keys, lps, tis, tls

    # serving-path K-step fused decode — the shared factory
    # (models/qwen3.make_decode_k_serve) holds the definition and the
    # static-sampling recompile-surface rationale
    _decode_k_serve = qwen3.make_decode_k_serve(cfg)

    @partial(jax.jit, donate_argnames=("cache",), static_argnames=("top_n",))
    def _decode_logits(params, cache: KVCache, toks, lengths, ads=None,
                       ask=None, top_n: int = 0, active=None):
        """One batched decode step: last-token LOGITS [L, V] and, with
        an `ask` (core.sampling.RowAsk: per-lane keys, temperature,
        top-k, top-p, min-p, all traced), every lane's TOKEN chosen
        here, after the head (samplib.choose_rows). The serving path
        hands every step an ask (a lane that asked nothing is a greedy
        row nobody reads), so any mix of sampling configs is this ONE
        program; what a hop is answered with is the caller's choice
        (runtime/batch_executor: the token, or the lane's logits row
        where the hop carried no ask or one the sampler does not
        cover). Lanes not being served this step simply advance nothing
        host-side; their computed rows are discarded by the caller.
        `ads` (multi-tenant registry): the stacked LoRA pools +
        per-lane slot ids — a mixed-adapter window stays ONE dispatch
        (ops/lora pool contract). `active` [L] bool (a model with
        state-space layers hands it): a lane not served this step must
        keep its recurrent state as it is, where a dense lane's garbage
        row beyond the frontier is simply never read.

        Without an ask the third value is the experts each lane chose
        in each sparse layer, [Ls, L, K] int32 (the `moe.*` counters
        of /stats), or None for a model without experts. With one it
        is samplib.pack_rows' int32 [L, W]: token, next key, with
        `top_n` > 0 (static: a width of BLOCK_TOP_WIDTHS) the
        log-probabilities, and those experts: ONE small transfer."""
        pos = lengths[:, None]
        logits, nc, topi = qwen3.forward_cached(
            params, cfg, toks[:, None], pos, cache, lengths,
            real_end=lengths + 1, adapters=ads, write_mask=active,
        )
        last = logits[:, 0]
        chosen = topi[:, :, 0] if routes else None
        if ask is None:
            return nc, last, chosen
        return nc, last, samplib.choose_rows(last, ask, top_n, chosen)

    @partial(jax.jit, donate_argnames=("cache",))
    def _prefill_lane_logits(params, cache: KVCache, tokens, lane, start,
                             n, ads=None):
        """Chunk-ingest [1, S_bucket] tokens into ONE lane at `start`,
        returning last-real-token logits [V] (serving path: supports
        chunked prefill at any start_pos). `ads` carries a single-row
        "ids" for this lane's adapter slot."""
        lc = _lane_slice(cache, lane)
        logits, nc, _ = qwen3.forward_cached(
            params, cfg, tokens, None, lc, start, real_end=start + n,
            adapters=ads,
        )
        return _lane_write(cache, lane, nc), logits[0, n - 1]

    @partial(jax.jit, donate_argnames=("cache",),
             static_argnames=("temperature", "top_k", "top_p", "min_p", "top_n"))
    def _block_step(params, cache: KVCache, toks, known, lengths, live, keys,
                    temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                    min_p: float = 0.0, top_n: int = 0):
        """One block of every lane in ONE dispatch (a model generated by
        blocks, cfg.is_block_diffusion): `cfg.denoising_steps` passes
        over the block's B places, then the commit pass over the known
        block, each a forward_cached of [L, B] rows at `lengths` +
        arange(B). A denoising pass writes its rows' keys and values
        beyond the frontier like any chunk; the commit overwrites them;
        nothing reads beyond a lane's frontier + B.

        toks [L, B] and known [L, B] bool: the places the caller filled
        (a first block opened by prompt tokens); what `toks` holds
        elsewhere is not read. A pass picks a token at every place
        still masked (argmax at temperature 0, else core.sampling.sample
        under the lane's key) and makes B / steps of them known: the
        leftmost (`remask` "sequential") or those whose token is most
        probable ("low_confidence"). A pass with nothing left to make
        known changes nothing. With `top_n` > 0 each place's
        log-probability and top-n are those of the pass that made it
        known. Lanes not `live` compute and are discarded by the caller;
        theirs are written at most B short of the buffer's end (a full
        lane's frontier would clamp the write back over rows of another
        position).

        Returns (cache, samplib.pack_bits of (tokens [L, B],
        made-known-at pass [L, B] (-1: by the caller), keys' [L, 2],
        log-probabilities [L, B], top ids [L, B, n], top
        log-probabilities [L, B, n]): ONE array for one transfer; the
        experts every row chose in every pass [passes, Ls, L, B, K])."""
        steps = cfg.denoising_steps
        per = B // steps
        lengths = jnp.where(live, lengths, jnp.minimum(lengths, cache.max_len - B))
        pos = lengths[:, None] + jnp.arange(B)[None, :]
        end = lengths + B
        x = jnp.where(known, toks, jnp.int32(cfg.mask_token_id))
        at = jnp.where(known, -1, steps).astype(jnp.int32)
        lps = jnp.zeros((L, B), jnp.float32)
        tis = jnp.zeros((L, B, top_n), jnp.int32)
        tls = jnp.zeros((L, B, top_n), jnp.float32)
        chosen = []
        for step in range(steps):
            with jax.named_scope("block_denoise"):
                logits, cache, topi = qwen3.forward_cached(
                    params, cfg, x, pos, cache, lengths, real_end=end
                )
            chosen.append(topi)
            with jax.named_scope("block_select"):
                rows = logits.reshape(L * B, -1)
                if temperature == 0.0:
                    tok = jnp.argmax(rows, axis=-1)
                else:
                    pairs = jax.vmap(jax.random.split)(keys)  # [L, 2, 2]
                    keys = pairs[:, 0]
                    subs = jax.vmap(lambda k: jax.random.split(k, B))(pairs[:, 1])
                    tok = jax.vmap(
                        lambda l, k: samplib.sample(
                            l[None], k, temperature, top_k, top_p, min_p)[0]
                    )(rows, subs.reshape(L * B, 2))
                tok = tok.astype(jnp.int32)
                confident = cfg.remask == "low_confidence"
                if top_n or confident:
                    lp, ti, tl = samplib.logprob_topn(rows, tok, top_n)
                    lp = lp.reshape(L, B)
                # the B / steps best of the places still masked: the
                # leftmost, or the surest of their token
                score = lp if confident else -jnp.arange(B, dtype=jnp.float32)[None, :]
                score = jnp.where(known, -jnp.inf, jnp.broadcast_to(score, (L, B)))
                _, idx = jax.lax.top_k(score, per)
                newly = jnp.zeros((L, B), bool).at[jnp.arange(L)[:, None], idx].set(True)
                newly &= ~known
                x = jnp.where(newly, tok.reshape(L, B), x)
                at = jnp.where(newly, step, at)
                if top_n:
                    lps = jnp.where(newly, lp, lps)
                    tis = jnp.where(newly[..., None], ti.reshape(L, B, top_n), tis)
                    tls = jnp.where(newly[..., None], tl.reshape(L, B, top_n), tls)
                known = known | newly
        with jax.named_scope("block_commit"):
            _, cache, topi = qwen3.forward_cached(
                params, cfg, x, pos, cache, lengths, real_end=end
            )
        chosen.append(topi)
        return (cache, samplib.pack_bits(x, at, keys, lps, tis, tls),
                jnp.stack(chosen) if routes else None)

    @partial(jax.jit, donate_argnames=("cache",), static_argnames=("m",))
    def _fork_lane(cache: KVCache, src, dst, m: int):
        """Copy the first m KV slots of lane `src` into lane `dst`
        (prefix-cache fork). Donated + dynamic_update_slice so XLA
        updates the cache in place — never a whole-cache copy."""
        if cache.s is not None:
            raise ValueError(
                f"{cfg.name}: a recurrent state is the state after ALL of the "
                "parent's tokens; no prefix of it can seed another lane"
            )
        ks = jax.lax.dynamic_slice_in_dim(cache.k, src, 1, axis=1)[:, :, :m]
        vs = jax.lax.dynamic_slice_in_dim(cache.v, src, 1, axis=1)[:, :, :m]
        zero = jnp.int32(0)
        at_dst = (zero, dst) + (zero,) * (cache.k.ndim - 2)  # a latent cache has no head axis
        nk = jax.lax.dynamic_update_slice(cache.k, ks, at_dst)
        nv = jax.lax.dynamic_update_slice(cache.v, vs, at_dst)
        kl, vl = cache.k_loc, cache.v_loc
        if kl is not None:
            # rings are fixed-size: the child takes the parent's WHOLE
            # ring (the caller enforces the fork-margin alias guard)
            rs = jax.lax.dynamic_slice_in_dim(kl, src, 1, axis=1)
            vs_l = jax.lax.dynamic_slice_in_dim(vl, src, 1, axis=1)
            kl = jax.lax.dynamic_update_slice(
                kl, rs, (zero, dst, zero, zero, zero)
            )
            vl = jax.lax.dynamic_update_slice(
                vl, vs_l, (zero, dst, zero, zero, zero)
            )
        return KVCache(k=nk, v=nv, length=cache.length, k_loc=kl, v_loc=vl)

    @partial(jax.jit, donate_argnames=("cache",), static_argnames=("top_n",))
    def _decode_logits_paged(params, cache: PagedKVCache, toks, lengths,
                             active, ads=None, ask=None, top_n: int = 0):
        """Paged sibling of _decode_logits: reads/writes go through
        the block table, and lanes NOT in this window (`active`
        False) drop their garbage writes — pool blocks are shared
        property, unlike the dense layout's lane-private rows. With
        an `ask` the third value is the packed rows (no experts: the
        paged program returns no routing)."""
        pos = lengths[:, None]
        logits, nc, _ = qwen3.forward_cached(
            params, cfg, toks[:, None], pos, cache, lengths,
            real_end=lengths + 1, write_mask=active, adapters=ads,
        )
        last = logits[:, 0]
        if ask is None:
            return nc, last
        return nc, last, samplib.choose_rows(last, ask, top_n)

    @partial(jax.jit, donate_argnames=("cache",))
    def _prefill_lane_logits_paged(params, cache: PagedKVCache, tokens,
                                   table_row, start, n, ads=None):
        """Chunk-ingest [1, S_bucket] tokens through ONE lane's block-
        table row; the pools are global, so no lane_slice/lane_write."""
        lc = PagedKVCache(
            k=cache.k, v=cache.v, table=table_row, length=cache.length
        )
        logits, nc, _ = qwen3.forward_cached(
            params, cfg, tokens, None, lc, start, real_end=start + n,
            adapters=ads,
        )
        return (
            PagedKVCache(k=nc.k, v=nc.v, table=cache.table,
                         length=cache.length),
            logits[0, n - 1],
        )

    @partial(jax.jit, donate_argnames=("cache",))
    def _copy_blocks(cache: PagedKVCache, src, dst):
        """CoW block copies (src/dst [n] int32) in place under
        donation (core.cache.paged_copy_blocks)."""
        return dataclasses.replace(
            cache,
            k=cache.k.at[:, dst].set(cache.k[:, src]),
            v=cache.v.at[:, dst].set(cache.v[:, src]),
        )

    return LanePrograms(
        prefill_lane=_prefill_lane,
        decode_all=_decode_all,
        decode_scan=_decode_scan,
        decode_k_serve=_decode_k_serve,
        decode_logits=_decode_logits,
        prefill_lane_logits=_prefill_lane_logits,
        block_step=_block_step,
        decode_logits_paged=_decode_logits_paged,
        prefill_lane_logits_paged=_prefill_lane_logits_paged,
        copy_blocks=_copy_blocks,
        fork_lane=_fork_lane,
    )


class BatchedEngine:
    """N-lane continuous-batching engine on one device."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        lanes: int = 8,
        max_len: int = 2048,
        sampling_cfg: Optional[SamplingConfig] = None,
        block_size: int = 0,
        kv_blocks: int = 0,
    ):
        self.cfg = cfg
        # one host->device transfer, here: a stage checkpoint loads as numpy
        # (parallel.stages.load_stage_checkpoint), and numpy leaves handed to
        # a jit are copied to the device again on EVERY call — the whole
        # model per token on a chip. Arrays already on a device stay put.
        self.params = jax.device_put(params)
        self.lanes = lanes
        self.max_len = max_len
        self.sampling = sampling_cfg or SamplingConfig()
        # paged KV (block_size > 0): lanes map to refcounted block chains
        # of ONE pool instead of dense [lanes, max_len] rows
        # (core.cache.BlockPool) — the SERVING jits below grow paged
        # siblings; the library loop (admit/decode/generate_all) stays on
        # the dense layout (runtime/batch_executor is the paged consumer).
        self.pool: Optional[BlockPool] = None
        if block_size > 0:
            self.pool = BlockPool(
                cfg, cfg.num_layers, lanes, max_len,
                block_size=block_size, num_blocks=kv_blocks or None,
            )
            self.cache = self.pool.cache
        else:
            # ring-split layout for sliding-window models: each lane's
            # sliding layers live in O(window) rings (core/cache.py). Lane
            # REUSE over a stale ring is safe without zeroing: slot
            # attribution is derived from the lane's length, so
            # never-written-this-session slots are either attributed
            # negative positions (masked) or overwritten by the session's
            # own next write before their position can enter any window.
            self.cache = KVCache.create(cfg, cfg.num_layers, lanes, max_len)
        # host mirrors (device sync per step would stall the pipeline)
        self.lengths = [0] * lanes
        self.free: List[int] = list(range(lanes))

        # the dense-lane decode program also returns the experts each lane
        # chose (the third value of models/qwen3.forward_cached)
        self.routes = cfg.is_moe and block_size == 0
        # the programs are a function of the configuration, made once for
        # it (lane_programs); an attribute set on an instance shadows its
        # own and touches nobody else's
        programs = lane_programs(cfg, self.sampling, lanes, block_size > 0, traced_switches())
        self._prefill_lane = programs.prefill_lane
        self._decode_all = programs.decode_all
        self._decode_scan = programs.decode_scan
        self._decode_k_serve = programs.decode_k_serve
        self._decode_logits = programs.decode_logits
        self._prefill_lane_logits = programs.prefill_lane_logits
        self._block_step = programs.block_step
        self._decode_logits_paged = programs.decode_logits_paged
        self._prefill_lane_logits_paged = programs.prefill_lane_logits_paged
        self._copy_blocks = programs.copy_blocks
        self._fork_lane = programs.fork_lane

    def fork_lane(self, src: int, dst: int, m: int) -> None:
        """Seed lane `dst` with the first `m` KV slots of lane `src`.
        Caller manages lane bookkeeping (lengths/free) and device locking."""
        self.cache = self._fork_lane(
            self.cache, jnp.int32(src), jnp.int32(dst), m
        )

    # -- lane management -----------------------------------------------------

    def admit(self, prompt_ids: Sequence[int], key=None, top_n: int = 0,
              want_lp: bool = False):
        """Claim a lane and prefill it; returns (lane, first_token), or
        (lane, first_token, lp, (top_ids, top_lps)) when want_lp."""
        if self.pool is not None:
            raise RuntimeError(
                "paged BatchedEngine serves through the executor surface "
                "(runtime/batch_executor) — the library loop is dense-only"
            )
        if not self.free:
            raise RuntimeError("no free lanes")
        if len(prompt_ids) + 1 > self.max_len:
            raise BufferError(f"prompt of {len(prompt_ids)} exceeds max_len")
        lane = self.free.pop()
        n = len(prompt_ids)
        b = min(bucket_len(n), self.max_len)
        toks = jnp.asarray([list(prompt_ids) + [0] * (b - n)], jnp.int32)
        key = key if key is not None else jax.random.PRNGKey(0)
        self.cache, tok, lp, ti, tl = self._prefill_lane(
            self.params, self.cache, toks, jnp.int32(lane), jnp.int32(n), key, b,
            top_n, want_lp,
        )
        self.lengths[lane] = n
        if want_lp:
            return (
                lane, int(tok[0]), float(lp[0]),
                (np.asarray(ti[0]).tolist(), np.asarray(tl[0]).tolist()),
            )
        return lane, int(tok[0])

    def release(self, lane: int) -> None:
        self.lengths[lane] = 0
        self.free.append(lane)

    def decode(self, toks: Sequence[int], active: Sequence[bool], keys=None):
        """One step for every lane; returns next tokens [lanes] (np).

        Callers advance self.lengths for lanes they treat as active."""
        if keys is None:
            keys = jnp.zeros((self.lanes, 2), jnp.uint32)
        self.cache, ntok, _lp, _ti, _tl = self._decode_all(
            self.params,
            self.cache,
            jnp.asarray(toks, jnp.int32),
            jnp.asarray(self.lengths, jnp.int32),
            jnp.asarray(active, bool),
            keys,
        )
        for i, a in enumerate(active):
            if a:
                self.lengths[i] += 1
        return np.asarray(ntok)

    def decode_chunk(self, toks: Sequence[int], active: Sequence[bool], steps: int,
                     keys=None, top_n: int = 0, want_lp: bool = False):
        """`steps` fused decode steps for every active lane in one dispatch.

        Returns (tokens [steps, lanes] np, advanced per-lane keys [lanes, 2]);
        with want_lp additionally (lps [steps, lanes], top_ids
        [steps, lanes, top_n], top_lps [steps, lanes, top_n]).
        Caller guarantees headroom: max active lane length + steps <= max_len
        (every active lane's KV writes must stay in bounds)."""
        if keys is None:
            keys = jnp.zeros((self.lanes, 2), jnp.uint32)
        self.cache, seq, nkeys, lps, tis, tls = self._decode_scan(
            self.params,
            self.cache,
            jnp.asarray(toks, jnp.int32),
            jnp.asarray(self.lengths, jnp.int32),
            jnp.asarray(active, bool),
            keys,
            steps,
            top_n,
            want_lp,
        )
        for i, a in enumerate(active):
            if a:
                self.lengths[i] += steps
        if want_lp:
            return (
                np.asarray(seq), nkeys,
                np.asarray(lps), np.asarray(tis), np.asarray(tls),
            )
        return np.asarray(seq), nkeys

    # -- convenience: generate a whole workload with refill -------------------

    def generate_all(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        chunk: int = 1,
        logprob_sink: Optional[List[List[float]]] = None,
        top_n: int = 0,
        top_sink: Optional[List] = None,
    ) -> List[List[int]]:
        """Run a queue of prompts to completion with continuous lane refill.

        Per-sequence PRNG chains match core.generate.Engine exactly (chained
        split per emitted token, seeded seed+index), so each sequence's
        tokens equal a solo Engine run with the same seed.

        chunk > 1 fuses up to `chunk` decode steps per dispatch (one compiled
        scan instead of `chunk` host round trips); tokens are bit-identical
        to chunk=1 — a lane finishing mid-chunk (eos OR exhausted budget)
        just wastes the rest of its chunk (bounded by `chunk`), truncated
        host-side; lane refill lands on chunk boundaries. Chunk size is
        bounded by KV headroom and the LONGEST remaining budget, so one
        nearly-done lane never collapses the others to tiny chunks; only a
        KV-headroom tail (< chunk) drops to per-step.

        `logprob_sink` (optional list, cleared) is filled with one
        PER-SEQUENCE list of model log-probabilities aligned with the
        returned ids; `top_sink` with `top_n > 0` likewise with per-step
        (top_ids, top_lps) pairs — same semantics as the solo engine,
        computed on device. Tokens are bit-identical with or without."""
        want_lp = logprob_sink is not None or top_sink is not None
        results: List[Optional[List[int]]] = [None] * len(prompts)
        lp_results: List[Optional[List[float]]] = [None] * len(prompts)
        top_results: List[Optional[List]] = [None] * len(prompts)
        queue = list(range(len(prompts)))
        lane_seq: Dict[int, int] = {}
        lane_key: Dict[int, jax.Array] = {}
        out: Dict[int, List[int]] = {}
        lp_out: Dict[int, List[float]] = {}
        top_out: Dict[int, List] = {}

        def finish(lane, cap: Optional[int] = None):
            i = lane_seq.pop(lane)
            results[i] = out.pop(lane) if cap is None else out.pop(lane)[:cap]
            if want_lp:
                lp_results[i] = lp_out.pop(lane)
                top_results[i] = top_out.pop(lane)
                if cap is not None:
                    lp_results[i] = lp_results[i][:cap]
                    top_results[i] = top_results[i][:cap]
            del lane_key[lane]
            self.release(lane)

        def admit_next():
            while queue and self.free:
                i = queue.pop(0)
                key = jax.random.PRNGKey(seed + i)
                key, sub = jax.random.split(key)
                if want_lp:
                    lane, tok, lp, top = self.admit(
                        prompts[i], sub, top_n=top_n, want_lp=True
                    )
                    lp_out[lane] = [lp]
                    top_out[lane] = [top]
                else:
                    lane, tok = self.admit(prompts[i], sub)
                lane_seq[lane] = i
                lane_key[lane] = key
                out[lane] = [tok]
                if (eos_token_id is not None and tok == eos_token_id) or (
                    max_new_tokens <= 1
                ):
                    finish(lane, cap=max_new_tokens)

        admit_next()
        while lane_seq:
            s = 1
            if chunk > 1:
                # fused chunk size: bounded by KV headroom (head - 1 so the
                # per-token max_len release below can only land on a chunk
                # boundary) and the LONGEST remaining budget — a lane that
                # exhausts its budget mid-chunk is truncated host-side and
                # released at the boundary (the same bounded-waste class as
                # an eos tail), so one nearly-finished lane does not
                # collapse every other lane to tiny chunks
                rem = max(max_new_tokens - len(out[l]) for l in lane_seq)
                head = self.max_len - max(self.lengths[l] for l in lane_seq)
                s = max(1, min(chunk, rem, head - 1))
                s = 1 << (s.bit_length() - 1)  # pow2: bounded compile set
            # one path for any s: for s == 1 the in-graph key split equals
            # the host-side split (and greedy never reads keys), so
            # decode_chunk(s=1) is bit-identical to the old per-step decode
            toks = [0] * self.lanes
            active = [False] * self.lanes
            keys = [jnp.zeros((2,), jnp.uint32)] * self.lanes
            for lane in lane_seq:
                toks[lane] = out[lane][-1]
                active[lane] = True
                keys[lane] = lane_key[lane]
            if want_lp:
                seq, nkeys, lps, tis, tls = self.decode_chunk(
                    toks, active, s, jnp.stack(keys), top_n=top_n, want_lp=True
                )
            else:
                seq, nkeys = self.decode_chunk(toks, active, s, jnp.stack(keys))
            for lane in list(lane_seq):
                lane_key[lane] = nkeys[lane]
                done = False
                for j in range(s):
                    t = int(seq[j, lane])
                    out[lane].append(t)
                    if want_lp:
                        lp_out[lane].append(float(lps[j, lane]))
                        top_out[lane].append(
                            (tis[j, lane].tolist(), tls[j, lane].tolist())
                        )
                    if len(out[lane]) >= max_new_tokens or (
                        eos_token_id is not None and t == eos_token_id
                    ):
                        done = True
                        break
                done = done or self.lengths[lane] + 1 >= self.max_len
                if done:
                    finish(lane)
            admit_next()
        if logprob_sink is not None:
            logprob_sink.clear()
            logprob_sink.extend(r if r is not None else [] for r in lp_results)
        if top_sink is not None:
            top_sink.clear()
            top_sink.extend(r if r is not None else [] for r in top_results)
        return [r if r is not None else [] for r in results]
