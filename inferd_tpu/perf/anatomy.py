"""Step-anatomy profiler: where does a decode step's time go?

Round 5's first on-chip battery showed bs=1 decode at 11.4% of the HBM
roofline (~12.8 ms/step where ~1.5 ms is the weight-read floor) and nobody
could say where the other ~11 ms went (VERDICT r05 weak #1). This module
decomposes one decode step into separately-jitted sub-graphs built from
the SAME model components the real step runs (models/qwen3 blocks, the
production sampler, the production cache write) and times each:

    embed      token-id gather from the embedding table
    attention  L layers: input_norm + qkv projections + rope + attention
               over the populated cache + o_proj (+ residual)
    mlp        L layers: pre-norm + SwiGLU / MoE block (+ residual)
    lm_head    final norm + unembed matmul (quantized shadow when present)
    sampling   the temperature/top-k/top-p sampler over a [B, V] row
    kv_write   per-layer one-slot dynamic_update_slice into the KV buffers

Timing discipline: each phase runs `short`- and `long`-iteration
`lax.scan` loops whose bodies depend on the carry (LICM cannot hoist
them), timed in INTERLEAVED PAIRS with full materialization per window
(utils/profiling.interleaved_pair_times + paired_delta_stats) — the same
discipline the decode bench uses, so fixed dispatch overhead cancels and
congestion can't invert the differencing. Each phase also gets its
roofline attribution (phase bytes from perf/roofline over the chip's
bandwidth), so the output directly names which phase is furthest from
what the hardware allows.

CPU-runnable for tests (tiny presets, seconds); on TPU via
`python -m inferd_tpu.perf anatomy` (a bench_battery leg).

The phase sub-graphs are jitted SEPARATELY, so their sum differs from the
fused whole step by whatever fusion across phase boundaries buys (plus
rope/norm bits counted in more than one place); the whole step is timed
too and the residual is reported as `unattributed_ms` rather than
silently spread across phases.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import ModelConfig, SamplingConfig
from inferd_tpu.core.cache import KVCache, from_wire
from inferd_tpu.core import sampling as samplib
from inferd_tpu.models import qwen3
from inferd_tpu.ops.quant import apply_quant_mode, qdot
from inferd_tpu.perf import roofline as rl
from inferd_tpu.utils.profiling import (
    interleaved_pair_times,
    paired_delta_stats,
)

PHASES = (
    "embed", "attention", "mlp", "lm_head", "sampling", "kv_write",
    # dispatch is HOST overhead, not device compute: per-token ms of the
    # K=1 serving pattern (one jit dispatch + one host sync per token)
    # MINUS the same step inside a scan — exactly what the K-step fused
    # decode loop (models/qwen3.decode_k) amortizes. Excluded from
    # phase_sum/unattributed (those reconcile the fused device step).
    "dispatch",
)


def _scan_loops(body, operand, short: int, long_: int):
    """Warmed (compiled) short/long scan loops over `body` (carry ->
    carry). Split from the measurement so a live tick loop can hold the
    compiled callables across ticks — jax.jit keys on the function
    object, so rebuilding these per call re-traces and recompiles."""

    def loop(n):
        @jax.jit
        def run(op):
            out, _ = jax.lax.scan(lambda c, _: (body(c), None), op, None, length=n)
            return out

        return run

    run_s, run_l = loop(short), loop(long_)
    np.asarray(jax.tree.leaves(run_s(operand))[0])  # compile + warm
    np.asarray(jax.tree.leaves(run_l(operand))[0])
    return run_s, run_l


def _measure_loops(run_s, run_l, operand, short: int, long_: int,
                   pairs: int):
    """Per-iteration ms from pre-compiled loops, interleaved-paired with
    full materialization per window. Returns (ms, n_valid, spread_pt)."""

    def timer(fn):
        def t() -> float:
            t0 = time.perf_counter()
            np.asarray(jax.tree.leaves(fn(operand))[0])  # materializing the result IS the timed quantity
            return time.perf_counter() - t0

        return t

    ts, tl = interleaved_pair_times(timer(run_s), timer(run_l), pairs)
    per_s, n_valid, spread, _ = paired_delta_stats(ts, tl, short, long_)
    return per_s * 1e3, n_valid, spread


def _paired_scan_ms(body, operand, short: int, long_: int, pairs: int):
    """Per-iteration ms of `body` (carry -> carry) with fixed dispatch
    overhead cancelled: short/long scan windows timed in interleaved
    pairs, full materialization per window. Returns (ms, n_valid,
    spread_pt)."""
    run_s, run_l = _scan_loops(body, operand, short, long_)
    return _measure_loops(run_s, run_l, operand, short, long_, pairs)


def _bounded(x: jax.Array) -> jax.Array:
    """Rescale a residual-stream carry so it can't diverge over a long
    scan with random weights (the rescale is O(B*H) — noise next to the
    phase's weight reads)."""
    mag = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return (x.astype(jnp.float32) / (1.0 + mag)).astype(x.dtype)


def _build_suite(
    cfg: ModelConfig,
    params: Optional[Any],
    quant: str,
    ctx: int,
    batch: int,
    short: int,
    long_: int,
    sampling: Optional[SamplingConfig],
    paged_block_size: int,
) -> Dict[str, Any]:
    """Build every phase sub-graph (bodies + operands), the fused
    step, and the roofline byte attribution for ONE target
    configuration. Shared by profile_step (one-shot offline profile)
    and AnatomySession (the live tick's compile-once reuse): the
    bodies close over the SAME tensors, so a session can hold their
    compiled scan loops across ticks without rebuilding anything."""
    sc = sampling or SamplingConfig()
    L = cfg.num_layers
    if params is None:
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        params = apply_quant_mode(
            quant, params, tie_word_embeddings=cfg.tie_word_embeddings
        )
    # checkpoint-loaded executor params are host numpy arrays; the phase
    # bodies index them with TRACED operands (embed's token gather), which
    # numpy rejects — normalize to jax arrays (no-op for live device
    # params, one host->device transfer otherwise)
    params = jax.tree.map(jnp.asarray, params)
    max_len = ctx + long_ + short + 16
    kv_dt = cfg.kv_jnp_dtype
    kvshape = (L, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    kc = (jax.random.normal(jax.random.PRNGKey(1), kvshape, jnp.float32) * 0.3
          ).astype(kv_dt)
    vc = (jax.random.normal(jax.random.PRNGKey(2), kvshape, jnp.float32) * 0.3
          ).astype(kv_dt)
    tok0 = jnp.full((batch, 1), 7, jnp.int32)
    hid0 = jax.random.normal(
        jax.random.PRNGKey(3), (batch, 1, cfg.hidden_size), jnp.float32
    ).astype(cfg.jnp_dtype)
    key0 = jax.random.PRNGKey(0)
    eps, p1 = cfg.rms_norm_eps, cfg.rms_norm_plus_one
    q_positions = jnp.full((batch, 1), ctx, jnp.int32)
    cos, sin = qwen3.rope_cos_sin(
        q_positions, cfg.head_dim, cfg.rope_theta, cfg
    )

    # ---- whole fused step (the thing the phases must add up to) ----------
    def step_body(carry):
        tok, cache, key = carry
        key, sub = jax.random.split(key)
        pos = jnp.broadcast_to(cache.length, (batch, 1))
        logits, nc, _ = qwen3.forward_cached(
            params, cfg, tok, pos, cache, cache.length,
            real_end=cache.length + 1,
        )
        cache = dataclasses.replace(nc, length=cache.length + 1)
        ntok = samplib.sample(
            logits[:, 0], sub, sc.temperature, sc.top_k, sc.top_p, sc.min_p
        )
        return (ntok[:, None], cache, key)

    # the fused step runs the layout serving runs (rows where a head is narrow)
    cache0 = KVCache(k=from_wire(kc, cfg, True), v=from_wire(vc, cfg, True), length=jnp.int32(ctx))

    # ---- embed -----------------------------------------------------------
    def embed_body(tok):
        e = qwen3.embed(params, tok, cfg)
        bump = (e[:, :, 0].astype(jnp.float32) * 1e3).astype(jnp.int32) % 7
        return (tok + 1 + bump) % cfg.vocab_size

    # ---- attention (projections + rope + attend + o_proj, all L layers) --
    # paged mode: per layer, K/V live in a PERMUTED block pool and the
    # attend reads them through the block table via the PRODUCTION paged
    # dispatch (ops.attention.decode_gqa(block_table=)): the Pallas
    # chain-walk kernel when the autotune registry enables it on this
    # chip, the gather_block_kv + XLA path otherwise — so the timed
    # phase attributes whichever paged read path serving actually runs.
    # The permutation keeps XLA from folding the gather into a no-op view.
    if paged_block_size > 0:
        from inferd_tpu.ops import attention as attention_ops

        bs = int(paged_block_size)
        nb = -(-max_len // bs)  # blocks per lane (ceil)
        pad = nb * bs - max_len
        kc_pad = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vc_pad = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        # [L, B*nb, bs, Nkv, D] pools, blocks stored in permuted order
        perm = np.random.RandomState(0).permutation(batch * nb)
        inv = np.argsort(perm)
        kpool = kc_pad.reshape(
            L, batch * nb, bs, cfg.num_kv_heads, cfg.head_dim
        )[:, perm]
        vpool = vc_pad.reshape(
            L, batch * nb, bs, cfg.num_kv_heads, cfg.head_dim
        )[:, perm]
        # table[b, j] -> pool index of the block covering positions
        # [j*bs, (j+1)*bs) of lane b: the inverse permutation
        block_table = jnp.asarray(
            inv.reshape(batch, nb), jnp.int32
        )
    else:
        kpool = vpool = block_table = None

    def attn_body(h):
        def layer(hh, xs):
            lp, kb, vb = xs
            x = qwen3.rms_norm(hh, lp["input_norm"], eps, p1)
            q = qdot(x, lp["q_proj"])
            k = qdot(x, lp["k_proj"])
            v = qdot(x, lp["v_proj"])
            if cfg.attn_bias:
                q = q + lp["q_bias"]
                k = k + lp["k_bias"]
                v = v + lp["v_bias"]
            d = cfg.head_dim
            q = q.reshape(batch, 1, q.shape[-1] // d, d)
            k = k.reshape(batch, 1, k.shape[-1] // d, d)
            v = v.reshape(batch, 1, v.shape[-1] // d, d)
            if cfg.qk_norm:
                q = qwen3.rms_norm(q, lp["q_norm"], eps)
                k = qwen3.rms_norm(k, lp["k_norm"], eps)
            q = qwen3.apply_rope(q, cos, sin)
            k = qwen3.apply_rope(k, cos, sin)
            sinks = lp["sinks"] if cfg.attn_sinks else None
            if block_table is not None:
                attn = attention_ops.decode_gqa(
                    q, kb, vb, q_positions, jnp.int32(ctx),
                    scale=cfg.attn_scale, softcap=cfg.attn_logit_softcap,
                    sinks=sinks, block_table=block_table,
                )
            else:
                attn = qwen3._attend(
                    cfg, q, kb, vb, q_positions, jnp.int32(ctx), sinks=sinks
                )
            out = qdot(attn, lp["o_proj"])
            if cfg.o_bias:
                out = out + lp["o_bias"]
            if cfg.sandwich_norm:
                out = qwen3.rms_norm(out, lp["post_norm"], eps, p1)
            # the phase excludes the cache write (its own phase), so fold
            # k/v into the output with a negligible term — otherwise the
            # k/v projections are dead code and XLA DCEs their HBM reads
            # out of the loop (the exact chip_probe layers_ms bug class)
            keep = (
                jnp.sum(k.astype(jnp.float32)) + jnp.sum(v.astype(jnp.float32))
            ) * jnp.float32(1e-6)
            return hh + out.astype(hh.dtype) + keep.astype(hh.dtype), None

        kv_xs = (
            (params["layers"], kpool, vpool)
            if block_table is not None else (params["layers"], kc, vc)
        )
        out, _ = jax.lax.scan(layer, h, kv_xs)
        return _bounded(out)

    # ---- mlp -------------------------------------------------------------
    def mlp_body(h):
        def layer(hh, lp):
            pre = lp["pre_ffn_norm"] if cfg.sandwich_norm else lp["post_norm"]
            x = qwen3.rms_norm(hh, pre, eps, p1)
            if cfg.is_moe:
                out = qwen3.moe_mlp(lp, cfg, x)
            else:
                out = qwen3.swiglu_mlp(lp, x, qwen3.act_fn(cfg))
            if cfg.sandwich_norm:
                out = qwen3.rms_norm(out, lp["post_ffn_norm"], eps, p1)
            return hh + out.astype(hh.dtype), None

        out, _ = jax.lax.scan(layer, h, params["layers"])
        return _bounded(out)

    # ---- lm head ---------------------------------------------------------
    def head_body(h):
        logits = qwen3.unembed(params, cfg, h)
        return h + (logits[..., :1] * 1e-6).astype(h.dtype)

    # ---- sampling --------------------------------------------------------
    logits0 = jax.random.normal(
        jax.random.PRNGKey(4), (batch, cfg.vocab_size), jnp.float32
    )

    def sample_body(carry):
        lg, key = carry
        key, sub = jax.random.split(key)
        tok = samplib.sample(lg, sub, sc.temperature, sc.top_k, sc.top_p, sc.min_p)
        lg = lg + (tok[:, None] % 7).astype(jnp.float32) * 1e-6
        return (lg, key)

    # ---- kv cache write --------------------------------------------------
    rem = max_len - ctx

    def kvw_body(carry):
        k_, v_, i = carry
        pos = ctx + (i % rem)
        ck = jax.lax.dynamic_slice(
            k_, (0, 0, i % 2, 0, 0),
            (L, batch, 1, cfg.num_kv_heads, cfg.head_dim),
        )
        cv = jax.lax.dynamic_slice(
            v_, (0, 0, i % 2, 0, 0),
            (L, batch, 1, cfg.num_kv_heads, cfg.head_dim),
        )
        k_ = jax.lax.dynamic_update_slice(k_, ck, (0, 0, pos, 0, 0))
        v_ = jax.lax.dynamic_update_slice(v_, cv, (0, 0, pos, 0, 0))
        return (k_, v_, i + 1)

    cost = rl.decode_step_cost(cfg, quant=quant, ctx=ctx, batch=batch)
    phase_bytes = {
        "embed": cost.embed_gather_bytes,
        "attention": cost.attn_weight_bytes + cost.kv_read_bytes,
        "mlp": cost.mlp_weight_bytes,
        "lm_head": cost.head_bytes,
        "sampling": 0,
        "kv_write": cost.kv_write_bytes,
    }
    return {
        "runs": {
            "embed": (embed_body, tok0),
            "attention": (attn_body, hid0),
            "mlp": (mlp_body, hid0),
            "lm_head": (head_body, hid0),
            "sampling": (sample_body, (logits0, key0)),
            "kv_write": (kvw_body, (kc, vc, jnp.int32(0))),
        },
        "phase_bytes": phase_bytes,
        "step_body": step_body,
        "carry0": (tok0, cache0, key0),
        "cost": cost,
    }


def profile_step(
    cfg: ModelConfig,
    params: Optional[Any] = None,
    quant: str = "none",
    ctx: int = 256,
    batch: int = 1,
    pairs: int = 3,
    short: int = 4,
    long_: int = 12,
    sampling: Optional[SamplingConfig] = None,
    chip: Optional[rl.ChipSpec] = None,
    phases: Optional[Any] = None,
    with_step: bool = True,
    paged_block_size: int = 0,
) -> Dict[str, Any]:
    """Profile one decode step's anatomy at `ctx` cached tokens.

    `params` defaults to random init (+ `quant` applied via
    ops.quant.apply_quant_mode — same entry point as serving). When the
    caller hands in `params` they are used AS IS — a production executor
    passes its live, already-quantized serving weights and `quant` only
    informs the roofline byte accounting. Returns a JSON-ready dict:
    per-phase ms / roofline ms / roofline frac, the fused whole-step ms,
    and the unattributed residual.

    `phases` (optional subset of PHASES) limits which phase sub-graphs are
    timed — with `with_step` the whole fused step is timed too (it anchors
    the `dispatch` phase and the unattributed residual). `with_step=False`
    skips the fused step entirely (step/reconciliation fields go null) —
    the live-anatomy tick (obs.prof) times one phase per tick against a
    serving executor's weights and must not rebuild the whole model's
    step jit per tick; stage-slice executors can't even express it (their
    params hold a layer slice, not the full model). The `dispatch` phase
    needs the fused step as its anchor, so it requires `with_step`.

    `paged_block_size > 0` times the attention phase through the PAGED
    read path: per layer, K/V are gathered from a permuted block pool
    through a block table (ops.attention.gather_block_kv — the exact
    production paged-KV view materialization) before attending, so a
    paged executor's live anatomy includes the gather cost the dense
    path doesn't pay.

    The `dispatch` phase times the SAME fused step driven by a host loop
    (one jit dispatch + one host sync per token — the K=1 serving
    pattern) and reports the per-token delta over the scan-driven step:
    the host-loop overhead the multi-step `decode_k` inner loop amortizes
    (ROADMAP S1).
    """
    chip = chip or rl.detect_chip()
    suite = _build_suite(
        cfg, params, quant, ctx, batch, short, long_, sampling,
        paged_block_size,
    )
    cost = suite["cost"]
    phase_bytes = suite["phase_bytes"]
    step_body, carry0 = suite["step_body"], suite["carry0"]
    want = set(PHASES if phases is None else phases)
    unknown = want - set(PHASES)
    if unknown:
        raise ValueError(f"unknown anatomy phases: {sorted(unknown)}")
    if "dispatch" in want and not with_step:
        raise ValueError(
            "the dispatch phase needs the fused step as its anchor — "
            "drop it from phases or keep with_step=True"
        )
    # every DEVICE phase present? (dispatch is host overhead and does not
    # join the fused-step reconciliation)
    device_complete = (set(PHASES) - {"dispatch"}) <= want
    phase_out: Dict[str, Any] = {}
    for name, (body, operand) in suite["runs"].items():
        if name not in want:
            continue
        ms, n_valid, spread = _paired_scan_ms(body, operand, short, long_, pairs)
        b = phase_bytes[name]
        roof_ms = b / (chip.hbm_gbps * 1e9) * 1e3
        phase_out[name] = {
            "ms": round(ms, 4),
            "bytes": int(b),
            "roofline_ms": round(roof_ms, 4),
            "roofline_frac": round(roof_ms / ms, 4) if ms > 0 else None,
            "pairs_valid": n_valid,
            "spread_pt": spread,
        }

    if with_step:
        step_ms, step_valid, step_spread = _paired_scan_ms(
            step_body, carry0, short, long_, pairs
        )
    else:
        step_ms, step_valid, step_spread = None, 0, 0.0
    # phase_sum reconciles the DEVICE phases against the fused step;
    # compute it before the host-overhead dispatch phase joins the dict
    phase_sum = sum(p["ms"] for p in phase_out.values())

    if "dispatch" in want:
        # the K=1 serving pattern: one separately-dispatched jitted step
        # + one host sync per token. kc/vc are reused read-only (the jit
        # is NOT donated — the per-step cache copy a donation-less loop
        # pays is itself part of what the fused loop removes on real
        # serving paths, but donating here would destroy the shared
        # buffers the scan-based phases also time; the dominant measured
        # term is the dispatch+sync round trip either way).
        step1 = jax.jit(step_body)
        np.asarray(step1(carry0)[0])  # compile+warm once, not a per-iteration sync

        def host_run(n: int):
            def t() -> float:
                c = carry0
                t0 = time.perf_counter()
                for _ in range(n):
                    c = step1(c)
                    np.asarray(c[0])  # the per-token host sync IS the measured quantity
                return time.perf_counter() - t0

            return t

        ts_h, tl_h = interleaved_pair_times(
            host_run(short), host_run(long_), pairs
        )
        host_ms_s, host_valid, host_spread, _ = paired_delta_stats(
            ts_h, tl_h, short, long_
        )
        host_ms = host_ms_s * 1e3
        phase_out["dispatch"] = {
            "ms": round(max(host_ms - step_ms, 0.0), 4),
            "hostloop_step_ms": round(host_ms, 4),
            "bytes": 0,
            "roofline_ms": 0.0,
            "roofline_frac": None,
            "pairs_valid": host_valid,
            "spread_pt": host_spread,
        }

    whole = rl.roofline(cost, chip)
    return {
        "preset": cfg.name,
        "quant": quant,
        "ctx": ctx,
        "batch": batch,
        "chip": chip.key,
        "paged_block_size": int(paged_block_size),
        "phases": phase_out,
        "step_ms": round(step_ms, 4) if step_ms is not None else None,
        "step_pairs_valid": step_valid,
        "step_spread_pt": step_spread,
        "step_roofline_ms": round(whole.floor_ms, 4),
        "step_roofline_frac": (
            round(whole.floor_ms / step_ms, 4)
            if step_ms is not None and step_ms > 0 else None
        ),
        # the reconciliation fields only mean anything when EVERY device
        # phase was timed against the fused step — a --phases subset (or
        # with_step=False) would misreport the whole step as unattributed
        # residual, so they go null instead
        "phase_sum_ms": round(phase_sum, 4) if device_complete else None,
        "unattributed_ms": (
            round(step_ms - phase_sum, 4)
            if device_complete and step_ms is not None else None
        ),
        "pairs": pairs,
        "window_iters": [short, long_],
    }


class AnatomySession:
    """Compile-once live-anatomy scans over one target configuration.

    `profile_step` builds fresh closures per call, so jax.jit re-traces
    and recompiles every phase scan every time — fine for a one-shot
    offline profile, ruinous for a recurring production tick (a real
    model's L-layer scan compiles for seconds, and the tick holds the
    executor's device lock while it does). A session builds the phase
    suite ONCE (same tensors, same bodies) and caches each phase's
    warmed scan loops on first measure, so every later tick pays only
    the tiny short/long scan windows. The live tick (obs.prof) keeps one
    session per target signature and rebuilds only when the signature —
    (preset, layers, quant, ctx bucket, batch, paged block, chip) —
    actually changes.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Optional[Any] = None,
        quant: str = "none",
        ctx: int = 256,
        batch: int = 1,
        short: int = 2,
        long_: int = 4,
        sampling: Optional[SamplingConfig] = None,
        chip: Optional[rl.ChipSpec] = None,
        paged_block_size: int = 0,
    ):
        self.chip = chip or rl.detect_chip()
        self.short, self.long_ = short, long_
        self._suite = _build_suite(
            cfg, params, quant, ctx, batch, short, long_, sampling,
            paged_block_size,
        )
        self._loops: Dict[str, Any] = {}

    @property
    def phases(self):
        return tuple(self._suite["runs"])

    def measure(self, phase: str, pairs: int = 1) -> Dict[str, Any]:
        """One phase's measurement (profile_step `phases[...]` shape).
        First call per phase compiles and caches the scan loops; later
        calls reuse them."""
        if phase not in self._suite["runs"]:
            raise ValueError(
                f"unknown session phase {phase!r}; have {self.phases}"
            )
        body, operand = self._suite["runs"][phase]
        loops = self._loops.get(phase)
        if loops is None:
            loops = _scan_loops(body, operand, self.short, self.long_)
            self._loops[phase] = loops
        ms, n_valid, spread = _measure_loops(
            loops[0], loops[1], operand, self.short, self.long_, pairs
        )
        b = self._suite["phase_bytes"][phase]
        roof_ms = b / (self.chip.hbm_gbps * 1e9) * 1e3
        return {
            "ms": round(ms, 4),
            "bytes": int(b),
            "roofline_ms": round(roof_ms, 4),
            "roofline_frac": round(roof_ms / ms, 4) if ms > 0 else None,
            "pairs_valid": n_valid,
            "spread_pt": spread,
        }
