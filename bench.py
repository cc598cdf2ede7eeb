#!/usr/bin/env python
"""Headline benchmark: single-chip decode throughput for Qwen3-0.6B (the
reference's chain-path model) in the reference's decode regime (50-token
generations, batch 1 — /root/reference/petals/send_message.py:46-47).

Always prints ONE JSON line (never a bare stack trace):
  {"metric": ..., "value": tok/s, "unit": "tok/s", "vs_baseline": ratio,
   "device": "tpu"|"cpu", ...}

`--device` names the platform and the bench runs on it in THIS process:
`tpu` (the default) fails with a non-zero exit when JAX finds no chip — it
never measures the CPU in its place; `cpu` is for the tiny smoke runs of
run.sh and the tests. The configs whose legs start node processes
(pipeline-cpu, pipeline-paired, swarm-*, canary, overload, cache-affinity,
failover, lora-tenants) pin those nodes to the CPU and have no chip form:
they refuse `--device tpu`.

`vs_baseline` compares against a faithfully reference-shaped decode on the
SAME hardware: the swarm path's no-KV-cache full-sequence recompute per token
(SURVEY B4 — /root/reference/petals/partitioned_models.py:145-151). The
reference published no absolute numbers, so its own algorithmic regime on
identical silicon is the honest denominator.

Extra configs (the BASELINE.json targets):
  --config pipeline-cpu   BASELINE config 1: 0.6B split into 2 stages served
                          by 2 local CPU worker processes via the stock node
                          CLI; vs_baseline = fraction of the single-process
                          engine's tok/s (pipeline efficiency).
  --config pipelined      in-mesh microbatched pipeline (PipelinedEngine)
                          over a pp mesh; vs_baseline = aggregate tok/s
                          versus the single-device engine.
  --config flash          flash-attention kernel vs the XLA attention path
                          on decode shapes (TPU validates the Mosaic
                          compile; CPU runs the interpreter as a smoke test).
"""

import argparse
import json
import os
import subprocess
import sys
import time

# light import: utils.platform pulls no jax at module scope, so this cannot
# initialize a backend before main() pins the platform
from inferd_tpu.utils.platform import is_cpu, is_tpu


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


def bench_decode(
    cfg_name: str,
    steps: int,
    reps: int,
    quant_mode: str = "none",
    ctx: int = 0,
    kv_dtype: str = "model",
):
    """`ctx` > 0 measures LONG-CONTEXT decode: prefill a ctx-token prompt,
    then time decode steps attending over that cache — the regime where the
    KV read (not the weight read) dominates and `--kv-dtype float8_e4m3fn`
    halves it. ctx=0 is the reference's short regime (64-token prompt)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.core.generate import Engine
    from inferd_tpu.models import qwen3

    cfg = get_config(cfg_name)
    if kv_dtype != "model":
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    params = jax.block_until_ready(qwen3.init_params(cfg, jax.random.PRNGKey(0)))
    # logical model size, counted BEFORE quantization (the quantized tree
    # adds scale vectors and a tied-head shadow that are storage, not params)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    if quant_mode != "none":
        from inferd_tpu.ops import quant

        params = quant.apply_quant_mode(
            quant_mode, params, tie_word_embeddings=cfg.tie_word_embeddings
        )
    prompt_len = ctx if ctx > 0 else 64
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (1, prompt_len), 0, cfg.vocab_size, dtype=jnp.int32
    )

    import statistics

    import numpy as np

    from inferd_tpu.utils.profiling import (
        interleaved_pair_times, paired_delta_stats,
    )

    # --- ours: fused-scan decode over a functional KV cache -----------------
    # Timing forces a device->host transfer per rep: a materialized output
    # ends the timed region when the device has finished, not when the call
    # was enqueued. Every dispatch also pays a fixed host cost that varies
    # with host load — so the PRIMARY number is the steady-state per-token
    # rate from differencing two generation lengths (fixed overhead
    # cancels). Round 5 measured the two window lengths in separate
    # best-of-reps blocks minutes apart and load INVERTED them inside a leg
    # stamped valid; the windows now run in INTERLEAVED PAIRS (the round-4 pipeline-leg discipline, shared helper
    # in utils/profiling) with per-pair validity — each valid pair's
    # differenced steady time is <= its own e2e time by construction, and
    # e2e is the median over the SAME valid pairs, so steady >= e2e in
    # tok/s holds whenever steady_timing_valid is true.
    steps_long = steps * 3
    engine = Engine(cfg, params, max_len=max(512, prompt_len + steps_long))

    seed_box = {"n": 0}

    def run_once(n_steps: int) -> float:
        seed_box["n"] += 1
        t0 = time.perf_counter()
        np.asarray(
            engine.generate_scan(prompt, prompt_len, n_steps, seed=seed_box["n"])
        )
        return time.perf_counter() - t0

    # compile BOTH window lengths before any timed pair
    np.asarray(engine.generate_scan(prompt, prompt_len, steps))
    np.asarray(engine.generate_scan(prompt, prompt_len, steps_long))
    pairs = max(2, reps)
    ts_w, tl_w = interleaved_pair_times(
        lambda: run_once(steps), lambda: run_once(steps_long), pairs
    )
    per_tok_s, n_valid, spread_pt, ts_valid = paired_delta_stats(
        ts_w, tl_w, steps, steps_long
    )
    e2e_t = statistics.median(ts_valid)
    ours_e2e = steps / e2e_t
    steady_valid = n_valid >= max(1, pairs // 2)
    # n_valid == 0 (every pair congestion-inverted): paired_delta_stats
    # already degraded per_tok_s to the amortized long-window time — the
    # one definition of that fallback lives in utils/profiling
    ours = 1.0 / per_tok_s
    overhead_ms = (
        max(e2e_t - steps * per_tok_s, 0.0) * 1e3 if n_valid > 0 else 0.0
    )

    # --- reference-shaped: full-sequence recompute per token (no KV cache) --
    # fixed padded buffer sized for the LONG run: one compile, and the same
    # length-independent per-step regime for both differencing points.
    # Long-context runs skip it (a 32K-token full forward PER TOKEN would
    # take longer than the whole bench budget; across-kv-dtype comparison
    # is two invocations of this config instead).
    naive = None
    naive_valid = True
    if ctx == 0:
        total = prompt_len + steps_long

        @jax.jit
        def naive_step(params, tokens, n):
            logits, _, _ = qwen3.forward(params, cfg, tokens)
            return jnp.argmax(logits[0, n - 1])

        buf0 = jnp.zeros((1, total), jnp.int32).at[:, :prompt_len].set(prompt)
        np.asarray(naive_step(params, buf0, prompt_len))  # compile

        def naive_time(n_steps: int, n_reps: int) -> float:
            ts = []
            for _ in range(n_reps):  # same estimator as "ours": best of reps
                buf = buf0
                t0 = time.perf_counter()
                for i in range(n_steps):
                    tok = naive_step(params, buf, prompt_len + i)
                    buf = buf.at[0, prompt_len + i].set(tok)
                np.asarray(buf)  # the final buffer depends on every step
                ts.append(time.perf_counter() - t0)
            return min(ts)

        # the naive regime recomputes the whole (padded, fixed `total`)
        # sequence every token, so its per-step cost is length-independent
        # here — the short run differenced against fixed overhead would be
        # noise-dominated; difference two step counts instead, like "ours"
        nt_short = naive_time(steps, min(reps, 3))
        nt_long = naive_time(steps_long, 2)
        if nt_long - nt_short > 0:
            naive = (steps_long - steps) / (nt_long - nt_short)
        else:
            # congestion flipped the naive windows: amortized fallback.
            # Only the DENOMINATOR is affected — steady_timing_valid
            # describes the primary metric's paired windows, not this one
            naive = steps_long / nt_long
            naive_valid = False

    # roofline framing: bs=1 decode is HBM-bound — the analytic cost model
    # (perf/roofline, the audited replacement for the ad-hoc weight-bytes
    # arithmetic this block used to carry) supplies the ceiling
    metric = f"{cfg.name.replace('-', '_')}_decode_tok_per_s_bs1"
    if ctx > 0:
        metric += f"_ctx{ctx}"
    if kv_dtype != "model":
        metric += f"_kv-{kv_dtype}"
    result = {
        "metric": metric,
        "value": round(ours, 2),
        "unit": "tok/s",
        "vs_baseline": None if naive is None else round(ours / naive, 2),
        "naive_tok_per_s": None if naive is None else round(naive, 2),
        "naive_timing_valid": naive_valid,
        "e2e_tok_per_s": round(ours_e2e, 2),  # includes fixed dispatch RTT
        "dispatch_overhead_ms": round(overhead_ms, 1),
        "steady_timing_valid": steady_valid,
        "steady_spread_pt": spread_pt,
        "timing_methodology": "interleaved-paired",
        "pairs": pairs,
        "pairs_valid": n_valid,
        "model_params": n_params,
    }
    if ctx > 0:
        result["ctx"] = ctx
    from inferd_tpu.perf import roofline as rl

    cost = rl.decode_step_cost(cfg, quant=quant_mode, ctx=ctx, batch=1)
    if ctx > 0:
        result["kv_bytes_at_ctx"] = cost.kv_read_bytes
    if is_tpu():
        chip = rl.detect_chip()
        result["hbm_roofline_frac"] = round(rl.roofline_frac(ours, cost, chip), 3)
        result["roofline_ceiling_tok_s"] = round(
            rl.roofline(cost, chip).ceiling_tok_s, 1
        )
        result["roofline_chip"] = chip.key
    if quant_mode != "none":
        from inferd_tpu.ops import quant

        result["metric"] += f"_{quant_mode}"
        result["quant"] = quant_mode
        result["param_bytes"] = quant.quantized_bytes(params)
    return result


def bench_decode_multistep(
    cfg_name: str,
    steps: int,
    reps: int,
    ks=(1, 4, 8, 16),
    quant_mode: str = "none",
):
    """K-tokens-per-dispatch decode sweep through the SERVING surface (the
    single-stage Qwen3StageExecutor and its multi-step fused decode path,
    models/qwen3.decode_k): for each K, decode the same token budget with
    one dispatch + one host sync per K tokens, and report the steady
    per-token rate per K. The amortization claim this leg gates
    (`perf check` ordering): some K > 1 must be at least as fast as K=1 —
    per-token dispatch overhead is real and the fused loop exists to
    remove it.

    Token-exactness is asserted in-leg: every K's greedy stream must equal
    the K=1 client-style loop (argmax over shipped logits), or the leg
    reports token_exact=false and fails.

    Timing: interleaved short/long paired windows per K (the round-6
    decode methodology, utils/profiling). Each window restarts the session
    and re-prefills, so the fixed prefill cost cancels in the differencing
    exactly like fixed dispatch RTT.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import statistics

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import StageSpec, extract_stage_params
    from inferd_tpu.runtime.executor import Qwen3StageExecutor
    from inferd_tpu.utils.profiling import (
        interleaved_pair_times, paired_delta_stats,
    )

    cfg = get_config(cfg_name)
    params = jax.block_until_ready(qwen3.init_params(cfg, jax.random.PRNGKey(0)))
    if quant_mode != "none":
        from inferd_tpu.ops import quant

        params = quant.apply_quant_mode(
            quant_mode, params, tie_word_embeddings=cfg.tie_word_embeddings
        )
    spec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    sp = extract_stage_params(params, cfg, spec)
    prompt_len = 64
    steps_long = steps * 3
    max_len = prompt_len + steps_long + 16
    ex = Qwen3StageExecutor(
        cfg, spec, sp, max_len=max_len, initial_kv_len=max_len
    )
    prompt = np.asarray(
        jax.random.randint(
            jax.random.PRNGKey(1), (prompt_len,), 0, cfg.vocab_size,
            dtype=jnp.int32,
        )
    ).tolist()

    def run_kstep(k: int, n_steps: int, sid: str):
        """Prefill + decode n_steps greedy tokens, K per dispatch."""
        ex.end_session(sid)
        r = ex.process(
            sid, {"tokens": [prompt], "start_pos": 0, "real_len": prompt_len}
        )
        out = [int(np.argmax(r["logits"][0]))]
        pos = prompt_len
        while len(out) < n_steps:
            rr = ex.process(sid, {
                "tokens": [[out[-1]]], "start_pos": pos,
                "decode_steps": min(k, n_steps - len(out)),
            })
            out.extend(int(t) for t in rr["tokens"][0])
            pos += rr["real_len"]
        return out

    def run_client_loop(n_steps: int, sid: str):
        """The K=1 reference: per-token dispatch, client-side argmax."""
        ex.end_session(sid)
        r = ex.process(
            sid, {"tokens": [prompt], "start_pos": 0, "real_len": prompt_len}
        )
        out = [int(np.argmax(r["logits"][0]))]
        pos = prompt_len
        while len(out) < n_steps:
            r = ex.process(
                sid, {"tokens": [[out[-1]]], "start_pos": pos, "real_len": 1}
            )
            out.append(int(np.argmax(r["logits"][0])))
            pos += 1
        return out

    ref = run_client_loop(steps_long, "ref")
    token_exact = True
    per_k = {}
    per_k_e2e = {}
    per_k_valid = {}
    pairs = max(2, reps)
    for k in ks:
        got = run_kstep(k, steps_long, f"k{k}")  # compile + warm BOTH windows
        run_kstep(k, steps, f"k{k}")
        if got != ref:
            token_exact = False

        def timed(n_steps: int, _k=k):
            def t() -> float:
                t0 = time.perf_counter()
                run_kstep(_k, n_steps, f"k{_k}")
                return time.perf_counter() - t0

            return t

        ts_w, tl_w = interleaved_pair_times(timed(steps), timed(steps_long), pairs)
        per_tok_s, n_valid, _spread, ts_valid = paired_delta_stats(
            ts_w, tl_w, steps, steps_long
        )
        per_k[str(k)] = round(1.0 / per_tok_s, 2)
        per_k_e2e[str(k)] = round(steps / statistics.median(ts_valid), 2)
        per_k_valid[str(k)] = n_valid
    base = per_k.get("1")
    multi = {kk: vv for kk, vv in per_k.items() if kk != "1"}
    best_k, best = (
        max(multi.items(), key=lambda it: it[1]) if multi else (None, None)
    )
    result = {
        "metric": f"{cfg.name.replace('-', '_')}_decode_multistep_tok_per_s_bs1",
        "value": best if best is not None else base,
        "unit": "tok/s",
        "per_k": per_k,
        "per_k_e2e": per_k_e2e,
        "per_k_pairs_valid": per_k_valid,
        "k_best": best_k,
        "speedup_best_vs_k1": (
            round(best / base, 3) if base and best is not None else None
        ),
        "token_exact": token_exact,
        "steady_timing_valid": all(
            v >= max(1, pairs // 2) for v in per_k_valid.values()
        ),
        "timing_methodology": "interleaved-paired",
        "pairs": pairs,
        "steps": steps,
    }
    from inferd_tpu.perf import roofline as rl

    cost = rl.decode_step_cost(cfg, quant=quant_mode, ctx=0, batch=1)
    if is_tpu() and best is not None:
        chip = rl.detect_chip()
        result["hbm_roofline_frac"] = round(rl.roofline_frac(best, cost, chip), 3)
        result["roofline_ceiling_tok_s"] = round(
            rl.roofline(cost, chip).ceiling_tok_s, 1
        )
        result["roofline_chip"] = chip.key
    if quant_mode != "none":
        result["metric"] += f"_{quant_mode}"
        result["quant"] = quant_mode
    if not token_exact:
        result["error"] = "K>1 greedy stream diverged from the K=1 loop"
    return result


def bench_kernels(cfg_name: str, steps: int = 6):
    """Round-19 decode-kernel grading leg: the three Pallas kernels (paged
    decode-attention, dequant-fused quant GEMV, fused LoRA lane-delta)
    against their XLA siblings, forced ON vs OFF on the same host with
    every stream token-exact cross-checked.

    The graded quantities are the DIMENSIONLESS kernel-vs-xla ratios from
    the roofline bytes model (perf/roofline.py: paged_attn_step_bytes /
    quant_matvec_bytes / lora_delta_step_bytes), evaluated at the
    qwen3-0.6b serving point — structural HBM traffic, machine-portable by
    construction. CPU wall clock would time the Pallas INTERPRETER, not
    the kernels (interpret mode runs the grid as data-dependent slices —
    60-80x off), so the CPU-proxy artifact grades bytes and correctness
    here and leaves wall-clock verdicts to `sweep_attn --kernels` on real
    hardware (the autotune registry entries the dispatches consult).

    token_exact is MEASURED, not modeled: a paged stage executor, an
    int4-quantized executor, and a multi-tenant LoRA executor each decode
    the same greedy stream with the kernels forced on and forced off."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.ops import attention as att
    from inferd_tpu.ops import lora as lora_ops
    from inferd_tpu.ops import quant
    from inferd_tpu.perf import roofline as rl

    cfg = get_config(cfg_name)

    # -- graded ratios: structural bytes at the 0.6b serving point ---------
    serving = get_config("qwen3-0.6b")
    h, i = serving.hidden_size, serving.intermediate_size
    paged_b = rl.paged_attn_step_bytes(
        batch=8, ctx=1000, kv_dim=serving.kv_dim,
        kv_size=jnp.dtype(serving.kv_jnp_dtype).itemsize,
        block_size=32, table_blocks=256,
    )
    q8_b = rl.quant_matvec_bytes(h, i, "int8")
    q4_b = rl.quant_matvec_bytes(h, i, "int4")
    lora_b = rl.lora_delta_step_bytes(batch=8, d_in=h, rank=8, d_out=h)
    ratios = {
        "paged_vs_xla": round(paged_b["xla"] / paged_b["kernel"], 3),
        "quant_int8_vs_xla": round(q8_b["xla"] / q8_b["kernel"], 3),
        "quant_int4_vs_xla": round(q4_b["xla"] / q4_b["kernel"], 3),
        "lora_vs_xla": round(lora_b["xla"] / lora_b["kernel"], 3),
    }

    prompt_len = 16
    prompt = np.asarray(
        jax.random.randint(
            jax.random.PRNGKey(1), (prompt_len,), 0, cfg.vocab_size,
            dtype=jnp.int32,
        )
    ).tolist()
    params = jax.block_until_ready(
        qwen3.init_params(cfg, jax.random.PRNGKey(0))
    )

    def greedy(ex, sid, adapter=None):
        payload = {
            "tokens": [prompt], "start_pos": 0, "real_len": prompt_len,
        }
        if adapter is not None:
            payload["adapter"] = adapter
        r = ex.process(sid, payload)
        out = [int(np.argmax(r["logits"][0]))]
        pos = prompt_len
        for _ in range(steps - 1):
            r = ex.process(sid, {
                "tokens": [[out[-1]]], "start_pos": pos, "real_len": 1,
            })
            out.append(int(np.argmax(r["logits"][0])))
            pos += 1
        return out

    # -- paged decode-attention: stage executor over paged KV --------------
    from inferd_tpu.parallel.stages import (
        Manifest, StageSpec, extract_stage_params,
    )
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    spec = list(Manifest.even_split(cfg.name, 1).stage_specs())[0]
    sp = extract_stage_params(params, cfg, spec)

    def paged_stream(force):
        old = att.FORCE_PAGED_KERNEL
        att.FORCE_PAGED_KERNEL = force
        try:
            ex = BatchedStageExecutor(
                cfg, spec, sp, lanes=2, max_len=64, block_size=8,
            )
            return greedy(ex, "pg")
        finally:
            att.FORCE_PAGED_KERNEL = old

    paged_exact = paged_stream(True) == paged_stream(False)

    # -- quant GEMV: int4-quantized executor (dequant scheme: the kernel
    # mirrors it bit-for-bit; the grouped scheme's allclose parity is
    # tier-1 test coverage) ------------------------------------------------
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    qparams = quant.apply_quant_mode(
        "int4", params, tie_word_embeddings=cfg.tie_word_embeddings
    )
    sspec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    sparams = extract_stage_params(qparams, cfg, sspec)

    def quant_stream(force):
        old_force, old_mode = quant.FORCE_QUANT_KERNEL, quant.INT4_MODE
        quant.FORCE_QUANT_KERNEL = force
        quant.INT4_MODE = "dequant"
        try:
            ex = Qwen3StageExecutor(
                cfg, sspec, sparams, max_len=64, initial_kv_len=64
            )
            return greedy(ex, "qt")
        finally:
            quant.FORCE_QUANT_KERNEL = old_force
            quant.INT4_MODE = old_mode

    quant_exact = quant_stream(True) == quant_stream(False)

    # -- fused LoRA lane-delta: multi-tenant batched executor --------------
    from inferd_tpu.runtime.adapters import AdapterRegistry
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    with tempfile.TemporaryDirectory() as tmp:
        g = np.random.default_rng(3)
        r = 4
        dims = {
            "q_proj": (cfg.hidden_size, cfg.q_dim),
            "down_proj": (cfg.intermediate_size, cfg.hidden_size),
        }
        layers = {
            name: (
                g.normal(0, 0.25, (cfg.num_layers, din, r)).astype(np.float32),
                g.normal(0, 0.25, (cfg.num_layers, r, dout)).astype(np.float32),
            )
            for name, (din, dout) in dims.items()
        }
        adir = os.path.join(tmp, "ten0")
        lora_ops.save_adapter(adir, layers, alpha=8, r=r)

        def lora_stream(force):
            old = lora_ops.FORCE_LORA_KERNEL
            lora_ops.FORCE_LORA_KERNEL = force
            try:
                ex = BatchedExecutor(
                    cfg, params, lanes=2, max_len=64,
                    adapters=AdapterRegistry(cfg, [adir]),
                )
                return (
                    greedy(ex, "ln", adapter="ten0"), greedy(ex, "lb")
                )
            finally:
                lora_ops.FORCE_LORA_KERNEL = old

        lora_exact = lora_stream(True) == lora_stream(False)

    token_exact = paged_exact and quant_exact and lora_exact
    value = min(ratios.values())
    result = {
        "metric": "kernels_min_bytes_ratio",
        "value": value,
        "unit": "ratio",
        "min_kernel_vs_xla": value,
        **ratios,
        "token_exact": token_exact,
        "paged_token_exact": paged_exact,
        "quant_token_exact": quant_exact,
        "lora_token_exact": lora_exact,
        "bytes_model": {
            "paged": paged_b, "quant_int8": q8_b, "quant_int4": q4_b,
            "lora": lora_b,
        },
        "bytes_model_point": {
            "config": serving.name, "batch": 8, "ctx": 1000,
            "block_size": 32, "table_blocks": 256, "lora_rank": 8,
        },
        "e2e_config": cfg.name,
        "steps": steps,
        "timing_methodology": "structural-bytes-model",
        "note": (
            "CPU-proxy grading: ratios are roofline HBM bytes "
            "(perf/roofline.py), token_exact is measured forced-on vs "
            "forced-off; wall-clock verdicts come from sweep_attn "
            "--kernels on hardware"
        ),
    }
    if not token_exact:
        result["error"] = (
            "kernel-forced stream diverged from the XLA sibling stream"
        )
    return result


def _two_stage_cluster(
    cfg_name: str, base_http: int, base_gossip: int, backend: str = "qwen3",
    node_args=(), stages: int = 2, extra_nodes=(),
):
    """Shared scaffolding for the multi-process pipeline legs: split
    `cfg_name` into `stages` random-init stages in a temp parts store
    (qwen3 backend; the counter backend is model-free and skips it),
    launch one stock-CLI CPU node process per stage, and guarantee
    teardown (terminate -> wait -> kill -> rmtree) whatever the
    measurement does. Yields the process list so callers' warm-up loops
    can fail fast on a dead child instead of burning their whole deadline
    on connection retries.

    `extra_nodes`: [(stage, [extra node args])] EXTRA replicas beyond the
    one-per-stage baseline (ports continue after the base nodes) — the
    overload leg uses this to add a chaos-injected second replica."""
    import shutil
    import tempfile

    work = tempfile.mkdtemp(prefix="bench_pipe_")
    env = dict(os.environ, JAX_PLATFORMS="cpu", INFERD_DEVICE="cpu")
    procs = []
    try:
        if backend == "qwen3":
            subprocess.run(
                [sys.executable, "-m", "inferd_tpu.tools.split_model",
                 "--model", cfg_name, "--stages", str(stages),
                 "--out", f"{work}/parts", "--random-init"],
                env=env, check=True, capture_output=True, timeout=600,
            )
        launches = [(stage, ()) for stage in range(stages)]
        launches += [(int(s), tuple(extra)) for s, extra in extra_nodes]
        for idx, (stage, extra) in enumerate(launches):
            cmd = [
                sys.executable, "-m", "inferd_tpu.tools.run_node",
                "--model", cfg_name, "--num-stages", str(stages),
                "--backend", backend,
                "--stage", str(stage), "--parts", f"{work}/parts",
                "--device", "cpu", "--host", "127.0.0.1",
                "--port", str(base_http + idx),
                "--gossip-port", str(base_gossip + idx),
                "--bootstrap", "" if idx == 0 else f"127.0.0.1:{base_gossip}",
                "--name", f"bench-n{idx}",
                *node_args, *extra,
            ]
            procs.append(subprocess.Popen(
                cmd, env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            ))
        yield procs
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(work, ignore_errors=True)


def _raise_if_dead(procs) -> None:
    """A node child that already EXITED can never answer — warm-up loops
    fail fast instead of retrying into their deadline."""
    dead = [p for p in procs if p.poll() is not None]
    if dead:
        raise RuntimeError(
            f"{len(dead)} node process(es) exited during warm-up "
            f"(rc={[p.returncode for p in dead]}) — stale port or "
            f"startup failure"
        )


async def _cluster_warmup(client, prompt, steps: int,
                          deadline_s: float = 600.0, procs=()):
    """Generate until the cluster answers: both stages up, buckets
    compiled; fails fast on a dead child (_raise_if_dead)."""
    import asyncio

    deadline = time.monotonic() + deadline_s
    while True:
        _raise_if_dead(procs)
        try:
            await client.generate_ids(prompt, max_new_tokens=steps)
            return
        except Exception:
            if time.monotonic() > deadline:
                raise
            await asyncio.sleep(1.0)


async def _fetch_hop_p50(base_http: int, strict: bool = False):
    """p50 inter-stage hop latency from the stage-0 node's relay histogram
    (the north-star companion metric). NOTE: hop.relay_ms times the full
    downstream round trip, which INCLUDES the next stage's compute.
    strict=True propagates the underlying failure (for legs where this
    number IS the product); the default degrades to None (companion
    metric on a best-effort basis)."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{base_http}/stats") as r:
                snap = await r.json()
        return snap["histograms"]["hop.relay_ms"]["p50_ms"]
    except Exception:
        if strict:
            raise
        return None



async def _paired_windows(side_single, side_other, pairs: int):
    """Interleaved paired measurement core (shared by the process and
    in-mesh pipeline legs): each pair times one window of each side back to
    back, ALTERNATING which goes first — a linear host-load drift then
    biases half the pairs up and half down and the median cancels it.
    side_single(seed) / side_other() return rates; either may be async.
    Returns (ratios other/single, single_rates, other_rates)."""
    import inspect

    async def call(fn, *a):
        r = fn(*a)
        return await r if inspect.isawaitable(r) else r

    ratios, single_rates, other_rates = [], [], []
    for i in range(pairs):
        if i % 2 == 0:
            s = await call(side_single, i + 1)
            p = await call(side_other)
        else:
            p = await call(side_other)
            s = await call(side_single, i + 1)
        ratios.append(p / s)
        single_rates.append(s)
        other_rates.append(p)
    return ratios, single_rates, other_rates


def _ratio_stats(ratios):
    """(median, spread) of per-pair ratios; spread = half the IQR in
    percentage points (falls back to the range for < 3 pairs)."""
    import statistics

    med = statistics.median(ratios)
    qs = statistics.quantiles(ratios, n=4) if len(ratios) >= 3 else [
        min(ratios), med, max(ratios)
    ]
    return med, round((qs[2] - qs[0]) / 2 * 100, 1)


def bench_hop_overhead(requests: int = 200):
    """The framework's OWN per-hop cost, isolated: a 2-stage chain of
    counter-model nodes (zero compute) driven end to end. What remains is
    exactly the serving stack — aiohttp server+client, wire codec,
    scheduler handoff, relay pick, gossip bookkeeping. This bounds the
    transport term of the north-star hop story independently of model
    compute and of how many cores the host timeshares: measured ~1.7 ms
    per full client->s0->s1->client round trip (0.8 ms p50 for the
    s0->s1 relay leg) on the 1-core CI host — so the paired CPU ratio's
    gap to 1.0 is stage-compute timesharing, not framework overhead."""
    import asyncio

    import aiohttp

    from inferd_tpu.runtime import wire

    base_http, base_gossip = 16450, 17450
    with _two_stage_cluster(
        "tiny", base_http, base_gossip, backend="counter"
    ) as procs:

        async def drive():
            deadline = time.monotonic() + 300
            async with aiohttp.ClientSession() as s:
                async def once(i):
                    body = wire.pack({
                        "task_id": f"t{i}", "session_id": f"s{i}",
                        "stage": 0, "payload": {"state": 0, "trace": []},
                    })
                    async with s.post(
                        f"http://127.0.0.1:{base_http}/forward", data=body
                    ) as r:
                        await r.read()
                        if r.status != 200:
                            raise RuntimeError(f"status {r.status}")
                while True:  # cluster warm-up (fail fast on a dead child)
                    _raise_if_dead(procs)
                    try:
                        await once(-1)
                        break
                    except Exception:
                        if time.monotonic() > deadline:
                            raise
                        await asyncio.sleep(1.0)
                t0 = time.perf_counter()
                for i in range(requests):
                    await once(i)
                per_req = (time.perf_counter() - t0) / requests * 1e3
                # p50, not mean: the warm-up request's cold-path relay
                # sample (TCP connect, first-touch) must not skew the
                # attribution headline
                # strict: the relay number IS this bench's product — a
                # missing /stats histogram fails with its root cause, not
                # a silent null in the artifact
                return per_req, await _fetch_hop_p50(base_http, strict=True)

        per_req, relay_p50 = asyncio.run(drive())
        return {
            "framework_roundtrip_ms": round(per_req, 2),
            "framework_relay_hop_ms": round(relay_p50, 2),
            "requests": requests,
            "note": "zero-compute counter chain: serving-stack cost only",
        }


def bench_pipeline_cpu(cfg_name: str, steps: int):
    """BASELINE config 1: 2 pipeline stages as 2 local CPU node processes,
    driven by the SwarmClient through the stock node CLI."""
    import asyncio

    base_http, base_gossip = 16250, 17250
    with _two_stage_cluster(cfg_name, base_http, base_gossip) as procs:
        from inferd_tpu.client.swarm_client import SwarmClient
        from inferd_tpu.config import SamplingConfig

        prompt = list(range(3, 3 + 16))

        async def run():
            async with SwarmClient(
                [("127.0.0.1", base_http)],
                sampling=SamplingConfig(temperature=0.0),
            ) as c:
                await _cluster_warmup(c, prompt, 2, procs=procs)
                t0 = time.perf_counter()
                out = await c.generate_ids(prompt, max_new_tokens=steps)
                dt = time.perf_counter() - t0
                return len(out) / dt, await _fetch_hop_p50(base_http)

        pipe_tps, hop_p50_ms = asyncio.run(run())

        # single-process engine on the same host = the 1-chip denominator
        import jax
        import jax.numpy as jnp

        from inferd_tpu.config import get_config
        from inferd_tpu.core.generate import Engine
        from inferd_tpu.models import qwen3

        import numpy as np

        cfg = get_config(cfg_name)
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        engine = Engine(cfg, params, max_len=256)
        ptok = jnp.asarray([prompt], jnp.int32)
        np.asarray(engine.generate_scan(ptok, len(prompt), steps))
        t0 = time.perf_counter()
        np.asarray(engine.generate_scan(ptok, len(prompt), steps, seed=1))
        single_tps = steps / (time.perf_counter() - t0)

        return {
            "metric": f"{cfg_name.replace('-', '_')}_pipeline2_cpu_tok_per_s",
            "value": round(pipe_tps, 2),
            "unit": "tok/s",
            "vs_baseline": round(pipe_tps / single_tps, 3),
            "single_process_tok_per_s": round(single_tps, 2),
            "stages": 2,
            "workers": "2 local CPU node processes (stock node CLI)",
            # includes the downstream stage's forward compute, not
            # pure transport (see bench_hop_overhead for the wire cost)
            "relay_roundtrip_incl_compute_ms": hop_p50_ms,
        }


def bench_pipeline_paired(
    cfg_name: str = "bench-pipe", pairs: int = 5, window: int = 12
):
    """Noise-proofed north-star proxy (the BASELINE config-1 ratio,
    measured so the >=80% bar is pass/fail-able from the artifact).

    Round 2/3 measured the 2-stage pipeline and the single-process engine
    in SEPARATE runs minutes apart on a shared host, and the ratio swung
    +-20pt with host load. Here the two are
    measured in INTERLEAVED PAIRED windows: each pair times one window of
    each back to back, alternating which side goes first, and the reported
    ratio is the MEDIAN of per-pair ratios. Host-load drift hits both
    sides of a pair near-equally and cancels in the per-pair ratio; the
    per-pair spread is reported alongside so the claim is falsifiable.

    The model is the `bench-pipe` preset (config.py): Qwen3 topology at a
    width where a decode step's compute dominates the inter-stage hop (the
    regime the north star grades) while a full paired run still finishes
    in minutes on a 1-core CPU host. The full-size flavor remains
    available as `--config pipeline-cpu --model qwen3-0.6b`.
    """
    import asyncio
    import statistics

    base_http, base_gossip = 16350, 17350
    with _two_stage_cluster(cfg_name, base_http, base_gossip) as procs:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from inferd_tpu.client.swarm_client import SwarmClient
        from inferd_tpu.config import SamplingConfig, get_config
        from inferd_tpu.core.generate import Engine
        from inferd_tpu.models import qwen3

        cfg = get_config(cfg_name)
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        engine = Engine(
            cfg, params, max_len=256, sampling_cfg=SamplingConfig(temperature=0.0)
        )
        prompt = list(range(3, 3 + 16))
        ptok = jnp.asarray([prompt], jnp.int32)

        def single_window(seed: int) -> float:
            t0 = time.perf_counter()
            np.asarray(engine.generate_scan(ptok, len(prompt), window, seed=seed))
            return window / (time.perf_counter() - t0)

        async def run():
            async with SwarmClient(
                [("127.0.0.1", base_http)],
                sampling=SamplingConfig(temperature=0.0),
            ) as c:
                await _cluster_warmup(c, prompt, window, procs=procs)

                async def pipe_window() -> float:
                    t0 = time.perf_counter()
                    out = await c.generate_ids(prompt, max_new_tokens=window)
                    return len(out) / (time.perf_counter() - t0)

                # single-side warmup (compiles the `window`-step scan) must
                # happen before any timed pair
                single_window(seed=0)
                r = await _paired_windows(single_window, pipe_window, pairs)
                return (*r, await _fetch_hop_p50(base_http))

        ratios, single_rates, pipe_rates, hop_p50 = asyncio.run(run())
        med, spread_pt = _ratio_stats(ratios)
        return {
            "metric": f"{cfg_name.replace('-', '_')}_pipeline2_paired_ratio",
            "value": round(med, 3),
            "unit": "pipeline/single tok_per_s ratio",
            "vs_baseline": round(med / 0.80, 3),  # >=1.0 passes the 80% bar
            "pipeline_tok_per_s": round(statistics.median(pipe_rates), 2),
            "single_process_tok_per_s": round(statistics.median(single_rates), 2),
            "pairs": pairs,
            "window_tokens": window,
            "ratio_spread_pt": spread_pt,
            "ratio_min": round(min(ratios), 3),
            "ratio_max": round(max(ratios), 3),
            # the full downstream relay round trip INCLUDING the next
            # stage's forward compute — NOT pure transport (the serving
            # stack's own wire cost is the separate framework_hop_ms leg)
            "relay_roundtrip_incl_compute_ms": hop_p50,
            "stages": 2,
            "workers": "2 local CPU node processes (stock node CLI), "
                       "interleaved paired windows",
        }


def bench_swarm_agg(
    cfg_name: str = "bench-pipe", sessions: int = 8, steps: int = 16,
    window_ms: float = 50.0,
):
    """Stage-level continuous batching through the SWARM pipeline: N
    concurrent sessions driven through a 2-stage local chain of stock-CLI
    node processes started with --stage-lanes (runtime/stage_batch), vs
    the SERIAL swarm baseline (the same cluster, the same sessions, one
    at a time — what every round before this one measured). Concurrent
    sessions' single-token decode steps co-batch into one device step per
    stage per arrival window, and same-next-hop co-batches relay as ONE
    coalesced envelope — so aggregate tok/s scales with concurrency
    instead of dividing by it. CPU-runnable (this is a serving-stack
    mechanism, not a chip mechanism); on TPU the same leg measures the
    real HBM-bound win.

    The serial side runs on the SAME cluster: a solo session never pays
    the arrival window (window.co_possible), so serial here equals the
    pre-batching swarm path, same processes, same compile state."""
    import asyncio

    base_http, base_gossip = 16650, 17650
    node_args = [
        "--stage-lanes", str(sessions), "--window-ms", str(window_ms),
        "--capacity", str(max(8, sessions)),
    ]
    with _two_stage_cluster(
        cfg_name, base_http, base_gossip, node_args=node_args
    ) as procs:
        from inferd_tpu.client.swarm_client import SwarmClient
        from inferd_tpu.config import SamplingConfig

        prompt = list(range(3, 3 + 16))

        async def exec_stats():
            import aiohttp

            try:
                async with aiohttp.ClientSession() as s:
                    async with s.get(
                        f"http://127.0.0.1:{base_http}/stats"
                    ) as r:
                        snap = await r.json()
                ex = snap.get("executor", {})
                return ex.get("batched_tokens", 0), ex.get("batched_steps", 0)
            except Exception:
                return None  # companion metric, best effort

        async def run():
            async with SwarmClient(
                [("127.0.0.1", base_http)],
                sampling=SamplingConfig(temperature=0.0),
            ) as c:
                await _cluster_warmup(c, prompt, steps, procs=procs)
                ref = await c.generate_ids(prompt, max_new_tokens=steps)

                # concurrent warm-up: compiles the co-batched decode step
                # and fills every lane once, so neither timed side pays a
                # compile
                await asyncio.gather(*(
                    c.generate_ids(prompt, max_new_tokens=steps)
                    for _ in range(sessions)
                ))

                # serial baseline: one session at a time (the solo session
                # skips the window wait entirely)
                t0 = time.perf_counter()
                serial_outs = []
                for _ in range(sessions):
                    serial_outs.append(
                        await c.generate_ids(prompt, max_new_tokens=steps)
                    )
                serial_agg = sessions * steps / (time.perf_counter() - t0)

                # concurrent co-batched side (co-batch counters diffed
                # around it so the serial phase's batches-of-one don't
                # dilute the reported mean)
                before = await exec_stats()
                t0 = time.perf_counter()
                conc_outs = await asyncio.gather(*(
                    c.generate_ids(prompt, max_new_tokens=steps)
                    for _ in range(sessions)
                ))
                conc_agg = sessions * steps / (time.perf_counter() - t0)
                after = await exec_stats()
                cobatch = None
                if before is not None and after is not None:
                    dt, ds = after[0] - before[0], after[1] - before[1]
                    cobatch = round(dt / ds, 2) if ds else None

                # token-exactness across BOTH paths (greedy, same prompt):
                # co-batching must never change what a session decodes
                for o in serial_outs + conc_outs:
                    if o != ref:
                        raise RuntimeError(
                            f"co-batched stream diverged: {o} != {ref}"
                        )
                return conc_agg, serial_agg, cobatch

        conc_agg, serial_agg, cobatch = asyncio.run(run())
        return {
            "metric": f"{cfg_name.replace('-', '_')}_swarm_agg_tok_per_s",
            "value": round(conc_agg, 2),
            "unit": "tok/s",
            # the headline ratio: concurrent aggregate over the serial
            # swarm baseline on the same cluster (>= 1 by construction of
            # the mechanism; the perf gate enforces the ordering)
            "vs_baseline": round(conc_agg / serial_agg, 3),
            "serial_tok_per_s": round(serial_agg, 2),
            "sessions": sessions,
            "steps_per_session": steps,
            "stages": 2,
            "window_ms": window_ms,
            "mean_cobatch": cobatch,
            "token_exact": True,
            "workers": "2 local CPU node processes (stock node CLI, "
                       "--stage-lanes continuous batching)",
        }


def bench_swarm_mixed(
    cfg_name: str = "bench-pipe", sessions: int = 6, steps: int = 6,
    waves: int = 3, window_ms: float = 25.0, block_size: int = 32,
    prefix_tokens: int = 256,
):
    """Paged-KV mixed workload: N sessions with MIXED prompt lengths, all
    sharing one pinned system prefix, churning over `waves` admission
    waves — through a single-stage stock-CLI node once with the dense
    lane slab and once with --paged-kv (block pool + CoW shared-prefix
    caching + chunked prefill) on an otherwise IDENTICAL cluster.

    The paged side's claim is structural: after the first wave seeds the
    prefix index, every later admission maps the shared region read-only
    (zero prefill FLOPs for it) while the dense side re-prefills every
    prompt every wave — so paged aggregate tok/s must be >= dense on the
    same hardware. Token-exactness is the hard bar: every stream (both
    sides, every wave) must equal the dense serial reference, or the leg
    errors and the perf gate fails hard."""
    import asyncio

    def mixed_prompts():
        prefix = [(i * 7 + 3) % 97 + 3 for i in range(prefix_tokens)]
        prompts = []
        for i in range(sessions):
            suf_len = 4 + (i * 9) % 29  # mixed 4..32-token suffixes
            prompts.append(
                prefix + [(i * 13 + j * 5 + 7) % 89 + 2
                          for j in range(suf_len)]
            )
        return prefix, prompts

    prefix, prompts = mixed_prompts()
    max_len = prefix_tokens + 64 + steps + 16
    results: dict = {}
    base_http, base_gossip = 16950, 17950

    for idx, (mode, extra) in enumerate((
        ("dense", []),
        ("paged", ["--paged-kv", str(block_size),
                   "--prefill-chunk", str(4 * block_size)]),
    )):
        node_args = [
            "--stage-lanes", str(sessions), "--window-ms", str(window_ms),
            "--capacity", str(max(8, sessions)),
            "--max-len", str(max_len), *extra,
        ]
        with _two_stage_cluster(
            cfg_name, base_http + 10 * idx, base_gossip + 10 * idx,
            node_args=node_args, stages=1,
        ) as procs:
            from inferd_tpu.client.swarm_client import SwarmClient
            from inferd_tpu.config import SamplingConfig

            port = base_http + 10 * idx

            async def stats():
                import aiohttp

                try:
                    async with aiohttp.ClientSession() as s:
                        async with s.get(
                            f"http://127.0.0.1:{port}/stats"
                        ) as r:
                            snap = await r.json()
                    return snap.get("executor", {})
                except Exception:
                    return {}  # companion metrics, best effort

            async def run():
                async with SwarmClient(
                    [("127.0.0.1", port)],
                    sampling=SamplingConfig(temperature=0.0),
                ) as c:
                    await _cluster_warmup(c, prompts[0], steps, procs=procs)
                    # seed the shared prefix (paged: registers/caches its
                    # blocks; dense: the same call for fairness) + compile
                    # every prompt-length bucket and the co-batched step
                    await c.generate_ids(prefix + [5], max_new_tokens=2)
                    await asyncio.gather(*(
                        c.generate_ids(p, max_new_tokens=steps)
                        for p in prompts
                    ))
                    # dense serial reference = the token-exactness bar
                    refs = []
                    for p in prompts:
                        refs.append(
                            await c.generate_ids(p, max_new_tokens=steps)
                        )
                    before = await stats()
                    t0 = time.perf_counter()
                    for _w in range(waves):
                        outs = await asyncio.gather(*(
                            c.generate_ids(p, max_new_tokens=steps)
                            for p in prompts
                        ))
                        for o, r in zip(outs, refs):
                            if o != r:
                                raise RuntimeError(
                                    f"{mode} stream diverged: {o} != {r}"
                                )
                    agg = (waves * sessions * steps
                           / (time.perf_counter() - t0))
                    after = await stats()
                    return agg, before, after, refs

            agg, before, after, refs = asyncio.run(run())
            pg = after.get("paged") or {}
            results[mode] = {
                "agg": agg,
                "refs": refs,
                "prefill_tokens": (
                    after.get("prefill_tokens", 0)
                    - before.get("prefill_tokens", 0)
                ),
                "prefix_hit_tokens": pg.get("prefix_hit_tokens", 0),
                "cow_shared": pg.get("cow_shared", 0),
                "blocks_used": pg.get("blocks_used", 0),
            }

    paged, dense = results["paged"], results["dense"]
    # cross-mode token-exactness: the paged path must decode the SAME
    # streams the dense path does, prompt for prompt (the in-wave checks
    # above only catch within-mode drift)
    if paged["refs"] != dense["refs"]:
        raise RuntimeError(
            "paged streams diverged from dense: "
            f"{paged['refs']} != {dense['refs']}"
        )
    return {
        "metric": f"{cfg_name.replace('-', '_')}_swarm_mixed_tok_per_s",
        "value": round(paged["agg"], 2),
        "unit": "tok/s",
        # the headline ratio the gate regresses on: paged aggregate over
        # dense on the same cluster config (dimensionless — portable
        # across hosts, like the multistep K-speedup)
        "vs_baseline": round(paged["agg"] / dense["agg"], 3),
        "paged_vs_dense": round(paged["agg"] / dense["agg"], 3),
        "dense_tok_per_s": round(dense["agg"], 2),
        "sessions": sessions,
        "steps_per_session": steps,
        "waves": waves,
        "prefix_tokens": prefix_tokens,
        "block_size": block_size,
        "window_ms": window_ms,
        "token_exact": True,
        # shared-prefix effectiveness: tokens the paged side actually
        # prefilled vs what the dense side recomputed for the same waves
        "paged_prefill_tokens": paged["prefill_tokens"],
        "dense_prefill_tokens": dense["prefill_tokens"],
        "prefix_hit_tokens": paged["prefix_hit_tokens"],
        "blocks_used": paged["blocks_used"],
        "cow_shared": paged["cow_shared"],
        "workers": "1 local CPU node process per mode (stock node CLI, "
                   "--stage-lanes; paged side adds --paged-kv "
                   "--prefill-chunk)",
    }


def bench_cache_affinity(
    cfg_name: str = "bench-pipe", groups: int = 6, per_group: int = 1,
    steps: int = 6, waves: int = 4, window_ms: float = 25.0,
    block_size: int = 32, prefix_tokens: int = 192, kv_blocks: int = 0,
):
    """Cache-affinity routing (ISSUE 13): a TWO-replica single-stage
    paged cluster serves `groups` shared-prefix session families over
    `waves` churn waves (every generation is a fresh session; only the
    pool's prefix index carries state across waves), once with DIGEST
    ROUTING ON — the entry pick is the real
    `control.path_finder.min_load_node` scored by the prompt's
    core.prefix.AffinityProbe against the replicas' gossiped `pfx`
    digests, read from live gossip via /stats — and once OFF (the
    round-robin scatter a digest-blind balancer produces), on separate
    but IDENTICAL clusters.

    The pool is sized so one replica cannot hold every family's prefix
    blocks: scattered placement keeps re-prefilling and evicting, while
    affinity placement converges family->replica and later waves map
    their prefixes read-only. The claim is the FLEET prefill-tokens-
    avoided (summed pool prefix_hit_tokens deltas): routing-on must
    strictly exceed routing-off on the same workload, and the
    dimensionless hit-rate ratio is the committed perf-gate prior.
    Token-exactness is the hard bar: every stream, every wave, both
    modes, both replicas must match (the paged prefix-hit path is
    token-exact by PR 8's contract — this leg re-proves it across
    replicas)."""
    import asyncio

    from inferd_tpu.control import path_finder as pflib
    from inferd_tpu.core import prefix as prefixlib

    sessions = groups * per_group
    lanes = sessions  # affinity may herd a whole wave onto one replica

    def build_prompts():
        out = []
        for g in range(groups):
            prefix = [(g * 97 + i * 7 + 3) % 89 + 3
                      for i in range(prefix_tokens)]
            for s in range(per_group):
                suf_len = 4 + (s * 9 + g * 5) % 25  # mixed 4..28 suffixes
                out.append(
                    prefix + [(g * 13 + s * 11 + j * 5 + 7) % 83 + 2
                              for j in range(suf_len)]
                )
        return out

    # contiguous group order + WAVE-ROTATED round-robin below (a real
    # digest-blind balancer keeps rotating; it does not restart at the
    # same replica every wave): the OFF baseline re-scatters every
    # family across both replicas wave after wave
    prompts = build_prompts()
    max_len = prefix_tokens + 64 + steps + 16
    if kv_blocks <= 0:
        # tight by construction: ONE replica can hold about HALF the
        # families' prefix chains (plus one live session's blocks) — so
        # converged (affinity) placement stays resident wave after wave
        # while scattered placement keeps evicting and re-prefilling.
        # Sessions within a wave run SEQUENTIALLY below, so live demand
        # is bounded at one chain and the pressure is exactly the
        # index-residency contest, never an allocation race.
        pblocks = prefix_tokens // block_size
        kv_blocks = pblocks * (max(1, groups // 2) + 1) + 12
    results: dict = {}
    base_http, base_gossip = 18950, 19950

    for idx, (mode, use_affinity) in enumerate(
        (("affinity", True), ("rr", False))
    ):
        node_args = [
            "--stage-lanes", str(lanes), "--window-ms", str(window_ms),
            "--capacity", str(max(8, sessions)),
            "--max-len", str(max_len),
            "--paged-kv", str(block_size), "--kv-blocks", str(kv_blocks),
            "--prefill-chunk", str(4 * block_size),
        ]
        with _two_stage_cluster(
            cfg_name, base_http + 10 * idx, base_gossip + 10 * idx,
            node_args=node_args, stages=1, extra_nodes=[(0, ())],
        ) as procs:
            from inferd_tpu.client.swarm_client import SwarmClient
            from inferd_tpu.config import SamplingConfig

            ports = [base_http + 10 * idx, base_http + 10 * idx + 1]

            async def stats(port):
                import aiohttp

                async with aiohttp.ClientSession() as s:
                    async with s.get(
                        f"http://127.0.0.1:{port}/stats"
                    ) as r:
                        return await r.json()

            async def fleet_counters():
                hit = pre = 0
                for port in ports:
                    ex = (await stats(port)).get("executor", {})
                    hit += (ex.get("paged") or {}).get(
                        "prefix_hit_tokens", 0
                    )
                    pre += ex.get("prefill_tokens", 0)
                return hit, pre

            async def stage0_map():
                # the live gossip view (any node's merged DHT snapshot
                # carries every replica's `pfx` digest + load)
                dht = (await stats(ports[0])).get("dht", {})
                return dht.get("0", dht.get(0, {}))

            async def pick_entry(i: int, wave: int, prompt) -> int:
                if not use_affinity:
                    return (i + wave) % 2
                stage_map = await stage0_map()
                probe = prefixlib.AffinityProbe(prompt)
                if not stage_map or all(
                    probe.depth_frac(v) <= 0.0 for v in stage_map.values()
                ):
                    # cold fleet: same scatter as the baseline — the
                    # bonus only ever steers toward an ACTUAL holder
                    return (i + wave) % 2
                _nid, val = pflib.min_load_node(stage_map, affinity=probe)
                return ports.index(int(val["port"]))

            async def run():
                clients = [
                    SwarmClient(
                        [("127.0.0.1", port)],
                        sampling=SamplingConfig(temperature=0.0),
                    )
                    for port in ports
                ]
                for c in clients:
                    await c.__aenter__()
                try:
                    # warm BOTH replicas with a NEUTRAL family (compiles
                    # the prefill buckets + decode step; its keys share
                    # nothing with the measured prompts)
                    warm = [(i * 17 + 5) % 71 + 2
                            for i in range(prefix_tokens + 8)]
                    await _cluster_warmup(
                        clients[0], warm, steps, procs=procs
                    )
                    await _cluster_warmup(
                        clients[1], warm, steps, procs=procs
                    )
                    # wait for digest gossip to surface both replicas
                    for _ in range(100):
                        if len(await stage0_map()) >= 2:
                            break
                        await asyncio.sleep(0.1)
                    before_hit, before_pre = await fleet_counters()
                    refs = None
                    picks_log = []
                    t0 = time.perf_counter()
                    for _w in range(waves):
                        picks, outs = [], []
                        # sequential within a wave: the pick must see the
                        # digest state the PREVIOUS session left behind
                        # (that is the steering being measured), and live
                        # pool demand stays one chain — the tight pool
                        # contests index residency, never admission
                        for i, p in enumerate(prompts):
                            k = await pick_entry(i, _w, p)
                            picks.append(k)
                            outs.append(await clients[k].generate_ids(
                                p, max_new_tokens=steps
                            ))
                        picks_log.append(picks)
                        if refs is None:
                            refs = outs
                        elif outs != refs:
                            raise RuntimeError(
                                f"{mode} streams diverged across waves: "
                                f"{outs} != {refs}"
                            )
                    wall = time.perf_counter() - t0
                    after_hit, after_pre = await fleet_counters()
                    return {
                        "refs": refs,
                        "saved": after_hit - before_hit,
                        "prefilled": after_pre - before_pre,
                        "agg": waves * sessions * steps / wall,
                        "picks": picks_log,
                    }
                finally:
                    for c in clients:
                        await c.__aexit__(None, None, None)

            results[mode] = asyncio.run(run())

    on, off = results["affinity"], results["rr"]
    if on["refs"] != off["refs"]:
        raise RuntimeError(
            "affinity-routed streams diverged from round-robin: "
            f"{on['refs']} != {off['refs']}"
        )
    frac = lambda r: r["saved"] / max(r["saved"] + r["prefilled"], 1)  # noqa: E731
    hit_on, hit_off = frac(on), frac(off)
    return {
        "metric": f"{cfg_name.replace('-', '_')}_cache_affinity_saved_tokens",
        "value": int(on["saved"]),
        "unit": "tokens",
        # the gate's dimensionless prior is hit_frac_on (0..1, machine-
        # portable): the off baseline legitimately bottoms out at ZERO
        # hits under rotation + a tight pool, so an on/off RATIO would be
        # unbounded and useless as a prior. The on-beats-off claim is the
        # gate's HARD invariant over saved_tokens_on/off instead;
        # vs_baseline displays the (clamped) ratio for humans.
        "vs_baseline": round(min(hit_on / max(hit_off, 1e-9), 999.0), 3),
        "hit_frac_prior": round(hit_on, 4),
        "saved_tokens_on": int(on["saved"]),
        "saved_tokens_off": int(off["saved"]),
        "prefill_tokens_on": int(on["prefilled"]),
        "prefill_tokens_off": int(off["prefilled"]),
        "hit_frac_on": round(hit_on, 4),
        "hit_frac_off": round(hit_off, 4),
        # wall-clock rates INCLUDING the bench's in-loop routing reads
        # (the ON side polls /stats once per pick — a harness transport
        # artifact; a production router scores its own in-process gossip
        # view): context only, never this leg's claim or a gate input
        "wall_tok_per_s_on": round(on["agg"], 2),
        "wall_tok_per_s_off": round(off["agg"], 2),
        "groups": groups,
        "sessions": sessions,
        "steps_per_session": steps,
        "waves": waves,
        "prefix_tokens": prefix_tokens,
        "block_size": block_size,
        "kv_blocks": kv_blocks,
        "token_exact": True,
        "workers": "2 stage-0 replicas per mode (stock node CLI, "
                   "--stage-lanes --paged-kv); entry picked per session "
                   "by min_load_node + AffinityProbe over live gossip "
                   "digests (on) vs round-robin (off)",
    }


def _write_tenant_adapters(cfg, out_dir: str, tenants: int, r: int = 4):
    """Synthetic peft-format tenant catalog: one adapter dir per tenant
    (deterministic per-tenant weights, STRONG enough to move greedy
    argmax — the token-exactness claim needs tenants whose streams
    actually differ). Returns the dir list in tenant order."""
    import numpy as np

    from inferd_tpu.ops import lora as loralib

    L, h, q = cfg.num_layers, cfg.hidden_size, cfg.q_dim
    kv, inter = cfg.kv_dim, cfg.intermediate_size
    dims = {
        "q_proj": (h, q), "v_proj": (h, kv),
        "gate_proj": (h, inter), "down_proj": (inter, h),
    }
    dirs = []
    for t in range(tenants):
        g = np.random.default_rng(1000 + t)
        layers = {
            name: (
                g.normal(0.0, 0.25, (L, din, r)).astype(np.float32),
                g.normal(0.0, 0.25, (L, r, dout)).astype(np.float32),
            )
            for name, (din, dout) in dims.items()
        }
        dirs.append(loralib.save_adapter(
            os.path.join(out_dir, f"tenant{t}"), layers, alpha=8, r=r,
        ))
    return dirs


def bench_lora_tenants(
    cfg_name: str = "tiny", tenants: int = 4, steps: int = 8,
    window_ms: float = 8.0, prompt_tokens: int = 12,
):
    """Multi-tenant LoRA serving (ISSUE 15): ONE single-stage replica
    (`--batch-lanes N --adapters d0,..,dN-1`, stock node CLI) serves N
    tenants, each generating with ITS OWN adapter via the per-session
    `adapter` envelope key.

    Two phases on the SAME cluster: CO-BATCHED — all N tenants decode
    concurrently, so heterogeneous-adapter decode steps coalesce into one
    gathered dispatch (the tentpole claim) — and SERIAL — the same N
    streams one tenant at a time (what N dedicated merged replicas would
    cost in device dispatches, minus their N-times weight memory). The
    headline is the dimensionless co-batch/serial aggregate ratio.

    Correctness is the hard bar: every tenant's stream must be TOKEN-
    EXACT vs an in-process solo reference serving the MERGED adapter
    (ops.lora.merge_adapter over the same split checkpoint) — the
    unmerged batched apply may not drift from the merged math — and the
    tenants' streams must actually differ (a degenerate base-model
    stream matching everything would prove nothing)."""
    import asyncio
    import shutil
    import tempfile

    import numpy as np

    from inferd_tpu.config import PRESETS
    from inferd_tpu.ops import lora as loralib

    cfg = PRESETS[cfg_name]
    work = tempfile.mkdtemp(prefix="bench_lora_")
    env = dict(os.environ, JAX_PLATFORMS="cpu", INFERD_DEVICE="cpu")
    base_http, base_gossip = 20950, 21950
    max_len = prompt_tokens + steps + 16
    procs = []
    try:
        adapter_dirs = _write_tenant_adapters(cfg, work, tenants)
        subprocess.run(
            [sys.executable, "-m", "inferd_tpu.tools.split_model",
             "--model", cfg_name, "--stages", "1",
             "--out", f"{work}/parts", "--random-init"],
            env=env, check=True, capture_output=True, timeout=600,
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "inferd_tpu.tools.run_node",
             "--model", cfg_name, "--num-stages", "1",
             "--stage", "0", "--parts", f"{work}/parts",
             "--device", "cpu", "--host", "127.0.0.1",
             "--port", str(base_http), "--gossip-port", str(base_gossip),
             "--bootstrap", "", "--name", "bench-lora-n0",
             "--batch-lanes", str(tenants),
             "--window-ms", str(window_ms),
             "--max-len", str(max_len),
             "--capacity", str(max(8, tenants)),
             "--adapters", ",".join(adapter_dirs)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        ))

        from inferd_tpu.client.swarm_client import SwarmClient
        from inferd_tpu.config import SamplingConfig

        # per-tenant prompts share a stem and diverge on one token, so
        # the co-batch window mixes adapters over near-identical shapes
        prompts = [
            [(i * 7 + 3) % 89 + 3 for i in range(prompt_tokens - 1)] + [3 + t]
            for t in range(tenants)
        ]

        async def run():
            import aiohttp

            clients = [
                SwarmClient(
                    [("127.0.0.1", base_http)],
                    sampling=SamplingConfig(temperature=0.0),
                    adapter=os.path.basename(adapter_dirs[t]),
                )
                for t in range(tenants)
            ]
            for c in clients:
                await c.__aenter__()
            try:
                # warm-up: compiles the prefill bucket + the adapter
                # decode graph, and pre-loads every tenant's slot
                for t, c in enumerate(clients):
                    await _cluster_warmup(
                        c, prompts[t], steps, procs=procs
                    )
                # CO-BATCHED: every tenant decodes concurrently — mixed-
                # adapter windows coalesce into single gathered dispatches
                t0 = time.perf_counter()
                cob = await asyncio.gather(*[
                    c.generate_ids(prompts[t], max_new_tokens=steps)
                    for t, c in enumerate(clients)
                ])
                cob_wall = time.perf_counter() - t0
                # SERIAL: the same tenant streams one at a time on the
                # same cluster (per-tenant serial baseline)
                t0 = time.perf_counter()
                ser = []
                for t, c in enumerate(clients):
                    ser.append(await c.generate_ids(
                        prompts[t], max_new_tokens=steps
                    ))
                ser_wall = time.perf_counter() - t0
                async with aiohttp.ClientSession() as s:
                    async with s.get(
                        f"http://127.0.0.1:{base_http}/stats"
                    ) as r:
                        stats = await r.json()
                return cob, cob_wall, ser, ser_wall, stats
            finally:
                for c in clients:
                    await c.__aexit__(None, None, None)

        cob, cob_wall, ser, ser_wall, stats = asyncio.run(run())

        # in-process MERGED references: the same split checkpoint with
        # each tenant's adapter merged the classic --lora way — the
        # batched UNMERGED path must reproduce every stream exactly
        from inferd_tpu.parallel import stages as stagelib
        from inferd_tpu.runtime.batch_executor import BatchedExecutor
        from inferd_tpu.utils.platform import force_platform

        force_platform("cpu")
        params, _spec, _name = stagelib.load_stage_checkpoint(
            stagelib.stage_checkpoint_path(f"{work}/parts", 0)
        )
        refs = []
        for t, adir in enumerate(adapter_dirs):
            merged = loralib.merge_adapter(
                params, loralib.load_adapter(cfg, adir)
            )
            ex = BatchedExecutor(cfg, merged, lanes=1, max_len=max_len)
            out = ex.process("ref", {
                "tokens": [prompts[t]], "start_pos": 0,
                "real_len": len(prompts[t]),
            })
            toks = [int(np.argmax(out["logits"][0]))]
            pos = len(prompts[t])
            for _ in range(steps - 1):
                o = ex.process("ref", {
                    "tokens": [[toks[-1]]], "start_pos": pos, "real_len": 1,
                })
                toks.append(int(np.argmax(o["logits"][0])))
                pos += 1
            ex.end_session("ref")
            refs.append(toks)

        exact = cob == refs and ser == refs
        if not exact:
            raise RuntimeError(
                f"tenant streams diverged from merged references: "
                f"cobatch={cob} serial={ser} refs={refs}"
            )
        distinct = len({tuple(s) for s in cob})
        if distinct < 2:
            raise RuntimeError(
                f"all {tenants} tenant streams identical ({cob[0]}) — "
                "the adapters are not discriminating; token-exactness "
                "would be vacuous"
            )
        astats = (stats.get("executor") or {}).get("adapters") or {}
        cob_agg = tenants * steps / cob_wall
        ser_agg = tenants * steps / ser_wall
        return {
            "metric": f"{cfg_name.replace('-', '_')}_lora_tenants_tok_per_s",
            "value": round(cob_agg, 2),
            "unit": "tok/s",
            # the gate's dimensionless prior AND hard ordering claim:
            # co-batched multi-adapter aggregate must strictly beat
            # serving the same tenants one at a time on the same device
            "vs_baseline": round(cob_agg / ser_agg, 3),
            "cobatch_vs_serial": round(cob_agg / ser_agg, 3),
            "serial_tok_per_s": round(ser_agg, 2),
            "tenants": tenants,
            "steps_per_tenant": steps,
            "prompt_tokens": prompt_tokens,
            "window_ms": window_ms,
            "token_exact": True,
            "distinct_streams": distinct,
            "adapter_loads": int(astats.get("loads", 0)),
            "adapter_resident": int(astats.get("resident", 0)),
            "adapter_evictions": int(astats.get("evictions", 0)),
            "workers": "1 local CPU node (stock CLI, --batch-lanes "
                       "--adapters): N tenants co-batched vs the same "
                       "streams serial; token-exact vs in-process merged "
                       "solo references",
        }
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        shutil.rmtree(work, ignore_errors=True)


def bench_canary(
    cfg_name: str = "bench-pipe", interval_s: float = 0.5,
    min_ok: int = 2, deadline_s: float = 120.0,
):
    """Canary-prober dryrun on a REAL 2-stage chain (obs.canary): both
    stock-CLI node processes start with --canary-interval, so each runs
    the low-rate synthetic /generate probe against the gossiped entry
    replicas through the real pipeline. The leg waits until the entry
    node's canary.ok counter shows probes completing end to end, then
    reports the probe counts + latency quantiles read back from the
    node's own canary.* series — and HARD-asserts the user-SLI
    separation: the probes' X-Inferd-Canary requests must not move
    generate.requests (synthetic load must never flatter or poison the
    numbers users are judged by)."""
    import asyncio

    base_http, base_gossip = 16850, 17850
    with _two_stage_cluster(
        cfg_name, base_http, base_gossip,
        node_args=["--canary-interval", str(interval_s)],
    ) as procs:
        from inferd_tpu.client.swarm_client import SwarmClient
        from inferd_tpu.config import SamplingConfig

        prompt = list(range(3, 3 + 8))

        async def run():
            import aiohttp

            async with SwarmClient(
                [("127.0.0.1", base_http)],
                sampling=SamplingConfig(temperature=0.0),
            ) as c:
                await _cluster_warmup(c, prompt, 4, procs=procs)
            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=10)
            ) as s:

                async def stats():
                    async with s.get(
                        f"http://127.0.0.1:{base_http}/stats"
                    ) as r:
                        return await r.json()

                before = await stats()
                deadline = time.monotonic() + deadline_s
                after = before
                while time.monotonic() < deadline:
                    _raise_if_dead(procs)
                    await asyncio.sleep(interval_s)
                    after = await stats()
                    if (
                        after["counters"].get("canary.ok", 0)
                        - before["counters"].get("canary.ok", 0)
                        >= min_ok
                    ):
                        break
                return before, after

        before, after = asyncio.run(run())
        cb, ca = before["counters"], after["counters"]
        ok = ca.get("canary.ok", 0) - cb.get("canary.ok", 0)
        probes = ca.get("canary.probes", 0) - cb.get("canary.probes", 0)
        fails = ca.get("canary.fail", 0) - cb.get("canary.fail", 0)
        if ok < min_ok:
            raise RuntimeError(
                f"canary probes never completed: {ok} ok / {probes} "
                f"attempted / {fails} failed within {deadline_s}s"
            )
        sli_moved = (
            ca.get("generate.requests", 0) - cb.get("generate.requests", 0)
        )
        if sli_moved:
            raise RuntimeError(
                f"user-SLI leak: {sli_moved} canary probe(s) counted into "
                "generate.requests despite the X-Inferd-Canary header"
            )
        wall = (after.get("histograms") or {}).get("canary.wall_ms") or {}
        ttft = (after.get("histograms") or {}).get("canary.ttft_ms") or {}
        return {
            "metric": f"{cfg_name.replace('-', '_')}_canary_probe_ok",
            "value": ok,
            "unit": "probes",
            "probes": probes,
            "fails": fails,
            "interval_s": interval_s,
            "wall_p50_ms": wall.get("p50_ms"),
            "ttft_p50_ms": ttft.get("p50_ms"),
            "user_sli_isolated": True,
            "workers": "2 local CPU node processes (stock node CLI, "
                       "--canary-interval probing)",
        }


def bench_overload(
    cfg_name: str = "bench-pipe", sessions: int = 4, steps: int = 6,
    waves: int = 3, deadline_s: float = 25.0,
    chaos: str = "drop=0.3,stall_p=0.15,seed=7", hop_timeout_s: float = 1.0,
):
    """Overload-containment leg (docs/SERVING.md 'Overload &
    reliability'): saturate a 2-stage chain whose stage-1 replica PAIR
    has one chaos-injected member (drop + slow-loris stall) and gate
    GOODPUT — tokens of generations that completed within their
    end-to-end deadline, per second — against an identical fault-free
    cluster.

    What the containment plane must deliver under this chaos:
      * goodput >= 70% of the fault-free run (deadline-clamped hop
        timeouts bound every stall; dead-peer cooldown steers fresh
        sessions off the sick replica; jittered budgeted retries redo
        dropped work without a storm);
      * ZERO requests hung past their deadline (+slack) — the deadline
        plane's whole point;
      * hedge extra load <= 5% (the ratio budget's guarantee);
      * every completed stream TOKEN-EXACT vs its own first run (greedy
        determinism across restarts — fast-but-wrong is not goodput).
    """
    import asyncio
    import random as _random

    HUNG_SLACK_S = 2.0  # scheduling + final-post grace past the deadline
    prompts = [
        [3 + i, 7, 11, 19 + i, 5, 2 + i, 13, 17]
        for i in range(sessions)
    ]
    base_http, base_gossip = 16750, 17750
    node_args = ["--hop-timeout", str(hop_timeout_s),
                 "--capacity", str(max(8, sessions))]
    results: dict = {}

    for idx, (mode, sick_args) in enumerate((
        ("fault_free", []),
        ("chaos", ["--chaos", chaos]),
    )):
        bh, bg = base_http + 20 * idx, base_gossip + 20 * idx
        with _two_stage_cluster(
            cfg_name, bh, bg, node_args=node_args,
            stages=2, extra_nodes=[(1, sick_args)],
        ) as procs:
            from inferd_tpu.client.swarm_client import SwarmClient
            from inferd_tpu.config import SamplingConfig

            async def stats():
                import aiohttp

                try:
                    async with aiohttp.ClientSession() as s:
                        async with s.get(
                            f"http://127.0.0.1:{bh}/stats"
                        ) as r:
                            return await r.json()
                except Exception:
                    return {}

            async def run():
                async with SwarmClient(
                    [("127.0.0.1", bh)],
                    sampling=SamplingConfig(temperature=0.0),
                ) as c:
                    await _cluster_warmup(c, prompts[0], steps, procs=procs)
                    # reference streams (also compiles every bucket);
                    # generous retries — this phase is setup, not metric
                    refs = []
                    for i, p in enumerate(prompts):
                        refs.append(await c.generate_ids(
                            p, max_new_tokens=steps, session_retries=10,
                            retry_delay_s=0.2,
                            retry_rng=_random.Random(100 + i),
                        ))
                    good_tokens = 0
                    hung = 0
                    failed = 0
                    exact = True

                    async def one(i, p, ref, seed):
                        s0 = time.perf_counter()
                        try:
                            out = await c.generate_ids(
                                p, max_new_tokens=steps,
                                deadline_s=deadline_s, session_retries=8,
                                retry_delay_s=0.2,
                                retry_rng=_random.Random(seed),
                            )
                        except Exception:
                            out = None
                        return out, time.perf_counter() - s0, ref

                    t0 = time.perf_counter()
                    for wave in range(waves):
                        outs = await asyncio.gather(*(
                            one(i, p, r, 1000 * wave + i)
                            for i, (p, r) in enumerate(zip(prompts, refs))
                        ))
                        for out, wall, ref in outs:
                            if wall > deadline_s + HUNG_SLACK_S:
                                hung += 1
                            if out is not None and wall <= deadline_s:
                                if out != ref:
                                    exact = False
                                good_tokens += len(out)
                            else:
                                failed += 1
                    wall = time.perf_counter() - t0
                    return good_tokens / wall, hung, failed, exact, (
                        await stats()
                    )

            goodput, hung, failed, exact, snap = asyncio.run(run())
            counters = snap.get("counters", {})
            overload = snap.get("overload", {})
            results[mode] = {
                "goodput": goodput, "hung": hung, "failed": failed,
                "exact": exact,
                "hedge_extra_frac": (
                    overload.get("hedge", {}).get("extra_frac", 0.0)
                ),
                "hedge_fired": counters.get("hedge.fired", 0),
                "hedge_won": counters.get("hedge.won", 0),
                "deadline_expired": counters.get("deadline.expired", 0),
                "peer_cooldowns": counters.get("peer.cooldown", 0),
                "sheds": counters.get("admission.shed", 0),
            }

    ff, ch = results["fault_free"], results["chaos"]
    token_exact = ff["exact"] and ch["exact"]
    if not token_exact:
        raise RuntimeError(
            "overload leg: a within-deadline stream diverged from its "
            "reference — fast-but-wrong is not goodput"
        )
    ratio = ch["goodput"] / ff["goodput"] if ff["goodput"] > 0 else 0.0
    return {
        "metric": f"{cfg_name.replace('-', '_')}_overload_goodput_tok_per_s",
        "value": round(ch["goodput"], 2),
        "unit": "tok/s",
        # the gate's headline: within-deadline goodput under chaos over
        # the fault-free run on an identical cluster (dimensionless —
        # portable across hosts like the multistep/paged ratios)
        "vs_baseline": round(ratio, 3),
        "goodput_ratio": round(ratio, 3),
        "fault_free_tok_per_s": round(ff["goodput"], 2),
        "hung_requests": ff["hung"] + ch["hung"],
        "failed_requests": ch["failed"],
        "fault_free_failed_requests": ff["failed"],
        "hedge_extra_frac": ch["hedge_extra_frac"],
        "hedge_fired": ch["hedge_fired"],
        "hedge_won": ch["hedge_won"],
        "deadline_expired": ch["deadline_expired"],
        "peer_cooldowns": ch["peer_cooldowns"],
        "token_exact": True,
        "sessions": sessions,
        "steps_per_session": steps,
        "waves": waves,
        "deadline_s": deadline_s,
        "hop_timeout_s": hop_timeout_s,
        "chaos": chaos,
        "workers": "2-stage CPU chain + 1 extra stage-1 replica per mode "
                   "(stock node CLI; chaos mode injects drop+stall on the "
                   "extra replica)",
    }


def bench_failover(
    cfg_name: str = "bench-pipe", steps: int = 24, ctx: int = 256,
    kill_at: int = 8, repl_interval_s: float = 0.15,
    hop_timeout_s: float = 2.0, block_size: int = 16,
):
    """Crash-failover leg (docs/SERVING.md 'Failover & durability'):
    SIGKILL the KV-holding replica mid-generation — no graceful stop,
    no drain handoff, the KV dies with the process — and measure what
    recovery costs with async standby replication ON vs OFF on an
    identical single-stage two-replica cluster (paged --batch-lanes,
    stock node CLI).

    ON: the survivor holds the session's replicated KV prefix (shipped
    block-aligned by the repl tick); the client's failed-over chunk
    triggers a standby PROMOTION and re-prefills only the tokens past
    the replication frontier (<= re_prefill_cap, the bounded RPO) — no
    client restart. OFF: today's path — the 409 restarts the whole
    generation and re-prefills the full prompt. Both modes must finish
    TOKEN-EXACT vs their own uninterrupted reference (greedy
    determinism across the failover); the headline is the dimensionless
    recovery_gain = recovery_off_ms / recovery_on_ms (the measured RTO
    win), gated against the committed prior like the overload/paged
    ratios."""
    import asyncio
    import random as _random

    prompt = [(7 * i + 3) % 311 + 2 for i in range(ctx)]
    base_http, base_gossip = 16950, 17950
    results: dict = {}

    for idx, (mode, extra_flags) in enumerate((
        ("repl_off", []),
        ("repl_on", ["--standby-repl", "--repl-interval",
                     str(repl_interval_s)]),
    )):
        bh, bg = base_http + 20 * idx, base_gossip + 20 * idx
        node_args = [
            "--batch-lanes", "4", "--paged-kv", str(block_size),
            "--hop-timeout", str(hop_timeout_s), "--capacity", "8",
            *extra_flags,
        ]
        with _two_stage_cluster(
            cfg_name, bh, bg, node_args=node_args, stages=1,
            extra_nodes=[(0, [])],
        ) as procs:
            from inferd_tpu.client.swarm_client import SwarmClient
            from inferd_tpu.config import SamplingConfig

            async def stats(port):
                import aiohttp

                try:
                    async with aiohttp.ClientSession() as s:
                        async with s.get(
                            f"http://127.0.0.1:{port}/stats"
                        ) as r:
                            return await r.json()
                except Exception:
                    return {}

            async def run():
                # warm BOTH replicas first (each compiles its own prefill
                # buckets + decode jits): the measurement is steady-state
                # failover onto a WARM survivor — production replicas are
                # compiled long before a peer crashes, and leaving B cold
                # would bill XLA compile time to whichever mode runs
                # first, not to the recovery paths under test
                async with SwarmClient(
                    [("127.0.0.1", bh + 1)],
                    sampling=SamplingConfig(temperature=0.0),
                ) as wc:
                    await _cluster_warmup(wc, prompt, steps, procs=procs)
                async with SwarmClient(
                    [("127.0.0.1", bh), ("127.0.0.1", bh + 1)],
                    sampling=SamplingConfig(temperature=0.0),
                ) as c:
                    await _cluster_warmup(c, prompt, steps, procs=procs)
                    # uninterrupted reference on the SAME cluster (also
                    # compiles every bucket): the kill run must reproduce
                    # exactly these ids through the failover
                    ref = await c.generate_ids(
                        prompt, max_new_tokens=steps, seed=11,
                        session_retries=6, retry_delay_s=0.25,
                        retry_rng=_random.Random(42),
                    )
                    arrive: dict = {}
                    state = {"idx": 0, "t_kill": None, "restarts": 0}

                    async def on_token(tok):
                        if tok is None:
                            # restart marker: previously streamed tokens
                            # are void, the deterministic re-run re-streams
                            state["restarts"] += 1
                            state["idx"] = 0
                            return
                        i = state["idx"]
                        state["idx"] = i + 1
                        arrive[i] = time.perf_counter()
                        if i + 1 == kill_at and state["t_kill"] is None:
                            # quiesce long enough for the replication tick
                            # to ship the frontier, then SIGKILL the KV
                            # holder. on_token runs BETWEEN steps, so no
                            # request is in flight: the kill lands at a
                            # deterministic point in the token stream.
                            await asyncio.sleep(4 * repl_interval_s)
                            procs[0].kill()
                            state["t_kill"] = time.perf_counter()

                    out = await c.generate_ids(
                        prompt, max_new_tokens=steps, seed=11,
                        session_retries=6, retry_delay_s=0.25,
                        retry_rng=_random.Random(13), on_token=on_token,
                    )
                    return ref, out, arrive, state, await stats(bh + 1)

            ref, out, arrive, state, snap = asyncio.run(run())
            if state["t_kill"] is None:
                raise RuntimeError(
                    f"failover leg ({mode}): the kill point was never "
                    f"reached ({state['idx']} of {kill_at} tokens)"
                )
            # recovery = kill -> the first NEW token past the kill point
            # (index kill_at; restarts re-stream earlier indices first
            # and overwrite, so this stamp is progress, not echo)
            rec_ms = (
                (arrive[kill_at] - state["t_kill"]) * 1e3
                if kill_at in arrive else None
            )
            counters = snap.get("counters", {})
            results[mode] = {
                "exact": out == ref,
                "recovery_ms": rec_ms,
                "restarts": state["restarts"],
                "promotions": counters.get("repl.promotions", 0),
                "resumed_tokens": counters.get("repl.resumed_tokens", 0),
                "tail_tokens": counters.get("repl.tail_tokens", 0),
                "stale": counters.get("repl.stale", 0),
            }

    off, on = results["repl_off"], results["repl_on"]
    if not (off["exact"] and on["exact"]):
        raise RuntimeError(
            "failover leg: a post-failover stream diverged from its "
            "reference — recovery must be token-exact in BOTH modes "
            f"(off={off['exact']}, on={on['exact']})"
        )
    if off["recovery_ms"] is None or on["recovery_ms"] is None:
        raise RuntimeError("failover leg: no post-kill token observed")
    # a full restart re-prefills the whole prompt per attempt; a
    # promotion re-prefills only the offered tail (the standby's own
    # counter — tokens between its frontier and the client's position)
    re_off = ctx * max(1, int(off["restarts"]))
    re_on = (
        int(on["tail_tokens"]) if on["promotions"]
        else ctx * max(1, int(on["restarts"]))
    )
    gain = (
        off["recovery_ms"] / on["recovery_ms"]
        if on["recovery_ms"] and on["recovery_ms"] > 0 else 0.0
    )
    return {
        "metric": f"{cfg_name.replace('-', '_')}_failover_recovery_ms",
        "value": round(on["recovery_ms"], 1),
        "unit": "ms",
        # the gate's headline: restart-recovery over promotion-recovery
        # on the same cluster (dimensionless — portable across hosts
        # like the multistep/paged/overload ratios); > 1 = replication
        # beats the full-restart baseline
        "vs_baseline": round(gain, 3),
        "recovery_gain": round(gain, 3),
        "recovery_off_ms": round(off["recovery_ms"], 1),
        "re_prefilled_on": int(re_on),
        "re_prefilled_off": int(re_off),
        # bounded RPO: the tail a promotion may re-prefill — one partial
        # block (never shipped: immutable-full-blocks-only) plus a tick's
        # worth of decode (quiesced before the kill, so ~a block)
        "re_prefill_cap": 2 * block_size,
        "promotions": int(on["promotions"]),
        "restarts_on": int(on["restarts"]),
        "restarts_off": int(off["restarts"]),
        "repl_resumed_tokens": int(on["resumed_tokens"]),
        "standby_stale": int(on["stale"]),
        "token_exact": True,
        "ctx": ctx,
        "steps_per_session": steps,
        "kill_at": kill_at,
        "repl_interval_s": repl_interval_s,
        "block_size": block_size,
        "workers": "single-stage CPU replica pair (stock node CLI, "
                   "--batch-lanes --paged-kv); SIGKILL the KV holder "
                   "mid-generation, continue on the survivor (standby "
                   "promotion vs full client restart)",
    }


def bench_pipeline_mesh_paired(
    cfg_name: str = "bench-pipe", pairs: int = 5, window: int = 12, pp: int = 2
):
    """The north-star ratio on the mechanism BASELINE config 2 actually
    grades: the in-mesh pipeline, where the inter-stage hop is a
    `lax.ppermute` inside ONE jitted SPMD program (runtime/mesh_executor
    serving path) instead of the process leg's HTTP hop. Same interleaved
    paired-window methodology as bench_pipeline_paired; the denominator is
    the single-device HOST-LOOP engine (the 1-chip serving shape — one
    dispatch per token, client-side sampling), so both sides pay the same
    per-token host costs and the ratio isolates the pipeline's hop tax.

    On CPU this runs over virtual devices (shard_map executes ranks
    serially on one core — the ratio measures program overhead, not
    parallel speedup); on a TPU pod slice the same code measures the real
    ICI hop. Single-chip TPU hosts can't run it (needs >= pp devices)."""
    import statistics

    import jax
    import numpy as np

    from inferd_tpu.config import SamplingConfig, get_config
    from inferd_tpu.core.generate import Engine
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.infer import PipelinedEngine

    devs = jax.devices()
    if len(devs) < pp:
        raise RuntimeError(f"pipeline-mesh needs {pp} devices, have {len(devs)}")
    cfg = get_config(cfg_name)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=pp), devs[:pp])
    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=1, batch=1, max_len=256
    )
    single = Engine(
        cfg, params, max_len=256, sampling_cfg=SamplingConfig(temperature=0.0)
    )
    prompt = list(range(3, 3 + 16))

    def single_window(seed: int) -> float:
        t0 = time.perf_counter()
        single.generate(prompt, max_new_tokens=window, seed=seed)
        return window / (time.perf_counter() - t0)

    def mesh_window() -> float:
        t0 = time.perf_counter()
        logits = eng.step_slot(0, np.asarray([prompt]), len(prompt), reset=True)
        out = [int(np.argmax(logits[0]))]
        pos = len(prompt)
        for _ in range(window - 1):
            logits = eng.step_slot(
                0, np.asarray([[out[-1]]]), 1, False, start_pos=pos
            )
            pos += 1
            out.append(int(np.argmax(logits[0])))
        return window / (time.perf_counter() - t0)

    single_window(0)  # compile both sides before any timed pair
    mesh_window()
    single_window(0)  # throwaway pair: first post-compile windows run cold
    mesh_window()  # (allocator/cache effects) and would skew the spread
    import asyncio

    ratios, single_rates, pipe_rates = asyncio.run(
        _paired_windows(single_window, mesh_window, pairs)
    )
    med, spread_pt = _ratio_stats(ratios)
    result = {
        "metric": f"{cfg_name.replace('-', '_')}_pipeline_mesh_pp{pp}_paired_ratio",
        "value": round(med, 3),
        "unit": "mesh-pipelined/single tok_per_s ratio",
        "vs_baseline": round(med / 0.80, 3),  # >=1.0 passes the 80% bar
        "pipelined_tok_per_s": round(statistics.median(pipe_rates), 2),
        "single_host_loop_tok_per_s": round(statistics.median(single_rates), 2),
        "pairs": pairs,
        "window_tokens": window,
        "ratio_spread_pt": spread_pt,
        "ratio_min": round(min(ratios), 3),
        "ratio_max": round(max(ratios), 3),
        "pp": pp,
        "hop": "lax.ppermute inside one jitted SPMD program",
    }
    if is_cpu():
        # Virtual CPU devices execute the pp ranks SERIALLY, so every
        # bubble tick's compute lands on the wall clock; a single session
        # (mb=1) uses mb*pp of the pp*(mb+pp-1) rank-ticks per pass and the
        # raw ratio is bounded by that fraction regardless of hop cost. On
        # parallel hardware ranks overlap and the raw ratio IS the real
        # number; here the normalized ratio isolates what the leg actually
        # grades on this substrate — hop + SPMD program overhead.
        frac = (1 * pp) / (pp * (1 + pp - 1))
        result["serial_emulation_ceiling"] = round(frac, 3)
        result["normalized_ratio"] = round(med / frac, 3)
        result["normalized_passes_80pct_bar"] = bool(med / frac >= 0.80)
    return result


def bench_pipelined(
    cfg_name: str, steps: int, pp: int, mb: int, tp: int = 1, ep: int = 1
):
    """In-mesh microbatched pipelined decode (PipelinedEngine) versus the
    single-device engine: aggregate tok/s over MB in-flight sequences.
    `tp` > 1 additionally runs each pipeline rank tensor-parallel; `ep` > 1
    shards a MoE config's experts (dense configs reject it)."""
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import SamplingConfig, get_config
    from inferd_tpu.core.generate import Engine
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel import mesh as meshlib
    from inferd_tpu.parallel.infer import PipelinedEngine

    devs = jax.devices()
    pp = min(pp, max(1, len(devs) // (tp * ep)))
    cfg = get_config(cfg_name)
    if cfg.num_layers % pp:
        pp = max(d for d in range(1, pp + 1) if cfg.num_layers % d == 0)
    mesh = meshlib.make_mesh(
        meshlib.MeshPlan(pp=pp, tp=tp, ep=ep), devs[: pp * tp * ep]
    )
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))

    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=mb, batch=1, max_len=256,
        sampling_cfg=SamplingConfig(temperature=0.0),
    )
    prompt_len = 16
    import numpy as np

    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=prompt_len)) for _ in range(mb)]
    eng.generate(prompts, max_new_tokens=2)  # compile
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new_tokens=steps)
    pipe_tps = sum(len(o) for o in out) / (time.perf_counter() - t0)

    single = Engine(cfg, params, max_len=256, sampling_cfg=SamplingConfig(temperature=0.0))
    ptok = jnp.asarray([prompts[0]], jnp.int32)
    np.asarray(single.generate_scan(ptok, prompt_len, steps))
    t0 = time.perf_counter()
    np.asarray(single.generate_scan(ptok, prompt_len, steps, seed=1))
    single_tps = steps / (time.perf_counter() - t0)

    return {
        "metric": (
            f"{cfg.name.replace('-', '_')}_pipelined_pp{pp}"
            + (f"_tp{tp}" if tp > 1 else "")
            + (f"_ep{ep}" if ep > 1 else "")
            + f"_mb{mb}_tok_per_s"
        ),
        "value": round(pipe_tps, 2),
        "unit": "tok/s",
        "vs_baseline": round(pipe_tps / single_tps, 3),
        "single_device_tok_per_s": round(single_tps, 2),
    }


def bench_batched(cfg_name: str, steps: int, lanes: int):
    """Continuous batching: aggregate decode tok/s over `lanes` concurrent
    sequences in ONE device step vs the single-sequence engine (weights are
    read once per batched step — the bs=1 bandwidth wall amortizes).

    Primary value = the batched device step rate, measured as a fused scan
    (batch `lanes`, one dispatch for the whole generation — the serving
    host loop pays a host dispatch and a sync per token, which measures the
    host, not the chip). The BatchedEngine serving loop —
    the same device step driven token-by-token with lane admission/refill —
    is reported alongside as serving_loop_tok_per_s."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inferd_tpu.config import SamplingConfig, get_config
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.generate import Engine

    from inferd_tpu.models import qwen3

    cfg = get_config(cfg_name)
    params = jax.block_until_ready(qwen3.init_params(cfg, jax.random.PRNGKey(0)))
    sc = SamplingConfig(temperature=0.0)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=16)) for _ in range(lanes)]

    # fused-scan batched decode: [lanes, S] prompts through one dispatch
    single = Engine(cfg, params, max_len=256, sampling_cfg=sc)
    btok = jnp.asarray(prompts, jnp.int32)
    np.asarray(single.generate_scan(btok, 16, steps))  # compile
    t0 = time.perf_counter()
    np.asarray(single.generate_scan(btok, 16, steps, seed=1))
    agg = lanes * steps / (time.perf_counter() - t0)

    # serving loop: same step, host-driven with admission/eviction/refill
    eng = BatchedEngine(cfg, params, lanes=lanes, max_len=256, sampling_cfg=sc)
    eng.generate_all(prompts, max_new_tokens=2)  # compile (drains + frees lanes)
    t0 = time.perf_counter()
    out = eng.generate_all(prompts, max_new_tokens=steps)
    loop_agg = sum(len(o) for o in out) / (time.perf_counter() - t0)

    # chunked serving loop: decode fused 32 steps per dispatch — the
    # per-dispatch host cost amortizes 32x; tokens are
    # bit-identical to the per-step loop (tests/test_batch.py)
    # warmup runs the FULL schedule so every pow2 tail size compiles too
    eng.generate_all(prompts, max_new_tokens=steps, chunk=32)
    t0 = time.perf_counter()
    out = eng.generate_all(prompts, max_new_tokens=steps, chunk=32)
    chunk_agg = sum(len(o) for o in out) / (time.perf_counter() - t0)

    ptok = jnp.asarray([prompts[0]], jnp.int32)
    np.asarray(single.generate_scan(ptok, 16, steps))
    t0 = time.perf_counter()
    np.asarray(single.generate_scan(ptok, 16, steps, seed=1))
    single_tps = steps / (time.perf_counter() - t0)

    return {
        "metric": f"{cfg.name.replace('-', '_')}_batched_lanes{lanes}_tok_per_s",
        "value": round(agg, 2),
        "unit": "tok/s",
        "vs_baseline": round(agg / single_tps, 3),
        "single_seq_tok_per_s": round(single_tps, 2),
        "serving_loop_tok_per_s": round(loop_agg, 2),
        "chunked_loop_tok_per_s": round(chunk_agg, 2),
        "lanes": lanes,
    }


def bench_spec(
    cfg_name: str = "bench-pipe", pairs: int = 5, window: int = 24,
    draft_layers: int = 0, k: int = 4, lanes: int = 4,
):
    """Speculative decoding leg (VERDICT r04 #1d): the lane-spec engine
    (core.spec_batch, greedy self-draft) vs the PLAIN per-token serving
    loop on the same model, interleaved-paired like the pipeline legs.

    HONESTY NOTE (carried in the JSON): weights are RANDOM-INIT, so the
    accept rate measures only the structural agreement between the
    target's own truncated prefix and its full stack on random weights —
    real-checkpoint accept rates (the engine's actual value) need the
    egress-gated real-weight artifact (run.sh --hf). The RATIO is still
    meaningful mechanics: per emitted token the spec side pays
    1 draft-scan + 1/(accepted+1) verify dispatches instead of one full
    forward dispatch.

    Also reports the CONCURRENT flavor: `lanes` sessions speculating in
    coalesced rounds (one draft scan + one verify per round for all of
    them) as spec_lanes{N}_agg_tok_per_s."""
    import asyncio
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from inferd_tpu.config import SamplingConfig, get_config
    from inferd_tpu.core.batch import BatchedEngine
    from inferd_tpu.core.generate import Engine
    from inferd_tpu.core.spec_batch import (
        LaneSpecRunner, generate_lanes, make_draft_cache,
    )
    from inferd_tpu.core.speculative import self_draft
    from inferd_tpu.models import qwen3

    cfg = get_config(cfg_name)
    draft_layers = draft_layers or max(1, cfg.num_layers // 4)
    params = jax.block_until_ready(
        qwen3.init_params(cfg, jax.random.PRNGKey(0))
    )
    sc = SamplingConfig(temperature=0.0)
    dcfg, dparams = self_draft(cfg, params, draft_layers)
    plain = Engine(cfg, params, max_len=256, sampling_cfg=sc)
    engine = BatchedEngine(cfg, params, lanes=lanes, max_len=256, sampling_cfg=sc)
    runner = LaneSpecRunner(cfg, dcfg, k=k)
    state = {"dcache": make_draft_cache(dcfg, lanes, 256)}
    prompt = list(range(3, 3 + 16))
    accept_rates = []

    def plain_window(seed: int) -> float:
        # the per-token serving loop: one device dispatch per token (the
        # regime speculation exists to beat)
        t0 = time.perf_counter()
        out = plain.generate(prompt, max_new_tokens=window)
        return len(out) / (time.perf_counter() - t0)

    def spec_window() -> float:
        t0 = time.perf_counter()
        outs, state["dcache"], rate = generate_lanes(
            engine, runner, params, dparams, state["dcache"], [prompt],
            max_new_tokens=window,
        )
        dt = time.perf_counter() - t0
        accept_rates.append(rate)
        return len(outs[0]) / dt

    # warmups compile both sides (plain loop + spec prefill/round)
    plain.generate(prompt, max_new_tokens=2)
    _, state["dcache"], warm_rate = generate_lanes(
        engine, runner, params, dparams, state["dcache"], [prompt],
        max_new_tokens=max(k + 2, 4),
    )
    ratios, plain_rates, spec_rates = asyncio.run(
        _paired_windows(plain_window, spec_window, pairs)
    )
    med, spread_pt = _ratio_stats(ratios)

    # the mechanism's CEILING on this substrate: a draft that always
    # agrees (draft == target) — real-checkpoint accept rates land the
    # ratio between `value` (random-weight floor) and this
    full_runner = LaneSpecRunner(cfg, cfg, k=k)
    full_state = {"dcache": make_draft_cache(cfg, lanes, 256)}

    def full_window() -> float:
        t0 = time.perf_counter()
        outs, full_state["dcache"], _ = generate_lanes(
            engine, full_runner, params, params, full_state["dcache"],
            [prompt], max_new_tokens=window,
        )
        return len(outs[0]) / (time.perf_counter() - t0)

    full_window()  # compile
    fr, _, _ = asyncio.run(_paired_windows(plain_window, full_window, 3))
    full_med, _ = _ratio_stats(fr)

    # concurrent flavor: `lanes` sessions' rounds coalesce — one draft
    # scan + one verify serves all of them
    many = [list(np.random.RandomState(i).randint(3, cfg.vocab_size - 1,
                                                  size=16)) for i in range(lanes)]
    outs, state["dcache"], lane_rate = generate_lanes(
        engine, runner, params, dparams, state["dcache"], many,
        max_new_tokens=4,
    )  # compile the all-lanes-active round shape
    t0 = time.perf_counter()
    outs, state["dcache"], lane_rate = generate_lanes(
        engine, runner, params, dparams, state["dcache"], many,
        max_new_tokens=window,
    )
    lanes_agg = sum(len(o) for o in outs) / (time.perf_counter() - t0)

    return {
        "metric": f"{cfg.name.replace('-', '_')}_spec_vs_plain_ratio",
        "value": round(med, 3),
        "unit": "speculative/plain per-token-loop tok_per_s ratio",
        "vs_baseline": round(med, 3),
        "spec_tok_per_s": round(statistics.median(spec_rates), 2),
        "plain_loop_tok_per_s": round(statistics.median(plain_rates), 2),
        "accept_rate": round(statistics.median(accept_rates), 3),
        "full_accept_ceiling_ratio": round(full_med, 3),
        "pairs": pairs,
        "window_tokens": window,
        "ratio_spread_pt": spread_pt,
        "draft_layers": draft_layers,
        "k": k,
        f"spec_lanes{lanes}_agg_tok_per_s": round(lanes_agg, 2),
        "weights": "random-init (accept_rate NOT representative of real "
                   "checkpoints; ratio mechanics are)",
    }


_CC_SCRIPT = r"""
import sys, time, json
from inferd_tpu.utils.platform import (
    enable_compile_cache, force_platform, require_platform,
)
force_platform(sys.argv[1])
cache = enable_compile_cache()
require_platform(sys.argv[1])
import jax
from inferd_tpu.config import SamplingConfig, get_config
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
cfg = get_config(sys.argv[2])
params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
eng = Engine(cfg, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
t0 = time.time()
eng.generate([3, 7, 11], max_new_tokens=2)  # prefill + decode jits
print(json.dumps({
    "time_to_first_tokens_s": round(time.time() - t0, 3),
    "hits": cache.hits, "cache_dir": cache.directory,
}))
"""


def bench_compile_cache(cfg_name: str = "bench-pipe", device: str = "cpu"):
    """Compile-cache warm/cold delta: two processes, one after the other,
    share the persistent cache; the second reports its persistent-cache HIT
    count (jax.monitoring — an auditable re-jit-avoided number, not a
    timing inference) plus the time-to-first-tokens delta on a real model
    engine. BASELINE config 4's timing half.

    The children go through utils.platform.enable_compile_cache like every
    CLI; this leg points them at a `bench_compile_cache/` subdirectory of
    wherever that rule keeps the cache (a fixed path — the cache's key
    includes it) and empties it first, so the first child is cold. On a
    chip the caller must hold no JAX backend: each child needs the chip."""
    import json as jsonlib
    import shutil

    from inferd_tpu.utils.platform import COMPILE_CACHE_ENV, compile_cache_dir

    d = os.path.join(compile_cache_dir(), "bench_compile_cache")
    shutil.rmtree(d, ignore_errors=True)
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", _CC_SCRIPT, device, cfg_name],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": device, COMPILE_CACHE_ENV: d,
                 "JAX_ENABLE_COMPILATION_CACHE": "true"},
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"compile-cache child failed: {r.stderr[-400:]}"
            )
        outs.append(jsonlib.loads(r.stdout.strip().splitlines()[-1]))
    cold, warm = outs
    return {
        "metric": f"{cfg_name.replace('-', '_')}_compile_cache_warm_cold",
        "value": round(cold["time_to_first_tokens_s"]
                       - warm["time_to_first_tokens_s"], 3),
        "unit": "s saved to first tokens (warm vs cold process)",
        "vs_baseline": None,
        "cold_time_to_first_tokens_s": cold["time_to_first_tokens_s"],
        "warm_time_to_first_tokens_s": warm["time_to_first_tokens_s"],
        "warm_cache_hits": warm["hits"],
        "cold_cache_hits": cold["hits"],
        "device": device,
    }


def bench_disagg_handoff(cfg_name: str = "bench-pipe", ctx: int = 384,
                         reps: int = 3):
    """Disaggregated prefill->decode handoff cost at a realistic KV size
    (VERDICT r04 #5): prefill `ctx` tokens on replica A, hand the session
    to replica B via /export_session, report the median server-measured
    handoff time + payload bytes. Two in-process nodes on loopback — the
    number is the FRAMEWORK cost (export + wire + import + adopt), the
    same work a cross-host handoff does minus the physical link."""
    import asyncio
    import statistics
    import tempfile

    import jax
    import numpy as np

    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.config import SamplingConfig, get_config
    from inferd_tpu.control.dht import SwarmDHT
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, split_and_save
    from inferd_tpu.runtime.node import Node, NodeInfo

    cfg = get_config(cfg_name)
    base = 16450
    with tempfile.TemporaryDirectory(prefix="bench_disagg_") as work:
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        split_and_save(params, cfg, Manifest.even_split(cfg.name, 1), work)

        def mk(idx):
            info = NodeInfo(
                name=f"dgb{idx}", host="127.0.0.1", port=base + idx,
                stage=0, num_stages=1, capacity=8, model_name=cfg.name,
            )
            dht = SwarmDHT(
                info.node_id, base + 100 + idx,
                bootstrap=[] if idx == 0 else [("127.0.0.1", base + 100)],
                host="127.0.0.1", gossip_period_s=0.05, ttl_s=10.0,
            )
            return Node(
                info, cfg, work, dht, backend="qwen3", max_len=ctx + 128,
                rebalance_period_s=600.0,
            )

        async def run():
            a, b = mk(0), mk(1)
            await a.start()
            await b.start()
            try:
                rng = np.random.RandomState(0)
                ms, nbytes = [], 0
                async with SwarmClient(
                    [("127.0.0.1", base)],
                    sampling=SamplingConfig(temperature=0.0),
                ) as c:
                    for r in range(reps + 1):  # +1 warmup (compiles)
                        sid = f"bench-disagg-{r}"
                        ids = rng.randint(3, cfg.vocab_size - 1, size=ctx)
                        pos = 0
                        for i in range(0, ctx, c.prefill_chunk):
                            chunk = [int(t) for t in ids[i:i + c.prefill_chunk]]
                            await c._step(sid, chunk, pos)
                            pos += len(chunk)
                        resp = await c._post(
                            "/export_session",
                            {"session_id": sid, "target_host": "127.0.0.1",
                             "target_port": base + 1},
                        )
                        if not resp.get("ok"):
                            raise RuntimeError(f"handoff declined: {resp}")
                        if r:  # skip the compile-warmup rep
                            ms.append(float(resp["ms"]))
                        nbytes = int(resp["bytes"])
                        await c._post_url(
                            f"http://127.0.0.1:{base + 1}/end_session",
                            {"session_id": sid, "stage": 0},
                        )
                return statistics.median(ms), nbytes
            finally:
                await a.stop()
                await b.stop()

        med_ms, nbytes = asyncio.run(run())
    return {
        "metric": f"{cfg.name.replace('-', '_')}_disagg_handoff_ms",
        "value": round(med_ms, 2),
        "unit": "ms per session handoff (export+wire+import+adopt)",
        "vs_baseline": None,
        "handoff_bytes": nbytes,
        "ctx_tokens": ctx,
        "reps": reps,
    }


def bench_prefill(cfg_name: str, reps: int, seq: int = 2048):
    """Prefill throughput (tokens/s ingesting a long prompt in one chunk) —
    the compute-bound counterpart of the decode benchmark; MFU framing
    against the chip's peak bf16 FLOPs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from inferd_tpu.config import get_config
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config(cfg_name)
    params = jax.block_until_ready(qwen3.init_params(cfg, jax.random.PRNGKey(0)))
    seq = min(seq, cfg.max_position_embeddings)
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (1, seq), 0, cfg.vocab_size, jnp.int32
    )
    cache0 = KVCache.create(cfg, cfg.num_layers, 1, seq, ring=False)

    @jax.jit
    def prefill(params, toks, k, v):
        logits, nk, nv = qwen3.forward(params, cfg, toks, None, k, v, jnp.int32(0))
        return logits[0, -1]

    np.asarray(prefill(params, toks, cache0.k, cache0.v))  # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(prefill(params, toks, cache0.k, cache0.v))  # jaxlint: disable=J003 -- materializing the result IS the timed quantity
        times.append(time.perf_counter() - t0)
    tps = seq / min(times)

    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    result = {
        "metric": f"{cfg.name.replace('-', '_')}_prefill_tok_per_s",
        "value": round(tps, 2),
        "unit": "tok/s",
        "vs_baseline": None,
        "seq_len": seq,
        "model_params": n_params,
    }
    if is_tpu():
        from inferd_tpu.perf import roofline as rl

        chip = rl.detect_chip()  # one audited chip-spec table (perf/roofline)
        flops_per_tok = 2.0 * n_params  # matmul FLOPs, attention excluded
        result["mfu"] = round(
            tps * flops_per_tok / (chip.peak_bf16_tflops * 1e12), 4
        )
        result["roofline_chip"] = chip.key
    return result


FLASH_T = 8192  # KV buffer length for the flash config (one metric name)


def bench_flash(steps: int):
    """Flash kernel vs XLA attention on decode shapes (1 query over a long
    KV buffer). On TPU this validates the Mosaic compile on hardware."""
    import jax
    import jax.numpy as jnp

    from inferd_tpu.ops import attention as att

    on_tpu = is_tpu()
    b, nq, nkv, d = 1, 16, 8, 128
    t = FLASH_T
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, 1, nq, d), dt)
    k = jax.random.normal(key, (b, t, nkv, d), dt)
    v = jax.random.normal(key, (b, t, nkv, d), dt)
    kv_len = jnp.int32(t - 5)
    q_start = jnp.full((b,), t - 5, jnp.int32)

    from inferd_tpu.models.qwen3 import gqa_attention

    flash = lambda q, k, v: att.flash_gqa(
        q, k, v, q_start=q_start, kv_len=kv_len,
        interpret=not on_tpu, stream=False)
    flash_stream = lambda q, k, v: att.flash_gqa(
        q, k, v, q_start=q_start, kv_len=kv_len,
        interpret=not on_tpu, stream=True)
    xla = lambda q, k, v: gqa_attention(
        q, k, v, jnp.broadcast_to(q_start[:, None], (b, 1)), kv_len)

    import numpy as np

    fo = jax.block_until_ready(jax.jit(flash)(q, k, v))
    so = jax.block_until_ready(jax.jit(flash_stream)(q, k, v))
    xo = jax.block_until_ready(jax.jit(xla)(q, k, v))
    err = float(jnp.max(jnp.abs(fo.astype(jnp.float32) - xo.astype(jnp.float32))))
    err_s = float(jnp.max(jnp.abs(so.astype(jnp.float32) - xo.astype(jnp.float32))))

    from inferd_tpu.utils.profiling import chained_attention_rate

    def timeit(fn, n=steps):
        # dispatch-overhead-free timing shared with tools/sweep_attn (ONE
        # definition of the harness that sets the dispatch policy)
        return chained_attention_rate(fn, q, k, v, n)

    f_rate, s_rate, x_rate = timeit(flash), timeit(flash_stream), timeit(xla)
    return {
        "metric": f"flash_gqa_decode_t{t}_calls_per_s",
        "value": round(f_rate, 2),
        "unit": "calls/s",
        "vs_baseline": round(f_rate / x_rate, 3),
        "xla_calls_per_s": round(x_rate, 2),
        "stream_calls_per_s": round(s_rate, 2),  # no-VMEM-cap long-context kernel
        "max_abs_err_vs_xla": err,
        "stream_max_abs_err_vs_xla": err_s,
        "kernel_mode": "mosaic" if on_tpu else "interpret",
    }


def _default_run_extras() -> dict:
    """North-star proxy legs merged into the default `python bench.py
    --device cpu` run's single JSON line (the config-1 ratio must reach the
    artifact, not live in prose):

      * pipeline_ratio — the interleaved-paired 2-stage-pipeline /
        single-process ratio (bench_pipeline_paired), with its spread, so
        the >=80% bar (BASELINE.json:5) is pass/fail-able from the
        artifact;
      * batched — the continuous-batching aggregate (bench-pipe flavor).

    CPU only: the pipeline legs start node processes pinned to the CPU and
    the in-mesh leg needs two devices, so beside a decode measured on one
    chip they would mix devices in one line (main() skips them there).

    Never fatal: each leg degrades to an *_error field; the primary decode
    metric always survives."""
    extras = {}

    def leg(name, fn):
        try:
            fn()
        except Exception as e:
            import traceback

            traceback.print_exc(file=sys.stderr)
            extras[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]

    def pipeline():
        r = bench_pipeline_paired()
        extras["pipeline_ratio"] = r["value"]
        extras["pipeline_ratio_spread_pt"] = r["ratio_spread_pt"]
        # not `hop_p50_ms`: the value includes the downstream stage's
        # forward compute, and next to framework_hop_ms that name was
        # misread as transport
        extras["relay_roundtrip_incl_compute_ms"] = r[
            "relay_roundtrip_incl_compute_ms"
        ]
        extras["pipeline_passes_80pct_bar"] = bool(r["value"] >= 0.80)
        extras["pipeline"] = r

    def framework_hop():
        # the serving stack's own hop cost, compute-free — the bound that
        # separates "framework overhead" from "host timesharing" in the
        # pipeline ratio above
        r = bench_hop_overhead()
        extras["framework_hop_ms"] = r["framework_relay_hop_ms"]
        extras["framework_roundtrip_ms"] = r["framework_roundtrip_ms"]

    def pipeline_mesh():
        # the in-mesh flavor (ppermute hop — BASELINE config 2's mechanism)
        # on 2 virtual CPU devices in-process
        r = bench_pipeline_mesh_paired(pairs=7)
        extras["pipeline_mesh_ratio"] = r["value"]
        extras["pipeline_mesh_spread_pt"] = r["ratio_spread_pt"]
        extras["pipeline_mesh_normalized_ratio"] = r.get("normalized_ratio")
        extras["pipeline_mesh_passes_80pct_bar"] = bool(
            r.get("normalized_ratio", r["value"]) >= 0.80
        )
        extras["pipeline_mesh"] = r

    def batched():
        res = bench_batched("bench-pipe", steps=16, lanes=8)
        res["device"] = "cpu"
        extras["batched_agg_tok_per_s"] = res.get("value")
        extras["batched_vs_single"] = res.get("vs_baseline")
        extras["batched"] = res

    def spec():
        # random weights: the ratio's mechanics are representative, the
        # acceptance rate is not (see bench_spec)
        res = bench_spec(pairs=5)
        res["device"] = "cpu"
        extras["spec_vs_plain_ratio"] = res.get("value")
        extras["spec_accept_rate_random_weights"] = res.get("accept_rate")
        extras["spec"] = res

    def disagg():
        # disaggregated prefill->decode handoff cost at a realistic KV
        # size (framework cost: export + wire + import + adopt)
        r = bench_disagg_handoff()
        extras["disagg_handoff_ms"] = r["value"]
        extras["disagg_handoff_bytes"] = r["handoff_bytes"]
        extras["disagg"] = r

    def compile_cache():
        # warm/cold witness: cache hits + time-to-first-tokens delta
        # across two processes sharing a cache dir
        r = bench_compile_cache(device="cpu")
        extras["compile_cache_saved_s"] = r["value"]
        extras["compile_cache_warm_hits"] = r["warm_cache_hits"]
        extras["compile_cache"] = r

    leg("pipeline", pipeline)
    leg("framework_hop", framework_hop)
    leg("pipeline_mesh", pipeline_mesh)
    leg("batched", batched)
    leg("spec", spec)
    leg("disagg", disagg)
    leg("compile_cache", compile_cache)
    return extras


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--device", default="tpu", choices=["cpu", "tpu"],
        help="platform the bench runs on, in this process; tpu fails when "
        "JAX finds no chip (never a CPU measurement in its place)",
    )
    ap.add_argument(
        "--config", default="decode",
        choices=["decode", "decode-multistep", "pipeline-cpu",
                 "pipeline-paired", "pipeline-mesh",
                 "pipelined", "flash", "batched", "prefill", "spec",
                 "compile-cache", "swarm-agg", "swarm-mixed", "canary",
                 "overload", "cache-affinity", "failover", "lora-tenants",
                 "kernels"],
    )
    ap.add_argument("--kill-at", type=int, default=0,
                    help="failover: kill the KV holder after this many "
                    "generated tokens (0 = steps // 3)")
    ap.add_argument("--deadline-s", type=float, default=25.0,
                    help="overload: per-generation end-to-end deadline")
    ap.add_argument("--chaos", default="drop=0.3,stall_p=0.15,seed=7",
                    help="overload: chaos spec injected on the extra "
                    "stage-1 replica (utils/chaos.py syntax)")
    ap.add_argument("--waves", type=int, default=3,
                    help="swarm-mixed: admission waves (session churn)")
    ap.add_argument("--prefix-tokens", type=int, default=0,
                    help="swarm-mixed: shared system-prefix length "
                    "(0 = config default)")
    ap.add_argument("--k-sweep", default="1,4,8,16",
                    help="decode-multistep: comma-separated K values "
                    "(tokens per dispatch) to sweep")
    ap.add_argument("--tiny", action="store_true", help="tiny model (CPU smoke run)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--pp", type=int, default=4, help="pipelined: mesh depth")
    ap.add_argument("--mb", type=int, default=8, help="pipelined: microbatch slots")
    ap.add_argument("--tp", type=int, default=1,
                    help="pipelined: tensor-parallel width per pipeline rank")
    ap.add_argument("--ep", type=int, default=1,
                    help="pipelined: expert-parallel width (MoE configs)")
    ap.add_argument("--model", default="",
                    help="config preset override (default: qwen3-0.6b, or "
                    "tiny with --tiny; e.g. qwen3-moe-30b-a3b, tiny-moe)")
    ap.add_argument("--ctx", type=int, default=0,
                    help="decode: long-context mode — prefill this many "
                    "prompt tokens, then measure decode over that cache")
    ap.add_argument("--kv-dtype", default="model",
                    help="decode: KV cache storage dtype (e.g. "
                    "float8_e4m3fn halves the KV read at long ctx)")
    ap.add_argument(
        "--quant", default="none", choices=["none", "int8", "w8a8", "int8-kernel", "int4"],
        help="decode config: weight-only int8 (dequant-in-dot), dynamic "
        "w8a8, or int8-kernel (Pallas w8a16 matmul)",
    )
    ap.add_argument(
        "--lanes", type=int, default=8, help="batched: concurrent session lanes",
    )
    ap.add_argument("--pairs", type=int, default=5,
                    help="pipeline-paired: number of interleaved pairs")
    ap.add_argument("--pair-window", type=int, default=12,
                    help="pipeline-paired: tokens per measurement window")
    ap.add_argument("--no-extras", action="store_true",
                    help="skip the default run's pipeline-ratio/batched legs")
    args = ap.parse_args()
    platform = args.device
    # configs whose legs start node processes: those nodes are pinned to
    # --device cpu (several processes cannot share one chip), so the config
    # has no chip form and says so instead of measuring the CPU under a
    # tpu request
    cpu_process_configs = (
        "pipeline-cpu", "pipeline-paired", "swarm-agg", "swarm-mixed",
        "canary", "overload", "cache-affinity", "failover", "lora-tenants",
    )
    if platform == "tpu" and args.config in cpu_process_configs:
        emit({
            "metric": args.config, "value": None, "vs_baseline": None,
            "device": platform,
            "error": f"--config {args.config} starts node processes pinned "
                     "to the CPU and has no chip form; pass --device cpu",
        })
        sys.exit(2)
    # the default CPU run carries the north-star proxy legs in the same
    # JSON line; beside an on-chip decode they would mix devices
    want_extras = (
        args.config == "decode" and platform == "cpu" and not args.no_extras
    )
    if platform == "cpu" and (want_extras or args.config == "pipeline-mesh"):
        # the in-mesh paired leg needs >= 2 devices in THIS process; the
        # flag must be set before jax's backend initializes here
        n = args.pp if args.config == "pipeline-mesh" else 2
        if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""
        ):
            os.environ["XLA_FLAGS"] = (
                f"{os.environ.get('XLA_FLAGS', '')} "
                f"--xla_force_host_platform_device_count={n}"
            ).strip()
    note = (
        "multi-process CPU config" if args.config in cpu_process_configs
        else ""
    )
    if (
        args.config == "pipelined"
        and platform == "cpu"
        and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
    ):
        # a pp(x tp) mesh needs multiple devices; on CPU use virtual ones
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} "
            f"--xla_force_host_platform_device_count={args.pp * args.tp * args.ep}"
        ).strip()

    cfg_name = args.model or ("tiny" if args.tiny else "qwen3-0.6b")
    try:
        from inferd_tpu.utils.platform import force_platform, require_platform

        force_platform(platform)
        if args.config != "compile-cache":
            # (that leg's two children each need the chip to themselves:
            # this process must not hold a backend)
            require_platform(platform)
        if args.config == "decode":
            result = bench_decode(
                cfg_name, args.steps, args.reps, args.quant,
                ctx=args.ctx, kv_dtype=args.kv_dtype,
            )
        elif args.config == "decode-multistep":
            ks = tuple(
                int(x) for x in args.k_sweep.split(",") if x.strip()
            )
            result = bench_decode_multistep(
                cfg_name, args.steps, args.reps, ks=ks, quant_mode=args.quant,
            )
        elif args.config == "pipeline-cpu":
            result = bench_pipeline_cpu(cfg_name, args.steps)
        elif args.config == "pipeline-paired":
            result = bench_pipeline_paired(
                args.model or "bench-pipe", args.pairs, args.pair_window
            )
        elif args.config == "pipeline-mesh":
            result = bench_pipeline_mesh_paired(
                args.model or "bench-pipe", args.pairs, args.pair_window,
                pp=args.pp,
            )
        elif args.config == "pipelined":
            result = bench_pipelined(
                cfg_name, args.steps, args.pp, args.mb, args.tp, args.ep
            )
        elif args.config == "batched":
            result = bench_batched(cfg_name, args.steps, args.lanes)
        elif args.config == "swarm-agg":
            result = bench_swarm_agg(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                sessions=args.lanes,
                steps=min(args.steps, 16) if args.tiny else args.steps,
            )
        elif args.config == "swarm-mixed":
            result = bench_swarm_mixed(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                sessions=min(args.lanes, 4) if args.tiny else args.lanes,
                steps=min(args.steps, 6) if args.tiny else args.steps,
                waves=args.waves,
                block_size=16 if args.tiny else 32,
                prefix_tokens=args.prefix_tokens
                or (192 if args.tiny else 256),
            )
        elif args.config == "cache-affinity":
            result = bench_cache_affinity(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                steps=min(args.steps, 6) if args.tiny else args.steps,
                waves=args.waves,
                block_size=16 if args.tiny else 32,
                prefix_tokens=args.prefix_tokens
                or (96 if args.tiny else 192),
            )
        elif args.config == "lora-tenants":
            result = bench_lora_tenants(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                tenants=min(args.lanes, 4) if args.tiny else args.lanes,
                steps=min(args.steps, 8) if args.tiny else args.steps,
            )
        elif args.config == "canary":
            result = bench_canary(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
            )
        elif args.config == "kernels":
            result = bench_kernels(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                steps=min(args.steps, 6) if args.tiny else args.steps,
            )
        elif args.config == "overload":
            result = bench_overload(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                sessions=min(args.lanes, 4) if args.tiny else args.lanes,
                steps=min(args.steps, 6) if args.tiny else args.steps,
                waves=args.waves,
                deadline_s=args.deadline_s,
                chaos=args.chaos,
            )
        elif args.config == "failover":
            fo_steps = min(args.steps, 16) if args.tiny else args.steps
            result = bench_failover(
                args.model or ("tiny" if args.tiny else "bench-pipe"),
                steps=fo_steps,
                ctx=args.ctx or (96 if args.tiny else 256),
                kill_at=args.kill_at or max(4, fo_steps // 3),
                block_size=16,
            )
        elif args.config == "spec":
            result = bench_spec(args.model or "bench-pipe", args.pairs)
        elif args.config == "compile-cache":
            result = bench_compile_cache(
                args.model or "bench-pipe", device=platform
            )
        elif args.config == "prefill":
            result = bench_prefill(cfg_name, args.reps)
        else:
            result = bench_flash(args.steps)
        result["device"] = platform
        if note:
            result["note"] = note
        if want_extras:
            result.update(_default_run_extras())
        emit(result)
    except Exception as e:  # never a bare stack trace on stdout
        import traceback

        traceback.print_exc(file=sys.stderr)
        failed_metric = {
            "decode": f"{cfg_name.replace('-', '_')}_decode_tok_per_s_bs1",
            "decode-multistep":
                f"{cfg_name.replace('-', '_')}_decode_multistep_tok_per_s_bs1",
            "pipeline-cpu": f"{cfg_name.replace('-', '_')}_pipeline2_cpu_tok_per_s",
            "pipeline-paired": f"{(args.model or 'bench-pipe').replace('-', '_')}"
                               "_pipeline2_paired_ratio",
            "pipeline-mesh": f"{(args.model or 'bench-pipe').replace('-', '_')}"
                             f"_pipeline_mesh_pp{args.pp}_paired_ratio",
            "pipelined": f"{cfg_name.replace('-', '_')}_pipelined_tok_per_s",
            "batched": f"{cfg_name.replace('-', '_')}_batched_lanes{args.lanes}_tok_per_s",
            "spec": f"{(args.model or 'bench-pipe').replace('-', '_')}"
                    "_spec_vs_plain_ratio",
            "compile-cache": f"{(args.model or 'bench-pipe').replace('-', '_')}"
                             "_compile_cache_warm_cold",
            "prefill": f"{cfg_name.replace('-', '_')}_prefill_tok_per_s",
            "flash": f"flash_gqa_decode_t{FLASH_T}_calls_per_s",
            "swarm-agg": f"{(args.model or ('tiny' if args.tiny else 'bench-pipe')).replace('-', '_')}"
                         "_swarm_agg_tok_per_s",
            "swarm-mixed": f"{(args.model or ('tiny' if args.tiny else 'bench-pipe')).replace('-', '_')}"
                           "_swarm_mixed_tok_per_s",
            "overload": f"{(args.model or ('tiny' if args.tiny else 'bench-pipe')).replace('-', '_')}"
                        "_overload_goodput_tok_per_s",
            "cache-affinity": f"{(args.model or ('tiny' if args.tiny else 'bench-pipe')).replace('-', '_')}"
                              "_cache_affinity_saved_tokens",
            "failover": f"{(args.model or ('tiny' if args.tiny else 'bench-pipe')).replace('-', '_')}"
                        "_failover_recovery_ms",
            "lora-tenants": f"{(args.model or ('tiny' if args.tiny else 'bench-pipe')).replace('-', '_')}"
                            "_lora_tenants_tok_per_s",
            "kernels": "kernels_min_bytes_ratio",
        }[args.config]
        emit({
            "metric": failed_metric,
            "value": None,
            "unit": {"flash": "calls/s", "kernels": "ratio"}.get(
                args.config, "tok/s"),
            "vs_baseline": None,
            "device": platform,
            "error": f"{type(e).__name__}: {e}"[:400],
            "note": note,
        })
        sys.exit(1)


if __name__ == "__main__":
    main()
