"""Hardware sweep: flash kernels vs XLA attention across shapes — and,
since round 6, the POPULATOR for the perf/autotune dispatch registry.

Times each path with N calls chained inside one jitted scan (serial data
dependency; one materialization) so the per-dispatch host cost doesn't
pollute the numbers. Prints one JSON line per (shape, path).

This sweep originally set the frozen `auto` dispatch policy in
ops/attention.flash_enabled (_XLA_SCORE_BUDGET). With `--populate`, each
shape's measured winner is instead RECORDED in the autotune registry
(perf/autotune.py; bench_artifacts/autotune.json by default), which the
`auto` dispatch consults per (chip, shape, dtype) — so a new TPU
generation's sweep changes dispatch by committing a measurement artifact,
not by editing a constant. The frozen heuristic remains the cold-registry
fallback.

Usage: JAX_PLATFORMS=tpu python -m inferd_tpu.tools.sweep_attn \
           [--gemma] [--ckv] [--populate] [--int4]

--gemma sweeps the Gemma-2 attention recipe (softcap 50, scale 256**-0.5)
with window 0 (global layer) and 4096 (sliding layer). The structural
question for dispatch policy: past what T does the kernels' window-bounded
kv loop (O(window) compute) overtake XLA's O(T) full-buffer pass on the
sliding layers?

--ckv additionally sweeps COMPRESSED-KV decode shapes (fp8 K/V buffers,
bf16 queries) — the combination the frozen heuristic refuses to route to
the kernels (Mosaic narrow-load caution) and therefore the one only a
measurement can enable (VERDICT r05 weak #3). Since round 7 the sweep's
"xla" side at decode shapes IS the fused S=1 fast path
(ops/attention.decode_gqa — dequant-fused compressed-KV upcast, no
S-broadcast intermediates): gqa_attention routes every single-query call
through it, so the recorded winners grade the path production decode
actually runs.

--quant times bf16 against every weight-quant CLI flag on decode-shaped
matvecs and records the rates (registry key quant_decode|<chip>), so
ops.quant.apply_quant_mode can warn whenever a requested flag was
measured slower than bf16 on this chip — the r05 "quant slower than
bf16" inversion can stand, but never silently.

--int4 times the two Int4Weight contraction schemes (grouped vs dequant,
ops/quant._int4_mode) on decode-shaped matvecs and records the chip's
winner under the registry's int4_mode key.
"""
import argparse
import json

import jax
import jax.numpy as jnp

from inferd_tpu.models.qwen3 import gqa_attention
from inferd_tpu.ops import attention as att

from inferd_tpu.utils.profiling import chained_attention_rate as timeit_chained


def timeit(fn, q, k, v, n):
    # shared harness (utils.profiling): ONE definition of the trick that
    # defeats XLA loop hoisting, used by bench.py's flash config too
    return timeit_chained(fn, q, k, v, n)


def shapes():
    # decode: 1 query over a long KV buffer
    for t in (2048, 8192, 32768):
        yield "decode", 1, t, 200 if t <= 8192 else 50
    # prefill: S queries over an S-long buffer
    for s in (512, 1024, 2048, 4096):
        yield "prefill", s, s, 20 if s <= 2048 else 8


def _rates_only(row: dict) -> dict:
    return {k: v for k, v in row.items() if isinstance(v, (int, float))
            and k not in ("s", "t", "window")}


def sweep_int4(populate: bool, reg, chip: str, n: int = 50):
    """Grouped vs dequant int4 contraction on a decode-shaped matvec
    (bs=1 [1,K] x int4 [K,N], the regime quantization exists for)."""
    import time

    import numpy as np

    from inferd_tpu.ops import quant

    k_dim, n_dim = 2048, 6144
    w = quant.quantize_int4(
        jax.random.normal(jax.random.PRNGKey(0), (k_dim, n_dim), jnp.float32)
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (1, k_dim), jnp.float32)
    rates = {}
    for mode in ("grouped", "dequant"):
        old = quant.INT4_MODE
        quant.INT4_MODE = mode
        try:
            @jax.jit
            def loop(x):
                def body(c, _):
                    y = quant.qdot(c, w)
                    return (x + jnp.float32(1e-6) * y[:, :k_dim]), None

                out, _ = jax.lax.scan(body, x, None, length=n)
                return out

            np.asarray(loop(x))  # jaxlint: disable=J003 -- compile+warm once per timed mode, not a per-iteration sync
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(loop(x))  # jaxlint: disable=J003 -- materializing the result IS the timed quantity
                best = min(best, time.perf_counter() - t0)
            rates[mode] = round(n / best, 2)
        finally:
            quant.INT4_MODE = old
    winner = max(rates, key=rates.get)
    row = {"regime": "int4_qdot", "k": k_dim, "n": n_dim, "winner": winner,
           **rates}
    if populate:
        from inferd_tpu.perf import autotune

        reg.record(autotune.int4_key(chip), winner, rates,
                   source="sweep_attn --int4")
        row["recorded"] = autotune.int4_key(chip)
    print(json.dumps(row), flush=True)


def sweep_quant_modes(populate: bool, reg, chip: str, n: int = 50):
    """bf16 vs every weight-quant flag on a decode-shaped matvec stack
    (bs=1 [1,K] through gate/up/down-shaped linears — the weight-read-
    bound regime quantization exists for). Records rates keyed by the
    CLI flag plus a "bf16" baseline under the registry's quant_decode
    key, so apply_quant_mode can warn whenever a requested flag was
    measured SLOWER than bf16 on this chip (the r05 inversion: int8 at
    0.69x bf16 served silently)."""
    import time

    import numpy as np

    from inferd_tpu.ops import quant

    k_dim, n_dim = 2048, 6144
    w_full = jax.random.normal(
        jax.random.PRNGKey(0), (k_dim, n_dim), jnp.float32
    )
    wd = jax.random.normal(
        jax.random.PRNGKey(2), (n_dim, k_dim), jnp.float32
    )
    x = jax.random.normal(jax.random.PRNGKey(1), (1, k_dim), jnp.float32)
    flags = ("bf16", "int8", "w8a8", "int8-kernel", "int4")
    rates = {}
    for flag in flags:
        old = quant.QDOT_MODE
        try:
            if flag == "bf16":
                w_up, w_down = w_full, wd
            elif flag == "int4":
                w_up, w_down = (
                    quant.quantize_int4(w_full), quant.quantize_int4(wd)
                )
                quant.QDOT_MODE = "dequant"
            else:
                w_up, w_down = quant.quantize(w_full), quant.quantize(wd)
                quant.QDOT_MODE = {
                    "w8a8": "int8", "int8-kernel": "kernel"
                }.get(flag, "dequant")

            @jax.jit
            def loop(x):
                def body(c, _):
                    y = quant.qdot(c, w_up)
                    z = quant.qdot(y, w_down)
                    return c + jnp.float32(1e-6) * z, None

                out, _ = jax.lax.scan(body, x, None, length=n)
                return out

            np.asarray(loop(x))  # jaxlint: disable=J003 -- compile+warm once per timed mode, not a per-iteration sync
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(loop(x))  # jaxlint: disable=J003 -- materializing the result IS the timed quantity
                best = min(best, time.perf_counter() - t0)
            rates[flag] = round(n / best, 2)
        except Exception as e:
            rates[flag] = None
            print(json.dumps({
                "regime": "quant_decode", "flag": flag,
                "error": f"{type(e).__name__}: {e}"[:120],
            }), flush=True)
        finally:
            quant.QDOT_MODE = old
    good = {k: v for k, v in rates.items() if isinstance(v, (int, float))}
    winner = max(good, key=good.get) if good else None
    row = {"regime": "quant_decode", "k": k_dim, "n": n_dim,
           "winner": winner, **rates}
    if populate and winner is not None:
        from inferd_tpu.perf import autotune

        reg.record(autotune.quant_key(chip), winner, good,
                   source="sweep_attn --quant")
        row["recorded"] = autotune.quant_key(chip)
    print(json.dumps(row), flush=True)


def _best_of_3(loop, x0, n: int) -> float:
    """Chained-scan rate (calls/s), best of 3 — the sweep's shared timing
    discipline (serial dependency defeats loop hoisting; one
    materialization per timed run)."""
    import time

    import numpy as np

    np.asarray(loop(x0))  # compile + warm once per timed path
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(loop(x0))  # materializing the result IS the timed quantity
        best = min(best, time.perf_counter() - t0)
    return round(n / best, 2)


def _chained(fn, n: int):
    """jit a serial chain of n calls: body output feeds the next input."""
    @jax.jit
    def loop(x):
        def body(c, _):
            return fn(c), None

        out, _ = jax.lax.scan(body, x, None, length=n)
        return out

    return loop


def grade_paged_kernel(n: int = 20):
    """Paged decode-attention: Pallas chain-walk kernel vs the XLA
    gather_block_kv + dense decode path, at the shape the kernel exists
    for — a block TABLE far wider than any live chain (gang-scheduled
    windows size tables for the longest tenant; the gather materializes
    the full table width as dense KV, the kernel's chain walk skips past
    the live blocks)."""
    import numpy as np

    from inferd_tpu.utils.platform import is_tpu

    dt = jnp.bfloat16 if is_tpu() else jnp.float32
    b, nkv, g, d = 4, 8, 2, 64
    nq = nkv * g
    bs, mb, used = 16, 64, 3
    nb = 1 + b * used  # block 0 = scratch
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (nb, bs, nkv, d), dt)
    vp = jax.random.normal(jax.random.PRNGKey(1), (nb, bs, nkv, d), dt)
    tbl = np.zeros((b, mb), np.int32)
    order = np.random.default_rng(7).permutation(np.arange(1, nb))
    for lane in range(b):
        tbl[lane, :used] = order[lane * used:(lane + 1) * used]
    table = jnp.asarray(tbl)
    q = jax.random.normal(jax.random.PRNGKey(2), (b, 1, nq, d), dt)
    q_pos = jnp.full((b, 1), used * bs - 3, jnp.int32)
    kv_valid = jnp.full((b,), used * bs - 2, jnp.int32)

    def step(x):
        y = att.decode_gqa(
            x, kp, vp, q_positions=q_pos, kv_valid_len=kv_valid,
            block_table=table,
        )
        return x + jnp.asarray(1e-6, dt) * y.reshape(x.shape)

    rates = {}
    for name, force in (("kernel", True), ("xla", False)):
        old = att.FORCE_PAGED_KERNEL
        att.FORCE_PAGED_KERNEL = force
        try:
            rates[name] = _best_of_3(_chained(step, n), q, n)
        finally:
            att.FORCE_PAGED_KERNEL = old
    return rates


def grade_quant_kernels(n: int = 30):
    """Decode-GEMV quant kernels vs their XLA siblings: w8a16_matmul vs
    the dequant-mode dot (kernel_int8/xla_int8) and w4a16_matvec vs
    whatever scheme _int4_mode picks (kernel_int4/xla_int4), on the
    bs=1 weight-read-bound matvec stack quantization exists for."""
    from inferd_tpu.ops import quant

    k_dim, n_dim = 2048, 6144
    w_full = jax.random.normal(jax.random.PRNGKey(0), (k_dim, n_dim),
                               jnp.float32)
    wd = jax.random.normal(jax.random.PRNGKey(2), (n_dim, k_dim),
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, k_dim), jnp.float32)
    weights = {
        "int8": (quant.quantize(w_full), quant.quantize(wd)),
        "int4": (quant.quantize_int4(w_full), quant.quantize_int4(wd)),
    }
    rates = {}
    for scheme, (w_up, w_down) in weights.items():
        def step(c, w_up=w_up, w_down=w_down):
            y = quant.qdot(c, w_up)
            z = quant.qdot(y, w_down)
            return c + jnp.float32(1e-6) * z

        for side, force in (("kernel", True), ("xla", False)):
            old_mode, old_force = quant.QDOT_MODE, quant.FORCE_QUANT_KERNEL
            quant.QDOT_MODE = "dequant"
            quant.FORCE_QUANT_KERNEL = force
            try:
                rates[f"{side}_{scheme}"] = _best_of_3(_chained(step, n), x, n)
            finally:
                quant.QDOT_MODE = old_mode
                quant.FORCE_QUANT_KERNEL = old_force
    return rates


def grade_lora_kernel(n: int = 20):
    """Fused LoRA lane-delta kernel vs the gather_lanes + lane_delta XLA
    sibling at a registry-shaped pool: the sibling's per-dispatch cost is
    dominated by gathering [B, L, in, r]/[B, L, r, out] per-lane pool
    copies that the kernel never materializes (slot ids index the stacked
    pools inside the BlockSpec index maps)."""
    from inferd_tpu.ops import lora as lora_ops

    slots, n_layers, d_model, r = 8, 2, 2048, 8
    b, s = 4, 1
    a_pool = jax.random.normal(
        jax.random.PRNGKey(0), (slots, n_layers, d_model, r), jnp.float32
    ) * 0.05
    b_pool = jax.random.normal(
        jax.random.PRNGKey(1), (slots, n_layers, r, d_model), jnp.float32
    ) * 0.05
    scale = jnp.ones((slots,), jnp.float32)
    ids = jnp.asarray([0, 3, 1, 5], jnp.int32)
    adapters = {"a": {"q_proj": a_pool}, "b": {"q_proj": b_pool},
                "scale": scale, "ids": ids}
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, d_model), jnp.float32)

    from inferd_tpu.utils.platform import is_tpu

    interp = not is_tpu()

    def step_xla(c):
        per, sc = lora_ops.gather_lanes(adapters)
        out = c
        for lay in range(n_layers):
            a_l = per["q_proj"][0][lay]
            b_l = per["q_proj"][1][lay]
            out = out + jnp.float32(1e-6) * lora_ops.lane_delta(
                out, a_l, b_l, sc
            )
        return out

    def step_kernel(c):
        out = c
        for lay in range(n_layers):
            out = out + jnp.float32(1e-6) * lora_ops.fused_lane_delta(
                out, a_pool, b_pool, scale, ids, jnp.int32(lay),
                interpret=interp,
            )
        return out

    return {
        "kernel": _best_of_3(_chained(step_kernel, n), x, n),
        "xla": _best_of_3(_chained(step_xla, n), x, n),
    }


def sweep_kernels(populate: bool, reg, chip: str):
    """Grade the three round-19 decode kernels against their XLA siblings
    and record per-chip verdicts the dispatches consult:

      paged_decode|<chip>  winner "kernel"|"xla"   (paged_kernel_enabled)
      quant_decode|<chip>  kernel_*/xla_* rate pairs MERGED into the flag
                           sweep's entry — winner field untouched
                           (quant_kernel_winner derives from the pairs)
      lora_delta|<chip>    winner "kernel"|"xla"   (fused_delta_enabled)
    """
    from inferd_tpu.perf import autotune

    paged = grade_paged_kernel()
    row = {"regime": "paged_decode", **paged,
           "winner": "kernel" if paged["kernel"] >= paged["xla"] else "xla"}
    if populate:
        reg.record(autotune.paged_decode_key(chip), row["winner"], paged,
                   source="sweep_attn --kernels")
        row["recorded"] = autotune.paged_decode_key(chip)
    print(json.dumps(row), flush=True)

    qrates = grade_quant_kernels()
    verdict = "kernel" if all(
        qrates[f"kernel_{s}"] >= qrates[f"xla_{s}"] for s in ("int8", "int4")
    ) else "xla"
    row = {"regime": "quant_kernels", **qrates, "verdict": verdict}
    if populate:
        qkey = autotune.quant_key(chip)
        prev = reg.lookup(qkey) or {}
        merged = dict(prev.get("rates") or {})
        merged.update(qrates)
        reg.record(qkey, prev.get("winner") or verdict, merged,
                   source=(prev.get("source") or "") + "+sweep_attn --kernels")
        row["recorded"] = qkey
    print(json.dumps(row), flush=True)

    lrates = grade_lora_kernel()
    row = {"regime": "lora_delta", **lrates,
           "winner": "kernel" if lrates["kernel"] >= lrates["xla"] else "xla"}
    if populate:
        reg.record(autotune.lora_delta_key(chip), row["winner"], lrates,
                   source="sweep_attn --kernels")
        row["recorded"] = autotune.lora_delta_key(chip)
    print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gemma", action="store_true",
                    help="sweep the Gemma-2 recipe (softcap+scale+window)")
    ap.add_argument("--ckv", action="store_true",
                    help="also sweep compressed-KV (fp8 buffer) decode shapes")
    ap.add_argument("--populate", action="store_true",
                    help="record each shape's winner in the autotune "
                    "registry (perf/autotune.py) consulted by `auto` "
                    "dispatch; prints the registry path at the end")
    ap.add_argument("--int4", action="store_true",
                    help="also time int4 grouped-vs-dequant contraction "
                    "and record the chip's int4_mode winner")
    ap.add_argument("--quant", action="store_true",
                    help="also time bf16 vs every weight-quant flag on "
                    "decode-shaped matvecs and record the rates under "
                    "quant_decode|<chip> (apply_quant_mode warns when a "
                    "requested flag measured slower than bf16)")
    ap.add_argument("--kernels", action="store_true",
                    help="grade the round-19 decode kernels (paged "
                    "attention, quant GEMV, fused LoRA delta) vs their "
                    "XLA siblings and record per-chip winners under "
                    "paged_decode|, quant_decode| and lora_delta|")
    args = ap.parse_args()
    # backend probe stays OUT of module scope: importing this module must
    # never initialize a backend (a chip belongs to the process that
    # computes on it, not to whoever imported a tool)
    from inferd_tpu.utils.platform import is_tpu

    on_tpu = is_tpu()
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    b, nq, nkv, d = 1, 16, 8, 128
    key = jax.random.PRNGKey(0)
    reg = chip = None
    if args.populate or args.int4 or args.quant or args.kernels:
        from inferd_tpu.perf import autotune

        reg = autotune.get_registry(refresh=True)
        chip = autotune.chip_key()

    # the registry key embeds the activation dtype as a config-style name
    dtype_name = jnp.dtype(dt).name

    # gemma recipe: (scale, softcap, windows-to-sweep); plain: defaults
    variants = [(None, 0.0, [None])]
    if args.gemma:
        variants = [(256.0 ** -0.5, 50.0, [0, 4096])]
    kv_dtypes = [dt] + ([jnp.float8_e4m3fn] if args.ckv else [])
    for regime, s, t, n in shapes():
        for kv_dt in kv_dtypes:
            compressed = kv_dt != dt
            if compressed and regime != "decode":
                continue  # compressed-KV dispatch only matters for decode
            q = jax.random.normal(key, (b, s, nq, d), dt)
            k = jax.random.normal(key, (b, t, nkv, d), dt).astype(kv_dt)
            v = jax.random.normal(key, (b, t, nkv, d), dt).astype(kv_dt)
            kv_len = jnp.int32(t) if regime == "prefill" else jnp.int32(t - 5)
            q0 = 0 if regime == "prefill" else t - 5
            q_start = jnp.full((b,), q0, jnp.int32)

            for scale, cap, windows in variants:
                for win in windows:
                    w = None if win is None else jnp.int32(win)
                    paths = {
                        "xla": lambda q, k, v: gqa_attention(
                            q, k, v,
                            q0 + jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
                            kv_len, scale=scale, softcap=cap, window=w),
                        "stream": lambda q, k, v: att.flash_gqa(
                            q, k, v, q_start=q_start, kv_len=kv_len,
                            interpret=not on_tpu, stream=True,
                            scale=scale, softcap=cap, window=w),
                    }
                    if att._kv_fits_vmem(t, d, kv_dt):
                        paths["resident"] = lambda q, k, v: att.flash_gqa(
                            q, k, v, q_start=q_start, kv_len=kv_len,
                            interpret=not on_tpu, stream=False,
                            scale=scale, softcap=cap, window=w)
                    row = {"regime": regime, "s": s, "t": t}
                    if compressed:
                        row["kv_dtype"] = jnp.dtype(kv_dt).name
                    if args.gemma:
                        row["window"] = win
                    for name, fn in paths.items():
                        try:
                            row[name] = round(timeit(fn, q, k, v, n), 2)
                        except Exception as e:
                            row[name] = f"ERR {type(e).__name__}: {e}"[:120]
                    # registry population: plain (non-gemma) recipe only —
                    # the model's auto dispatch keys on shape, not on the
                    # softcap/window variant, so only the plain rows map
                    if args.populate and not args.gemma:
                        from inferd_tpu.perf import autotune

                        rates = _rates_only(row)
                        kernel_best = max(
                            (v for k2, v in rates.items()
                             if k2 in ("stream", "resident")),
                            default=None,
                        )
                        xla_rate = rates.get("xla")
                        if kernel_best is not None and xla_rate is not None:
                            winner = (
                                "flash" if kernel_best > xla_rate else "xla"
                            )
                            akey = autotune.attn_key(
                                chip, b, s, t, nq, nkv, d, dtype_name,
                                compressed,
                            )
                            reg.record(akey, winner, rates,
                                       source="sweep_attn")
                            row["winner"] = winner
                            row["recorded"] = akey
                    print(json.dumps(row), flush=True)
    if args.int4:
        sweep_int4(args.populate, reg, chip)
    if args.quant:
        sweep_quant_modes(args.populate, reg, chip)
    if args.kernels:
        sweep_kernels(args.populate, reg, chip)
    if args.populate:
        path = reg.save()
        print(json.dumps({"registry": path, "entries": len(reg.entries)}),
              flush=True)


if __name__ == "__main__":
    main()
