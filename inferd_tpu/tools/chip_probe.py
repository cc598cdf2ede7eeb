"""Measure what THIS chip can actually do, so bench numbers have a
denominator that isn't a spec sheet.

The decode bench frames bs=1 decode against the v5e's nominal 819 GB/s
HBM bandwidth (bench.py bench_decode), but a chip may deliver a fraction
of nominal, and the right response to a low roofline_frac differs
completely depending on whether the ceiling is the chip or the graph.
This probe measures, all inside single-dispatch `lax.scan` loops (so the
per-dispatch host cost amortizes away):

  * read-only HBM bandwidth        (sum over a large bf16 array)
  * read+write HBM bandwidth       (scaled copy of a large array)
  * MXU bf16 matmul throughput     (4096^3 matmul chain)
  * bs=1 matvec effective BW       (the decode regime: [1,K] @ [K,N])
  * per-component decode step cost (embed / layer stack / lm head),
    each differenced over two scan lengths so fixed overhead cancels

Usage:  python -m inferd_tpu.tools.chip_probe [--model bench-pipe]
Prints one JSON object; exits nonzero if no accelerator is attached.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timed(fn, *args, reps: int = 3) -> float:
    """Best-of-reps wall time of a jitted fn; materializes the result so
    the timing ends when the device has finished, not when the call was
    enqueued."""
    np.asarray(jax.tree.leaves(fn(*args))[0])  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jax.tree.leaves(fn(*args))[0])  # jaxlint: disable=J003 -- materializing the result IS the timed quantity
        best = min(best, time.perf_counter() - t0)
    return best


def _scan_pair(fn, operand, short: int, long: int, reps: int = 3) -> float:
    """Per-iteration time of `fn` with fixed dispatch overhead cancelled:
    run scan(short) and scan(long) in single dispatches, difference."""

    def loop(n):
        @jax.jit
        def run(x):
            def body(c, _):
                return fn(c), None

            out, _ = jax.lax.scan(body, x, None, length=n)
            return out

        return run

    t_s = _timed(loop(short), operand, reps=reps)
    t_l = _timed(loop(long), operand, reps=reps)
    if t_l <= t_s:
        return t_l / long  # congestion flipped the windows; amortized rate
    return (t_l - t_s) / (long - short)


def probe_bandwidth(gb: float = 1.0) -> dict:
    """Every body must DEPEND ON THE CARRY or XLA's loop-invariant code
    motion hoists it out of the scan and the probe times a no-op. Read:
    a [1,K] @ [K,N] dot whose left operand is carried — the dot streams
    the full weight matrix from HBM each iteration and cannot be hoisted
    or algebraically factored. Copy: c + 1 over the carried array — a
    full read+write per iteration that no simplifier can elide."""
    elems = int(gb * (1 << 30) // 2)  # bf16 elements
    k = 8192
    n = max(elems // k, k)
    w = jnp.ones((k, n), jnp.bfloat16)
    row = jnp.full((1, k), jnp.bfloat16(1e-3))

    def read_step(c):
        y = c @ w  # [1, N] — reads all of w
        return (y[:, :k] * jnp.bfloat16(1e-4) + c) * jnp.bfloat16(0.5)

    read_t = _scan_pair(read_step, row, 2, 6)
    x = jnp.ones((k * n,), jnp.bfloat16)
    copy_t = _scan_pair(lambda c: c + jnp.bfloat16(1.0), x, 2, 6)
    bytes_rd = k * n * 2
    return {
        "hbm_read_gbps": round(bytes_rd / read_t / 1e9, 1),
        "hbm_copy_gbps": round(2 * bytes_rd / copy_t / 1e9, 1),
    }


def probe_mxu(dim: int = 4096) -> dict:
    a = jnp.ones((dim, dim), jnp.bfloat16)
    t = _scan_pair(lambda c: jnp.tanh(c @ a), a, 2, 6)
    flops = 2 * dim**3
    return {"mxu_bf16_tflops": round(flops / t / 1e12, 1)}


def probe_matvec(k: int = 4096, n: int = 16384) -> dict:
    """The bs=1 decode regime: activation [1,K] @ weight [K,N]. BW-bound;
    effective GB/s here is the honest decode roofline denominator."""
    w = jnp.ones((k, n), jnp.bfloat16)
    x = jnp.ones((1, k), jnp.bfloat16)

    def step(c):
        y = c @ w  # [1, N]
        return (y[:, :k] + x) / jnp.bfloat16(2.0) if n >= k else x + y.sum()

    t = _scan_pair(step, x, 4, 12)
    return {"matvec_eff_gbps": round(k * n * 2 / t / 1e9, 1)}


def probe_decode_components(cfg_name: str) -> dict:
    from inferd_tpu.config import get_config
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config(cfg_name)
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    max_len = 512
    cache = KVCache.create(cfg, cfg.num_layers, 1, max_len, ring=False)
    pos = jnp.full((1, 1), 64, jnp.int32)
    tok = jnp.full((1, 1), 7, jnp.int32)

    # the token index must depend on the carry or the gather hoists out
    # of the scan (LICM) and embed_ms times nothing
    def embed_step(c):  # c: [1, 1] int32 token id
        e = qwen3.embed(params, c, cfg)
        bump = (e[0, 0, 0] * jnp.bfloat16(1e3)).astype(jnp.int32) % 7
        return (c + 1 + bump) % cfg.vocab_size

    emb_t = _scan_pair(embed_step, tok, 8, 24)

    hidden0 = jnp.ones((1, 1, cfg.hidden_size), cfg.jnp_dtype)

    def layers_step(carry):
        h, c = carry
        out, nc, _ = qwen3.forward_layers_cached(
            params["layers"], cfg, h, pos, c, jnp.int32(64)
        )
        # thread the returned cache through the scan carry: when it was
        # returned-and-dropped, the cache write was dead code, XLA
        # DCE'd it out of the loop, and layers_ms/layers_eff_gbps timed a
        # write-free pseudo-step (undercounting a real decode step). As
        # carry, iteration i+1's attention reads what iteration i wrote,
        # so the write is live — the same dependency a real decode has.
        return (out, nc)

    layers_t = _scan_pair(layers_step, (hidden0, cache), 4, 12)

    def head_step(h):
        logits = qwen3.unembed(params, cfg, h)
        return h + logits[..., :1].astype(h.dtype)

    head_t = _scan_pair(head_step, hidden0, 4, 12)

    layer_bytes = sum(
        int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(params["layers"])
    )
    return {
        "model": cfg.name,
        "embed_ms": round(emb_t * 1e3, 3),
        "layers_ms": round(layers_t * 1e3, 3),
        "lm_head_ms": round(head_t * 1e3, 3),
        "layers_eff_gbps": round(layer_bytes / layers_t / 1e9, 1),
        "layer_stack_bytes": layer_bytes,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("chip_probe")
    ap.add_argument("--model", default="qwen3-0.6b")
    ap.add_argument("--skip-model", action="store_true")
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes (smoke-testing the probe itself)")
    ap.add_argument("--device", default="auto", choices=["auto", "cpu", "tpu"],
                    help="pinned before the first backend use")
    args = ap.parse_args(argv)
    from inferd_tpu.utils.platform import (
        force_platform, is_cpu, require_platform,
    )

    # pin before the first backend use, then verify: a platform asked for
    # by name that JAX did not resolve must never be timed under its name
    force_platform(args.device)
    try:
        require_platform(args.device)
    except RuntimeError as e:
        print(f"chip_probe: {e}", file=sys.stderr)
        return 2
    backend = jax.default_backend()
    if is_cpu() and args.device != "cpu":
        print(
            "chip_probe: no accelerator attached (backend is cpu); pass "
            "--device cpu to probe the host on purpose", file=sys.stderr,
        )
        return 2
    out = {
        "backend": backend,
        "device": str(jax.devices()[0]),
    }
    if args.small:
        out.update(probe_bandwidth(gb=1 / 64))
        out.update(probe_mxu(dim=256))
        out.update(probe_matvec(k=256, n=1024))
    else:
        out.update(probe_bandwidth())
        out.update(probe_mxu())
        out.update(probe_matvec())
    if not args.skip_model:
        out["decode_components"] = probe_decode_components(args.model)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
