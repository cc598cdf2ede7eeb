"""On-chip benchmark battery -> committed, driver-auditable artifacts.

Each leg shells out to bench.py with `--device tpu`: one process after
another, each alone on the chip, this parent never touching JAX. The result JSON — plus timestamp, argv, and wall
time — is appended to `bench_artifacts/BENCH_tpu_<utc-stamp>.jsonl`, one
line per leg, ready to `git add`. A leg that finds no chip fails; nothing
here measures the CPU under a chip leg's name.

  python -m inferd_tpu.tools.bench_battery            # the chip legs
  python -m inferd_tpu.tools.bench_battery --smoke    # tiny CPU legs (tests)

The default battery covers decode (short + 8K context, bf16 + fp8 KV), int8
and int8-kernel, prefill, batched lanes, the flash-kernel sweep, and the
gemma2 8K windowed decode (the ring-KV long-context leg). Legs whose config
starts node processes (swarm_*, overload, cache_affinity, failover) exist
only in the --smoke table: those nodes are pinned to the CPU, so the config
has no chip form yet (bench.py refuses it under --device tpu).
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench.py")
ARTIFACT_DIR = os.path.join(REPO, "bench_artifacts")

# each leg: (name, argv tail, per-leg timeout seconds).
# --no-extras everywhere: only the on-chip leg matters here (the CPU
# pipeline-ratio/batched proxy legs ride the default --device cpu run).
DEFAULT_LEGS = [
    ("decode", ["--config", "decode", "--no-extras"], 900),
    ("decode_ctx8k", ["--config", "decode", "--ctx", "8192", "--no-extras"], 1200),
    ("decode_ctx8k_fp8kv",
     ["--config", "decode", "--ctx", "8192", "--kv-dtype", "float8_e4m3fn",
      "--no-extras"], 1200),
    ("decode_int8", ["--config", "decode", "--quant", "int8", "--no-extras"], 900),
    ("decode_int8_kernel",
     ["--config", "decode", "--quant", "int8-kernel", "--no-extras"], 900),
    ("decode_int4", ["--config", "decode", "--quant", "int4", "--no-extras"], 900),
    ("prefill", ["--config", "prefill"], 900),
    ("batched_lanes8", ["--config", "batched", "--lanes", "8"], 1200),
    ("flash", ["--config", "flash"], 900),
    ("gemma2_ctx8k",
     ["--config", "decode", "--model", "gemma2-2b", "--ctx", "8192",
      "--no-extras"], 1500),
    # round-5 legs: the speculative ratio ON CHIP (floor + full-accept
    # ceiling; accept_rate still random-weight) and the compile-cache
    # warm/cold witness where the delta is tens of seconds, not two
    ("spec", ["--config", "spec"], 1500),
    ("compile_cache", ["--config", "compile-cache"], 1500),
    # round-6 leg: the north-star model's single-chip denominator —
    # qwen3-8b int8 fits v5e's 16 GB HBM where bf16 (~16.4 GB) does not
    ("decode_8b_int8",
     ["--config", "decode", "--model", "qwen3-8b", "--quant", "int8",
      "--no-extras"], 2400),
    ("decode_multistep", ["--config", "decode-multistep"], 1800),
    # round-19 leg (on-chip roofline gap): the three Pallas decode
    # kernels (paged attention, dequant GEMV, fused LoRA lane-delta)
    # forced on vs off — `perf check` hard-errors when any kernel-forced
    # stream diverges or any kernel-vs-xla bytes ratio drops below 1;
    # on a TPU host pair this with `sweep_attn --kernels --populate` so
    # the wall-clock verdicts land in the autotune registry
    ("kernels", ["--config", "kernels"], 1800),
]

SMOKE_LEGS = [
    ("decode_tiny", ["--config", "decode", "--tiny", "--device", "cpu",
                     "--steps", "8", "--reps", "1"], 600),
    # CPU stand-in for the 8B int8 leg: same argv shape (decode + --quant
    # int8) on the tiny preset, so the battery machinery that will carry
    # the north-star denominator is dryrun-tested offline
    ("decode_tiny_int8",
     ["--config", "decode", "--tiny", "--quant", "int8", "--device", "cpu",
      "--steps", "8", "--reps", "1"], 600),
    ("prefill_tiny", ["--config", "prefill", "--tiny", "--device", "cpu",
                      "--reps", "1"], 600),
    # CPU stand-in for the swarm aggregate-throughput leg: 4 concurrent
    # sessions through a 2-stage --stage-lanes chain vs the serial swarm
    # baseline (stage-level continuous batching, runtime/stage_batch) —
    # dryrun-tests the same argv shape the full leg uses
    # paged-KV mixed-workload smoke: same argv shape as the full
    # swarm_mixed leg on the tiny preset (dense + paged clusters, shared
    # prefix, churn) — dryrun-tests the whole --paged-kv serving stack
    ("swarm_mixed_tiny",
     ["--config", "swarm-mixed", "--tiny", "--device", "cpu", "--lanes", "4",
      "--steps", "4", "--waves", "2"], 1200),
    ("swarm_agg_tiny",
     ["--config", "swarm-agg", "--tiny", "--lanes", "4", "--steps", "6",
      "--device", "cpu"], 900),
    # round-7 smoke sibling: same argv shape as decode_multistep so the
    # K-step evidence machinery is dryrun-tested on every offline
    # battery run
    ("decode_multistep_tiny",
     ["--config", "decode-multistep", "--tiny", "--device", "cpu",
      "--steps", "6", "--reps", "2", "--k-sweep", "1,4,8"], 900),
    # canary-prober dryrun: a real 2-stage chain with --canary-interval,
    # asserting probes complete end to end AND never leak into the user
    # SLI series (obs.canary; docs/OBSERVABILITY.md)
    ("canary_tiny",
     ["--config", "canary", "--tiny", "--device", "cpu"], 900),
    # overload-containment smoke: the run.sh 0b4 leg's argv shape — a
    # chaos (drop+stall) stage-1 replica vs a fault-free twin cluster,
    # gating within-deadline goodput, zero hung requests, and the hedge
    # budget (docs/SERVING.md "Overload & reliability")
    ("overload_tiny",
     ["--config", "overload", "--tiny", "--device", "cpu", "--lanes", "4",
      "--steps", "4", "--waves", "2", "--deadline-s", "25"], 1200),
    # cache-affinity smoke: the run.sh 0b5 leg's argv shape — digest
    # routing on vs off over two paged stage-0 replicas, gating fleet
    # prefill-tokens-avoided (docs/OBSERVABILITY.md memory plane)
    ("cache_affinity_tiny",
     ["--config", "cache-affinity", "--tiny", "--device", "cpu",
      "--steps", "4", "--waves", "4"], 1200),
    # crash-failover smoke: the run.sh 0b6 leg's argv shape — kill the
    # KV holder mid-generation, standby replication on vs off, gating
    # token-exact recovery, bounded re-prefill, and the recovery gain
    # (docs/SERVING.md "Failover & durability")
    ("failover_tiny",
     ["--config", "failover", "--tiny", "--device", "cpu",
      "--steps", "16"], 1200),
    # decode-kernel smoke: the run.sh 0b8 leg's argv shape — all three
    # Pallas kernels forced on vs off (interpret mode on CPU), gating
    # measured token-exactness and the structural kernel-vs-xla
    # HBM-bytes ratios (docs/PERF.md "Kernel dispatch")
    ("kernels_tiny",
     ["--config", "kernels", "--tiny", "--device", "cpu",
      "--steps", "6"], 1200),
]


def run_leg(name: str, tail, timeout_s: int, device_args):
    argv = [sys.executable, BENCH, *tail, *device_args]
    t0 = time.time()
    entry = {
        "leg": name,
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "argv": argv[2:],
    }
    try:
        proc = subprocess.run(
            argv, capture_output=True, text=True, timeout=timeout_s, cwd=REPO,
        )
        entry["wall_s"] = round(time.time() - t0, 1)
        entry["rc"] = proc.returncode
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        try:
            entry["result"] = json.loads(line)
        except Exception:
            entry["error"] = f"non-JSON bench output: {line[:300]!r}"
            entry["stderr_tail"] = proc.stderr[-500:]
    except subprocess.TimeoutExpired:
        entry["wall_s"] = round(time.time() - t0, 1)
        entry["error"] = f"leg timed out after {timeout_s}s"
    except Exception as e:
        entry["wall_s"] = round(time.time() - t0, 1)
        entry["error"] = f"{type(e).__name__}: {e}"[:300]
    return entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_battery", description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU legs (exercises the machinery offline)")
    ap.add_argument("--legs", default="",
                    help="comma-separated subset of leg names to run")
    ap.add_argument("--out", default="",
                    help="output .jsonl path (default: bench_artifacts/"
                    "BENCH_tpu_<utc-stamp>.jsonl)")
    args = ap.parse_args(argv)

    legs = SMOKE_LEGS if args.smoke else DEFAULT_LEGS
    if args.legs:
        want = {x.strip() for x in args.legs.split(",") if x.strip()}
        unknown = want - {n for n, _, _ in legs}
        if unknown:
            print(f"unknown legs: {sorted(unknown)}", file=sys.stderr)
            return 2
        legs = [l for l in legs if l[0] in want]

    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y%m%d_%H%M%S")
    prefix = "BENCH_smoke_" if args.smoke else "BENCH_tpu_"
    out = args.out or os.path.join(ARTIFACT_DIR, f"{prefix}{stamp}.jsonl")
    device_args = [] if args.smoke else ["--device", "tpu"]

    n_ok = 0
    with open(out, "a") as f:
        for name, tail, timeout_s in legs:
            print(f"[battery] {name}: bench.py {' '.join(tail)}",
                  file=sys.stderr, flush=True)
            entry = run_leg(name, tail, timeout_s, device_args)
            f.write(json.dumps(entry) + "\n")
            f.flush()
            ok = "result" in entry and entry.get("rc") == 0
            n_ok += ok
            print(f"[battery] {name}: {'ok' if ok else 'FAILED'} "
                  f"({entry.get('wall_s')}s)", file=sys.stderr, flush=True)
    print(out)  # the artifact path is the stdout contract
    return 0 if n_ok == len(legs) else 1


if __name__ == "__main__":
    sys.exit(main())
