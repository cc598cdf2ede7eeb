#!/usr/bin/env bash
# End-to-end local demo (reference /root/reference/run.sh:1-5: split the
# model, generate the deployment, bring up the cluster, run the client) —
# on loopback processes instead of docker, with --random-init weights so it
# runs in zero-egress environments. Pass --hf to load real Qwen3-0.6B
# weights from the HF cache instead.
#
#   ./run.sh            # tiny random-init demo, counter-checked
#   ./run.sh --hf       # real qwen3-0.6b weights (needs HF cache)
#
# A CPU demo: it starts one PROCESS per node on this host, and a chip belongs
# to one process at a time. The chip is exercised by `python chip_smoke.py`
# (one node process, then the plain engine, one after the other).
set -euo pipefail
cd "$(dirname "$0")"
export JAX_PLATFORMS=cpu
# XLA:CPU can refuse, or crash on, executables a sibling process cached on
# the same host (tests/conftest.py): the CPU demo compiles anew
export JAX_ENABLE_COMPILATION_CACHE=false

MODEL=tiny
EXTRA=(--random-init)
if [[ "${1:-}" == "--hf" ]]; then MODEL=qwen3-0.6b; EXTRA=(); fi

WORK=$(mktemp -d)
trap 'kill $(jobs -p) 2>/dev/null || true; rm -rf "$WORK"' EXIT

echo "== 0/4 jaxlint static analysis (docs/ANALYSIS.md)"
python -m inferd_tpu.analysis check inferd_tpu/ tests/ bench.py \
    __graft_entry__.py chip_smoke.py --baseline analysis-baseline.json --jobs 0

echo "== 0a/4 observability contract drift (HARD — docs/ANALYSIS.md 'contracts')"
# emitted journal events / /metrics series / gossip keys must match the
# docs/OBSERVABILITY.md tables; deliberate gaps live in
# analysis-contracts.json with a reason each
python -m inferd_tpu.analysis contracts

echo "== 0b/4 perf regression gate on committed artifacts (advisory — docs/PERF.md)"
python -m inferd_tpu.perf check \
    --artifact bench_artifacts/BENCH_tpu_r05.jsonl \
    || echo "perf gate: ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"
# swarm co-batching ordering (swarm_agg >= serial baseline — docs/SERVING.md)
python -m inferd_tpu.perf check \
    --artifact bench_artifacts/BENCH_swarm_r06.json \
    || echo "perf gate (swarm_agg): ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"

echo "== 0b2/4 multi-step fused decode ordering gate (HARD — docs/PERF.md §6)"
# fresh tiny K-sweep through the serving executor; `perf check` hard-errors
# when every K>1 loses to K=1 (the fused inner loop's whole claim) or when
# the committed K-speedup (bench_artifacts/BENCH_multistep_cpu_r07.json,
# the dimensionless CPU-proxy prior) regressed >= 20%
python bench.py --config decode-multistep --tiny --device cpu \
    --steps 12 --reps 3 > "$WORK/multistep.json"
python -m inferd_tpu.perf check --artifact "$WORK/multistep.json" \
    --prior bench_artifacts/BENCH_multistep_cpu_r07.json

echo "== 0b3/4 paged-KV mixed-workload ordering gate (HARD — docs/SERVING.md)"
# fresh tiny dense-vs-paged cluster pair (mixed prompt lengths, one shared
# prefix, session churn); `perf check` hard-errors when the paged aggregate
# loses to dense on the same cluster, when any stream diverges
# (token_exact), or when the committed paged/dense ratio
# (bench_artifacts/BENCH_paged_cpu_r08.json, the dimensionless CPU-proxy
# prior) regressed >= 20%
python bench.py --config swarm-mixed --tiny --lanes 4 --steps 4 --waves 2 \
    --device cpu > "$WORK/swarm_mixed.json"
python -m inferd_tpu.perf check --artifact "$WORK/swarm_mixed.json" \
    --prior bench_artifacts/BENCH_paged_cpu_r08.json

echo "== 0b4/4 overload-containment goodput gate (HARD — docs/SERVING.md 'Overload & reliability')"
# fresh tiny 2-stage chain + one chaos-injected (drop+stall) stage-1
# replica vs an identical fault-free cluster; `perf check` hard-errors
# when within-deadline goodput falls under 70% of fault-free, when ANY
# request outlives its deadline, when hedges exceed their 5% budget, or
# when the committed goodput ratio
# (bench_artifacts/BENCH_overload_cpu_r10.json, dimensionless CPU-proxy
# prior) regressed >= 20%
python bench.py --config overload --tiny --device cpu \
    --lanes 4 --steps 4 --waves 3 --deadline-s 25 > "$WORK/overload.json"
python -m inferd_tpu.perf check --artifact "$WORK/overload.json" \
    --prior bench_artifacts/BENCH_overload_cpu_r10.json

echo "== 0b5/4 cache-affinity routing gate (HARD — docs/OBSERVABILITY.md 'Memory-plane observability')"
# fresh tiny two-replica mixed-churn cluster, digest routing on vs off
# (token-exact both sides); `perf check` hard-errors when routing-on
# fails to STRICTLY beat routing-off on fleet prefill-tokens-avoided,
# when any stream diverges, or when the committed routing-on hit rate
# (bench_artifacts/BENCH_cache_cpu_r13.json, the dimensionless
# CPU-proxy prior) regressed >= 20%
python bench.py --config cache-affinity --tiny --device cpu \
    --steps 4 --waves 4 > "$WORK/cache_affinity.json"
python -m inferd_tpu.perf check --artifact "$WORK/cache_affinity.json" \
    --prior bench_artifacts/BENCH_cache_cpu_r13.json

echo "== 0b6/4 crash-failover recovery gate (HARD — docs/SERVING.md 'Failover & durability')"
# fresh tiny single-stage replica pair; SIGKILL the KV holder
# mid-generation with async standby replication on vs off. `perf check`
# hard-errors when any stream diverges (token_exact), when the
# replication-on kill re-prefills more than the replication-lag bound
# (or falls back to a full restart), when promotion fails to beat the
# restart baseline, or when the committed dimensionless recovery gain
# (bench_artifacts/BENCH_failover_cpu_r14.json, CPU-proxy prior)
# regressed >= 20%
python bench.py --config failover --tiny --device cpu \
    --steps 16 > "$WORK/failover.json"
python -m inferd_tpu.perf check --artifact "$WORK/failover.json" \
    --prior bench_artifacts/BENCH_failover_cpu_r14.json

echo "== 0b7/4 multi-tenant LoRA co-batch gate (HARD — docs/SERVING.md 'Multi-tenant adapters')"
# fresh tiny single-replica multi-adapter cluster: N tenants' sessions
# decode with their OWN adapters via the batched unmerged apply, once
# co-batched and once serial on the same cluster; `perf check`
# hard-errors when any tenant's stream diverges from its merged solo
# reference (token_exact), when the co-batched aggregate fails to
# STRICTLY beat per-tenant serial, when the registry recorded zero
# hot-loads, or when the committed co-batch/serial ratio
# (bench_artifacts/BENCH_lora_cpu_r15.json, dimensionless CPU-proxy
# prior) regressed >= 20%
python bench.py --config lora-tenants --tiny --device cpu \
    --lanes 4 --steps 8 > "$WORK/lora_tenants.json"
python -m inferd_tpu.perf check --artifact "$WORK/lora_tenants.json" \
    --prior bench_artifacts/BENCH_lora_cpu_r15.json

echo "== 0b8/4 decode-kernel roofline gate (HARD — docs/PERF.md 'Kernel dispatch')"
# the three round-19 Pallas decode kernels (paged attention, dequant
# GEMV, fused LoRA lane-delta) each forced ON vs OFF on the same host:
# `perf check` hard-errors when any kernel-forced greedy stream
# diverges from its XLA sibling (token_exact, measured), when any
# kernel's structural kernel-vs-xla HBM-bytes ratio drops below 1
# (the kernel would move MORE bytes than the path it replaces), or
# when the committed worst-case ratio
# (bench_artifacts/BENCH_kernels_cpu_r19.json, dimensionless
# CPU-proxy prior — wall-clock verdicts live in the autotune registry
# via `sweep_attn --kernels` on hardware) regressed >= 20%
python bench.py --config kernels --tiny --device cpu \
    --steps 6 > "$WORK/kernels.json"
python -m inferd_tpu.perf check --artifact "$WORK/kernels.json" \
    --prior bench_artifacts/BENCH_kernels_cpu_r19.json

echo "== 0c/4 span-merge smoke over the committed fixture (advisory — docs/OBSERVABILITY.md)"
python -m inferd_tpu.obs merge --check tests/data/spans \
    || echo "obs merge: ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"

echo "== 0d/4 SLO health smoke over the committed scrape (advisory — docs/OBSERVABILITY.md)"
python -m inferd_tpu.obs health --check tests/data/health \
    || echo "obs health: ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"
# burn-rate rules over the committed windowed-history fixture (one
# firing degraded, one quiet — the multi-window SLO engine's smoke)
python -m inferd_tpu.obs health --check tests/data/health_burn \
    || echo "obs health (burn): ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"

echo "== 0e/4 fleet SLI smoke over the committed collector artifacts (advisory — docs/OBSERVABILITY.md)"
python -m inferd_tpu.obs fleet --check tests/data/fleet \
    || echo "obs fleet: ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"

echo "== 0g/4 fleet-simulator scenario replay over committed fixtures (advisory — docs/CONTROL.md §5)"
# deterministic 1000-node-class control-plane rehearsal: replays every
# committed non-slow scenario fixture (adoption race, drain wave,
# hysteresis regression, retry storm) through the REAL
# DHT/balancer/D*-Lite code and enforces each fixture's gates + exact
# trace hash; the 1000-node churn sweep is fixture-flagged slow and
# runs in the slow test lane (tests/test_sim.py -m slow)
python -m inferd_tpu.sim --check tests/data/sim \
    || echo "sim check: ADVISORY failure (non-blocking in run.sh; tier-1 gates it)"

echo "== 1/4 split $MODEL into 2 stages -> $WORK/parts"
python -m inferd_tpu.tools.split_model --model "$MODEL" --stages 2 \
    --out "$WORK/parts" "${EXTRA[@]}"

echo "== 2/4 generate local launcher"
python - "$MODEL" "$WORK" <<'EOF'
import sys
from inferd_tpu.parallel.stages import Manifest
model, work = sys.argv[1], sys.argv[2]
m = Manifest.even_split(model, 2)
open(f"{work}/cluster.yaml", "w").write(m.to_yaml())
EOF
python -m inferd_tpu.tools.deploy --manifest "$WORK/cluster.yaml" \
    --mode local --out "$WORK/launch.sh" --parts "$WORK/parts" \
    --device cpu

echo "== 3/4 launch cluster"
MANIFEST="$WORK/cluster.yaml" bash "$WORK/launch.sh" &
sleep 1

echo "== 4/4 generate via the swarm client"
python - <<'EOF'
import asyncio, os
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import SamplingConfig

async def main():
    async with SwarmClient([("127.0.0.1", 6050)], sampling=SamplingConfig(temperature=0.0)) as c:
        for i in range(600):
            try:
                ids = await c.generate_ids([3, 7, 11, 19], max_new_tokens=8)
                break
            except Exception:
                await asyncio.sleep(0.5)
        else:
            raise SystemExit("cluster never came up")
        print("generated ids:", ids)

        # prefix caching: pin a shared prefix once; the next generation
        # forks its per-stage KV instead of re-prefilling it
        await c.pin_prefix([3, 7, 11])
        ids2 = await c.generate_ids([3, 7, 11, 19], max_new_tokens=8)
        assert ids2 == ids, (ids2, ids)
        print("pinned-prefix fork: same ids", ids2)

        # server-driven generation: ONE round trip, tokens streamed back
        streamed = []
        ids3 = await c.generate_server_side_stream(
            [3, 7, 11, 19], streamed.append, max_new_tokens=8
        )
        assert ids3 == ids and streamed == ids, (ids3, streamed)
        print("server-side stream: same ids, streamed incrementally")

asyncio.run(main())
EOF
echo "== done"
