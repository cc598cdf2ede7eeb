"""Node bootstrap CLI: start one swarm node process.

Capability parity with /root/reference/petals/run_node.py:40-86 (load the
cluster yaml, resolve own IP, parse BOOTSTRAP_NODES / INITIAL_STAGE /
NODE_NAME from the environment, start the DHT then the node, block forever)
— redesigned:

  * `--device {auto,tpu,cpu}` selects the JAX platform before the first
    backend initialization (the north-star CLI surface: `run_node --device
    tpu` hosts the stage as a jit-compiled module on a TPU chip; the CPU
    path is identical code on the host platform). A platform asked for by
    name is verified after start-up: a node told `tpu` that resolved
    anything else exits non-zero instead of serving from the CPU;
  * config precedence: CLI flag > environment variable > manifest > default
    (the reference hardcoded ports 6050/7050 at run_node.py:45-46 — here
    they're the defaults, not constants);
  * graceful shutdown: SIGINT/SIGTERM withdraws the node's DHT record
    (tombstone) so routing stops picking it immediately instead of waiting
    for the liveness TTL.

Usage:
  python -m inferd_tpu.tools.run_node --manifest examples/cluster.yaml \
      --name node0 --parts parts/ --device tpu
  BOOTSTRAP_NODES=10.0.0.2:7050 INITIAL_STAGE=1 NODE_NAME=node1 \
      python -m inferd_tpu.tools.run_node --manifest cluster.yaml --parts parts/
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import socket
from typing import List, Optional, Tuple

DEFAULT_HTTP_PORT = 6050  # reference run_node.py:45
DEFAULT_GOSSIP_PORT = 7050  # reference run_node.py:46


def get_own_ip() -> str:
    """Best-effort routable self-IP (reference run_node.py:9-13)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))  # no packets sent; just picks the route
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


def parse_bootstrap(value: Optional[str]) -> List[Tuple[str, int]]:
    """Parse `host:port,host:port` (reference run_node.py:15-26)."""
    if not value:
        return []
    out = []
    for part in value.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, port = part.rpartition(":")
        if not host:
            raise ValueError(f"bootstrap entry {part!r} is not host:port")
        out.append((host, int(port)))
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="run_node", description="Start one inferd-tpu swarm node."
    )
    ap.add_argument("--manifest", help="cluster topology yaml")
    ap.add_argument(
        "--model", default="qwen3-0.6b",
        help="model preset for manifest-less mode (with --num-stages)",
    )
    ap.add_argument(
        "--num-stages", type=int, default=2,
        help="pipeline depth for manifest-less mode (even layer split)",
    )
    ap.add_argument(
        "--name",
        default=os.environ.get("NODE_NAME"),
        help="this node's name in the manifest (env NODE_NAME)",
    )
    ap.add_argument(
        "--stage",
        type=int,
        default=None,
        help="initial stage override (env INITIAL_STAGE; default: manifest entry)",
    )
    ap.add_argument(
        "--parts",
        default="parts/",
        help="shared stage-checkpoint store (written by tools.split_model)",
    )
    ap.add_argument(
        "--backend",
        default="qwen3",
        choices=["qwen3", "counter"],
        help="'counter' = model-free distribution-test backend",
    )
    ap.add_argument(
        "--device",
        default=os.environ.get("INFERD_DEVICE", "auto"),
        choices=["auto", "tpu", "cpu"],
        help="JAX platform for stage compute (env INFERD_DEVICE)",
    )
    ap.add_argument(
        "--mesh",
        default=os.environ.get("INFERD_MESH", ""),
        help="host the WHOLE model in-mesh pipelined over this node's "
        "chips, e.g. 'pp=4' or 'pp=8' (env INFERD_MESH). Requires a "
        "1-stage topology; pipeline hops become ICI ppermute inside one "
        "compiled program instead of HTTP relays",
    )
    ap.add_argument(
        "--mesh-slots", type=int, default=8,
        help="concurrent session slots (microbatches) for --mesh mode",
    )
    ap.add_argument(
        "--batch-lanes", type=int,
        default=int(os.environ.get("INFERD_BATCH_LANES", "0")),
        help="continuous batching: serve the whole model with this many "
        "session lanes; concurrent sessions' decode steps run as ONE "
        "device step (env INFERD_BATCH_LANES; 0 = off; single-stage "
        "topology only)",
    )
    ap.add_argument(
        "--stage-lanes", type=int,
        default=int(os.environ.get("INFERD_STAGE_LANES", "0")),
        help="stage-level continuous batching: serve this node's PIPELINE "
        "STAGE with this many session lanes; co-arriving decode steps of "
        "concurrent sessions run as ONE device step per arrival window, "
        "and same-next-hop co-batches relay as one coalesced envelope "
        "(env INFERD_STAGE_LANES; 0 = off; any multi-stage topology — "
        "the whole-model single-stage flavor is --batch-lanes)",
    )
    ap.add_argument(
        "--window-ms", type=float,
        default=float(os.environ.get("INFERD_WINDOW_MS", "2.0")),
        help="arrival-window length for --stage-lanes decode co-batching "
        "(env INFERD_WINDOW_MS); a solo session never pays it. Reaches "
        "the --stage-lanes window only: --batch-lanes derives its wait "
        "from the turns and steps it observes, --mesh keeps its 3 ms",
    )
    ap.add_argument(
        "--paged-kv", type=int,
        default=int(os.environ.get("INFERD_PAGED_KV", "0")),
        help="paged KV block size in tokens (env INFERD_PAGED_KV; 0 = "
        "dense lane slab). Lanes map to chains of fixed-size pool blocks "
        "through a block table: allocation/eviction become per-block, and "
        "sessions sharing a pinned/cached prompt prefix map its blocks "
        "read-only (copy-on-write) instead of re-prefilling it. Needs "
        "--batch-lanes or --stage-lanes; uniform-layout models only",
    )
    ap.add_argument(
        "--kv-blocks", type=int,
        default=int(os.environ.get("INFERD_KV_BLOCKS", "0")),
        help="paged KV pool size in blocks (env INFERD_KV_BLOCKS; 0 = "
        "full provisioning: lanes x ceil(max_len/block)). Set lower to "
        "overcommit HBM on mixed-length traffic — overflow surfaces as "
        "per-session KV errors, not OOM",
    )
    ap.add_argument(
        "--prefill-chunk", type=int,
        default=int(os.environ.get("INFERD_PREFILL_CHUNK", "0")),
        help="server-side chunked prefill: ingest prompts in dispatches "
        "of at most this many tokens, releasing the device between "
        "chunks so co-batched decode windows interleave instead of "
        "stalling behind a long admission (env INFERD_PREFILL_CHUNK; "
        "0 = whole-prompt dispatches)",
    )
    ap.add_argument(
        "--spec-draft-layers", type=int,
        default=int(os.environ.get("INFERD_SPEC_DRAFT_LAYERS", "0")),
        help="speculative /generate: self-draft with the target's first N "
        "layers; greedy server-side generations propose-and-verify "
        "(token-exact) instead of one forward per token (env "
        "INFERD_SPEC_DRAFT_LAYERS; 0 = off; single-stage topology only)",
    )
    ap.add_argument(
        "--spec-k", type=int, default=int(os.environ.get("INFERD_SPEC_K", "4")),
        help="speculative /generate: draft tokens per verify chunk",
    )
    ap.add_argument("--host", default=os.environ.get("NODE_IP") or None)
    ap.add_argument("--port", type=int, default=int(os.environ.get("NODE_PORT", DEFAULT_HTTP_PORT)))
    ap.add_argument(
        "--gossip-port",
        type=int,
        default=int(os.environ.get("GOSSIP_PORT", DEFAULT_GOSSIP_PORT)),
    )
    ap.add_argument(
        "--bootstrap",
        default=os.environ.get("BOOTSTRAP_NODES", ""),
        help="comma-separated host:port gossip seeds (env BOOTSTRAP_NODES)",
    )
    ap.add_argument("--capacity", type=int, default=4, help="advertised task capacity")
    ap.add_argument("--max-len", type=int, default=4096, help="per-session KV budget")
    ap.add_argument(
        "--rebalance-period", type=float, default=10.0,
        help="seconds between balancer passes (reference node.py:61)",
    )
    ap.add_argument(
        "--chaos",
        default=os.environ.get("INFERD_CHAOS", ""),
        help="fault injection spec, e.g. 'drop=0.2,delay_ms=50' or "
        "'die_after=10' (env INFERD_CHAOS) — resilience testing only",
    )
    ap.add_argument(
        "--quant",
        default=os.environ.get("INFERD_QUANT", "none"),
        choices=["none", "int8", "w8a8", "int8-kernel", "int4"],
        help="serving quantization: weight-only int8 (dequant-in-dot), "
        "dynamic-activation w8a8, int8-kernel (Pallas w8a16 matmul — "
        "structurally halved weight reads), or int4 (group-wise w4a16, "
        "quarter the weight bytes) (env INFERD_QUANT)",
    )
    ap.add_argument(
        "--lora",
        default=os.environ.get("INFERD_LORA", ""),
        help="peft LoRA adapter directory merged into this node's stage "
        "weights at load time, before quantization (env INFERD_LORA); "
        "mutually exclusive with --adapters",
    )
    ap.add_argument(
        "--adapters",
        default=os.environ.get("INFERD_ADAPTERS", ""),
        help="multi-tenant LoRA: comma-separated peft adapter directories "
        "forming this node's adapter CATALOG (env INFERD_ADAPTERS). "
        "Sessions admitted with an `adapter` envelope key decode with "
        "that adapter's weights via the batched unmerged apply — "
        "heterogeneous-adapter sessions co-batch in ONE device step; "
        "adapters hot-load/evict through a refcounted slot registry and "
        "replicas gossip residency (`ada`) for affinity routing. Needs "
        "--batch-lanes or --stage-lanes; mutually exclusive with --lora",
    )
    ap.add_argument(
        "--adapter-slots", type=int,
        default=int(os.environ.get("INFERD_ADAPTER_SLOTS", "0")),
        help="device-resident adapter slots incl. the permanent base "
        "slot 0 (env INFERD_ADAPTER_SLOTS; 0 = catalog size + 1). Fewer "
        "slots than tenants => idle adapters LRU-evict and cache-miss "
        "admissions hot-load",
    )
    ap.add_argument(
        "--kv-dtype",
        default=os.environ.get("INFERD_KV_DTYPE", "model"),
        choices=["model", "float8_e4m3fn"],
        help="KV cache storage dtype (env INFERD_KV_DTYPE): float8_e4m3fn "
        "halves the per-token KV read that dominates long-context decode",
    )
    ap.add_argument(
        "--coordinator",
        default=os.environ.get("INFERD_COORDINATOR", ""),
        help="multi-host mesh: jax.distributed coordinator address "
        "host:port (env INFERD_COORDINATOR). With --num-processes/"
        "--process-id, all hosts' chips form ONE global mesh — in-mesh "
        "pipeline hops ride ICI within a slice and DCN across hosts, "
        "the XLA-collective analogue of a NCCL/MPI multi-host backend",
    )
    ap.add_argument(
        "--num-processes", type=int,
        default=int(os.environ.get("INFERD_NUM_PROCESSES", "1")),
        help="total host processes in the multi-host mesh",
    )
    ap.add_argument(
        "--process-id", type=int,
        default=int(os.environ.get("INFERD_PROCESS_ID", "0")),
        help="this host's rank in the multi-host mesh",
    )
    ap.add_argument(
        "--enable-profiling",
        action="store_true",
        default=os.environ.get("INFERD_PROFILING", "") == "1",
        help="expose the POST /profile jax.profiler endpoint (off by "
        "default: any peer could otherwise start traces and fill disk)",
    )
    ap.add_argument(
        "--trace-dir",
        default=os.environ.get("INFERD_TRACE_DIR", ""),
        help="append this node's request spans to "
        "<dir>/<node_id>.spans.jsonl for `python -m inferd_tpu.obs "
        "merge` (tracing itself is always on unless INFERD_TRACE=0; "
        "without a dir, spans live only in the /spans ring)",
    )
    ap.add_argument(
        "--canary-interval", type=float,
        default=float(os.environ.get("INFERD_CANARY_INTERVAL", "0")),
        help="seconds between synthetic canary probes of the swarm's "
        "entry replicas (env INFERD_CANARY_INTERVAL; 0 = off). Probes "
        "stream a tiny fixed prompt through the real chain and record "
        "ONLY canary.* series — user SLIs never see them "
        "(docs/OBSERVABILITY.md)",
    )
    ap.add_argument(
        "--hop-timeout", type=float,
        default=float(os.environ.get("INFERD_HOP_TIMEOUT", "120")),
        help="per-hop relay/HTTP timeout in seconds (env "
        "INFERD_HOP_TIMEOUT). With deadline-carrying requests the "
        "effective hop timeout is min(this, remaining deadline) — a "
        "stalled peer costs at most the smaller of the two",
    )
    ap.add_argument(
        "--hedge-delay-ms", type=float,
        default=float(os.environ.get("INFERD_HEDGE_DELAY_MS", "0")),
        help="hedged decode relays: wait this long on the primary before "
        "firing the same envelope at a second replica (env "
        "INFERD_HEDGE_DELAY_MS; 0 = adaptive, the trailing-window hop "
        "p95). Hedges are capped at <=5%% extra load by a ratio budget "
        "(docs/SERVING.md 'Overload & reliability')",
    )
    ap.add_argument(
        "--hedge-mode",
        default=os.environ.get("INFERD_HEDGE_MODE", "advertised"),
        choices=["advertised", "any", "off"],
        help="which second replica a hedge may fire at: 'advertised' "
        "(default) = only one whose gossip record advertises the "
        "session's KV (truly idempotent); 'any' = the second-best ranked "
        "replica (stateless backends); 'off' = never hedge",
    )
    ap.add_argument(
        "--admission-reserve", type=float,
        default=float(os.environ.get("INFERD_ADMISSION_RESERVE", "0.05")),
        help="pool-aware admission control: shed NEW sessions (503 "
        "code 'busy' + Retry-After) while the --paged-kv block pool has "
        "fewer than this fraction of its blocks free (env "
        "INFERD_ADMISSION_RESERVE)",
    )
    ap.add_argument(
        "--standby-repl",
        action="store_true",
        default=os.environ.get("INFERD_STANDBY_REPL", "") == "1",
        help="crash-tolerant sessions: asynchronously replicate each "
        "resident session's completed KV to a gossip-chosen same-stage "
        "standby (env INFERD_STANDBY_REPL=1). On the holder's crash the "
        "standby PROMOTES the replicated prefix and the client "
        "re-prefills only the tokens past the replication frontier "
        "(bounded RPO) instead of restarting. Off by default: absent, "
        "wire, gossip, and /metrics stay byte-identical "
        "(docs/SERVING.md 'Failover & durability')",
    )
    ap.add_argument(
        "--repl-interval", type=float,
        default=float(os.environ.get("INFERD_REPL_INTERVAL", "0.5")),
        help="seconds between standby-replication ticks (env "
        "INFERD_REPL_INTERVAL); the tick interval bounds the RPO — "
        "tokens committed since the last shipped frontier re-prefill "
        "after a promotion",
    )
    ap.add_argument(
        "--rescue-bounces", type=int,
        default=int(os.environ.get("INFERD_RESCUE_BOUNCES", "6")),
        help="how many times a mid-session chunk landing on a replica "
        "without its KV bounces through gossip-advertised holders "
        "before degrading to the client's 409/restart path (env "
        "INFERD_RESCUE_BOUNCES); exhaustion journals "
        "session.rescue_failed",
    )
    ap.add_argument("--log-level", default="INFO")
    return ap


def parse_mesh(value: str):
    """Parse 'pp=4' / 'pp=2,tp=2' / 'pp=2,sp=2' into a MeshPlan; '' ->
    None. Serving meshes are pp (ICI pipeline hops), optionally x tp
    (Megatron psums in the cached decoder blocks) x ep (MoE expert
    sharding; the engine rejects ep on dense configs) x sp (LONG-CONTEXT
    prefill: the prompt's sequence axis shards over sp with ring
    attention; decode replicates over sp). dp stays a training-path axis:
    the serving program has no collective for it."""
    if not value:
        return None
    from inferd_tpu.parallel.mesh import AXES, MeshPlan

    sizes = {}
    for part in value.split(","):
        axis, _, n = part.strip().partition("=")
        if axis not in AXES or not n.isdigit():
            raise ValueError(f"bad mesh spec {part!r}; want e.g. 'pp=4'")
        sizes[axis] = int(n)
    plan = MeshPlan(**sizes)
    if plan.num_devices < 2:
        raise ValueError("--mesh needs >=2 devices (1 chip is --device alone)")
    if plan.num_devices != plan.pp * plan.tp * plan.ep * plan.sp:
        raise ValueError(
            f"--mesh serving supports the pp, tp, ep, and sp axes (got "
            f"{value!r}); dp sharding is a training-path feature"
        )
    return plan


def check_servable(cfg, args, num_stages: int = 1) -> None:
    """The one place that refuses every serving path that cannot run the
    model, with a sentence that names the path. A model generated by blocks
    (cfg.is_block_diffusion) is served whole from the dense lanes of
    --batch-lanes, in its own dtype, --kv-dtype or --quant, and by nothing
    else: every other path steps a token a call. So is, by its name, a
    model with latent attention or with a leading dense group, and one with
    state-space layers (cfg.has_state_layers: its recurrent state lives in
    the dense lanes' StateEntry and nowhere else; --kv-dtype and --quant run
    it unchanged). A model whose full layers carry no rope (cfg.nope_kinds),
    whose attention output is gated (cfg.attn_gate) or that holds a share of
    its experts (cfg.router_experts) is served from the lanes too, in its own
    dtype or --kv-dtype: no sharding rule or stage knows the gate, a stage or
    a traced rank knows no layer's kind, and a share is already one rank's
    part; --quant runs it too (ops/quant quantizes both weight stacks'
    projections, the held experts and the shared expert; a quantised expert
    weight takes the dense expert product), but where a dense group leads,
    which the last table refuses. That table also keeps a model whose
    residual is a stream of hidden states (cfg.hc_mult) off every path with a
    boundary inside the model: a mesh tick, a stage's lanes, a relay's hop and
    a self-draft all hand on one hidden state a token. A model whose layers
    are ONE sublayer each (cfg.single_sublayer: three weight stacks, by kind)
    comes first, with the reason that is its own: everything that cuts a
    model between layers counts a layer as a mixer and its feed-forward."""
    if cfg.single_sublayer:
        _refuse(cfg, {
            "--mesh (a pipeline rank's share is counted in layers of a mixer and a "
            "feed-forward; stacks of single sublayers are not sharded)": args.mesh,
            "--stage-lanes (a stage holds one stack of whole layers, not a stack a "
            "kind of sublayer)": args.stage_lanes > 0,
            "--paged-kv (the paged pool holds keys and values for every layer, and "
            "here one sublayer in eleven has any)": args.paged_kv > 0,
            "--spec-draft-layers (a self-draft is the first layers of ONE stack; a "
            "recurrent state does not roll back either)": args.spec_draft_layers > 0,
            "a manifest of several stages (parallel/stages slices one stack of whole "
            "layers; the three stacks of sublayers are kept whole)": num_stages > 1,
        })
    if cfg.nope_kinds or cfg.attn_gate or cfg.router_experts:
        _refuse(cfg, {
            "--mesh (a traced rank knows no layer's kind, no sharding rule names the "
            "attention gate, and a share of the experts is already one rank's)": args.mesh,
            "--stage-lanes (a stage's relay hands every layer one rope)": args.stage_lanes > 0,
            "--paged-kv (the paged pool keeps no ring for the windowed layers)":
                args.paged_kv > 0,
            "--spec-draft-layers (no self-draft over a share of the experts)":
                args.spec_draft_layers > 0,
            "--lora": bool(args.lora),
            "--adapters (the registry knows no gate projection)": bool(args.adapters),
            "--standby-repl (a standby resumes through the stage path)": args.standby_repl,
            "serving without --batch-lanes (only the lane executor hands each layer its "
            "kind's rope and ring)": args.backend == "qwen3" and args.batch_lanes <= 0,
            "a manifest of several stages (the rings are laid out from layer 0)":
                num_stages > 1,
        })
    if cfg.has_state_layers:
        _refuse(cfg, {
            "--mesh (no recurrent state in a mesh slot, and its two weight stacks "
            "are not sharded)": args.mesh,
            "--stage-lanes (a stage holds one stack of layers)": args.stage_lanes > 0,
            "--paged-kv (the paged pool has no state entry)": args.paged_kv > 0,
            "--spec-draft-layers (a rejected draft would need the state back, and a "
            "recurrent state does not roll back)": args.spec_draft_layers > 0,
            "--lora": bool(args.lora),
            "--adapters (the registry targets attention's dense projections)":
                bool(args.adapters),
            "--standby-repl (no handoff or standby export of a recurrent state)":
                args.standby_repl,
            "serving without --batch-lanes (only the lane executor holds a StateEntry)":
                args.backend == "qwen3" and args.batch_lanes <= 0,
            "a manifest of several stages (the two weight stacks are kept whole)":
                num_stages > 1,
        })
    if cfg.is_block_diffusion:
        _refuse(cfg, {
            "--mesh (a pipeline pass steps one token a slot)": args.mesh,
            "--stage-lanes (the relay's hop carries one token)": args.stage_lanes > 0,
            "--paged-kv (a denoising pass writes beyond the frontier, which no "
            "block chain covers)": args.paged_kv > 0,
            "--spec-draft-layers (a draft proposes token by token)": args.spec_draft_layers > 0,
            "--lora": bool(args.lora),
            "--adapters (the block program takes no adapter)": bool(args.adapters),
            "--standby-repl (the standby resumes token by token)": args.standby_repl,
            "serving without --batch-lanes (only the lane executor runs a block step)":
                args.backend == "qwen3" and args.batch_lanes <= 0,
            "a manifest of several stages (a block step runs the whole model)": num_stages > 1,
        })
    if not (cfg.is_mla or cfg.num_dense_layers or cfg.hc_mult):
        return
    # a residual stream of cfg.hc_mult hidden states lives inside one program:
    # every boundary between two hands on ONE, [B, S, H]
    stream = cfg.hc_mult > 0
    _refuse(cfg, {
        "--mesh (no latent cache or layer groups under a mesh)": args.mesh,
        "--mesh (a tick hands the next rank one hidden state, the residual stream is "
        f"{cfg.hc_mult})": stream and args.mesh,
        "--stage-lanes (a stage holds one group of layers)": args.stage_lanes > 0,
        "--stage-lanes (a stage's lanes take and give one hidden state a token, not the "
        f"stream's {cfg.hc_mult})": stream and args.stage_lanes > 0,
        "a manifest of several stages (a relay's hop carries one hidden state a token, not "
        f"the stream's {cfg.hc_mult})": stream and num_stages > 1,
        "--spec-draft-layers (a draft over the first layers would read the head off a "
        "stream that the layers left out still mix)": stream and args.spec_draft_layers > 0,
        "--paged-kv (the paged pool has no latent entry)": args.paged_kv > 0,
        "--quant (the latent projections and a leading dense group have no quantized form)":
            args.quant != "none",
        "--spec-draft-layers (no self-draft over layer groups)": args.spec_draft_layers > 0,
        "--lora": bool(args.lora),
        "--adapters (the registry targets per-head dense projections)": bool(args.adapters),
        "--standby-repl (no handoff or standby export of a latent cache)": args.standby_repl,
        "serving without --batch-lanes (only the lane executor runs its layer groups)":
            args.backend == "qwen3" and args.batch_lanes <= 0,
    })


def _refuse(cfg, refused: dict) -> None:
    hit = [what for what, on in refused.items() if on]
    if hit:
        raise SystemExit(f"run_node: {cfg.name} cannot be served with " + "; ".join(hit))


async def _run(args, cache_stats=None) -> None:
    # heavyweight imports AFTER main() pinned the platform
    from inferd_tpu.control.dht import SwarmDHT
    from inferd_tpu.parallel.stages import Manifest
    from inferd_tpu.runtime.node import Node, NodeInfo
    from inferd_tpu.utils.chaos import Chaos

    mesh_plan = parse_mesh(args.mesh)
    if args.manifest:
        manifest = Manifest.from_yaml(args.manifest)
    else:
        # manifest-less mode: an even layer split, identity from flags/env
        # (mesh/batched modes host the whole model => single swarm stage)
        whole_model = mesh_plan is not None or args.batch_lanes > 0
        manifest = Manifest.even_split(
            args.model, 1 if whole_model else args.num_stages
        )
    manifest.validate()

    name = args.name or (None if args.manifest else f"node-{os.getpid()}")
    if not name:
        raise SystemExit("--name (or NODE_NAME) is required with a manifest")
    stage = args.stage
    if stage is None:
        env_stage = os.environ.get("INITIAL_STAGE")
        if env_stage is not None:
            stage = int(env_stage)
        elif args.manifest:
            stage = manifest.node(name).stage
        else:
            stage = 0

    cfg = manifest.config
    check_servable(cfg, args, manifest.num_stages)
    if args.kv_dtype != "model":
        import dataclasses

        cfg = dataclasses.replace(cfg, kv_dtype=args.kv_dtype)

    host = args.host or get_own_ip()
    info = NodeInfo(
        name=name,
        host=host,
        port=args.port,
        stage=stage,
        num_stages=manifest.num_stages,
        capacity=args.capacity,
        model_name=manifest.model_name,
    )
    dht = SwarmDHT(
        info.node_id,
        args.gossip_port,
        bootstrap=parse_bootstrap(args.bootstrap),
        host="0.0.0.0",
    )
    node = Node(
        info,
        cfg,
        args.parts,
        dht,
        backend=args.backend,
        max_len=args.max_len,
        rebalance_period_s=args.rebalance_period,
        hop_timeout_s=args.hop_timeout,
        chaos=Chaos.parse(args.chaos),
        enable_profiling=args.enable_profiling,
        mesh_plan=mesh_plan,
        mesh_slots=args.mesh_slots,
        quant=args.quant,
        batch_lanes=args.batch_lanes,
        stage_lanes=args.stage_lanes,
        paged_block_size=args.paged_kv,
        kv_blocks=args.kv_blocks,
        prefill_chunk=args.prefill_chunk,
        window_ms=args.window_ms,
        spec_draft_layers=args.spec_draft_layers,
        spec_k=args.spec_k,
        lora=args.lora or None,
        adapters=args.adapters or None,
        adapter_slots=args.adapter_slots,
        trace_dir=args.trace_dir or None,
        canary_interval_s=args.canary_interval,
        hedge_delay_ms=args.hedge_delay_ms,
        hedge_mode=args.hedge_mode,
        admission_reserve=args.admission_reserve,
        standby_repl=args.standby_repl,
        repl_interval_s=args.repl_interval,
        rescue_bounces=args.rescue_bounces,
        compile_cache=cache_stats,
    )

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:  # non-unix
            pass

    await node.start()
    logging.getLogger(__name__).info(
        "node %s serving stage %d/%d on %s:%d (gossip :%d, device=%s)",
        name, stage, manifest.num_stages, host, args.port,
        args.gossip_port, args.device,
    )
    await stop.wait()
    await node.stop()


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from inferd_tpu.utils.platform import (
        enable_compile_cache, force_platform, require_platform,
    )

    # before the first backend use ("auto" leaves jax's discovery alone)
    force_platform(args.device)

    # warm restarts, stage migrations and sibling processes load compiled
    # executables instead of re-running XLA (JAX_COMPILATION_CACHE_DIR, or
    # the fixed path in the checkout — utils.platform.enable_compile_cache)
    cache_stats = enable_compile_cache()
    if args.coordinator:
        # multi-host mesh: must run BEFORE any backend touch so every
        # process sees the global device set (jax.devices() then spans all
        # hosts and the --mesh plan shards over ICI + DCN)
        import jax

        jax.distributed.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    logging.basicConfig(
        level=args.log_level.upper(),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    # after jax.distributed (which must precede any backend touch): a node
    # asked for a platform by name serves on that platform or not at all
    if args.backend != "counter":
        require_platform(args.device)
    asyncio.run(_run(args, cache_stats))


if __name__ == "__main__":
    main()
