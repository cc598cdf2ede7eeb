"""Swarm node: hosts one pipeline stage, relays activations, rebalances.

Capability parity with /root/reference/petals/node.py:14-158 (aiohttp server
with /nn_forward + /reassign, relay to the next stage's best node, periodic
rebalance loop) and node_info.py / task_scheduler.py, redesigned:

  * stage compute runs in a worker thread pool — the event loop keeps
    serving network I/O during a forward (reference ran torch synchronously
    inside the async handler, SURVEY B5);
  * load metric = actual in-flight requests, announced to the swarm store on
    every change (reference: task_scheduler.py:16-36);
  * stage migration WORKS: /reassign (and the balancer) loads the target
    stage's checkpoint from the shared parts store, swaps the executor, and
    re-announces (the reference's set_stage was a no-op and its weight path
    was wrong — SURVEY B1/B2);
  * wire format is the safe msgpack tensor codec (runtime/wire.py), not
    base64 JSON or pickle.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import math
import os
import threading
import time
import uuid
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional, Tuple, Union

import aiohttp
import numpy as np
from aiohttp import ClientSession, ClientTimeout, web

from inferd_tpu.config import ModelConfig
from inferd_tpu.control.balance import Balancer
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.control.path_finder import NoNodeForStage, PathFinder, node_addr
from inferd_tpu.obs import canary as canarylib
from inferd_tpu.obs import devtel as devtellib
from inferd_tpu.obs import events as eventslib
from inferd_tpu.obs import export as obs_export
from inferd_tpu.obs import health as healthlib
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.obs import tsdb as tsdblib
from inferd_tpu.parallel import stages as stagelib
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.runtime import repl as repllib
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.executor import BLOCK_TOP_WIDTHS, call_kind, make_executor
from inferd_tpu.runtime.window import WindowedBatcher
from inferd_tpu.utils import lockwatch
from inferd_tpu.utils import retry as retrylib
from inferd_tpu.utils.chaos import Chaos, ChaosDrop
from inferd_tpu.utils.metrics import Metrics
from inferd_tpu.utils.profiling import Profiler

log = logging.getLogger(__name__)


def _warmup_executor(executor, journal=None) -> None:
    """Best-effort eager compile of a freshly loaded executor's decode-step
    jit: one single-token forward through a throwaway session, so the first
    REAL request after a stage migration doesn't pay XLA compile latency
    (and so reshard.ms_to_serving measures the full reassign ->
    ready-to-serve interval, compile included). Works for every executor
    type via the shared process() contract; non-first stages feed a dummy
    hidden row. Failures are swallowed — warmup must never block serving
    (the first real request just compiles lazily, the pre-migration
    behavior) — but PROMOTED to a journal event + `events.
    executor.warmup_failed` counter: a silently failed warmup is exactly
    when a migrated node starts eating first-request compile storms, and
    a debug log line is invisible then (the counter doubles as a free SLO
    rule input — obs.health DEFAULT_RULES)."""
    sid = "__warmup__"
    t0 = time.perf_counter()
    try:
        spec = getattr(executor, "spec", None)
        cfg = getattr(executor, "cfg", None)
        if spec is not None and not spec.is_first:
            payload = {
                "hidden": np.zeros((1, 1, cfg.hidden_size), np.float32),
                "start_pos": 0, "real_len": 1,
            }
        else:
            payload = {"tokens": [[1]], "start_pos": 0, "real_len": 1}
        blk = getattr(cfg, "block_length", 1)
        if blk > 1:
            # a model generated by blocks: a whole block in, then a block
            # step without log-probabilities (what traffic asks) and one at
            # the first top-n width (a variant each: runtime/executor.py),
            # each followed by the hop it promised, as a generation sends
            # it (the lane executor runs that hop's block ahead under the key
            # the step left on the device: the small program that hands it
            # over is one a width too)
            executor.process(sid, {"tokens": [[1] * blk], "start_pos": 0, "real_len": blk})
            chain: Dict[str, Any] = {}
            for i, ask in enumerate((
                {"ahead": 1}, {}, {"top_logprobs": BLOCK_TOP_WIDTHS[0], "ahead": 1},
                {"top_logprobs": BLOCK_TOP_WIDTHS[0]},
            )):
                res = executor.process(sid, {
                    "tokens": [[0] * blk], "start_pos": blk * (i + 1), "real_len": blk,
                    "block": {"known": 0, **ask, **chain},
                })
                chain = {"key": res["key"]}
        else:
            executor.process(sid, payload)
            if hasattr(executor, "sampled_rows"):
                # an executor whose decode step chooses the tokens: the
                # step without log-probabilities (what traffic asks) and at
                # the first top-n width (a variant each), each followed by
                # the hop it promised, as a generation sends it (the lane
                # executor runs that hop's row ahead from what the step left
                # on the device: the small program that hands it over is
                # one a width too), then a hop that asks nothing (its
                # logits row is copied out alone)
                tok, chain = 1, {}
                for i, ask in enumerate((
                    {"sampling": {}, "ahead": 1}, {"sampling": {}},
                    {"sampling": {}, "top_logprobs": BLOCK_TOP_WIDTHS[0], "ahead": 1},
                    {"sampling": {}, "top_logprobs": BLOCK_TOP_WIDTHS[0]}, {},
                )):
                    res = executor.process(sid, {
                        "tokens": [[tok]], "start_pos": 1 + i, "real_len": 1, **ask, **chain,
                    })
                    if res.get("tokens") is not None:
                        tok, chain = int(res["tokens"][0][0]), {"key": res["key"]}
        if hasattr(executor, "process_batch"):
            # stage-batch executors serve decode through a SEPARATE
            # co-batched jit — compile it too (it is the serving hot path)
            step = dict(payload, start_pos=1)
            executor.process(sid, step)
        if journal is not None:
            journal.emit(
                "executor.warmup_ok",
                ms=round((time.perf_counter() - t0) * 1e3, 1),
            )
    except Exception as e:
        log.warning(
            "executor warmup failed (first request will compile): %s", e,
            exc_info=True,
        )
        if journal is not None:
            journal.emit(
                "executor.warmup_failed",
                error=f"{type(e).__name__}: {e}"[:200],
                ms=round((time.perf_counter() - t0) * 1e3, 1),
            )
    finally:
        try:
            executor.end_session(sid)
        except Exception:
            pass


# canonical home moved next to the gossip record schema (control.dht);
# re-exported here for the existing runtime/tests import surface
from inferd_tpu.control.dht import sess_hash  # noqa: E402,F401

class _ClientGone(Exception):
    """The streaming client disconnected mid-write: abort the stream
    quietly (no restart re-run for a dead socket)."""


def _as_response(out: Union[web.Response, Dict[str, Any]]) -> web.Response:
    """What a handler core returned, for the socket: a reply dict is
    packed here (the core's local caller takes it unpacked)."""
    if isinstance(out, dict):
        return web.Response(body=wire.pack(out))
    return out


def _is_decode_step(payload) -> bool:
    """True when the /forward payload is a single-token decode step at an
    established frontier — the only shape the stage window co-batches
    (prefill chunks and new sessions keep the per-session path)."""
    if not isinstance(payload, dict):
        return False
    try:
        if int(payload.get("start_pos", 0)) <= 0:
            return False
        x = payload.get("tokens")
        if x is None:
            x = payload.get("hidden")
        n = payload.get("real_len")
        if n is None:
            n = np.shape(x)[1]
        return int(n) == 1
    except Exception:
        return False  # malformed payloads fail in the guarded compute


def _committed_tokens(result) -> int:
    """Real tokens one executor call committed, for the `compute` span:
    K of a fused K-step result, else the chunk's real length as the
    executor reports it (1 for a decode step; never the padded bucket)."""
    if not isinstance(result, dict):
        return 1
    if "tokens" in result:
        return len(result["tokens"][0])
    return int(result.get("real_len", 1))


#: Buckets for the /generate user-SLI histograms: the SAME whole-chain
#: ladder the canary probes use (obs.canary), so probe and user latency
#: compare bucket for bucket.
_GENERATE_BOUNDS_MS = canarylib.CHAIN_BOUNDS_MS

FORWARD_PATH = "/forward"
REASSIGN_PATH = "/reassign"
END_SESSION_PATH = "/end_session"
FORK_SESSION_PATH = "/fork_session"
GENERATE_PATH = "/generate"
IMPORT_SESSION_PATH = "/import_session"
EXPORT_SESSION_PATH = "/export_session"
DRAIN_PATH = "/drain"
REPLICATE_SESSION_PATH = "/replicate_session"


@dataclasses.dataclass
class NodeInfo:
    """Node identity + placement (reference node_info.py:1-28, with a
    set_stage that actually updates state — fixing B1)."""

    name: str
    host: str
    port: int
    stage: int
    num_stages: int
    capacity: int = 4
    model_name: str = ""

    @property
    def node_id(self) -> str:
        return f"{self.host}:{self.port}"

    def set_stage(self, stage: int) -> None:
        self.stage = stage


class TaskScheduler:
    """Runs stage compute off the event loop; load = in-flight count.

    A hop that enters or leaves moves one integer and calls nobody: both
    run on the one loop thread with no await between read and write, so
    no lock is needed, and whoever is about to READ the load (the gossip
    send, a local replica pick: Node._refresh_record) compares `ticks`
    with what its record was built at."""

    def __init__(self, workers: int = 2):
        # hops taken in (handed to the pool, or to the executor's window
        # from the loop: Node._process_hop) and hops done (or raised)
        self.entered = 0
        self.left = 0
        self._pool = ThreadPoolExecutor(max_workers=workers, thread_name_prefix="stage")

    @property
    def inflight(self) -> int:
        return self.entered - self.left

    @property
    def ticks(self) -> int:
        """Load changes seen: every hop is two."""
        return self.entered + self.left

    async def run(self, fn, *args):
        return (await self.run_stamped(fn, *args))[0]

    async def run_stamped(self, fn, *args):
        """run(), and beside the result the moment this coroutine ran
        again on the event loop once the worker was done: where the hop's
        `resume` span ends."""
        self.entered += 1
        try:
            out = await self.offload(fn, *args)
            return out, tracelib.now()
        finally:
            self.left += 1

    def offload(self, fn, *args) -> "asyncio.Future":
        """`fn` on a worker of the pool, uncounted: part of a hop that is
        counted already, or work that is nobody's hop."""
        return asyncio.get_running_loop().run_in_executor(self._pool, fn, *args)

    def shutdown(self) -> None:
        self._pool.shutdown(wait=False)


class Node:
    """One swarm node process."""

    def __init__(
        self,
        info: NodeInfo,
        cfg: ModelConfig,
        parts_dir: str,
        dht: SwarmDHT,
        backend: str = "qwen3",
        max_len: int = 4096,
        rebalance_period_s: float = 10.0,
        hop_timeout_s: float = 120.0,
        max_sessions: int = 64,
        chaos: Optional[Chaos] = None,
        enable_profiling: bool = False,
        mesh_plan: Optional[MeshPlan] = None,
        mesh_slots: int = 8,
        quant: str = "none",
        batch_lanes: int = 0,
        stage_lanes: int = 0,
        paged_block_size: int = 0,
        kv_blocks: int = 0,
        prefill_chunk: int = 0,
        window_ms: float = 2.0,
        spec_draft_layers: int = 0,
        spec_k: int = 4,
        lora: Optional[str] = None,
        adapters: Optional[str] = None,
        adapter_slots: int = 0,
        trace_dir: Optional[str] = None,
        canary_interval_s: float = 0.0,
        hedge_delay_ms: float = 0.0,
        hedge_mode: str = "advertised",
        admission_reserve: float = 0.05,
        standby_repl: bool = False,
        repl_interval_s: float = 0.5,
        rescue_bounces: int = 6,
        compile_cache: Optional[Any] = None,
    ):
        self.info = info
        self.cfg = cfg
        self.parts_dir = parts_dir
        self.dht = dht
        self.backend = backend
        self.max_len = max_len
        self.hop_timeout_s = hop_timeout_s
        self.max_sessions = max_sessions
        self.metrics = Metrics()
        # swarm-wide request tracing (obs.trace): spans recorded host-side
        # into this ring, periodically appended to
        # <trace_dir>/<node_id>.spans.jsonl when --trace-dir is set (the
        # merge CLI's per-node input), always served live at /spans. A
        # decoded token leaves ten spans a session: this ring of SAMPLED
        # spans holds what a poller reads every few seconds (the
        # benchmark's traced run: five) at some thousands of hops a
        # second; what is once a request, a chunk or a step, and the whole
        # of a profiler capture, lies in the recorder's ring of kept spans
        # (obs.trace.KEPT_CAP) and outlives a window
        self.tracer = tracelib.SpanRecorder(service=info.node_id, cap=32768)
        # fleet flight recorder (obs.events): typed events (migrations,
        # rescues, dead peers, lane evictions, compiles, ...) with the
        # active trace_id attached; flushed next to the span file as
        # <trace_dir>/<node_id>.events.jsonl, served live at /events, and
        # mirrored into `events.*` counters for /metrics + SLO rules
        self.journal = eventslib.EventJournal(
            service=info.node_id, metrics=self.metrics
        )
        # late-bind the lock sanitizer's inversion journal (process-
        # global: multi-node tests share one watcher, last node wins —
        # inversions are process properties, not per-node ones). The
        # emit rides the journal's own INFERD_EVENTS gate.
        lockwatch.set_journal(self.journal.emit)
        # XLA compile detector (obs.devtel): wraps the executor's jitted
        # fns; each cache-size growth becomes compile.begin/end events, a
        # compile.events counter, and a compile.ms histogram sample
        self.compile_watch = devtellib.CompileWatch(self.metrics, self.journal)
        self.trace_dir = trace_dir
        # windowed telemetry plane (obs.tsdb): bounded rings of per-window
        # deltas over this registry, sampled by the 1 s telemetry tick —
        # the trailing-window source behind gossip/health quantiles,
        # GET /metrics/history, burn-rate SLO rules, and fleet SLIs
        self.tsdb = tsdblib.Tsdb(
            self.metrics, service=info.node_id,
            meta={"stage": info.stage, "num_stages": info.num_stages},
        )
        self.tsdb_period_s = 1.0
        # trailing horizon for the gossiped/windowed quantiles — "the
        # last minute" by default; tests shrink it to fast-forward aging
        self.window_s = tsdblib.TRAILING_WINDOW_S
        # synthetic canary prober (obs.canary): off unless run_node
        # --canary-interval > 0; probes the swarm's entry replicas at a
        # bounded rate, recording ONLY canary.* series
        self.canary_interval_s = canary_interval_s
        self.canary: Optional[canarylib.CanaryProber] = None
        self._capture_task: Optional[asyncio.Task] = None
        # event-loop stall watchdog (J009's dynamic twin) — started by
        # start() when lockwatch + events are on, journals `loop.stall`
        self._stall_detector: Optional[lockwatch.LoopStallDetector] = None
        # replica-outlier self-detection result ({"value","median","mad",
        # "field"} while this node's trailing p99 diverges from its stage
        # peers) — journaled, gossiped as `outlier`, penalized by routing
        self._outlier_info: Optional[Dict[str, Any]] = None
        self._tsdb_task: Optional[asyncio.Task] = None
        self._windowed_cache: Tuple[float, Optional[Dict[str, float]]] = (0.0, None)
        # SLO verdict + obs gossip fields, cached ~1 s (a burst of urgent
        # announces, and /health may be polled aggressively)
        self._health_cache: Tuple[float, Optional[Dict[str, Any]]] = (0.0, None)
        self.chaos = chaos
        self.enable_profiling = enable_profiling
        # ---- overload-containment plane (docs/SERVING.md) ----
        # graceful drain: POST /drain flips this; new admissions shed 503
        # code "draining", gossip carries a `draining` flag both routers
        # treat as an exclusion, residents finish or hand off
        self._draining = False
        # pool-aware admission: shed NEW sessions when the paged-KV block
        # pool's free count falls below this fraction of the pool
        # (ROADMAP 2d: backpressure on blocks_free, not lane count)
        self.admission_reserve = admission_reserve
        # hedged relays: after an adaptive (trailing hop p95) delay, an
        # idempotent decode-step relay fires a second copy at another
        # replica and takes the first success. hedge_delay_ms > 0 pins
        # the delay (tests); "advertised" hedges only at replicas that
        # advertise the session's KV, "any" at the second-best ranked
        # pick (stateless backends), "off" disables. The ratio budget
        # caps hedges at <= 5% extra load however slow the tail gets.
        self.hedge_delay_ms = hedge_delay_ms
        self.hedge_mode = hedge_mode
        self.hedge_budget = retrylib.RatioBudget(ratio=0.05, burst=2)
        # the node-side retry budget: the rescue loop's blind re-relays
        # draw from this bucket (same abstraction as the client bucket),
        # so a dead stage produces a bounded rescue rate, not a storm
        self.retry_budget = retrylib.RetryBudget(rate_per_s=4.0, burst=16)
        # dead-peer cooldown (outlier-ejection-lite): a replica whose
        # relay just failed at transport level or answered 5xx is
        # avoided by the FRESH-pick step of _pick_next for this many
        # seconds — new sessions steer around a stalling/dropping
        # replica instead of rediscovering it per request. Never an
        # exclusion for affinity/holder/route picks (KV correctness
        # beats steering) and never applied when it would empty a stage.
        self.peer_cooldown_s = 10.0
        self._peer_cooldown: Dict[str, float] = {}
        # ---- crash-tolerant sessions (async standby KV replication) ----
        # OFF by default: with the flag absent the wire, gossip records,
        # and /metrics stay byte-identical to a build without the plane
        # (docs/SERVING.md "Failover & durability"). Enabled, a periodic
        # tick ships each resident session's newly completed KV past a
        # per-session frontier to a gossip-chosen same-stage standby
        # (anti-affinity: never this node), and THIS node accumulates
        # peers' deltas host-side in the StandbyStore — promoted into
        # the executor only when a failed-over chunk actually arrives.
        self.standby_repl = bool(standby_repl)
        self.repl_interval_s = repl_interval_s
        self.standby: Optional[repllib.StandbyStore] = (
            repllib.StandbyStore(max_sessions=max_sessions)
            if self.standby_repl else None
        )
        self.replicator: Optional[repllib.SessionReplicator] = (
            repllib.SessionReplicator(self._repl_candidates)
            if self.standby_repl else None
        )
        self._repl_task: Optional[asyncio.Task] = None
        # standby peers that recently declined/failed a replication ship:
        # skipped by the standby pick for peer_cooldown_s so a dead or
        # repl-disabled peer isn't re-shipped every tick
        self._repl_peer_cooldown: Dict[str, float] = {}
        # rescue give-up cap: how many times a mid-session chunk landing
        # without its KV bounces through gossip-advertised holders before
        # degrading to the client's 409/restart path (--rescue-bounces;
        # the end_session twin below stays intentionally fixed at ONE
        # bounce — freeing KV early is pure best-effort housekeeping)
        self.rescue_bounces = max(1, int(rescue_bounces))
        self.mesh_plan = mesh_plan
        self.mesh_slots = mesh_slots
        self.quant = quant
        self.batch_lanes = batch_lanes
        # stage-level continuous batching: co-arriving /forward decode
        # steps of concurrent sessions run as ONE device step per window
        # (runtime/stage_batch + runtime/window), and co-batched entries
        # sharing a next hop relay as ONE coalesced envelope (wire.multi)
        self.stage_lanes = stage_lanes
        # paged KV (core.cache.BlockPool): block-granular allocation +
        # refcounted shared-prefix caching with copy-on-write on the lane
        # executors (--paged-kv BLOCK_SIZE; 0 = dense lane slab)
        self.paged_block_size = paged_block_size
        self.kv_blocks = kv_blocks
        # server-side chunked prefill: long admissions ingest in chunks
        # with the device lock released between them, so co-batched decode
        # windows interleave (--prefill-chunk TOKENS; 0 = whole-prompt)
        self.prefill_chunk = prefill_chunk
        self.window_ms = window_ms
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self.spec_draft_layers = spec_draft_layers
        self.spec_k = spec_k
        self.lora = lora
        self._lora_adapter = None  # parsed once on first executor load
        # multi-tenant LoRA registry (run_node --adapters; runtime/
        # adapters.AdapterRegistry): catalog of adapter dirs, bounded
        # device-resident slots, per-session binding via the `adapter`
        # envelope key. STRICTLY exclusive with the merged --lora path —
        # merged weights plus per-lane deltas would serve every tenant
        # two adapters (ops.lora.check_exclusive_modes, loud by contract)
        from inferd_tpu.ops import lora as loralib

        loralib.check_exclusive_modes(lora, adapters, owner=info.node_id)
        self.adapters_spec = adapters
        self.adapter_slots = adapter_slots
        self.adapter_registry = None  # built with the executor
        # lazy self-drafting speculative engines for /generate, one per
        # distinct SAMPLING CONFIG (the warp parameters are baked into each
        # engine's jits — greedy requests share one engine, every sampled
        # config gets its own; caches are per-call so engines only cost
        # compile time). Small LRU: an adversarial client cycling
        # temperatures must not accumulate unbounded jit caches.
        # False value = that config's build/run failed (fast path off);
        # _spec_unsupported = structurally impossible on this executor.
        self._spec_engines: "OrderedDict[tuple, Any]" = OrderedDict()
        self._spec_engines_max = 4
        self._spec_unsupported = False
        self._spec_lock = asyncio.Lock()  # one spec run at a time: the
        # opportunistic shed keeps concurrent requests on the batchable loop
        # static top-N width every spec engine/runner compiles with
        # (core.spec_batch.SPEC_TOP_N — one definition; requests asking
        # for more alternatives take the regular loop instead)
        from inferd_tpu.core.spec_batch import SPEC_TOP_N

        self._spec_top_n = SPEC_TOP_N
        self.profiler = Profiler(recorder=self.tracer)
        if mesh_plan is not None and batch_lanes > 0:
            raise ValueError(
                "--mesh and --batch-lanes are mutually exclusive executor "
                "modes (in-mesh pipelined vs single-device continuous "
                "batching) — pick one"
            )
        if stage_lanes > 0 and (mesh_plan is not None or batch_lanes > 0):
            raise ValueError(
                "--stage-lanes (stage-level continuous batching) is "
                "mutually exclusive with --mesh and --batch-lanes"
            )
        if stage_lanes > 0 and backend != "qwen3":
            raise ValueError("--stage-lanes needs the qwen3 backend")
        if paged_block_size > 0 and not (batch_lanes > 0 or stage_lanes > 0):
            raise ValueError(
                "--paged-kv runs on the lane executors — pair it with "
                "--batch-lanes or --stage-lanes"
            )
        if adapters and not (batch_lanes > 0 or stage_lanes > 0):
            raise ValueError(
                "--adapters (multi-tenant batched LoRA) runs on the lane "
                "executors — pair it with --batch-lanes or --stage-lanes"
            )
        if adapters and backend != "qwen3":
            raise ValueError("--adapters needs the qwen3 backend")
        if mesh_plan is not None and info.num_stages != 1:
            raise ValueError(
                "--mesh hosts the WHOLE model pipelined over this node's "
                f"chips, so the swarm topology must be single-stage "
                f"(num_stages={info.num_stages})"
            )

        from inferd_tpu import native as _native

        self.wire_codec = "native" if _native.codec is not None else "python"
        if _native.codec is None:
            log.info(
                "native wire codec unavailable — running the pure-Python "
                "codec (slower serialization on the hop hot path)"
            )

        self.executor = self._load_executor(info.stage)
        # what this node computes on, as JAX reports it (node.start, /stats):
        # a caller checks the device instead of trusting the flag it passed.
        # The model-free counter backend never touches a JAX backend, so it
        # must not claim a chip just to describe one.
        from inferd_tpu.utils.platform import device_facts

        self.device: Dict[str, Any] = (
            device_facts() if backend != "counter"
            else {"platform": "none", "device_kind": "", "device_count": 0}
        )
        # persistent compile cache counters of this process
        # (utils.platform.CompileCacheStats; None = cache not enabled)
        self.compile_cache = compile_cache
        # continuous batching coalesces decode steps of CONCURRENT requests:
        # the worker pool must admit at least one thread per lane or mesh
        # slot (plus one, so a prefill never starves the decode flusher) or
        # the batch window can never fill past the pool size
        lanes = mesh_slots if mesh_plan is not None else batch_lanes or stage_lanes
        self.scheduler = TaskScheduler(
            workers=max(2, lanes + 1) if lanes else 2,
        )
        # the gossip record is rebuilt when somebody is about to read it
        # (_refresh_record), not when the load moves: `scheduler.ticks`
        # at the last build (-1: stale whatever the ticks say) and the
        # records built (/stats `announce`)
        self._record_tick = -1
        self._record_builds = 0
        dht.before_read = self._refresh_record
        self.balancer = Balancer(
            dht,
            info.num_stages,
            get_own_stage=lambda: self.info.stage,
            change_stage=self.change_stage,
            period_s=rebalance_period_s,
            on_event=self.journal.emit,
        )
        self.path_finder = PathFinder(
            dht, info.num_stages, on_empty_stage=self.balancer.adopt_stage
        )

        self._http: Optional[ClientSession] = None
        self._runner: Optional[web.AppRunner] = None
        self._stopped = asyncio.Event()
        self._sweep_task: Optional[asyncio.Task] = None
        # lazy generation loop of /generate (client.local_client: its hops
        # are calls into _serve_local); persistent so its pinned prefix
        # sessions survive across requests
        self._generate_client = None
        self._generate_client_lock = asyncio.Lock()
        # session affinity: (session_id, stage) -> (node_id, ts). A session's
        # KV cache lives on the specific replica that served its earlier
        # chunks — min-load per request would break multi-step generation
        # whenever a stage has >1 replica.
        self._session_next: "OrderedDict[Tuple[str, int], Tuple[str, float]]" = OrderedDict()
        self._session_next_cap = 8192
        # service-time EWMA announced to the swarm (svc_ms): feeds the
        # chain planner's measured-latency edge-cost term on every node
        # (whole-chain routing itself lives in PathFinder.find_best_chain —
        # the reference's designed-but-unwired D*-Lite, wired via
        # _plan_route below)
        self._svc_ewma: Optional[float] = None

    # ------------------------------------------------------------ lifecycle

    def _quantize(self, params, needs_head: bool = True):
        """Apply the node's serving quantization (run_node --quant) to a
        freshly loaded checkpoint. Weight-only int8 halves the per-token
        HBM weight read — the bs=1 decode bottleneck (ops.quant).
        needs_head=False for non-last stages: they hold embed only for the
        token gather and must not allocate a tied-head shadow."""
        from inferd_tpu.ops import quant as quantlib

        return quantlib.apply_quant_mode(
            self.quant, params,
            tie_word_embeddings=self.cfg.tie_word_embeddings,
            needs_head=needs_head,
        )

    def _apply_lora(self, params, spec):
        """Merge the node's LoRA adapter (run_node --lora) into this stage's
        weight slice — BEFORE quantization, so the adapted weights quantize
        and shard exactly like the base checkpoint (ops.lora)."""
        from inferd_tpu.ops import lora as loralib

        # loud, never a silent pass-through: merged weights + the
        # registry's per-lane deltas would serve every tenant TWO
        # adapters (re-checked here because change_stage reloads params
        # long after __init__'s check)
        loralib.check_exclusive_modes(
            self.lora, self.adapters_spec, owner=self.info.node_id
        )
        if not self.lora:
            return params
        if self._lora_adapter is None:
            self._lora_adapter = loralib.load_adapter(self.cfg, self.lora)
            log.info("merged LoRA adapter from %s", self.lora)
        sliced = loralib.slice_adapter(
            self._lora_adapter, spec.start_layer, spec.end_layer + 1,
            owner=f"{self.info.node_id} stage {spec.stage}",
        )
        return loralib.merge_adapter(params, sliced)

    def _build_adapter_registry(self, spec):
        """The stage's adapter registry (run_node --adapters), holding
        each catalog adapter's THIS-STAGE layer slice; journal events
        wire through the node's flight recorder."""
        from inferd_tpu.runtime.adapters import AdapterRegistry

        reg = AdapterRegistry(
            self.cfg, self.adapters_spec, slots=self.adapter_slots,
            start_layer=spec.start_layer, end_layer=spec.end_layer + 1,
            on_event=self._executor_event,
            owner=f"{self.info.node_id} stage {spec.stage}",
        )
        self.adapter_registry = reg
        return reg

    def _load_executor(self, stage: int):
        """Build the stage executor, then wire its observability hooks:
        lane-pool events (lane.evict, ...) flow into the journal, and the
        compile watch wraps its jitted fns so migrations' recompile
        storms become visible compile.begin/end events instead of
        mystery first-request latency."""
        ex = self._build_executor(stage)
        if hasattr(ex, "on_event"):
            ex.on_event = self._executor_event
        if hasattr(ex, "tracer"):
            # the lane and mesh executors stamp the parts of `compute`
            # (batch_wait, lock_wait, device, copy_out) on this recorder
            ex.tracer = self.tracer
        self.compile_watch.instrument_executor(ex)
        return ex

    #: Wide eviction-age buckets (ms): prefix entries live seconds (churn
    #: thrash) to hours (cold housekeeping) — the default 10 s ladder
    #: would saturate everything interesting into +Inf.
    _EVICT_AGE_BOUNDS_MS = [
        100, 500, 1000, 5000, 15_000, 60_000, 300_000, 900_000,
        3_600_000, 14_400_000,
    ]

    def _executor_event(self, etype: str, **attrs):
        """Executor flight-recorder hook: journal every event (as before)
        and additionally feed the metrics the journal alone can't carry —
        the prefix-eviction AGE histogram (`kv.prefix_evict_age_ms`): an
        eviction population aging out young means the prefix index is
        thrashing under churn (grow the pool / raise pins), aging out old
        means ordinary LRU housekeeping. Events-gated like every kv.*
        series so a disabled node's /metrics stays byte-identical."""
        if (
            etype == "prefix.evict" and eventslib.enabled()
            and isinstance(attrs.get("age_ms"), (int, float))
        ):
            self.metrics.observe(
                "kv.prefix_evict_age_ms", float(attrs["age_ms"]),
                bounds_ms=self._EVICT_AGE_BOUNDS_MS,
            )
        return self.journal.emit(etype, **attrs)

    def _build_executor(self, stage: int):
        if self.backend == "counter":
            spec = stagelib.StageSpec(stage, self.info.num_stages, stage, stage)
            return make_executor(self.cfg, spec, backend="counter")
        if self.batch_lanes > 0:
            # continuous batching: whole model, sessions map to batch lanes,
            # concurrent decode steps coalesce into one device step
            from inferd_tpu.runtime.batch_executor import BatchedExecutor

            if self.info.num_stages != 1:
                raise ValueError(
                    "--batch-lanes hosts the WHOLE model, so the swarm "
                    f"topology must be single-stage (got {self.info.num_stages})"
                )
            path = stagelib.stage_checkpoint_path(self.parts_dir, 0)
            params, spec, model_name = stagelib.load_stage_checkpoint(path)
            if spec.num_stages != 1:
                raise ValueError(
                    f"--batch-lanes needs a 1-stage checkpoint, got stage "
                    f"{spec.stage}/{spec.num_stages} at {path}"
                )
            self.info.model_name = model_name
            ex = BatchedExecutor(
                self.cfg, self._quantize(self._apply_lora(params, spec)),
                lanes=self.batch_lanes, max_len=self.max_len,
                block_size=self.paged_block_size, kv_blocks=self.kv_blocks,
                prefill_chunk=self.prefill_chunk,
                adapters=(
                    self._build_adapter_registry(spec)
                    if self.adapters_spec else None
                ),
            )
            if self.spec_draft_layers > 0:
                # lane-batched speculation (core.spec_batch): concurrent
                # /generate requests speculate TOGETHER instead of shedding
                # to the regular loop (the solo engine path stays for
                # single-stage stage executors). Capacity note: every
                # lane's budget shrinks by k+1 (verify-chunk headroom).
                try:
                    ex.enable_spec(self.spec_draft_layers, self.spec_k)
                except ValueError as e:
                    log.warning(
                        "lane speculation disabled (%s); serving without", e
                    )
            return ex
        if self.mesh_plan is not None:
            # north-star serving path: whole model in-mesh pipelined over
            # this node's chips (stage checkpoint 0 of a 1-stage manifest
            # holds the full params)
            from inferd_tpu.runtime.mesh_executor import MeshExecutor

            path = stagelib.stage_checkpoint_path(self.parts_dir, 0)
            params, spec, model_name = stagelib.load_stage_checkpoint(path)
            if spec.num_stages != 1:
                raise ValueError(
                    f"mesh mode needs a 1-stage checkpoint, got stage "
                    f"{spec.stage}/{spec.num_stages} at {path}"
                )
            self.info.model_name = model_name
            return MeshExecutor(
                self.cfg, self._quantize(self._apply_lora(params, spec)),
                self.mesh_plan,
                num_slots=self.mesh_slots, max_len=self.max_len,
                # in-mesh speculation: draft layers replicate on every
                # rank, the verify chunk rides the ppermute pipeline —
                # --mesh pp=N nodes can finally speculate (r04 weak #1)
                spec_draft_layers=self.spec_draft_layers,
                spec_k=self.spec_k,
            )
        path = stagelib.stage_checkpoint_path(self.parts_dir, stage)
        params, spec, model_name = stagelib.load_stage_checkpoint(path)
        if spec.stage != stage:
            raise ValueError(f"checkpoint {path} is for stage {spec.stage}, not {stage}")
        self.info.model_name = model_name
        if self.stage_lanes > 0:
            # stage-level continuous batching: sessions map to lanes of ONE
            # shared stage KV cache; co-arriving decode steps run as one
            # device step (the window lives on the node — _attach_window)
            from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

            ex = BatchedStageExecutor(
                self.cfg, spec,
                self._quantize(
                    self._apply_lora(params, spec), needs_head=spec.is_last
                ),
                lanes=self.stage_lanes, max_len=self.max_len,
                session_ttl_s=600.0,
                block_size=self.paged_block_size, kv_blocks=self.kv_blocks,
                prefill_chunk=self.prefill_chunk,
                adapters=(
                    self._build_adapter_registry(spec)
                    if self.adapters_spec else None
                ),
            )
            self._attach_window(ex)
            return ex
        return make_executor(
            self.cfg, spec,
            self._quantize(self._apply_lora(params, spec), needs_head=spec.is_last),
            max_len=self.max_len, max_sessions=self.max_sessions,
        )

    def _attach_window(self, executor) -> None:
        """Give a batch-capable executor its arrival window: co-arriving
        decode steps from different sessions become ONE process_batch
        device step (runtime/window semantics), and the flusher relays the
        co-batch as coalesced envelopes. The window is bound to THIS
        executor instance so a stage migration's swapped-in executor gets
        its own (requests bind the executor at entry, so an in-flight
        window always flushes against the executor it admitted on)."""
        batcher = WindowedBatcher(
            self.window_ms / 1e3,
            lambda entries, _ex=executor: self._run_stage_window(_ex, entries),
            # lock-free live-session count: a solo session must not pay
            # the window latency (and co_possible is called under the
            # batcher's lock — taking the executor's lock here would
            # invert the on_drop -> invalidate lock order)
            co_possible=executor.co_possible,
            # continuous batching: the batch forms at DEVICE-LOCK
            # acquisition (process_batch's drain), not at flusher wake-up,
            # so entries arriving mid-step join the next step instead of
            # fragmenting into a convoy of mini-batches
            swap_in_run=True,
            # gang formation: wait (bounded by window_ms) for every live
            # idle session's step — merges phase-offset session cohorts
            # into one lockstep co-batch (see window.py)
            gang_target=executor.gang_target,
        )
        executor.window = batcher
        batcher.on_event = self.journal.emit
        executor.on_drop = lambda sid: batcher.invalidate(
            lambda payload, _sid=sid: payload[0] == _sid,
            ValueError(f"session {sid} ended mid-request"),
        )

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        await self.dht.start()
        self._http = ClientSession(timeout=ClientTimeout(total=self.hop_timeout_s))
        app = web.Application(client_max_size=1 << 30)
        app.add_routes(
            [
                web.post(FORWARD_PATH, self.handle_forward),
                web.post(REASSIGN_PATH, self.handle_reassign),
                web.post(END_SESSION_PATH, self.handle_end_session),
                web.post(FORK_SESSION_PATH, self.handle_fork_session),
                web.post(GENERATE_PATH, self.handle_generate),
                web.post(IMPORT_SESSION_PATH, self.handle_import_session),
                web.post(EXPORT_SESSION_PATH, self.handle_export_session),
                web.post(DRAIN_PATH, self.handle_drain),
                web.post(REPLICATE_SESSION_PATH, self.handle_replicate_session),
                web.get("/health", self.handle_health),
                web.get("/stats", self.handle_stats),
                web.get("/metrics", self.handle_metrics),
                web.get("/metrics/history", self.handle_metrics_history),
                web.get("/spans", self.handle_spans),
                web.get("/events", self.handle_events),
                web.post("/profile", self.handle_profile),
            ]
        )
        # bounded graceful drain on stop(); crash() drops it to zero
        self._runner = web.AppRunner(app, shutdown_timeout=5.0)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.info.host, self.info.port)
        await site.start()
        self.journal.emit(
            "node.start", stage=self.info.stage,
            num_stages=self.info.num_stages, **self.device,
        )
        # pay the decode-step compile before the swarm can route here (the
        # same eager warm-up a stage migration runs): the first real request
        # must not eat it, and a warm-up that failed is on the record
        # (executor.warmup_failed) before any request is. The counter
        # backend has nothing to compile.
        if self.backend != "counter":
            await self._loop.run_in_executor(
                None, _warmup_executor, self.executor, self.journal
            )
        self.announce()
        self.balancer.start()
        self._sweep_task = asyncio.create_task(self._sweep_loop())
        self._tsdb_task = asyncio.create_task(self._tsdb_loop())
        if lockwatch.watching() and eventslib.enabled():
            # stall watchdog: a handler blocking this loop > 50 ms shows
            # up as a `loop.stall` event (env-gated like the lock proxies
            # — INFERD_LOCKWATCH=0 keeps production byte-identical)
            self._stall_detector = lockwatch.LoopStallDetector(
                on_event=self.journal.emit
            ).start()
        if self.standby_repl:
            if not callable(
                getattr(self.executor, "export_session_delta", None)
            ):
                # a loud no-op beats a silent one: the operator asked for
                # crash tolerance, but this executor type (e.g. --mesh)
                # has no incremental export surface yet — this node will
                # ACCEPT peers' shadows and promote them, but its own
                # resident sessions ship nothing and still pay a full
                # restart on a crash
                log.warning(
                    "--standby-repl: executor %s has no "
                    "export_session_delta — this node accepts standby "
                    "shadows but cannot replicate its own sessions "
                    "(crash recovery for residents stays the client-"
                    "restart path)",
                    type(self.executor).__name__,
                )
            self._repl_task = asyncio.create_task(self._repl_loop())
        if self.chaos is not None and getattr(self.chaos, "crash_after", 0):
            # chaos crash_after=N: abrupt handler death — no graceful
            # stop, no handoff, KV lost. The hook schedules crash() (the
            # SIGKILL-equivalent teardown) so failover tests can kill a
            # KV holder deterministically after N forwards
            loop = asyncio.get_running_loop()
            self.chaos.on_crash = lambda: loop.create_task(self.crash())
        if self.canary_interval_s > 0:
            self.canary = canarylib.CanaryProber(
                self._canary_targets, self.metrics, journal=self.journal,
                tracer=self.tracer, interval_s=self.canary_interval_s,
                timeout_s=min(self.hop_timeout_s, 30.0),
            )
            self.canary.start()
        if self.spec_draft_layers > 0:
            # compile the greedy speculative engine off the critical path;
            # the first request then hits a warm engine (or waits briefly
            # on the shared build) instead of paying it alone
            self._spec_prebuild_task = asyncio.create_task(
                self._prebuild_spec_engine()
            )
        log.info(
            "node %s up: stage %d/%d on %s:%d",
            self.info.name, self.info.stage, self.info.num_stages,
            self.info.host, self.info.port,
        )

    async def stop(self) -> None:
        self.dht.withdraw()
        if self._stall_detector is not None:
            self._stall_detector.stop()
            self._stall_detector = None
        if self._repl_task:
            self._repl_task.cancel()
            try:
                await self._repl_task
            except asyncio.CancelledError:
                pass
            self._repl_task = None
        if self._sweep_task:
            self._sweep_task.cancel()
            try:
                await self._sweep_task
            except asyncio.CancelledError:
                pass
        if self._tsdb_task:
            self._tsdb_task.cancel()
            try:
                await self._tsdb_task
            except asyncio.CancelledError:
                pass
            self._tsdb_task = None
        if self.canary is not None:
            await self.canary.stop()
            self.canary = None
        if self._capture_task is not None:
            self._capture_task.cancel()
            try:
                await self._capture_task
            except asyncio.CancelledError:
                pass
            self._capture_task = None
        if self.profiler.active_dir is not None:
            # a capture window still open at shutdown: close it so the
            # trace flushes
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, self.profiler.stop
                )
            except Exception:
                log.exception("profiler stop at shutdown failed")
        t = getattr(self, "_spec_prebuild_task", None)
        if t is not None:
            t.cancel()
            try:
                await t
            except asyncio.CancelledError:
                pass
        await self.balancer.stop()
        if self._generate_client is not None:
            try:
                # drops its pinned prefix sessions
                await self._generate_client.__aexit__(None, None, None)
            except Exception:
                pass
            self._generate_client = None
        if self.chaos is not None:
            # stalled (slow-loris) handlers never finish on their own —
            # they must not hold the graceful drain below hostage
            self.chaos.cancel_stalls()
        if self._runner:
            # stop accepting + drain in-flight requests BEFORE the session
            # export: a chunk completing after the export snapshot would be
            # missing from the handed-off copy and 409 the failed-over
            # client into a restart
            await self._runner.cleanup()
        # graceful shutdown hands live session KV to surviving same-stage
        # replicas (the same machinery as migration handoff), so a client
        # that fails over to another entry continues WITHOUT a session
        # restart. Best effort: a crash (no stop()) still loses the KV and
        # falls back to the client's restart path.
        await self._export_and_handoff(self.executor, self.info.stage)
        if self._http:
            await self._http.close()
        await self.dht.stop()
        self.scheduler.shutdown()
        self.journal.emit("node.stop", stage=self.info.stage)
        self._flush_obs()  # final flush: the merge/postmortem CLIs read these
        self._stopped.set()

    async def _export_and_handoff(self, executor, stage: int) -> None:
        """Export `executor`'s live session KV and ship it to the remaining
        replicas of `stage` (shared by graceful stop() and change_stage
        migration). Best effort: failures degrade to client restarts."""
        export = getattr(executor, "export_sessions", None)
        if export is None or self._http is None:
            return
        try:
            loop = asyncio.get_running_loop()
            exported = await loop.run_in_executor(None, export)
            if exported:
                await self._handoff_sessions(exported, stage)
        except Exception:
            log.exception("session handoff failed (clients will restart)")

    # ------------------------------------------------------------- announce

    def _advertised_sessions(self) -> list:
        """Hashes of the sessions whose KV lives HERE — gossiped in this
        node's record so a peer (a failed-over entry, a mid-chain relay)
        can route a session's next chunk to the replica actually holding
        it instead of 409ing into a client restart."""
        store = getattr(self.executor, "sessions", None)
        ids_fn = getattr(store, "ids", None)
        if not callable(ids_fn):
            return []
        # keep the NEWEST 128 (insertion order) — a just-adopted handoff
        # session must make the advert, or the failed-over client that the
        # handoff exists for can't find it
        return sorted(sess_hash(s) for s in ids_fn()[-128:])

    def _advertised_standby(self) -> list:
        """Hashes of the sessions whose REPLICATED (shadow) KV lives here
        — gossiped as `standby` so the rescue path can find a promotion
        target when no live `sess` holder remains. Only ever present
        with --standby-repl on: a disabled node's gossip record stays
        byte-identical to a build without the replication plane."""
        if self.standby is None:
            return []
        return sorted(sess_hash(s) for s in self.standby.ids()[-128:])

    def _windowed_gossip(self) -> Dict[str, float]:
        """TRAILING-WINDOW hop/service quantiles for gossip and /health
        (obs.tsdb, last 60 s) — replacing the all-time numbers PR 3
        gossiped: a replica that was slow an hour ago and recovered must
        stop reporting an elevated p99 within the window horizon, or
        routing and outlier detection act on history instead of now.
        Cached ~1 s (announce() runs per session event); the inline
        sample() keeps the window current between telemetry ticks
        (mid-bucket samples merge idempotently). Keys are omitted when
        the window holds no observations — never backfilled from the
        cumulative histograms."""
        now = time.monotonic()
        ts, cached = self._windowed_cache
        if cached is not None and now - ts < 1.0:
            return cached
        self.tsdb.sample()
        out: Dict[str, float] = {}
        hq = self.tsdb.trailing_quantiles("hop.relay_ms", self.window_s)
        if hq is not None:
            out["hop_p50_ms"] = hq["p50_ms"]
            out["hop_p99_ms"] = hq["p99_ms"]
        # trailing stage-compute p99: the outlier detector's fallback
        # comparison field — last-stage replicas relay nothing, so they
        # have no hop series to compare on (obs.canary.detect_outliers)
        sq = self.tsdb.trailing_quantiles(
            "stage.compute_ms", self.window_s, qs=(0.99,)
        )
        if sq is not None:
            out["svc_p99_ms"] = sq["p99_ms"]
        self._windowed_cache = (now, out)
        return out

    def _canary_targets(self):
        """Current entry-replica candidates for the canary prober: the
        gossiped stage-0 records (every chain starts there)."""
        return sorted(
            (str(v["host"]), int(v["port"]))
            for v in self.dht.get_stage(0).values()
            if v.get("host") and v.get("port")
        )

    async def _tsdb_loop(self) -> None:
        """Fixed-cadence telemetry tick: fold the registry into the
        windowed rings every `tsdb_period_s` (so idle periods age the
        window out instead of freezing it), refresh gauges every 5th
        tick, and every 2nd tick run replica-outlier self-detection and
        re-announce (non-urgent — the gossip loop carries it): the
        gossiped trailing quantiles must keep tracking the window even
        when no load change triggers an announce, or peers would compare
        against quantiles frozen at each node's last request."""
        tick = 0
        while True:
            await asyncio.sleep(self.tsdb_period_s)
            tick += 1
            try:
                if tick % 5 == 0 and eventslib.enabled():
                    self._update_gauges()
                self.tsdb.sample()
                if tick % 2 == 0:
                    # announce first: the outlier check then reads the
                    # record just built (a flip announces again, urgently)
                    self.announce(urgent=False)
                    self._check_outlier()
            except Exception:
                log.exception("telemetry tick failed")

    def _check_outlier(self) -> None:
        """Flag THIS node when its trailing p99 diverges >= k*MAD from
        its stage peers' (obs.canary.detect_outliers over the gossiped
        windowed quantiles, own record overlaid with the freshest local
        window). Transitions journal `replica.outlier`/`.outlier_cleared`
        and re-announce urgently so the gossiped `outlier` flag — and the
        routing penalty every peer applies to it — propagates within a
        gossip period, not a cache lifetime."""
        if not eventslib.enabled():
            self._outlier_info = None
            return
        stage_map = {
            nid: dict(rec)
            for nid, rec in self.dht.get_stage(self.info.stage).items()
        }
        own = stage_map.setdefault(self.info.node_id, {})
        own.update(self._windowed_gossip())
        info = canarylib.detect_outliers(stage_map).get(self.info.node_id)
        was = self._outlier_info is not None
        self._outlier_info = info
        if info is not None and not was:
            self.journal.emit(
                "replica.outlier", stage=self.info.stage,
                field=info["field"], value=round(info["value"], 3),
                median=round(info["median"], 3), mad=round(info["mad"], 3),
            )
        elif info is None and was:
            self.journal.emit(
                "replica.outlier_cleared", stage=self.info.stage
            )
        if (info is not None) != was:
            self._health_cache = (0.0, None)  # gossip carries the flag
            self.announce()

    def _cobatch_mean(self) -> Optional[float]:
        """Mean co-batch size of this node's stage window (None when the
        node doesn't window) — gossiped so the dashboard shows batching
        effectiveness per node with zero extra round trips."""
        win = getattr(getattr(self, "executor", None), "window", None)
        if win is None:
            return None
        return win.stats()["mean_batch"]

    def _health_state(self) -> Dict[str, Any]:
        """SLO verdict over this node's own registry + journal + gossiped
        peers, plus the obs gossip fields derived from the same snapshot
        (health column, hbm%, compile count for the dashboard). Cached
        ~1 s: announce() runs per session event and must not re-evaluate
        the rule set (or re-scrape device memory) each time."""
        now = time.monotonic()
        ts, cached = self._health_cache
        if cached is not None and now - ts < 1.0:
            return cached
        self._update_gauges()
        self.tsdb.sample()
        snap = self.metrics.snapshot()
        # TRAILING-WINDOW histogram summaries replace the all-time ones
        # for rule evaluation: `hop.relay_ms.p99_ms < 2000` must judge
        # the last minute, not the process's whole life — a recovered
        # node stops firing within the window horizon. A histogram with
        # no observations inside the window resolves to nothing, so its
        # rules SKIP (no data is not green).
        trailing: Dict[str, Any] = {}
        for name in snap["histograms"]:
            s = self.tsdb.trailing_summary(name)
            if s is not None:
                trailing[name] = {k: round(v, 3) for k, v in s.items()}
        rule_snap = dict(snap, histograms=trailing)
        peers: Dict[str, Dict[str, Any]] = {}
        for stage_map in self.dht.get_all(self.info.num_stages).values():
            for nid, rec in stage_map.items():
                if nid != self.info.node_id:
                    peers[nid] = rec
        # events=None (not []) when the journal is killed: event rules
        # must SKIP (no data), not evaluate against a silent ring —
        # metric-only rules (queue.depth, hop p99, trace.dropped, hbm)
        # keep working so INFERD_EVENTS=0 doesn't blind the SLO engine
        verdict = healthlib.evaluate(
            healthlib.DEFAULT_RULES, rule_snap,
            events=self.journal.events() if eventslib.enabled() else None,
            peers=peers,
            histories=[self.tsdb.history()],
        )
        gossip: Dict[str, Any] = {"health": verdict["status"]}
        if self._outlier_info is not None:
            # self-detected replica outlier: peers' routing applies
            # OUTLIER_PENALTY to this record (control/path_finder, dstar)
            gossip["outlier"] = 1
        frac = snap["gauges"].get("hbm.frac")
        if frac is not None:
            gossip["hbm"] = round(float(frac), 3)
        # short-window availability burn (obs.health.burn_gauges, already
        # refreshed into the registry by _update_gauges): gossiped so
        # fleet controllers (control.autoscale, tools/collector) see
        # which stage is burning user error budget without scraping
        # every node — the SLO-side scale-up trigger next to kvfree
        burn = snap["gauges"].get("burn.availability")
        if burn is not None:
            gossip["burn"] = round(float(burn), 2)
        # trailing-window prefix-cache hit rate (memory-plane SLI): the
        # collector's per-stage `cachehit` column and the dashboard cell;
        # omitted when the window saw no prompt traffic (windowed
        # semantics — never a frozen ratio), on dense executors, and with
        # events disabled (the kv.* series don't exist then)
        ch = self._cachehit_frac()
        if ch is not None:
            gossip["cachehit"] = ch
        compiles = snap["counters"].get("compile.events")
        if compiles:
            gossip["compiles"] = int(compiles)
        cached = {"verdict": verdict, "gossip": gossip}
        self._health_cache = (now, cached)
        return cached

    def _kvfree_frac(self) -> Optional[float]:
        """Paged-KV block-pool free fraction (blocks_free / num_blocks) —
        gossiped as `kvfree` so fleet controllers see the MEMORY capacity
        signal PR 10's admission shed gates on locally: a replica about
        to shed is about to shed no matter what its lane load says. The
        same watermark feeds control.autoscale's scale-up trigger. None
        (key omitted) on dense executors — absent is not 1.0."""
        pool = getattr(self.executor, "pool", None)
        if pool is None:
            return None
        try:
            total = int(pool.num_blocks)
            free = int(pool.blocks_free)
        except Exception:
            return None
        return round(free / total, 4) if total else None

    def announce(self, urgent: bool = True) -> None:
        """Build this node's record and publish it: urgent (membership,
        a session's arrival or departure, a flag a router acts on)
        gossips at once; urgent=False leaves it to the next send. The
        load a hop moves is NOT announced: see _refresh_record."""
        # first, so that the reads this build makes itself (the health
        # verdict's peers, an urgent send's serialisation) find it fresh
        self._record_tick = self.scheduler.ticks
        self._record_builds += 1
        sess = self._advertised_sessions()
        stand = self._advertised_standby()
        wq = self._windowed_gossip()
        cb = self._cobatch_mean()
        kvfree = self._kvfree_frac()
        pfx = self._prefix_digest()
        ada = self._adapter_digest()
        shedding = self._pool_under_reserve() is not None
        obs_gossip = (
            self._health_state()["gossip"]
            if eventslib.enabled() else {}
        )
        self.dht.announce(
            {
                "name": self.info.name,
                "stage": self.info.stage,
                "load": self.scheduler.inflight,
                "cap": self.info.capacity,
                "host": self.info.host,
                "port": self.info.port,
                "model": self.info.model_name,
                **(
                    {"svc_ms": round(self._svc_ewma, 3)}
                    if self._svc_ewma is not None
                    else {}
                ),
                # trailing-window quantiles (_windowed_gossip): same key
                # names PR 3 gossiped, windowed semantics — old peers
                # read them unchanged, plus the new svc_p99_ms which
                # they (and any other unknown key) simply ignore
                **wq,
                **({"cobatch": cb} if cb is not None else {}),
                # block-pool free fraction: a control-plane capacity
                # signal (ungated — it must survive INFERD_EVENTS=0,
                # like load/cap); old peers ignore the unknown key
                **({"kvfree": kvfree} if kvfree is not None else {}),
                # memory-plane routing signals (ungated, like kvfree):
                # `pfx` = the prefix-index digest entry routers score
                # cache affinity against (core.prefix.make_digest);
                # `shed` = currently under the admission watermark, so
                # routers suppress the affinity bonus and penalize
                # affinity-scored picks here. Old peers pass both keys
                # through bit-true and ignore them (the PR 7 mixed-
                # version gossip contract).
                **({"pfx": pfx} if pfx else {}),
                # resident-adapter digest (multi-tenant LoRA, the `pfx`
                # pattern): bounded name list routers score adapter
                # affinity against (runtime/adapters.AdapterAffinity).
                # OMITTED without --adapters (the kill-switch contract
                # keeps disabled records byte-identical) but PRESENT —
                # `[]` — with an empty registry: key presence marks
                # adapter capability for handoff/standby target picks;
                # old peers pass the key through bit-true
                **({"ada": ada} if ada is not None else {}),
                **({"shed": 1} if shedding else {}),
                **obs_gossip,
                # drain flag: both routers (min-load ranked pick and the
                # D*-Lite planner) treat it as an exclusion; old peers
                # ignore the unknown key and keep routing here — drain
                # converges at fleet-upgrade speed, never breaks mixed
                **({"draining": 1} if self._draining else {}),
                **({"sess": sess} if sess else {}),
                # replicated-session advert (crash-tolerant sessions):
                # ONLY emitted with --standby-repl on AND shadows held —
                # the kill-switch contract keeps disabled records
                # byte-identical. Old peers ignore the unknown key.
                **({"standby": stand} if stand else {}),
            },
            urgent=urgent,
        )

    def _refresh_record(self) -> None:
        """dht.before_read: the own record is about to be read (a gossip
        send, a local `get_stage` / `get_all` that may rank this node by
        its load). Rebuild it if a hop has entered or left the scheduler
        since it was built, or _record_moved() marked it: a peer receives
        the load AT the send, a local reader the exact `inflight`, and
        the hops in between cost two integer updates."""
        if self._record_tick != self.scheduler.ticks:
            self.announce(urgent=False)

    def _record_moved(self) -> None:
        """Something the record advertises changed and no peer needs it
        before the next gossip send: the next reader rebuilds it."""
        self._record_tick = -1

    def _obs_file(self, suffix: str) -> Optional[str]:
        if not self.trace_dir:
            return None
        return os.path.join(
            self.trace_dir,
            self.info.node_id.replace(":", "_") + suffix,
        )

    def _span_file(self) -> Optional[str]:
        return self._obs_file(".spans.jsonl")

    def _flush_obs(self) -> None:
        """Flush the per-node observability artifacts the offline CLIs
        (merge, health, postmortem) consume: new spans and journal events
        append to their JSONL files WITHOUT draining the rings — /spans,
        /events, and the gossiped summaries must keep seeing the recent
        buffers between flushes — and one metrics snapshot line appends
        per flush (the incident report's "metrics window")."""
        path = self._span_file()
        if path is None:
            return
        try:
            self.tracer.flush_jsonl(path)
        except OSError:
            log.exception("span dump to %s failed", path)
        if not eventslib.enabled():
            return
        try:
            self.journal.flush_jsonl(self._obs_file(".events.jsonl"))
            self._update_gauges()
            line = json.dumps(
                {
                    "ts": tracelib.now(),
                    "service": self.info.node_id,
                    **self.metrics.snapshot(),
                },
                separators=(",", ":"),
            )
            with open(self._obs_file(".metrics.jsonl"), "a") as f:
                f.write(line + "\n")
            # windowed-history dump (OVERWRITTEN, not appended — the
            # rings carry their own retention): the offline half of the
            # fleet SLI pipeline (`obs fleet`, `obs health --check` burn
            # rules) reads these next to the span/event files. Written
            # via rename so a kill mid-dump can't leave a truncated file
            self.tsdb.sample()
            hist_path = self._obs_file(".history.json")
            with open(hist_path + ".tmp", "w") as f:
                json.dump(self.tsdb.history(), f, separators=(",", ":"))
            os.replace(hist_path + ".tmp", hist_path)
        except OSError:
            log.exception("journal/metrics dump failed")

    async def _sweep_loop(self, period_s: float = 30.0) -> None:
        """Collect orphaned sessions: executor KV caches past their idle TTL
        and stale session-affinity entries. Also flushes the span ring to
        the per-node JSONL file so a long trace outlives the ring cap."""
        while True:
            await asyncio.sleep(period_s)
            try:
                sessions = getattr(self.executor, "sessions", None)
                if sessions is not None:
                    dropped = sessions.sweep()
                    if dropped:
                        self.metrics.inc("sessions.swept", dropped)
                if self.standby is not None:
                    swept = self.standby.sweep()
                    if swept and eventslib.enabled():
                        self.metrics.inc("repl.standby_swept", swept)
                cutoff = time.monotonic() - 3600.0
                while self._session_next:
                    key, (_, ts) = next(iter(self._session_next.items()))
                    if ts >= cutoff:
                        break
                    self._session_next.popitem(last=False)
                self._flush_obs()
            except Exception:
                log.exception("session sweep failed")

    # ------------------------------------------------------------- handlers

    async def handle_forward(self, request: web.Request) -> web.Response:
        t0 = time.perf_counter()
        try:
            env = wire.unpack(await request.read())
        except Exception as e:
            return self._error_response(400, f"bad envelope: {e}")
        if isinstance(env, dict) and env.get(wire.MULTI_KEY) is not None:
            return await self._handle_multi_forward(env, t0)
        return _as_response(await self._forward_one(env, t0))

    async def _handle_multi_forward(self, env, t0: float) -> web.Response:
        """A coalesced relay envelope: N sessions' decode activations in
        one POST (wire.coalesce_forward). Fan the frames back out into
        single-session envelopes and run them CONCURRENTLY through the
        ordinary forward path — on a windowed executor they co-arrive and
        co-batch into one device step; every other path (rescue, re-route,
        chain) applies per frame unchanged. The reply is one multi
        envelope carrying each frame's packed reply + status."""
        try:
            frames = wire.split_forward(env)
        except Exception as e:
            return self._error_response(400, f"bad multi envelope: {e}")
        self.metrics.inc("forward.multi_envelopes")
        self.metrics.inc("forward.multi_frames", len(frames))
        resps = [
            _as_response(r) for r in await asyncio.gather(
                *(self._forward_one(f, t0) for f in frames)
            )
        ]
        multi = [
            {"status": r.status, "body": bytes(r.body or b"")} for r in resps
        ]
        return web.Response(body=wire.pack({wire.MULTI_KEY: multi}))

    async def _forward_one(
        self, env, t0: float, via: str = "http",
    ) -> Union[web.Response, Dict[str, Any]]:
        """The ONE forward path, entered by the /forward route (`via`
        "http") and by the node's own generation loop ("local",
        _serve_local) with the same envelope dict. A hop this node
        finishes comes back as the reply DICT — the route packs it, the
        local caller reads it as it is; every other outcome (an error, a
        relayed hop's downstream reply) is a Response holding wire bytes."""
        if not tracelib.enabled():
            return await self._forward_inner(env, t0, None, via)
        # server umbrella span for this hop: parented to the `trace` key
        # riding the envelope (a client step span or an upstream relay
        # span — its send/recv pair brackets this span for the merge
        # CLI's skew correction); queue/compute/relay children hang off it
        # (a hop of this node's own loop: the `step` itself, with its `keep`)
        parent = tracelib.adopt(tracelib.SpanContext.from_wire(env.get(tracelib.WIRE_KEY)))
        tin = (
            parent.child() if parent is not None
            else tracelib.SpanContext(tracelib.new_id(), tracelib.new_id())
        )
        t_wall = tracelib.now()
        try:
            return await self._forward_inner(env, t0, tin, via)
        finally:
            try:
                stage_attr = int(env.get("stage", 0))
            except (TypeError, ValueError):
                stage_attr = -1
            self.tracer.record_span(
                "forward", "server", t_wall, tracelib.now(),
                parent=parent, ctx=tin,
                attrs={"stage": stage_attr, "via": via},
            )

    async def _forward_inner(
        self, env: Dict[str, Any], t0: float,
        tin: Optional[tracelib.SpanContext], via: str,
    ) -> Union[web.Response, Dict[str, Any]]:
        stage = int(env.get("stage", 0))
        session_id = env.get("session_id") or str(uuid.uuid4())
        # opaque on the wire; made without a system call (tracelib.new_id)
        task_id = env.get("task_id") or tracelib.new_id()
        # end-to-end deadline riding the envelope (absent on deadline-less
        # traffic and from old peers — behavior is then identical to
        # before deadlines existed). An EXPIRED budget fast-fails with
        # the typed non-retryable `deadline` code BEFORE any relay,
        # rescue bounce, or compute: a request that cannot make it back
        # in time must stop consuming the chain's work.
        deadline_ms = env.get(retrylib.DEADLINE_KEY)
        rem = retrylib.remaining_s(deadline_ms)
        if rem is not None and rem <= 0:
            return self._deadline_response(tin, session_id, stage, "entry")

        if stage != self.info.stage:
            self.metrics.inc("forward.mismatch")
            if not env.get("relay", True):
                # chain mode promises a FIXED topology: a mismatch means the
                # client's server_addrs list is stale (this node migrated) or
                # misordered. Rerouting via the DHT would silently violate
                # that contract and orphan the session's KV on a replica the
                # client will never address again — fail loudly instead.
                return self._error_response(
                    409,
                    f"wrong stage: this node serves {self.info.stage}, not {stage}",
                    code="wrong_stage",
                )
            # wrong node for this stage: relay to a correct one (reference
            # node.py:139-141), excluding ourselves to avoid a loop
            try:
                return await self._relay(
                    env, stage, exclude={self.info.node_id}, tin=tin,
                    span_attrs={"mismatch": True},
                )
            except NoNodeForStage as e:
                if stage != self.info.stage:
                    return self._error_response(503, str(e))
                # the empty-stage recovery hook migrated *us* to this stage
                # during the retry loop — serve the request locally

        try:
            start_pos = int(env.get("payload", {}).get("start_pos", -1))
        except (TypeError, ValueError, AttributeError):
            start_pos = -1  # malformed payloads fail in the guarded compute

        if start_pos == 0:
            # ADMISSION CONTROL: a brand-new session asks this replica to
            # allocate KV it will hold for the session's whole life —
            # shed it (typed 503 + a Retry-After pacing hint derived from
            # window occupancy) while draining or while the paged block
            # pool is under its free-watermark reserve. Mid-session
            # chunks (start_pos > 0) are never shed here: their KV is
            # already resident and finishing them RELEASES capacity.
            shed = self._admission_shed()
            if shed is not None:
                code, msg = shed
                ra = self._retry_after_s()
                self.metrics.inc("admission.shed")
                self.journal.emit(
                    "admission.shed", trace=tin, session=session_id,
                    stage=stage, code=code, retry_after=ra,
                )
                return self._error_response(
                    503, msg, code=code, retry_after=ra
                )

        if (
            env.get("relay", True)
            and "route" not in env
            and start_pos == 0
            and stage + 1 < self.info.num_stages
        ):
            # NEW session entering here: plan the whole downstream chain via
            # the incremental D*-Lite planner; the route rides the envelope
            # so every relay hop follows the planned replica (affinity then
            # pins it). Planning failure (e.g. an empty stage mid-recovery)
            # falls back to the per-hop min-load pick. A tenant session's
            # adapter earns downstream holders the bounded affinity bonus
            # (runtime/adapters.AdapterAffinity through dstar.node_cost) —
            # a miss just hot-loads there, so the bonus is pure savings.
            ad_key = (env.get("payload") or {}).get("adapter")
            affinity = None
            if ad_key is not None:
                from inferd_tpu.runtime.adapters import AdapterAffinity

                affinity = AdapterAffinity(str(ad_key))
            route = self._plan_route(stage + 1, affinity=affinity)
            if route:
                env["route"] = route

        if (
            env.get("relay", True)
            and not env.get("rescued")
            and start_pos > 0
            and env.get("session_id") is not None
            and not self._holds_session(session_id)
        ):
            # mid-session chunk landed on a replica WITHOUT its KV (a client
            # failed over to a different entry, or a relay's affinity map
            # died with it). The gossip record of the replica actually
            # holding the session advertises it — relay DIRECTLY there
            # instead of 409ing the client into a full restart; with no
            # live `sess` holder, a peer advertising the session under
            # `standby` (async KV replication — runtime/repl) is the
            # promotion target. The "rescued" marker caps this at ONE
            # bounce: a stale advert of a dead holder must not ping-pong
            # between surviving replicas. Short retry loop: the chunk may
            # be RACING a dying node's graceful handoff — within ~1 s the
            # KV lands on a surviving replica (possibly this one) and the
            # chunk proceeds. Bounce count: --rescue-bounces.
            attempts = 0
            last_rescue_err = "no holder advertised"
            for rescue_attempt in range(self.rescue_bounces):
                if self._holds_session(session_id):
                    break  # the handoff landed HERE: serve locally below
                rem = retrylib.remaining_s(deadline_ms)
                if rem is not None and rem <= 0:
                    # the end-to-end budget died while we waited out the
                    # handoff: stop bouncing dead work around the stage
                    return self._deadline_response(
                        tin, session_id, stage, "rescue"
                    )
                if rescue_attempt and not self.retry_budget.try_acquire():
                    # rescue re-relays are retries too: the shared bucket
                    # bounds a dead stage's blind-bounce rate (the first
                    # lookup each request stays free — budgets bound
                    # AMPLIFICATION, not recovery itself)
                    self.metrics.inc("rescue.budget_denied")
                    last_rescue_err = "rescue retry budget denied"
                    break
                attempts = rescue_attempt + 1
                holder = self._gossip_session_holder(
                    session_id, stage, exclude={self.info.node_id}
                )
                standby_kind = holder is None
                if standby_kind:
                    # no live holder advertises the session: a standby
                    # replica may hold its replicated prefix — relaying
                    # there lets it PROMOTE (or offer the client a
                    # bounded resume) instead of 409ing into a restart
                    holder = self._gossip_standby_holder(
                        session_id, stage, exclude={self.info.node_id}
                    )
                if holder is not None:
                    self.metrics.inc("sessions.rescue_relay")
                    # flight recorder: a rescue is the fleet ACTING on a
                    # dead/moved replica — postmortems interleave this
                    # with the peer.dead that caused it
                    self.journal.emit(
                        "session.rescue", trace=tin, session=session_id,
                        stage=stage, holder=holder,
                        attempt=rescue_attempt,
                        **({"standby": 1} if standby_kind else {}),
                    )
                    try:
                        t_resc = time.perf_counter()
                        resp = await self._relay(
                            {**env, "rescued": True}, stage,
                            exclude={self.info.node_id}, prefer=holder,
                            tin=tin, phase="rescue", attempts=1,
                        )
                        # rescue bounces belong in the hop-latency series
                        # too (the old span-derived gossip quantiles
                        # covered relay AND rescue phases): a replica
                        # whose forwards constantly fail over through
                        # slow rescues must not gossip a healthy hop p99
                        self.metrics.observe(
                            "hop.relay_ms",
                            (time.perf_counter() - t_resc) * 1e3,
                        )
                    except NoNodeForStage:
                        resp = None
                        last_rescue_err = "no node for stage"
                    if resp is not None and resp.status < 500:
                        if standby_kind:
                            # the standby ANSWERED (a promotion, or the
                            # typed resume offer the client acts on):
                            # repoint affinity so the session's next
                            # chunks go straight there instead of
                            # re-discovering it per chunk
                            key = (session_id, stage)
                            self._session_next[key] = (
                                holder, time.monotonic()
                            )
                            self._session_next.move_to_end(key)
                        return resp
                    last_rescue_err = (
                        f"holder {holder} answered {resp.status}"
                        if resp is not None
                        else f"holder {holder} unreachable"
                    )
                    # dead/stale holder: wait out the handoff and re-check
                if self._standby_len(session_id, stage) is not None:
                    # the advertised holder is gone (or nothing advertises
                    # the session at all — e.g. the crashed primary's
                    # record already TTL'd) and WE hold the replicated
                    # prefix FOR THIS STAGE: stop waiting out the bounce
                    # budget — every sleep here is pure added RTO — and
                    # promote locally
                    break
                await asyncio.sleep(0.15)
            if (
                not self._holds_session(session_id)
                and self._standby_len(session_id, stage) is None
            ):
                # the fleet STOPPED acting: the give-up must be visible
                # in postmortems next to the peer.dead that caused it —
                # falling silently into the client's 409 reads as "the
                # swarm never noticed" (the one-bounce end_session twin
                # stays silent by design: freeing KV early is pure
                # housekeeping, nothing user-visible was lost)
                self.metrics.inc("sessions.rescue_failed")
                self.journal.emit(
                    "session.rescue_failed", trace=tin,
                    session=session_id, stage=stage, attempts=attempts,
                    error=last_rescue_err,
                )
            # no holder materialized: serve locally -> 409 -> restart

        if (
            start_pos > 0
            and env.get("session_id") is not None
            and not self._holds_session(session_id)
        ):
            # standby promotion (crash-tolerant sessions): THIS node holds
            # the session's replicated KV prefix — either promote it into
            # the executor and serve the chunk (start_pos inside the
            # frontier: the replay-rollback protocol recomputes the
            # overlap deterministically), or answer the typed resume
            # offer so the client re-prefills ONLY the tokens past the
            # frontier instead of the whole context. Runs for rescued
            # relays and direct failovers alike; a stale/partial shadow
            # degrades to the ordinary 409/restart path below — never a
            # divergent token.
            promo = await self._promote_or_offer(
                session_id, stage, start_pos, tin
            )
            if promo is not None:
                return promo

        self.metrics.inc("forward.requests")
        if via == "local":
            # the hops /generate served in process: local / requests is
            # the share of this node's forwards that touched no socket
            self.metrics.inc("forward.local")
        if self.chaos is not None:
            try:
                await self.chaos.before_forward()
            except ChaosDrop as e:
                self.metrics.inc("chaos.dropped")
                return self._error_response(500, str(e))
        t_q = tracelib.now()  # queue-span anchor: enqueue -> worker pickup
        # bind the executor NOW: a request that passed the stage check
        # must compute on the executor of that stage even if a
        # migration swaps self.executor while this request waits in the
        # scheduler queue (the swapped-in executor serves a DIFFERENT
        # stage — its process() would reject or, worse, mis-shape)
        executor = self.executor
        _pl = env.get("payload")
        if (
            isinstance(_pl, dict) and _pl.get("adapter") is not None
            and getattr(executor, "adapters", None) is None
        ):
            # a tenant-addressed chunk on a replica with no registry:
            # LOUD deterministic reject — serving the base model instead
            # would be silent tenant corruption (the lane executors raise
            # this themselves; this guard covers solo/mesh/counter)
            return self._error_response(
                409,
                f"payload names adapter {_pl.get('adapter')!r} but this "
                "replica serves no adapter registry (--adapters)",
                code="no_adapter_registry",
            )
        # stage-level continuous batching: single-token decode steps join
        # the executor's arrival window; co-arrivals run as ONE device
        # step and their relays coalesce (see _run_stage_window)
        use_window = (
            getattr(executor, "window", None) is not None
            and _is_decode_step(env.get("payload"))
        )
        try:
            if use_window:
                win_res = await self.scheduler.run(
                    executor.window.submit, (session_id, env, tin, t_q)
                )
            else:
                (result, pure_ms, w0, w1, cctx), t_res = await self._process_hop(
                    executor, session_id, env.get("payload", {}), tin,
                )
        except BufferError as e:  # KV budget exceeded: deterministic
            # the executors' BufferError now names the session AND lane
            # (core.cache.ensure_room owner contract): the journal event
            # and the 409 the client sees carry the SAME identity
            self.journal.emit(
                "kv.overflow", trace=tin, session=session_id, stage=stage,
                error=str(e),
            )
            return self._error_response(409, str(e), code="overflow")
        except RuntimeError as e:
            from inferd_tpu.runtime.adapters import AdapterCapacityError
            from inferd_tpu.runtime.batch_executor import CapacityError

            if isinstance(e, (CapacityError, AdapterCapacityError)):
                # transient backpressure (busy lanes / every adapter slot
                # held by live sessions or pins): retryable 503
                return self._error_response(503, str(e), code="busy")
            log.exception("stage compute failed")
            self._maybe_oom_event(e, tin, stage)
            return self._error_response(500, str(e))
        except ValueError as e:
            from inferd_tpu.runtime.adapters import UnknownAdapterError

            if isinstance(e, UnknownAdapterError):
                # a name outside this node's --adapters catalog is a
                # permanent config error: a typed non-retryable code,
                # never the restart-and-retry `session_state` loop
                return self._error_response(409, str(e), code="unknown_adapter")
            # out-of-order/replayed chunk — the session's KV here doesn't
            # match (e.g. its replica died and we're a fresh pick); a client
            # restarting with a new session recovers
            return self._error_response(409, str(e), code="session_state")
        except Exception as e:  # compute failure
            log.exception("stage compute failed")
            self._maybe_oom_event(e, tin, stage)
            return self._error_response(500, f"stage compute failed: {e}")
        if use_window:
            if win_res[0] == "relayed":
                # the window flusher already relayed this entry (possibly
                # coalesced with its co-batch) and holds the reply body
                _, status, body = win_res
                return web.Response(status=status, body=body)
            # local result (final stage / chain mode): the flusher recorded
            # the window+compute spans and the svc EWMA — fall through to
            # the shared response shaping below
            result = win_res[1]
            # windowed entries are single-token DECODE steps, which never
            # carry tokens_saved — popped anyway so the strip-before-wire
            # contract holds uniformly if that invariant ever moves
            saved = (
                int(result.pop("tokens_saved", 0))
                if isinstance(result, dict) else 0
            )
        else:
            # per-request shared-prefix saving (paged executors stamp it
            # on prefill results): popped here so relayed payloads stay
            # byte-identical to pre-digest builds; re-attached to FINAL
            # results below so the caller sees its own tokens_saved
            saved = (
                int(result.pop("tokens_saved", 0))
                if isinstance(result, dict) else 0
            )
            self.metrics.observe(
                "stage.compute_ms", (time.perf_counter() - t0) * 1e3
            )
            if eventslib.enabled():
                # per-stage token-throughput counter (every chain stage
                # touches every token — the fleet aggregator sums LAST
                # stages only, obs.fleet): K for a fused K-step result,
                # 1 per ordinary step/prefill chunk
                self.metrics.inc(
                    "stage.tokens",
                    len(result["tokens"][0])
                    if isinstance(result, dict) and "tokens" in result
                    else 1,
                )
            if tin is not None:
                # host-side span pair for this hop: worker-pool wait, then
                # the executor's pure compute (wall stamps from the worker)
                self.tracer.record_span(
                    "queue", "queue", t_q, w0, parent=tin,
                    attrs={"stage": stage},
                )
                self.tracer.record_span(
                    "compute", "compute", w0, w1, parent=tin, ctx=cctx,
                    # `kind`/`tokens` as the window path has them: a decode
                    # step or a prefill chunk, and the tokens it committed.
                    # A prefill that mapped cached prefix blocks carries
                    # how many tokens it SKIPPED — per-request memory-
                    # plane attribution in merged timelines
                    attrs={"stage": stage, "ms": round(pure_ms, 3),
                           "kind": call_kind(_pl),
                           "tokens": _committed_tokens(result),
                           **({"tokens_saved": saved} if saved else {})},
                )
                # the worker was done at w1; this coroutine ran again at
                # t_res: the hop's wait for the event loop
                self.tracer.record_span(
                    "resume", "resume", w1, t_res, parent=tin,
                )
            # service-time EWMA: announced as svc_ms, feeding every
            # planner's measured-latency edge-cost term (carried by the 1 s
            # gossip loop). PURE compute time (timed inside the worker):
            # queue wait is already the load/cap term of node_cost —
            # folding it in here too would double-charge queued nodes and
            # amplify route herding.
            self._svc_ewma = (
                pure_ms if self._svc_ewma is None
                else 0.8 * self._svc_ewma + 0.2 * pure_ms
            )

        if not env.get("relay", True):
            # chain mode (hub-and-spoke): the CLIENT drives each stage in
            # turn and carries activations between them — the reference's
            # gRPC slice topology (/root/reference/models/qwen3/client/
            # rpc_client.py:46-57) behind the same endpoint. Return this
            # stage's raw result instead of relaying it onward.
            if saved and isinstance(result, dict):
                result["tokens_saved"] = saved
            return web.Response(
                body=wire.pack(
                    {
                        "task_id": task_id,
                        "session_id": session_id,
                        "stage": stage,
                        "result": result,
                        "served_by": self.info.node_id,
                    }
                )
            )

        if self._is_final(result):
            if saved:
                # the caller's own per-request SLI: how much prefill its
                # prompt skipped on this replica (key absent on cold
                # prefills and old builds — additive wire change)
                result["tokens_saved"] = saved
            # the reply as a dict: handle_forward packs it for the socket,
            # the node's own generation loop samples from it unpacked
            return {
                "task_id": task_id,
                "session_id": session_id,
                "result_for_user": result,
                "served_by": self.info.node_id,
            }

        rem = retrylib.remaining_s(deadline_ms)
        if rem is not None and rem <= 0:
            # the budget died DURING compute: relaying the activations
            # downstream would be dead work for every remaining stage —
            # this check is what stops a 3-stage chain from finishing a
            # request nobody is waiting for
            return self._deadline_response(
                tin, session_id, stage, "post-compute"
            )
        next_env = {
            "task_id": task_id,
            "session_id": session_id,
            "stage": stage + 1,
            "payload": result,
        }
        if start_pos == 0:
            # multi-tenant LoRA: the session->adapter binding happens at
            # EVERY stage's admission, so the first chunk's `adapter` key
            # rides the relay — each downstream stage binds its own slice
            ad = (env.get("payload") or {}).get("adapter")
            if ad is not None:
                result["adapter"] = ad
        if "route" in env:
            next_env["route"] = env["route"]
        if deadline_ms is not None:
            next_env[retrylib.DEADLINE_KEY] = deadline_ms
        try:
            t1 = time.perf_counter()
            resp = await self._relay(next_env, stage + 1, tin=tin)
            self.metrics.observe("hop.relay_ms", (time.perf_counter() - t1) * 1e3)
            return resp
        except NoNodeForStage as e:
            return self._error_response(503, f"no next node: {e}")

    def _maybe_oom_event(
        self, e: BaseException, tin: Optional[tracelib.SpanContext],
        stage: int,
    ) -> None:
        """Journal a device OOM when a compute failure smells like one
        (XLA raises RESOURCE_EXHAUSTED RuntimeErrors) — the single most
        postmortem-relevant failure a TPU node produces."""
        msg = str(e)
        if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
            self.journal.emit(
                "oom", trace=tin, stage=stage,
                error=f"{type(e).__name__}: {msg}"[:200],
            )

    def _deadline_response(
        self, tin: Optional[tracelib.SpanContext], session_id: Optional[str],
        stage: int, where: str,
    ) -> web.Response:
        """The typed deadline failure: 408 + code "deadline" (non-
        retryable under the client's ServerError contract — the budget is
        a property of the REQUEST, not of any replica, so another attempt
        cannot succeed either), journaled so postmortems can tell
        "overloaded and shedding correctly" from "failing"."""
        self.metrics.inc("deadline.expired")
        self.journal.emit(
            "deadline.exceeded", trace=tin, session=session_id, stage=stage,
            where=where,
        )
        return self._error_response(
            408, f"deadline exceeded ({where})", code="deadline"
        )

    def _admission_shed(self):
        """(code, message) when NEW sessions must be shed, else None:
        "draining" after POST /drain, "busy" when the paged-KV block pool
        is below its free-watermark reserve (admission_reserve x pool;
        ROADMAP 2d — a pool-backed node's real capacity is blocks_free,
        not lane count)."""
        if self._draining:
            return (
                "draining",
                "node is draining: not admitting new sessions",
            )
        low = self._pool_under_reserve()
        if low is not None:
            free, total, reserve = low
            return (
                "busy",
                f"KV block pool low: {free} free of {total} "
                f"(admission reserve {reserve})",
            )
        return None

    def _pool_under_reserve(self):
        """(free, total, reserve) when the paged block pool is below its
        admission watermark, else None — shared by the admission shed
        above and the gossiped `shed` flag (routers suppress the
        cache-affinity bonus and penalize affinity-scored picks on a
        shedding replica: obs.canary.under_admission_watermark)."""
        pool = getattr(self.executor, "pool", None)
        if pool is None:
            return None
        try:
            total = int(pool.num_blocks)
            free = int(pool.blocks_free)
        except Exception:
            return None  # duck-typed executor without pool counters
        reserve = max(1, int(self.admission_reserve * total))
        if free < reserve:
            return (free, total, reserve)
        return None

    def _prefix_digest(self) -> Optional[Dict[str, Any]]:
        """The executor's gossip-ready prefix digest (`pfx` field), or
        None (key omitted): which prompt prefixes this replica already
        holds as KV blocks, truncated-key form (core.prefix.make_digest).
        Entry routers score new sessions' prompts against it
        (control.path_finder / control.dstar cache-affinity bonus)."""
        fn = getattr(self.executor, "prefix_digest", None)
        if not callable(fn):
            return None
        try:
            return fn()
        except Exception:
            log.debug("prefix digest unavailable", exc_info=True)
            return None

    def _adapter_digest(self):
        """Resident non-base adapter names (bounded — runtime/adapters
        ADA_GOSSIP_MAX), or None (key omitted): which tenants' adapters
        this replica already holds device-resident. Entry routers score
        new sessions' `adapter` against it (AdapterAffinity — the same
        bounded bonus seam as the `pfx` digest); a miss is a HOT-LOAD on
        the landing replica, never a reject. A registry with NOTHING
        resident announces `[]`, not omission: key PRESENCE is the
        capability marker tenant-session handoff/standby target picks
        require, so an adapter-stamped payload is never offered to an
        old-release or registry-less peer that would silently adopt it
        onto the base weights."""
        reg = getattr(self.executor, "adapters", None)
        if reg is None:
            return None
        try:
            return reg.resident_names()
        except Exception:
            log.debug("adapter digest unavailable", exc_info=True)
            return None

    def _cachehit_frac(self) -> Optional[float]:
        """Trailing-window prefix-cache hit rate: tokens the pool served
        from cached blocks over all prompt tokens admitted (hits +
        actually-prefilled), from the windowed kv.prefix_* counters the
        devtel refresh mirrors (obs.tsdb). None — key omitted — when the
        window saw no prompt traffic or the series don't exist (dense
        executors, events disabled): stale ratios must age out with the
        window, never freeze."""
        h = self.tsdb.history()
        hit = tsdblib.trailing_sum(h, "kv.prefix_hit_tokens")
        pre = tsdblib.trailing_sum(h, "kv.prefill_tokens")
        if hit is None or pre is None:
            return None
        denom = hit + pre
        if denom <= 0:
            return None
        return round(hit / denom, 4)

    def _retry_after_s(self) -> float:
        """Retry-After hint for shed responses, derived from window
        occupancy: roughly one arrival window per unit of queue pressure
        (inflight/cap), floored at 50 ms and capped at 5 s so a burst of
        shed clients smears itself across a few windows instead of
        re-arriving as one synchronized wave."""
        inflight = self.scheduler.inflight if hasattr(self, "scheduler") else 0
        cap = max(1, self.info.capacity)
        base = max(self.window_ms / 1e3, 0.05)
        return round(min(5.0, base * (1.0 + inflight / cap)), 3)

    def _holds_session(self, session_id: str) -> bool:
        store = getattr(self.executor, "sessions", None)
        try:
            return store is not None and session_id in store
        except TypeError:
            return False

    def _gossip_session_holder(
        self, session_id: str, stage: int, exclude=None
    ) -> Optional[str]:
        """node_id of a live same-stage replica advertising this session's
        KV in its gossip record (see _advertised_sessions), or None."""
        h = sess_hash(session_id)
        for nid, value in self.dht.get_stage(stage).items():
            if exclude and nid in exclude:
                continue
            if h in (value.get("sess") or ()):
                return nid
        return None

    def _gossip_standby_holder(
        self, session_id: str, stage: int, exclude=None
    ) -> Optional[str]:
        """node_id of a live same-stage replica advertising this
        session's REPLICATED prefix (`standby` gossip field — async KV
        replication, runtime/repl), or None. Consulted only after the
        `sess` lookup comes up empty: a live authoritative holder always
        beats a lagging shadow."""
        h = sess_hash(session_id)
        for nid, value in self.dht.get_stage(stage).items():
            if exclude and nid in exclude:
                continue
            if h in (value.get("standby") or ()):
                return nid
        return None

    def _standby_len(
        self, session_id: str, stage: Optional[int] = None
    ) -> Optional[int]:
        """Replicated frontier of a locally held shadow session, or None
        (replication off / session unknown here / — with `stage` — the
        shadow belongs to a DIFFERENT stage, e.g. one this node served
        before a migration: promotion could never use it, so the rescue
        loop must not short-circuit on it either)."""
        if self.standby is None:
            return None
        if stage is not None and self.standby.stage_of(session_id) != stage:
            return None
        return self.standby.length(session_id)

    def _promote_standby_sync(self, session_id: str) -> bool:
        """Worker thread: import the accumulated shadow KV into the
        executor through the ordinary handoff path — the fail-closed
        validator (runtime/handoff.decode) is the promotion gate, so a
        corrupt or wrong-layout shadow rejects cleanly instead of
        corrupting a lane."""
        assert self.standby is not None
        payload = self.standby.payload(session_id)
        if payload is None:
            return False
        imp = getattr(self.executor, "import_session", None)
        if imp is None:
            return False
        try:
            return bool(imp(session_id, payload))
        except Exception:
            log.exception("standby promotion import failed")
            return False

    async def _promote_or_offer(
        self, session_id: str, stage: int, start_pos: int,
        tin: Optional[tracelib.SpanContext],
    ) -> Optional[web.Response]:
        """Resolve a KV-less mid-session chunk against the local
        StandbyStore. Returns a Response to send (the typed resume
        offer), or None — either the shadow was promoted (the caller
        serves the chunk against the now-resident session) or there is
        nothing usable here (the caller degrades to the ordinary
        409/restart path)."""
        if self.standby is None:
            return None
        F = self.standby.length(session_id)
        if F is None or F <= 0 or self.standby.stage_of(session_id) != stage:
            return None
        if start_pos > F:
            # promotion OFFER: we hold the replicated prefix up to F.
            # The 409 keeps code "session_state" (old clients restart
            # fully — exactly today's degraded path) and adds
            # `resume_from`: new clients re-send only [F, start_pos) —
            # the re-prefill is bounded by the replication lag.
            if eventslib.enabled():
                self.metrics.inc("repl.offers")
                self.metrics.inc("repl.tail_tokens", start_pos - F)
            self.journal.emit(
                "standby.offer", trace=tin, session=session_id,
                stage=stage, frontier=F, chunk_start=start_pos,
            )
            return self._error_response(
                409,
                f"session {session_id}: standby KV reaches {F} < chunk "
                f"start {start_pos} — resume from {F}",
                code="session_state", resume_from=F,
            )
        ok = await self.scheduler.run(self._promote_standby_sync, session_id)
        if ok:
            self.standby.drop(session_id)
            if eventslib.enabled():
                self.metrics.inc("repl.promotions")
                self.metrics.inc("repl.resumed_tokens", F)
            self.journal.emit(
                "standby.promote", trace=tin, session=session_id,
                stage=stage, frontier=F, chunk_start=start_pos,
            )
            # advertise the promoted session NOW (`sess`): the failed-
            # over client's next chunks route straight here, mirroring
            # handle_import_session's adopt-then-announce
            self.announce()
            return None  # resident now: the caller serves the chunk
        # import declined — which covers BOTH a validation failure and a
        # transient capacity miss (no free lane / pool blocks during the
        # mass-failover spike a crash creates; import_session folds both
        # into False). KEEP the shadow: a capacity miss may promote fine
        # on the client's very next resume retry, and a truly corrupt
        # shadow is abandoned when the client restarts under a fresh
        # session id (the TTL sweep collects it). Dropping here would
        # convert a momentary full pool into a permanent full restart.
        if eventslib.enabled():
            self.metrics.inc("repl.stale")
        self.journal.emit(
            "standby.stale", trace=tin, session=session_id, stage=stage,
            frontier=F,
        )
        return None  # degrade: ordinary 409 -> client restart

    # ------------------------------------------ standby replication (primary)

    def _repl_candidates(self):
        """Ranked same-stage standby candidates for the replicator —
        path_finder.ranked_nodes ordering (outlier-penalized, draining-
        excluded), minus this node (anti-affinity: the standby must
        survive the primary's crash) and peers cooling down after a
        failed/declined ship."""
        from inferd_tpu.control.path_finder import ranked_nodes

        now = time.monotonic()
        self._repl_peer_cooldown = {
            nid: t for nid, t in self._repl_peer_cooldown.items() if t > now
        }
        exclude = {self.info.node_id, *self._repl_peer_cooldown}
        stage_map = self.dht.get_stage(self.info.stage)
        cands = ranked_nodes(stage_map, exclude=exclude)
        if not cands and len(stage_map) > 1:
            # every peer is cooling down: better a recently flaky standby
            # than none (the cooldown bounds RETRY RATE, not recovery)
            cands = ranked_nodes(stage_map, exclude={self.info.node_id})
        return cands

    async def _repl_loop(self) -> None:
        """Replication tick: ship newly completed KV past each resident
        session's frontier to its sticky standby (runtime/repl). Purely
        additive and best-effort — a failed ship costs nothing but RPO."""
        while True:
            await asyncio.sleep(self.repl_interval_s)
            try:
                await self._repl_tick()
            except Exception:
                log.exception("standby replication tick failed")

    async def _repl_tick(self) -> None:
        assert self.replicator is not None
        ex = self.executor
        lengths_fn = getattr(ex, "session_lengths", None)
        delta_fn = getattr(ex, "export_session_delta", None)
        if (
            not callable(lengths_fn) or not callable(delta_fn)
            or self._http is None or self._draining
        ):
            return
        loop = asyncio.get_running_loop()
        lengths = await loop.run_in_executor(None, lengths_fn)
        # silent forget for sessions that merely lost residency (LRU
        # lane eviction, live handoff): their standby shadows STAY — a
        # continuing stream promotes off them. Explicit client ends send
        # a drop notice from handle_end_session instead.
        self.replicator.prune(lengths)
        if eventslib.enabled():
            self.metrics.set_gauge(
                "repl.lag_tokens", float(self.replicator.lag_tokens(lengths))
            )
        def ship_failed(sid: str, standby: str, count_error: bool) -> None:
            # one definition of "this standby didn't take the delta":
            # forget the sticky pick (re-pick next tick, re-ship from 0)
            # and cool the peer down so a dead/declining one isn't
            # re-tried every tick
            self.replicator.note_standby_dead(sid)
            self._repl_peer_cooldown[standby] = (
                time.monotonic() + self.peer_cooldown_s
            )
            if count_error and eventslib.enabled():
                self.metrics.inc("repl.ship_errors")

        ad_fn = getattr(ex, "session_adapters", None)
        ad_map = ad_fn() if callable(ad_fn) else None
        for sid, standby, frontier in self.replicator.plan(lengths, ad_map):
            rec = self.dht.get_stage(self.info.stage).get(standby)
            if rec is None:
                self.replicator.note_standby_dead(sid)
                continue
            delta = await loop.run_in_executor(None, delta_fn, sid, frontier)
            if delta is None:
                continue  # e.g. paged: no full block completed yet
            body = wire.pack({
                "session_id": sid, "stage": self.info.stage, **delta,
            })
            try:
                host, port = node_addr(rec)
                async with self._http.post(
                    f"http://{host}:{port}{REPLICATE_SESSION_PATH}",
                    data=body,
                ) as r:
                    resp = (
                        wire.unpack(await r.read()) if r.status == 200
                        else None
                    )
            except (OSError, asyncio.TimeoutError, aiohttp.ClientError):
                ship_failed(sid, standby, count_error=True)
                continue
            if not isinstance(resp, dict):
                # non-200 (e.g. the peer runs without --standby-repl) or
                # garbage: cool the peer down and re-pick next tick
                ship_failed(sid, standby, count_error=True)
                continue
            ok = bool(resp.get("ok"))
            if not ok and (resp.get("serving") or resp.get("unservable")):
                # the "standby" actually SERVES this session (a drain
                # adopted it there), or it can never promote this
                # tenant's adapter (no registry / name outside its
                # catalog): stop shadowing, cool it down, re-pick next
                # tick — not a ship error, a mis-pick
                ship_failed(sid, standby, count_error=False)
                continue
            peer_len = resp.get("length") if ok else resp.get("have")
            self.replicator.record(sid, standby, ok, peer_len, len(body))
            if eventslib.enabled():
                if ok:
                    self.metrics.inc("repl.bytes", len(body))
                    self.metrics.inc("repl.ships")
                    if frontier == 0:
                        # journal the session's arrival on its standby
                        # once per (session, standby) sync, not per tick
                        self.journal.emit(
                            "session.replicated", session=sid,
                            standby=standby,
                            **{"length": int(peer_len or 0)},
                        )
                else:
                    self.metrics.inc("repl.ship_declined")

    async def _send_standby_drop(self, session_id: str, standby: str) -> None:
        """Best-effort drop notice to an ended session's sticky standby
        (the standby's TTL sweep is the backstop when this never lands)."""
        rec = self.dht.get_stage(self.info.stage).get(standby)
        if rec is None or self._http is None:
            return
        try:
            host, port = node_addr(rec)
            async with self._http.post(
                f"http://{host}:{port}{REPLICATE_SESSION_PATH}",
                data=wire.pack({
                    "session_id": session_id, "stage": self.info.stage,
                    "drop": True,
                }),
            ):
                pass
        except (OSError, asyncio.TimeoutError, aiohttp.ClientError):
            pass

    async def handle_replicate_session(
        self, request: web.Request
    ) -> web.Response:
        """Accept one async-replication delta into the StandbyStore
        (host-side shadow KV — no lane, no device state until
        promotion). POST {"session_id", "stage", "start", handoff
        payload} -> {"ok": true, "length": L} or {"ok": false, "have":
        H} (the primary re-syncs from H). 501 with --standby-repl off —
        a replication-blind node must say so, not silently eat bytes."""
        if self.standby is None:
            return self._error_response(
                501,
                "standby replication disabled (start with --standby-repl)",
                code="repl_off",
            )
        try:
            env = wire.unpack(await request.read())
            session_id = env["session_id"]
            stage = int(env["stage"])
        except Exception as e:
            return self._error_response(400, f"bad replicate_session: {e}")
        if stage != self.info.stage:
            return self._error_response(
                409,
                f"wrong stage: this node serves {self.info.stage}, not {stage}",
                code="wrong_stage",
            )
        if env.get("drop"):
            # the primary's session ended: free the shadow (and its
            # `standby` advert) now instead of waiting out the TTL
            had = session_id in self.standby
            self.standby.drop(session_id)
            if had:
                self._record_moved()
            return web.Response(body=wire.pack({"ok": True, "length": 0}))
        if self._holds_session(session_id):
            # we SERVE this session (e.g. adopted it via drain handoff):
            # shadowing ourselves is meaningless — tell the primary to
            # pick another standby
            return web.Response(body=wire.pack(
                {"ok": False, "have": 0, "serving": True}
            ))
        from inferd_tpu.runtime.adapters import registry_can_serve

        if not registry_can_serve(self.executor, env.get("adapter")):
            # a tenant delta this replica can NEVER promote (no
            # registry, or the name is outside our catalog): declining
            # NOW makes the primary re-pick instead of streaming
            # shadows toward a guaranteed promotion decline — a
            # bounded-RPO promise that was silently void
            if eventslib.enabled():
                self.metrics.inc("repl.recv_declined")
            return web.Response(body=wire.pack(
                {"ok": False, "have": 0, "unservable": True}
            ))
        had = session_id in self.standby
        ok, have = await asyncio.get_running_loop().run_in_executor(
            None, self.standby.apply, session_id, stage, env
        )
        if eventslib.enabled():
            self.metrics.inc("repl.recv" if ok else "repl.recv_declined")
        if ok and not had:
            # the `standby` advert must reach peers before the primary
            # dies for the rescue path to find us — non-urgent: the 1 s
            # gossip loop carries it well inside the record TTL
            self._record_moved()
        body = {"ok": ok, "length": have} if ok else {"ok": False, "have": have}
        return web.Response(body=wire.pack(body))

    async def _process_hop(self, executor, session_id: str,
                           payload: Dict[str, Any], tin=None):
        """One executor call and where it ran: ((the result, its pure
        compute time in ms, its start and end stamps, the `compute` span's
        context), the moment this coroutine ran again).

        Where the executor offers the call a form that needs no thread
        (`begin_hop`: runtime/batch_executor.py says which calls; what
        decides is the payload and the executor, nothing else) the hop
        stays on the event loop: it is admitted here, handed to the
        executor's window, and this coroutine sleeps on a future that the
        drain which answers it resolves, with the futures of every other
        hop it answered, in ONE `call_soon_threadsafe` (`_hops_answered`).
        `compute` then runs from the admission to that hand-over, `deliver`
        from the step's copy-out to it, `resume` from it to here, and
        `queue` is the few microseconds before the admission. Only a hop
        whose answer is a step still to be waited for (a request's first:
        `end_hop` says so) hands that wait to a worker. Any other call
        (`begin_hop` absent or None) takes a worker for its whole length,
        `_timed_process`."""
        begin = getattr(executor, "begin_hop", None)
        hop = cctx = None
        if begin is not None:
            token = None
            if tin is not None and tracelib.enabled():
                # current while the entry is made: its waits parent to it
                cctx = tin.child()
                token = tracelib.set_current(cctx)
            try:
                w0 = tracelib.now()
                hop = begin(session_id, payload, self._hops_answered)
            finally:
                if token is not None:
                    tracelib.reset_current(token)
        if hop is None:
            return await self.scheduler.run_stamped(
                self._timed_process, executor, session_id, payload, tin,
            )
        sched = self.scheduler
        sched.entered += 1
        try:
            # the window's hand-over finds the future on the hop's entry
            fut = hop.entry.waiter = asyncio.get_running_loop().create_future()
            try:
                await fut
            except asyncio.CancelledError:
                self._abandon_hop(executor, hop, fut)
                raise
            t_res = tracelib.now()
            entry = hop.entry
            w1 = entry.t_handed
            result = executor.end_hop(hop, block=False)
            if result is None:
                # a step to wait for, or the session table is held for
                # long: the rest of the hop is a worker's
                result, _, _, w1, _ = await sched.offload(
                    self._timed, executor, cctx, executor.end_hop, hop,
                )
                t_res = tracelib.now()
            elif cctx is not None and entry.t_out is not None:
                self.tracer.record_span(
                    "deliver", "deliver", entry.t_out, w1, parent=cctx,
                )
            return (result, (w1 - w0) * 1e3, w0, w1, cctx), t_res
        finally:
            sched.left += 1

    def _hops_answered(self, entries) -> None:
        """The window's hand-over (any thread): the hops of `entries` have
        their answers. ONE wake-up of the loop for all of them."""
        try:
            self._loop.call_soon_threadsafe(self._resume_hops, entries)
        except RuntimeError:
            pass  # the loop is closed: nobody waits any more

    @staticmethod
    def _resume_hops(entries) -> None:
        for e in entries:
            if not e.waiter.done():
                e.waiter.set_result(None)

    def _abandon_hop(self, executor, hop, fut) -> None:
        """The hop's coroutine was cancelled while its entry was in the
        window (its caller went away): the entry still gets its answer,
        and a worker ends the hop then as `process` would have (the
        session leaves flight, a dying lane is freed)."""
        def end(_=None):
            try:
                self.scheduler.offload(self._end_quietly, executor, hop)
            except RuntimeError:
                pass  # the pool is shut down

        if fut.done() and not fut.cancelled():
            end()
        else:
            hop.entry.waiter = asyncio.get_running_loop().create_future()
            hop.entry.waiter.add_done_callback(end)

    @staticmethod
    def _end_quietly(executor, hop) -> None:
        try:
            executor.end_hop(hop)
        except Exception:
            pass  # nobody is left to tell

    def _timed_process(self, executor, session_id: str,
                       payload: Dict[str, Any], tin=None):
        """Executor call + its pure compute time in ms and wall-clock
        start/end stamps (runs in the worker thread, so the measurement
        excludes the pool's queue wait; the wall stamps become the
        compute span and bound the queue span). The executor is passed
        in, bound at request entry — see handle_forward's migration-race
        note."""
        ctx = tin.child() if tin is not None and tracelib.enabled() else None
        return Node._timed(executor, ctx, executor.process, session_id, payload)

    @staticmethod
    def _timed(executor, ctx, call, *args):
        """`call(*args)` on this worker thread under the `compute` span's
        context `ctx` (None: no span is wanted), timed: what
        `_timed_process` returns."""
        # the compute span's context is allocated by the caller and made
        # current in this worker thread, so what the executor stamps inside
        # the call (obs.trace.region: batch_wait, lock_wait, device,
        # copy_out) parents to the span the caller records afterwards
        token = None
        marks: Dict[str, float] = {}
        if ctx is not None:
            token = tracelib.set_current(ctx)
            marks, mtoken = tracelib.open_marks()
        try:
            w0 = tracelib.now()
            t = time.perf_counter()
            result = call(*args)
            pure_ms = (time.perf_counter() - t) * 1e3
        finally:
            if token is not None:
                tracelib.close_marks(mtoken)
                tracelib.reset_current(token)
        w1 = w0 + pure_ms / 1e3
        if "t_out" in marks:
            # a windowed step served this call: from its copy_out's end
            # (the flusher's stamp, on the recorder the node handed the
            # executor) to this worker being back
            executor.tracer.record_span(
                "deliver", "deliver", marks["t_out"], w1, parent=ctx,
            )
        return result, pure_ms, w0, w1, ctx

    def _is_final(self, result: Dict[str, Any]) -> bool:
        # "tokens": a multi-step fused decode result (single-stage
        # topologies only — already sampled on device, nothing to relay)
        return (
            "logits" in result or "tokens" in result
            or "result_for_user" in result
        )

    # ------------------------------------------ stage-window flush + relay

    def _run_stage_window(self, executor, entries) -> None:
        """WindowedBatcher flush callback (worker thread, no locks held):
        ONE co-batched device step for every co-arrived decode entry, then
        ONE relay per next-hop group instead of one per session.

        Entry payloads are (session_id, env, tin, t_enqueue). Per-entry
        failures set entry.error (one stale session must not fail its
        co-batch); entries that need no relay resolve to ("local", result)
        and the handler coroutine shapes the response; relayed entries
        resolve to ("relayed", status, body) with the downstream reply.
        The relay runs on the event loop while THIS worker thread blocks —
        the batcher has already reset its flusher slot, so the next
        window's compute overlaps this window's downstream send."""
        w0 = tracelib.now()
        t0 = time.perf_counter()
        items = [
            (e.payload[0], (e.payload[1].get("payload") or {}))
            for e in entries
        ]
        drained: list = []
        # window end / compute start stamp: set at DRAIN time (after the
        # device lock was acquired), not at flush entry — drain-absorbed
        # entries were enqueued while the previous step held the device,
        # so stamping w0 would give their window spans negative durations
        marks = {"drain": w0}

        def drain():
            """Continuous batching: once the executor holds the device,
            absorb the entries that arrived while the PREVIOUS step was
            running (otherwise arrival phase, not load, sets the batch
            size). We own the drained entries: results AND events are
            ours to deliver (window.drain_pending contract)."""
            extra = executor.window.drain_pending()
            marks["drain"] = tracelib.now()
            drained.extend(extra)
            return [
                (e.payload[0], (e.payload[1].get("payload") or {}))
                for e in extra
            ]

        try:
            outs = executor.process_batch(items, drain=drain)
            entries = list(entries) + drained
        except Exception as exc:
            # process_batch failed wholesale: the flush loop propagates to
            # ITS entries, but the drained ones are ours to fail + release
            for e in drained:
                e.error = exc
                e.event.set()
            raise
        pure_ms = (time.perf_counter() - t0) * 1e3
        w1 = tracelib.now()
        n_live = sum(1 for o in outs if not isinstance(o, Exception))
        # token-true accounting: a multi-step fused decode entry commits
        # K tokens in this one dispatch (its result carries them under
        # "tokens"); counting 1 would understate /metrics tok/s and the
        # `obs merge` per-token breakdowns by K
        n_tok = sum(
            len(o["tokens"][0]) if isinstance(o, dict) and "tokens" in o else 1
            for o in outs if not isinstance(o, Exception)
        )
        if n_live:
            self.metrics.observe("stage.compute_ms", pure_ms)
            if eventslib.enabled():
                # token-true per-stage throughput counter (see the
                # non-window sibling in _forward_inner)
                self.metrics.inc("stage.tokens", n_tok)
            # co-batch-size histogram (in TOKENS per device step): the
            # mechanism's whole value proposition, observable at /metrics
            # and in `perf check`
            self.metrics.observe(
                "window.cobatch", n_tok,
                # tokens per dispatch now reaches lanes x K (e.g. 8 lanes
                # at K=16 = 128): bounds extend past the old lane-count
                # domain so K-step windows keep histogram resolution
                bounds_ms=[1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024],
            )
            self._svc_ewma = (
                pure_ms if self._svc_ewma is None
                else 0.8 * self._svc_ewma + 0.2 * pure_ms
            )
        relays = []
        traced = tracelib.enabled()
        try:
            self._distribute_window(entries, outs, relays, marks["drain"],
                                    w1, pure_ms, n_live, traced, n_tok)
        finally:
            # the flush loop signals only its OWN entries; drained ones
            # release here, after their results/errors landed
            for e in drained:
                if e.error is None and e.result is None:
                    e.error = RuntimeError("window flush dropped an entry")
                e.event.set()

    def _distribute_window(self, entries, outs, relays, t_drain, w1,
                           pure_ms, n_live, traced, n_tok=None) -> None:
        if n_tok is None:
            n_tok = n_live
        for e, out in zip(entries, outs):
            _sid, env, tin, t_q = e.payload
            stage_attr = int(env.get("stage", -1) or -1)
            if tin is not None and traced:
                # `window` phase: enqueue -> batch formation (the
                # co-batching wait this PR introduces — merge CLI
                # breakdowns show it next to queue/compute); clamped in
                # case an entry slipped in between drain and stamp. Then
                # the shared batched step from the drain point. `tokens`
                # counts real committed tokens (K per multi-step entry) so
                # per-token breakdowns divide by the truth.
                self.tracer.record_span(
                    "window", "window", t_q, max(t_q, t_drain), parent=tin,
                    attrs={"stage": stage_attr, "cobatch": n_live,
                           "tokens": n_tok},
                )
                self.tracer.record_span(
                    "compute", "compute", max(t_q, t_drain), w1, parent=tin,
                    attrs={"stage": stage_attr, "ms": round(pure_ms, 3),
                           "cobatch": n_live, "tokens": n_tok},
                )
            if isinstance(out, Exception):
                e.error = out
                continue
            if self._is_final(out) or not env.get("relay", True):
                e.result = ("local", out)
            else:
                relays.append((e, env, out))
        if not relays:
            return
        if self._loop is None or self._loop.is_closed():
            err = RuntimeError("node event loop unavailable for relay")
            for e, _env, _out in relays:
                e.error = err
            return
        # block THIS worker thread on the loop-side relay; entries release
        # when their downstream replies land
        asyncio.run_coroutine_threadsafe(
            self._relay_window(relays), self._loop
        ).result(timeout=self.hop_timeout_s * 2 + 30)

    async def _relay_window(self, relays) -> None:
        """Coalesced relay of one flushed window (event loop). Groups the
        window's entries by their picked next hop; a group of one takes
        the ordinary single-session relay, a larger group ships ONE
        wire.coalesce_forward envelope (N HTTP hops -> 1). Sets each
        entry's result/error; never raises."""
        groups: "OrderedDict[str, tuple]" = OrderedDict()
        for e, env, result in relays:
            stage = int(env.get("stage", 0)) + 1
            next_env = {
                "task_id": env.get("task_id"),
                "session_id": env.get("session_id"),
                "stage": stage,
                "payload": result,
            }
            if "route" in env:
                next_env["route"] = env["route"]
            if retrylib.DEADLINE_KEY in env:
                # the deadline follows the session's work downstream —
                # coalesced frames carry it per session (split_forward
                # reconstructs it on the receiver)
                next_env[retrylib.DEADLINE_KEY] = env[retrylib.DEADLINE_KEY]
            try:
                nid, value = await self._pick_next(
                    env.get("session_id"), stage, route=env.get("route")
                )
            except NoNodeForStage as exc:
                e.result = (
                    "relayed", 503,
                    wire.pack({"error": f"no next node: {exc}"}),
                )
                continue
            except Exception as exc:
                e.error = exc
                continue
            if nid not in groups:
                groups[nid] = (value, [])
            groups[nid][1].append((e, next_env))
        # groups relay CONCURRENTLY: when affinity splits a window over
        # several next hops, total relay time is the max downstream RTT,
        # not the sum (and the flusher's completion timeout stays a
        # per-hop bound, never a per-window one)
        await asyncio.gather(*(
            self._relay_entry_single(*members[0]) if len(members) == 1
            else self._relay_group(nid, value, members)
            for nid, (value, members) in groups.items()
        ))

    async def _relay_entry_single(self, e, next_env) -> None:
        """One windowed entry's ordinary single-session relay (identical
        bytes to the pre-window path — what keeps old nodes decodable)."""
        tin = e.payload[2]
        try:
            resp = await self._relay(next_env, next_env["stage"], tin=tin)
            e.result = ("relayed", resp.status, bytes(resp.body or b""))
        except NoNodeForStage as exc:
            e.result = (
                "relayed", 503, wire.pack({"error": f"no next node: {exc}"})
            )
        except Exception as exc:
            e.error = exc

    async def _relay_group(self, nid, value, members) -> None:
        """ONE coalesced envelope for a same-next-hop group. Any failure
        (transport, an old peer rejecting the multi form, a malformed
        reply) falls back to per-session relays — coalescing is an
        optimization, never a new failure mode."""
        traced = tracelib.enabled()
        envs, spans = [], []
        for e, next_env in members:
            tin = e.payload[2]
            rctx = None
            if tin is not None and traced:
                rctx = tin.child()
                next_env = {**next_env, tracelib.WIRE_KEY: rctx.to_wire()}
            envs.append(next_env)
            spans.append((tin, rctx))
        stage = envs[0]["stage"]
        t_wall = tracelib.now()
        try:
            body = wire.pack(wire.coalesce_forward(envs))
            self.metrics.inc("hop.bytes_total", len(body))
            self.metrics.inc("hop.count")
            self.metrics.inc("hop.coalesced")
            self.metrics.inc("hop.coalesced_sessions", len(members))
            host, port = node_addr(value)
            assert self._http is not None
            async with self._http.post(
                f"http://{host}:{port}{FORWARD_PATH}", data=body
            ) as r:
                raw = await r.read()
                if r.status != 200:
                    raise RuntimeError(
                        f"multi relay to {nid} answered {r.status}"
                    )
            reply = wire.unpack(raw)
            frames = (
                reply.get(wire.MULTI_KEY) if isinstance(reply, dict) else None
            )
            if not isinstance(frames, list) or len(frames) != len(members):
                raise RuntimeError(f"bad multi reply from {nid}")
            for (e, _ne), fr in zip(members, frames):
                e.result = (
                    "relayed",
                    int(fr.get("status", 500)),
                    bytes(fr.get("body") or b""),
                )
        except Exception as exc:
            # per-session fallback: an old node that cannot decode the
            # multi envelope (or a dead hop) degrades to N single relays,
            # each with its own re-pick/502 handling
            log.warning(
                "coalesced relay to %s failed (%s); per-session fallback",
                nid, exc,
            )
            self.metrics.inc("hop.coalesced_fallback")
            self.journal.emit(
                "relay.coalesced_fallback", peer=nid, stage=stage,
                sessions=len(members),
                error=f"{type(exc).__name__}: {exc}"[:120],
            )
            for _e, next_env in members:
                next_env.pop(tracelib.WIRE_KEY, None)  # _relay re-stamps
            # concurrent, like the pre-coalescing path: N sequential
            # fallback relays would turn one slow peer into sum-of-RTTs
            await asyncio.gather(*(
                self._relay_entry_single(e, next_env)
                for e, next_env in members
            ))
        finally:
            if traced:
                t1 = tracelib.now()
                for tin, rctx in spans:
                    if rctx is not None:
                        self.tracer.record_span(
                            "relay", "relay", t_wall, t1, parent=tin,
                            ctx=rctx,
                            attrs={"stage": stage,
                                   "coalesced": len(members)},
                        )

    def _plan_route(
        self, start_stage: int, affinity=None,
    ) -> Optional[Dict[str, str]]:
        """Whole-chain route {str(stage): node_id} for stages start_stage..
        last, from PathFinder.find_best_chain (the long-lived incremental
        D*-Lite planner). `affinity` (e.g. the session's AdapterAffinity)
        re-ranks the chain's FIRST stage by the bounded affinity bonus —
        dstar.node_cost composition: suppressed on shedding/draining,
        dominated by the outlier penalty. Returns None when no complete
        chain exists (caller degrades to per-hop picks)."""
        try:
            chain = self.path_finder.find_best_chain(
                start_stage, affinity=affinity
            )
        except NoNodeForStage:
            self.metrics.inc("route.plan_failed")
            return None
        except Exception:
            log.exception("chain planning failed; per-hop fallback")
            self.metrics.inc("route.plan_failed")
            return None
        self.metrics.inc("route.planned")
        return {
            str(s): nid
            for s, (nid, _) in enumerate(chain, start=start_stage)
        }

    async def _pick_next(
        self, session_id: Optional[str], stage: int, exclude=None, route=None,
        prefer: Optional[str] = None,
    ):
        """Next-replica pick. `prefer` (a node_id the caller already
        verified, e.g. the rescue path's gossip holder) wins outright when
        live and not excluded. Otherwise, in priority order: (1) local
        session affinity
        — the replica this node already routed the session to; (2) the
        swarm-shared session location — a replica ADVERTISING the session's
        KV in its gossip record (rescues sessions whose affinity map died
        with another node); (3) the planned D*-Lite route riding the
        envelope (new sessions); (4) min-load pick."""
        key = (session_id, stage) if session_id else None
        if prefer is not None and (not exclude or prefer not in exclude):
            value = self.dht.get_stage(stage).get(prefer)
            if value is not None:
                if key is not None:
                    self._session_next[key] = (prefer, time.monotonic())
                    self._session_next.move_to_end(key)
                return prefer, value
        if key is not None and key in self._session_next:
            nid, _ = self._session_next[key]
            value = self.dht.get_stage(stage).get(nid)
            if value is not None and (not exclude or nid not in exclude):
                self._session_next[key] = (nid, time.monotonic())
                self._session_next.move_to_end(key)
                return nid, value
            # the remembered replica is gone; its KV is lost — fall through
            # to a fresh pick (the executor there will reject mid-session
            # chunks and the client restarts the session)
            self._session_next.pop(key, None)
        if session_id is not None:
            nid = self._gossip_session_holder(session_id, stage, exclude)
            if nid is not None:
                value = self.dht.get_stage(stage).get(nid)
                if value is not None:
                    self.metrics.inc("route.sess_gossip")
                    self._session_next[key] = (nid, time.monotonic())
                    self._session_next.move_to_end(key)
                    while len(self._session_next) > self._session_next_cap:
                        self._session_next.popitem(last=False)
                    return nid, value
        if route:
            nid = route.get(str(stage))
            if nid and (not exclude or nid not in exclude):
                value = self.dht.get_stage(stage).get(nid)
                if value is not None:
                    self.metrics.inc("route.followed")
                    if key is not None:
                        self._session_next[key] = (nid, time.monotonic())
                        self._session_next.move_to_end(key)
                        while len(self._session_next) > self._session_next_cap:
                            self._session_next.popitem(last=False)
                    return nid, value
            # planned replica died between planning and arrival: fall
            # through to the fresh pick (and let affinity re-pin)
            self.metrics.inc("route.stale")
        nid, value = await self.path_finder.find_best_node(
            stage, exclude=self._with_cooldown(stage, exclude)
        )
        if key is not None:
            self._session_next[key] = (nid, time.monotonic())
            self._session_next.move_to_end(key)
            while len(self._session_next) > self._session_next_cap:
                self._session_next.popitem(last=False)
        return nid, value

    def _with_cooldown(self, stage: int, exclude):
        """Exclude-set for the FRESH min-load pick, augmented with peers
        still inside their dead-peer cooldown (_note_peer_failure) —
        unless that would leave the stage with no candidate at all
        (availability beats steering). Affinity/holder/route picks never
        consult this: a session's KV location is correctness, not a
        steering preference."""
        now = time.monotonic()
        if self._peer_cooldown:
            self._peer_cooldown = {
                k: t for k, t in self._peer_cooldown.items() if t > now
            }
        base = set(exclude or ())
        cooling = set(self._peer_cooldown) - base
        if not cooling:
            return exclude
        alive = set(self.dht.get_stage(stage)) - base
        if alive - cooling:
            return base | cooling
        return exclude

    def _note_peer_failure(self, node_id: str) -> None:
        """Start (or extend) a replica's dead-peer cooldown after a
        transport-dead or 5xx-answering relay: fresh picks steer around
        it for peer_cooldown_s instead of rediscovering the failure once
        per new session — the routing half of overload containment (a
        stalling replica otherwise keeps collecting half a stage's
        admissions at one hop-timeout each)."""
        self._peer_cooldown[node_id] = (
            time.monotonic() + self.peer_cooldown_s
        )
        self.metrics.inc("peer.cooldown")
        # the CHAIN planner folds the death in immediately (INF in-edges,
        # incremental D*-Lite compute + its own resurrect-proof cooldown)
        # instead of replanning sessions into the corpse until its gossip
        # record TTLs out (control.path_finder.note_peer_dead)
        self.path_finder.note_peer_dead(node_id)

    async def _relay(
        self, env: Dict[str, Any], stage: int, exclude=None,
        prefer: Optional[str] = None,
        tin: Optional[tracelib.SpanContext] = None, phase: str = "relay",
        span_attrs: Optional[Dict[str, Any]] = None,
        attempts: int = 2,
    ) -> web.Response:
        """Relay to the picked next node; on a dead hop (its DHT record
        hasn't TTL'd out yet), re-pick once excluding it, then surface a
        wire-packed 502 — never an unhandled exception (aiohttp would turn
        that into a bare HTML 500 the client can't parse).

        When `tin` (this node's server span) is set and tracing is on, the
        hop records a `phase` span ("relay", or "rescue" from the rescue
        path) whose id rides the forwarded envelope's `trace` key — its
        send/recv interval brackets the remote node's spans, which is the
        anchor pair the merge CLI corrects clock skew with.

        Overload plane: the per-hop HTTP timeout is the REMAINING
        end-to-end budget when a `deadline_ms` rides the envelope
        (clamped by hop_timeout_s) — a stalled peer costs at most what
        the request had left, never a full static timeout. Idempotent
        single-token decode relays may HEDGE: after an adaptive delay
        (trailing hop p95, or hedge_delay_ms when pinned) the same
        envelope fires at a second replica and the first 200 wins, the
        loser is cancelled — under the <=5% hedge_budget (see
        _relay_exchange)."""
        assert self._http is not None
        exclude = set(exclude or ())
        session_id = env.get("session_id")
        deadline_ms = env.get(retrylib.DEADLINE_KEY)
        # hedging only on the plain relay path: the rescue path already
        # targets a verified holder, and a mismatch re-route is rare
        # enough that a second copy buys nothing
        may_hedge = (
            phase == "relay" and prefer is None
            and self.hedge_mode != "off"
            and _is_decode_step(env.get("payload"))
        )
        relay_ctx: Optional[tracelib.SpanContext] = None
        t_wall = 0.0
        if tin is not None and tracelib.enabled():
            relay_ctx = tin.child()
            env = {**env, tracelib.WIRE_KEY: relay_ctx.to_wire()}
            t_wall = tracelib.now()
        body = wire.pack(env)  # pack once: env carries multi-MB activations
        # bytes-per-hop visibility (/stats): avg = bytes_total / count
        self.metrics.inc("hop.bytes_total", len(body))
        self.metrics.inc("hop.count")
        self.hedge_budget.note()  # one primary send (the <=5% denominator)
        last_err: Optional[Exception] = None
        try:
            # attempts=1 (the rescue path): the caller targets ONE
            # verified holder and runs its own bounded bounce loop — the
            # blind re-pick here would only spin the empty-stage recovery
            # hook (adopt + retry sleeps) once per bounce
            for attempt in range(attempts):
                node_id, value = await self._pick_next(
                    session_id, stage, exclude, route=env.get("route"),
                    prefer=prefer if attempt == 0 else None,
                )
                rem = retrylib.remaining_s(deadline_ms)
                if rem is not None and rem <= 0:
                    return self._deadline_response(
                        tin, session_id, stage, "relay"
                    )
                timeout_s = (
                    self.hop_timeout_s if rem is None
                    # +50 ms so the downstream node's own typed 408 wins
                    # the race against our transport timeout
                    else min(self.hop_timeout_s, rem + 0.05)
                )
                try:
                    status, raw = await self._relay_exchange(
                        body, stage, node_id, value, timeout_s,
                        session_id=session_id, exclude=exclude,
                        allow_hedge=(may_hedge and attempt == 0), tin=tin,
                    )
                    if status >= 500 and status != 503:
                        # the hop answered, but broken (chaos drop, a
                        # compute crash): steer fresh picks away for a
                        # beat. 503 is EXEMPT — a shed/draining replica
                        # told us when to come back, it isn't sick.
                        self._note_peer_failure(node_id)
                    return web.Response(status=status, body=raw)
                except (OSError, asyncio.TimeoutError, aiohttp.ClientError) as e:
                    last_err = e
                    self._note_peer_failure(node_id)
                    exclude.add(node_id)
                    if session_id is not None:
                        # the replica (and this session's KV on it) is gone
                        self._session_next.pop((session_id, stage), None)
                    self.metrics.inc("hop.dead")
                    self.journal.emit(
                        "peer.dead", trace=tin, peer=node_id, stage=stage,
                        error=f"{type(e).__name__}: {e}"[:120],
                    )
                    log.warning("next hop %s for stage %d unreachable: %s", node_id, stage, e)
            return self._error_response(502, f"next hop unreachable: {last_err}")
        finally:
            if relay_ctx is not None:
                self.tracer.record_span(
                    "relay", phase, t_wall, tracelib.now(), parent=tin,
                    ctx=relay_ctx,
                    attrs={"stage": stage, **(span_attrs or {})},
                )

    async def _post_forward_raw(
        self, value: Dict[str, Any], body: bytes, timeout_s: float
    ) -> Tuple[int, bytes]:
        """One /forward POST to a gossip record -> (status, raw reply)."""
        assert self._http is not None
        host, port = node_addr(value)
        async with self._http.post(
            f"http://{host}:{port}{FORWARD_PATH}", data=body,
            timeout=aiohttp.ClientTimeout(total=timeout_s),
        ) as r:
            return r.status, await r.read()

    def _hedge_delay_s(self, timeout_s: float) -> float:
        """How long to wait on the primary before firing the hedge:
        hedge_delay_ms when pinned (tests/ops), else the trailing-window
        hop p95 ("The Tail at Scale": hedge only the slowest ~5%), with a
        250 ms fallback while the window is empty. Never more than half
        the hop timeout — a hedge that can't finish is pure waste."""
        if self.hedge_delay_ms > 0:
            d = self.hedge_delay_ms / 1e3
        else:
            q = self.tsdb.trailing_quantiles(
                "hop.relay_ms", self.window_s, qs=(0.95,)
            )
            d = q["p95_ms"] / 1e3 if q else 0.25
        return max(0.001, min(d, timeout_s * 0.5))

    def _hedge_target(
        self, session_id: Optional[str], stage: int, exclude: set
    ):
        """(node_id, value) to hedge at, or None. "advertised" (default):
        only a replica whose gossip record advertises this session's KV —
        it can serve the decode step without a session restart, so the
        hedge is genuinely idempotent. "any": the best-ranked OTHER
        replica (stateless backends, where any replica can serve)."""
        if self.hedge_mode == "any":
            ranked = self.path_finder.find_ranked(stage, exclude=exclude)
            return ranked[0] if ranked else None
        if session_id is None:
            return None
        nid = self._gossip_session_holder(session_id, stage, exclude=exclude)
        if nid is None:
            return None
        value = self.dht.get_stage(stage).get(nid)
        return None if value is None else (nid, value)

    async def _relay_exchange(
        self, body: bytes, stage: int, node_id: str, value: Dict[str, Any],
        timeout_s: float, session_id: Optional[str], exclude: set,
        allow_hedge: bool, tin: Optional[tracelib.SpanContext],
    ) -> Tuple[int, bytes]:
        """One hop exchange, optionally hedged: POST the primary; if it
        hasn't answered within the hedge delay and a target + budget
        exist, POST the identical bytes at the second replica and take
        the FIRST 200, cancelling the loser (hedge.fired/won/cancelled
        counters + journal).

        Resolution rules: ANY primary response — 200 or not — concludes
        the exchange immediately (the pre-hedge contract: a deterministic
        409/500 from the picked replica must reach the caller's
        retry/re-pick logic at once, not after the hedge resolves); a
        hedge response concludes it only on 200 (a fast 409 from a
        KV-less hedge target must not mask the primary's real answer).
        When the primary DIES at transport level the hedge gets its
        chance (it fired because the primary already stalled, so its
        answer is normally already in hand); if neither succeeds the
        primary's outcome is raised, keeping the caller's dead-hop
        bookkeeping about the replica it actually picked."""
        primary = asyncio.ensure_future(
            self._post_forward_raw(value, body, timeout_s)
        )
        hedge_to = None
        if allow_hedge:
            done, _ = await asyncio.wait(
                {primary}, timeout=self._hedge_delay_s(timeout_s)
            )
            if primary in done:
                return primary.result()  # may raise: caller handles
            hedge_to = self._hedge_target(
                session_id, stage, exclude={node_id, *exclude}
            )
            if hedge_to is not None and not self.hedge_budget.try_acquire():
                hedge_to = None  # over the <=5% extra-load budget
        if hedge_to is None:
            return await primary
        hid, hvalue = hedge_to
        self.metrics.inc("hedge.fired")
        self.journal.emit(
            "hedge.fired", trace=tin, stage=stage, primary=node_id,
            hedge=hid, session=session_id,
        )
        hedge = asyncio.ensure_future(
            self._post_forward_raw(hvalue, body, timeout_s)
        )
        outcomes: Dict[Any, Any] = {}
        pending = {primary, hedge}
        try:
            while pending:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED
                )
                for t in done:
                    try:
                        status, raw = t.result()
                    except asyncio.CancelledError:
                        raise
                    except Exception as e:
                        outcomes[t] = e
                        continue
                    if t is primary:
                        # the picked replica ANSWERED: that is the
                        # exchange's result, 200 or not — the hedge
                        # only ever covers a primary that stays silent
                        self.metrics.inc("hedge.cancelled")
                        return status, raw
                    if status == 200:
                        self.metrics.inc("hedge.won")
                        self.journal.emit(
                            "hedge.won", trace=tin, stage=stage,
                            hedge=hid, session=session_id,
                        )
                        if session_id is not None:
                            # the winner proved it holds/serves this
                            # session: repoint affinity so the next
                            # step goes straight there
                            key = (session_id, stage)
                            self._session_next[key] = (
                                hid, time.monotonic()
                            )
                            self._session_next.move_to_end(key)
                        return status, raw
                    outcomes[t] = (status, raw)
        finally:
            # whatever got us out (a winner, both losing, cancellation):
            # no in-flight copy survives this exchange
            for t in (primary, hedge):
                if not t.done():
                    t.cancel()
            await asyncio.gather(primary, hedge, return_exceptions=True)
        # reaching here means BOTH tasks resolved without a definitive
        # answer; a primary RESPONSE always returned in-loop, so the
        # primary's outcome is necessarily its exception — raise it (the
        # caller's dead-hop bookkeeping is about the replica it picked)
        pr = outcomes.get(primary)
        assert isinstance(pr, Exception), pr
        raise pr

    async def handle_import_session(self, request: web.Request) -> web.Response:
        """Adopt a migrating replica's session KV (live-migration handoff —
        see change_stage). POST {"session_id", "stage", "k", "v", "length"}
        -> {"ok": bool}. Only accepted for this node's current stage."""
        try:
            env = wire.unpack(await request.read())
            session_id = env["session_id"]
            stage = int(env["stage"])
        except Exception as e:
            return self._error_response(400, f"bad import_session: {e}")
        if stage != self.info.stage:
            return self._error_response(
                409, f"wrong stage: this node serves {self.info.stage}, not {stage}",
                code="wrong_stage",
            )
        imp = getattr(self.executor, "import_session", None)
        ok = False
        # handoff-phase span, parented to the exporter's span riding the
        # envelope: the adoption cost shows up in the same trace as the
        # export that shipped it
        parent = tracelib.SpanContext.from_wire(env.get(tracelib.WIRE_KEY))
        t_wall = tracelib.now()
        if imp is not None:
            try:
                ok = bool(await self.scheduler.run(imp, session_id, env))
            except Exception:
                log.exception("import_session failed")
        self.tracer.record_span(
            "import_session", "handoff", t_wall, tracelib.now(), parent=parent,
            attrs={"stage": stage, "ok": ok},
        )
        if ok:
            self.metrics.inc("sessions.imported")
            # advertise the adopted session NOW: the failed-over client's
            # next chunk routes here via the gossip session location, and
            # waiting for the next request-driven announce would race it
            self.announce()
        return web.Response(body=wire.pack({"ok": ok}))

    async def handle_export_session(self, request: web.Request) -> web.Response:
        """Deliberate single-session handoff — the DISAGGREGATED
        prefill->decode primitive: POST {"session_id", "target_host",
        "target_port"} exports that session's KV, ships it to the target
        replica's /import_session, and (on success) drops it here; the
        caller continues decoding against the target TOKEN-EXACT with zero
        restarts. A prefill-heavy request can land on any replica, prefill
        there, and decode somewhere cheaper — the reference pins a
        session's KV to one server forever
        (/root/reference/models/qwen3/server/qwen3_server_module.py:220).
        Replies {"ok": true, "bytes": N, "ms": T}; /stats carries the
        cumulative handoff.bytes counter and handoff.ms histogram."""
        try:
            env = wire.unpack(await request.read())
            session_id = env["session_id"]
            host = str(env["target_host"])
            port = int(env["target_port"])
        except Exception as e:
            return self._error_response(400, f"bad export_session: {e}")
        export = getattr(self.executor, "export_sessions", None)
        if export is None:
            return self._error_response(
                501, "this executor cannot export sessions", code="no_export"
            )
        t0 = time.perf_counter()
        try:
            exported = await self.scheduler.run(
                lambda: export(only=session_id)
            )
        except Exception as e:
            return self._error_response(500, f"export failed: {e}")
        if not exported:
            return self._error_response(
                404, f"no session {session_id} here", code="unknown_session"
            )
        sid, payload = exported[0]
        # handoff-phase span: its id rides the import envelope so the
        # importer's adoption span nests under this export in the merged
        # timeline (the disaggregated prefill->decode hop, attributable)
        h_parent = tracelib.SpanContext.from_wire(env.get(tracelib.WIRE_KEY))
        hctx: Optional[tracelib.SpanContext] = None
        t_wall = tracelib.now()
        if tracelib.enabled():
            hctx = tracelib.SpanContext(
                h_parent.trace_id if h_parent is not None else tracelib.new_id(),
                tracelib.new_id(),
            )
        body = wire.pack({
            "session_id": sid, "stage": self.info.stage, **payload,
            **({tracelib.WIRE_KEY: hctx.to_wire()} if hctx is not None else {}),
        })
        assert self._http is not None
        try:
            async with self._http.post(
                f"http://{host}:{port}{IMPORT_SESSION_PATH}", data=body
            ) as r:
                raw = await r.read()
                try:
                    resp = wire.unpack(raw) if r.status == 200 else None
                except Exception:
                    resp = None  # garbage 200 body == declined, not a 500
        except (OSError, asyncio.TimeoutError, aiohttp.ClientError) as e:
            return self._error_response(502, f"target unreachable: {e}")
        if not (isinstance(resp, dict) and resp.get("ok")):
            return self._error_response(
                502, f"target declined the session: {resp}", code="import_failed"
            )
        # the target owns the session now: drop the local copy so the
        # lane/slot frees (the caller's next step goes to the target)
        end = getattr(self.executor, "end_session", None)
        if end is not None:
            try:
                await self.scheduler.run(end, session_id)
            except Exception:
                log.exception("local end_session after handoff failed")
        ms = (time.perf_counter() - t0) * 1e3
        self.metrics.inc("handoff.bytes", len(body))
        self.metrics.observe("handoff.ms", ms)
        self.metrics.inc("sessions.handed_off")
        if hctx is not None:
            self.tracer.record_span(
                "export_session", "handoff", t_wall, tracelib.now(),
                parent=h_parent, ctx=hctx,
                attrs={"stage": self.info.stage, "bytes": len(body)},
            )
        self.announce()  # stop advertising the departed session promptly
        return web.Response(body=wire.pack({
            "ok": True, "bytes": len(body), "ms": round(ms, 3),
        }))

    async def handle_drain(self, request: web.Request) -> web.Response:
        """POST /drain — graceful drain: stop admitting NEW sessions
        (typed 503 code "draining" with a Retry-After hint), gossip a
        `draining` flag both routers treat as an exclusion, then finish
        or hand off resident sessions: after a bounded settle (optional
        body key "wait_s", default 5 s — lets in-flight steps reach a
        chunk boundary) every resident session's KV ships to a surviving
        same-stage replica (/import_session) and the adopted copies drop
        here, so failed-over clients continue token-exact via the gossip
        session-location rescue instead of restarting. Residents no
        replica adopts keep being served HERE until they finish or TTL
        out (drain never kills live work). Idempotent; replies
        {"ok", "draining", "resident", "handed_off"}."""
        env: Dict[str, Any] = {}
        try:
            raw = await request.read()
            if raw:
                parsed = wire.unpack(raw)
                if isinstance(parsed, dict):
                    env = parsed
        except Exception:
            pass  # an empty/garbage body still means "drain"
        try:
            wait_s = float(env.get("wait_s", 5.0))
        except (TypeError, ValueError):
            wait_s = 5.0
        if not self._draining:
            self._draining = True
            self.metrics.inc("drain.requests")
            self.journal.emit("node.draining", stage=self.info.stage)
            self._health_cache = (0.0, None)  # verdict predates the flag
            # urgent: routers must exclude this replica within one gossip
            # beat, not one cache lifetime
            self.announce()
        deadline = time.monotonic() + max(0.0, wait_s)
        while time.monotonic() < deadline and self.scheduler.inflight > 0:
            await asyncio.sleep(0.05)
        store = getattr(self.executor, "sessions", None)
        try:
            resident = len(store) if store is not None else 0
        except TypeError:
            resident = 0
        handed = await self._drain_handoff()
        self.journal.emit(
            "node.drained", stage=self.info.stage, resident=resident,
            handed_off=handed,
        )
        return web.Response(body=wire.pack({
            "ok": True, "draining": True, "resident": resident,
            "handed_off": handed,
        }))

    async def _drain_handoff(self) -> int:
        """Ship every resident session's KV to surviving same-stage
        replicas and drop the local copy of each ADOPTED one (unlike the
        stop()-path handoff, this node keeps serving — un-adopted
        sessions must stay resident). Returns how many handed off."""
        export = getattr(self.executor, "export_sessions", None)
        if export is None or self._http is None:
            return 0
        try:
            loop = asyncio.get_running_loop()
            exported = await loop.run_in_executor(None, export)
        except Exception:
            log.exception("drain export failed (residents stay local)")
            return 0
        if not exported:
            return 0
        exported_len = {
            sid: int(payload.get("length", -1)) for sid, payload in exported
        }
        adopted = await self._handoff_sessions(exported, self.info.stage)
        dropped = 0
        for sid in adopted:
            # mid-session chunks are deliberately never shed, so a decode
            # step may have ADVANCED this session while its snapshot was
            # in flight — dropping the newer local copy would strand the
            # client on the adopter's stale KV (409 -> full restart).
            # Re-export just this session and compare frontiers: advanced
            # means it keeps being served HERE (drain finishes residents
            # it can't hand off cleanly; the adopter's stale copy TTLs
            # out). A step landing between this check and end_session
            # still degrades to the client's restart path — containment
            # narrows the race, correctness never depended on it.
            try:
                again = export(only=sid)
            except Exception:
                continue  # can't verify: keep the local copy
            cur_len = (
                int(again[0][1].get("length", -2)) if again else -2
            )
            if cur_len != exported_len.get(sid, -1):
                continue
            try:
                self.executor.end_session(sid)
                dropped += 1
            except Exception:
                log.exception("drain: local end_session failed")
        if dropped:
            self.metrics.inc("drain.handed_off", dropped)
            self.announce()  # stop advertising the departed sessions NOW
        return dropped

    async def _handoff_sessions(self, exported, old_stage: int):
        """Ship a migrating executor's session KV to the live replicas of
        the stage being vacated, so in-flight generations continue without
        a client-side session restart (the reference's migration loses all
        sessions; SURVEY §7 'their KV lives on the old node'). Best effort:
        a failed import just means that session's next chunk 409s and the
        client restarts — exactly the pre-handoff behavior. Returns the
        session ids a replica actually adopted (the drain path drops its
        local copies of exactly those)."""
        assert self._http is not None
        replicas = {
            nid: val
            for nid, val in self.dht.get_stage(old_stage).items()
            if nid != self.info.node_id
        }
        if not replicas:
            return []

        async def ship(sid, payload):
            # per-session handoff span; its id rides the import envelope so
            # the adopter's span joins the same trace
            hctx: Optional[tracelib.SpanContext] = None
            if tracelib.enabled():
                hctx = tracelib.SpanContext(tracelib.new_id(), tracelib.new_id())
            t_wall = tracelib.now()
            adopted = False
            # pack INSIDE the per-session scope: one unserializable session
            # must not abort every other session's handoff
            body = wire.pack({
                "session_id": sid, "stage": old_stage, **payload,
                **({tracelib.WIRE_KEY: hctx.to_wire()} if hctx is not None else {}),
            })
            # a tenant session's payload only goes to adapter-CAPABLE
            # peers (gossiped `ada` key, present even when empty): an
            # old-release or registry-less replica would silently adopt
            # it onto the base weights — its handoff codec ignores the
            # unknown `adapter` key instead of declining
            targets = replicas if payload.get("adapter") is None else {
                nid: val for nid, val in replicas.items() if "ada" in val
            }
            try:
                for nid, val in targets.items():
                    host, port = node_addr(val)
                    try:
                        async with self._http.post(
                            f"http://{host}:{port}{IMPORT_SESSION_PATH}", data=body
                        ) as r:
                            raw = await r.read()
                            resp = wire.unpack(raw) if r.status == 200 else None
                        if isinstance(resp, dict) and resp.get("ok"):
                            self.metrics.inc("sessions.exported")
                            adopted = True
                            return sid  # one adopting replica is enough
                    except Exception:
                        # anything wrong with THIS replica (dead, garbage body,
                        # version mismatch) must not abort the other replicas or
                        # the other sessions' handoffs
                        continue
            finally:
                if hctx is not None:
                    self.tracer.record_span(
                        "handoff", "handoff", t_wall, tracelib.now(), ctx=hctx,
                        attrs={"stage": old_stage, "ok": adopted},
                    )

        # ship sessions concurrently: a dead replica costs ~one hop timeout
        # total, not S * timeout serially (reassign awaits this handoff);
        # return_exceptions so one bad session can't abort its siblings
        results = await asyncio.gather(
            *(ship(s, p) for s, p in exported), return_exceptions=True
        )
        adopted_sids = []
        for r in results:
            if isinstance(r, BaseException):
                log.warning("session handoff failed for one session: %s", r)
            elif r:
                adopted_sids.append(r)
        return adopted_sids

    async def handle_reassign(self, request: web.Request) -> web.Response:
        """Admin-forced migration: POST {"stage": int} (reference
        node.py:82-91, functioning)."""
        try:
            env = wire.unpack(await request.read())
            target = int(env["stage"])
        except Exception as e:
            return self._error_response(400, f"bad reassign request: {e}")
        if not 0 <= target < self.info.num_stages:
            return self._error_response(400, f"stage {target} out of range")
        try:
            await self.change_stage(target)
        except Exception as e:
            log.exception("reassign failed")
            return self._error_response(500, f"reassign failed: {e}")
        return web.Response(body=wire.pack({"ok": True, "stage": target}))

    async def handle_fork_session(self, request: web.Request) -> web.Response:
        """Seed a new session's KV from an existing session's prefix, here
        and on downstream stages (distributed prefix caching — see
        executor.fork_session). POST {"session_id", "parent_session_id",
        "prefix_len", "stage", "relay"}. Responds {"ok": bool, "stage": N};
        ok is True only if EVERY stage from here on forked. A False is a
        clean miss (parent evicted/unknown here — all serving executors
        implement fork_session; getattr guards custom ones that don't) —
        the client falls back to a full prefill."""
        try:
            env = wire.unpack(await request.read())
        except Exception as e:
            return self._error_response(400, f"bad fork_session: {e}")
        return await self._fork_session(env)

    async def _fork_session(self, env: Dict[str, Any]) -> web.Response:
        """handle_fork_session past the socket: the route and the node's
        own generation loop (_serve_local) both enter here."""
        try:
            new_sid = env["session_id"]
            parent_sid = env["parent_session_id"]
            prefix_len = int(env["prefix_len"])
        except Exception as e:
            return self._error_response(400, f"bad fork_session: {e}")
        stage = int(env.get("stage", self.info.stage))
        relay = env.get("relay", True)

        if stage != self.info.stage:
            if not relay:
                return self._error_response(
                    409,
                    f"wrong stage: this node serves {self.info.stage}, not {stage}",
                    code="wrong_stage",
                )
            try:
                return await self._relay_fork(env, stage)
            except NoNodeForStage as e:
                return self._error_response(503, str(e))

        fork = getattr(self.executor, "fork_session", None)
        ok = False
        if fork is not None:
            try:
                ok = bool(
                    await self.scheduler.run(fork, new_sid, parent_sid, prefix_len)
                )
            except Exception:
                log.exception("fork_session failed")
                ok = False
        self.metrics.inc("fork.ok" if ok else "fork.miss")
        if not ok:
            return web.Response(body=wire.pack({"ok": False, "stage": stage}))
        if not relay or stage + 1 >= self.info.num_stages:
            return web.Response(body=wire.pack({"ok": True, "stage": stage}))
        # downstream stages must fork the same parent; a partially-forked
        # chain reports ok=False and the client's end_session cleans it up
        next_env = dict(env, stage=stage + 1)
        try:
            return await self._relay_fork(next_env, stage + 1)
        except NoNodeForStage as e:
            return self._error_response(503, f"no next node for fork: {e}")

    async def _relay_fork(self, env: Dict[str, Any], stage: int) -> web.Response:
        """Relay a fork along the PARENT session's affinity route (the
        replicas actually holding the parent's KV), pinning the new
        session's affinity to the same replicas as it goes.

        ONE attempt, no re-pick: only the parent's replica can hold its KV —
        a different replica would answer a misleading clean ok=False miss
        (which makes the client permanently unpin a prefix that survived a
        network blip). A transport failure surfaces as a 502 instead, which
        the client treats as transient (pin kept, full prefill this once)."""
        assert self._http is not None
        parent_sid = env.get("parent_session_id")
        new_sid = env.get("session_id")
        body = wire.pack(env)
        node_id, value = await self._pick_next(parent_sid, stage)
        host, port = node_addr(value)
        url = f"http://{host}:{port}{FORK_SESSION_PATH}"
        try:
            async with self._http.post(url, data=body) as r:
                raw = await r.read()
                if r.status == 200 and new_sid is not None:
                    key = (new_sid, stage)
                    self._session_next[key] = (node_id, time.monotonic())
                    self._session_next.move_to_end(key)
                return web.Response(status=r.status, body=raw)
        except (OSError, asyncio.TimeoutError, aiohttp.ClientError) as e:
            self.metrics.inc("hop.dead")
            self.journal.emit(
                "peer.dead", peer=node_id, stage=stage,
                error=f"{type(e).__name__}: {e}"[:120],
            )
            return self._error_response(502, f"fork hop unreachable: {e}")

    def _build_spec_engine(self, sampling):
        """Self-drafting speculative engine over the executor's full-model
        params: the target's first `spec_draft_layers` layers propose,
        the full stack verifies — token-exact for greedy requests and
        DISTRIBUTION-exact (standard rejection scheme) for sampled ones
        (core.speculative). Only possible when this node hosts the whole
        model with addressable params (stage or batched executor; the mesh
        executor's params are sharded). `sampling` is baked into the
        engine's jits; the caller caches one engine per config."""
        if (
            self.spec_draft_layers <= 0
            or self.info.num_stages != 1
            or self.spec_draft_layers >= self.cfg.num_layers
            or self.mesh_plan is not None  # mesh params are pp/tp-sharded
            # batched executors speculate on their own lanes
            # (core.spec_batch) — a second solo engine would double the
            # cache memory to serve one request at a time
            or getattr(self.executor, "spec_enabled", lambda: False)()
        ):
            return False
        params = getattr(self.executor, "params", None)
        if params is None:
            eng = getattr(self.executor, "engine", None)
            params = getattr(eng, "params", None)
        if not isinstance(params, dict) or "embed" not in params:
            return False
        from inferd_tpu.core.speculative import SpeculativeEngine, self_draft

        dcfg, draft_params = self_draft(self.cfg, params, self.spec_draft_layers)
        return SpeculativeEngine(
            self.cfg, params, dcfg, draft_params, k=self.spec_k,
            max_len=self.max_len,
            sampling_cfg=sampling,
            top_n=self._spec_top_n,
        )

    async def handle_generate(self, request: web.Request) -> web.Response:
        """Traced entry for /generate: the X-Inferd-Trace header (the
        trace surface of this endpoint — there is no per-hop envelope on
        the outer request) parents a `server`-phase umbrella span, and the
        contextvar makes every span of the node's self-driven token loop
        (its generation loop's steps, the forward hops they make) nest
        under it. NOT phase "sample": the merge CLI counts sample-phase
        spans as emitted tokens, and an umbrella would inflate every
        server-driven generation by one. With tracing disabled this is a
        passthrough."""
        # user-SLI accounting for this request: wall/ttft/token stamps
        # collected by the inner paths, folded into the generate.* series
        # on the way out — UNLESS the X-Inferd-Canary header marks it
        # synthetic (obs.canary): probe traffic must never flatter or
        # poison the numbers users are judged by. Canary requests tag
        # their server span instead, so traces stay attributable.
        is_canary = request.headers.get(canarylib.CANARY_HEADER) is not None
        sli: Dict[str, Any] = {
            "t0": time.perf_counter(), "ttft_ms": None, "tokens": 0,
            "canary": is_canary,
        }
        status = 500  # an exception escaping the handler IS a server error
        try:
            if not tracelib.enabled():
                resp = await self._handle_generate_inner(request, sli)
            else:
                parent = tracelib.SpanContext.from_header(
                    request.headers.get(tracelib.TRACE_HEADER)
                )
                # the root of a request: kept, and so is all of its
                # admission beneath it (obs.trace)
                with self.tracer.span(
                    "generate", "server", parent=parent,
                    attrs={"canary": 1} if is_canary else None, keep=True,
                ):
                    resp = await self._accepted(request, sli)
            status = resp.status
            return resp
        finally:
            self._record_generate_sli(sli, status)

    def _record_generate_sli(self, sli: Dict[str, Any], status: int) -> None:
        """Fold one finished /generate into the user-SLI series —
        generate.requests/errors counters plus the wall_ms/ttft_ms/
        tpot_ms/tokens series the windowed tsdb turns into fleet
        TTFT/TPOT percentiles and the availability burn-rate SLI
        (obs.fleet, obs.health BURN_SLIS). Canary-tagged requests are
        excluded by construction. Only SUCCESSFUL responses record
        latency: a fast 503 shed or 400 reject folded into wall_ms
        would DROP the fleet percentiles during the exact incident
        they exist to expose (errors burn the error budget instead).
        The whole family rides the INFERD_EVENTS kill switch so a
        disabled node's /metrics stays byte-identical."""
        if sli["canary"] or not eventslib.enabled():
            return
        m = self.metrics
        m.inc("generate.requests")
        if sli.get("error"):
            # a STREAMED failure rides an already-sent 200: the handler
            # wrote an {"error": ...} line instead of a status code, so
            # the in-band marker — not resp.status — is the truth here
            status = 500
        if status >= 400:
            if status >= 500:
                m.inc("generate.errors")  # 4xx = caller bug, not burn
            return
        wall_ms = (time.perf_counter() - sli["t0"]) * 1e3
        m.observe("generate.wall_ms", wall_ms, bounds_ms=_GENERATE_BOUNDS_MS)
        n = int(sli.get("tokens") or 0)
        if n > 0:
            m.inc("generate.tokens", n)
            m.observe("generate.tpot_ms", wall_ms / n)
        if sli.get("ttft_ms") is not None:
            m.observe(
                "generate.ttft_ms", sli["ttft_ms"],
                bounds_ms=_GENERATE_BOUNDS_MS,
            )

    async def _accepted(self, request: web.Request, sli: Dict[str, Any]) -> web.Response:
        """`_handle_generate_inner`, with the request's arrival left as a
        mark for the generation loop: its `accept`-phase span runs from
        here to where the loop's `generate` span opens, and the loop
        records it there (client/base.py generate_ids), not when the
        request is over. A request that never reaches the loop (refused;
        a speculative path) has no `accept`."""
        marks, token = tracelib.open_marks()
        marks["t_arrived"] = tracelib.now()
        try:
            return await self._handle_generate_inner(request, sli)
        finally:
            tracelib.close_marks(token)

    async def _handle_generate_inner(
        self, request: web.Request, sli: Optional[Dict[str, Any]] = None,
    ) -> web.Response:
        """Server-driven generation: ONE request returns a whole generation.

        The client-side token loop (client.base) costs a network round trip
        per token — fine on a LAN, ruinous for a high-latency client. Here
        the NODE runs that same loop against itself, in process: each hop
        is a call into the forward path the /forward route enters
        (_serve_local), and a hop this node finishes hands the sampler
        the executor's logits with no bytes packed and no socket touched.
        A hop that leaves the node (a later stage of a chain; wrong-stage
        entry relays to stage 0 as usual) is relayed as ever and only its
        reply is unpacked. The caller pays one round trip total. POST
        {"prompt_ids": [...], "max_new_tokens", "sampling": {temperature,
        top_k, top_p, min_p}, "seed", "eos_token_id", "pin_prefix_len",
        "stream"} -> {"ids": [...]}.  pin_prefix_len > 0 marks the first N
        prompt ids as a shared prefix: the node pins them once (a node-held
        pinned session) and forks it for this and later generations.

        stream=true switches to a chunked newline-delimited-JSON response:
        one {"t": id} line per sampled token as it is produced, a
        {"restart": true} line if a mid-generation failure forces a
        deterministic re-run (previously streamed tokens are void), and a
        final {"done": true, "ids": [...]} (or {"error": ...}) line.

        Seed contract for SAMPLED (temperature > 0) requests: on batched
        and mesh nodes the speculative lane path is chosen structurally
        (per request shape, never per load), so a repeated (prompt, seed,
        sampling) request replays the same stream. On single-stage SOLO
        nodes with --spec-draft-layers the fast path is opportunistic —
        a request arriving while the solo spec engine is busy takes the
        regular loop, whose key schedule differs from the rejection-
        sampled engine's — so identical sampled requests under CONCURRENT
        load may return different (identically distributed) streams.
        Clients needing exact sampled replay should use greedy, logprobs
        (which pins the regular loop), or a batched/mesh node."""
        from inferd_tpu.config import SamplingConfig

        if self._draining:
            # a /generate is a NEW server-driven session by definition:
            # drain sheds it before any parsing or pinning happens
            return self._error_response(
                503, "node is draining: not accepting new generations",
                code="draining", retry_after=self._retry_after_s(),
            )
        try:
            env = wire.unpack(await request.read())
            ids = [int(t) for t in env["prompt_ids"]]
            if not ids:
                raise ValueError("prompt_ids must be non-empty")
            max_new = int(env.get("max_new_tokens", 50))
            seed = int(env.get("seed", 0))
            eos = env.get("eos_token_id")
            eos = None if eos is None else int(eos)
            pin_len = int(env.get("pin_prefix_len", 0))
            stream = bool(env.get("stream", False))
            want_lp = bool(env.get("logprobs", False))
            top_n = int(env.get("top_logprobs", 0))
            if top_n < 0 or top_n > 64:
                raise ValueError(f"top_logprobs {top_n} out of range [0, 64]")
            # tolerate unknown sampling keys: a NEWER client talking to
            # this node mid-rolling-upgrade must not 400 on a knob this
            # version doesn't know (the mirror of the client omitting
            # default-valued new keys)
            known = {f.name for f in dataclasses.fields(SamplingConfig)}
            raw_sampling = dict(env.get("sampling") or {})
            ignored_keys = sorted(set(raw_sampling) - known)
            if ignored_keys:
                # observable, not fatal: a typo'd knob or a newer client's
                # feature silently changing sampling semantics is worse
                # than a log line + an echo in the payload
                log.warning(
                    "ignoring unknown sampling keys %s", ignored_keys
                )
            sampling = SamplingConfig(
                **{k: v for k, v in raw_sampling.items() if k in known}
            )
        except Exception as e:
            return self._error_response(400, f"bad generate request: {e}")
        if pin_len < 0 or pin_len > len(ids):
            return self._error_response(400, f"pin_prefix_len {pin_len} out of range")
        if pin_len and self._block_length() > 1:
            return self._error_response(
                400, "pin_prefix_len: a model generated by blocks serves no pinned prefix")
        # optional end-to-end deadline on the WHOLE server-driven
        # generation (epoch ms, same key as the /forward envelopes): an
        # already-expired budget sheds here, and the regular token loop
        # carries the remainder so every inner hop fast-fails on time
        gen_rem = retrylib.remaining_s(env.get(retrylib.DEADLINE_KEY))
        if gen_rem is not None and gen_rem <= 0:
            self.metrics.inc("deadline.expired")
            self.journal.emit(
                "deadline.exceeded", stage=self.info.stage, where="generate"
            )
            return self._error_response(
                408, "deadline exceeded (generate admission)", code="deadline"
            )

        # batched/mesh nodes speculate on their ENGINE LANES/SLOTS
        # (core.spec_batch / parallel.infer): concurrent requests' rounds
        # coalesce instead of shedding to the regular loop, streamed
        # requests emit each accepted run as it lands, and PINNED-PREFIX
        # requests fork the shared pin instead of re-prefilling. Greedy is
        # token-exact with the regular loop; sampled is distribution-exact
        # (no per-token logprob trail — logprob requests take the regular
        # loop).
        if (
            self.spec_draft_layers > 0
            and getattr(self.executor, "spec_enabled", lambda: False)()
            and (
                (
                    # greedy: logprobs/top-N ride the verify chunk's TARGET
                    # logits (the runners' static SPEC_TOP_N width);
                    # streamed lp keeps the regular loop (per-token lp
                    # lines)
                    sampling.temperature == 0.0
                    and not (stream and (want_lp or top_n))
                    and top_n <= self._spec_top_n
                )
                or (sampling.temperature > 0.0 and not want_lp and top_n == 0)
            )
        ):
            if stream:
                return await self._generate_streaming_lanes(
                    request, ids, max_new, eos, seed, sampling, ignored_keys,
                    pin_len=pin_len, sli=sli,
                )
            resp = await self._generate_speculative_lanes(
                ids, max_new, eos, seed, sampling, ignored_keys,
                pin_len=pin_len, want_lp=want_lp, top_n=top_n, sli=sli,
            )
            if resp is not None:
                return resp

        # unpinned requests take the speculative fast path when the node
        # was started with --spec-draft-layers. Greedy requests get the
        # token-exact draft-propose/verify loop (the caller cannot tell
        # except by latency; logprobs ride along from the verify chunk's
        # TARGET logits up to the engine's static top-N width). Sampled
        # (temperature > 0) requests get the rejection-sampled engine —
        # the emitted stream is DISTRIBUTED exactly as target-only
        # sampling (not token-identical to the regular loop's key
        # schedule; a given (engine, seed) is still deterministic) — but
        # have no per-token logprob trail, so logprob requests take the
        # regular loop. Streamed requests emit each accepted run as it
        # lands (logprob streams keep the regular loop: its per-token
        # lines carry lp fields the run-level hook doesn't).
        if (
            pin_len == 0
            and self.spec_draft_layers > 0
            and (
                (
                    sampling.temperature == 0.0
                    # streamed requests skip the fast path only when they
                    # also want logprobs/top-N (the run-level stream hook
                    # carries no per-token lp fields)
                    and not (stream and (want_lp or top_n))
                    and top_n <= self._spec_top_n
                )
                or (sampling.temperature > 0.0 and not want_lp and top_n == 0)
            )
            and not self._spec_lock.locked()  # opportunistic: a busy spec
            # engine must not serialize concurrent requests behind it —
            # waiters take the regular (batchable) loop instead
        ):
            if stream:
                return await self._generate_streaming_solo_spec(
                    request, ids, max_new, eos, seed, sampling, ignored_keys,
                    sli=sli,
                )
            resp = await self._generate_speculative(
                ids, max_new, eos, seed, sampling, ignored_keys,
                want_lp=want_lp, top_n=top_n, sli=sli,
            )
            if resp is not None:
                return resp

        c = await self._get_generate_client()
        if stream:
            return await self._generate_streaming(
                request, c, ids, max_new, eos, seed, sampling, pin_len,
                want_lp, ignored_keys, top_n, sli=sli,
            )

        from inferd_tpu.client.base import ServerError

        try:
            lps = [] if want_lp else None
            tops = [] if top_n else None
            if pin_len:
                await c.pin_prefix(ids[:pin_len])
            out = await c.generate_ids(
                ids, max_new_tokens=max_new, eos_token_id=eos, seed=seed,
                sampling=sampling, logprob_sink=lps,
                top_n=top_n, top_sink=tops, deadline_s=gen_rem,
            )
        except ServerError as e:
            # pass the inner status + machine-readable code through: a 409
            # overflow must NOT come back as a retryable-looking 500 (the
            # caller's ServerError.retryable contract)
            return self._error_response(e.status, str(e), code=e.code)
        except Exception as e:
            return self._error_response(500, f"generation failed: {e}")
        if sli is not None:
            sli["tokens"] = len(out)
        payload = {"ids": out, "session_tokens": len(out)}
        if want_lp:
            payload["logprobs"] = lps
        if tops is not None:
            payload["top_logprobs"] = [list(t) for t in tops]
        if ignored_keys:
            payload["ignored_sampling_keys"] = ignored_keys
        return web.Response(body=wire.pack(payload))

    def _block_length(self) -> int:
        """The block length of the model this node loaded (1: a token a
        step)."""
        return int(getattr(getattr(self.executor, "cfg", None), "block_length", 1))

    async def _get_generate_client(self):
        """Lazy generation loop shared by all /generate requests
        (persistent so node-held prefix pins survive across requests):
        the swarm client's loop with its hops served in process
        (_serve_local) instead of posted to this node's own port."""
        from inferd_tpu.client.local_client import LocalClient

        async with self._generate_client_lock:
            if self._generate_client is None:
                c = LocalClient(
                    self._serve_local, (self.info.host, self.info.port),
                    timeout_s=self.hop_timeout_s, block_length=self._block_length(),
                )
                # share the NODE's span ring: the loop's step/sample
                # spans belong in this node's JSONL file, not a parallel
                # "client" buffer nobody exports
                c.tracer = self.tracer
                await c.__aenter__()
                self._generate_client = c
        return self._generate_client

    async def _serve_local(
        self, path: str, env: Dict[str, Any],
    ) -> Union[web.Response, Dict[str, Any]]:
        """The generation loop's transport: each hop enters the handler
        core its route would have entered, with the envelope as built."""
        if path == FORWARD_PATH:
            return await self._forward_one(env, time.perf_counter(), "local")
        if path == END_SESSION_PATH:
            return await self._end_session(env)
        if path == FORK_SESSION_PATH:
            return await self._fork_session(env)
        raise ValueError(f"no in-process handler for {path}")

    @staticmethod
    def _spec_key(sampling):
        """(cache key, normalized config) for the per-sampling-config
        speculative engines. Greedy ignores the warp parameters entirely —
        normalize so greedy clients with different top-k/p defaults share
        ONE engine instead of compiling behaviorally identical
        duplicates."""
        if sampling.temperature == 0.0:
            return (0.0, 0, 1.0, 0.0), dataclasses.replace(
                sampling, temperature=0.0, top_k=0, top_p=1.0, min_p=0.0
            )
        return (
            (sampling.temperature, sampling.top_k, sampling.top_p,
             sampling.min_p),
            sampling,
        )

    async def _ensure_spec_engine_locked(self, key, sampling):
        """Build-or-get the speculative engine for `key` (MUST hold
        _spec_lock). None = unsupported/demoted — caller takes the
        regular loop."""
        if self._spec_unsupported:
            return None
        eng = self._spec_engines.get(key)
        if eng is None:
            loop = asyncio.get_running_loop()
            try:
                eng = await loop.run_in_executor(
                    None, self._build_spec_engine, sampling
                )
                if eng is False:
                    # STRUCTURAL: this executor can't self-draft (wrong
                    # topology/params shape) — config-independent, stop
                    # probing until a migration rebuilds the executor
                    self._spec_unsupported = True
                    return None
            except Exception:
                # transient/config-specific build failure: demote THIS
                # config only; other configs may still build fine
                log.exception("speculative engine build failed")
                eng = False
            self._insert_spec_engine_locked(key, eng)
        else:
            self._spec_engines.move_to_end(key)
        return None if eng is False else eng

    def _insert_spec_engine_locked(self, key, eng) -> None:
        """Cache insert + caps (MUST hold _spec_lock). The LRU cap counts
        LIVE engines only: False demotion markers must neither cost a live
        slot (inserting a marker must not evict a compiled engine) nor be
        evicted by live-engine pressure (a demoted config must STAY off —
        re-building it would re-fail and re-log per request)."""
        self._spec_engines[key] = eng
        live = [
            k for k, v in self._spec_engines.items() if v is not False
        ]
        while len(live) > self._spec_engines_max:
            del self._spec_engines[live.pop(0)]  # oldest live
        while len(self._spec_engines) > 64:  # marker flood cap
            self._spec_engines.popitem(last=False)

    async def _prebuild_spec_engine(self) -> None:
        """Background prebuild of the GREEDY speculative engine right
        after start(): the first greedy /generate otherwise pays the whole
        draft+target jit build on its own latency (seconds on CPU, tens of
        seconds for a real model on TPU). Builds OUTSIDE _spec_lock —
        locked() doubles as handle_generate's busy-shed signal, so holding
        it through a multi-second compile would bounce every early greedy
        request to the regular loop (a request racing the prebuild at
        worst duplicates the build; both results are identical and the
        insert is last-writer-wins under the lock)."""
        from inferd_tpu.config import SamplingConfig

        try:
            loop = asyncio.get_running_loop()
            if getattr(self.executor, "spec_enabled", lambda: False)():
                # batched node: warm the GREEDY lane runner's jits with one
                # tiny open/round/close so the first real request doesn't
                # pay the round compile alone
                t0 = time.monotonic()
                await loop.run_in_executor(None, self.executor.spec_warmup)
                self.metrics.observe(
                    "spec.engine_build_ms", (time.monotonic() - t0) * 1e3,
                    bounds_ms=(10, 100, 1000, 10_000, 60_000, 120_000),
                )
                return
            key, sampling = self._spec_key(SamplingConfig(temperature=0.0))
            # capture the executor the build reads: a migrate() swapping
            # the executor mid-build must not leave a stale-params engine
            # in the cache (the insert below is skipped instead)
            built_for = self.executor
            t0 = time.monotonic()
            eng = await loop.run_in_executor(
                None, self._build_spec_engine, sampling
            )
            self.metrics.observe(
                "spec.engine_build_ms", (time.monotonic() - t0) * 1e3,
                bounds_ms=(10, 100, 1000, 10_000, 60_000, 120_000),
            )
            async with self._spec_lock:
                if eng is False:
                    self._spec_unsupported = True
                elif self.executor is not built_for:
                    log.info("executor changed mid-prebuild; dropping engine")
                elif not self._spec_engines.get(key):
                    # insert if absent OR demoted: a racing request's
                    # TRANSIENT build failure may have left a False marker
                    # for this key; the engine in hand is known-good, so
                    # good-engine-wins (the cap logic applies either way)
                    self._insert_spec_engine_locked(key, eng)
        except Exception:
            log.debug("speculative prebuild failed", exc_info=True)

    async def _generate_speculative(
        self, ids, max_new: int, eos, seed: int, sampling, ignored_keys=(),
        want_lp: bool = False, top_n: int = 0,
        sli: Optional[Dict[str, Any]] = None,
    ) -> Optional[web.Response]:
        """Speculative fast path; None = unavailable/failed (caller falls
        back to the regular loop). Logprobs/top-N (greedy only) come from
        the verify chunk's TARGET logits — identical to the regular loop's
        values. One engine per sampling config (LRU-capped): the warp
        parameters are static in the engine's jits."""
        # greedy ignores the warp parameters entirely — normalize the key
        # so greedy clients with different top-k/p defaults share ONE
        # engine instead of compiling behaviorally identical duplicates
        key, sampling = self._spec_key(sampling)
        async with self._spec_lock:
            eng = await self._ensure_spec_engine_locked(key, sampling)
            if eng is None:
                return None
            lps = [] if want_lp else None
            tops = [] if top_n else None
            try:
                out, acceptance, drafted, accepted = await self.scheduler.run(
                    lambda: eng.generate_with_stats(
                        ids, max_new, eos_token_id=eos, seed=seed,
                        logprob_sink=lps, top_sink=tops,
                    )
                )
            except Exception:
                # demote THIS config: a deterministic failure would
                # otherwise re-run (and re-log) on every matching request;
                # its fast path stays off until restart/migration
                log.exception(
                    "speculative generate failed; disabling the fast path "
                    "for this sampling config and falling back to the loop"
                )
                self._spec_engines[key] = False
                self.metrics.inc("generate.speculative_fallback")
                return None
            # production acceptance-rate observability (/stats):
            # spec.proposed/spec.accepted accumulate across requests
            self.metrics.inc("spec.proposed", drafted)
            self.metrics.inc("spec.accepted", accepted)
        self.metrics.inc("generate.speculative")
        if sli is not None:
            sli["tokens"] = len(out)
        payload = {
            "ids": out,
            "session_tokens": len(out),
            "speculative": True,
            "draft_acceptance": acceptance,
            "spec_accept_rate": acceptance,
        }
        if lps is not None:
            payload["logprobs"] = lps
        if tops is not None:
            # the engine reports its static jit width; trim to the request
            payload["top_logprobs"] = [
                [ti[:top_n], tl[:top_n]] for ti, tl in tops
            ]
        if ignored_keys:
            payload["ignored_sampling_keys"] = list(ignored_keys)
        return web.Response(body=wire.pack(payload))

    async def _generate_streaming(
        self, request, c, ids, max_new: int, eos, seed: int, sampling,
        pin_len: int, want_lp: bool = False, ignored_keys=(), top_n: int = 0,
        sli: Optional[Dict[str, Any]] = None,
    ) -> web.StreamResponse:
        """Chunked ndjson streaming flavor of /generate (see handle_generate
        docstring for the line protocol)."""
        import json as jsonlib

        resp = web.StreamResponse(headers={"Content-Type": "application/x-ndjson"})
        resp.enable_chunked_encoding()
        await resp.prepare(request)

        lps = [] if want_lp else None
        tops = [] if top_n else None

        async def on_token(tok):
            if tok is None:
                line = {"restart": True}
                if sli is not None:
                    # restarted: previously streamed tokens are VOID, so
                    # both the count and the first-token stamp reset —
                    # TTFT must mean the first token the user got to keep
                    sli["tokens"] = 0
                    sli["ttft_ms"] = None
            else:
                line = {"t": int(tok)}
                if sli is not None:
                    # user-SLI stamps: TTFT is the FIRST emitted token
                    # (the number a streaming user actually waits on)
                    if sli["ttft_ms"] is None:
                        sli["ttft_ms"] = (
                            time.perf_counter() - sli["t0"]
                        ) * 1e3
                    sli["tokens"] += 1
                if lps is not None:
                    # the loop appends to the sink BEFORE invoking the hook
                    line["lp"] = lps[-1]
                if tops is not None:
                    line["top"] = list(tops[-1])
            await resp.write(jsonlib.dumps(line).encode() + b"\n")

        try:
            if pin_len:
                await c.pin_prefix(ids[:pin_len])
            out = await c.generate_ids(
                ids, max_new_tokens=max_new, eos_token_id=eos, seed=seed,
                sampling=sampling, on_token=on_token, logprob_sink=lps,
                top_n=top_n, top_sink=tops,
            )
            done = {"done": True, "ids": out}
            if lps is not None:
                done["logprobs"] = lps
            if tops is not None:
                done["top_logprobs"] = [list(t) for t in tops]
            if ignored_keys:
                done["ignored_sampling_keys"] = list(ignored_keys)
            await resp.write(jsonlib.dumps(done).encode() + b"\n")
        except Exception as e:
            # the 200 header is already gone — surface the failure as a
            # terminal line instead of a status code, and mark the SLI
            # record so a broken stream burns the error budget instead
            # of polluting the latency percentiles as a "success".
            # Connection-class failures are the CLIENT hanging up, not a
            # server fault — they must not burn availability
            if sli is not None and not isinstance(
                e, (ConnectionResetError, OSError, aiohttp.ClientError)
            ):
                sli["error"] = True
            try:
                await resp.write(
                    jsonlib.dumps({"error": f"{type(e).__name__}: {e}"[:300]}).encode()
                    + b"\n"
                )
            except Exception:
                pass
        try:
            await resp.write_eof()
        except Exception:
            pass  # client disconnected mid-stream: close quietly
        return resp

    async def _run_speculative_lanes(
        self, ids, max_new: int, eos, seed: int, sampling, emit=None,
        pin_len: int = 0, want_lp: bool = False, top_n: int = 0,
        lp_sink=None, top_sink=None,
    ):
        """Drive one /generate request through the batched executor's lane
        speculation (executor.spec_open/spec_step/spec_close). Returns
        (ids, drafted, accepted) or None when the fast path is unavailable
        (no lane, prompt over the spec-capped budget, or a failure) — the
        caller falls back to the regular loop. `emit` (async, called with
        each accepted run as it lands) powers the streaming flavor.
        `pin_len` composes speculation with prefix caching: the node pins
        the prefix once (the regular loop's shared pin) and the spec
        session forks it instead of re-prefilling. `want_lp`/`top_n`
        (greedy only) fill `lp_sink`/`top_sink` with the TARGET model's
        per-token logprob trail from the verify chunks."""
        from inferd_tpu.runtime.batch_executor import CapacityError
        from inferd_tpu.runtime.spec_serving import SpecForkMiss

        ex = self.executor
        if len(ids) + max_new > ex.cap:
            # the regular loop surfaces the overflow with the proper
            # 409/KV-overflow contract; the fast path just declines
            return None
        parent = prefix_logits = None
        if pin_len:
            c = await self._get_generate_client()
            try:
                await c.pin_prefix(ids[:pin_len])
            except Exception:
                log.exception("prefix pin failed; regular loop serves it")
                return None
            ent = c.pinned_parent(ids[:pin_len])
            if ent is None:
                return None
            parent, pin_logits = ent
            if pin_len == len(ids):
                prefix_logits = pin_logits
        want = want_lp or top_n > 0
        sid = "spec-" + uuid.uuid4().hex

        def record(lp, top):
            if lp_sink is not None:
                lp_sink.append(float(lp))
            if top_sink is not None and top is not None:
                ti, tls = top
                top_sink.append((ti[:top_n], tls[:top_n]))

        try:
            first, first_lp = await self.scheduler.run(
                ex.spec_open, sid, ids, sampling, seed, parent, pin_len,
                prefix_logits, want,
            )
        except (CapacityError, BufferError, SpecForkMiss):
            self.metrics.inc("generate.speculative_fallback")
            return None
        except Exception:
            log.exception("lane spec open failed; falling back to the loop")
            self.metrics.inc("generate.speculative_fallback")
            return None
        out = [int(first)]
        if want and first_lp is not None:
            record(first_lp[0], (first_lp[1], first_lp[2]))
        drafted = accepted = 0
        k = ex.spec_k
        try:
            if emit is not None:
                await emit(out[:])
            while len(out) < max_new and (eos is None or out[-1] != eos):
                res = await self.scheduler.run(
                    ex.spec_step, sid, out[-1],
                    out[-2] if len(out) > 1 else 0,
                )
                if res is None:
                    # inside the verify-chunk headroom: finish with plain
                    # batched decode steps (same distribution/greedy stream)
                    tok, tail_lp = await self.scheduler.run(
                        ex.spec_tail_step, sid, out[-1]
                    )
                    out.append(int(tok))
                    if want and tail_lp is not None:
                        record(tail_lp[0], (tail_lp[1], tail_lp[2]))
                    if emit is not None:
                        await emit(out[-1:])
                    continue
                if want:
                    toks, n, lps, tops = res
                else:
                    toks, n = res
                    lps = tops = None
                drafted += k
                accepted += max(0, n - 1)
                run = []
                for j, t in enumerate(toks):
                    out.append(int(t))
                    run.append(int(t))
                    if want:
                        record(lps[j], tops[j])
                    if (eos is not None and t == eos) or len(out) >= max_new:
                        break
                if emit is not None and run:
                    await emit(run)
        finally:
            # OFF the event loop: spec_close takes the executor's step
            # lock, which a concurrent round can hold for a whole device
            # dispatch — blocking here would freeze HTTP + gossip for that
            # long. shield() keeps the close running to completion even if
            # this handler task is being cancelled (client disconnect).
            try:
                await asyncio.shield(
                    asyncio.get_running_loop().run_in_executor(
                        None, ex.spec_close, sid
                    )
                )
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("spec_close failed")
        self.metrics.inc("spec.proposed", drafted)
        self.metrics.inc("spec.accepted", accepted)
        self.metrics.inc("generate.speculative")
        if parent is not None:
            self.metrics.inc("generate.speculative_pinned")
        return out, drafted, accepted

    async def _generate_speculative_lanes(
        self, ids, max_new: int, eos, seed: int, sampling, ignored_keys=(),
        pin_len: int = 0, want_lp: bool = False, top_n: int = 0,
        sli: Optional[Dict[str, Any]] = None,
    ) -> Optional[web.Response]:
        """Non-streamed lane-speculative /generate; None = fall back."""
        lps = [] if want_lp else None
        tops = [] if top_n else None
        try:
            res = await self._run_speculative_lanes(
                ids, max_new, eos, seed, sampling, pin_len=pin_len,
                want_lp=want_lp, top_n=top_n, lp_sink=lps, top_sink=tops,
            )
        except Exception:
            log.exception("lane speculative generate failed; falling back")
            self.metrics.inc("generate.speculative_fallback")
            return None
        if res is None:
            return None
        out, drafted, accepted = res
        if sli is not None:
            sli["tokens"] = len(out)
        rate = accepted / max(drafted, 1)
        payload = {
            "ids": out,
            "session_tokens": len(out),
            "speculative": True,
            "draft_acceptance": rate,
            "spec_accept_rate": rate,
        }
        if lps is not None:
            payload["logprobs"] = lps[: len(out)]
        if tops is not None:
            payload["top_logprobs"] = [list(t) for t in tops[: len(out)]]
        if ignored_keys:
            payload["ignored_sampling_keys"] = ignored_keys
        return web.Response(body=wire.pack(payload))

    async def _stream_spec_common(
        self, request, ids, max_new: int, eos, seed: int, sampling,
        ignored_keys, produce, pin_len: int = 0,
        sli: Optional[Dict[str, Any]] = None,
    ) -> web.StreamResponse:
        """ONE scaffold for both streamed speculative flavors (lane/mesh
        rounds and the solo engine): `produce(emit)` runs the speculative
        generation, calling `await emit(run)` with each accepted run, and
        returns (out, drafted, accepted) — or None for a clean DECLINE
        (nothing emitted), or raises for a mid-flight failure.

        Contract handling lives here exactly once: a decline before any
        byte falls back to the regular streaming loop in-place; a
        mid-flight failure emits {"restart": true} and re-runs on the
        regular loop (streamed tokens are void, per the /generate
        docstring); a CLIENT DISCONNECT mid-stream (emit's write raises)
        aborts quietly — no restart, no wasted re-generation."""
        import json as jsonlib

        resp = web.StreamResponse(
            headers={"Content-Type": "application/x-ndjson"}
        )
        resp.enable_chunked_encoding()
        state = {"prepared": False}

        async def _write(obj) -> None:
            if not state["prepared"]:
                await resp.prepare(request)
                state["prepared"] = True
            await resp.write(jsonlib.dumps(obj).encode() + b"\n")

        async def emit(run):
            try:
                for t in run:
                    await _write({"t": int(t)})
                    if sli is not None:
                        if sli["ttft_ms"] is None:
                            sli["ttft_ms"] = (
                                time.perf_counter() - sli["t0"]
                            ) * 1e3
                        sli["tokens"] += 1
            except (ConnectionResetError, OSError, aiohttp.ClientError) as e:
                raise _ClientGone() from e

        try:
            try:
                res = await produce(emit)
            except _ClientGone:
                return resp  # client hung up: no restart, no re-run
            except Exception:
                log.exception("speculative stream failed")
                self.metrics.inc("generate.speculative_fallback")
                res = None
            if res is None and not state["prepared"]:
                # declined before any byte went out: the regular streaming
                # loop serves the request instead (keeping its prefix pin)
                c = await self._get_generate_client()
                return await self._generate_streaming(
                    request, c, ids, max_new, eos, seed, sampling, pin_len,
                    False, ignored_keys, 0, sli=sli,
                )
            if res is not None:
                out, drafted, accepted = res
                rate = accepted / max(drafted, 1)
                done = {
                    "done": True, "ids": out, "speculative": True,
                    "draft_acceptance": rate, "spec_accept_rate": rate,
                }
            else:
                # mid-flight failure: void the streamed tokens and re-run
                # deterministically on the regular loop (the same contract
                # the non-spec streaming path honors on a node failure)
                await _write({"restart": True})
                if sli is not None:
                    sli["tokens"] = 0
                    sli["ttft_ms"] = None

                async def on_token(tok):
                    if tok is None:
                        if sli is not None:
                            sli["tokens"] = 0
                            sli["ttft_ms"] = None
                        await _write({"restart": True})
                        return
                    await _write({"t": int(tok)})
                    if sli is not None:
                        if sli["ttft_ms"] is None:
                            sli["ttft_ms"] = (
                                time.perf_counter() - sli["t0"]
                            ) * 1e3
                        sli["tokens"] += 1

                c = await self._get_generate_client()
                out = await c.generate_ids(
                    ids, max_new_tokens=max_new, eos_token_id=eos,
                    seed=seed, sampling=sampling, on_token=on_token,
                )
                done = {"done": True, "ids": out}
            if ignored_keys:
                done["ignored_sampling_keys"] = list(ignored_keys)
            await _write(done)
        except Exception as e:
            # broken stream burns, never "succeeds" — unless it's the
            # CLIENT disconnecting (connection-class errors), which is
            # no server fault and must not burn availability
            if sli is not None and not isinstance(
                e, (_ClientGone, ConnectionResetError, OSError,
                    aiohttp.ClientError)
            ):
                sli["error"] = True
            try:
                await _write({"error": f"{type(e).__name__}: {e}"[:300]})
            except Exception:
                pass
        try:
            await resp.write_eof()
        except Exception:
            pass
        return resp

    async def _generate_streaming_solo_spec(
        self, request, ids, max_new: int, eos, seed: int, sampling,
        ignored_keys=(), sli: Optional[Dict[str, Any]] = None,
    ) -> web.StreamResponse:
        """Streamed SOLO-engine speculative /generate (stage-executor
        nodes): the engine's on_tokens hook posts each accepted run from
        the worker thread onto the event loop, which streams it out. The
        decline/restart/disconnect contracts live in _stream_spec_common."""
        key, sampling_n = self._spec_key(sampling)
        loop = asyncio.get_running_loop()

        async def produce(emit):
            async with self._spec_lock:
                eng = await self._ensure_spec_engine_locked(key, sampling_n)
                if eng is None:
                    return None  # decline: regular streaming serves it
                q: asyncio.Queue = asyncio.Queue()

                def on_tokens(run):
                    loop.call_soon_threadsafe(q.put_nowait, list(run))

                gen = asyncio.ensure_future(self.scheduler.run(
                    lambda: eng.generate_with_stats(
                        ids, max_new, eos_token_id=eos, seed=seed,
                        on_tokens=on_tokens,
                    )
                ))
                try:
                    while True:
                        getter = asyncio.ensure_future(q.get())
                        done_set, _ = await asyncio.wait(
                            {getter, gen},
                            return_when=asyncio.FIRST_COMPLETED,
                        )
                        if getter in done_set:
                            run = getter.result()
                        else:
                            getter.cancel()
                            if q.empty():
                                break
                            run = q.get_nowait()
                        await emit(run)
                    out, rate, drafted, accepted = await gen
                except _ClientGone:
                    # the engine thread is uncancellable — let it finish
                    # quietly (per-call caches, no shared state) and keep
                    # its eventual exception from logging as unretrieved
                    gen.add_done_callback(
                        lambda f: f.cancelled() or f.exception()
                    )
                    raise
                except Exception:
                    # deterministic engine failure: demote THIS config like
                    # the non-streamed path (we hold _spec_lock) so every
                    # later matching request doesn't re-fail + re-log
                    self._spec_engines[key] = False
                    raise
                self.metrics.inc("spec.proposed", drafted)
                self.metrics.inc("spec.accepted", accepted)
                self.metrics.inc("generate.speculative")
                return out, drafted, accepted

        return await self._stream_spec_common(
            request, ids, max_new, eos, seed, sampling, ignored_keys, produce,
            sli=sli,
        )

    async def _generate_streaming_lanes(
        self, request, ids, max_new: int, eos, seed: int, sampling,
        ignored_keys=(), pin_len: int = 0,
        sli: Optional[Dict[str, Any]] = None,
    ) -> web.StreamResponse:
        """Streamed lane/slot-speculative /generate (batched and mesh
        executors): each ACCEPTED RUN is emitted the moment its round
        lands. The decline/restart/disconnect contracts live in
        _stream_spec_common."""

        async def produce(emit):
            return await self._run_speculative_lanes(
                ids, max_new, eos, seed, sampling, emit=emit,
                pin_len=pin_len,
            )

        return await self._stream_spec_common(
            request, ids, max_new, eos, seed, sampling, ignored_keys, produce,
            pin_len=pin_len, sli=sli,
        )

    async def handle_end_session(self, request: web.Request) -> web.Response:
        """Drop a session's KV cache here and on downstream stages."""
        try:
            env = wire.unpack(await request.read())
        except Exception as e:
            return self._error_response(400, f"bad end_session: {e}")
        return await self._end_session(env)

    async def _end_session(self, env: Dict[str, Any]) -> web.Response:
        """handle_end_session past the socket (see _fork_session)."""
        try:
            session_id = env["session_id"]
        except Exception as e:
            return self._error_response(400, f"bad end_session: {e}")
        if (
            env.get("relay", True)
            and not env.get("rescued")
            and not self._holds_session(session_id)
        ):
            # the session's KV for THIS stage lives on another replica (the
            # client ended it via a failed-over entry): forward the end
            # there so the KV is freed now, not at the idle-TTL sweep.
            # One bounce max ("rescued"), best effort.
            holder = self._gossip_session_holder(
                session_id, self.info.stage, exclude={self.info.node_id}
            )
            if holder is not None:
                value = self.dht.get_stage(self.info.stage).get(holder)
                if value is not None:
                    try:
                        assert self._http is not None
                        host, port = node_addr(value)
                        async with self._http.post(
                            f"http://{host}:{port}{END_SESSION_PATH}",
                            data=wire.pack({**env, "rescued": True}),
                        ) as r:
                            body = await r.read()
                        return web.Response(status=r.status, body=body)
                    except Exception:
                        pass  # holder unreachable: TTL sweep collects it
        self.executor.end_session(session_id)
        self._record_moved()  # stop advertising the session's KV
        if self.replicator is not None:
            # EXPLICIT end: free the session's standby shadow now (fire-
            # and-forget) instead of letting a finished 8k-ctx session's
            # KV copy sit in standby RAM, advertised, for the whole TTL.
            # Only here — mere residency loss (LRU eviction, handoff)
            # must KEEP the shadow, it may be the stream's only copy.
            standby = self.replicator.pop_standby(session_id)
            if standby is not None:
                asyncio.create_task(
                    self._send_standby_drop(session_id, standby)
                )
        stage = int(env.get("stage", self.info.stage))
        if not env.get("relay", True):
            return web.Response(body=wire.pack({"ok": True}))
        if stage + 1 < self.info.num_stages:
            try:
                # follow the session-affinity route so the replica actually
                # holding the KV cache is the one that drops it
                node_id, value = await self._pick_next(session_id, stage + 1)
                host, port = node_addr(value)
                assert self._http is not None
                await self._http.post(
                    f"http://{host}:{port}{END_SESSION_PATH}",
                    data=wire.pack({"session_id": session_id, "stage": stage + 1}),
                )
            except Exception:
                pass  # best effort: the periodic sweep collects orphans
        self._session_next.pop((session_id, stage + 1), None)
        return web.Response(body=wire.pack({"ok": True}))

    async def handle_health(self, request: web.Request) -> web.Response:
        """GET /health — identity plus the SLO verdict: `status` is
        ok|degraded|failing with the firing rules attached, so a load
        balancer (or a human with curl) gets an EVALUATED answer instead
        of four raw numbers to interpret."""
        body = {
            "node": self.info.name,
            "node_id": self.info.node_id,
            "stage": self.info.stage,
            "num_stages": self.info.num_stages,
            "inflight": self.scheduler.inflight,
            "sessions": len(getattr(self.executor, "sessions", [])),
        }
        # the verdict survives INFERD_EVENTS=0: metric-only rules keep
        # evaluating (event rules skip — _health_state passes events=None),
        # so the kill switch sheds journal overhead without blinding the
        # SLO engine; only GOSSIP stays events-gated (announce), keeping
        # the wire byte-identical per the kill-switch contract
        state = self._health_state()
        v = state["verdict"]
        body.update(
            status=v["status"],
            firing=v["firing"],
            rules={"evaluated": v["evaluated"], "skipped": v["skipped"]},
            **{
                k: state["gossip"][k]
                for k in ("hbm", "compiles") if k in state["gossip"]
            },
        )
        wq = self._windowed_gossip()
        if wq:
            # the trailing-window quantiles the verdict was judged on
            # (and the numbers this node gossips) — NOT all-time
            body["window"] = wq
        if self._outlier_info is not None:
            body["outlier"] = {
                k: round(v, 3) if isinstance(v, float) else v
                for k, v in self._outlier_info.items()
            }
        if eventslib.enabled():
            body["events"] = self.journal.stats()["recorded"]
        return web.json_response(body)

    def _update_gauges(self) -> None:
        """Refresh point-in-time gauges at scrape time (inflight requests,
        live sessions, KV bytes, worker-queue depth, span-ring state) —
        levels, not counters, so they are set rather than incremented."""
        m = self.metrics
        m.set_gauge("inflight", self.scheduler.inflight)
        store = getattr(self.executor, "sessions", None)
        try:
            m.set_gauge("sessions", len(store) if store is not None else 0)
        except TypeError:
            pass
        kvb = getattr(store, "kv_bytes", None)
        if callable(kvb):
            try:
                m.set_gauge("kv.bytes", kvb())
            except Exception:
                log.debug("kv_bytes gauge failed", exc_info=True)
        q = getattr(getattr(self.scheduler, "_pool", None), "_work_queue", None)
        if q is not None:
            try:
                m.set_gauge("queue.depth", q.qsize())
            except Exception:
                pass
        cb = self._cobatch_mean()
        if cb is not None:
            # mean sessions per co-batched device step (level, not a
            # counter — the window.cobatch histogram carries the shape)
            m.set_gauge("window.mean_cobatch", cb)
        ts = self.tracer.stats()
        m.set_gauge("trace.spans", ts["recorded"])
        m.set_gauge("trace.dropped", ts["dropped"])
        m.set_gauge("trace.buffered", ts["buffered"])
        # cumulative span-recording cost: perf/gate.check_span_overhead
        # warns when this exceeds 1% of cumulative stage.compute_ms
        m.set_gauge("trace.overhead_ms", ts["overhead_ms"])
        if eventslib.enabled():
            # device telemetry (HBM + KV occupancy; graceful CPU no-op)
            # and journal health — all gated on the events kill switch so
            # a disabled node's /metrics stays byte-identical to pre-PR
            devtellib.refresh_gauges(m, self.executor)
            es = self.journal.stats()
            m.set_gauge("events.count", es["recorded"])
            m.set_gauge("events.dropped", es["dropped"])
            m.set_gauge("events.buffered", es["buffered"])
            # budgeted by perf.gate alongside trace.overhead_ms (<=1% of
            # cumulative stage compute keeps always-on defensible)
            m.set_gauge("events.overhead_ms", es["overhead_ms"])
            # telemetry-plane costs ride the same budget: tsdb sampling
            # and canary bookkeeping must never silently eat the decode
            # wins (perf/gate.check_span_overhead)
            m.set_gauge("tsdb.overhead_ms", round(self.tsdb.overhead_ms, 3))
            # overload plane: drain state + the hedge budget's realized
            # extra-load fraction (the <=5% guarantee, observable) +
            # node-side retry-budget level (a dry bucket during an
            # incident = the containment working, not a failure)
            m.set_gauge("draining", 1.0 if self._draining else 0.0)
            m.set_gauge(
                "hedge.extra_frac", round(self.hedge_budget.extra_frac(), 4)
            )
            m.set_gauge(
                "retry.budget_tokens", round(self.retry_budget.tokens(), 2)
            )
            m.set_gauge(
                "replica.outlier", 1.0 if self._outlier_info else 0.0
            )
            if self.standby is not None:
                # crash-tolerance plane: shadow sessions held FOR peers
                # and their host-RAM cost (repl.lag_tokens — the primary-
                # side bounded-RPO gauge — refreshes in the repl tick).
                # Flag-gated like every repl.* series: a disabled node's
                # /metrics stays byte-identical to a build without them
                m.set_gauge(
                    "repl.standby_sessions", float(len(self.standby))
                )
                m.set_gauge(
                    "repl.standby_bytes", float(self.standby.bytes_held())
                )
            # trailing-window prefix-cache hit rate as a live gauge (the
            # gossiped `cachehit` field's /metrics face; rule input e.g.
            # `kv.cachehit > 0.1` for shared-prefix fleets). Only set
            # when the window saw prompt traffic — scrape-to-scrape the
            # last observed ratio may linger, but the gossip/fleet paths
            # use the windowed series directly
            ch = self._cachehit_frac()
            if ch is not None:
                m.set_gauge("kv.cachehit", ch)
            # short-window burn rates as live gauges (the SLO rules gate
            # on both windows; these feed dashboards/scrapes)
            for name, val in healthlib.burn_gauges(
                [self.tsdb.history()]
            ).items():
                m.set_gauge(name, val)
            if self.canary is not None:
                m.set_gauge(
                    "canary.overhead_ms", round(self.canary.overhead_ms, 3)
                )
            lw = lockwatch.stats()
            if lw["checks"]:
                # lock-order sanitizer cost, same perf.gate 1% budget;
                # only exported while locks are actually watched so a
                # non-instrumented node's /metrics stays byte-identical
                m.set_gauge(
                    "lockwatch.overhead_ms", round(lw["overhead_ms"], 3)
                )

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """GET /metrics — Prometheus text exposition of the node registry
        (counters, the gauges refreshed above, full histogram buckets)."""
        self._update_gauges()
        text = obs_export.prometheus_text(
            self.metrics, labels={"node": self.info.node_id}
        )
        return web.Response(
            body=text.encode(),
            headers={"Content-Type": obs_export.CONTENT_TYPE},
        )

    async def handle_metrics_history(self, request: web.Request) -> web.Response:
        """GET /metrics/history — the windowed tsdb rings as ONE JSON
        object (obs.tsdb schema: per-level counter/gauge rings + mergeable
        histogram bucket deltas). The pull surface of the fleet SLI
        pipeline: tools/collector --history fetches these per node and
        merges bucket deltas into fleet percentiles (obs.fleet) — never
        averages of averages."""
        self._update_gauges()
        self.tsdb.sample()
        return web.json_response(self.tsdb.history())

    async def handle_spans(self, request: web.Request) -> web.Response:
        """GET /spans — the live span ring as newline-delimited JSON
        (non-draining; the merge CLI's ad-hoc input for a running node)."""
        # off the event loop: both rings full are most of a second of
        # json.dumps, and every session's token loop runs on this loop
        body = await asyncio.get_running_loop().run_in_executor(
            None,
            lambda: ("\n".join(self.tracer.jsonl_lines()) + "\n").encode(),
        )
        return web.Response(
            body=body, headers={"Content-Type": "application/x-ndjson"},
        )

    async def handle_events(self, request: web.Request) -> web.Response:
        """GET /events — the live event journal as newline-delimited JSON
        (non-draining; the postmortem CLI's ad-hoc input for a running
        node, mirroring /spans)."""
        body = "\n".join(self.journal.jsonl_lines()) + "\n"
        return web.Response(
            body=body.encode(),
            headers={"Content-Type": "application/x-ndjson"},
        )

    async def handle_stats(self, request: web.Request) -> web.Response:
        self._update_gauges()
        snap = self.metrics.snapshot()
        # per-device bytes ride along (empty where the backend reports no
        # memory_stats): on a mesh it shows every chip holding its share
        snap["device"] = dict(
            self.device, memory=devtellib.device_memory_stats()
        )
        snap["wire_codec"] = self.wire_codec
        # what a client has to know of the model to drive it: a model
        # generated by blocks is asked for blocks (client/base.py)
        snap["model"] = {"name": self.info.model_name, "block_length": self._block_length()}
        if self.cfg.hc_mult:  # a residual stream of this many hidden states (absent: the plain add)
            snap["model"]["stream_width"] = self.cfg.hc_mult
        if self.cfg.single_sublayer:  # layers that are one sublayer each, counted by kind
            snap["model"]["sublayers"] = self.cfg.sublayer_counts
        if self.compile_cache is not None:
            snap["compile_cache"] = self.compile_cache.as_dict()
        snap["trace"] = self.tracer.stats()
        proposed = snap["counters"].get("spec.proposed", 0)
        if proposed:
            # cumulative production acceptance rate — the speculative
            # engine's whole value proposition, observable in the field
            snap["spec"] = {
                "proposed": proposed,
                "accepted": snap["counters"].get("spec.accepted", 0),
                "accept_rate": snap["counters"].get("spec.accepted", 0) / proposed,
            }
        snap["dht"] = {str(k): v for k, v in self.dht.get_all(self.info.num_stages).items()}
        # the load tick's ledger: records built against load changes seen
        # (a hop is two ticks and builds nothing: _refresh_record)
        snap["announce"] = {
            "builds": self._record_builds, "ticks": self.scheduler.ticks,
        }
        # overload-containment state: drain flag + both budgets' ledgers
        # (the bench's hedge-extra-load and retry-amplification evidence)
        snap["overload"] = {
            "draining": self._draining,
            "retry_budget": self.retry_budget.stats(),
            "hedge": self.hedge_budget.stats(),
        }
        if self.replicator is not None and self.standby is not None:
            # crash-tolerance ledgers (absent with --standby-repl off):
            # the failover bench reads promotions/frontiers from here
            snap["repl"] = {
                "sessions_tracked": len(self.replicator.state),
                "shipped_bytes": self.replicator.shipped_bytes,
                "ship_errors": self.replicator.ship_errors,
                "standby_sessions": len(self.standby),
                "standby_bytes": self.standby.bytes_held(),
            }
        stats_fn = getattr(self.executor, "stats", None)
        if callable(stats_fn):
            snap["executor"] = stats_fn()
        return web.json_response(snap)

    async def handle_profile(self, request: web.Request) -> web.Response:
        """POST {"action": "start"|"stop"|"window", ...} — on-demand
        jax.profiler trace (TensorBoard-loadable; SURVEY §5 gap).

        "window" is the fleet-coordinated form (tools/collector
        --capture): {"action": "window", "seconds": S, "capture_id": ID}
        starts a BOUNDED capture that stops itself after S seconds (S
        clamped to 60), tagged with the fleet-wide capture_id. The
        capture window is recorded as a `capture` span as it STARTS (so
        the clock-skew-corrected span merge lines wire spans up with the
        on-device trace; `capture_close` times the write), journaled,
        and the obs artifacts flush when it closes so the collector can
        assemble the bundle immediately.

        Opt-in only (--enable-profiling): an open profiler endpoint lets any
        peer degrade the node and fill its disk with traces (ADVICE r1)."""
        if not self.enable_profiling:
            return self._error_response(
                403, "profiling disabled (start the node with --enable-profiling)"
            )
        try:
            env = wire.unpack(await request.read())
            action = env["action"]
        except Exception as e:
            return self._error_response(400, f"bad profile request: {e}")
        loop = asyncio.get_running_loop()
        try:
            # off the event loop: start/stop do blocking work (first jax
            # import, mkdir, trace finalization) that would otherwise stall
            # the gossip heartbeat and get this node declared dead
            if action == "start":
                d = await loop.run_in_executor(
                    None, self.profiler.start, env.get("name") or env.get("dir")
                )
            elif action == "stop":
                d = await loop.run_in_executor(None, self.profiler.stop)
            elif action == "window":
                return await self._profile_window(env, loop)
            else:
                return self._error_response(400, f"unknown action {action!r}")
        except ValueError as e:
            return self._error_response(400, str(e))
        except RuntimeError as e:
            return self._error_response(409, str(e))
        return web.Response(body=wire.pack({"ok": True, "dir": d}))

    async def _profile_window(self, env, loop) -> web.Response:
        """One bounded, capture_id-tagged jax.profiler window."""
        try:
            seconds = min(max(float(env.get("seconds", 3.0)), 0.1), 60.0)
        except (TypeError, ValueError):
            return self._error_response(400, "bad seconds")
        capture_id = str(
            env.get("capture_id") or time.strftime("%Y%m%d-%H%M%S")
        )
        label = os.path.join(
            capture_id, self.info.node_id.replace(":", "_")
        )
        d = await loop.run_in_executor(None, self.profiler.start, label)
        # the capture span: t0 is the instant the trace's own anchor event
        # ends (Profiler.start), t1 the planned end. Its [t0, t1] brackets
        # the on-device trace, so after the skew-corrected merge the wire
        # spans of every node line up against every node's device
        # timeline. Recorded NOW: writing a large trace takes seconds, and
        # a reader must not be left without its clock anchor meanwhile
        t_start = self.profiler.started_at
        self.tracer.record_span(
            "capture", "capture", t_start, t_start + seconds,
            attrs={"capture_id": capture_id, "dir": d}, keep=True,
        )
        if eventslib.enabled():
            self.metrics.inc("prof.captures")
        self.journal.emit(
            "profile.capture", capture_id=capture_id,
            seconds=round(seconds, 3), dir=d,
        )

        async def _close() -> None:
            await asyncio.sleep(max(0.0, t_start + seconds - tracelib.now()))
            t_stop = tracelib.now()
            try:
                await loop.run_in_executor(None, self.profiler.stop)
            except Exception:
                log.exception("capture %s stop failed", capture_id)
            # stop called -> trace written: what closing the capture cost
            self.tracer.record_span(
                "capture_close", "capture", t_stop, tracelib.now(),
                attrs={"capture_id": capture_id}, keep=True,
            )
            self.journal.emit(
                "profile.capture_done", capture_id=capture_id, dir=d
            )
            self._flush_obs()

        self._capture_task = asyncio.create_task(_close())
        return web.Response(body=wire.pack({
            "ok": True, "dir": d, "capture_id": capture_id,
            "seconds": seconds,
        }))

    def _error_response(
        self, status: int, message: str, code: Optional[str] = None,
        retry_after: Optional[float] = None,
        resume_from: Optional[int] = None,
    ) -> web.Response:
        """Wire-packed error. `code` is machine-readable for clients:
        "session_state" (KV gone/out-of-order — a fresh session fixes it),
        "overflow" (KV budget exceeded — deterministic), "wrong_stage"
        (stale chain topology — deterministic), "deadline" (end-to-end
        budget spent — deterministic for THIS request), "busy"/"draining"
        (admission shed — transient; `retry_after` seconds, carried both
        in the body and as the standard Retry-After header, says when to
        come back). `resume_from` rides a session_state 409 when a
        standby holds the session's replicated KV prefix up to that
        position: a resume-aware client re-sends only the tail instead
        of restarting (old clients ignore the key and restart — today's
        path, by design)."""
        self.metrics.inc("errors")
        body: Dict[str, Any] = {"error": message}
        if code:
            body["code"] = code
        if resume_from is not None:
            body["resume_from"] = int(resume_from)
        headers = None
        if retry_after is not None:
            body["retry_after"] = retry_after
            # the HTTP header must be integer delta-seconds (RFC 7231);
            # the sub-second precision rides the wire body instead
            headers = {"Retry-After": str(max(0, math.ceil(retry_after)))}
        return web.Response(
            status=status, body=wire.pack(body), headers=headers
        )

    async def crash(self) -> None:
        """Fault-injection: die like a killed process — no DHT withdrawal
        (no tombstone gossip), sockets just close. Peers must detect the
        death via record-TTL expiry, exactly as with a real hard crash.
        Tests use this; production shutdown is stop()."""
        if self._sweep_task:
            self._sweep_task.cancel()
        if self._repl_task:
            self._repl_task.cancel()
        await self.balancer.stop()
        self.dht.kill()
        if self._http:
            await self._http.close()
        if self.chaos is not None:
            self.chaos.cancel_stalls()  # see stop(): unblock the cleanup
        if self._runner:
            try:
                # no graceful drain: cleanup() would wait for in-flight
                # handlers to answer — a real SIGKILL doesn't. Private attr
                # (no public setter post-construction); the constructor's
                # shutdown_timeout=5.0 bounds the drain even if a future
                # aiohttp renames it and this becomes a no-op.
                self._runner._shutdown_timeout = 0.0
            except Exception:
                pass
            await self._runner.cleanup()
        self.scheduler.shutdown()
        self._stopped.set()

    # ------------------------------------------------------------ migration

    async def change_stage(self, target: int) -> None:
        """Live migration to another stage: load its checkpoint (shared
        parts store), swap the executor, re-announce. In-flight requests
        finish on the old executor; new requests see the new stage."""
        if target == self.info.stage:
            return
        t0 = time.perf_counter()
        loop = asyncio.get_running_loop()
        new_executor = await loop.run_in_executor(None, self._load_executor, target)
        # eager warmup: pay the new stage's first jit compile NOW, off the
        # serving path, and time it — reassign -> ready-to-serve is the
        # latency half of BASELINE config 4 ("re-shards layer blocks
        # live"), exported as reshard.ms_to_serving. With a
        # persistent compilation cache (always on in run_node) the warm path
        # skips XLA re-compiles and this interval collapses to checkpoint
        # load + cache hits.
        await loop.run_in_executor(
            None, _warmup_executor, new_executor, self.journal
        )
        old_stage = self.info.stage
        old = self.executor
        self.executor = new_executor
        self._spec_engines.clear()  # built over the OLD executor's params
        self._spec_unsupported = False
        if self.standby is not None:
            # shadows and frontiers are STAGE-keyed: after the swap this
            # node can neither promote the old stage's shadows (wrong
            # layer slice — import would fail closed) nor extend its old
            # frontiers, and keeping them advertised under the NEW stage
            # map would misdirect peers' standby rescues — drop both
            self.standby.clear()
            self.replicator.state.clear()
        self.path_finder.planner = None  # planned from the OLD stage's view
        self.info.set_stage(target)
        self.tsdb.meta["stage"] = target  # fleet SLIs group by stage
        self.announce()
        self.metrics.inc("migrations")
        seconds = time.perf_counter() - t0
        # wider buckets than the hop histograms: a cold migration (nothing
        # in the compile cache yet) pays XLA recompiles and runs well past the
        # default 10 s cap — quantiles must not saturate to inf there
        self.metrics.observe(
            "reshard.ms_to_serving", seconds * 1e3,
            bounds_ms=[100, 250, 500, 1000, 2500, 5000, 10_000, 30_000,
                       60_000, 120_000, 300_000, 600_000],
        )
        self.journal.emit(
            "stage.migrate",
            **{"from": old_stage, "to": target,
               "ms_to_serving": round(seconds * 1e3, 1)},
        )
        self._health_cache = (0.0, None)  # stale stage in the cached verdict
        log.info(
            "node %s migrated to stage %d (ready to serve in %.2fs)",
            self.info.name, target, seconds,
        )
        # live handoff: ship the vacated executor's session KV to the old
        # stage's remaining replicas (off the critical path — the node is
        # already serving its new stage)
        await self._export_and_handoff(old, old_stage)
        del old
