"""Device/XLA telemetry: HBM gauges, KV occupancy, compile events.

The fleet's device state was completely uninstrumented: no node could
answer "how close is this replica to an HBM OOM?" or "did that migration
trigger a recompile storm?" without attaching a profiler. This module
closes the gap with three per-scrape surfaces, all flowing into the
existing /metrics exposition and the gossip record:

  * `hbm_summary` — aggregated `jax.local_devices()[*].memory_stats()`
    (bytes in use / limit / peak, and their fraction). TPU runtimes
    report these; CPU (and any backend without memory_stats) degrades to
    None and the gauges are simply absent — never a crash, never a fake
    zero;
  * `kv_occupancy` — fraction of the executor's lane-pool KV budget in
    use (filled positions / lanes x max_len), the serving-level memory
    signal that exists even where the runtime reports nothing;
  * `CompileWatch` — counts XLA compiles and times them, reusing the
    J001 retrace bookkeeping idiom from analysis/sanitizers.py: a
    wrapped jitted callable's `_cache_size()` delta across one call
    means THAT call traced+compiled, so the call's latency is the
    compile cost. Each detected compile emits paired `compile.begin`/
    `compile.end` journal events (elapsed ms on the end event), bumps a
    `compile.events` counter, and feeds a wide-bucket `compile.ms`
    histogram — recompile storms after a migration become a visible
    series instead of a mystery latency cliff.

jax is imported lazily inside functions: importing this module (or the
obs package) on a client machine must not claim a chip, and the journal/
health layers stay importable with no jax at all.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from inferd_tpu.obs import events as eventslib

#: jitted-callable attribute names CompileWatch knows how to wrap on the
#: serving executors: runtime/executor.Qwen3StageExecutor._run,
#: runtime/stage_batch's co-batched decode + per-lane prefill jits, and
#: the core.batch.BatchedEngine jits the --batch-lanes executor serves
#: through (reached via its `engine` sub-object — see
#: instrument_executor), as the --mesh executor's PipelinedEngine is.
_EXECUTOR_JIT_ATTRS = (
    "_run", "_decode_all", "_prefill_lane",
    "_decode_scan", "_decode_logits", "_prefill_lane_logits", "_fork_lane",
    # paged-KV (--paged-kv) dispatch surfaces
    "_decode_all_paged", "_prefill_lane_paged",
    "_decode_logits_paged", "_prefill_lane_logits_paged", "_copy_blocks",
    # the mesh engine's serving programs (parallel.infer.PipelinedEngine,
    # reached as executor.engine): jit products around the shard_map pass
    "_step_raw", "_step_raw_multi", "_fork_slot",
)


def program_name(fn: Any) -> str:
    """The name a profiler trace prints for a jitted callable's program
    (`jit_<function>`), through CompileWatch's wrapper."""
    return "jit_" + getattr(getattr(fn, "__wrapped__", fn), "__name__", "?")


_COMPILE_BOUNDS_MS = [10, 50, 100, 500, 1000, 5000, 10_000, 60_000, 120_000]


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device memory stats, one dict per local device that reports
    them ([] on CPU/unsupported backends — the graceful fallback)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return []
    out: List[Dict[str, Any]] = []
    for d in devices:
        ms_fn = getattr(d, "memory_stats", None)
        if not callable(ms_fn):
            continue
        try:
            ms = ms_fn()
        except Exception:
            continue
        if not isinstance(ms, dict) or "bytes_in_use" not in ms:
            continue
        out.append(
            {
                "device": str(d),
                "bytes_in_use": int(ms.get("bytes_in_use", 0)),
                "bytes_limit": int(ms.get("bytes_limit", 0)),
                "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
            }
        )
    return out


def hbm_summary() -> Optional[Dict[str, float]]:
    """Aggregate HBM state over the local devices, or None when no
    device reports memory stats (CPU fallback)."""
    per_dev = device_memory_stats()
    if not per_dev:
        return None
    in_use = sum(d["bytes_in_use"] for d in per_dev)
    limit = sum(d["bytes_limit"] for d in per_dev)
    peak = sum(d["peak_bytes_in_use"] for d in per_dev)
    return {
        "bytes_in_use": float(in_use),
        "bytes_limit": float(limit),
        "peak_bytes_in_use": float(peak),
        "frac": (in_use / limit) if limit > 0 else 0.0,
        "devices": float(len(per_dev)),
    }


def kv_occupancy(executor: Any) -> Optional[float]:
    """Fraction of the executor's lane-pool KV positions in use, or None
    when the executor has no lane pool. Prefers an executor-provided
    `kv_occupancy()`; falls back to the `lengths`/`max_len` host mirrors
    every lane-slotted executor keeps."""
    fn = getattr(executor, "kv_occupancy", None)
    if callable(fn):
        try:
            return float(fn())
        except Exception:
            return None
    lengths = getattr(executor, "lengths", None)
    max_len = getattr(executor, "max_len", None)
    if not isinstance(lengths, (list, tuple)) or not lengths or not max_len:
        return None
    try:
        return float(sum(int(n) for n in lengths)) / (len(lengths) * int(max_len))
    except (TypeError, ValueError):
        return None


def refresh_gauges(metrics: Any, executor: Any = None) -> None:
    """Refresh the device-telemetry gauges at scrape time (the node calls
    this from _update_gauges). Gated on the events kill switch so a
    disabled node's /metrics output stays byte-identical to a build
    without this subsystem."""
    if not eventslib.enabled():
        return
    h = hbm_summary()
    if h is not None:
        metrics.set_gauge("hbm.bytes_in_use", h["bytes_in_use"])
        metrics.set_gauge("hbm.bytes_limit", h["bytes_limit"])
        metrics.set_gauge("hbm.peak_bytes_in_use", h["peak_bytes_in_use"])
        metrics.set_gauge("hbm.frac", round(h["frac"], 6))
    if executor is not None:
        occ = kv_occupancy(executor)
        if occ is not None:
            metrics.set_gauge("kv.occupancy", round(occ, 6))
        for name, value in block_pool_gauges(executor).items():
            metrics.set_gauge(name, value)
        for name, value in block_pool_counters(executor).items():
            metrics.set_counter(name, value)
        gauges, counters = adapter_series(executor)
        for name, value in gauges.items():
            metrics.set_gauge(name, value)
        for name, value in counters.items():
            metrics.set_counter(name, value)


def _block_stats(executor: Any) -> Dict[str, Any]:
    """block_stats() from a paged executor, {} on dense/failed — the one
    guard shared by the gauge and counter exporters below."""
    fn = getattr(executor, "block_stats", None)
    if not callable(fn):
        return {}
    try:
        stats = fn()
    except Exception:
        return {}
    return stats if isinstance(stats, dict) else {}


def block_pool_gauges(executor: Any) -> Dict[str, float]:
    """Paged-KV block-pool gauges from an executor exposing
    `block_stats()` (runtime/stage_batch, runtime/batch_executor in
    --paged-kv mode): pool pressure (`kv.blocks_free`/`kv.blocks_used`),
    the dedupe the pool is earning (`kv.cow_shared` — blocks mapped by
    more than one holder), prefix-cache residency (`pins.resident`) and
    index size (`kv.prefix_entries`). Dense executors (no block_stats /
    returns None) contribute nothing — the gauges are absent, never fake
    zeros."""
    stats = _block_stats(executor)
    if not stats:
        return {}
    return {
        "kv.blocks_free": float(stats.get("blocks_free", 0)),
        "kv.blocks_used": float(stats.get("blocks_used", 0)),
        "kv.cow_shared": float(stats.get("cow_shared", 0)),
        "pins.resident": float(stats.get("pins_resident", 0)),
        "kv.prefix_entries": float(stats.get("prefix_entries", 0)),
    }


def block_pool_counters(executor: Any) -> Dict[str, float]:
    """Monotone block-pool counters mirrored into the registry at scrape
    time (Metrics.set_counter): the pool already counts them
    (core.cache.BlockPool.block_stats) but devtel silently dropped them
    until ISSUE 13 — so the fleet could see the pool's SIZE and not its
    EFFECTIVENESS. As registry counters they become windowed tsdb rates
    (`kv.prefix_hit_tokens` per second IS prefill-tokens-avoided per
    second), /metrics `_total` series, and fleet-SLI inputs (obs.fleet).
    `kv.prefill_tokens` (tokens prefill actually computed) rides along
    from the executor's own counter — the hit-rate denominator's other
    half."""
    stats = _block_stats(executor)
    if not stats:
        return {}
    out = {
        "kv.prefix_hit_tokens": float(stats.get("prefix_hit_tokens", 0)),
        "kv.prefix_evictions": float(stats.get("prefix_evictions", 0)),
        "kv.cow_splits": float(stats.get("cow_splits", 0)),
    }
    prefill = getattr(executor, "prefill_tokens", None)
    if isinstance(prefill, (int, float)):
        out["kv.prefill_tokens"] = float(prefill)
    return out


def adapter_series(executor: Any):
    """(gauges, counters) for a multi-tenant adapter registry
    (runtime/adapters.AdapterRegistry via the executor's `adapters`
    attribute): residency/pins/slots as levels, loads/evictions as
    monotone counters (windowed tsdb rates — `adapter.loads` per second
    IS the hot-load churn rate). Executors WITHOUT a registry contribute
    nothing: the `adapter.*` series are absent, never fake zeros — the
    --adapters kill-switch contract for /metrics."""
    reg = getattr(executor, "adapters", None)
    if reg is None:
        return {}, {}
    try:
        stats = reg.stats()
    except Exception:
        return {}, {}
    gauges = {
        "adapter.resident": float(stats.get("resident", 0)),
        "adapter.slots": float(stats.get("slots", 0)),
        "adapter.pinned": float(stats.get("pinned", 0)),
    }
    counters = {
        "adapter.loads": float(stats.get("loads", 0)),
        "adapter.evictions": float(stats.get("evictions", 0)),
    }
    return gauges, counters


class CompileWatch:
    """Detect and time XLA compiles on wrapped jitted callables.

    `watch(fn, name)` returns a call-compatible wrapper (donated args,
    kwargs, aux outputs all pass through untouched): each call reads the
    jit cache size before and after — the sanitizers.RetraceGuard
    `register()` bookkeeping — and a growth means this call paid a trace
    + compile, so its wall latency is attributed as the compile cost.
    Steady-state calls add two integer reads; the hot path stays clean.
    """

    def __init__(self, metrics: Any = None, journal: Any = None):
        self.metrics = metrics
        self.journal = journal
        self.compiles = 0

    def record(self, name: str, elapsed_ms: float, t0: Optional[float] = None):
        """One observed compile: paired journal events + counter +
        histogram. `t0` back-dates compile.begin to the compile's start
        (events are stamped at emit time otherwise)."""
        self.compiles += 1
        if self.journal is not None:
            self.journal.emit("compile.begin", ts=t0, name=name)
            self.journal.emit(
                "compile.end", name=name, elapsed_ms=round(elapsed_ms, 3)
            )
        if self.metrics is not None and eventslib.enabled():
            self.metrics.inc("compile.events")
            self.metrics.observe(
                "compile.ms", elapsed_ms, bounds_ms=_COMPILE_BOUNDS_MS
            )

    def watch(self, fn: Any, name: str) -> Any:
        cache_size = getattr(fn, "_cache_size", None)
        if not callable(cache_size):
            return fn  # not a jit product on this jax version: pass through

        def wrapped(*args, **kwargs):
            if not eventslib.enabled():
                return fn(*args, **kwargs)
            try:
                before = cache_size()
            except Exception:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            try:
                grew = cache_size() > before
            except Exception:
                grew = False
            if grew:
                dt_ms = (time.perf_counter() - t0) * 1e3
                from inferd_tpu.obs import trace as tracelib

                self.record(name, dt_ms, t0=tracelib.now() - dt_ms / 1e3)
            return out

        wrapped.__wrapped__ = fn
        # dedicated double-wrap sentinel: jax.jit products themselves
        # carry __wrapped__ (functools.wraps over the user fn), so THAT
        # attribute cannot distinguish "already watched" from "plain jit"
        wrapped._compile_watched = True
        return wrapped

    def instrument_executor(self, executor: Any, label: str = "") -> None:
        """Wrap the executor's known jitted attrs (the bucket-compile
        sites: a new prefill bucket length or a first decode step each
        shows up as one compile event). Executors that serve through an
        inner engine object (BatchedExecutor -> core.batch.BatchedEngine)
        get the engine's jits wrapped too — the actual device-dispatch
        surface on the --batch-lanes path."""
        targets = [(executor, label or type(executor).__name__)]
        engine = getattr(executor, "engine", None)
        if engine is not None:
            targets.append((engine, f"{targets[0][1]}.engine"))
        for obj, lbl in targets:
            for attr in _EXECUTOR_JIT_ATTRS:
                fn = getattr(obj, attr, None)
                if fn is None or getattr(fn, "_compile_watched", False):
                    continue
                wrapped = self.watch(fn, f"{lbl}.{attr}")
                if wrapped is not fn:
                    setattr(obj, attr, wrapped)
