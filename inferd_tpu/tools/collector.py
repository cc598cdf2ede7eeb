"""Swarm metrics collector: periodic CSV time series of per-stage state.

Capability parity with the reference's sim collector
(/root/reference/petals/test_rebalance.py:13-66: sample the DHT every
period, write min load / total capacity / tasks running / server count per
stage to `metrics_log.csv` for the notebook to plot) — as a standalone tool
usable against any live swarm, not only the in-process sim. Consumed by
inferd_tpu.tools.plot_metrics (the metrics.ipynb replacement).

With --history the collector ALSO polls every gossiped node's
GET /metrics/history (the windowed tsdb rings, obs.tsdb) and appends one
fleet SLI sample per period — fleet TTFT/TPOT/tok-per-s percentiles from
MERGED per-node bucket deltas (obs.fleet), never averages of averages —
as rolling NDJSON next to the CSV, the `obs fleet` CLI's input.

With --capture ID the collector instead triggers ONE fleet-coordinated
profiling capture: a simultaneous bounded jax.profiler window (POST
/profile {"action": "window"}) on every gossiped node, tagged with the
capture id, then merges the per-node spans with the clock-skew-corrected
span merge (obs.merge) into a Chrome-trace bundle + manifest so wire
spans line up with the on-device kernel slices (docs/OBSERVABILITY.md).

Usage:
  python -m inferd_tpu.tools.collector --bootstrap 10.0.0.2:7050 \
      --stages 3 --out metrics_log.csv --period 1 --history
  python -m inferd_tpu.tools.collector --bootstrap 10.0.0.2:7050 \
      --capture cap-2026-08-04 --capture-seconds 5
"""

from __future__ import annotations

import argparse
import asyncio
import csv
import json
import logging
import time
from typing import Any, Awaitable, Callable, Dict, IO, List, Optional

log = logging.getLogger(__name__)

SwarmMap = Dict[int, Dict[str, Dict[str, Any]]]

FIELDS = [
    "ts",
    "stage",
    "servers",
    "tasks_running",
    "total_cap",
    "min_load",
    "max_load",
    # legacy aliases (one release): same values as the explicit columns
    # below — PR 3 wrote the median replica's p50 under hop_p50_ms but
    # the WORST replica's p99 under hop_p99_ms, two different
    # aggregations behind one naming scheme
    "hop_p50_ms",
    "hop_p99_ms",
    # explicit aggregation semantics: median replica's p50 / worst
    # replica's p99
    "hop_p50_med_ms",
    "hop_p99_worst_ms",
    "hbm_frac",
    "health",
    # replicas currently gossiping the `outlier` self-flag (obs.canary)
    "outliers",
    # fleet capacity signals (PR 12): tightest replica's paged-KV
    # block-pool free fraction (gossiped `kvfree`) and the worst
    # replica's short-window availability burn (gossiped `burn`) — the
    # two inputs control.autoscale scales on; blank on old peers
    "kvfree_min",
    "burn_max",
    # memory-plane observability (ISSUE 13): the stage's trailing-window
    # prefix-cache hit rate (median replica's gossiped `cachehit`, as a
    # percentage) — blank on dense stages, idle windows, and old peers
    "cachehit",
    # multi-tenant LoRA (ISSUE 15): the stage's resident-adapter union
    # (gossiped `ada` name lists, space-joined) — blank on registry-less
    # replicas and old peers
    "adapters",
    # control.autoscale advisory for this stage (only with --autoscale)
    "autoscale",
]


def stage_rows(swarm_map: SwarmMap, ts: Optional[float] = None) -> list:
    """One CSV row per stage (the reference's per-stage columns,
    test_rebalance.py:38-64, normalized to long form, plus the
    span-derived hop-latency quantiles nodes gossip: per-stage p50 is the
    median of the replicas' p50s, p99 the worst replica's p99)."""
    from statistics import median

    ts = ts if ts is not None else time.time()
    rows = []
    for stage in sorted(swarm_map):
        nodes = swarm_map[stage]
        loads = [int(v.get("load", 0)) for v in nodes.values()]
        caps = [int(v.get("cap", 0)) for v in nodes.values()]
        p50s = [
            float(v["hop_p50_ms"]) for v in nodes.values()
            if v.get("hop_p50_ms") is not None
        ]
        p99s = [
            float(v["hop_p99_ms"]) for v in nodes.values()
            if v.get("hop_p99_ms") is not None
        ]
        fracs = [
            float(v["hbm"]) for v in nodes.values()
            if v.get("hbm") is not None
        ]
        # the stage's health is its WORST replica's verdict — a degraded
        # replica degrades the stage (obs.health gossip field)
        # unknown verdict strings (mixed-version gossip) rank below
        # failing: a garbled value must never displace a real failure
        rank = {"ok": 0, "degraded": 1, "failing": 3}
        healths = [
            str(v["health"]) for v in nodes.values()
            if v.get("health") is not None
        ]
        # mixed-version safe: old peers gossip neither `outlier` nor the
        # windowed quantiles — they just don't contribute to these cells
        outliers = sorted(
            nid for nid, v in nodes.items() if v.get("outlier")
        )
        kvfrees = [
            float(v["kvfree"]) for v in nodes.values()
            if isinstance(v.get("kvfree"), (int, float))
        ]
        burns = [
            float(v["burn"]) for v in nodes.values()
            if isinstance(v.get("burn"), (int, float))
        ]
        cachehits = [
            float(v["cachehit"]) for v in nodes.values()
            if isinstance(v.get("cachehit"), (int, float))
        ]
        # mixed-version safe: old peers gossip no `ada` list and simply
        # don't contribute names to the cell
        adapters = sorted({
            str(name)
            for v in nodes.values()
            if isinstance(v.get("ada"), (list, tuple))
            for name in v["ada"]
        })
        p50_med = round(median(p50s), 3) if p50s else ""
        p99_worst = round(max(p99s), 3) if p99s else ""
        rows.append(
            {
                "ts": round(ts, 3),
                "stage": stage,
                "servers": len(nodes),
                "tasks_running": sum(loads),
                "total_cap": sum(caps),
                "min_load": min(loads) if loads else 0,
                "max_load": max(loads) if loads else 0,
                "hop_p50_ms": p50_med,
                "hop_p99_ms": p99_worst,
                "hop_p50_med_ms": p50_med,
                "hop_p99_worst_ms": p99_worst,
                "hbm_frac": round(max(fracs), 3) if fracs else "",
                "health": (
                    max(healths, key=lambda h: rank.get(h, 2))
                    if healths else ""
                ),
                "outliers": " ".join(outliers),
                # tightest pool / worst burn set the cell: autoscaling
                # (and a human) reacts to the constrained replica
                "kvfree_min": round(min(kvfrees), 4) if kvfrees else "",
                "burn_max": round(max(burns), 2) if burns else "",
                # the MEDIAN replica's hit rate, as a percentage: the
                # stage-typical cache effectiveness (min/max both lie
                # under affinity routing — a deliberately cold spare is
                # not a regression, one hot replica is not the stage)
                "cachehit": (
                    round(median(cachehits) * 100, 1) if cachehits else ""
                ),
                "adapters": " ".join(adapters),
                "autoscale": "",
            }
        )
    return rows


async def fetch_histories(
    swarm_map: SwarmMap, timeout_s: float = 5.0
) -> List[Dict[str, Any]]:
    """GET /metrics/history from every distinct gossiped node — the
    pull half of the fleet SLI pipeline. Old builds without the endpoint,
    dead nodes, and invalid payloads are skipped (mixed-version fleets
    degrade, never crash the collector)."""
    import aiohttp

    from inferd_tpu.obs import tsdb as tsdblib

    addrs = sorted(
        {
            (str(v["host"]), int(v["port"]))
            for nodes in swarm_map.values()
            for v in nodes.values()
            if v.get("host") and v.get("port")
        }
    )
    if not addrs:
        return []

    async with aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=timeout_s)
    ) as http:

        async def one(host: str, port: int):
            try:
                async with http.get(
                    f"http://{host}:{port}/metrics/history"
                ) as r:
                    if r.status != 200:
                        return None
                    obj = await r.json()
            except Exception:
                return None
            return obj if not tsdblib.validate_history(obj) else None

        results = await asyncio.gather(*(one(h, p) for h, p in addrs))
    return [r for r in results if r is not None]


class Collector:
    """Samples a swarm-map source into CSV until stopped; with
    `ndjson_path` set, each period also merges the nodes' windowed
    histories into one fleet SLI sample (obs.fleet) appended as NDJSON;
    with `autoscaler` set (an control.autoscale.AutoScaler), each period
    also evaluates the scaling policy over the same swarm map and fills
    the per-stage `autoscale` advisory column (and logs the decisions —
    the collector ADVISES, an operator or an external provisioner
    executes; the policy itself is sim-validated, inferd_tpu.sim)."""

    def __init__(
        self,
        source: Callable[[], Awaitable[SwarmMap]],
        out: IO[str],
        period_s: float = 1.0,
        ndjson_path: Optional[str] = None,
        history_fetch: Callable[[SwarmMap], Awaitable[List[Dict[str, Any]]]] = fetch_histories,
        autoscaler: Optional[Any] = None,
    ):
        self.source = source
        self.period_s = period_s
        self._writer = csv.DictWriter(out, fieldnames=FIELDS)
        self._writer.writeheader()
        self._out = out
        self.ndjson_path = ndjson_path
        self.history_fetch = history_fetch
        self.autoscaler = autoscaler
        self.samples = 0
        self.fleet_samples = 0
        self.autoscale_actions = 0

    async def sample_once(self) -> None:
        swarm_map = await self.source()
        advice: Dict[int, str] = {}
        if self.autoscaler is not None:
            actions = self.autoscaler.decide(swarm_map)
            self.autoscale_actions += len(actions)
            for act in actions:
                advice[act.stage] = (
                    advice.get(act.stage, "") + act.render()
                ).strip()
                log.info("autoscale advisory: %s", act.render())
        for row in stage_rows(swarm_map):
            if advice:
                row["autoscale"] = advice.get(row["stage"], "")
            self._writer.writerow(row)
        self._out.flush()
        if self.ndjson_path:
            from inferd_tpu.obs import fleet as fleetlib

            histories = await self.history_fetch(swarm_map)
            if histories:
                fleetlib.write_ndjson(
                    self.ndjson_path, fleetlib.fleet_sample(histories)
                )
                self.fleet_samples += 1
        self.samples += 1

    async def run(self, duration_s: Optional[float] = None) -> None:
        deadline = time.monotonic() + duration_s if duration_s else None
        while deadline is None or time.monotonic() < deadline:
            try:
                await self.sample_once()
            except Exception as e:
                # skip the sample but say so — a persistent failure (bad
                # bootstrap, full disk) must not masquerade as a quiet run
                log.warning("collector sample failed: %s", e)
            await asyncio.sleep(self.period_s)


async def capture_fleet(
    swarm_map: SwarmMap,
    capture_id: str,
    seconds: float,
    out_dir: str,
    timeout_s: float = 10.0,
) -> Dict[str, Any]:
    """Fleet-coordinated profiling capture: trigger a SIMULTANEOUS
    bounded jax.profiler window (POST /profile {"action": "window"})
    tagged with one `capture_id` on every gossiped node, wait it out,
    pull every node's /spans, and merge them with the clock-skew-
    corrected span merge (obs.merge) into one Perfetto/Chrome-trace
    bundle — each node's `capture` span brackets its on-device trace, so
    wire spans line up with kernel slices across the whole fleet.

    Writes into `out_dir`:
      * `<node>.spans.jsonl` — the raw per-node span dumps;
      * `<capture_id>.trace.json` — the skew-corrected Chrome trace;
      * `<capture_id>.capture.json` — the manifest: per-node profiler
        artifact directories (the TensorBoard-loadable device traces
        live on each node's disk), clock offsets, and per-node status.

    Nodes without --enable-profiling (403), old builds without the
    window action, and dead nodes are recorded as errors in the
    manifest — a mixed fleet degrades, it doesn't abort the capture."""
    import os

    import aiohttp

    from inferd_tpu.obs import export as obs_export
    from inferd_tpu.obs import merge as mergelib
    from inferd_tpu.runtime import wire

    addrs = sorted(
        {
            (str(v["host"]), int(v["port"]))
            for nodes in swarm_map.values()
            for v in nodes.values()
            if v.get("host") and v.get("port")
        }
    )
    os.makedirs(out_dir, exist_ok=True)
    body = wire.pack({
        "action": "window", "seconds": seconds, "capture_id": capture_id,
    })
    nodes: Dict[str, Any] = {}
    async with aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=timeout_s + seconds)
    ) as http:

        async def trigger(host: str, port: int):
            node_id = f"{host}:{port}"
            try:
                async with http.post(
                    f"http://{host}:{port}/profile", data=body
                ) as r:
                    obj = wire.unpack(await r.read())
                    if r.status != 200:
                        return node_id, {"error": obj.get("error", f"status {r.status}")}
                    return node_id, {"dir": obj.get("dir")}
            except Exception as e:
                return node_id, {"error": str(e)}

        # SIMULTANEOUS trigger: one gather, not a sequential walk — the
        # whole point is that every replica's window covers the same
        # wall-clock interval
        for node_id, res in await asyncio.gather(
            *(trigger(h, p) for h, p in addrs)
        ):
            nodes[node_id] = res
        await asyncio.sleep(seconds + 1.0)

        async def spans(host: str, port: int):
            node_id = f"{host}:{port}"
            try:
                async with http.get(f"http://{host}:{port}/spans") as r:
                    if r.status != 200:
                        return node_id, None
                    return node_id, await r.text()
            except Exception:
                return node_id, None

        span_files: List[str] = []
        for node_id, text in await asyncio.gather(
            *(spans(h, p) for h, p in addrs)
        ):
            if not text:
                continue
            path = os.path.join(
                out_dir, node_id.replace(":", "_") + ".spans.jsonl"
            )
            with open(path, "w") as f:
                f.write(text)
            span_files.append(path)

    merged = mergelib.merge_paths(span_files) if span_files else {
        "spans": [], "offsets": {}, "traces": [],
    }
    trace_path = os.path.join(out_dir, f"{capture_id}.trace.json")
    with open(trace_path, "w") as f:
        json.dump(
            obs_export.chrome_trace(merged["spans"]), f,
            separators=(",", ":"),
        )
    manifest = {
        "capture_id": capture_id,
        "seconds": seconds,
        "nodes": nodes,
        "offsets": merged["offsets"],
        "traces": len(merged["traces"]),
        "spans": len(merged["spans"]),
        "trace_json": trace_path,
    }
    with open(
        os.path.join(out_dir, f"{capture_id}.capture.json"), "w"
    ) as f:
        json.dump(manifest, f, indent=1)
    return manifest


async def _main(args) -> None:
    from inferd_tpu.tools.dashboard import gossip_source
    from inferd_tpu.tools.run_node import parse_bootstrap

    source, start, stop = gossip_source(
        parse_bootstrap(args.bootstrap), num_stages=args.stages or None,
        listen_port=args.listen_port,
    )
    await start()
    try:
        if args.capture:
            # one fleet-coordinated capture instead of the CSV loop:
            # wait for gossip to surface the fleet, then trigger
            for _ in range(50):
                if await source():
                    break
                await asyncio.sleep(0.1)
            manifest = await capture_fleet(
                await source(), args.capture, args.capture_seconds,
                args.capture_out or args.capture,
            )
            print(json.dumps(manifest, indent=1))
            if not manifest["nodes"]:
                # an empty bundle must not masquerade as a working
                # capture to a script checking the exit code: zero nodes
                # means gossip surfaced no fleet at all (typo'd
                # --bootstrap, or peers slower than the wait loop) —
                # distinct from per-node degradation, which is recorded
                # in the manifest and still exits 0
                raise SystemExit(
                    f"capture {args.capture}: no nodes found in gossip — "
                    "check --bootstrap"
                )
            return
        ndjson = args.ndjson or (
            (args.out + ".ndjson") if args.history else None
        )
        autoscaler = None
        if args.autoscale:
            from inferd_tpu.control.autoscale import AutoScaler

            if not args.stages:
                raise SystemExit("--autoscale needs --stages")
            autoscaler = AutoScaler(args.stages)
        with open(args.out, "w", newline="") as f:
            await Collector(
                source, f, period_s=args.period, ndjson_path=ndjson,
                autoscaler=autoscaler,
            ).run(duration_s=args.duration or None)
    finally:
        await stop()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="collector", description=__doc__)
    ap.add_argument("--bootstrap", required=True, help="gossip seeds host:port,...")
    ap.add_argument("--stages", type=int, default=0)
    ap.add_argument("--out", default="metrics_log.csv")
    ap.add_argument("--period", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=0, help="seconds (0 = forever)")
    ap.add_argument("--listen-port", type=int, default=0)
    ap.add_argument(
        "--history", action="store_true",
        help="also poll each node's /metrics/history and append fleet "
        "SLI samples (obs.fleet) as NDJSON next to the CSV",
    )
    ap.add_argument(
        "--ndjson", default="",
        help="fleet-sample NDJSON path (default: <out>.ndjson with "
        "--history)",
    )
    ap.add_argument(
        "--autoscale", action="store_true",
        help="evaluate the control.autoscale policy over each gossip "
        "sample and fill the per-stage `autoscale` advisory column "
        "(requires --stages; the collector advises, it never executes)",
    )
    ap.add_argument(
        "--capture", default="",
        help="fleet-coordinated profiling capture: trigger one bounded "
        "jax.profiler window tagged with this capture id on EVERY "
        "gossiped node simultaneously, then merge the per-node spans "
        "(clock-skew corrected) into one Chrome-trace bundle + manifest "
        "(nodes need --enable-profiling)",
    )
    ap.add_argument(
        "--capture-seconds", type=float, default=3.0,
        help="capture window length per node (clamped to 60 node-side)",
    )
    ap.add_argument(
        "--capture-out", default="",
        help="bundle output directory (default: ./<capture_id>/)",
    )
    args = ap.parse_args(argv)
    try:
        asyncio.run(_main(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
