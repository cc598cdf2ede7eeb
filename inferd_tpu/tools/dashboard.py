"""Console dashboard: live per-stage swarm state.

Capability parity with /root/reference/dashboard/dashboard.py:7-30 (a
background thread rendering a PrettyTable of (stage, node, load) every few
seconds from a pluggable `source_function` fed DHT-shaped data) —
redesigned: no third-party table dependency, two real data sources instead
of a canned JSON file, and per-hop latency columns from the node /stats
metrics (the observability the reference lacked, SURVEY §5).

Sources:
  * `gossip`: join the swarm's gossip as a silent observer (a SwarmDHT that
    never announces) — zero load on the nodes, sees exactly what routing
    sees, including TTL expiry of dead nodes;
  * `node`: poll one node's /stats endpoint over HTTP (includes that node's
    merged DHT view + its latency histograms).

Usage:
  python -m inferd_tpu.tools.dashboard --bootstrap 10.0.0.2:7050
  python -m inferd_tpu.tools.dashboard --node 10.0.0.2:6050 --period 3
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import time
from typing import Any, Awaitable, Callable, Dict, Optional

SwarmMap = Dict[int, Dict[str, Dict[str, Any]]]  # stage -> node_id -> value


def _ms_cell(v: Dict[str, Any], key: str) -> str:
    """One gossiped millisecond quantile rendered independently — a peer
    carrying only one of p50/p99 (mixed-version gossip, or a window with
    a single observation bucket) must not blank the other out (the PR 3
    cell merged both behind one "-" fallback)."""
    x = v.get(key)
    if x is None:
        return "-"
    return f"{float(x):.0f}"


def _outlier_cell(v: Dict[str, Any]) -> str:
    """"!" when the replica self-flags as a trailing-p99 outlier
    (obs.canary; routing penalizes it), else ""."""
    return "!" if v.get("outlier") else ""


def _cobatch_cell(v: Dict[str, Any]) -> str:
    """Mean sessions per co-batched decode step (gossiped as `cobatch` by
    stage-window nodes, runtime/node.announce), or "-"."""
    cb = v.get("cobatch")
    if cb is None:
        return "-"
    return f"{float(cb):.1f}"


def _kvfree_cell(v: Dict[str, Any]) -> str:
    """Paged-KV block-pool free fraction as a percentage (gossiped as
    `kvfree` by paged replicas, runtime/node.announce — the admission /
    autoscale watermark), or "-" (dense executors, old peers)."""
    kf = v.get("kvfree")
    if not isinstance(kf, (int, float)):
        return "-"
    return f"{float(kf) * 100:.0f}%"


def _cachehit_cell(v: Dict[str, Any]) -> str:
    """Trailing-window prefix-cache hit rate as a percentage (gossiped as
    `cachehit` by paged replicas — runtime/node.announce via the
    kv.prefix_* windowed series), or "-" (dense executors, idle windows,
    old peers)."""
    ch = v.get("cachehit")
    if not isinstance(ch, (int, float)):
        return "-"
    return f"{float(ch) * 100:.0f}%"


def _ada_cell(v: Dict[str, Any]) -> str:
    """Resident-adapter count (gossiped as `ada` by multi-tenant
    replicas — runtime/node.announce via the adapter registry), or "-"
    (registry-less replicas, old peers)."""
    ada = v.get("ada")
    if not isinstance(ada, (list, tuple)):
        return "-"
    return str(len(ada))


def _hbm_cell(v: Dict[str, Any]) -> str:
    """HBM in-use fraction as a percentage (gossiped as `hbm` by nodes
    whose runtime reports memory_stats — obs.devtel), or "-" (CPU)."""
    frac = v.get("hbm")
    if frac is None:
        return "-"
    return f"{float(frac) * 100:.0f}%"


def _compiles_cell(v: Dict[str, Any]) -> str:
    """Cumulative XLA compile events (gossiped as `compiles` — a rising
    number on a serving node is a recompile storm), or "-"."""
    c = v.get("compiles")
    if c is None:
        return "-"
    return str(int(c))


def _health_cell(v: Dict[str, Any]) -> str:
    """SLO verdict (gossiped as `health` — obs.health), or "-"."""
    h = v.get("health")
    if h is None:
        return "-"
    return str(h)


def render_table(swarm_map: SwarmMap, ts: Optional[float] = None) -> str:
    """Fixed-width table of (stage, node id, name, load/cap, trailing hop
    p50 and p99 as SEPARATE columns, outlier flag, mean co-batch, hbm%,
    compiles, health, model). Hop quantiles are the nodes' gossiped
    TRAILING-WINDOW numbers (obs.tsdb) — "now", not process lifetime."""
    header = (
        f"{'stage':>5}  {'node':<21} {'name':<12} {'load':>4}/{'cap':<4} "
        f"{'hop p50':>8} {'hop p99':>8} {'out':>3} "
        f"{'cobatch':>7} {'kvfree':>6} {'cache%':>6} {'ada':>3} {'hbm%':>5} "
        f"{'compiles':>8} {'health':<8} {'model':<16}"
    )
    rule = "-" * len(header)
    lines = [header, rule]
    total_nodes = 0
    for stage in sorted(swarm_map):
        nodes = swarm_map[stage]
        if not nodes:
            lines.append(f"{stage:>5}  {'<no servers>':<21}")
            continue
        for node_id, v in sorted(nodes.items()):
            total_nodes += 1
            lines.append(
                f"{stage:>5}  {node_id:<21} {str(v.get('name', '')):<12} "
                f"{v.get('load', '?'):>4}/{str(v.get('cap', '?')):<4} "
                f"{_ms_cell(v, 'hop_p50_ms'):>8} "
                f"{_ms_cell(v, 'hop_p99_ms'):>8} "
                f"{_outlier_cell(v):>3} "
                f"{_cobatch_cell(v):>7} "
                f"{_kvfree_cell(v):>6} "
                f"{_cachehit_cell(v):>6} "
                f"{_ada_cell(v):>3} "
                f"{_hbm_cell(v):>5} "
                f"{_compiles_cell(v):>8} "
                f"{_health_cell(v):<8} "
                f"{str(v.get('model', '')):<16}"
            )
    stamp = time.strftime("%H:%M:%S", time.localtime(ts or time.time()))
    lines.append(rule)
    lines.append(f"{total_nodes} node(s), {len(swarm_map)} stage(s) @ {stamp}")
    return "\n".join(lines)


class Dashboard:
    """Periodically renders the swarm map from a pluggable async source
    (the reference's `source_function` contract, dashboard.py:12-14)."""

    def __init__(
        self,
        source: Callable[[], Awaitable[SwarmMap]],
        period_s: float = 3.0,  # reference cadence, dashboard.py:22
        out=sys.stdout,
        clear_screen: bool = True,
    ):
        self.source = source
        self.period_s = period_s
        self.out = out
        self.clear_screen = clear_screen
        self._task: Optional[asyncio.Task] = None

    async def render_once(self) -> str:
        text = render_table(await self.source())
        if self.clear_screen:
            self.out.write("\x1b[2J\x1b[H")
        self.out.write(text + "\n")
        self.out.flush()
        return text

    async def run(self) -> None:
        while True:
            try:
                await self.render_once()
            except Exception as e:
                self.out.write(f"dashboard source error: {e}\n")
                self.out.flush()
            await asyncio.sleep(self.period_s)

    def start(self) -> None:
        self._task = asyncio.create_task(self.run())

    async def stop(self) -> None:
        if self._task:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass


# ---------------------------------------------------------------------------
# Sources
# ---------------------------------------------------------------------------


def gossip_source(bootstrap, num_stages: Optional[int] = None, listen_port: int = 0):
    """Silent gossip observer. Returns (source_fn, start, stop) — the
    observer's DHT must be started inside the caller's event loop."""
    import uuid

    from inferd_tpu.control.dht import SwarmDHT

    # unique observer id: two dashboards (same port config, different hosts,
    # or a restart) must not clobber each other's peer entry on the nodes
    dht = SwarmDHT(
        f"observer:{uuid.uuid4().hex[:8]}", listen_port, bootstrap=bootstrap,
        host="0.0.0.0",
    )

    async def source() -> SwarmMap:
        return dht.get_all(num_stages)

    return source, dht.start, dht.stop


def node_source(host: str, port: int):
    """Poll one node's /stats endpoint (its merged DHT view)."""
    import aiohttp

    async def source() -> SwarmMap:
        async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=5)
        ) as http:
            async with http.get(f"http://{host}:{port}/stats") as r:
                data = await r.json()
        return {int(k): v for k, v in data.get("dht", {}).items()}

    return source


async def _main(args) -> None:
    if args.node:
        host, _, port = args.node.rpartition(":")
        dash = Dashboard(node_source(host, int(port)), period_s=args.period)
        await dash.run()
    else:
        from inferd_tpu.tools.run_node import parse_bootstrap

        source, start, stop = gossip_source(
            parse_bootstrap(args.bootstrap), num_stages=args.stages or None,
            listen_port=args.listen_port,
        )
        await start()
        try:
            await Dashboard(source, period_s=args.period).run()
        finally:
            await stop()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="dashboard", description=__doc__)
    ap.add_argument("--bootstrap", default="", help="gossip seeds host:port,... (observer mode)")
    ap.add_argument("--node", default="", help="host:port of a node's /stats to poll instead")
    ap.add_argument("--listen-port", type=int, default=0, help="observer UDP port (0 = ephemeral)")
    ap.add_argument("--stages", type=int, default=0, help="show this many stages even if empty")
    ap.add_argument("--period", type=float, default=3.0)
    args = ap.parse_args(argv)
    if not args.bootstrap and not args.node:
        ap.error("need --bootstrap (gossip observer) or --node (stats poller)")
    try:
        asyncio.run(_main(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
