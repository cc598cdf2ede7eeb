"""Dashboard / collector / plot tests (the reference's dashboard +
metrics-CSV + notebook trio, SURVEY §2 'Console dashboard' / 'Multi-node
rebalance sim' / 'Metrics plots', as asserted units)."""

import asyncio
import csv
import io
import os

import pytest

from inferd_tpu.tools.collector import FIELDS, Collector, stage_rows
from inferd_tpu.tools.dashboard import Dashboard, gossip_source, render_table

SAMPLE = {
    0: {"10.0.0.2:6050": {"name": "node0", "load": 1, "cap": 4, "model": "qwen3-0.6b"}},
    1: {
        "10.0.0.3:6050": {"name": "node1", "load": 3, "cap": 4, "model": "qwen3-0.6b"},
        "10.0.0.4:6050": {"name": "node2", "load": 0, "cap": 4, "model": "qwen3-0.6b"},
    },
    2: {},
}


def test_render_table_contents():
    text = render_table(SAMPLE, ts=0.0)
    assert "node0" in text and "10.0.0.3:6050" in text
    assert "<no servers>" in text  # empty stage shown, not hidden
    assert "3 node(s), 3 stage(s)" in text
    # one line per node + header/rules/footer
    assert len(text.splitlines()) == 3 + 4 + 1


def test_stage_rows_aggregation():
    rows = stage_rows(SAMPLE, ts=100.0)
    assert [r["stage"] for r in rows] == [0, 1, 2]
    r1 = rows[1]
    assert r1["servers"] == 2
    assert r1["tasks_running"] == 3
    assert r1["total_cap"] == 8
    assert r1["min_load"] == 0 and r1["max_load"] == 3
    r2 = rows[2]
    assert r2["servers"] == 0 and r2["tasks_running"] == 0


@pytest.mark.asyncio
async def test_dashboard_renders_from_source():
    calls = []

    async def source():
        calls.append(1)
        return SAMPLE

    out = io.StringIO()
    dash = Dashboard(source, period_s=0.01, out=out, clear_screen=False)
    text = await dash.render_once()
    assert "node0" in text
    assert calls == [1]


@pytest.mark.asyncio
async def test_collector_writes_csv():
    async def source():
        return SAMPLE

    buf = io.StringIO()
    c = Collector(source, buf, period_s=0.01)
    await c.sample_once()
    await c.sample_once()
    rows = list(csv.DictReader(io.StringIO(buf.getvalue())))
    assert len(rows) == 6  # 3 stages x 2 samples
    assert rows[0]["stage"] == "0"
    assert rows[1]["tasks_running"] == "3"


@pytest.mark.asyncio
async def test_gossip_observer_sees_swarm():
    """A silent gossip observer converges on the nodes' records without
    announcing anything itself."""
    from inferd_tpu.control.dht import SwarmDHT

    base = 19300
    a = SwarmDHT("a", base, host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0)
    b = SwarmDHT(
        "b", base + 1, bootstrap=[("127.0.0.1", base)], host="127.0.0.1",
        gossip_period_s=0.05, ttl_s=5.0,
    )
    await a.start()
    await b.start()
    a.announce({"stage": 0, "load": 0, "cap": 4, "name": "a"})
    b.announce({"stage": 1, "load": 1, "cap": 4, "name": "b"})
    source, start, stop = gossip_source([("127.0.0.1", base)], num_stages=2, listen_port=base + 2)
    await start()
    try:
        for _ in range(100):
            m = await source()
            if m[0] and m[1]:
                break
            await asyncio.sleep(0.05)
        assert m[0] and m[1], m
        # the observer never announced: nodes must not see a third record
        assert len(a.alive_records()) == 2
    finally:
        await stop()
        await a.stop()
        await b.stop()


def test_plot_metrics_renders_png(tmp_path):
    from inferd_tpu.tools import plot_metrics

    csv_path = tmp_path / "m.csv"
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=FIELDS)
        w.writeheader()
        for t in range(5):
            for s in range(2):
                w.writerow(
                    {
                        "ts": 100 + t, "stage": s, "servers": 1 + s,
                        "tasks_running": t % 3, "total_cap": 4,
                        "min_load": 0, "max_load": t % 3,
                    }
                )
    out = tmp_path / "m.png"
    plot_metrics.main([str(csv_path), "--out", str(out)])
    assert os.path.getsize(out) > 1000


def test_collector_explicit_hop_columns_and_aliases():
    """The PR 3 conflation fix: hop_p50_ms (median replica) and
    hop_p99_ms (WORST replica) keep their values as one-release aliases,
    while the explicit hop_p50_med_ms / hop_p99_worst_ms columns name
    the aggregation — and outlier-flagged replicas land in `outliers`."""
    from inferd_tpu.tools.collector import FIELDS, stage_rows

    sample = {
        0: {
            "a": {"load": 1, "cap": 4, "hop_p50_ms": 10.0, "hop_p99_ms": 50.0},
            "b": {"load": 0, "cap": 4, "hop_p50_ms": 20.0, "hop_p99_ms": 90.0,
                  "outlier": 1},
            "c": {"load": 0, "cap": 4, "hop_p50_ms": 30.0, "hop_p99_ms": 70.0},
        },
    }
    assert {"hop_p50_med_ms", "hop_p99_worst_ms", "outliers"} <= set(FIELDS)
    row = stage_rows(sample, ts=1.0)[0]
    assert row["hop_p50_med_ms"] == 20.0  # median replica's p50
    assert row["hop_p99_worst_ms"] == 90.0  # worst replica's p99
    # aliases carry the SAME values for one release
    assert row["hop_p50_ms"] == row["hop_p50_med_ms"]
    assert row["hop_p99_ms"] == row["hop_p99_worst_ms"]
    assert row["outliers"] == "b"


def test_collector_renders_rows_from_old_peers():
    """Mixed-version fleets: records from pre-PR-7 peers lack the
    windowed-quantile and outlier keys entirely — the collector must
    still emit their stage rows with blank cells, never crash or invent
    defaults."""
    from inferd_tpu.tools.collector import stage_rows

    sample = {
        0: {"old": {"load": 2, "cap": 4}},  # nothing but the PR-1 schema
        1: {
            "old2": {"load": 0, "cap": 4},
            "new": {"load": 0, "cap": 4, "hop_p50_ms": 5.0,
                    "hop_p99_ms": 9.0, "svc_p99_ms": 7.0},
        },
    }
    rows = stage_rows(sample, ts=1.0)
    assert rows[0]["hop_p50_med_ms"] == "" and rows[0]["outliers"] == ""
    # the single new replica's numbers still aggregate
    assert rows[1]["hop_p50_med_ms"] == 5.0
    assert rows[1]["hop_p99_worst_ms"] == 9.0


def test_dashboard_independent_hop_cells_and_outlier_marker():
    """The dashboard renders hop p50 and p99 as SEPARATE columns with
    independent '-' fallbacks (the old single cell blanked both when
    either was missing) plus the outlier marker."""
    from inferd_tpu.tools.dashboard import render_table

    table = render_table({
        0: {
            "10.0.0.2:6050": {"name": "full", "load": 0, "cap": 4,
                              "hop_p50_ms": 4.0, "hop_p99_ms": 40.0},
            "10.0.0.3:6050": {"name": "p50only", "load": 0, "cap": 4,
                              "hop_p50_ms": 6.0},
            "10.0.0.4:6050": {"name": "oldpeer", "load": 0, "cap": 4},
            "10.0.0.5:6050": {"name": "flagged", "load": 0, "cap": 4,
                              "hop_p50_ms": 5.0, "hop_p99_ms": 400.0,
                              "outlier": 1},
        },
    })
    assert "hop p50" in table and "hop p99" in table and "out" in table
    rows = {
        ln.split()[2]: ln.split()
        for ln in table.splitlines() if "10.0.0." in ln
    }
    # tokens: [stage, node, name, load/cap, hop_p50, hop_p99, out?/...]
    assert rows["full"][4] == "4" and rows["full"][5] == "40"
    # a peer carrying only p50 renders it, with "-" only for p99
    assert rows["p50only"][4] == "6" and rows["p50only"][5] == "-"
    assert rows["oldpeer"][4] == "-" and rows["oldpeer"][5] == "-"
    assert rows["flagged"][5] == "400" and rows["flagged"][6] == "!"
    # non-flagged rows collapse the empty out cell (next token is the
    # cobatch "-"), never a stray marker
    assert "!" not in rows["oldpeer"]


# a peer of an older build still gossips `roofline` / `perf`: both are
# keys this build does not know, and are shown as any unknown key is
_OLDER = {"name": "older", "load": 1, "cap": 4, "hop_p50_ms": 4.0,
          "hop_p99_ms": 40.0, "health": "ok"}
_OLDER_GOSSIPS = dict(_OLDER, roofline=0.05, perf=1)


def test_collector_ignores_an_older_peers_roofline_and_perf():
    with_keys = stage_rows({0: {"10.0.0.2:6050": _OLDER_GOSSIPS}}, ts=1.0)
    without = stage_rows({0: {"10.0.0.2:6050": _OLDER}}, ts=1.0)
    assert with_keys == without
    assert set(with_keys[0]) == set(FIELDS)
    assert not {"roofline_worst", "perf"} & set(FIELDS)


def test_dashboard_ignores_an_older_peers_roofline_and_perf():
    with_keys = render_table({0: {"10.0.0.2:6050": _OLDER_GOSSIPS}}, ts=0.0)
    without = render_table({0: {"10.0.0.2:6050": _OLDER}}, ts=0.0)
    assert with_keys == without
    assert "roof%" not in with_keys and "!perf" not in with_keys
