"""Fleet telemetry plane tests (PR 7): windowed tsdb rings, trailing
quantiles replacing all-time ones in gossip//health, multi-window
burn-rate SLO rules, canary probing with user-SLI isolation, MAD
replica-outlier detection feeding routing, the fleet SLI aggregator, and
the perf-gate budget extension — unit level plus the e2e fault-injection
acceptance (a slowed stage replica is flagged, routed around, and shows
up in `obs fleet` output assembled from per-node artifacts alone)."""

import asyncio
import functools
import json
import os

import pytest

from inferd_tpu.obs import canary as canarylib
from inferd_tpu.obs import fleet as fleetlib
from inferd_tpu.obs import health as healthlib
from inferd_tpu.obs import tsdb as tsdblib
from inferd_tpu.obs.__main__ import main as obs_main
from inferd_tpu.utils.metrics import Metrics

import test_node_e2e as e2e
from conftest import port_block
from test_node_e2e import _start_all, _stop_all, tiny_parts  # noqa: F401

PORTS = port_block(__file__)
_mk_node = functools.partial(e2e._mk_node, ports=PORTS)  # its nodes, this module's ports

FLEET_FIXTURE = os.path.join(os.path.dirname(__file__), "data", "fleet")
BURN_FIXTURE = os.path.join(os.path.dirname(__file__), "data", "health_burn")


def _clocked_tsdb(metrics, **kw):
    clock = [1000.0]
    t = tsdblib.Tsdb(metrics, clock=lambda: clock[0], **kw)
    return t, clock


# ---------------------------------------------------------------- tsdb core


def test_tsdb_counter_rates_and_windows():
    m = Metrics()
    t, clock = _clocked_tsdb(m, service="n0")
    t.sample()
    for _ in range(30):
        clock[0] += 1.0
        m.inc("forward.requests", 4)
        t.sample()
    # ~4/s over any window the series lived (bucket-edge inclusion can
    # over-read by one bucket: a 10 s horizon spans 11 bucket starts)
    assert t.trailing_rate("forward.requests", 10.0) == pytest.approx(4.0, rel=0.15)
    assert t.trailing_rate("forward.requests", 30.0) == pytest.approx(4.0, rel=0.1)
    assert t.trailing_rate("missing.series") is None
    # idle minute: the window empties, the rate decays to zero
    clock[0] += 120.0
    t.sample()
    assert t.trailing_rate("forward.requests", 60.0) == pytest.approx(0.0)


def test_tsdb_counter_reset_rebaselines():
    """A counter that goes BACKWARD (process restart feeding the same
    registry name) re-baselines instead of booking a negative delta."""
    m = Metrics()
    m.inc("c", 100)
    t, clock = _clocked_tsdb(m)
    t.sample()  # first sighting: the pre-existing 100 is baseline, not a burst
    m.inc("c", 20)
    clock[0] += 1
    t.sample()
    m.set_counter("c", 5.0)  # simulated reset (through the locked API)
    clock[0] += 1
    t.sample()
    m.inc("c", 5)
    clock[0] += 1
    t.sample()
    total = sum(v for _t, v in t.history()["counters"]["c"][0])
    assert total == 25  # 20 before the reset + 5 after; neither the
    # attach-time 100 nor a negative reset delta ever booked


def test_tsdb_attach_baseline_vs_sparse_first_event():
    """Two baselining contracts at once: counters that PRE-EXIST the
    tsdb are attach-time baselines (their past must not book as one
    burst), while a series born LATER books from zero — a sparse
    counter's first event (one canary failure) must land in the
    window, not vanish into a first-sighting baseline."""
    m = Metrics()
    m.inc("old.counter", 500)
    t, clock = _clocked_tsdb(m)
    clock[0] += 1
    m.inc("canary.fail")  # born post-attach: the single event books
    t.sample()
    assert t.trailing_rate("old.counter", 60.0) == 0.0
    total = sum(v for _t, v in t.history()["counters"]["canary.fail"][0])
    assert total == 1


def test_tsdb_young_series_not_diluted():
    """A counter born 10 s ago must not spread its burst over a 60 s
    window it never lived (the reach clamp)."""
    m = Metrics()
    t, clock = _clocked_tsdb(m)
    t.sample()
    for _ in range(10):
        clock[0] += 1.0
        m.inc("errors", 6)
        t.sample()
    # 60 observed in ~10 lived seconds: ~6/s, NOT 1/s
    assert t.trailing_rate("errors", 60.0) == pytest.approx(6.0, rel=0.15)


def test_tsdb_gauge_last_wins_and_staleness():
    m = Metrics()
    t, clock = _clocked_tsdb(m)
    m.set_gauge("queue.depth", 3)
    t.sample()
    clock[0] += 5
    m.set_gauge("queue.depth", 9)
    t.sample()
    assert tsdblib.trailing_gauge(t.history(), "queue.depth", 60.0) == 9.0
    clock[0] += 600
    t.sample()  # gauge still set, current bucket carries it
    assert tsdblib.trailing_gauge(t.history(), "queue.depth", 60.0) == 9.0


def test_tsdb_slow_then_recovered_p99_drops_within_horizon():
    """THE acceptance property the cumulative Histogram cannot provide:
    a replica that was slow and then recovered stops reporting an
    elevated trailing p99 once the slow samples age past the horizon —
    while the all-time histogram keeps the elevated p99 forever."""
    m = Metrics()
    t, clock = _clocked_tsdb(m)
    t.sample()
    for _ in range(20):
        clock[0] += 1.0
        m.observe("hop.relay_ms", 900.0)  # the bad minute
        t.sample()
    bad = t.trailing_quantiles("hop.relay_ms", 60.0)
    assert bad["p99_ms"] >= 900.0
    # recovery: a minute of fast hops pushes the slow ones out of window
    for _ in range(70):
        clock[0] += 1.0
        m.observe("hop.relay_ms", 2.0)
        t.sample()
    good = t.trailing_quantiles("hop.relay_ms", 60.0)
    assert good["p99_ms"] <= 10.0, good
    # the cumulative histogram still reports the incident — forever
    assert m.histograms["hop.relay_ms"].quantile(0.99) >= 900.0


def test_tsdb_downsampling_ladder_reach():
    """Old data lives only in the coarse levels; queries pick the finest
    level whose reach covers the horizon."""
    m = Metrics()
    t, clock = _clocked_tsdb(m, levels=((1.0, 10), (10.0, 20), (60.0, 30)))
    t.sample()
    for _ in range(120):
        clock[0] += 1.0
        m.inc("c", 1)
        t.sample()
    rings = t.history()["counters"]["c"]
    assert len(rings[0]) == 10  # fine level: capped, recent only
    assert sum(v for _t, v in rings[1]) > sum(v for _t, v in rings[0])
    # 100 s horizon exceeds the 10-bucket 1 s level: level 1 serves it
    h = t.history()
    assert tsdblib._pick_level(h, 5.0) == 0
    assert tsdblib._pick_level(h, 100.0) == 1
    assert tsdblib._pick_level(h, 100000.0) == 2  # clamped to coarsest


def test_tsdb_fleet_merge_is_bucket_true():
    """Merged fleet percentiles come from SUMMED bucket deltas — one
    slow node among fast ones shifts the fleet p99 but not the p50
    (an average-of-averages would corrupt both)."""
    hs = []
    for node, lat in (("a", 2.0), ("b", 2.0), ("c", 2.0), ("d", 5000.0)):
        m = Metrics()
        t, clock = _clocked_tsdb(m, service=node)
        t.sample()
        for _ in range(30):
            clock[0] += 1.0
            m.observe("hop.relay_ms", lat)
            t.sample()
        hs.append(t.history())
    q = tsdblib.merged_quantiles(hs, "hop.relay_ms", 60.0)
    assert q["p50_ms"] <= 5.0  # 3/4 of samples are fast
    assert q["p99_ms"] >= 5000.0  # the slow node owns the tail
    assert q["count"] > 60
    # a node with MISMATCHED bucket bounds degrades (skipped), not corrupts
    m = Metrics()
    t, clock = _clocked_tsdb(m, service="weird")
    t.sample()
    clock[0] += 1
    m.observe("hop.relay_ms", 3.0, bounds_ms=[1, 2, 3])
    t.sample()
    q2 = tsdblib.merged_quantiles(hs + [t.history()], "hop.relay_ms", 60.0)
    assert q2["count"] == q["count"]


def test_history_schema_validates_and_golden_fixture():
    """The /metrics/history JSON schema: live objects and the committed
    golden fixture both pass validate_history; corruptions are named."""
    m = Metrics()
    t, clock = _clocked_tsdb(m)
    m.observe("h", 1.0)
    t.sample()  # "h" born post-attach: its first observation books
    clock[0] += 1
    m.inc("c")
    m.set_gauge("g", 2)
    m.observe("h", 3.0)
    t.sample()
    h = t.history()
    assert tsdblib.validate_history(h) == []
    # committed golden fixture (regenerate via the script in its header
    # comment... it is deterministic: fixed clock, fixed drives)
    fixture = tsdblib.load_history_file(
        os.path.join(FLEET_FIXTURE, "node0.history.json")
    )
    assert fixture["service"] == "10.0.0.2:6050"
    assert fixture["meta"]["stage"] == 0
    # trailing queries over the committed rings are deterministic
    q = tsdblib.trailing_quantiles(fixture, "generate.ttft_ms", 60.0)
    assert q is not None and q["p50_ms"] > 0
    # corruption: negative bucket count
    bad = json.loads(json.dumps(h))
    bad["histograms"]["h"]["levels"][0][0][1][0] = -1
    assert any("bucket" in p for p in tsdblib.validate_history(bad))
    # corruption: counts/total mismatch
    bad2 = json.loads(json.dumps(h))
    bad2["histograms"]["h"]["levels"][0][0][2] += 5
    assert any("total" in p for p in tsdblib.validate_history(bad2))
    assert tsdblib.validate_history([1, 2]) == ["history is not a JSON object"]


# ------------------------------------------------------------- burn rules


def test_burn_rule_parse_forms_and_errors():
    r = healthlib.Rule.parse("burn:availability[5m,1h] > 14")
    assert r.signal == "burn:availability[5m,1h]"
    sig = healthlib.BurnSignal.parse("availability@99.5[5m,1h]")
    assert sig.objective == 99.5
    assert sig.windows == (300.0, 3600.0)
    # the canary-excluded generate.* family, NOT the node-wide counters
    # probe traffic bumps (obs.health.BURN_SLIS rationale)
    assert sig.bad == "generate.errors" and sig.total == "generate.requests"
    for bad in (
        "burn:nope[5m] > 1",            # unknown SLI
        "burn:availability > 1",        # no window
        "burn:availability[5q] > 1",    # bad unit
        "burn:availability[1m,5m,1h] > 1",  # too many windows
        "burn:availability@200[5m] > 1",    # objective out of range
    ):
        with pytest.raises(ValueError):
            healthlib.Rule.parse(bad)


def _burning_history(error_frac=0.1, seconds=3900, step=5.0):
    m = Metrics()
    t, clock = _clocked_tsdb(m)
    t.sample()
    for i in range(int(seconds / step)):
        clock[0] += step
        m.inc("generate.requests", 10)
        if error_frac and i % int(1 / error_frac) == 0:
            m.inc("generate.errors", 10 * error_frac * (1 / error_frac))
        t.sample()
    return t.history(), clock[0]


def test_burn_rule_needs_both_windows():
    """The multi-window AND: a burst that only poisons the short window
    does not fire; sustained burn firing both windows does."""
    rule = healthlib.Rule.parse("burn:availability[5m,1h] > 14")
    # sustained 10% errors vs 0.1% budget = 100x in both windows
    h, now = _burning_history(error_frac=0.1)
    fired, val, _ = healthlib.evaluate_rule(rule, {}, histories=[h], now=now)
    assert fired is True and val > 14
    # clean hour, then a 2-minute burst: short window burns, long does not
    m = Metrics()
    t, clock = _clocked_tsdb(m)
    t.sample()
    for _ in range(720):
        clock[0] += 5.0
        m.inc("generate.requests", 10)
        t.sample()
    for _ in range(24):
        clock[0] += 5.0
        m.inc("generate.requests", 10)
        m.inc("generate.errors", 1)
        t.sample()
    fired, _, _ = healthlib.evaluate_rule(
        rule, {}, histories=[t.history()], now=clock[0]
    )
    assert fired is False  # the 1h window vetoes the flap
    # no history at all: SKIP, not green
    assert healthlib.evaluate_rule(rule, {}) == (None, None, None)


def test_burn_fixture_one_firing_one_quiet(capsys):
    """Acceptance: the committed health_burn fixture evaluates one
    firing burn rule (availability, degraded) and one quiet one (canary)
    through `obs health --check` — rc 0, since degraded is not failing."""
    assert obs_main(["health", "--check", BURN_FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "DEGRADED" in out
    assert "burn:availability[5m,1h] > 14" in out
    assert "burn:canary" not in out.split("firing")[0] or True
    assert "2 rules evaluated, 1 firing" in out


def test_burn_failing_severity_fails_check(tmp_path, capsys):
    """A burn rule at failing severity flips the check's exit code."""
    h, _now = _burning_history(error_frac=0.1)
    d = tmp_path / "scrape"
    d.mkdir()
    (d / "node0.history.json").write_text(json.dumps(h))
    (d / "rules.json").write_text(json.dumps(
        [{"rule": "burn:availability[5m,1h] > 14", "severity": "failing"}]
    ))
    assert obs_main(["health", "--check", str(d)]) == 1
    assert "FAILING" in capsys.readouterr().out


def test_load_scrape_skips_truncated_history(tmp_path):
    """A node killed mid-dump leaves a truncated *.history.json — the
    loader skips it (degrade-don't-crash) instead of failing the whole
    verdict, and a lone bad file leaves histories=None (burn rules
    SKIP)."""
    h, _now = _burning_history()
    d = tmp_path / "scrape"
    d.mkdir()
    good = json.dumps(h)
    (d / "a.history.json").write_text(good)
    (d / "b.history.json").write_text(good[: len(good) // 2])  # truncated
    loaded = healthlib.load_scrape([str(d)])
    assert len(loaded["histories"]) == 1
    (d / "a.history.json").unlink()
    loaded = healthlib.load_scrape([str(d)])
    assert loaded["histories"] is None


def test_burn_gauges():
    h, now = _burning_history(error_frac=0.1)
    g = healthlib.burn_gauges([h], now=now)
    assert g["burn.availability"] > 14
    assert "burn.canary" not in g  # no canary series in this history
    assert healthlib.burn_gauges(None) == {}


# -------------------------------------------------------- outlier detection


def _stage_map(**vals):
    return {
        nid: {"hop_p99_ms": v} if v is not None else {}
        for nid, v in vals.items()
    }


def test_detect_outliers_mad_flag_and_one_sided():
    flagged = canarylib.detect_outliers(
        _stage_map(a=10.0, b=12.0, c=11.0, d=300.0)
    )
    assert set(flagged) == {"d"}
    assert flagged["d"]["field"] == "hop_p99_ms"
    assert flagged["d"]["value"] == 300.0
    # one-sided: an unusually FAST replica is not a problem
    assert canarylib.detect_outliers(
        _stage_map(a=100.0, b=110.0, c=105.0, d=0.5)
    ) == {}
    # an ultra-tight stage never flags micro-jitter (the MAD floor)
    assert canarylib.detect_outliers(
        _stage_map(a=1.0, b=1.1, c=1.05, d=1.4)
    ) == {}


def test_detect_outliers_fallback_and_mixed_version():
    # fewer than min_peers carry hop_p99_ms (last-stage replicas, or old
    # peers): the comparison retries on svc_p99_ms
    sm = {
        "a": {"svc_p99_ms": 5.0},
        "b": {"svc_p99_ms": 6.0},
        "c": {"svc_p99_ms": 5.5},
        "d": {"svc_p99_ms": 200.0},
    }
    flagged = canarylib.detect_outliers(sm)
    assert set(flagged) == {"d"} and flagged["d"]["field"] == "svc_p99_ms"
    # mixed-version: records lacking BOTH fields simply don't vote
    sm["old"] = {"load": 1, "cap": 4}
    assert set(canarylib.detect_outliers(sm)) == {"d"}
    # not enough voters on either field: no verdict at all
    assert canarylib.detect_outliers(
        {"a": {"svc_p99_ms": 1.0}, "b": {"svc_p99_ms": 500.0}}
    ) == {}


def test_outlier_penalty_in_routing():
    from inferd_tpu.control.dstar import node_cost
    from inferd_tpu.control.path_finder import min_load_node

    stage = {
        "busy": {"load": 3, "cap": 4},
        "flagged": {"load": 0, "cap": 4, "outlier": 1},
    }
    # the idle-but-flagged replica loses to a 75%-loaded healthy one
    nid, _ = min_load_node(stage)
    assert nid == "busy"
    assert node_cost(stage["flagged"]) > node_cost(stage["busy"])
    # penalty, not exclusion: an all-flagged stage stays routable
    nid, _ = min_load_node({"f1": {"load": 0, "cap": 4, "outlier": 1}})
    assert nid == "f1"


# ------------------------------------------------------------ fleet SLIs


def test_fleet_fixture_check_and_report(capsys):
    """run.sh step 0e's tier-1 gate: the committed collector artifacts
    render a fleet report and pass --check."""
    assert obs_main(["fleet", "--check", FLEET_FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "fleet SLI report" in out
    assert "obs fleet check: OK" in out
    assert "canary: probes/min" in out
    assert "stage 0" in out and "stage 1" in out


def test_fleet_sample_semantics():
    histories = [
        tsdblib.load_history_file(
            os.path.join(FLEET_FIXTURE, f"node{i}.history.json")
        )
        for i in (0, 1)
    ]
    s = fleetlib.fleet_sample(histories)
    # tok/s sums LAST-stage replicas only: node1 (stage 1/2) alone, so
    # the fleet rate equals its per-stage rate — never doubled by depth
    assert s["fleet"]["tok_per_s"] == s["per_stage"]["1"]["tok_per_s"]
    # canary series separated from the user TTFT series
    assert s["canary"]["probe_per_min"] > 0
    assert s["fleet"]["ttft_ms"]["count"] > 0
    # explicit per-stage aggregation naming (the collector-satellite fix)
    assert "hop_p50_med_ms" in s["per_stage"]["0"]
    assert "hop_p99_worst_ms" in s["per_stage"]["0"]
    assert s["per_stage"]["0"]["outliers"] == []


def test_fleet_check_catches_empty_pipeline(tmp_path):
    assert fleetlib.check_samples([]) == ["no fleet samples found"]
    hollow = {"v": 1, "ts": 1.0, "nodes": 0, "fleet": {}, "canary": {},
              "per_stage": {}}
    assert any(
        "zero SLI series" in p for p in fleetlib.check_samples([hollow])
    )
    p = tmp_path / "x.ndjson"
    p.write_text("garbage\n" + json.dumps(hollow) + "\n")
    samples = fleetlib.load_samples([str(p)])
    assert len(samples) == 1  # garbage line skipped, sample loaded


# ------------------------------------------------- exposition / gate / kill


def test_exposition_validates_new_metric_families():
    """Every new series family — canary.*, burn.*, tsdb/replica gauges,
    the windowed generate.* histograms — renders to a valid Prometheus
    exposition (monotone buckets, well-formed lines)."""
    from inferd_tpu.obs import export

    m = Metrics()
    m.inc("canary.probes", 5)
    m.inc("canary.ok", 4)
    m.inc("canary.fail", 1)
    m.observe("canary.wall_ms", 450.0, bounds_ms=[10, 100, 1000, 10000])
    m.observe("canary.ttft_ms", 120.0, bounds_ms=[10, 100, 1000, 10000])
    m.set_gauge("burn.availability", 2.5)
    m.set_gauge("burn.canary", 0.0)
    m.set_gauge("tsdb.overhead_ms", 1.25)
    m.set_gauge("canary.overhead_ms", 0.5)
    m.set_gauge("replica.outlier", 1.0)
    m.inc("generate.requests", 3)
    m.inc("generate.tokens", 24)
    m.inc("stage.tokens", 24)
    m.observe("generate.ttft_ms", 130.0, bounds_ms=[10, 100, 1000])
    m.observe("generate.tpot_ms", 18.0)
    m.observe("generate.wall_ms", 400.0, bounds_ms=[10, 100, 1000])
    text = export.prometheus_text(m, labels={"node": "10.0.0.2:6050"})
    assert export.validate_exposition(text) == []
    assert "inferd_canary_probes_total" in text
    assert "inferd_burn_availability" in text
    assert "inferd_generate_ttft_ms_bucket" in text


def test_gate_budgets_tsdb_and_canary_overhead():
    from inferd_tpu.perf.gate import check_span_overhead

    snap = {
        "gauges": {"tsdb.overhead_ms": 5.0, "canary.overhead_ms": 0.01},
        "histograms": {"stage.compute_ms": {"count": 10, "mean_ms": 10.0}},
    }
    findings = check_span_overhead(snap)
    assert len(findings) == 1 and "tsdb-sampling" in findings[0].message
    snap["gauges"]["canary.overhead_ms"] = 9.0
    assert any(
        "canary-probing" in f.message for f in check_span_overhead(snap)
    )
    snap["gauges"] = {"tsdb.overhead_ms": 0.5, "canary.overhead_ms": 0.5}
    assert check_span_overhead(snap) == []


def test_measured_tsdb_overhead_inside_budget():
    """Acceptance: the measured sampling cost stays under the 1% bar at
    a realistic ratio — the tick runs at 1 Hz, so 1000 samples span
    1000 s of wall time; a SERVING node at even 2.5% compute duty cycle
    (1000 x 25 ms) dwarfs the ~0.1 ms/sample the rings cost."""
    from inferd_tpu.perf.gate import check_span_overhead

    m = Metrics()
    for i in range(40):  # a realistically wide registry
        m.inc(f"c{i}")
        m.observe(f"h{i % 8}", float(i))
    t, clock = _clocked_tsdb(m)
    for _ in range(1000):
        clock[0] += 1.0
        m.inc("c0")
        m.observe("h0", 1.0)
        t.sample()
    snap = {
        "gauges": {"tsdb.overhead_ms": t.overhead_ms},
        "histograms": {"stage.compute_ms": {"count": 1000, "mean_ms": 25.0}},
    }
    assert check_span_overhead(snap) == [], (
        f"1000 samples cost {t.overhead_ms:.1f} ms"
    )


def test_generate_sli_recorder_is_canary_and_kill_switch_gated(monkeypatch):
    import time as _time

    from inferd_tpu.runtime.node import Node

    class Carrier:
        pass

    c = Carrier()
    c.metrics = Metrics()
    sli = {"t0": _time.perf_counter(), "ttft_ms": 12.0, "tokens": 8,
           "canary": False}
    Node._record_generate_sli(c, dict(sli), 200)
    snap = c.metrics.snapshot()
    assert snap["counters"]["generate.requests"] == 1
    assert snap["counters"]["generate.tokens"] == 8
    assert snap["histograms"]["generate.ttft_ms"]["count"] == 1
    assert snap["histograms"]["generate.tpot_ms"]["count"] == 1
    # a 503 shed counts the request and burns the budget, but records
    # NO latency — a 1 ms fast-fail folded into wall_ms would DROP the
    # fleet percentiles during the exact incident they expose
    Node._record_generate_sli(c, dict(sli), 503)
    snap = c.metrics.snapshot()
    assert snap["counters"]["generate.requests"] == 2
    assert snap["counters"]["generate.errors"] == 1
    assert snap["histograms"]["generate.wall_ms"]["count"] == 1
    # a 400 is a caller bug: counted as a request, not as burn
    Node._record_generate_sli(c, dict(sli), 400)
    snap = c.metrics.snapshot()
    assert snap["counters"]["generate.requests"] == 3
    assert snap["counters"]["generate.errors"] == 1
    # canary-tagged: nothing recorded
    c2 = Carrier()
    c2.metrics = Metrics()
    Node._record_generate_sli(c2, dict(sli, canary=True), 200)
    Node._record_generate_sli(c2, dict(sli, canary=True), 500)
    assert c2.metrics.snapshot()["counters"] == {}
    # events kill switch: byte-identical /metrics means NO new series
    monkeypatch.setenv("INFERD_EVENTS", "0")
    c3 = Carrier()
    c3.metrics = Metrics()
    Node._record_generate_sli(c3, dict(sli), 200)
    assert c3.metrics.snapshot()["counters"] == {}


def test_battery_has_canary_smoke_leg():
    from inferd_tpu.tools.bench_battery import SMOKE_LEGS

    legs = dict((n, t) for n, t, _ in SMOKE_LEGS)
    assert "canary_tiny" in legs
    tail = legs["canary_tiny"]
    assert "--config" in tail and "canary" in tail and "--tiny" in tail


# --------------------------------------------------------------- canary unit


async def _serve_canary_target(handler):
    """Tiny aiohttp app standing in for a node's /generate."""
    from aiohttp import web

    app = web.Application()
    app.add_routes([web.post("/generate", handler)])
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, port


@pytest.mark.asyncio
async def test_canary_probe_success_and_failure_paths():
    import aiohttp
    from aiohttp import web

    from inferd_tpu.runtime import wire

    seen_headers = []

    async def good(request):
        seen_headers.append(dict(request.headers))
        env = wire.unpack(await request.read())
        assert env["prompt_ids"] and env["stream"] is True
        resp = web.StreamResponse()
        await resp.prepare(request)
        await resp.write(b'{"t": 5}\n')
        await resp.write(b'{"done": true, "ids": [5, 7]}\n')
        await resp.write_eof()
        return resp

    async def broken(request):
        resp = web.StreamResponse()
        await resp.prepare(request)
        await resp.write(b'{"t": 5}\n')  # stream dies before done
        await resp.write_eof()
        return resp

    class Journal:
        def __init__(self):
            self.events = []

        def emit(self, etype, **attrs):
            self.events.append((etype, attrs))

    for handler, want_ok in ((good, True), (broken, False)):
        runner, port = await _serve_canary_target(handler)
        m = Metrics()
        j = Journal()
        prober = canarylib.CanaryProber(
            lambda p=port: [("127.0.0.1", p)], m, journal=j, timeout_s=5.0,
        )
        prober._http = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=5)
        )
        try:
            rec = await prober.probe_once()
        finally:
            await prober.stop()
            await runner.cleanup()
        assert rec["ok"] is want_ok
        snap = m.snapshot()
        assert snap["counters"]["canary.probes"] == 1
        if want_ok:
            assert snap["counters"]["canary.ok"] == 1
            assert snap["histograms"]["canary.wall_ms"]["count"] == 1
            assert snap["histograms"]["canary.ttft_ms"]["count"] == 1
            assert rec["ttft_ms"] is not None
            assert j.events == []
        else:
            assert snap["counters"]["canary.fail"] == 1
            assert "canary.wall_ms" not in snap["histograms"]
            assert j.events and j.events[0][0] == "canary.fail"

    # the probe marks itself synthetic on the wire
    assert any(
        h.get(canarylib.CANARY_HEADER) == "1" for h in seen_headers
    )


@pytest.mark.asyncio
async def test_canary_probe_no_targets_and_dead_target():
    import aiohttp

    m = Metrics()
    prober = canarylib.CanaryProber(lambda: [], m)
    assert await prober.probe_once() is None
    assert m.snapshot()["counters"] == {}
    prober2 = canarylib.CanaryProber(
        lambda: [("127.0.0.1", 1)], m, timeout_s=2.0,
    )
    prober2._http = aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=2)
    )
    try:
        rec = await prober2.probe_once()
    finally:
        await prober2.stop()
    assert rec["ok"] is False and rec["error"]
    assert m.snapshot()["counters"]["canary.fail"] == 1


# ------------------------------------------------------- node integration


@pytest.mark.asyncio
async def test_node_windowed_gossip_recovers(tiny_parts):  # noqa: F811
    """Node-level acceptance: gossiped hop/svc quantiles come from the
    trailing window — after the slow samples age out, the node's own
    announce stops carrying the elevated p99 (impossible with the PR 3
    all-time source)."""
    nodes = [_mk_node(130, 0, 1, bootstrap_idx=130)]
    await _start_all(nodes)
    n = nodes[0]
    try:
        clock = [5000.0]
        n.tsdb = tsdblib.Tsdb(
            n.metrics, service=n.info.node_id,
            meta={"stage": 0, "num_stages": 1}, clock=lambda: clock[0],
        )
        n.tsdb.sample()
        for _ in range(10):
            clock[0] += 1.0
            n.metrics.observe("hop.relay_ms", 1500.0)
            n.metrics.observe("stage.compute_ms", 800.0)
            n.tsdb.sample()
        n._windowed_cache = (0.0, None)
        wq = n._windowed_gossip()
        assert wq["hop_p99_ms"] >= 1500.0
        assert wq["svc_p99_ms"] >= 800.0
        # recovery minute: fast traffic, slow samples age past horizon
        for _ in range(70):
            clock[0] += 1.0
            n.metrics.observe("hop.relay_ms", 1.0)
            n.metrics.observe("stage.compute_ms", 2.0)
            n.tsdb.sample()
        n._windowed_cache = (0.0, None)
        wq = n._windowed_gossip()
        assert wq["hop_p99_ms"] <= 10.0, wq
        assert wq["svc_p99_ms"] <= 10.0, wq
        # the all-time histograms still remember — the gossip must not
        assert n.metrics.histograms["hop.relay_ms"].quantile(0.99) >= 1500.0
        # idle past the horizon: the keys drop out instead of going stale
        clock[0] += 400.0
        n.tsdb.sample()
        n._windowed_cache = (0.0, None)
        assert "hop_p99_ms" not in n._windowed_gossip()
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_metrics_history_endpoint_schema(tiny_parts):  # noqa: F811
    import aiohttp

    nodes = [_mk_node(131, 0, 1, bootstrap_idx=131)]
    await _start_all(nodes)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{PORTS.http(131)}/metrics/history"
            ) as r:
                assert r.status == 200
                h = await r.json()
        assert tsdblib.validate_history(h) == []
        assert h["service"] == nodes[0].info.node_id
        assert h["meta"] == {"stage": 0, "num_stages": 1}
    finally:
        await _stop_all(nodes)


# ---------------------------------------------------- e2e fault injection


@pytest.mark.asyncio
async def test_outlier_flagging_routing_and_fleet_report(
    tiny_parts, tmp_path,  # noqa: F811
):
    """THE e2e acceptance: one stage-1 replica of three is artificially
    slowed (chaos delay). From windowed telemetry alone it self-flags
    `replica.outlier` (journal event + gossiped flag), every router's
    min-load pick and chain planner route new sessions away from it, the
    canary prober's canary.* series records its probes through the
    degraded fleet — and all of it is re-assembled OFFLINE from the
    per-node artifacts by `obs fleet`."""
    import aiohttp

    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.config import SamplingConfig
    from inferd_tpu.control.path_finder import min_load_node
    from inferd_tpu.runtime import wire
    from inferd_tpu.utils.chaos import Chaos

    parts, _params = tiny_parts
    obs_dir = str(tmp_path / "obs")
    nodes = [
        _mk_node(140, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=140),
        _mk_node(141, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=140),
        _mk_node(142, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=140),
        _mk_node(143, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=140),
    ]
    victim = nodes[3]
    # the quiet degradation — far past any healthy replica's steady
    # p99, so the divergence can't dip below the k*MAD bar mid-test
    victim.chaos = Chaos(delay_ms=600)
    for n in nodes:
        n.trace_dir = obs_dir
        n.tsdb_period_s = 0.1  # test-speed telemetry ticks
        n.window_s = 8.0  # short trailing window: warmup compile
        # spikes age out in seconds instead of a minute
    await _start_all(nodes)
    try:
        import numpy as np

        hidden_sz = nodes[1].cfg.hidden_size

        # chain warmup: compiles the entry's token buckets + self-client
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(140))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            await c.generate_ids([3, 7, 11, 19], max_new_tokens=2)

        async with aiohttp.ClientSession() as s:

            async def post(n, payload, sid):
                body = wire.pack(
                    {"stage": 1, "session_id": sid, "payload": payload}
                )
                async with s.post(
                    f"http://127.0.0.1:{n.info.port}/forward", data=body
                ) as r:
                    assert r.status == 200, await r.text()

            # per-replica warmup: compile the prefill + decode jits every
            # later canary/user request will hit — a first-touch XLA
            # compile on a HEALTHY replica mid-test would spike its
            # window into outlier territory and mask the real signal
            for n in nodes[1:]:
                sid = f"warm-{n.info.port}"
                await post(n, {
                    "hidden": np.zeros((1, 4, hidden_sz), np.float32),
                    "start_pos": 0, "real_len": 4,
                }, sid)
                await post(n, {
                    "hidden": np.zeros((1, 1, hidden_sz), np.float32),
                    "start_pos": 4, "real_len": 1,
                }, sid)

            # steady phase, longer than the window: every stage-1 replica
            # serves identical light traffic until every trailing window
            # holds only steady-state values (+ the victim's chaos delay)
            # — the outlier detector needs >= 3 voters carrying svc_p99_ms
            for rep in range(12):
                for n in nodes[1:]:
                    await post(n, {
                        "hidden": np.zeros((1, 1, hidden_sz), np.float32),
                        "start_pos": 0, "real_len": 1,
                    }, f"s-{n.info.port}-{rep}")
                await asyncio.sleep(0.3)

        # windowed telemetry flags the slowed replica within seconds
        for _ in range(120):
            if victim._outlier_info is not None:
                break
            await asyncio.sleep(0.1)
        assert victim._outlier_info is not None, (
            "victim never self-flagged: "
            f"{victim._windowed_gossip()} vs peers "
            f"{ {k: v.get('svc_p99_ms') for k, v in victim.dht.get_stage(1).items()} }"
        )
        assert victim._outlier_info["field"] in ("hop_p99_ms", "svc_p99_ms")
        evs = [
            ev for ev in victim.journal.events()
            if ev["type"] == "replica.outlier"
        ]
        assert evs, "no replica.outlier journal event"
        assert evs[0]["attrs"]["value"] >= evs[0]["attrs"]["median"]

        # the flag gossips to the entry node...
        for _ in range(100):
            rec = nodes[0].dht.get_stage(1).get(victim.info.node_id, {})
            if rec.get("outlier"):
                break
            await asyncio.sleep(0.05)
        assert rec.get("outlier") == 1, rec

        # ...and routing consumes it: with every replica idle, neither the
        # min-load pick nor the chain planner lands on the flagged one
        for _ in range(5):
            nid, _v = min_load_node(nodes[0].dht.get_stage(1))
            assert nid != victim.info.node_id
            chain = nodes[0].path_finder.find_best_chain(1)
            assert chain[0][0] != victim.info.node_id

        # canary probes through the (healthy remainder of the) fleet
        prober = canarylib.CanaryProber(
            lambda: [("127.0.0.1", PORTS.http(140))], nodes[0].metrics,
            journal=nodes[0].journal, tracer=nodes[0].tracer,
            interval_s=60.0, timeout_s=60.0,
        )
        prober._http = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=60)
        )
        try:
            for _ in range(2):
                rec = await prober.probe_once()
                assert rec is not None and rec["ok"], rec
        finally:
            await prober.stop()
        snap = nodes[0].metrics.snapshot()
        assert snap["counters"]["canary.ok"] == 2
        assert snap["counters"].get("generate.requests", 0) == 0, (
            "canary probes leaked into the user SLI series"
        )
        await asyncio.sleep(0.3)  # a telemetry tick samples the canary series

        # a real user request still completes, routed around the outlier
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(140))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            out = await c.generate_ids([3, 7, 11, 19], max_new_tokens=4)
            assert len(out) == 4

        # ---- the real collector pipeline captures the incident: pull
        # every node's /metrics/history, merge into ONE fleet sample,
        # persist as NDJSON (tools/collector --history does exactly this)
        from inferd_tpu.tools.collector import fetch_histories

        artifacts = str(tmp_path / "artifacts")
        histories = await fetch_histories(nodes[0].dht.get_all(2))
        assert len(histories) == 4, "history endpoint missing on a node"
        incident = fleetlib.fleet_sample(histories)
        fleetlib.write_ndjson(
            os.path.join(artifacts, "fleet.ndjson"), incident
        )
        assert victim.info.node_id in incident["per_stage"]["1"]["outliers"]
        assert incident["canary"]["probe_per_min"] > 0
        assert incident["fleet"]["tok_per_s"] is not None

        # ---- offline: the committed artifacts alone reproduce the story
        await _stop_all(nodes)  # final flush writes *.history.json too
        import glob as globlib

        assert len(globlib.glob(os.path.join(obs_dir, "*.history.json"))) == 4
        samples = fleetlib.load_samples([artifacts])
        assert samples, "no fleet sample loaded from the NDJSON artifact"
        s = samples[-1]
        assert victim.info.node_id in s["per_stage"]["1"]["outliers"]
        report = fleetlib.format_report(samples)
        assert "OUTLIER replicas" in report
        assert victim.info.node_id in report
        assert obs_main(["fleet", "--check", artifacts]) == 0
    finally:
        await _stop_all(nodes)
