"""Flight-recorder & fleet-health tests: the event journal (ring,
kill switch, trace capture, metrics mirroring), device telemetry
(graceful CPU fallback, KV occupancy, compile watch), the SLO rule
engine (parse/evaluate/verdict/event-rate/peer rules, the offline CLI
over the committed fixture), the postmortem assembly, the merge CLI's
negative-duration clamp, the trace epoch anchor, the extended perf-gate
overhead budget, and the /metrics byte-identity acceptance criterion
with events disabled."""

import json
import os
import time

import pytest

from inferd_tpu.obs import devtel, events, health, merge, postmortem, trace
from inferd_tpu.utils.metrics import Metrics

HEALTH_FIXTURE = os.path.join(os.path.dirname(__file__), "data", "health")


# -------------------------------------------------------------- journal


def test_journal_ring_cap_counts_and_stats():
    j = events.EventJournal("svc", cap=16)
    for i in range(40):
        j.emit("peer.dead", peer=f"n{i}")
    assert len(j) == 16
    st = j.stats()
    assert st["recorded"] == 40 and st["dropped"] == 24
    assert st["buffered"] == 16 and st["overhead_ms"] >= 0
    assert j.counts() == {"peer.dead": 16}
    # seq is a stable per-process ordinal (the JSONL dedup key)
    seqs = [ev["seq"] for ev in j.events()]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_journal_mirrors_event_counters_into_metrics():
    m = Metrics()
    j = events.EventJournal("svc", metrics=m)
    j.emit("session.rescue", session="s")
    j.emit("session.rescue", session="s2")
    j.emit("kv.overflow")
    c = m.snapshot()["counters"]
    assert c["events.session.rescue"] == 2
    assert c["events.kv.overflow"] == 1


def test_journal_trace_capture_explicit_and_contextvar():
    rec = trace.SpanRecorder("svc")
    j = events.EventJournal("svc")
    with rec.span("root", "server") as ctx:
        ev = j.emit("lane.evict", session="s")  # from the contextvar
    assert ev["trace"] == ctx.trace_id
    other = trace.SpanContext("tid123", "sid456")
    ev2 = j.emit("peer.dead", trace=other)  # explicit wins
    assert ev2["trace"] == "tid123"
    ev3 = j.emit("node.start")  # no context in scope: no trace key
    assert "trace" not in ev3


def test_journal_kill_switch_records_nothing(monkeypatch):
    monkeypatch.setenv("INFERD_EVENTS", "0")
    m = Metrics()
    j = events.EventJournal("svc", metrics=m)
    assert j.emit("peer.dead") is None
    assert len(j) == 0
    assert m.snapshot()["counters"] == {}


def test_journal_flush_jsonl_high_water_and_load(tmp_path):
    j = events.EventJournal("svc")
    j.emit("node.start", stage=0)
    j.emit("peer.dead", peer="x")
    path = str(tmp_path / "svc.events.jsonl")
    assert j.flush_jsonl(path) == 2
    assert len(j) == 2  # non-draining
    assert j.flush_jsonl(path) == 0  # nothing new: no duplicates
    j.emit("node.stop")
    assert j.flush_jsonl(path) == 1
    # loader: dedupes, tolerates garbage, time-sorts
    with open(path, "a") as f:
        f.write("{truncated\n")
        f.write(json.dumps({"type": "bogus"}) + "\n")  # no ts
    loaded = events.load_events([str(tmp_path)])
    assert [ev["type"] for ev in loaded] == [
        "node.start", "peer.dead", "node.stop",
    ]
    # dump_jsonl appends the WHOLE ring regardless of the flush mark;
    # the loader dedups the resulting duplicates
    assert j.dump_jsonl(path) == 3
    assert len(events.load_events([path])) == 3


def test_load_events_keeps_both_runs_of_a_restarted_node(tmp_path):
    """A restarted node reuses its node_id and journal file; seq restarts
    at 0 — the per-process run nonce keeps the loader from dropping the
    second run's events as duplicates (the postmortem-critical half)."""
    path = str(tmp_path / "n0.events.jsonl")
    run1 = events.EventJournal("n0")
    run1.emit("node.start", stage=0)
    run1.emit("peer.dead", peer="x")
    run1.flush_jsonl(path)
    run2 = events.EventJournal("n0")  # fresh process: seq restarts at 0
    run2.emit("node.start", stage=0)
    run2.emit("session.rescue", session="s")
    run2.flush_jsonl(path)
    loaded = events.load_events([path])
    assert len(loaded) == 4
    assert [ev["type"] for ev in loaded].count("node.start") == 2


def test_journal_rate_per_min_windows():
    j = events.EventJournal("svc")
    now = trace.now()
    j.emit("node.start", ts=now - 3600.0)  # pins the journal's reach
    for dt in (0.5, 1.0, 2.0):
        j.emit("session.rescue", ts=now - dt)
    j.emit("session.rescue", ts=now - 1800.0)  # outside the window
    assert j.rate_per_min("session.rescue", window_s=60.0) == pytest.approx(
        3.0, rel=0.05
    )
    assert j.rate_per_min("peer.dead") == 0.0
    assert events.EventJournal("empty").rate_per_min("peer.dead") == 0.0
    # a young journal clamps the window to its reach (floored at 30 s):
    # a 20-rescue storm on a node alive ~5 s reads as a storm (40/min,
    # not 20/min diluted over a minute it hasn't lived)...
    young = events.EventJournal("young")
    for i in range(20):
        young.emit("session.rescue", ts=now - 0.25 * i)
    assert young.rate_per_min("session.rescue", window_s=60.0) == (
        pytest.approx(40.0)
    )
    # ...while a SINGLE benign early event amplifies at most 2x — one
    # kv.overflow seconds after start must not breach the <10 rule
    single = events.EventJournal("single")
    single.emit("kv.overflow", ts=now - 2.0)
    assert single.rate_per_min("kv.overflow", window_s=60.0) <= 2.0


# ------------------------------------------------------- epoch anchoring


def test_trace_now_is_anchored_and_monotonic():
    a = trace.now()
    b = trace.now()
    assert b >= a  # perf_counter deltas can't run backwards
    assert abs(trace.now() - time.time()) < 5.0  # still wall-clock epoch


def test_span_durations_non_negative():
    rec = trace.SpanRecorder("svc")
    with rec.span("s", "compute"):
        pass
    (s,) = rec.spans()
    assert s["t1"] >= s["t0"]


def test_merge_counts_and_clamps_negative_duration_spans(tmp_path):
    """A legacy recorder that stamped across an NTP step produced
    t1 < t0; merge must clamp (not skip, not corrupt stage sums)."""
    spans = [
        {"trace": "t1", "span": "r", "parent": None, "name": "generate",
         "phase": "client", "service": "c", "t0": 0.0, "t1": 1.0},
        {"trace": "t1", "span": "neg", "parent": "r", "name": "compute",
         "phase": "compute", "service": "c", "t0": 0.5, "t1": 0.2,
         "attrs": {"stage": 0}},
    ]
    p = tmp_path / "c.spans.jsonl"
    with open(p, "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    result = merge.merge_paths([str(p)])
    assert result["skipped_lines"] == 0
    assert result["clamped_spans"] == 1
    t = result["traces"][0]
    assert t["spans"] == 2
    # clamped to zero duration: the stage sum is not poisoned negative
    assert t["stages"]["0"]["compute_ms"] == 0.0


# --------------------------------------------------------------- devtel


def test_hbm_summary_graceful_on_cpu():
    # CPU backends report no memory_stats: None, never a crash
    assert devtel.hbm_summary() is None or isinstance(
        devtel.hbm_summary(), dict
    )


def test_kv_occupancy_resolution():
    class Pool:
        lengths = [10, 0, 30, 0]
        max_len = 40

    assert devtel.kv_occupancy(Pool()) == pytest.approx(40 / 160)

    class Custom:
        def kv_occupancy(self):
            return 0.5

    assert devtel.kv_occupancy(Custom()) == 0.5
    assert devtel.kv_occupancy(object()) is None


def test_refresh_gauges_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("INFERD_EVENTS", "0")
    m = Metrics()

    class Pool:
        lengths = [10]
        max_len = 10

    devtel.refresh_gauges(m, Pool())
    assert m.snapshot()["gauges"] == {}


def test_compile_watch_detects_jit_compiles():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    m = Metrics()
    j = events.EventJournal("svc")
    watch = devtel.CompileWatch(m, j)
    step = watch.watch(jax.jit(lambda x: x * 2 + 1), "step")
    assert int(step(jnp.int32(3))) == 7  # first call: traces + compiles
    assert watch.compiles == 1
    assert int(step(jnp.int32(4))) == 9  # cached: no new compile
    assert watch.compiles == 1
    step(jnp.float32(1.5))  # new dtype: a real recompile
    assert watch.compiles == 2
    types = [ev["type"] for ev in j.events()]
    assert types.count("compile.begin") == 2
    assert types.count("compile.end") == 2
    ends = [ev for ev in j.events() if ev["type"] == "compile.end"]
    assert all(ev["attrs"]["elapsed_ms"] >= 0 for ev in ends)
    snap = m.snapshot()
    assert snap["counters"]["compile.events"] == 2
    assert snap["histograms"]["compile.ms"]["count"] == 2
    # non-jit callables pass through unwrapped
    plain = devtel.CompileWatch().watch(lambda x: x, "plain")
    assert plain(5) == 5


def test_instrument_executor_wraps_real_jits():
    """Regression: jax.jit products carry functools-style __wrapped__
    themselves, so the double-wrap guard must use its own sentinel — a
    guard on __wrapped__ silently skipped EVERY executor jit and left
    the compile watch dead on the production path."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    class Ex:
        _run = staticmethod(jax.jit(lambda x: x + 1))

    ex = Ex()
    watch = devtel.CompileWatch(Metrics(), events.EventJournal("svc"))
    watch.instrument_executor(ex, label="Ex")
    assert getattr(ex._run, "_compile_watched", False), (
        "instrument_executor left the jitted attr unwrapped"
    )
    assert int(ex._run(jnp.int32(1))) == 2
    assert watch.compiles == 1
    before = ex._run
    watch.instrument_executor(ex, label="Ex")  # idempotent: no re-wrap
    assert ex._run is before

    class Engine:
        _decode_all = staticmethod(jax.jit(lambda x: x * 3))

    class BatchedEx:  # --batch-lanes shape: jits live on .engine
        engine = Engine()

    bex = BatchedEx()
    watch.instrument_executor(bex, label="BatchedEx")
    assert getattr(bex.engine._decode_all, "_compile_watched", False)
    assert int(bex.engine._decode_all(jnp.int32(2))) == 6
    assert watch.compiles == 2


# --------------------------------------------------------------- health


def test_rule_parse_and_errors():
    r = health.Rule.parse("hop.relay_ms.p99_ms < 250")
    assert (r.signal, r.op, r.threshold) == ("hop.relay_ms.p99_ms", "<", 250.0)
    assert r.expr == "hop.relay_ms.p99_ms < 250"
    with pytest.raises(ValueError, match="bad SLO rule"):
        health.Rule.parse("not a rule")
    with pytest.raises(ValueError, match="severity"):
        health.Rule.parse("x < 1", severity="catastrophic")


def test_evaluate_fires_skips_and_ranks_severity():
    snap = {
        "gauges": {"queue.depth": 20.0, "trace.dropped": 0.0},
        "histograms": {"hop.relay_ms": {"p99_ms": 3000.0}},
    }
    rules = [
        health.Rule.parse("queue.depth < 16"),                    # fires
        health.Rule.parse("trace.dropped == 0"),                  # ok
        health.Rule.parse("hop.relay_ms.p99_ms < 2000", "failing"),  # fires
        health.Rule.parse("hbm.frac < 0.95"),                     # skipped
    ]
    v = health.evaluate(rules, snap)
    assert v["status"] == "failing"
    assert v["evaluated"] == 3 and v["skipped"] == 1
    assert {f["rule"] for f in v["firing"]} == {
        "queue.depth < 16", "hop.relay_ms.p99_ms < 2000",
    }
    # degraded when only degraded-severity rules fire
    v2 = health.evaluate(rules[:2], snap)
    assert v2["status"] == "degraded"
    # ok when nothing fires
    assert health.evaluate(rules[1:2], snap)["status"] == "ok"


def test_evaluate_event_rules_count_and_rate():
    now = trace.now()
    evs = [
        {"ts": now - 1.0, "type": "session.rescue", "service": "n"},
        {"ts": now - 2.0, "type": "session.rescue", "service": "n"},
        {"ts": now - 3600.0, "type": "session.rescue", "service": "n"},
    ]
    count_rule = health.Rule.parse("event:session.rescue == 0")
    rate_rule = health.Rule.parse("event:session.rescue/min < 1")
    v = health.evaluate([count_rule, rate_rule], {}, events=evs, now=now)
    assert {f["rule"] for f in v["firing"]} == {
        "event:session.rescue == 0", "event:session.rescue/min < 1",
    }
    # the count rule sees ALL scoped events; the rate rule only the window
    by_rule = {f["rule"]: f["value"] for f in v["firing"]}
    assert by_rule["event:session.rescue == 0"] == 3.0
    assert by_rule["event:session.rescue/min < 1"] == pytest.approx(2.0)
    # no events provided at all -> event rules skip
    v2 = health.evaluate([count_rule], {})
    assert v2["evaluated"] == 0 and v2["skipped"] == 1
    # empty journal -> evaluates to zero, rule passes
    v3 = health.evaluate([count_rule], {}, events=[])
    assert v3["evaluated"] == 1 and v3["status"] == "ok"


def test_evaluate_peer_rules():
    rule = health.Rule.parse("peer:hop_p99_ms < 100")
    peers = {
        "10.0.0.2:6050": {"hop_p99_ms": 50.0},
        "10.0.0.3:6050": {"hop_p99_ms": 900.0},
    }
    v = health.evaluate([rule], {}, peers=peers)
    assert v["firing"][0]["peer"] == "10.0.0.3:6050"
    assert v["firing"][0]["value"] == 900.0
    # no peers (None), an EMPTY peer map (single-replica swarm), and
    # peers that don't carry the field all SKIP: no data is not passing
    assert health.evaluate([rule], {})["skipped"] == 1
    assert health.evaluate([rule], {}, peers={})["skipped"] == 1
    assert health.evaluate(
        [rule], {}, peers={"a": {"load": 1}}
    )["skipped"] == 1


def test_health_cli_check_over_committed_fixture(capsys):
    from inferd_tpu.obs.__main__ import main

    assert main(["health", "--check", HEALTH_FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "obs health check: OK" in out


def test_health_cli_check_fails_on_breach(tmp_path):
    from inferd_tpu.obs.__main__ import main

    (tmp_path / "bad.stats.json").write_text(json.dumps({
        "gauges": {"hbm.frac": 0.99, "queue.depth": 0, "trace.dropped": 0},
    }))
    assert main(["health", "--check", str(tmp_path)]) == 1
    # custom rules file overrides the defaults
    (tmp_path / "rules.json").write_text(json.dumps(
        [{"rule": "hbm.frac < 1.5", "severity": "failing"}]
    ))
    assert main(["health", "--check", str(tmp_path)]) == 0


def test_prefixed_signals_no_engine_knows_are_skipped_never_green(tmp_path, capsys):
    """A rules file written for an older build may still name `roofline:`
    / `phase:` signals. They resolve as every unknown metric does: the
    rule is skipped and counted as skipped, whatever gauges the scrape
    carries under similar names, and a file of nothing else fails
    `--check` (zero evaluated is not a pass)."""
    from inferd_tpu.obs.__main__ import main

    (tmp_path / "n.stats.json").write_text(json.dumps({"gauges": {
        "roofline.frac": 0.5, "roofline.live_frac": 0.4, "queue.depth": 0,
    }}))
    gone = ["roofline:frac > 0.02", "phase:attn/frac > 0.1", "roofline:live_frac > 0.1"]
    (tmp_path / "rules.json").write_text(json.dumps(gone + ["queue.depth < 16"]))
    assert main(["health", "--check", str(tmp_path)]) == 0
    assert "(0 firing, 1 evaluated, 3 skipped)" in capsys.readouterr().out
    (tmp_path / "rules.json").write_text(json.dumps(gone))
    assert main(["health", "--check", str(tmp_path)]) == 1
    assert "(0 firing, 0 evaluated, 3 skipped)" in capsys.readouterr().out


# ---------------------------------------------------- /metrics byte parity


def test_metrics_byte_identical_with_events_disabled(monkeypatch):
    """Acceptance: with events disabled, every emit site and gauge
    refresh is a no-op, so the Prometheus exposition is byte-identical
    to a registry the subsystem never touched."""
    from inferd_tpu.obs import export

    def drive(m):
        # the pre-PR instrumentation still runs either way
        m.inc("forward.requests")
        m.observe("stage.compute_ms", 5.0)
        m.set_gauge("inflight", 1)
        # this PR's surfaces: journal, compile watch, devtel gauges
        j = events.EventJournal("n0", metrics=m)
        j.emit("peer.dead", peer="x")
        j.emit("executor.warmup_failed", error="boom")
        watch = devtel.CompileWatch(m, j)
        watch.record("step", 12.0)
        devtel.refresh_gauges(m, None)
        if events.enabled():
            st = j.stats()
            m.set_gauge("events.count", st["recorded"])
            m.set_gauge("events.overhead_ms", st["overhead_ms"])
        return m

    monkeypatch.setenv("INFERD_EVENTS", "0")
    disabled = export.prometheus_text(drive(Metrics()))
    baseline = Metrics()
    baseline.inc("forward.requests")
    baseline.observe("stage.compute_ms", 5.0)
    baseline.set_gauge("inflight", 1)
    assert disabled == export.prometheus_text(baseline)
    monkeypatch.setenv("INFERD_EVENTS", "1")
    enabled_text = export.prometheus_text(drive(Metrics()))
    assert "inferd_events_peer_dead_total" in enabled_text
    assert "inferd_events_executor_warmup_failed_total" in enabled_text
    assert "inferd_compile_events_total" in enabled_text
    assert "inferd_events_overhead_ms" in enabled_text
    assert export.validate_exposition(enabled_text) == []


# ------------------------------------------------------- gate extension


def test_gate_budgets_event_journal_overhead():
    from inferd_tpu.perf.gate import check_span_overhead

    snap = {
        "gauges": {"trace.overhead_ms": 0.5, "events.overhead_ms": 5.0},
        "histograms": {"stage.compute_ms": {"count": 10, "mean_ms": 10.0}},
    }
    findings = check_span_overhead(snap)  # events at 5%, spans at 0.5%
    assert len(findings) == 1
    assert "event-journal" in findings[0].message
    snap["gauges"]["events.overhead_ms"] = 0.5
    assert check_span_overhead(snap) == []
    snap["gauges"]["trace.overhead_ms"] = 9.0
    assert "span-recording" in check_span_overhead(snap)[0].message


def test_measured_journal_overhead_inside_budget():
    """Acceptance: a realistic emit volume stays under the 1% budget
    against a plausible compute accumulation (1000 steps x 10 ms)."""
    from inferd_tpu.perf.gate import check_span_overhead

    m = Metrics()
    j = events.EventJournal("n0", metrics=m)
    for i in range(1000):
        j.emit("session.rescue", session=f"s{i % 7}", stage=1, holder="x")
    snap = {
        "gauges": {"events.overhead_ms": j.stats()["overhead_ms"]},
        "histograms": {"stage.compute_ms": {"count": 1000, "mean_ms": 10.0}},
    }
    assert check_span_overhead(snap) == [], (
        f"1000 events cost {j.stats()['overhead_ms']} ms"
    )


# ----------------------------------------------------------- postmortem


def _incident_artifacts(tmp_path):
    """Synthetic 2-node incident: client -> A relays to B; B's clock is
    skewed +2 s; a peer.dead on A mid-relay and a session.rescue on B,
    plus per-node metrics snapshots."""
    tid = "inc00000000000001"
    spans = {
        "client": [
            {"trace": tid, "span": "r", "parent": None, "name": "generate",
             "phase": "client", "service": "client", "t0": 100.0, "t1": 101.0},
            {"trace": tid, "span": "st", "parent": "r", "name": "step",
             "phase": "wire", "service": "client", "t0": 100.05, "t1": 100.95},
        ],
        "A": [
            {"trace": tid, "span": "af", "parent": "st", "name": "forward",
             "phase": "server", "service": "A", "t0": 100.1, "t1": 100.9,
             "attrs": {"stage": 0}},
            {"trace": tid, "span": "ac", "parent": "af", "name": "compute",
             "phase": "compute", "service": "A", "t0": 100.12, "t1": 100.3,
             "attrs": {"stage": 0}},
            {"trace": tid, "span": "ar", "parent": "af", "name": "relay",
             "phase": "relay", "service": "A", "t0": 100.32, "t1": 100.88,
             "attrs": {"stage": 1}},
        ],
        "B": [
            {"trace": tid, "span": "bf", "parent": "ar", "name": "forward",
             "phase": "server", "service": "B", "t0": 102.4, "t1": 102.85,
             "attrs": {"stage": 1}},
            {"trace": tid, "span": "br", "parent": "bf", "name": "relay",
             "phase": "rescue", "service": "B", "t0": 102.45, "t1": 102.8,
             "attrs": {"stage": 1}},
        ],
    }
    evs = {
        "A": [
            {"ts": 100.35, "type": "peer.dead", "service": "A",
             "trace": tid, "attrs": {"peer": "dead:1", "stage": 1}, "seq": 0},
        ],
        "B": [
            {"ts": 102.5, "type": "session.rescue", "service": "B",
             "trace": tid, "attrs": {"holder": "dead:1"}, "seq": 0},
            # fleet context WITHOUT the trace id, inside the window
            {"ts": 102.6, "type": "lane.evict", "service": "B",
             "attrs": {"session": "other"}, "seq": 1},
            # far outside the window and traceless: excluded
            {"ts": 500.0, "type": "node.stop", "service": "B", "seq": 2},
        ],
    }
    mets = {
        "A": {"ts": 100.5, "service": "A",
              "gauges": {"hbm.frac": 0.97, "trace.dropped": 0.0},
              "counters": {}, "histograms": {}},
        "B": {"ts": 102.6, "service": "B",
              "gauges": {"trace.dropped": 0.0}, "counters": {},
              "histograms": {}},
    }
    for svc in ("client", "A", "B"):
        with open(tmp_path / f"{svc}.spans.jsonl", "w") as f:
            for s in spans[svc]:
                f.write(json.dumps(s) + "\n")
        if svc in evs:
            with open(tmp_path / f"{svc}.events.jsonl", "w") as f:
                for ev in evs[svc]:
                    f.write(json.dumps(ev) + "\n")
        if svc in mets:
            with open(tmp_path / f"{svc}.metrics.jsonl", "w") as f:
                f.write(json.dumps(mets[svc]) + "\n")
    return tid


def test_postmortem_report_assembly(tmp_path):
    tid = _incident_artifacts(tmp_path)
    report = postmortem.build_report(tid, [str(tmp_path)])
    # merged per-stage timeline, skew-corrected (B ran +2 s fast)
    assert set(report["timeline"]["stages"]) == {"0", "1"}
    assert report["offsets"]["B"] == pytest.approx(-2.0, abs=0.1)
    # events: the trace's own + windowed fleet context, never the
    # out-of-window traceless one; B's event ts got B's clock correction
    types = {ev["type"] for ev in report["events"]}
    assert types == {"peer.dead", "session.rescue", "lane.evict"}
    rescue = next(
        ev for ev in report["events"] if ev["type"] == "session.rescue"
    )
    assert rescue["ts"] == pytest.approx(100.5, abs=0.1)
    # interleaved log is time-sorted and mixes spans with events
    ts = [e["t"] for e in report["entries"]]
    assert ts == sorted(ts)
    assert {e["kind"] for e in report["entries"]} == {"span", "event"}
    # SLO: peer.dead fires on A (count rule), hbm breach fires from A's
    # metrics snapshot
    fired = {(f["service"], f["rule"]) for f in report["firing"]}
    assert ("A", "event:peer.dead == 0") in fired
    assert ("A", "hbm.frac < 0.95") in fired
    # first divergent hop: A's relay overlaps the peer.dead event
    div = report["first_divergent_hop"]
    assert div["service"] == "A" and div["phase"] == "relay"
    assert "peer.dead" in div["reason"]
    # unknown trace raises (and the CLI turns it into exit 1)
    with pytest.raises(ValueError, match="no spans"):
        postmortem.build_report("nope", [str(tmp_path)])


def test_postmortem_cli(tmp_path, capsys):
    from inferd_tpu.obs.__main__ import main

    tid = _incident_artifacts(tmp_path)
    out = tmp_path / "report.json"
    assert main(["postmortem", tid, str(tmp_path), "--out", str(out)]) == 0
    text = capsys.readouterr().out
    assert "first divergent hop" in text
    assert "session.rescue" in text
    data = json.load(open(out))
    assert data["trace"] == tid and data["firing"]
    assert main(["postmortem", "missing", str(tmp_path)]) == 1


# ------------------------------------------------------- console columns


def test_dashboard_health_hbm_compile_columns():
    from inferd_tpu.tools.dashboard import render_table

    sample = {
        0: {
            "10.0.0.2:6050": {
                "name": "n0", "load": 1, "cap": 4, "model": "m",
                "hbm": 0.62, "compiles": 7, "health": "ok",
            },
            "10.0.0.3:6050": {
                "name": "n1", "load": 0, "cap": 4, "model": "m",
                "health": "failing",
            },
        },
    }
    text = render_table(sample, ts=0.0)
    assert "hbm%" in text and "compiles" in text and "health" in text
    assert "62%" in text and " 7 " in text
    assert "ok" in text and "failing" in text


def test_collector_hbm_and_health_fields():
    from inferd_tpu.tools.collector import FIELDS, stage_rows

    assert "hbm_frac" in FIELDS and "health" in FIELDS
    sample = {
        0: {
            "a": {"load": 1, "cap": 4, "hbm": 0.5, "health": "ok"},
            "b": {"load": 0, "cap": 4, "hbm": 0.9, "health": "degraded"},
        },
        1: {"c": {"load": 0, "cap": 4}},
    }
    rows = stage_rows(sample, ts=1.0)
    assert rows[0]["hbm_frac"] == pytest.approx(0.9)  # worst replica
    assert rows[0]["health"] == "degraded"  # worst replica's verdict
    assert rows[1]["hbm_frac"] == "" and rows[1]["health"] == ""
    assert set(rows[0]) == set(FIELDS)


def test_collector_unknown_health_never_displaces_failing():
    """Mixed-version gossip: an unrecognized verdict string ranks above
    ok/degraded (suspicious) but must NEVER outrank a real failing
    replica in the worst-replica column."""
    from inferd_tpu.tools.collector import stage_rows

    sample = {
        0: {
            "a": {"load": 0, "cap": 4, "health": "failing"},
            "b": {"load": 0, "cap": 4, "health": "unknown-verdict"},
        },
        1: {
            "c": {"load": 0, "cap": 4, "health": "ok"},
            "d": {"load": 0, "cap": 4, "health": "unknown-verdict"},
        },
    }
    rows = stage_rows(sample, ts=1.0)
    assert rows[0]["health"] == "failing"
    assert rows[1]["health"] == "unknown-verdict"


def test_default_rules_survive_event_kill_switch():
    """INFERD_EVENTS=0 makes the node pass events=None to evaluate
    (node._health_state): the event-rate rules must SKIP, but the
    metric-only DEFAULT_RULES keep evaluating — the journal kill switch
    sheds overhead without blinding the SLO engine."""
    snap = {
        "gauges": {"queue.depth": 20.0, "trace.dropped": 0.0},
        "histograms": {"hop.relay_ms": {"p99_ms": 100.0}},
    }
    v = health.evaluate(health.DEFAULT_RULES, snap, events=None)
    n_event = sum(
        1 for r in health.DEFAULT_RULES if r.signal.startswith("event:")
    )
    n_burn = sum(
        1 for r in health.DEFAULT_RULES if r.signal.startswith("burn:")
    )
    n_peer = sum(
        1 for r in health.DEFAULT_RULES if r.signal.startswith("peer:")
    )
    assert v["evaluated"] == 3  # queue.depth, trace.dropped, hop p99
    # every event rule (events=None), every burn rule (histories=None),
    # every peer rule (no peers passed), plus the absent hbm.frac gauge
    assert v["skipped"] == n_event + n_burn + n_peer + 1
    assert {f["rule"] for f in v["firing"]} == {"queue.depth < 16"}
    assert v["status"] == "degraded"
