"""inferd_tpu/obs tests: span recorder + context propagation, Prometheus
exposition, Chrome export, span-merge/skew-correction properties over
shuffled/duplicated/partially-missing JSONL, the merge CLI --check smoke
over the committed fixture, wire trace-key compatibility, the perf-gate
span-overhead check, and the satellite fixes (Profiler.stop wedge,
Histogram.summary lock consistency, dashboard/collector hop columns)."""

import glob
import json
import os
import random

import numpy as np
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.obs import export, merge, trace
from inferd_tpu.runtime import wire
from inferd_tpu.utils.metrics import Metrics

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "data", "spans")


# ------------------------------------------------------------- recorder


def test_span_recorder_ring_cap_and_stats():
    rec = trace.SpanRecorder("svc", cap=16)
    for i in range(40):
        rec.record_span("s", "compute", float(i), float(i) + 0.5)
    assert len(rec) == 16
    st = rec.stats()
    assert st["recorded"] == 40
    assert st["dropped"] == 24
    assert st["buffered"] == 16
    assert st["overhead_ms"] >= 0


def test_span_context_nesting_and_propagation_surfaces():
    rec = trace.SpanRecorder("svc")
    assert trace.current() is None
    with rec.span("root", "client") as root_ctx:
        assert trace.current() == root_ctx
        assert trace.wire_ctx() == {"id": root_ctx.trace_id, "span": root_ctx.span_id}
        hdr = trace.header_ctx()
        assert hdr == {trace.TRACE_HEADER: root_ctx.to_header()}
        with rec.span("child", "wire") as child_ctx:
            assert child_ctx.trace_id == root_ctx.trace_id
        # context restored after the child block
        assert trace.current() == root_ctx
    assert trace.current() is None
    spans = {s["name"]: s for s in rec.spans()}
    assert spans["child"]["parent"] == root_ctx.span_id
    assert spans["root"]["parent"] is None
    assert spans["child"]["t0"] >= spans["root"]["t0"]
    assert spans["child"]["t1"] <= spans["root"]["t1"]
    # header/wire round trips
    assert trace.SpanContext.from_header(root_ctx.to_header()) == root_ctx
    assert trace.SpanContext.from_wire(root_ctx.to_wire()) == root_ctx
    assert trace.SpanContext.from_wire({"bogus": 1}) is None
    assert trace.SpanContext.from_header(None) is None


def test_recorder_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("INFERD_TRACE", "0")
    rec = trace.SpanRecorder("svc")
    assert rec.record_span("s", "compute", 0.0, 1.0) is None
    with rec.span("root", "client") as ctx:
        assert ctx is None
        assert trace.wire_ctx() is None
        assert trace.header_ctx() is None
    assert len(rec) == 0


def test_recorder_dump_jsonl_drains_and_appends(tmp_path):
    rec = trace.SpanRecorder("svc")
    rec.record_span("a", "compute", 0.0, 1.0)
    path = str(tmp_path / "svc.spans.jsonl")
    assert rec.dump_jsonl(path) == 1
    assert len(rec) == 0
    rec.record_span("b", "compute", 1.0, 2.0)
    assert rec.dump_jsonl(path) == 1
    names = [json.loads(ln)["name"] for ln in open(path)]
    assert names == ["a", "b"]


def test_recorder_flush_jsonl_keeps_ring_live(tmp_path):
    """The periodic exporter must NOT drain the ring: /spans and the
    gossiped hop quantiles read the live buffer between flushes, while
    the JSONL file receives every span exactly once."""
    rec = trace.SpanRecorder("svc")
    rec.record_span("a", "relay", 0.0, 1.0)
    rec.record_span("b", "relay", 1.0, 2.0)
    path = str(tmp_path / "svc.spans.jsonl")
    assert rec.flush_jsonl(path) == 2
    assert len(rec) == 2  # ring intact
    assert rec.flush_jsonl(path) == 0  # nothing new: no duplicates
    rec.record_span("c", "relay", 2.0, 3.0)
    assert rec.flush_jsonl(path) == 1  # only the new span appends
    names = [json.loads(ln)["name"] for ln in open(path)]
    assert names == ["a", "b", "c"]


# ------------------------------------------------- wire envelope compat


def test_disabled_tracing_envelope_byte_identical(monkeypatch):
    """Acceptance: tracing disabled-by-config leaves the /forward envelope
    byte-identical to the untraced format."""
    monkeypatch.setenv("INFERD_TRACE", "0")
    # the envelope's opaque task id (no uuid4 since PR 54: trace.new_id)
    monkeypatch.setattr(trace, "new_id", lambda: "%016x" % 7)
    env = SwarmClient([("127.0.0.1", 1)])._forward_env("sess", [1, 2, 3], 5)
    assert set(env) == {"task_id", "session_id", "stage", "payload"}
    manual = {
        "task_id": "%016x" % 7,
        "session_id": "sess",
        "stage": 0,
        "payload": {
            "tokens": np.asarray([[1, 2, 3]], dtype=np.int32),
            "start_pos": 5,
            "real_len": 3,
        },
    }
    assert wire.pack(env) == wire.pack(manual)
    # enabled, inside a step span: the ONLY delta is the trace key
    monkeypatch.setenv("INFERD_TRACE", "1")
    rec = trace.SpanRecorder("client")
    with rec.span("step", "wire") as ctx:
        env2 = SwarmClient([("127.0.0.1", 1)])._forward_env("sess", [1, 2, 3], 5)
    assert set(env2) == set(env) | {"trace"}
    assert env2["trace"] == {"id": ctx.trace_id, "span": ctx.span_id}
    # enabled but NO active context: still no trace key
    assert "trace" not in SwarmClient([("127.0.0.1", 1)])._forward_env("sess", [1], 0)


def test_wire_trace_key_round_trips_both_generations(monkeypatch):
    """v1 nodes round-trip envelopes carrying `trace`; legacy decoders
    tolerate (ignore) it — toggled per call via INFERD_WIRE, no reimport."""
    env = {
        "task_id": "t",
        "session_id": "s",
        "stage": 1,
        "payload": {
            "tokens": np.asarray([[1, 2]], dtype=np.int32),
            "start_pos": 0,
            "real_len": 2,
        },
        "trace": {"id": "abc123", "span": "def456"},
    }
    for mode in ("v1", "legacy", "v1"):
        monkeypatch.setenv("INFERD_WIRE", mode)
        out = wire.unpack(wire.pack(env))
        assert out["trace"] == {"id": "abc123", "span": "def456"}
        assert out["session_id"] == "s" and out["stage"] == 1
        np.testing.assert_array_equal(
            out["payload"]["tokens"], env["payload"]["tokens"]
        )
    # a legacy (msgpack-only) decoder sees the trace key as a plain dict
    # and the rest of the envelope intact — unknown keys are ignored by
    # every handler, so mixed-version swarms interoperate
    legacy_blob = wire.pack_legacy(env)
    out = wire.unpack(legacy_blob)
    assert out["trace"]["id"] == "abc123"


# ------------------------------------------------------------ prometheus


def test_prometheus_exposition_valid_and_complete():
    m = Metrics()
    m.inc("forward.requests", 3)
    m.inc("hop.bytes_total", 1024)
    m.set_gauge("kv.bytes", 12345)
    m.set_gauge("inflight", 2)
    m.observe("stage.compute_ms", 7.0)
    m.observe("stage.compute_ms", 0.05)
    m.observe("stage.compute_ms", 99999.0)  # lands in +Inf bucket
    text = export.prometheus_text(m, labels={"node": "1.2.3.4:6050"})
    assert export.validate_exposition(text) == []
    assert 'inferd_forward_requests_total{node="1.2.3.4:6050"} 3' in text
    assert 'inferd_kv_bytes{node="1.2.3.4:6050"} 12345' in text
    assert "# TYPE inferd_inflight gauge" in text
    assert "# TYPE inferd_stage_compute_ms histogram" in text
    assert 'le="+Inf"} 3' in text
    assert 'inferd_stage_compute_ms_count{node="1.2.3.4:6050"} 3' in text


def test_prometheus_name_sanitization_and_validator_catches_garbage():
    m = Metrics()
    m.inc("weird-name.with/slash")
    text = export.prometheus_text(m)
    assert "inferd_weird_name_with_slash_total 1" in text
    assert export.validate_exposition(text) == []
    assert export.validate_exposition("not a metric line!\n") != []
    assert export.validate_exposition("x_bucket 2\nx_bucket 1\n") != []


def test_chrome_trace_events():
    spans = [
        {"trace": "t1", "span": "a", "parent": None, "name": "root",
         "phase": "client", "service": "client", "t0": 10.0, "t1": 10.5},
        {"trace": "t1", "span": "b", "parent": "a", "name": "step",
         "phase": "wire", "service": "nodeA", "t0": 10.1, "t1": 10.4,
         "attrs": {"stage": 0}},
    ]
    out = export.chrome_trace(spans, offsets={"nodeA": -1.0})
    evs = out["traceEvents"]
    assert len(evs) == 2
    root, step = evs
    assert root["ph"] == "X" and root["pid"] == "client"
    assert root["ts"] == pytest.approx(10.0 * 1e6)
    assert root["dur"] == pytest.approx(0.5 * 1e6)
    assert step["ts"] == pytest.approx(9.1 * 1e6)  # offset applied
    assert step["args"]["parent"] == "a" and step["args"]["stage"] == 0


# ------------------------------------------------------- merge properties


def _mk_skewed_trace(skew_b=5.0, trace_id="t1"):
    """client -> nodeA -> nodeB synthetic trace; nodeB's clock is ahead by
    `skew_b` seconds. Returns {service: [span, ...]} in TRUE time + skew."""
    out = {"client": [], "A": [], "B": []}

    def add(svc, sid, parent, name, phase, t0, t1, skew=0.0, **attrs):
        s = {"trace": trace_id, "span": sid, "parent": parent, "name": name,
             "phase": phase, "service": svc,
             "t0": t0 + skew, "t1": t1 + skew}
        if attrs:
            s["attrs"] = attrs
        out[svc].append(s)

    add("client", "root", None, "generate", "client", 0.0, 1.0)
    add("client", "step", "root", "step", "wire", 0.05, 0.95)
    add("client", "samp", "root", "sample", "sample", 0.96, 0.97)
    add("A", "af", "step", "forward", "server", 0.10, 0.90, stage=0)
    add("A", "aq", "af", "queue", "queue", 0.11, 0.12, stage=0)
    add("A", "ac", "af", "compute", "compute", 0.12, 0.50, stage=0)
    add("A", "ar", "af", "relay", "relay", 0.52, 0.88, stage=1)
    add("B", "bf", "ar", "forward", "server", 0.55, 0.85, skew=skew_b, stage=1)
    add("B", "bq", "bf", "queue", "queue", 0.56, 0.57, skew=skew_b, stage=1)
    add("B", "bc", "bf", "compute", "compute", 0.57, 0.84, skew=skew_b, stage=1)
    return out


def _write_files(tmp_path, by_svc, shuffle=True, dup=0, seed=0):
    rng = random.Random(seed)
    paths = []
    for svc, spans in by_svc.items():
        spans = list(spans)
        if shuffle:
            rng.shuffle(spans)
        spans += [spans[i % len(spans)] for i in range(dup)]
        p = tmp_path / f"{svc}.spans.jsonl"
        with open(p, "w") as f:
            for s in spans:
                f.write(json.dumps(s) + "\n")
        paths.append(str(p))
    return paths


def test_merge_corrects_clock_skew_on_shuffled_duplicated_input(tmp_path):
    by_svc = _mk_skewed_trace(skew_b=5.0)
    paths = _write_files(tmp_path, by_svc, shuffle=True, dup=3)
    result = merge.merge_paths(paths)
    assert result["skipped_lines"] == 0
    # B's clock ran 5 s ahead; the hop send/recv anchors (A's relay span
    # bracketing B's forward span) pin the correction
    assert result["offsets"]["client"] == 0.0
    assert result["offsets"]["B"] == pytest.approx(-5.0, abs=0.05)
    assert len(result["traces"]) == 1
    t = result["traces"][0]
    assert t["nest_violations"] == []
    assert t["spans"] == 10  # duplicates deduped
    assert t["services"] == ["A", "B", "client"]
    assert t["wall_ms"] == pytest.approx(1000.0, abs=1.0)
    assert t["tokens"] == 1
    assert t["ttft_ms"] == pytest.approx(970.0, abs=1.0)
    assert t["stages"]["0"]["compute_ms"] == pytest.approx(380.0, abs=1.0)
    assert t["stages"]["1"]["compute_ms"] == pytest.approx(270.0, abs=1.0)
    # skew-corrected spans nest: B's forward lies inside A's relay
    by_id = {s["span"]: s for s in result["spans"]}
    assert by_id["bf"]["t0"] >= by_id["ar"]["t0"]
    assert by_id["bf"]["t1"] <= by_id["ar"]["t1"]


def test_merge_tolerates_missing_spans_and_bad_lines(tmp_path):
    by_svc = _mk_skewed_trace(skew_b=2.0)
    # drop nodeB's forward span (the cross-node parent): its children
    # become orphans, the trace still merges
    by_svc["B"] = [s for s in by_svc["B"] if s["span"] != "bf"]
    paths = _write_files(tmp_path, by_svc, shuffle=True)
    with open(tmp_path / "garbage.spans.jsonl", "w") as f:
        f.write("{truncated\n")
        f.write(json.dumps({"trace": "t1", "span": "x"}) + "\n")  # no times
        f.write("\n")
    result = merge.merge_paths([str(tmp_path)])
    assert result["skipped_lines"] == 2
    assert len(result["traces"]) == 1
    t = result["traces"][0]
    assert t["root"]["name"] == "generate"
    # orphans (parent missing) are never nesting violations
    assert t["nest_violations"] == []
    assert t["spans"] == 9


def test_merge_multiple_traces_sorted(tmp_path):
    a = _mk_skewed_trace(skew_b=0.0, trace_id="t-early")
    b = _mk_skewed_trace(skew_b=0.0, trace_id="t-late")
    for spans in b.values():
        for s in spans:
            s["t0"] += 100.0
            s["t1"] += 100.0
    both = {svc: a[svc] + b[svc] for svc in a}
    result = merge.merge_paths(_write_files(tmp_path, both))
    assert [t["trace"] for t in result["traces"]] == ["t-early", "t-late"]
    assert all(t["nest_violations"] == [] for t in result["traces"])


def test_merge_cli_check_over_committed_fixture(tmp_path):
    from inferd_tpu.obs.__main__ import main

    out = tmp_path / "traces.json"
    chrome = tmp_path / "chrome.json"
    rc = main([
        "merge", "--check", "--out", str(out), "--chrome", str(chrome),
        FIXTURE_DIR,
    ])
    assert rc == 0
    data = json.load(open(out))
    assert len(data["traces"]) == 1
    t = data["traces"][0]
    assert t["nest_violations"] == []
    assert set(t["stages"]) == {"0", "1", "2"}
    # the fixture's node clocks are skewed +2.5 s / -1.25 s; the merge
    # recovered the corrections from the hop anchors alone
    assert data["offsets"]["10.0.0.11:6050"] == pytest.approx(-2.5, abs=0.05)
    assert data["offsets"]["10.0.0.13:6050"] == pytest.approx(1.25, abs=0.05)
    ev = json.load(open(chrome))
    assert len(ev["traceEvents"]) == t["spans"]


def test_merge_cli_check_fails_on_garbage(tmp_path):
    from inferd_tpu.obs.__main__ import main

    p = tmp_path / "bad.spans.jsonl"
    p.write_text("{nope\n")
    assert main(["merge", "--check", str(p)]) == 1


# ---------------------------------------------------------- gate overhead


def test_gate_span_overhead_check():
    from inferd_tpu.perf.gate import check_span_overhead

    snap = {
        "gauges": {"trace.overhead_ms": 5.0},
        "histograms": {"stage.compute_ms": {"count": 10, "mean_ms": 10.0}},
    }
    findings = check_span_overhead(snap)  # 5 ms on 100 ms compute: 5%
    assert len(findings) == 1
    assert findings[0].severity == "warning"
    assert findings[0].check == "overhead"
    snap["gauges"]["trace.overhead_ms"] = 0.5  # 0.5% — inside budget
    assert check_span_overhead(snap) == []
    assert check_span_overhead({}) == []
    # counters fallback (older snapshot shape)
    assert check_span_overhead({
        "counters": {"trace.overhead_ms": 50.0},
        "histograms": {"stage.compute_ms": {"count": 10, "mean_ms": 10.0}},
    })[0].severity == "warning"


def test_perf_check_cli_stats_flag(tmp_path, capsys):
    from inferd_tpu.perf.__main__ import main
    from inferd_tpu.perf.gate import DEFAULT_ARTIFACT

    p = tmp_path / "stats.json"
    p.write_text(json.dumps({
        "gauges": {"trace.overhead_ms": 50.0},
        "histograms": {"stage.compute_ms": {"count": 10, "mean_ms": 10.0}},
    }))
    rc = main(["check", "--artifact", DEFAULT_ARTIFACT, "--stats", str(p)])
    assert rc == 0  # overhead findings are warning-severity only
    assert "span-recording overhead" in capsys.readouterr().out


# ---------------------------------------------------------- satellite fixes


def test_profiler_stop_unwedges_after_failure(monkeypatch, tmp_path):
    """A capture whose session raises as it stops must not leave the profiler
    stuck 'running' forever (the /profile endpoint would 409 every
    subsequent start with no recovery short of a restart)."""
    from jax._src.lib import _profiler

    from inferd_tpu.utils.profiling import Profiler

    class Session:
        ends = [RuntimeError("trace finalization failed"), b"xspace"]

        def __init__(self, options):
            pass

        def stop(self):
            end = self.ends.pop(0)
            if isinstance(end, Exception):
                raise end
            return end

    monkeypatch.setattr(_profiler, "ProfilerSession", Session)
    p = Profiler(base_dir=str(tmp_path))
    p.start("x")
    with pytest.raises(RuntimeError, match="finalization failed"):
        p.stop()
    assert p.active_dir is None  # cleared despite the failure
    # fully recovered: start works again (no "already running" 409)...
    d = p.start("y")
    # ...and a clean stop returns the new dir, the session's bytes in it
    assert p.stop() == d
    (written,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    with open(written, "rb") as f:
        assert f.read() == b"xspace"
    # a second stop correctly reports nothing running
    with pytest.raises(RuntimeError, match="no profile running"):
        p.stop()


def test_histogram_summary_single_lock_snapshot(monkeypatch):
    """summary() must compute every quantile from ONE locked snapshot: a
    concurrent observe between per-quantile lock acquisitions could yield
    quantiles disagreeing with the summary's own count."""
    from inferd_tpu.utils import metrics as mlib

    h = mlib.Histogram()
    for v in (1.0, 2.0, 3.0, 500.0):
        h.observe(v)

    def poisoned(self, q):
        raise AssertionError("summary() must not re-lock via quantile()")

    monkeypatch.setattr(mlib.Histogram, "quantile", poisoned)
    s = h.summary()
    assert s["count"] == 4
    assert s["mean_ms"] == pytest.approx(126.5)
    assert s["p50_ms"] == 2.5
    assert s["p99_ms"] == 1000.0


def test_metrics_gauges_in_snapshot():
    m = Metrics()
    m.set_gauge("inflight", 3)
    m.set_gauge("inflight", 1)  # last write wins
    snap = m.snapshot()
    assert snap["gauges"] == {"inflight": 1.0}
    counters, gauges, hists = m.export_state()
    assert gauges == {"inflight": 1.0}
    assert counters == {} and hists == {}


# ----------------------------------------------------- console tool columns


def test_dashboard_hop_latency_column():
    from inferd_tpu.tools.dashboard import render_table

    sample = {
        0: {
            "10.0.0.2:6050": {
                "name": "n0", "load": 1, "cap": 4, "model": "m",
                "hop_p50_ms": 12.0, "hop_p99_ms": 80.0,
            },
            "10.0.0.3:6050": {"name": "n1", "load": 0, "cap": 4, "model": "m"},
        },
    }
    text = render_table(sample, ts=0.0)
    # PR 7: separate columns with independent fallbacks (the single
    # merged "p50/p99" cell blanked both when either side was missing)
    assert "hop p50" in text and "hop p99" in text
    row = next(ln.split() for ln in text.splitlines() if "10.0.0.2" in ln)
    assert row[4] == "12" and row[5] == "80"  # windowed quantiles rendered
    row = next(ln.split() for ln in text.splitlines() if "10.0.0.3" in ln)
    assert row[4] == "-" and row[5] == "-"  # no-data cells


def test_collector_hop_latency_fields():
    from inferd_tpu.tools.collector import FIELDS, stage_rows

    assert "hop_p50_ms" in FIELDS and "hop_p99_ms" in FIELDS
    sample = {
        0: {
            "a": {"load": 1, "cap": 4, "hop_p50_ms": 10.0, "hop_p99_ms": 50.0},
            "b": {"load": 0, "cap": 4, "hop_p50_ms": 20.0, "hop_p99_ms": 90.0},
        },
        1: {"c": {"load": 0, "cap": 4}},
    }
    rows = stage_rows(sample, ts=1.0)
    assert rows[0]["hop_p50_ms"] == pytest.approx(15.0)  # median of replicas
    assert rows[0]["hop_p99_ms"] == pytest.approx(90.0)  # worst replica
    assert rows[1]["hop_p50_ms"] == "" and rows[1]["hop_p99_ms"] == ""
    assert set(rows[0]) == set(FIELDS)
