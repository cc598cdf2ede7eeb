"""Every per-layer metric of the manifest has a reader that run.py finds
by name; on a hand-made run each of EXPECT returns the number its docstring
says, and nothing where there is nothing to read."""

import json
import os

import pytest

import run as harness
from conftest import REPO


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name="qwen3-4b-1chip"):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def a_run():
    """A 10 s window [100, 110] on the harness clock = [1000, 1010] on the
    node's; two sessions taking turns at 50 ms a step."""
    reqs = []
    for c in range(2):
        t = 99.0 + c * 0.025
        reqs.append({"sent": t, "prompt_len": 300, "asked": 400, "error": None, "done": None,
                     "tokens": [1] * 200, "token_t": [t + 0.2 + 0.05 * i for i in range(200)]})
    reqs.append({"sent": 104.0, "prompt_len": 3000, "asked": 4, "error": None, "done": 104.9,
                 "tokens": [1] * 4, "token_t": [104.6, 104.7, 104.8, 104.9]})
    spans = [{"name": "capture", "t0": 1003.0, "t1": 1007.5}]
    for i in range(100):
        spans.append({"name": "compute", "t0": 1000.05 + 0.1 * i, "t1": 1000.09 + 0.1 * i})
        spans.append({"name": "forward", "t0": 1000.04 + 0.1 * i, "t1": 1000.10 + 0.1 * i})
    ex0 = {"batched_steps": 1000, "batched_tokens": 1100, "lanes_busy": 2}
    ex1 = {"batched_steps": 1200, "batched_tokens": 1400, "lanes_busy": 2}
    mem = [{"bytes_in_use": 11e9, "bytes_limit": 16e9, "peak_bytes_in_use": 12e9}]
    return {
        "requests": reqs, "lags_ms": [0.1, 0.2, 0.4], "w0": 100.0, "w1": 110.0,
        "wall0": 1000.0, "wall1": 1010.0, "seconds": 10.0, "spans": spans, "slots": 5,
        "stats0": {"executor": ex0, "compile_cache": {"misses": 7}},
        "stats1": {"executor": ex1, "compile_cache": {"misses": 7}, "device": {"memory": mem}},
        "events0": [], "events1": [{"type": "compile.begin", "ts": 990.0}],
        "polls": [(99.0, {"lanes_busy": 1}), (101.0, {"lanes_busy": 2}), (105.0, {"lanes_busy": 3})],
        "config": config(), "rehearse": False,
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1},
        "trace": {"window_s": 4.0, "busy_s": 3.0, "modules": {
            "jit__decode_logits": {"count": 80, "total_s": 2.6, "median_s": 0.0326},
            "jit__prefill_lane_logits": {"count": 6, "total_s": 0.33, "median_s": 0.055}}},
    }


EXPECT = {
    "loadgen.lag_ms_p99": pytest.approx(0.4),           # only the request sent in the window
    "loadgen.ttft_ms_p95": pytest.approx(600.0),
    "loadgen.ttft_ms_p50": pytest.approx(600.0),
    "loadgen.gap_ms_p95": pytest.approx(50.0),
    "node.forward_ms_p50": pytest.approx(60.0),
    "window.compute_ms_p50": pytest.approx(40.0),
    "window.mean_cobatch": pytest.approx(1.5),
    "kv.sessions_resident_mean": pytest.approx(2.5),
    "engine.compiles_in_window": 0,
    "device.idle_share": pytest.approx(25.0),
    "device.hbm_peak_share": pytest.approx(75.0),
    "kernels.decode_roofline": pytest.approx(30.59, rel=1e-3),   # by hand below
    "kernels.prefill_roofline": pytest.approx(37.62, rel=1e-3),
}


@pytest.mark.parametrize("metric", sorted(EXPECT))
def test_reader_is_found_and_reads(metric):
    value = harness.load_reader(metric)(a_run())
    assert isinstance(value, (int, float))
    assert value == EXPECT[metric]
    if metric.endswith("_roofline"):
        assert 0 < value < 100


def test_every_metric_of_the_manifest_has_a_checked_reader():
    """The readers of the spans inside an executor call read nothing from
    this run, which holds no span with an id: test_span_readers.py feeds
    them. Between the two files no metric of the manifest goes unread."""
    from test_span_readers import NEW

    names = [m["name"] for m in manifest()["per_layer"]]
    assert sorted(names) == sorted([*EXPECT, *NEW])
    for metric in NEW:
        assert harness.load_reader(metric)(a_run()) is None


def test_decode_roofline_by_hand():
    """8.045 GB of weights + the live tokens (two sessions of 300 + 117 at
    the window's middle) x 147 456 B, over 819 GB/s: 9.97 ms; the program's
    median is 32.6 ms."""
    value = harness.load_reader("kernels.decode_roofline")(a_run())
    assert value == pytest.approx(100 * (8.0449e9 + 834 * 147456) / 819e9 / 0.0326, rel=1e-3)


def test_prefill_roofline_counts_the_work_inside_the_traced_stretch():
    run = a_run()  # the 3000-token prompt's send-to-first-token lies inside [103, 107]
    import opsbytes
    work = opsbytes.prefill(run["config"], 3000)
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "compute"
    value = harness.load_reader("kernels.prefill_roofline")(run)
    assert value == pytest.approx(100 * least["seconds"] / 0.33, rel=1e-6)


def test_readers_return_nothing_where_there_is_nothing():
    run = a_run()
    run["trace"]["modules"] = {}
    run["polls"], run["stats1"]["device"] = [], {}
    run["stats1"]["executor"] = run["stats0"]["executor"]
    for metric in ("kernels.decode_roofline", "kernels.prefill_roofline",
                   "kv.sessions_resident_mean", "device.hbm_peak_share", "window.mean_cobatch"):
        assert harness.load_reader(metric)(run) is None


def test_unknown_device_kind_is_an_error():
    import opsbytes
    with pytest.raises(KeyError):
        opsbytes.peaks("TPU v9")
    with pytest.raises(KeyError):
        opsbytes.peaks("cpu")
