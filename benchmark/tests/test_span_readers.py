"""The readers PR 25 added: each is fed a hand-made run (spans with parent
links as /spans gives them, two /stats snapshots) and its number is checked
by hand; each reads None from a run of a program that stamps nothing of the
kind (the parent commit of PR 25)."""

import pytest

import run as harness

NEW = [
    "node.queue_ms_p50", "node.token_host_ms_p50", "window.queue_ms_p50",
    "window.lock_wait_ms_p50", "window.device_ms_p50", "window.prefill_device_ms_p50",
    "window.copy_out_ms_p50", "device.busy_share_host", "device.capture_slowdown",
    "mesh.bubble_share",
]


def span(name, t0, ms, sid=None, parent=None, **attrs):
    s = {"name": name, "t0": t0, "t1": t0 + ms / 1e3, "span": sid or f"{name}@{t0}",
         "parent": parent, "trace": "t"}
    if attrs:
        s["attrs"] = attrs
    return s


def a_run():
    """A 10 s window [1000, 1010] on the node's clock, a capture over
    [1004, 1006]. Ten decode tokens, one every 100 ms from 1001.0; each is
    a `step` of 60 ms over a `forward` of 55 ms over a `queue` of 2 ms and
    a `compute` of 50 ms = batch_wait 3 + lock_wait 10 + device 30 +
    copy_out 5 + 2 of its own. One prefill chunk of 80 ms on the device
    before them, and one decode token before the window."""
    out = [span("capture", 1004.0, 2000.0, capture_id="bench")]
    starts = [999.0] + [1001.0 + 0.1 * i for i in range(10)]
    for i, t in enumerate(starts):
        step, fwd, comp = f"s{i}", f"f{i}", f"c{i}"
        out += [
            span("step", t, 60, step, "g"),
            span("forward", t + 0.002, 55, fwd, step),
            span("queue", t + 0.003, 2, None, fwd),
            span("compute", t + 0.005, 50, comp, fwd, kind="decode", tokens=1),
            span("batch_wait", t + 0.005, 3, None, comp, flusher=1),
            span("lock_wait", t + 0.008, 10, None, comp, kind="decode"),
            span("device", t + 0.018, 30, None, comp, kind="decode", tokens=1, cobatch=1,
                 program="jit__decode_logits"),
            span("copy_out", t + 0.048, 5, None, comp, bytes=3038720),
        ]
    # a prefill chunk: no batch_wait, its own step/forward/compute
    out += [
        span("step", 1000.5, 100, "sp", "g"),
        span("forward", 1000.502, 96, "fp", "sp"),
        span("queue", 1000.503, 1, None, "fp"),
        span("compute", 1000.504, 92, "cp", "fp", kind="prefill", tokens=512),
        span("lock_wait", 1000.504, 4, None, "cp", kind="prefill"),
        span("device", 1000.508, 80, None, "cp", kind="prefill", tokens=512, cobatch=1,
             program="jit__prefill_lane_logits"),
        span("copy_out", 1000.588, 1, None, "cp", bytes=607744),
    ]
    pipe0 = {"passes": 100, "stage_ticks": 4400, "stage_ticks_useful": 800}
    pipe1 = {"passes": 110, "stage_ticks": 4400 + 9 * 44 + 16, "stage_ticks_useful": 800 + 9 * 8 + 4}
    return {
        "spans": sorted(out, key=lambda s: s["t0"]), "wall0": 1000.0, "wall1": 1010.0,
        "stats0": {"executor": {"pipeline": pipe0}},
        "stats1": {"executor": {"pipeline": pipe1}},
    }


EXPECT = {
    "node.queue_ms_p50": pytest.approx(2.0),           # 10 of 2 ms, 1 of 1 ms
    "node.token_host_ms_p50": pytest.approx(10.0),     # step 60 - compute 50; the prefill step is left out
    "window.queue_ms_p50": pytest.approx(3.0),
    "window.lock_wait_ms_p50": pytest.approx(10.0),    # 10 of 10 ms, the prefill's 4 ms
    "window.device_ms_p50": pytest.approx(30.0),
    "window.prefill_device_ms_p50": pytest.approx(80.0),
    "window.copy_out_ms_p50": pytest.approx(5.0),
    "device.busy_share_host": pytest.approx(100 * (10 * 0.030 + 0.080) / 10),
    "mesh.bubble_share": pytest.approx(100 * (1 - 76 / 412)),
}


@pytest.mark.parametrize("metric", sorted(EXPECT))
def test_reader_reads_the_number(metric):
    assert harness.load_reader(metric)(a_run()) == EXPECT[metric]


def test_capture_slowdown_by_hand():
    """Before the capture [1004, 1006]: the prefill chunk and ten decode
    steps in 4 s, 2.75 steps/s. Inside it: none, then three at the rate of
    1.5/s; steps after it (the profiler is writing its trace) count on
    neither side."""
    run = a_run()
    assert harness.load_reader("device.capture_slowdown")(run) == pytest.approx(100.0)
    for i, t in enumerate((1004.1, 1004.9, 1005.7, 1006.5, 1007.0, 1007.5)):
        run["spans"].append(span("device", t, 30, f"late{i}", "c1", kind="decode"))
    value = harness.load_reader("device.capture_slowdown")(run)
    assert value == pytest.approx(100 * (1 - 1.5 / 2.75))
    run["wall0"] = 1004.5  # a capture that began before the window: nothing to compare with
    assert harness.load_reader("device.capture_slowdown")(run) is None


def test_a_decode_step_with_two_computes_is_not_a_single_token():
    run = a_run()
    run["spans"].append(span("compute", 1001.06, 1, "extra", "f1", kind="decode", tokens=1))
    costs_before = harness.load_reader("node.token_host_ms_p50")(a_run())
    assert harness.load_reader("node.token_host_ms_p50")(run) == costs_before


@pytest.mark.parametrize("metric", NEW)
def test_reader_reads_nothing_from_an_older_program(metric):
    """The parent of PR 25: `compute` without `kind`, no span inside it, no
    `pipeline` counters."""
    run = a_run()
    keep = ("step", "forward", "compute", "capture")
    run["spans"] = [dict(s, attrs={}) for s in run["spans"] if s["name"] in keep]
    run["stats0"] = run["stats1"] = {"executor": {"batched_steps": 5}}
    assert harness.load_reader(metric)(run) is None


def test_busy_share_clips_to_the_window_and_merges_overlaps():
    run = a_run()
    run["spans"] = [span("device", 999.0, 2000), span("device", 1000.5, 1000),
                    span("device", 1009.0, 5000)]
    assert harness.load_reader("device.busy_share_host")(run) == pytest.approx(100 * (1.5 + 1.0) / 10)


def test_every_new_reader_is_in_the_manifest():
    import json
    import os

    from conftest import REPO

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert set(NEW) <= set(names)
