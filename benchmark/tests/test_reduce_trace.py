"""reduce_trace.py: the interval arithmetic on hand-made planes, and the
whole reduction on the small v5e trace recorded by make_trace.py."""

import os

import pytest

import reduce_trace as rt

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "tiny_step.xplane.pb")


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert rt.union_s(iv) == 3.0
    assert rt.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert rt.gaps([], 1.0, 2.0) == [(1.0, 2.0)]
    assert rt.gaps([(0.0, 9.0)], 1.0, 2.0) == []


def planes(shift=0.0):
    """One chip: a 10 ms step every 50 ms, ten times; two ops in a step
    with 1 ms between them; the python line holds the start_trace call."""
    mods, ops = [], []
    for i in range(10):
        a = 0.100 + 0.050 * i
        mods.append((f"jit__decode_logits({123})", a, a + 0.010))
        ops.append(("fusion.1", a, a + 0.004))
        ops.append(("dot.7", a + 0.005, a + 0.010))
    mods.append(("jit__prefill_lane_logits(9)", 0.700, 0.900))
    ops.append(("dot.7", 0.700, 0.900))
    return {
        "/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops, "Steps": []},
        "/host:CPU": {"python": [("$profiler.py:101 start_trace", 0.0, 0.020),
                                 ("tail", 0.999, 1.000)]},
    }


def spans(wall0=1000.0):
    """The node's spans on a wall clock that is the trace's + wall0: a
    compute span around every step (2 ms before, 3 ms after), a forward
    span around that, the capture span from the end of start_trace."""
    out = [{"name": "capture", "t0": wall0 + 0.020, "t1": wall0 + 1.2}]
    for i in range(10):
        a = wall0 + 0.100 + 0.050 * i
        out.append({"name": "compute", "t0": a - 0.002, "t1": a + 0.013})
        out.append({"name": "forward", "t0": a - 0.004, "t1": a + 0.020})
        out.append({"name": "sample", "t0": a + 0.021, "t1": a + 0.040})
    out.append({"name": "compute", "t0": wall0 + 0.698, "t1": wall0 + 0.905})
    return out


def test_reduce_hand_made_planes():
    r = rt.reduce(planes(), spans())
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(10 * 0.009 + 0.2)
    dec = rt.find_module(r["modules"], "^jit__decode_logits")
    assert dec["count"] == 10 and dec["median_s"] == pytest.approx(0.010)
    assert rt.find_module(r["modules"], "^jit__prefill")["median_s"] == pytest.approx(0.2)
    assert rt.find_module(r["modules"], "^jit_nothing") is None
    assert r["device_ops"][0][0] == "dot.7"
    assert r["device_ops"][0][1] == pytest.approx(10 * 0.005 + 0.2)
    gaps = dict(r["idle_gaps"])
    # between steps 40 ms: 19 ms of it under `sample`, under half -> the span
    # covering most is `sample`, but it covers less than half: "no span"
    assert set(gaps) <= {"no span", "sample", "forward", "compute"}
    assert sum(gaps.values()) == pytest.approx(r["long_gaps"]["total_s"])
    assert r["alignment"]["executions_inside_compute"] == 1.0
    assert abs(r["alignment"]["shift_from_capture_ms"]) <= 2.0


def test_alignment_recovers_a_capture_span_that_is_late():
    """capture.t0 taken 12 ms late: the fit on the compute spans finds it."""
    sp = spans()
    sp[0]["t0"] += 0.012
    r = rt.reduce(planes(), sp)
    assert r["alignment"]["executions_inside_compute"] == 1.0
    assert r["alignment"]["shift_from_capture_ms"] == pytest.approx(-12.0, abs=2.0)


def test_gap_goes_to_the_span_that_covers_it():
    by = {"compute": [(0.0, 1.0)], "forward": [(0.0, 2.0)], "sample": [(1.5, 1.6)]}
    assert rt.attribute((0.2, 0.4), by) == "compute"      # both cover it: the shorter
    assert rt.attribute((1.1, 1.4), by) == "forward"
    assert rt.attribute((2.5, 3.0), by) == "no span"
    assert rt.attribute((1.5, 1.9), {"sample": [(1.5, 1.6)]}) == "no span"  # under half


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        rt.reduce({"/host:CPU": {"python": [("x", 0.0, 1.0)]}}, [])


@pytest.mark.skipif(not os.path.isfile(SAMPLE), reason="no recorded trace")
def test_recorded_v5e_trace():
    """make_trace.py on the chip: `tiny_step` six times, 10 ms apart."""
    r = rt.reduce(rt.read_planes(SAMPLE), [])
    assert [d["name"] for d in r["devices"]] == ["/device:TPU:0"]
    step = rt.find_module(r["modules"], "^jit_tiny_step")
    assert step["count"] == 6
    assert 0 < step["median_s"] < 0.005
    assert 0 < r["busy_s"] < 6 * 0.005 < r["window_s"]
    assert r["long_gaps"]["count"] >= 5          # the pauses between the runs
    assert r["idle_gaps"][0][0] == "not attributed"
    assert r["device_ops"] and all(s > 0 for _n, s in r["device_ops"])
