"""Inside the executor call (docs/OBSERVABILITY.md "Inside `compute`"):
the lane and mesh executors stamp `batch_wait`, `lock_wait`, `device` and
`copy_out` under the call's `compute` span, the arrival window counts its
waits, the mesh engine counts its pipeline's stage-ticks and journals its
compiles, and a capture keeps its clock anchor from the moment it starts.

Each executor is built once (module fixtures): two sessions prefill one
after the other, then their decode steps co-arrive behind a barrier while
the test keeps the flusher from the device (as a prefill would), so that
the step is a co-batch of 2 by construction: both executors take their
batch when they get the device. On the lanes the test holds the device
lock; the mesh admits a call under the lock its passes run under, so there
the flusher is stopped on its way to that lock. The tests read what that
left behind."""

import asyncio
import glob
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from inferd_tpu.config import TINY
from inferd_tpu.models import qwen3
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.obs.devtel import CompileWatch
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.runtime.node import Node
from inferd_tpu.runtime.window import WindowedBatcher
from conftest import port_block
from test_mesh_node import hold_flusher

PORTS = port_block(__file__)

PROMPTS = {"a": [3, 7, 11], "b": [5, 13, 17]}
PARTS = ("lane", "batch_wait", "lock_wait", "device", "copy_out", "deliver")
PP, SLOTS = 4, 4
HELD_S = 0.1  # how long the lanes' device lock is held under both entries


class Journal:
    def __init__(self):
        self.events = []

    def emit(self, etype, **attrs):
        self.events.append((etype, attrs))


def timed(ex, rec, sid, payload):
    """One executor call as the node makes it: Node._timed_process
    allocates the `compute` context and makes it current in this thread;
    the caller records the span afterwards."""
    tin = tracelib.SpanContext(tracelib.new_id(), tracelib.new_id())
    result, ms, w0, w1, ctx = Node._timed_process(None, ex, sid, payload, tin)
    if ctx is not None:
        rec.record_span("compute", "compute", w0, w1, parent=tin, ctx=ctx,
                        attrs={"sid": sid})
    return result


def hold_dev_lock(ex):
    ex._dev_lock.acquire()
    return ex._dev_lock.release


def drive(ex, hold, prefix=""):
    """Prefill both sessions, then one co-arriving decode step of each:
    both arrive while `hold(ex)` keeps the flusher from the device, let go
    HELD_S after the second. Returns {(sid, "prefill"|"decode"): logits}."""
    rec = tracelib.SpanRecorder("test")
    ex.tracer = rec
    out = {}
    for s, ids in PROMPTS.items():
        r = timed(ex, rec, prefix + s, {"tokens": [ids], "start_pos": 0, "real_len": 3})
        out[s, "prefill"] = np.asarray(r["logits"])
    barrier = threading.Barrier(len(PROMPTS))

    def step(s):
        barrier.wait()
        tok = int(out[s, "prefill"][0].argmax())
        r = timed(ex, rec, prefix + s, {"tokens": [[tok]], "start_pos": 3, "real_len": 1})
        out[s, "decode"] = np.asarray(r["logits"])

    threads = [threading.Thread(target=step, args=(s,)) for s in PROMPTS]
    release = hold(ex)
    for t in threads:
        t.start()
    while len(ex._batcher._pending) < len(PROMPTS):
        time.sleep(0.001)
    time.sleep(HELD_S)
    release()
    for t in threads:
        t.join(timeout=120)
    assert len(out) == 4
    return rec, out


@pytest.fixture(scope="module")
def params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lanes(params):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    ex = BatchedExecutor(TINY, params, lanes=4, max_len=64, window_ms=400.0)
    rec, out = drive(ex, hold_dev_lock)
    return {"ex": ex, "spans": rec.spans(), "out": out, "stats": ex.stats(),
            "hold": hold_dev_lock,
            "programs": {"prefill": "jit__prefill_lane_logits",
                         "decode": "jit__decode_logits"}}


@pytest.fixture(scope="module")
def mesh(params):
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    ex = MeshExecutor(TINY, params, MeshPlan(pp=PP), num_slots=SLOTS, max_len=64,
                      devices=jax.devices()[:PP], window_ms=400.0)
    journal = Journal()
    CompileWatch(journal=journal).instrument_executor(ex)
    rec, out = drive(ex, hold_flusher)
    return {"ex": ex, "spans": rec.spans(), "out": out, "stats": ex.stats(),
            "events": journal.events, "hold": hold_flusher,
            "programs": {"prefill": "jit__step_raw", "decode": "jit__step_raw_multi"}}


@pytest.fixture(params=["lanes", "mesh"])
def driven(request):
    return request.getfixturevalue(request.param)


def children(spans, compute):
    return sorted((s for s in spans if s["parent"] == compute["span"]),
                  key=lambda s: s["t0"])


def computes(spans, kind):
    """The `compute` spans whose call was a prefill / a decode step: a
    decode call is one that waited in the arrival window."""
    out = []
    for c in (s for s in spans if s["name"] == "compute"):
        waited = any(k["name"] == "batch_wait" for k in children(spans, c))
        if waited == (kind == "decode"):
            out.append(c)
    return out


def test_prefill_call_leaves_its_three_parts(driven):
    spans = driven["spans"]
    calls = computes(spans, "prefill")
    assert len(calls) == 2
    for c in calls:
        kids = children(spans, c)
        # a session's first call binds its lane (slot) first: a lane never
        # used before has stood free for no known time
        assert [k["name"] for k in kids] == ["lane", "lock_wait", "device", "copy_out"]
        lane, lock, dev, copy = kids
        assert lane["attrs"] == {"lane": lane["attrs"]["lane"], "new": 1, "evicted": 0,
                                 "vacant_ms": None}
        # the wait for the lock, and how long the chunk's dispatch (the
        # mesh: its whole pass) then held it
        assert lock["attrs"] == {"kind": "prefill", "held_ms": lock["attrs"]["held_ms"]}
        assert 0 < lock["attrs"]["held_ms"] < 60e3
        assert dev["attrs"] == {"kind": "prefill", "tokens": 3, "cobatch": 1,
                                "program": driven["programs"]["prefill"],
                                # the lanes' span stands for every chunk dispatched
                                **({"chunks": 1} if driven["stats"]["mode"] == "batched" else {})}
        assert copy["attrs"] == {"bytes": TINY.vocab_size * 4}


def test_decode_step_is_one_device_step_and_a_wait_per_entry(driven):
    spans = driven["spans"]
    calls = computes(spans, "decode")
    assert len(calls) == 2
    names = [[k["name"] for k in children(spans, c)] for c in calls]
    # the flusher's call holds the step; the co-arrival's only its waits
    # (the time the device was somebody else's, then the wait for expected
    # sessions); each ends with the way back from the step's copy_out to
    # its own worker (tests/test_host_turn.py)
    assert sorted(names, key=len) == [
        ["lock_wait", "batch_wait", "deliver"],
        ["lock_wait", "batch_wait", "device", "copy_out", "deliver"],
    ]
    dev = [s for s in spans if s["name"] == "device" and s["attrs"]["kind"] == "decode"]
    assert len(dev) == 1
    # the flusher waited for the step it had just dispatched: it saw it end
    assert dev[0]["attrs"] == {"kind": "decode", "tokens": 2, "cobatch": 2,
                               "program": driven["programs"]["decode"], "waited": 1}
    locks = [s for s in spans if s["name"] == "lock_wait" and s["attrs"]["kind"] == "decode"]
    assert len(locks) == 2
    # each from its own submit, for as long as the device was held
    assert all(HELD_S <= lk["t1"] - lk["t0"] < HELD_S + 0.5 for lk in locks)
    waits = [s for s in spans if s["name"] == "batch_wait"]
    assert sorted(w["attrs"]["flusher"] for w in waits) == [0, 1]
    copies = [k for c in calls for k in children(spans, c) if k["name"] == "copy_out"]
    # two raw /forward steps: every lane's / slot's row, and the step's own
    # small array (a token and a key a lane: tests/test_device_sampling.py)
    assert [k["attrs"]["bytes"] for k in copies] == [4 * TINY.vocab_size * 4 + 4 * 3 * 4]


def test_parts_lie_inside_their_compute_and_do_not_overlap(driven):
    spans = driven["spans"]
    for c in (s for s in spans if s["name"] == "compute"):
        kids = children(spans, c)
        assert kids and all(k["name"] in PARTS for k in kids)
        assert c["t0"] <= kids[0]["t0"] and kids[-1]["t1"] <= c["t1"]
        for a, b in zip(kids, kids[1:]):
            assert a["t1"] <= b["t0"]
        assert all(k["trace"] == c["trace"] for k in kids)


def test_window_counters_are_fed_by_the_same_stamps(driven):
    st = driven["stats"]
    assert st["queue_waits"] == 2 == st["batched_tokens"]
    waits = [s for s in driven["spans"] if s["name"] == "batch_wait"]
    assert st["queue_wait_ms_sum"] == pytest.approx(
        sum((w["t1"] - w["t0"]) * 1e3 for w in waits), abs=0.01)
    # nobody was expected: the whole wait was for the lock, none of it the
    # 400 ms the window starts its turn estimate from
    assert st["queue_wait_ms_sum"] < 50


def test_pipeline_counters_count_ticks_and_live_slots(mesh):
    """Two one-slot prefill passes (one row) and one decode pass with 2 of
    the 4 slots live (every slot a row): PP ticks on PP stages, a stage-tick
    counting the rows it carries, a live slot using PP of them."""
    assert mesh["stats"]["pipeline"] == {
        "passes": 3,
        "stage_ticks": 2 * PP * PP * 1 + PP * PP * SLOTS,
        "stage_ticks_useful": 2 * PP + 2 * PP,
    }


def test_one_more_decode_pass_adds_its_ticks(mesh):
    ex = mesh["ex"]
    before = dict(ex.stats()["pipeline"])
    ex.process("a", {"tokens": [[1]], "start_pos": 4, "real_len": 1})
    after = ex.stats()["pipeline"]
    assert after["passes"] - before["passes"] == 1
    assert after["stage_ticks"] - before["stage_ticks"] == PP * PP * SLOTS
    assert after["stage_ticks_useful"] - before["stage_ticks_useful"] == 1 * PP


def test_mesh_engine_compiles_are_journaled(mesh):
    begun = [a["name"] for t, a in mesh["events"] if t == "compile.begin"]
    assert "MeshExecutor.engine._step_raw" in begun
    assert "MeshExecutor.engine._step_raw_multi" in begun
    assert len([t for t, _a in mesh["events"] if t == "compile.end"]) == len(begun)


def test_trace_off_records_nothing_and_changes_no_result(driven, monkeypatch):
    monkeypatch.setenv("INFERD_TRACE", "0")
    rec, out = drive(driven["ex"], driven["hold"], prefix="off-")
    assert rec.spans() == []
    for key, logits in driven["out"].items():
        np.testing.assert_array_equal(out[key], logits)


# ------------------------------------------------ the arrival window alone


def _released_together(batcher, n):
    barrier, got = threading.Barrier(n), {}

    def one(i):
        barrier.wait()
        got[i] = batcher.submit(i)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    return got


@pytest.mark.parametrize("mode", ["plain", "swap_in_run", "absorbed"])
def test_every_entry_served_has_its_wait_stamped(mode):
    """queue_waits equals the entries served whichever way a flush takes
    them: the flusher's own swap, a swap_in_run callback's drain, or a
    running step's drain_pending absorbing a later arrival."""
    rec = tracelib.SpanRecorder("w")
    first_running, may_finish = threading.Event(), threading.Event()

    def deliver(entries):
        for e in entries:
            e.result = ("ok", e.payload)
            e.event.set()

    def run_batch(entries):
        if mode == "swap_in_run":
            deliver(batcher.drain_pending())
        elif mode == "absorbed" and not first_running.is_set():
            first_running.set()
            may_finish.wait(timeout=30)
            deliver(entries + batcher.drain_pending())
        else:
            deliver(entries)

    batcher = WindowedBatcher(
        0.2, run_batch, co_possible=lambda: mode != "absorbed" or first_running.is_set(),
        swap_in_run=(mode == "swap_in_run"),
    )
    batcher.tracer = rec
    if mode == "absorbed":
        got = {}
        a = threading.Thread(target=lambda: got.update(a=batcher.submit("a")))
        a.start()
        assert first_running.wait(timeout=30)
        b = threading.Thread(target=lambda: got.update(b=batcher.submit("b")))
        b.start()
        while not batcher._pending:  # b sits in its window: a's step takes it
            threading.Event().wait(0.005)
        may_finish.set()
        a.join(timeout=30)
        b.join(timeout=30)
        assert got == {"a": ("ok", "a"), "b": ("ok", "b")}
        n = 2
    else:
        n = 3
        assert _released_together(batcher, n) == {i: ("ok", i) for i in range(n)}
    st = batcher.stats()
    assert st["queue_waits"] == n == st["batched_tokens"]
    waits = [s for s in rec.spans() if s["name"] == "batch_wait"]
    assert len(waits) == n and all(w["t1"] >= w["t0"] for w in waits)
    assert st["queue_wait_ms_sum"] == pytest.approx(
        sum((w["t1"] - w["t0"]) * 1e3 for w in waits), abs=0.01)


def test_region_is_a_no_op_without_a_recorder_or_with_tracing_off(monkeypatch):
    with tracelib.region(None, "device", kind="decode") as at:
        at["bytes"] = 1
    rec = tracelib.SpanRecorder("r")
    monkeypatch.setenv("INFERD_TRACE", "0")
    with tracelib.region(rec, "device"):
        pass
    lock = threading.Lock()
    with tracelib.holding(lock, rec, kind="decode"):
        assert lock.locked()
    assert not lock.locked() and rec.spans() == []


def test_region_records_one_span_per_parent():
    rec = tracelib.SpanRecorder("r")
    parents = [tracelib.SpanContext("t1", "p1"), tracelib.SpanContext("t2", "p2")]
    with tracelib.region(rec, "lock_wait", parents, kind="decode"):
        pass
    got = rec.spans()
    assert [(s["trace"], s["parent"]) for s in got] == [("t1", "p1"), ("t2", "p2")]
    assert got[0]["t0"] == got[1]["t0"] and got[0]["attrs"] == {"kind": "decode"}


# ----------------------------------------------- sessions resident, /stats

# what `kv.sessions_resident_mean` polls each second: `executor.lanes_busy`
# on the lane paths, `executor.sessions` under --mesh
RESIDENT = {
    "dense": ("tiny", "lanes"),
    "latent": ("tiny-dsv2", "lanes"),
    "state": ("tiny-granite-h", "lanes"),
    "ring": ("tiny-gemma2", "lanes"),
    "stage_lanes": ("tiny", "stage"),
    "mesh_pp2": ("tiny", "mesh"),
}


def _resident_executor(model, path):
    from inferd_tpu.config import get_config

    cfg = get_config(model)
    weights = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    if path == "lanes":
        from inferd_tpu.runtime.batch_executor import BatchedExecutor

        return BatchedExecutor(cfg, weights, lanes=3, max_len=64), "lanes_busy"
    if path == "stage":
        from inferd_tpu.parallel.stages import Manifest, extract_stage_params
        from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

        (spec,) = Manifest.even_split(model, 1).stage_specs()
        sp = extract_stage_params(weights, cfg, spec)
        return BatchedStageExecutor(cfg, spec, sp, lanes=3, max_len=64), "lanes_busy"
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    return MeshExecutor(cfg, weights, MeshPlan(pp=2), num_slots=3, max_len=64,
                        devices=jax.devices()[:2]), "sessions"


@pytest.mark.parametrize("layout", list(RESIDENT))
def test_stats_count_the_sessions_resident(layout):
    """0 at rest, n with n sessions holding a lane, unmoved by a decode
    step, one fewer after `end_session`, 0 after the node's sweep took the
    stale ones, and a lane given back is taken again."""
    ex, key = _resident_executor(*RESIDENT[layout])

    def resident():
        return ex.stats()[key]

    assert resident() == 0
    for n, sid in enumerate(("a", "b", "c"), start=1):
        ex.process(sid, {"tokens": [[3, 7, 11]], "start_pos": 0, "real_len": 3})
        assert resident() == n
    ex.process("b", {"tokens": [[5]], "start_pos": 3, "real_len": 1})
    assert resident() == 3
    ex.end_session("a")
    assert resident() == 2
    store = ex.sessions  # what Node._sweep_loop sweeps
    store.ttl_s = -1.0
    assert store.sweep() == 2 and resident() == 0
    ex.process("d", {"tokens": [[3, 7, 11]], "start_pos": 0, "real_len": 3})
    assert resident() == 1


# ------------------------------------------------------------ the capture


@pytest.mark.asyncio
async def test_capture_span_is_there_before_the_capture_closes(tmp_path):
    """POST /profile window: the `capture` span (the clock anchor of every
    reader) is in /spans while the capture is still open, `capture_close`
    once the trace is written; the written trace holds the anchor event
    whose end the span's t0 names, and no python call stack."""
    import aiohttp

    from inferd_tpu.runtime import wire
    from test_node_e2e import _mk_node

    node = _mk_node(190, 0, 1, bootstrap_idx=190, ports=PORTS)
    node.enable_profiling = True
    node.profiler.base_dir = str(tmp_path / "profiles")
    await node.start()
    try:
        async with aiohttp.ClientSession() as http:
            body = wire.pack({"action": "window", "seconds": 1.0, "capture_id": "t"})
            async with http.post(f"http://127.0.0.1:{PORTS.http(190)}/profile", data=body) as r:
                assert r.status == 200
            assert node.profiler.active_dir is not None  # still capturing
            open_spans = {s["name"]: s for s in node.tracer.spans()}
            cap = open_spans["capture"]
            assert "capture_close" not in open_spans
            assert cap["t1"] - cap["t0"] == pytest.approx(1.0)
            assert cap["t0"] == node.profiler.started_at
            assert node.tracer.annotating
            await asyncio.wait_for(node._capture_task, timeout=60)
        assert node.profiler.active_dir is None and not node.tracer.annotating
        closed = {s["name"]: s for s in node.tracer.spans()}
        assert closed["capture_close"]["t0"] >= cap["t1"] - 1e-3
        assert closed["capture_close"]["attrs"] == {"capture_id": "t"}
        types = [ev["type"] for ev in node.journal.events()]
        assert types.index("profile.capture") < types.index("profile.capture_done")
    finally:
        await node.stop()

    (path,) = glob.glob(str(tmp_path / "profiles" / "**" / "*.xplane.pb"), recursive=True)
    names = [e.name for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events]
    anchors = [n for n in names if "start_trace" in n]
    assert anchors == ["inferd.start_trace.anchor"]
    assert not any(n.startswith("$") for n in names)  # the python tracer's events


def test_profiler_refuses_a_second_start(tmp_path):
    """One capture at a time: `stop` hands back the directory `start`
    named, and a second `start` while one runs raises (the endpoint's 409)
    and leaves the first running."""
    from inferd_tpu.utils.profiling import Profiler

    prof = Profiler(base_dir=str(tmp_path / "profiles"))
    d = prof.start("cap1")
    assert prof.stop() == d
    d2 = prof.start("cap2")
    with pytest.raises(RuntimeError, match="already running"):
        prof.start("cap3")
    assert prof.active_dir == d2
    assert prof.stop() == d2


@pytest.mark.asyncio
async def test_collector_capture_fleet(tmp_path):
    """Fleet-coordinated capture: the collector triggers one bounded
    capture_id-tagged /profile window on every node simultaneously, then
    merges the per-node spans into a Chrome-trace bundle + manifest. A
    node without --enable-profiling degrades to a recorded error instead
    of aborting the capture (mixed-fleet contract); the capturing node's
    `capture` span (bracketing the device trace) rides the bundle."""
    from inferd_tpu.tools.collector import capture_fleet
    from test_node_e2e import _mk_node, _start_all, _stop_all

    nodes = [
        _mk_node(170, 0, 2, bootstrap_idx=170, ports=PORTS),
        _mk_node(171, 1, 2, bootstrap_idx=170, ports=PORTS),
    ]
    cap, no_cap = nodes[0], nodes[1]
    cap.enable_profiling = True
    cap.profiler.base_dir = str(tmp_path / "profiles")
    await _start_all(nodes)
    try:
        swarm_map = cap.dht.get_all(2)
        out_dir = str(tmp_path / "bundle")
        manifest = await capture_fleet(
            swarm_map, "cap-test", seconds=0.4, out_dir=out_dir
        )
        assert manifest["capture_id"] == "cap-test"
        rec_cap = manifest["nodes"][cap.info.node_id]
        rec_no = manifest["nodes"][no_cap.info.node_id]
        assert "cap-test" in rec_cap["dir"]
        assert "disabled" in rec_no["error"]
        # the device-trace artifacts landed under the tagged dir
        assert os.path.isdir(rec_cap["dir"])
        # the bundle: chrome trace with the capture span in it
        with open(os.path.join(out_dir, "cap-test.trace.json")) as f:
            chrome = json.load(f)
        cap_events = [
            ev for ev in chrome["traceEvents"]
            if ev["name"] == "capture"
            and ev["args"].get("capture_id") == "cap-test"
        ]
        assert len(cap_events) == 1
        assert cap_events[0]["dur"] >= 0.4 * 1e6 * 0.5
        # writing the trace takes as long as the host lets it: wait for
        # the close itself, not for a guess at when it will have happened
        await asyncio.wait_for(cap._capture_task, timeout=120)
        # the capture journaled open AND close on the capturing node
        types = [ev["type"] for ev in cap.journal.events()]
        assert "profile.capture" in types
        assert "profile.capture_done" in types
        # profiler closed itself after the bounded window
        assert cap.profiler.active_dir is None
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_capture_fleet_empty_swarm(tmp_path):
    """A capture against an empty swarm map yields an empty manifest —
    the CLI turns that into a nonzero exit (an empty bundle must not
    read as a working capture)."""
    from inferd_tpu.tools.collector import capture_fleet

    manifest = await capture_fleet({}, "none", 0.1, str(tmp_path / "b"))
    assert manifest["nodes"] == {} and manifest["spans"] == 0


async def _post_profile(http, port, env):
    from inferd_tpu.runtime import wire

    async with http.post(f"http://127.0.0.1:{port}/profile", data=wire.pack(env)) as r:
        return r.status, wire.unpack(await r.read())


@pytest.mark.parametrize(
    "case", ["bad_seconds", "clamped_high", "clamped_low", "second_window", "two_threads"]
)
@pytest.mark.asyncio
async def test_profile_endpoint_edges(case, tmp_path):
    """What /profile answers at its edges: `seconds` that is no number is
    refused before anything starts, a window is held to [0.1, 60] s and
    says so, a second window while one is open is a 409 that leaves the
    first to close, and `start` / `stop` need not share a thread."""
    import aiohttp

    from test_node_e2e import _mk_node

    node = _mk_node(172, 0, 1, bootstrap_idx=172, ports=PORTS)
    node.enable_profiling = True
    node.profiler.base_dir = str(tmp_path / "profiles")
    port = PORTS.http(172)
    await node.start()
    try:
        async with aiohttp.ClientSession() as http:
            if case == "bad_seconds":
                status, obj = await _post_profile(
                    http, port, {"action": "window", "seconds": "soon"})
                assert status == 400 and "bad seconds" in obj["error"]
                assert node.profiler.active_dir is None
                assert not [s for s in node.tracer.spans() if s["name"] == "capture"]
            elif case in ("clamped_high", "clamped_low"):
                asked, held = (1000, 60.0) if case == "clamped_high" else (0, 0.1)
                status, obj = await _post_profile(
                    http, port, {"action": "window", "seconds": asked, "capture_id": "c"})
                assert status == 200 and obj["seconds"] == held
                (cap,) = [s for s in node.tracer.spans() if s["name"] == "capture"]
                assert cap["t1"] - cap["t0"] == pytest.approx(held)
            elif case == "second_window":
                status, first = await _post_profile(
                    http, port, {"action": "window", "seconds": 0.5, "capture_id": "one"})
                assert status == 200
                task = node._capture_task
                status, obj = await _post_profile(
                    http, port, {"action": "window", "seconds": 0.5, "capture_id": "two"})
                assert status == 409 and "already running" in obj["error"]
                assert node._capture_task is task and node.profiler.active_dir == first["dir"]
                await asyncio.wait_for(task, timeout=120)
                closes = [s for s in node.tracer.spans() if s["name"] == "capture_close"]
                assert [s["attrs"] for s in closes] == [{"capture_id": "one"}]
                assert node.profiler.active_dir is None
            else:
                status, started = await _post_profile(
                    http, port, {"action": "start", "name": "t"})
                assert status == 200
                got = {}
                stopper = threading.Thread(
                    target=lambda: got.update(dir=node.profiler.stop(), by=threading.get_ident()))
                stopper.start()
                await asyncio.get_running_loop().run_in_executor(None, stopper.join)
                assert got["dir"] == started["dir"] and got["by"] != threading.get_ident()
                assert node.profiler.active_dir is None
                status, _ = await _post_profile(http, port, {"action": "start", "name": "u"})
                assert status == 200  # nothing was left held
    finally:
        await node.stop()
