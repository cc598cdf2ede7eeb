"""A request's admission (docs/OBSERVABILITY.md "A request's admission"): what
the span recorder may forget and what it keeps (`keep`, inherited from the
parent; never on the wire), the spans over a request's way from `/generate`'s
arrival to its first answered decode (block) hop and over its release
(`accept`, `open`, `lane`, `ride`, `close`; `first` on one `step`), the
attributes and counters at the same boundaries, and the benchmark's readers of
all of it, on a run of this tree and on one without any of it.

Each topology is served once (a module fixture): one node of three lanes
(slots), its generation loop's prefill chunk set to 4 so that a prompt of 8 is
two chunks; four streamed /generate requests: a long one, two newcomers side by
side once the long one is decoding (their first hops RIDE: the long one's rows
are run ahead, so no drain waits for them), and one more when all are done (it
takes a lane that has stood free).

A rider gives the next drain ONE STEP'S TIME to come and run the row it
promised (`StepAhead._ridden`: `released.wait`), else its next hop is back first
and rides again. The tiny model's step is a millisecond or two on the CPU,
under a host turn on any busy machine (the reverse of a chip's), so a newcomer
rode twice whenever the long session's turn was slow: one run in six of
`-k "tiled or counters"` beside eight busy processes, on the parent tree as on
this one (PERF.md section 7, PR 58 (f)). The topologies are therefore served
with that wait held at the code's own cap, whatever the step took
(`_patient_riders`): a released rider still goes at once, and one whom no drain
releases still rides again."""

import asyncio
import importlib.util
import json
import os
import sys
import threading

import aiohttp
import jax
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig, get_config
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.models import qwen3
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import step_ahead, wire
from inferd_tpu.runtime.node import Node, NodeInfo
from inferd_tpu.runtime.window import Entry

from conftest import port_block  # noqa: E402

PORTS, HOST = port_block(__file__), "127.0.0.1"
GREEDY = SamplingConfig(temperature=0.0)
PROMPTS = ([3, 7, 11, 19, 23, 29, 31, 37], [5, 13, 17, 41, 43, 47, 53, 59])
NEW, LONG_NEW = 12, 120
LIMIT_S = 240  # a topology's whole service, compiles included
ONCE_A_STEP = ("device", "copy_out", "turn")
ADMISSION = ("accept", "open", "lane", "ride", "close")
TOPOLOGIES = {
    "lanes": (0, "tiny", {"batch_lanes": 3}),
    "mesh": (1, "tiny", {"mesh_plan": MeshPlan(pp=2), "mesh_slots": 3}),
    "block": (2, "tiny-sdar", {"batch_lanes": 3}),
}

# ---------------------------------------------------------------------------
# (i) the ring
# ---------------------------------------------------------------------------


def test_kept_spans_survive_ten_times_the_sampled_rings_capacity():
    rec = tracelib.SpanRecorder("t", cap=64)
    root = rec.record_span("generate", "server", 0.0, 1.0, keep=True)
    kept = [rec.record_span("accept", "accept", 0.0, 0.1, parent=root)]
    for i in range(640):  # a hop's spans: sampled, the oldest go
        rec.record_span("step", "wire", 1.0 + i, 1.5 + i)
    kept.append(rec.record_span("turn", "turn", 700.0, 700.1, keep=True))
    names = [s["name"] for s in rec.spans()]
    assert names.count("step") == 64 and names[:2] == ["generate", "accept"] and names[-1] == "turn"
    assert all(c.keep for c in kept) and root.keep
    assert rec.stats() == {
        "service": "t", "buffered": 67, "recorded": 643, "dropped": 576, "kept": 3,
        "kept_dropped": 0, "overhead_ms": rec.stats()["overhead_ms"],
    }
    assert len(rec) == 67


def test_the_kept_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(tracelib, "KEPT_CAP", 8)
    rec = tracelib.SpanRecorder("t", cap=16)
    for i in range(11):
        rec.record_span("device", "device", float(i), i + 0.5, keep=True)
    st = rec.stats()
    assert (st["kept"], st["kept_dropped"], st["dropped"], st["buffered"]) == (11, 3, 0, 8)
    assert [s["t0"] for s in rec.spans()] == [float(i) for i in range(3, 11)]


@pytest.mark.parametrize("root_keep", [True, False])
def test_keep_is_inherited_from_the_parent_whichever_way_a_span_is_made(root_keep):
    rec = tracelib.SpanRecorder("t")
    with rec.span("generate", "client", keep=root_keep) as root:
        assert root.keep is root_keep and tracelib.current() is root
        with rec.span("step", "wire") as step:  # None: the parent's
            assert step.keep is root_keep
            child = step.child()  # what runtime/node.py hands its worker
            assert child.keep is root_keep and child.trace_id == root.trace_id
            token = tracelib.set_current(child)
            try:
                with tracelib.region(rec, "lane"):
                    pass
                entry = Entry(("payload",))  # a window entry carries the submitter's context
            finally:
                tracelib.reset_current(token)
            rec.record_span("batch_wait", "batch_wait", 0.0, 1.0, parent=entry.ctx)
            rec.record_span("compute", "compute", 0.0, 1.0, parent=step, ctx=child)
        # said outright, either way, whatever the parent is
        rec.record_span("sample", "sample", 0.0, 1.0, parent=root, keep=False)
        with rec.span("step", "wire", keep=not root_keep) as other:
            assert other.keep is (not root_keep)
        with tracelib.region(rec, "device", keep=True):
            pass
    kept = {s["name"] for s in rec._kept}
    sampled = {s["name"] for s in rec._buf}
    inherit = {"generate", "lane", "batch_wait", "compute"}
    assert "sample" in sampled and "device" in kept
    assert inherit <= (kept if root_keep else sampled)
    assert sorted(s["name"] for s in rec.spans()).count("step") == 2
    assert sum(s["name"] == "step" for s in rec._kept) == 1  # one of the two, either way


def test_adopt_finds_the_current_span_behind_what_the_wire_gave():
    rec = tracelib.SpanRecorder("t")
    with rec.span("step", "wire", keep=True) as step:
        read = tracelib.SpanContext.from_wire(tracelib.wire_ctx())
        assert read == tracelib.SpanContext(step.trace_id, step.span_id) and not read.keep
        assert tracelib.adopt(read) is step  # a hop made as a call: the loop's own span
        other = tracelib.SpanContext(step.trace_id, tracelib.new_id())
        assert tracelib.adopt(other) is other and tracelib.adopt(None) is None
    assert tracelib.adopt(read) is read  # over a socket nothing is current


def test_everything_is_kept_while_a_capture_annotates():
    rec = tracelib.SpanRecorder("t", cap=16)
    rec.annotating = True
    for i in range(40):
        rec.record_span("step", "wire", float(i), i + 0.5)
    with tracelib.region(rec, "lock_wait"):  # also the profiler's annotation
        pass
    rec.annotating = False
    rec.record_span("step", "wire", 50.0, 50.5)
    st = rec.stats()
    assert (st["kept"], st["dropped"], st["kept_dropped"], st["recorded"]) == (41, 0, 0, 42)
    assert len(rec._buf) == 1


def test_every_reader_of_the_recorder_gets_each_span_once_in_t0_order(tmp_path):
    rec = tracelib.SpanRecorder("t", cap=16)
    path = str(tmp_path / "t.spans.jsonl")
    made = []
    for i in range(6):
        made.append(rec.record_span("step", "wire", 10.0 - i, 11.0, keep=bool(i % 2)).span_id)
    assert rec.flush_jsonl(path) == 6 and rec.flush_jsonl(path) == 0
    made.append(rec.record_span("turn", "turn", 0.5, 0.6, keep=True).span_id)
    made.append(rec.record_span("emit", "emit", 0.4, 0.6).span_id)
    assert rec.flush_jsonl(path) == 2  # only what came since, from both rings
    with open(path) as f:
        flushed = [json.loads(line) for line in f]
    lines = list(rec.jsonl_lines())
    assert list(rec.jsonl_lines()) == lines  # a read takes nothing away
    assert lines == [json.dumps(s, separators=(",", ":")) for s in rec.spans()]
    listed = [json.loads(line) for line in lines]
    for got in (rec.spans(), listed, rec.drain()):
        assert sorted(s["span"] for s in got) == sorted(made)
        assert [s["t0"] for s in got] == sorted(s["t0"] for s in got)
    assert sorted(s["span"] for s in flushed) == sorted(made)
    assert [s["t0"] for s in flushed[:6]] == sorted(s["t0"] for s in flushed[:6])
    assert len(rec) == 0 and rec.spans() == []


@pytest.mark.parametrize("tracing", ["on", "off"])
def test_keep_never_reaches_the_wire_or_the_header(tracing, monkeypatch):
    if tracing == "off":
        monkeypatch.setenv("INFERD_TRACE", "0")
    env = {"session_id": "s", "task_id": "t", "stage": 0, "payload": {"tokens": [[1]]}}
    packed = {}
    for keep in (True, False):
        token = tracelib.set_current(tracelib.SpanContext("a" * 16, "b" * 16, keep))
        try:
            packed[keep] = wire.pack(tracelib.attach_wire(dict(env)))
            header = tracelib.header_ctx()
        finally:
            tracelib.reset_current(token)
        assert header == (None if tracing == "off" else {"X-Inferd-Trace": "a" * 16 + "-" + "b" * 16})
    assert packed[True] == packed[False]
    # the parent's bytes: the two ids under `trace`, or with tracing off no key at all
    want = dict(env) if tracing == "off" else {**env, "trace": {"id": "a" * 16, "span": "b" * 16}}
    assert packed[True] == wire.pack(want)
    ctx = tracelib.SpanContext("a" * 16, "b" * 16, True)
    assert ctx.to_wire() == {"id": "a" * 16, "span": "b" * 16}
    assert tracelib.SpanContext.from_header(ctx.to_header()) == tracelib.SpanContext("a" * 16, "b" * 16)
    rec = tracelib.SpanRecorder("t")
    if tracing == "off":  # nothing is stamped
        with tracelib.region(rec, "lane"), tracelib.holding(FakeLock(), rec):
            pass
        assert rec.record_span("turn", "turn", 0.0, 1.0, keep=True) is None and len(rec) == 0


class FakeLock:
    def acquire(self):
        return True

    def release(self):
        pass

    __enter__ = acquire

    def __exit__(self, *exc):
        pass


def test_a_held_lock_says_how_long_it_was_held():
    import threading
    import time

    rec = tracelib.SpanRecorder("t")
    lock = threading.Lock()
    with tracelib.holding(lock, rec, kind="prefill"):
        assert lock.locked()
        time.sleep(0.02)
    (span,) = rec.spans()
    assert not lock.locked() and span["name"] == "lock_wait"
    assert span["attrs"]["kind"] == "prefill" and 20.0 <= span["attrs"]["held_ms"] < 2000.0
    assert span["t1"] - span["t0"] < 0.02  # the WAIT, as before: nobody held the lock


# ---------------------------------------------------------------------------
# (ii) one streamed /generate on a node of each kind
# ---------------------------------------------------------------------------


class _Patient(threading.Event):
    """A step's `released`, waited for as long as `_ridden` ever waits (its
    cap, 0.1 s) where it asks for a step's time."""

    def wait(self, timeout=None):
        return super().wait(None if timeout is None else 0.1)


def _patient_riders(mp):
    made = step_ahead._Step.__init__

    def init(self, *a, **kw):
        made(self, *a, **kw)
        self.released = _Patient()

    mp.setattr(step_ahead._Step, "__init__", init)


async def _serve(idx, model, kw, parts_dir, tmp):
    cfg = TINY if model == "tiny" else get_config(model)
    info = NodeInfo(name=f"ad{idx}", host=HOST, port=PORTS.http(idx), stage=0,
                    num_stages=1, capacity=8, model_name=model)
    dht = SwarmDHT(info.node_id, PORTS.gossip(idx), bootstrap=[], host=HOST,
                   gossip_period_s=0.05, ttl_s=5.0)
    node = Node(info, cfg, parts_dir, dht, backend="qwen3", max_len=160,
                rebalance_period_s=600.0, **kw)
    await node.start()
    try:
        for _ in range(2400):  # the warm-up compiles what the hops run
            if any(e["type"].startswith("executor.warmup_") for e in node.journal.events()):
                break
            await asyncio.sleep(0.05)
        (await node._get_generate_client()).prefill_chunk = 4
        node.tracer.drain()  # the warm-up's spans
        before = {"executor": node.executor.stats(), "trace": node.tracer.stats()}
        wall0 = tracelib.now()
        decoding, got = asyncio.Event(), []

        def on_long(tok):
            got.append(tok)
            if len(got) == 4:
                decoding.set()

        async with SwarmClient([(HOST, info.port)], sampling=GREEDY) as c:
            async def newcomers():
                await decoding.wait()
                return await asyncio.gather(*(
                    c.generate_server_side_stream(p, lambda t: None, NEW) for p in PROMPTS))

            long, out = await asyncio.gather(
                c.generate_server_side_stream(PROMPTS[0], on_long, LONG_NEW), newcomers())
            out.append(await c.generate_server_side_stream(PROMPTS[1], lambda t: None, NEW))
        assert [len(o) for o in out] == [NEW] * 3 and len(long) == LONG_NEW
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://{HOST}:{info.port}/spans") as r:
                served = [json.loads(line) for line in (await r.text()).splitlines() if line]
        path = os.path.join(tmp, f"{idx}.spans.jsonl")
        flushed = []
        if node.tracer.flush_jsonl(path):
            with open(path) as f:
                flushed = [json.loads(line) for line in f]
        return {
            "spans": node.tracer.spans(), "served": served, "flushed": flushed,
            "kept": {s["span"] for s in node.tracer._kept},
            "stats0": before, "wall0": wall0, "wall1": tracelib.now(),
            "stats1": {"executor": node.executor.stats(), "trace": node.tracer.stats()},
        }
    finally:
        await node.stop()


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    out = {}
    for model in ("tiny", "tiny-sdar"):
        cfg = TINY if model == "tiny" else get_config(model)
        d = tmp_path_factory.mktemp(f"ad-{model}")
        split_and_save(qwen3.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                       Manifest.even_split(model, 1), str(d))
        out[model] = str(d)
    return out


@pytest.fixture(scope="module")
def served(parts, devices8, tmp_path_factory):
    cache = {}

    def of(topology):
        if topology not in cache:
            idx, model, kw = TOPOLOGIES[topology]
            with pytest.MonkeyPatch.context() as mp:
                _patient_riders(mp)
                cache[topology] = asyncio.run(asyncio.wait_for(
                    _serve(idx, model, kw, parts[model], str(tmp_path_factory.mktemp("ad-spans"))),
                    LIMIT_S))
        return cache[topology]

    return of


def _requests(run):
    """A request's spans by name, per trace id of a server `generate`."""
    out = []
    for root in (s for s in run["spans"] if s["name"] == "generate" and s["phase"] == "server"):
        mine = [s for s in run["spans"] if s["trace"] == root["trace"]]
        out.append({"root": root, "all": mine,
                    **{n: [s for s in mine if s["name"] == n]
                       for n in ADMISSION + ("step", "sample", "emit", "compute", "generate")}})
    return out


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_a_requests_admission_is_tiled_from_arrival_to_its_first_answered_hop(topology, served):
    run = served(topology)
    reqs = sorted(_requests(run), key=lambda r: r["root"]["t0"])
    assert len(reqs) == 4
    uncovered = []
    by_id = {s["span"]: s for s in run["spans"]}
    for i, r in enumerate(reqs):
        # once a request, all of its trace; a hop RIDES where other sessions'
        # rows are run ahead in the drain that takes it (the newcomers', as
        # their arrival falls; tests/test_step_ahead.py holds a ride itself),
        # else its drain answers it
        assert [len(r[n]) for n in ("accept", "open", "lane", "close")] == [1, 1, 1, 1]
        assert len(r["ride"]) <= int(i in (1, 2))
        accept, opened, lane, close = (r[n][0] for n in ("accept", "open", "lane", "close"))
        loop = next(s for s in r["generate"] if s["phase"] == "client")
        assert accept["parent"] == r["root"]["span"] and accept["attrs"] == {"prompt": 8, "stream": 1}
        assert opened["parent"] == loop["span"] == close["parent"]
        steps = sorted(r["step"], key=lambda s: s["t0"])
        first = [s for s in steps if s["attrs"].get("first")]
        assert len(first) == 1 and steps.index(first[0]) == 2  # behind the two chunks
        chunks = steps[:2]
        assert [s["attrs"]["n"] for s in chunks] == [4, 4]
        # the first token's pair lies between the last chunk and the first
        # hop (a model generated by blocks has none: its first hop makes it)
        pair = [s for n in ("sample", "emit") for s in r[n] if s["t0"] < first[0]["t0"]]
        assert [s["name"] for s in pair] == ([] if topology == "block" else ["sample", "emit"])
        chain = [accept, opened, *chunks, *pair, first[0]]
        assert r["root"]["t0"] <= accept["t0"] and accept["t1"] == opened["t0"]
        gaps = [b["t0"] - a["t1"] for a, b in zip(chain, chain[1:])]
        assert all(g >= 0 for g in gaps), gaps
        uncovered.append(sum(gaps) + accept["t0"] - r["root"]["t0"])
        assert steps[-1]["t1"] <= close["t0"] and close["t1"] <= loop["t1"]
        # beneath the steps: the lane is bound in the first chunk's call,
        # the first hop rides the step it is handed
        computes = {c["span"]: by_id[by_id[c["parent"]]["parent"]] for c in r["compute"]}
        assert computes[lane["parent"]] is chunks[0]
        assert lane["attrs"]["new"] == 1 and lane["attrs"]["evicted"] == 0
        for ride in r["ride"]:
            assert computes[ride["parent"]] is first[0] and ride["attrs"]["behind"] >= 0
        # all of it is kept, and of the hops after the first nothing is
        assert {s["span"] for s in chain + r["ride"] + [lane, close, r["root"], loop]} <= run["kept"]
        later = [s for s in steps[3:]]
        assert later and not {s["span"] for s in later} & run["kept"]
    assert sorted(uncovered)[1] <= 1e-3  # the parts abut: a millisecond a request at most
    # a lane that served a request before (the warm-up's among them) says
    # how long it stood free; one never used has stood free for no known time
    vacant = [r["lane"][0]["attrs"]["vacant_ms"] for r in reqs]
    assert None in vacant[:3] and vacant[3] is not None
    assert all(v >= 0 for v in vacant if v is not None)


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_what_a_request_keeps_does_not_grow_with_its_tokens(topology, served):
    run = served(topology)
    reqs = sorted(_requests(run), key=lambda r: r["root"]["t0"])
    counts = [
        sorted(s["name"] for s in r["all"]
               if s["span"] in run["kept"] and s["name"] not in ONCE_A_STEP + ("ride",))
        for r in reqs
    ]
    assert len(reqs[0]["step"]) > len(reqs[1]["step"]) + 20  # ten times the tokens
    assert counts[0] == counts[1] == counts[2] == counts[3] and 20 <= len(counts[0]) <= 48
    # once a step: every step's `device` and `copy_out` and every `turn` is kept
    for name in ONCE_A_STEP:
        of = [s for s in run["spans"] if s["name"] == name]
        assert of and {s["span"] for s in of} <= run["kept"]
    ex0, ex1 = run["stats0"]["executor"], run["stats1"]["executor"]
    decode = [s for s in run["spans"] if s["name"] == "device" and s["attrs"]["kind"] != "prefill"]
    assert len(decode) == ex1["batched_steps"] - ex0["batched_steps"]
    assert run["stats1"]["trace"]["kept_dropped"] == 0


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_spans_and_the_flushed_file_give_each_span_once(topology, served):
    run = served(topology)
    ids = [s["span"] for s in run["served"]]
    assert len(ids) == len(set(ids)) and set(ids) == {s["span"] for s in run["spans"]}
    assert [s["t0"] for s in run["served"]] == sorted(s["t0"] for s in run["served"])
    flushed = [s["span"] for s in run["flushed"]]
    assert len(flushed) == len(set(flushed)) and set(flushed) == set(ids)


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_the_counters_of_an_admission_are_fed_by_the_same_boundaries(topology, served):
    run = served(topology)
    moved = {k: run["stats1"]["executor"][k] - run["stats0"]["executor"][k]
             for k in ("admissions", "lane_vacant_ms_sum", "rides", "ride_ms_sum",
                       "steps_waited", "steps_found_done")}
    lanes = [s for s in run["spans"] if s["name"] == "lane"]
    rides = [s for s in run["spans"] if s["name"] == "ride"]
    assert moved["admissions"] == len(lanes) == 4 and moved["rides"] == len(rides) <= 2
    assert moved["lane_vacant_ms_sum"] == pytest.approx(
        sum(s["attrs"]["vacant_ms"] or 0.0 for s in lanes), abs=0.01)
    # the counter's ride ends where the span's last call begins
    assert bool(rides) == (moved["ride_ms_sum"] > 0)
    assert moved["ride_ms_sum"] <= sum((s["t1"] - s["t0"]) * 1e3 + 0.1 for s in rides)
    seen = [s["attrs"]["waited"] for s in run["spans"]
            if s["name"] == "device" and s["attrs"]["kind"] != "prefill"]
    assert (moved["steps_waited"], moved["steps_found_done"]) == (sum(seen), len(seen) - sum(seen))
    holds = [s["attrs"]["held_ms"] for s in run["spans"]
             if s["name"] == "lock_wait" and s["attrs"]["kind"] == "prefill"]
    assert len(holds) == 8 and all(h > 0 for h in holds)


def test_tracing_off_stamps_nothing_and_keeps_the_counters(parts, devices8, monkeypatch, tmp_path):
    monkeypatch.setenv("INFERD_TRACE", "0")
    idx, model, kw = TOPOLOGIES["lanes"]
    run = asyncio.run(asyncio.wait_for(_serve(idx + 10, model, kw, parts[model], str(tmp_path)), LIMIT_S))
    assert run["spans"] == [] and run["served"] == [] and run["stats1"]["trace"]["recorded"] == 0
    ex0, ex1 = run["stats0"]["executor"], run["stats1"]["executor"]
    assert ex1["admissions"] - ex0["admissions"] == 4 and ex1["lane_vacant_ms_sum"] > 0
    assert ex1["steps_waited"] + ex1["steps_found_done"] > ex0["steps_waited"] + ex0["steps_found_done"]


# ---------------------------------------------------------------------------
# (iv) the benchmark's readers
# ---------------------------------------------------------------------------

READERS = (
    "node.accept_ms_p50", "node.open_ms_p50", "node.close_ms_p50", "node.admission_ms_p50",
    "window.lane_bind_ms_p50", "kv.lane_vacant_ms_p50", "window.ride_ms_p50",
    "window.prefill_hold_ms_p50", "window.step_seen_share", "window.device_seen_ms_p50",
    "window.turn_cut_ms_p50",
)


def _reader(metric):
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            metric.replace(".", "_"), os.path.join(bench, "layer_metrics", f"{metric}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    return mod.read


def _the_parents(run):
    """The run as a program older than these spans leaves it."""
    spans = []
    for s in run["spans"]:
        if s["name"] in ADMISSION:
            continue
        attrs = {k: v for k, v in (s.get("attrs") or {}).items()
                 if k not in ("first", "waited", "held_ms", "chunks")}
        spans.append({**s, "attrs": attrs})
    stats = []
    for st in (run["stats0"], run["stats1"]):
        ex = {k: v for k, v in st["executor"].items()
              if k not in ("admissions", "lane_vacant_ms_sum", "rides", "ride_ms_sum",
                           "steps_waited", "steps_found_done")}
        stats.append({**st, "executor": ex})
    return {**run, "spans": spans, "stats0": stats[0], "stats1": stats[1]}


def _a_turn_cut_into(run):
    """The run with one more turn: somebody's, 9 ms long, its formation over
    after 2: a prefill held the device's lock for the rest."""
    t0 = run["wall0"] + 0.001
    cut = {"trace": "t", "span": "cut", "parent": None, "name": "turn", "phase": "turn",
           "t0": t0, "t1": t0 + 0.009,
           "attrs": {"kind": "decode", "cobatch": 2, "expected": 2, "how": "full",
                     "formed_ms": 2.0, "first_ms": 0.5, "last": None}}
    return {**run, "spans": run["spans"] + [cut]}


@pytest.mark.parametrize("program", ["this_tree", "the_parent"])
@pytest.mark.parametrize("metric", READERS)
def test_each_reader_reads_a_number_here_and_nothing_on_the_parent(metric, program, served):
    run = served("lanes")
    read = _reader(metric)
    if program == "the_parent":
        assert read(_the_parents(run)) is None or metric == "window.turn_cut_ms_p50"
        assert read({**run, "spans": [], "stats0": {}, "stats1": {}}) is None
        return
    if metric == "window.turn_cut_ms_p50":
        uncut = [s for s in run["spans"] if s["name"] != "turn" or
                 (s["t1"] - s["t0"]) * 1e3 - s["attrs"]["formed_ms"] <= 1.0]
        assert read(_a_turn_cut_into({**run, "spans": uncut})) == pytest.approx(9.0, abs=1e-3)
        return
    got = read(run)
    assert isinstance(got, float) and got >= 0
    by_name = {n: sorted((s["t1"] - s["t0"]) * 1e3 for s in run["spans"] if s["name"] == n)
               for n in ADMISSION}
    simple = {"node.accept_ms_p50": "accept", "node.open_ms_p50": "open",
              "node.close_ms_p50": "close", "window.lane_bind_ms_p50": "lane",
              "window.ride_ms_p50": "ride"}
    if metric in simple:
        of = by_name[simple[metric]]
        assert got == pytest.approx((of[len(of) // 2] + of[(len(of) - 1) // 2]) / 2)
    elif metric == "node.admission_ms_p50":
        whole = sorted(
            (next(s for s in r["step"] if s["attrs"].get("first"))["t1"] - r["accept"][0]["t0"]) * 1e3
            for r in _requests(run))
        assert got == pytest.approx((whole[1] + whole[2]) / 2)
    elif metric == "kv.lane_vacant_ms_p50":
        vacant = sorted(s["attrs"]["vacant_ms"] for s in run["spans"]
                        if s["name"] == "lane" and s["attrs"]["vacant_ms"] is not None)
        assert vacant[0] <= got <= vacant[-1] and len(vacant) < 4  # a lane never used has none
    elif metric == "window.step_seen_share":
        assert 0 < got <= 100


def test_the_admission_reader_says_nothing_where_its_parts_do_not_tile(served):
    run = served("lanes")
    read = _reader("node.admission_ms_p50")
    moved = [dict(s, t1=s["t1"] + 0.005) if s["name"] == "open" else s for s in run["spans"]]
    assert read({**run, "spans": moved}) == read(run)  # an overlap hides nothing
    holed = [s for s in run["spans"]  # every request's second chunk is gone
             if not (s["name"] == "step" and s["attrs"]["start_pos"] == 4 and s["attrs"]["n"] == 4)]
    assert read({**run, "spans": holed}) is None  # more than a millisecond a request under no span
