"""Between two steps (docs/OBSERVABILITY.md "Between two steps"): what a
served generation leaves behind for every decode (or block) hop: `deliver`
under its `compute`, `resume` under its `forward`, `emit` for what it
streamed: and for every step the window's `turn`, whose `last` names the
`compute` of the entry that came back last; the parts of that session's way
from the step before abut; the counters of `/stats` `executor` are fed by
the same stamps; INFERD_TRACE=0 records none of the spans and keeps the
counters.

Each topology is served once (a module fixture): one node, two sessions
streamed side by side through /generate: the lane executor on two lanes,
the mesh executor on two virtual devices, and a model generated by blocks
on two lanes."""

import asyncio

import jax
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig, get_config
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime.node import Node, NodeInfo

from conftest import port_block  # noqa: E402

PORTS, HOST = port_block(__file__), "127.0.0.1"
GREEDY = SamplingConfig(temperature=0.0)
PROMPTS = ([3, 7, 11, 19, 23, 29, 31, 37], [5, 13, 17, 41, 43, 47, 53, 59])
NEW = 12
LIMIT_S = 240  # a topology's whole service, compiles included
HOP_KINDS = ("decode", "block")
COUNTERS = ("turns", "turn_ms_sum", "session_turns", "session_turn_ms_sum", "turn_est_ms")
TOPOLOGIES = {
    "lanes": (0, "tiny", {"batch_lanes": 2}),
    "mesh": (1, "tiny", {"mesh_plan": MeshPlan(pp=2), "mesh_slots": 2}),
    "block": (2, "tiny-sdar", {"batch_lanes": 2}),
}


async def _serve(idx, model, kw, parts_dir):
    """Start the node, stream both prompts side by side, stop it; what it
    left: the spans, `/stats` `executor` before and after, the tokens."""
    cfg = TINY if model == "tiny" else get_config(model)
    info = NodeInfo(name=f"ht{idx}", host=HOST, port=PORTS.http(idx), stage=0,
                    num_stages=1, capacity=8, model_name=model)
    dht = SwarmDHT(info.node_id, PORTS.gossip(idx), bootstrap=[], host=HOST,
                   gossip_period_s=0.05, ttl_s=5.0)
    node = Node(info, cfg, parts_dir, dht, backend="qwen3", max_len=64,
                rebalance_period_s=600.0, **kw)
    await node.start()
    try:
        for _ in range(2400):  # the warm-up compiles what the hops run
            if any(e["type"].startswith("executor.warmup_") for e in node.journal.events()):
                break
            await asyncio.sleep(0.05)
        before = node.executor.stats()
        streamed = [[] for _ in PROMPTS]
        async with SwarmClient([(HOST, info.port)], sampling=GREEDY) as c:
            out = await asyncio.gather(*(
                c.generate_server_side_stream(p, got.append, NEW)
                for p, got in zip(PROMPTS, streamed)
            ))
        assert [list(o) for o in out] == streamed and all(len(o) == NEW for o in out)
        return {"spans": node.tracer.spans(), "before": before,
                "after": node.executor.stats(), "block": cfg.block_length}
    finally:
        await node.stop()


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    out = {}
    for model in ("tiny", "tiny-sdar"):
        cfg = TINY if model == "tiny" else get_config(model)
        d = tmp_path_factory.mktemp(f"ht-{model}")
        split_and_save(qwen3.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                       Manifest.even_split(model, 1), str(d))
        out[model] = str(d)
    return out


@pytest.fixture(scope="module")
def served(parts, devices8):
    cache = {}

    def of(topology):
        if topology not in cache:
            idx, model, kw = TOPOLOGIES[topology]
            cache[topology] = asyncio.run(asyncio.wait_for(
                _serve(idx, model, kw, parts[model]), LIMIT_S))
        return cache[topology]

    return of


def _hops(spans):
    """Every decode / block call with what hangs around it, and each one's
    hop before in its generation."""
    by_id = {s["span"]: s for s in spans}

    def kids(parent, name):
        return [s for s in spans if s["parent"] == parent["span"] and s["name"] == name]

    hops = []
    for c in spans:
        if c["name"] != "compute" or c["attrs"].get("kind") not in HOP_KINDS:
            continue
        forward = by_id[c["parent"]]
        step = by_id[forward["parent"]]
        assert forward["name"] == "forward" and step["name"] == "step"
        waits = kids(c, "lock_wait") + kids(c, "batch_wait")
        hops.append({
            "compute": c, "forward": forward, "step": step,
            "deliver": kids(c, "deliver"), "resume": kids(forward, "resume"),
            "queue": kids(forward, "queue"), "submit": min(w["t0"] for w in waits),
            "taken": max(w["t1"] for w in waits),  # the drain that took it
        })
    for h in hops:
        mine = sorted((s for s in spans if s["name"] == "step"
                       and s["trace"] == h["step"]["trace"]), key=lambda s: s["t0"])
        at = mine.index(h["step"])
        h["prev"] = next((p for p in hops if at and p["step"] is mine[at - 1]), None)
    return hops


def _turns(run):
    """The turns that ended since the generation began (the warm-up's own
    steps lie before `before` was read)."""
    began = min(s["t0"] for s in run["spans"] if s["name"] == "generate")
    return sorted((s for s in run["spans"] if s["name"] == "turn" and s["t1"] >= began),
                  key=lambda s: s["t0"])


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_every_hop_leaves_its_parts_and_every_step_its_turn(served, topology):
    run = served(topology)
    spans, hops = run["spans"], _hops(run["spans"])
    per_hop = 1 if run["block"] == 1 else run["block"]
    assert len(hops) >= 2 * (NEW // per_hop - 1)
    for h in hops:
        assert len(h["deliver"]) == len(h["resume"]) == len(h["queue"]) == 1
        assert "attrs" not in h["deliver"][0] and h["deliver"][0]["phase"] == "deliver"
    # one `emit` a streamed token; one a hop where a hop carries a block
    emits = [s for s in spans if s["name"] == "emit"]
    assert sum(e["attrs"]["tokens"] for e in emits) == 2 * NEW
    if run["block"] == 1:
        assert len(emits) == 2 * NEW  # the token after the prefill has one too
        assert len(hops) == 2 * (NEW - 1)
    else:
        assert len(emits) == len(hops)
    generations = {s["span"] for s in spans if s["name"] == "generate"}
    assert all(e["parent"] in generations for e in emits)
    # one turn a step: between two device steps of the window's kind lies
    # exactly one, and it ends before the later one is dispatched
    steps = sorted((s for s in spans if s["name"] == "device"
                    and s["attrs"]["kind"] in HOP_KINDS), key=lambda s: s["t0"])
    turns = _turns(run)
    mine = [s for s in steps if s["t0"] >= turns[0]["t1"]]
    computes = {h["compute"]["span"]: h for h in hops}
    beside = sum(d["t0"] < t["t1"] and t["t0"] < d["t1"] for t in turns for d in steps)
    # both executors keep one step ahead, block steps and the mesh's passes
    # too: a drain dispatches the NEXT step (two where nobody was ahead yet:
    # the riders' own and the one behind it), so a step runs BESIDE the turn
    # that follows its drain; a drain dispatches none where every hop it
    # took was its generation's last (the sessions' ends: one such turn
    # where the two end together, two where the machine's load has pulled
    # them apart)
    followed = {id(h["prev"]) for h in hops}
    ended = [all(id(h) not in followed for h in hops if h["taken"] == t["t1"]) for t in turns]
    assert 1 <= sum(ended) <= 2
    assert len(mine) >= len(turns) - sum(ended) and len(turns) >= NEW // per_hop - 1
    assert beside >= NEW // per_hop // 2
    for i, t in enumerate(turns):
        a = t["attrs"]
        # the drain that ended the turn dispatched a step before the next drain came
        until = turns[i + 1]["t1"] if i + 1 < len(turns) else float("inf")
        assert ended[i] or any(t["t1"] <= s["t0"] <= until for s in steps)
        # the step dispatched at the drain that ended the turn (none where
        # every entry was a generation's last hop)
        step = min((s for s in steps if s["t0"] >= t["t1"]), key=lambda s: s["t0"],
                   default=steps[-1])
        assert t["parent"] is None and t["phase"] == "turn"
        assert a["kind"] == step["attrs"]["kind"]
        assert a["how"] in ("full", "timeout", "solo") and 0 <= a["expected"] <= 2
        assert a["formed_ms"] <= (t["t1"] - t["t0"]) * 1e3 + 1e-3
        # the entries this drain took carry its stamp as their wait's end
        taken = [h["submit"] for h in hops if h["taken"] == t["t1"]]
        assert len(taken) == a["cobatch"]
        assert computes[a["last"]]["submit"] == max(taken)
        assert abs(a["first_ms"] - (min(taken) - t["t0"]) * 1e3) < 2e-3
    # a formation is owed the sessions of the last two steps: both, here
    # (three block hops a session, the first of which rides a step that no
    # drain waits for, may be over before both were owed at once)
    owed = max(t["attrs"]["expected"] for t in turns)
    assert owed == 2 or (topology == "block" and owed == 1)


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_the_last_entrys_parts_abut_and_sum_to_the_turn(served, topology):
    run = served(topology)
    hops = {h["compute"]["span"]: h for h in _hops(run["spans"])}
    walked = 0
    for t in _turns(run):
        hop = hops[t["attrs"]["last"]]
        prev = hop["prev"]
        if prev is None:
            continue  # the step before was its prefill: no `deliver` to start from
        walked += 1
        deliver, resume = prev["deliver"][0], prev["resume"][0]
        queue = hop["queue"][0]
        order = [
            deliver["t0"], deliver["t1"], resume["t1"], prev["step"]["t1"],
            hop["step"]["t0"], queue["t0"], hop["compute"]["t0"], hop["submit"], t["t1"],
        ]
        assert order == sorted(order)
        assert abs(deliver["t1"] - prev["compute"]["t1"]) < 1e-4
        assert abs(resume["t0"] - prev["compute"]["t1"]) < 1e-4
        assert abs(queue["t1"] - hop["compute"]["t0"]) < 1e-4
        # the turn starts inside that session's `deliver` or, where the
        # session sat out a step, after it; what of the parts lies inside
        # the turn is the turn (the parts are `order`'s neighbours). A hop
        # that RODE its step (its `ride` says so) was answered by its own
        # thread, after the drain that dispatched the step had returned and
        # the turn begun: there the parts start at that drain (the hop's
        # `taken`), and the ride up to its step's end is one of them
        rides = [s for s in run["spans"]
                 if s["name"] == "ride" and s["parent"] == prev["compute"]["span"]]
        if rides:
            ride, = rides
            assert ride["t0"] <= deliver["t0"] <= ride["t1"] <= prev["compute"]["t1"]
            order = [prev["taken"], ride["t0"]] + order
            assert order == sorted(order)
        assert order[0] <= t["t0"]
        inside = sum(
            max(0.0, min(b, t["t1"]) - max(a, t["t0"])) for a, b in zip(order, order[1:])
        )
        assert abs((t["t1"] - t["t0"]) - inside) < 1e-3
    assert walked >= NEW // (1 if run["block"] == 1 else run["block"]) - 2


@pytest.mark.parametrize("topology", list(TOPOLOGIES))
def test_the_counters_are_the_spans_sums(served, topology):
    run = served(topology)
    delta = {k: run["after"][k] - run["before"][k] for k in COUNTERS[:4]}
    owed = [t for t in _turns(run) if t["attrs"]["expected"] > 0]
    assert delta["turns"] == len(owed) > 0
    total = sum(t["t1"] - t["t0"] for t in owed) * 1e3
    assert abs(delta["turn_ms_sum"] - total) <= 0.01 * total + 0.01
    # a session's own turn: its result out (the window notes it as the step
    # returns, inside `deliver`) -> its next submit; one a hop that follows
    # a hop, and every one shorter than deliver.t0 -> submit
    hops = _hops(run["spans"])
    followed = [h for h in hops if h["prev"] is not None]
    assert delta["session_turns"] == len(followed)
    upper = sum(h["submit"] - h["prev"]["deliver"][0]["t0"] for h in followed) * 1e3
    lower = sum(h["submit"] - h["prev"]["compute"]["t1"] for h in followed) * 1e3
    assert lower <= delta["session_turn_ms_sum"] + 0.01 <= upper + 0.02
    assert run["after"]["turn_est_ms"] > 0


@pytest.mark.asyncio
async def test_trace_off_records_none_of_them_and_keeps_the_counters(parts, monkeypatch):
    monkeypatch.setenv("INFERD_TRACE", "0")
    run = await asyncio.wait_for(_serve(3, "tiny", {"batch_lanes": 2}, parts["tiny"]), LIMIT_S)
    assert run["spans"] == []
    for k in COUNTERS:
        assert k in run["after"]
    assert run["after"]["turns"] - run["before"]["turns"] >= NEW - 2
    assert run["after"]["turn_ms_sum"] > run["before"]["turn_ms_sum"]
    # a session's first decode hop follows its prefill, not a step of the window
    assert run["after"]["session_turns"] - run["before"]["session_turns"] == 2 * (NEW - 2)
    assert run["after"]["session_turn_ms_sum"] > run["before"]["session_turn_ms_sum"]


def test_a_span_id_costs_no_system_call(monkeypatch):
    """`uuid.uuid4()` is an `os.urandom` call an id, and a system call gives
    up the GIL: on a node's event loop every recorded span handed the loop's
    thread away (PERF.md section 6, PR 40). Ids come from the process's own
    generator: 16 hex digits, none twice, whatever thread asks."""
    import os
    import threading

    from inferd_tpu.obs import trace as tracelib

    def refuse(n):
        raise AssertionError("a span id asked the system for entropy")

    monkeypatch.setattr(os, "urandom", refuse)
    rec = tracelib.SpanRecorder("ids")
    got = []

    def record():
        for _ in range(2000):
            t = tracelib.now()
            ctx = rec.record_span("a", "a", t, t)
            got.append((ctx.trace_id, ctx.span_id))

    threads = [threading.Thread(target=record) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    ids = [i for pair in got for i in pair]
    assert len(ids) == 16000 == len(set(ids))
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)


def test_a_forked_child_draws_ids_of_its_own():
    import os

    from inferd_tpu.obs import trace as tracelib

    r, w = os.pipe()
    pid = os.fork()
    if pid == 0:  # the child: what it draws first
        os.write(w, tracelib.new_id().encode())
        os._exit(0)
    os.waitpid(pid, 0)
    assert os.read(r, 16).decode() != tracelib.new_id()
