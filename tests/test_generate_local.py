"""/generate serves its own hops in process (client/local_client.py,
Node._serve_local): the node's generation loop enters the forward path
the /forward route enters, as a Python call. What a caller of /generate
gets — tokens, log-probabilities, top-lists, error statuses and codes,
the restart line — is what an outside SwarmClient posting /forward to
the same node gets; a hop the node finishes touches no socket
(`forward.local` counts it, the route is never entered); the spans keep
the shape the benchmark's readers walk."""

import asyncio
import importlib.util
import json
import os
import time

import aiohttp
import jax
import numpy as np
import pytest

from inferd_tpu.client.base import ServerError
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.node import Node, NodeInfo
from inferd_tpu.utils import retry as retrylib
from inferd_tpu.utils.chaos import Chaos

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)
HOST = "127.0.0.1"
GREEDY = SamplingConfig(temperature=0.0)
SAMPLED = SamplingConfig(temperature=0.8, top_k=20, top_p=0.95)
PROMPT = [3, 7, 11, 19, 23]
NEW = 6


@pytest.fixture(scope="module")
def parts(tmp_path_factory):
    """One checkpoint split in one stage and in two."""
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    out = {}
    for n in (1, 2):
        d = tmp_path_factory.mktemp(f"parts{n}")
        split_and_save(params, TINY, Manifest.even_split("tiny", n), str(d))
        out[n] = str(d)
    return out


def _mk_node(idx, parts_dir, stage=0, num_stages=1, bootstrap_idx=None, **kw):
    info = NodeInfo(
        name=f"gl{idx}", host=HOST, port=PORTS.http(idx), stage=stage,
        num_stages=num_stages, capacity=8, model_name="tiny",
    )
    boot = [] if bootstrap_idx in (None, idx) else [(HOST, PORTS.gossip(bootstrap_idx))]
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx), bootstrap=boot, host=HOST,
        gossip_period_s=0.05, ttl_s=5.0,
    )
    node = Node(
        info, TINY, parts_dir, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, **kw,
    )
    # every entry of the aiohttp /forward route is counted (start() binds
    # the attribute it finds)
    node.route_entries = 0
    route = node.handle_forward

    async def counted(request):
        node.route_entries += 1
        return await route(request)

    node.handle_forward = counted
    return node


async def _start(nodes):
    for n in nodes:
        await n.start()
    for _ in range(200):
        if all(
            all(n.dht.get_all(n.info.num_stages)[s] for s in range(n.info.num_stages))
            for n in nodes
        ):
            return
        await asyncio.sleep(0.05)
    raise TimeoutError("swarm did not converge")


async def _stop(nodes):
    for n in nodes:
        try:
            await n.stop()
        except Exception:
            pass


def _counters(node):
    return node.metrics.snapshot()["counters"]


TOPOLOGIES = {
    "lanes": lambda p: [_mk_node(0, p[1], batch_lanes=4)],
    "solo": lambda p: [_mk_node(1, p[1])],
    "mesh": lambda p: [_mk_node(2, p[1], mesh_plan=MeshPlan(pp=2), mesh_slots=3)],
    "two_stage": lambda p: [
        _mk_node(3 + i, p[2], stage=i, num_stages=2, bootstrap_idx=3)
        for i in range(2)
    ],
}


@pytest.mark.asyncio
@pytest.mark.parametrize("topology", list(TOPOLOGIES))
async def test_generate_matches_an_outside_client(parts, devices8, topology):
    """Streamed and not, greedy, seeded sampled, with log-probabilities
    and top-lists, with a pinned prefix: /generate answers what a
    SwarmClient outside the node computes from /forward replies."""
    nodes = TOPOLOGIES[topology](parts)
    await _start(nodes)
    entry = [(HOST, nodes[0].info.port)]
    try:
        async with SwarmClient(entry, sampling=GREEDY) as c:
            got = {}
            got["greedy"] = await c.generate_server_side(PROMPT, NEW)
            streamed = []
            got["streamed"] = await c.generate_server_side_stream(
                PROMPT, streamed.append, NEW
            )
            assert streamed == got["streamed"]
            got["sampled"] = await c.generate_server_side(
                PROMPT, NEW, seed=7, sampling=SAMPLED
            )
            lps, tops = [], []
            got["logprobs"] = await c.generate_server_side(
                PROMPT, NEW, logprob_sink=lps, top_logprobs=8, top_sink=tops
            )
            # twice: the first pins the prefix and forks it, the second
            # forks the pin it finds
            got["pinned"] = [
                await c.generate_server_side(PROMPT, NEW, pin_prefix_len=3)
                for _ in range(2)
            ]
            # prompt == the pin: the first token comes from the pin's own row
            got["pin_is_prompt"] = await c.generate_server_side(
                PROMPT[:3], NEW, pin_prefix_len=3
            )

            first = _counters(nodes[0])
            assert first["forward.local"] == first["forward.requests"] > 0
            if len(nodes) == 1:
                # the node served every hop itself: its own port saw no /forward
                assert nodes[0].route_entries == 0
            else:
                # stage 0 relays each hop once, to stage 1's route; the
                # hop from the loop into stage 0 is the one that is gone
                assert nodes[0].route_entries == 0
                assert nodes[1].route_entries == first["forward.requests"]
                assert _counters(nodes[1]).get("forward.local", 0) == 0

            want = {}
            want["greedy"] = await c.generate_ids(PROMPT, NEW)
            want["streamed"] = want["greedy"]
            want["sampled"] = await c.generate_ids(
                PROMPT, NEW, seed=7, sampling=SAMPLED
            )
            wlps, wtops = [], []
            want["logprobs"] = await c.generate_ids(
                PROMPT, NEW, logprob_sink=wlps, top_n=8, top_sink=wtops
            )
            await c.pin_prefix(PROMPT[:3])
            want["pinned"] = [await c.generate_ids(PROMPT, NEW) for _ in range(2)]
            want["pin_is_prompt"] = await c.generate_ids(PROMPT[:3], NEW)
        assert got == want
        assert len(got["greedy"]) == NEW and got["sampled"] != got["greedy"]
        # exactly: the same float32 row went through the same float64 code
        assert lps == wlps
        assert [(list(i), list(l)) for i, l in tops] == [
            (list(i), list(l)) for i, l in wtops
        ]
        after = _counters(nodes[0])
        assert nodes[0].route_entries > 0  # the outside client's posts
        assert after["forward.local"] == first["forward.local"]
        assert after["forward.local"] < after["forward.requests"]
    finally:
        await _stop(nodes)


def _env(session_id, tokens, start_pos, **extra):
    return {
        "task_id": "t", "session_id": session_id, "stage": 0,
        "payload": {
            "tokens": np.asarray([tokens], dtype=np.int32),
            "start_pos": start_pos, "real_len": len(tokens),
        },
        **extra,
    }


async def _hop_error(post, env):
    with pytest.raises(ServerError) as ei:
        await post("/forward", env)
    e = ei.value
    # "<where> error <status>: <the node's message>"
    return (e.status, e.code, e.retry_after, e.resume_from,
            str(e).split(": ", 1)[1])


ERRORS = {
    # KV overflow: a chunk longer than the 64-slot lane
    "overflow": dict(status=409, code="overflow", env=lambda: _env("o", list(range(1, 70)), 0)),
    # admission shed by the block pool's watermark: typed, paced
    "busy": dict(status=503, code="busy", env=lambda: _env("b", PROMPT, 0)),
    "deadline": dict(
        status=408, code="deadline",
        env=lambda: _env("d", PROMPT, 0, deadline_ms=(time.time() - 5.0) * 1e3),
    ),
    "chaos_drop": dict(status=500, code=None, env=lambda: _env("c", PROMPT, 0)),
    "draining": dict(status=503, code="draining", env=lambda: _env("r", PROMPT, 0)),
}


@pytest.mark.asyncio
@pytest.mark.parametrize("case", list(ERRORS))
async def test_errors_come_back_as_over_http(parts, monkeypatch, case):
    """The in-process hop raises the ServerError the HTTP hop raised
    (status, code, retry_after, resume_from, message), and /generate
    passes status and code on as it did."""
    spec = ERRORS[case]
    idx = 10 + list(ERRORS).index(case)
    chaos = {"chaos_drop": Chaos(drop=1.0), "deadline": Chaos(delay_ms=300.0)}.get(case)
    node = _mk_node(idx, parts[1], batch_lanes=2, chaos=chaos)
    # the retry loop's pacing is not what is compared
    monkeypatch.setattr(retrylib, "backoff_delay", lambda *a, **k: 0.0)
    await _start([node])
    try:
        if case == "busy":
            node._pool_under_reserve = lambda: (0, 8, 1)
        if case == "draining":
            node._draining = True
        local = await node._get_generate_client()
        async with SwarmClient([(HOST, node.info.port)], sampling=GREEDY) as c:
            # the deadline case compares the node's own entry check: its
            # chaos delay is for the end-to-end request below
            if case == "deadline":
                node.chaos.delay_ms = 0.0
            over_http = await _hop_error(c._post, spec["env"]())
            in_process = await _hop_error(local._post, spec["env"]())
            assert in_process == over_http
            assert in_process[:2] == (spec["status"], spec["code"])
            if case in ("busy", "draining"):
                assert in_process[2] == node._retry_after_s() > 0
            assert node.route_entries == 1  # the outside post alone

            if case == "deadline":
                node.chaos.delay_ms = 300.0
            kw = {}
            prompt = PROMPT
            if case == "overflow":
                prompt = list(range(1, 61))  # decode runs off the lane's end
                kw["max_new_tokens"] = 16
            if case == "deadline":
                # admitted with budget left; the first hop outlives it and
                # the loop's next hop fails before it is made
                kw["deadline_s"] = 0.15
            with pytest.raises(ServerError) as ei:
                await c.generate_server_side(prompt, **kw)
            assert (ei.value.status, ei.value.code) == (spec["status"], spec["code"])
            assert node.route_entries == 1
    finally:
        await _stop([node])


@pytest.mark.parametrize("entry", ["process", "begin_hop"])
@pytest.mark.asyncio
async def test_a_failure_mid_generation_restarts_the_stream(parts, monkeypatch, entry):
    """A retryable failure after tokens were streamed: a {"restart": true}
    line, then the deterministic re-run's tokens, the same as undisturbed.
    Whichever way the hop went in: on a worker (`process`; the loop's form
    taken away) or on the event loop (`begin_hop`: a prefill and a request's
    first decode hop, which rides a step, still enter `process`)."""
    node = _mk_node(20 + (entry == "begin_hop"), parts[1], batch_lanes=2)
    monkeypatch.setattr(retrylib, "backoff_delay", lambda *a, **k: 0.0)
    await _start([node])
    try:
        async with SwarmClient([(HOST, node.info.port)], sampling=GREEDY) as c:
            want = await c.generate_server_side(PROMPT, NEW)
        enter, calls = getattr(node.executor, entry), []

        def failing_once(session_id, payload, *hand):
            calls.append(session_id)
            if len(calls) == 4:  # the prefill and two decode steps went through
                raise RuntimeError("injected compute failure")
            return enter(session_id, payload, *hand)

        if entry == "process":
            node.executor.begin_hop = None  # every hop takes a worker, through `process`
        # (`begin_hop` sees every call on its way in: the prefill, which it
        # leaves to `process`, and every decode hop)
        setattr(node.executor, entry, failing_once)
        body = wire.pack({
            "prompt_ids": PROMPT, "max_new_tokens": NEW, "stream": True,
            "sampling": {"temperature": 0.0},
        })
        async with aiohttp.ClientSession() as http:
            async with http.post(
                f"http://{HOST}:{node.info.port}/generate", data=body
            ) as r:
                assert r.status == 200
                lines = [json.loads(x) for x in (await r.read()).splitlines() if x.strip()]
        cut = lines.index({"restart": True})
        assert [x["t"] for x in lines[:cut]] == want[:3]
        assert [x["t"] for x in lines[cut + 1:-1]] == want
        assert lines[-1]["done"] and lines[-1]["ids"] == want
        assert node.route_entries == 0
    finally:
        await _stop([node])


def _load_reader(monkeypatch, name):
    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    monkeypatch.syspath_prepend(bench)  # the reader imports its neighbours
    spec = importlib.util.spec_from_file_location(
        "reader_under_test", os.path.join(bench, "layer_metrics", name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.asyncio
async def test_spans_keep_the_shape_the_readers_walk(parts, monkeypatch):
    """compute -> forward (via local) -> step, a queue span under the same
    forward, and the benchmark's reader of the host turn reads a number."""
    monkeypatch.setenv("INFERD_TRACE", "1")
    node = _mk_node(21, parts[1], batch_lanes=2)
    await _start([node])
    try:
        t0 = time.time()
        async with SwarmClient([(HOST, node.info.port)], sampling=GREEDY) as c:
            await c.generate_server_side(PROMPT, NEW)
        spans = node.tracer.spans()
    finally:
        await _stop([node])
    by_id = {s["span"]: s for s in spans}
    decodes = [
        s for s in spans
        if s["name"] == "compute" and s["attrs"].get("kind") == "decode"
    ]
    assert len(decodes) == NEW - 1
    for c in decodes:
        assert c["attrs"]["tokens"] == 1
        forward = by_id[c["parent"]]
        assert forward["name"] == "forward" and forward["attrs"]["via"] == "local"
        step = by_id[forward["parent"]]
        assert step["name"] == "step" and step["t0"] <= forward["t0"] <= forward["t1"] <= step["t1"]
        queues = [s for s in spans if s["name"] == "queue" and s["parent"] == forward["span"]]
        assert len(queues) == 1
    assert all(s["attrs"]["via"] == "local" for s in spans if s["name"] == "forward")
    read = _load_reader(monkeypatch, "node.token_host_ms_p50")
    cost = read({"spans": spans, "wall0": t0 - 1.0, "wall1": time.time() + 1.0})
    assert cost is not None and 0.0 <= cost < 1e4
