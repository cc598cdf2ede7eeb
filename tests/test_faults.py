"""Fault injection + failure recovery tests (SURVEY §5: the reference had
recovery *mechanisms* but no way to test them; here they're asserted):
chaos drop/delay, session-restart on node death, the flight-recorder
incident flow (peer.dead -> session.rescue journal sequence + the
postmortem CLI assembling it from the per-node JSONL artifacts), and the
on-demand jax.profiler endpoint."""

import asyncio
import functools
import glob
import os

import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.generate import Engine
from inferd_tpu.utils.chaos import Chaos, ChaosDrop

import test_node_e2e as e2e
from conftest import port_block
from test_node_e2e import _start_all, _stop_all, tiny_parts  # noqa: F401

PORTS = port_block(__file__)
_mk_node = functools.partial(e2e._mk_node, ports=PORTS)  # its nodes, this module's ports


def test_chaos_parse():
    c = Chaos.parse("drop=0.25,delay_ms=10,seed=3")
    assert c.drop == 0.25 and c.delay_ms == 10 and c.seed == 3
    assert Chaos.parse("") is None and Chaos.parse(None) is None
    with pytest.raises(ValueError):
        Chaos.parse("explode=1")


@pytest.mark.asyncio
async def test_chaos_drop_rate():
    c = Chaos(drop=0.5, seed=0)
    dropped = 0
    for _ in range(200):
        try:
            await c.before_forward()
        except ChaosDrop:
            dropped += 1
    assert 60 <= dropped <= 140  # ~50% of 200


@pytest.mark.asyncio
async def test_chaos_drop_surfaces_as_500():
    nodes = [_mk_node(70 + i, i, 2, bootstrap_idx=70) for i in range(2)]
    nodes[0].chaos = Chaos(drop=1.0)  # stage 0 drops everything
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(70))]) as c:
            with pytest.raises(RuntimeError, match="chaos drop"):
                await c._post(
                    "/forward", {"stage": 0, "session_id": "s", "payload": {}}
                )
        assert nodes[0].metrics.snapshot()["counters"]["chaos.dropped"] >= 1
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_node_death_mid_generation_recovers(tiny_parts):  # noqa: F811
    """Kill the only stage-1 node mid-generation: its record TTLs out, the
    spare node adopts stage 1 (empty-stage recovery), and the client's
    session-restart retry completes the SAME tokens (greedy determinism)."""
    parts, params = tiny_parts
    # n0: stage 0.  n1: stage 1 (will die).  n2: spare replica on stage 0
    # that must migrate to stage 1 after the death.
    nodes = [
        _mk_node(80, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=80),
        _mk_node(81, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=80),
        _mk_node(82, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=80),
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(80))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            # healthy first pass
            assert await c.generate_ids(prompt, max_new_tokens=6) == expected

            # stage 1's only server hard-crashes: no tombstone gossip, no
            # graceful anything — peers must detect the death via record-TTL
            # expiry (1.5 s in these tests)
            n1 = nodes[1]
            await n1.crash()
            nodes.remove(n1)

            # generation must still complete: retries span the TTL window
            # (1.5 s in these tests) + adoption by a spare
            got = await c.generate_ids(
                prompt, max_new_tokens=6, session_retries=8, retry_delay_s=0.5
            )
            assert got == expected
            # someone now serves stage 1
            stage1 = nodes[0].dht.get_stage(1)
            assert stage1, "no node adopted the dead stage"
    finally:
        await _stop_all(nodes)


@pytest.fixture(scope="module")
def tiny_parts3(tmp_path_factory):
    """TINY split into THREE stages — the incident e2e needs a mid-chain
    stage with a replica pair so a kill forces a rescue, not an adoption."""
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, split_and_save

    parts = tmp_path_factory.mktemp("parts3")
    params = qwen3.init_params(TINY, __import__("jax").random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 3)
    split_and_save(params, TINY, manifest, str(parts))
    return str(parts), params


@pytest.mark.asyncio
async def test_incident_journal_and_postmortem(tiny_parts3, tmp_path):
    """Kill the stage-1 replica HOLDING a session's KV mid-generation.

    Asserts the flight-recorder incident flow end to end: the upstream
    node journals `peer.dead` for the crashed hop, the surviving replica
    journals `session.rescue` (it saw a mid-session chunk without the KV
    while gossip still advertised the dead holder), both carry the
    request's trace_id, the generation still completes token-exact via
    the client's session restart — and `obs postmortem <trace_id>`
    assembles timeline + interleaved events + firing SLO rules entirely
    from the per-node JSONL artifacts (--trace-dir output)."""
    from inferd_tpu.obs import postmortem as pmlib
    from inferd_tpu.obs.__main__ import main as obs_main

    parts, params = tiny_parts3
    obs_dir = str(tmp_path / "obs")
    # n44: stage 0 (entry). n45+n46: stage-1 replica pair (one will die).
    # n47: stage 2.
    nodes = [
        _mk_node(44, 0, 3, backend="qwen3", parts=parts, bootstrap_idx=44),
        _mk_node(45, 1, 3, backend="qwen3", parts=parts, bootstrap_idx=44),
        _mk_node(46, 1, 3, backend="qwen3", parts=parts, bootstrap_idx=44),
        _mk_node(47, 2, 3, backend="qwen3", parts=parts, bootstrap_idx=44),
    ]
    for n in nodes:
        n.trace_dir = obs_dir
    await _start_all(nodes)
    live = list(nodes)
    stage1 = [nodes[1], nodes[2]]
    try:
        engine = Engine(
            TINY, params, max_len=64,
            sampling_cfg=SamplingConfig(temperature=0.0),
        )
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=24)

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(44))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            tokens = []
            state = {}

            async def on_token(tok):
                # crash the KV holder BETWEEN steps (the hook is awaited
                # inside the client's token loop, so no request is
                # mid-flight at the victim): the next mid-session chunk
                # then fails at connection level (peer.dead), lands on
                # the survivor without its KV while gossip still
                # advertises the corpse (session.rescue), and 409s the
                # client into a session restart. A crash during an
                # in-flight step would surface as a 500 from the dying
                # handler instead and skip the rescue path entirely.
                tokens.append(tok)
                if len(tokens) == 3 and "victim" not in state:
                    victim = next(
                        (n for n in stage1 if len(n.executor.sessions) > 0),
                        None,
                    )
                    assert victim is not None, (
                        "no stage-1 replica held the session"
                    )
                    state["victim"] = victim
                    await victim.crash()

            got = await c.generate_ids(
                prompt, max_new_tokens=24, session_retries=10,
                retry_delay_s=0.4, on_token=on_token,
            )
            assert got == expected  # greedy determinism across the restart
            victim = state["victim"]
            live.remove(victim)
            survivor = next(n for n in stage1 if n is not victim)

            # the client's generate umbrella span carries the trace id
            roots = [
                s for s in c.tracer.spans()
                if s["name"] == "generate" and s.get("parent") is None
            ]
            assert roots, "client recorded no generate root span"
            tid = roots[0]["trace"]
            c.tracer.dump_jsonl(os.path.join(obs_dir, "client.spans.jsonl"))

        # ---- journal sequence: peer.dead -> session.rescue, same trace
        dead_evs = [
            ev for ev in nodes[0].journal.events()
            if ev["type"] == "peer.dead"
        ]
        assert dead_evs, "entry node journaled no peer.dead"
        assert any(ev.get("trace") == tid for ev in dead_evs)
        rescue_evs = [
            ev for ev in survivor.journal.events()
            if ev["type"] == "session.rescue"
        ]
        assert rescue_evs, "survivor journaled no session.rescue"
        assert any(ev.get("trace") == tid for ev in rescue_evs)
        assert min(ev["ts"] for ev in dead_evs) <= min(
            ev["ts"] for ev in rescue_evs
        ), "peer.dead must precede the rescue it caused"
        # the rescue relay's span joined the same trace on the survivor
        assert any(
            s.get("phase") == "rescue" and s["trace"] == tid
            for s in survivor.tracer.spans()
        )

        # ---- postmortem from the per-node JSONL artifacts alone
        await _stop_all(live)  # final flush writes spans/events/metrics
        live.clear()
        assert glob.glob(os.path.join(obs_dir, "*.events.jsonl"))
        assert glob.glob(os.path.join(obs_dir, "*.metrics.jsonl"))
        report = pmlib.build_report(tid, [obs_dir])
        assert report["timeline"]["stages"], "no per-stage timeline"
        ev_types = {ev["type"] for ev in report["events"]}
        assert {"peer.dead", "session.rescue"} <= ev_types
        kinds = {e["kind"] for e in report["entries"]}
        assert kinds == {"span", "event"}, "events not interleaved with spans"
        fired = {f["rule"] for f in report["firing"]}
        assert "event:peer.dead == 0" in fired, f"no firing SLO rule: {fired}"
        assert report["first_divergent_hop"] is not None
        # the CLI renders the same report from the same artifacts
        assert obs_main(["postmortem", tid, obs_dir]) == 0
    finally:
        await _stop_all(live)


@pytest.mark.asyncio
async def test_profile_endpoint_writes_trace(tmp_path):
    nodes = [_mk_node(95, 0, 1, bootstrap_idx=95)]
    nodes[0].enable_profiling = True  # endpoint is opt-in (ADVICE r1)
    nodes[0].profiler.base_dir = str(tmp_path)  # confine traces to tmp
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(95))]) as c:
            d = str(tmp_path / "trace")
            r = await c._post("/profile", {"action": "start", "name": "trace"})
            assert r["ok"] and r["dir"] == d
            # the endpoint is not a write-anywhere primitive
            r2 = await c._post("/profile", {"action": "stop"})
            with pytest.raises(RuntimeError, match="escapes profile dir"):
                await c._post("/profile", {"action": "start", "name": "../evil"})
            with pytest.raises(RuntimeError, match="escapes profile dir"):
                await c._post("/profile", {"action": "start", "name": "/tmp/evil"})
            r = await c._post("/profile", {"action": "start", "name": "trace"})
            # double start -> 409
            with pytest.raises(RuntimeError, match="already running"):
                await c._post("/profile", {"action": "start"})
            # some jax work to capture
            await c._post("/forward", {"stage": 0, "session_id": "p", "payload": {}})
            r = await c._post("/profile", {"action": "stop"})
            assert r["ok"]
            files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
            assert files, "profiler wrote nothing"
            # stop without start -> 409
            with pytest.raises(RuntimeError, match="no profile"):
                await c._post("/profile", {"action": "stop"})
            # gate: with profiling disabled the endpoint refuses outright
            nodes[0].enable_profiling = False
            with pytest.raises(RuntimeError, match="profiling disabled"):
                await c._post("/profile", {"action": "start"})
    finally:
        await _stop_all(nodes)


def test_server_error_retryability():
    from inferd_tpu.client.base import ServerError

    assert ServerError("x", 500).retryable  # transient node trouble
    assert ServerError("x", 502).retryable  # dead next hop
    assert ServerError("x", 409, code="session_state").retryable  # KV lost
    assert not ServerError("x", 409, code="overflow").retryable
    assert not ServerError("x", 409, code="wrong_stage").retryable
    assert not ServerError("x", 400).retryable  # malformed request
    # the overload plane's typed codes: an expired end-to-end deadline is
    # deterministic for the request (non-retryable); a shed is transient
    assert not ServerError("x", 408, code="deadline").retryable
    assert ServerError("x", 503, code="busy", retry_after=0.2).retryable
    assert ServerError("x", 503, code="draining").retryable


# ---------------------------------------------------------------------------
# PR 10 — overload containment: chaos extensions, backoff/budgets,
# deadlines, hedged relays, admission control, graceful drain
# ---------------------------------------------------------------------------


def test_chaos_parse_extended():
    c = Chaos.parse("jitter_ms=5:50,stall_p=0.3,drop_after=7,seed=9")
    assert c.jitter_ms == (5.0, 50.0)
    assert c.stall_p == 0.3 and c.drop_after == 7 and c.seed == 9
    # composes with the original keys
    c2 = Chaos.parse("drop=0.1,delay_ms=2,jitter_ms=0:1,stall_p=0.05")
    assert c2.drop == 0.1 and c2.delay_ms == 2 and c2.stall_p == 0.05
    with pytest.raises(ValueError, match="A:B"):
        Chaos.parse("jitter_ms=5")  # range syntax required
    with pytest.raises(ValueError, match="inverted"):
        Chaos.parse("jitter_ms=9:1")


@pytest.mark.asyncio
async def test_chaos_drop_after_healthy_then_sick():
    c = Chaos(drop_after=3, seed=0)
    for _ in range(3):  # healthy phase: first N forwards serve normally
        await c.before_forward()
    for _ in range(5):  # sick phase: everything drops
        with pytest.raises(ChaosDrop, match="drop_after"):
            await c.before_forward()


@pytest.mark.asyncio
async def test_chaos_stall_never_responds():
    """stall_p accepts the forward then never answers — the slow-loris
    that exercises deadline expiry and hedging (a drop answers instantly;
    only a stall makes the caller WAIT)."""
    c = Chaos(stall_p=1.0, seed=0)
    with pytest.raises(asyncio.TimeoutError):
        await asyncio.wait_for(c.before_forward(), timeout=0.1)
    # seeded composability: stall_p=0 never stalls, jitter still applies
    c2 = Chaos(jitter_ms=(0.0, 1.0), seed=1)
    await asyncio.wait_for(c2.before_forward(), timeout=1.0)


def test_backoff_full_jitter_deterministic():
    import random

    from inferd_tpu.utils.retry import backoff_delay

    rng = random.Random(42)
    sched = [backoff_delay(a, base_s=0.5, cap_s=4.0, rng=rng) for a in range(1, 6)]
    rng2 = random.Random(42)
    sched2 = [backoff_delay(a, base_s=0.5, cap_s=4.0, rng=rng2) for a in range(1, 6)]
    assert sched == sched2  # seeded => deterministic (the tests' contract)
    # full jitter: every delay inside [0, min(cap, base * 2^(n-1))]
    for i, d in enumerate(sched, start=1):
        assert 0.0 <= d <= min(4.0, 0.5 * 2 ** (i - 1))
    # the ceiling actually caps (attempt 5 would be 8.0 uncapped)
    assert all(d <= 4.0 for d in sched)


def test_retry_budget_token_bucket():
    from inferd_tpu.utils.retry import RatioBudget, RetryBudget

    t = [0.0]
    b = RetryBudget(rate_per_s=2.0, burst=3, clock=lambda: t[0])
    assert [b.try_acquire() for _ in range(4)] == [True, True, True, False]
    t[0] += 1.0  # refill 2 tokens
    assert b.try_acquire() and b.try_acquire() and not b.try_acquire()
    assert b.stats()["denied"] == 2
    # hedge ratio budget: <=5% of primaries + burst floor
    h = RatioBudget(ratio=0.05, burst=1)
    h.note(100)
    assert h.try_acquire()  # 1 <= 5 + 1
    for _ in range(5):
        h.try_acquire()
    assert not h.try_acquire()  # 7 > 0.05*100 + 1
    assert h.extra_frac() <= 0.06


class _FailingClient:
    """GenerationClient over a transport that always fails — the retry
    loop's unit harness (no HTTP, no nodes)."""

    def __init__(self, exc):
        from inferd_tpu.client.base import GenerationClient

        class C(GenerationClient):
            def __init__(inner):
                super().__init__()
                inner.steps = 0

            async def _step(inner, session_id, tokens, start_pos):
                inner.steps += 1
                raise exc

            async def _end_session(inner, session_id):
                pass

        self.client = C()


@pytest.mark.asyncio
async def test_retry_budget_exhaustion_surfaces_original_error():
    """When the per-process retry bucket is dry, generate_ids raises the
    ORIGINAL failure after the allowed retries — bounded amplification,
    and the operator sees what actually broke, not a budget error."""
    import random

    from inferd_tpu.client.base import ServerError
    from inferd_tpu.utils.retry import RetryBudget

    err = ServerError("boom: stage 1 down", 503)
    h = _FailingClient(err)
    budget = RetryBudget(rate_per_s=0.0, burst=2)  # exactly 2 retries, ever
    with pytest.raises(ServerError, match="boom"):
        await h.client.generate_ids(
            [1, 2, 3], max_new_tokens=2, session_retries=10,
            retry_delay_s=0.001, retry_budget=budget,
            retry_rng=random.Random(0),
        )
    # 1 initial attempt + the 2 budgeted retries; the other 8 never ran
    assert h.client.steps == 3
    assert budget.stats()["denied"] >= 1


@pytest.mark.asyncio
async def test_retry_honors_retry_after_hint():
    """A busy 503 carrying Retry-After paces the retry loop: the next
    attempt waits at least the hint, not just the jittered backoff."""
    import random
    import time as _time

    from inferd_tpu.client.base import ServerError

    err = ServerError("busy", 503, code="busy", retry_after=0.3)
    h = _FailingClient(err)
    t0 = _time.monotonic()
    with pytest.raises(ServerError):
        await h.client.generate_ids(
            [1], max_new_tokens=1, session_retries=1,
            retry_delay_s=0.001, retry_rng=random.Random(0),
        )
    assert _time.monotonic() - t0 >= 0.28  # waited the hint, not ~1 ms
    assert h.client.steps == 2


@pytest.mark.asyncio
async def test_client_deadline_stops_retries():
    """Once the end-to-end budget is spent, the retry loop stops with the
    typed non-retryable deadline error instead of burning attempts."""
    import random

    from inferd_tpu.client.base import ServerError

    h = _FailingClient(ServerError("transient", 500))
    with pytest.raises(ServerError) as ei:
        await h.client.generate_ids(
            [1], max_new_tokens=1, session_retries=5, retry_delay_s=0.2,
            deadline_s=0.0, retry_rng=random.Random(0),
        )
    assert ei.value.code == "deadline" and not ei.value.retryable
    assert h.client.steps <= 1  # no retry survived the dead budget


def test_wire_deadline_compat():
    """deadline_ms rides the envelope ONLY when a deadline is active
    (deadline-less traffic stays byte-identical), survives both wire
    generations and the coalesce/split round trip, and an absent key
    means 'no deadline' (what an old peer's envelopes look like)."""
    import numpy as np

    from inferd_tpu.client import base as clientbase
    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.runtime import wire
    from inferd_tpu.utils.retry import remaining_s

    env = SwarmClient([("127.0.0.1", 1)])._forward_env("s", [1, 2], 0)
    assert "deadline_ms" not in env  # no active deadline -> no new key
    tok = clientbase._DEADLINE_MS.set(1e15)
    try:
        env2 = SwarmClient([("127.0.0.1", 1)])._forward_env("s", [1, 2], 0)
    finally:
        clientbase._DEADLINE_MS.reset(tok)
    assert env2["deadline_ms"] == 1e15
    # both wire generations carry it (old peers DECODE legacy envelopes
    # and simply ignore the unknown key)
    for codec in (wire.pack, wire.pack_legacy):
        rt = wire.unpack(codec(env2))
        assert rt["deadline_ms"] == 1e15
    # coalesced multi envelopes: the per-session frames keep their own
    # deadline through split_forward (deadlines are per REQUEST)
    envs = []
    for i, dl in enumerate((1e15, None)):
        e = {
            "task_id": f"t{i}", "session_id": f"s{i}", "stage": 1,
            "payload": {
                "hidden": np.zeros((1, 1, 4), np.float32),
                "start_pos": 7, "real_len": 1,
            },
        }
        if dl is not None:
            e["deadline_ms"] = dl
        envs.append(e)
    split = wire.split_forward(wire.coalesce_forward(envs))
    assert split[0]["deadline_ms"] == 1e15
    assert "deadline_ms" not in split[1]
    # absent/garbage deadline == no deadline (fail open on old peers)
    assert remaining_s(None) is None
    assert remaining_s("not-a-number") is None


def test_ranked_nodes_draining_exclusion():
    from inferd_tpu.control.dstar import node_cost
    from inferd_tpu.control.path_finder import min_load_node, ranked_nodes

    stage_map = {
        "a": {"load": 0, "cap": 4, "host": "h", "port": 1},
        "b": {"load": 1, "cap": 4, "host": "h", "port": 2},
        "c": {"load": 0, "cap": 4, "host": "h", "port": 3, "draining": 1},
    }
    ranked = ranked_nodes(stage_map)
    # draining replica excluded outright; best-first among the rest
    assert [nid for nid, _ in ranked] == ["a", "b"]
    assert min_load_node(stage_map)[0] == "a"
    # availability beats drain: a stage with ONLY draining replicas
    # stays routable
    only_draining = {"c": dict(stage_map["c"])}
    assert min_load_node(only_draining)[0] == "c"
    # the planner's edge cost treats drain as exclusion-grade
    assert node_cost(stage_map["c"]) > node_cost(stage_map["b"]) + 1e5


@pytest.mark.asyncio
async def test_deadline_expired_entry_fast_fails(tiny_parts):  # noqa: F811
    """An envelope whose deadline is already spent fails with the typed
    non-retryable `deadline` 408 BEFORE any compute or relay: the
    downstream stage never sees the request (no dead work down the
    chain), and the decision lands in the journal."""
    import time as _time

    from inferd_tpu.client.base import ServerError

    nodes = [_mk_node(60 + i, i, 2, bootstrap_idx=60) for i in range(2)]
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(60))]) as c:
            with pytest.raises(ServerError) as ei:
                await c._post("/forward", {
                    "stage": 0, "session_id": "dl", "task_id": "t",
                    "payload": {"state": 0, "start_pos": 0, "real_len": 1},
                    "deadline_ms": (_time.time() - 5.0) * 1e3,  # spent
                })
        e = ei.value
        assert e.status == 408 and e.code == "deadline" and not e.retryable
        snap0 = nodes[0].metrics.snapshot()["counters"]
        snap1 = nodes[1].metrics.snapshot()["counters"]
        assert snap0.get("deadline.expired", 0) >= 1
        # the entry fast-failed: nothing was computed or relayed
        assert snap0.get("forward.requests", 0) == 0
        assert snap1.get("forward.requests", 0) == 0
        assert any(
            ev["type"] == "deadline.exceeded"
            for ev in nodes[0].journal.events()
        )
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_deadline_expires_mid_chain_no_downstream_relay():
    """The budget dies DURING stage-0 work (chaos delay longer than the
    remaining deadline): the post-compute check fails the request with
    the typed 408 instead of relaying dead activations to stage 1."""
    import time as _time

    from inferd_tpu.client.base import ServerError

    nodes = [_mk_node(64 + i, i, 2, bootstrap_idx=64) for i in range(2)]
    nodes[0].chaos = Chaos(delay_ms=400)  # slower than the budget below
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(64))]) as c:
            with pytest.raises(ServerError) as ei:
                await c._post("/forward", {
                    "stage": 0, "session_id": "dm", "task_id": "t",
                    "payload": {"state": 0, "start_pos": 0, "real_len": 1},
                    "deadline_ms": (_time.time() + 0.15) * 1e3,
                })
        assert ei.value.status == 408 and ei.value.code == "deadline"
        # stage 0 DID compute (the budget died under it) ...
        assert nodes[0].metrics.snapshot()["counters"].get(
            "forward.requests", 0) >= 1
        # ... but nothing was relayed onward
        assert nodes[1].metrics.snapshot()["counters"].get(
            "forward.requests", 0) == 0
        evs = [
            ev for ev in nodes[0].journal.events()
            if ev["type"] == "deadline.exceeded"
        ]
        assert evs and evs[-1]["attrs"]["where"] == "post-compute"
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_hedge_wins_when_primary_stalls():
    """Hedged relay end to end: the session's affinity replica slow-
    lorises (stall_p=1 — accepts, never answers), the hedge fires at the
    second-best ranked replica after hedge_delay_ms, the hedge's 200
    wins, the stalled primary is cancelled, and affinity repoints to the
    winner. hedge.fired/won counters + journal record it."""
    nodes = [_mk_node(67 + i, min(i, 1), 2, bootstrap_idx=67) for i in range(3)]
    # n1 and n2 are the stage-1 replica pair; n1 stalls forever
    nodes[1].chaos = Chaos(stall_p=1.0, seed=0)
    n0 = nodes[0]
    n0.hedge_mode = "any"  # counter backend is stateless: any replica works
    n0.hedge_delay_ms = 50.0
    await _start_all(nodes)
    try:
        import time as _time

        # pin the session's affinity to the stalled replica — the exact
        # "sick replica holds the session" shape hedging exists for
        n0._session_next[("hsess", 1)] = (nodes[1].info.node_id, _time.monotonic())
        env = {
            "task_id": "t", "session_id": "hsess", "stage": 1,
            "rescued": True,  # single bounce: the receiver serves locally
            "payload": {"state": 1, "start_pos": 5, "real_len": 1},
        }
        resp = await n0._relay(env, 1)
        assert resp.status == 200
        from inferd_tpu.runtime import wire as wirelib

        body = wirelib.unpack(bytes(resp.body))
        assert body["result_for_user"]["state"] == 2  # stage 1 computed
        counters = n0.metrics.snapshot()["counters"]
        assert counters.get("hedge.fired", 0) == 1
        assert counters.get("hedge.won", 0) == 1
        assert counters.get("hedge.cancelled", 0) == 0
        types = [ev["type"] for ev in n0.journal.events()]
        assert "hedge.fired" in types and "hedge.won" in types
        # affinity repointed to the winner for the session's next steps
        assert n0._session_next[("hsess", 1)][0] == nodes[2].info.node_id
        # extra-load ledger: 1 hedge against 1 primary, budget-tracked
        assert n0.hedge_budget.stats()["fired"] == 1
    finally:
        # the stalled handler sleeps ~forever: crash() skips the graceful
        # drain so teardown doesn't wait out aiohttp's shutdown timeout
        await nodes[1].crash()
        await _stop_all([nodes[0], nodes[2]])


@pytest.mark.asyncio
async def test_admission_shed_pool_watermark_and_retry_after():
    """Pool-aware admission (ROADMAP 2d): when the paged-KV block pool is
    under its reserve, NEW sessions shed with a typed 503 "busy" carrying
    a Retry-After hint — while mid-session chunks keep flowing (finishing
    them RELEASES capacity)."""
    from types import SimpleNamespace

    from inferd_tpu.client.base import ServerError

    nodes = [_mk_node(73, 0, 1, bootstrap_idx=73)]
    n0 = nodes[0]
    await _start_all(nodes)
    try:
        # duck-typed pool counters on the live executor: 2 free of 100
        # is under the 5% reserve
        n0.executor.pool = SimpleNamespace(num_blocks=100, blocks_free=2)
        async with SwarmClient([("127.0.0.1", PORTS.http(73))]) as c:
            with pytest.raises(ServerError) as ei:
                await c._post("/forward", {
                    "stage": 0, "session_id": "new", "task_id": "t",
                    "payload": {"state": 0, "start_pos": 0, "real_len": 1},
                })
            e = ei.value
            assert e.status == 503 and e.code == "busy"
            assert e.retry_after is not None and e.retry_after > 0
            assert e.retryable  # a shed is transient, not fatal
            # mid-session traffic is NOT shed (rescued skips the holder
            # bounce; the counter executor serves it)
            r = await c._post("/forward", {
                "stage": 0, "session_id": "old", "task_id": "t2",
                "rescued": True,
                "payload": {"state": 0, "start_pos": 3, "real_len": 1},
            })
            assert r["result_for_user"]["state"] == 1
        counters = n0.metrics.snapshot()["counters"]
        assert counters.get("admission.shed", 0) == 1
        assert any(
            ev["type"] == "admission.shed" and ev["attrs"]["code"] == "busy"
            for ev in n0.journal.events()
        )
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_drain_hands_off_resident_session_token_exact(tiny_parts):  # noqa: F811
    """POST /drain mid-generation on the entry replica: residents hand
    off to the surviving stage-0 replica, the failed-over continuation
    rides the gossip session-location rescue, and the stream completes
    TOKEN-EXACT with no session restart. New sessions shed with the
    typed draining 503; gossip's draining flag excludes the node from
    ranked routing."""
    from inferd_tpu.client.base import ServerError
    from inferd_tpu.control.path_finder import ranked_nodes

    parts, params = tiny_parts
    # n0 + n1: stage-0 replica pair (n0 is the entry and will drain);
    # n2: stage 1
    nodes = [
        _mk_node(84, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=84),
        _mk_node(85, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=84),
        _mk_node(86, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=84),
    ]
    await _start_all(nodes)
    try:
        engine = Engine(
            TINY, params, max_len=64,
            sampling_cfg=SamplingConfig(temperature=0.0),
        )
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=10)

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(84))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            state = {}

            async def on_token(tok):
                if tok is None:
                    return  # restart marker: keep counting fresh tokens
                state.setdefault("toks", []).append(tok)
                if len(state["toks"]) == 3 and "drained" not in state:
                    # between steps (the hook is awaited inside the token
                    # loop): drain the entry while it holds the session
                    state["drained"] = await c._post(
                        "/drain", {"wait_s": 2.0}
                    )

            got = await c.generate_ids(
                prompt, max_new_tokens=10, session_retries=6,
                retry_delay_s=0.3, on_token=on_token,
            )
            assert got == expected  # token-exact across the drain
            drained = state["drained"]
            assert drained["ok"] and drained["draining"]
            assert drained["handed_off"] >= 1  # the resident session moved

            # new sessions shed at the draining entry with the typed 503
            with pytest.raises(ServerError) as ei:
                await c._post("/forward", {
                    "stage": 0, "session_id": "fresh", "task_id": "t",
                    "payload": {
                        "tokens": [[3]], "start_pos": 0, "real_len": 1,
                    },
                })
            assert ei.value.status == 503 and ei.value.code == "draining"
            assert ei.value.retry_after is not None

        # journal recorded the drain lifecycle
        types = [ev["type"] for ev in nodes[0].journal.events()]
        assert "node.draining" in types and "node.drained" in types
        # gossip carries the flag and ranked routing excludes the drainer
        stage0 = nodes[2].dht.get_stage(0)
        assert stage0[nodes[0].info.node_id].get("draining") == 1
        ranked = ranked_nodes(stage0)
        assert [nid for nid, _ in ranked] == [nodes[1].info.node_id]
    finally:
        await _stop_all(nodes)
