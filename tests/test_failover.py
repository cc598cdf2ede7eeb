"""Crash-tolerant sessions (ISSUE 14): async standby KV replication,
bounded-RPO promotion, measured failover — plus the rescue give-up
journal, chaos crash_after, partial drain-handoff behavior, and the
kill-switch parity contract (replication off => gossip/wire//metrics
byte-identical to a build without the plane)."""

import asyncio
import time

import jax
import numpy as np
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import stages as stagelib
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import repl as repllib
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.node import Node, NodeInfo
from inferd_tpu.utils.chaos import Chaos, ChaosDrop

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)


# --------------------------------------------------------------- fixtures


@pytest.fixture(scope="module")
def tiny_parts1(tmp_path_factory):
    """TINY as a single whole-model stage (the standby-replication e2e
    topology: a stage-0 replica PAIR serving the full model)."""
    parts = tmp_path_factory.mktemp("parts1")
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 1)
    split_and_save(params, TINY, manifest, str(parts))
    return str(parts), params


def _solo_executor(parts):
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    path = stagelib.stage_checkpoint_path(parts, 0)
    params, spec, _name = stagelib.load_stage_checkpoint(path)
    return Qwen3StageExecutor(TINY, spec, params, max_len=64)


def _batched_executor(parts, block_size=8):
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    path = stagelib.stage_checkpoint_path(parts, 0)
    params, _spec, _name = stagelib.load_stage_checkpoint(path)
    return BatchedExecutor(
        TINY, params, lanes=2, max_len=64, block_size=block_size,
    )


def _mk(idx, *, parts, bootstrap_idx=0, chaos=None, **node_kw):
    info = NodeInfo(
        name=f"f{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=0, num_stages=1, capacity=4, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx),
        bootstrap=(
            [("127.0.0.1", PORTS.gossip(bootstrap_idx))]
            if idx != bootstrap_idx else []
        ),
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, chaos=chaos, hop_timeout_s=2.0,
        **node_kw,
    )


async def _start_all(nodes):
    for n in nodes:
        await n.start()

    async def converged():
        for n in nodes:
            if not n.dht.get_stage(0):
                return False
        return True

    for _ in range(100):
        if await converged():
            return
        await asyncio.sleep(0.05)
    raise TimeoutError("swarm did not converge")


async def _stop_all(nodes):
    for n in nodes:
        try:
            await n.stop()
        except Exception:
            pass


def _drive(ex, sid, prompt, steps):
    """Greedy-generate on a raw executor via the process() surface;
    returns (tokens, final position)."""
    out = []
    r = ex.process(sid, {
        "tokens": [list(prompt)], "start_pos": 0, "real_len": len(prompt),
    })
    pos = len(prompt)
    tok = int(np.argmax(np.asarray(r["logits"])[0]))
    out.append(tok)
    for _ in range(steps - 1):
        r = ex.process(sid, {
            "tokens": [[tok]], "start_pos": pos, "real_len": 1,
        })
        pos += 1
        tok = int(np.argmax(np.asarray(r["logits"])[0]))
        out.append(tok)
    return out, pos


# ---------------------------------------------------- chaos crash_after


def test_chaos_crash_after_parse_and_compose():
    c = Chaos.parse("crash_after=3,drop=0.5,seed=2")
    assert c.crash_after == 3 and c.drop == 0.5 and c.seed == 2
    # still composes with the probabilistic keys and parses alone
    assert Chaos.parse("crash_after=7").crash_after == 7


@pytest.mark.asyncio
async def test_chaos_crash_after_fires_once_then_keeps_dropping():
    c = Chaos(crash_after=2)
    crashes = []
    c.on_crash = lambda: crashes.append(1)
    await c.before_forward()
    await c.before_forward()  # forward 2: still healthy
    assert crashes == []
    for _ in range(3):
        with pytest.raises(ChaosDrop, match="crash_after"):
            await c.before_forward()
    # the hook fired exactly once; every later forward still fails (the
    # node is "dead" — it must not come back healthy)
    assert crashes == [1]


# ------------------------------------------------- executor delta export


def test_solo_delta_export_accumulate_import_token_exact(tiny_parts1):
    parts, _params = tiny_parts1
    a = _solo_executor(parts)
    b = _solo_executor(parts)
    prompt = [3, 7, 11, 19, 5, 2]
    ref_ex = _solo_executor(parts)
    ref, _ = _drive(ref_ex, "ref", prompt, 8)

    store = repllib.StandbyStore()
    out_a, pos = _drive(a, "s", prompt, 4)
    assert a.session_lengths() == {"s": pos}
    # ship in two deltas: [0, F) then [F, pos)
    d1 = a.export_session_delta("s", 0)
    assert d1[repllib.START_KEY] == 0 and d1["length"] == pos
    ok, have = store.apply("s", 0, {"session_id": "s", "stage": 0, **d1})
    assert ok and have == pos
    # nothing new -> no delta
    assert a.export_session_delta("s", pos) is None
    out_a2, pos2 = [], pos
    tok = out_a[-1]
    for _ in range(2):
        r = a.process("s", {"tokens": [[tok]], "start_pos": pos2,
                            "real_len": 1})
        pos2 += 1
        tok = int(np.argmax(np.asarray(r["logits"])[0]))
        out_a2.append(tok)
    d2 = a.export_session_delta("s", pos)
    assert d2[repllib.START_KEY] == pos and d2["length"] == pos2
    ok, have = store.apply("s", 0, {"session_id": "s", "stage": 0, **d2})
    assert ok and have == pos2

    # promote on B: import the accumulated payload, continue decoding —
    # the continuation must be TOKEN-EXACT vs the uninterrupted run
    assert b.import_session("s", store.payload("s"))
    tail = []
    for _ in range(8 - 4 - 2):
        r = b.process("s", {"tokens": [[tok]], "start_pos": pos2,
                            "real_len": 1})
        pos2 += 1
        tok = int(np.argmax(np.asarray(r["logits"])[0]))
        tail.append(tok)
    assert out_a + out_a2 + tail == ref


def test_batched_paged_delta_block_aligned(tiny_parts1):
    parts, _params = tiny_parts1
    a = _batched_executor(parts, block_size=8)
    b = _batched_executor(parts, block_size=8)
    ref_ex = _batched_executor(parts, block_size=8)
    prompt = [3, 7, 11, 19, 5, 2, 13, 17, 23, 29]  # 10 tokens
    ref, _ = _drive(ref_ex, "ref", prompt, 12)

    store = repllib.StandbyStore()
    out_a, pos = _drive(a, "s", prompt, 3)  # KV length 12
    d1 = a.export_session_delta("s", 0)
    # paged: only IMMUTABLE FULL BLOCKS ship — the partial tail block
    # stays behind (bounded RPO, docs/SERVING.md)
    assert d1["length"] == (pos // 8) * 8 == 8
    assert np.asarray(d1["k"]).shape[2] == 8
    ok, have = store.apply("s", 0, {"session_id": "s", "stage": 0, **d1})
    assert ok and have == 8

    def advance(n, tok):
        nonlocal pos
        got = []
        for _ in range(n):
            r = a.process("s", {"tokens": [[tok]], "start_pos": pos,
                                "real_len": 1})
            pos += 1
            tok = int(np.argmax(np.asarray(r["logits"])[0]))
            got.append(tok)
        return got

    # advance past the next block boundary and ship the delta
    extra = advance(4, out_a[-1])  # KV length 16
    d2 = a.export_session_delta("s", 8)
    assert d2[repllib.START_KEY] == 8 and d2["length"] == 16
    ok, have = store.apply("s", 0, {"session_id": "s", "stage": 0, **d2})
    assert ok and have == 16
    # two more steps that never replicate (the crash window): the
    # standby's frontier stays one partial block behind
    tail = advance(2, extra[-1])  # KV length 18, frontier 16
    assert out_a + extra + tail == ref[:9]

    # promote on B: import the replicated prefix, re-prefill ONLY the
    # tokens past the frontier (known stream positions 16..17 — the
    # bounded re-prefill a resume-aware client sends), then continue
    assert b.import_session("s", store.payload("s"))
    known = list(prompt) + out_a + extra + tail  # token at index = position
    replay = known[16:pos]
    assert len(replay) == pos - 16 == 2  # << the 8-token prompt blocks
    p = 16
    r = None
    for t in replay:
        r = b.process("s", {"tokens": [[t]], "start_pos": p, "real_len": 1})
        p += 1
    tok_b = int(np.argmax(np.asarray(r["logits"])[0]))
    # the recomputed continuation matches the uninterrupted stream
    assert tok_b == ref[9]


def test_standby_store_gap_resync_and_sweep():
    store = repllib.StandbyStore(ttl_s=0.0)
    k = np.zeros((2, 1, 4, 1, 2), np.float32)
    base = {"k": k, "v": k, "length": 4, repllib.START_KEY: 0}
    ok, have = store.apply("s", 0, dict(base))
    assert ok and have == 4
    # a delta past the frontier declines and reports what it HAS
    gap = {"k": k, "v": k, "length": 12, repllib.START_KEY: 8}
    ok, have = store.apply("s", 0, dict(gap))
    assert not ok and have == 4
    # a mid-stream delta for an UNKNOWN session asks for a full re-sync
    ok, have = store.apply("s2", 0, dict(gap))
    assert not ok and have == 0
    # wrong stage declines
    ok, have = store.apply("s", 1, {
        "k": k, "v": k, "length": 8, repllib.START_KEY: 4,
    })
    assert not ok
    # start == 0 REPLACES (primary re-synced from scratch)
    ok, have = store.apply("s", 0, dict(base))
    assert ok and have == 4
    # TTL sweep drops idle shadows
    assert store.sweep() == 1 and len(store) == 0


def test_replicator_sticky_standby_and_frontier_reset():
    cands = [("b", {}), ("c", {"shed": 1})]
    r = repllib.SessionReplicator(lambda: list(cands))
    plan = r.plan({"s": 10})
    assert plan == [("s", "b", 0)]  # shedding candidate loses the pick
    r.record("s", "b", True, 10, 100)
    assert r.plan({"s": 10}) == []  # nothing new
    assert r.plan({"s": 14}) == [("s", "b", 10)]  # sticky standby
    assert r.lag_tokens({"s": 14}) == 4
    # standby death: forget it; the next pick re-ships from 0
    r.note_standby_dead("s")
    cands[:] = [("c", {"shed": 1})]
    assert r.plan({"s": 14}) == [("s", "c", 0)]  # last resort: shedding
    # a declined ship resets the frontier to what the peer reports
    r.record("s", "c", False, 6, 0)
    assert r.plan({"s": 14}) == [("s", "c", 6)]
    # residency loss prunes SILENTLY (the shadow may be the stream's
    # only surviving copy); an explicit end pops the drop-notice target
    r2 = repllib.SessionReplicator(lambda: [("b", {})])
    r2.record("x", "b", True, 4, 10)
    r2.prune([])
    assert r2.state == {} and r2.pop_standby("x") is None
    assert r.pop_standby("s") == "c"
    assert r.state == {}


# ------------------------------------------------------------- node e2e


@pytest.mark.asyncio
async def test_standby_promotion_e2e_token_exact(tiny_parts1):
    """Crash the KV holder mid-generation (chaos crash_after — the
    deterministic kill): the survivor PROMOTES its replicated shadow and
    the stream completes token-exact with NO client restart."""
    parts, params = tiny_parts1
    nodes = [
        _mk(0, parts=parts, standby_repl=True, repl_interval_s=0.05,
            chaos=Chaos(crash_after=5)),
        _mk(1, parts=parts, standby_repl=True, repl_interval_s=0.05),
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64,
                        sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=8)

        restarts = []

        async def on_token(tok):
            if tok is None:
                restarts.append(1)
                return
            # pace the decode so the 50 ms replication tick ships the
            # frontier before the crash at forward 6 (prefill + 4 steps
            # serve, the 6th forward kills node 0)
            await asyncio.sleep(0.06)

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(0)), ("127.0.0.1", PORTS.http(1))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            got = await c.generate_ids(
                prompt, max_new_tokens=8, session_retries=4,
                retry_delay_s=0.2, on_token=on_token,
            )
        assert got == expected
        assert restarts == [], "promotion must continue, not restart"
        counters = nodes[1].metrics.snapshot()["counters"]
        assert counters.get("repl.promotions") == 1
        assert counters.get("repl.resumed_tokens", 0) >= len(prompt)
        types = [e["type"] for e in nodes[1].journal.events()]
        assert "standby.promote" in types
        # the promoted session advertised under `sess` on the survivor
        assert counters.get("repl.stale", 0) == 0
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_ended_session_drops_shadow_promptly(tiny_parts1):
    """A finished session's shadow must not sit in the standby's RAM
    (or keep a stale `standby` advert) for the TTL: the primary's next
    replication tick sends a drop notice."""
    parts, _params = tiny_parts1
    nodes = [
        _mk(0, parts=parts, standby_repl=True, repl_interval_s=0.05),
        _mk(1, parts=parts, standby_repl=True, repl_interval_s=0.05),
    ]
    await _start_all(nodes)
    try:
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(0)), ("127.0.0.1", PORTS.http(1))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:

            async def on_token(tok):
                await asyncio.sleep(0.06)  # let the tick ship a shadow

            await c.generate_ids(
                [3, 7, 11, 19], max_new_tokens=6, on_token=on_token,
            )
        # the generation ended (the client sent /end_session): within a
        # few ticks every shadow it left behind is dropped fleet-wide
        for _ in range(40):
            if all(len(n.standby) == 0 for n in nodes):
                break
            await asyncio.sleep(0.05)
        assert all(len(n.standby) == 0 for n in nodes)
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_stale_standby_degrades_to_restart_token_exact(tiny_parts1):
    """A corrupt shadow must NEVER produce a wrong token: promotion
    fails closed (standby.stale) and the client full-restarts — exactly
    the pre-replication path — still token-exact."""
    parts, params = tiny_parts1
    nodes = [
        _mk(0, parts=parts, standby_repl=True, repl_interval_s=0.05),
        _mk(1, parts=parts, standby_repl=True, repl_interval_s=0.05),
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64,
                        sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=8)
        restarts = []
        state = {"n": 0, "killed": False}

        async def on_token(tok):
            if tok is None:
                restarts.append(1)
                return
            state["n"] += 1
            await asyncio.sleep(0.06)
            if state["n"] == 4 and not state["killed"]:
                state["killed"] = True
                # corrupt EVERY shadow the standby holds (truncated k:
                # the handoff validator rejects it at import), then
                # crash the holder abruptly
                sb = nodes[1].standby
                for sid in sb.ids():
                    sh = sb._shadows[sid]
                    if sh.ks:
                        # truncate the FIRST (prompt-sized) segment: the
                        # reassembled payload then covers fewer slots
                        # than its claimed length and the handoff
                        # validator must reject it at import
                        sh.ks[0] = sh.ks[0][:, :, :1]
                await nodes[0].crash()

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(0)), ("127.0.0.1", PORTS.http(1))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            got = await c.generate_ids(
                prompt, max_new_tokens=8, session_retries=6,
                retry_delay_s=0.2, on_token=on_token,
            )
        assert got == expected
        assert len(restarts) >= 1, "stale standby must degrade to restart"
        types = [e["type"] for e in nodes[1].journal.events()]
        assert "standby.stale" in types
        assert nodes[1].metrics.snapshot()["counters"].get(
            "repl.promotions", 0
        ) == 0
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_kill_switch_parity_flag_off(tiny_parts1):
    """--standby-repl absent: gossip records carry no `standby` key, no
    repl.* series exist at /metrics or /stats, and /replicate_session
    answers 501 — byte-identical surfaces to a build without the plane."""
    import aiohttp

    from inferd_tpu.obs import export as obs_export

    parts, _params = tiny_parts1
    nodes = [_mk(0, parts=parts), _mk(1, parts=parts)]
    await _start_all(nodes)
    try:
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(0))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            await c.generate_ids([3, 7, 11, 19], max_new_tokens=4)
        await asyncio.sleep(0.3)  # a few gossip + tick periods
        for n in nodes:
            rec = n.dht.get_stage(0).get(n.info.node_id, {})
            assert "standby" not in rec
            text = obs_export.prometheus_text(n.metrics)
            assert "repl_" not in text and "standby" not in text
            snap = n.metrics.snapshot()
            assert not any(
                k.startswith("repl.") for k in snap["counters"]
            )
            assert not any(k.startswith("repl.") for k in snap["gauges"])
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{PORTS.http()}/replicate_session",
                data=wire.pack({"session_id": "x", "stage": 0,
                                "k": np.zeros((1, 1, 1, 1, 1)),
                                "v": np.zeros((1, 1, 1, 1, 1)),
                                "length": 1, "start": 0}),
            ) as r:
                assert r.status == 501
                body = wire.unpack(await r.read())
                assert body["code"] == "repl_off"
            async with s.get(f"http://127.0.0.1:{PORTS.http()}/stats") as r:
                assert "repl" not in await r.json()
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_rescue_failed_event_and_bounce_flag(tiny_parts1):
    """The rescue give-up is journaled (session.rescue_failed with
    attempts + error) and --rescue-bounces caps the loop."""
    from inferd_tpu.client.base import ServerError

    parts, _params = tiny_parts1
    nodes = [_mk(0, parts=parts, rescue_bounces=2)]
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(0))]) as c:
            with pytest.raises(ServerError) as ei:
                await c._post("/forward", {
                    "stage": 0, "session_id": "ghost",
                    "payload": {"tokens": np.asarray([[5]], np.int32),
                                "start_pos": 9, "real_len": 1},
                })
            assert ei.value.status == 409
            assert ei.value.code == "session_state"
        evs = [
            e for e in nodes[0].journal.events()
            if e["type"] == "session.rescue_failed"
        ]
        assert len(evs) == 1
        assert evs[0]["attrs"]["attempts"] == 2
        assert "no holder" in evs[0]["attrs"]["error"]
    finally:
        await _stop_all(nodes)


# ------------------------------------------- partial drain-handoff (sat)


async def _seed_sessions(port, sids, prompt=(3, 7, 11, 19)):
    async with SwarmClient([("127.0.0.1", port)]) as c:
        for sid in sids:
            await c._post("/forward", {
                "stage": 0, "session_id": sid,
                "payload": {
                    "tokens": np.asarray([list(prompt)], np.int32),
                    "start_pos": 0, "real_len": len(prompt),
                },
            })


@pytest.mark.asyncio
async def test_partial_handoff_no_loss_no_double_adopt(tiny_parts1):
    """_handoff_sessions with one peer whose import always fails: every
    session is adopted EXACTLY ONCE (by the healthy peer) or stays
    cleanly resident — never lost, never double-adopted."""
    parts, _params = tiny_parts1
    nodes = [_mk(i, parts=parts) for i in range(3)]
    await _start_all(nodes)
    try:
        sids = ["h1", "h2", "h3"]
        await _seed_sessions(PORTS.http(0), sids)
        calls = {"n": 0}
        real_import = nodes[2].executor.import_session

        def broken_import(sid, payload):
            calls["n"] += 1
            raise RuntimeError("mid-handoff import explosion")

        nodes[2].executor.import_session = broken_import
        dropped = await asyncio.wait_for(nodes[0]._drain_handoff(), 15)
        held_1 = [s for s in sids if nodes[1]._holds_session(s)]
        held_2 = [s for s in sids if nodes[2]._holds_session(s)]
        held_0 = [s for s in sids if nodes[0]._holds_session(s)]
        assert held_2 == []  # the broken peer adopted nothing
        for s in sids:
            # exactly once somewhere, or still resident on the source
            assert (s in held_1) != (s in held_0), (held_0, held_1)
        assert dropped == len(held_1)
        nodes[2].executor.import_session = real_import
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_partial_handoff_peer_death_no_hang(tiny_parts1):
    """A peer that accepts the TCP connection and never answers (died
    mid-handoff) must not hang the drain: the per-hop timeout bounds it
    and every session still lands exactly once on the live peer."""
    parts, _params = tiny_parts1
    nodes = [_mk(i, parts=parts) for i in range(2)]

    stalled = []

    async def black_hole(reader, writer):
        stalled.append(1)
        try:
            await asyncio.sleep(30)
        finally:
            writer.close()

    server = await asyncio.start_server(black_hole, "127.0.0.1", PORTS.http(50))
    await _start_all(nodes)
    try:
        sids = ["p1", "p2"]
        await _seed_sessions(PORTS.http(0), sids)
        real_get_stage = nodes[0].dht.get_stage

        def with_fake(stage):
            m = dict(real_get_stage(stage))
            # the stalled corpse sorts FIRST so every ship tries it
            # before the live peer
            m = {"000:fake": {"host": "127.0.0.1", "port": PORTS.http(50),
                             "stage": 0, "load": 0, "cap": 4}, **m}
            return m

        nodes[0].dht.get_stage = with_fake
        t0 = time.monotonic()
        dropped = await asyncio.wait_for(nodes[0]._drain_handoff(), 20)
        wall = time.monotonic() - t0
        nodes[0].dht.get_stage = real_get_stage
        assert stalled, "the dead peer was never even tried"
        # bounded: ~one hop timeout (2 s), never the 30 s stall
        assert wall < 15
        for s in sids:
            on_live = nodes[1]._holds_session(s)
            on_src = nodes[0]._holds_session(s)
            assert on_live != on_src, (s, on_live, on_src)
        assert dropped == sum(
            1 for s in sids if nodes[1]._holds_session(s)
        )
    finally:
        server.close()
        await _stop_all(nodes)


# ------------------------------------------------------------ perf gate


def _failover_leg(**over):
    base = {
        "metric": "tiny_failover_recovery_ms", "value": 700.0,
        "unit": "ms", "recovery_gain": 2.2, "recovery_off_ms": 1540.0,
        "re_prefilled_on": 4, "re_prefilled_off": 96,
        "re_prefill_cap": 32, "promotions": 1, "restarts_on": 0,
        "restarts_off": 1, "token_exact": True,
    }
    base.update(over)
    return [("failover", base)]


def test_gate_failover_invariants():
    from inferd_tpu.perf.gate import check_artifact

    assert not [
        f for f in check_artifact(_failover_leg()) if f.severity == "error"
    ]
    for bad in (
        {"recovery_gain": 0.9},          # promotion lost to restart
        {"promotions": 0},               # plane never exercised
        {"restarts_on": 1},              # fell back to a restart
        {"re_prefilled_on": 96},         # saved nothing
        {"re_prefilled_on": 40},         # past the lag bound (cap 32)
        {"token_exact": False},          # divergent stream
    ):
        errs = [
            f for f in check_artifact(_failover_leg(**bad))
            if f.severity == "error"
        ]
        assert errs, f"expected a hard error for {bad}"


def test_gate_failover_prior_regression():
    from inferd_tpu.perf.gate import check_artifact

    cur = _failover_leg(recovery_gain=1.5)
    prior = _failover_leg(recovery_gain=2.5)
    errs = [
        f for f in check_artifact(cur, prior)
        if f.severity == "error" and f.check == "regression"
    ]
    assert errs and "recovery_gain" in errs[0].message
    # a small drift passes
    ok = check_artifact(_failover_leg(recovery_gain=2.1), prior)
    assert not [
        f for f in ok if f.severity == "error" and f.check == "regression"
    ]
    # a prior missing the gain SKIPS (never falls through to raw ms,
    # which is lower-is-better and would invert)
    noprior = _failover_leg()
    del noprior[0][1]["recovery_gain"]
    out = check_artifact(_failover_leg(value=9000.0), noprior)
    assert not [
        f for f in out if f.severity == "error" and f.check == "regression"
    ]
