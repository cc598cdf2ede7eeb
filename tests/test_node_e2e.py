"""In-process multi-node swarm tests over loopback (the reference's
test_rebalance.py sim idea, SURVEY §4, as a real asserted pytest suite):
counter-model pipeline traversal, distributed-vs-single-process golden
generation, wrong-node relay, admin reassign, and dead-stage adoption."""

import asyncio

import numpy as np
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig, get_config
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime import wire
from inferd_tpu.runtime.node import Node, NodeInfo

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)


def _mk_node(
    idx, stage, num_stages, *, backend="counter", parts="", bootstrap_idx=0,
    rebalance_period_s=600.0, capacity=4, lora="", ports=None,
):
    """Node `idx` of the block `ports` (a module that imports this helper
    hands its own; default: this module's): HTTP on ports.http(idx), gossip
    UDP on ports.gossip(idx)."""
    ports = ports or PORTS
    info = NodeInfo(
        name=f"n{idx}", host="127.0.0.1", port=ports.http(idx),
        stage=stage, num_stages=num_stages, capacity=capacity, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, ports.gossip(idx),
        bootstrap=[("127.0.0.1", ports.gossip(bootstrap_idx))] if idx != bootstrap_idx else [],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
    )
    return Node(
        info, TINY, parts, dht, backend=backend, max_len=64,
        rebalance_period_s=rebalance_period_s, lora=lora or None,
    )


async def _start_all(nodes):
    for n in nodes:
        await n.start()
    # wait until every node sees every stage populated
    async def converged():
        for n in nodes:
            m = n.dht.get_all(n.info.num_stages)
            if any(not m[s] for s in range(n.info.num_stages)):
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


@pytest.mark.asyncio
async def test_counter_pipeline_three_stages():
    nodes = [_mk_node(i, i, 3) for i in range(3)]
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(0))]) as c:
            resp = await c._post(
                "/forward",
                {"stage": 0, "session_id": "s1", "payload": {}},
            )
        r = resp["result_for_user"]["result_for_user"]
        assert r["state"] == 3
        assert r["trace"] == [0, 1, 2]
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_wrong_entry_node_relays():
    """A request sent to a non-stage-0 node must be relayed to stage 0 and
    still complete (reference node.py:139-141 behavior)."""
    nodes = [_mk_node(i, i, 3) for i in range(3)]
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(2))]) as c:  # entry = stage 2
            resp = await c._post("/forward", {"stage": 0, "session_id": "s2", "payload": {}})
        assert resp["result_for_user"]["result_for_user"]["trace"] == [0, 1, 2]
    finally:
        await _stop_all(nodes)


@pytest.fixture(scope="module")
def tiny_parts(tmp_path_factory):
    parts = tmp_path_factory.mktemp("parts")
    params = qwen3.init_params(TINY, __import__("jax").random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 2)
    split_and_save(params, TINY, manifest, str(parts))
    return str(parts), params


@pytest.mark.asyncio
async def test_distributed_generation_matches_engine(tiny_parts):
    """Golden distributed test: 2-stage qwen3 swarm over HTTP == single-
    process engine, token for token (greedy)."""
    parts, params = tiny_parts
    nodes = [
        _mk_node(10 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=10)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(10))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == expected
    finally:
        await _stop_all(nodes)


def _write_tiny_adapter(tmp_path, r=4, alpha=8, seed=11, std=0.3):
    """Synthesize a peft-format adapter dir for TINY (no peft needed).

    std=0.3: the adapter-changes-the-output assert below compares GREEDY
    token streams, and TINY's random-init logits are near-degenerate (a
    single token dominates every step) — a 0.05-std adapter's logit
    perturbation is too small to flip any argmax, so base and merged
    engines emit identical streams and the assert fails spuriously
    (observed on this box). 0.3 flips the stream decisively while still
    exercising the exact same load/slice/merge path."""
    import json as _json

    from safetensors.numpy import save_file

    rng = np.random.RandomState(seed)
    dims = {
        "q_proj": (TINY.hidden_size, TINY.q_dim),
        "k_proj": (TINY.hidden_size, TINY.kv_dim),
        "v_proj": (TINY.hidden_size, TINY.kv_dim),
        "o_proj": (TINY.q_dim, TINY.hidden_size),
        "gate_proj": (TINY.hidden_size, TINY.intermediate_size),
        "up_proj": (TINY.hidden_size, TINY.intermediate_size),
        "down_proj": (TINY.intermediate_size, TINY.hidden_size),
    }
    sd = {}
    for i in range(TINY.num_layers):
        for name, (din, dout) in dims.items():
            mod = "self_attn" if name.endswith(("q_proj", "k_proj", "v_proj", "o_proj")) else "mlp"
            pre = f"base_model.model.model.layers.{i}.{mod}.{name}"
            sd[f"{pre}.lora_A.weight"] = rng.normal(0, std, (r, din)).astype(np.float32)
            sd[f"{pre}.lora_B.weight"] = rng.normal(0, std, (dout, r)).astype(np.float32)
    adir = tmp_path / "adapter"
    adir.mkdir()
    save_file(sd, str(adir / "adapter_model.safetensors"))
    (adir / "adapter_config.json").write_text(
        _json.dumps({"lora_alpha": alpha, "r": r})
    )
    return str(adir)


@pytest.mark.asyncio
async def test_lora_swarm_matches_merged_engine(tiny_parts, tmp_path):
    """run_node --lora e2e: a 2-stage swarm whose nodes merge a peft-format
    adapter into their stage slices must equal a single-process Engine over
    the fully merged params, token for token — pinning the per-stage
    slice_adapter offsets (spec.start_layer..end_layer+1)."""
    from inferd_tpu.ops import lora as loralib

    parts, params = tiny_parts
    adir = _write_tiny_adapter(tmp_path)
    nodes = [
        _mk_node(70 + i, i, 2, backend="qwen3", parts=parts,
                 bootstrap_idx=70, lora=adir)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        merged = loralib.merge_adapter(
            params, loralib.load_adapter(TINY, adir)
        )
        engine = Engine(TINY, merged, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)
        # the adapter must actually change the output vs the base weights
        base_engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        assert base_engine.generate(prompt, max_new_tokens=6) != expected
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(70))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == expected
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_reassign_endpoint(tiny_parts):
    """Admin /reassign migrates a node to a new stage and it serves it
    (the reference's dead B1/B2 path, working)."""
    parts, params = tiny_parts
    nodes = [
        _mk_node(20 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=20)
        for i in range(2)
    ]
    # extra replica on stage 0 that we'll move to stage 1
    extra = _mk_node(22, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=20)
    nodes.append(extra)
    await _start_all(nodes)
    try:
        import aiohttp

        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"http://127.0.0.1:{PORTS.http(22)}/reassign", data=wire.pack({"stage": 1})
            ) as r:
                assert r.status == 200
        assert extra.info.stage == 1
        assert extra.executor.spec.is_last
        # reshard-latency observability (BASELINE config 4's timing half):
        # the reassign -> ready-to-serve interval is recorded, and the
        # eager warmup means it INCLUDES the new stage's decode compile
        hist = extra.metrics.snapshot()["histograms"]
        assert hist["reshard.ms_to_serving"]["count"] == 1
        assert hist["reshard.ms_to_serving"]["p50_ms"] > 0
        # swarm converges on the new membership
        for _ in range(100):
            if len(nodes[0].dht.get_stage(1)) == 2:
                break
            await asyncio.sleep(0.05)
        assert len(nodes[0].dht.get_stage(1)) == 2
        # and the moved node actually serves stage 1 traffic end to end
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(20))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            out = await c.generate_ids([5, 6], max_new_tokens=3)
        assert len(out) == 3
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_dead_stage_adoption():
    """Stage-0 node dies; a request entering via a stage-1 replica triggers
    adoption: one replica migrates to stage 0 and the request completes
    (reference path_finder.py:74-82 retry semantics, functioning)."""
    n0 = _mk_node(30, 0, 2, bootstrap_idx=30)
    n1a = _mk_node(31, 1, 2, bootstrap_idx=30)
    n1b = _mk_node(32, 1, 2, bootstrap_idx=30)
    nodes = [n0, n1a, n1b]
    await _start_all(nodes)
    try:
        await n0.stop()  # silent death; TTL (1.5 s) expires its record
        await asyncio.sleep(2.0)
        assert len(n1a.dht.get_stage(0)) == 0
        async with SwarmClient([("127.0.0.1", PORTS.http(31))], timeout_s=30.0) as c:
            resp = await c._post("/forward", {"stage": 0, "session_id": "s3", "payload": {}})
        r = resp["result_for_user"]["result_for_user"]
        assert r["state"] == 2
        assert r["trace"] == [0, 1]
        # exactly one replica adopted stage 0
        stages = sorted([n1a.info.stage, n1b.info.stage])
        assert stages == [0, 1]
    finally:
        await _stop_all(nodes[1:])


@pytest.mark.asyncio
async def test_reassign_hands_off_sessions(tiny_parts):
    """Live migration keeps sessions alive: when the replica holding a
    session's KV is reassigned to another stage, it ships the KV to the
    remaining replica of its old stage, and the client's in-flight
    generation continues WITHOUT a session restart (the reference's
    migration would orphan every session — SURVEY §7 hard parts)."""
    parts, params = tiny_parts
    n0 = _mk_node(60, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=60)
    n1a = _mk_node(61, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=60)
    n1b = _mk_node(62, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=60)
    nodes = [n0, n1a, n1b]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(60))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            sid = "mig-session"
            logits = await c._step(sid, prompt, 0)
            toks = [int(np.argmax(logits))]
            pos = len(prompt)
            for _ in range(2):
                logits = await c._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            holder = n1a if len(n1a.executor.sessions) else n1b
            other = n1b if holder is n1a else n1a
            assert len(holder.executor.sessions) == 1

            import aiohttp

            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{holder.info.port}/reassign",
                    data=wire.pack({"stage": 0}),
                ) as r:
                    assert r.status == 200
            # the handoff runs inside change_stage: the session must now
            # live on the remaining stage-1 replica
            assert sid in other.executor.sessions
            assert other.metrics.snapshot()["counters"].get("sessions.imported", 0) >= 1
            # wait until routing sees the holder gone from stage 1
            for _ in range(100):
                if len(n0.dht.get_stage(1)) == 1:
                    break
                await asyncio.sleep(0.05)
            # continue decoding — no session restart (a restart would need a
            # fresh prefill; _step would 409 on out-of-order otherwise)
            for _ in range(3):
                logits = await c._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            await c._end_session(sid)
        assert toks == expected
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_reassign_without_replica_degrades_to_restart(tiny_parts):
    """Migration with NO remaining replica of the old stage: the handoff
    has nowhere to ship, the moved node re-adopts... no — the stage goes
    empty until adoption; a generation in flight restarts under a fresh
    session (the pre-handoff behavior) and still completes via the
    adoption path."""
    parts, params = tiny_parts
    n0a = _mk_node(70, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=70)
    n0b = _mk_node(71, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=70)
    n1 = _mk_node(72, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=70)
    nodes = [n0a, n0b, n1]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=4)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(70)), ("127.0.0.1", PORTS.http(71))],
            sampling=SamplingConfig(temperature=0.0), timeout_s=60.0,
        ) as c:
            # start a session, then migrate stage 1's ONLY node to stage 0:
            # its sessions have no adopter; subsequent chunks 5xx/409 and the
            # client restarts, completing once a replica adopts stage 1
            logits = await c._step("deg-session", prompt, 0)
            assert logits.shape[-1] == TINY.vocab_size
            import aiohttp

            async with aiohttp.ClientSession() as s:
                async with s.post(
                    f"http://127.0.0.1:{n1.info.port}/reassign",
                    data=wire.pack({"stage": 0}),
                ) as r:
                    assert r.status == 200
            got = await c.generate_ids(
                prompt, max_new_tokens=4, session_retries=4, retry_delay_s=0.5
            )
        assert got == expected
    finally:
        await _stop_all(nodes)


def test_session_export_import_fp8_kv(tiny_parts):
    """fp8-KV sessions survive the handoff wire trip: the codec can't carry
    float8, so export ships a same-shape uint8 byte view + dtype name and
    import views it back. Continuation on the importer matches the
    exporter's own continuation."""
    import dataclasses

    from inferd_tpu.parallel.stages import StageSpec, extract_stage_params
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    _, params = tiny_parts
    cfg = dataclasses.replace(TINY, kv_dtype="float8_e4m3fn")
    spec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    sp = extract_stage_params(params, cfg, spec)
    ex1 = Qwen3StageExecutor(cfg, spec, sp, max_len=64)
    ex2 = Qwen3StageExecutor(cfg, spec, sp, max_len=64)

    prompt = [3, 7, 11, 19]
    out1 = ex1.process("s", {"tokens": np.asarray([prompt]), "start_pos": 0})
    exported = ex1.export_sessions()
    assert len(exported) == 1 and exported[0][1]["kv_dtype"] == "float8_e4m3fn"
    # emulate the transport: the payload must survive the wire codec
    payload = wire.unpack(wire.pack(exported[0][1]))
    assert ex2.import_session("s", payload)

    tok = int(np.argmax(out1["logits"][0]))
    step = {"tokens": np.asarray([[tok]]), "start_pos": len(prompt)}
    a = ex1.process("s", dict(step))
    b = ex2.process("s", dict(step))
    np.testing.assert_allclose(a["logits"], b["logits"], rtol=2e-5, atol=2e-5)


@pytest.mark.asyncio
async def test_session_affinity_sticky_across_load_changes():
    """Once a session lands on a replica, later chunks follow it even when
    the other replica becomes less loaded (KV cache lives there)."""
    n = _mk_node(40, 0, 2)
    n.dht._started = False  # offline: seed records directly
    rec_a = {"stage": 1, "load": 0, "cap": 1, "host": "127.0.0.1", "port": 1}
    rec_b = {"stage": 1, "load": 5, "cap": 1, "host": "127.0.0.1", "port": 2}

    class Seed:
        def __init__(self, recs):
            self.recs = recs

        def get_stage(self, stage):
            return self.recs

        def get_all(self, num):
            return {1: self.recs}

    n.dht.get_stage = Seed({"A": rec_a, "B": rec_b}).get_stage  # type: ignore
    n.path_finder.dht = n.dht

    nid1, _ = await n._pick_next("sess", 1)
    assert nid1 == "A"  # min load
    # A becomes heavily loaded; the session must still route to A
    rec_a["load"] = 100
    nid2, _ = await n._pick_next("sess", 1)
    assert nid2 == "A"
    # but a NEW session picks the now-lighter B
    nid3, _ = await n._pick_next("sess2", 1)
    assert nid3 == "B"
    # if A disappears, the affinity entry is dropped and re-picked
    n.dht.get_stage = Seed({"B": rec_b}).get_stage  # type: ignore
    nid4, _ = await n._pick_next("sess", 1)
    assert nid4 == "B"


def test_chunked_prefill_with_padded_growth_matches_full():
    """Chunked prefill whose padded writes cross the cache bucket boundary
    must equal a one-shot forward (regression: overflow check must use the
    padded length, not the real length)."""
    import jax

    from inferd_tpu.parallel.stages import StageSpec, extract_stage_params
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    cfg = TINY
    params = qwen3.init_params(cfg, __import__("jax").random.PRNGKey(1))
    spec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    ex = Qwen3StageExecutor(
        cfg, spec, extract_stage_params(params, cfg, spec),
        max_len=64, initial_kv_len=16,
    )
    toks = np.asarray(
        __import__("jax").random.randint(
            __import__("jax").random.PRNGKey(2), (1, 21), 0, cfg.vocab_size
        )
    )
    # chunk 1: 14 real -> padded 16 fills the 16-slot bucket exactly;
    # chunk 2: 3 real at start 14 -> padded write of 4 would clamp without
    # the padded-length growth; chunk 3: 4 more.
    out1 = ex.process("s", {"tokens": toks[:, :14], "start_pos": 0})
    out2 = ex.process("s", {"tokens": toks[:, 14:17], "start_pos": 14})
    out3 = ex.process("s", {"tokens": toks[:, 17:21], "start_pos": 17})

    full_logits, _, _ = qwen3.forward(params, cfg, __import__("jax").numpy.asarray(toks))
    np.testing.assert_allclose(
        out3["logits"][0], np.asarray(full_logits[0, 20]), rtol=1e-4, atol=1e-4
    )


def test_balancer_decision_logic():
    """Pure decision test over a fake snapshot (no sockets)."""
    from inferd_tpu.control.balance import Balancer, stage_loads

    class FakeDHT:
        def __init__(self, snap):
            self.snap = snap

        def get_all(self, n):
            return self.snap

    snap = {
        0: {"a": {"load": 8, "cap": 1}},
        1: {"b": {"load": 0, "cap": 1}, "c": {"load": 0, "cap": 1}},
    }
    assert stage_loads(snap) == {0: 8.0, 1: 0.0}

    moved = []

    async def change(stage):
        moved.append(stage)

    b = Balancer(FakeDHT(snap), 2, get_own_stage=lambda: 1, change_stage=change)
    assert asyncio.run(b.rebalance_once()) is True
    assert moved == [0]

    # own stage is the only replica -> must not abandon it
    snap2 = {0: {"a": {"load": 8, "cap": 1}}, 1: {"b": {"load": 0, "cap": 1}}}
    b2 = Balancer(FakeDHT(snap2), 2, get_own_stage=lambda: 1, change_stage=change)
    assert asyncio.run(b2.rebalance_once()) is False

    # balanced -> no move
    snap3 = {
        0: {"a": {"load": 1, "cap": 1}},
        1: {"b": {"load": 1, "cap": 1}, "c": {"load": 1, "cap": 1}},
    }
    b3 = Balancer(FakeDHT(snap3), 2, get_own_stage=lambda: 1, change_stage=change)
    assert asyncio.run(b3.rebalance_once()) is False


@pytest.mark.asyncio
async def test_chunked_prefill_matches_single_shot(tiny_parts):
    """Client-side chunked prefill (prefill_chunk smaller than the prompt)
    must produce exactly the tokens of one-shot prefill — the stage
    executors consume sequential start_pos chunks into the same session
    cache."""
    parts, params = tiny_parts
    nodes = [
        _mk_node(50 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=50)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        prompt = [3, 7, 11, 19, 23, 29, 31, 37, 41, 2]
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(50))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            whole = await c.generate_ids(prompt, max_new_tokens=6)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(50))], sampling=SamplingConfig(temperature=0.0),
            prefill_chunk=3,
        ) as c:
            chunked = await c.generate_ids(prompt, max_new_tokens=6)
        assert chunked == whole
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_fp8_kv_swarm_matches_fp8_engine(tiny_parts):
    """Nodes serving with kv_dtype=float8_e4m3fn produce exactly the tokens
    of a single-process engine using the same compressed-cache config."""
    import dataclasses as _dc

    parts, params = tiny_parts
    cfg8 = _dc.replace(TINY, kv_dtype="float8_e4m3fn")
    nodes = []
    for i in range(2):
        info = NodeInfo(
            name=f"f{i}", host="127.0.0.1", port=PORTS.http(60 + i),
            stage=i, num_stages=2, capacity=4, model_name="tiny",
        )
        dht = SwarmDHT(
            info.node_id, PORTS.gossip(60 + i),
            bootstrap=[] if i == 0 else [("127.0.0.1", PORTS.gossip(60))],
            host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
        )
        nodes.append(Node(
            info, cfg8, parts, dht, backend="qwen3", max_len=64,
            rebalance_period_s=600.0,
        ))
    await _start_all(nodes)
    try:
        engine = Engine(cfg8, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        want = engine.generate(prompt, max_new_tokens=6)
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(60))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == want
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_entry_failover_rescued_via_gossip_sessions(tiny_parts):
    """Swarm-shared session location: a mid-session chunk posted to a
    DIFFERENT same-stage entry (the client failed over; the new entry has
    no local affinity and no KV) is relayed to the replica ADVERTISING the
    session in its gossip record — the generation continues without a
    session restart (round-2 weak #7)."""
    parts, params = tiny_parts
    n0a = _mk_node(80, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=80)
    n0b = _mk_node(81, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=80)
    n1 = _mk_node(82, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=80)
    nodes = [n0a, n0b, n1]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)
        sid = "failover-session"
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(80))], sampling=SamplingConfig(temperature=0.0)
        ) as c_a:
            logits = await c_a._step(sid, prompt, 0)
            toks = [int(np.argmax(logits))]
            pos = len(prompt)
            for _ in range(2):
                logits = await c_a._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
        assert sid in n0a.executor.sessions  # stage-0 KV lives on n0a
        # wait for n0a's session advert to reach n0b's gossip view
        from inferd_tpu.runtime.node import sess_hash

        for _ in range(100):
            v = n0b.dht.get_stage(0).get(n0a.info.node_id, {})
            if sess_hash(sid) in (v.get("sess") or ()):
                break
            await asyncio.sleep(0.05)
        else:
            raise TimeoutError("session advert never gossiped")
        # client fails over: remaining chunks enter via n0b
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(81))], sampling=SamplingConfig(temperature=0.0)
        ) as c_b:
            for _ in range(3):
                logits = await c_b._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            await c_b._end_session(sid)
        assert toks == expected
        m = n0b.metrics.snapshot()["counters"]
        assert m.get("sessions.rescue_relay", 0) >= 1
    finally:
        await _stop_all(nodes)


@pytest.fixture(scope="module")
def tiny_parts3(tmp_path_factory):
    parts = tmp_path_factory.mktemp("parts3")
    params = qwen3.init_params(TINY, __import__("jax").random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 3)
    split_and_save(params, TINY, manifest, str(parts))
    return str(parts), params


@pytest.mark.asyncio
async def test_trace_merged_timeline_three_stage_swarm(tiny_parts3, tmp_path):
    """Distributed-tracing e2e (docs/OBSERVABILITY.md): a generation
    through a 3-stage swarm ENTERED AT THE WRONG NODE (the stage-1
    replica, forcing a relay-mismatch hop) yields ONE merged trace whose
    spans nest correctly across client + all three nodes, carry per-stage
    queue/compute/relay breakdowns, and account for >= 90% of the
    measured client wall time."""
    import time as _time

    from inferd_tpu.obs import merge as obs_merge

    parts, params = tiny_parts3
    nodes = [
        _mk_node(90 + i, i, 3, backend="qwen3", parts=parts, bootstrap_idx=90)
        for i in range(3)
    ]
    await _start_all(nodes)
    spans_dir = tmp_path / "spans"
    try:
        prompt = [3, 7, 11, 19]
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(91))],  # stage-1 entry: every chunk
            # arrives at the wrong node and relays to stage 0 first
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            t0 = _time.perf_counter()
            out = await c.generate_ids(prompt, max_new_tokens=4)
            wall_ms = (_time.perf_counter() - t0) * 1e3
            assert len(out) == 4
            c.tracer.dump_jsonl(str(spans_dir / "client.spans.jsonl"))
        # dump BEFORE stopping: graceful-stop handoffs would add their own
        # traces to the ring
        for n in nodes:
            n.tracer.dump_jsonl(
                str(spans_dir / (n.info.node_id.replace(":", "_") + ".spans.jsonl"))
            )
    finally:
        await _stop_all(nodes)

    result = obs_merge.merge_paths([str(spans_dir)])
    assert result["skipped_lines"] == 0
    assert len(result["traces"]) == 1  # one generation == one trace
    t = result["traces"][0]
    assert t["root"]["name"] == "generate"
    assert t["root"]["service"] == "client"
    # every child nests inside its parent after skew correction
    assert t["nest_violations"] == []
    # client + all three stage nodes participated
    assert len(t["services"]) == 4
    # per-stage breakdown: compute on every stage, queue spans present
    assert set(t["stages"]) == {"0", "1", "2"}
    for row in t["stages"].values():
        assert row.get("compute_ms", 0) > 0
        assert row.get("queue_ms", 0) >= 0
    # the wrong-entry node recorded the mismatch relay hop(s)
    mismatch = [
        s for s in result["spans"]
        if s["service"] == nodes[1].info.node_id
        and s.get("phase") == "relay"
        and (s.get("attrs") or {}).get("mismatch")
    ]
    assert mismatch, "stage-1 entry must relay-mismatch to stage 0"
    # the merged timeline accounts for >= 90% of the measured wall time:
    # the root span covers the timed call and its direct children (step +
    # sample spans) cover the root
    assert t["wall_ms"] >= 0.9 * wall_ms
    assert t["coverage"] >= 0.9
    # token accounting: 4 sampled tokens, TTFT inside the wall
    assert t["tokens"] == 4
    assert t["ttft_ms"] is not None and 0 < t["ttft_ms"] <= t["wall_ms"]
    assert t["per_token_ms"] is not None and t["per_token_ms"] > 0


@pytest.mark.asyncio
async def test_trace_server_side_generate_joins_client_trace(
    tiny_parts, tmp_path
):
    """/generate tracing rides the X-Inferd-Trace header: a standalone
    client's server-side generation merges into ONE trace rooted at the
    CLIENT, with the node's self-driven token loop nested under the
    node's /generate umbrella — and the umbrella (phase `server`, not
    `sample`) must not inflate the token count."""
    from inferd_tpu.obs import merge as obs_merge

    parts, params = tiny_parts
    nodes = [
        _mk_node(98 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=98)
        for i in range(2)
    ]
    await _start_all(nodes)
    spans_dir = tmp_path / "spans"
    try:
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(98))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            ids = await c.generate_server_side([3, 7, 11, 19], max_new_tokens=3)
            assert len(ids) == 3
            c.tracer.dump_jsonl(str(spans_dir / "client.spans.jsonl"))
        for n in nodes:
            n.tracer.dump_jsonl(
                str(spans_dir / (n.info.node_id.replace(":", "_") + ".spans.jsonl"))
            )
    finally:
        await _stop_all(nodes)
    result = obs_merge.merge_paths([str(spans_dir)])
    assert len(result["traces"]) == 1
    t = result["traces"][0]
    assert t["root"]["service"] == "client"
    assert t["tokens"] == 3  # umbrella not counted as a sampled token
    assert t["nest_violations"] == []
    # the node-side /generate umbrella exists and is server-phase
    assert any(
        s["name"] == "generate" and s["phase"] == "server"
        for s in result["spans"]
    )


@pytest.mark.asyncio
async def test_metrics_endpoint_prometheus_and_spans():
    """/metrics serves parseable Prometheus text exposition including the
    new gauges, and /spans serves the live ring as ndjson."""
    import aiohttp

    from inferd_tpu.obs import export as obs_export

    nodes = [_mk_node(95, 0, 1)]
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(95))]) as c:
            await c._post(
                "/forward", {"stage": 0, "session_id": "m1", "payload": {}}
            )
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{PORTS.http(95)}/metrics") as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                text = await r.text()
            async with s.get(f"http://127.0.0.1:{PORTS.http(95)}/spans") as r:
                assert r.status == 200
                ndjson = await r.text()
        assert obs_export.validate_exposition(text) == []
        # counters, gauges (inflight/sessions/queue depth/span ring), and
        # histogram series all present
        assert "inferd_forward_requests_total" in text
        assert "inferd_inflight" in text
        assert "inferd_sessions" in text
        assert "inferd_queue_depth" in text
        assert "inferd_trace_overhead_ms" in text
        assert "inferd_stage_compute_ms_bucket" in text
        import json as _json

        spans = [
            _json.loads(ln) for ln in ndjson.splitlines() if ln.strip()
        ]
        assert any(sp["name"] == "forward" for sp in spans)
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_tracing_disabled_leaves_envelope_and_behavior_intact(
    tiny_parts, monkeypatch
):
    """INFERD_TRACE=0: no spans recorded anywhere, no `trace` key on the
    wire, generation identical."""
    monkeypatch.setenv("INFERD_TRACE", "0")
    parts, params = tiny_parts
    nodes = [
        _mk_node(96 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=96)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64,
                        sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(96))], sampling=SamplingConfig(temperature=0.0)
        ) as c:
            got = await c.generate_ids(prompt, max_new_tokens=4)
            assert got == engine.generate(prompt, max_new_tokens=4)
            assert c.tracer.spans() == []
        for n in nodes:
            assert n.tracer.spans() == []
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_graceful_entry_death_hands_off_and_failover_continues(tiny_parts):
    """The entry node STOPS mid-generation: its graceful shutdown hands the
    session KV to the surviving same-stage replica, the client fails over
    to it, and the generation continues WITHOUT a session restart (the
    round-2 verdict's swarm-shared-affinity e2e)."""
    parts, params = tiny_parts
    n0a = _mk_node(85, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=85)
    n0b = _mk_node(86, 0, 2, backend="qwen3", parts=parts, bootstrap_idx=85)
    n1 = _mk_node(87, 1, 2, backend="qwen3", parts=parts, bootstrap_idx=85)
    nodes = [n0a, n0b, n1]
    await _start_all(nodes)
    stopped = []
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)
        sid = "dying-entry-session"
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(85)), ("127.0.0.1", PORTS.http(86))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            logits = await c._step(sid, prompt, 0)
            toks = [int(np.argmax(logits))]
            pos = len(prompt)
            for _ in range(2):
                logits = await c._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            assert sid in n0a.executor.sessions
            # the entry dies gracefully: handoff ships its stage-0 KV to n0b
            await n0a.stop()
            stopped.append(n0a)
            assert sid in n0b.executor.sessions
            assert n0b.metrics.snapshot()["counters"].get("sessions.imported", 0) >= 1
            # the client's entry failover lands on n0b, which now HOLDS the
            # session — generation continues, no restart possible (the raw
            # protocol would 409 on any out-of-order position)
            for _ in range(3):
                logits = await c._step(sid, [toks[-1]], pos)
                pos += 1
                toks.append(int(np.argmax(logits)))
            await c._end_session(sid)
        assert toks == expected
    finally:
        await _stop_all([n for n in nodes if n not in stopped])


@pytest.mark.asyncio
async def test_node_says_what_device_it_computes_on(tiny_parts):
    """`node.start` and /stats carry platform, device_kind and device count
    as JAX reports them — a caller (chip_smoke.py) checks the device the
    node resolved instead of trusting the flag it passed — and a node that
    has started has its warm-up on the record. The model-free counter
    backend describes no device: it never initializes a JAX backend."""
    import aiohttp
    import jax

    parts, _params = tiny_parts
    nodes = [
        _mk_node(40 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=40)
        for i in range(2)
    ]
    counter = _mk_node(45, 0, 1, bootstrap_idx=45)
    await _start_all(nodes)
    await counter.start()
    try:
        want = {
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        }
        async with aiohttp.ClientSession() as http:
            async with http.get(f"http://127.0.0.1:{PORTS.http(40)}/stats") as r:
                stats = await r.json()
            async with http.get(f"http://127.0.0.1:{PORTS.http(45)}/stats") as r:
                cstats = await r.json()
        memory = stats["device"].pop("memory")
        assert stats["device"] == want and isinstance(memory, list)
        assert stats["wire_codec"] in ("native", "python")
        events = {e["type"]: e for e in nodes[0].journal.events()}
        start = events["node.start"]["attrs"]
        assert {k: start[k] for k in want} == want
        assert "executor.warmup_ok" in events
        assert "executor.warmup_failed" not in events
        assert cstats["device"]["platform"] == "none"
        assert "executor.warmup_ok" not in {
            e["type"] for e in counter.journal.events()
        }
    finally:
        await _stop_all(nodes + [counter])
