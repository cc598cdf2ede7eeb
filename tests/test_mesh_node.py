# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Mesh-backed node serving path (north-star BASELINE config 2): a node
whose executor pipelines the WHOLE model over an in-mesh pp axis, behind
the stock /forward surface — SwarmClient generation must match the
single-process engine token for token, sessions must map to cache slots
with eviction, and the protocol guards must hold."""

import asyncio
import threading
import time

import jax
import numpy as np
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.mesh import MeshPlan
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime.node import Node, NodeInfo


from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def mesh_parts(tmp_path_factory):
    """1-stage checkpoint: mesh mode hosts the whole model."""
    parts = tmp_path_factory.mktemp("mesh_parts")
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    split_and_save(params, TINY, Manifest.even_split("tiny", 1), str(parts))
    return str(parts), params


def _mk_mesh_node(idx, parts, pp=2, slots=3, max_len=64, tp=1):
    info = NodeInfo(
        name=f"m{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=0, num_stages=1, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx), bootstrap=[],
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=max_len,
        rebalance_period_s=600.0, mesh_plan=MeshPlan(pp=pp, tp=tp),
        mesh_slots=slots,
    )


@pytest.mark.asyncio
async def test_mesh_node_generation_matches_engine(mesh_parts, devices8):
    """SwarmClient -> mesh-backed node (pp=2 over the virtual CPU mesh)
    == single-process Engine, token for token (greedy)."""
    parts, params = mesh_parts
    node = _mk_mesh_node(0, parts)
    await node.start()
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY)
        prompt = [3, 7, 11, 19, 23]
        expected = engine.generate(prompt, max_new_tokens=6)
        async with SwarmClient([("127.0.0.1", PORTS.http(0))], sampling=GREEDY) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == expected
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_tp_mesh_node_generation_matches_engine(mesh_parts, devices8):
    """run_node --mesh pp=2,tp=2 serving: the cached decoder blocks run
    tensor-parallel (Megatron psums) inside the pipelined pass — same
    tokens as the single-process engine."""
    parts, params = mesh_parts
    node = _mk_mesh_node(5, parts, pp=2, tp=2)
    await node.start()
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY)
        prompt = [3, 7, 11, 19, 23]
        expected = engine.generate(prompt, max_new_tokens=6)
        async with SwarmClient([("127.0.0.1", PORTS.http(5))], sampling=GREEDY) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == expected
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_mesh_node_fork_e2e(mesh_parts, devices8):
    """Pinned client against a mesh-backed node: the fork lands in a cache
    slot (PipelinedEngine.fork_slot, shard-local per pp rank) and
    generations match the engine."""
    parts, params = mesh_parts
    node = _mk_mesh_node(7, parts)
    await node.start()
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY)
        prefix = [3, 7, 11, 19, 5, 2]
        prompt = prefix + [4, 9]
        expected = engine.generate(prompt, 5)
        from inferd_tpu.client.swarm_client import SwarmClient

        async with SwarmClient([("127.0.0.1", PORTS.http(7))], sampling=GREEDY) as c:
            await c.pin_prefix(prefix)
            got = [await c.generate_ids(prompt, 5) for _ in range(2)]
        assert got == [expected, expected]
        assert node.metrics.snapshot()["counters"].get("fork.ok", 0) >= 2
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_mesh_node_concurrent_sessions(mesh_parts, devices8):
    """Multiple interleaved sessions occupy distinct cache slots and each
    matches its own single-process generation."""
    parts, params = mesh_parts
    node = _mk_mesh_node(1, parts)
    await node.start()
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY)
        prompts = [[3, 7, 11], [5, 2, 9, 13], [1, 4]]
        expected = [engine.generate(p, max_new_tokens=5) for p in prompts]

        async def gen(p):
            async with SwarmClient([("127.0.0.1", PORTS.http(1))], sampling=GREEDY) as c:
                return await c.generate_ids(p, max_new_tokens=5)

        got = await asyncio.gather(*(gen(p) for p in prompts))
        assert list(got) == expected
    finally:
        await node.stop()


@pytest.mark.asyncio
async def test_mesh_node_slot_eviction_and_refill(mesh_parts, devices8):
    """More sessions than slots: LRU session is evicted; its slot serves the
    newcomer; the evicted session can no longer resume mid-stream."""
    parts, params = mesh_parts
    node = _mk_mesh_node(2, parts, slots=2)
    await node.start()
    try:
        ex = node.executor
        # three sessions through 2 slots
        for sid in ("a", "b", "c"):
            ex.process(sid, {"tokens": [[3, 7, 11, 19]], "start_pos": 0, "real_len": 4})
        assert len(ex.sessions) == 2 and "a" not in ex.sessions
        # evicted session resuming mid-stream is refused (its cache is gone)
        with pytest.raises(ValueError, match="unknown session"):
            ex.process("a", {"tokens": [[1]], "start_pos": 4, "real_len": 1})
        # live session continues fine
        r1 = ex.process("b", {"tokens": [[1]], "start_pos": 4, "real_len": 1})
        # a REPLAY of the last chunk (client re-sent after a lost response)
        # rolls the slot back and recomputes identically
        r2 = ex.process("b", {"tokens": [[1]], "start_pos": 4, "real_len": 1})
        np.testing.assert_allclose(
            np.asarray(r1["logits"]), np.asarray(r2["logits"]),
            rtol=1e-6, atol=1e-6,
        )
        # a FUTURE chunk is still refused
        with pytest.raises(ValueError, match="out-of-order"):
            ex.process("b", {"tokens": [[1]], "start_pos": 9, "real_len": 1})
        # end_session frees the slot
        ex.end_session("b")
        assert len(ex.sessions) == 1
        # overflow guard
        with pytest.raises(BufferError, match="KV overflow"):
            ex.process("c", {"tokens": [[0] * 61], "start_pos": 4, "real_len": 61})
    finally:
        await node.stop()


def test_mesh_requires_single_stage(mesh_parts, devices8):
    parts, _ = mesh_parts
    info = NodeInfo(
        name="bad", host="127.0.0.1", port=PORTS.http(50), stage=0, num_stages=2
    )
    dht = SwarmDHT(info.node_id, PORTS.gossip(50), bootstrap=[], host="127.0.0.1")
    with pytest.raises(ValueError, match="single-stage"):
        Node(info, TINY, parts, dht, mesh_plan=MeshPlan(pp=2))


def test_parse_mesh_cli():
    from inferd_tpu.tools.run_node import parse_mesh

    assert parse_mesh("") is None
    assert parse_mesh("pp=4").pp == 4
    plan = parse_mesh("pp=2,tp=1")
    assert (plan.pp, plan.tp) == (2, 1)
    plan = parse_mesh("pp=2,tp=2")  # pp x tp serving (round-2 tail)
    assert (plan.pp, plan.tp) == (2, 2)
    plan = parse_mesh("tp=2")  # tp-only serving
    assert (plan.pp, plan.tp) == (1, 2)
    with pytest.raises(ValueError, match="bad mesh spec"):
        parse_mesh("zz=4")
    with pytest.raises(ValueError, match=">=2 devices"):
        parse_mesh("pp=1")


def test_mesh_rejects_dp_axis(devices8):
    """The serving mesh is pp x tp x ep x sp (sp legalized in round 5 for
    sequence-parallel prefill; decode replicates over it): dp is the one
    axis left that would shard params with no serving collective."""
    from inferd_tpu.parallel.infer import PipelinedEngine

    mesh = meshlib.make_mesh(MeshPlan(pp=2, dp=2), jax.devices()[:4])
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="pp\\(x tp x ep x sp\\) mesh"):
        PipelinedEngine(TINY, params, mesh, num_microbatches=1)

    from inferd_tpu.tools.run_node import parse_mesh

    with pytest.raises(ValueError, match="pp, tp, ep, and sp axes"):
        parse_mesh("pp=2,dp=2")
    assert parse_mesh("pp=2,sp=2").sp == 2  # round 5: sp serves prefill


def test_boundary_chunk_fills_cache_exactly(mesh_parts, devices8):
    """A chunk whose PADDED bucket would spill past max_len must not clamp
    the cache write (code-review r2: 4 + 60 tokens into max_len=64). The
    two-chunk session's final logits must match a one-shot prefill."""
    import numpy as np

    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    parts, params = mesh_parts
    ex = MeshExecutor(TINY, params, MeshPlan(pp=2), num_slots=2, max_len=64)
    rng = np.random.RandomState(11)
    seq = rng.randint(0, TINY.vocab_size, size=64).astype(np.int32)

    out_a = ex.process("s", {"tokens": seq[None, :4], "start_pos": 0, "real_len": 4})
    out_b = ex.process("s", {"tokens": seq[None, 4:], "start_pos": 4, "real_len": 60})

    ex2 = MeshExecutor(TINY, params, MeshPlan(pp=2), num_slots=2, max_len=64)
    ref = ex2.process("r", {"tokens": seq[None, :], "start_pos": 0, "real_len": 64})
    np.testing.assert_allclose(out_b["logits"], ref["logits"], rtol=2e-5, atol=2e-5)


def hold_flusher(ex):
    """Keep the decode flusher from the mesh, as a prefill holding `_lock`
    would, until the returned function is called. The executor admits a
    call under the lock its passes run under, so a held `_lock` would keep
    the entries out of the window as well: the flusher is stopped on its
    way to the lock instead, and whoever arrives meanwhile is pending."""
    gate = threading.Event()
    run = ex._batcher._run_batch

    def gated(entries):
        gate.wait(timeout=60)
        run(entries)

    def release():
        ex._batcher._run_batch = run
        gate.set()

    ex._batcher._run_batch = gated
    return release


def _prefill(ex, sid, ids):
    r = ex.process(sid, {"tokens": [ids], "start_pos": 0, "real_len": len(ids)})
    return np.asarray(r["logits"])[0]


def _decode(ex, sid, tok, pos):
    r = ex.process(sid, {"tokens": [[tok]], "start_pos": pos, "real_len": 1})
    return np.asarray(r["logits"])[0]


def _together(ex, fns):
    """Run `fns` on a thread each; their first decode steps are all pending
    before the flusher gets the mesh."""
    threads = [threading.Thread(target=fn) for fn in fns]
    release = hold_flusher(ex)
    for t in threads:
        t.start()
    while len(ex._batcher._pending) < len(fns):
        time.sleep(0.001)
    release()
    for t in threads:
        t.join(timeout=120)


def test_mesh_decode_steps_coalesce(mesh_parts):
    """Co-arriving sessions' decode steps must share ONE pipeline pass
    (engine.dispatch_slots): all three are pending when the flusher gets the
    mesh, and results must match solo slot steps."""
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    parts, params = mesh_parts
    ex = MeshExecutor(
        TINY, params, MeshPlan(pp=2), num_slots=4, max_len=64,
        devices=jax.devices()[:2],
    )
    prompts = {f"ms{i}": [3 + i, 7, 11] for i in range(3)}
    solo = {}
    for s, ids in prompts.items():  # one session at a time: passes of one
        tok = int(_prefill(ex, "solo", ids).argmax())
        solo[s] = (tok, _decode(ex, "solo", tok, 3))
        ex.end_session("solo")
    for s, ids in prompts.items():
        assert int(_prefill(ex, s, ids).argmax()) == solo[s][0]
    before = ex.stats()
    results = {}
    _together(ex, [
        lambda s=s: results.update({s: _decode(ex, s, solo[s][0], 3)})
        for s in prompts
    ])
    assert len(results) == 3
    after = ex.stats()
    assert after["batched_steps"] - before["batched_steps"] == 1
    assert after["batched_tokens"] - before["batched_tokens"] == 3
    for s in prompts:
        np.testing.assert_allclose(results[s], solo[s][1], rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def mesh8(mesh_parts):
    """Eight slots over pp=2, as the four-chip cell runs them over pp=4."""
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    parts, params = mesh_parts
    return MeshExecutor(
        TINY, params, MeshPlan(pp=2), num_slots=8, max_len=64,
        devices=jax.devices()[:2], window_ms=400.0,
    )


def test_mesh_pass_takes_every_live_slot(mesh8, monkeypatch):
    """Eight threads on eight slots, four tokens each: the pass formed
    while the mesh was held carries all eight, the passes after it wait for
    whom the last ones served, and every session reads what it reads alone.
    A formation waits at most a step for a session's turn (window.py,
    `_cap_s`), and on the chip a pass (37 ms) outlasts a turn (6 ms); the
    tiny model's pass of rows on a loaded CPU does not, so the test holds
    the pass at 50 ms: the regime the wait is made for."""
    ex, steps = mesh8, 4
    finish = ex._finish

    def chip_long_pass(step):
        if not step.done:
            time.sleep(0.05)
        finish(step)

    monkeypatch.setattr(ex, "_finish", chip_long_pass)
    prompts = {f"e{i}": [3 + i, 7, 11 + i] for i in range(8)}
    solo = {}
    for s, ids in prompts.items():
        toks, rows = [int(_prefill(ex, "solo", ids).argmax())], []
        for k in range(steps):
            rows.append(_decode(ex, "solo", toks[-1], 3 + k))
            toks.append(int(rows[-1].argmax()))
        solo[s] = (toks, rows)
        ex.end_session("solo")
    for s, ids in prompts.items():
        _prefill(ex, s, ids)
    before = ex.stats()
    rows = {s: [] for s in prompts}

    def session(s, ks):
        for k in ks:
            rows[s].append(_decode(ex, s, solo[s][0][k], 3 + k))

    try:
        _together(ex, [lambda s=s: session(s, [0]) for s in prompts])
        one = ex.stats()
        threads = [threading.Thread(target=session, args=(s, range(1, steps)))
                   for s in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        after = ex.stats()
    finally:
        for s in prompts:
            ex.end_session(s)
    assert one["batched_steps"] - before["batched_steps"] == 1
    assert one["batched_tokens"] - before["batched_tokens"] == 8
    assert after["batched_tokens"] - before["batched_tokens"] == 8 * steps
    # the later passes form on their own: a wake-up swap behind a running
    # pass gives passes of one or two
    passes = after["batched_steps"] - before["batched_steps"]
    assert 8 * steps / passes >= 4
    assert after["gang_full"] > before["gang_full"]
    for s in prompts:
        for got, want in zip(rows[s], solo[s][1]):
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("leaves", ["ends", "restarts", "replays_a_chunk"])
def test_mesh_pass_does_not_wait_for_a_slot_that_left(mesh8, leaves):
    """A session that a pass served and that then ends, or goes back into
    a prefill, is not waited for: the next pass forms with whoever is left
    and no formation runs into the cap. (`idle` only keeps a co-arrival
    possible; no pass has served it, so nobody waits for it either.)"""
    ex = mesh8
    prompts = {f"{leaves}{i}": [5 + i, 13, 17 + i, 2] for i in range(3)}
    stays, goes, idle = prompts
    tok = {s: int(_prefill(ex, s, ids).argmax()) for s, ids in prompts.items()}
    try:
        rows = {}
        _together(ex, [
            lambda s=s: rows.update({s: _decode(ex, s, tok[s], 4)})
            for s in (stays, goes)
        ])
        assert len(rows) == 2
        before = ex.stats()
        if leaves == "ends":
            ex.end_session(goes)
        elif leaves == "restarts":
            _prefill(ex, goes, prompts[goes])
        else:  # the client lost a reply and sends the chunk's tail again
            ex.process(goes, {"tokens": [prompts[goes][2:]], "start_pos": 2, "real_len": 2})
        _decode(ex, stays, int(rows[stays].argmax()), 5)
        after = ex.stats()
    finally:
        for s in prompts:
            ex.end_session(s)
    assert after["gang_timeout"] == before["gang_timeout"]
    assert after["gang_full"] - before["gang_full"] == 1
    assert after["batched_steps"] - before["batched_steps"] == 1
    assert after["batched_tokens"] - before["batched_tokens"] == 1


@pytest.mark.parametrize("flags,workers", [
    ({"mesh_plan": MeshPlan(pp=2), "mesh_slots": 8}, 9),
    ({"mesh_plan": MeshPlan(pp=2), "mesh_slots": 3}, 4),
    ({"batch_lanes": 4}, 5),
    ({}, 2),
])
def test_worker_pool_admits_a_thread_per_session(mesh_parts, devices8, flags, workers):
    """The node's pool has a thread for every session its executor can have
    in a step, and one for a prefill: slots under --mesh, lanes under
    --batch-lanes, two for a plain stage."""
    parts, _params = mesh_parts
    info = NodeInfo(
        name="pool", host="127.0.0.1", port=PORTS.http(90),
        stage=0, num_stages=1, model_name="tiny",
    )
    dht = SwarmDHT(info.node_id, PORTS.gossip(90), bootstrap=[], host="127.0.0.1")
    node = Node(info, TINY, parts, dht, backend="qwen3", max_len=64, **flags)
    try:
        assert node.scheduler._pool._max_workers == workers
    finally:
        node.scheduler.shutdown()


def test_mesh_executor_handoff_roundtrip(mesh_parts, devices8):
    """--mesh replicas hand sessions off: export a slot from one mesh
    executor (layer axis reassembled across pp ranks), import into a peer
    running a DIFFERENT pp split, identical continuation logits."""
    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    parts, params = mesh_parts
    a = MeshExecutor(TINY, params, MeshPlan(pp=2), num_slots=2, max_len=64,
                     devices=devices8[:2])
    b = MeshExecutor(TINY, params, MeshPlan(pp=4), num_slots=2, max_len=64,
                     devices=devices8[:4])
    prompt = [3, 7, 11, 19, 5]
    a.process("s", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    exported = dict(a.export_sessions())["s"]
    assert exported["length"] == len(prompt)
    assert b.import_session("s", exported)
    step = {"tokens": [[4]], "start_pos": len(prompt), "real_len": 1}
    la = a.process("s", dict(step))["logits"]
    lb = b.process("s", dict(step))["logits"]
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=2e-5, atol=2e-5)
    # wrong layer count rejected; duplicate session rejected
    bad = dict(exported)
    bad["k"] = bad["k"][:-1]
    bad["v"] = bad["v"][:-1]
    assert not b.import_session("s2", bad)
    assert not b.import_session("s", exported)


# ---------------------------------------------------------------------------
# O(window) ring KV on the in-mesh path (VERDICT r03 item 3): sliding-window
# models served via --mesh store sliding layers as rings — parity with the
# uniform layout and the solo engine, handoff/replay/fork under the ring
# margin, and the odd-split fallback staying observable.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gemma_tiny():
    from inferd_tpu.config import get_config

    cfg = get_config("tiny-gemma2")
    return cfg, qwen3.init_params(cfg, jax.random.PRNGKey(0))


def test_mesh_ring_parity_sliding_models(devices8):
    """PipelinedEngine ring layout == uniform layout == solo Engine for
    both sliding-window families on a pp=2 mesh; the ring layout stores
    measurably less KV (the memory win the design pays for)."""
    from inferd_tpu.config import get_config
    from inferd_tpu.parallel.infer import PipelinedEngine, ring_split_ok

    for name in ("tiny-gemma2", "tiny-gptoss"):
        cfg = get_config(name)
        params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
        solo = Engine(cfg, params, max_len=512, sampling_cfg=GREEDY)
        prompt = [3, 7, 11, 19, 5]
        want = solo.generate(prompt, max_new_tokens=8)
        mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2), jax.devices()[:2])
        assert ring_split_ok(cfg, 2)
        sizes = {}
        for ring in (None, False):
            eng = PipelinedEngine(
                cfg, params, mesh, num_microbatches=2, batch=1,
                max_len=512, sampling_cfg=GREEDY, ring=ring,
            )
            assert eng.ring_active == (ring is None)
            got = eng.generate([prompt], 8)[0]
            assert got == want, (name, ring, got, want)
            total = eng.caches.k.size + eng.caches.v.size
            if eng.caches.k_loc is not None:
                total += eng.caches.k_loc.size + eng.caches.v_loc.size
            sizes[bool(eng.ring_active)] = total
        # half the layers store O(window)+margin instead of max_len=512
        assert sizes[True] < 0.65 * sizes[False], sizes


def test_mesh_ring_tp_parity(gemma_tiny, devices8):
    """Ring storage composes with tensor parallelism: pp=2 x tp=2 serving
    of a sliding-window model stays token-exact (rings hold each rank's
    local kv heads)."""
    from inferd_tpu.parallel.infer import PipelinedEngine

    cfg, params = gemma_tiny
    solo = Engine(cfg, params, max_len=64, sampling_cfg=GREEDY)
    prompt = [5, 2, 9, 13]
    want = solo.generate(prompt, max_new_tokens=6)
    mesh = meshlib.make_mesh(meshlib.MeshPlan(pp=2, tp=2), devices8[:4])
    eng = PipelinedEngine(
        cfg, params, mesh, num_microbatches=2, batch=1, max_len=64,
        sampling_cfg=GREEDY,
    )
    assert eng.ring_active
    assert eng.generate([prompt], 6)[0] == want


def test_mesh_ring_executor_handoff_and_fallback(gemma_tiny, devices8):
    """Mesh executors hand RING sessions off between different (ring-
    capable) pp splits token-exact; an odd layers-per-rank split falls
    back to uniform KV, says so in stats(), and fails the ring handoff
    CLOSED (layout mismatch -> clean miss, no corruption)."""
    import dataclasses as dc

    from inferd_tpu.config import get_config
    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    cfg, params = gemma_tiny
    a = MeshExecutor(cfg, params, MeshPlan(pp=2), num_slots=2, max_len=64,
                     devices=devices8[:2])
    b = MeshExecutor(cfg, params, MeshPlan(pp=1), num_slots=2, max_len=64,
                     devices=devices8[:1])
    assert a.engine.ring_active and b.engine.ring_active
    assert not a.stats()["kv_window_fallback"]
    prompt = [3, 7, 11, 19, 5]
    a.process("s", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    exported = dict(a.export_sessions())["s"]
    assert "k_loc" in exported  # rings ship whole
    assert b.import_session("s", exported)
    step = {"tokens": [[4]], "start_pos": len(prompt), "real_len": 1}
    la = a.process("s", dict(step))["logits"]
    lb = b.process("s", dict(step))["logits"]
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=2e-5, atol=2e-5)

    # odd layers-per-rank: 6-layer variant at pp=2 -> 3 per rank
    cfg_odd = dc.replace(cfg, name="tiny-gemma2-l6", num_layers=6)
    params_odd = qwen3.init_params(cfg_odd, jax.random.PRNGKey(1))
    c = MeshExecutor(cfg_odd, params_odd, MeshPlan(pp=2), num_slots=2,
                     max_len=64, devices=devices8[:2])
    assert not c.engine.ring_active
    assert c.stats()["kv_window_fallback"]
    # uniform still serves correctly
    solo = Engine(cfg_odd, params_odd, max_len=64, sampling_cfg=GREEDY)
    want = solo.generate(prompt, max_new_tokens=4)
    got = [int(np.argmax(c.process(
        "u", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)}
    )["logits"][0]))]
    pos = len(prompt)
    for _ in range(3):
        got.append(int(np.argmax(c.process(
            "u", {"tokens": [[got[-1]]], "start_pos": pos, "real_len": 1}
        )["logits"][0])))
        pos += 1
    assert got == want
    # a ring payload into a uniform-layout executor fails closed
    assert not c.import_session("sx", exported)


def test_mesh_ring_replay_margin(gemma_tiny, devices8):
    """Deterministic chunk replay on the ring mesh path: rollback within
    the ring margin recomputes token-exact; rollback past the high-water
    margin is REFUSED (the rings have already overwritten those slots —
    accepting would corrupt silently)."""
    from inferd_tpu.core.cache import RING_MARGIN
    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    cfg, params = gemma_tiny
    ex = MeshExecutor(cfg, params, MeshPlan(pp=2), num_slots=2, max_len=256,
                      devices=devices8[:2])
    assert ex.engine.ring_active
    rng = np.random.RandomState(0)
    chunks = [list(rng.randint(0, cfg.vocab_size, size=32)) for _ in range(3)]
    pos = 0
    outs = []
    for ch in chunks:  # stream 96 positions in (> RING_MARGIN + window)
        outs.append(ex.process(
            "r", {"tokens": [ch], "start_pos": pos, "real_len": len(ch)}
        )["logits"])
        pos += len(ch)
    # replay the LAST chunk (depth 32 < margin): identical logits
    replay = ex.process(
        "r", {"tokens": [chunks[-1]], "start_pos": 64, "real_len": 32}
    )["logits"]
    np.testing.assert_allclose(
        np.asarray(replay), np.asarray(outs[-1]), rtol=2e-5, atol=2e-5
    )
    # replay reaching past the margin (high-water 96, target 16 -> depth 80)
    assert 96 - 16 > RING_MARGIN
    with pytest.raises(ValueError, match="ring margin"):
        ex.process(
            "r", {"tokens": [chunks[0]], "start_pos": 16, "real_len": 32}
        )
