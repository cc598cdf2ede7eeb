"""Disaggregated prefill->decode (round 5, VERDICT r04 #5): a session
prefills on one replica, its KV hands off to a decode replica via
/export_session, and decoding continues there TOKEN-EXACT with zero
restarts. The reference pins a session's KV to one server forever
(qwen3_server_module.py:220); here placement is a per-phase choice."""

import asyncio

import jax
import pytest

from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.control.dht import SwarmDHT
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import Manifest, split_and_save
from inferd_tpu.runtime.node import Node, NodeInfo

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)
GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module")
def whole_parts(tmp_path_factory):
    parts = tmp_path_factory.mktemp("disagg_parts")
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    split_and_save(params, TINY, Manifest.even_split("tiny", 1), str(parts))
    return str(parts), params


def _mk_node(idx, parts, batch_lanes=0):
    info = NodeInfo(
        name=f"dg{idx}", host="127.0.0.1", port=PORTS.http(idx),
        stage=0, num_stages=1, capacity=8, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx), bootstrap=(
            [] if idx == 0 else [("127.0.0.1", PORTS.gossip())]
        ),
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, batch_lanes=batch_lanes,
    )


@pytest.mark.asyncio
async def test_prefill_on_a_decode_on_b_token_exact(whole_parts):
    """Prefill on replica A, decode on replica B: the stream equals a
    single-replica greedy run token for token (zero restarts — the
    disaggregated client has no restart path, so exactness IS the proof),
    and A's /stats carries the handoff telemetry."""
    parts, params = whole_parts
    a = _mk_node(0, parts)
    b = _mk_node(1, parts)
    await a.start()
    await b.start()
    try:
        prompt = [3, 7, 11, 2, 5, 13]
        want = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY).generate(
            prompt, max_new_tokens=12
        )
        async with SwarmClient([("127.0.0.1", PORTS.http())], sampling=GREEDY) as c:
            got = await c.generate_ids_disaggregated(
                prompt, ("127.0.0.1", PORTS.http(1)), max_new_tokens=12
            )
        assert got == want
        snap = a.metrics.snapshot()
        assert snap["counters"]["handoff.bytes"] > 0
        assert snap["counters"]["sessions.handed_off"] == 1
        assert snap["histograms"]["handoff.ms"]["count"] == 1
        # A no longer holds the session; B adopted it (then ended it)
        assert b.metrics.snapshot()["counters"]["sessions.imported"] == 1
    finally:
        await a.stop()
        await b.stop()


@pytest.mark.asyncio
async def test_disagg_across_executor_types(whole_parts):
    """Prefill on a stage-executor replica, decode on a CONTINUOUS-
    BATCHING replica: the shared handoff codec re-homes the session across
    executor types mid-stream, token-exact."""
    parts, params = whole_parts
    a = _mk_node(2, parts)
    b = _mk_node(3, parts, batch_lanes=4)
    await a.start()
    await b.start()
    try:
        prompt = [9, 8, 7, 6, 5]
        want = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY).generate(
            prompt, max_new_tokens=10
        )
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(2))], sampling=GREEDY
        ) as c:
            got = await c.generate_ids_disaggregated(
                prompt, ("127.0.0.1", PORTS.http(3)), max_new_tokens=10
            )
        assert got == want
    finally:
        await a.stop()
        await b.stop()


@pytest.mark.asyncio
async def test_export_unknown_session_404(whole_parts):
    parts, _ = whole_parts
    a = _mk_node(4, parts)
    await a.start()
    try:
        from inferd_tpu.client.base import ServerError

        async with SwarmClient(
            [("127.0.0.1", PORTS.http(4))], sampling=GREEDY
        ) as c:
            with pytest.raises(ServerError) as ei:
                await c._post(
                    "/export_session",
                    {"session_id": "nope", "target_host": "127.0.0.1",
                     "target_port": PORTS.http(4)},
                )
            assert ei.value.status == 404
    finally:
        await a.stop()


@pytest.mark.asyncio
async def test_disagg_between_mesh_replicas(whole_parts, devices8):
    """Prefill on one --mesh pp=2 replica, decode on another: the slot KV
    exports across the pp split (layer axis reassembled), re-homes, and
    the stream stays token-exact."""
    from inferd_tpu.parallel.mesh import MeshPlan

    parts, params = whole_parts

    def mk_mesh(idx):
        info = NodeInfo(
            name=f"dgm{idx}", host="127.0.0.1", port=PORTS.http(10 + idx),
            stage=0, num_stages=1, capacity=8, model_name="tiny",
        )
        dht = SwarmDHT(
            info.node_id, PORTS.gossip(10 + idx), bootstrap=(
                [] if idx == 0 else [("127.0.0.1", PORTS.gossip(10))]
            ),
            host="127.0.0.1", gossip_period_s=0.05, ttl_s=5.0,
        )
        return Node(
            info, TINY, parts, dht, backend="qwen3", max_len=64,
            rebalance_period_s=600.0, mesh_plan=MeshPlan(pp=2),
            mesh_slots=2,
        )

    a, b = mk_mesh(0), mk_mesh(1)
    await a.start()
    await b.start()
    try:
        prompt = [3, 7, 11, 2, 5]
        want = Engine(TINY, params, max_len=64, sampling_cfg=GREEDY).generate(
            prompt, max_new_tokens=10
        )
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(10))], sampling=GREEDY
        ) as c:
            got = await c.generate_ids_disaggregated(
                prompt, ("127.0.0.1", PORTS.http(11)), max_new_tokens=10
            )
        assert got == want
        assert a.metrics.snapshot()["counters"]["sessions.handed_off"] == 1
    finally:
        await a.stop()
        await b.stop()
