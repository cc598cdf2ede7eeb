"""Chain-mode (hub-and-spoke) tests: the client drives each stage server
directly with `relay: false` — parity with the reference's gRPC slice
(/root/reference/models/qwen3/client/rpc_client.py:36-57) served by the
same unified node runtime as the swarm path."""

import functools

import pytest

from inferd_tpu.client.chain_client import ChainClient
from inferd_tpu.client.swarm_client import SwarmClient
from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.generate import Engine

import test_node_e2e as e2e
from conftest import port_block
from test_node_e2e import _start_all, _stop_all, tiny_parts  # noqa: F401

PORTS = port_block(__file__)
_mk_node = functools.partial(e2e._mk_node, ports=PORTS)  # its nodes, this module's ports


@pytest.mark.asyncio
async def test_chain_counter_no_relay():
    """relay=false returns each stage's raw result instead of relaying; the
    client carries the payload between stages."""
    nodes = [_mk_node(30 + i, i, 3, bootstrap_idx=30) for i in range(3)]
    await _start_all(nodes)
    try:
        async with ChainClient(
            [("127.0.0.1", PORTS.http(30 + i)) for i in range(3)]
        ) as c:
            payload = {}
            for stage in range(3):
                resp = await c._post(
                    ("127.0.0.1", PORTS.http(30 + stage)),
                    "/forward",
                    {
                        "stage": stage,
                        "session_id": "chain1",
                        "relay": False,
                        "payload": payload,
                    },
                )
                # hub-and-spoke: the serving node answers for itself only
                assert resp["served_by"] == f"127.0.0.1:{PORTS.http(30 + stage)}"
                payload = dict(resp["result"])
                payload.pop("result_for_user", None)
            assert payload["state"] == 3
            assert payload["trace"] == [0, 1, 2]
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_chain_generation_matches_engine(tiny_parts):  # noqa: F811
    """Golden chain test: fixed 2-server chain == single-process engine,
    token for token (greedy), KV cached server-side per session."""
    parts, params = tiny_parts
    nodes = [
        _mk_node(40 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=40)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        engine = Engine(TINY, params, max_len=64, sampling_cfg=SamplingConfig(temperature=0.0))
        prompt = [3, 7, 11, 19]
        expected = engine.generate(prompt, max_new_tokens=6)
        async with ChainClient(
            [("127.0.0.1", PORTS.http(40)), ("127.0.0.1", PORTS.http(41))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            got = await c.generate_ids(prompt, max_new_tokens=6)
        assert got == expected
        # sessions were ended on both servers by end_session
        for n in nodes:
            assert len(n.executor.sessions) == 0
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_chain_end_session_is_local(tiny_parts):  # noqa: F811
    """relay=false end_session drops only the addressed server's cache."""
    parts, params = tiny_parts
    nodes = [
        _mk_node(50 + i, i, 2, backend="qwen3", parts=parts, bootstrap_idx=50)
        for i in range(2)
    ]
    await _start_all(nodes)
    try:
        async with ChainClient(
            [("127.0.0.1", PORTS.http(50)), ("127.0.0.1", PORTS.http(51))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            await c._forward_through_chain("s-local", [1, 2, 3], 0)
            assert len(nodes[0].executor.sessions) == 1
            assert len(nodes[1].executor.sessions) == 1
            await c._post(
                ("127.0.0.1", PORTS.http(50)),
                "/end_session",
                {"session_id": "s-local", "stage": 0, "relay": False},
            )
            assert len(nodes[0].executor.sessions) == 0
            assert len(nodes[1].executor.sessions) == 1  # untouched
    finally:
        await _stop_all(nodes)


@pytest.mark.asyncio
async def test_chain_wrong_stage_fails_loudly():
    """A relay=false request to a node serving a different stage must be
    rejected (409), not silently rerouted via the DHT — the chain client's
    fixed-topology contract."""
    nodes = [_mk_node(60 + i, i, 2, bootstrap_idx=60) for i in range(2)]
    await _start_all(nodes)
    try:
        async with SwarmClient([("127.0.0.1", PORTS.http(61))]) as c:  # node serving stage 1
            with pytest.raises(RuntimeError, match="wrong stage"):
                await c._post(
                    "/forward",
                    {"stage": 0, "session_id": "x", "relay": False, "payload": {}},
                )
    finally:
        await _stop_all(nodes)
