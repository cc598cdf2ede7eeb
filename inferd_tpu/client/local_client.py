"""The generation loop of a node that serves its own hops.

/generate runs SwarmClient's loop (prefill, sample, step, pins, restarts,
resume) on the node itself. Its hops enter the node's handler cores as
Python calls: the envelope dict goes in as built, and a hop this node
finishes comes back as the dict the executor's result was put in — no
bytes packed, no socket. Every decode hop asks for its token (base.
_decode_ask) and a whole-model executor answers with it: the row's logits
never leave the device; a prefill chunk's logits row is a view of what
copy_out made. A
hop that had to leave the node (a multi-stage chain, a wrong-stage entry,
a rescue) comes back as the downstream reply's bytes and is unpacked here
exactly as the HTTP client unpacks them. Which case applies is read off
what the handler returns.
"""

from __future__ import annotations

import asyncio
from typing import Any, Awaitable, Callable, Dict, Tuple

from inferd_tpu.client.base import unpack_reply
from inferd_tpu.client.swarm_client import SwarmClient


class LocalClient(SwarmClient):
    """SwarmClient with the transport replaced by `serve(path, envelope)`
    — the owning node's handler core for /forward, /end_session and
    /fork_session. `serve` returns a reply dict, or a response object
    (`status`, `body`, `headers`) holding wire bytes."""

    def __init__(
        self, serve: Callable[[str, Dict[str, Any]], Awaitable[Any]],
        addr: Tuple[str, int], timeout_s: float, block_length: int = 1,
    ):
        super().__init__([addr], timeout_s=timeout_s)
        self._serve = serve
        self._block = block_length  # the node loaded the model: nothing to ask

    async def __aenter__(self):
        return self  # no HTTP session: nothing here opens a connection

    async def _post(self, path: str, body: Dict[str, Any]) -> Dict[str, Any]:
        where = f"local {path}"
        # the hop keeps the HTTP hop's bounds: the static timeout, or what
        # is left of the end-to-end deadline (a stalled handler must cost
        # the generation no more than it did over the socket)
        async with asyncio.timeout(self._hop_timeout_s(where)):
            out = await self._serve(path, body)
        if isinstance(out, dict):
            return out
        return unpack_reply(where, out.status, out.body, out.headers)
