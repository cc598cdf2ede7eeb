"""Swarm generation client: drives the pipeline, sampling where the last
stage does not (client.base: every decode hop asks for its token).

Capability parity with both reference clients — the swarm token loop
(/root/reference/petals/send_message.py:27-60) and the gRPC generation
client (/root/reference/models/qwen3/client/client.py:204-287) — unified:
the client sends tokens to any stage-0 node and receives from the last
stage (relay unwind) the token it chose, or last-token logits, which it
samples locally (temperature/top-k/top-p, the reference's warper chain),
and keeps per-session KV on the nodes. Pure numpy — importing this never initializes JAX (a TPU client
machine shouldn't claim a chip to sample 20 logits).

The outer generation loop lives in client.base.GenerationClient (shared
with ChainClient); this class supplies the relay transport: every chunk
enters at a stage-0 node and the swarm routes it onward.
"""

from __future__ import annotations

import asyncio
import logging
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

import aiohttp
import numpy as np

from inferd_tpu.client.base import GenerationClient, sample_np  # noqa: F401 (re-export)
from inferd_tpu.config import SamplingConfig
from inferd_tpu.core.tokenizer import Tokenizer
from inferd_tpu.utils import retry as retrylib

log = logging.getLogger(__name__)


class SwarmClient(GenerationClient):
    """Async client for a running swarm (relay topology)."""

    def __init__(
        self,
        entry_nodes: Sequence[Tuple[str, int]],
        sampling: Optional[SamplingConfig] = None,
        tokenizer: Optional[Tokenizer] = None,
        timeout_s: float = 300.0,
        prefill_chunk: int = 512,
        adapter: Optional[str] = None,
    ):
        if not entry_nodes:
            raise ValueError("need at least one entry node address")
        super().__init__(
            sampling, tokenizer, timeout_s, prefill_chunk, adapter=adapter
        )
        self.entry_nodes = [tuple(a) for a in entry_nodes]

    async def _post(self, path: str, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST to the first healthy entry node (stage-0 failover)."""
        from inferd_tpu.client.base import ServerError

        last_err: Optional[Exception] = None
        for host, port in self.entry_nodes:
            try:
                return await self._post_url(f"http://{host}:{port}{path}", body)
            except (OSError, asyncio.TimeoutError, aiohttp.ClientError, ValueError) as e:
                # ValueError: non-wire/truncated body (base._post_url) — the
                # endpoint is broken even if it spoke HTTP; try the next one
                last_err = e
                log.warning("entry node %s:%d unreachable: %s", host, port, e)
            except ServerError as e:
                if e.status < 500:
                    raise  # deterministic (400/409...): another entry won't differ
                # 5xx: THIS entry is unhealthy (e.g. draining mid-shutdown).
                # Another entry can serve the chunk — mid-session ones too,
                # now that nodes advertise session locations via gossip and
                # relay to the KV holder (runtime/node.py rescue path).
                last_err = e
                log.warning("entry node %s:%d unhealthy: %s", host, port, e)
        if isinstance(last_err, ServerError):
            raise last_err
        raise ConnectionError(f"no entry node reachable: {last_err}")

    def _forward_env(self, session_id: str, tokens: List[int], start_pos: int):
        """The ONE /forward envelope definition (entry-routed _step and the
        direct-URL disaggregated decode share it). The active trace
        context rides as a `trace` key next to session_id/task_id; with
        tracing disabled (INFERD_TRACE=0) the key is OMITTED so the
        envelope stays byte-identical to the untraced format. The active
        end-to-end deadline rides the same way (`deadline_ms`, omitted
        when no deadline is set — old peers ignore the key, deadline-less
        traffic stays byte-exact). A client bound to a tenant adapter
        stamps the `adapter` key on the FIRST chunk only (start_pos 0 —
        admission binds the session; omitted otherwise, so base-model
        envelopes stay byte-identical)."""
        from inferd_tpu.client.base import deadline_wire
        from inferd_tpu.obs import trace as tracelib

        return tracelib.attach_wire({
            # an opaque id, made without a system call: uuid4 reads the
            # kernel's random source, and on a node's loop thread (the
            # LocalClient's hops) every such call hands the GIL to whichever
            # pool worker is awake (obs.trace.new_id, PERF.md section 6)
            "task_id": tracelib.new_id(),
            "session_id": session_id,
            "stage": 0,
            "payload": {
                "tokens": np.asarray([tokens], dtype=np.int32),
                "start_pos": start_pos,
                "real_len": len(tokens),
                **(
                    {"adapter": self.adapter}
                    if self.adapter is not None and start_pos == 0 else {}
                ),
            },
            **deadline_wire(),
        })

    async def _forward(
        self, session_id: str, tokens: List[int], start_pos: int, **extra
    ) -> Dict[str, Any]:
        env = self._forward_env(session_id, tokens, start_pos)
        env["payload"].update(extra)
        return (await self._post("/forward", env))["result_for_user"]

    async def _step(
        self, session_id: str, tokens: List[int], start_pos: int
    ) -> np.ndarray:
        result = await self._forward(session_id, tokens, start_pos)
        return np.asarray(result["logits"])[0]

    async def _asking_step(
        self, session_id: str, tokens: List[int], start_pos: int,
        ask: Dict[str, Any],
    ) -> Dict[str, Any]:
        """The ask rides the payload's top level (stages that do not read
        it relay hidden states on and the last answers with logits)."""
        return await self._forward(session_id, tokens, start_pos, **ask)

    async def _block_length(self) -> int:
        """/stats `model.block_length` of the first entry node that answers;
        a node that says nothing of its model serves token by token."""
        if self._block is not None:
            return self._block
        assert self._http is not None, "use `async with <client>(...)`"
        for host, port in self.entry_nodes:
            try:
                async with self._http.get(f"http://{host}:{port}/stats") as r:
                    model = (await r.json()).get("model") or {}
                self._block = int(model.get("block_length", 1))
                return self._block
            except (OSError, asyncio.TimeoutError, aiohttp.ClientError, ValueError):
                continue  # the generation's own hop reports an entry that is down
        return 1

    async def _end_session(self, session_id: str) -> None:
        await self._post("/end_session", {"session_id": session_id, "stage": 0})

    async def generate_ids_disaggregated(
        self,
        prompt_ids: Sequence[int],
        decode_node: Tuple[str, int],
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        sampling: Optional[SamplingConfig] = None,
    ) -> List[int]:
        """DISAGGREGATED prefill->decode: prefill on this client's entry
        replica (wherever capacity for the long compute-bound prefill
        is), hand the session's KV to `decode_node` via /export_session,
        and run the bandwidth-bound decode loop THERE — token-exact with
        a single-replica generation, zero restarts. The reference pins a
        session's KV to one server forever (qwen3_server_module.py:220);
        this build's handoff codec makes placement a per-phase choice."""
        from inferd_tpu.client.base import ServerError, sample_np

        if not prompt_ids:
            raise ValueError("prompt_ids must be non-empty")
        s = sampling or self.sampling
        rng = np.random.default_rng(seed)
        sid = str(uuid.uuid4())
        dh, dp = decode_node
        durl = f"http://{dh}:{dp}"
        out: List[int] = []
        handed_off = False
        try:
            # phase 1: chunked prefill on the entry replica
            pos = 0
            logits = None
            ids = [int(t) for t in prompt_ids]
            for i in range(0, len(ids), self.prefill_chunk):
                chunk = ids[i : i + self.prefill_chunk]
                logits = await self._step(sid, chunk, pos)
                pos += len(chunk)
            assert logits is not None
            # phase 2: hand the session to the decode replica
            resp = await self._post(
                "/export_session",
                {"session_id": sid, "target_host": dh, "target_port": dp},
            )
            if not resp.get("ok"):
                raise ServerError(f"handoff declined: {resp}", 502)
            # phase 3: decode against the target, token-exact
            tok = sample_np(logits, rng, s.temperature, s.top_k, s.top_p, s.min_p)
            out.append(tok)
            handed_off = True
            while len(out) < max_new_tokens and tok != eos_token_id:
                r = await self._post_url(
                    f"{durl}/forward", self._forward_env(sid, [tok], pos)
                )
                logits = np.asarray(r["result_for_user"]["logits"])[0]
                pos += 1
                tok = sample_np(logits, rng, s.temperature, s.top_k, s.top_p, s.min_p)
                out.append(tok)
        finally:
            try:
                await self._post_url(
                    f"{durl}/end_session", {"session_id": sid, "stage": 0}
                )
            except Exception:
                pass  # best effort: TTL sweep collects orphans
            if not handed_off:
                # a failure BEFORE the handoff leaves the session (a
                # pinned lane on batched replicas) on the ENTRY node —
                # free it now, not at the TTL sweep
                try:
                    await self._end_session(sid)
                except Exception:
                    pass
        return out

    async def generate_server_side(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        pin_prefix_len: int = 0,
        sampling: Optional[SamplingConfig] = None,
        logprob_sink: Optional[List[float]] = None,
        top_logprobs: int = 0,
        top_sink: Optional[List] = None,
        return_payload: bool = False,
        deadline_s: Optional[float] = None,
    ) -> List[int]:
        """One-round-trip generation: the NODE runs the token loop against
        itself (/generate) and returns the finished ids — for clients far
        from the swarm, where a per-token round trip would dominate.
        `pin_prefix_len` marks the first N prompt ids as a shared prefix the
        node pins and forks server-side. `logprob_sink` (the same out-param
        convention as generate_ids — stable return type) collects each
        token's model log-probability; `top_sink` with `top_logprobs > 0`
        collects per-token (top_ids, top_lps) alternatives.
        `return_payload=True` returns the node's whole reply dict instead
        of just ids (e.g. `speculative`/`spec_accept_rate` telemetry)."""
        s = sampling or self.sampling
        want_lp = logprob_sink is not None
        # client root span: makes _post_url send the X-Inferd-Trace header,
        # so the node's server-side token loop joins THIS trace and the
        # merged timeline keeps the client's wall-clock view
        with self.tracer.span(
            "generate", "client",
            attrs={"prompt": len(prompt_ids), "max_new": max_new_tokens,
                   "server_side": True},
        ):
            resp = await self._post(
                "/generate",
                {
                    "prompt_ids": [int(t) for t in prompt_ids],
                    "max_new_tokens": max_new_tokens,
                    "eos_token_id": eos_token_id,
                    "seed": seed,
                    "pin_prefix_len": pin_prefix_len,
                    # end-to-end budget for the WHOLE server-driven
                    # generation; rides only when set (old nodes ignore
                    # the key, deadline-less bodies stay byte-identical)
                    **(
                        {"deadline_ms":
                         retrylib.deadline_ms_from_now(deadline_s)}
                        if deadline_s is not None else {}
                    ),
                    # like min_p below: only ride when set (rolling upgrades)
                    **({"logprobs": True} if want_lp else {}),
                    **({"top_logprobs": top_logprobs} if top_logprobs else {}),
                    # min_p rides only when set: pre-min-p nodes reject
                    # unknown sampling keys (rolling-upgrade compatibility)
                    "sampling": {
                        "temperature": s.temperature,
                        "top_k": s.top_k,
                        "top_p": s.top_p,
                        **({"min_p": s.min_p} if s.min_p else {}),
                    },
                },
            )
        ids = [int(t) for t in resp["ids"]]
        if want_lp:
            logprob_sink.clear()
            logprob_sink.extend(float(x) for x in resp.get("logprobs") or [])
        if top_sink is not None:
            top_sink.clear()
            top_sink.extend(
                ([int(i) for i in ti], [float(x) for x in tl])
                for ti, tl in (resp.get("top_logprobs") or [])
            )
        if return_payload:
            return resp
        return ids

    async def generate_server_side_stream(
        self,
        prompt_ids: Sequence[int],
        on_token,
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        pin_prefix_len: int = 0,
        sampling: Optional[SamplingConfig] = None,
    ) -> List[int]:
        """Streaming flavor of generate_server_side: `on_token(id)` fires as
        each token arrives (None = restart marker — previously streamed
        tokens are void); returns the final ids. Transport is chunked
        newline-delimited JSON from the node's /generate."""
        from inferd_tpu.runtime import wire

        s = sampling or self.sampling
        body = wire.pack(
            {
                "prompt_ids": [int(t) for t in prompt_ids],
                "max_new_tokens": max_new_tokens,
                "eos_token_id": eos_token_id,
                "seed": seed,
                "pin_prefix_len": pin_prefix_len,
                "stream": True,
                # min_p rides only when set: pre-min-p nodes reject
                # unknown sampling keys (rolling-upgrade compatibility)
                "sampling": {
                    "temperature": s.temperature,
                    "top_k": s.top_k,
                    "top_p": s.top_p,
                    **({"min_p": s.min_p} if s.min_p else {}),
                },
            }
        )
        assert self._http is not None, "use `async with SwarmClient(...)`"
        # per-request timeout: the session-wide ClientTimeout(total=...)
        # would cap the WHOLE stream, making generations longer than
        # timeout_s impossible; bound inactivity between chunks instead
        # (tokens arrive continuously while the generation is healthy)
        stream_timeout = aiohttp.ClientTimeout(
            total=None, sock_connect=min(self.timeout_s, 60.0),
            sock_read=self.timeout_s,
        )
        from inferd_tpu.obs import trace as tracelib

        # client root span (see generate_server_side): without it no
        # X-Inferd-Trace header ever rides, and a standalone client's
        # server-driven streams would be invisible in merged timelines
        with self.tracer.span(
            "generate", "client",
            attrs={"prompt": len(prompt_ids), "max_new": max_new_tokens,
                   "server_side": True, "stream": True},
        ):
            trace_headers = tracelib.header_ctx()
            return await self._stream_entry_loop(
                body, stream_timeout, trace_headers, on_token
            )

    async def _stream_entry_loop(
        self, body, stream_timeout, trace_headers, on_token
    ) -> List[int]:
        """The entry-node failover loop of generate_server_side_stream
        (split out so the root span wraps it cleanly)."""
        import json as jsonlib

        from inferd_tpu.client.base import _emit

        last_err: Optional[Exception] = None
        emitted_any = False
        for host, port in self.entry_nodes:
            url = f"http://{host}:{port}/generate"
            try:
                async with self._http.post(
                    url, data=body, timeout=stream_timeout,
                    headers=trace_headers,
                ) as r:
                    if r.status != 200:
                        # deterministic app error (400/409...): preserve the
                        # ServerError status/code contract — do NOT fail over
                        # and retry the identical bad request
                        from inferd_tpu.client.base import ServerError
                        from inferd_tpu.runtime import wire as wirelib

                        raw = await r.read()
                        try:
                            data = wirelib.unpack(raw)
                        except Exception:
                            data = {}
                        detail = data.get("error", raw[:200]) if isinstance(data, dict) else raw[:200]
                        code = data.get("code") if isinstance(data, dict) else None
                        raise ServerError(
                            f"{url} error {r.status}: {detail}", r.status, code
                        )
                    ids: Optional[List[int]] = None
                    # manual line splitting over iter_any(): aiohttp's line
                    # iterator caps a line at ~64 KB, which the terminal
                    # {"done", "ids": [...]} line exceeds on long generations
                    buf = b""
                    async for chunk in r.content.iter_any():
                        buf += chunk
                        while b"\n" in buf:
                            line, buf = buf.split(b"\n", 1)
                            if not line.strip():
                                continue
                            obj = jsonlib.loads(line)
                            if "t" in obj:
                                emitted_any = True
                                await _emit(on_token, int(obj["t"]))
                            elif obj.get("restart"):
                                await _emit(on_token, None)
                            elif obj.get("done"):
                                ids = [int(t) for t in obj["ids"]]
                            elif "error" in obj:
                                raise RuntimeError(
                                    f"server-side generation: {obj['error']}"
                                )
                    if ids is None:
                        raise ConnectionError(f"{url} stream ended without done line")
                    return ids
            except (OSError, asyncio.TimeoutError, aiohttp.ClientError) as e:
                last_err = e
                log.warning("entry node %s:%d unreachable: %s", host, port, e)
                if emitted_any:
                    # failing over re-streams from scratch on the next node:
                    # void what the consumer already saw (same contract as
                    # the server-side retry's restart marker)
                    await _emit(on_token, None)
                    emitted_any = False
        raise ConnectionError(f"no entry node reachable: {last_err}")

    async def _fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        """Fork the parent's per-stage KV prefix swarm-wide: the request
        enters at stage 0 and relays along the parent's affinity route."""
        resp = await self._post(
            "/fork_session",
            {
                "session_id": new_session_id,
                "parent_session_id": parent_session_id,
                "prefix_len": prefix_len,
                "stage": 0,
            },
        )
        return bool(resp.get("ok"))
