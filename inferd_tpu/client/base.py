"""Shared generation-client front end.

Both client topologies — SwarmClient (relay: enter at stage 0, the swarm
routes hop-to-hop, reference petals/send_message.py:27-60) and ChainClient
(hub-and-spoke: the client drives each stage, reference models/qwen3/client/
client.py:204-287) — run the exact same outer loop: tokenize, prefill, then
sample-append-step until EOS/budget, then drop the session's server-side KV.
That loop lives here once; subclasses provide only the transport step.

Who samples: the first token is sampled here, from the last prefill
chunk's logits. Every DECODE hop then carries an ask (`_decode_ask`: the
sampling config, the session's PRNG chain, the log-probabilities wanted):
an executor that chooses tokens on the device (`--batch-lanes`, `--mesh`)
answers with `tokens` and the chain's next `key`, and no logits leave it;
any other (a stage executor behind a relay, a node of an older tree, a
transport that cannot carry the ask) answers with `logits` and the loop
samples as it always did (`sample_np`). Which case applies is read off
the reply: there is no switch.
"""

from __future__ import annotations

import asyncio
import contextvars
import random
import time
import uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence

import aiohttp
import numpy as np
from aiohttp import ClientSession, ClientTimeout

from inferd_tpu.config import SamplingConfig
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core.tokenizer import Tokenizer
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.runtime import wire
from inferd_tpu.utils import retry as retrylib


class ServerError(RuntimeError):
    """Non-200 wire response. `code` is the node's machine-readable error
    class (runtime.node error codes); `retryable` says whether restarting
    the generation under a fresh session can possibly help; `retry_after`
    (seconds, optional) is the node's busy-503 pacing hint — the retry
    loop waits at least this long instead of hammering a shedding node."""

    def __init__(
        self, message: str, status: int, code: Optional[str] = None,
        retry_after: Optional[float] = None,
        resume_from: Optional[int] = None,
    ):
        super().__init__(message)
        self.status = status
        self.code = code
        self.retry_after = retry_after
        # standby promotion offer (crash-tolerant sessions): a
        # session_state 409 carrying the replicated-KV frontier — the
        # generation loop re-sends only the tokens past it (bounded
        # re-prefill) instead of restarting the whole session
        self.resume_from = resume_from

    @property
    def retryable(self) -> bool:
        # 5xx: transient node-side trouble (compute crash, dead next hop, no
        # server for a stage yet — adoption may fix it). "session_state":
        # this session's KV is gone/out-of-order on the serving replica
        # (e.g. it died and a fresh one answered) — a new session rebuilds
        # it. Everything else (wrong_stage topology errors, KV overflow,
        # malformed requests, an expired end-to-end deadline) is
        # deterministic for this request: retrying cannot succeed.
        return self.status >= 500 or self.code == "session_state"


# end-to-end deadline of the generation currently running in THIS asyncio
# task (set by generate_ids when the caller passes deadline_s). A
# contextvar — not a client attribute — so concurrent generations on one
# shared client each carry their own budget. Transports read it via
# deadline_wire() when building envelopes; absent a deadline the wire key
# is omitted and envelopes stay byte-identical to the pre-deadline format.
_DEADLINE_MS: "contextvars.ContextVar[Optional[float]]" = contextvars.ContextVar(
    "inferd_deadline_ms", default=None
)


def current_deadline_ms() -> Optional[float]:
    """The active generation's absolute deadline (epoch ms), or None."""
    return _DEADLINE_MS.get()


def deadline_wire() -> Dict[str, float]:
    """{"deadline_ms": ...} for the active deadline, {} when none rides —
    splat into wire envelopes so deadline-less traffic stays byte-exact."""
    d = _DEADLINE_MS.get()
    return {retrylib.DEADLINE_KEY: d} if d is not None else {}


def _deadline_error(detail: str) -> ServerError:
    """The client-side flavor of the node's typed 408: non-retryable by
    construction (status < 500, code != session_state) — once the
    end-to-end budget is gone, another attempt can only waste work."""
    return ServerError(f"deadline exceeded: {detail}", 408, code="deadline")


def sample_np(
    logits: np.ndarray,  # [V] float32
    rng: np.random.Generator,
    temperature: float = 0.6,
    top_k: int = 20,
    top_p: float = 0.95,
    min_p: float = 0.0,
) -> int:
    """numpy mirror of inferd_tpu.core.sampling (same filter semantics —
    the reference's warper chain, client.py:95-120, plus min-p)."""
    logits = np.asarray(logits, dtype=np.float64)
    if temperature == 0.0:
        return int(np.argmax(logits))
    logits = logits / temperature
    if 0 < top_k < logits.shape[-1]:
        kth = np.partition(logits, -top_k)[-top_k]
        logits = np.where(logits < kth, -np.inf, logits)
    if top_p < 1.0:
        order = np.argsort(logits)[::-1]
        probs = _softmax(logits[order])
        cum = np.cumsum(probs)
        keep = (cum - probs) < top_p
        keep[0] = True
        drop = order[~keep]
        logits[drop] = -np.inf
    if min_p >= 1.0:
        raise ValueError(f"min_p must be in [0, 1), got {min_p}")
    if min_p > 0.0:
        logits = np.where(logits < np.max(logits) + np.log(min_p), -np.inf, logits)
    probs = _softmax(logits)
    return int(rng.choice(logits.shape[-1], p=probs))


def logprob_np(logits: np.ndarray, tok: int) -> float:
    """Model log-probability of `tok` under the UNWARPED logits (the
    standard serving-API meaning: what the model assigned, not what the
    sampler drew from). float64 log-softmax for stability."""
    l = np.asarray(logits, dtype=np.float64)
    l = l - np.max(l)
    return float(l[tok] - np.log(np.sum(np.exp(l))))


def top_logprobs_np(logits: np.ndarray, n: int):
    """Top-n (ids, logprobs) alternatives under the UNWARPED logits,
    descending — the serving-API top_logprobs surface. The client computes
    this locally from the logits it already receives every step."""
    l = np.asarray(logits, dtype=np.float64)
    l = l - np.max(l)
    lps = l - np.log(np.sum(np.exp(l)))
    idx = np.argsort(-lps, kind="stable")[:n]
    return idx.astype(int).tolist(), lps[idx].tolist()


async def _emit(cb, token) -> None:
    """Invoke a sync-or-async on_token callback."""
    r = cb(token)
    if asyncio.iscoroutine(r):
        await r


def _softmax(x: np.ndarray) -> np.ndarray:
    m = np.max(x[np.isfinite(x)]) if np.any(np.isfinite(x)) else 0.0
    e = np.exp(np.clip(x - m, -700, 0))
    s = e.sum()
    return e / s


def unpack_reply(where: str, status: int, raw: bytes, headers) -> Dict[str, Any]:
    """A node's reply bytes -> the reply dict, or the ServerError its
    status/code/retry_after/resume_from describe. The ONE reply contract
    for every transport: the HTTP clients hand it what they read off the
    socket, the node's in-process generation loop what a relay brought
    back."""
    try:
        data = wire.unpack(raw)
    except Exception:
        snippet = raw[:200].decode("utf-8", "replace")
        # ValueError: transport-level garbage (error page, truncated
        # stream) — callers with multiple endpoints treat it as
        # "this endpoint is bad" and fail over
        raise ValueError(f"{where} returned non-wire body (HTTP {status}): {snippet!r}")
    if status == 200:
        return data
    detail = data.get("error", data) if isinstance(data, dict) else data
    code = data.get("code") if isinstance(data, dict) else None
    ra = data.get("retry_after") if isinstance(data, dict) else None
    if ra is None:
        # busy 503s also carry the standard header — parse it
        # so a plain-HTTP shed (no wire body) still paces us
        ra = headers.get("Retry-After")
    try:
        ra = None if ra is None else float(ra)
    except (TypeError, ValueError):
        ra = None
    rf = data.get("resume_from") if isinstance(data, dict) else None
    try:
        rf = None if rf is None else int(rf)
    except (TypeError, ValueError):
        rf = None
    raise ServerError(
        f"{where} error {status}: {detail}", status, code,
        retry_after=ra, resume_from=rf,
    )


class GenerationClient:
    """Base: the sampling/EOS/session loop over an abstract transport.

    Subclasses implement `_step` (one pipeline pass: token chunk in,
    last-token logits out) and `_end_session` (drop server-side KV).
    """

    def __init__(
        self,
        sampling: Optional[SamplingConfig] = None,
        tokenizer: Optional[Tokenizer] = None,
        timeout_s: float = 300.0,
        prefill_chunk: int = 512,
        adapter: Optional[str] = None,
    ):
        self.sampling = sampling or SamplingConfig()
        self.tokenizer = tokenizer
        self.timeout_s = timeout_s
        # multi-tenant LoRA: this client's sessions decode with the named
        # adapter (the per-session `adapter` envelope key, stamped on the
        # first chunk — admission maps it to a registry slot server-side;
        # None = the base model, envelopes byte-identical to pre-adapter)
        self.adapter = adapter
        # long prompts prefill in sequential chunks of this many tokens:
        # bounds the per-hop wire message and keeps every node compiling the
        # same bucketed shapes instead of one giant prompt-sized program
        # (the reference ships the full prompt in one request,
        # send_message.py:27-49 / client.py:217-236)
        self.prefill_chunk = max(1, prefill_chunk)
        self._http: Optional[ClientSession] = None
        # pinned prefixes: (prompt-prefix ids) -> (session_id, last logits).
        # The pinned session stays alive server-side (its per-stage KV is the
        # distributed prefix cache); generations whose prompt starts with a
        # pinned prefix FORK it instead of re-prefilling those tokens.
        # LRU-capped: each pin holds a [V] logits array here and a pinned
        # KV session per stage server-side — unbounded pins on a long-lived
        # client (e.g. the node's /generate loop) would grow RSS and
        # crowd the servers' session stores.
        self._pins: "OrderedDict[tuple, tuple]" = OrderedDict()
        self.max_pins = 8
        self._pin_lock = asyncio.Lock()
        # per-client span ring (obs.trace): every generation records a
        # `generate` root span with per-step wire spans and per-token
        # sample spans under it; the trace context rides the /forward
        # envelope and the X-Inferd-Trace header so node-side spans merge
        # into the same end-to-end timeline. A co-located serving layer
        # (the node's /generate loop, client.local_client) swaps in its own recorder so
        # all of a node's spans land in one JSONL file.
        self.tracer = tracelib.SpanRecorder(service="client")
        # the served model's block length: 1 generates a token a step, > 1
        # by blocks (_generate_blocks). None = not yet asked of the node
        self._block: Optional[int] = None

    async def __aenter__(self):
        self._http = ClientSession(timeout=ClientTimeout(total=self.timeout_s))
        return self

    async def __aexit__(self, *exc) -> None:
        for ids in list(self._pins):
            sid, _ = self._pins.pop(ids)
            try:
                await self._end_session(sid)
            except Exception:
                pass  # best effort: nodes TTL-sweep orphaned sessions
        if self._http:
            await self._http.close()

    # -- transport interface (subclass responsibility) ----------------------

    async def _step(
        self, session_id: str, tokens: List[int], start_pos: int
    ) -> np.ndarray:
        """One pipeline pass; returns last-token logits [V]."""
        raise NotImplementedError

    async def _forward(
        self, session_id: str, tokens: List[int], start_pos: int, **extra
    ) -> Dict[str, Any]:
        """One pipeline pass with further payload keys (`block`,
        `want_logits`); returns the last stage's result as it is."""
        raise NotImplementedError

    async def _asking_step(
        self, session_id: str, tokens: List[int], start_pos: int,
        ask: Dict[str, Any],
    ) -> Dict[str, Any]:
        """One DECODE pass that carries a sampling ask (`_decode_ask`);
        returns the last stage's result as it is: "tokens" / "key" (/
        "logprobs", "top_ids", "top_lps") from an executor that chose the
        token, "logits" [1, V] from one that did not. A transport that
        carries no ask (this default) is answered with logits."""
        return {"logits": (await self._step(session_id, tokens, start_pos))[None]}

    async def _block_length(self) -> int:
        """The served model's block length as its node reports it (asked
        once); a transport that cannot ask serves token by token."""
        return self._block or 1

    async def _end_session(self, session_id: str) -> None:
        raise NotImplementedError

    async def _fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        """Seed a new session from a parent's KV prefix on every stage.
        Default: unsupported (callers fall back to a full prefill)."""
        return False

    async def _traced_step(
        self, session_id: str, tokens: List[int], start_pos: int,
        ask: Optional[Dict[str, Any]] = None, first: bool = False,
    ):
        """One pipeline pass wrapped in a `wire`-phase span: the envelope
        the subclass transport builds inside parents to this span (the
        contextvar carries it), so node-side spans nest under the step.
        Returns last-token logits [V]; with an `ask` (a decode hop), the
        reply dict of `_asking_step`. A prefill chunk's span, and that of
        the session's `first` decode hop (`first: 1`), is kept with all
        beneath it (obs.trace: once a request); a later hop's is sampled."""
        with self._step_span(start_pos, len(tokens), ask is None, first):
            if ask is not None:
                return await self._asking_step(session_id, tokens, start_pos, ask)
            return await self._step(session_id, tokens, start_pos)

    def _step_span(self, start_pos: int, n: int, prefill: bool, first: bool):
        """The `step` span of one hop of the generation loop (see
        `_traced_step` for which are kept)."""
        attrs = {"start_pos": start_pos, "n": n}
        if first:
            attrs["first"] = 1
        return self.tracer.span("step", "wire", attrs=attrs, keep=prefill or first)

    def _sample_traced(
        self, logits: np.ndarray, rng, s: SamplingConfig, keep: bool = False,
    ) -> int:
        """Client-side sampling with a `sample`-phase span (sub-ms, but it
        closes the per-token timeline: step + sample account for the whole
        decode iteration). `sample` and `emit` are children of `generate`,
        which is kept: they say themselves whether they are (the first
        token's pair, once a request)."""
        t0 = tracelib.now()
        tok = sample_np(logits, rng, s.temperature, s.top_k, s.top_p, s.min_p)
        self.tracer.record_span(
            "sample", "sample", t0, tracelib.now(), parent=tracelib.current(), keep=keep,
        )
        return tok

    @staticmethod
    def _decode_ask(s: SamplingConfig, want_lp: bool, top_n: int) -> Dict[str, Any]:
        """What every decode hop and block hop asks of an executor that can
        choose tokens on the device (the keys runtime/executor.parse_ask
        reads; the PRNG chain's "seed" / "key" ride beside it)."""
        return {
            "sampling": {"temperature": s.temperature, "top_k": s.top_k,
                         "top_p": s.top_p, "min_p": s.min_p},
            "logprobs": want_lp,
            "top_logprobs": top_n,
        }

    def _record_emit(self, t0: float, tokens: int, keep: bool = False) -> None:
        """The `emit`-phase span of what the caller's `on_token` took for
        one hop's tokens (a token; a block's): with `sample` it fills the
        stretch between two `step`s, and a callback that yields to the
        event loop (a stream's write) shows here."""
        self.tracer.record_span(
            "emit", "emit", t0, tracelib.now(), parent=tracelib.current(),
            attrs={"tokens": tokens}, keep=keep,
        )

    async def _emit_traced(self, on_token, tok: int, keep: bool = False) -> None:
        t0 = tracelib.now()
        await _emit(on_token, tok)
        self._record_emit(t0, 1, keep)

    def _record_open(self, t_open: float) -> None:
        """The `open`-phase span: the loop's `generate` opened (`t_open`) ->
        NOW, where the first chunk's `step` begins."""
        self.tracer.record_span(
            "open", "open", t_open, tracelib.now(), parent=tracelib.current()
        )

    async def _close_session(self, session_id: str) -> None:
        """The loop gives its session back, under a `close`-phase span."""
        with tracelib.region(self.tracer, "close"):
            try:
                await self._end_session(session_id)
            except Exception:
                pass  # best effort: nodes TTL-sweep orphaned sessions

    # -- shared helpers ------------------------------------------------------

    def _hop_timeout_s(self, where: str) -> float:
        """One hop's time limit: the static client timeout, or what is
        left of the active end-to-end deadline where that is less (plus a
        beat for the node's own typed 408 to make it back). A spent
        budget fails HERE instead of shipping a request every hop would
        only fast-fail anyway."""
        rem = retrylib.remaining_s(_DEADLINE_MS.get())
        if rem is None:
            return self.timeout_s
        if rem <= 0:
            raise _deadline_error(f"before POST {where}")
        return min(self.timeout_s, rem + 0.25)

    async def _post_url(self, url: str, body: Dict[str, Any]) -> Dict[str, Any]:
        """POST a wire envelope; unpack defensively (a plain-HTTP error page
        or truncated body must surface the status, not a msgpack error).
        The active trace context (if any) rides as the X-Inferd-Trace
        header — the propagation surface for endpoints whose envelope has
        no `trace` key (/generate)."""
        assert self._http is not None, "use `async with <client>(...)`"
        async with self._http.post(
            url, data=wire.pack(body), headers=tracelib.header_ctx(),
            timeout=ClientTimeout(total=self._hop_timeout_s(url)),
        ) as r:
            return unpack_reply(url, r.status, await r.read(), r.headers)

    # -- public API ----------------------------------------------------------

    def pinned_parent(self, prefix_ids: Sequence[int]):
        """(parent_session_id, last-token logits) of a held pin, or None —
        lets a co-located serving layer (the node's speculative path) fork
        the pinned session directly instead of re-prefilling the prefix."""
        return self._pins.get(prefixlib.normalize_ids(prefix_ids))

    async def pin_prefix(self, prefix_ids: Sequence[int]) -> None:
        """Prefill `prefix_ids` under a dedicated long-lived session whose
        per-stage KV becomes a shared prefix cache: subsequent generations
        with a prompt starting in these ids fork it server-side instead of
        re-prefilling the prefix (the shared-system-prompt serving win).
        Pinned sessions are dropped on client exit."""
        ids = prefixlib.normalize_ids(prefix_ids)
        if await self._block_length() > 1:
            raise ValueError("a model generated by blocks serves no pinned prefix")
        if ids in self._pins:
            self._pins.move_to_end(ids)
            return
        # single-flight: a burst of concurrent pins of the same prefix must
        # run ONE prefill, not N redundant ones with N-1 discarded sessions
        async with self._pin_lock:
            if ids in self._pins:
                self._pins.move_to_end(ids)
                return
            sid = str(uuid.uuid4())
            pos = 0
            logits: Optional[np.ndarray] = None
            for i in range(0, len(ids), self.prefill_chunk):
                chunk = list(ids[i : i + self.prefill_chunk])
                logits = await self._traced_step(sid, chunk, pos)
                pos += len(chunk)
            assert logits is not None
            # a copy of the pin's own row: what a transport hands back may
            # be a view into a whole co-batch's [L, V] array, and a pin
            # outlives the step that made it
            self._pins[ids] = (sid, np.array(logits))
            while len(self._pins) > self.max_pins:
                _, (old_sid, _l) = self._pins.popitem(last=False)
                try:
                    await self._end_session(old_sid)
                except Exception:
                    pass  # best effort: servers TTL-sweep orphans

    def _longest_pin(self, prompt_ids: List[int]):
        return prefixlib.longest_prefix_match(self._pins, prompt_ids)

    async def generate_ids(
        self,
        prompt_ids: Sequence[int],
        max_new_tokens: int = 64,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        session_retries: int = 2,
        retry_delay_s: float = 1.0,
        sampling: Optional[SamplingConfig] = None,
        on_token=None,
        logprob_sink: Optional[List[float]] = None,
        top_n: int = 0,
        top_sink: Optional[List] = None,
        deadline_s: Optional[float] = None,
        retry_cap_s: float = 8.0,
        retry_rng: Optional[random.Random] = None,
        retry_budget: Optional[retrylib.RetryBudget] = None,
    ) -> List[int]:
        """Prefill + token-by-token decode; returns the new ids.

        `logprob_sink` (optional list) collects each emitted token's model
        log-probability (log-softmax of the raw logits), in step with the
        returned ids; cleared at the start of every attempt so restarts
        stay consistent. `top_sink` with `top_n > 0` likewise collects the
        top-N (ids, logprobs) alternatives per step: float32 on the device
        where the hop was answered with its token, else computed here
        from the logits.

        A mid-generation failure (a node died — its KV cache with it)
        restarts the WHOLE generation under a fresh session, up to
        `session_retries` times: the swarm needs a beat to detect the death
        (record TTL) and adopt the orphaned stage, after which the full
        prompt re-prefills on the adopting replica. Deterministic given the
        same seed, so a restart yields the same tokens (the first from
        numpy's generator under `seed`, the rest, where the executor
        chooses them, under the jax key chain `seed` roots).

        `on_token` (optional async or sync callable) is invoked with each
        new token id as it is sampled — the streaming hook. On a retried
        attempt it is called with None first (restart marker: previously
        streamed tokens are void, the deterministic re-run re-streams).

        Overload containment (docs/SERVING.md "Overload & reliability"):
        `deadline_s` stamps an absolute `deadline_ms` into every wire
        envelope — hops fast-fail with the typed non-retryable `deadline`
        error once the end-to-end budget is spent, and this loop stops
        retrying then too. Retry pacing is capped exponential backoff
        with FULL jitter (base `retry_delay_s`, cap `retry_cap_s`;
        `retry_rng` seeds it for deterministic tests), raised to a busy
        node's `Retry-After` hint when one rides the 503. Every retry
        spends a token from `retry_budget` (default: the per-process
        bucket shared across sessions) — when the bucket is dry the
        ORIGINAL error surfaces instead of amplifying a storm."""
        if not prompt_ids:
            raise ValueError("prompt_ids must be non-empty")
        budget = retry_budget or retrylib.DEFAULT_RETRY_BUDGET
        rng = retry_rng  # None -> module-level random (decorrelated)
        dl_token = None
        if deadline_s is not None:
            dl_token = _DEADLINE_MS.set(
                retrylib.deadline_ms_from_now(deadline_s)
            )
        # root span of the end-to-end timeline: one trace per generation,
        # retries included (restart attempts show up as extra step spans)
        try:
            # `open` begins; and where a server's handler left the stamp of
            # the request's arrival (runtime/node.py _accepted), `accept` ends
            t_open, t_in = tracelib.now(), tracelib.marked("t_arrived")
            if t_in is not None:
                self.tracer.record_span(
                    "accept", "accept", t_in, t_open, parent=tracelib.current(),
                    attrs={"prompt": len(prompt_ids), "stream": int(on_token is not None)},
                )
            with self.tracer.span(
                "generate", "client",
                attrs={"prompt": len(prompt_ids), "max_new": max_new_tokens},
                keep=True,
            ):
                last_err: Optional[Exception] = None
                for attempt in range(1 + session_retries):
                    if attempt:
                        t_open = None  # a restart's `open` begins with its attempt
                        assert last_err is not None
                        if not budget.try_acquire():
                            # retry budget dry: bounded retry rate beats a
                            # storm — surface the ORIGINAL failure
                            raise last_err
                        delay = retrylib.backoff_delay(
                            attempt, retry_delay_s, retry_cap_s, rng
                        )
                        ra = getattr(last_err, "retry_after", None)
                        if ra is not None:
                            # a shedding node said when to come back:
                            # honor it (jitter still rides on top)
                            delay = max(delay, float(ra))
                        rem = retrylib.remaining_s(_DEADLINE_MS.get())
                        if rem is not None and rem <= delay:
                            # the budget can't survive the wait: stop now
                            raise _deadline_error(
                                "retry pacing exceeds the remaining budget"
                            ) from last_err
                        await asyncio.sleep(delay)
                        if on_token is not None:
                            await _emit(on_token, None)
                    try:
                        return await self._generate_once(
                            list(prompt_ids), max_new_tokens, eos_token_id, seed,
                            sampling or self.sampling, on_token, logprob_sink,
                            top_n, top_sink, t_open,
                        )
                    except ServerError as e:
                        if not e.retryable:
                            raise  # deterministic failure: retrying cannot succeed
                        last_err = e
                    except (
                        ConnectionError, OSError, asyncio.TimeoutError, aiohttp.ClientError
                    ) as e:
                        # transport-level death (includes ServerDisconnectedError /
                        # ClientPayloadError, which are ClientError but NOT OSError —
                        # the chain client posts raw, without SwarmClient's
                        # ConnectionError wrapping)
                        last_err = e
                assert last_err is not None
                raise last_err
        finally:
            if dl_token is not None:
                _DEADLINE_MS.reset(dl_token)

    async def _step_resuming(
        self, session_id: str, toks: List[int], pos: int,
        known: List[int], resumes: List[int],
        ask: Optional[Dict[str, Any]] = None, first: bool = False,
    ):
        """_traced_step with standby-promotion resume: a session_state
        409 carrying `resume_from` F means the answering replica holds
        the session's REPLICATED KV up to F (async standby replication,
        runtime/repl) — re-send only known[F:pos], the tokens past the
        replication frontier, and retry the step. The session id and
        every already-emitted token survive: this is a bounded tail
        re-prefill, not a restart. `known` is the absolute token stream
        (prompt + generated so far), `resumes` a one-element mutable
        budget shared across the generation so a flapping fleet can't
        loop us; exhausted/ineligible errors propagate into the ordinary
        full-restart retry loop — exactly the pre-replication behavior.
        A decode hop's `ask` rides the step and its retry unchanged (the
        same key: the retried hop draws the same token); the replayed
        chunks between are prefill and carry none."""
        try:
            return await self._traced_step(session_id, toks, pos, ask, first)
        except ServerError as e:
            f = e.resume_from
            if f is None or not 0 <= int(f) < pos or resumes[0] <= 0:
                raise
            resumes[0] -= 1
            p = int(f)
            replay = known[p:pos]
            for i in range(0, len(replay), self.prefill_chunk):
                chunk = replay[i : i + self.prefill_chunk]
                # replay chunks resume too (budget-bounded recursion): a
                # multi-stage pipeline may hold a LOWER frontier on
                # another stage's standby, and its offer surfaces on the
                # REPLAY chunk that first reaches that stage — each offer
                # walks the resume point back until every stage can serve
                await self._step_resuming(
                    session_id, chunk, p, known, resumes
                )
                p += len(chunk)
            return await self._step_resuming(
                session_id, toks, pos, known, resumes, ask, first
            )

    async def _generate_once(
        self,
        prompt_ids: List[int],
        max_new_tokens: int,
        eos_token_id: Optional[int],
        seed: int,
        sampling: Optional[SamplingConfig] = None,
        on_token=None,
        logprob_sink: Optional[List[float]] = None,
        top_n: int = 0,
        top_sink: Optional[List] = None,
        t_open: Optional[float] = None,
    ) -> List[int]:
        t_open = t_open or tracelib.now()
        blk = await self._block_length()
        if blk > 1:
            return await self._generate_blocks(
                blk, prompt_ids, max_new_tokens, eos_token_id, seed,
                sampling or self.sampling, on_token, logprob_sink, top_n, top_sink,
                t_open,
            )
        session_id = str(uuid.uuid4())
        rng = np.random.default_rng(seed)
        s = sampling or self.sampling
        out: List[int] = []
        # absolute token stream + resume budget for _step_resuming (the
        # standby-promotion partial-restart path)
        known: List[int] = list(prompt_ids)
        resumes = [4]
        if logprob_sink is not None:
            logprob_sink.clear()  # deterministic restarts re-fill
        if top_sink is not None:
            top_sink.clear()
        try:
            pos = 0
            logits: Optional[np.ndarray] = None
            pin = self._longest_pin(prompt_ids)
            if pin is not None:
                parent_sid, pin_logits = self._pins[pin]
                self._pins.move_to_end(pin)  # LRU: reuse refreshes the pin
                forked = transient = False
                try:
                    forked = await self._fork_session(
                        session_id, parent_sid, len(pin)
                    )
                except Exception:
                    # transport-level trouble: the parent may be perfectly
                    # alive — keep the pin for the next generation
                    transient = True
                if forked:
                    pos = len(pin)
                    logits = pin_logits  # used as-is when the prompt IS the pin
                else:
                    if not transient:
                        # clean miss (ok=False): the parent is truly gone
                        # (evicted / node died / executor without forking) —
                        # a stale pin would miss on every future call too
                        self._pins.pop(pin, None)
                    # clean any partially-forked stages, then fall back to
                    # the full prefill below
                    try:
                        await self._end_session(session_id)
                    except Exception:
                        pass
            self._record_open(t_open)
            for i in range(pos, len(prompt_ids), self.prefill_chunk):
                chunk = prompt_ids[i : i + self.prefill_chunk]
                logits = await self._step_resuming(
                    session_id, chunk, pos, known, resumes
                )
                pos += len(chunk)
            assert logits is not None
            # the first token's `sample` and `emit` are once a request: kept
            tok = self._sample_traced(logits, rng, s, keep=True)
            out.append(tok)
            known.append(tok)
            if logprob_sink is not None:
                logprob_sink.append(logprob_np(logits, tok))
            if top_sink is not None:
                top_sink.append(top_logprobs_np(logits, top_n))
            if on_token is not None:
                await self._emit_traced(on_token, tok, keep=True)
            # every decode hop asks for its token: an executor that samples
            # on the device answers with it (and the session's next key:
            # `seed` roots the chain on the first such hop), any other with
            # logits, sampled here as the first token was. Which case
            # applies is read off the reply.
            ask = self._decode_ask(
                s, logprob_sink is not None, top_n if top_sink is not None else 0
            )
            if eos_token_id is not None:
                ask["eos"] = int(eos_token_id)
            chain: Dict[str, Any] = {"seed": seed}
            while len(out) < max_new_tokens and tok != eos_token_id:
                # `ahead`: the hops that follow this one unless `eos` ends
                # the generation: an executor that keeps a step ahead of its
                # sessions runs that many more rows for this one, and none
                # past them (runtime/executor.parse_decode_ask)
                res = await self._step_resuming(
                    session_id, [tok], pos, known, resumes,
                    {**ask, **chain, "ahead": max_new_tokens - len(out) - 1},
                    first=len(out) == 1,
                )
                pos += 1
                if res.get("logits") is not None:
                    logits = np.asarray(res["logits"])[0]
                    tok = self._sample_traced(logits, rng, s)
                    lp = logprob_np(logits, tok) if logprob_sink is not None else None
                    top = top_logprobs_np(logits, top_n) if top_sink is not None else None
                else:
                    t0 = tracelib.now()
                    tok, chain = int(res["tokens"][0][0]), {"key": res["key"]}
                    lp = float(res["logprobs"][0]) if logprob_sink is not None else None
                    top = (
                        [int(i) for i in res["top_ids"][0][:top_n]],
                        [float(x) for x in res["top_lps"][0][:top_n]],
                    ) if top_sink is not None else None
                    # the token was chosen inside the step: the span only
                    # closes the per-token timeline (obs.merge counts them)
                    self.tracer.record_span(
                        "sample", "sample", t0, tracelib.now(),
                        parent=tracelib.current(), attrs={"on": "device"}, keep=False,
                    )
                out.append(tok)
                known.append(tok)
                if logprob_sink is not None:
                    logprob_sink.append(lp)
                if top_sink is not None:
                    top_sink.append(top)
                if on_token is not None:
                    await self._emit_traced(on_token, tok)
        finally:
            await self._close_session(session_id)
        return out

    async def _generate_blocks(
        self, blk: int, prompt_ids: List[int], max_new_tokens: int,
        eos_token_id: Optional[int], seed: int, s: SamplingConfig,
        on_token, logprob_sink, top_n: int, top_sink, t_open: float,
    ) -> List[int]:
        """The loop for a model generated by blocks of `blk`: the prompt's
        whole blocks are ingested in chunks (whose logits nobody reads: none
        is asked for), the tokens left over open the first block, and every
        further hop carries one block and is answered with its tokens,
        chosen on the device under `s` and the session's key chain, with
        the log-probabilities of the pass that made each known. Tokens are
        emitted in order; the loop stops at `max_new_tokens` (the last
        block's surplus is dropped) and at `eos_token_id` inside a block
        (what follows it is dropped)."""
        session_id = str(uuid.uuid4())
        out: List[int] = []
        for sink in (logprob_sink, top_sink):
            if sink is not None:
                sink.clear()  # deterministic restarts re-fill
        want = self._decode_ask(
            s, logprob_sink is not None, top_n if top_sink is not None else 0
        )
        if eos_token_id is not None:
            want["eos"] = int(eos_token_id)
        try:
            whole = len(prompt_ids) // blk * blk
            chunk = max(blk, self.prefill_chunk // blk * blk)
            self._record_open(t_open)
            for pos in range(0, whole, chunk):
                toks = prompt_ids[pos : min(pos + chunk, whole)]
                with self._step_span(pos, len(toks), True, False):
                    await self._forward(session_id, toks, pos, want_logits=False)
            pos, head, key = whole, prompt_ids[whole:], None
            while len(out) < max_new_tokens and (not out or out[-1] != eos_token_id):
                # `ahead`: the block hops that follow this one unless `eos`
                # ends the generation, as a decode hop's ask counts tokens
                # (runtime/executor.parse_block)
                left = max_new_tokens - len(out) - (blk - len(head))
                call = dict(want, known=len(head), ahead=max(0, -(-left // blk)),
                            **({"seed": seed} if key is None else {"key": key}))
                with self._step_span(pos, blk, False, key is None):
                    res = await self._forward(
                        session_id, head + [0] * (blk - len(head)), pos, block=call
                    )
                t_emit, had = tracelib.now(), len(out)
                for j in range(len(head), blk):
                    tok = int(res["tokens"][0][j])
                    out.append(tok)
                    if logprob_sink is not None:
                        logprob_sink.append(float(res["logprobs"][j]))
                    if top_sink is not None:
                        top_sink.append((
                            [int(i) for i in res["top_ids"][j][:top_n]],
                            [float(x) for x in res["top_lps"][j][:top_n]],
                        ))
                    if on_token is not None:
                        await _emit(on_token, tok)
                    if len(out) >= max_new_tokens or tok == eos_token_id:
                        break
                if on_token is not None:
                    self._record_emit(t_emit, len(out) - had)  # one a block
                pos, head, key = pos + blk, [], res["key"]
        finally:
            await self._close_session(session_id)
        return out

    async def generate(
        self, prompt: str, max_new_tokens: int = 64, seed: int = 0, chat: bool = True
    ) -> str:
        """Text in, text out (chat template when the tokenizer has one)."""
        tok = self.tokenizer or Tokenizer()
        if chat:
            ids = tok.apply_chat_template(
                [{"role": "user", "content": prompt}], add_generation_prompt=True
            )
        else:
            ids = tok.encode(prompt)
        new_ids = await self.generate_ids(
            ids, max_new_tokens, eos_token_id=tok.eos_token_id, seed=seed
        )
        return tok.decode(new_ids)
