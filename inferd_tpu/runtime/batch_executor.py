"""Continuous-batching stage executor: concurrent sessions' decode steps
coalesce into ONE device step.

The reference serves strictly one request at a time per node (a lone
pipeline pass per token, /root/reference/petals/send_message.py:27-49 /
server.py:25-54); every session re-reads all the weights per token. This
executor keeps the node's `/forward` contract (a hop that asks for no
token is answered with logits, to be sampled by its caller) but
maps sessions to lanes of core.batch.BatchedEngine and batches the
single-token decode steps of whichever sessions arrive within a short
window — aggregate tok/s then scales with concurrency instead of dividing
by it (weights are read once per BATCHED step).

Concurrency design (process() runs on the node's worker thread pool):
  * decode steps (real_len == 1 at the session's frontier) enqueue into a
    pending batch; the FIRST arrival becomes the flusher — it waits out a
    drain that is still running, then (no lock held) for the lanes the
    last two steps served, takes the device lock, runs one batched step
    for every lane pending AT THAT MOMENT, and hands each waiting thread
    what its hop asked for: the token the step chose under the hop's own
    sampling (a decode hop's ask, runtime/executor.parse_decode_ask: what
    the generation loop sends with every decode hop), or the lane's logits
    (runtime/window.py, formation; docs/SERVING.md "A decode hop's ask");
  * dense lanes run ONE STEP AHEAD of the hops they answer
    (`_decode_ahead`, docs/SERVING.md "One step ahead"): a hop whose ask
    says more hops follow is answered from the row a step ran for it
    before it arrived, and the drain that answers it first dispatches the
    row of the hop after it, fed by the token and key the step before
    left on the device: the sessions take their turn beside a step, not
    between two;
  * prefill chunks (multi-token or unknown session) run solo under the
    same device lock (per-lane cache writes, other lanes untouched);
  * a model generated by blocks (cfg.is_block_diffusion) has a third kind
    of call instead of the decode step: `block`, a whole block at a
    frontier that is a multiple of the block length. Block calls of
    different lanes ride ONE BatchedEngine._block_step through the same
    window as decode steps do, one step ahead as they are (a block hop's
    `block` call promises the hops that follow it as a decode hop's ask
    does); the frontier advances by a block a call;
  * whole-model executor: is_first and is_last (tokens in, a token or
    last-token logits out) — like MeshExecutor it hosts a 1-stage swarm topology.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Optional

import numpy as np

from inferd_tpu.config import ModelConfig
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import RING_MARGIN, from_wire, sync_paged, wire_heads
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core import sampling as samplib
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.models import qwen3
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.obs.devtel import program_name
from inferd_tpu.obs.events import emit_safely
from inferd_tpu.runtime.adapters import AdapterBindingMixin
from inferd_tpu.runtime.executor import (
    BlockCall, cache_intact, call_kind, parse_block, parse_decode_ask, parse_kstep,
)
from inferd_tpu.runtime.spec_serving import SpecForkMiss, SpecServing
from inferd_tpu.runtime.step_ahead import Admissions, StepAhead, _Ahead, _Step
from inferd_tpu.runtime.window import WindowedBatcher
from inferd_tpu.utils import lockwatch

Params = Any


class CapacityError(RuntimeError):
    """All lanes are serving in-flight requests — transient backpressure
    (the node maps this to a retryable 503, unlike deterministic KV
    overflow which is a 409)."""


class _BlockStep(_Step):
    """One dispatched BatchedEngine._block_step (a model generated by
    blocks): a `_Step` whose rows are whole blocks, `asks` {lane: BlockCall}
    for every lane it ran. `toks[lane]` are the tokens it made known (not
    those the caller filled)."""

    __slots__ = ("chosen", "made", "passes", "block")

    def __init__(self, packed, chosen, top_n, asks, prev, program, made, passes, block):
        super().__init__(packed, None, top_n, asks, [], list(asks), prev, program)
        self.chosen = chosen  # the experts every row chose in every pass | None
        self.made, self.passes, self.block = made, passes, block

    def span(self) -> Dict[str, Any]:
        live = len(self.lanes)
        return {"kind": "block", "tokens": self.made, "cobatch": live,
                "rows": live * self.block * self.passes, "passes": self.passes,
                "program": self.program}

    def fed(self, lane: int, tok, ask, ran) -> bool:
        # a block of which the caller knows nothing: its tokens are not read
        return (
            ask.known == 0 and ask.sampling == ran.sampling and ask.top_n == ran.top_n
            and self.keys[lane] == ask.key.tolist()
        )

    def ends(self, lane: int, eos: int) -> bool:
        return eos in self.toks[lane]

    def out(self, host: np.ndarray, ex: "BatchedExecutor") -> None:
        blk, top_n = self.block, self.top_n
        x, order, self.keys, lps, tis, tls = (a.tolist() for a in samplib.unpack_bits(
            host, (np.int32, (blk,)), (np.int32, (blk,)), (np.uint32, (2,)),
            (np.float32, (blk,)), (np.int32, (blk, top_n)), (np.float32, (blk, top_n)),
        ))
        self.toks = {lane: [t for t, at in zip(x[lane], order[lane]) if at >= 0]
                     for lane in self.lanes}
        self.replies = {
            lane: {
                "tokens": [x[lane]],
                "order": order[lane],  # the pass that made each place known (-1: the caller)
                "key": self.keys[lane],
                **({"logprobs": lps[lane], "top_ids": tis[lane], "top_lps": tls[lane]}
                   if self.asks[lane].top_n else {}),
            }
            for lane in self.lanes
        }
        if self.chosen is not None:  # came out with the tokens: no wait
            routed = np.asarray(self.chosen)[:, :, self.lanes]  # [passes, layers, live, block, k]
            self.chosen = None
            for per_pass in routed.reshape(*routed.shape[:2], -1, routed.shape[-1]):
                ex._count_routing(per_pass, ex.engine.lanes * blk)


class _Hop:
    """One admitted call between `_enter` and `_leave`: what the parts of
    `process` hand each other. `entry` is the window's, for a hop no thread
    waits on (`begin_hop`)."""

    __slots__ = ("session_id", "lane", "kind", "toks", "start_pos", "real_len",
                 "ask", "acquired", "payload", "entry")

    def __init__(self, session_id, lane, kind, toks, start_pos, real_len, ask,
                 acquired, payload):
        self.session_id, self.lane, self.kind, self.toks = session_id, lane, kind, toks
        self.start_pos, self.real_len, self.ask = start_pos, real_len, ask
        self.acquired, self.payload = acquired, payload
        self.entry = None


class BatchedExecutor(SpecServing, AdapterBindingMixin, StepAhead):
    """Whole-model, lane-per-session executor with windowed decode batching.

    Node executor contract (runtime/node.py): process(session_id, payload)
    -> {"logits": [1, V], ...} or, for a one-token decode hop that asks
    for its token (runtime/executor.parse_decode_ask), {"tokens": [[id]],
    "key", ...}; end_session(session_id).
    """

    is_first = True
    is_last = True

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        lanes: int = 8,
        max_len: int = 4096,
        window_ms: float = 3.0,
        session_ttl_s: float = 600.0,
        block_size: int = 0,
        kv_blocks: int = 0,
        prefill_chunk: int = 0,
        adapters=None,
    ):
        self.cfg = cfg
        self.engine = BatchedEngine(
            cfg, params, lanes=lanes, max_len=max_len,
            block_size=block_size, kv_blocks=kv_blocks,
        )
        # multi-tenant LoRA registry (runtime/adapters.AdapterRegistry;
        # None = single-model serving, every jit traces exactly as
        # before): sessions admitted with an `adapter` payload key map to
        # registry slots, and every batched dispatch gathers per-lane
        # slot ids into the unmerged apply (ops.lora.lane_delta)
        self.adapters = adapters
        self._session_adapter: Dict[str, str] = {}
        self._lane_slot = [0] * lanes  # slot 0 = the zero base adapter
        # paged KV (block_size > 0, core.cache.BlockPool): per-block
        # allocation/eviction + refcounted shared-prefix blocks with CoW;
        # None = the classic dense lane slab
        self.pool = self.engine.pool
        # server-side chunked prefill: dispatches of at most this many
        # tokens with the device lock RELEASED between them, so decode
        # windows interleave instead of stalling behind a long admission
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_tokens = 0  # tokens actually computed by prefill
        # one-token decode hops answered with a token chosen on the device,
        # and those answered with their [V] logits row (/stats `executor`)
        self.sampled_rows = 0
        self.logit_rows = 0
        # decode and block hops by who carried them (/stats `executor`):
        # answered on their caller's event loop, no worker thread taken
        # (`begin_hop` / `end_hop`), or served on one (`process`; a hop that
        # rode a step and waited for it)
        self.hops_inline = 0
        self.hops_pooled = 0
        # one step ahead (dense lanes, `_decode_ahead`; a row is a token's,
        # or a whole block's for a model generated by blocks): lane -> the
        # row a step ran before its hop arrived, unclaimed; lane -> (the step
        # its waiting hop rode, the hop's ask) where that ask promised a hop
        # to follow: the next drain runs that hop's row ahead of it; the last
        # step dispatched (the only one whose output a drain may find on the
        # device alone); rows run ahead, those that answered a hop, those
        # dropped
        self._ahead: Dict[int, _Ahead] = {}
        self._carry: Dict[int, tuple] = {}
        self._last_step: Optional[_Step] = None
        # the logits of the last prefill chunk dispatched on dense lanes:
        # while they are not ready a chunk is on the device (`_settle`)
        self._chunk = None
        self.ahead_rows = 0
        self.ahead_claimed = 0
        self.ahead_dropped = 0
        # a request's admission (/stats `executor`; the `lane` span)
        self._admit = Admissions()
        # cumulative expert-routing counters over decode steps, or over
        # the passes of block steps (/stats `executor` `moe.*`); None where
        # the program returns no routing (no experts, or a paged / windowed
        # layout)
        self._moe = (
            dict(steps=0, assignments=0, assignments_hottest=0, experts_touched=0,
                 assignments_here=0, experts_touched_here=0, rows_multiplied=0)
            if self.engine.routes else None
        )
        # what the programs read of the dense lanes' slabs (/stats `executor`
        # `kv.slots_read` / `kv.slots_held`, _count_read); None where no
        # program reads a slab by its prefix (a paged pool)
        self._read = dict(slots_read=0, slots_held=0) if self.pool is None else None
        # a model generated by blocks (/stats `executor` `diffusion.*`):
        # dispatches, live lanes x passes, places made known (those the
        # caller filled are not counted), rows computed, block hops answered
        self._diffusion = (
            dict(block_steps=0, lane_passes=0, tokens=0, rows=0, hops=0)
            if cfg.is_block_diffusion else None
        )
        if cfg.is_block_diffusion and (block_size or max_len % cfg.block_length):
            raise ValueError(
                f"{cfg.name}: block generation serves from dense lanes whose "
                f"length is a multiple of the block ({cfg.block_length})"
            )
        self.max_len = max_len
        self.ttl_s = session_ttl_s

        # serializes device steps; INFERD_FAIR_DEVLOCK swaps in the
        # ticketed FIFO mutex (lockwatch.FairDeviceLock), and lockwatch
        # wraps either in an order-checking proxy when instrumented
        self._dev_lock = lockwatch.make_lock(
            "dev", fair=lockwatch.fair_devlock_enabled()
        )
        # ring replay safety: per-lane high-water mark of positions ever
        # written THIS claimant; only diverges from the lane length across
        # replay rollbacks (effective hi = max(mark, length))
        self._lane_hi: Dict[int, int] = {}
        # guards session/lane + pending state
        self._mu = lockwatch.make_lock("mu")
        self._sessions: Dict[str, int] = {}  # session -> lane
        self._last_used: Dict[str, float] = {}
        self._inflight: Dict[str, int] = {}  # session -> active request count
        self._dying: Dict[int, str] = {}  # lane -> ended session awaiting drain
        self._batcher = WindowedBatcher(
            # only the start value of the batcher's own estimate of a
            # session's turn (result out -> next submit); see `expect`
            window_ms / 1e3,
            self._run_decode_batch,
            # a solo session should not pay the window latency
            co_possible=lambda: len(self._sessions) > 1,
            # formation: the batch is drained under _dev_lock, and the
            # flusher waits for the lanes the last two steps served.
            # _drop (end, eviction, sweep) and a prefill take a lane out
            # of that expectation at once.
            swap_in_run=True,
            expect=lambda payload: payload[0],
            kind="block" if cfg.is_block_diffusion else "decode",
        )
        self._spec_window_s = window_ms / 1e3
        # lane-batched speculation (enable_spec): None until enabled
        self._spec: "dict | None" = None
        # flight-recorder hook (the node wires its journal's emit):
        # lane.evict events for the fleet postmortem record
        self.on_event = None
        if self.pool is not None:
            # prefix-index eviction telemetry (same contract as the stage
            # executor): journal the reclaimed entry's age so the memory
            # plane can tell housekeeping from working-set thrash
            self.pool.on_evict = lambda key, age_s: emit_safely(
                self.on_event, "prefix.evict",
                age_ms=round(age_s * 1e3, 1),
                # digest_key: the ONE truncation — journal keys must stay
                # joinable against the gossiped `pfx` digest entries
                key=prefixlib.digest_key(key),
            )

    @property
    def tracer(self):
        """Span recorder (the node wires its own, next to on_event): the
        decode flush stamps device / copy_out and the dense prefill also
        its lock_wait under the call's `compute` span; the batcher stamps
        a decode entry's lock_wait and batch_wait."""
        return self._batcher.tracer

    @tracer.setter
    def tracer(self, recorder) -> None:
        self._batcher.tracer = recorder

    # -- lane-batched speculative serving (core.spec_batch) ------------------
    #
    # A speculating session is an ordinary engine lane: its target KV rows
    # ARE the lane's rows, so spec rounds interleave freely with regular
    # /forward decode batching on the same device. While speculation is
    # enabled, EVERY admission (spec or regular) is capped at
    # max_len - (k+1): the verify chunk writes k+1 rows at every lane's
    # frontier (garbage for non-participants), and a lane closer than that
    # to max_len would be clamp-corrupted (core.spec_batch headroom
    # contract). The node surfaces the reduced capacity as ordinary KV
    # overflow. The session-level drive (runner LRU, round coalescing,
    # deferred frees) is the shared SpecServing mixin; only the
    # lane-storage hooks live here.

    @property
    def _spec_mu(self):
        return self._mu

    def _spec_session_slot(self, session_id):
        return self._sessions.get(session_id)

    def _spec_session_len(self, session_id, lane):
        return self.engine.lengths[lane]

    def _spec_free_slot(self, session_id, lane):
        self.engine.lengths[lane] = 0
        self.engine.free.append(lane)

    def _spec_drop(self, session_id):
        self._drop(session_id)

    def _spec_new_runner(self, sampling):
        from inferd_tpu.core.spec_batch import LaneSpecRunner

        return LaneSpecRunner(
            self.cfg, self._spec["dcfg"], self._spec["k"], sampling=sampling
        )

    def _spec_plain_submit(self, lane, last_tok, session_id):
        return self._batcher.submit((lane, last_tok, None))

    def enable_spec(self, draft_layers: int, k: int) -> None:
        """Self-drafting lane speculation: the model's first `draft_layers`
        layers propose, the full stack verifies (layer-truncated self-draft,
        core.speculative.self_draft — one definition shared with the solo
        engine). Raises ValueError for structurally impossible configs
        (ring margin, layer counts); the caller logs and serves without."""
        from inferd_tpu.core import spec_batch
        from inferd_tpu.core.speculative import self_draft

        if self.pool is not None:
            raise ValueError(
                "lane speculation is not supported with paged KV yet "
                "(the verify chunk writes k+1 rows at every lane's "
                "frontier — a block-table write path for it is future "
                "work); serve --paged-kv without --spec-draft-layers"
            )
        if self.adapters is not None:
            raise ValueError(
                "lane speculation is not supported with the adapter "
                "registry yet (the layer-truncated self-draft would "
                "draft with the BASE model while the target verifies "
                "per-tenant weights — acceptance would collapse); serve "
                "--adapters without --spec-draft-layers"
            )
        if not 0 < draft_layers < self.cfg.num_layers:
            raise ValueError(
                f"draft_layers must be in (0, {self.cfg.num_layers})"
            )
        dcfg, dparams = self_draft(self.cfg, self.engine.params, draft_layers)
        spec_batch.check_ring_margin(self.cfg, dcfg, k)
        self._spec = {
            **self._spec_init(k, self.engine.lanes),
            "dcfg": dcfg,
            "dparams": dparams,
            "dcache": spec_batch.make_draft_cache(
                dcfg, self.engine.lanes, self.max_len
            ),
        }

    def spec_open(
        self, session_id: str, prompt_ids, sampling, seed: int = 0,
        parent: "str | None" = None, pin_len: int = 0,
        prefix_logits=None, want_lp: bool = False,
    ):
        """Claim a lane, prefill target + draft caches, return the first
        emitted token. The session stays marked in-flight until
        spec_close() — between rounds an idle lane must not be LRU-evicted
        by a concurrent admission. Raises CapacityError (no lane) or
        BufferError (prompt exceeds the spec-capped budget).

        `parent` + `pin_len` compose speculation with PREFIX CACHING: the
        lane forks the parent session's first pin_len KV slots (the same
        fork the regular loop uses), the target prefills only the suffix,
        and the DRAFT prefills the whole prompt (its layer-truncated cache
        has no pinned copy — a fraction of the saved target work). When
        the prompt IS the prefix, `prefix_logits` (the pin's stored
        last-token logits) seeds the first token. A fork miss raises
        SpecForkMiss — the caller falls back to a plain open or the
        regular loop."""
        import jax
        import jax.numpy as jnp

        sp = self._spec
        if sp is None:
            raise RuntimeError("speculation not enabled on this executor")
        n = len(prompt_ids)
        if n + 1 > self.cap:
            raise BufferError(
                f"prompt of {n} exceeds spec-capped capacity {self.cap}"
            )
        runner, batcher, rkey = self._spec_runner(sampling)
        forked = False
        if parent is not None and 0 < pin_len <= n:
            if not self.fork_session(session_id, parent, pin_len):
                raise SpecForkMiss(f"prefix fork from {parent} missed")
            forked = True
        with self._mu:
            if forked:
                # fork_session released _mu after claiming: re-validate the
                # un-inflight child wasn't LRU-evicted in the window
                if self._sessions.get(session_id) is None:
                    raise SpecForkMiss("forked lane evicted before open")
            if self._inflight.get(session_id):
                raise ValueError(f"session {session_id}: concurrent request")
            lane = self._lane_for(session_id, new_ok=not forked)
            if not forked and self.engine.lengths[lane]:
                self.engine.lengths[lane] = 0
                self._lane_hi[lane] = 0
            self._inflight[session_id] = 1
        try:
            start = pin_len if forked else 0
            suffix = list(prompt_ids[start:])
            b = min(bucket_len(n), self.max_len)
            padded = np.zeros((1, b), np.int32)
            padded[0, :n] = np.asarray(prompt_ids, np.int32)
            with self._dev_lock:
                if suffix:
                    sb = min(bucket_len(len(suffix)), self.max_len - start)
                    spad = np.zeros((1, sb), np.int32)
                    spad[0, : len(suffix)] = np.asarray(suffix, np.int32)
                    self.engine.cache, logits = self.engine._prefill_lane_logits(
                        self.engine.params, self.engine.cache,
                        jnp.asarray(spad), jnp.int32(lane), jnp.int32(start),
                        jnp.int32(len(suffix)),
                    )
                else:
                    if prefix_logits is None:
                        raise SpecForkMiss(
                            "prompt == pinned prefix but no stored logits"
                        )
                    logits = np.asarray(prefix_logits)
                # draft: always the FULL prompt from 0 (no pinned draft KV)
                sp["dcache"] = runner.draft_prefill(
                    sp["dparams"], sp["dcache"], padded, lane, 0, n
                )
                with self._mu:
                    self.engine.lengths[lane] = n
                    self._lane_hi[lane] = max(self._lane_hi.get(lane, 0), n)
                    sp["dlens"][lane] = n
            key, sub = jax.random.split(jax.random.PRNGKey(seed))
            first = runner.first_token(np.asarray(logits), sub)
            first_lp = (
                runner.row_lp(np.asarray(logits), first) if want_lp else None
            )
            with self._mu:
                sp["sid"][session_id] = (runner, batcher, rkey, want_lp)
                sp["keys"][session_id] = key
                sp["count"][rkey] = sp["count"].get(rkey, 0) + 1
            return first, first_lp
        except Exception:
            with self._mu:
                self._inflight.pop(session_id, None)
                self._drop(session_id)
            raise

    def _run_spec_batch(self, runner, entries) -> None:
        """Spec-batcher flush: ONE coalesced round for every waiting lane
        (window.py calls this with no locks held)."""
        sp = self._spec
        L = self.engine.lanes
        with self._dev_lock:
            active = np.zeros((L,), bool)
            last = np.zeros((L,), np.int32)
            catch = np.zeros((L,), np.int32)
            catch_mask = np.zeros((L,), bool)
            keys = np.zeros((L, 2), np.uint32)
            sampled = runner.sampling.temperature > 0.0
            with self._mu:
                dlens = np.asarray(sp["dlens"], np.int32)
                wants = {}
                for e in entries:
                    lane, sid, lt, pt, sub = e.payload
                    active[lane] = True
                    last[lane] = lt
                    ent = sp["sid"].get(sid)
                    wants[lane] = bool(ent and ent[3])
                    if sp["dlens"][lane] < self.engine.lengths[lane]:
                        catch[lane] = pt
                        catch_mask[lane] = True
                    if sampled:
                        keys[lane] = sub
            want_flush = any(wants.values())
            res = runner.run_round(
                self.engine.params, sp["dparams"], self.engine, sp["dcache"],
                last, catch, catch_mask, dlens, active,
                keys if sampled else None, want_lp=want_flush,
            )
            if want_flush:
                toks, n_new, dcache, lps, tis, tls = res
            else:
                toks, n_new, dcache = res
            sp["dcache"] = dcache
            with self._mu:
                for e in entries:
                    lane, sid, _, _, _ = e.payload
                    n = int(n_new[lane])
                    old = self.engine.lengths[lane]
                    self.engine.lengths[lane] = old + n
                    sp["dlens"][lane] = old + min(n, runner.k)
                    self._lane_hi[lane] = max(
                        self._lane_hi.get(lane, 0), old + runner.k + 1
                    )
                    e.result = self._spec_entry_result(
                        wants.get(lane), toks[lane], n,
                        lps[lane] if want_flush else None,
                        tis[lane] if want_flush else None,
                        tls[lane] if want_flush else None,
                    )

    # -- lane/session bookkeeping (call under self._mu) ----------------------

    def _lane_for(self, session_id: str, new_ok: bool, protect=()) -> int:
        return self._admit_lane(session_id, new_ok, protect)[0]

    def _admit_lane(self, session_id: str, new_ok: bool, protect=()):
        """The session's lane, and what binding it found (`Admissions.bound`;
        `Admissions.KNOWN` for a session that had its lane)."""
        lane = self._sessions.get(session_id)
        if lane is not None:
            self._last_used[session_id] = time.monotonic()
            return lane, Admissions.KNOWN
        if not new_ok:
            raise ValueError(
                f"session {session_id}: unknown session resumed mid-stream "
                "(cache evicted or node restarted)"
            )
        evicted = not self.engine.free
        if evicted:
            # LRU-evict a session with NO request in flight (neither waiting
            # in the decode batch nor mid-prefill on another thread);
            # `protect` shields a fork's parent from being its own victim
            victims = [
                s
                for s in self._sessions
                if not self._inflight.get(s) and s not in protect
            ]
            if not victims:
                raise CapacityError("all lanes busy with in-flight requests")
            oldest = min(victims, key=lambda s: self._last_used.get(s, 0.0))
            emit_safely(
                self.on_event, "lane.evict", session=oldest,
                lane=self._sessions.get(oldest),
                idle_s=round(
                    time.monotonic() - self._last_used.get(oldest, 0.0), 3
                ),
                claimant=session_id,
            )
            self._drop(oldest)
        lane = self.engine.free.pop()
        self._sessions[session_id] = lane
        self._last_used[session_id] = time.monotonic()
        self._lane_hi[lane] = 0  # fresh claimant: old marks are meaningless
        return lane, self._admit.bound(lane, evicted)

    def _drop(self, session_id: str) -> None:
        lane = self._sessions.pop(session_id, None)
        self._last_used.pop(session_id, None)
        self._release_adapter_locked(session_id)
        if lane is None:
            return
        self._forget(lane)
        # invalidate decode entries still waiting in the batch window — a
        # later flusher step must never write this lane on the old
        # session's behalf once a new session may own it
        self._batcher.invalidate(
            lambda payload, _lane=lane: payload[0] == _lane,
            ValueError(f"session {session_id} ended mid-request"),
        )
        if self._inflight.get(session_id):
            # a request is mid-device-step (e.g. swapped into a flusher
            # batch): defer the free until it drains, else a new claimant
            # would share the lane with the stale write
            self._dying[lane] = session_id
        else:
            self._free_lane(lane)

    # -- one step ahead: the records (call under self._mu) --------------------

    def _forget(self, lane: int) -> None:
        """Drop what was run ahead for `lane` (its session ended, was
        evicted, forked from, exported, or sent something else than the hop
        the row was run for): the row stays where it was written, beyond
        the lane's length, and whoever writes there next overwrites it."""
        if self._ahead.pop(lane, None) is not None:
            self.ahead_dropped += 1
        self._carry.pop(lane, None)

    def _write_lens(self) -> np.ndarray:
        """The lanes' lengths as a program that writes EVERY lane's row
        takes them: a lane with an unclaimed row run ahead writes past it,
        so that the row survives the steps its session sits out."""
        lens = np.asarray(self.engine.lengths, np.int32)
        for lane, rec in self._ahead.items():
            lens[lane] += rec.width
        return lens

    def _free_lane(self, lane: int) -> None:
        """Return a lane to the free list (under self._mu). Paged: the
        chain frees per-block — cached/pinned prefix blocks survive via
        their index references."""
        self.engine.lengths[lane] = 0
        self._lane_slot[lane] = 0  # back to the base adapter
        if self.pool is not None:
            self.pool.release_lane(lane)
        self.engine.free.append(lane)
        self._admit.freed(lane)

    # -- executor contract ---------------------------------------------------

    def process(self, session_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """One call, on the caller's thread, in three parts: before the
        window (`_enter`), the device's part (a prefill's chunks; a decode
        or block hop's wait in the window), after it (`_leave`, `_reply`).
        A caller that must not block makes the first and the last itself,
        around an entry no thread waits on: `begin_hop` / `end_hop`."""
        hop = self._enter(session_id, payload)
        try:
            try:
                if hop.kind == "prefill":
                    res = self._prefill_solo(
                        session_id, hop.lane, hop.toks, hop.start_pos, hop.real_len,
                        want_logits=payload.get("want_logits", True),
                    )
                else:
                    res = self._decode_batched(session_id, hop.lane, *self._asks(hop))
            finally:
                self._leave(hop, pooled=True)
            return self._reply(hop, res)
        except Exception:
            self._unacquire(hop.acquired)
            raise

    def _unacquire(self, acquired) -> None:
        """A call died: an admission that died before _bind_adapter_locked
        consumed the reference must give it back, or the slot leaks a
        refcount and can never be evicted."""
        if acquired is not None and acquired[1]:
            self.adapters.release(acquired[0])

    #: the longest a caller that must not block waits for `_mu` (`begin_hop`,
    #: `end_hop`): its holders are short and host-only but for a session's
    #: export or import, which reads or writes the lane's slab under it
    LOOP_MU_WAIT_S = 0.002

    def begin_hop(self, session_id: str, payload: Dict[str, Any], hand) -> "Optional[_Hop]":
        """The part of `process` before the window, for a caller that must
        not block (the node's event loop): the call is admitted as
        `process` admits it, by the same code, and handed to the window
        with no thread waiting on it (WindowedBatcher.submit_nowait: `hand`
        is called once the entry is answered, with every other entry of the
        drain that answered it). Returns the hop, to be ended by `end_hop`
        then; raises what `process` would have raised before its wait.

        None where this is not a call to make that way, and nothing has
        been done: anything but a decode or block hop of a known session on
        dense lanes whose ask a step's sampler covers (`_decode_ahead`'s
        hops; a prefill chunk spends tens of ms inside device calls, K-step
        and paged dispatches wait for their step inside the drain, a
        speculating executor runs nothing ahead), or `_mu` was not to be
        had within LOOP_MU_WAIT_S (somebody exports or imports a
        session). `process` serves those, on a thread."""
        hop = self._enter(session_id, payload, loop=True)
        if hop is None:
            return None
        try:
            hop.entry = self._batcher.submit_nowait((hop.lane, *self._asks(hop)), hand)
        except Exception:
            self._leave(hop, pooled=None)
            raise
        return hop

    def end_hop(self, hop: "_Hop", block: bool = True) -> Optional[Dict[str, Any]]:
        """The part of `process` after the window, once the hop's entry has
        been handed back: the reply, or the entry's error raised. With
        `block` false (the event loop) None where ending the hop would
        block, and nothing has been done: the drain answered with a step to
        ride, which is waited for (`_ridden`), or `_mu` is held for long;
        a worker thread calls again, with `block`."""
        e = hop.entry
        if e.error is None and isinstance(e.result, _Step):
            if not block:
                return None
            self._batcher.record_waits(e)
            try:
                res = self._ridden(e.result, hop.lane)
            finally:
                self._leave(hop, pooled=True)
            return self._reply(hop, res)
        if not self._leave(hop, pooled=block, wait_s=-1 if block else self.LOOP_MU_WAIT_S):
            return None
        self._batcher.record_waits(e)
        if block and e.t_out is not None:
            tracelib.mark("t_out", e.t_out)  # `deliver` ends with this worker (Node._timed)
        if e.error is not None:
            raise e.error
        return self._reply(hop, e.result)

    def _enter(self, session_id: str, payload: Dict[str, Any], loop: bool = False):
        """Before the window or the device: what the call is, whether its
        session may make it now (under `_mu`; no device call), its lane.
        `loop`: for `begin_hop`, which see for the None."""
        start_pos = int(payload.get("start_pos", 0))
        if loop and (
            start_pos <= 0 or self.pool is not None or self._spec is not None
            or payload.get("adapter") is not None  # `_resolve_adapter` takes `_mu` for it
        ):
            return None
        # a session's first call: where its `lane` span begins (once a request)
        t_in = tracelib.now() if start_pos == 0 else None
        toks = np.asarray(payload["tokens"], dtype=np.int32)
        if toks.ndim != 2 or toks.shape[0] != 1:
            raise ValueError(f"batched stage expects tokens [1, S], got {toks.shape}")
        real_len = int(payload.get("real_len", toks.shape[1]))
        kind = call_kind(payload)
        decode = kind == "decode"
        blk = self.cfg.block_length
        if self.cfg.is_block_diffusion:
            if decode or start_pos % blk or real_len % blk or (
                kind == "block" and real_len != blk
            ):
                raise ValueError(
                    f"{self.cfg.name} is generated by blocks of {blk}: a prefill "
                    f"chunk ingests whole blocks and a block call carries one, "
                    f"at a multiple of {blk} (got {kind}, start_pos {start_pos}, "
                    f"{real_len} tokens)"
                )
        elif kind == "block":
            raise ValueError(f"{self.cfg.name} is not generated by blocks")
        call = parse_block(payload, blk) if kind == "block" else None
        # a hop that asks for its token (parse_decode_ask) is answered with
        # it; any other with its logits row; a block hop's ask is its call
        ask = parse_decode_ask(payload) if decode and not payload.get("decode_steps") else call
        if loop and ask is None:
            return None
        acquired = self._resolve_adapter(session_id, payload, start_pos)
        if not self._mu.acquire(timeout=self.LOOP_MU_WAIT_S if loop else -1):
            return None
        try:  # under _mu: the admission; the session is in flight from here to `_leave`
            if self._inflight.get(session_id):
                # a duplicate/replayed request racing the original would
                # pass the frontier check and double-advance the lane
                raise ValueError(
                    f"session {session_id}: concurrent request (one step at "
                    "a time per session)"
                )
            lane, bound = self._admit_lane(session_id, new_ok=start_pos == 0)
            owner = f"session {session_id}, lane {lane}"
            have = self.engine.lengths[lane]
            # whatever this call is, the lane is back: the next drain runs
            # nothing ahead on the strength of the hop before it
            self._carry.pop(lane, None)
            if start_pos == 0 and have:
                # session restart under the same id: reset the lane
                self.engine.lengths[lane] = 0
                self._lane_hi[lane] = 0
                self._forget(lane)
                if self.pool is not None:
                    self.pool.release_lane(lane)
                have = 0
            if start_pos + real_len > self.cap:
                # overflow is checked BEFORE any frontier mutation: a
                # rejected oversized replay must not leave the lane rolled
                # back with nothing recomputed. `cap` < max_len while
                # speculation is enabled (verify-chunk headroom: EVERY lane
                # must stay k+1 short of the physical buffer).
                raise BufferError(
                    f"session {session_id}: KV overflow "
                    f"({start_pos}+{real_len} > {self.cap})"
                )
            if lane in self._ahead:
                # before any frontier moves: a call that is not the hop the
                # row was run for drops it (or is refused: `_claim`)
                self._claim(lane, start_pos, int(toks[0, 0]), ask)
            if start_pos != have:
                if not 0 < start_pos < have:
                    raise ValueError(
                        f"session {session_id}: start_pos {start_pos} != cache "
                        f"length {have} (out-of-order chunk)"
                    )
                if self.cfg.has_state_layers:
                    # before any frontier moves: the lane stays as it was
                    raise ValueError(
                        f"session {session_id}: replay to {start_pos} of {have} tokens: "
                        f"{self.cfg.name} holds a recurrent state, which does not roll "
                        "back; restart the session at 0"
                    )
                hi = max(self._lane_hi.get(lane, 0), have)
                if (
                    self.engine.cache.k_loc is not None
                    and hi - start_pos > RING_MARGIN
                ):
                    raise ValueError(
                        f"session {session_id}: replay rollback to "
                        f"{start_pos} exceeds the ring margin (high-water "
                        f"mark {hi})"
                    )
                # deterministic chunk REPLAY (client re-sent after a lost
                # response): roll the lane's frontier back and recompute —
                # identical KV; ring lanes stay exact while the HIGH-WATER
                # mark is within the margin (the same contract as the stage
                # executor's replay path). Preserve the pre-rollback
                # frontier as the mark: hi only diverges from the length
                # across rollbacks.
                self._lane_hi[lane] = hi
                self.engine.lengths[lane] = start_pos
                if self.pool is not None:
                    # a replay rewrite into a SHARED region splits those
                    # blocks copy-on-write first — the recompute must not
                    # scribble on blocks other lanes / the prefix index
                    # still read (copies apply at the next dispatch)
                    before = self.pool.cow_splits
                    self.pool.make_writable(lane, start_pos, owner=owner)
                    if self.pool.cow_splits != before:
                        emit_safely(
                            self.on_event, "kv.cow_split",
                            session=session_id, lane=lane,
                            from_pos=start_pos,
                            blocks=self.pool.cow_splits - before,
                        )
            if self.pool is not None and decode:
                # decode dispatches write positions [start_pos,
                # start_pos + K): the chain must cover them before the jit
                # scatters (prefill ensures per chunk instead)
                k_req = max(1, min(int(payload.get("decode_steps") or 0),
                                   self.cap - start_pos))
                self.pool.ensure(lane, start_pos + k_req, owner=owner)
            self._bind_adapter_locked(session_id, lane, start_pos, acquired)
            self._inflight[session_id] = 1
            if kind == "prefill":
                # inside a prefill: no decode step waits for this lane
                # until one has served it again
                self._batcher.unexpect(lambda p, _lane=lane: p[0] == _lane)
        except Exception:
            self._unacquire(acquired)
            raise
        finally:
            self._mu.release()
        self._lane_span(t_in, lane, bound)  # process entered -> _mu released
        return _Hop(session_id, lane, kind, toks, start_pos, real_len, ask, acquired, payload)

    def _asks(self, hop: "_Hop"):
        """What the window is handed beside the hop's lane: its token (a
        block hop: its block's tokens) and its ask (a K-step call: its
        budget-clamped parse_kstep; None: its logits row)."""
        if hop.kind == "block":
            return hop.toks[0, :self.cfg.block_length], hop.ask
        ks = parse_kstep(hop.payload, self.cap - hop.start_pos)
        # multi-step fused decode: K on-device-sampled tokens per dispatch;
        # co-arrived K-step lanes fuse into one K-step scan (see
        # _run_decode_batch)
        return int(hop.toks[0, 0]), hop.ask if ks is None else ks

    def _leave(self, hop: "_Hop", pooled: Optional[bool], wait_s: float = -1) -> bool:
        """After the window or the device: the session is no longer in
        flight, and a lane whose session ended meanwhile is freed. Counts
        the decode or block hop as one a worker thread served (`pooled`) or
        none did (None: the hop never reached the window, not counted).
        False where `_mu` was not to be had within `wait_s` (nothing
        done)."""
        if not self._mu.acquire(timeout=wait_s):
            return False
        try:
            self._inflight.pop(hop.session_id, None)
            if self._dying.get(hop.lane) == hop.session_id:  # ended mid-request
                del self._dying[hop.lane]
                self._free_lane(hop.lane)
            if pooled is not None and hop.kind != "prefill":
                if pooled:
                    self.hops_pooled += 1
                else:
                    self.hops_inline += 1
        finally:
            self._mu.release()
        return True

    def _reply(self, hop: "_Hop", res) -> Dict[str, Any]:
        """The call's reply from what the window or the prefill gave."""
        if isinstance(res, dict):
            if "decode_steps" in res:  # a K-step call says what it committed
                return {**res, "start_pos": hop.start_pos}
            # a block hop; a decode hop answered with its token
            width = self.cfg.block_length if hop.kind == "block" else 1
            return {**res, "real_len": width, "start_pos": hop.start_pos}
        logits, saved = (res, 0) if hop.kind == "decode" else res
        return {
            # None where the caller asked for none (`want_logits` false: a
            # chunk of a prompt whose last row nobody samples from)
            "logits": None if logits is None else logits[None, :],
            "real_len": hop.real_len,
            "start_pos": hop.start_pos,
            # per-request shared-prefix saving (stage_batch contract):
            # span attr + kv.saved_tokens at the node, stripped before
            # the reply; omitted on cold prefills
            **({"tokens_saved": saved} if saved else {}),
        }

    def _sync_paged(self):
        """core.cache.sync_paged over this executor's state: call under
        self._dev_lock; rebinds engine.cache (the copy jit donates)."""
        self.engine.cache = sync_paged(
            self.pool, self.engine.cache, self.engine._copy_blocks,
            self._mu,
        )
        return self.engine.cache

    def _prefill_solo(self, session_id: str, lane: int, toks: np.ndarray,
                      start: int, n: int, want_logits: bool = True):
        """Prompt ingestion: shared-prefix skip (paged — full blocks whose
        chained token hash is cached/pinned map read-only, zero prefill
        FLOPs for the shared region), then `prefill_chunk`-token
        dispatches with the device lock RELEASED between chunks so decode
        windows interleave, then prefix registration (paged) so later
        sessions skip what this one computed."""
        import jax.numpy as jnp

        owner = f"session {session_id}, lane {lane}"
        pos = start
        keys = None
        saved = 0
        with self._mu:
            ad_name = self._session_adapter.get(session_id)
            ads = self._ads([self._lane_slot[lane]])
        if self.pool is not None and start == 0:
            ids = [int(t) for t in toks[0, :n]]
            # adapter sessions salt the chain: their KV depends on the
            # adapter weights, so tenants must never share prefix blocks
            # across adapters (one tenant's sessions still do)
            keys = prefixlib.block_keys(
                ids, self.pool.block_size, salt=ad_name
            )
            # map at most the blocks covering n - 1 tokens: the LAST
            # prompt token always computes (its logits are the response)
            nmap = (n - 1) // self.pool.block_size
            with self._mu:
                cov = self.pool.map_prefix(lane, keys[:nmap])
            if cov:
                pos = saved = cov
                with self._mu:
                    self.engine.lengths[lane] = cov
                    self._lane_hi[lane] = max(self._lane_hi.get(lane, 0), cov)
                emit_safely(
                    self.on_event, "prefix.hit", session=session_id,
                    lane=lane, tokens=cov,
                )
        end = start + n
        step = self.prefill_chunk if self.prefill_chunk > 0 else end - pos
        logits = None
        chunks = 0  # dispatches so far: the last one's `device` span says how many it stands for
        with contextlib.ExitStack() as dev:
            while pos < end:
                c = min(step, end - pos)
                # cap the padded bucket so the in-jit dynamic_update_slice can
                # never clamp into older slots near the end of the cache (the
                # stage executor's _cache_for guards the same invariant); a
                # capped tail shape compiles its own program — rare and bounded
                b = min(bucket_len(c), self.max_len - pos)
                padded = np.zeros((1, b), np.int32)
                padded[0, :c] = toks[0, pos - start: pos - start + c]
                if self.pool is not None:
                    with self._mu:
                        self.pool.ensure(lane, pos + c, owner=owner)
                with tracelib.holding(self._dev_lock, self.tracer, kind="prefill"):
                    if self.pool is not None:
                        cache = self._sync_paged()
                        self.engine.cache, logits = (
                            self.engine._prefill_lane_logits_paged(
                                self.engine.params, cache, jnp.asarray(padded),
                                jnp.asarray(self.pool.table[lane:lane + 1]),
                                jnp.int32(pos), jnp.int32(c), ads=ads,
                            )
                        )
                    else:
                        fn = self.engine._prefill_lane_logits
                        chunks += 1
                        if pos + c == end:
                            # the chunk whose logits are the response: its
                            # `device` span closes below, once the lock is
                            # released and the result is ready (earlier
                            # chunks of a --prefill-chunk admission are
                            # dispatched without a wait and get no span:
                            # `chunks` counts them into this one; how long
                            # each held the lock is its `lock_wait`'s
                            # `held_ms`)
                            dev.enter_context(tracelib.region(
                                self.tracer, "device", keep=True, kind="prefill",
                                tokens=c, cobatch=1, program=program_name(fn),
                                chunks=chunks,
                            ))
                        self.engine.cache, logits = fn(
                            self.engine.params, self.engine.cache,
                            jnp.asarray(padded),
                            jnp.int32(lane), jnp.int32(pos), jnp.int32(c),
                            ads=ads,
                        )
                        self._chunk = logits
                    # advance the lane BEFORE releasing the device lock: a
                    # flusher snapshots lengths under the same lock order
                    # (_dev_lock, _mu), so it can never scatter a decode write
                    # over these fresh rows at the stale position
                    with self._mu:
                        self.engine.lengths[lane] = pos + c  # real tokens only
                        self.prefill_tokens += c
                        # a block's valid length is its real end, a causal chunk's its bucket's
                        self._count_read(
                            1, pos + (c if self.cfg.is_block_diffusion else b), q_len=b)
                pos += c
                if self.prefill_chunk > 0 and pos < end:
                    # explicit yield between chunks: threading.Lock is NOT
                    # fair — without this, the chunk loop can re-acquire the
                    # device before a waiting decode flusher ever wakes, and
                    # chunking would bound nothing. Sub-ms: noise next to a
                    # chunk dispatch. The ticketed FairDeviceLock grants in
                    # arrival order, so there the yield is dead weight.
                    if not lockwatch.is_fair(self._dev_lock):
                        time.sleep(0.0005)
            if self.pool is not None and keys:
                with self._mu:
                    self.pool.register_prefix(lane, keys)
            # the wait np.asarray below would make anyway — OUTSIDE the
            # device lock, as it always was: the next step of another
            # session is dispatched while this chunk still runs
            logits.block_until_ready()
        if not want_logits:
            return None, saved
        # ONE boundary transfer: only the LAST chunk's logits are the
        # response — mid-chunk logits never leave the device
        with tracelib.region(self.tracer, "copy_out", keep=True) as at:
            out = np.asarray(logits, np.float32)
            at["bytes"] = out.nbytes
        return out, saved

    def _decode_batched(self, session_id: str, lane: int, token, ks=None):
        """Hand a hop (a decode hop's token, a block hop's tokens) and its
        ask to the window and return what it is answered with."""
        res = self._batcher.submit((lane, token, ks))
        # a step that other sessions' drains do not wait for (`_decode_ahead`):
        # this thread waits for it
        return self._ridden(res, lane) if isinstance(res, _Step) else res

    def _run_decode_batch(self, _empty) -> None:
        """Flush callback: ONE batched device step for every lane whose
        entry is pending when the device lock is acquired
        (runtime/window.py calls this with an empty list and no locks
        held, once its formation wait is over; an entry that arrived
        while the previous step or a prefill held the device is drained
        with the rest). A drain that finds nothing (every waiting entry
        was invalidated meanwhile) runs no program and counts no step.

        Entries partition into one-token hops (ONE _decode_dispatch for
        all of them, whatever each asks: a token chosen on the device
        under its own sampling, or its logits row) and multi-step fused
        decode (`ks` payload from parse_kstep: K on-device-sampled tokens
        per dispatch). K-step entries sharing a sampling config fuse into
        ONE K-step scan (models/qwen3.decode_k via the engine's
        _decode_k_serve) with K = the group's minimum budget-clamped
        request — co-batched lanes decode K steps per window when every
        lane has >= K budget, degrading toward K=1 at boundaries. A lane
        whose `eos` fires mid-window deactivates in-graph; its result
        carries only the really-committed tokens.

        Failure isolation is per DISPATCH: a window can run one single
        step plus several K-step group scans, and a raising dispatch must
        not clobber results another dispatch already committed (lengths
        advanced, e.result set) — each dispatch marks only ITS entries
        failed and the flush returns normally, so submit() raises for
        exactly the sessions whose device step died. Isolation holds for
        HOST-side failures (the cache untouched); a device-side failure
        after the jit donated the cache invalidates the shared buffers,
        so the window stops dispatching and fails the remaining entries
        with a clear error (executor.cache_intact) — committed results
        still stand."""
        from inferd_tpu.runtime.executor import fuse_kstep_group, kstep_hi

        poisoned: Optional[Exception] = None
        with self._dev_lock:
            self._settle()
            # the batcher stamps each entry's lock_wait and batch_wait
            entries = self._batcher.drain_pending()
            blocks = [e for e in entries if isinstance(e.payload[2], BlockCall)]
            if blocks:  # a model generated by blocks has no other batched call
                self._decode_ahead(blocks, self._dispatch_blocks)
                return
            # one-token hops, with an ask (SampleAsk) or without (None),
            # ride ONE program; only K-step calls dispatch apart
            single = [e for e in entries if not isinstance(e.payload[2], dict)]
            kstep = [e for e in entries if isinstance(e.payload[2], dict)]
            if single:
                try:
                    if self.pool is None:
                        self._decode_ahead(single, self._dispatch_rows)
                    else:
                        self._decode_dispatch(single)
                except Exception as exc:
                    for e in single:
                        e.error = exc
                    # the drain counted every live entry as served; net
                    # failed entries to zero so /stats batched_tokens
                    # stays token-true
                    self._batcher.n_served -= len(single)
                    if not cache_intact(self.engine.cache):
                        poisoned = exc
            groups: Dict[tuple, list] = {}
            for e in kstep:
                groups.setdefault(e.payload[2]["sampling"], []).append(e)
            for _sampling, grp in groups.items():
                if poisoned is not None:
                    # a donated-cache dispatch died device-side: the KV
                    # buffers are gone, dispatching would only raise a
                    # deleted-buffer error — fail the rest clearly
                    for e in grp:
                        e.error = RuntimeError(
                            "KV cache invalidated by an earlier dispatch "
                            f"failure in this window: {poisoned}"
                        )
                    self._batcher.n_served -= len(grp)  # see the single step's note
                    continue
                try:
                    with self._mu:
                        lens = self._write_lens().tolist()
                        ids = list(self._lane_slot)
                    kg, seq, n_new, nkeys, self.engine.cache = (
                        fuse_kstep_group(
                            self.engine._decode_k_serve, self.engine.params,
                            self._sync_paged() if self.pool is not None
                            else self.engine.cache,
                            lens, self.engine.lanes,
                            [e.payload for e in grp],
                            ads=self._ads(ids),
                        )
                    )
                    with self._mu:
                        for e in grp:
                            lane = e.payload[0]
                            n = int(n_new[lane])
                            old = self.engine.lengths[lane]
                            self.engine.lengths[lane] = old + n
                            self._lane_hi[lane] = max(
                                self._lane_hi.get(lane, 0),
                                kstep_hi(old, n, kg),
                            )
                    served_tokens = 0
                    for e in grp:
                        lane = e.payload[0]
                        n = int(n_new[lane])
                        served_tokens += n
                        e.result = {
                            "tokens": [seq[:n, lane].tolist()],
                            "real_len": n,
                            "decode_steps": kg,
                            "key": nkeys[lane].tolist(),
                        }
                    # token-true stats: the drain counts one
                    # served unit per ENTRY; a K-step entry really served
                    # n tokens — /stats batched_tokens and mean_batch
                    # must reflect tokens, not dispatches
                    self._batcher.n_served += served_tokens - len(grp)
                except Exception as exc:
                    for e in grp:
                        e.error = exc
                    self._batcher.n_served -= len(grp)  # see the single step's note
                    if not cache_intact(self.engine.cache):
                        poisoned = exc

    def _decode_dispatch(self, entries) -> None:
        """ONE decode step (under _dev_lock) for the one-token hops of a
        drain on PAGED lanes, whatever each asks: the program chooses every
        lane's token under the lane's own sampling (core.sampling.choose_rows:
        traced per lane, so a mix of configs is one program and a new one
        compiles nothing), and ONE small array leaves the device: tokens,
        next keys, the log-probabilities at the widest top-n a lane asked.
        An entry with an ask is answered with its token; one without (a raw
        /forward, an ask the sampler does not cover) with its logits row:
        that row alone where it is the only one, else the step's whole
        [L, V]. The step is waited for here, inside the drain (dense lanes:
        `_decode_ahead`)."""
        eng = self.engine
        with self._mu:
            # snapshot under _mu; host arrays in: the call places them all at once
            lens = np.asarray(eng.lengths, np.int32)
            ids = list(self._lane_slot)
        toks = np.zeros((eng.lanes,), np.int32)
        active = np.zeros((eng.lanes,), bool)
        for e in entries:
            toks[e.payload[0]], active[e.payload[0]] = e.payload[1], True
        asks = {e.payload[0]: e.payload[2] for e in entries if e.payload[2] is not None}
        plain = [e.payload[0] for e in entries if e.payload[2] is None]
        ask, top_n = samplib.RowAsk.of(eng.lanes, asks)
        eng.cache, logits, packed = eng._decode_logits_paged(
            eng.params, self._sync_paged(), toks, lens, active,
            ads=self._ads(ids), ask=ask, top_n=top_n,
        )
        packed.copy_to_host_async()
        self._batcher.step_seen(not packed.is_ready())  # `_finish`'s counters: the drain waits here
        with tracelib.region(self.tracer, "copy_out", keep=True) as at:
            host = np.asarray(packed)
            rows, moved = samplib.logits_out(logits, plain)
            at["bytes"] = host.nbytes + moved
        self._batcher.stamp_out(entries)  # `deliver` starts
        replies, _ = samplib.row_replies(host, top_n, asks)
        with self._mu:
            for e in entries:
                eng.lengths[e.payload[0]] += 1
            self.sampled_rows += len(asks)
            self.logit_rows += len(plain)
        for e in entries:
            lane = e.payload[0]
            e.result = replies[lane] if lane in replies else rows[lane]

    # -- one step ahead (dense lanes; docs/SERVING.md "One step ahead") ------

    def _runs_ahead(self, lane: int, ask, src: _Step) -> bool:
        """Whether the hop after the one `ask` came with gets its row run
        before it arrives (under _mu; the lane's length is that row's
        position): the ask promised the hop, the row and what an idle lane
        writes past it fit (two rows of the hop's width: a token, a block),
        what `src` chose for it, where the host knows it already, does not
        end the generation (the token; a token IN the block), and no
        speculative round reads the lanes' lengths."""
        return (
            ask is not None and ask.ahead >= 1 and self._spec is None
            and self.engine.lengths[lane] + 2 * self.cfg.block_length <= self.cap
            and not (src.done and (src.error is not None or src.ends(lane, ask.eos)))
        )

    def _settle(self) -> None:
        """Before a drain (under _dev_lock): while a prefill chunk is on the
        device the next step cannot start before the chunk ends, so nothing
        is won by dispatching it now, and a session whose prefill is ending
        would find two steps queued ahead of its first decode hop. The drain
        waits out the step dispatched before it instead (it lies behind or
        before the chunk), then takes whoever has arrived meanwhile, the
        newcomer among them, and feeds the next step from the host's copy
        of what that step chose."""
        prev, chunk = self._last_step, self._chunk
        if chunk is not None and prev is not None and not prev.done and not chunk.is_ready():
            self._finish(prev)

    def _decode_ahead(self, entries, dispatch) -> None:
        """The hops of a drain on dense lanes (under _dev_lock), one step
        ahead of the sessions: one-token hops (`dispatch` is
        `_dispatch_rows`) or the block hops of a model generated by blocks
        (`_dispatch_blocks`); a ROW below is what a hop asks for, a token's
        or a block's. In this order:

        1. Each entry is CLAIMED (`_claim`: a step already ran the row this
           hop asks for, before it arrived) or RIDES (no row was run: a
           session's first decode or block hop, a hop whose ask promised
           nothing, a raw /forward). ONE program (a sampling group, for
           blocks) is dispatched for the rows to come: for every claimed
           lane whose ask says a hop follows, and for every lane that rode
           the last step under such an ask and is not back yet, the NEXT
           row, fed by what the last step's packed array holds for it where
           the device still holds it alone (core.sampling.ahead_rows /
           ahead_block_keys: no round trip); for every rider its own row
           from what its hop carries, as before.
        2. The step dispatched at the previous drain is waited for and
           copied out (`_finish`), and each claimed entry is answered from
           the step that ran its row. The sessions take their turn while
           the step of (1) runs.
        3. A rider is answered by its own thread (`_decode_batched` waits
           for the step it rode), so no drain waits for a newcomer and a
           newcomer waits for no drain. Where nobody is ahead (no claimed
           entry, no row run ahead in this step: a lone session, a cohort's
           first hops, callers that promise nothing) the drain is what it
           was: the flusher waits for the step and answers; the riders'
           next rows, where promised, are dispatched right behind it.

        `batched_steps` counts the programs dispatched and `batched_tokens`
        the rows they ran for a session (the drain counted one step and its
        entries)."""
        eng, width = self.engine, self.cfg.block_length
        prev = self._last_step
        claimed, riders, answers, conts = [], [], {}, {}
        with self._mu:
            for e in entries:
                lane, tok, ask = e.payload
                try:
                    mine = self._claim(lane, eng.lengths[lane], tok, ask)
                except ValueError as exc:
                    e.error = exc
                    continue
                (claimed if mine else riders).append(e)
            for e in claimed:  # the hop takes its row: the frontier moves past it
                lane, _, ask = e.payload
                answers[lane] = src = self._ahead.pop(lane).step
                eng.lengths[lane] += width
                if self._runs_ahead(lane, ask, src):
                    conts[lane] = (src, ask)
            if self._carry:
                self._take_carry(conts, {e.payload[0] for e in entries})
            self.ahead_claimed += len(claimed)
            if self._diffusion is None:
                self.sampled_rows += len(claimed)
            else:
                self._diffusion["hops"] += len(claimed)
        rows = len(conts)
        steps = dispatch(conts, riders) if conts or riders else []
        if prev is not None:
            prev.released.set()
        # nobody is ahead: the flusher answers its riders itself, and runs
        # their next rows right behind the steps they ride
        sync = bool(riders) and not claimed and not conts
        programs = len(steps)
        if sync:
            nxt = {}
            with self._mu:
                self._take_carry(nxt)
            if nxt:
                programs, rows = programs + len(dispatch(nxt, [])), rows + len(nxt)
        if prev is not None:
            self._wait_out(prev)
        if sync:
            for step in steps:
                self._wait_out(step)
        self._batcher.stamp_out(claimed + riders if sync else claimed)  # `deliver` starts
        for e in claimed:
            lane = e.payload[0]
            if answers[lane].error is not None:
                e.error = answers[lane].error
            else:
                e.result = answers[lane].replies[lane]
        ran = {lane: step for step in steps for lane in step.lanes} if riders else {}
        for e in riders:
            lane = e.payload[0]
            step = ran.get(lane)
            if step is None:
                continue  # its dispatch failed: it has its error
            if not sync:
                e.result = step  # `_decode_batched` waits for it
            elif step.error is not None:
                e.error = step.error
            else:
                e.result = step.reply(lane)
        failed = len(entries) - len(claimed) - len(riders)
        self._batcher.n_steps += programs - 1
        self._batcher.n_served += rows - len(claimed) - failed

    def _record_ahead(self, conts):
        """Record the rows of `conts` as run ahead (under _mu, before their
        dispatch; `_recorded` undoes it if that fails): each lies AT its
        lane's length and counts as written (a ring has given up its oldest
        slot). Returns the records and the lengths the program is handed
        (`_write_lens`, a row of `conts` at its own lane's length)."""
        width, made = self.cfg.block_length, {}
        for lane, (src, ask) in conts.items():
            at = self.engine.lengths[lane]
            made[lane] = self._ahead[lane] = _Ahead(at, width, src, ask)
            self._lane_hi[lane] = max(self._lane_hi.get(lane, 0), at + width)
        lens = self._write_lens()
        for lane in conts:
            lens[lane] -= width
        return made, lens

    def _dispatch_rows(self, conts, riders) -> list:
        """ONE `_decode_logits` (under _dev_lock) over the rows of `conts`,
        {lane: (the step whose output is the row's input, the ask it runs
        under)}: rows run AHEAD of their hops, recorded in `_ahead`; and of
        `riders`: entries waiting for their row, run from their host token.
        The program, its shapes and the placement of what it is handed are
        those of a step fed from the host alone: the tokens and keys a
        still-running step holds come through core.sampling.ahead_rows as
        arrays of the host arrays' kind. Returns the step, in a list."""
        eng = self.engine
        last = self._fed_by(conts)
        toks = np.zeros((eng.lanes,), np.int32)
        active = np.zeros((eng.lanes,), bool)
        from_dev = np.zeros((eng.lanes,), bool)
        asks, plain, carry = {}, [], {}
        with self._mu:
            ids = list(self._lane_slot)
            for lane, (_, ask) in conts.items():
                asks[lane], active[lane] = ask, True
            made, lens = self._record_ahead(conts)
            for e in riders:
                lane, tok, ask = e.payload
                toks[lane], active[lane] = tok, True
                if ask is None:
                    plain.append(lane)
                else:
                    asks[lane] = ask
                    if ask.ahead >= 1:
                        carry[lane] = ask
        fn = eng._decode_logits
        try:
            ask, top_n = samplib.RowAsk.of(eng.lanes, asks)
            keys = ask.keys
            for lane, (src, _) in conts.items():
                if src.done:  # the host has read it (a rider's thread may finish `last` any time)
                    toks[lane], keys[lane] = src.toks[lane], src.keys[lane]
                else:
                    from_dev[lane] = True
            if from_dev.any():
                toks, keys = samplib.ahead_rows(last.packed, toks, keys, from_dev)
                ask = samplib.RowAsk(keys, ask.warp)
            eng.cache, logits, packed = fn(
                eng.params, eng.cache, toks, lens, ads=self._ads(ids), ask=ask, top_n=top_n,
                # a lane not in this step keeps its recurrent state
                **({"active": active} if self.cfg.has_state_layers else {}),
            )
        except Exception:
            with self._mu:
                self._recorded(made, None)
            raise
        # the transfer is queued behind the step: the small array is on
        # its way when the device is done
        packed.copy_to_host_async()
        step = self._last_step = _Step(
            packed, logits if plain else None, top_n, asks, plain,
            list(conts) + [e.payload[0] for e in riders], last, program_name(fn),
        )
        with self._mu:
            self._recorded(made, step)
            for e in riders:
                eng.lengths[e.payload[0]] += 1
            for lane, ask in carry.items():
                self._carry[lane] = (step, ask)
            # a row lies AT its lane's length; a lane that keeps its state sits the read out
            self._count_read(
                eng.lanes, int((lens[active] if self.cfg.has_state_layers else lens).max()) + 1)
            self.ahead_rows += len(conts)
            self.sampled_rows += len(riders) - len(plain)
            self.logit_rows += len(plain)
        return [step]

    def _dispatch_blocks(self, conts, riders) -> list:
        """`_dispatch_rows` for a model generated by blocks: ONE
        BatchedEngine._block_step for the lanes of `conts` and `riders` that
        share a sampling config (static in the program; greedy callers are
        one group). Returns the steps dispatched. A failed dispatch fails
        its own riders and leaves no row recorded (the isolation of
        _run_decode_batch): what was claimed is answered from steps that
        ran before."""
        groups: Dict[tuple, tuple] = {}
        for lane, cont in conts.items():
            groups.setdefault(cont[1].sampling, ({}, []))[0][lane] = cont
        for e in riders:
            groups.setdefault(e.payload[2].sampling, ({}, []))[1].append(e)
        steps = []
        poisoned: Optional[Exception] = None
        for sampling, (ahead, grp) in groups.items():
            try:
                if poisoned is not None:
                    raise RuntimeError(
                        "KV cache invalidated by an earlier dispatch failure "
                        f"in this window: {poisoned}"
                    )
                steps.append(self._block_dispatch(sampling, ahead, grp))
            except Exception as exc:
                for e in grp:
                    e.error = exc
                self._batcher.n_served -= len(grp) + len(ahead)  # see _run_decode_batch
                if not cache_intact(self.engine.cache):
                    poisoned = exc
        for step in steps[1:]:  # one drain: its riders wait for the same next drain
            step.released = steps[0].released
        return steps

    def _block_dispatch(self, sampling: tuple, conts, grp) -> _BlockStep:
        """One block step, at the widest top-n any of its lanes asked: the
        NEXT block of the lanes of `conts` (nothing of it known, the key the
        block before left: core.sampling.ahead_block_keys where the device
        holds it alone), the block each entry of `grp` carries. The inputs
        have the shapes and dtypes of a step fed from the host alone. A
        rider's frontier advances by a block; one run ahead moves when its
        hop claims it."""
        import jax.numpy as jnp

        eng, blk = self.engine, self.cfg.block_length
        passes = self.cfg.denoising_steps + 1
        last = self._fed_by(conts)
        toks = np.zeros((eng.lanes, blk), np.int32)
        known = np.zeros((eng.lanes, blk), bool)
        live = np.zeros((eng.lanes,), bool)
        keys = np.zeros((eng.lanes, 2), np.uint32)
        from_dev = np.zeros((eng.lanes,), bool)
        asks = {lane: ask for lane, (_, ask) in conts.items()}
        for e in grp:
            lane, tokens, call = e.payload
            toks[lane], keys[lane], asks[lane] = tokens, call.key, call
            known[lane, :call.known] = True
        lanes = list(asks)
        live[lanes] = True
        with self._mu:
            made, lens = self._record_ahead(conts)
        fn = eng._block_step
        try:
            for lane, (src, _) in conts.items():
                if src.done:  # the host has read it (see _dispatch_rows)
                    keys[lane] = src.keys[lane]
                else:
                    from_dev[lane] = True
            if from_dev.any():
                keys = samplib.ahead_block_keys(last.packed, keys, from_dev, block=blk)
            top_n = max(ask.top_n for ask in asks.values())
            t, tk, tp, mp = sampling
            eng.cache, packed, chosen = fn(
                eng.params, eng.cache, jnp.asarray(toks), jnp.asarray(known),
                jnp.asarray(lens), jnp.asarray(live), jnp.asarray(keys),
                temperature=t, top_k=tk, top_p=tp, min_p=mp, top_n=top_n,
            )
        except Exception:
            with self._mu:
                self._recorded(made, None)
            raise
        packed.copy_to_host_async()  # queued behind the step (see _dispatch_rows)
        tokens = int((~known[lanes]).sum())
        step = self._last_step = _BlockStep(
            packed, chosen, top_n, asks, last, program_name(fn), tokens, passes, blk)
        with self._mu:
            self._recorded(made, step)
            for e in grp:
                lane, _, call = e.payload
                eng.lengths[lane] += blk
                if call.ahead >= 1:
                    self._carry[lane] = (step, call)
            self._count_read(eng.lanes, int(lens.max()) + blk, q_len=blk, passes=passes)
            self.ahead_rows += len(conts)
            d = self._diffusion
            d["hops"] += len(grp)
            d["block_steps"] += 1
            d["lane_passes"] += len(lanes) * passes
            d["tokens"] += tokens
            d["rows"] += len(lanes) * blk * passes
        return step

    def _count_read(self, rows: int, longest: int, q_len: int = 1, passes: int = 1) -> None:
        """`kv.slots_read` / `kv.slots_held` of one dispatched program (under
        self._mu): of the slots `rows` lanes hold in a full layer's slab, those
        the program read for `q_len` queries a row where the longest row's valid
        length is `longest`: the rung the program chose from the lengths it was
        handed, by the function it asks (models.qwen3.read_rungs / read_rung),
        once a forward pass. A windowed layer on dense lanes reads its window's
        slice and is not counted; K-step and speculative dispatches are not."""
        if self._read is None:
            return
        cache = self.engine.cache
        rungs = qwen3.read_rungs(
            self.cfg, (cache.k, cache.v), q_len, rows, self.cfg.kv_jnp_dtype != self.cfg.jnp_dtype)
        self._read["slots_read"] += passes * rows * rungs[int(qwen3.read_rung(longest, rungs))]
        self._read["slots_held"] += passes * rows * self.max_len

    def _count_routing(self, chosen: np.ndarray, rows: int) -> None:
        """The `moe.*` counters of one decode step (or one pass of a block
        step) from the experts its
        live rows chose, `chosen` [sparse layers, live rows, K]: the
        assignments made, those that fell on each layer's most loaded
        expert, and the distinct experts hit, each summed over the layers
        and counted over the ROUTER's width; of the assignments and of the
        experts hit, those held here (all of them, but for a rank's share);
        and the rows the expert product multiplied for the live rows' pairs
        held here, padding to its row tile included: the tile the program
        took for the `rows` it ran (models.qwen3.expert_row_tile)."""
        cfg = self.cfg
        per_layer = [np.bincount(layer.ravel(), minlength=cfg.router_width)
                     for layer in chosen]
        here = slice(cfg.expert_offset, cfg.expert_offset + cfg.num_experts)
        tile = qwen3.routed_row_tile(
            qwen3.routed_weight(self.engine.params), rows, chosen.shape[2], cfg.num_experts,
            cfg.router_width)
        multiplied = sum(
            qwen3.expert_rows_multiplied(c[here], tile, chosen.shape[1]) for c in per_layer)
        with self._mu:
            self._moe["rows_multiplied"] += multiplied
            self._moe["steps"] += 1
            self._moe["assignments"] += int(chosen.size)
            self._moe["assignments_hottest"] += int(sum(c.max() for c in per_layer))
            self._moe["experts_touched"] += int(sum((c > 0).sum() for c in per_layer))
            self._moe["assignments_here"] += int(sum(c[here].sum() for c in per_layer))
            self._moe["experts_touched_here"] += int(sum((c[here] > 0).sum() for c in per_layer))

    def _refuse_latent(self, what: str) -> None:
        if self.cfg.is_mla:
            raise ValueError(
                f"{self.cfg.name}: {what} of a latent cache is not supported "
                "(the handoff schema carries keys and values per head)"
            )
        if self.cfg.has_state_layers:
            raise ValueError(
                f"{self.cfg.name}: {what} of a recurrent state is not supported "
                "(the handoff schema carries keys and values per token, and no "
                "prefix of a state can be cut from it)"
            )

    def end_session(self, session_id: str) -> None:
        with self._mu:
            self._drop(session_id)

    def fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        """Seed a new session's lane with the parent lane's first
        `prefix_len` KV slots (prefix caching on the batched path). False on
        any miss — unknown/short parent, no claimable lane — and the caller
        falls back to a full prefill.

        Paged mode maps the parent's full blocks READ-ONLY into the child
        (refcounted, CoW on divergence) and queues a private copy of only
        the partial tail block — O(1) device work instead of a prefix-
        sized buffer copy."""
        if prefix_len <= 0 or self.cfg.is_block_diffusion or self.cfg.has_state_layers:
            # a model generated by blocks forks nothing yet; a recurrent
            # state is the state after ALL of the parent's tokens
            return False
        with self._mu:
            if self._session_adapter.get(parent_session_id):
                # the fork flow admits the child WITHOUT an adapter key:
                # decoding adapter-built KV with the base adapter would
                # diverge silently — the clean False re-prefills instead
                return False
        if self.pool is not None:
            with self._mu:
                plane = self._sessions.get(parent_session_id)
                if (
                    plane is None
                    or self.engine.lengths[plane] < prefix_len
                    or new_session_id in self._sessions
                ):
                    return False
                try:
                    lane = self._lane_for(
                        new_session_id, new_ok=True,
                        protect=(parent_session_id,),
                    )
                except CapacityError:
                    return False
                try:
                    self.pool.fork_lane(
                        plane, lane, prefix_len,
                        owner=f"session {new_session_id}, lane {lane}",
                    )
                except BufferError:
                    self._drop(new_session_id)
                    return False
                self.engine.lengths[lane] = prefix_len
                self._lane_hi[lane] = prefix_len
            return True
        with self._dev_lock:  # lock order matches _prefill_solo
            with self._mu:
                plane = self._sessions.get(parent_session_id)
                if (
                    plane is None
                    or self.engine.lengths[plane] < prefix_len
                    or new_session_id in self._sessions
                ):
                    return False
                parent_hi = max(
                    self._lane_hi.get(plane, 0), self.engine.lengths[plane]
                )
                # the copy reads the parent's lane: nothing run ahead of its
                # session is left standing beside it (the mark above counts
                # the row: a ring has given up its oldest slot to it)
                self._forget(plane)
                if (
                    self.engine.cache.k_loc is not None
                    and parent_hi - prefix_len > RING_MARGIN
                ):
                    # ring KV: the parent ran past the margin since the fork
                    # point — its sliding-layer rings hold slots whose stale
                    # data would alias into the child's windows (same guard
                    # as the stage executor's fork_session)
                    return False
                try:
                    lane = self._lane_for(
                        new_session_id, new_ok=True,
                        protect=(parent_session_id,),
                    )
                except CapacityError:
                    return False
                # mark the child in flight: between here and the length
                # write below, _mu is released while the device copy runs —
                # an un-inflight child could be LRU-evicted by a concurrent
                # claim and its lane handed to another session mid-fork
                self._inflight[new_session_id] = 1
            try:
                m = min(bucket_len(prefix_len), self.max_len)
                self.engine.fork_lane(plane, lane, m)
                with self._mu:
                    self.engine.lengths[lane] = prefix_len
                    # the child's rings carry the parent's stale slots:
                    # use the parent_hi validated under the SAME _mu hold
                    # as the margin check (a re-read here would race a
                    # parent restart/eviction resetting its mark while the
                    # device copy still took the OLD ring content)
                    self._lane_hi[lane] = parent_hi
            finally:
                with self._mu:
                    self._inflight.pop(new_session_id, None)
                    if self._dying.get(lane) == new_session_id:
                        # ended mid-fork (end_session deferred the free)
                        del self._dying[lane]
                        self._free_lane(lane)
        return True

    def export_sessions(self, only: "str | None" = None):
        """Snapshot live sessions' lane KV for migration/shutdown handoff
        (the shared runtime/handoff schema), so runtime/node.py's
        _export_and_handoff and /import_session work unchanged for
        --batch-lanes replicas. `only` exports a single session (the
        deliberate prefill->decode handoff path)."""
        self._refuse_latent("handoff export")
        out = []
        with self._dev_lock:  # quiesce the device first
            if self.pool is not None:
                # apply queued CoW copies BEFORE reading the pools: a
                # session forked/rolled-back since the last dispatch still
                # has its private-copy blocks pending — exporting through
                # the repointed table would ship uninitialized blocks
                self._sync_paged()
            self._export_locked(out, only)
        return out

    def _export_locked(self, out, only) -> None:
        from inferd_tpu.runtime import handoff

        with self._mu:
            for sid, lane in list(self._sessions.items()):
                if only is not None and sid != only:
                    continue
                n = self.engine.lengths[lane]
                if n == 0:
                    continue
                self._forget(lane)  # the snapshot ends at the lane's length
                if self.pool is not None:
                    # dense materialization through the block table, ONE
                    # device gather per session's chain (never a whole-pool
                    # host pull — the pool is fleet capacity, the session
                    # is a handful of blocks); the wire schema stays the
                    # dense one, so paged/dense replicas interchange
                    # sessions freely
                    nb = self.pool.blocks_for(n)
                    chain = self.pool.table[lane, :nb]
                    cache = self.engine.cache
                    kd = np.asarray(cache.k[:, chain])
                    vd = np.asarray(cache.v[:, chain])
                    layers = kd.shape[0]
                    kd = kd.reshape(
                        layers, nb * self.pool.block_size, *kd.shape[3:]
                    )[:, None, :n]
                    vd = vd.reshape(
                        layers, nb * self.pool.block_size, *vd.shape[3:]
                    )[:, None, :n]
                    out.append((sid, self._stamp_adapter(
                        sid, handoff.encode(kd, vd, n, None, None, None)
                    )))
                    continue
                kl = vl = hi = None
                if self.engine.cache.k_loc is not None:
                    kl = np.asarray(self.engine.cache.k_loc[:, lane : lane + 1])
                    vl = np.asarray(self.engine.cache.v_loc[:, lane : lane + 1])
                    hi = max(self._lane_hi.get(lane, 0), n)
                lanes = lambda a: wire_heads(np.asarray(a[:, lane : lane + 1, :n]), self.cfg)
                out.append((sid, self._stamp_adapter(sid, handoff.encode(
                    lanes(self.engine.cache.k), lanes(self.engine.cache.v), n, kl, vl, hi,
                ))))

    def _stamp_adapter(self, sid: str, payload: Dict[str, Any]):
        """Ride the session's adapter binding on its handoff payload
        (caller holds self._mu): the importer/standby must rebind the
        tenant's adapter or DECLINE — an adopted tenant session silently
        resuming on the base weights would be exactly the tenant
        corruption the admission path rejects loudly. Base sessions gain
        no key (payloads byte-identical to pre-adapter)."""
        name = self._session_adapter.get(sid)
        if name is not None:
            payload["adapter"] = name
        return payload

    def session_lengths(self) -> Dict[str, int]:
        """{session_id: committed KV length} — the cheap frontier surface
        the standby replicator polls (runtime/repl.SessionReplicator)."""
        with self._mu:
            return {
                sid: int(self.engine.lengths[lane])
                for sid, lane in self._sessions.items()
                if self.engine.lengths[lane] > 0
            }

    def export_session_delta(self, session_id: str, since: int):
        """Incremental flavor of export_sessions for standby replication
        (handoff schema + a "start" key; None = nothing new). PAGED
        lanes ship exactly the IMMUTABLE FULL BLOCKS past the frontier —
        the partial tail block is still being written and re-ships once
        it fills, so the standby's RPO is bounded by block_size on top
        of the tick interval. Dense lanes ship the slab delta directly
        (rings whole, like the stage executor's sibling)."""
        from inferd_tpu.runtime import handoff
        from inferd_tpu.runtime.repl import START_KEY

        self._refuse_latent("standby export")
        since = max(0, int(since))
        # cheap nothing-to-ship early-out under _mu alone: the common
        # replication tick (every resident session, every interval) must
        # not contend on the decode hot path's device lock just to
        # discover no block/slot completed since the last ship
        with self._mu:
            lane = self._sessions.get(session_id)
            if lane is None:
                return None
            n = int(self.engine.lengths[lane])
            if self.pool is not None:
                bs = self.pool.block_size
                if (n // bs) * bs <= (since // bs) * bs:
                    return None
            elif n <= since:
                return None
        with self._dev_lock:
            if self.pool is not None:
                self._sync_paged()  # queued CoW copies must land first
            with self._mu:
                lane = self._sessions.get(session_id)
                if lane is None:
                    return None
                n = int(self.engine.lengths[lane])
                if self.pool is not None:
                    bs = self.pool.block_size
                    if since % bs:
                        # a foreign frontier (e.g. adopted mid-stream from
                        # a dense peer): restart block-aligned
                        since = (since // bs) * bs
                    end = (n // bs) * bs
                    if end <= since:
                        return None
                    chain = self.pool.table[lane, since // bs: end // bs]
                    cache = self.engine.cache
                    # one device gather of just this session's new blocks
                    # (never a whole-pool host pull — export_sessions'
                    # discipline): [L, nb, bs, ...] -> [L, 1, nb*bs, ...]
                    kd = np.asarray(cache.k[:, chain])
                    vd = np.asarray(cache.v[:, chain])
                    layers = kd.shape[0]
                    kd = kd.reshape(layers, end - since, *kd.shape[3:])[:, None]
                    vd = vd.reshape(layers, end - since, *vd.shape[3:])[:, None]
                    payload = self._stamp_adapter(
                        session_id,
                        handoff.encode(kd, vd, end, None, None, None),
                    )
                    payload[START_KEY] = since
                    return payload
                if n <= since:
                    return None
                kl = vl = hi = None
                if self.engine.cache.k_loc is not None:
                    kl = np.asarray(self.engine.cache.k_loc[:, lane: lane + 1])
                    vl = np.asarray(self.engine.cache.v_loc[:, lane: lane + 1])
                    hi = max(self._lane_hi.get(lane, 0), n)
                lanes = lambda a: wire_heads(np.asarray(a[:, lane: lane + 1, since:n]), self.cfg)
                payload = self._stamp_adapter(session_id, handoff.encode(
                    lanes(self.engine.cache.k), lanes(self.engine.cache.v), n, kl, vl, hi,
                ))
                payload[START_KEY] = since
                return payload

    def import_session(self, session_id: str, payload: Dict[str, Any]) -> bool:
        """Adopt a migrated session into a free lane (same-model batched
        replicas; schema/shape mismatches reject cleanly — the shared
        runtime/handoff validator fails closed BEFORE a lane is claimed)."""
        import jax.numpy as jnp

        from inferd_tpu.core.cache import KVCache
        from inferd_tpu.runtime import handoff

        self._refuse_latent("import")
        ring = self.engine.cache.k_loc is not None
        # validate against the spec-capped capacity: an imported session
        # longer than cap would break the verify-chunk headroom contract
        dec = handoff.decode(
            payload, self.cfg, self.cfg.num_layers, 0, self.cap,
            want_ring=ring,
        )
        if dec is None:
            return False
        # a tenant session's KV was built WITH its adapter: rebind here
        # (hot-loading if needed — before any executor lock) or DECLINE,
        # so the session lands on a replica that can serve it instead of
        # silently continuing on the base weights. The fail-closed False
        # degrades to the client's full restart, whose first chunk
        # re-states the adapter key.
        ad_name = payload.get("adapter")
        if ad_name is not None:
            if self.adapters is None:
                return False
            try:
                self.adapters.acquire(str(ad_name))
            except Exception:
                return False
            ad_name = str(ad_name)
        k, v, n = dec["k"], dec["v"], dec["n"]
        k_loc, v_loc = dec["k_loc"], dec["v_loc"]
        if self.pool is not None:
            # _import_paged owns the acquired reference from here: its
            # early declines release it, its post-bind rollbacks release
            # through _drop
            return self._import_paged(session_id, k, v, n, ad_name)
        # the wire carries heads; dense lanes of a narrow head store rows
        k, v = (from_wire(a, self.cfg, uniform=not ring) for a in (k, v))
        with self._dev_lock, self._mu:
            if session_id in self._sessions:
                if ad_name is not None:
                    self.adapters.release(ad_name)
                return False
            try:
                lane = self._lane_for(session_id, new_ok=True)
            except CapacityError:
                if ad_name is not None:
                    self.adapters.release(ad_name)
                return False
            if ad_name is not None:
                # bound BEFORE the risky device writes: the rollback
                # path's _drop releases the reference with the session
                self._session_adapter[session_id] = ad_name
                self._lane_slot[lane] = self.adapters.slot_of(ad_name)
            try:
                t = min(k.shape[2], self.max_len)
                cache = self.engine.cache
                nk = cache.k.at[:, lane, :t].set(
                    jnp.asarray(k[:, 0, :t], cache.k.dtype)
                )
                nv = cache.v.at[:, lane, :t].set(
                    jnp.asarray(v[:, 0, :t], cache.v.dtype)
                )
                nkl, nvl = cache.k_loc, cache.v_loc
                if k_loc is not None:
                    nkl = cache.k_loc.at[:, lane].set(
                        jnp.asarray(k_loc[:, 0], cache.k_loc.dtype)
                    )
                    nvl = cache.v_loc.at[:, lane].set(
                        jnp.asarray(v_loc[:, 0], cache.v_loc.dtype)
                    )
                self.engine.cache = KVCache(
                    k=nk, v=nv, length=cache.length, k_loc=nkl, v_loc=nvl
                )
            except Exception:
                # rollback: a half-adopted session must not pin the lane
                # (the mesh path has the same guard)
                self._drop(session_id)
                return False
            self.engine.lengths[lane] = n
            self._lane_hi[lane] = dec["hi"]
        return True

    def _import_paged(self, session_id: str, k, v, n: int,
                      ad_name: "str | None" = None) -> bool:
        """Adopt a migrated session into pool blocks: allocate a chain,
        reshape the dense [L, 1, n, ...] snapshot into block granularity,
        scatter it into the pools in one update. `ad_name`: the tenant
        adapter the caller already acquire()d — bound to the lane on
        claim (so _drop rollbacks release it), released here on the
        pre-claim declines."""
        import jax.numpy as jnp

        with self._dev_lock, self._mu:
            if session_id in self._sessions:
                if ad_name is not None:
                    self.adapters.release(ad_name)
                return False
            try:
                lane = self._lane_for(session_id, new_ok=True)
            except CapacityError:
                if ad_name is not None:
                    self.adapters.release(ad_name)
                return False
            if ad_name is not None:
                self._session_adapter[session_id] = ad_name
                self._lane_slot[lane] = self.adapters.slot_of(ad_name)
            try:
                self.pool.ensure(
                    lane, n, owner=f"session {session_id}, lane {lane}"
                )
            except BufferError:
                self._drop(session_id)
                return False
            try:
                bs = self.pool.block_size
                nb = self.pool.blocks_for(n)
                pad = [(0, 0), (0, nb * bs - n), (0, 0), (0, 0)]
                layers = k.shape[0]
                kp = np.pad(k[:, 0, :n], pad).reshape(
                    layers, nb, bs, *k.shape[3:]
                )
                vp = np.pad(v[:, 0, :n], pad).reshape(
                    layers, nb, bs, *v.shape[3:]
                )
                chain = jnp.asarray(self.pool.table[lane, :nb])
                cache = self.engine.cache
                dt = cache.k.dtype
                self.engine.cache = type(cache)(
                    k=cache.k.at[:, chain].set(jnp.asarray(kp, dt)),
                    v=cache.v.at[:, chain].set(jnp.asarray(vp, dt)),
                    table=cache.table, length=cache.length,
                )
            except Exception:
                self._drop(session_id)
                return False
            self.engine.lengths[lane] = n
            self._lane_hi[lane] = n
        return True

    # -- prefix caching (paged mode) -----------------------------------------

    def pin_prefix(self, prefix_ids) -> int:
        """Prefill `prefix_ids` once into pool blocks and PIN them
        (resident until unpinned; later sessions map the region read-only
        instead of recomputing it) — the Engine pin store generalized to
        refcounted pool blocks. Returns the pinned token coverage."""
        if self.cfg.has_state_layers:
            raise ValueError(
                f"{self.cfg.name}: pin_prefix: a recurrent state cannot be shared "
                "at a prefix (no snapshot of it is kept)"
            )
        if self.pool is None:
            raise ValueError("pin_prefix needs paged KV (--paged-kv)")
        ids = [int(t) for t in prefix_ids]
        if not ids:
            raise ValueError("prefix ids must be non-empty")
        keys = prefixlib.block_keys(ids, self.pool.block_size)
        sid = "__pin__" + (keys[-1].hex() if keys else "short")
        self.process(sid, {
            "tokens": [ids], "start_pos": 0, "real_len": len(ids),
        })
        with self._mu:
            self.pool.pin(keys)
        self.end_session(sid)
        return len(keys) * self.pool.block_size

    def unpin_prefix(self, prefix_ids) -> None:
        if self.pool is None:
            return
        with self._mu:
            self.pool.unpin(prefixlib.block_keys(
                [int(t) for t in prefix_ids], self.pool.block_size
            ))

    def block_stats(self) -> "Dict[str, Any] | None":
        """Block-pool gauges for obs.devtel (None on the dense layout)."""
        if self.pool is None:
            return None
        with self._mu:
            return self.pool.block_stats()

    def prefix_digest(self) -> "Dict[str, Any] | None":
        """Gossip-ready digest of the pool's hot prefix index
        (core.prefix.make_digest; the stage_batch contract) — the
        whole-model executor always has token-keyed prefixes, so only
        dense mode and an empty index return None (key omitted from
        gossip, never an empty decoy)."""
        if self.pool is None:
            return None
        with self._mu:
            keys = self.pool.digest_keys(prefixlib.DIGEST_GOSSIP_KEYS)
            bs = self.pool.block_size
        if not keys:
            return None
        return prefixlib.make_digest(keys, bs)

    def stats(self) -> Dict[str, Any]:
        """Batching effectiveness for /stats: lane occupancy + how many
        decode steps actually coalesced (tok-per-weight-read is the whole
        point of this executor)."""
        out = self.spec_stats()
        with self._mu:
            out.update(
                mode="batched",
                lanes=self.engine.lanes,
                lanes_busy=self.engine.lanes - len(self.engine.free),
                prefill_tokens=self.prefill_tokens,
                sampled_rows=self.sampled_rows,
                logit_rows=self.logit_rows,
                ahead_rows=self.ahead_rows,
                ahead_claimed=self.ahead_claimed,
                ahead_dropped=self.ahead_dropped,
                hops_inline=self.hops_inline,
                hops_pooled=self.hops_pooled,
                **self._admit.stats(),
                **self._batcher.stats(),
            )
            # how a token's keys lie: "rows" (all kv heads in one row: a head
            # narrower than a tile on dense lanes), "heads" or "latent"
            out["kv_layout"] = (
                "heads" if self.pool is not None else self.engine.cache.layout(self.cfg)
            )
            if self.pool is not None:
                out["paged"] = self.pool.block_stats()
            else:  # what the lanes' cache really allocates
                state = self.engine.cache.state_bytes
                nbytes = self.engine.cache.nbytes - state  # the buffers that grow with tokens
                out["kv_cache_bytes"] = nbytes
                out["kv_bytes_per_token"] = nbytes // (self.engine.lanes * self.max_len)
                if self.cfg.has_state_layers:  # what a session holds whatever its length
                    out["state_bytes"] = state
                    out["state_bytes_per_session"] = state // self.engine.lanes
                if self._read is not None:
                    out["kv"] = dict(self._read)
                if self.engine.cache.k_loc is not None:  # what a session's windowed
                    #   layers hold whatever its length: their rings
                    out["kv"].update(
                        window=int(self.cfg.sliding_window),
                        ring_bytes_per_session=2 * int(self.engine.cache.k_loc.nbytes)
                        // self.engine.lanes,
                    )
            if self.cfg.hc_mult:  # what a token's residual stream is between two sublayers
                out["stream_bytes_per_token"] = (
                    self.cfg.hc_mult * self.cfg.hidden_size * self.cfg.jnp_dtype.itemsize)
            if self._moe is not None:
                out["moe"] = dict(self._moe, experts=self.cfg.router_width,
                                  experts_held=self.cfg.num_experts)
                if self.cfg.moe_latent_size:  # the width the routed experts work in
                    out["moe"]["latent_size"] = self.cfg.moe_latent_size
            if self._diffusion is not None:
                out["diffusion"] = dict(self._diffusion)
            if self.adapters is not None:
                out["adapters"] = self.adapters.stats()
            return out

    # -- node sweep surface (runtime/node.py:_sweep_loop) --------------------

    @property
    def sessions(self):
        return self

    def sweep(self) -> int:
        if not self._mu.acquire(blocking=False):
            return 0
        try:
            now = time.monotonic()
            stale = [
                s
                for s, t in self._last_used.items()
                if now - t > self.ttl_s and not self._inflight.get(s)
            ]
            for s in stale:
                self._drop(s)
            return len(stale)
        finally:
            self._mu.release()

    def ids(self):
        """Live session ids (gossip session-location advertising)."""
        with self._mu:
            return list(self._sessions)

    def kv_occupancy(self) -> float:
        """Fraction of the KV budget in use — the serving memory-pressure
        signal obs.devtel gauges per scrape. Paged: blocks used / blocks
        total; dense: filled positions / lanes x max_len."""
        with self._mu:
            if self.pool is not None:
                total = self.pool.num_blocks - 1
                return self.pool.blocks_used / float(total) if total else 0.0
            return sum(self.engine.lengths) / float(
                self.engine.lanes * self.max_len
            )

    def __len__(self) -> int:
        return len(self._sessions)

    def __contains__(self, session_id: str) -> bool:
        return session_id in self._sessions
