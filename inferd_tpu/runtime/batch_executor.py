"""Continuous-batching stage executor: concurrent sessions' decode steps
coalesce into ONE device step.

The reference serves strictly one request at a time per node (a lone
pipeline pass per token, /root/reference/petals/send_message.py:27-49 /
server.py:25-54); every session re-reads all the weights per token. This
executor keeps the node's `/forward` + client-side-sampling contract but
maps sessions to lanes of core.batch.BatchedEngine and batches the
single-token decode steps of whichever sessions arrive within a short
window — aggregate tok/s then scales with concurrency instead of dividing
by it (weights are read once per BATCHED step).

Concurrency design (process() runs on the node's worker thread pool):
  * decode steps (real_len == 1 at the session's frontier) enqueue into a
    pending batch; the FIRST arrival becomes the flusher — it waits out a
    step that is still running, then (no lock held) for the lanes the
    last two steps served, takes the device lock, runs one batched step
    for every lane pending AT THAT MOMENT, and distributes each lane's
    logits to its waiting thread (runtime/window.py, formation);
  * prefill chunks (multi-token or unknown session) run solo under the
    same device lock (per-lane cache writes, other lanes untouched);
  * whole-model executor: is_first and is_last (tokens in, last-token
    logits out) — like MeshExecutor it hosts a 1-stage swarm topology.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from inferd_tpu.config import ModelConfig
from inferd_tpu.core.batch import BatchedEngine
from inferd_tpu.core.cache import RING_MARGIN, sync_paged
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.obs.devtel import program_name
from inferd_tpu.obs.events import emit_safely
from inferd_tpu.runtime.adapters import AdapterBindingMixin
from inferd_tpu.runtime.spec_serving import SpecForkMiss, SpecServing
from inferd_tpu.runtime.window import WindowedBatcher
from inferd_tpu.utils import lockwatch

Params = Any


class CapacityError(RuntimeError):
    """All lanes are serving in-flight requests — transient backpressure
    (the node maps this to a retryable 503, unlike deterministic KV
    overflow which is a 409)."""


class BatchedExecutor(SpecServing, AdapterBindingMixin):
    """Whole-model, lane-per-session executor with windowed decode batching.

    Node executor contract (runtime/node.py): process(session_id, payload)
    -> {"logits": [1, V], ...}; end_session(session_id).
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
        # cumulative expert-routing counters over decode steps (/stats
        # `executor` `moe.*`); None where the decode program returns no
        # routing (no experts, or a paged / windowed layout)
        self._moe = (
            dict(steps=0, assignments=0, assignments_hottest=0, experts_touched=0)
            if self.engine.routes else None
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
        lane = self._sessions.get(session_id)
        if lane is not None:
            self._last_used[session_id] = time.monotonic()
            return lane
        if not new_ok:
            raise ValueError(
                f"session {session_id}: unknown session resumed mid-stream "
                "(cache evicted or node restarted)"
            )
        if not self.engine.free:
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
        return lane

    def _drop(self, session_id: str) -> None:
        lane = self._sessions.pop(session_id, None)
        self._last_used.pop(session_id, None)
        self._release_adapter_locked(session_id)
        if lane is None:
            return
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

    def _free_lane(self, lane: int) -> None:
        """Return a lane to the free list (under self._mu). Paged: the
        chain frees per-block — cached/pinned prefix blocks survive via
        their index references."""
        self.engine.lengths[lane] = 0
        self._lane_slot[lane] = 0  # back to the base adapter
        if self.pool is not None:
            self.pool.release_lane(lane)
        self.engine.free.append(lane)

    # -- executor contract ---------------------------------------------------

    def process(self, session_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        toks = np.asarray(payload["tokens"], dtype=np.int32)
        if toks.ndim != 2 or toks.shape[0] != 1:
            raise ValueError(f"batched stage expects tokens [1, S], got {toks.shape}")
        start_pos = int(payload.get("start_pos", 0))
        real_len = int(payload.get("real_len", toks.shape[1]))

        acquired = self._resolve_adapter(session_id, payload, start_pos)
        try:
            return self._process_inner(
                session_id, payload, toks, start_pos, real_len, acquired
            )
        except Exception:
            # an admission that died before _bind_adapter_locked consumed
            # the reference must give it back, or the slot leaks a
            # refcount and can never be evicted
            if acquired is not None and acquired[1]:
                self.adapters.release(acquired[0])
            raise

    def _process_inner(self, session_id: str, payload: Dict[str, Any],
                       toks, start_pos: int, real_len: int, acquired):
        # one token at an established frontier; anything else is a prefill
        decode = real_len == 1 and start_pos > 0
        with self._mu:
            if self._inflight.get(session_id):
                # a duplicate/replayed request racing the original would
                # pass the frontier check and double-advance the lane
                raise ValueError(
                    f"session {session_id}: concurrent request (one step at "
                    "a time per session)"
                )
            lane = self._lane_for(session_id, new_ok=start_pos == 0)
            owner = f"session {session_id}, lane {lane}"
            have = self.engine.lengths[lane]
            if start_pos == 0 and have:
                # session restart under the same id: reset the lane
                self.engine.lengths[lane] = 0
                self._lane_hi[lane] = 0
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
            if start_pos != have:
                if not 0 < start_pos < have:
                    raise ValueError(
                        f"session {session_id}: start_pos {start_pos} != cache "
                        f"length {have} (out-of-order chunk)"
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
            if not decode:
                # inside a prefill: no decode step waits for this lane
                # until one has served it again
                self._batcher.unexpect(lambda p, _lane=lane: p[0] == _lane)

        try:
            if decode:
                from inferd_tpu.runtime.executor import parse_kstep

                ks = parse_kstep(payload, self.cap - start_pos)
                if ks is not None:
                    # multi-step fused decode: K on-device-sampled tokens
                    # per dispatch; co-arrived K-step lanes fuse into one
                    # K-step scan (see _run_decode_batch)
                    res = self._decode_batched(
                        session_id, lane, int(toks[0, 0]), ks
                    )
                    return {**res, "start_pos": start_pos}
                logits = self._decode_batched(session_id, lane, int(toks[0, 0]))
                saved = 0
            else:
                logits, saved = self._prefill_solo(
                    session_id, lane, toks, start_pos, real_len
                )
        finally:
            with self._mu:
                self._inflight.pop(session_id, None)
                if self._dying.get(lane) == session_id:  # ended mid-request
                    del self._dying[lane]
                    self._free_lane(lane)
        return {
            "logits": logits[None, :],
            "real_len": real_len,
            "start_pos": start_pos,
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
                      start: int, n: int):
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
                        if pos + c == end:
                            # the chunk whose logits are the response: its
                            # `device` span closes below, once the lock is
                            # released and the result is ready (earlier
                            # chunks of a --prefill-chunk admission are
                            # dispatched without a wait and get no span)
                            dev.enter_context(tracelib.region(
                                self.tracer, "device", kind="prefill",
                                tokens=c, cobatch=1, program=program_name(fn),
                            ))
                        self.engine.cache, logits = fn(
                            self.engine.params, self.engine.cache,
                            jnp.asarray(padded),
                            jnp.int32(lane), jnp.int32(pos), jnp.int32(c),
                            ads=ads,
                        )
                    # advance the lane BEFORE releasing the device lock: a
                    # flusher snapshots lengths under the same lock order
                    # (_dev_lock, _mu), so it can never scatter a decode write
                    # over these fresh rows at the stale position
                    with self._mu:
                        self.engine.lengths[lane] = pos + c  # real tokens only
                        self.prefill_tokens += c
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
        # ONE boundary transfer: only the LAST chunk's logits are the
        # response — mid-chunk logits never leave the device
        with tracelib.region(self.tracer, "copy_out") as at:
            out = np.asarray(logits, np.float32)
            at["bytes"] = out.nbytes
        return out, saved

    def _decode_batched(self, session_id: str, lane: int, token: int, ks=None):
        return self._batcher.submit((lane, token, ks))

    def _run_decode_batch(self, _empty) -> None:
        """Flush callback: ONE batched device step for every lane whose
        entry is pending when the device lock is acquired
        (runtime/window.py calls this with an empty list and no locks
        held, once its formation wait is over; an entry that arrived
        while the previous step or a prefill held the device is drained
        with the rest). A drain that finds nothing (every waiting entry
        was invalidated meanwhile) runs no program and counts no step.

        Entries partition into the classic logits contract (client-side
        sampling, one token per dispatch) and multi-step fused decode
        (`ks` payload from parse_kstep: K on-device-sampled tokens per
        dispatch). K-step entries sharing a sampling config fuse into ONE
        K-step scan (models/qwen3.decode_k via the engine's
        _decode_k_serve) with K = the group's minimum budget-clamped
        request — co-batched lanes decode K steps per window when every
        lane has >= K budget, degrading toward K=1 at boundaries. A lane
        whose `eos` fires mid-window deactivates in-graph; its result
        carries only the really-committed tokens.

        Failure isolation is per DISPATCH: a window can run one legacy
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
        import jax.numpy as jnp

        from inferd_tpu.runtime.executor import (
            cache_intact, fuse_kstep_group, kstep_hi,
        )

        poisoned: Optional[Exception] = None
        with self._dev_lock:
            # the batcher stamps each entry's lock_wait and batch_wait
            entries = self._batcher.drain_pending()
            legacy = [e for e in entries if e.payload[2] is None]
            kstep = [e for e in entries if e.payload[2] is not None]
            if legacy:
                try:
                    with self._mu:
                        lens = list(self.engine.lengths)  # snapshot under _mu
                        ids = list(self._lane_slot)
                    ads = self._ads(ids)
                    toks = [0] * self.engine.lanes
                    active = [False] * self.engine.lanes
                    for e in legacy:
                        lane, token, _ks = e.payload
                        toks[lane] = token
                        active[lane] = True
                    routed = None  # the paged program returns no routing
                    if self.pool is not None:
                        self.engine.cache, logits = (
                            self.engine._decode_logits_paged(
                                self.engine.params, self._sync_paged(),
                                jnp.asarray(toks, jnp.int32),
                                jnp.asarray(lens, jnp.int32),
                                jnp.asarray(active),
                                ads=ads,
                            )
                        )
                    else:
                        fn = self.engine._decode_logits
                        with tracelib.region(
                            self.tracer, "device", kind="decode",
                            tokens=len(legacy), cobatch=len(legacy),
                            program=program_name(fn),
                        ):
                            self.engine.cache, logits, routed = fn(
                                self.engine.params, self.engine.cache,
                                jnp.asarray(toks, jnp.int32),
                                jnp.asarray(lens, jnp.int32),
                                ads=ads,
                            )
                            logits.block_until_ready()
                    with tracelib.region(self.tracer, "copy_out") as at:
                        out = np.asarray(logits, np.float32)
                        at["bytes"] = out.nbytes
                    if routed is not None:  # came out with the logits: no wait
                        self._count_routing(
                            np.asarray(routed)[:, [e.payload[0] for e in legacy]]
                        )
                    with self._mu:
                        for e in legacy:
                            self.engine.lengths[e.payload[0]] += 1
                    for e in legacy:
                        e.result = out[e.payload[0]]
                except Exception as exc:
                    for e in legacy:
                        e.error = exc
                    # the drain counted every live entry as served; net
                    # failed entries to zero so /stats batched_tokens
                    # stays token-true
                    self._batcher.n_served -= len(legacy)
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
                    self._batcher.n_served -= len(grp)  # see legacy note
                    continue
                try:
                    with self._mu:
                        lens = list(self.engine.lengths)
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
                            n = int(n_new[lane])  # jaxlint: disable=J003 -- n_new is a HOST array (fuse_kstep_group materialized it)
                            old = self.engine.lengths[lane]
                            self.engine.lengths[lane] = old + n
                            self._lane_hi[lane] = max(
                                self._lane_hi.get(lane, 0),
                                kstep_hi(old, n, kg),
                            )
                    served_tokens = 0
                    for e in grp:
                        lane = e.payload[0]
                        n = int(n_new[lane])  # jaxlint: disable=J003 -- host array
                        served_tokens += n
                        e.result = {
                            "tokens": [seq[:n, lane].tolist()],  # jaxlint: disable=J003 -- host array row unpack, no device sync
                            "real_len": n,
                            "decode_steps": kg,
                            "key": nkeys[lane].tolist(),  # jaxlint: disable=J003 -- host array row unpack, no device sync
                        }
                    # token-true stats: the drain counts one
                    # served unit per ENTRY; a K-step entry really served
                    # n tokens — /stats batched_tokens and mean_batch
                    # must reflect tokens, not dispatches
                    self._batcher.n_served += served_tokens - len(grp)
                except Exception as exc:
                    for e in grp:
                        e.error = exc
                    self._batcher.n_served -= len(grp)  # see legacy note
                    if not cache_intact(self.engine.cache):
                        poisoned = exc

    def _count_routing(self, chosen: np.ndarray) -> None:
        """The `moe.*` counters of one decode step from the experts its
        live rows chose, `chosen` [sparse layers, live rows, K]: the
        assignments made, those that fell on each layer's most loaded
        expert, and the distinct experts hit, each summed over the layers."""
        per_layer = [np.bincount(layer.ravel(), minlength=self.cfg.num_experts)
                     for layer in chosen]
        with self._mu:
            self._moe["steps"] += 1
            self._moe["assignments"] += int(chosen.size)
            self._moe["assignments_hottest"] += int(sum(c.max() for c in per_layer))
            self._moe["experts_touched"] += int(sum((c > 0).sum() for c in per_layer))

    def _refuse_latent(self, what: str) -> None:
        if self.cfg.is_mla:
            raise ValueError(
                f"{self.cfg.name}: {what} of a latent cache is not supported "
                "(the handoff schema carries keys and values per head)"
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
        if prefix_len <= 0:
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
                out.append((sid, self._stamp_adapter(sid, handoff.encode(
                    np.asarray(self.engine.cache.k[:, lane : lane + 1, :n]),
                    np.asarray(self.engine.cache.v[:, lane : lane + 1, :n]),
                    n, kl, vl, hi,
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
                payload = self._stamp_adapter(session_id, handoff.encode(
                    np.asarray(self.engine.cache.k[:, lane: lane + 1, since:n]),
                    np.asarray(self.engine.cache.v[:, lane: lane + 1, since:n]),
                    n, kl, vl, hi,
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

    def anatomy_target(self) -> Dict[str, Any]:
        """Live step-anatomy inputs for the continuous profiling plane
        (obs.prof.LiveAnatomy): this executor's REAL serving weights
        (already quantized/LoRA-merged at load) and paged/dense cache
        config, with ctx tracking the current decode frontier — rounded
        UP to a 64-token bucket so the scan shapes (and their XLA
        compilations) stay stable as the frontier drifts token by token.
        Whole-model executor: every device phase applies."""
        with self._mu:
            ctx = max(self.engine.lengths, default=0)
        ctx = -(-max(ctx, 32) // 64) * 64  # 64-token shape bucket
        return {
            "cfg": self.cfg,
            "params": self.engine.params,
            "phases": (
                "embed", "attention", "mlp", "lm_head", "sampling",
                "kv_write",
            ),
            "ctx": min(ctx, max(self.max_len - 64, 32)),
            "batch": 1,
            "paged_block_size": (
                self.pool.block_size if self.pool is not None else 0
            ),
            # full-co-batch ceiling basis for roofline.live_frac: the
            # replica's aggregate tok/s is judged against what the chip
            # allows at ALL lanes, not one (obs.prof.AnatomyTarget)
            "ceiling_batch": self.engine.lanes,
        }

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
                **self._batcher.stats(),
            )
            if self.pool is not None:
                out["paged"] = self.pool.block_stats()
            else:  # what the lanes' cache really allocates
                nbytes = self.engine.cache.nbytes
                out["kv_cache_bytes"] = nbytes
                out["kv_bytes_per_token"] = nbytes // (self.engine.lanes * self.max_len)
            if self._moe is not None:
                out["moe"] = dict(self._moe, experts=self.cfg.num_experts)
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
