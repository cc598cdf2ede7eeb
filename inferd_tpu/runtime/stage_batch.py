"""Continuous batching for a PIPELINE STAGE: concurrent sessions' decode
steps through this stage run as ONE device step.

The swarm pipeline path — the paper's headline capability — served
concurrent sessions one at a time: Qwen3StageExecutor.process is hardwired
to batch=1, so every /forward ran the stage forward per session under the
device lock and aggregate tok/s DIVIDED by concurrency. This executor is
the stage-level sibling of runtime/batch_executor.BatchedExecutor (whole
model, one node) and core/batch.BatchedEngine (library layer): sessions
map to LANES of one shared [layers, lanes, max_len, ...] stage KV cache,
and single-token decode steps from whichever sessions co-arrive stack into
one jitted [lanes, 1, H] stage forward — weights are read once per batched
step instead of once per session per token (Orca-style iteration-level
batching, Yu et al. OSDI '22, applied per pipeline stage a la Petals'
server-side cross-client batching).

Division of labor with runtime/node.py: the NODE owns the arrival window
(runtime/window.WindowedBatcher) and the coalesced relay of co-batched
results; this executor owns lanes, admission, and the batched device step
(`process_batch`). `process()` keeps the single-session executor contract
(prefill chunks run per-lane; a solo decode step is a batch of one), so
warmup, chain mode, and non-windowed callers work unchanged.

Concurrency protocol (mirrors BatchedExecutor): `_mu` guards lane/session
bookkeeping, `_dev_lock` serializes device steps; a session is marked
in-flight for the duration of its step so LRU eviction/teardown can never
hand its lane to a new claimant while a stale write is pending (teardown
mid-step defers the lane free until the step drains — `_dying`).
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from inferd_tpu.config import ModelConfig
from inferd_tpu.core.cache import (
    RING_MARGIN, BlockPool, KVCache, PagedKVCache, sync_paged,
)
from inferd_tpu.core import prefix as prefixlib
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.obs.events import emit_safely
from inferd_tpu.parallel.stages import StageSpec
from inferd_tpu.runtime.adapters import AdapterBindingMixin
from inferd_tpu.utils import lockwatch

Params = Any


class BatchedStageExecutor(AdapterBindingMixin):
    """Lane-slotted multi-session executor for one pipeline stage.

    Node executor contract (runtime/node.py): process(session_id, payload)
    -> {"hidden": [1, S, H]} or {"logits": [1, V]} (+ start_pos/real_len);
    end_session(session_id). Extra surface: process_batch(items) — the
    node's window flush callback — runs every item's decode step in ONE
    device dispatch and returns per-item results (exceptions per item,
    never batch-wide, so one bad session cannot fail its co-batch).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        spec: StageSpec,
        stage_params: Params,
        lanes: int = 8,
        max_len: int = 4096,
        session_ttl_s: float = 600.0,
        block_size: int = 0,
        kv_blocks: int = 0,
        prefill_chunk: int = 0,
        adapters=None,
    ):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.spec = spec
        # one host->device transfer, here: a stage checkpoint loads as numpy
        # (parallel.stages.load_stage_checkpoint), and numpy leaves handed to
        # a jit are copied to the device again on EVERY call — the whole
        # model per token on a chip. Arrays already on a device stay put.
        self.params = jax.device_put(stage_params)
        self.lanes = lanes
        self.max_len = max_len
        self.ttl_s = session_ttl_s
        # multi-tenant LoRA registry (runtime/adapters.AdapterRegistry;
        # None = single-model serving): the registry holds THIS STAGE'S
        # layer slice of each adapter, sessions bind slots at admission,
        # and every co-batched dispatch gathers per-lane slot ids into
        # the unmerged apply (ops.lora.lane_delta) — a mixed-adapter
        # window is still ONE device step
        self.adapters = adapters
        self._session_adapter: Dict[str, str] = {}
        self._lane_slot = [0] * lanes  # slot 0 = the zero base adapter
        # server-side chunked prefill: a prompt longer than this many
        # tokens ingests as multiple dispatches, RELEASING the device lock
        # between chunks so co-batched decode windows interleave instead
        # of head-of-line-blocking behind a 4k-token admission (0 = off)
        self.prefill_chunk = int(prefill_chunk)

        # paged KV (block_size > 0): lanes map to chains of fixed-size
        # blocks through a block table instead of dense [lanes, max_len]
        # rows — allocation/eviction/sharing become per-block, and pinned/
        # cached shared prefixes map read-only into many lanes (CoW on
        # first divergent write). Dense (block_size == 0) stays the
        # bit-identical classic layout.
        self.pool: Optional[BlockPool] = None
        if block_size > 0:
            self.pool = BlockPool(
                cfg, spec.num_layers, lanes, max_len,
                block_size=block_size, num_blocks=kv_blocks or None,
            )
            self.cache = self.pool.cache
        else:
            self.cache = KVCache.create(
                cfg, spec.num_layers, lanes, max_len,
                layer_offset=spec.start_layer,
            )
        self.lengths = [0] * lanes  # host mirror (no device sync per step)
        self.free: List[int] = list(range(lanes))
        # tokens actually computed by prefill dispatches (the shared-prefix
        # saving is visible as the gap vs tokens admitted)
        self.prefill_tokens = 0

        # serializes device steps; INFERD_FAIR_DEVLOCK swaps in the
        # ticketed FIFO mutex (lockwatch.FairDeviceLock), and lockwatch
        # wraps either in an order-checking proxy when instrumented
        self._dev_lock = lockwatch.make_lock(
            "dev", fair=lockwatch.fair_devlock_enabled()
        )
        # guards session/lane bookkeeping
        self._mu = lockwatch.make_lock("mu")
        self._sessions: Dict[str, int] = {}  # session -> lane
        self._last_used: Dict[str, float] = {}
        self._inflight: Dict[str, int] = {}
        self._dying: Dict[int, str] = {}  # lane -> ended session mid-step
        # ring replay safety: per-lane high-water mark of positions ever
        # written by the CURRENT claimant (same contract as
        # BatchedExecutor._lane_hi)
        self._lane_hi: Dict[int, int] = {}
        # set by the node so a dropped session's entries still waiting in
        # the arrival window fail fast (runtime/window.invalidate) instead
        # of racing the lane's next owner
        self.on_drop: Optional[Callable[[str], None]] = None
        # flight-recorder hook (the node wires its journal's emit):
        # lane.evict events — an LRU eviction is a capacity decision that
        # silently costs some session its KV, exactly what a postmortem
        # needs on the record
        self.on_event: Optional[Callable[..., Any]] = None
        if self.pool is not None:
            # prefix-index eviction telemetry: journal the reclaimed
            # entry's age (time since last touch) so the memory plane can
            # tell LRU housekeeping (stale ages) from working-set thrash
            # (young ages). Reads self.on_event at CALL time — the node
            # wires the hook after construction.
            self.pool.on_evict = lambda key, age_s: emit_safely(
                self.on_event, "prefix.evict",
                age_ms=round(age_s * 1e3, 1),
                # digest_key: the ONE truncation — journal keys must stay
                # joinable against the gossiped `pfx` digest entries
                key=prefixlib.digest_key(key),
            )
        # co-batching effectiveness (stats()): device steps + entries served
        self._batched_steps = 0
        self._batched_tokens = 0

        cfg_ = cfg
        spec_ = spec
        from inferd_tpu.core.cache import lane_slice as _lane_slice
        from inferd_tpu.core.cache import lane_write as _lane_write
        from inferd_tpu.models import qwen3

        @partial(jax.jit, donate_argnames=("cache",))
        def _decode_all(params, x, cache: KVCache, lengths, ads=None):
            """One co-batched decode step over every lane.

            x: tokens [L, 1] on the first stage, hidden [L, 1, H]
            otherwise; lengths [L] = per-lane KV fill. Lanes without a
            live entry this window compute garbage at their own frontier
            slot; the slot is rewritten by the lane's next real step
            before its position can be read (the core/batch invariant).
            `ads`: the stage-sliced multi-tenant LoRA pools + per-lane
            slot ids — a mixed-adapter window stays ONE dispatch.
            """
            if spec_.is_first:
                hidden = qwen3.embed(params, x, cfg_)
            else:
                hidden = x
            positions = lengths[:, None]  # [L, 1] absolute per lane
            hidden, nc, _ = qwen3.forward_layers_cached(
                params["layers"], cfg_, hidden, positions, cache, lengths,
                real_end=lengths + 1, layer_offset=spec_.start_layer,
                adapters=ads,
            )
            if spec_.is_last:
                logits = qwen3.unembed(params, cfg_, hidden)[:, 0]  # [L, V]
                return {"logits": logits}, nc
            return {"hidden": hidden}, nc

        @partial(jax.jit, donate_argnames=("cache",))
        def _prefill_lane(params, x, cache: KVCache, lane, start, n,
                          ads=None):
            """Chunk-ingest ONE lane: x [1, S_bucket] tokens or
            [1, S_bucket, H] hidden at absolute `start`; ragged prompts
            never pad against each other (per-lane prefill, the
            core/batch design)."""
            if spec_.is_first:
                hidden = qwen3.embed(params, x, cfg_)
            else:
                hidden = x
            s = hidden.shape[1]
            positions = start + jnp.broadcast_to(
                jnp.arange(s), hidden.shape[:2]
            )
            lc = _lane_slice(cache, lane)
            hidden, nc, _ = qwen3.forward_layers_cached(
                params["layers"], cfg_, hidden, positions, lc, start,
                real_end=start + n, layer_offset=spec_.start_layer,
                adapters=ads,
            )
            cache = _lane_write(cache, lane, nc)
            if spec_.is_last:
                last = hidden[0, n - 1]
                logits = qwen3.unembed(params, cfg_, last[None, None, :])[0, 0]
                return {"logits": logits[None]}, cache  # [1, V]
            return {"hidden": hidden}, cache

        @partial(jax.jit, donate_argnames=("cache",))
        def _decode_all_paged(params, x, cache: PagedKVCache, lengths,
                              active, ads=None):
            """Paged sibling of _decode_all: writes scatter through the
            block table, reads gather through it, and NON-participating
            lanes' garbage writes are DROPPED (`active`) — blocks are
            shared property, so the dense path's overwrite-later
            invariant does not apply."""
            if spec_.is_first:
                hidden = qwen3.embed(params, x, cfg_)
            else:
                hidden = x
            positions = lengths[:, None]
            hidden, nc, _ = qwen3.forward_layers_cached(
                params["layers"], cfg_, hidden, positions, cache, lengths,
                real_end=lengths + 1, layer_offset=spec_.start_layer,
                write_mask=active, adapters=ads,
            )
            if spec_.is_last:
                logits = qwen3.unembed(params, cfg_, hidden)[:, 0]
                return {"logits": logits}, nc
            return {"hidden": hidden}, nc

        @partial(jax.jit, donate_argnames=("cache",))
        def _prefill_lane_paged(params, x, cache: PagedKVCache, table_row,
                                start, n, ads=None):
            """Chunk-ingest ONE lane through its block-table row
            (table_row [1, MB]): the pools are global, so the scatter
            needs no lane_slice/lane_write round trip."""
            if spec_.is_first:
                hidden = qwen3.embed(params, x, cfg_)
            else:
                hidden = x
            s = hidden.shape[1]
            positions = start + jnp.broadcast_to(
                jnp.arange(s), hidden.shape[:2]
            )
            lc = PagedKVCache(
                k=cache.k, v=cache.v, table=table_row, length=cache.length
            )
            hidden, nc, _ = qwen3.forward_layers_cached(
                params["layers"], cfg_, hidden, positions, lc, start,
                real_end=start + n, layer_offset=spec_.start_layer,
                adapters=ads,
            )
            cache = PagedKVCache(
                k=nc.k, v=nc.v, table=cache.table, length=cache.length
            )
            if spec_.is_last:
                last = hidden[0, n - 1]
                logits = qwen3.unembed(params, cfg_, last[None, None, :])[0, 0]
                return {"logits": logits[None]}, cache
            return {"hidden": hidden}, cache

        @partial(jax.jit, donate_argnames=("cache",))
        def _copy_blocks(cache: PagedKVCache, src, dst):
            """CoW block copies (src/dst [n] int32), in place under
            donation — applied before the next dispatch that reads a
            freshly split lane (core.cache.paged_copy_blocks)."""
            import dataclasses

            return dataclasses.replace(
                cache,
                k=cache.k.at[:, dst].set(cache.k[:, src]),
                v=cache.v.at[:, dst].set(cache.v[:, src]),
            )

        self._decode_all = _decode_all
        self._prefill_lane = _prefill_lane
        self._decode_all_paged = _decode_all_paged
        self._prefill_lane_paged = _prefill_lane_paged
        self._copy_blocks = _copy_blocks
        self._jax = jax
        self._jnp = jnp

        # multi-step fused decode over the co-batched lanes (single-stage
        # topologies only — a pipeline stage's next token depends on every
        # other stage, so multi-stage swarms keep the per-token relay and
        # amortize via co-batching alone). One compiled K-step scan
        # (models/qwen3.decode_k) decodes K on-device-sampled tokens for
        # every participating lane per dispatch.
        self._decode_k_all = None
        if spec.is_first and spec.is_last:
            # shared serving jit (models/qwen3.make_decode_k_serve) — the
            # same definition core.batch.BatchedEngine dispatches, so the
            # fuse_kstep_group contract cannot drift between executors
            self._decode_k_all = qwen3.make_decode_k_serve(cfg_)

    def co_possible(self) -> bool:
        """More than one live session -> a window wait can pay off.
        LOCK-FREE read (dict len is atomic): called under the node
        batcher's lock, while _drop_locked holds self._mu when it
        invalidates that same batcher — taking _mu here would be an
        ABBA deadlock."""
        return len(self._sessions) > 1

    def gang_target(self) -> int:
        """How many decode entries a window flusher should hope for: the
        live sessions that are NOT currently mid-step here (an in-flight
        session — e.g. one still prefilling — cannot also have a decode
        step waiting). LOCK-FREE reads, same reasoning as co_possible;
        the value is advisory (the window cap bounds any staleness)."""
        return len(self._sessions) - len(self._inflight)

    # -- lane/session bookkeeping (call under self._mu) ----------------------

    def _lane_for(self, session_id: str, new_ok: bool) -> int:
        lane = self._sessions.get(session_id)
        if lane is not None:
            self._last_used[session_id] = time.monotonic()
            return lane
        if not new_ok:
            raise ValueError(
                f"session {session_id}: unknown session resumed mid-stream "
                "(cache evicted or node restarted)"
            )
        if not self.free:
            from inferd_tpu.runtime.batch_executor import CapacityError

            victims = [
                s for s in self._sessions if not self._inflight.get(s)
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
            self._drop_locked(oldest)
        lane = self.free.pop()
        self._sessions[session_id] = lane
        self._last_used[session_id] = time.monotonic()
        self._lane_hi[lane] = 0
        return lane

    def _drop_locked(self, session_id: str) -> None:
        lane = self._sessions.pop(session_id, None)
        self._last_used.pop(session_id, None)
        self._release_adapter_locked(session_id)
        if lane is None:
            return
        # fail-fast entries still waiting in the node's arrival window: a
        # later flush must never write this lane on the old session's
        # behalf once a new claimant may own it
        if self.on_drop is not None:
            self.on_drop(session_id)
        if self._inflight.get(session_id):
            self._dying[lane] = session_id  # free deferred until drain
        else:
            self._free_lane_locked(lane)

    def _free_lane_locked(self, lane: int) -> None:
        self.lengths[lane] = 0
        self._lane_slot[lane] = 0  # back to the base adapter
        if self.pool is not None:
            # per-block free: cached/pinned prefix blocks survive through
            # their index references; everything else returns to the pool
            self.pool.release_lane(lane)
        self.free.append(lane)

    def _finish_locked(self, session_id: str, lane: int) -> None:
        self._inflight.pop(session_id, None)
        if self._dying.get(lane) == session_id:  # ended mid-step
            del self._dying[lane]
            self._free_lane_locked(lane)

    # -- admission (shared by decode co-batches and solo prefill) ------------

    def _admit_locked(
        self, session_id: str, start_pos: int, real_len: int, new_ok: bool,
        ensure_upto: Optional[int] = None,
    ) -> int:
        """Validate + in-flight-mark one chunk; returns its lane. MUST
        hold self._mu. ONE definition of the admission protocol
        (concurrency, restart reset, overflow, out-of-order, replay
        rollback under the ring margin) for both the co-batched decode
        path and the per-lane prefill path — mirrors
        BatchedExecutor.process admission.

        Paged extras: `ensure_upto` pre-allocates the lane's block chain
        to cover that many positions (decode/K-step dispatches write at
        known frontiers; prefill manages its own per-chunk ensure so
        shared-prefix mapping can claim the chain first), a restart
        releases the old chain per-block, and a replay rollback into a
        SHARED region queues copy-on-write splits for the device lock to
        apply — the rewrite must never scribble on blocks other lanes or
        the prefix index still read."""
        if self._inflight.get(session_id):
            raise ValueError(
                f"session {session_id}: concurrent request (one step at a "
                "time per session)"
            )
        lane = self._lane_for(session_id, new_ok=new_ok)
        owner = f"session {session_id}, lane {lane}"
        have = self.lengths[lane]
        if start_pos == 0 and have:
            # session restart under the same id: reset the lane
            self.lengths[lane] = 0
            self._lane_hi[lane] = 0
            if self.pool is not None:
                self.pool.release_lane(lane)
            have = 0
        if start_pos + real_len > self.max_len:
            raise BufferError(
                f"session {session_id}: KV overflow "
                f"({start_pos}+{real_len} > {self.max_len}, lane {lane})"
            )
        if start_pos != have:
            if not 0 < start_pos < have:
                raise ValueError(
                    f"session {session_id}: start_pos {start_pos} != cache "
                    f"length {have} (out-of-order chunk)"
                )
            hi = max(self._lane_hi.get(lane, 0), have)
            if self.cache.k_loc is not None and hi - start_pos > RING_MARGIN:
                raise ValueError(
                    f"session {session_id}: replay rollback to {start_pos} "
                    f"exceeds the ring margin (high-water mark {hi})"
                )
            # deterministic chunk REPLAY: roll the frontier back and
            # recompute (identical KV); preserve the pre-rollback frontier
            # as the ring high-water mark
            self._lane_hi[lane] = hi
            self.lengths[lane] = start_pos
            if self.pool is not None:
                before = self.pool.cow_splits
                self.pool.make_writable(lane, start_pos, owner=owner)
                if self.pool.cow_splits != before:
                    emit_safely(
                        self.on_event, "kv.cow_split", session=session_id,
                        lane=lane, from_pos=start_pos,
                        blocks=self.pool.cow_splits - before,
                    )
        if self.pool is not None and ensure_upto is not None:
            self.pool.ensure(lane, ensure_upto, owner=owner)
        self._inflight[session_id] = 1
        return lane

    # -- executor contract ---------------------------------------------------

    def process_batch(
        self,
        items: List[Tuple[str, Dict[str, Any]]],
        drain: Optional[Callable[[], List[Tuple[str, Dict[str, Any]]]]] = None,
    ) -> List[Any]:
        """ONE co-batched device step for every item's single-token decode.

        items: [(session_id, payload)] where each payload is a decode step
        ({"tokens": [1,1]} or {"hidden": [1,1,H]}, start_pos > 0,
        real_len == 1) — optionally carrying "decode_steps" (+ sampling/
        eos/key) for the multi-step fused path. Returns a list aligned
        with `items` (plus any drained extras, appended in drain order): a
        result dict per served item, or the Exception that rejected it
        (per-item — a stale session in the window must not fail its
        co-batch).

        Single-token items run as ONE batched step (client-side-sampling
        logits contract). Multi-step items (single-stage topologies only)
        fuse into ONE K-step scan per sampling config with K = the
        group's minimum budget-clamped request — co-batched lanes decode
        K steps per window when every lane has >= K budget, falling back
        toward K=1 at stop-condition/budget boundaries. Mixed windows run
        both dispatches under one device-lock hold.

        `drain` (optional) is called once the DEVICE LOCK is held and may
        return more items to fold into the same step — the continuous-
        batching hook: entries that arrived while the previous step was
        still running join this step instead of forming a lagging
        under-filled window (runtime/window.drain_pending).
        """
        from inferd_tpu.runtime.executor import (
            cache_intact, fuse_kstep_group, kstep_hi, parse_kstep,
        )

        out: List[Any] = [None] * len(items)
        served: List[Tuple[int, str, int, Any, int, Any]] = []
        taken: set = set()

        def admit(batch_items, base: int) -> None:
            """Validate + mark each item (under self._mu)."""
            for j, (sid, payload) in enumerate(batch_items):
                i = base + j
                try:
                    x, start_pos, real_len = self._parse(payload)
                    nm = payload.get("adapter")
                    if nm is not None and (
                        self.adapters is None
                        or self._session_adapter.get(sid) != str(nm)
                    ):
                        # decode steps are mid-session: the binding
                        # happened at admission — a mismatch is a routing
                        # bug, never served silently with other weights
                        raise ValueError(
                            f"session {sid}: decode-step adapter {nm!r} "
                            "does not match the admitted binding"
                        )
                    if real_len != 1 or start_pos <= 0:
                        raise ValueError(
                            "process_batch co-batches single-token decode "
                            f"steps only (real_len={real_len}, "
                            f"start_pos={start_pos})"
                        )
                    ks = parse_kstep(payload, self.max_len - start_pos)
                    if ks is not None and self._decode_k_all is None:
                        raise ValueError(
                            "decode_steps requires a single-stage "
                            "(whole-model) topology — pipeline stages "
                            "relay per token"
                        )
                    if sid in taken:
                        raise ValueError(
                            f"session {sid}: concurrent request (two steps "
                            "in one window)"
                        )
                    lane = self._admit_locked(
                        sid, start_pos, 1, new_ok=False,
                        # paged: the dispatch writes positions
                        # [start_pos, start_pos + K) — the chain must
                        # cover them before the jit scatters
                        ensure_upto=start_pos + (ks["k"] if ks else 1),
                    )
                    taken.add(sid)
                    served.append((i, sid, lane, x, start_pos, ks))
                except Exception as e:  # per-item rejection
                    out[i] = e

        with self._mu:
            admit(items, 0)
        if not served and drain is None:
            return out
        try:
            jnp = self._jnp
            with self._dev_lock:
                if drain is not None:
                    extra = drain()
                    if extra:
                        base = len(out)
                        out.extend([None] * len(extra))
                        with self._mu:
                            admit(extra, base)
                if not served:
                    return out
                # failure isolation is per DISPATCH (the batch_executor
                # contract): a mixed window runs one legacy step plus one
                # K-step scan per sampling group, and a raising dispatch
                # must fail only ITS entries — results another dispatch
                # already committed (lengths advanced, out[i] set) and
                # dispatches not yet run stay healthy. That holds for
                # HOST-side failures; a device-side failure after the jit
                # donated the cache invalidates the shared buffers, so
                # the window stops dispatching and fails the remaining
                # entries clearly (executor.cache_intact)
                poisoned = None
                legacy = [s for s in served if s[5] is None]
                kstep = [s for s in served if s[5] is not None]
                if legacy:
                    try:
                        with self._mu:
                            lens = list(self.lengths)
                            slot_ids = list(self._lane_slot)
                        ads = self._ads(slot_ids)
                        if self.spec.is_first:
                            xs = np.zeros((self.lanes, 1), np.int32)
                        else:
                            h0 = np.asarray(legacy[0][3])
                            xs = np.zeros(
                                (self.lanes, 1, h0.shape[-1]), h0.dtype
                            )
                        for _i, _sid, lane, x, _sp, _ks in legacy:
                            # x is already a HOST array (_parse
                            # materialized the wire payload); this is a
                            # host-to-host copy
                            xs[lane] = x[0]
                        xd = (jnp.asarray(xs) if self.spec.is_first
                              else jnp.asarray(xs, self.cfg.jnp_dtype))
                        if self.pool is not None:
                            act = np.zeros((self.lanes,), bool)
                            for _i, _sid, lane, _x, _sp, _ks in legacy:
                                act[lane] = True
                            res, self.cache = self._decode_all_paged(
                                self.params, xd, self._sync_paged(),
                                jnp.asarray(lens, jnp.int32),
                                jnp.asarray(act), ads=ads,
                            )
                        else:
                            res, self.cache = self._decode_all(
                                self.params, xd, self.cache,
                                jnp.asarray(lens, jnp.int32), ads=ads,
                            )
                        key = "logits" if self.spec.is_last else "hidden"
                        vals = np.asarray(res[key])
                        with self._mu:
                            for _i, _sid, lane, _x, _sp, _ks in legacy:
                                self.lengths[lane] += 1
                            self._batched_steps += 1
                            self._batched_tokens += len(legacy)
                        for i, _sid, lane, _x, sp, _ks in legacy:
                            out[i] = {
                                key: vals[lane][None],  # [1, 1, H] or [1, V]
                                "real_len": 1,
                                "start_pos": sp,
                            }
                    except Exception as e:
                        for i, _sid, _lane, _x, _sp, _ks in legacy:
                            out[i] = e
                        if not cache_intact(self.cache):
                            poisoned = e
                groups: Dict[tuple, list] = {}
                for s in kstep:
                    groups.setdefault(s[5]["sampling"], []).append(s)
                def run_group(grp):
                    with self._mu:
                        lens = list(self.lengths)
                        slot_ids = list(self._lane_slot)
                    kg, seq, n_new, nkeys, self.cache = fuse_kstep_group(
                        self._decode_k_all, self.params,
                        self._sync_paged() if self.pool is not None
                        else self.cache,
                        lens, self.lanes,
                        # x is already a HOST array (_parse materialized
                        # the wire payload)
                        [(lane, int(np.asarray(x)[0, 0]), ks)  # host-to-host copy, no device sync
                         for _i, _sid, lane, x, _sp, ks in grp],
                        ads=self._ads(slot_ids),
                    )
                    with self._mu:
                        n_served = 0
                        for _i, _sid, lane, _x, _sp, _ks in grp:
                            n = int(n_new[lane])  # n_new is a HOST array (materialized above)
                            old = self.lengths[lane]
                            self.lengths[lane] = old + n
                            self._lane_hi[lane] = max(
                                self._lane_hi.get(lane, 0),
                                kstep_hi(old, n, kg),
                            )
                            n_served += n
                        self._batched_steps += 1
                        # token-true co-batch accounting: K tokens per
                        # lane per dispatch, not 1 (the /stats and
                        # mean_batch numbers must reflect real tokens)
                        self._batched_tokens += n_served
                    for i, _sid, lane, _x, sp, _ks in grp:
                        n = int(n_new[lane])  # host array
                        out[i] = {
                            "tokens": [seq[:n, lane].tolist()],  # host array row unpack, no device sync
                            "real_len": n,
                            "decode_steps": kg,
                            "start_pos": sp,
                            "key": nkeys[lane].tolist(),  # host array row unpack, no device sync
                        }

                for _sampling, grp in groups.items():
                    if poisoned is not None:
                        err = RuntimeError(
                            "KV cache invalidated by an earlier dispatch "
                            f"failure in this window: {poisoned}"
                        )
                        for i, _sid, _lane, _x, _sp, _ks in grp:
                            out[i] = err
                        continue
                    try:
                        run_group(grp)
                    except Exception as e:
                        for i, _sid, _lane, _x, _sp, _ks in grp:
                            out[i] = e
                        if not cache_intact(self.cache):
                            poisoned = e
        except Exception as e:
            for i, _sid, _lane, _x, _sp, _ks in served:
                if out[i] is None:
                    out[i] = e
        finally:
            with self._mu:
                for _i, sid, lane, _x, _sp, _ks in served:
                    self._finish_locked(sid, lane)
        return out

    def _sync_paged(self):
        """core.cache.sync_paged over this executor's state: call under
        self._dev_lock; rebinds self.cache (the copy jit donates)."""
        self.cache = sync_paged(
            self.pool, self.cache, self._copy_blocks, self._mu
        )
        return self.cache

    def process(self, session_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Single-session contract: prefill chunks run per-lane; a decode
        step is a co-batch of one (the node's window is the place decode
        steps actually coalesce)."""
        x, start_pos, real_len = self._parse(payload)
        if real_len == 1 and start_pos > 0:
            res = self.process_batch([(session_id, payload)])[0]
            if isinstance(res, Exception):
                raise res
            return res
        return self._prefill_solo(session_id, payload, start_pos, real_len)

    def _parse(self, payload: Dict[str, Any]):
        """(x, start_pos, real_len) with x the raw [1, S(, H)] array."""
        start_pos = int(payload.get("start_pos", 0))
        if self.spec.is_first:
            x = np.asarray(payload["tokens"], dtype=np.int32)
        else:
            x = np.asarray(payload["hidden"])
        if x.ndim < 2 or x.shape[0] != 1:
            raise ValueError(f"stage batch expects [1, S(, H)], got {x.shape}")
        real_len = int(payload.get("real_len", x.shape[1]))
        return x, start_pos, real_len

    def _prefill_solo(
        self, session_id: str, payload: Dict[str, Any], start_pos: int,
        real_len: int,
    ) -> Dict[str, Any]:
        """Per-lane prompt ingestion, in up to three phases:

          1. shared-prefix SKIP (paged, whole-model stages, start_pos 0):
             full blocks whose chained token hash is already in the pool's
             prefix index map read-only into this lane — zero prefill
             FLOPs for the shared region, CoW on later divergence. At
             least the prompt's last token always computes (its logits
             are the response).
          2. chunked prefill: the remaining tokens ingest in
             `prefill_chunk`-token dispatches, RELEASING the device lock
             between chunks so co-batched decode windows interleave
             instead of stalling behind a long admission.
          3. registration (paged, first stage): the prompt's full blocks
             publish into the prefix index so later sessions sharing the
             prefix skip it.
        """
        jnp = self._jnp
        x, _, _ = self._parse(payload)
        acquired = self._resolve_adapter(session_id, payload, start_pos)
        try:
            with self._mu:
                lane = self._admit_locked(
                    session_id, start_pos, real_len, new_ok=start_pos == 0
                )
                self._bind_adapter_locked(
                    session_id, lane, start_pos, acquired
                )
        except Exception:
            # an admission that died before the binding consumed the
            # reference must give it back (slot refcount hygiene)
            if acquired is not None and acquired[1]:
                self.adapters.release(acquired[0])
            raise
        owner = f"session {session_id}, lane {lane}"
        try:
            pos = start_pos
            keys = None
            saved = 0
            with self._mu:
                ad_name = self._session_adapter.get(session_id)
                ads = self._ads([self._lane_slot[lane]])
            whole = self.spec.is_first and self.spec.is_last
            if self.pool is not None and self.spec.is_first and start_pos == 0:
                ids = [int(t) for t in x[0, :real_len]]
                # adapter sessions salt the chain: tenants must never
                # share prefix KV across adapters (core.prefix.block_keys)
                keys = prefixlib.block_keys(
                    ids, self.pool.block_size, salt=ad_name
                )
            if self.pool is not None and whole and start_pos == 0 and keys:
                # map at most the blocks covering real_len - 1 tokens: the
                # LAST prompt token must always compute (its logits seed
                # the first decode step)
                nmap = (real_len - 1) // self.pool.block_size
                with self._mu:
                    cov = self.pool.map_prefix(lane, keys[:nmap])
                if cov:
                    pos = saved = cov
                    with self._mu:
                        self.lengths[lane] = cov
                        self._lane_hi[lane] = max(
                            self._lane_hi.get(lane, 0), cov
                        )
                    emit_safely(
                        self.on_event, "prefix.hit", session=session_id,
                        lane=lane, tokens=cov,
                    )

            end = start_pos + real_len
            step = self.prefill_chunk if self.prefill_chunk > 0 else (
                end - pos
            )
            hidden_parts: List[Tuple[Any, int]] = []  # (device array, n)
            last = None
            key = "logits" if self.spec.is_last else "hidden"
            while pos < end:
                n = min(step, end - pos)
                chunk = x[:, pos - start_pos: pos - start_pos + n]
                # cap the padded bucket so the in-jit update can never
                # clamp into older slots near the end of the cache (the
                # BatchedExecutor._prefill_solo invariant); paged chains
                # are ensured per chunk instead
                b = min(bucket_len(n), self.max_len - pos)
                if self.spec.is_first:
                    padded = np.zeros((1, b), np.int32)
                    padded[0, :n] = chunk[0]
                    xd = jnp.asarray(padded)
                else:
                    padded = np.zeros((1, b, x.shape[2]), np.float32)
                    padded[0, :n] = chunk[0]
                    xd = jnp.asarray(padded, self.cfg.jnp_dtype)
                if self.pool is not None:
                    with self._mu:
                        self.pool.ensure(lane, pos + n, owner=owner)
                with self._dev_lock:
                    if self.pool is not None:
                        cache = self._sync_paged()
                        res, self.cache = self._prefill_lane_paged(
                            self.params, xd, cache,
                            jnp.asarray(self.pool.table[lane:lane + 1]),
                            jnp.int32(pos), jnp.int32(n), ads=ads,
                        )
                    else:
                        res, self.cache = self._prefill_lane(
                            self.params, xd, self.cache, jnp.int32(lane),
                            jnp.int32(pos), jnp.int32(n), ads=ads,
                        )
                    # keep results ON DEVICE inside the chunk loop — ONE
                    # boundary transfer after it (below)
                    if key == "hidden":
                        hidden_parts.append((res[key], n))
                    else:
                        last = res[key]
                    # advance BEFORE releasing the device lock: a window
                    # flush snapshots lengths under the same lock order
                    with self._mu:
                        self.lengths[lane] = pos + n
                        self._lane_hi[lane] = max(
                            self._lane_hi.get(lane, 0), pos + n
                        )
                        self.prefill_tokens += n
                pos += n
                if self.prefill_chunk > 0 and pos < end:
                    # explicit yield between chunks: threading.Lock is
                    # NOT fair — without this, the chunk loop can
                    # re-acquire the device before a waiting decode
                    # flusher ever wakes, and chunking would bound
                    # nothing. Sub-ms: noise next to a chunk dispatch.
                    # The ticketed FairDeviceLock grants in arrival
                    # order, so there the yield is dead weight.
                    if not lockwatch.is_fair(self._dev_lock):
                        time.sleep(0.0005)
            if self.pool is not None and whole and keys:
                with self._mu:
                    self.pool.register_prefix(lane, keys)
        finally:
            with self._mu:
                self._finish_locked(session_id, lane)
        if key == "hidden":
            # ship only the real rows (wire diet — the stage executor's
            # contract; downstream re-pads to its own bucket); one
            # device_get for every chunk's rows
            host = self._jax.device_get([p for p, _n in hidden_parts])
            trimmed = [h[:, :n_] for h, (_p, n_) in zip(host, hidden_parts)]
            val = (trimmed[0] if len(trimmed) == 1
                   else np.concatenate(trimmed, axis=1))
        else:
            val = np.asarray(last)
        return {
            key: val, "real_len": real_len, "start_pos": start_pos,
            # per-request shared-prefix saving: the node stamps it on the
            # prefill's compute span + kv.saved_tokens and strips it
            # before the reply/relay (key omitted on a cold prefill so
            # cold envelopes stay byte-identical to pre-digest builds)
            **({"tokens_saved": saved} if saved else {}),
        }

    def end_session(self, session_id: str) -> None:
        with self._mu:
            self._drop_locked(session_id)

    # -- prefix caching (paged mode) -----------------------------------------

    def pin_prefix(self, prefix_ids) -> int:
        """Prefill `prefix_ids` once into pool blocks and PIN them: the
        blocks stay resident (never evicted for space) and every later
        session whose prompt starts with them maps the region read-only
        instead of recomputing it — the Engine pin store generalized to
        refcounted pool blocks. Whole-model paged stages only. Returns
        the pinned token coverage (full blocks)."""
        if self.pool is None or not (self.spec.is_first and self.spec.is_last):
            raise ValueError(
                "pin_prefix needs paged KV on a whole-model stage"
            )
        ids = [int(t) for t in prefix_ids]
        if not ids:
            raise ValueError("prefix ids must be non-empty")
        keys = prefixlib.block_keys(ids, self.pool.block_size)
        sid = "__pin__" + keys[-1].hex() if keys else "__pin__short"
        # an ordinary prefill under a reserved session id registers the
        # blocks; the pin marks them and the teardown returns the lane
        # while the index references keep the blocks alive
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

    def fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        """Seed a new session with the parent's first `prefix_len`
        positions. Paged mode maps the parent's full blocks READ-ONLY
        into the child (refcount, CoW on divergence) and copies only the
        partial tail block — the node's pinned-session fork flow rides
        the block pool instead of duplicating whole lane rows. Dense
        stage lanes return False (full prefill fallback), as before."""
        if self.pool is None or prefix_len <= 0:
            return False
        with self._mu:
            if self._session_adapter.get(parent_session_id):
                # the fork flow admits the child WITHOUT an adapter key:
                # decoding adapter-built KV with the base adapter would
                # diverge silently — the clean False re-prefills instead
                return False
            plane = self._sessions.get(parent_session_id)
            if (
                plane is None
                or self.lengths[plane] < prefix_len
                or new_session_id in self._sessions
            ):
                return False
            try:
                lane = self._lane_for(new_session_id, new_ok=True)
            except Exception:
                return False
            try:
                self.pool.fork_lane(
                    plane, lane, prefix_len,
                    owner=f"session {new_session_id}, lane {lane}",
                )
            except BufferError:
                self._drop_locked(new_session_id)
                return False
            self.lengths[lane] = prefix_len
            self._lane_hi[lane] = prefix_len
        return True

    # -- node surfaces (sweep loop, gossip adverts, /stats, kv gauge) --------

    @property
    def sessions(self):
        return self

    def sweep(self) -> int:
        if not self._mu.acquire(blocking=False):
            return 0
        try:
            now = time.monotonic()
            stale = [
                s for s, t in self._last_used.items()
                if now - t > self.ttl_s and not self._inflight.get(s)
            ]
            for s in stale:
                self._drop_locked(s)
            return len(stale)
        finally:
            self._mu.release()

    def ids(self):
        with self._mu:
            return list(self._sessions)

    def kv_occupancy(self) -> float:
        """Fraction of the KV budget in use — the serving memory-pressure
        signal obs.devtel gauges per scrape. Paged: blocks used / blocks
        total (the pool's true capacity unit); dense: filled positions /
        lanes x max_len."""
        with self._mu:
            if self.pool is not None:
                total = self.pool.num_blocks - 1
                return self.pool.blocks_used / float(total) if total else 0.0
            return sum(self.lengths) / float(self.lanes * self.max_len)

    def block_stats(self) -> Optional[Dict[str, Any]]:
        """Block-pool gauges for obs.devtel (None on the dense layout)."""
        if self.pool is None:
            return None
        with self._mu:
            return self.pool.block_stats()

    def prefix_digest(self) -> Optional[Dict[str, Any]]:
        """Gossip-ready digest of the pool's hot prefix index
        (core.prefix.make_digest over digest_keys: pinned entries first,
        then MRU) — the `pfx` record field entry routers score
        cache-affinity against. None on dense stages, inner pipeline
        stages (their index keys hash token ids they never see), and an
        empty index — the key is then OMITTED from gossip, never an
        empty decoy."""
        if self.pool is None or not (self.spec.is_first and self.spec.is_last):
            return None
        with self._mu:
            keys = self.pool.digest_keys(prefixlib.DIGEST_GOSSIP_KEYS)
            bs = self.pool.block_size
        if not keys:
            return None
        return prefixlib.make_digest(keys, bs)

    def kv_bytes(self) -> int:
        total = 0
        for arr in (self.cache.k, self.cache.v,
                    getattr(self.cache, "k_loc", None),
                    getattr(self.cache, "v_loc", None)):
            total += int(getattr(arr, "nbytes", 0) or 0)
        return total

    def __len__(self) -> int:
        with self._mu:
            return len(self._sessions)

    def __contains__(self, session_id: str) -> bool:
        with self._mu:
            return session_id in self._sessions

    def stats(self) -> Dict[str, Any]:
        with self._mu:
            steps, toks = self._batched_steps, self._batched_tokens
            out = {
                "mode": "stage_batched",
                "stage": self.spec.stage,
                "lanes": self.lanes,
                "lanes_busy": self.lanes - len(self.free),
                "batched_steps": steps,
                "batched_tokens": toks,
                "mean_batch": round(toks / steps, 3) if steps else 0.0,
                "prefill_tokens": self.prefill_tokens,
            }
            if self.pool is not None:
                out["paged"] = self.pool.block_stats()
            if self.adapters is not None:
                out["adapters"] = self.adapters.stats()
            return out
