"""Mesh-backed whole-model executor: the north-star serving path.

The plain swarm topology hosts one stage per node and relays activations
over HTTP (runtime/node.py — the reference's design, petals/node.py:102-117,
upgraded). This executor is the TPU-native fusion BASELINE config 2 scores:
a node that owns N chips hosts the WHOLE model pipelined over an in-mesh
`pp` axis (parallel/infer.py) behind the SAME `/forward` surface — the
inter-stage hop becomes a `lax.ppermute` over ICI inside one jitted SPMD
program instead of a network round trip, and the swarm sees a single-stage
pipeline (is_first and is_last both true: tokens in; out, the token the
pass chose where the decode hop asked for it (docs/SERVING.md "A decode
hop's ask"), else last-token logits for the caller to sample: the
reference contract, client.py:204-287).

Sessions map to microbatch slots of the engine's persistent sharded KV
caches (one slot = one session's cache lane), with idle-TTL sweep and
slot refill on end_session — the per-session server-side cache story
(qwen3_server_module.py:220) carried over to the mesh.

process() is called from the node's worker thread pool (a thread per slot
and one more); an internal lock serializes device steps (the engine's
donated caches admit one step at a time) and guards the session table, so
a call is admitted under it too. Prefills run one at a time; decode steps
go through the arrival window (runtime/window.py, formation), whose
flusher takes every entry that is pending once it holds the lock: ONE
pipeline pass advances every session that was waiting when the mesh freed,
and chooses the token of every one whose hop asked for it. The drain keeps
one pass AHEAD of the sessions (`_decode_ahead`, docs/SERVING.md "One step
ahead"): a hop whose ask says more hops follow is answered from the row a
pass ran for it before it arrived, and the drain that answers it first
dispatches the pass of the hops after it, fed by the tokens and keys the
last pass left on the devices, so the sessions take their turn beside a
pass and not between two.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from inferd_tpu.config import ModelConfig
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.obs.devtel import program_name
from inferd_tpu.parallel import mesh as meshlib
from inferd_tpu.parallel.infer import PipelinedEngine
from inferd_tpu.runtime.executor import parse_decode_ask
from inferd_tpu.runtime.spec_serving import SpecForkMiss, SpecServing
from inferd_tpu.runtime.step_ahead import Admissions, StepAhead, _Ahead, _Step

log = logging.getLogger(__name__)


class SlotSessions:
    """session_id -> cache slot, with idle TTL; free slots recycle.

    Exposes the same sweep()/__len__ surface the node's sweep loop expects
    (runtime/node.py:_sweep_loop). Locking contract: get/assign/drop are
    called by MeshExecutor UNDER its step lock; sweep() runs on the node's
    event loop, so it takes that same lock itself — otherwise a sweep could
    free a slot mid-step and hand it to a second session (cross-session KV
    corruption)."""

    def __init__(self, num_slots: int, ttl_s: float, lock: threading.Lock):
        self.ttl_s = ttl_s
        self._step_lock = lock
        self._slots: Dict[str, int] = {}
        self._last_used: Dict[str, float] = {}
        self._free = list(range(num_slots))
        # slots bound to new sessions (/stats `executor`, the `lane` span)
        self.admit = Admissions()

    def get(self, session_id: str) -> Optional[int]:
        slot = self._slots.get(session_id)
        if slot is not None:
            self._last_used[session_id] = time.monotonic()
        return slot

    def assign(self, session_id: str, protected=()) -> int:
        evicted = not self._free
        if evicted:
            # evict the least-recently-used session (the stage executor's
            # SessionStore policy — a stale session loses its cache) that
            # is not protected (e.g. has a request in flight)
            victims = {s: t for s, t in self._last_used.items() if s not in protected}
            if not victims:
                raise BufferError("all slots busy with in-flight requests")
            oldest = min(victims, key=victims.get)
            self.drop(oldest)
        slot = self._free.pop()
        self._slots[session_id] = slot
        self._last_used[session_id] = time.monotonic()
        self.admit.bound(slot, evicted)
        return slot

    def drop(self, session_id: str) -> None:
        slot = self.unmap(session_id)
        if slot is not None:
            self.free_slot(slot)

    def unmap(self, session_id: str):
        """Remove the session->slot mapping WITHOUT freeing the slot (the
        caller defers the free until an in-flight request drains)."""
        self._last_used.pop(session_id, None)
        return self._slots.pop(session_id, None)

    def free_slot(self, slot: int) -> None:
        self._free.append(slot)
        self.admit.freed(slot)

    def owner(self, slot: int) -> Optional[str]:
        """The session that holds `slot`, if one does."""
        return next((s for s, held in self._slots.items() if held == slot), None)

    def sweep(self) -> int:
        # Non-blocking: sweep() runs on the node's event loop, and a device
        # step (held under the same lock) can take seconds — blocking here
        # would freeze HTTP handling and gossip for that long. A busy round
        # just defers expiry to the next sweep.
        if not self._step_lock.acquire(blocking=False):
            return 0
        try:
            now = time.monotonic()
            stale = [s for s, t in self._last_used.items() if now - t > self.ttl_s]
            for s in stale:
                self.drop(s)
            return len(stale)
        finally:
            self._step_lock.release()

    def __len__(self) -> int:
        return len(self._slots)

    def __contains__(self, session_id: str) -> bool:
        return session_id in self._slots

    def ids(self):
        """Live session ids (gossip session-location advertising). Lock-free
        point-in-time key copy: callers (announce) tolerate staleness, and
        taking the step lock here could block the event loop for a whole
        device step."""
        return list(self._slots)


class MeshExecutor(SpecServing, StepAhead):
    """Whole-model stage executor pipelined over an in-mesh pp axis."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        plan: meshlib.MeshPlan,
        num_slots: int = 8,
        max_len: int = 4096,
        session_ttl_s: float = 600.0,
        devices=None,
        window_ms: float = 3.0,
        spec_draft_layers: int = 0,
        spec_k: int = 4,
    ):
        import jax

        devs = list(devices) if devices is not None else jax.devices()
        if plan.num_devices > len(devs):
            raise ValueError(
                f"mesh plan needs {plan.num_devices} devices, have {len(devs)}"
            )
        mesh = meshlib.make_mesh(plan, devs[: plan.num_devices])
        self.cfg = cfg
        self.plan = plan
        self.max_len = max_len
        self.engine = PipelinedEngine(
            cfg, params, mesh,
            num_microbatches=num_slots, batch=1, max_len=max_len,
        )
        # Sliding-window models run O(window) RING storage on their sliding
        # layers whenever every pp rank's layer slice starts on an even
        # global index (parallel.infer.ring_split_ok — then the rank-local
        # sliding/global pattern is one STATIC program on all ranks). Only
        # the odd-layers-per-rank niche (e.g. Gemma-2's 26 layers at pp=2)
        # keeps the uniform mask-only fallback; observable, not silent.
        self.kv_window_fallback = bool(
            cfg.sliding_window and not self.engine.ring_active
        )
        if self.kv_window_fallback:
            log.warning(
                "mesh executor: sliding-window model %s uses uniform KV "
                "(O(context) reads on sliding layers: %d layers per pp "
                "rank is odd, so the ring layout cannot be one SPMD "
                "program — pick a pp that divides the layers evenly)",
                cfg.name, cfg.num_layers // plan.pp,
            )
        self._lock = threading.Lock()
        self.sessions = SlotSessions(num_slots, session_ttl_s, self._lock)
        # host mirror of each session's cache length (device sync per step
        # would stall the pipeline)
        self._session_len: Dict[str, int] = {}
        # ring-KV replay safety (mirrors the stage executor): high-water
        # mark of positions ever written per session — a replay rollback or
        # a fork truncation is exact only while (hi - target) stays under
        # RING_MARGIN (core.cache aliasing invariant). Guarded by _lock.
        self._ring_hi: Dict[str, int] = {}
        self._inflight: Dict[str, int] = {}  # session -> active request count
        self._dying: Dict[int, str] = {}  # slot -> ended session awaiting drain
        # one-token decode hops answered with a token chosen on the device,
        # and those answered with their [V] logits row (/stats `executor`)
        self.sampled_rows = 0
        self.logit_rows = 0
        # one pass ahead (`_decode_ahead`; runtime/step_ahead.py names the
        # records): slot -> the row a pass ran before its hop arrived,
        # unclaimed; slot -> (the pass its waiting hop rode, the hop's ask)
        # where that ask promised a hop to follow; the last pass dispatched;
        # rows run ahead, those that answered a hop, those dropped. Guarded
        # by _lock.
        self._ahead: Dict[int, _Ahead] = {}
        self._carry: Dict[int, tuple] = {}
        self._last_step: Optional[_Step] = None
        # the repair of a dropped row (`_forget`) is a small program of its
        # own: compiled here, not under the first session that drops one
        self.engine.set_slot_length(0, 0)
        self.ahead_rows = 0
        self.ahead_claimed = 0
        self.ahead_dropped = 0
        # decode coalescing: the pipeline pass natively interleaves all MB
        # slots and costs the same whatever rides it, so a pass takes every
        # session that is waiting when the mesh frees
        from inferd_tpu.runtime.window import WindowedBatcher

        self._batcher = WindowedBatcher(
            # only the start value of the batcher's own estimate of a
            # session's turn (result out -> next submit); see `expect`
            window_ms / 1e3,
            self._run_decode_batch,
            co_possible=lambda: len(self.sessions) > 1,
            # formation, as on the lanes (runtime/batch_executor.py): the
            # batch is drained under _lock, and the flusher waits for the
            # slots the last two passes served. end_session, _spec_drop and
            # a prefill take a slot out of that expectation at once.
            swap_in_run=True,
            expect=lambda payload: payload[0],
        )
        self._spec_window_s = window_ms / 1e3
        # in-mesh lane... slot speculation (parallel.infer.MeshSpecRunner):
        # None until enabled. Structurally impossible configs (ring margin,
        # layer counts) log + serve without.
        self._spec = None
        if spec_draft_layers > 0:
            try:
                self.enable_spec(spec_draft_layers, spec_k, params)
            except (ValueError, RuntimeError) as e:
                log.warning("mesh speculation disabled (%s); serving without", e)

    @property
    def tracer(self):
        """Span recorder (the node wires its own): the prefill step stamps
        lock_wait here, the engine's raw steps device / copy_out, the
        batcher a decode entry's lock_wait and batch_wait — all under the
        call's `compute`."""
        return self._batcher.tracer

    @tracer.setter
    def tracer(self, recorder) -> None:
        self._batcher.tracer = recorder
        self.engine.tracer = recorder

    # -- slot-batched speculative serving (parallel.infer.MeshSpecRunner) ----
    #
    # Mirrors runtime/batch_executor's lane speculation with slots in place
    # of lanes: a speculating session is an ordinary microbatch slot, spec
    # rounds interleave with regular /forward decode flushes under the same
    # step lock, and EVERY live session is capped at max_len - (k+1) so the
    # verify chunk's K+1 frontier writes can never clamp into valid KV
    # (core.spec_batch headroom contract; dead slots' garbage writes are
    # self-contained). The session-level drive is the shared SpecServing
    # mixin; the structural difference here: cache lengths advance IN-JIT
    # (PipelinedCaches.lengths), so the flush syncs host mirrors from the
    # returned n_new instead of advancing device state.

    @property
    def _spec_mu(self):
        return self._lock

    def _spec_session_slot(self, session_id):
        return self.sessions.get(session_id)

    def _spec_session_len(self, session_id, slot):
        return self._session_len.get(session_id, 0)

    def _spec_free_slot(self, session_id, slot):
        self.sessions.free_slot(slot)
        self._session_len.pop(session_id, None)
        self._ring_hi.pop(session_id, None)

    def _spec_drop(self, session_id):
        slot = self.sessions.unmap(session_id)
        if slot is None:
            return
        self._batcher.invalidate(
            lambda payload, _s=slot: payload[0] == _s,
            ValueError(f"session {session_id} closed"),
        )
        if self._inflight.get(session_id):
            self._dying[slot] = session_id
        else:
            self._spec_free_slot(session_id, slot)

    def _spec_new_runner(self, sampling):
        from inferd_tpu.parallel.infer import MeshSpecRunner

        return MeshSpecRunner(self.engine, sampling)

    def _spec_plain_submit(self, slot, last_tok, session_id):
        return self._batcher.submit((slot, last_tok, session_id, None))

    def enable_spec(self, draft_layers: int, k: int, raw_params) -> None:
        self.engine.enable_spec(draft_layers, k, raw_params)
        self._spec = self._spec_init(k, self.engine.mb)

    def spec_open(self, session_id: str, prompt_ids, sampling, seed: int = 0,
                  parent: "str | None" = None, pin_len: int = 0,
                  prefix_logits=None, want_lp: bool = False):
        """Claim a slot, prefill target + draft, return the first token.
        The session stays in-flight until spec_close (idle slots between
        rounds must not be evicted). Raises BufferError on budget/slots.
        `parent`/`pin_len`/`prefix_logits` compose speculation with prefix
        caching exactly like batch_executor.spec_open (fork the parent
        slot's prefix KV, target-prefill the suffix, draft-prefill the
        whole prompt); a fork miss raises SpecForkMiss."""
        import jax
        from inferd_tpu.core.generate import bucket_len

        sp = self._spec
        if sp is None:
            raise RuntimeError("speculation not enabled on this executor")
        n = len(prompt_ids)
        if n + 1 > self.cap:
            raise BufferError(
                f"prompt of {n} exceeds spec-capped capacity {self.cap}"
            )
        runner, batcher, rkey = self._spec_runner(sampling)
        toks = np.asarray([list(prompt_ids)], np.int32)
        forked = False
        if parent is not None and 0 < pin_len <= n:
            # fork_session takes self._lock internally: call it first
            if not self.fork_session(session_id, parent, pin_len):
                raise SpecForkMiss(f"prefix fork from {parent} missed")
            forked = True
        with self._lock:
            if self._inflight.get(session_id):
                raise ValueError(f"session {session_id}: concurrent request")
            if forked:
                slot = self.sessions.get(session_id)
                if slot is None:  # evicted in the unlocked window
                    raise SpecForkMiss("forked slot evicted before open")
            else:
                slot = self._assign(session_id)
            self._inflight[session_id] = 1
            try:
                start = pin_len if forked else 0
                suffix = toks[:, start:]
                if suffix.shape[1]:
                    logits = self.engine.step_slot(
                        slot, suffix, n - start, reset=not forked,
                        start_pos=start,
                    )
                else:
                    if prefix_logits is None:
                        raise SpecForkMiss(
                            "prompt == pinned prefix but no stored logits"
                        )
                    logits = np.asarray(prefix_logits)[None]
                b = min(bucket_len(n), self.max_len)
                padded = np.zeros((1, b), np.int32)
                padded[0, :n] = toks[0]
                runner.draft_prefill(padded, slot, 0, n)
                self._session_len[session_id] = n
                if self.engine.ring_active:
                    self._ring_hi[session_id] = max(
                        self._ring_hi.get(session_id, 0), n
                    )
                sp["dlens"][slot] = n
                sp["sid"][session_id] = (runner, batcher, rkey, want_lp)
                key, sub = jax.random.split(jax.random.PRNGKey(seed))
                sp["keys"][session_id] = key
                sp["count"][rkey] = sp["count"].get(rkey, 0) + 1
            except Exception:
                self._inflight.pop(session_id, None)
                self.sessions.drop(session_id)
                self._session_len.pop(session_id, None)
                raise
        first = runner.first_token(logits[0], sub)
        first_lp = runner.row_lp(logits[0], first) if want_lp else None
        return first, first_lp

    def _run_spec_batch(self, runner, entries) -> None:
        """Spec flush: ONE SPMD round advances every waiting slot."""
        sp = self._spec
        MB = self.engine.mb
        with self._lock:
            active = np.zeros((MB,), bool)
            last = np.zeros((MB,), np.int32)
            catch = np.zeros((MB,), np.int32)
            catch_mask = np.zeros((MB,), bool)
            keys = np.zeros((MB, 2), np.uint32)
            sampled = runner.sampling.temperature > 0.0
            wants = {}
            for e in entries:
                slot, sid, lt, pt, sub = e.payload
                active[slot] = True
                last[slot] = lt
                ent = sp["sid"].get(sid)
                wants[slot] = bool(ent and ent[3])
                if sp["dlens"][slot] < self._session_len.get(sid, 0):
                    catch[slot] = pt
                    catch_mask[slot] = True
                if sampled:
                    keys[slot] = sub
            dlens = np.asarray(sp["dlens"], np.int32)
            want_flush = any(wants.values())
            res = runner.run_round(
                last, catch, catch_mask, dlens, active,
                keys if sampled else None, want_lp=want_flush,
            )
            if want_flush:
                toks, n_new, lps, tis, tls = res
            else:
                toks, n_new = res
            for e in entries:
                slot, sid, _, _, _ = e.payload
                n = int(n_new[slot])
                old = self._session_len.get(sid, 0)
                self._session_len[sid] = old + n
                sp["dlens"][slot] = old + min(n, runner.k)
                if self.engine.ring_active:
                    self._ring_hi[sid] = max(
                        self._ring_hi.get(sid, 0), old + runner.k + 1
                    )
                e.result = self._spec_entry_result(
                    wants.get(slot), toks[slot], n,
                    lps[slot] if want_flush else None,
                    tis[slot] if want_flush else None,
                    tls[slot] if want_flush else None,
                )

    # -- node executor surface (same contract as Qwen3StageExecutor) --------

    def process(self, session_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """payload: {"tokens": int32 [1, S], "start_pos": int, "real_len"}.
        The mesh node is first AND last stage, so the reply carries
        last-real-token logits [1, V] or, for a one-token decode hop that
        asks for its token (runtime/executor.parse_decode_ask), {"tokens":
        [[id]], "key", ...}."""
        toks = np.asarray(payload["tokens"], dtype=np.int32)
        if toks.ndim != 2 or toks.shape[0] != 1:
            raise ValueError(f"mesh stage expects tokens [1, S], got {toks.shape}")
        start_pos = int(payload.get("start_pos", 0))
        # a session's first call: where its `lane` span begins (once a request)
        t_in = tracelib.now() if start_pos == 0 else None
        real_len = int(payload.get("real_len", toks.shape[1]))
        decode = real_len == 1 and start_pos > 0
        # a hop that asks for its token (parse_decode_ask) is answered with
        # it; any other with its logits row
        ask = parse_decode_ask(payload) if decode else None

        with self._lock:
            if self._inflight.get(session_id):
                # a duplicate/replayed request racing the original would
                # pass the frontier check and double-advance the slot
                raise ValueError(
                    f"session {session_id}: concurrent request (one step at "
                    "a time per session)"
                )
            slot = self.sessions.get(session_id)
            new = slot is None
            bound = Admissions.KNOWN
            if new:
                if start_pos != 0:
                    raise ValueError(
                        f"session {session_id}: unknown session resumed at "
                        f"start_pos {start_pos} (cache evicted or node restarted)"
                    )
                slot = self._assign(session_id)
                bound = self.sessions.admit.last
            else:
                have = self._session_len.get(session_id, 0)
                # whatever this call is, the slot is back: the next drain
                # runs nothing ahead on the strength of the hop before it
                self._carry.pop(slot, None)
                if start_pos == 0 and have:
                    # session restart under the same id: reset the slot
                    self._session_len[session_id] = 0
                    self._ring_hi.pop(session_id, None)
                    self._forget(slot, freed=True)  # the prefill resets the length
                    have = 0
                    new = True  # step with reset
                if start_pos + real_len > self.cap:
                    # checked BEFORE the rollback mutation (a rejected
                    # oversized replay must not leave the slot rolled back).
                    # `cap` < max_len while speculation is enabled
                    # (verify-chunk headroom on every live session).
                    raise BufferError(
                        f"session {session_id}: KV overflow "
                        f"({start_pos}+{real_len} > {self.cap})"
                    )
                if slot in self._ahead:
                    # before any frontier moves: a call that is not the hop
                    # the row was run for drops it (`_claim`, `_forget`)
                    self._claim(slot, start_pos, int(toks[0, 0]), ask)
                if start_pos != have:
                    if 0 < start_pos < have:
                        # deterministic chunk REPLAY (a client re-sent after
                        # a lost response): roll the slot's frontier back
                        # and recompute — identical KV (deterministic
                        # forward). Ring storage bounds the depth: past the
                        # margin the rings have already overwritten the
                        # rolled-back positions (same guard as the stage
                        # executor's replay path); uniform layouts accept
                        # any depth.
                        if self.engine.ring_active:
                            from inferd_tpu.core.cache import RING_MARGIN

                            hi = max(self._ring_hi.get(session_id, 0), have)
                            if hi - start_pos > RING_MARGIN:
                                raise ValueError(
                                    f"session {session_id}: replay rollback "
                                    f"to {start_pos} exceeds the ring margin "
                                    f"(high-water mark {hi})"
                                )
                        self.engine.set_slot_length(slot, start_pos)
                        self._session_len[session_id] = start_pos
                    else:
                        raise ValueError(
                            f"session {session_id}: start_pos {start_pos} != "
                            f"cache length {have} (out-of-order chunk)"
                        )
            if start_pos + real_len > self.cap:
                raise BufferError(
                    f"session {session_id}: KV overflow "
                    f"({start_pos}+{real_len} > {self.cap})"
                )
            self._inflight[session_id] = 1
            if not decode:
                # inside a prefill: no decode pass waits for this slot
                # until one has served it again
                self._batcher.unexpect(lambda p, _s=slot: p[0] == _s)
        # process entered -> _lock released (a drain holds _lock to the end
        # of the pass before its own)
        self._lane_span(t_in, slot, bound)

        try:
            if decode:
                res = self._batcher.submit((slot, int(toks[0, 0]), session_id, ask))
                if isinstance(res, _Step):
                    # a pass that other sessions' drains do not wait for
                    # (`_decode_ahead`): this thread waits for it
                    res = self._ridden(res, slot)
                if isinstance(res, dict):
                    return {**res, "real_len": 1, "start_pos": start_pos}
                logits = res[None, :]
            elif (
                start_pos == 0 and real_len > 1 and self.engine.sp_active
            ):
                # sequence-parallel prefill: the prompt shards over the sp
                # axis (ring attention per layer), K/V gathers into the
                # slot's cache — each chip pays 1/sp of the prefill; decode
                # continues on the standard pass token-exact. Chunked
                # continuations (start_pos > 0) use the standard path.
                with self._lock:
                    logits = self.engine.sp_prefill_slot(slot, toks, real_len)
                    self._session_len[session_id] = real_len
            else:
                with tracelib.holding(self._lock, self.tracer, kind="prefill"):
                    logits = self.engine.step_slot(
                        slot, toks, real_len, reset=new, start_pos=start_pos
                    )
                    self._session_len[session_id] = start_pos + real_len
                    if self.engine.ring_active:
                        self._ring_hi[session_id] = max(
                            self._ring_hi.get(session_id, 0),
                            start_pos + real_len,
                        )
        finally:
            with self._lock:
                self._inflight.pop(session_id, None)
                if self._dying.get(slot) == session_id:  # ended mid-request
                    del self._dying[slot]
                    self._session_len.pop(session_id, None)
                    self._ring_hi.pop(session_id, None)
                    self.sessions.free_slot(slot)

        return {
            "logits": logits,
            "real_len": real_len,
            "start_pos": start_pos,
        }

    def export_sessions(self, only: "str | None" = None):
        """Snapshot live sessions' slot KV for migration/shutdown handoff
        (stage-executor payload schema; layer axis reassembled across
        pp/tp ranks by PipelinedEngine.export_slot) — so _export_and_handoff
        and /import_session work unchanged for --mesh replicas. `only`
        exports a single session (the prefill->decode handoff path)."""
        from inferd_tpu.runtime import handoff

        out = []
        with self._lock:
            pairs = [
                (sid, self.sessions.get(sid))
                for sid in self.sessions.ids()
                if only is None or sid == only
            ]
            for sid, slot in pairs:
                if slot is None:
                    continue
                self._forget(slot)  # the slot reads as long as the host believes
                k, v, ln, kl, vl = self.engine.export_slot(slot)
                if ln <= 0:
                    continue
                hi = max(self._ring_hi.get(sid, 0), ln) if kl is not None else None
                out.append((sid, handoff.encode(
                    np.ascontiguousarray(k[:, :, :ln]),
                    np.ascontiguousarray(v[:, :, :ln]), ln,
                    k_loc=None if kl is None else np.ascontiguousarray(kl),
                    v_loc=None if vl is None else np.ascontiguousarray(vl),
                    hi=hi,
                )))
        return out

    def import_session(self, session_id: str, payload: Dict[str, Any]) -> bool:
        """Adopt a migrated session into a free slot (same-model mesh
        replicas — possibly a DIFFERENT pp/tp split: import_slot re-shards
        onto this mesh). Shape mismatches reject cleanly."""
        from inferd_tpu.runtime import handoff

        if payload.get("adapter") is not None:
            # a tenant session's KV was built with its adapter; the mesh
            # executor has no registry (--adapters is lane-executor-only)
            # so adopting would silently resume on the base weights —
            # decline and let it land on a registry replica or restart
            return False
        dec = handoff.decode(
            payload, self.cfg, self.cfg.num_layers, 0, self.cap,
            want_ring=self.engine.ring_active,
        )
        if dec is None:
            return False
        k, v, n = dec["k"], dec["v"], dec["n"]
        with self._lock:
            if session_id in self.sessions:
                return False
            try:
                slot = self._assign(session_id)
            except BufferError:
                return False
            try:
                self.engine.import_slot(
                    slot, k, v, n, k_loc=dec["k_loc"], v_loc=dec["v_loc"]
                )
            except (ValueError, BufferError):
                self.sessions.drop(session_id)
                return False
            self._session_len[session_id] = n
            if self.engine.ring_active:
                # the source's rings' stale slots reach ITS high-water mark
                # — the replay guard here must inherit the true value
                self._ring_hi[session_id] = dec["hi"]
        return True

    def stats(self):
        """Coalescing effectiveness for /stats."""
        return {
            "mode": "mesh",
            "pp": self.plan.pp,
            "slots": self.engine.mb,
            "sessions": len(self.sessions),
            "kv_window_fallback": self.kv_window_fallback,
            "kv_layout": self.engine.caches.layout,
            "sampled_rows": self.sampled_rows,
            "logit_rows": self.logit_rows,
            # rows run ahead of their hops (`_decode_ahead`), those a hop
            # claimed, those dropped (the lane executor's names and meanings)
            "ahead_rows": self.ahead_rows,
            "ahead_claimed": self.ahead_claimed,
            "ahead_dropped": self.ahead_dropped,
            **self.sessions.admit.stats(),
            **self._batcher.stats(),
            # pipeline passes of the raw serving steps and how many of
            # their stage-ticks did a live session's work (the rest are
            # fill, drain and idle-slot bubbles)
            "pipeline": {
                "passes": self.engine.passes,
                "stage_ticks": self.engine.stage_ticks,
                "stage_ticks_useful": self.engine.stage_ticks_useful,
            },
            **self.spec_stats(),
        }

    def _run_decode_batch(self, _entries) -> None:
        """Flush callback (runtime/window.py, formation): the hops of every
        slot whose entry is pending once the mesh is ours, one pass ahead of
        their sessions (`_decode_ahead`)."""
        with self._lock:
            self._settle()
            # the batcher stamps each entry's lock_wait and batch_wait
            entries = self._batcher.drain_pending()
            if entries:  # else every waiting entry was invalidated: no pass
                self._decode_ahead(entries)

    # -- one pass ahead (docs/SERVING.md "One step ahead"; call under _lock) --

    def _assign(self, session_id: str, keep=()) -> int:
        """A slot for a new session, whoever had to give it up (`keep`:
        sessions that must not, beside those with a request in flight)."""
        slot = self.sessions.assign(session_id, protected=set(self._inflight) | set(keep))
        # assign() may have evicted a session: drop orphaned lengths and
        # ring marks, a leftover mark under this id (it belongs to a previous
        # session's rings and would wrongly reject legal replays), and
        # whatever a pass ran ahead for the slot's last holder
        self._session_len = {
            s: l for s, l in self._session_len.items() if s in self.sessions
        }
        self._ring_hi = {
            s: h for s, h in self._ring_hi.items() if s in self.sessions and s != session_id
        }
        self._forget(slot, freed=True)
        return slot

    def _forget(self, slot: int, freed: bool = False) -> None:
        """Drop what was run ahead for `slot` (its session ended, was
        evicted, forked from, exported, or sent something else than the hop
        the row was run for). The pass moved the slot's length ON THE
        DEVICES past the row; the host's mirror never moved. Whoever reads
        the slot next has to find the length the host believes: it is set
        back to the row's position, behind whatever pass is still running
        (`freed`: the slot's next holder sets its length itself: a prefill
        that resets it, a fork, an import). The row stays where it was
        written, beyond that length, and whoever writes there next
        overwrites it."""
        rec = self._ahead.pop(slot, None)
        if rec is not None:
            self.ahead_dropped += 1
            if not freed:
                self.engine.set_slot_length(slot, rec.pos)
        self._carry.pop(slot, None)

    def _runs_ahead(self, slot: int, ask, src: _Step) -> bool:
        """Whether the hop after the one `ask` came with gets its row run
        before it arrives (the mirror of the slot's length is that row's
        position): the ask promised the hop, the slot still has its session,
        the row and the one after it fit, what `src` chose for it, where the
        host knows it already, does not end the generation, and no
        speculative round reads the slots' lengths."""
        sid = self.sessions.owner(slot)
        return (
            ask is not None and ask.ahead >= 1 and self._spec is None and sid is not None
            and self._session_len.get(sid, 0) + 2 <= self.cap
            and not (src.done and (src.error is not None or src.ends(slot, ask.eos)))
        )

    def _settle(self) -> None:
        """Before a drain: a prefill holds _lock to the end of its pass,
        which ran behind the pass dispatched before it, so a drain that
        follows a prefill finds that pass ended (and a session whose prefill
        is ending never finds two passes between it and its first decode
        hop: while anyone else holds _lock at most one pass is unfinished).
        Such a pass is read now, so the next one is fed from the host's copy
        of what it chose and runs no row past an `eos` the host can see."""
        prev = self._last_step
        if prev is not None and not prev.done and prev.packed.is_ready():
            self._finish(prev)

    def _decode_ahead(self, entries) -> None:
        """The hops of a drain, one pass ahead of the sessions; the lanes'
        order (BatchedExecutor._decode_ahead):

        1. Each entry is CLAIMED (`_claim`: a pass already ran the row this
           hop asks for, before it arrived: its mirror moves past the row
           now) or RIDES (no row was run: a session's first decode hop, a
           hop whose ask promised nothing, a raw /forward). ONE pass is
           dispatched for the rows to come: for every claimed slot whose
           ask says a hop follows, and for every slot that rode the last
           pass under such an ask and is not back yet, the NEXT row, fed by
           what the last pass's packed array holds for it where the devices
           still hold it alone (no round trip); for every rider its own row
           from what its hop carries.
        2. The pass dispatched at the previous drain is waited for and
           copied out (`_finish`), and each claimed entry is answered from
           the pass that ran its row. The sessions take their turn while
           the pass of (1) runs.
        3. A rider is answered by its own thread (`_ridden` waits for the
           pass it rode, under no lock). Where nobody is ahead (no claimed
           entry, no row run ahead in this pass: a lone session, a cohort's
           first hops, callers that promise nothing) the drain is what it
           was: the flusher waits for the pass and answers; the riders'
           next rows, where promised, are dispatched right behind it.

        `batched_steps` counts the passes dispatched and `batched_tokens`
        the rows they ran for a session (the drain counted one pass and its
        entries); `pipeline.*` count a pass at its dispatch."""
        prev = self._last_step
        claimed, riders, answers, conts = [], [], {}, {}
        for e in entries:
            slot, tok, sid, ask = e.payload
            mine = self._claim(slot, self._session_len.get(sid, 0), tok, ask)
            (claimed if mine else riders).append(e)
        for e in claimed:  # the hop takes its row: the mirror moves past it
            slot, _, sid, ask = e.payload
            answers[slot] = src = self._ahead.pop(slot).step
            self._session_len[sid] += 1
            if self._runs_ahead(slot, ask, src):
                conts[slot] = (src, ask)
        if self._carry:
            self._take_carry(conts, {e.payload[0] for e in entries})
        self.ahead_claimed += len(claimed)
        self.sampled_rows += len(claimed)
        # a dispatch that failed (None) ran no row and has failed its riders
        step = self._dispatch_rows(conts, riders) if conts or riders else None
        programs, rows = (1, len(conts)) if step is not None else (0, 0)
        if prev is not None:
            prev.released.set()
        # nobody is ahead: the flusher answers its riders itself, and runs
        # their next rows right behind the pass they ride
        sync = bool(riders) and not claimed and not conts
        if sync and step is not None:
            nxt = {}
            self._take_carry(nxt)
            if nxt and self._dispatch_rows(nxt, []) is not None:
                programs, rows = programs + 1, rows + len(nxt)
        if prev is not None:
            self._wait_out(prev)
        if sync and step is not None:
            self._wait_out(step)
        self._batcher.stamp_out(claimed + riders if sync else claimed)  # `deliver` starts
        for e in claimed:
            slot = e.payload[0]
            if answers[slot].error is not None:
                e.error = answers[slot].error
            else:
                e.result = answers[slot].replies[slot]
        for e in riders if step is not None else ():
            if not sync:
                e.result = step  # `process` waits for it (`_ridden`)
            elif step.error is not None:
                e.error = step.error
            else:
                e.result = step.reply(e.payload[0])
        self._batcher.n_steps += programs - 1
        self._batcher.n_served += rows - len(claimed) - (len(riders) if step is None else 0)

    def _dispatch_rows(self, conts, riders) -> Optional[_Step]:
        """ONE pass (PipelinedEngine.dispatch_slots) over the rows of
        `conts`, {slot: (the pass whose output is the row's input, the ask
        it runs under)}: rows run AHEAD of their hops, recorded in `_ahead`
        (each AT the mirror of its slot's length; it counts as written: a
        ring has given up its oldest slot); and of `riders`: entries waiting
        for their row, run from their host token, their mirrors moved at
        once. The lengths themselves move inside the pass. A dispatch that
        raises fails its riders, leaves no row recorded and returns None."""
        eng = self.engine
        last = self._fed_by(conts)
        toks, asks, plain, carry, ahead, made = {}, {}, [], {}, [], {}
        for slot, (src, ask) in conts.items():
            if src.done:  # the host has read it (a rider's thread may finish `last` any time)
                toks[slot] = src.toks[slot]
                asks[slot] = ask._replace(key=np.asarray(src.keys[slot], np.uint32))
            else:
                ahead.append(slot)
                asks[slot] = ask
            sid = self.sessions.owner(slot)
            made[slot] = self._ahead[slot] = _Ahead(self._session_len[sid], 1, src, ask)
            if eng.ring_active:
                self._ring_hi[sid] = max(self._ring_hi.get(sid, 0), made[slot].pos + 1)
        live = []  # riders whose session has not ended meanwhile (`_dying`)
        for e in riders:
            slot, tok, sid, ask = e.payload
            toks[slot] = tok
            alive = self._dying.get(slot) != sid
            if alive:
                live.append(sid)
            if ask is None:
                plain.append(slot)
            else:
                asks[slot] = ask
                if ask.ahead >= 1 and alive:
                    carry[slot] = ask
        try:
            logits, packed, top_n = eng.dispatch_slots(
                toks, asks, ahead, last.packed if ahead else None
            )
        except Exception as exc:
            self._recorded(made, None)
            for e in riders:
                e.error = exc
            return None
        step = self._last_step = _Step(
            packed, logits if plain else None, top_n, asks, plain,
            list(conts) + [e.payload[0] for e in riders], last,
            program_name(eng._step_raw_multi),
        )
        self._recorded(made, step)
        for sid in live:  # in lockstep with the device-side length
            self._session_len[sid] = self._session_len.get(sid, 0) + 1
            if eng.ring_active:
                self._ring_hi[sid] = max(self._ring_hi.get(sid, 0), self._session_len[sid])
        for slot, ask in carry.items():
            self._carry[slot] = (step, ask)
        self.ahead_rows += len(conts)
        self.sampled_rows += len(riders) - len(plain)
        self.logit_rows += len(plain)
        return step

    def fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        """Seed a new session's slot from the parent slot's KV prefix
        (prefix caching on the in-mesh pipelined path — the copy is
        shard-local on every pp rank). False on any miss; the caller falls
        back to a full prefill."""
        if prefix_len <= 0:
            return False
        with self._lock:
            pslot = self.sessions.get(parent_session_id)
            if (
                pslot is None
                or self._session_len.get(parent_session_id, 0) < prefix_len
                or new_session_id in self.sessions
            ):
                return False
            if self.engine.ring_active:
                # ring fork-truncation margin (core.cache aliasing
                # invariant): the child's rings carry parent data up to the
                # parent's HIGH-WATER mark; slots past prefix_len stay
                # structurally outside every window only while the
                # truncation depth is under the margin
                from inferd_tpu.core.cache import RING_MARGIN

                phi = max(
                    self._ring_hi.get(parent_session_id, 0),
                    self._session_len.get(parent_session_id, 0),
                )
                if phi - prefix_len > RING_MARGIN:
                    return False
            try:
                slot = self._assign(new_session_id, keep=(parent_session_id,))
            except BufferError:
                return False
            self._forget(pslot)  # a row run ahead for the parent: the child copies none of it
            self.engine.fork_slot(pslot, slot, prefix_len)
            self._session_len[new_session_id] = prefix_len
            if self.engine.ring_active:
                # the child's rings inherit the PARENT's stale frontier
                self._ring_hi[new_session_id] = max(
                    self._ring_hi.get(parent_session_id, 0),
                    self._session_len.get(parent_session_id, 0),
                )
        return True

    def end_session(self, session_id: str) -> None:
        with self._lock:
            slot = self.sessions.unmap(session_id)
            if slot is None:
                return
            # fail-fast decode entries still waiting in the window; a
            # request mid-device-step defers the slot free until it drains
            self._batcher.invalidate(
                lambda payload, _s=slot: payload[0] == _s,
                ValueError(f"session {session_id} ended mid-request"),
            )
            self._forget(slot, freed=True)
            if self._inflight.get(session_id):
                self._dying[slot] = session_id
            else:
                self.sessions.free_slot(slot)
                self._session_len.pop(session_id, None)
                self._ring_hi.pop(session_id, None)
