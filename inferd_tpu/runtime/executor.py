"""Stage executor: jitted per-stage forward with per-session KV caches.

The compute half of a node. Capability parity with the reference's
`Qwen3Server.send` (/root/reference/models/qwen3/server/
qwen3_server_module.py:237-255 — run my layer range with a per-session
DynamicCache) and `PartitionedQwen2.forward` (/root/reference/petals/
partitioned_models.py:145-168 — first/inner/last stage dispatch), redesigned:

  * functional preallocated KV caches per session (static shapes for jit),
    bucket-grown on demand, LRU-evicted;
  * prompt chunks padded to power-of-two buckets so XLA compiles once per
    bucket instead of once per length;
  * RoPE is computed from absolute positions inside the stage, so the wire
    carries only (tokens|hidden, start_pos) — not cos/sin/mask tensors like
    the reference's 5-tensor gRPC payload (rpc_client.py:47-54).

Thread-safety: process() is called from a worker thread pool (the node keeps
compute off its event loop — fixing reference bug B5); a per-session lock
serializes steps of one session while different sessions run concurrently.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import ModelConfig
from inferd_tpu.core.cache import RING_MARGIN, KVCache, from_wire, grow, wire_heads
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.core.sampling import rows_cover
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import StageSpec


class SessionStore:
    """session_id -> KVCache with LRU eviction and idle TTL."""

    def __init__(self, max_sessions: int = 64, ttl_s: float = 600.0):
        self.max_sessions = max_sessions
        self.ttl_s = ttl_s
        self._lock = threading.Lock()
        self._caches: Dict[str, KVCache] = {}
        self._locks: Dict[str, threading.Lock] = {}
        self._last_used: Dict[str, float] = {}

    def lock_for(self, session_id: str) -> threading.Lock:
        with self._lock:
            if session_id not in self._locks:
                self._locks[session_id] = threading.Lock()
            return self._locks[session_id]

    def get(self, session_id: str) -> Optional[KVCache]:
        with self._lock:
            c = self._caches.get(session_id)
            if c is not None:
                self._last_used[session_id] = time.monotonic()
            return c

    def put(self, session_id: str, cache: KVCache) -> None:
        with self._lock:
            self._caches[session_id] = cache
            self._last_used[session_id] = time.monotonic()
            self._evict_locked()

    def drop(self, session_id: str) -> None:
        with self._lock:
            self._caches.pop(session_id, None)
            self._locks.pop(session_id, None)
            self._last_used.pop(session_id, None)

    def items_snapshot(self):
        """Point-in-time [(session_id, cache)] — for migration export."""
        with self._lock:
            return list(self._caches.items())

    def kv_bytes(self) -> int:
        """Total bytes of live session KV buffers — the node's /metrics
        `kv.bytes` gauge (capacity-planning observability)."""
        total = 0
        for _sid, c in self.items_snapshot():
            for arr in (c.k, c.v, c.k_loc, c.v_loc):
                total += int(getattr(arr, "nbytes", 0) or 0)
        return total

    def sweep(self) -> int:
        """Drop sessions idle for > ttl_s; returns count dropped."""
        now = time.monotonic()
        with self._lock:
            stale = [s for s, t in self._last_used.items() if now - t > self.ttl_s]
            for s in stale:
                self._caches.pop(s, None)
                self._locks.pop(s, None)
                self._last_used.pop(s, None)
            return len(stale)

    def __len__(self) -> int:
        with self._lock:
            return len(self._caches)

    def __contains__(self, session_id: str) -> bool:
        with self._lock:
            return session_id in self._caches

    def ids(self):
        """Live session ids (for the gossip session-location advertising,
        runtime/node.py announce)."""
        with self._lock:
            return list(self._caches)

    def _evict_locked(self) -> None:
        while len(self._caches) > self.max_sessions:
            oldest = min(self._last_used, key=self._last_used.get)
            self._caches.pop(oldest, None)
            self._locks.pop(oldest, None)
            self._last_used.pop(oldest, None)


#: top log-probabilities a step that samples on the device computes: none,
#: or one of these widths (static in the program, so every width is one
#: compiled variant; the node's warm-up compiles none and the first, what
#: the benchmark's probe asks)
BLOCK_TOP_WIDTHS = (8, 64)


class SampleAsk(NamedTuple):
    """How a hop asks for its tokens to be chosen on the device."""

    sampling: tuple  # (temperature, top_k, top_p, min_p)
    want: int  # log-probabilities: 0 none, else the top-n asked (the token's own: 1)
    key: Any  # uint32 [2]: the session's PRNG chain
    # a decode hop alone (parse_decode_ask): how many hops of this session
    # will follow this one if no `eos` ends it (0: none is promised), and
    # the token that would (-1: none). What a lane executor may run ahead.
    ahead: int = 0
    eos: int = -1

    @property
    def top_n(self) -> int:
        """The width of BLOCK_TOP_WIDTHS that serves `want` (0: none is
        asked; None: none is wide enough)."""
        if not self.want:
            return 0
        return next((w for w in BLOCK_TOP_WIDTHS if w >= self.want), None)


def root_key(seed: int) -> np.ndarray:
    """jax.random.PRNGKey(seed) as host uint32 [2]. For the seeds a 32-bit
    PRNGKey takes it is [0, seed] (threefry), written here: the first
    decode hop of every generation carries a seed, and its parse should
    dispatch nothing to the device."""
    if -2**31 <= seed < 2**31:
        return np.array([0, seed & 0xFFFFFFFF], np.uint32)
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


def parse_ask(d: Dict[str, Any]) -> SampleAsk:
    """The ONE reader of a sampling ask, whichever call carries it (a
    K-step call and a decode hop at the payload's top level, a block call
    inside its `block` key): optional "sampling" ({temperature, top_k,
    top_p, min_p}: greedy default), optional "key" ([2] uint32, the
    session's PRNG chain as the last reply returned it) / "seed" (derives
    the chain's root when no key rides yet), optional "top_logprobs": n
    (with "logprobs": true alone, the token's own)."""
    s = d.get("sampling") or {}
    sampling = (
        float(s.get("temperature", 0.0)), int(s.get("top_k", 0)),
        float(s.get("top_p", 1.0)), float(s.get("min_p", 0.0)),
    )
    if not 0.0 <= sampling[3] < 1.0:
        raise ValueError(f"min_p must be in [0, 1), got {sampling[3]}")
    key = d.get("key")
    if key is None:
        key = root_key(int(d.get("seed", 0) or 0))
    return SampleAsk(
        sampling=sampling,
        want=max(int(d.get("top_logprobs", 0) or 0), 1 if d.get("logprobs") else 0),
        key=np.asarray(key, np.uint32),
    )


def parse_kstep(payload: Dict[str, Any], budget: int):
    """Parse a multi-step fused-decode request out of a /forward payload,
    shared by all three executors (solo/batched/stage-batch) so the wire
    contract cannot drift.

    Payload keys: "decode_steps" (requested K), optional "eos" (stop token
    id; absent = none), and the sampling ask as parse_ask reads it
    ("sampling", "key" / "seed").

    Returns None when the payload requests no multi-step decode, else
    {"k": K clamped into [1, budget] (falling back toward K=1 at budget
    boundaries so the KV write can never overflow), "sampling": tuple,
    "eos": int (-1 = none), "key": uint32 [2]}.
    """
    k_req = int(payload.get("decode_steps") or 0)
    if k_req <= 0:
        return None
    if budget < 1:
        raise BufferError(f"KV overflow: no budget for a decode step ({budget})")
    ask = parse_ask(payload)
    eos = payload.get("eos")
    return {
        "k": max(1, min(k_req, int(budget))),
        "sampling": ask.sampling,
        "eos": -1 if eos is None else int(eos),
        "key": ask.key,
    }


def parse_decode_ask(payload: Dict[str, Any]) -> Optional[SampleAsk]:
    """The ask of a one-token decode hop that a step's sampler can honour
    (core.sampling.sample_rows), or None: the hop carries none (no
    "sampling" key: a raw /forward), or one outside the device form (top-p
    with no top-k, a top-k over the candidates, more top log-probabilities
    than the widest variant): that hop is answered with its logits.

    Two more optional keys ride a decode hop's ask: "ahead": n, the hops
    of this session that will follow this one (the generation loop's
    `max_new_tokens` less what it has; absent or 0: no promise), and "eos":
    the token id that ends the generation early. An executor that keeps a
    step ahead of its sessions (runtime/batch_executor.py) runs a lane's
    next step before its hop arrives only where the ask promised one."""
    if payload.get("sampling") is None:
        return None
    ask = parse_ask(payload)
    if ask.top_n is None or not rows_cover(*ask.sampling):
        return None
    return ask._replace(**_promise(payload))


def _promise(d: Dict[str, Any]) -> Dict[str, int]:
    """The two keys by which a decode hop's ask and a block hop's `block`
    call say what follows: "ahead" (absent, 0 or negative: no promise) and
    "eos" (absent: -1, no token ends the generation early)."""
    eos = d.get("eos")
    return {"ahead": max(0, int(d.get("ahead") or 0)), "eos": -1 if eos is None else int(eos)}


def call_kind(payload: Dict[str, Any]) -> str:
    """What a /forward payload asks of a whole-model executor, by name:
    "block" (a block of a model generated by blocks: its `block` key),
    "decode" (one token at an established frontier) or "prefill" (anything
    else). The name rides the `compute` and `device` spans, so no reader
    infers a kind from sizes."""
    if not isinstance(payload, dict):
        return "prefill"
    if payload.get("block") is not None:
        return "block"
    try:
        x = payload.get("tokens")
        if x is None:
            x = payload.get("hidden")
        n = payload.get("real_len")
        n = np.shape(x)[1] if n is None else int(n)
        decode = n == 1 and int(payload.get("start_pos", 0)) > 0
    except Exception:
        return "prefill"  # a malformed payload fails in the guarded compute
    return "decode" if decode else "prefill"


class BlockCall(NamedTuple):
    """One lane's part of a block step (BatchedEngine._block_step)."""

    known: int  # leading places of the block the caller filled
    sampling: tuple  # (temperature, top_k, top_p, min_p): static in the program
    top_n: int  # 0 = no log-probabilities, else a width of BLOCK_TOP_WIDTHS
    key: Any  # uint32 [2]: the session's PRNG chain
    # as a decode hop's ask has them (SampleAsk): how many BLOCK hops of this
    # session will follow this one if no `eos` ends it (0: none is promised),
    # and the token that would (-1: none)
    ahead: int = 0
    eos: int = -1


def parse_block(payload: Dict[str, Any], block_length: int) -> BlockCall:
    """The `block` key of a /forward payload: {"known": leading places
    filled, the sampling ask as parse_ask reads it, and optionally what a
    decode hop's ask may carry (parse_decode_ask): "ahead": n, the block
    hops that will follow this one, and "eos"}."""
    b = payload["block"]
    known = int(b.get("known", 0))
    if not 0 <= known < block_length:
        raise ValueError(f"block call: known {known} outside [0, {block_length})")
    ask = parse_ask(b)
    sampling = ask.sampling
    if sampling[0] == 0.0:
        sampling = (0.0, 0, 1.0, 0.0)  # greedy reads no filter: one variant
    if ask.top_n is None:
        raise ValueError(
            f"block call: top_logprobs {ask.want} over {BLOCK_TOP_WIDTHS[-1]}"
        )
    return BlockCall(known=known, sampling=sampling, top_n=ask.top_n, key=ask.key, **_promise(b))


def cache_intact(cache) -> bool:
    """Whether the shared KV cache survived a raising dispatch. The
    decode jits DONATE the cache: a failure raised before dispatch (host
    -side — admission, shape, a bug in array build) leaves the buffers
    untouched and per-dispatch isolation holds, but a device-side
    failure after donation leaves the executor's cache reference
    pointing at deleted buffers — every later dispatch would die on it,
    so the window must stop dispatching and fail the REMAINING entries
    (already-committed results stay committed) with a clear error."""
    k = getattr(cache, "k", None)
    return not (hasattr(k, "is_deleted") and k.is_deleted())


def kstep_hi(start: int, n: int, k: int) -> int:
    """Ring high-water frontier after a K-step window: `n` committed
    writes plus ONE frozen-frontier garbage slot when eos deactivated the
    lane early — a frozen row rewrites the SAME frontier slot each tail
    step (models/qwen3.decode_k semantics), it does not advance, so the
    mark must not claim the full K. Overstating it makes the
    `hi - start_pos > RING_MARGIN` replay guard reject legitimate
    rollbacks after an early stop."""
    return start + min(n + 1, k)


def fuse_kstep_group(decode_k_fn, params, cache, lens, lanes: int, grp,
                     ads=None):
    """Run one sampling-group of co-batched K-step lanes as ONE fused scan
    — the shared core of BatchedExecutor._run_decode_batch and
    BatchedStageExecutor.process_batch, so the group invariants (group K =
    the MINIMUM budget-clamped request; one boundary sync of K tokens per
    dispatch) have exactly one definition.

    decode_k_fn: a jit with the _decode_k_serve signature
    (params, cache, toks, lengths, active, keys, eos, k, t, tk, tp, mp,
    ads=None) -> (cache, seq, n_new, keys'). grp: [(lane, token, ks)]
    where every parse_kstep dict shares one sampling tuple. `ads`: the
    multi-tenant LoRA pools + per-lane slot ids (ops/lora pool contract)
    — every fused step serves each lane its own adapter. Returns
    (kg, seq [kg, L], n_new [L], nkeys [L, 2], new_cache) with the three
    arrays already materialized on the host.
    """
    kg = min(ks["k"] for _lane, _tok, ks in grp)
    toks = np.zeros((lanes,), np.int32)
    active = np.zeros((lanes,), bool)
    eos = np.full((lanes,), -1, np.int32)
    keys = np.zeros((lanes, 2), np.uint32)
    sampling = None
    for lane, token, ks in grp:
        toks[lane] = token
        active[lane] = True
        eos[lane] = ks["eos"]
        keys[lane] = ks["key"]
        sampling = ks["sampling"]
    t, tk, tp, mp = sampling
    cache, seq, n_new, nkeys = decode_k_fn(
        params, cache, jnp.asarray(toks), jnp.asarray(lens, jnp.int32),
        jnp.asarray(active), jnp.asarray(keys), jnp.asarray(eos),
        kg, t, tk, tp, mp, ads=ads,
    )
    # ONE boundary transfer per fused K-step dispatch (the core/batch
    # generate_all pattern); every host read downstream comes off these
    # three materialized arrays
    seq = np.asarray(seq)  # single per-dispatch boundary sync of K tokens for every lane
    n_new = np.asarray(n_new)  # same single boundary sync
    nkeys = np.asarray(nkeys)  # same single boundary sync
    return kg, seq, n_new, nkeys, cache


class Qwen3StageExecutor:
    """Executes one pipeline stage of a Qwen3-family model."""

    def __init__(
        self,
        cfg: ModelConfig,
        spec: StageSpec,
        stage_params: Dict[str, Any],
        max_len: int = 4096,
        max_sessions: int = 64,
        session_ttl_s: float = 600.0,
        initial_kv_len: int = 256,
    ):
        self.cfg = cfg
        self.spec = spec
        # one host->device transfer, here: a stage checkpoint loads as numpy
        # (parallel.stages.load_stage_checkpoint), and numpy leaves handed to
        # a jit are copied to the device again on EVERY call — the whole
        # model per token on a chip. Arrays already on a device stay put.
        self.params = jax.device_put(stage_params)
        self.max_len = max_len
        self.initial_kv_len = initial_kv_len
        self.sessions = SessionStore(max_sessions, session_ttl_s)
        # ring-KV replay safety: high-water mark of positions ever written
        # per session. A replay rollback is safe only while hi - start_pos
        # stays under RING_MARGIN (the aliasing invariant); guarding on the
        # CURRENT length alone would let compound replays walk the frontier
        # back past data the rings have already overwritten. Own lock: the
        # per-session locks don't cover cross-session mutations (prune).
        self._ring_hi: Dict[str, int] = {}
        self._hi_lock = threading.Lock()

        cfg_ = cfg
        spec_ = spec

        # cache donation: the KV update writes in place on device instead of
        # XLA copying the whole per-session buffer every step (the engines
        # already do this; the caller always rebinds to the returned cache).
        # If a dispatch fails mid-flight the donated-but-stale store entry
        # surfaces as a deleted-array error on the session's NEXT chunk ->
        # 500 -> the client restarts the session (retryable by design).
        @partial(jax.jit, donate_argnames=("cache",))
        def _run(params, x, start_pos, cache: KVCache, real_len):
            # x: tokens [B, S] on the first stage, hidden [B, S, H] otherwise
            if spec_.is_first:
                hidden = qwen3.embed(params, x, cfg_)
            else:
                hidden = x
            s = hidden.shape[1]
            positions = start_pos + jnp.broadcast_to(jnp.arange(s), hidden.shape[:2])
            hidden, nc, _ = qwen3.forward_layers_cached(
                params["layers"], cfg_, hidden, positions, cache, cache.length,
                real_end=cache.length + real_len,
                layer_offset=spec_.start_layer,
            )
            new_cache = dataclasses.replace(nc, length=cache.length + real_len)
            if spec_.is_last:
                # client-side sampling: ship float32 logits of the LAST real
                # token only (reference ships full hidden states every hop)
                last = hidden[jnp.arange(hidden.shape[0]), real_len - 1]
                logits = qwen3.unembed(params, cfg_, last[:, None, :])[:, 0]
                return {"logits": logits}, new_cache
            return {"hidden": hidden}, new_cache

        self._run = _run

        # multi-step fused decode (single-stage topologies only: the K-step
        # inner loop needs the whole model — a pipeline stage's next token
        # depends on every other stage, so multi-stage swarms keep the
        # per-token relay and amortize dispatch via stage co-batching
        # instead). Sampling runs ON DEVICE (models/qwen3.decode_k), so the
        # host syncs once per K tokens instead of shipping logits per token.
        self._decode_k = None
        if spec.is_first and spec.is_last:

            @partial(
                jax.jit, donate_argnames=("cache",),
                static_argnames=("k", "temperature", "top_k", "top_p",
                                 "min_p"),
            )
            def _decode_k(params, tok, cache: KVCache, key, eos, k: int,
                          temperature: float, top_k: int, top_p: float,
                          min_p: float):
                lengths = jnp.broadcast_to(cache.length, (1,))
                nc, seq, n_new, keys, _lps, _tis, _tls = qwen3.decode_k(
                    params, cfg_, tok, cache, lengths,
                    jnp.ones((1,), bool), key[None], k,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    min_p=min_p, eos=eos,
                )
                nc = dataclasses.replace(nc, length=cache.length + n_new[0])
                return seq[:, 0], n_new[0], keys[0], nc

            self._decode_k = _decode_k

    # -- session cache management ------------------------------------------

    def _cache_for(self, session_id: str, real_len: int, padded_len: int) -> KVCache:
        """Cache with room for the PADDED chunk write (the jitted update
        writes padded_len rows; sizing by real_len alone would let
        dynamic_update_slice clamp and silently overwrite the newest real
        slots). The real-token budget is still capped at max_len."""
        needed = max(real_len, padded_len)
        cache = self.sessions.get(session_id)
        if cache is None:
            # a NEW incarnation (first chunk, or the id was evicted): any
            # leftover high-water mark belongs to the old rings and would
            # wrongly reject this session's legal replays
            with self._hi_lock:
                self._ring_hi.pop(session_id, None)
            cache = KVCache.create(
                self.cfg,
                self.spec.num_layers,
                1,
                max(self.initial_kv_len, bucket_len(needed)),
                layer_offset=self.spec.start_layer,
            )
        if int(cache.length) + real_len > self.max_len:
            raise BufferError(
                f"session {session_id}: KV overflow ({int(cache.length)}+{real_len} > {self.max_len})"
            )
        if int(cache.length) + needed > cache.max_len:
            cache = grow(cache, bucket_len(int(cache.length) + needed))
        return cache

    def _rollback_for(
        self, session_id: str, cache: KVCache, start_pos: int
    ) -> KVCache:
        """Resolve a chunk whose start_pos is not the session frontier: a
        chunk STARTING BEFORE the frontier is a deterministic REPLAY (the
        client re-sent after a lost response — e.g. an entry died
        mid-answer and its handed-off KV already holds the chunk): roll
        back to the chunk start and recompute. The rewritten KV is
        identical (deterministic forward); ring buffers stay exact while
        the rollback depth is under the ring margin (core.cache aliasing
        invariant). Call under the session lock."""
        cur = int(cache.length)
        if start_pos == cur:
            return cache
        if not 0 <= start_pos < cur:
            raise ValueError(
                f"session {session_id}: start_pos {start_pos} != cache "
                f"length {cur} (out-of-order chunk)"
            )
        with self._hi_lock:
            hi = max(self._ring_hi.get(session_id, 0), cur)
        if cache.k_loc is not None and hi - start_pos > RING_MARGIN:
            raise ValueError(
                f"session {session_id}: replay rollback to "
                f"{start_pos} exceeds the ring margin (high-water "
                f"mark {hi})"
            )
        return dataclasses.replace(cache, length=jnp.int32(start_pos))

    # -- public API ---------------------------------------------------------

    def process(self, session_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Run this stage for one request.

        payload: {"tokens": int32 [B, S]} on stage 0, else {"hidden": [B, S, H]};
        plus "start_pos": int (absolute position of the chunk's first token).
        Padded chunks pass "real_len" (tokens beyond it are bucket padding).
        Returns {"hidden": ...} or, on the last stage, {"logits": [B, V]}.

        A payload carrying "decode_steps" takes the multi-step fused
        decode path instead (single-stage topologies; see
        _process_decode_k).
        """
        # route on the SAME predicate parse_kstep uses (k_req > 0): a
        # zero/negative decode_steps is a legacy single-token step on
        # every executor, not an assertion failure here alone
        if int(payload.get("decode_steps") or 0) > 0:
            return self._process_decode_k(session_id, payload)
        start_pos = int(payload.get("start_pos", 0))
        if self.spec.is_first:
            toks = np.asarray(payload["tokens"], dtype=np.int32)
            real_len = int(payload.get("real_len", toks.shape[1]))
            # pad prompt chunks to a power-of-two bucket (single-token decode
            # steps stay unpadded) so jit compiles once per bucket
            if toks.shape[1] > 1:
                b = bucket_len(toks.shape[1])
                toks = np.pad(toks, [(0, 0), (0, b - toks.shape[1])])
            x = jnp.asarray(toks)
        else:
            h = np.asarray(payload["hidden"])
            real_len = int(payload.get("real_len", h.shape[1]))
            # upstream ships only real rows (wire diet); re-pad to the bucket
            # locally so jit still compiles once per bucket
            if h.shape[1] > 1:
                b = bucket_len(max(h.shape[1], real_len))
                h = np.pad(h, [(0, 0), (0, b - h.shape[1]), (0, 0)])
            x = jnp.asarray(h, dtype=self.cfg.jnp_dtype)

        lock = self.sessions.lock_for(session_id)
        with lock:
            cache = self._cache_for(session_id, real_len, int(x.shape[1]))
            cache = self._rollback_for(session_id, cache, start_pos)
            out, new_cache = self._run(
                self.params, x, jnp.int32(start_pos), cache, jnp.int32(real_len)
            )
            self.sessions.put(session_id, new_cache)
            if new_cache.k_loc is not None:
                with self._hi_lock:
                    self._ring_hi[session_id] = max(
                        self._ring_hi.get(session_id, 0), start_pos + real_len
                    )
                    if len(self._ring_hi) > 2 * self.sessions.max_sessions:
                        # opportunistic prune: drop marks for evicted sessions
                        live = set(self.sessions.ids())
                        self._ring_hi = {
                            s: h for s, h in self._ring_hi.items() if s in live
                        }

        result = {k: np.asarray(v) for k, v in out.items()}
        if "hidden" in result:
            # ship only the real rows: a 17-token chunk must not ride the
            # wire as 32 rows of [B, S, H] bucket padding (VERDICT r1 #8)
            result["hidden"] = result["hidden"][:, :real_len]
        # relay metadata: downstream stages need the chunk's absolute
        # position and real (unpadded) length
        result["real_len"] = real_len
        result["start_pos"] = start_pos
        return result

    def _process_decode_k(
        self, session_id: str, payload: Dict[str, Any]
    ) -> Dict[str, Any]:
        """Multi-step fused decode for a solo session: K decode steps +
        on-device sampling in ONE dispatch (models/qwen3.decode_k) —
        one host sync per K tokens instead of one logits round trip per
        token.

        payload: {"tokens": [[last_tok]], "start_pos", "decode_steps": K}
        plus the optional parse_kstep keys (sampling/eos/key/seed).
        Returns {"tokens": [[t_0..t_{n-1}]], "real_len": n (tokens
        actually committed — n < K only when `eos` fired mid-window),
        "decode_steps": the K actually run (clamped at the KV budget),
        "start_pos", "key": the advanced PRNG chain}.

        The session frontier advances by exactly n, and the replay-
        rollback protocol is untouched: a re-sent chunk starting before
        the frontier rolls back and recomputes deterministically
        (greedy, or sampled with the same key).
        """
        if self._decode_k is None:
            raise ValueError(
                "decode_steps requires a single-stage (whole-model) "
                "topology — pipeline stages relay per token"
            )
        toks = np.asarray(payload["tokens"], dtype=np.int32)
        if toks.shape != (1, 1):
            raise ValueError(
                f"multi-step decode expects tokens [1, 1], got {toks.shape}"
            )
        start_pos = int(payload.get("start_pos", 0))
        if start_pos <= 0:
            raise ValueError(
                "multi-step decode needs an established frontier "
                "(start_pos > 0)"
            )
        ks = parse_kstep(payload, self.max_len - start_pos)
        assert ks is not None
        k_eff = ks["k"]
        lock = self.sessions.lock_for(session_id)
        with lock:
            cache = self._cache_for(session_id, 1, 1)
            cache = self._rollback_for(session_id, cache, start_pos)
            if start_pos + k_eff > cache.max_len:
                cache = grow(cache, bucket_len(start_pos + k_eff))
            t, tk, tp, mp = ks["sampling"]
            seq, n_new, nkey, new_cache = self._decode_k(
                self.params, jnp.asarray(toks[0]), cache,
                jnp.asarray(ks["key"]), jnp.int32(ks["eos"]), k_eff,
                t, tk, tp, mp,
            )
            seq = np.asarray(seq)
            n = int(n_new)
            self.sessions.put(session_id, new_cache)
            if new_cache.k_loc is not None:
                with self._hi_lock:
                    self._ring_hi[session_id] = max(
                        self._ring_hi.get(session_id, 0),
                        kstep_hi(start_pos, n, k_eff),
                    )
        return {
            "tokens": [seq[:n].tolist()],
            "real_len": n,
            "decode_steps": k_eff,
            "start_pos": start_pos,
            "key": np.asarray(nkey).tolist(),
        }

    def end_session(self, session_id: str) -> None:
        self.sessions.drop(session_id)
        with self._hi_lock:
            self._ring_hi.pop(session_id, None)

    def export_sessions(self, only: "str | None" = None):
        """Snapshot every live session's KV as host arrays for migration
        handoff: [(sid, {"k", "v", "length"[, "kv_dtype"][, "k_loc",
        "v_loc"]})]. Global-layer slots past `length` are garbage and not
        shipped (slice to the populated prefix); sliding-layer RINGS ship
        whole (every slot may be live — they're O(window) anyway). Narrow
        float dtypes the wire codec doesn't carry (fp8 KV) ship as a
        same-shape uint8 byte view plus their dtype name. `only` exports a
        single session (the deliberate prefill->decode handoff path)."""
        from inferd_tpu.runtime import handoff

        out = []
        for sid, cache in self.sessions.items_snapshot():
            if only is not None and sid != only:
                continue
            with self.sessions.lock_for(sid):
                cur = self.sessions.get(sid)
                if cur is None:
                    continue
                n = int(cur.length)
                if n == 0:
                    continue
                hi = None
                kl = vl = None
                if cur.k_loc is not None:
                    kl, vl = np.asarray(cur.k_loc), np.asarray(cur.v_loc)
                    with self._hi_lock:
                        # the rings' stale slots reach the HIGH-WATER mark,
                        # which a replay rollback can leave above `length` —
                        # the importer's replay guard needs the true value
                        hi = max(self._ring_hi.get(sid, 0), n)
                out.append((sid, handoff.encode(
                    wire_heads(np.asarray(cur.k[:, :, :n]), self.cfg),
                    wire_heads(np.asarray(cur.v[:, :, :n]), self.cfg),
                    n, kl, vl, hi,
                )))
        return out

    def session_lengths(self) -> Dict[str, int]:
        """{session_id: committed KV length} — the cheap frontier surface
        the standby replicator polls (runtime/repl.SessionReplicator)."""
        out = {}
        for sid, cache in self.sessions.items_snapshot():
            n = int(cache.length)
            if n > 0:
                out[sid] = n
        return out

    def export_session_delta(self, session_id: str, since: int):
        """Incremental flavor of export_sessions for standby replication:
        the handoff-schema payload covering positions [since, length)
        plus a "start" key, or None when the session is unknown or holds
        nothing new. Sliding-layer rings ship WHOLE with every delta
        (every slot may be live and they're O(window)); global layers
        ship only the new slots. since == 0 degenerates to the full
        export_sessions payload + start."""
        from inferd_tpu.runtime import handoff
        from inferd_tpu.runtime.repl import START_KEY

        with self.sessions.lock_for(session_id):
            cur = self.sessions.get(session_id)
            if cur is None:
                return None
            n = int(cur.length)
            since = max(0, int(since))
            if n <= since:
                return None
            hi = None
            kl = vl = None
            if cur.k_loc is not None:
                kl, vl = np.asarray(cur.k_loc), np.asarray(cur.v_loc)
                with self._hi_lock:
                    hi = max(self._ring_hi.get(session_id, 0), n)
            payload = handoff.encode(
                wire_heads(np.asarray(cur.k[:, :, since:n]), self.cfg),
                wire_heads(np.asarray(cur.v[:, :, since:n]), self.cfg),
                n, kl, vl, hi,
            )
            payload[START_KEY] = since
            return payload

    def import_session(self, session_id: str, payload: Dict[str, Any]) -> bool:
        """Adopt a migrated session's KV (the receiving replica serves the
        same stage, so layer/head shapes must match). Never clobbers an
        existing session of the same id."""
        from inferd_tpu.runtime import handoff

        if payload.get("adapter") is not None:
            # a tenant session's KV was built with its adapter; the solo
            # executor has no registry (--adapters is lane-executor-only)
            # so adopting would silently resume on the base weights —
            # decline and let it land on a registry replica or restart
            return False
        dec = handoff.decode(
            payload, self.cfg, self.spec.num_layers, self.spec.start_layer,
            self.max_len, want_ring=self.cfg.sliding_window > 0,
        )
        if dec is None:
            return False
        n = dec["n"]
        k_loc, v_loc = dec["k_loc"], dec["v_loc"]
        # the wire carries heads; this model's lanes may be stored as rows
        k = from_wire(dec["k"], self.cfg, uniform=k_loc is None)
        v = from_wire(dec["v"], self.cfg, uniform=k_loc is None)
        with self.sessions.lock_for(session_id):
            if self.sessions.get(session_id) is not None:
                return False
            buf = max(self.initial_kv_len, bucket_len(n))
            if buf < k.shape[2]:  # shipped more than the target bucket: trim
                k, v = k[:, :, :buf], v[:, :, :buf]
            elif buf > k.shape[2]:
                pad = [(0, 0), (0, 0), (0, buf - k.shape[2])] + [(0, 0)] * (k.ndim - 3)
                k = np.pad(k, pad)
                v = np.pad(v, pad)
            cache = KVCache(
                k=jnp.asarray(k, self.cfg.kv_jnp_dtype),
                v=jnp.asarray(v, self.cfg.kv_jnp_dtype),
                length=jnp.int32(n),
                k_loc=None if k_loc is None else jnp.asarray(k_loc, self.cfg.kv_jnp_dtype),
                v_loc=None if v_loc is None else jnp.asarray(v_loc, self.cfg.kv_jnp_dtype),
            )
            self.sessions.put(session_id, cache)
            if k_loc is not None:
                with self._hi_lock:
                    self._ring_hi[session_id] = dec["hi"]
        return True

    def fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        """Seed a NEW session's KV with the first `prefix_len` slots of an
        existing session's cache — stage-local prefix caching. Distributed
        prefix reuse = every stage of the pipeline forking the same parent
        (the client drives this; inner stages never see tokens, so a
        token-hash cache could only ever work on stage 0).

        Returns False when the parent is unknown here or too short — the
        caller falls back to a full prefill."""
        if prefix_len <= 0:
            return False
        with self.sessions.lock_for(parent_session_id):
            parent = self.sessions.get(parent_session_id)
            if parent is None or int(parent.length) < prefix_len:
                return False
            with self._hi_lock:
                parent_hi = max(
                    self._ring_hi.get(parent_session_id, 0), int(parent.length)
                )
            if (
                parent.k_loc is not None
                and parent_hi - prefix_len > RING_MARGIN
            ):
                # ring KV: the parent's stream ran more than the ring margin
                # past the fork point, so its sliding-layer rings have
                # overwritten slots whose stale data would alias INTO the
                # child's windows (models/qwen3._ring_attend_update
                # invariant). Pinned prefixes never advance, so the prefix-
                # cache path is unaffected; a clean False re-prefills.
                return False
            # slice to the fork's own bucket: a long-running parent must not
            # make every child carry its full buffer
            nb = min(
                max(self.initial_kv_len, bucket_len(prefix_len)), parent.max_len
            )
            if nb == parent.max_len:
                # a full-width slice short-circuits to the SAME array object;
                # the child's first donated step would delete the parent's
                # cache through the shared buffer — force a real copy
                k, v = jnp.copy(parent.k), jnp.copy(parent.v)
            else:
                k, v = parent.k[:, :, :nb], parent.v[:, :, :nb]
            child = KVCache(
                k=k, v=v, length=jnp.int32(prefix_len),
                # rings are fixed-size: always a full copy (sharing any leaf
                # with the parent would let the child's donated steps delete
                # the parent's buffers)
                k_loc=None if parent.k_loc is None else jnp.copy(parent.k_loc),
                v_loc=None if parent.v_loc is None else jnp.copy(parent.v_loc),
            )
        self.sessions.put(new_session_id, child)
        if child.k_loc is not None:
            # the child inherits the parent's ring CONTENT, whose stale
            # slots reach up to the parent's high-water mark
            with self._hi_lock:
                self._ring_hi[new_session_id] = max(parent_hi, prefix_len)
        return True


class CounterStageExecutor:
    """Counter-model backend behind the same process() surface (the
    reference's NNForwardTask trick, task.py:24-42, as a first-class
    executor — distribution logic testable with no model weights)."""

    def __init__(self, spec: StageSpec):
        from inferd_tpu.models.counter import CounterStage

        self.spec = spec
        self.model = CounterStage(spec.stage, spec.num_stages)
        self.sessions = SessionStore()

    def process(self, session_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        return self.model.forward(payload, session_id)

    def end_session(self, session_id: str) -> None:
        self.sessions.drop(session_id)

    def fork_session(
        self, new_session_id: str, parent_session_id: str, prefix_len: int
    ) -> bool:
        # counter state rides the payload, not the session — nothing to copy
        return True


def make_executor(
    cfg: ModelConfig,
    spec: StageSpec,
    stage_params: Optional[Dict[str, Any]] = None,
    backend: str = "qwen3",
    **kw,
):
    if backend == "counter":
        return CounterStageExecutor(spec)
    assert stage_params is not None, "qwen3 backend needs stage params"
    return Qwen3StageExecutor(cfg, spec, stage_params, **kw)
