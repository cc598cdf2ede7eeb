"""Functional per-session KV cache.

Replaces the reference's server-side mutable `DynamicCache` keyed by session
id (/root/reference/models/qwen3/server/qwen3_server_module.py:220,253) with
an explicit, preallocated, fixed-shape buffer threaded through jitted calls —
the TPU-idiomatic design: XLA sees one static shape per (batch, max_len)
bucket instead of a shape that grows every token (which would trigger a
recompile per step).

Layout: k/v are [num_global_layers, batch, max_len, num_kv_heads, head_dim],
or [num_global_layers, batch, max_len, num_kv_heads * head_dim] where a head is
narrower than the chip's tile (`rows_layout`: one row a token);
`length` is the number of populated positions. Overflow is checked host-side
(`ensure_room`) because in-jit dynamic_update_slice clamps silently (see
models/qwen3.decoder_layer contract).

This module owns the LAYOUTS. For one layer a cache entry is one value: none
(cache-free), `DenseEntry`, `RowEntry`, `LatentEntry`, `RingEntry`, `PagedEntry` or
`StateEntry` (a recurrent state, the one entry that does not grow with tokens);
stacked over layers they are what the one layer scan of
models/qwen3.forward_layers CARRIES: a layer writes its chunk's rows into
the stack at its own index and reads its slab as a view of the stack, so a
donated cache is updated where it lies and no layer's slab is copied out or
back. `KVCache` and `PagedKVCache` turn themselves into stacked entries
(`entries`) and back (`with_entries`), and `ctx` gives what is the same for
every layer (`CacheCtx`). The model file holds one write-then-read function
per entry type, taking (the stacked entries, the layer's index), and never
takes a cache's arrays apart; a new layout is a new entry type here and one
function there.

Sliding-window models (Gemma-2, GPT-OSS, afmoe) additionally carry RING buffers
`k_loc`/`v_loc` [num_sliding_layers, batch, ring, kv, d] for their "sliding"
layers (cfg.layer_pattern: alternating, or a listed period): a sliding layer never attends past its window,
so its storage is O(window), not O(context) — position p lives at slot
p % ring until position p + ring overwrites it. `ring = round16(window) +
RING_MARGIN`; the margin is what makes speculative rollback and bounded
fork-truncation safe (models/qwen3._ring_attend_update documents the
aliasing invariant). For non-sliding models `k_loc`/`v_loc` are None and
the layout is exactly the classic single-buffer one.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from inferd_tpu.config import FFN_KINDS, ModelConfig

# Extra ring slots past the (16-rounded) window. Bounds how far "newer"
# data may sit in a slot whose formula position is already inside some
# window: speculative rollback depth and fork truncation depth must both
# stay under this margin (enforced at those call sites).
RING_MARGIN = 64


# The chip's (8, 128) tile. A K/V array whose last axis is narrower than its
# minor dimension pads every row to it, and the compiler then re-lays the whole
# stack between the layout its scatter of rows wants and the one its dots want.
# A head WIDER than that dimension among fewer kv heads than the tile has
# sublanes is stored unpadded ([.., 2, 256] compiles to T(2,128) tiles), but
# the step's dots then read each layer's slab through a T-minor copy of it (for
# a described v5e: 0.54 GB of temporaries at 16 x 32 768 x 2 x 256, a slab
# written once more a layer and step; as rows 0.013 GB).
TILE_LANES = 128
TILE_SUBLANES = 8


def rows_layout(cfg: ModelConfig) -> bool:
    """Do uniform dense lanes of this model store a token's keys (values) of
    ALL kv heads as one row [.., Nkv * D] (`RowEntry`) and not as [.., Nkv, D]
    (`DenseEntry`)? Where a head is narrower than a tile, where it is
    wider than one among fewer kv heads than a tile has sublanes, and where
    heads as wide as a tile are more than its sublanes and no whole number
    of them (30 of 128: the head axis pads to 32, and the described-v5e
    compile of the decode step re-lays both stacks whole, 2 x 2.0 GB of
    temporaries at 4 x 16 x 4096, over the chip's memory): the row is
    the shape that is written and read where it lies. Fewer heads than
    sublanes, each as wide as a tile (2 of 128), are stored unpadded
    (T(2,128)) but every step copies the prefix it reads T-minor before its
    dot (for a described v5e, 32 x 4096: two copies of up to 67 MB a step); a
    model with state layers takes the row there (Nemotron-H), the per-head
    models that were measured with heads (4 of 128: `sdar-block-chat`) keep
    them until a PR measures them the other way. Rings and paged pools
    keep heads; a latent cache has none."""
    if cfg.is_mla:
        return False
    if cfg.head_dim == TILE_LANES:  # a head axis over one tile's sublanes that pads (30 to 32)
        if cfg.num_kv_heads < TILE_SUBLANES:
            return cfg.has_state_layers
        return cfg.num_kv_heads > TILE_SUBLANES and cfg.num_kv_heads % TILE_SUBLANES > 0
    return cfg.head_dim < TILE_LANES or cfg.num_kv_heads < TILE_SUBLANES


def state_fold(cfg: ModelConfig) -> int:
    """How many heads of a delta-rule state lie SIDE BY SIDE on the minor axis
    of what is held (`state_held_shape`). A value size over one tile's lanes
    that is no whole number of them (192: one and a half) pads every row of
    [.., Dk, Dv] to the next tile, a third more bytes held and moved every
    step; 128 / gcd(Dv, 128) heads beside each other (two of 192: 384, three
    tiles) are whole tiles. 1: the state is held as it is computed (every
    value size that is a whole number of tiles, or under one; Mamba-2)."""
    if cfg.state_kind != "delta":
        return 1
    dv = cfg.linear_value_head_dim
    if dv <= TILE_LANES or dv % TILE_LANES == 0:
        return 1
    fold = TILE_LANES // math.gcd(dv, TILE_LANES)
    return fold if cfg.linear_value_heads % fold == 0 else 1


def state_held_shape(cfg: ModelConfig) -> Tuple[int, ...]:
    """What a session's recurrent state is HELD as in one state layer, after
    the lane axis: cfg.state_shape, or with `state_fold` heads side by side
    [heads / fold, Dk, fold * Dv] (head g * fold + i in columns [i * Dv,
    (i + 1) * Dv) of row-block g)."""
    fold = state_fold(cfg)
    if fold == 1:
        return cfg.state_shape
    heads, dk, dv = cfg.state_shape
    return (heads // fold, dk, fold * dv)


def state_heads_apart(held, fold: int):
    """A state as it is held, [.., G, Dk, fold * Dv], head by head:
    [.., G * fold, Dk, Dv] (numpy or jax; `fold` 1: as it is)."""
    if fold == 1:
        return held
    *lead, g, dk, fdv = held.shape
    apart = held.reshape(*lead, g, dk, fold, fdv // fold)
    return apart.swapaxes(-2, -3).reshape(*lead, g * fold, dk, fdv // fold)


def state_heads_beside(state, fold: int):
    """The inverse of `state_heads_apart`: [.., H, Dk, Dv] with `fold` heads
    side by side, [.., H / fold, Dk, fold * Dv]."""
    if fold == 1:
        return state
    *lead, h, dk, dv = state.shape
    beside = state.reshape(*lead, h // fold, fold, dk, dv)
    return beside.swapaxes(-2, -3).reshape(*lead, h // fold, dk, fold * dv)


def lane_shape(cfg: ModelConfig) -> Tuple[int, ...]:
    """What follows [.., B, T] in uniform dense K / V lanes: a head axis, or
    for a narrow head one row of all kv heads."""
    if rows_layout(cfg):
        return (cfg.num_kv_heads * cfg.head_dim,)
    return (cfg.num_kv_heads, cfg.head_dim)


def wire_heads(a: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    """A host copy of K/V lanes [L, B, T, ...] in the shape every handoff
    payload has, [L, B, T, Nkv, D], whatever layout it was stored in (a
    row-major reshape on the host: free)."""
    return a if a.ndim == 5 else a.reshape(*a.shape[:3], -1, cfg.head_dim)


def from_wire(a: np.ndarray, cfg: ModelConfig, uniform: bool) -> np.ndarray:
    """The inverse of `wire_heads`: wire-shaped lanes [L, B, T, Nkv, D] as
    `KVCache.create` lays this model's out; `uniform`: the cache has no
    rings (a payload without them), which is where rows are stored."""
    return a.reshape(*a.shape[:3], -1) if uniform and rows_layout(cfg) else a


def ring_slots(cfg: ModelConfig) -> int:
    """Ring length for sliding layers: 16-rounded window + safety margin."""
    return (int(cfg.sliding_window) + 15) // 16 * 16 + RING_MARGIN


def sliding_layer_ids(
    cfg: ModelConfig, num_layers: int, layer_offset: int
) -> List[int]:
    """Stack-local indices of the SLIDING layers (static python): those
    whose global layer index (layer_offset + i) is "sliding" in
    cfg.layer_pattern (the Gemma-2/GPT-OSS alternation, or a listed period)."""
    kinds = cfg.layer_pattern
    return [
        i for i in range(num_layers)
        if kinds[(layer_offset + i) % len(kinds)] == "sliding"
    ]


# ---------------------------------------------------------------------------
# One layer's cache entry, by layout (the shapes in the comments). The same
# types with a leading layer axis on every array are the stacked entries the
# layer scan carries and the write-then-read functions take, with an index.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DenseEntry:
    """Dense lanes: slot index == absolute position."""

    k: jax.Array  # [B, T, Nkv, D]
    v: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RowEntry:
    """Dense lanes of heads narrower than a tile (`rows_layout`): a token's
    keys of all kv heads are ONE row, kv head n in columns [n*D, (n+1)*D)."""

    k: jax.Array  # [B, T, Nkv * D]
    v: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentEntry:
    """Dense lanes of a latent (MLA) cache: nothing per head."""

    c: jax.Array  # [B, T, R] normed latents
    r: jax.Array  # [B, T, Dr] the roped key all heads share


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RingEntry:
    """A sliding layer's O(window) ring: position p lives at slot p % R."""

    k: jax.Array  # [B, R, Nkv, D]
    v: jax.Array
    window: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedEntry:
    """A layer's block pool, read and written through CacheCtx.table."""

    k: jax.Array  # [NB, bs, Nkv, D]
    v: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StateEntry:
    """A state-space layer's whole memory of a session, the same size
    whatever the session's length and not indexed by position: it cannot be
    truncated, rolled back or cut at a prefix."""

    s: jax.Array  # [B, *state_held_shape(cfg)] in cfg.state_dtype: the recurrent state
    #   (Mamba-2 [heads, P, N]; the delta rule [value heads, Dk, Dv], or with
    #   `state_fold` heads side by side [value heads / fold, Dk, fold * Dv])
    conv: jax.Array  # [B, K-1, conv_dim]: the last inputs of the causal convolution


class CacheCtx(NamedTuple):
    """What a cached forward needs beside the entries, the same for every
    layer: where the chunk is written and, for a paged pool, through what."""

    write_pos: Any  # slot where the chunk's first token goes: scalar, or [B] per row
    real_end: Any = None  # scalar or [B]: first bucket-padding position
    #   (ring and paged writes skip the padding; None = write_pos + S)
    table: Optional[jax.Array] = None  # [B, MB] int32 block table (paged)
    write_mask: Optional[jax.Array] = None  # [B] bool: rows whose writes commit
    #   (a False row writes nothing, in any layout; None = every row's do)


def _nbytes(*arrays) -> int:
    """Bytes of the arrays that are there (of their shapes, where abstract)."""
    return sum(int(a.size) * a.dtype.itemsize for a in arrays if a is not None)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k: jax.Array  # [Lg, B, T, Nkv, D] global (full-length) layers
    v: jax.Array  # [Lg, B, T, Nkv, D]; rows (rows_layout): [Lg, B, T, Nkv * D] both;
    #   a latent (MLA) cache: k [L, B, T, R], v [L, B, T, Dr]
    length: jax.Array  # int32 scalar: populated positions
    k_loc: Optional[jax.Array] = None  # [Ll, B, R, Nkv, D] sliding-layer rings
    v_loc: Optional[jax.Array] = None
    # a model with state-space layers (cfg.has_state_layers): k and v hold
    # its ATTENTION layers only, these its state layers' StateEntry stack
    s: Optional[jax.Array] = None  # [Lm, B, *state_held_shape(cfg)] in cfg.state_dtype
    conv: Optional[jax.Array] = None  # [Lm, B, K-1, conv_dim] in the model's dtype

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def batch(self) -> int:
        return self.k.shape[1]

    @property
    def ring(self) -> Optional[int]:
        return None if self.k_loc is None else self.k_loc.shape[2]

    @staticmethod
    def create(
        cfg: ModelConfig,
        num_layers: int,
        batch: int,
        max_len: int,
        dtype=None,
        layer_offset: int = 0,
        ring: Optional[bool] = None,
    ) -> "KVCache":
        """ring=None auto-enables ring storage for sliding-window configs;
        ring=False forces the classic uniform full-length layout (the
        comparison/compat path — also what executors with a TRACED layer
        offset must use)."""
        dt = dtype or cfg.kv_jnp_dtype
        heads = (cfg.num_kv_heads, cfg.head_dim)
        lane = lane_shape(cfg)  # uniform lanes only: beside rings, heads
        if cfg.has_state_layers:
            # keys and values (in the kv dtype) for the attention layers, a
            # state and the convolution's last inputs for the others
            # (of layers that are one sublayer each, the experts hold neither)
            la = cfg.layers_of("attention", num_layers)
            lm = cfg.layers_of(cfg.state_kind, num_layers)
            shape = (la, batch, max_len, *lane)
            return KVCache(
                k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt), length=jnp.int32(0),
                s=jnp.zeros((lm, batch, *state_held_shape(cfg)), jnp.dtype(cfg.state_dtype)),
                conv=jnp.zeros((lm, batch, *cfg.state_conv_shape), cfg.jnp_dtype),
            )
        if cfg.is_mla:
            # a latent cache: per token and layer the normed latent (`k`)
            # and the one roped key all heads share (`v`); nothing per head
            return KVCache(
                k=jnp.zeros((num_layers, batch, max_len, cfg.kv_lora_rank), dt),
                v=jnp.zeros((num_layers, batch, max_len, cfg.qk_rope_head_dim), dt),
                length=jnp.int32(0),
            )
        use_ring = cfg.sliding_window > 0 if ring is None else (
            ring and cfg.sliding_window > 0
        )
        loc = sliding_layer_ids(cfg, num_layers, layer_offset) if use_ring else []
        if not loc:  # uniform layout (forced, no window, or global-only slice)
            shape = (num_layers, batch, max_len, *lane)
            return KVCache(
                k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt), length=jnp.int32(0)
            )
        lg = num_layers - len(loc)
        r = ring_slots(cfg)
        gshape = (lg, batch, max_len, *heads)
        lshape = (len(loc), batch, r, *heads)
        return KVCache(
            k=jnp.zeros(gshape, dt),
            v=jnp.zeros(gshape, dt),
            length=jnp.int32(0),
            k_loc=jnp.zeros(lshape, dt),
            v_loc=jnp.zeros(lshape, dt),
        )

    @property
    def nbytes(self) -> int:
        """Bytes allocated to the cache's buffers."""
        return self.state_bytes + _nbytes(self.k, self.v, self.k_loc, self.v_loc)

    @property
    def state_bytes(self) -> int:
        """Bytes of the buffers that do not grow with tokens (StateEntry)."""
        return _nbytes(self.s, self.conv)

    def ensure_room(self, new_tokens: int, owner: Optional[str] = None) -> None:
        """Host-side overflow guard — call before dispatching a jitted step.
        Rings never overflow (they wrap); the global buffers bound growth.

        `owner` names the session/lane this cache serves; it rides the
        raised BufferError so the error a client sees and the kv.overflow
        journal event the node records carry the same identity."""
        used = int(self.length)
        if used + new_tokens > self.max_len:
            who = f" ({owner})" if owner else ""
            raise BufferError(
                f"KV cache overflow{who}: {used} used + {new_tokens} new > "
                f"{self.max_len}"
            )

    def entries(self, cfg: ModelConfig) -> tuple:
        """The layers' entries, stacked: one stack per kind of
        cfg.layer_pattern (in the order the kinds first appear in it) when
        the storage is split by kind (rings, states), else one stack holding
        every layer in layer order."""
        if cfg.is_mla:
            return (LatentEntry(c=self.k, r=self.v),)
        glob = (RowEntry if self.k.ndim == 4 else DenseEntry)(k=self.k, v=self.v)
        if self.s is None and self.k_loc is None:
            return (glob,)
        by_kind = {
            cfg.state_kind: None if self.s is None else StateEntry(s=self.s, conv=self.conv),
            "sliding": None if self.k_loc is None else RingEntry(
                k=self.k_loc, v=self.v_loc, window=int(cfg.sliding_window)),
        }
        return tuple(  # a kind that is a feed-forward alone holds nothing
            None if kind in FFN_KINDS else by_kind.get(kind) or glob
            for kind in dict.fromkeys(cfg.layer_pattern))

    def with_entries(self, entries: tuple) -> "KVCache":
        """Inverse of `entries`: the same cache (and length) over new buffers."""
        if isinstance(entries[0], LatentEntry):
            return KVCache(k=entries[0].c, v=entries[0].r, length=self.length)
        by_type = {type(e): e for e in entries if e is not None}
        glob = by_type.get(RowEntry) or by_type[DenseEntry]
        ring, state = by_type.get(RingEntry), by_type.get(StateEntry)
        return KVCache(
            k=glob.k, v=glob.v, length=self.length,
            k_loc=None if ring is None else ring.k,
            v_loc=None if ring is None else ring.v,
            s=None if state is None else state.s,
            conv=None if state is None else state.conv,
        )

    def layout(self, cfg: ModelConfig) -> str:
        """What `entries` makes of k and v: "latent", "rows" or "heads"."""
        if cfg.is_mla:
            return "latent"
        return "rows" if self.k.ndim == 4 else "heads"

    @staticmethod
    def ctx(write_pos, real_end=None, write_mask=None) -> CacheCtx:
        """Dense lanes are lane-private: no table."""
        return CacheCtx(write_pos, real_end, write_mask=write_mask)

    def updated(self, k: jax.Array, v: jax.Array, new_tokens) -> "KVCache":
        """New cache with written buffers and advanced length (pure)."""
        return dataclasses.replace(self, k=k, v=v, length=self.length + new_tokens)


def lane_slice(cache: KVCache, lane) -> KVCache:
    """One lane's KVCache view, [.., 1, ..] on the batch axis (global,
    ring and state buffers). Shared by the lane-indexed engines (core.batch
    prefill, core.spec_batch draft prefill) so the handling of the optional
    fields lives in exactly one place."""
    sl = lambda a: None if a is None else jax.lax.dynamic_slice_in_dim(a, lane, 1, axis=1)
    return KVCache(
        k=sl(cache.k), v=sl(cache.v), length=cache.length,
        k_loc=sl(cache.k_loc), v_loc=sl(cache.v_loc), s=sl(cache.s), conv=sl(cache.conv),
    )


def lane_write(cache: KVCache, lane, nc: KVCache) -> KVCache:
    """Write a lane_slice-shaped cache back into `lane` (inverse of
    lane_slice; in-place under donation)."""
    up = lambda a, b: None if a is None else jax.lax.dynamic_update_slice_in_dim(
        a, b, lane, axis=1)
    return KVCache(
        k=up(cache.k, nc.k), v=up(cache.v, nc.v), length=cache.length,
        k_loc=up(cache.k_loc, nc.k_loc), v_loc=up(cache.v_loc, nc.v_loc),
        s=up(cache.s, nc.s), conv=up(cache.conv, nc.conv),
    )


# ---------------------------------------------------------------------------
# Paged KV: block pool + block tables (vLLM's PagedAttention lesson,
# redesigned for jit-static shapes)
# ---------------------------------------------------------------------------
#
# The dense lane slab ([layers, lanes, max_len, ...]) charges every lane the
# worst-case context: a 40-token chat reserves the same HBM as a 4k-token
# document. The paged layout stores K/V in a pool of fixed-size BLOCKS
# ([layers, num_blocks, block_size, ...]) and maps each lane to a chain of
# blocks through an int32 [lanes, max_blocks] BLOCK TABLE: chain slot j of a
# lane covers absolute positions [j*block_size, (j+1)*block_size). Allocation,
# eviction, and sharing become per-block:
#
#   * a lane holds ceil(len/block_size) blocks, not max_len slots;
#   * blocks are REFCOUNTED, so a pinned/cached shared prefix maps read-only
#     into many lanes' tables at once (each new session skips that prefill
#     entirely) and copy-on-write splits a block only on the first divergent
#     write (SGLang's RadixAttention lesson, hash-chain flavored);
#   * attention gathers K/V through the table (ops.attention block-table
#     path), which is exact vs the dense layout: the gathered view is
#     position-contiguous, so slot index == absolute position and the same
#     causal/validity mask applies bit-for-bit.
#
# Device/host split: `PagedKVCache` is the jit-visible pytree (pools + the
# table as an operand — shapes static, so one compiled program serves any
# allocation state); `BlockPool` is the HOST-side allocator that owns the
# table mirror, refcounts, the free list, and the prefix index. Executors
# mutate the pool under their own bookkeeping lock and stamp a fresh table
# into the dispatch cache (a [lanes, max_blocks] int32 — trivial next to the
# step itself).
#
# Block 0 is a reserved SCRATCH block: unallocated table entries point at it,
# so in-graph writes from non-participating lanes (the co-batch garbage-step
# invariant) and reads past a lane's frontier land somewhere harmless — reads
# of it are always masked (slot >= valid length), writes to it are never
# attended.


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Jit-visible paged KV state: block pools + the lanes' block table.

    k/v: [L, num_blocks, block_size, Nkv, D] (block 0 = scratch);
    table: [lanes, max_blocks] int32 (chain slot j of lane b covers
    positions [j*bs, (j+1)*bs); unallocated entries = 0);
    length: int32 scalar, kept for interface parity with KVCache (lane
    executors track per-lane lengths host-side and ignore it).
    """

    k: jax.Array
    v: jax.Array
    table: jax.Array
    length: jax.Array

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def max_blocks(self) -> int:
        return self.table.shape[1]

    @property
    def max_len(self) -> int:
        """Per-lane positional capacity (the dense-equivalent max_len)."""
        return self.max_blocks * self.block_size

    @property
    def batch(self) -> int:
        return self.table.shape[0]

    @property
    def k_loc(self):
        """Paged storage is uniform-layout only (sliding-window models keep
        their dense rings on the classic path); None keeps the executors'
        `cache.k_loc is not None` ring checks working unchanged."""
        return None

    v_loc = k_loc

    def entries(self, cfg: ModelConfig) -> tuple:
        """One stack of pools for every layer (paged storage is one layout
        by construction)."""
        return (PagedEntry(k=self.k, v=self.v),)

    def with_entries(self, entries: tuple) -> "PagedKVCache":
        return dataclasses.replace(self, k=entries[0].k, v=entries[0].v)

    def ctx(self, write_pos, real_end=None, write_mask=None) -> CacheCtx:
        return CacheCtx(write_pos, real_end, self.table, write_mask)

    @staticmethod
    def create(
        cfg: ModelConfig,
        num_layers: int,
        lanes: int,
        max_len: int,
        block_size: int = 32,
        num_blocks: Optional[int] = None,
        dtype=None,
    ) -> "PagedKVCache":
        dt = dtype or cfg.kv_jnp_dtype
        bs = int(block_size)
        mb = -(-int(max_len) // bs)  # ceil: blocks per lane chain
        nb = (lanes * mb + 1) if num_blocks is None else int(num_blocks)
        shape = (num_layers, nb, bs, cfg.num_kv_heads, cfg.head_dim)
        return PagedKVCache(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            table=jnp.zeros((lanes, mb), jnp.int32),
            length=jnp.int32(0),
        )


class _PrefixEntry:
    """One cached/pinned prefix block in the pool's prefix index. The index
    holds its OWN reference on the block (refcount +1), so the block
    survives the sessions that produced it and can be mapped into later
    lanes until evicted for space (pinned entries are never evicted).
    `ts` is the entry's last-touch time (index/registration/hit) on the
    pool's clock — an eviction's AGE (now - ts) is how long the entry sat
    cold before space pressure reclaimed it, the memory-plane telemetry's
    thrash-vs-working-set signal (obs: kv.prefix_evict_age_ms)."""

    __slots__ = ("block", "pinned", "ts")

    def __init__(self, block: int, pinned: bool = False, ts: float = 0.0):
        self.block = block
        self.pinned = pinned
        self.ts = ts


class BlockPool:
    """Host-side allocator for a PagedKVCache: free list, per-lane block
    chains, refcounts, copy-on-write, and the shared-prefix index.

    NOT thread-safe by itself — callers (the lane executors) mutate it
    under the same bookkeeping lock that guards their lane/session state.
    Device copies implied by CoW splits are returned as (src, dst) block
    pairs for the caller to apply under its device lock (`drain_copies`).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        num_layers: int,
        lanes: int,
        max_len: int,
        block_size: int = 32,
        num_blocks: Optional[int] = None,
        dtype=None,
        clock: Optional[Callable[[], float]] = None,
    ):
        if cfg.is_mla or cfg.has_state_layers:
            raise ValueError(
                f"{cfg.name}: the paged pool has no latent or state entry "
                "(such a cache is served from dense lanes)"
            )
        if cfg.sliding_window > 0:
            # rings already make sliding layers O(window); paging the
            # uniform layout under them would need a second table per
            # layer class — out of scope, and the capacity win lives in
            # the global layers anyway
            raise ValueError(
                "paged KV supports uniform-layout models only "
                "(sliding-window models keep the dense ring layout)"
            )
        self.cfg = cfg
        self.block_size = int(block_size)
        self.lanes = int(lanes)
        self.max_blocks = -(-int(max_len) // self.block_size)
        self.cache = PagedKVCache.create(
            cfg, num_layers, lanes, max_len, block_size=self.block_size,
            num_blocks=num_blocks, dtype=dtype,
        )
        self.num_blocks = self.cache.num_blocks
        if self.num_blocks < 2:
            raise ValueError("paged KV needs >= 2 blocks (block 0 is scratch)")
        # host mirrors (never read back from device)
        self.table = np.zeros((self.lanes, self.max_blocks), np.int32)
        self.refcount = np.zeros((self.num_blocks,), np.int32)
        self.refcount[0] = 1  # scratch block: never allocated, never freed
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self.lane_blocks = [0] * self.lanes  # chain length per lane
        self.lane_shared = [0] * self.lanes  # leading read-only blocks
        # prefix index: chained block-content key -> entry (LRU order)
        self._index: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        self._pending_copies: List[Tuple[int, int]] = []
        # effectiveness counters (surface in executor stats / gauges)
        self.cow_splits = 0
        self.prefix_hit_tokens = 0
        self.prefix_evictions = 0
        # entry-age clock + eviction observer: `on_evict(key, age_s)`
        # fires per reclaimed index entry with how long it sat since its
        # last touch (the executors wire it to a journal `prefix.evict`
        # event; failures are the HOOK's problem, never the allocator's)
        self.clock = clock if clock is not None else time.monotonic
        self.on_evict: Optional[Callable[[bytes, float], None]] = None

    # ------------------------------------------------------------ allocation

    def blocks_for(self, upto: int) -> int:
        return -(-int(upto) // self.block_size)

    def _alloc(self, owner: str) -> int:
        if not self._free:
            self._evict_cached(1)
        if not self._free:
            raise BufferError(
                f"KV block pool exhausted ({owner}): 0 free of "
                f"{self.num_blocks - 1} blocks "
                f"(block_size={self.block_size})"
            )
        b = self._free.pop()
        self.refcount[b] = 1
        return b

    def ensure(self, lane: int, upto: int, owner: str = "") -> None:
        """Grow `lane`'s chain with private blocks until it covers
        positions [0, upto). Raises BufferError carrying `owner` (the
        session/lane identity) when the pool cannot satisfy it."""
        need = self.blocks_for(upto)
        if need > self.max_blocks:
            raise BufferError(
                f"KV overflow ({owner}): {upto} > "
                f"{self.max_blocks * self.block_size}"
            )
        for j in range(self.lane_blocks[lane], need):
            self.table[lane, j] = self._alloc(owner)
            # advance incrementally: a mid-ensure exhaustion must leave
            # the blocks already claimed releasable, not leaked
            self.lane_blocks[lane] = j + 1

    def _decref(self, block: int) -> None:
        if block <= 0:
            return
        self.refcount[block] -= 1
        if self.refcount[block] == 0:
            self._free.append(block)

    def release_lane(self, lane: int) -> None:
        """Return a lane's chain to the pool (shared/cached blocks survive
        through their index references)."""
        for j in range(self.lane_blocks[lane]):
            self._decref(int(self.table[lane, j]))
        self.table[lane, :] = 0
        self.lane_blocks[lane] = 0
        self.lane_shared[lane] = 0

    # ------------------------------------------------------------ sharing

    def map_prefix(self, lane: int, keys: Sequence[bytes]) -> int:
        """Map the longest indexed run of `keys` into a FRESH lane's chain
        as read-only shared blocks; returns the number of tokens covered.
        The lane must be empty (admission calls this before any prefill)."""
        assert self.lane_blocks[lane] == 0
        m = 0
        for key in keys:
            ent = self._index.get(key)
            if ent is None:
                break
            self._index.move_to_end(key)
            ent.ts = self.clock()
            self.table[lane, m] = ent.block
            self.refcount[ent.block] += 1
            m += 1
        self.lane_blocks[lane] = m
        self.lane_shared[lane] = m
        covered = m * self.block_size
        self.prefix_hit_tokens += covered
        return covered

    def register_prefix(self, lane: int, keys: Sequence[bytes]) -> int:
        """Publish a lane's leading blocks into the prefix index under
        their content keys (after the lane's prefill wrote them). Blocks
        already indexed (the shared ones this lane mapped) are touched,
        not duplicated. Returns newly indexed block count."""
        added = 0
        for j, key in enumerate(keys):
            if j >= self.lane_blocks[lane]:
                break
            ent = self._index.get(key)
            if ent is not None:
                self._index.move_to_end(key)
                ent.ts = self.clock()
                continue
            block = int(self.table[lane, j])
            if block <= 0 or j < self.lane_shared[lane]:
                continue
            self._index[key] = _PrefixEntry(block, ts=self.clock())
            self.refcount[block] += 1  # the index's own reference
            added += 1
        return added

    def pin(self, keys: Sequence[bytes]) -> int:
        """Mark indexed entries pinned (never evicted for space); returns
        how many of `keys` were found."""
        n = 0
        for key in keys:
            ent = self._index.get(key)
            if ent is not None:
                ent.pinned = True
                n += 1
        return n

    def unpin(self, keys: Sequence[bytes]) -> None:
        for key in keys:
            ent = self._index.get(key)
            if ent is not None:
                ent.pinned = False

    def _evict_cached(self, need: int) -> None:
        """Drop LRU unpinned index entries whose block is otherwise unused
        (refcount 1 == only the index holds it) until `need` blocks are
        free. Entries still mapped into live lanes are skipped — their
        blocks could not be reclaimed anyway."""
        if need <= len(self._free):
            return
        for key in list(self._index):
            ent = self._index[key]
            if ent.pinned or self.refcount[ent.block] != 1:
                continue
            del self._index[key]
            self._decref(ent.block)
            self.prefix_evictions += 1
            if self.on_evict is not None:
                try:
                    self.on_evict(key, max(0.0, self.clock() - ent.ts))
                except Exception:
                    pass  # telemetry must never fail an allocation
            if len(self._free) >= need:
                return

    # ------------------------------------------------------------ CoW

    def make_writable(self, lane: int, from_pos: int, owner: str = "") -> None:
        """Copy-on-write split every MULTIPLY-REFERENCED block of `lane`
        covering positions >= from_pos (the first divergent write): each
        gets a private copy, the table repoints, and the (src, dst)
        device copy is queued for `drain_copies`.

        The writable test is the REFCOUNT, not just the mapped-prefix
        prefix (`lane_shared`): a lane that PUBLISHED its own blocks
        (register_prefix) or was fork_lane'd FROM holds blocks the index
        / a child still reads at refcount >= 2 with lane_shared
        untouched — an in-place rollback rewrite there would silently
        corrupt every future sharer. A block whose only extra reference
        is a pending copy gets split too (conservative, rare, correct).
        The common decode case (private frontier) costs one refcount
        compare per chain block past from_pos."""
        first = int(from_pos) // self.block_size
        for j in range(first, self.lane_blocks[lane]):
            old = int(self.table[lane, j])
            if old <= 0 or self.refcount[old] <= 1:
                continue
            new = self._alloc(owner)
            self._queue_copy(old, new)
            self.table[lane, j] = new
            self._decref(old)
            self.cow_splits += 1
        self.lane_shared[lane] = min(self.lane_shared[lane], first)

    def _queue_copy(self, src: int, dst: int) -> None:
        """Queue a device block copy. The queue holds its OWN reference on
        `src` (released at drain) so a teardown/restart freeing the source
        lane between queue and apply cannot recycle the block under the
        pending copy."""
        self.refcount[src] += 1
        self._pending_copies.append((src, dst))

    def fork_lane(
        self, src: int, dst: int, prefix_len: int, owner: str = ""
    ) -> None:
        """Seed FRESH lane `dst` with lane `src`'s first `prefix_len`
        positions: full blocks map read-only (refcounted, CoW on later
        divergence); a partial tail block gets a private copy (queued for
        drain_copies). The block-pool flavor of the dense executors'
        fork_session device copy."""
        assert self.lane_blocks[dst] == 0
        full = int(prefix_len) // self.block_size
        for j in range(full):
            b = int(self.table[src, j])
            self.table[dst, j] = b
            self.refcount[b] += 1
        self.lane_shared[dst] = full
        self.lane_blocks[dst] = full
        if prefix_len % self.block_size:
            nb = self._alloc(owner)
            self._queue_copy(int(self.table[src, full]), nb)
            self.table[dst, full] = nb
            self.lane_blocks[dst] = full + 1

    def drain_copies(self) -> List[Tuple[int, int]]:
        """Take the queued CoW (src, dst) block copies; the caller applies
        them on device (under its device lock) BEFORE the next dispatch
        that reads the split lane. Releases the queue's source references
        — a source freed here may be recycled by a LATER allocation, but
        device content only changes in dispatches, which the caller
        serializes after the copy."""
        out, self._pending_copies = self._pending_copies, []
        for src, _dst in out:
            self._decref(src)
        return out

    # ------------------------------------------------------------ dispatch

    def device_table(self, max_blocks: Optional[int] = None):
        """Fresh device table from the host mirror — stamp into the
        dispatch cache (executors: dataclasses.replace(cache, table=...)).
        `max_blocks` (chain_clamp) narrows the stamped width so dispatches
        gather/walk only slots some lane can actually reach."""
        if max_blocks is None:
            return jnp.asarray(self.table)
        return jnp.asarray(self.table[:, :max_blocks])

    def chain_clamp(self) -> int:
        """Power-of-two bucket of the window's MAXIMUM allocated chain
        length (>= 1, capped at the full table width). Stamping tables at
        this width (sync_paged) keeps short sessions co-batched with long
        ones from gathering — and masking — scratch-block slots nobody
        can attend to: the XLA fallback's gather_block_kv materializes
        O(width * bs) per layer per step, so width is the bandwidth term.
        Bucketed so jit retraces per power-of-two growth step, the same
        coarseness every other bucketed dispatch shape uses. Blocks are
        allocated BEFORE the dispatch that writes them (ensure), so every
        lane's write frontier sits inside its allocated chain and the
        clamp can never cut off a real read or write."""
        used = max(self.lane_blocks) if self.lane_blocks else 0
        bucket = 1
        while bucket < used:
            bucket <<= 1
        return min(bucket, self.max_blocks)

    # ------------------------------------------------------------ gauges

    @property
    def blocks_used(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def cow_shared(self) -> int:
        """Blocks currently mapped by more than one holder (lanes and/or
        the prefix index) — the dedupe the pool is earning its keep with."""
        return int(np.sum(self.refcount[1:] >= 2))

    @property
    def pins_resident(self) -> int:
        return sum(1 for e in self._index.values() if e.pinned)

    def digest_keys(self, limit: int = 0) -> List[bytes]:
        """Size-bounded selection of indexed prefix keys for the gossiped
        digest (core.prefix.make_digest): PINNED entries first (they are
        resident by contract — the strongest affinity promise a replica
        can gossip), then most-recently-touched cache entries until
        `limit`. Keys are chained, so any included key identifies its
        whole prefix; MRU ordering makes the digest track the HOT working
        set when the index outgrows the budget."""
        from inferd_tpu.core import prefix as prefixlib

        if limit <= 0:
            limit = prefixlib.DIGEST_MAX_KEYS
        out: List[bytes] = [
            k for k, e in self._index.items() if e.pinned
        ][:limit]
        if len(out) < limit:
            seen = set(out)
            for k in reversed(self._index):  # MRU first
                if k in seen:
                    continue
                out.append(k)
                if len(out) >= limit:
                    break
        return out

    def block_stats(self) -> Dict[str, Any]:
        return {
            "block_size": self.block_size,
            "blocks_total": self.num_blocks - 1,
            "blocks_used": self.blocks_used,
            "blocks_free": self.blocks_free,
            "cow_shared": self.cow_shared,
            "cow_splits": self.cow_splits,
            "prefix_entries": len(self._index),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_evictions": self.prefix_evictions,
            "pins_resident": self.pins_resident,
        }


def paged_copy_blocks(cache: PagedKVCache, pairs: List[Tuple[int, int]],
                      copy_fn: Callable) -> PagedKVCache:
    """Apply queued CoW block copies on device via `copy_fn` (a jitted
    (cache, src [n], dst [n]) -> cache with the cache donated). Groups all
    pairs into one call; `n` varies rarely (CoW splits are admission-time
    events), so the compile set stays small."""
    if not pairs:
        return cache
    src = jnp.asarray([p[0] for p in pairs], jnp.int32)
    dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
    return copy_fn(cache, src, dst)


def sync_paged(pool: BlockPool, cache: PagedKVCache, copy_fn: Callable,
               mu) -> PagedKVCache:
    """Dispatch-ready paged cache: apply queued CoW block copies and
    stamp the CURRENT block table (the host mirror moved since the last
    dispatch — allocations, prefix maps, splits). The ONE implementation
    behind both lane executors' `_sync_paged` (a drifted copy here would
    be a correctness bug, not a style problem). Call under the caller's
    DEVICE lock with `mu` (its bookkeeping lock) NOT held; the caller
    must rebind its cache reference to the return value (the copy jit
    donates)."""
    with mu:
        pairs = pool.drain_copies()
        # chain-length clamp: stamp only the (bucketed) max allocated
        # chain width, so the paged read path — XLA gather_block_kv and
        # the Pallas chain-walk kernel alike — does O(longest chain) work
        # per lane instead of O(full table width)
        table = pool.device_table(pool.chain_clamp())
    if pairs:
        cache = paged_copy_blocks(cache, pairs, copy_fn)
    return dataclasses.replace(cache, table=table)


def grow(cache: KVCache, new_max_len: int) -> KVCache:
    """Host-side reallocation to a larger bucket (copies populated slots).

    Used by the session registry when a session outgrows its bucket; pairs
    with bucketed jit shapes so growth is rare and amortized. Ring buffers
    are fixed-size by construction and carry over untouched.
    """
    if new_max_len <= cache.max_len:
        return cache
    pad = [(0, 0), (0, 0), (0, new_max_len - cache.max_len)] + [(0, 0)] * (cache.k.ndim - 3)
    return dataclasses.replace(cache, k=jnp.pad(cache.k, pad), v=jnp.pad(cache.v, pad))
