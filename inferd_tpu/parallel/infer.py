"""In-mesh pipelined inference: microbatched decode over the `pp` axis.

The swarm runs pipeline parallelism BETWEEN processes (one stage per node,
activations over HTTP — runtime/node.py). This module is the in-mesh
counterpart the north star asks for (BASELINE.json configs 2-3: one stage
per TPU chip, `lax.ppermute` activation hops, microbatched interleaved
pipelining): the whole multi-stage decode step is ONE jitted SPMD program
over a `Mesh`, so a pipeline hop is an ICI collective-permute instead of a
network round trip.

Schedule: GPipe-style interleaving over MB microbatch slots. Each tick,
every pp rank runs its layer slice on the microbatch currently resident,
reading and writing that microbatch's slice of the rank-local KV cache,
then rotates activations one stage forward. A decode step costs MB + PP - 1
ticks and advances MB*B sequences by one token — the bubble amortizes away
as MB grows (the reference's swarm has exactly one activation in flight per
request, SURVEY §2.1 'no microbatching'). The SERVING decode pass
(`_rows_pass`, behind `step_slots`) is lock-step with its callers (every
reply goes through the host before the next pass), so there a tick's cost
is the stage's weight read whatever it carries: it runs ONE microbatch
whose rows are the slots, PP ticks, the head once.

`PipelinedEngine` is a real generation engine, not a demo:
  * temperature/top-k/top-p sampling + EOS stop (core.sampling), fused into
    the jitted step — per-sequence PRNG chains identical to the
    single-process `Engine.generate` loop, so the two are parity-testable
    with temperature > 0;
  * ragged prompts: each slot prefills independently, padded to a
    power-of-two bucket (one compile per bucket, reference regime where
    every prompt length recompiled — here bucketed like core.generate);
  * persistent KV caches (allocated once, donated through every step) with
    slot REFILL: when a sequence finishes, its slot is reassigned to the
    next queued prompt while the other slots keep decoding — the in-mesh
    form of continuous batching.

Capability lineage: the reference's pipeline relay (petals/node.py:102-130),
per-session server-side KV (qwen3_server_module.py:220), and client
generation loop semantics (client.py:204-287) — rebuilt as compiled SPMD
programs with the KV cache sharded over `pp` alongside the layers it
belongs to (cache never crosses a chip boundary; only the [B, H] hidden
vector rides the ICI).
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from inferd_tpu.config import ModelConfig, SamplingConfig
from inferd_tpu.core import sampling as samplib
from inferd_tpu.core.cache import KVCache, from_wire, lane_shape, wire_heads
from inferd_tpu.core.generate import bucket_len
from inferd_tpu.models import qwen3
from inferd_tpu.obs import trace as tracelib
from inferd_tpu.obs.devtel import program_name
from inferd_tpu.parallel import mesh as meshlib

Params = Dict[str, Any]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "lengths", "k_loc", "v_loc"],
    meta_fields=[],
)
@dataclasses.dataclass
class PipelinedCaches:
    """KV caches for MB microbatch slots, sharded over pp on the layer axis.

    Uniform layout: k/v [L, MB, B, T, n_kv, head_dim] (L sharded over pp —
    each rank holds caches only for its own layers), or
    [L, MB, B, T, n_kv * head_dim] where a head is narrower than a tile
    (core.cache.rows_layout: one row a token, as KVCache.create lays dense
    lanes); lengths: [MB] valid prefix per slot (uniform within a slot);
    k_loc/v_loc None.

    Split layout (sliding-window configs where every pp rank's layer slice
    starts on an even global index — see ring_split_ok): k/v hold only the
    GLOBAL (full-attention) layers [Lg, MB, B, T, n_kv, d] and k_loc/v_loc
    hold the sliding layers as O(window) RING buffers
    [Ll, MB, B, R, n_kv, d] (core.cache ring invariant) — the in-mesh path
    stops paying O(context) HBM reads/storage on half a Gemma-2/GPT-OSS
    model's layers (VERDICT r03 item 3)."""

    k: jax.Array
    v: jax.Array
    lengths: jax.Array
    k_loc: Optional[jax.Array] = None
    v_loc: Optional[jax.Array] = None

    @property
    def layout(self) -> str:
        """ "rows" where k and v hold one row a token, else "heads"."""
        return "rows" if self.k.ndim == 5 else "heads"


def ring_split_ok(cfg: ModelConfig, pp: int) -> bool:
    """Can the pipelined cache use O(window) ring storage for sliding
    layers? Requires every rank's slice to start on a PERIOD boundary of
    cfg.layer_pattern (an even global layer index for the alternation) —
    then the sliding/global pattern is the SAME static one on all ranks and
    the one SPMD program stays rank-independent. True for pp == 1 (any
    length; the layer scan unrolls what lies outside whole periods) and for
    whole periods per rank; otherwise (e.g. Gemma-2's 26 layers at pp=2: 13
    a rank) the uniform mask-only fallback, observable via stats()."""
    if not cfg.sliding_window:
        return False
    per = cfg.num_layers // pp
    return pp == 1 or per % len(cfg.layer_pattern) == 0


@functools.lru_cache(maxsize=64)
def _sharded_zeros_fn(shape, dtype, sharding):
    # cached per (shape, dtype, sharding): a fresh lambda per call would be
    # a jit-cache miss and recompile the zero-fill on every allocation
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def make_caches(
    cfg: ModelConfig,
    mesh: Mesh,
    num_microbatches: int,
    batch: int,
    max_len: int,
    ring: Optional[bool] = None,
) -> PipelinedCaches:
    """ring=None auto-selects the split ring layout when ring_split_ok;
    ring=False forces the classic uniform layout (comparison/compat path —
    also what odd layers-per-rank splits must use)."""
    pp = mesh.shape["pp"]
    use_ring = ring_split_ok(cfg, pp) if ring is None else (
        ring and ring_split_ok(cfg, pp)
    )
    sharding = NamedSharding(mesh, cache_spec(mesh))
    if not use_ring:
        shape = (cfg.num_layers, num_microbatches, batch, max_len, *lane_shape(cfg))
        zeros = _sharded_zeros_fn(shape, cfg.kv_jnp_dtype, sharding)
        return PipelinedCaches(
            k=zeros(), v=zeros(), lengths=jnp.zeros((num_microbatches,), jnp.int32)
        )
    from inferd_tpu.core.cache import ring_slots, sliding_layer_ids

    ll = len(sliding_layer_ids(cfg, cfg.num_layers, 0))
    lg = cfg.num_layers - ll
    r = ring_slots(cfg)
    gshape = (lg, num_microbatches, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    lshape = (ll, num_microbatches, batch, r, cfg.num_kv_heads, cfg.head_dim)
    gz = _sharded_zeros_fn(gshape, cfg.kv_jnp_dtype, sharding)
    lz = _sharded_zeros_fn(lshape, cfg.kv_jnp_dtype, sharding)
    return PipelinedCaches(
        k=gz(), v=gz(), lengths=jnp.zeros((num_microbatches,), jnp.int32),
        k_loc=lz(), v_loc=lz(),
    )


def _pipeline_pass(
    params: Params,  # rank-local layer slice; embed/norm/head replicated
    x: jax.Array,  # [N, B, S] int32 tokens for N in-flight microbatches
    slots: jax.Array,  # [N] cache slot each in-flight microbatch writes to
    last_idx: jax.Array,  # scalar: index within S of the last REAL token
    k: jax.Array,  # [L_local, MB, B, T, kv, d] (split: global layers only)
    v: jax.Array,
    lengths: jax.Array,  # [MB]
    k_loc: Optional[jax.Array] = None,  # split: [Ll_local, MB, B, R, kv, d]
    v_loc: Optional[jax.Array] = None,  # sliding-layer rings
    *,
    cfg: ModelConfig,
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
    full_logits: bool = False,
):
    """One interleaved pass: N microbatches move through every stage, each
    reading/writing cache slot slots[i] at start offset lengths[slots[i]].
    Returns (new_k, new_v, last-real-token logits [N, B, V] — replicated),
    plus (new_k_loc, new_v_loc) before the logits when the rings are passed. With
    `full_logits`, the logits buffer is [N, B, S, V] — every chunk
    position unembedded (the speculative VERIFY shape: the accept frontier
    needs the target's distribution at all K+1 positions; S is the small
    verify chunk there, so the extra unembed cost is K·|vocab| per slot).

    With `tp_axis`, each pp rank's layer slice additionally runs on a
    tensor-parallel head/expert shard (models/qwen3.decoder_layer psums the
    two row-parallel projections); the KV cache then holds local kv heads
    only, and embed/norm/lm_head stay replicated so the hop/logits logic is
    unchanged — pp x tp serving in one SPMD program.

    With rings (`split`: sliding-window configs passing ring_split_ok), each
    rank's slice runs with a STATIC layer offset of 0:
    every rank's slice starts on an even global index, so the rank-local
    sliding/global alternation is identical across ranks and sliding layers
    read/write O(window) rings — the same program on every rank, which is
    what shard_map requires. The traced-offset design this replaces could
    never make the pattern static (mesh_executor r03 fallback)."""
    split = k_loc is not None
    pp = lax.axis_size("pp")
    idx = lax.axis_index("pp")
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    n, b, s = x.shape
    h = cfg.hidden_size

    state = jnp.zeros((b, s, h), cfg.jnp_dtype)
    if full_logits:
        logits_buf = jnp.zeros((n, b, s, cfg.vocab_size), jnp.float32)
    else:
        logits_buf = jnp.zeros((n, b, cfg.vocab_size), jnp.float32)

    def tick(carry, t):
        state, bufs, logits_buf = carry
        # which in-flight microbatch is resident on this rank at tick t
        m = t - idx
        valid = (m >= 0) & (m < n)
        mi = jnp.clip(m, 0, n - 1)
        slot = slots[mi]

        # stage-0 input: embed microbatch t's tokens
        emb = qwen3.embed(params, x[jnp.clip(t, 0, n - 1)], cfg)
        inp = jnp.where(idx == 0, emb, state)

        start = lengths[slot]
        positions = start + jnp.broadcast_to(jnp.arange(s), (b, s))
        # the resident slot's cache view (None rings are no leaves)
        old = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, slot, axis=1, keepdims=False), bufs
        )
        # split: every rank's slice starts on an even global index, so the
        # STATIC offset 0 gives all ranks one pattern; real_end is ABSOLUTE
        # (first bucket-padding position in the stream): the chunk's real
        # rows are start..start+last_idx. Uniform: the rank's own (traced)
        # offset, windows mask-only
        y, nc, _ = qwen3.forward_layers_cached(
            params["layers"], cfg, inp, positions,
            KVCache(k=old[0], v=old[1], length=start, k_loc=old[2], v_loc=old[3]),
            start, real_end=start + last_idx + 1 if split else None,
            layer_offset=0 if split else idx * (cfg.num_layers // pp),
            tp_axis=tp_axis, ep_axis=ep_axis,
        )
        # cache writeback for the resident slot: on bubble ticks write the
        # ORIGINAL slice back (no-op) — the select stays slice-sized
        # instead of cache-sized
        bufs = jax.tree.map(
            lambda a, new, was: lax.dynamic_update_index_in_dim(
                a, jnp.where(valid, new, was), slot, axis=1
            ),
            bufs, (nc.k, nc.v, nc.k_loc, nc.v_loc), old,
        )

        # last rank: unembed the last REAL token into the output slot
        # (or, for the speculative verify shape, the WHOLE chunk)
        out_m = t - (pp - 1)
        oc = jnp.clip(out_m, 0, n - 1)
        if full_logits:
            logits = qwen3.unembed(params, cfg, y).astype(jnp.float32)  # [B, S, V]
        else:
            last_h = lax.dynamic_index_in_dim(y, last_idx, axis=1, keepdims=True)
            logits = qwen3.unembed(params, cfg, last_h)[:, 0].astype(jnp.float32)
        write = (idx == pp - 1) & (out_m >= 0)
        cur = lax.dynamic_index_in_dim(logits_buf, oc, axis=0, keepdims=False)
        logits_buf = lax.dynamic_update_index_in_dim(
            logits_buf, jnp.where(write, logits, cur), oc, axis=0
        )

        state = lax.ppermute(y, "pp", perm)
        return (state, bufs, logits_buf), None

    (_, (k, v, k_loc, v_loc), logits_buf), _ = lax.scan(
        tick, (state, (k, v, k_loc, v_loc), logits_buf), jnp.arange(n + pp - 1)
    )
    # only the last rank filled the buffer; psum replicates it
    logits_buf = lax.psum(
        jnp.where(idx == pp - 1, logits_buf, jnp.zeros_like(logits_buf)), "pp"
    )
    rings = (k_loc, v_loc) if split else ()
    return (k, v, *rings, logits_buf)


def _rows_pass(
    params: Params,  # rank-local layer slice; embed/norm/head replicated
    toks: jax.Array,  # [MB] int32: each slot's next token
    active: jax.Array,  # [MB] bool: the slots this pass advances
    k: jax.Array,  # [L_local, MB, B=1, T, kv, d] (split: global layers only)
    v: jax.Array,
    lengths: jax.Array,  # [MB]
    k_loc: Optional[jax.Array] = None,  # split: [Ll_local, MB, B=1, R, kv, d]
    v_loc: Optional[jax.Array] = None,
    *,
    cfg: ModelConfig,
    tp_axis: Optional[str] = None,
    ep_axis: Optional[str] = None,
):
    """The serving DECODE pass: ONE microbatch whose rows are the slots, so
    a pass is pp ticks and in its own tick (t == rank) a stage reads its
    weights once for every live session. The stage's stacks with MB and B
    merged ARE dense lanes [L_local, MB, T, kv, d]: the tick scan carries
    them and the layer scan writes each row at (layer, row, lengths[row])
    where it lies, exactly the lane executor's decode step
    (core/batch._decode_logits), rings included. A stage runs its layers,
    and so commits keys and values, only in its own tick (a lax.cond: in
    every other tick it only hands on what the hop brought, which also
    keeps a profiler capture of a pass to one stage's operations a chip),
    and there only for an active row (write_mask): an inactive slot
    (mid-prefill, free, or full at max_len) is not touched. The head runs
    once, after the last tick, on the last rank's output. Returns
    (k', v', [k_loc', v_loc',] logits [MB, V] float32, replicated).

    Shares forward_layers_cached and the hop with _pipeline_pass and
    nothing else: that one wants a scalar start and N microbatches, this
    one a vector of starts and one."""
    split = k_loc is not None
    pp = lax.axis_size("pp")
    idx = lax.axis_index("pp")
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    stacks = (k, v, k_loc, v_loc)
    # [L, MB, B, ...] -> [L, MB * B, ...]: the slots as the lanes' rows
    lanes = jax.tree.map(lambda a: a.reshape(a.shape[0], -1, *a.shape[3:]), stacks)
    emb = qwen3.embed(params, toks[:, None], cfg)  # [MB, 1, H]
    positions = lengths[:, None]

    def own_tick(inp, lanes):
        y, nc, _ = qwen3.forward_layers_cached(
            params["layers"], cfg, inp, positions,
            KVCache(k=lanes[0], v=lanes[1], length=lengths, k_loc=lanes[2], v_loc=lanes[3]),
            lengths, real_end=lengths + 1,
            # as _pipeline_pass: a static 0 under the split, else the rank's own
            layer_offset=0 if split else idx * (cfg.num_layers // pp),
            write_mask=active, tp_axis=tp_axis, ep_axis=ep_axis,
        )
        return y, (nc.k, nc.v, nc.k_loc, nc.v_loc)

    def tick(carry, t):
        state, lanes = carry
        # the microbatch enters stage 0 in tick 0; a stage runs its layers
        # in its own tick and in no other (the hop is every rank's)
        y, lanes = lax.cond(
            t == idx, own_tick, lambda inp, lanes: (inp, lanes),
            jnp.where(idx == 0, emb, state), lanes,
        )
        return (lax.ppermute(y, "pp", perm), lanes), None

    (state, lanes), _ = lax.scan(tick, (jnp.zeros_like(emb), lanes), jnp.arange(pp))
    # the last hop brought the last rank's output to rank 0; every rank
    # takes it and unembeds its replicated head: [MB, H] rides, not [MB, V]
    last_h = lax.psum(jnp.where(idx == 0, state, jnp.zeros_like(state)), "pp")
    logits = qwen3.unembed(params, cfg, last_h)[:, 0].astype(jnp.float32)
    k, v, k_loc, v_loc = jax.tree.map(lambda a, was: a.reshape(was.shape), lanes, stacks)
    rings = (k_loc, v_loc) if split else ()
    return (k, v, *rings, logits)


def cache_spec(mesh: Mesh) -> P:
    """PipelinedCaches k/v spec: layers shard over pp; with tp in the mesh
    the kv-head axis (4 of [L, MB, B, T, n_kv, d]) shards over tp too (in
    the row layout [L, MB, B, T, n_kv * d] the same axis 4: a rank's kv
    heads are one run of the row's columns)."""
    if mesh.shape.get("tp", 1) > 1:
        return P("pp", None, None, None, "tp")
    return P("pp")


def make_sp_prefill_pass(cfg: ModelConfig, mesh: Mesh, params: Params):
    """Sequence-parallel PREFILL for serving (VERDICT r04 #3): the prompt's
    sequence axis shards over `sp`, each pp stage runs its layer slice on
    its LOCAL block with RING attention over sp (parallel.ring — K/V blocks
    rotate via ppermute, nothing bigger than [S/sp, S/sp] materializes),
    and the per-layer K/V gathers over sp into the DECODE cache layout at
    the end — so a long-context prompt costs each chip 1/sp of the
    attention/MLP work and 1/sp of the peak activation memory, then decode
    continues on the standard (sp-replicated) pipeline pass token-exact.

    Returns a shard_map'd fn (params, x [B, S], positions [B, S], n) ->
    (k [L, B, S, Nkv, D], v, last-real-token logits [B, V] replicated).
    The reference's prefill is a full-sequence forward on ONE machine with
    O(seq^2) eager attention (qwen3_server_module.py:67-89); SURVEY §7
    names sequence sharding the idiomatic TPU extension axis."""
    from inferd_tpu.parallel.tp import sharded_forward_layers

    pspecs = meshlib.param_specs_for(params, cfg, layer_axis="pp")
    tp_on = mesh.shape.get("tp", 1) > 1
    kv_spec = P("pp", None, None, "tp") if tp_on else P("pp")

    def _pass(p, x, positions, n):
        pp = lax.axis_size("pp")
        idx = lax.axis_index("pp")
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        n_local = jax.tree.leaves(p["layers"])[0].shape[0]

        emb = qwen3.embed(p, x, cfg)  # local block [B, S_local, H]
        state = jnp.where(idx == 0, emb, jnp.zeros_like(emb))
        ks_buf = vs_buf = None
        for t in range(pp):  # static: one stage works per tick, like
            # _pipeline_pass with a single in-flight microbatch
            out, (ks, vs) = sharded_forward_layers(
                p["layers"], cfg, state, positions, "tp", "sp",
                layer_offset=idx * n_local, return_kv=True,
            )
            valid = idx == t
            if ks_buf is None:
                ks_buf = jnp.zeros_like(ks)
                vs_buf = jnp.zeros_like(vs)
            ks_buf = jnp.where(valid, ks, ks_buf)
            vs_buf = jnp.where(valid, vs, vs_buf)
            state = jnp.where(valid, out, state)
            if t < pp - 1:
                state = lax.ppermute(state, "pp", perm)

        # last-REAL-token logits: the row lives on one sp rank's block of
        # the LAST pp stage; select + psum(sp) replicates the row, unembed,
        # psum(pp) masked to the last rank replicates the logits
        row_mask = (positions == n - 1)[..., None].astype(state.dtype)
        row = lax.psum(jnp.sum(state * row_mask, axis=1), "sp")  # [B, H]
        lg = qwen3.unembed(p, cfg, row[:, None])[:, 0].astype(jnp.float32)
        logits = lax.psum(
            jnp.where(idx == pp - 1, lg, jnp.zeros_like(lg)), "pp"
        )

        # K/V for the decode cache: gather the sequence axis over sp —
        # each rank then holds full-T KV for its own layers (the decode
        # pass's sp-replicated layout)
        k_full = lax.all_gather(ks_buf, "sp", axis=2, tiled=True)
        v_full = lax.all_gather(vs_buf, "sp", axis=2, tiled=True)
        return k_full, v_full, logits

    return jax.shard_map(
        _pass,
        mesh=mesh,
        in_specs=(pspecs, P(None, "sp"), P(None, "sp"), P()),
        out_specs=(kv_spec, kv_spec, P()),
        check_vma=False,
    )


def make_pipeline_pass(
    cfg: ModelConfig,
    mesh: Mesh,
    params: Optional[Params] = None,
    ring: Optional[bool] = None,
    full_logits: bool = False,
    rows: bool = False,
):
    """shard_map'd pipeline pass: (params, x[N,B,S], slots[N], last_idx,
    k, v, lengths) -> (k', v', logits[N,B,V]) — or, in the split ring
    layout (ring_split_ok; `ring` mirrors make_caches), (params, x, slots,
    last_idx, k, v, lengths, k_loc, v_loc) -> (k', v', k_loc', v_loc',
    logits). With `rows` it is the serving decode pass over every slot as
    a row (_rows_pass): (params, toks[MB], active[MB], k, v, lengths[,
    k_loc, v_loc]) -> (k', v'[, k_loc', v_loc'], logits[MB,V]). Layers and
    caches shard over pp — and over tp (head/expert axes,
    mesh.layer_param_specs) when the mesh has one; everything else
    replicates. Pass `params` so the spec tree matches structurally
    (quantized leaves expand to q/scale pairs)."""
    if params is not None:
        pspecs = meshlib.param_specs_for(params, cfg, layer_axis="pp")
    else:
        pspecs = meshlib.model_param_specs(cfg, layer_axis="pp")
    tp_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
    ep_axis = "ep" if mesh.shape.get("ep", 1) > 1 else None
    kv = cache_spec(mesh)
    split = ring_split_ok(cfg, mesh.shape["pp"]) if ring is None else (
        ring and ring_split_ok(cfg, mesh.shape["pp"])
    )
    rings = (kv, kv) if split else ()  # the sliding layers' k_loc, v_loc
    axes = dict(cfg=cfg, tp_axis=tp_axis, ep_axis=ep_axis)
    if rows:
        fn, lead = partial(_rows_pass, **axes), (P(), P())
    else:
        fn = partial(_pipeline_pass, full_logits=full_logits, **axes)
        lead = (P(), P(), P())
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(pspecs, *lead, kv, kv, P(), *rings),
        out_specs=(kv, kv, *rings, P()),
        check_vma=False,
    )


def _over_caches(raw_pass):
    """A make_pipeline_pass program as a function of (params, what leads
    its caches, PipelinedCaches, lengths) -> (k', v', k_loc', v_loc',
    logits), the rings None where the caches have none."""

    def passfn(params, *args):
        *lead, caches, lengths = args
        rings = () if caches.k_loc is None else (caches.k_loc, caches.v_loc)
        *bufs, logits = raw_pass(
            params, *lead, caches.k, caches.v, lengths, *rings
        )
        nk, nv, nkl, nvl = (*bufs, None, None)[:4]
        return nk, nv, nkl, nvl, logits

    return passfn


class PipelinedEngine:
    """Generation engine over the in-mesh pipeline. The host loop calls one
    jitted step per token; MB*B sequences advance together, finished slots
    refill from the queue. Not thread-safe: self.caches is donated through
    every step, so callers must serialize generate()/prefill_slot()/
    decode_step() externally (one request at a time, or a lock)."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        mesh: Mesh,
        num_microbatches: int,
        batch: int = 1,
        max_len: int = 512,
        sampling_cfg: Optional[SamplingConfig] = None,
        ring: Optional[bool] = None,
    ):
        if cfg.num_layers % mesh.shape["pp"]:
            raise ValueError(
                f"num_layers {cfg.num_layers} not divisible by pp={mesh.shape['pp']}"
            )
        # the one divisibility oracle (heads, kv heads, experts,
        # intermediate) — shared with the train step and the dryrun
        meshlib.check_divisibility(
            cfg,
            meshlib.MeshPlan(
                pp=mesh.shape["pp"], tp=mesh.shape.get("tp", 1),
                ep=mesh.shape.get("ep", 1),
            ),
        )
        if mesh.shape.get("ep", 1) > 1 and not cfg.is_moe:
            raise ValueError("ep axis needs a MoE config (dense has no experts)")
        allowed = ("pp", "tp", "ep", "sp")
        bad = [a for a, n in mesh.shape.items() if a not in allowed and n != 1]
        if bad:
            # the pipeline pass reduces over pp (hops), tp (Megatron psums)
            # and ep (expert combine) only; dp params would shard without
            # their collectives — wrong logits. sp is allowed: PREFILL
            # shards the sequence over it (make_sp_prefill_pass) and the
            # decode pass simply replicates over it.
            raise ValueError(
                f"PipelinedEngine needs a pp(x tp x ep x sp) mesh; axes {bad} have size > 1"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.mb = num_microbatches
        self.batch = batch
        self.max_len = max_len
        self.sampling = sampling_cfg or SamplingConfig()
        if mesh.shape.get("sp", 1) > 1 and cfg.sliding_window and ring is None:
            # sp prefill adopts gathered K/V into the cache directly — the
            # ring layout's slot arithmetic doesn't admit a bulk adopt, so
            # sliding-window models serve sp with the uniform cache
            # (O(context) storage on sliding layers; the sp win is prefill
            # compute/activations, documented trade)
            ring = False
        self.params = meshlib.shard_params(params, cfg, mesh, layer_axis="pp")
        self.caches = make_caches(
            cfg, mesh, num_microbatches, batch, max_len, ring=ring
        )
        # split ring layout active? (sliding-window config + rank-aligned
        # split + not forced off) — decided once; every jit below branches
        # on it at trace time
        self.ring_active = self.caches.k_loc is not None

        passfn = _over_caches(make_pipeline_pass(cfg, mesh, params=params, ring=ring))
        rows_passfn = _over_caches(
            make_pipeline_pass(cfg, mesh, params=params, ring=ring, rows=True)
        )
        sampling = self.sampling

        def _sample_lanes(logits, keys, done, prev, eos, top_n=0,
                          want_lp=False):
            """Advance each lane's PRNG chain and sample its next token.
            logits [N, V] f32; keys [N, 2] uint32; done/prev [N].
            Chain: key, sub = split(key); sample(logits[None], sub) — the
            exact schedule of core.generate.Engine.generate, so a pipelined
            lane and a single-process run with the same seed emit the same
            tokens. Also returns each lane's emitted-token model logprob +
            top-N alternatives (garbage for done lanes; the host skips
            them)."""
            sp = jax.vmap(lambda kk: jax.random.split(kk))(keys)  # [N, 2, 2]
            nkeys, subs = sp[:, 0], sp[:, 1]
            if sampling.temperature == 0.0:
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                toks = jax.vmap(
                    lambda l, kk: samplib.sample(
                        l[None], kk, sampling.temperature, sampling.top_k,
                        sampling.top_p, sampling.min_p,
                    )[0]
                )(logits, subs).astype(jnp.int32)
            toks = jnp.where(done, prev, toks)
            ndone = done | (toks == eos)
            # want_lp static: the no-logprob path never pays the full-vocab
            # log-softmax (each variant compiles separately)
            n_rows = logits.shape[0]
            lp, ti, tl = (
                samplib.logprob_topn(logits, toks, top_n) if want_lp
                else (jnp.zeros((n_rows,), jnp.float32),
                      jnp.zeros((n_rows, 0), jnp.int32),
                      jnp.zeros((n_rows, 0), jnp.float32))
            )
            return nkeys, toks, ndone, lp, ti, tl

        @partial(jax.jit, donate_argnames=("caches",),
                 static_argnames=("top_n", "want_lp"))
        def _prefill(params, caches: PipelinedCaches, tokens, slot, real_len, keys, eos,
                     top_n: int = 0, want_lp: bool = False):
            # tokens [1, B, S_bucket]; slot/real_len scalars; keys [B, 2]
            lengths0 = caches.lengths.at[slot].set(0)
            nk, nv, nkl, nvl, logits = passfn(
                params, tokens, slot[None], real_len - 1, caches, lengths0
            )
            new = PipelinedCaches(
                k=nk, v=nv, lengths=lengths0.at[slot].set(real_len),
                k_loc=nkl, v_loc=nvl,
            )
            nkeys, toks, done, lp, ti, tl = _sample_lanes(
                logits[0], keys, jnp.zeros((tokens.shape[1],), bool),
                jnp.zeros((tokens.shape[1],), jnp.int32), eos, top_n, want_lp,
            )
            return new, toks, nkeys, done, lp, ti, tl

        @partial(jax.jit, donate_argnames=("caches",),
                 static_argnames=("top_n", "want_lp"))
        def _decode(params, caches: PipelinedCaches, tok, active, keys, done, eos,
                    top_n: int = 0, want_lp: bool = False):
            # tok [MB, B] int32; active [MB] bool; keys [MB, B, 2]; done [MB, B]
            mb, b = tok.shape
            nk, nv, nkl, nvl, logits = passfn(
                params, tok[..., None], jnp.arange(mb), jnp.int32(0),
                caches, caches.lengths,
            )
            new = PipelinedCaches(
                k=nk, v=nv, lengths=caches.lengths + active.astype(jnp.int32),
                k_loc=nkl, v_loc=nvl,
            )
            nkeys, toks, ndone, lp, ti, tl = _sample_lanes(
                logits.reshape(mb * b, -1), keys.reshape(mb * b, 2),
                done.reshape(mb * b), tok.reshape(mb * b), eos, top_n, want_lp,
            )
            return (
                new, toks.reshape(mb, b), nkeys.reshape(mb, b, 2),
                ndone.reshape(mb, b), lp.reshape(mb, b),
                ti.reshape(mb, b, -1), tl.reshape(mb, b, -1),
            )

        @partial(jax.jit, donate_argnames=("caches",))
        def _step_raw(params, caches: PipelinedCaches, tokens, slot, real_len, reset):
            # server-side raw step: one slot (a prefill chunk), no sampling:
            # the caller samples the first token from the logits it ships
            lengths0 = jnp.where(
                reset, caches.lengths.at[slot].set(0), caches.lengths
            )
            nk, nv, nkl, nvl, logits = passfn(
                params, tokens, slot[None], real_len - 1, caches, lengths0
            )
            new = PipelinedCaches(
                k=nk, v=nv, lengths=lengths0.at[slot].add(real_len),
                k_loc=nkl, v_loc=nvl,
            )
            return new, logits[0]

        @partial(jax.jit, donate_argnames=("caches",), static_argnames=("top_n",))
        def _step_raw_multi(params, caches: PipelinedCaches, toks, active,
                            ask=None, top_n: int = 0):
            # server-side MULTI-slot decode: co-arriving sessions ride one
            # pass as the ROWS of its one microbatch (_rows_pass: pp ticks,
            # each stage reads its weights once for all of them). toks [MB]
            # int32, active [MB] bool; inactive slots compute at their
            # frontier but write nothing, do not advance and do not
            # surface. Returns logits [MB, V] and, with an `ask`
            # (core.sampling.RowAsk: per-slot keys and sampling, traced),
            # every slot's token chosen here, after the head, packed with
            # its next key and (`top_n` > 0, static) its log-probabilities
            # for one transfer (core.sampling.choose_rows, the lane
            # executor's sampler).
            nk, nv, nkl, nvl, logits = rows_passfn(
                params, toks, active, caches, caches.lengths
            )
            new_lengths = jnp.where(active, caches.lengths + 1, caches.lengths)
            new = PipelinedCaches(
                k=nk, v=nv, lengths=new_lengths, k_loc=nkl, v_loc=nvl
            )
            if ask is None:
                return new, logits
            return new, logits, samplib.choose_rows(logits, ask, top_n)

        @partial(jax.jit, donate_argnames=("caches",), static_argnames=("m",))
        def _fork_slot(caches: PipelinedCaches, src, dst, prefix_len, m: int):
            """Copy slot src's first m KV slots into slot dst and set dst's
            length to prefix_len (prefix-cache fork). The slot axis is
            unsharded — the copy is shard-local on every pp rank; donation
            keeps it in place. Ring buffers copy WHOLE (every slot may be
            live); the caller (mesh executor) enforces the fork-truncation
            margin that keeps the child's stale "newer" slots structurally
            outside every window (core.cache aliasing invariant)."""
            ks = jax.lax.dynamic_slice_in_dim(caches.k, src, 1, axis=1)[:, :, :, :m]
            vs = jax.lax.dynamic_slice_in_dim(caches.v, src, 1, axis=1)[:, :, :, :m]
            zero = jnp.int32(0)
            idx = (zero, dst) + (zero,) * (caches.k.ndim - 2)  # rows have no head axis
            k_loc, v_loc = caches.k_loc, caches.v_loc
            if k_loc is not None:
                kl = jax.lax.dynamic_slice_in_dim(k_loc, src, 1, axis=1)
                vl = jax.lax.dynamic_slice_in_dim(v_loc, src, 1, axis=1)
                k_loc = jax.lax.dynamic_update_slice(k_loc, kl, idx)
                v_loc = jax.lax.dynamic_update_slice(v_loc, vl, idx)
            return PipelinedCaches(
                k=jax.lax.dynamic_update_slice(caches.k, ks, idx),
                v=jax.lax.dynamic_update_slice(caches.v, vs, idx),
                lengths=caches.lengths.at[dst].set(prefix_len),
                k_loc=k_loc, v_loc=v_loc,
            )

        self._prefill = _prefill
        self._decode = _decode
        self._step_raw = _step_raw
        self._step_raw_multi = _step_raw_multi
        self._fork_slot = _fork_slot
        # speculative state (enable_spec): draft params replicated on every
        # mesh rank + a slot-indexed draft cache; None until enabled
        self.spec_dcfg = None
        self.spec_dparams = None
        self.spec_dcache = None
        self.spec_k = 0
        self._passfn_full = None
        self._ring_arg = ring
        # sequence-parallel prefill (built lazily on first use): requires
        # an sp axis > 1 and the uniform cache layout (see ctor). The raw
        # tree is kept ONLY on sp meshes (param_specs_for needs its
        # structure) — holding it on every engine would pin a full host
        # copy of the weights for nothing
        self._sp_raw_params = params if mesh.shape.get("sp", 1) > 1 else None
        self._sp_prefill_fn = None
        # span recorder handed down by the serving layer (MeshExecutor):
        # the raw serving steps stamp `device` and `copy_out` with it
        self.tracer = None
        # pipeline-pass counters of the raw serving steps, host arithmetic
        # from each pass's shape and active mask: a serving pass is one
        # microbatch of `rows` rows (a prefill step's one slot, a decode
        # pass's every slot) through pp ticks on pp stages; a stage-tick
        # counts the row places it carries, of which a live slot uses pp
        # (its row in its own tick on every stage)
        self.passes = 0
        self.stage_ticks = 0
        self.stage_ticks_useful = 0
        # how a decode pass takes its tokens and keys (`dispatch_slots`):
        # core.sampling.ahead_rows with its two results laid out one way on
        # the mesh whatever it read them from, and what it reads where
        # nothing comes from the pass before (a packed array's first three
        # columns; placed on the mesh at the first pass)
        repl = NamedSharding(mesh, P())
        self._feed = jax.jit(samplib.ahead_rows, out_shardings=(repl, repl))
        self._no_pass = None

    @property
    def sp_active(self) -> bool:
        """Is sequence-parallel prefill available? (sp axis > 1 and a
        bulk-adoptable cache layout.)"""
        return self.mesh.shape.get("sp", 1) > 1 and not self.ring_active

    def sp_prefill_slot(self, slot: int, tokens: np.ndarray, real_len: int):
        """Reset `slot` and prefill it SEQUENCE-PARALLEL: tokens [B, S]
        (B == batch == 1 serving shape) shard over sp, ring attention per
        layer, K/V gathered into the slot's cache rows. Returns last-real-
        token logits [B, V] — the same contract as step_slot(reset=True)
        for a start-0 chunk, token-exact with it."""
        if not self.sp_active:
            raise RuntimeError("sp prefill needs an sp>1 mesh (uniform cache)")
        b, s = tokens.shape
        if b != 1 or self.batch != 1:
            # the padding/logits plumbing below is single-lane; a silent
            # [0]-index would drop every other lane's prompt
            raise ValueError("sp prefill supports batch=1 slots only")
        if s > real_len:
            tokens, s = tokens[:, :real_len], real_len
        if real_len + 1 > self.max_len:
            raise BufferError(f"prompt {real_len} exceeds max_len {self.max_len}")
        if self._sp_prefill_fn is None:
            sp_pass = make_sp_prefill_pass(
                self.cfg, self.mesh, self._sp_raw_params
            )

            @partial(jax.jit, donate_argnames=("caches",))
            def _sp_prefill(params, caches: PipelinedCaches, x, positions,
                            slot, n):
                k_full, v_full, logits = sp_pass(params, x, positions, n)
                zero = jnp.int32(0)
                at = (zero, slot) + (zero,) * (caches.k.ndim - 2)
                # [L, B, S, Nkv, D] as the slot's lanes are stored (heads or rows)
                put = lambda a, full: jax.lax.dynamic_update_slice(
                    a, full.reshape(*full.shape[:3], *a.shape[4:])[:, None].astype(a.dtype), at
                )
                return PipelinedCaches(
                    k=put(caches.k, k_full), v=put(caches.v, v_full),
                    lengths=caches.lengths.at[slot].set(n),
                    k_loc=caches.k_loc, v_loc=caches.v_loc,
                ), logits

            self._sp_prefill_fn = _sp_prefill
        sp = self.mesh.shape["sp"]
        # pad to a bucket divisible by sp (both are powers of two in
        # practice; the lcm round-up keeps oddball sp honest)
        sb = min(bucket_len(real_len), self.max_len)
        if sb % sp:
            sb = ((sb + sp - 1) // sp) * sp
        if sb > self.max_len:
            raise BufferError(
                f"sp-padded prompt bucket {sb} exceeds max_len {self.max_len}"
            )
        padded = np.zeros((1, sb), np.int32)
        padded[0, :s] = np.asarray(tokens[0], np.int32)
        positions = np.broadcast_to(np.arange(sb, dtype=np.int32), (1, sb))
        self.caches, logits = self._sp_prefill_fn(
            self.params, self.caches, jnp.asarray(padded),
            jnp.asarray(positions), jnp.int32(slot), jnp.int32(real_len),
        )
        return np.asarray(logits)

    def enable_spec(self, draft_layers: int, k: int, raw_params: Params) -> None:
        """In-mesh speculation (VERDICT r04 #1b): the draft layers are
        SMALL by construction (layer-truncated self-draft), so they
        REPLICATE on every pp/tp rank — the draft scan runs identically
        everywhere with no collectives, and only the verify chunk rides
        the ppermute pipeline. One spec round = ONE jitted SPMD program
        (draft scan + (K+1)-token pipeline pass + accept frontier).

        `raw_params` is the UNSHARDED checkpoint (the ctor's input): the
        draft slice must not inherit the pp/tp layer sharding."""
        from jax.sharding import NamedSharding

        from inferd_tpu.core import spec_batch as sbl
        from inferd_tpu.core.cache import KVCache
        from inferd_tpu.core.speculative import self_draft

        dcfg, dparams = self_draft(self.cfg, raw_params, draft_layers)
        sbl.check_ring_margin(self.cfg, dcfg, k)
        repl = NamedSharding(self.mesh, P())
        self.spec_dcfg = dcfg
        self.spec_dparams = jax.device_put(dparams, repl)
        self.spec_dcache = jax.device_put(
            KVCache.create(dcfg, dcfg.num_layers, self.mb, self.max_len), repl
        )
        self.spec_k = k
        passfn_full = _over_caches(make_pipeline_pass(
            self.cfg, self.mesh, params=raw_params, ring=self._ring_arg,
            full_logits=True,
        ))
        self._passfn_full = passfn_full


    def fork_slot(self, src: int, dst: int, prefix_len: int) -> None:
        """Seed slot `dst` with the first `prefix_len` cache entries of slot
        `src` (bucketed copy; caller manages slot bookkeeping/locking)."""
        m = min(bucket_len(prefix_len), self.max_len)
        self.caches = self._fork_slot(
            self.caches, jnp.int32(src), jnp.int32(dst), jnp.int32(prefix_len), m
        )

    def set_slot_length(self, slot: int, n: int) -> None:
        """Force a slot's cache frontier (deterministic replay rollback: a
        client re-sent a chunk after a lost response — positions past n are
        recomputed identically by the re-sent chunks). With ring storage
        the CALLER must bound the rollback depth by the ring margin (the
        mesh executor tracks per-session high-water marks, mirroring the
        stage executor's replay guard); uniform layouts accept any depth."""
        self.caches = PipelinedCaches(
            k=self.caches.k, v=self.caches.v,
            lengths=self.caches.lengths.at[slot].set(n),
            k_loc=self.caches.k_loc, v_loc=self.caches.v_loc,
        )

    def export_slot(self, slot: int):
        """A slot's session KV as GLOBAL host arrays + its length: (k, v,
        length, k_loc, v_loc) — k/v [Lg, B, T, Nkv, D] (the layer axis
        reassembles across pp ranks, kv heads across tp), k_loc/v_loc the
        sliding-layer rings [Ll, B, R, Nkv, D] (whole) or None for uniform
        layouts. The elastic-reshard/checkpoint surface: an exported slot
        can be imported into an engine with a DIFFERENT mesh split."""
        k = wire_heads(np.asarray(jax.device_get(self.caches.k[:, slot])), self.cfg)
        v = wire_heads(np.asarray(jax.device_get(self.caches.v[:, slot])), self.cfg)
        if self.caches.k_loc is None:
            return k, v, int(self.caches.lengths[slot]), None, None
        kl = np.asarray(jax.device_get(self.caches.k_loc[:, slot]))
        vl = np.asarray(jax.device_get(self.caches.v_loc[:, slot]))
        return k, v, int(self.caches.lengths[slot]), kl, vl

    def import_slot(
        self, slot: int, k, v, length: int, k_loc=None, v_loc=None
    ) -> None:
        """Adopt a slot's KV exported from another engine (possibly a
        different pp/tp split of the SAME model): buffers re-shard onto
        this mesh's cache layout; the session continues mid-stream. Ring
        layouts require matching ring payloads (k_loc/v_loc) — slot
        attribution is position % R on both sides, so the rings copy
        verbatim; a uniform payload into a ring engine (or vice versa)
        rejects (the handoff codec fails closed the same way)."""
        ring = self.caches.k_loc is not None
        if ring != (k_loc is not None):
            raise ValueError(
                "slot KV layout mismatch: engine ring storage is "
                f"{'on' if ring else 'off'} but payload rings are "
                f"{'present' if k_loc is not None else 'absent'}"
            )
        want = (self.caches.k.shape[0], self.batch, self.cfg.num_kv_heads, self.cfg.head_dim)
        got = (k.shape[0], k.shape[1]) + tuple(k.shape[3:])
        if got != want or v.shape != k.shape:
            raise ValueError(f"slot KV shape {k.shape} does not match this engine")
        if length > self.max_len:
            raise BufferError(f"imported length {length} exceeds max_len")
        # [L, B, T, Nkv, D] as exported, whatever this engine stores
        k, v = (from_wire(a, self.cfg, uniform=not ring) for a in (k, v))
        t = k.shape[2]
        if t < self.max_len:
            pad = [(0, 0), (0, 0), (0, self.max_len - t)] + [(0, 0)] * (k.ndim - 3)
            k, v = np.pad(k, pad), np.pad(v, pad)
        elif t > self.max_len:
            k, v = k[:, :, : self.max_len], v[:, :, : self.max_len]
        kk = jnp.asarray(k, self.caches.k.dtype)
        vv = jnp.asarray(v, self.caches.v.dtype)
        zero = jnp.int32(0)
        idx = (zero, jnp.int32(slot)) + (zero,) * (self.caches.k.ndim - 2)  # rings: with heads, 6
        new_k_loc, new_v_loc = self.caches.k_loc, self.caches.v_loc
        if ring:
            lshape = (self.caches.k_loc.shape[0], self.batch,
                      self.caches.k_loc.shape[3])
            if (k_loc.shape[0], k_loc.shape[1], k_loc.shape[2]) != lshape or (
                v_loc.shape != k_loc.shape
            ):
                raise ValueError(
                    f"ring payload shape {k_loc.shape} does not match this "
                    f"engine's rings"
                )
            kkl = jnp.asarray(k_loc, self.caches.k_loc.dtype)
            vvl = jnp.asarray(v_loc, self.caches.v_loc.dtype)
            new_k_loc = jax.lax.dynamic_update_slice(
                self.caches.k_loc, kkl[:, None], idx
            )
            new_v_loc = jax.lax.dynamic_update_slice(
                self.caches.v_loc, vvl[:, None], idx
            )
        self.caches = PipelinedCaches(
            k=jax.lax.dynamic_update_slice(self.caches.k, kk[:, None], idx),
            v=jax.lax.dynamic_update_slice(self.caches.v, vv[:, None], idx),
            lengths=self.caches.lengths.at[slot].set(length),
            k_loc=new_k_loc, v_loc=new_v_loc,
        )

    # -- slot-level primitives (the generate() loop below drives them; a
    # serving layer can drive slots per-session directly) -------------------

    def prefill_slot(
        self, slot: int, prompts: np.ndarray, keys: jax.Array, eos: int,
        top_n: int = 0, want_lp: bool = False,
    ):
        """Reset `slot` and prefill it with prompts [B, real_len] (uniform
        length within the slot). Returns (first_tok [B], keys' [B,2],
        done [B]) — plus (lp [B], top_ids [B,n], top_lps [B,n]) when
        want_lp. Pads to a power-of-two bucket: one compile per bucket."""
        b, real_len = prompts.shape
        if b != self.batch:
            raise ValueError(f"slot holds {self.batch} lanes, got {b} prompts")
        if real_len + 1 > self.max_len:
            raise BufferError(f"prompt {real_len} exceeds max_len {self.max_len}")
        sb = min(bucket_len(real_len), self.max_len)
        padded = np.zeros((1, b, sb), np.int32)
        padded[0, :, :real_len] = prompts
        self.caches, tok, nkeys, done, lp, ti, tl = self._prefill(
            self.params, self.caches, jnp.asarray(padded),
            jnp.int32(slot), jnp.int32(real_len), keys, jnp.int32(eos), top_n,
            want_lp,
        )
        if want_lp:
            return tok, nkeys, done, lp, ti, tl
        return tok, nkeys, done

    def step_slot(
        self,
        slot: int,
        tokens: np.ndarray,
        real_len: int,
        reset: bool,
        start_pos: int = 0,
    ) -> np.ndarray:
        """Raw single-slot step for a serving layer: run tokens [B, S]
        (prompt chunk or single decode token) through the whole pipeline,
        updating slot's cache; returns float32 logits [B, V] of the last
        real token. reset=True starts the slot over (new session). Prompt
        chunks pad to a power-of-two bucket (one compile per bucket);
        `start_pos` (the slot's current length) caps the bucket so the
        padded cache write can never spill past max_len — dynamic_update_
        slice would CLAMP the start and silently corrupt the oldest slots
        (models/qwen3.decoder_layer caller contract)."""
        b, s = tokens.shape
        if b != self.batch:
            raise ValueError(f"slot holds {self.batch} lanes, got {b}")
        if start_pos + real_len > self.max_len:
            raise BufferError(
                f"slot {slot}: {start_pos}+{real_len} exceeds max_len {self.max_len}"
            )
        if s > real_len:  # caller-side padding: keep only the real rows
            tokens, s = tokens[:, :real_len], real_len
        if s > 1:
            sb = min(bucket_len(real_len), self.max_len - start_pos)
            padded = np.zeros((1, b, sb), np.int32)
            padded[0, :, :s] = tokens
        else:
            padded = np.asarray(tokens, np.int32)[None]
        with tracelib.region(  # once a step: kept, as its copy_out is (obs.trace)
            self.tracer, "device", keep=True, kind="prefill" if s > 1 else "decode",
            tokens=real_len, cobatch=1,
            program=program_name(self._step_raw),
        ):
            self.caches, logits = self._step_raw(
                self.params, self.caches, jnp.asarray(padded),
                jnp.int32(slot), jnp.int32(real_len), jnp.bool_(reset),
            )
            logits.block_until_ready()
        self._count_pass(1, 1)
        with tracelib.region(self.tracer, "copy_out", keep=True) as at:
            out = np.asarray(logits)
            at["bytes"] = out.nbytes
        return out

    def dispatch_slots(self, tokens_by_slot, asks=None, ahead=(), last=None):
        """Dispatch ONE pipeline pass that decodes a token for several slots
        (requires batch == 1 per slot — the serving shape) and return without
        waiting for it: (logits [MB, V], packed, top_n), `packed` the pass's
        one small array in core.sampling.pack_rows' layout, on its way to the
        host once the pass is done. tokens_by_slot: {slot: token} from the
        host; `asks`: {slot: an ask with `.sampling`, `.key`, `.want`,
        `.top_n` (runtime/executor.SampleAsk)} for the slots whose token the
        pass chooses; `ahead`: slots whose token and key are those the pass
        before left ON THE DEVICES in its packed array `last` (no round trip:
        core.sampling.ahead_rows).

        Every pass takes its tokens and keys through ahead_rows (`_feed`), a
        pass fed from the host alone too (over `_no_pass`): what it hands
        back lies on the mesh, and a jitted program is compiled anew for
        inputs that lie elsewhere, so `_step_raw_multi` sees ONE placement
        of its inputs whoever feeds it and compiles once a `top_n`. The lengths
        advance inside the pass (`PipelinedCaches.lengths`); the counters
        count it here, at its dispatch."""
        if self.batch != 1:
            raise ValueError("step_slots supports batch=1 slots only")
        asks = asks or {}
        toks = np.zeros((self.mb,), np.int32)
        active = np.zeros((self.mb,), bool)
        from_dev = np.zeros((self.mb,), bool)
        for slot, tok in tokens_by_slot.items():
            toks[slot] = tok
            active[slot] = True
        for slot in ahead:
            from_dev[slot] = active[slot] = True
        ask, top_n = samplib.RowAsk.of(self.mb, asks)
        if self._no_pass is None:
            self._no_pass = jax.device_put(
                np.zeros((self.mb, 3), np.int32), NamedSharding(self.mesh, P(None, None))
            )  # laid out as a pass leaves its packed array: `_feed` compiles once a width
        toks, keys = self._feed(last if len(ahead) else self._no_pass, toks, ask.keys, from_dev)
        self.caches, logits, packed = self._step_raw_multi(
            self.params, self.caches, toks, active,
            ask=samplib.RowAsk(keys, ask.warp), top_n=top_n,
        )
        packed.copy_to_host_async()  # queued behind the pass: on its way when it is done
        self._count_pass(self.mb, int(active.sum()))
        return logits, packed, top_n

    def step_slots(self, tokens_by_slot, asks=None) -> dict:
        """`dispatch_slots` and the wait for it, for a caller that keeps
        nothing ahead: returns, a slot, its reply (core.sampling.row_replies:
        {"tokens": [[id]], "key", ...}) where it asked, else its logits [V]
        float32: ONE small transfer for all the asked; a slot without an ask
        gets its logits row (that row alone where it is the only one, else
        the pass's whole [MB, V])."""
        asks = asks or {}
        plain = [slot for slot in tokens_by_slot if slot not in asks]
        live = len(tokens_by_slot)
        with tracelib.region(
            self.tracer, "device", keep=True, kind="decode", tokens=live, cobatch=live,
            program=program_name(self._step_raw_multi),
        ):
            logits, packed, top_n = self.dispatch_slots(tokens_by_slot, asks)
            packed.block_until_ready()
        with tracelib.region(self.tracer, "copy_out", keep=True) as at:
            host = np.asarray(packed)
            rows, moved = samplib.logits_out(logits, plain)
            at["bytes"] = host.nbytes + moved
        replies, _ = samplib.row_replies(host, top_n, asks)
        return {slot: replies[slot] if slot in replies else rows[slot]
                for slot in tokens_by_slot}

    def _count_pass(self, rows: int, live: int) -> None:
        """One serving pass of one microbatch of `rows` rows, `live` of them
        doing a session's work (see the counters in __init__)."""
        pp = self.mesh.shape["pp"]
        self.passes += 1
        self.stage_ticks += pp * pp * rows
        self.stage_ticks_useful += live * pp

    def slot_length(self, slot: int) -> int:
        return int(self.caches.lengths[slot])

    def decode_step(self, tok, active, keys, done, eos: int,
                    top_n: int = 0, want_lp: bool = False):
        """Advance every active slot by one token; returns (tok', keys',
        done') — plus (lp [MB,B], top_ids, top_lps) when want_lp. tok
        [MB, B] int32, active [MB] bool, keys [MB, B, 2]."""
        self.caches, ntok, nkeys, ndone, lp, ti, tl = self._decode(
            self.params, self.caches, tok, active, keys, done, jnp.int32(eos),
            top_n, want_lp,
        )
        if want_lp:
            return ntok, nkeys, ndone, lp, ti, tl
        return ntok, nkeys, ndone

    # -- generation loop ----------------------------------------------------

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int,
        eos_token_id: Optional[int] = None,
        seed: int = 0,
        logprob_sink: Optional[List[List[float]]] = None,
        top_n: int = 0,
        top_sink: Optional[List] = None,
    ) -> List[List[int]]:
        """Generate for an arbitrary list of ragged prompts. Sequences are
        assigned to free (slot, lane) pairs in arrival order; a slot whose
        sequences all finished is refilled from the queue while the other
        slots keep decoding. Sequence i's sampling chain is seeded
        PRNGKey(seed + i) — identical to Engine.generate(prompt_i,
        seed=seed+i). Returns one token list per prompt (EOS included,
        like the reference loop client.py:268-272).

        `logprob_sink` / `top_sink` (+ top_n): per-sequence model-logprob
        and top-N-alternative lists aligned with the returned ids — same
        semantics as the solo/batched engines, device-computed."""
        nseq = len(prompts)
        want_lp = logprob_sink is not None or top_sink is not None
        if logprob_sink is not None:
            logprob_sink.clear()
            logprob_sink.extend([] for _ in range(nseq))
        if top_sink is not None:
            top_sink.clear()
            top_sink.extend([] for _ in range(nseq))
        if max_new_tokens <= 0 or nseq == 0:
            return [[] for _ in range(nseq)]
        for i, p in enumerate(prompts):
            if len(p) == 0:
                raise ValueError(f"prompt {i} is empty")
            if len(p) + max_new_tokens > self.max_len:
                raise BufferError(
                    f"prompt {i}: {len(p)} + {max_new_tokens} new tokens "
                    f"exceeds max_len {self.max_len}"
                )
        eos = -1 if eos_token_id is None else int(eos_token_id)

        # group sequences of equal prompt length into slot-sized batches
        # (lanes of one slot share a cache length; across slots anything goes)
        by_len: Dict[int, deque] = {}
        for i in sorted(range(nseq), key=lambda i: len(prompts[i])):
            by_len.setdefault(len(prompts[i]), deque()).append(i)
        queue = deque()
        for ln in sorted(by_len):
            q = by_len[ln]
            while q:
                queue.append([q.popleft() for _ in range(min(self.batch, len(q)))])

        results: List[List[int]] = [[] for _ in range(nseq)]
        mb, b = self.mb, self.batch
        # host-side state mirrors, one decode-step sync per token
        tok = np.zeros((mb, b), np.int32)
        active = np.zeros((mb,), bool)
        done = np.ones((mb, b), bool)
        keys = np.zeros((mb, b, 2), np.uint32)
        slot_seqs: List[Optional[List[Optional[int]]]] = [None] * mb
        steps_left = [0] * mb

        def fill(slot: int) -> None:
            if not queue:
                return
            group = queue.popleft()
            # short groups duplicate their first lane (marked done at birth)
            lanes: List[Optional[int]] = list(group) + [None] * (b - len(group))
            arr = np.stack(
                [np.asarray(prompts[i if i is not None else group[0]], np.int32)
                 for i in lanes]
            )
            lane_keys = jnp.stack(
                [jax.random.PRNGKey(seed + (i if i is not None else 0))
                 for i in lanes]
            )
            if want_lp:
                ftok, nkeys, fdone, flp, fti, ftl = self.prefill_slot(
                    slot, arr, lane_keys, eos, top_n=top_n, want_lp=True
                )
                flp, fti, ftl = np.asarray(flp), np.asarray(fti), np.asarray(ftl)
            else:
                ftok, nkeys, fdone = self.prefill_slot(slot, arr, lane_keys, eos)
            ftok, fdone = np.asarray(ftok), np.array(fdone)
            for lane, i in enumerate(lanes):
                if i is None:
                    fdone[lane] = True
                    continue
                results[i].append(int(ftok[lane]))
                if want_lp:
                    if logprob_sink is not None:
                        logprob_sink[i].append(float(flp[lane]))
                    if top_sink is not None:
                        top_sink[i].append(
                            (fti[lane].tolist(), ftl[lane].tolist())
                        )
            tok[slot] = ftok
            done[slot] = fdone
            keys[slot] = np.asarray(nkeys)
            slot_seqs[slot] = lanes
            steps_left[slot] = max_new_tokens - 1
            active[slot] = True

        while True:
            for m in range(mb):
                if not active[m]:
                    fill(m)
            # retire slots that are already finished (all lanes done at
            # prefill, or step budget 0)
            for m in range(mb):
                if active[m] and (done[m].all() or steps_left[m] <= 0):
                    active[m] = False
                    slot_seqs[m] = None
            if not active.any():
                if queue:
                    continue
                break
            if want_lp:
                ntok, nkeys, ndone, slp, sti, stl = self.decode_step(
                    jnp.asarray(tok), jnp.asarray(active), jnp.asarray(keys),
                    jnp.asarray(done), eos, top_n=top_n, want_lp=True,
                )
                slp, sti, stl = np.asarray(slp), np.asarray(sti), np.asarray(stl)
            else:
                ntok, nkeys, ndone = self.decode_step(
                    jnp.asarray(tok), jnp.asarray(active), jnp.asarray(keys),
                    jnp.asarray(done), eos,
                )
            ntok_np, ndone_np = np.array(ntok), np.array(ndone)
            keys = np.array(nkeys)
            for m in range(mb):
                if not active[m]:
                    continue
                lanes = slot_seqs[m]
                for lane in range(b):
                    i = lanes[lane]
                    if i is None or done[m, lane]:
                        continue
                    results[i].append(int(ntok_np[m, lane]))
                    if want_lp:
                        if logprob_sink is not None:
                            logprob_sink[i].append(float(slp[m, lane]))
                        if top_sink is not None:
                            top_sink[i].append(
                                (sti[m, lane].tolist(), stl[m, lane].tolist())
                            )
                steps_left[m] -= 1
                if ndone_np[m].all() or steps_left[m] <= 0:
                    active[m] = False
                    slot_seqs[m] = None
            tok, done = ntok_np, ndone_np
        return results

    def generate_array(self, prompts: jax.Array, max_new_tokens: int) -> jax.Array:
        """Uniform-length convenience wrapper: prompts [MB, B, S] int32 ->
        [MB, B, max_new_tokens] (no EOS; sampling per sampling_cfg with
        per-sequence seeds 0..MB*B-1 — greedy when temperature == 0)."""
        mbs, b, s = prompts.shape
        flat = np.asarray(prompts).reshape(mbs * b, s)
        out = self.generate([list(row) for row in flat], max_new_tokens)
        return jnp.asarray(np.asarray(out, np.int32).reshape(mbs, b, max_new_tokens))


class MeshSpecRunner:
    """Jitted speculative rounds for ONE sampling config over a
    PipelinedEngine's microbatch slots — the in-mesh sibling of
    core.spec_batch.LaneSpecRunner (same draft-scan/accept building
    blocks; the TARGET verify runs through the ppermute pipeline pass
    with full-chunk logits instead of a flat forward). The caller
    (runtime/mesh_executor) serializes rounds under its step lock."""

    def __init__(self, engine: PipelinedEngine, sampling=None):
        if engine.spec_dcfg is None:
            raise RuntimeError("engine.enable_spec() first")
        from inferd_tpu.core import spec_batch as sbl
        from inferd_tpu.core.cache import KVCache, lane_slice, lane_write

        self.engine = engine
        self.k = K = engine.spec_k
        self.sampling = sampling or SamplingConfig(temperature=0.0)
        sc = self.sampling
        cfg, dcfg, MB = engine.cfg, engine.spec_dcfg, engine.mb
        passfn_full = engine._passfn_full

        @partial(jax.jit, donate_argnames=("dcache",))
        def _draft_prefill(dp, dcache: KVCache, tokens, slot, start, n):
            lc = lane_slice(dcache, slot)
            _, nc, _ = qwen3.forward_cached(
                dp, dcfg, tokens, None, lc, start, real_end=start + n
            )
            return lane_write(dcache, slot, nc)

        def _verify(params, caches, last, d):
            """(K+1)-token verify chunk for every slot through ONE
            pipeline pass; returns (new cache parts, logits [MB, K+1, V])."""
            chunk = jnp.concatenate([last[:, None], d], axis=1)[:, None, :]
            nk, nv, nkl, nvl, logits = passfn_full(
                params, chunk, jnp.arange(MB), jnp.int32(K), caches,
                caches.lengths,
            )
            return nk, nv, nkl, nvl, logits[:, 0]

        TOPN = self.top_n = sbl.SPEC_TOP_N

        @partial(jax.jit, donate_argnames=("caches", "dcache"),
                 static_argnames=("want_lp",))
        def _round_greedy(params, dp, caches: PipelinedCaches, dcache,
                          last, catch, catch_mask, dlens, active,
                          want_lp: bool = False):
            dcache, dl0 = sbl.catch_up(dp, dcfg, dcache, catch, catch_mask, dlens)
            dcache, d, _ = sbl.draft_scan(
                dp, dcfg, dcache, last, dl0, active, K, sc
            )
            nk, nv, nkl, nvl, tl = _verify(params, caches, last, d)
            greedy = jnp.argmax(tl, axis=-1).astype(jnp.int32)
            toks, n_new = sbl.greedy_accept(d, greedy, active, K)
            new = PipelinedCaches(
                k=nk, v=nv, lengths=caches.lengths + n_new,
                k_loc=nkl, v_loc=nvl,
            )
            lp, ti, tls = sbl.chunk_logprob_trail(tl, greedy, K, TOPN, want_lp)
            return toks, n_new, new, dcache, lp, ti, tls

        @partial(jax.jit, donate_argnames=("caches", "dcache"))
        def _round_sampled(params, dp, caches: PipelinedCaches, dcache,
                           last, catch, catch_mask, dlens, active, keys):
            draft_keys, akeys, rskeys = sbl.split_round_keys(keys, K)
            dcache, dl0 = sbl.catch_up(dp, dcfg, dcache, catch, catch_mask, dlens)
            dcache, d, dprobs = sbl.draft_scan(
                dp, dcfg, dcache, last, dl0, active, K, sc, draft_keys
            )
            nk, nv, nkl, nvl, tl = _verify(params, caches, last, d)
            tprobs = samplib.warped_probs(tl, sc)
            toks, n_new = sbl.rejection_accept(
                d, dprobs, tprobs, active, akeys, rskeys, K
            )
            new = PipelinedCaches(
                k=nk, v=nv, lengths=caches.lengths + n_new,
                k_loc=nkl, v_loc=nvl,
            )
            return toks, n_new, new, dcache

        @jax.jit
        def _first_token(logits, key):
            row = logits[None]
            if sc.temperature == 0.0:
                return jnp.argmax(row, axis=-1)[0].astype(jnp.int32)
            return samplib.sample(
                row, key, sc.temperature, sc.top_k, sc.top_p, sc.min_p
            )[0].astype(jnp.int32)

        self._draft_prefill_fn = _draft_prefill
        self._round_greedy = _round_greedy
        self._round_sampled = _round_sampled
        self._first_token_fn = _first_token

    def draft_prefill(self, tokens: np.ndarray, slot: int, start: int, n: int):
        e = self.engine
        e.spec_dcache = self._draft_prefill_fn(
            e.spec_dparams, e.spec_dcache, jnp.asarray(tokens, jnp.int32),
            jnp.int32(slot), jnp.int32(start), jnp.int32(n),
        )

    def first_token(self, logits: np.ndarray, key) -> int:
        return int(self._first_token_fn(jnp.asarray(logits), key))

    def row_lp(self, logits: np.ndarray, tok: int):
        """(logprob, top_ids list, top_lps list) of `tok` under `logits`."""
        from inferd_tpu.core.spec_batch import row_logprob

        lp, ti, tls = row_logprob(jnp.asarray(logits), int(tok), self.top_n)
        return float(lp), np.asarray(ti).tolist(), np.asarray(tls).tolist()

    def run_round(self, last, catch, catch_mask, dlens, active, keys=None,
                  want_lp: bool = False):
        """One coalesced round over the engine's slots (all MB compute;
        only `active` advance — in-jit on the cache lengths). Returns
        (toks [MB, K+1] np, n_new [MB] np) — plus (lp, top_ids, top_lps)
        when want_lp (greedy only). Headroom contract: the caller
        (mesh executor) caps every LIVE session at max_len - (k+1); dead
        slots' frontier garbage writes are self-contained."""
        e = self.engine
        args = (
            e.params, e.spec_dparams, e.caches, e.spec_dcache,
            jnp.asarray(last, jnp.int32), jnp.asarray(catch, jnp.int32),
            jnp.asarray(catch_mask, bool), jnp.asarray(dlens, jnp.int32),
            jnp.asarray(active, bool),
        )
        lp = ti = tls = None
        if self.sampling.temperature == 0.0:
            toks, n_new, caches, dcache, lp, ti, tls = self._round_greedy(
                *args, want_lp=want_lp
            )
        else:
            if want_lp:
                raise ValueError(
                    "speculative logprobs are greedy-only (the sampled "
                    "rejection round has no per-token logprob trail)"
                )
            if keys is None:
                raise ValueError("sampled rounds need per-slot keys")
            toks, n_new, caches, dcache = self._round_sampled(
                *args, jnp.asarray(keys, jnp.uint32)
            )
        e.caches = caches
        e.spec_dcache = dcache
        if want_lp:
            return (
                np.asarray(toks), np.asarray(n_new),
                np.asarray(lp), np.asarray(ti), np.asarray(tls),
            )
        return np.asarray(toks), np.asarray(n_new)
