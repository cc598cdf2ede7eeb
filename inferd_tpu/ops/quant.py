"""Weight-only quantization (int8 w8a16, group-wise int4 w4a16, dynamic
w8a8) for decode-bandwidth-bound serving.

Single-sequence decode reads every weight byte once per token, so tok/s is
capped by weights-bytes/HBM-bandwidth (scaling-book roofline). The reference
serves bf16 torch weights and has no quantization story
(/root/reference/models/qwen3/server/qwen3_server_module.py:212-217); halving
the bytes with int8 weights + per-output-channel float scales roughly doubles
the bs=1 decode ceiling on a v5e while keeping activations, KV cache, norms,
router, and embedding in bf16 (the quality-sensitive parts).

Scheme: symmetric per-output-channel. For a weight W [..., K, N] contracted
over K, scale[..., n] = max_k |W[..., k, n]| / 127 and q = round(W / scale).
Because the scale is per OUTPUT channel, `x @ W  ==  (x @ q) * scale` exactly
— so the dequant multiply rides AFTER the matmul on the [.., N] result and
the MXU sees the int8 tensor directly (no [K, N] bf16 rematerialization in
HBM, which would forfeit the bandwidth win).

`QuantWeight` is a pytree node: stacked-layer `lax.scan`, stage slicing
(models.qwen3.slice_layers), checkpointing, and tree.map-based sharding all
work unchanged on the (q, scale) leaves.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax
import jax.numpy as jnp

from inferd_tpu.utils.platform import is_tpu

Params = Any


@dataclasses.dataclass
class _QWeightBase:
    """Shared (q, scale) pytree/duck-typing contract for every quantized
    weight format: two array leaves, and `shape`/`ndim` mirroring the
    ORIGINAL weight so model code can stay format-agnostic."""

    q: jax.Array
    scale: jax.Array

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):  # duck-type the original weight's shape
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantWeight(_QWeightBase):
    """int8 weights + per-output-channel scales for one linear layer.

    q:     int8 [..., K, N]  (same leading/batch dims as the original)
    scale: float32 [..., N]  (contraction axis reduced away)
    """

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale[..., None, :]).astype(dtype)


def quantize(w: jax.Array) -> QuantWeight:
    """Symmetric per-output-channel int8 over the second-to-last axis
    (the contraction axis of every linear in models/)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2)  # [..., N]
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127).astype(jnp.int8)
    return QuantWeight(q=q, scale=scale)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Int4Weight(_QWeightBase):
    """GROUP-WISE int4 weights (w4a16) for one linear layer: quarter the
    HBM bytes of bf16 (the bs=1 decode ceiling doubles again vs int8).

    q:     int8 [..., K/2, N] with TWO 4-bit two's-complement values
           packed per byte along the CONTRACTION axis (packed=True; odd-K
           tiny test configs fall back to one value per int8 byte,
           packed=False). The jnp.int4 dtype is deliberately avoided: on
           the round-5 hardware window, merely STAGING an S4[28,3072,1024]
           weight to the TPU crashed jit with a RecursionError, so the
           battery's int4 leg never produced an on-chip number and fell
           back to CPU (bench_artifacts/BENCH_tpu_r05.jsonl decode_int4,
           device:"cpu", note field) — int8 shift/mask unpacking is
           portable VPU code with no exotic-dtype staging path.
    scale: float32 [..., G, N] — G groups along the CONTRACTION axis
           (group size K/G, default 128; int4's 15 levels need per-group
           ranging to hold accuracy, per-output-channel like int8 would
           clip outliers badly).

    Because scales vary ALONG K, the dequant cannot ride after the whole
    dot the way the int8 per-output-channel scheme does. Two contraction
    schemes exist (see _int4_mode): "grouped" contracts per group on the
    narrow tensor and applies each group's scale to its partial sum with
    no full-rank float intermediate; "dequant" widens group-wise into one
    [K, N] operand and runs a single MXU dot (the widen fuses into the
    dot's operand stream, same contract as int8 "dequant" mode). Both are
    exact; which is faster is a hardware question, so the default is
    per-backend and measured, not assumed."""

    packed: bool = True

    def tree_flatten(self):
        return (self.q, self.scale), self.packed

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, packed=aux)

    @property
    def shape(self):  # duck-type the ORIGINAL [..., K, N] weight shape
        s = self.q.shape
        if not self.packed:
            return s
        return s[:-2] + (s[-2] * 2,) + s[-1:]

    def unpacked(self) -> jax.Array:
        """int8 [..., K, N] in [-7, 7]: arithmetic-shift nibble unpack
        (sign-extending), interleaved back to original K order."""
        if not self.packed:
            return self.q
        lo = jnp.left_shift(self.q, 4) >> 4  # low nibble, sign-extended
        hi = self.q >> 4  # high nibble, arithmetic shift sign-extends
        pair = jnp.stack([lo, hi], axis=-2)  # [..., K/2, 2, N]
        s = self.q.shape
        return pair.reshape(*s[:-2], s[-2] * 2, s[-1])

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        qi = self.unpacked()
        k, n = qi.shape[-2], qi.shape[-1]
        g = self.scale.shape[-2]
        qf = qi.astype(jnp.float32).reshape(*qi.shape[:-2], g, k // g, n)
        return (qf * self.scale[..., :, None, :]).reshape(qi.shape).astype(dtype)


def _group_size(k: int, group: int) -> int:
    """Largest divisor of K that is <= the requested group size (tiny test
    configs have K < 128; oddball K must still split exactly)."""
    g = min(group, k)
    while k % g:
        g -= 1
    return g


def quantize_int4(w: jax.Array, group: int = 128) -> Int4Weight:
    """Symmetric group-wise int4 over the contraction axis (-2), stored
    nibble-packed in int8 (two K-adjacent values per byte) when K is even."""
    k, n = w.shape[-2], w.shape[-1]
    gs = _group_size(k, group)
    wf = w.astype(jnp.float32).reshape(*w.shape[:-2], k // gs, gs, n)
    amax = jnp.max(jnp.abs(wf), axis=-2)  # [..., G, N]
    scale = jnp.where(amax == 0.0, 1.0, amax / 7.0)
    q = jnp.clip(jnp.round(wf / scale[..., :, None, :]), -7, 7)
    qi = q.reshape(w.shape).astype(jnp.int8)
    if k % 2:
        return Int4Weight(q=qi, scale=scale, packed=False)
    lo = qi[..., 0::2, :] & jnp.int8(0x0F)
    hi = jnp.left_shift(qi[..., 1::2, :], 4)
    return Int4Weight(q=(lo | hi).astype(jnp.int8), scale=scale, packed=True)


WeightLike = Union[jax.Array, QuantWeight, Int4Weight]

# How qdot/qeinsum contract against an int8 weight:
#   "dequant" — convert the int8 operand to the activation dtype inline and
#               run a bf16 MXU dot. Numerically the safest (w8a16); whether
#               the bandwidth win survives depends on XLA fusing the convert
#               into the dot's operand stream instead of rematerializing a
#               bf16 copy in HBM (measured on hardware via bench --quant).
#   "int8"    — dynamic symmetric per-row activation quantization, then a
#               native int8 x int8 -> int32 MXU dot (guaranteed: the int8
#               bytes are what crosses HBM, and v5e int8 matmul throughput
#               is 2x bf16). Output = xq @ wq * x_scale * w_scale.
#   "kernel"  — Pallas w8a16 matmul (ops/qmatmul.py): int8 blocks stream
#               through VMEM and dequantize in-register, making the
#               half-bandwidth read structural rather than dependent on
#               XLA fusing the convert (2D weights only; others fall back
#               to "dequant").
QDOT_MODE = "dequant"

# How Int4Weight contracts (see the class docstring for the two schemes):
#   "auto"    — "dequant" on TPU, "grouped" elsewhere. The grouped scheme
#               lowers to a G-batched stack of [1, K/G] x [K/G, N] matvecs
#               per matmul — a shape XLA:TPU tiles poorly onto the MXU —
#               while a single dot over the group-wise-widened operand is
#               the standard MXU mapping with the widen fused into its
#               operand stream. No on-chip int4 number exists yet (the
#               round-5 window's int4 leg crashed staging jnp.int4 weights
#               and fell back to CPU — BENCH_tpu_r05.jsonl decode_int4),
#               so the TPU default is the conservative scheme; the next
#               window's battery re-measures both via this flag.
#   "grouped" / "dequant" — force one scheme (tests, re-measurement).
INT4_MODE = "auto"

# Round-19 decode-GEMV kernel dispatch (ops/qmatmul.py): when the autotune
# registry's quant_decode entry carries kernel_*/xla_* rate pairs showing
# the Pallas kernels winning on this chip, qdot routes decode-shaped 2-D
# contractions through them — w8a16_matmul under QDOT_MODE="dequant" and
# w4a16_matvec for Int4Weight (mirroring whichever scheme _int4_mode
# picked). Cold registry -> the XLA paths, byte-identical. Tests force
# either side deterministically via this override.
FORCE_QUANT_KERNEL: Optional[bool] = None


def _quant_kernel_enabled() -> bool:
    if FORCE_QUANT_KERNEL is not None:
        return FORCE_QUANT_KERNEL
    from inferd_tpu.perf import autotune

    return autotune.quant_kernel_winner() == "kernel"


def _int4_mode() -> str:
    if INT4_MODE != "auto":
        return INT4_MODE
    # `auto` consults the autotune registry first (perf/autotune.py): a
    # hardware window that measured both schemes on this chip decides;
    # cold registry -> the frozen per-backend default, bit-for-bit.
    from inferd_tpu.perf import autotune

    measured = autotune.int4_winner()
    if measured is not None:
        return measured
    return "dequant" if is_tpu() else "grouped"


def _dynamic_quant_rows(x: jax.Array):
    """Per-row (last-axis) symmetric int8 activation quantization."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return xq.astype(jnp.int8), scale


def qdot(x: jax.Array, w: WeightLike) -> jax.Array:
    """x [..., K] @ w [K, N] where w may be quantized (see QDOT_MODE)."""
    if isinstance(w, Int4Weight):
        mode = _int4_mode()
        if w.ndim == 2 and _quant_kernel_enabled():
            from inferd_tpu.ops.qmatmul import MAX_KERNEL_ROWS, w4a16_matvec

            lead = x.shape[:-1]
            rows = 1
            for d in lead:
                rows *= d
            if rows <= MAX_KERNEL_ROWS:  # decode shapes; prefill falls through
                y2 = w4a16_matvec(
                    x.reshape(-1, x.shape[-1]), w, scheme=mode,
                    interpret=not is_tpu(),
                )
                return y2.reshape(lead + (w.shape[-1],))
        if w.ndim != 2 or mode == "dequant":
            return x @ w.dequantize(x.dtype)
        # grouped contraction: y = sum_g (x_g @ q_g) * s_g — the scales
        # vary along K, so each group's scale applies to its own partial
        # sum (exact; see Int4Weight)
        k, n = w.shape
        g = w.scale.shape[-2]
        xg = x.reshape(*x.shape[:-1], g, k // g)
        qg = w.unpacked().reshape(g, k // g, n).astype(x.dtype)
        y = jnp.einsum("...gk,gkn->...gn", xg, qg)
        return (
            (y.astype(jnp.float32) * w.scale).sum(axis=-2).astype(x.dtype)
        )
    if not isinstance(w, QuantWeight):
        return x @ w
    if w.q.ndim == 2 and (
        QDOT_MODE == "kernel"
        or (QDOT_MODE == "dequant" and _quant_kernel_enabled())
    ):
        from inferd_tpu.ops.qmatmul import MAX_KERNEL_ROWS, w8a16_matmul

        lead = x.shape[:-1]
        rows = 1
        for d in lead:
            rows *= d
        if rows <= MAX_KERNEL_ROWS:  # decode shapes; prefill falls through
            y2 = w8a16_matmul(
                x.reshape(-1, x.shape[-1]), w.q, w.scale,
                interpret=not is_tpu(),
            )
            return y2.reshape(lead + (w.q.shape[-1],))
    if QDOT_MODE == "int8":
        xq, xs = _dynamic_quant_rows(x)
        y = jax.lax.dot_general(
            xq, w.q, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        return (y * xs * w.scale).astype(x.dtype)
    y = x @ w.q.astype(x.dtype)
    return (y.astype(jnp.float32) * w.scale).astype(x.dtype)


def _int4_grouped_einsum(spec: str, x: jax.Array, w: "Int4Weight"):
    """Grouped contraction for an Int4Weight under an arbitrary
    single-contraction einsum: split the contraction axis into (G, K/G) on
    BOTH operands, contract per group on the NARROW tensor, then apply
    each group's scale to its partial sum and reduce over groups in one
    final einsum — the exact int4 sibling of the dense qdot path, for the
    MoE expert einsums ("th,ehi->tei", "tei,eih->teh"). The int4 bytes are
    what crosses HBM; no full-rank float intermediate is materialized
    (VERDICT r04 weak #3 / ADVICE quant.py:214). Returns None when the
    spec shape doesn't fit (caller falls back to inline dequant)."""
    try:
        ins, out = spec.split("->")
        xs_, ws_ = ins.split(",")
    except ValueError:
        return None
    shared = [ch for ch in ws_ if ch in xs_ and ch not in out]
    if len(shared) != 1:
        return None
    c = shared[0]
    # quantize_int4 groups along the weight's -2 axis; x contracts on it
    if ws_.index(c) != len(ws_) - 2 or xs_.index(c) != len(xs_) - 1:
        return None
    # every OTHER weight axis must survive into the output: an axis summed
    # out before the scale multiply would apply sum-of-scales to a
    # sum-of-partials — silently wrong; the dequant fallback handles it
    if any(ch not in out for ch in ws_ if ch != c):
        return None
    g_letter = next(ch for ch in "gzyxwvu" if ch not in spec)
    qi = w.unpacked()
    k = qi.shape[-2]
    G = w.scale.shape[-2]
    gs = k // G
    xg = x.reshape(x.shape[:-1] + (G, gs))
    qg = qi.reshape(qi.shape[:-2] + (G, gs, qi.shape[-1])).astype(x.dtype)
    xs2 = xs_.replace(c, g_letter + c)
    ws2 = ws_.replace(c, g_letter + c)
    y = jnp.einsum(f"{xs2},{ws2}->{g_letter}{out}", xg, qg)
    # scale [..., G, N] carries the weight's non-contraction letters with
    # the contraction groups in place of c: scale-and-sum-over-groups in
    # one einsum (pure broadcast + reduction, no hidden contraction)
    return jnp.einsum(
        f"{g_letter}{out},{ws_.replace(c, g_letter)}->{out}",
        y.astype(jnp.float32), w.scale,
    ).astype(x.dtype)


def qeinsum(spec: str, x: jax.Array, w: WeightLike) -> jax.Array:
    """einsum over a possibly-quantized weight whose scale is per-output
    (valid iff every non-contracted weight axis survives in the output,
    which holds for the MoE expert einsums in models/qwen3.py: the scale
    axes trail the einsum output, e.g. [t,e,i] * scale[e,i])."""
    if isinstance(w, Int4Weight):
        if _int4_mode() == "grouped":
            y = _int4_grouped_einsum(spec, x, w)
            if y is not None:
                return y
        # dequant mode or unrecognized spec shape: one einsum over the
        # group-wise-widened operand (the widen fuses into the einsum's
        # operand stream; on TPU this is the MXU-mapped path)
        return jnp.einsum(spec, x, w.dequantize(x.dtype))
    if not isinstance(w, QuantWeight):
        return jnp.einsum(spec, x, w)
    if QDOT_MODE == "int8":
        xq, xs = _dynamic_quant_rows(x)
        y = jnp.einsum(spec, xq, w.q, preferred_element_type=jnp.int32)
        # x's batch axes lead the output in the model's einsums; pad the
        # per-row scale with trailing singleton dims to broadcast over the
        # weight-derived output axes
        xs_lead = xs[..., 0]
        xs_b = xs_lead.reshape(xs_lead.shape + (1,) * (y.ndim - xs_lead.ndim))
        return (y.astype(jnp.float32) * xs_b * w.scale).astype(x.dtype)
    y = jnp.einsum(spec, x, w.q.astype(x.dtype))
    return (y.astype(jnp.float32) * w.scale).astype(x.dtype)


# Leaves to quantize in a layers pytree (stacked [L, ...] — the per-layer
# contraction axis is still axis -2) and in the top-level params dict.
# Deliberately NOT listed: "router" — routing precision is quality-critical
# and the matrix is tiny.
_LAYER_LINEARS = (
    "q_proj", "k_proj", "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj",
    "in_proj", "out_proj",  # a Mamba-2 layer's two projections (`state_layers`)
    "attn_gate_proj",  # the attention output's gate (cfg.attn_gate)
    "shared_gate_proj", "shared_up_proj", "shared_down_proj",  # the shared expert beside routed ones
    "latent_in_proj", "latent_out_proj",  # into and out of the experts' latent (cfg.moe_latent_size)
)


def quantize_params(
    params: Params, tie_word_embeddings: bool = False, needs_head: bool = True,
    quantizer=quantize,
) -> Params:
    """Quantize every linear projection of a full-model / stage param tree.

    Kept in bf16: embedding table (the gather source), norms, biases,
    router. Untied lm_head [H, V] is quantized in place. For tied models
    the unembed matmul — the single largest weight read per decode step
    (H x V, 311 MB bf16 for Qwen3-0.6B) — gets a quantized SHADOW copy
    under "lm_head_q" (int8 of embed.T, +V/2 extra bytes vs the halved
    read) which models.qwen3.unembed prefers when present; the bf16 table
    still serves the embedding gather. Pass needs_head=False for pipeline
    stages that hold embed only for the token gather (non-last stages) so
    they don't allocate a dead shadow head.
    """
    out = dict(params)
    qtypes = (QuantWeight, Int4Weight)
    for group in ("layers", "state_layers", "ffn_layers"):
        if group not in out:
            continue
        layers = dict(out[group])
        for name in _LAYER_LINEARS:
            if name in layers and not isinstance(layers[name], qtypes):
                layers[name] = quantizer(layers[name])
        out[group] = layers
    if "lm_head" in out and not isinstance(out["lm_head"], qtypes):
        out["lm_head"] = quantizer(out["lm_head"])
    elif (
        needs_head
        and tie_word_embeddings
        and "embed" in out
        and "lm_head_q" not in out
    ):
        out["lm_head_q"] = quantizer(out["embed"].T)
    return out


# quant flags already warned-about this process (one line per flag, not
# one per model load)
_quant_warned: set = set()


def _warn_if_slower_than_bf16(flag: str) -> None:
    """Loud (stderr, once per flag per process) when the autotune registry
    holds a MEASURED decode rate for this quant flag that is below the
    same sweep's bf16 baseline on this chip — the r05 inversion ("int8
    0.69x bf16") must never be picked silently again. The flag is still
    honored (it is an explicit operator choice and the inversion is
    window-weather-sensitive); the committed rates in
    bench_artifacts/autotune.json are the record of why it stands.

    RETIRED when the same entry's round-19 kernel grading shows the Pallas
    decode-GEMV kernel for this flag's scheme winning its XLA sibling AND
    beating the bf16 baseline: dispatch then routes decode through the
    kernel (_quant_kernel_enabled), so the flag-sweep inversion no longer
    describes the serving path. Cold hosts (no kernel rates) keep the
    warning."""
    import sys

    if flag in _quant_warned:
        return
    try:
        from inferd_tpu.perf import autotune

        rates = autotune.quant_rates()
    except Exception:
        return  # cold/absent registry: nothing measured, nothing to say
    if not rates:
        return
    bf16, q = rates.get("bf16"), rates.get(flag)
    scheme = {"int8": "int8", "int8-kernel": "int8", "int4": "int4"}.get(flag)
    if scheme is not None and bf16:
        kern = rates.get(f"kernel_{scheme}")
        if (
            kern
            and kern >= bf16
            and autotune.quant_kernel_winner() == "kernel"
        ):
            return  # the fused kernel carries this flag's decode path now
    if bf16 and q and q < bf16:
        _quant_warned.add(flag)
        print(
            f"quant: measured decode rate for {flag!r} ({q:.1f}) is BELOW "
            f"the bf16 baseline ({bf16:.1f}) on this chip "
            "(bench_artifacts/autotune.json, sweep_attn --quant) — "
            "serving it anyway as requested",
            file=sys.stderr,
        )


def apply_quant_mode(
    flag: str,
    params: Params,
    tie_word_embeddings: bool = False,
    needs_head: bool = True,
) -> Params:
    """Single entry point for the CLI-facing quant flags ("none" | "int8" |
    "w8a8" | "int8-kernel" | "int4"): sets QDOT_MODE and quantizes the
    tree. Used by
    the node runtime, bench, and the generate CLI so the flag->mode mapping
    cannot diverge between surfaces. When the autotune registry carries a
    measured bf16-vs-quant decode rate for this chip showing the flag
    LOSING to bf16, a one-line stderr warning says so (never silent)."""
    global QDOT_MODE
    if flag == "none":
        return params
    _warn_if_slower_than_bf16(flag)
    if flag == "int4":
        # group-wise w4a16: QDOT_MODE is irrelevant (Int4Weight carries
        # its own contraction scheme), but reset it so a process that
        # switched modes earlier doesn't leak "int8"/"kernel" behavior
        # onto any residual QuantWeight leaves
        QDOT_MODE = "dequant"
        return quantize_params(
            params, tie_word_embeddings=tie_word_embeddings,
            needs_head=needs_head, quantizer=quantize_int4,
        )
    QDOT_MODE = {"w8a8": "int8", "int8-kernel": "kernel"}.get(flag, "dequant")
    return quantize_params(
        params, tie_word_embeddings=tie_word_embeddings, needs_head=needs_head
    )


def quantized_bytes(params: Params) -> int:
    """Total parameter bytes AS STORED (int8/int4 + scales + residual
    bf16). Even-K Int4Weight nibble-packs two values per int8 byte, so
    size*itemsize counts it at half; the odd-K fallback genuinely stores
    one value per byte (tiny test configs only) and is counted as such."""
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params)
    )
