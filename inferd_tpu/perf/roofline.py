"""Analytic roofline cost model for a decode step.

One audited source of truth for "what does this chip allow": from any
`ModelConfig` + quant mode + KV dtype + context + batch, compute the HBM
bytes a decode step must move and the FLOPs it must execute, then — against
a chip-spec table — the floor ms/step and ceiling tok/s. This replaces the
ad-hoc `hbm_roofline_frac` arithmetic previously scattered through
`bench.py` (V5E_HBM_GBPS literals) with one model the gate, the report CLI,
and the bench all agree on.

Accounting contract (docs/PERF.md derives the formulas):

  * bs=1 decode is HBM-bound: every *resident* weight byte that the step's
    matmuls touch is read once per token. Quantized linears count their
    stored bytes (intN + scales), not their logical bf16 size.
  * The embedding table is counted as a full read ONLY when it doubles as
    the unembed matrix (tied, unquantized). A quantized tied model reads
    the int8/int4 `lm_head_q` shadow instead, and the bf16 table is only
    gathered (batch x H bytes — counted, negligible). This deliberately
    diverges from bench.py's historical leaf-sum, which billed the gather
    as a full table read under quantization; the gate treats that drift as
    a warning, not an error, when auditing old artifacts.
  * MoE layers count router + the `num_experts_per_tok` ACTIVE experts
    (the floor assumes the gather reads only what routing selected).
  * KV read is 2 x L x ctx x kv_dim x itemsize(kv_dtype) per sequence; the
    KV write is one slot per layer.

Nothing here touches a JAX backend: chip detection is the caller's problem
(`detect_chip()` initializes the backend; `CHIP_SPECS[...]` does not), so
`python -m inferd_tpu.perf report` runs on a CPU-only host untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp

from inferd_tpu.config import ModelConfig
from inferd_tpu.ops.quant import _group_size

# CLI-facing quant flags this model understands (must stay in sync with
# ops.quant.apply_quant_mode). w8a8 and int8-kernel store the same bytes as
# int8; they differ in how the MXU contracts them, which the `compute_ms`
# half of the roofline reflects (w8a8 uses the int8 peak).
QUANT_MODES = ("none", "int8", "w8a8", "int8-kernel", "int4")

_SCALE_BYTES = 4  # every quant scheme stores float32 scales
INT4_GROUP = 128  # ops.quant.quantize_int4 default group size


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Published peak numbers for one accelerator generation. The roofline
    is a *ceiling* model, so nominal spec-sheet values are the right
    constants here; `tools/chip_probe.py` measures what the attached chip
    actually delivers when the gap itself is in question."""

    key: str
    description: str
    hbm_gbps: float  # HBM bandwidth, GB/s
    peak_bf16_tflops: float  # dense MXU bf16 peak, TFLOP/s
    peak_int8_tops: float  # dense MXU int8 peak, TOP/s
    hbm_gib: float  # HBM capacity, GiB


CHIP_SPECS: Dict[str, ChipSpec] = {
    s.key: s
    for s in [
        ChipSpec("v5e", "TPU v5e (v5 lite)", 819.0, 197.0, 394.0, 16.0),
        ChipSpec("v5p", "TPU v5p", 2765.0, 459.0, 918.0, 95.0),
        ChipSpec("v4", "TPU v4", 1228.0, 275.0, 275.0, 32.0),
        ChipSpec("v6e", "TPU v6e (Trillium)", 1640.0, 918.0, 1836.0, 32.0),
        # Order-of-magnitude placeholder so CPU smoke runs of the report
        # have a denominator; never used for real claims.
        ChipSpec("cpu", "host CPU (nominal)", 20.0, 0.2, 0.4, 64.0),
    ]
}

# device_kind() substring -> chip key (first match wins). v5e reports
# "TPU v5 lite"; v5p reports "TPU v5"; check the more specific first.
_KIND_MAP = (
    ("v5 lite", "v5e"),
    ("v5e", "v5e"),
    ("v5p", "v5p"),
    ("v5", "v5p"),
    ("v6", "v6e"),
    ("trillium", "v6e"),
    ("v4", "v4"),
)


def detect_chip() -> ChipSpec:
    """ChipSpec for the ATTACHED backend (initializes it — never call at
    import time). A TPU whose kind is not in the table is an error: a
    roofline share computed against another generation's peaks is a wrong
    number with a right-looking name."""
    from inferd_tpu.utils import platform

    if not platform.is_tpu():
        return CHIP_SPECS["cpu"]
    kind = platform.device_kind()
    for needle, key in _KIND_MAP:
        if needle in kind.lower():
            return CHIP_SPECS[key]
    raise KeyError(
        f"TPU device_kind {kind!r} is not in perf.roofline's peaks table "
        f"(have {sorted(k for k in CHIP_SPECS if k != 'cpu')}); add its "
        "published peaks rather than borrowing another generation's"
    )


def get_chip(key: str) -> ChipSpec:
    try:
        return CHIP_SPECS[key.lower()]
    except KeyError:
        raise KeyError(f"unknown chip {key!r}; have {sorted(CHIP_SPECS)}")


# ---------------------------------------------------------------------------
# Per-step cost
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StepCost:
    """Bytes moved and FLOPs executed by ONE decode step (all sequences of
    the batch together). Byte fields are HBM reads unless named otherwise."""

    cfg_name: str
    quant: str
    kv_dtype: str
    ctx: int
    batch: int
    embed_gather_bytes: int
    attn_weight_bytes: int
    mlp_weight_bytes: int
    head_bytes: int
    norm_bytes: int
    kv_read_bytes: int
    kv_write_bytes: int
    matmul_flops: int
    attn_flops: int

    @property
    def weight_bytes(self) -> int:
        return (
            self.attn_weight_bytes + self.mlp_weight_bytes + self.head_bytes
            + self.norm_bytes
        )

    @property
    def read_bytes(self) -> int:
        return self.weight_bytes + self.embed_gather_bytes + self.kv_read_bytes

    @property
    def total_bytes(self) -> int:
        return self.read_bytes + self.kv_write_bytes

    @property
    def flops(self) -> int:
        return self.matmul_flops + self.attn_flops


def _linear_bytes(k: int, n: int, quant: str, dsize: int) -> int:
    """Stored bytes of one [K, N] linear under a quant mode (what a decode
    step reads). int8: 1 byte/weight + f32 per-output-channel scales.
    int4: nibble-packed when K is even (ops.quant.quantize_int4) + f32
    per-(group, output) scales."""
    if quant == "none":
        return k * n * dsize
    if quant in ("int8", "w8a8", "int8-kernel"):
        return k * n + _SCALE_BYTES * n
    if quant == "int4":
        body = (k // 2) * n if k % 2 == 0 else k * n
        groups = k // _group_size(k, INT4_GROUP)
        return body + _SCALE_BYTES * groups * n
    raise ValueError(f"unknown quant mode {quant!r}; have {QUANT_MODES}")


def _linear_flops(k: int, n: int, batch: int) -> int:
    return 2 * batch * k * n


def decode_step_cost(
    cfg: ModelConfig,
    quant: str = "none",
    kv_dtype: Optional[str] = None,
    ctx: int = 0,
    batch: int = 1,
) -> StepCost:
    """Cost of one decode step (S=1 per sequence) for `batch` sequences
    attending over `ctx` cached tokens each.

    `kv_dtype` overrides the config's KV storage dtype (the bench's
    --kv-dtype flag); None uses cfg.kv_dtype. `quant` is the CLI flag
    vocabulary of ops.quant.apply_quant_mode.
    """
    if quant not in QUANT_MODES:
        raise ValueError(f"unknown quant mode {quant!r}; have {QUANT_MODES}")
    h, d, L = cfg.hidden_size, cfg.head_dim, cfg.num_layers
    qd, kvd = cfg.q_dim, cfg.kv_dim
    dsize = jnp.dtype(cfg.dtype).itemsize
    if kv_dtype is None:
        kv_size = jnp.dtype(cfg.kv_jnp_dtype).itemsize
    else:
        kv_size = jnp.dtype(
            cfg.dtype if kv_dtype == "model" else kv_dtype
        ).itemsize

    # -- attention stack ----------------------------------------------------
    attn_b = sum(
        _linear_bytes(kk, nn, quant, dsize)
        for kk, nn in ((h, qd), (h, kvd), (h, kvd), (qd, h))
    )
    attn_f = sum(
        _linear_flops(kk, nn, batch)
        for kk, nn in ((h, qd), (h, kvd), (h, kvd), (qd, h))
    )
    if cfg.attn_bias:
        attn_b += (qd + 2 * kvd) * dsize
    if cfg.o_bias:
        attn_b += h * dsize
    if cfg.attn_sinks:
        attn_b += cfg.num_heads * dsize
    attn_b *= L
    attn_f *= L

    # -- MLP stack ----------------------------------------------------------
    if cfg.is_moe:
        e, mi, act = cfg.num_experts, cfg.moe_intermediate_size, cfg.num_experts_per_tok
        mlp_b = h * e * dsize  # router (never quantized — ops.quant)
        mlp_f = _linear_flops(h, e, batch)
        per_expert_b = sum(
            _linear_bytes(kk, nn, quant, dsize)
            for kk, nn in ((h, mi), (h, mi), (mi, h))
        )
        per_expert_f = sum(
            _linear_flops(kk, nn, batch) for kk, nn in ((h, mi), (h, mi), (mi, h))
        )
        if cfg.moe_bias:
            per_expert_b += (2 * mi + h) * dsize
        if cfg.router_bias:
            mlp_b += e * dsize
        mlp_b += act * per_expert_b
        mlp_f += act * per_expert_f
    else:
        i = cfg.intermediate_size
        mlp_b = sum(
            _linear_bytes(kk, nn, quant, dsize)
            for kk, nn in ((h, i), (h, i), (i, h))
        )
        mlp_f = sum(
            _linear_flops(kk, nn, batch) for kk, nn in ((h, i), (h, i), (i, h))
        )
    mlp_b *= L
    mlp_f *= L

    # -- norms (small, but they ARE per-step HBM reads) ---------------------
    per_layer_norms = 2 * h + (2 * h if cfg.norm_placement == "both" else 0)
    if cfg.qk_norm:
        per_layer_norms += 2 * d
    norm_b = (L * per_layer_norms + h) * dsize  # + final_norm

    # -- unembed head -------------------------------------------------------
    if cfg.tie_word_embeddings:
        if quant == "none":
            # the bf16 table IS the unembed matrix: full read per step
            head_b = h * cfg.vocab_size * dsize
        else:
            # quantized shadow head (ops.quant.quantize_params lm_head_q);
            # the bf16 table stays resident but is only gathered
            head_b = _linear_bytes(h, cfg.vocab_size, quant, dsize)
    else:
        head_b = _linear_bytes(h, cfg.vocab_size, quant, dsize)
    head_f = _linear_flops(h, cfg.vocab_size, batch)

    # -- KV cache + embedding gather ----------------------------------------
    kv_read = 2 * L * ctx * kvd * kv_size * batch
    kv_write = 2 * L * kvd * kv_size * batch
    embed_gather = batch * h * dsize

    # -- attention score/value dot FLOPs (2 matmuls of [1, d] x [d, ctx]) ---
    attn_dot_f = 4 * batch * L * ctx * cfg.num_heads * d

    return StepCost(
        cfg_name=cfg.name,
        quant=quant,
        kv_dtype=(kv_dtype or cfg.kv_dtype),
        ctx=ctx,
        batch=batch,
        embed_gather_bytes=embed_gather,
        attn_weight_bytes=attn_b,
        mlp_weight_bytes=mlp_b,
        head_bytes=head_b,
        norm_bytes=norm_b,
        kv_read_bytes=kv_read,
        kv_write_bytes=kv_write,
        matmul_flops=attn_f + mlp_f + head_f,
        attn_flops=attn_dot_f,
    )


# ---------------------------------------------------------------------------
# Round-19 decode-kernel bytes model: per-step HBM traffic of each Pallas
# kernel vs its XLA sibling, at explicit shapes. These are the
# DIMENSIONLESS kernel-vs-xla ratios the kernels bench leg grades and
# run.sh step 0b8 hard-gates: interpret-mode wall clock on CPU times the
# Pallas INTERPRETER, not the kernel, so the CPU-proxy artifact grades
# structural bytes (what the roofline is made of) and leaves wall-clock
# verdicts to `sweep_attn --kernels` on real hardware. Every model is
# written down here, not in the bench, so docs/PERF.md's re-derivations
# and the gate read the same arithmetic.
# ---------------------------------------------------------------------------


def _pow2_bucket(n: int) -> int:
    """Mirror core.cache.BlockPool.chain_clamp's power-of-2 bucketing."""
    b = 1
    while b < n:
        b <<= 1
    return b


def paged_attn_step_bytes(
    batch: int,
    ctx: int,
    kv_dim: int,  # Nkv * D
    kv_size: int,  # bytes per KV element (2 bf16, 1 fp8)
    block_size: int,
    table_blocks: int,  # MB: the window's table width
) -> Dict[str, int]:
    """Per-layer KV bytes of one paged decode-attention step.

    xla (gather_block_kv sibling): reads the clamped table width's blocks
    from the pool, WRITES the dense [B, T, Nkv, D] gathered copy, then the
    attention contraction reads that copy back — three passes over the
    post-clamp gather width (power-of-2 bucket of the longest chain,
    core.cache.chain_clamp).

    kernel (Pallas chain walk): each lane's live chain blocks stream
    through VMEM exactly once (+1 scratch-block fetch per lane where the
    table's trailing zeros collapse into one revisit — consecutive grid
    steps with an unchanged block index don't re-fetch)."""
    chain = -(-ctx // block_size)  # blocks a full lane actually uses
    t_gather = min(_pow2_bucket(chain), table_blocks) * block_size
    xla = 3 * 2 * batch * t_gather * kv_dim * kv_size
    kernel = 2 * batch * (chain + 1) * block_size * kv_dim * kv_size
    return {"kernel": kernel, "xla": xla}


def quant_matvec_bytes(k: int, n: int, scheme: str) -> Dict[str, int]:
    """Weight bytes of one [1, K] x [K, N] decode matvec under a quant
    scheme ("int8" | "int4").

    kernel (ops/qmatmul): the quantized bytes are the ONLY weight bytes
    that cross HBM — blocks convert in VMEM (plus the f32 scales).

    xla (dequant-in-dot sibling): counts the measured failure mode the
    kernel exists to close — r05's inversion (int8 decode at 0.69x bf16,
    BENCH_tpu_r05) showed XLA rematerializing the widened operand at GEMV
    shapes instead of fusing the convert, so the sibling pays the
    quantized read PLUS a bf16 copy written and read back."""
    dsize = 2  # bf16 widened operand
    if scheme == "int8":
        q_bytes = k * n + _SCALE_BYTES * n
    elif scheme == "int4":
        q_bytes = (k // 2) * n if k % 2 == 0 else k * n
        q_bytes += _SCALE_BYTES * (k // _group_size(k, INT4_GROUP)) * n
    else:
        raise ValueError(f"unknown quant kernel scheme {scheme!r}")
    return {"kernel": q_bytes, "xla": q_bytes + 2 * dsize * k * n}


def lora_delta_step_bytes(
    batch: int, d_in: int, rank: int, d_out: int, pool_dsize: int = 4,
) -> Dict[str, int]:
    """Adapter-pool bytes of one layer's LoRA lane delta at ONE projection.

    kernel (ops/lora.fused_lane_delta): slot ids index the stacked pools
    inside the BlockSpec index maps, so each lane's own [in, r]/[r, out]
    matrices are read once and nothing else is materialized.

    xla (gather_lanes + lane_delta sibling): the per-dispatch gather reads
    the same pool rows, writes the per-lane [B, in, r]/[B, r, out] copies,
    and lane_delta reads them back — three passes."""
    row = batch * (d_in * rank + rank * d_out) * pool_dsize
    return {"kernel": row, "xla": 3 * row}


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Roofline:
    """Floor/ceiling for one StepCost on one chip."""

    cost: StepCost
    chip: ChipSpec
    hbm_ms: float  # time to move the step's bytes at peak bandwidth
    compute_ms: float  # time to execute the step's FLOPs at peak
    floor_ms: float  # max of the two: no step can beat this
    ceiling_tok_s: float  # aggregate tok/s ceiling (batch / floor)
    bound: str  # "hbm" | "flops"


def roofline(cost: StepCost, chip: ChipSpec) -> Roofline:
    hbm_s = cost.total_bytes / (chip.hbm_gbps * 1e9)
    # w8a8 contracts int8 x int8 on the MXU; every other mode runs the
    # dot in bf16 (dequant rides the operand stream)
    peak = (
        chip.peak_int8_tops if cost.quant == "w8a8" else chip.peak_bf16_tflops
    ) * 1e12
    comp_s = cost.flops / peak
    floor_s = max(hbm_s, comp_s, 1e-12)
    return Roofline(
        cost=cost,
        chip=chip,
        hbm_ms=hbm_s * 1e3,
        compute_ms=comp_s * 1e3,
        floor_ms=floor_s * 1e3,
        ceiling_tok_s=cost.batch / floor_s,
        bound="hbm" if hbm_s >= comp_s else "flops",
    )


def roofline_frac(measured_tok_s: float, cost: StepCost, chip: ChipSpec) -> float:
    """Fraction of the ceiling a measured aggregate tok/s achieves — THE
    definition of `hbm_roofline_frac` from round 6 on."""
    return measured_tok_s / roofline(cost, chip).ceiling_tok_s


def format_report(
    cfg: ModelConfig,
    chip: ChipSpec,
    ctx: int = 0,
    batch: int = 1,
    kv_dtypes=("model", "float8_e4m3fn"),
) -> str:
    """Human-readable roofline table: quant modes x KV dtypes for one
    preset on one chip. Pure string — the CLI prints it, tests parse it."""
    lines = [
        f"roofline: {cfg.name}  chip={chip.key} ({chip.description}, "
        f"{chip.hbm_gbps:.0f} GB/s HBM, {chip.peak_bf16_tflops:.0f} TF bf16)  "
        f"ctx={ctx} batch={batch}",
        f"{'quant':<12} {'kv_dtype':<15} {'read MB/step':>12} "
        f"{'floor ms':>9} {'ceiling tok/s':>14} {'bound':>6}",
    ]
    for quant in QUANT_MODES:
        for kvd in kv_dtypes:
            if ctx == 0 and kvd != kv_dtypes[0]:
                continue  # KV dtype is irrelevant with an empty cache
            c = decode_step_cost(cfg, quant=quant, kv_dtype=kvd, ctx=ctx, batch=batch)
            r = roofline(c, chip)
            lines.append(
                f"{quant:<12} {c.kv_dtype:<15} {c.total_bytes / 1e6:>12.1f} "
                f"{r.floor_ms:>9.3f} {r.ceiling_tok_s:>14.1f} {r.bound:>6}"
            )
    return "\n".join(lines)
