"""Pallas TPU w8a16 matmul: int8 weight tiles stream through VMEM and
dequantize in-register.

The quantization module's dequant-in-dot path (ops/quant.py QDOT_MODE=
"dequant") relies on XLA fusing `convert(int8->bf16) * scale` into the
dot's operand stream; if XLA materializes the converted weights instead,
the HBM read doubles back to bf16 size and the w8a16 bandwidth win
evaporates. This kernel makes the win structural: pallas_call's pipeline
fetches int8 blocks (half the bytes of bf16 — the only weight bytes that
cross HBM), converts them in VMEM, and feeds the MXU, with the per-output-
channel scale applied to the f32 accumulator.

Decode shapes are the target: x [M, K] with tiny M (1..64 rows = batch
lanes), W [K, N] with K = hidden (fits VMEM whole), N up to vocab-size
(gridded). The reference has no analogue (bf16 torch matmuls,
qwen3_server_module.py); this is the TPU-native hot-op layer the north
star asks for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _w8a16_kernel(x_ref, q_ref, s_ref, o_ref, *, out_dtype):
    # x_ref [M_pad, K] activation (bf16/f32), whole — M is tiny at decode
    # q_ref [K, bn] int8 weight block (the streamed operand)
    # s_ref [1, bn] f32 per-output-channel scales
    # o_ref [M_pad, bn]
    x = x_ref[...]
    w = q_ref[...].astype(x.dtype)  # int8 -> activation dtype, in VMEM
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] = (acc * s_ref[0]).astype(out_dtype)


# The kernel targets DECODE shapes: a handful of activation rows against a
# huge weight. Past this many rows (long prefill) the whole-x VMEM block
# would not fit and the dequant-in-dot path wins anyway (compute-bound).
MAX_KERNEL_ROWS = 64


def w8a16_matmul(
    x: jax.Array,  # [M, K] bf16/f32, M <= MAX_KERNEL_ROWS
    q: jax.Array,  # [K, N] int8
    scale: jax.Array,  # [N] f32
    *,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x @ dequantize(q, scale) with int8 as the only weight bytes read.

    Returns [M, N] in x.dtype. K must fit VMEM as an [K, block_n] int8
    block (K=1024..8192 with block_n=512 is 0.5..4 MB — fine). The weight
    and scale are NOT padded host-side (a jnp.pad of a vocab-size lm_head
    would copy ~150 MB through HBM per step); the N tail rides Pallas'
    boundary-block semantics — out-of-range lanes read garbage and their
    output columns are sliced off."""
    m, k = x.shape
    kq, n = q.shape
    assert k == kq, (x.shape, q.shape)
    assert m <= MAX_KERNEL_ROWS, (m, "use the dequant path for prefill")
    m_pad = _round_up(max(m, 8), 8)
    bn = min(block_n, _round_up(n, 128))

    xp = jnp.pad(x, ((0, m_pad - m), (0, 0)))  # tiny (decode rows)
    sp = scale.astype(jnp.float32)[None, :]  # [1, N]

    out = pl.pallas_call(
        functools.partial(_w8a16_kernel, out_dtype=x.dtype),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m_pad, k), lambda j: (0, 0)),
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m_pad, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        interpret=interpret,
    )(xp, q, sp)
    return out[:m, :n]


def _w4a16_kernel(
    x_ref,  # [M_pad, K] activation, whole — M is tiny at decode
    q_ref,  # [Kp, bn] int8: nibble-PACKED int4 weight block (Kp = K/2),
    #         or plain [-7, 7] bytes when packed=False (odd-K tiny configs)
    s_ref,  # [G, bn] f32 group scales (groups along K)
    o_ref,  # [M_pad, bn]
    *,
    out_dtype,
    groups: int,
    packed: bool,
    scheme: str,  # "dequant" | "grouped" — mirrors ops/quant._int4_mode
):
    """Dequant-fused int4 decode GEMV: the packed nibbles are the ONLY
    weight bytes that cross HBM (quarter of bf16); unpack (arithmetic-
    shift sign extension, the values of the Int4Weight.unpacked recipe) and
    the group-scale application both happen in VMEM. Both Int4Weight
    contraction schemes are implemented so the kernel's sibling is
    whatever _int4_mode picked — "dequant" widens group-wise and runs ONE
    dot; "grouped" contracts per group on the narrow tensor and applies
    each group's scale to its own partial sum (static unroll: G is
    K/group_size, a handful)."""
    x = x_ref[...]
    q = q_ref[...]
    if packed:
        # widen BEFORE shifting: Mosaic does not legalize shifts on int8
        # vectors for the v5e ("failed to legalize operation 'arith.shli'"),
        # it does on int32. Same values as the int8 recipe: shifting the
        # sign-extended byte up 28 and back down sign-extends the low nibble.
        q32 = q.astype(jnp.int32)
        lo = (q32 << 28) >> 28  # low nibble, sign-extended
        hi = q32 >> 4  # arithmetic shift sign-extends
        w = jnp.stack([lo, hi], axis=-2).reshape(2 * q.shape[0], q.shape[1])
    else:
        w = q
    k, bn = w.shape
    gs = k // groups
    if scheme == "dequant":
        wf = w.astype(jnp.float32).reshape(groups, gs, bn) * s_ref[...][:, None, :]
        acc = jax.lax.dot_general(
            x, wf.reshape(k, bn).astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
        acc = jnp.zeros((x.shape[0], bn), jnp.float32)
        for g in range(groups):
            yg = jax.lax.dot_general(
                x[:, g * gs:(g + 1) * gs],
                w[g * gs:(g + 1) * gs].astype(x.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc + yg * s_ref[g]
    o_ref[...] = acc.astype(out_dtype)


# The unpacked [K, block_n] block lives in VMEM as int32, then f32, then the
# activation dtype: ~11 bytes an element, 30.7 MB at the Qwen3-8B down
# projection (K = 12288) — over Mosaic's default 16 MB scoped limit, far
# under the v5e's 128 MiB of VMEM. (Splitting K across the grid would lift
# the need; until the kernel has a chip number that is not worth the code.)
_W4_VMEM_LIMIT = 64 * 1024 * 1024


def w4a16_matvec(
    x: jax.Array,  # [M, K] bf16/f32, M <= MAX_KERNEL_ROWS
    w,  # ops.quant.Int4Weight with 2-D q (one linear's weight)
    *,
    scheme: str = "dequant",  # which XLA sibling to mirror (_int4_mode)
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x @ w for a group-wise Int4Weight at decode GEMV shapes, nibble
    bytes streamed through VMEM with dequant fused into the contraction.

    Returns [M, N] in x.dtype. Same boundary-block contract as
    w8a16_matmul: weight/scales are NOT padded host-side; the N tail's
    out-of-range lanes read garbage that the final slice drops."""
    m, k = x.shape
    kk, n = w.shape  # ORIGINAL [K, N] (Int4Weight duck-types it)
    assert k == kk, (x.shape, w.shape)
    assert m <= MAX_KERNEL_ROWS, (m, "use the dequant path for prefill")
    groups = w.scale.shape[-2]
    m_pad = _round_up(max(m, 8), 8)
    bn = min(block_n, _round_up(n, 128))
    kp = w.q.shape[-2]  # K/2 packed rows (or K when packed=False)

    xp = jnp.pad(x, ((0, m_pad - m), (0, 0)))

    out = pl.pallas_call(
        functools.partial(
            _w4a16_kernel, out_dtype=x.dtype, groups=groups,
            packed=w.packed, scheme=scheme,
        ),
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((m_pad, k), lambda j: (0, 0)),
            pl.BlockSpec((kp, bn), lambda j: (0, j)),
            pl.BlockSpec((groups, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m_pad, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_W4_VMEM_LIMIT
        ),
        interpret=interpret,
    )(xp, w.q, w.scale.astype(jnp.float32))
    return out[:m, :n]
