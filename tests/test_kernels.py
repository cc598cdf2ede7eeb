"""Round-19 Pallas decode kernels: interpret-mode parity vs the XLA
siblings they replace, and token-exact end-to-end streams with the
kernels forced on.

Three kernels, one correctness bar each:
  * paged decode-attention (`ops.attention.paged_decode_gqa`) vs
    gather_block_kv + decode_gqa — same online-softmax math, no dense
    gather; scratch block 0 masked, sinks/softcap/window folded.
  * dequant-fused int4 GEMV (`ops.qmatmul.w4a16_matvec`) vs the qdot
    dequant / grouped XLA paths — the "dequant" scheme is BIT-exact by
    construction (identical op sequence), "grouped" matches the XLA
    grouped contraction to accumulation-order rounding.
  * fused LoRA lane-delta (`ops.lora.fused_lane_delta`) vs
    gather_lanes + lane_delta — bit-exact (same two f32 contractions,
    the gather just never materializes).

conftest pins INFERD_AUTOTUNE to an absent path, so with the FORCE
hooks left at None every dispatch below is registry-cold: the kernels
stay OFF and serving is byte-identical to the pre-kernel tree — that
cold-fallback identity is asserted here too.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from inferd_tpu.config import PRESETS
from inferd_tpu.models import qwen3
from inferd_tpu.ops import attention as att
from inferd_tpu.ops import lora as lora_ops
from inferd_tpu.ops import quant

TINY = PRESETS["tiny"]


@pytest.fixture(scope="module")
def tiny_params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


@pytest.fixture
def paged_forced():
    old = att.FORCE_PAGED_KERNEL
    att.FORCE_PAGED_KERNEL = True
    yield
    att.FORCE_PAGED_KERNEL = old


@pytest.fixture
def all_kernels_forced():
    olds = (att.FORCE_PAGED_KERNEL, quant.FORCE_QUANT_KERNEL,
            lora_ops.FORCE_LORA_KERNEL)
    att.FORCE_PAGED_KERNEL = True
    quant.FORCE_QUANT_KERNEL = True
    lora_ops.FORCE_LORA_KERNEL = True
    yield
    (att.FORCE_PAGED_KERNEL, quant.FORCE_QUANT_KERNEL,
     lora_ops.FORCE_LORA_KERNEL) = olds


# ---------------------------------------------------------------------------
# paged decode-attention kernel
# ---------------------------------------------------------------------------


def _paged_case(rng, b=2, nkv=2, g=2, d=16, bs=8, mb=4, pool_dtype=None):
    """Shuffled-chain paged pools + the equivalent dense view."""
    t = mb * bs
    nb = 1 + b * mb  # block 0 = scratch
    pool_k = rng.randn(nb, bs, nkv, d).astype(np.float32)
    pool_v = rng.randn(nb, bs, nkv, d).astype(np.float32)
    # deliberately shuffled, non-contiguous chains over blocks 1..nb-1
    perm = rng.permutation(nb - 1) + 1
    table = perm[: b * mb].reshape(b, mb).astype(np.int32)
    kd = pool_k[table].reshape(b, t, nkv, d)
    vd = pool_v[table].reshape(b, t, nkv, d)
    q = rng.randn(b, 1, nkv * g, d).astype(np.float32)
    if pool_dtype is not None:
        pool_k = np.asarray(jnp.asarray(pool_k, pool_dtype))
        kd = np.asarray(jnp.asarray(kd, pool_dtype))
        pool_v = np.asarray(jnp.asarray(pool_v, pool_dtype))
        vd = np.asarray(jnp.asarray(vd, pool_dtype))
    return pool_k, pool_v, table, kd, vd, q


@pytest.mark.parametrize("pool_dtype", [
    jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn,
])
def test_paged_kernel_shuffled_chain_parity(paged_forced, pool_dtype):
    """Kernel == XLA gather path over shuffled chains and ragged per-lane
    valid lengths, for full-width AND compressed-KV pools (the upcast
    stays dequant-fused inside the kernel)."""
    rng = np.random.RandomState(0)
    pool_k, pool_v, table, kd, vd, q = _paged_case(
        rng, pool_dtype=pool_dtype)
    qpos = jnp.asarray([[21], [30]], jnp.int32)
    valid = jnp.asarray([22, 31], jnp.int32)
    args = (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
            qpos, valid)
    kern = att.decode_gqa(*args, block_table=jnp.asarray(table))
    att.FORCE_PAGED_KERNEL = False
    xla = att.decode_gqa(*args, block_table=jnp.asarray(table))
    dense = att.decode_gqa(jnp.asarray(q), jnp.asarray(kd),
                           jnp.asarray(vd), qpos, valid)
    assert jnp.array_equal(xla, dense)  # gather path is exact by layout
    tol = 2e-6 if pool_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(kern, np.float32),
                               np.asarray(xla, np.float32),
                               rtol=tol, atol=tol)


def test_paged_kernel_scratch_and_garbage_blocks_masked(paged_forced):
    """Block 0 (scratch) and never-chained pool blocks hold garbage — the
    frozen-lane / unallocated-block state a live co-batched pool is
    always in — and must not leak into any lane's output. Finite garbage
    for the XLA parity check (the XLA gather's 0-weight x NaN would
    poison ITS output, not the kernel's), then NaN garbage to prove the
    kernel truly never reads those slots."""
    rng = np.random.RandomState(1)
    pool_k, pool_v, table, kd, vd, q = _paged_case(rng, b=2, mb=4)
    # lane 1's chain only covers 2 blocks of history; its tail table
    # entries point AT scratch (the executor stamps unallocated = 0)
    table = table.copy()
    table[1, 2:] = 0
    garbage = [0] + [blk for blk in range(pool_k.shape[0])
                     if blk not in set(table.flatten().tolist())]
    qpos = jnp.asarray([[21], [13]], jnp.int32)
    valid = jnp.asarray([22, 14], jnp.int32)  # lane 1 inside 2 blocks

    def run(fill, forced):
        pk, pv = pool_k.copy(), pool_v.copy()
        for blk in garbage:
            pk[blk] = fill
            pv[blk] = fill
        att.FORCE_PAGED_KERNEL = forced
        return att.decode_gqa(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), qpos,
            valid, block_table=jnp.asarray(table))

    kern = run(1e6, True)
    xla = run(1e6, False)
    assert np.isfinite(np.asarray(xla)).all()
    np.testing.assert_allclose(np.asarray(kern), np.asarray(xla),
                               rtol=2e-6, atol=2e-6)
    kern_nan = run(np.nan, True)
    assert np.isfinite(np.asarray(kern_nan)).all()
    assert jnp.array_equal(kern_nan, kern)


@pytest.mark.parametrize("softcap,window,with_sinks", [
    (30.0, None, False),   # gemma-2 logit softcap
    (0.0, 16, False),      # sliding window shorter than the chain
    (0.0, None, True),     # gpt-oss attention sinks
    (30.0, 16, True),      # all three folded together
])
def test_paged_kernel_sinks_softcap_window(paged_forced, softcap, window,
                                           with_sinks):
    rng = np.random.RandomState(2)
    pool_k, pool_v, table, kd, vd, q = _paged_case(rng)
    nq = q.shape[2]
    sinks = (jnp.asarray(rng.randn(nq), jnp.float32)
             if with_sinks else None)
    w = jnp.int32(window) if window else None
    qpos = jnp.asarray([[25], [28]], jnp.int32)
    valid = jnp.asarray([26, 29], jnp.int32)
    kw = dict(softcap=softcap, window=w, sinks=sinks,
              block_table=jnp.asarray(table))
    kern = att.decode_gqa(jnp.asarray(q), jnp.asarray(pool_k),
                          jnp.asarray(pool_v), qpos, valid, **kw)
    att.FORCE_PAGED_KERNEL = False
    xla = att.decode_gqa(jnp.asarray(q), jnp.asarray(pool_k),
                         jnp.asarray(pool_v), qpos, valid, **kw)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(xla),
                               rtol=2e-6, atol=2e-6)


def test_paged_dispatch_cold_registry_stays_xla():
    """FORCE hooks at None + cold registry (conftest pins the autotune
    path absent): every enable gate reports off and the block-table
    dispatch is byte-identical to the explicit gather + decode_gqa
    composition — registry-less hosts keep the pre-kernel bytes."""
    assert att.FORCE_PAGED_KERNEL is None
    assert not att.paged_kernel_enabled()
    assert not quant._quant_kernel_enabled()
    assert not lora_ops.fused_delta_enabled()
    rng = np.random.RandomState(3)
    pool_k, pool_v, table, kd, vd, q = _paged_case(rng)
    qpos = jnp.asarray([[21], [30]], jnp.int32)
    valid = jnp.asarray([22, 31], jnp.int32)
    via_dispatch = att.decode_gqa(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        qpos, valid, block_table=jnp.asarray(table))
    kg, vg = att.gather_block_kv(jnp.asarray(pool_k), jnp.asarray(pool_v),
                                 jnp.asarray(table))
    explicit = att.decode_gqa(jnp.asarray(q), kg, vg, qpos, valid)
    assert jnp.array_equal(via_dispatch, explicit)


# ---------------------------------------------------------------------------
# dequant-fused int4/int8 decode GEMV
# ---------------------------------------------------------------------------


def _int4_case(rng, m, k, n, x_dtype, group=32):
    x = jnp.asarray(rng.randn(m, k), x_dtype)
    w = quant.quantize_int4(
        jnp.asarray(rng.randn(k, n), jnp.float32), group=group)
    return x, w


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", [(1, 64, 96), (4, 64, 96), (3, 33, 96)])
def test_w4a16_dequant_scheme_bitexact(x_dtype, m, k, n):
    """The "dequant" scheme runs the same unpack -> scale -> cast -> dot
    sequence as `x @ w.dequantize(x.dtype)` — bit-exact, packed (even K)
    and unpacked (odd K) alike."""
    from inferd_tpu.ops.qmatmul import w4a16_matvec

    rng = np.random.RandomState(4)
    x, w = _int4_case(rng, m, k, n, x_dtype)
    got = w4a16_matvec(x, w, scheme="dequant", interpret=True)
    ref = x @ w.dequantize(x.dtype)
    assert got.dtype == ref.dtype
    assert jnp.array_equal(got, ref)


@pytest.mark.parametrize("x_dtype,tol", [
    (jnp.float32, 1e-5), (jnp.bfloat16, 2e-2),
])
def test_w4a16_grouped_scheme_allclose(x_dtype, tol):
    """The "grouped" scheme keeps per-group partials in f32 where the XLA
    sibling rounds them through x.dtype — parity to accumulation-order
    rounding, not bits."""
    from inferd_tpu.ops.qmatmul import w4a16_matvec

    rng = np.random.RandomState(5)
    x, w = _int4_case(rng, 2, 64, 96, x_dtype)
    got = w4a16_matvec(x, w, scheme="grouped", interpret=True)
    # the XLA grouped contraction qdot runs when the kernel is off
    g = w.scale.shape[-2]
    k = w.shape[0]
    xg = x.reshape(2, g, k // g)
    qg = w.unpacked().reshape(g, k // g, w.shape[1]).astype(x.dtype)
    y = jnp.einsum("bgk,gkn->bgn", xg, qg)
    ref = (y.astype(jnp.float32) * w.scale).sum(axis=-2).astype(x.dtype)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


def test_qdot_int4_kernel_routing_and_prefill_fallthrough():
    """With the kernel forced on, decode-shaped qdot routes through
    w4a16_matvec (identical bits under the dequant scheme) while
    prefill-shaped calls (rows > MAX_KERNEL_ROWS) fall through to the
    XLA path untouched."""
    from inferd_tpu.ops.qmatmul import MAX_KERNEL_ROWS

    rng = np.random.RandomState(6)
    x_dec, w = _int4_case(rng, 2, 64, 96, jnp.float32)
    x_pre = jnp.asarray(
        rng.randn(MAX_KERNEL_ROWS + 1, 64), jnp.float32)
    olds = quant.FORCE_QUANT_KERNEL, quant.INT4_MODE
    try:
        quant.INT4_MODE = "dequant"
        quant.FORCE_QUANT_KERNEL = False
        ref_dec = quant.qdot(x_dec, w)
        ref_pre = quant.qdot(x_pre, w)
        quant.FORCE_QUANT_KERNEL = True
        got_dec = quant.qdot(x_dec, w)
        got_pre = quant.qdot(x_pre, w)
    finally:
        quant.FORCE_QUANT_KERNEL, quant.INT4_MODE = olds
    assert jnp.array_equal(got_dec, ref_dec)   # kernel == dequant, bitwise
    assert jnp.array_equal(got_pre, ref_pre)   # fell through: same path


@pytest.mark.parametrize("x_dtype,tol", [
    (jnp.float32, 1e-5), (jnp.bfloat16, 2e-2),
])
def test_qdot_int8_dequant_mode_kernel_routing(x_dtype, tol):
    """QDOT_MODE="dequant" + registry-says-kernel routes int8 decode
    matvecs through w8a16_matmul; parity to the dequant XLA path is
    rounding-bounded (the kernel keeps the f32 scale-accumulate)."""
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(2, 64), x_dtype)
    w = quant.quantize(jnp.asarray(rng.randn(64, 96), jnp.float32))
    old = quant.FORCE_QUANT_KERNEL
    try:
        quant.FORCE_QUANT_KERNEL = False
        ref = quant.qdot(x, w)
        quant.FORCE_QUANT_KERNEL = True
        got = quant.qdot(x, w)
    finally:
        quant.FORCE_QUANT_KERNEL = old
    assert got.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# fused LoRA lane-delta kernel
# ---------------------------------------------------------------------------


def _lora_pools(rng, slots=4, n_layers=3, d_in=32, r=4, d_out=48):
    """Stacked pools with slot 0 = zero base and MIXED effective ranks
    (narrow tenants zero-pad their tail rank columns, exactly how the
    registry stacks a rank-2 adapter into a rank-4 pool)."""
    a = rng.randn(slots, n_layers, d_in, r).astype(np.float32) * 0.3
    b = rng.randn(slots, n_layers, r, d_out).astype(np.float32) * 0.3
    a[0] = 0.0
    b[0] = 0.0
    a[2, :, :, 2:] = 0.0  # slot 2: effective rank 2
    b[2, :, 2:, :] = 0.0
    scale = np.asarray([0.0, 2.0, 0.5, 1.25], np.float32)[:slots]
    return jnp.asarray(a), jnp.asarray(b), jnp.asarray(scale)


def test_fused_lane_delta_bitexact_mixed_ranks():
    """Kernel == gather_lanes + lane_delta, every layer, bit for bit —
    mixed-rank slots, slot-0 base lanes included."""
    rng = np.random.RandomState(8)
    a, b, scale = _lora_pools(rng)
    ids = jnp.asarray([2, 0, 1, 3], jnp.int32)  # incl. base lane
    x = jnp.asarray(rng.randn(4, 1, 32), jnp.float32)
    for layer in range(a.shape[1]):
        got = lora_ops.fused_lane_delta(
            x, a, b, scale, ids, jnp.int32(layer), interpret=True)
        ref = lora_ops.lane_delta(
            x, a[ids, layer], b[ids, layer], scale[ids])
        assert jnp.array_equal(got, ref), f"layer {layer}"
    # slot-0 lanes are an exact zero delta
    got0 = lora_ops.fused_lane_delta(
        x, a, b, scale, jnp.zeros(4, jnp.int32), jnp.int32(0),
        interpret=True)
    assert jnp.array_equal(got0, jnp.zeros_like(got0))


def test_apply_lane_delta_pools_form_matches_gather_form():
    """apply_lane_delta's fused pools form == its gather (layers) form at
    a projection, bit for bit; a target absent from the pools passes y
    through untouched."""
    rng = np.random.RandomState(9)
    a, b, scale = _lora_pools(rng)
    ids = jnp.asarray([1, 2, 0, 3], jnp.int32)
    x = jnp.asarray(rng.randn(4, 1, 32), jnp.float32)
    y = jnp.asarray(rng.randn(4, 1, 48), jnp.float32)
    adapters = {"a": {"q_proj": a}, "b": {"q_proj": b},
                "scale": scale, "ids": ids}
    old = lora_ops.FORCE_LORA_KERNEL
    try:
        lora_ops.FORCE_LORA_KERNEL = True
        fused = lora_ops.apply_lane_delta(
            y, x, "q_proj", {"pools": adapters, "layer": jnp.int32(1)})
        missing = lora_ops.apply_lane_delta(
            y, x, "up_proj", {"pools": adapters, "layer": jnp.int32(1)})
    finally:
        lora_ops.FORCE_LORA_KERNEL = old
    gathered = lora_ops.apply_lane_delta(
        y, x, "q_proj",
        {"layers": {"q_proj": (a[ids, 1], b[ids, 1])},
         "scale": scale[ids]})
    assert jnp.array_equal(fused, gathered)
    assert jnp.array_equal(missing, y)


# ---------------------------------------------------------------------------
# end-to-end: decode_k and both batched executors, kernels forced on
# ---------------------------------------------------------------------------


def _greedy_stream(ex, sid, prompt, steps, adapter=None):
    payload = {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)}
    if adapter is not None:
        payload["adapter"] = adapter
    out = ex.process(sid, payload)
    toks = [int(np.argmax(out["logits"][0]))]
    pos = len(prompt)
    for _ in range(steps - 1):
        o = ex.process(sid, {
            "tokens": [[toks[-1]]], "start_pos": pos, "real_len": 1,
        })
        toks.append(int(np.argmax(o["logits"][0])))
        pos += 1
    return toks


def test_decode_k_paged_token_exact_kernel_forced(all_kernels_forced,
                                                  tiny_params):
    """The fused K-step loop over a paged cache with the attention kernel
    forced on emits the same tokens as the dense cache with it off."""
    from inferd_tpu.core.cache import BlockPool, KVCache

    def run(forced):
        att.FORCE_PAGED_KERNEL = forced
        pool = BlockPool(TINY, TINY.num_layers, lanes=2, max_len=96,
                         block_size=16)
        serve = qwen3.make_decode_k_serve(TINY)
        toks = np.array([list(range(3, 19)), list(range(4, 20))], np.int32)
        b, n = toks.shape
        dense = KVCache.create(TINY, TINY.num_layers, b,
                               pool.max_blocks * pool.block_size, ring=False)
        for lane in range(b):
            pool.ensure(lane, n + 6, owner=f"lane {lane}")
        paged = dataclasses.replace(pool.cache, table=pool.device_table())
        pos = jnp.broadcast_to(jnp.arange(n), (b, n))
        cache = paged if forced else dense
        logits, cache, _ = qwen3.forward_cached(
            tiny_params, TINY, jnp.asarray(toks), pos, cache,
            jnp.int32(0), real_end=jnp.int32(n))
        tok = jnp.argmax(logits[:, n - 1], -1).astype(jnp.int32)
        lens = jnp.full((b,), n, jnp.int32)
        act = jnp.ones((b,), bool)
        keys = jnp.zeros((b, 2), jnp.uint32)
        eos = jnp.asarray([-1, -1], jnp.int32)
        _, seq, n_new, _ = serve(tiny_params, cache, tok, lens, act, keys,
                                 eos, 6, 0.0, 0, 1.0, 0.0)
        return np.asarray(seq), np.asarray(n_new)

    seq_k, n_k = run(True)
    seq_x, n_x = run(False)
    assert np.array_equal(seq_k, seq_x)
    assert np.array_equal(n_k, n_x)


def test_stage_executor_paged_cobatch_token_exact_kernels_forced(
        all_kernels_forced, tiny_params):
    """BatchedStageExecutor over a paged pool, staggered admissions (so
    co-batched steps see frozen lanes whose blocks hold stale garbage),
    every stream token-exact with the kernels forced on vs off."""
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    spec = list(Manifest.even_split("tiny", 1).stage_specs())[0]
    sp = extract_stage_params(tiny_params, TINY, spec)
    p_a = [3, 17, 42, 9, 5, 8, 2, 11]
    p_b = [6, 1, 33, 27]

    def run(forced):
        att.FORCE_PAGED_KERNEL = forced
        ex = BatchedStageExecutor(TINY, spec, sp, lanes=4, max_len=64,
                                  block_size=8)
        # stagger: A decodes alone first (B's future lane frozen), then
        # B joins the co-batch window
        a1 = _greedy_stream(ex, "a", p_a, 4)
        b1 = _greedy_stream(ex, "b", p_b, 6)
        a2 = _greedy_stream(ex, "a2", p_a, 4)
        return a1, b1, a2

    assert run(True) == run(False)


def test_batched_executor_lora_tenants_token_exact_kernels_forced(
        all_kernels_forced, tiny_params, tmp_path):
    """BatchedExecutor with two mixed-rank tenants + a base lane: every
    stream token-exact with the fused LoRA kernel forced on vs off."""
    from inferd_tpu.runtime.adapters import AdapterRegistry
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    g = np.random.default_rng(10)
    dirs = []
    for name, r, targets in (("t0", 4, ("q_proj", "down_proj")),
                             ("t1", 2, ("gate_proj",))):
        dims = {"q_proj": (TINY.hidden_size, TINY.q_dim),
                "down_proj": (TINY.intermediate_size, TINY.hidden_size),
                "gate_proj": (TINY.hidden_size, TINY.intermediate_size)}
        layers = {
            t: (g.normal(0, 0.25, (TINY.num_layers, dims[t][0], r))
                 .astype(np.float32),
                g.normal(0, 0.25, (TINY.num_layers, r, dims[t][1]))
                 .astype(np.float32))
            for t in targets
        }
        p = str(tmp_path / name)
        lora_ops.save_adapter(p, layers, alpha=8, r=r)
        dirs.append(p)
    prompt = [3, 17, 42, 9, 5, 8, 2, 11]

    def run(forced):
        lora_ops.FORCE_LORA_KERNEL = forced
        ex = BatchedExecutor(TINY, tiny_params, lanes=4, max_len=64,
                             adapters=AdapterRegistry(TINY, dirs))
        return (_greedy_stream(ex, "s0", prompt, 5, adapter="t0"),
                _greedy_stream(ex, "s1", prompt, 5, adapter="t1"),
                _greedy_stream(ex, "sb", prompt, 5))

    assert run(True) == run(False)


def test_quantized_executor_stream_token_exact_kernel_forced(tiny_params):
    """An int4-quantized single-stage executor decodes the same greedy
    stream with the dequant GEMV kernel forced on vs off."""
    from inferd_tpu.parallel.stages import StageSpec, extract_stage_params
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    qparams = quant.apply_quant_mode(
        "int4", tiny_params, tie_word_embeddings=TINY.tie_word_embeddings)
    spec = StageSpec(0, 1, 0, TINY.num_layers - 1)
    sp = extract_stage_params(qparams, TINY, spec)
    prompt = [3, 17, 42, 9, 5, 8, 2, 11]

    def run(forced):
        olds = quant.FORCE_QUANT_KERNEL, quant.INT4_MODE
        quant.FORCE_QUANT_KERNEL = forced
        quant.INT4_MODE = "dequant"
        try:
            ex = Qwen3StageExecutor(TINY, spec, sp, max_len=64,
                                    initial_kv_len=64)
            return _greedy_stream(ex, "q", prompt, 5)
        finally:
            quant.FORCE_QUANT_KERNEL, quant.INT4_MODE = olds

    assert run(True) == run(False)
