# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Pallas flash-attention kernel parity vs the XLA reference path.

Runs the kernel in the Pallas interpreter on the CPU mesh (conftest pins
JAX_PLATFORMS=cpu), asserting exactness properties the TPU kernel relies on.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import TINY
from inferd_tpu.models import qwen3
from inferd_tpu.models.qwen3 import gqa_attention
from inferd_tpu.ops.attention import flash_gqa


@pytest.fixture(scope="module")
def tiny_params():
    return qwen3.init_params(TINY, jax.random.PRNGKey(0))


def _rand_qkv(key, b, s, t, nq, nkv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, nq, d), dtype)
    k = jax.random.normal(kk, (b, t, nkv, d), dtype)
    v = jax.random.normal(kv, (b, t, nkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "b,s,t,nq,nkv,d,q_start,kv_len",
    [
        (1, 16, 16, 4, 2, 16, 0, 16),  # prefill from scratch
        (2, 8, 64, 4, 4, 32, 24, 32),  # chunk mid-sequence over a big buffer
        (1, 1, 64, 8, 2, 16, 40, 41),  # single-token decode step
        (2, 33, 70, 4, 2, 16, 0, 33),  # ragged (padded) shapes
    ],
)
def test_flash_matches_xla_cache_layout(b, s, t, nq, nkv, d, q_start, kv_len):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, s, t, nq, nkv, d)
    q_positions = q_start + jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(q, k, v, q_positions, jnp.int32(kv_len))
    got = flash_gqa(q, k, v, q_start=q_start, kv_len=kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_matches_xla_no_cache_offset():
    # cache-free stage forward mid-sequence: slot j = position q_start + j
    b, s, nq, nkv, d = 2, 24, 4, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, s, s, nq, nkv, d)
    pos = 100 + jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(q, k, v, pos, jnp.int32(s), kv_positions=pos)
    got = flash_gqa(q, k, v, q_start=pos[:, 0], kv_len=s, kv_start=pos[:, 0], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "stream"])
@pytest.mark.parametrize(
    "b,s,t,nq,nkv,d,q_start,kv_len,window",
    [
        (1, 16, 16, 4, 2, 16, 0, 16, 8),   # prefill, window < seq
        (1, 1, 64, 8, 2, 16, 40, 41, 8),   # decode far past the window
        (2, 8, 64, 4, 4, 32, 24, 32, 100), # window wider than context = global
        (1, 1, 64, 8, 2, 16, 40, 41, 0),   # window 0 = global (gemma odd layers)
    ],
)
def test_flash_sliding_window_matches_xla(stream, b, s, t, nq, nkv, d, q_start, kv_len, window):
    """Kernel sliding-window masking + kv-block loop floor == XLA reference,
    with the window as a TRACED scalar (per-layer scan input) and softcap +
    non-default scale stacked on (the full Gemma-2 attention recipe)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b, s, t, nq, nkv, d)
    q_positions = q_start + jnp.broadcast_to(jnp.arange(s), (b, s))
    scale, cap = 32.0 ** -0.5, 50.0
    ref = gqa_attention(
        q, k, v, q_positions, jnp.int32(kv_len),
        scale=scale, softcap=cap, window=jnp.int32(window),
    )

    @jax.jit
    def run(win):  # traced window, like the layer scan passes it
        return flash_gqa(
            q, k, v, q_start=q_start, kv_len=kv_len, interpret=True,
            stream=stream, scale=scale, softcap=cap, window=win,
        )

    got = run(jnp.int32(window))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "stream"])
@pytest.mark.parametrize(
    "b,s,t,nq,nkv,d,q_start,kv_len,window",
    [
        (1, 16, 16, 4, 2, 16, 0, 16, 0),    # prefill, global layer
        (1, 1, 64, 8, 2, 16, 40, 41, 8),    # decode, sliding layer
        (2, 8, 64, 4, 4, 32, 24, 32, 0),    # multi-batch chunk
        (2, 33, 70, 4, 2, 16, 0, 33, 0),    # ragged/padded rows keep zeros
    ],
)
def test_flash_sinks_match_xla(stream, b, s, t, nq, nkv, d, q_start, kv_len, window):
    """Attention sinks fold into the kernels' online-softmax denominator at
    finalize; must equal the XLA closed form for every packed-tile layout —
    including bucket-padding rows (which must still emit zeros)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), b, s, t, nq, nkv, d)
    sinks = jax.random.normal(jax.random.PRNGKey(12), (nq,)) * 2.0
    q_positions = q_start + jnp.broadcast_to(jnp.arange(s), (b, s))
    win = jnp.int32(window)
    ref = gqa_attention(
        q, k, v, q_positions, jnp.int32(kv_len), window=win, sinks=sinks
    )
    got = flash_gqa(
        q, k, v, q_start=q_start, kv_len=kv_len, interpret=True,
        stream=stream, window=win, sinks=sinks,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_full_model_forward_with_flash_kernel_gpt_oss():
    """Whole tiny-gptoss forward (sinks + window + yarn + biases) with
    attn_impl=flash_interpret == the XLA path."""
    from inferd_tpu.config import TINY_GPT_OSS

    cfg_x = dataclasses.replace(TINY_GPT_OSS, attn_impl="xla")
    cfg_f = dataclasses.replace(TINY_GPT_OSS, attn_impl="flash_interpret")
    params = qwen3.init_params(cfg_x, jax.random.PRNGKey(13))
    # randomize sinks so they matter
    params["layers"]["sinks"] = jax.random.normal(
        jax.random.PRNGKey(14), params["layers"]["sinks"].shape
    )
    tokens = jax.random.randint(jax.random.PRNGKey(15), (1, 12), 0, cfg_x.vocab_size)
    ref, _, _ = qwen3.forward(params, cfg_x, tokens)
    got, _, _ = qwen3.forward(params, cfg_f, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "stream"])
def test_flash_window_with_kv_start_offset(stream):
    """window > 0 combined with kv_start > 0 — the configuration the
    windowed-read fast path produces (a window-covering KV slice whose
    slot 0 holds a mid-sequence absolute position). Pins the kernels'
    window-floor arithmetic (lo_slot subtracts kv_start)."""
    b, s, t, nq, nkv, d = 2, 1, 32, 4, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(17), b, s, t, nq, nkv, d)
    kv_start, kv_len, q0, window = 100, 30, 129, 8
    pos = jnp.full((b, s), q0, jnp.int32)
    kvpos = kv_start + jnp.arange(t)
    ref = gqa_attention(
        q, k, v, pos, jnp.int32(kv_len), kv_positions=kvpos,
        window=jnp.int32(window),
    )
    got = flash_gqa(
        q, k, v, q_start=q0, kv_len=kv_len, kv_start=kv_start,
        interpret=True, stream=stream, window=jnp.int32(window),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_softcap_only_matches_xla():
    """Softcap without a window (a Gemma global layer) on both kernels."""
    b, s, t, nq, nkv, d = 2, 8, 64, 4, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(8), b, s, t, nq, nkv, d)
    pos = 24 + jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(q, k, v, pos, jnp.int32(32), softcap=30.0)
    for stream in (False, True):
        got = flash_gqa(
            q, k, v, q_start=24, kv_len=32, interpret=True,
            stream=stream, softcap=30.0,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_full_model_forward_with_flash_kernel_gemma():
    """Whole tiny-gemma2 forward with attn_impl=flash_interpret == XLA path:
    the per-layer window array reaches the kernel through the scan."""
    from inferd_tpu.config import TINY_GEMMA2

    cfg_x = dataclasses.replace(TINY_GEMMA2, attn_impl="xla")
    cfg_f = dataclasses.replace(TINY_GEMMA2, attn_impl="flash_interpret")
    params = qwen3.init_params(cfg_x, jax.random.PRNGKey(9))
    tokens = jax.random.randint(jax.random.PRNGKey(10), (1, 12), 0, cfg_x.vocab_size)
    ref, _, _ = qwen3.forward(params, cfg_x, tokens)
    got, _, _ = qwen3.forward(params, cfg_f, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_per_batch_lengths():
    b, s, t, nq, nkv, d = 3, 4, 32, 4, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, s, t, nq, nkv, d)
    q_start = jnp.array([0, 8, 20], jnp.int32)
    kv_len = q_start + s
    pos = q_start[:, None] + jnp.arange(s)[None, :]
    ref = gqa_attention(q, k, v, pos, kv_len)
    got = flash_gqa(q, k, v, q_start=q_start, kv_len=kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bf16_close_to_f32_reference():
    b, s, nq, nkv, d = 1, 32, 4, 2, 32
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b, s, s, nq, nkv, d, jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(q, k, v, pos, jnp.int32(s), kv_positions=pos)
    got = flash_gqa(q, k, v, q_start=0, kv_len=s, kv_start=0, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=0.1, atol=0.1
    )


def test_full_model_forward_with_flash_kernel(tiny_params):
    """End-to-end: whole tiny model with attn_impl=flash_interpret matches XLA."""
    cfg = TINY
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 20), 0, cfg.vocab_size)
    ref_logits, _, _ = qwen3.forward(tiny_params, cfg, tokens)
    fcfg = dataclasses.replace(cfg, attn_impl="flash_interpret")
    got_logits, _, _ = qwen3.forward(tiny_params, fcfg, tokens)
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-4
    )


def test_cached_decode_with_flash_kernel(tiny_params):
    """Prefill + cached decode through the kernel matches the XLA path."""
    from inferd_tpu.core.cache import KVCache

    cfg = dataclasses.replace(TINY, attn_impl="flash_interpret")
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 12), 0, cfg.vocab_size)
    cache = KVCache.create(cfg, cfg.num_layers, 1, 32, ring=False)

    ref_logits, _, _ = qwen3.forward(tiny_params, TINY, tokens)

    # prefill first 11 tokens, then decode token 12 against the cache
    logits, nk, nv = qwen3.forward(
        tiny_params, cfg, tokens[:, :11],
        k_cache=cache.k, v_cache=cache.v, cache_write_pos=jnp.int32(0),
    )
    step_logits, _, _ = qwen3.forward(
        tiny_params, cfg, tokens[:, 11:12],
        k_cache=nk, v_cache=nv, cache_write_pos=jnp.int32(11),
    )
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(ref_logits[:, 11]), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize(
    "b,s,t,nq,nkv,d,q_start,kv_len",
    [
        (1, 16, 16, 4, 2, 16, 0, 16),   # prefill from scratch
        (2, 8, 640, 4, 4, 32, 24, 32),  # chunk over a much larger buffer
        (1, 1, 512, 8, 2, 16, 300, 301),  # decode step, multi-block stream
        (2, 33, 384, 4, 2, 16, 0, 33),  # ragged (padded) shapes
    ],
)
def test_flash_stream_matches_xla(b, s, t, nq, nkv, d, q_start, kv_len):
    """The streaming kernel (kv blocks on an inner grid axis, state in
    scratch — the no-VMEM-cap long-context path) must match XLA exactly."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, s, t, nq, nkv, d)
    q_positions = q_start + jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(q, k, v, q_positions, jnp.int32(kv_len))
    got = flash_gqa(
        q, k, v, q_start=q_start, kv_len=kv_len, interpret=True,
        stream=True, block_k=128,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_auto_selects_stream_past_vmem_budget():
    """Auto dispatch: buffers past the VMEM budget go to the streaming
    kernel rather than falling back to XLA (VERDICT r1 A6 — the ~8K cap)."""
    from inferd_tpu.ops import attention as att

    assert att._kv_fits_vmem(4096, 128, jnp.bfloat16)
    assert not att._kv_fits_vmem(16384, 128, jnp.bfloat16)  # past the old cap
    # a long-buffer call runs (interpret) and matches the reference — with
    # shapes that actually exceed the budget, so stream=None resolves to the
    # STREAMING kernel (d must match the budget assertion above, else auto
    # quietly picks the resident kernel and this test pins nothing)
    b, s, t, nq, nkv, d = 1, 1, 16384, 2, 2, 128
    assert not att._kv_fits_vmem(t, d, jnp.float32)
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, s, t, nq, nkv, d)
    q_positions = jnp.full((b, s), 9000)
    ref = gqa_attention(q, k, v, q_positions, jnp.int32(9001))
    got = flash_gqa(q, k, v, q_start=9000, kv_len=9001, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "stream"])
@pytest.mark.parametrize(
    "b,s,t,nq,nkv,d,q_start,kv_len",
    [
        (1, 40, 64, 4, 2, 16, 20, 60),   # s_pad > block_q: multi-tile per head
        (2, 33, 96, 6, 2, 16, 0, 33),    # g=3 with per-batch rows, ragged s
    ],
)
def test_flash_packed_multitile_matches_xla(stream, b, s, t, nq, nkv, d, q_start, kv_len):
    """The s_pad >= block_q packing branch (long prefill: several tiles per
    query head, modulo position/frontier arithmetic) must match XLA — CI
    otherwise only exercises the small-S multi-head-per-tile branch."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), b, s, t, nq, nkv, d)
    q_positions = q_start + jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(q, k, v, q_positions, jnp.int32(kv_len))
    got = flash_gqa(
        q, k, v, q_start=q_start, kv_len=kv_len, interpret=True,
        stream=stream, block_q=32, block_k=32,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "stream"])
def test_flash_consumes_fp8_kv_directly(stream):
    """The kernels upcast compressed (fp8) K/V in VMEM after the block
    fetch — results must match the upcast-then-XLA reference within fp8
    storage noise."""
    b, s, t, nq, nkv, d = 1, 8, 128, 4, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(12), b, s, t, nq, nkv, d)
    k8 = k.astype(jnp.float8_e4m3fn)
    v8 = v.astype(jnp.float8_e4m3fn)
    q_positions = 100 + jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = gqa_attention(
        q, k8.astype(q.dtype), v8.astype(q.dtype), q_positions, jnp.int32(108)
    )
    got = flash_gqa(
        q, k8, v8, q_start=100, kv_len=108, interpret=True, stream=stream
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl,want", [
    ("auto", False), ("flash", False), ("flash_interpret", ValueError),
])
def test_no_interpreted_kernel_on_a_tpu(monkeypatch, impl, want):
    """On a TPU the kernels are compiled: nothing can select the Pallas
    interpreter there, and asking for it by name is refused."""
    from inferd_tpu.ops import attention as att

    cfg = dataclasses.replace(TINY, attn_impl=impl)
    assert att.flash_interpret(cfg) is True  # off the TPU: the only way
    monkeypatch.setattr(att, "is_tpu", lambda: True)
    if want is ValueError:
        with pytest.raises(ValueError, match="interpreter"):
            att.flash_interpret(cfg)
    else:
        assert att.flash_interpret(cfg) is want
