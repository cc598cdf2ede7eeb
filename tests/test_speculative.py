# jaxlint: file-disable=J003 -- test code: loops here sync per-iteration to ASSERT on values; they are verification loops, not serving hot paths
"""Speculative decoding (core.speculative): the greedy-exactness guarantee,
full-acceptance fast path, rollback correctness across rounds, and EOS.
Added scope beyond the reference's one-token-per-pass decode
(client.py:244-266)."""

import dataclasses

import jax
import numpy as np
import pytest

from inferd_tpu.config import TINY, SamplingConfig
from inferd_tpu.core.generate import Engine
from inferd_tpu.core.speculative import SpeculativeEngine
from inferd_tpu.models import qwen3


@pytest.fixture(scope="module")
def target():
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    return TINY, params


@pytest.mark.parametrize("k", [1, 3, 4])
def test_greedy_exactness_with_unrelated_draft(target, k):
    """With an arbitrary (even adversarial) draft, output must EXACTLY match
    the target's own greedy decode — only speed may differ."""
    cfg, params = target
    draft_cfg = dataclasses.replace(TINY, name="tiny-draft", num_layers=2)
    draft_params = qwen3.init_params(draft_cfg, jax.random.PRNGKey(99))

    engine = Engine(cfg, params, max_len=128, sampling_cfg=SamplingConfig(temperature=0.0))
    prompt = [3, 17, 42, 9]
    want = engine.generate(prompt, max_new_tokens=24)

    spec = SpeculativeEngine(cfg, params, draft_cfg, draft_params, k=k, max_len=128)
    got, acc = spec.generate(prompt, max_new_tokens=24)
    assert got == want
    assert 0.0 <= acc <= 1.0


def test_full_acceptance_when_draft_is_target(target):
    """Draft == target accepts every draft (acceptance 1.0) and still emits
    the exact greedy stream."""
    cfg, params = target
    engine = Engine(cfg, params, max_len=128, sampling_cfg=SamplingConfig(temperature=0.0))
    prompt = [5, 11, 2]
    want = engine.generate(prompt, max_new_tokens=20)

    spec = SpeculativeEngine(cfg, params, cfg, params, k=4, max_len=128)
    got, acc = spec.generate(prompt, max_new_tokens=20)
    assert got == want
    assert acc == 1.0


@pytest.mark.parametrize("family", ["gemma2", "gptoss"])
def test_greedy_exactness_new_families(family):
    """Speculative self-drafting stays token-exact for the sliding-window
    families: the truncated draft's first-N layers keep the global layer
    indices (offset 0), so its window pattern matches the target's prefix,
    and the verify chunk walks the full recipe (sinks/softcaps included)."""
    from inferd_tpu.config import TINY_GEMMA2, TINY_GPT_OSS
    from inferd_tpu.core.speculative import self_draft

    cfg = TINY_GEMMA2 if family == "gemma2" else TINY_GPT_OSS
    params = qwen3.init_params(cfg, jax.random.PRNGKey(31))
    engine = Engine(cfg, params, max_len=128, sampling_cfg=SamplingConfig(temperature=0.0))
    prompt = [3, 17, 42, 9, 8, 1, 5, 12, 2]
    want = engine.generate(prompt, max_new_tokens=16)  # walks past window 8

    dcfg, dparams = self_draft(cfg, params, 2)
    spec = SpeculativeEngine(cfg, params, dcfg, dparams, k=3, max_len=128)
    got, acc = spec.generate(prompt, max_new_tokens=16)
    assert got == want
    assert 0.0 <= acc <= 1.0


def test_eos_stops_mid_chunk(target):
    """EOS inside an accepted run truncates the output exactly where the
    target's own greedy decode would stop."""
    cfg, params = target
    engine = Engine(cfg, params, max_len=128, sampling_cfg=SamplingConfig(temperature=0.0))
    prompt = [7, 1, 13]
    ref = engine.generate(prompt, max_new_tokens=30)
    # pick the 6th emitted token as a fake EOS so it lands mid-stream
    eos = ref[5]
    want = engine.generate(prompt, max_new_tokens=30, eos_token_id=eos)

    spec = SpeculativeEngine(cfg, params, cfg, params, k=4, max_len=128)
    got, _ = spec.generate(prompt, max_new_tokens=30, eos_token_id=eos)
    assert got == want


def test_vocab_mismatch_rejected(target):
    cfg, params = target
    bad = dataclasses.replace(TINY, vocab_size=128)
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeEngine(cfg, params, bad, params, k=2)


def test_sampled_full_acceptance_when_draft_is_target(target):
    """With draft == target and temperature > 0, q == p at every position,
    so every draft is accepted (rate 1.0) and tokens flow."""
    cfg, params = target
    spec = SpeculativeEngine(
        cfg, params, cfg, params, k=4, max_len=128,
        sampling_cfg=SamplingConfig(temperature=0.8, top_k=10, top_p=0.95),
    )
    got, acc = spec.generate([5, 11, 2], max_new_tokens=20, seed=3)
    # q == p per token up to cross-program ulp noise (draft scan vs chunked
    # verify are different XLA programs), so near-total acceptance
    assert len(got) == 20 and acc >= 0.9


def test_sampled_distribution_matches_target(target):
    """The rejection scheme's output must be distributed exactly as
    target-only warped sampling, regardless of the (mismatched) draft:
    empirical first-emitted-token distribution over many seeds vs the
    target's warped probabilities, in total-variation distance."""
    import jax.numpy as jnp

    from inferd_tpu.core import sampling as samplib
    from inferd_tpu.core.cache import KVCache

    cfg, params = target
    draft_cfg = dataclasses.replace(TINY, name="tiny-draft2", num_layers=2)
    draft_params = qwen3.init_params(draft_cfg, jax.random.PRNGKey(77))
    sc = SamplingConfig(temperature=1.2, top_k=5, top_p=0.9)
    spec = SpeculativeEngine(
        cfg, params, draft_cfg, draft_params, k=3, max_len=64, sampling_cfg=sc
    )

    # fixed prefix: prompt + pending token x_n chosen greedily
    prompt = [3, 17, 42, 9]
    n = len(prompt)
    toks = jnp.asarray([prompt + [0] * (16 - n)], jnp.int32)

    # target's warped next-token distribution after [prompt, x_n]
    logits_p, _, _ = qwen3.forward(params, cfg, toks[:, :n])
    x_n = int(jnp.argmax(logits_p[0, n - 1]))
    logits_full, _, _ = qwen3.forward(
        params, cfg, jnp.asarray([prompt + [x_n] + [0] * (16 - n - 1)], jnp.int32)
    )
    want = np.asarray(
        jax.nn.softmax(
            samplib.warped_logits(
                logits_full[:, n], sc.temperature, sc.top_k, sc.top_p
            )
        )
    )[0]

    # one jitted prefill builds fresh cache buffers per trial (the spec step
    # donates its cache args, so each trial needs new buffers; jitting this
    # also avoids repeated eager scan dispatch, which segfaults XLA:CPU
    # when run under pytest)
    @jax.jit
    def prefill_caches(tp, dp, toks):
        tc = KVCache.create(cfg, cfg.num_layers, 1, 64, ring=False)
        dc = KVCache.create(draft_cfg, draft_cfg.num_layers, 1, 64, ring=False)
        _, tk, tv = qwen3.forward(tp, cfg, toks, None, tc.k, tc.v, jnp.int32(0))
        _, dk, dv = qwen3.forward(dp, draft_cfg, toks, None, dc.k, dc.v, jnp.int32(0))
        return tk, tv, dk, dv

    counts = np.zeros(cfg.vocab_size)
    trials = 600
    last = jnp.asarray([x_n], jnp.int32)
    for s in range(trials):
        tk, tv_, dk, dv = prefill_caches(params, draft_params, toks)
        tc = KVCache(k=tk, v=tv_, length=jnp.int32(n))
        dc = KVCache(k=dk, v=dv, length=jnp.int32(n))
        out_toks, n_new, _, _ = spec._spec_step_sampled(
            params, draft_params, last, tc, dc, jax.random.PRNGKey(10_000 + s)
        )
        counts[int(out_toks[0])] += 1
    emp = counts / trials
    tv = 0.5 * np.abs(emp - want).sum()
    assert tv < 0.10, f"TV distance {tv}"
