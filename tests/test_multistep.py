"""Multi-step fused decode (models/qwen3.decode_k): K tokens per dispatch
with on-device sampling, wired through all three executors.

The contract under test everywhere: decoding K tokens in ONE dispatch must
NEVER change what any session decodes — greedy streams are token-exact
against the K=1 client-side-argmax loop, sampled streams are token-exact
against chained K=1 on-device steps (same per-session key schedule), and
the stop-token / budget / replay edge cases degrade exactly like the
per-token path.
"""

import threading

import numpy as np
import pytest


@pytest.fixture(scope="module")
def solo_setup():
    import jax

    from inferd_tpu.config import TINY
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import StageSpec, extract_stage_params

    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    spec = StageSpec(0, 1, 0, TINY.num_layers - 1)
    sp = extract_stage_params(params, TINY, spec)
    return TINY, params, spec, sp


PROMPT = [3, 7, 11, 19]
SAMPLING = {"temperature": 0.8, "top_k": 8, "top_p": 0.95}


def _mk_solo(solo_setup, max_len=64):
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    cfg, _params, spec, sp = solo_setup
    return Qwen3StageExecutor(cfg, spec, sp, max_len=max_len)


def _client_loop(ex, prompt, steps, eos=None):
    """The K=1 reference: per-token dispatch, client-side argmax."""
    r = ex.process("ref", {"tokens": [prompt], "start_pos": 0,
                           "real_len": len(prompt)})
    out = [int(np.argmax(r["logits"][0]))]
    pos = len(prompt)
    while len(out) < steps and (eos is None or out[-1] != eos):
        r = ex.process("ref", {"tokens": [[out[-1]]], "start_pos": pos,
                               "real_len": 1})
        out.append(int(np.argmax(r["logits"][0])))
        pos += 1
    ex.end_session("ref")
    return out


def _kstep_loop(ex, sid, prompt, steps, k, eos=None, sampling=None, seed=0):
    """Drive the multi-step path: decode_steps=k per request, chaining the
    returned PRNG key. Returns the emitted stream."""
    r = ex.process(sid, {"tokens": [prompt], "start_pos": 0,
                         "real_len": len(prompt)})
    out = [int(np.argmax(r["logits"][0]))]
    pos = len(prompt)
    key = None
    while len(out) < steps and (eos is None or out[-1] != eos):
        pl = {"tokens": [[out[-1]]], "start_pos": pos,
              "decode_steps": min(k, steps - len(out))}
        if eos is not None:
            pl["eos"] = eos
        if sampling is not None:
            pl["sampling"] = sampling
            pl["seed"] = seed
        if key is not None:
            pl["key"] = key
        rr = ex.process(sid, pl)
        assert rr["real_len"] == len(rr["tokens"][0])
        if rr["real_len"] == 0:
            break
        out.extend(int(t) for t in rr["tokens"][0])
        pos += rr["real_len"]
        key = rr.get("key")
    ex.end_session(sid)
    return out


# ---------------------------------------------------------------------------
# solo executor (runtime/executor.Qwen3StageExecutor)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [2, 5, 8])
def test_solo_kstep_greedy_token_exact(solo_setup, k):
    ex = _mk_solo(solo_setup)
    ref = _client_loop(ex, PROMPT, 12)
    assert _kstep_loop(ex, f"k{k}", PROMPT, 12, k) == ref


@pytest.mark.parametrize("k", [2, 5, 8])
def test_solo_kstep_stop_token_mid_window(solo_setup, k):
    """eos fires inside a K window: the executor deactivates in-graph,
    commits only the tokens through the stop token (real_len < K), and
    the stream equals the K=1 loop with the same eos. Uses the SAMPLED
    path so the stream varies (tiny greedy degenerates to one token) and
    the stop genuinely lands mid-window."""
    ex = _mk_solo(solo_setup)
    ref = _kstep_loop(ex, "r1", PROMPT, 12, 1, sampling=SAMPLING, seed=7)
    eos = ref[5]  # force a stop mid-stream (and mid-window for k=5/8)
    cut = ref.index(eos) + 1
    assert 1 < cut <= 6  # genuinely mid-stream
    got = _kstep_loop(ex, f"k{k}", PROMPT, 12, k, eos=eos,
                      sampling=SAMPLING, seed=7)
    assert got == ref[:cut]


@pytest.mark.parametrize("k", [4, 8])
def test_solo_kstep_sampled_parity(solo_setup, k):
    """Sampling parity for the on-device greedy/temperature path: a K-step
    window with a chained per-session key emits bit-identical tokens to K
    chained single-step dispatches."""
    ex = _mk_solo(solo_setup)
    ref = _kstep_loop(ex, "s1", PROMPT, 10, 1, sampling=SAMPLING, seed=7)
    got = _kstep_loop(ex, f"s{k}", PROMPT, 10, k, sampling=SAMPLING, seed=7)
    assert got == ref
    assert len(set(ref)) > 1  # the sampled stream actually varies


def test_solo_kstep_budget_clamp_and_overflow(solo_setup):
    """K falls back toward K=1 at the KV budget boundary; a frontier at
    max_len raises BufferError like the per-token path."""
    ex = _mk_solo(solo_setup, max_len=10)
    r = ex.process("s", {"tokens": [PROMPT], "start_pos": 0, "real_len": 4})
    tok = int(np.argmax(r["logits"][0]))
    rr = ex.process("s", {"tokens": [[tok]], "start_pos": 4,
                          "decode_steps": 16})
    assert rr["decode_steps"] == 6 and rr["real_len"] == 6
    with pytest.raises(BufferError):
        ex.process("s", {"tokens": [[1]], "start_pos": 10, "decode_steps": 4})


def test_solo_kstep_replay_rollback(solo_setup):
    """A replayed K-step chunk (client re-sent after a lost response)
    rolls the frontier back and recomputes the identical window."""
    ex = _mk_solo(solo_setup)
    ex.process("s", {"tokens": [PROMPT], "start_pos": 0, "real_len": 4})
    r1 = ex.process("s", {"tokens": [[5]], "start_pos": 4, "decode_steps": 4})
    r2 = ex.process("s", {"tokens": [[5]], "start_pos": 4, "decode_steps": 4})
    assert r1["tokens"] == r2["tokens"]
    with pytest.raises(ValueError, match="out-of-order"):
        ex.process("s", {"tokens": [[5]], "start_pos": 50, "decode_steps": 4})


def test_multistage_stage_rejects_kstep(solo_setup):
    """A pipeline stage (not whole-model) must reject decode_steps: the
    next token depends on the other stages."""
    import jax

    from inferd_tpu.config import TINY
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    spec0 = list(Manifest.even_split("tiny", 2).stage_specs())[0]
    ex = Qwen3StageExecutor(
        TINY, spec0, extract_stage_params(params, TINY, spec0), max_len=64
    )
    ex.process("s", {"tokens": [PROMPT], "start_pos": 0, "real_len": 4})
    with pytest.raises(ValueError, match="single-stage"):
        ex.process("s", {"tokens": [[1]], "start_pos": 4, "decode_steps": 4})


# ---------------------------------------------------------------------------
# batched executor (runtime/batch_executor.BatchedExecutor)
# ---------------------------------------------------------------------------


def test_batched_kstep_cobatch_token_exact(solo_setup):
    """Concurrent sessions' K-step windows FUSE into one K-step scan per
    flush, and every stream equals its solo-executor run (same on-device
    sampler, same key chains). Also asserts token-true stats: a K-step
    entry counts K tokens, not 1."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg, params, _spec, _sp = solo_setup
    prompts = {"a": [3, 7, 11, 19], "b": [5, 2], "c": [9, 9, 4]}
    steps, k = 9, 4

    refs = {}
    ex = _mk_solo(solo_setup)
    for i, (sid, p) in enumerate(prompts.items()):
        refs[sid] = _kstep_loop(ex, sid, p, steps, 1, sampling=SAMPLING,
                                seed=i)

    bx = BatchedExecutor(cfg, params, lanes=4, max_len=64, window_ms=30.0)
    state = {}
    for i, (sid, p) in enumerate(prompts.items()):
        r = bx.process(sid, {"tokens": [p], "start_pos": 0,
                             "real_len": len(p)})
        state[sid] = {"pos": len(p), "out": [int(np.argmax(r["logits"][0]))],
                      "key": None, "seed": i}
    while any(len(s["out"]) < steps for s in state.values()):
        results = {}

        def go(sid):
            s = state[sid]
            pl = {"tokens": [[s["out"][-1]]], "start_pos": s["pos"],
                  "real_len": 1,
                  "decode_steps": min(k, steps - len(s["out"])),
                  "sampling": SAMPLING, "seed": s["seed"]}
            if s["key"] is not None:
                pl["key"] = s["key"]
            results[sid] = bx.process(sid, pl)

        ths = [threading.Thread(target=go, args=(sid,)) for sid in prompts]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        for sid, rr in results.items():
            s = state[sid]
            s["out"].extend(int(x) for x in rr["tokens"][0])
            s["pos"] += rr["real_len"]
            s["key"] = rr["key"]
    for sid in prompts:
        assert state[sid]["out"] == refs[sid], sid
    st = bx.stats()
    # 3 sessions x 8 decode tokens = 24 tokens; token-true accounting
    # means batched_tokens counts them all even though far fewer K-step
    # DISPATCH entries were served
    assert st["batched_tokens"] == 24
    assert st["batched_steps"] < 24


def test_batched_kstep_interop_with_legacy_window(solo_setup):
    """A window mixing a classic logits-contract decode with K-step
    entries serves both: per-path dispatches under one device-lock hold,
    neither stream corrupted."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg, params, _spec, _sp = solo_setup
    bx = BatchedExecutor(cfg, params, lanes=4, max_len=64, window_ms=40.0)
    pa, pb = [3, 7, 11, 19], [5, 2]
    ra = bx.process("a", {"tokens": [pa], "start_pos": 0, "real_len": 4})
    rb = bx.process("b", {"tokens": [pb], "start_pos": 0, "real_len": 2})
    ta, tb = int(np.argmax(ra["logits"][0])), int(np.argmax(rb["logits"][0]))
    results = {}

    def legacy():
        results["a"] = bx.process(
            "a", {"tokens": [[ta]], "start_pos": 4, "real_len": 1}
        )

    def kstep():
        results["b"] = bx.process(
            "b", {"tokens": [[tb]], "start_pos": 2, "real_len": 1,
                  "decode_steps": 3}
        )

    ths = [threading.Thread(target=legacy), threading.Thread(target=kstep)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert "logits" in results["a"] and results["a"]["real_len"] == 1
    assert len(results["b"]["tokens"][0]) == 3

    # both sessions' streams stay exact vs solo greedy
    ex = _mk_solo(solo_setup)
    ref_a = _client_loop(ex, pa, 2)
    assert [ta, int(np.argmax(results["a"]["logits"][0]))] == ref_a
    ref_b = _kstep_loop(ex, "rb", pb, 4, 3)
    assert [tb] + [int(x) for x in results["b"]["tokens"][0]] == ref_b


def test_kstep_hi_not_overstated_on_early_eos(solo_setup):
    """The ring high-water mark after an eos-stopped K window covers the
    committed tokens plus the ONE frozen-frontier garbage slot — not the
    full K, which would spuriously trip the `hi - start_pos >
    RING_MARGIN` replay guard after an early stop."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor
    from inferd_tpu.runtime.executor import kstep_hi

    assert kstep_hi(10, 16, 16) == 26  # full window: k committed writes
    assert kstep_hi(10, 3, 16) == 14  # early eos: n committed + 1 garbage
    assert kstep_hi(10, 0, 4) == 11

    cfg, params, _spec, _sp = solo_setup
    ex = _mk_solo(solo_setup)
    ref = _client_loop(ex, PROMPT, 4)
    eos = ref[1]  # fires mid-window below
    bx = BatchedExecutor(cfg, params, lanes=2, max_len=64, window_ms=5.0)
    r = bx.process("s", {"tokens": [PROMPT], "start_pos": 0, "real_len": 4})
    t0 = int(np.argmax(r["logits"][0]))
    assert t0 == ref[0]
    rr = bx.process("s", {"tokens": [[t0]], "start_pos": 4, "real_len": 1,
                          "decode_steps": 8, "eos": eos})
    n = rr["real_len"]
    assert n < 8 and rr["tokens"][0][-1] == eos
    lane = bx._sessions["s"]
    assert bx._lane_hi[lane] == 4 + n + 1


def test_batched_kstep_group_failure_is_isolated(solo_setup):
    """Per-dispatch error isolation: a window holding two K-step sampling
    groups where one group's device dispatch dies must fail ONLY that
    group's sessions. The surviving group's results commit (and stay
    token-exact), and the dead group's lane frontier does not move, so the
    client's ordinary retry from its old frontier recovers the stream."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor

    cfg, params, _spec, _sp = solo_setup
    bx = BatchedExecutor(cfg, params, lanes=4, max_len=64, window_ms=40.0)
    pa, pb = [3, 7, 11, 19], [5, 2]
    ra = bx.process("a", {"tokens": [pa], "start_pos": 0, "real_len": 4})
    rb = bx.process("b", {"tokens": [pb], "start_pos": 0, "real_len": 2})
    ta, tb = int(np.argmax(ra["logits"][0])), int(np.argmax(rb["logits"][0]))

    real = bx.engine._decode_k_serve

    def boom(params, cache, toks, lengths, active, keys, eos, k, t, tk,
             tp, mp, ads=None):
        if t > 0:  # the sampled group dies BEFORE touching the device
            raise RuntimeError("injected group failure")
        return real(params, cache, toks, lengths, active, keys, eos, k, t,
                    tk, tp, mp, ads=ads)

    bx.engine._decode_k_serve = boom
    try:
        results, errors = {}, {}

        def greedy():
            results["a"] = bx.process(
                "a", {"tokens": [[ta]], "start_pos": 4, "real_len": 1,
                      "decode_steps": 3}
            )

        def sampled():
            try:
                bx.process(
                    "b", {"tokens": [[tb]], "start_pos": 2, "real_len": 1,
                          "decode_steps": 3, "sampling": SAMPLING,
                          "seed": 1}
                )
            except Exception as e:  # noqa: BLE001 -- the assertion target
                errors["b"] = e

        ths = [threading.Thread(target=greedy),
               threading.Thread(target=sampled)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert "injected group failure" in str(errors["b"])
        assert len(results["a"]["tokens"][0]) == 3
    finally:
        bx.engine._decode_k_serve = real

    # survivor stream stays token-exact vs solo
    ex = _mk_solo(solo_setup)
    ref_a = _kstep_loop(ex, "ra", pa, 4, 3)
    assert [ta] + [int(x) for x in results["a"]["tokens"][0]] == ref_a
    # the failed lane never advanced: a plain retry from the client's old
    # frontier completes and matches the solo reference
    r2 = bx.process(
        "b", {"tokens": [[tb]], "start_pos": 2, "real_len": 1,
              "decode_steps": 3}
    )
    ref_b = _kstep_loop(ex, "rb", pb, 4, 3)
    assert [tb] + [int(x) for x in r2["tokens"][0]] == ref_b
    # token-true stats survive the failure: only the 3 + 3 tokens the
    # surviving dispatches really served are counted, never the failed
    # group's entries
    assert bx.stats()["batched_tokens"] == 6


def test_batched_kstep_device_failure_poisons_window_clearly(solo_setup):
    """Per-dispatch isolation only holds for HOST-side failures. A
    dispatch that dies DEVICE-side after the jit donated the cache
    leaves the shared KV buffers deleted: the window must stop
    dispatching and fail the remaining groups with a clear 'KV cache
    invalidated' error instead of handing them dead buffers."""
    from inferd_tpu.runtime.batch_executor import BatchedExecutor
    from inferd_tpu.runtime.window import Entry

    cfg, params, _spec, _sp = solo_setup
    bx = BatchedExecutor(cfg, params, lanes=4, max_len=64, window_ms=5.0)
    pa, pb = [3, 7, 11, 19], [5, 2]
    ra = bx.process("a", {"tokens": [pa], "start_pos": 0, "real_len": 4})
    rb = bx.process("b", {"tokens": [pb], "start_pos": 0, "real_len": 2})
    ta, tb = int(np.argmax(ra["logits"][0])), int(np.argmax(rb["logits"][0]))

    def boom(params, cache, toks, lens, ads=None, **_ask):
        cache.k.delete()  # what a failed donating jit leaves behind
        raise RuntimeError("injected device failure")

    la, lb = bx._sessions["a"], bx._sessions["b"]
    ea = Entry((la, ta, None))
    ks = {"k": 3, "sampling": (0.0, 0, 1.0, 0.0), "eos": -1,
          "key": np.zeros(2, np.uint32)}
    eb = Entry((lb, tb, ks))
    bx.engine._decode_logits = boom
    # both wait in the window; the flush drains them under the device lock
    bx._batcher._pending.extend([ea, eb])
    bx._run_decode_batch([])
    assert "injected device failure" in str(ea.error)
    assert "KV cache invalidated" in str(eb.error)
    assert eb.result is None


# ---------------------------------------------------------------------------
# shared primitive sanity (models/qwen3.decode_k)
# ---------------------------------------------------------------------------


def test_decode_k_counts_eos_token_then_freezes(solo_setup):
    """Direct decode_k semantics: the stop token itself is emitted and
    counted; subsequent steps freeze the row (n_new stops advancing) and
    its key chain keeps the documented always-split schedule."""
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import TINY
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg, params, _spec, _sp = solo_setup
    cache = KVCache.create(cfg, cfg.num_layers, 1, 32)
    # prefill via the model forward to establish a frontier
    toks = jnp.asarray([PROMPT], jnp.int32)
    _logits, nc, _ = qwen3.forward_cached(
        params, cfg, toks, None, cache, jnp.int32(0), real_end=4
    )
    import dataclasses

    cache = dataclasses.replace(nc, length=jnp.int32(4))
    lengths = jnp.asarray([4], jnp.int32)
    k = 6
    # greedy, no eos: full window commits
    c2, seq, n_new, _keys, _l, _t, _tl = qwen3.decode_k(
        params, cfg, jnp.asarray([PROMPT[-1]], jnp.int32), cache, lengths,
        jnp.ones((1,), bool), jnp.zeros((1, 2), jnp.uint32), k,
    )
    assert int(n_new[0]) == k
    stream = [int(x) for x in np.asarray(seq)[:, 0]]
    # rerun with eos = the 3rd emitted token: n_new stops there
    eos = stream[2]
    c3, seq2, n_new2, _k2, _l2, _t2, _tl2 = qwen3.decode_k(
        params, cfg, jnp.asarray([PROMPT[-1]], jnp.int32), c2, lengths,
        jnp.ones((1,), bool), jnp.zeros((1, 2), jnp.uint32), k,
        eos=jnp.int32(eos),
    )
    expect = stream.index(eos) + 1  # first occurrence stops the row
    assert int(n_new2[0]) == expect
    assert [int(x) for x in np.asarray(seq2)[:expect, 0]] == stream[:expect]
