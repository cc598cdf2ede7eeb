"""Stage-level continuous batching: runtime/stage_batch (lane-slotted
multi-session stage executor), runtime/window's drain/gang continuous-
batching mode, and the node-level arrival window with coalesced relay.

The contract under test everywhere: co-batching decode steps of
concurrent sessions must NEVER change what any session decodes — every
path is asserted token-exact against the solo (batch-of-one) pipeline.
"""

import threading
import time

import numpy as np
import pytest

from inferd_tpu.runtime.window import WindowedBatcher

# ---------------------------------------------------------------------------
# WindowedBatcher: invalidate / drain / gang (no jax, no model)
# ---------------------------------------------------------------------------


def _direct_flush(run=None):
    """run_batch that serves entries in place (classic mode)."""
    seen = []

    def run_batch(entries):
        for e in entries:
            seen.append(e.payload)
            e.result = ("ok", e.payload)
        if run:
            run(entries)

    return run_batch, seen


def test_invalidate_fails_waiting_entry_fast():
    """A session torn down while its entry is still WAITING in the window
    fails fast with the teardown error and never reaches run_batch — the
    freed lane's next owner can never race a stale write."""
    run_batch, seen = _direct_flush()
    b = WindowedBatcher(0.05, run_batch, co_possible=lambda: True)

    results = {}

    def submit(tag):
        try:
            results[tag] = b.submit((tag, "payload"))
        except Exception as e:
            results[tag] = e

    t1 = threading.Thread(target=submit, args=("a",))
    t1.start()  # becomes the flusher, sleeps the 50 ms window
    time.sleep(0.01)
    t2 = threading.Thread(target=submit, args=("b",))
    t2.start()  # waiter
    time.sleep(0.01)
    err = ValueError("session b ended mid-request")
    t0 = time.monotonic()
    b.invalidate(lambda p: p[0] == "b", err)
    t1.join(timeout=5)
    t2.join(timeout=5)
    assert time.monotonic() - t0 < 2.0  # fail-fast, not wait_timeout_s
    assert results["b"] is err
    assert results["a"] == ("ok", ("a", "payload"))
    # the invalidated entry never executed
    assert ("b", "payload") not in seen


def test_invalidated_entry_skipped_even_when_flushers_own():
    """Invalidating the FLUSHER's own entry mid-window: the flusher must
    raise the teardown error, and run_batch must not see the entry."""
    run_batch, seen = _direct_flush()
    b = WindowedBatcher(0.05, run_batch, co_possible=lambda: True)
    got = {}

    def submit():
        try:
            got["r"] = b.submit(("a", 1))
        except Exception as e:
            got["r"] = e

    t = threading.Thread(target=submit)
    t.start()
    time.sleep(0.01)
    err = ValueError("session a ended mid-request")
    b.invalidate(lambda p: p[0] == "a", err)
    t.join(timeout=5)
    assert got["r"] is err and seen == []


def _drain_flush(b_ref, record):
    """swap_in_run-mode run_batch: drains the pending list itself and owns
    result + event delivery for every drained entry (the node contract)."""

    def run_batch(entries):
        assert entries == []  # swap_in_run always passes an empty list
        drained = b_ref[0].drain_pending()
        record.append([e.payload for e in drained])
        for e in drained:
            e.result = ("ok", e.payload)
            e.event.set()

    return run_batch


def test_swap_in_run_drain_serves_all_pending():
    record = []
    b_ref = [None]
    b = WindowedBatcher(
        0.03, _drain_flush(b_ref, record), co_possible=lambda: True,
        swap_in_run=True,
    )
    b_ref[0] = b
    results = {}

    def submit(tag):
        results[tag] = b.submit((tag,))

    ts = [threading.Thread(target=submit, args=(t,)) for t in "abc"]
    for t in ts:
        t.start()
        time.sleep(0.002)
    for t in ts:
        t.join(timeout=5)
    assert results == {t: ("ok", (t,)) for t in "abc"}
    # everything pending was folded into the drains; nothing was dropped
    assert sorted(p for batch in record for (p,) in batch) == ["a", "b", "c"]
    assert b.stats()["batched_tokens"] == 3


def test_swap_in_run_invalidate_still_fails_fast():
    """invalidate in drain mode: the entry leaves the pending list before
    any drain, and its submitter raises the teardown error."""
    record = []
    b_ref = [None]
    b = WindowedBatcher(
        0.05, _drain_flush(b_ref, record), co_possible=lambda: True,
        swap_in_run=True,
    )
    b_ref[0] = b
    got = {}

    def submit(tag):
        try:
            got[tag] = b.submit((tag,))
        except Exception as e:
            got[tag] = e

    t1 = threading.Thread(target=submit, args=("a",))
    t1.start()
    time.sleep(0.01)
    err = ValueError("session a ended mid-request")
    b.invalidate(lambda p: p[0] == "a", err)
    t1.join(timeout=5)
    assert got["a"] is err
    assert all(("a",) not in batch for batch in record)


def test_gang_wait_flushes_early_at_target():
    """With a gang target, the flusher must flush as soon as the target
    count is pending — well before the (deliberately long) window cap."""
    record = []
    b_ref = [None]
    b = WindowedBatcher(
        5.0, _drain_flush(b_ref, record), co_possible=lambda: True,
        swap_in_run=True, gang_target=lambda: 2,
    )
    b_ref[0] = b
    results = {}

    def submit(tag):
        results[tag] = b.submit((tag,))

    t0 = time.monotonic()
    t1 = threading.Thread(target=submit, args=("a",))
    t2 = threading.Thread(target=submit, args=("b",))
    t1.start()
    time.sleep(0.01)
    t2.start()
    t1.join(timeout=10)
    t2.join(timeout=10)
    assert time.monotonic() - t0 < 2.0  # gang met -> no 5 s window
    assert results == {"a": ("ok", ("a",)), "b": ("ok", ("b",))}
    assert record and len(record[0]) == 2  # ONE co-batch of both


# ---------------------------------------------------------------------------
# BatchedStageExecutor: co-batched parity with the solo stage pipeline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stage_setup():
    import jax

    from inferd_tpu.config import TINY
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, extract_stage_params

    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    manifest = Manifest.even_split("tiny", 2)
    specs = list(manifest.stage_specs())
    sp = [extract_stage_params(params, TINY, s) for s in specs]
    return TINY, params, specs, sp


def _solo_chain(cfg, specs, sp, prompt, steps):
    """Reference stream: batch-of-one stage executors, greedy."""
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    e0 = Qwen3StageExecutor(cfg, specs[0], sp[0], max_len=64)
    e1 = Qwen3StageExecutor(cfg, specs[1], sp[1], max_len=64)
    r0 = e0.process("r", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    r1 = e1.process("r", {"hidden": r0["hidden"], "start_pos": 0, "real_len": len(prompt)})
    out = [int(np.argmax(r1["logits"][0]))]
    pos = len(prompt)
    for _ in range(steps - 1):
        r0 = e0.process("r", {"tokens": [[out[-1]]], "start_pos": pos, "real_len": 1})
        r1 = e1.process("r", {"hidden": r0["hidden"], "start_pos": pos, "real_len": 1})
        out.append(int(np.argmax(r1["logits"][0])))
        pos += 1
    return out


def test_cobatch_matches_solo_mixed_positions(stage_setup):
    """Sessions at DIFFERENT positions co-batch into one device step per
    stage and each stream equals its solo run, token for token."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, _params, specs, sp = stage_setup
    b0 = BatchedStageExecutor(cfg, specs[0], sp[0], lanes=4, max_len=64)
    b1 = BatchedStageExecutor(cfg, specs[1], sp[1], lanes=4, max_len=64)
    prompts = {"x": [3, 7, 11, 19], "y": [5, 2], "z": [9, 9, 4]}
    steps = 5
    state = {}
    for sid, p in prompts.items():
        r0 = b0.process(sid, {"tokens": [p], "start_pos": 0, "real_len": len(p)})
        r1 = b1.process(sid, {"hidden": r0["hidden"], "start_pos": 0, "real_len": len(p)})
        state[sid] = {"pos": len(p), "out": [int(np.argmax(r1["logits"][0]))]}
    for _ in range(steps - 1):
        items0 = [
            (sid, {"tokens": [[state[sid]["out"][-1]]],
                   "start_pos": state[sid]["pos"], "real_len": 1})
            for sid in prompts
        ]
        outs0 = b0.process_batch(items0)
        assert not any(isinstance(o, Exception) for o in outs0)
        items1 = [
            (sid, {"hidden": o["hidden"], "start_pos": state[sid]["pos"],
                   "real_len": 1})
            for (sid, _), o in zip(items0, outs0)
        ]
        outs1 = b1.process_batch(items1)
        for (sid, _), o in zip(items1, outs1):
            state[sid]["out"].append(int(np.argmax(o["logits"][0])))
            state[sid]["pos"] += 1
    assert b0.stats()["batched_steps"] == steps - 1  # truly ONE step per round
    assert b0.stats()["mean_batch"] == 3.0
    for sid, p in prompts.items():
        assert state[sid]["out"] == _solo_chain(cfg, specs, sp, p, steps), sid


def test_per_item_rejection_does_not_fail_cobatch(stage_setup):
    """A stale/unknown session in the window 409s alone; its co-batch
    still decodes correctly (per-item errors, never batch-wide)."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, _params, specs, sp = stage_setup
    b0 = BatchedStageExecutor(cfg, specs[0], sp[0], lanes=4, max_len=64)
    p = [3, 7, 11, 19]
    b0.process("live", {"tokens": [p], "start_pos": 0, "real_len": len(p)})
    outs = b0.process_batch([
        ("live", {"tokens": [[1]], "start_pos": len(p), "real_len": 1}),
        ("ghost", {"tokens": [[1]], "start_pos": 9, "real_len": 1}),
    ])
    assert isinstance(outs[1], ValueError)  # unknown session -> 409 class
    assert not isinstance(outs[0], Exception)
    assert outs[0]["hidden"].shape[:2] == (1, 1)


def test_session_end_mid_window_fails_fast_and_lane_is_reusable(stage_setup):
    """The acceptance scenario: a session ends while its decode entry is
    still waiting in the window. The entry fails fast with the teardown
    error (never a stale write), and the freed lane serves a NEW session
    with a correct stream."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, _params, specs, sp = stage_setup
    ex = BatchedStageExecutor(cfg, specs[0], sp[0], lanes=2, max_len=64)

    # node-style wiring (runtime/node._attach_window)
    def run_batch(entries):
        assert entries == []
        drained = ex.window.drain_pending()
        outs = ex.process_batch([(e.payload[0], e.payload[1]) for e in drained])
        for e, o in zip(drained, outs):
            if isinstance(o, Exception):
                e.error = o
            else:
                e.result = o
            e.event.set()

    ex.window = WindowedBatcher(
        1.0, run_batch, co_possible=ex.co_possible, swap_in_run=True,
        gang_target=ex.gang_target,
    )
    ex.on_drop = lambda sid: ex.window.invalidate(
        lambda payload, _sid=sid: payload[0] == _sid,
        ValueError(f"session {sid} ended mid-request"),
    )

    prompt = [3, 7, 11, 19]
    ex.process("a", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    # a second live-but-idle session makes co_possible true AND keeps the
    # gang target at 2, so the flusher genuinely WAITS in the (1 s)
    # window — the interval where the teardown must catch the entry
    ex.process("b", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    got = {}

    def submit():
        try:
            got["r"] = ex.window.submit(
                ("a", {"tokens": [[1]], "start_pos": len(prompt), "real_len": 1})
            )
        except Exception as e:
            got["r"] = e

    t0 = time.monotonic()
    t = threading.Thread(target=submit)
    t.start()
    time.sleep(0.05)
    ex.end_session("a")  # -> on_drop -> invalidate pending entry
    t.join(timeout=10)
    assert time.monotonic() - t0 < 0.9  # failed FAST, not at the window cap
    assert isinstance(got["r"], ValueError)
    assert "ended mid-request" in str(got["r"])
    assert "a" not in ex
    # the freed lane serves a fresh session with the exact solo stream
    out = ex.process("c", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    step = ex.process("c", {"tokens": [[5]], "start_pos": len(prompt), "real_len": 1})
    assert out["hidden"].shape[1] == len(prompt)
    assert step["hidden"].shape[:2] == (1, 1)
    assert len(ex) == 2 and "c" in ex and "b" in ex


def test_replay_rollback_and_overflow(stage_setup):
    """Decode replay (client re-sent after a lost response) recomputes
    token-exactly; overflow past max_len raises BufferError."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, _params, specs, sp = stage_setup
    ex = BatchedStageExecutor(cfg, specs[0], sp[0], lanes=2, max_len=64)
    prompt = [3, 7, 11, 19]
    ex.process("s", {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
    r1 = ex.process("s", {"tokens": [[5]], "start_pos": len(prompt), "real_len": 1})
    # replay the same step (frontier rolled back, recomputed identically)
    r2 = ex.process("s", {"tokens": [[5]], "start_pos": len(prompt), "real_len": 1})
    np.testing.assert_array_equal(r1["hidden"], r2["hidden"])
    with pytest.raises(ValueError, match="out-of-order"):
        ex.process("s", {"tokens": [[5]], "start_pos": 50, "real_len": 1})
    with pytest.raises(BufferError):
        ex.process("s", {"tokens": [[0] * 60], "start_pos": len(prompt) + 1,
                         "real_len": 60})


# ---------------------------------------------------------------------------
# Multi-step fused decode on the stage-batch executor (single-stage swarm)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def single_stage_setup():
    import jax

    from inferd_tpu.config import TINY
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import StageSpec, extract_stage_params

    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    spec = StageSpec(0, 1, 0, TINY.num_layers - 1)
    sp = extract_stage_params(params, TINY, spec)
    return TINY, spec, sp


_SAMP = {"temperature": 0.8, "top_k": 8, "top_p": 0.95}


def _solo_kstep(cfg, spec, sp, prompt, steps, seed):
    """Reference stream: the solo executor's K=1 on-device sampled loop."""
    from inferd_tpu.runtime.executor import Qwen3StageExecutor

    ex = Qwen3StageExecutor(cfg, spec, sp, max_len=64)
    r = ex.process("r", {"tokens": [prompt], "start_pos": 0,
                         "real_len": len(prompt)})
    out = [int(np.argmax(r["logits"][0]))]
    pos = len(prompt)
    key = None
    while len(out) < steps:
        pl = {"tokens": [[out[-1]]], "start_pos": pos, "decode_steps": 1,
              "sampling": _SAMP, "seed": seed}
        if key is not None:
            pl["key"] = key
        rr = ex.process("r", pl)
        out.extend(int(x) for x in rr["tokens"][0])
        pos += rr["real_len"]
        key = rr["key"]
    return out


def test_stage_batch_kstep_cobatch_token_exact(single_stage_setup):
    """Co-batched lanes decode K steps per window in ONE fused scan, and
    every session's sampled stream equals its solo K=1 run, token for
    token. Per-dispatch accounting counts K tokens per lane (satellite:
    truthful tok/s), and the group K is the MINIMUM of the window's
    budget-clamped requests."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, spec, sp = single_stage_setup
    prompts = {"x": [3, 7, 11, 19], "y": [5, 2], "z": [9, 9, 4]}
    steps, K = 9, 4
    refs = {
        sid: _solo_kstep(cfg, spec, sp, p, steps, i)
        for i, (sid, p) in enumerate(prompts.items())
    }
    bx = BatchedStageExecutor(cfg, spec, sp, lanes=4, max_len=64)
    state = {}
    for i, (sid, p) in enumerate(prompts.items()):
        r = bx.process(sid, {"tokens": [p], "start_pos": 0,
                             "real_len": len(p)})
        state[sid] = {"pos": len(p), "out": [int(np.argmax(r["logits"][0]))],
                      "key": None, "seed": i}
    rounds = 0
    while any(len(s["out"]) < steps for s in state.values()):
        items = []
        for sid, s in state.items():
            pl = {"tokens": [[s["out"][-1]]], "start_pos": s["pos"],
                  "real_len": 1,
                  "decode_steps": min(K, steps - len(s["out"])),
                  "sampling": _SAMP, "seed": s["seed"]}
            if s["key"] is not None:
                pl["key"] = s["key"]
            items.append((sid, pl))
        outs = bx.process_batch(items)
        rounds += 1
        for (sid, _), rr in zip(items, outs):
            assert not isinstance(rr, Exception), rr
            assert rr["real_len"] == len(rr["tokens"][0])
            s = state[sid]
            s["out"].extend(int(x) for x in rr["tokens"][0])
            s["pos"] += rr["real_len"]
            s["key"] = rr["key"]
    for sid in prompts:
        assert state[sid]["out"] == refs[sid], sid
    st = bx.stats()
    assert rounds == 2  # 8 decode tokens per lane at K=4
    assert st["batched_steps"] == rounds  # ONE fused dispatch per window
    assert st["batched_tokens"] == 3 * (steps - 1)  # token-true accounting


def test_stage_batch_kstep_replay_rollback_interaction(single_stage_setup):
    """The replay-rollback protocol survives K-step windows: after a
    window advanced a lane by K, a re-sent chunk starting inside that
    window rolls the frontier back and the re-decoded window is
    IDENTICAL (deterministic forward + same key), and a later chunk at
    the new frontier continues the stream exactly."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, spec, sp = single_stage_setup
    bx = BatchedStageExecutor(cfg, spec, sp, lanes=2, max_len=64)
    p = [3, 7, 11, 19]
    bx.process("s", {"tokens": [p], "start_pos": 0, "real_len": len(p)})
    pl = {"tokens": [[5]], "start_pos": 4, "real_len": 1, "decode_steps": 4,
          "sampling": _SAMP, "seed": 3}
    r1 = bx.process_batch([("s", pl)])[0]
    assert r1["real_len"] == 4
    # replay the SAME window (lost response): frontier rolls back 4 and
    # the recomputed tokens match bit for bit
    r2 = bx.process_batch([("s", pl)])[0]
    assert r2["tokens"] == r1["tokens"] and r2["key"] == r1["key"]
    # continue from the replayed frontier; mixed window with another lane
    bx.process("t", {"tokens": [p], "start_pos": 0, "real_len": len(p)})
    nxt = {"tokens": [[r2["tokens"][0][-1]]], "start_pos": 8, "real_len": 1,
           "decode_steps": 4, "sampling": _SAMP, "seed": 3, "key": r2["key"]}
    r3 = bx.process_batch([("s", nxt)])[0]
    assert not isinstance(r3, Exception) and r3["real_len"] == 4
    # out-of-order (past the frontier) still rejects
    bad = dict(nxt, start_pos=50)
    out = bx.process_batch([("s", bad)])[0]
    assert isinstance(out, ValueError)


def test_stage_batch_kstep_stop_token_and_budget(single_stage_setup):
    """Per-lane eos fires mid-window (only that lane truncates; co-lanes
    fill their K), and a lane near max_len clamps the whole group's K to
    its budget (falling back toward K=1 at the boundary)."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, spec, sp = single_stage_setup
    # budget: max_len 16; lane a at 4 (12 left), lane b at 2 (14 left)
    bx = BatchedStageExecutor(cfg, spec, sp, lanes=2, max_len=16)
    bx.process("a", {"tokens": [[3, 7, 11, 19]], "start_pos": 0, "real_len": 4})
    bx.process("b", {"tokens": [[5, 2]], "start_pos": 0, "real_len": 2})
    outs = bx.process_batch([
        ("a", {"tokens": [[1]], "start_pos": 4, "real_len": 1,
               "decode_steps": 50}),
        ("b", {"tokens": [[2]], "start_pos": 2, "real_len": 1,
               "decode_steps": 50}),
    ])
    assert outs[0]["decode_steps"] == 12 and outs[1]["decode_steps"] == 12

    # eos: find a token the reference stream emits mid-way, then rerun
    # with it as lane "e"'s stop token while lane "f" keeps decoding
    bx2 = BatchedStageExecutor(cfg, spec, sp, lanes=2, max_len=64)
    p = [3, 7, 11, 19]
    ref = _solo_kstep(cfg, spec, sp, p, 9, 5)
    eos = ref[4]
    cut = ref.index(eos) + 1
    bx2.process("e", {"tokens": [p], "start_pos": 0, "real_len": 4})
    bx2.process("f", {"tokens": [p], "start_pos": 0, "real_len": 4})
    outs = bx2.process_batch([
        ("e", {"tokens": [[ref[0]]], "start_pos": 4, "real_len": 1,
               "decode_steps": 8, "sampling": _SAMP, "seed": 5, "eos": eos}),
        ("f", {"tokens": [[ref[0]]], "start_pos": 4, "real_len": 1,
               "decode_steps": 8, "sampling": _SAMP, "seed": 5}),
    ])
    assert [ref[0]] + outs[0]["tokens"][0] == ref[:cut]  # stopped at eos
    assert outs[1]["real_len"] == 8  # co-lane unaffected by e's stop


def test_stage_batch_dispatch_failure_is_isolated(single_stage_setup):
    """Failure isolation is per DISPATCH in a mixed window: a raising
    K-step group must not fail the legacy step or the OTHER sampling
    group, and a raising legacy step must not fail the K-step groups.
    The failed lane's frontier never advances, so a plain retry
    recovers."""
    from inferd_tpu.runtime.stage_batch import BatchedStageExecutor

    cfg, spec, sp = single_stage_setup
    bx = BatchedStageExecutor(cfg, spec, sp, lanes=4, max_len=64)
    p = [3, 7, 11, 19]
    for sid in ("L", "g", "s"):
        bx.process(sid, {"tokens": [p], "start_pos": 0, "real_len": 4})

    real_k, real_legacy = bx._decode_k_all, bx._decode_all

    def boom_k(params, cache, toks, lengths, active, keys, eos, k, t, tk,
               tp, mp, ads=None):
        if t > 0:  # only the sampled group dies, before touching device
            raise RuntimeError("injected kstep group failure")
        return real_k(params, cache, toks, lengths, active, keys, eos, k,
                      t, tk, tp, mp, ads=ads)

    items = [
        ("L", {"tokens": [[1]], "start_pos": 4, "real_len": 1}),
        ("g", {"tokens": [[1]], "start_pos": 4, "real_len": 1,
               "decode_steps": 3}),
        ("s", {"tokens": [[1]], "start_pos": 4, "real_len": 1,
               "decode_steps": 3, "sampling": _SAMP, "seed": 2}),
    ]
    bx._decode_k_all = boom_k
    try:
        outs = bx.process_batch(items)
    finally:
        bx._decode_k_all = real_k
    assert "logits" in outs[0]  # legacy step survived
    assert len(outs[1]["tokens"][0]) == 3  # greedy group survived
    assert isinstance(outs[2], RuntimeError)  # only the sampled group died
    # the failed lane never advanced: the same request now succeeds
    r = bx.process_batch([items[2]])[0]
    assert not isinstance(r, Exception) and r["real_len"] == 3

    # converse: a dying legacy dispatch leaves the K-step group healthy
    def boom_legacy(*a, **kw):
        raise RuntimeError("injected legacy failure")

    items2 = [
        ("L", {"tokens": [[2]], "start_pos": 5, "real_len": 1}),
        ("g", {"tokens": [[outs[1]["tokens"][0][-1]]], "start_pos": 7,
               "real_len": 1, "decode_steps": 2}),
    ]
    bx._decode_all = boom_legacy
    try:
        outs2 = bx.process_batch(items2)
    finally:
        bx._decode_all = real_legacy
    assert isinstance(outs2[0], RuntimeError)
    assert len(outs2[1]["tokens"][0]) == 2


# ---------------------------------------------------------------------------
# Node e2e: 2-stage swarm, concurrent sessions, coalesced relay
# ---------------------------------------------------------------------------

from conftest import port_block  # noqa: E402

PORTS = port_block(__file__)


def _mk_node(idx, stage, parts, bootstrap_idx, lanes=8, window_ms=10.0):
    from inferd_tpu.config import TINY
    from inferd_tpu.control.dht import SwarmDHT
    from inferd_tpu.runtime.node import Node, NodeInfo

    info = NodeInfo(
        name=f"n{idx}", host="127.0.0.1", port=PORTS.http(idx), stage=stage,
        num_stages=2, capacity=16, model_name="tiny",
    )
    dht = SwarmDHT(
        info.node_id, PORTS.gossip(idx),
        bootstrap=(
            [("127.0.0.1", PORTS.gossip(bootstrap_idx))]
            if idx != bootstrap_idx else []
        ),
        host="127.0.0.1", gossip_period_s=0.05, ttl_s=1.5,
    )
    return Node(
        info, TINY, parts, dht, backend="qwen3", max_len=64,
        rebalance_period_s=600.0, stage_lanes=lanes, window_ms=window_ms,
    )


@pytest.fixture(scope="module")
def tiny_parts(tmp_path_factory):
    import jax

    from inferd_tpu.config import TINY
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import Manifest, split_and_save

    parts = tmp_path_factory.mktemp("parts")
    params = qwen3.init_params(TINY, jax.random.PRNGKey(0))
    split_and_save(params, TINY, Manifest.even_split("tiny", 2), str(parts))
    return str(parts), params


@pytest.mark.asyncio
async def test_swarm_cobatch_token_exact_e2e(tiny_parts):
    """The tentpole, end to end: 8 concurrent sessions (mixed prompt
    lengths -> mixed positions in every co-batch; mixed budgets -> some
    sessions END mid-window while others continue) through a 2-stage
    --stage-lanes swarm. Every stream must equal the single-process
    engine token for token, decode steps must actually co-batch, and
    same-hop co-batches must relay as coalesced multi envelopes."""
    import asyncio

    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.config import TINY, SamplingConfig
    from inferd_tpu.core.generate import Engine

    parts, params = tiny_parts
    nodes = [_mk_node(i, i, parts, 0) for i in range(2)]
    for n in nodes:
        await n.start()
    for _ in range(100):
        if all(all(n.dht.get_all(2)[s] for s in range(2)) for n in nodes):
            break
        await asyncio.sleep(0.05)
    try:
        engine = Engine(
            TINY, params, max_len=64,
            sampling_cfg=SamplingConfig(temperature=0.0),
        )
        # mixed lengths AND mixed budgets: session i ends after 3 + i % 5
        # tokens, so early finishers end mid-window for the others
        prompts = [
            [3, 7, 11, 19], [5, 2], [9, 9, 4], [1, 2, 3, 4, 5],
            [8, 8], [4, 4, 4], [17], [6, 5, 4, 3],
        ]
        budgets = [3 + i % 5 for i in range(len(prompts))]
        async with SwarmClient(
            [("127.0.0.1", PORTS.http(0))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            outs = await asyncio.gather(*(
                c.generate_ids(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)
            ))
        for p, b, got in zip(prompts, budgets, outs):
            assert got == engine.generate(p, max_new_tokens=b), p

        # decode steps actually co-batched on both stages
        for n in nodes:
            st = n.executor.stats()
            assert st["mode"] == "stage_batched"
            assert st["batched_steps"] >= 1
        assert nodes[0].executor.stats()["mean_batch"] > 1.0

        # the common-hop windows relayed as ONE coalesced envelope and the
        # downstream node decoded the multi form
        m0 = nodes[0].metrics.snapshot()["counters"]
        m1 = nodes[1].metrics.snapshot()["counters"]
        assert m0.get("hop.coalesced", 0) >= 1
        assert m1.get("forward.multi_envelopes", 0) == m0.get("hop.coalesced")
        assert m1.get("forward.multi_frames", 0) == m0.get(
            "hop.coalesced_sessions"
        )
        assert not m0.get("hop.coalesced_fallback")

        # observability: the co-batch histogram + gauge export at /metrics
        import aiohttp

        from inferd_tpu.obs.export import validate_exposition

        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{PORTS.http()}/metrics") as r:
                text = await r.text()
        assert r.status == 200
        validate_exposition(text)
        assert "inferd_window_cobatch_bucket" in text
        assert "inferd_window_mean_cobatch" in text

        # and the window phase landed in the span ring
        import json as jsonlib

        phases = {
            jsonlib.loads(line).get("phase")
            for line in nodes[0].tracer.jsonl_lines()
        }
        assert "window" in phases
    finally:
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass


@pytest.mark.asyncio
async def test_swarm_chain_mode_cobatch_no_relay(tiny_parts):
    """Chain mode (relay=False, the client carries activations) through
    stage-lanes nodes: decode steps still co-batch per stage, responses
    return directly (no coalesced relay involved), streams stay exact."""
    import asyncio

    from inferd_tpu.client.chain_client import ChainClient
    from inferd_tpu.config import TINY, SamplingConfig
    from inferd_tpu.core.generate import Engine

    parts, params = tiny_parts
    nodes = [_mk_node(10 + i, i, parts, 10) for i in range(2)]
    for n in nodes:
        await n.start()
    for _ in range(100):
        if all(all(n.dht.get_all(2)[s] for s in range(2)) for n in nodes):
            break
        await asyncio.sleep(0.05)
    try:
        engine = Engine(
            TINY, params, max_len=64,
            sampling_cfg=SamplingConfig(temperature=0.0),
        )
        prompts = [[3, 7, 11, 19], [5, 2], [9, 9, 4]]
        async with ChainClient(
            [("127.0.0.1", PORTS.http(10)), ("127.0.0.1", PORTS.http(11))],
            sampling=SamplingConfig(temperature=0.0),
        ) as c:
            outs = await asyncio.gather(*(
                c.generate_ids(p, max_new_tokens=4) for p in prompts
            ))
        for p, got in zip(prompts, outs):
            assert got == engine.generate(p, max_new_tokens=4), p
        assert nodes[0].metrics.snapshot()["counters"].get(
            "hop.coalesced", 0
        ) == 0  # chain mode: nothing to relay
    finally:
        for n in nodes:
            try:
                await n.stop()
            except Exception:
                pass
