"""One step ahead (docs/SERVING.md "One step ahead"): the lane executor runs
the row of a session's NEXT hop before that hop arrives, where the hop before
it promised one (`ahead` in a decode hop's ask), from the token and key the
step before left on the device.

Held here, on the executor itself (tiny presets, CPU, no node): the stream is
the one the hops give without the promise, token for token, key for key, with
the log-probabilities, for every layout a dense lane has; no row is run past a
request's budget; whatever is not the promised hop drops the row (or, on a
lane with a recurrent state, is refused) and nobody reads it; a newcomer, a
lone session and a late one are served; and every other path runs no row
ahead. A model generated by blocks (`tiny-sdar`) is held to the same: its hops
carry a block, its `block` call the promise, and a row run ahead is a block."""

import dataclasses
import threading

import jax
import numpy as np
import pytest

from inferd_tpu.config import get_config
from inferd_tpu.models import qwen3
from inferd_tpu.runtime.batch_executor import BatchedExecutor

PROMPT = [3, 7, 11, 19, 23]
OTHER = [5, 13, 17, 41]
NEW = 9
GREEDY = {}
SAMPLED = {"temperature": 0.9, "top_k": 12}
# a dense lane's layouts: keys and values per head (heads as wide as a tile),
# one row a token, latents, rings beside a slab, a recurrent state
LAYOUTS = {"heads": "tiny-wide", "rows": "tiny", "latent": "tiny-dsv2",
           "ring": "tiny-afmoe", "state": "tiny-granite-h",
           # the mesh executor (runtime/mesh_executor.py) over pp=2: slots for
           # lanes, the lengths on the devices; uniform slabs, and rings
           "mesh": "mesh:tiny", "mesh_ring": "mesh:tiny-gemma2"}
KINDS = {"lanes": "tiny", "mesh": "mesh:tiny"}  # the two executors, one model


def _config(model):
    if model == "tiny-wide":
        return dataclasses.replace(get_config("tiny"), name=model, head_dim=128)
    return get_config(model)


_made = {}


def _executor(model, **kw):
    """A fresh executor over one set of weights a model (made once):
    `mesh:<model>` a MeshExecutor of as many slots as lanes were asked."""
    mesh, model = model.startswith("mesh:"), model.removeprefix("mesh:")
    cfg = _config(model)
    if model not in _made:
        _made[model] = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    kw.setdefault("lanes", 3)
    kw.setdefault("max_len", 64)
    if not mesh:
        return BatchedExecutor(cfg, _made[model], **kw)
    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    ex = MeshExecutor(cfg, _made[model], MeshPlan(pp=2), num_slots=kw["lanes"],
                      max_len=kw["max_len"], devices=jax.devices()[:2])
    # the MESH engine's programs are still jitted closures of an instance
    # (ROADMAP.md D18: `PipelinedEngine`): a fresh engine of the same shapes
    # takes the first one's, compiled once a file. The lanes need nothing:
    # core.batch.lane_programs makes theirs once a configuration.
    first = _made.setdefault(("programs", model, kw["lanes"], kw["max_len"]), ex.engine)
    for name in ("_step_raw", "_step_raw_multi", "_fork_slot", "_feed"):
        setattr(ex.engine, name, getattr(first, name))
    return ex


def _lane(ex, sid):
    """The lane, or the mesh's slot, that holds session `sid`."""
    return ex._sessions[sid] if hasattr(ex, "_sessions") else ex.sessions._slots[sid]


def _length(ex, sid):
    """The length of `sid`'s lane as the host holds it. On the mesh the
    lengths live on the devices and a row run ahead has moved them: they
    must read the host's mirror, plus that row where one is unclaimed."""
    lane = _lane(ex, sid)
    if hasattr(ex, "_sessions"):
        return ex.engine.lengths[lane]
    assert ex.engine.slot_length(lane) == ex._session_len[sid] + (lane in ex._ahead)
    return ex._session_len[sid]


def _hi(ex, sid):
    """How far `sid`'s rings count as written."""
    return ex._lane_hi[_lane(ex, sid)] if hasattr(ex, "_sessions") else ex._ring_hi.get(sid)


class Session:
    """The generation loop's hops (client/base.py `_generate_once`), one
    call a hop, so that a test can stop between two."""

    def __init__(self, ex, sid, prompt=PROMPT, new=NEW, ahead=True, sampling=GREEDY,
                 seed=0, top=0, eos=None):
        self.ex, self.sid, self.new, self.ahead = ex, sid, new, ahead
        self.ask = {"sampling": sampling, "top_logprobs": top}
        if eos is not None:
            self.ask["eos"] = eos
        self.eos, self.chain = eos, {"seed": seed}
        res = ex.process(sid, {"tokens": [prompt], "start_pos": 0, "real_len": len(prompt)})
        self.pos = len(prompt)
        self.out, self.lps, self.tops, self.keys = [int(np.argmax(res["logits"][0]))], [], [], []

    def payload(self, tok=None, pos=None):
        more = {"ahead": self.new - len(self.out) - 1} if self.ahead else {}
        return {"tokens": [[self.out[-1] if tok is None else tok]], "real_len": 1,
                "start_pos": self.pos if pos is None else pos,
                **self.ask, **self.chain, **more}

    @property
    def over(self):
        return len(self.out) >= self.new or self.out[-1] == self.eos

    def hop(self):
        res = self.ex.process(self.sid, self.payload())
        self.pos += 1
        self.out.append(int(res["tokens"][0][0]))
        self.chain = {"key": res["key"]}
        self.keys.append(list(res["key"]))
        if self.ask["top_logprobs"]:
            self.lps.append(res["logprobs"][0])
            self.tops.append((res["top_ids"][0], res["top_lps"][0]))

    def run(self, hops=None):
        while not self.over and (hops is None or hops > 0):
            self.hop()
            hops = None if hops is None else hops - 1
        return self

    def end(self):
        self.ex.end_session(self.sid)
        return self


def _side_by_side(*sessions):
    """Each session's remaining hops on a thread of its own."""
    threads = [threading.Thread(target=s.run) for s in sessions]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)


def _at_rest(ex):
    s = ex.stats()
    return s["ahead_rows"] - s["ahead_claimed"] - s["ahead_dropped"]


# ---------------------------------------------------------------------------
# (a) the same stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("how", ["greedy", "sampled"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_stream_is_the_one_the_hops_give_without_the_promise(layout, how):
    ex = _executor(LAYOUTS[layout])
    kw = dict(sampling=GREEDY if how == "greedy" else SAMPLED, seed=7, top=3)
    plain = Session(ex, "plain", ahead=False, **kw).run().end()
    assert ex.stats()["ahead_rows"] == 0
    ahead = Session(ex, "ahead", **kw).run().end()
    assert ahead.out == plain.out and ahead.keys == plain.keys
    np.testing.assert_allclose(ahead.lps, plain.lps, rtol=1e-5, atol=1e-6)
    for (ia, la), (ip, lp) in zip(ahead.tops, plain.tops):
        assert ia == ip and len(ia) == 3
        np.testing.assert_allclose(la, lp, rtol=1e-5, atol=1e-6)
    if how == "sampled":
        assert len(set(map(tuple, ahead.keys))) == NEW - 1  # the chain moved
    s = ex.stats()
    # every hop but the first found its row run: nothing was run in vain
    assert s["ahead_claimed"] == s["ahead_rows"] == NEW - 2 and s["ahead_dropped"] == 0
    assert s["sampled_rows"] == 2 * (NEW - 1) and s["logit_rows"] == 0
    assert s["batched_tokens"] == 2 * (NEW - 1) == s["batched_steps"]


@pytest.mark.parametrize("layout", ["rows", "ring", "state", "mesh"])
def test_sessions_side_by_side_each_read_their_own_stream(layout):
    ex = _executor(LAYOUTS[layout])
    alone = [Session(ex, f"alone{i}", prompt=p, ahead=False, new=NEW + i).run().end().out
             for i, p in enumerate((PROMPT, OTHER, PROMPT[:3]))]
    rows0 = ex.stats()["batched_tokens"]
    sessions = [Session(ex, f"s{i}", prompt=p, new=NEW + i)
                for i, p in enumerate((PROMPT, OTHER, PROMPT[:3]))]
    _side_by_side(*sessions)
    assert [s.out for s in sessions] == alone
    s = ex.stats()
    hops = sum(len(x.out) - 1 for x in sessions)
    # a lane's first hop rides (a second may, where its session was back
    # before the drain that would have run its row); once a hop has claimed,
    # every later one does, however late it comes
    assert hops - 2 * len(sessions) <= s["ahead_claimed"] == s["ahead_rows"] <= hops - len(sessions)
    assert s["ahead_dropped"] == 0 and s["batched_tokens"] - rows0 == hops
    for x in sessions:
        x.end()
    assert _at_rest(ex) == 0 and ex.stats()["ahead_dropped"] == 0


# ---------------------------------------------------------------------------
# (b) the budget
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("new", [2, 3, 6])
def test_no_row_is_run_past_the_budget(new, kind):
    ex = _executor(KINDS[kind])
    s = Session(ex, "s", new=new).run()
    st = ex.stats()
    assert len(s.out) == new and _at_rest(ex) == 0 and st["ahead_dropped"] == 0
    assert st["ahead_rows"] == new - 2  # the first hop rides, the last is promised by the one before
    assert not ex._ahead and _length(ex, "s") == len(PROMPT) + new - 1
    s.end()
    assert ex.stats()["ahead_dropped"] == 0


@pytest.mark.parametrize("kind", list(KINDS))
def test_an_eos_costs_at_most_one_row_a_request(kind):
    ex = _executor(KINDS[kind])
    full = Session(ex, "full", sampling=SAMPLED, seed=3, new=12, ahead=False).run().end().out
    eos = full[5]
    cut = full[:full.index(eos) + 1]
    for i in range(3):
        s = Session(ex, f"s{i}", sampling=SAMPLED, seed=3, new=12, eos=eos).run()
        assert s.out == cut
        s.end()
        st = ex.stats()
        assert _at_rest(ex) == 0 and st["ahead_dropped"] <= i + 1
    # the token that ends a generation is known to the host before the row
    # after it would be dispatched wherever the step was waited for
    assert ex.stats()["ahead_dropped"] <= 3


@pytest.mark.parametrize("kind", list(KINDS))
def test_the_last_position_of_a_lane_is_not_run_ahead(kind):
    ex = _executor(KINDS[kind], max_len=12)
    s = Session(ex, "s", prompt=PROMPT, new=8).run()  # positions 5..11: the lane ends at 12
    assert len(s.out) == 8 and ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0
    plain = Session(_executor(KINDS[kind], max_len=12), "p", new=8, ahead=False).run()
    assert s.out == plain.out


# ---------------------------------------------------------------------------
# (c) what drops a row, and that nobody reads it
# ---------------------------------------------------------------------------


def _ahead_of(ex, sid):
    return ex._ahead.get(_lane(ex, sid))


def _fresh_stream(model, prompt, **kw):
    return Session(_executor(model), "fresh", prompt=prompt, ahead=False, **kw).run().out


def _end(ex, s):
    s.end()


def _other_token(ex, s):
    res = ex.process(s.sid, dict(s.payload(tok=(s.out[-1] + 1) % 256), ahead=0))
    assert "tokens" in res  # served as if no row had been run
    ex.end_session(s.sid)


def _replay(ex, s):
    s.out.pop()  # the hop before, sent again: its reply was lost
    res = ex.process(s.sid, dict(s.payload(pos=s.pos - 1), ahead=0))
    assert "tokens" in res
    ex.end_session(s.sid)


def _prefill_chunk(ex, s):
    ex.process(s.sid, {"tokens": [[9, 8, 7]], "start_pos": s.pos, "real_len": 3})
    ex.end_session(s.sid)


def _fork(ex, s):
    assert ex.fork_session("child", s.sid, len(PROMPT))
    ex.end_session("child")
    ex.end_session(s.sid)


def _export(ex, s):
    assert ex.export_sessions(only=s.sid)
    ex.end_session(s.sid)


DROPS = {"end_session": _end, "another_token": _other_token, "replay": _replay,
         "prefill_chunk": _prefill_chunk, "fork": _fork, "export": _export}


@pytest.mark.parametrize("layout", ["rows", "ring", "mesh", "mesh_ring"])
@pytest.mark.parametrize("what", list(DROPS))
def test_what_is_not_the_promised_hop_drops_the_row_and_nobody_reads_it(what, layout, monkeypatch):
    model = LAYOUTS[layout]
    mesh = layout.startswith("mesh")
    # a fork's child takes a lane (the mesh: one shape of the pass for all six)
    ex = _executor(model, lanes=2 if what == "fork" or mesh else 1)
    s = Session(ex, "s").run(hops=3)
    lane = _lane(ex, "s")
    rec = _ahead_of(ex, "s")
    assert rec is not None and rec.pos == s.pos == _length(ex, "s")
    if layout != "mesh":  # (uniform slabs on the mesh keep no mark)
        # the row counts as written: a ring has given up its oldest slot to it
        assert _hi(ex, "s") == s.pos + 1
    end, read = ex.end_session, []
    if mesh:
        # whoever uses the slot next finds the length the host believes: the
        # session itself where the drop left it its slot, until it ends

        def ends(sid):
            assert what == "end_session" or not ex._ahead  # the call before dropped it
            read.append(_length(ex, sid))
            end(sid)

        monkeypatch.setattr(ex, "end_session", ends)
    DROPS[what](ex, s)
    st = ex.stats()
    assert st["ahead_dropped"] == 1 and _at_rest(ex) == 0 and not ex._ahead
    assert read or not mesh
    # the lane's next session reads what a fresh executor's would
    nxt = Session(ex, "next", prompt=OTHER)
    assert _lane(ex, "next") == lane
    assert nxt.run().end().out == _fresh_stream(model, OTHER)


@pytest.mark.parametrize("what", ["another_token", "replay", "prefill_chunk"])
def test_a_recurrent_state_refuses_what_is_not_the_promised_hop(what):
    ex = _executor("tiny-granite-h", lanes=1)
    want = _fresh_stream("tiny-granite-h", PROMPT)
    s = Session(ex, "s").run(hops=3)
    bad = {
        "another_token": s.payload(tok=(s.out[-1] + 1) % 256),
        "replay": s.payload(tok=s.out[-2], pos=s.pos - 1),
        "prefill_chunk": {"tokens": [[9, 8, 7]], "start_pos": s.pos, "real_len": 3},
    }[what]
    with pytest.raises(ValueError, match="recurrent state|restart the session at 0"):
        ex.process("s", bad)
    # nothing moved: the row waits for the hop it was run for
    assert ex.stats()["ahead_dropped"] == 0 and _ahead_of(ex, "s") is not None
    assert s.run().out == want
    s.end()
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


def test_a_recurrent_state_lane_starts_its_next_session_clean():
    ex = _executor("tiny-granite-h", lanes=1)
    Session(ex, "s").run(hops=3).end()  # a row was run ahead and moved the state
    assert ex.stats()["ahead_dropped"] == 1
    assert Session(ex, "next", prompt=OTHER).run().out == _fresh_stream("tiny-granite-h", OTHER)
    # a restart at 0 under the same id resets the lane, row and all
    s = Session(ex, "again").run(hops=2)
    assert Session(ex, "again", prompt=OTHER).run().out == _fresh_stream("tiny-granite-h", OTHER)
    assert s.pos > 0 and _at_rest(ex) == 0


@pytest.mark.parametrize("kind", list(KINDS))
def test_an_evicted_sessions_row_goes_with_it(kind):
    ex = _executor(KINDS[kind], lanes=1)
    Session(ex, "old").run(hops=2)
    assert ex._ahead
    new = Session(ex, "new", prompt=OTHER).run().end()  # the one lane is taken from `old`
    assert new.out == _fresh_stream(KINDS[kind], OTHER)
    assert ex.stats()["ahead_dropped"] == 1 and _at_rest(ex) == 0


# ---------------------------------------------------------------------------
# (d) a lone session, a newcomer, a late one
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_lone_sessions_first_hop_is_answered_with_no_other_submit(kind):
    ex = _executor(KINDS[kind])
    s = Session(ex, "s")
    s.hop()  # returns: the flusher waited for the step itself
    st = ex.stats()
    # ... and ran the promised hop's row right behind it
    assert st["batched_steps"] == 2 and st["ahead_rows"] == 1 and st["ahead_claimed"] == 0
    s.hop()
    assert ex.stats()["ahead_claimed"] == 1 and ex.stats()["batched_steps"] == 3
    assert s.run().out == _fresh_stream(KINDS[kind], PROMPT)


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_newcomer_rides_beside_the_running_and_their_rows_go_on(kind):
    ex = _executor(KINDS[kind])
    alone = [_fresh_stream(KINDS[kind], p, new=24) for p in (PROMPT, OTHER)]
    late_alone = _fresh_stream(KINDS[kind], PROMPT[:3])
    a, b = Session(ex, "a", new=24), Session(ex, "b", prompt=OTHER, new=24)
    a.run(hops=2), b.run(hops=2)
    running = [threading.Thread(target=x.run) for x in (a, b)]
    for t in running:
        t.start()
    c = Session(ex, "c", prompt=PROMPT[:3]).run()  # prefill and hops beside them
    for t in running:
        t.join(120)
    assert [a.out, b.out] == alone and c.out == late_alone
    s = ex.stats()
    # the running sessions never rode again: each hop after the first claimed
    assert s["ahead_claimed"] >= (24 - 2) * 2 and s["ahead_dropped"] == 0
    assert _at_rest(ex) == 0


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_session_that_comes_back_late_is_left_out_and_rejoins(kind):
    ex = _executor(KINDS[kind])
    want_a, want_b = (_fresh_stream(KINDS[kind], p, new=12) for p in (PROMPT, OTHER))
    a, b = Session(ex, "a", new=12), Session(ex, "b", prompt=OTHER, new=12)
    a.run(hops=3), b.run(hops=3)
    rows = ex.stats()["ahead_rows"]
    rec = _ahead_of(ex, "a")
    b.run(hops=5)  # `a` sits five steps out: its one row waits, no other is run for it
    assert _ahead_of(ex, "a") is rec and ex.stats()["ahead_rows"] == rows + 5
    a.run(), b.run()
    assert a.out == want_a and b.out == want_b
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("when", ["before_the_drain", "inside_the_drain"])
def test_a_step_somebody_else_finished_still_feeds_the_next(when, kind, monkeypatch):
    """A rider's own thread may finish the last step at any moment: before
    the drain looks (the host's copy feeds the next step), or between the
    drain's look and its dispatch (the device's copy must still be there)."""
    ex = _executor(KINDS[kind])
    s = Session(ex, "s").run(hops=2)
    if when == "inside_the_drain":
        from inferd_tpu.core import sampling as samplib

        # where each executor takes what the step before left on the device
        holder, name = (samplib, "ahead_rows") if kind == "lanes" else (ex.engine, "_feed")
        real = getattr(holder, name)

        def finished_meanwhile(packed, *rest):
            ex._finish(ex._last_step)
            return real(packed, *rest)

        monkeypatch.setattr(holder, name, finished_meanwhile)
    while not s.over:
        if when == "before_the_drain":
            ex._finish(ex._last_step)
        s.hop()
    assert s.out == _fresh_stream(KINDS[kind], PROMPT)
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


class OnTheDevice:
    """A prefill chunk's logits that never come (`_settle` asks `is_ready`)."""

    def is_ready(self):
        return False


def test_behind_a_prefill_chunk_the_drain_waits_out_the_last_step_first():
    """While a prefill chunk is on the device the next step cannot start,
    so the drain first waits for the step dispatched before it (and so takes
    in a newcomer whose prefill is ending) and feeds the next step from the
    host's copy: same stream, every row still run ahead and claimed."""

    ex = _executor("tiny")
    s = Session(ex, "s").run(hops=2)
    ex._chunk = OnTheDevice()
    seen = []
    real = ex._batcher.drain_pending

    def drain():
        seen.append(ex._last_step.done)
        return real()

    ex._batcher.drain_pending = drain
    s.run()
    assert seen and all(seen)  # every drain found the last step finished
    assert s.out == _fresh_stream("tiny", PROMPT)
    st = ex.stats()
    assert st["ahead_claimed"] == st["ahead_rows"] == NEW - 2 and st["ahead_dropped"] == 0


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_hop_with_logits_rides_beside_rows_run_ahead(kind):
    ex = _executor(KINDS[kind])
    want = _fresh_stream(KINDS[kind], PROMPT, new=16)
    a = Session(ex, "a", new=16).run(hops=2)
    t = threading.Thread(target=a.run)
    t.start()
    raw = ex.process("raw", {"tokens": [OTHER], "start_pos": 0, "real_len": len(OTHER)})
    tok, got = int(np.argmax(raw["logits"][0])), []
    for i in range(4):  # a raw /forward: no ask, answered with its logits row
        got.append(tok)
        res = ex.process("raw", {"tokens": [[tok]], "start_pos": len(OTHER) + i, "real_len": 1})
        tok = int(np.argmax(res["logits"][0]))
    t.join(120)
    assert a.out == want and got == _fresh_stream(KINDS[kind], OTHER, new=4)
    assert ex.stats()["logit_rows"] == 4


# ---------------------------------------------------------------------------
# (e) every other path runs nothing ahead
# ---------------------------------------------------------------------------


def _no_budget(ex):
    Session(ex, "s", ahead=False).run().end()


def _k_steps(ex):
    ex.process("s", {"tokens": [PROMPT], "start_pos": 0, "real_len": len(PROMPT)})
    res = ex.process("s", {"tokens": [[1]], "start_pos": len(PROMPT), "real_len": 1,
                           "decode_steps": 4, "ahead": 8, "sampling": {}})
    assert len(res["tokens"][0]) == 4


def _block(ex):
    blk = ex.cfg.block_length
    ex.process("s", {"tokens": [[1] * blk], "start_pos": 0, "real_len": blk})
    res = ex.process("s", {"tokens": [[0] * blk], "start_pos": blk, "real_len": blk,
                           "block": {"known": 0}})  # no `ahead`: served as before PR 50
    assert len(res["tokens"][0]) == blk


def _speculating(ex):
    ex.enable_spec(2, 2)
    Session(ex, "s").run().end()  # a promise, but speculative rounds read the lanes' lengths


def _mesh_speculating(ex):
    ex.enable_spec(2, 2, _made["tiny"])
    assert ex.spec_enabled()
    Session(ex, "s").run().end()  # as on the lanes: look-ahead is off for the whole executor


OTHER_PATHS = {
    "no_budget_key": ("tiny", {}, _no_budget),
    "mesh_no_budget_key": ("mesh:tiny", {}, _no_budget),
    "mesh_speculating": ("mesh:tiny", {}, _mesh_speculating),
    "k_step_call": ("tiny", {}, _k_steps),
    "block_call": ("tiny-sdar", {}, _block),
    "paged_lane": ("tiny", {"block_size": 16, "kv_blocks": 24}, lambda ex: Session(ex, "s").run().end()),
    "speculating": ("tiny", {}, _speculating),
}


@pytest.mark.parametrize("path", list(OTHER_PATHS))
def test_every_other_path_runs_no_row_ahead(path):
    model, kw, drive = OTHER_PATHS[path]
    ex = _executor(model, **kw)
    drive(ex)
    s = ex.stats()
    assert s["ahead_rows"] == s["ahead_claimed"] == s["ahead_dropped"] == 0
    assert not ex._ahead


@pytest.mark.parametrize("lanes", ["dense", "paged"])
def test_a_row_dispatch_that_raises_fails_its_own_entries_only(lanes):
    """A one-token step that dies before it is dispatched (`_decode_ahead`
    on dense lanes, `_decode_dispatch` on paged ones) fails the hops of ITS
    drain: nothing moved on the lane, no token is counted as served, and
    the same hop, sent again, is served as if nothing had been."""
    kw = {"block_size": 16, "kv_blocks": 24} if lanes == "paged" else {}
    name = "_decode_logits_paged" if lanes == "paged" else "_decode_logits"
    ex = _executor("tiny", **kw)
    real = getattr(ex.engine, name)

    def boom(*a, **k):
        setattr(ex.engine, name, real)  # once
        raise RuntimeError("injected dispatch failure")

    s = Session(ex, "s", ahead=False).run(hops=2)
    before = ex.stats()
    setattr(ex.engine, name, boom)
    with pytest.raises(RuntimeError, match="injected dispatch failure"):
        s.hop()
    after = ex.stats()
    assert _length(ex, "s") == s.pos and ex._inflight == {}
    assert after["batched_tokens"] == before["batched_tokens"]
    assert s.run().end().out == _fresh_stream("tiny", PROMPT)
    assert _at_rest(ex) == 0


def test_the_ask_carries_the_budget():
    from inferd_tpu.runtime import executor as execlib

    ask = execlib.parse_decode_ask({"sampling": {}, "ahead": 5, "eos": 2})
    assert (ask.ahead, ask.eos) == (5, 2)
    ask = execlib.parse_decode_ask({"sampling": {}})
    assert (ask.ahead, ask.eos) == (0, -1)  # every caller that says nothing
    assert execlib.parse_decode_ask({"sampling": {}, "ahead": -3}).ahead == 0
    assert execlib.parse_ask({"ahead": 5}).ahead == 0  # a K-step call: not its
    assert execlib.parse_kstep({"decode_steps": 2, "ahead": 5}, 8).get("ahead") is None
    # a block hop's `block` call carries the same two keys
    call = execlib.parse_block({"block": {"known": 0, "ahead": 5, "eos": 2}}, 4)
    assert (call.ahead, call.eos) == (5, 2)
    call = execlib.parse_block({"block": {"known": 0}}, 4)
    assert (call.ahead, call.eos) == (0, -1)  # a caller that promises nothing
    assert execlib.parse_block({"block": {"known": 0, "ahead": -3}}, 4).ahead == 0


# ---------------------------------------------------------------------------
# (f) block hops (a model generated by blocks): the same, a block a row
# ---------------------------------------------------------------------------

BLK = get_config("tiny-sdar").block_length
LONG = [3, 7, 11, 19, 23, 29, 31, 37, 41, 43]  # two whole blocks and two tokens that open the third


class BlockSession:
    """The block loop's hops (client/base.py `_generate_blocks`), one call a
    hop: the prompt's whole blocks go in as one chunk, what is left of it
    opens the first block."""

    def __init__(self, ex, sid, prompt=LONG, new=14, ahead=True, sampling=GREEDY, seed=0,
                 top=0, eos=None):
        self.ex, self.sid, self.new, self.ahead, self.eos = ex, sid, new, ahead, eos
        self.ask = {"sampling": sampling, "top_logprobs": top}
        if eos is not None:
            self.ask["eos"] = eos
        whole = len(prompt) // BLK * BLK
        if whole:
            ex.process(sid, {"tokens": [prompt[:whole]], "start_pos": 0, "real_len": whole,
                             "want_logits": False})
        self.pos, self.head, self.chain = whole, list(prompt[whole:]), {"seed": seed}
        self.out, self.orders, self.keys, self.lps, self.hops = [], [], [], [], 0

    def payload(self, **block):
        left = self.new - len(self.out) - (BLK - len(self.head))
        more = {"ahead": max(0, -(-left // BLK))} if self.ahead else {}
        call = {"known": len(self.head), **self.ask, **self.chain, **more, **block}
        return {"tokens": [self.head + [0] * (BLK - len(self.head))], "start_pos": self.pos,
                "real_len": BLK, "block": call}

    @property
    def over(self):
        return len(self.out) >= self.new or (bool(self.out) and self.out[-1] == self.eos)

    def hop(self):
        res = self.ex.process(self.sid, self.payload())
        for j in range(len(self.head), BLK):
            self.out.append(int(res["tokens"][0][j]))
            if self.over:
                break
        self.orders.append(res["order"])
        self.keys.append(list(res["key"]))
        if self.ask["top_logprobs"]:
            self.lps.append((res["logprobs"], res["top_ids"], res["top_lps"]))
        self.pos, self.head, self.chain = self.pos + BLK, [], {"key": res["key"]}
        self.hops += 1

    run, end = Session.run, Session.end

    @property
    def read(self):
        return self.out, self.orders, self.keys, self.lps


def _fresh_blocks(prompt=LONG, **kw):
    return BlockSession(_executor("tiny-sdar"), "fresh", prompt=prompt, ahead=False, **kw).run().read


@pytest.mark.parametrize("beside", ["alone", "three_resident"])
@pytest.mark.parametrize("how", ["greedy", "sampled"])
def test_a_block_stream_is_the_one_the_hops_give_without_the_promise(how, beside):
    ex = _executor("tiny-sdar", lanes=4)
    kw = dict(sampling=GREEDY if how == "greedy" else SAMPLED, seed=7, top=3)
    want = _fresh_blocks(**kw)
    others = []
    if beside == "three_resident":
        # each holds a block run ahead that nobody claims while the others step
        others = [BlockSession(ex, f"o{i}", prompt=LONG[i:], new=20, **kw).run(hops=2)
                  for i in range(1, 4)]
        assert len(ex._ahead) == 3
    rows0, claimed0 = ex.stats()["ahead_rows"], ex.stats()["ahead_claimed"]
    got = BlockSession(ex, "s", **kw).run().end()
    # bit for bit: the program and its inputs are the same, only WHEN it runs differs
    assert got.read == want and len(got.out) == 14 and len(got.lps) == got.hops == 4
    if how == "sampled":
        assert len(set(map(tuple, got.keys))) == got.hops  # the chain moved
    s = ex.stats()
    # every hop but the first found its block run: nothing was run in vain
    assert s["ahead_claimed"] - claimed0 == s["ahead_rows"] - rows0 == got.hops - 1
    assert s["ahead_dropped"] == 0
    for i, o in enumerate(others, 1):
        assert o.run().end().read == _fresh_blocks(prompt=LONG[i:], new=20, **kw)
    assert _at_rest(ex) == 0 and ex.stats()["ahead_dropped"] == 0


def test_block_sessions_side_by_side_each_read_their_own_stream():
    ex = _executor("tiny-sdar")
    jobs = [(LONG, 22), (LONG[1:], 17), (OTHER, 12)]
    alone = [_fresh_blocks(prompt=p, new=n) for p, n in jobs]
    sessions = [BlockSession(ex, f"s{i}", prompt=p, new=n) for i, (p, n) in enumerate(jobs)]
    _side_by_side(*sessions)
    assert [x.read for x in sessions] == alone
    s = ex.stats()
    hops = sum(x.hops for x in sessions)
    assert hops - 2 * len(sessions) <= s["ahead_claimed"] == s["ahead_rows"] <= hops - len(sessions)
    assert s["ahead_dropped"] == 0 and s["diffusion"]["hops"] == hops == s["batched_tokens"]
    assert s["diffusion"]["block_steps"] == s["batched_steps"]


def test_block_sessions_of_two_sampling_configs_share_a_drain_not_a_program():
    """The block step's sampling is static: a drain dispatches one program a
    config, each running its own lanes' next blocks ahead."""
    ex = _executor("tiny-sdar")
    jobs = [(LONG, GREEDY), (LONG[1:], SAMPLED), (OTHER, SAMPLED)]
    alone = [_fresh_blocks(prompt=p, new=22, sampling=s, seed=4) for p, s in jobs]
    sessions = [BlockSession(ex, f"s{i}", prompt=p, new=22, sampling=s, seed=4)
                for i, (p, s) in enumerate(jobs)]
    _side_by_side(*sessions)
    assert [x.read for x in sessions] == alone
    s = ex.stats()
    hops = sum(x.hops for x in sessions)
    assert hops - 2 * len(sessions) <= s["ahead_claimed"] == s["ahead_rows"] <= hops - len(sessions)
    assert s["ahead_dropped"] == 0 and s["diffusion"]["hops"] == hops == s["batched_tokens"]
    # a sampled lane never rides the greedy lane's program
    assert s["diffusion"]["block_steps"] == s["batched_steps"] >= sessions[0].hops + sessions[1].hops


@pytest.mark.parametrize("new", [1, 2, 5, 6, 7, 13])
def test_no_block_is_run_past_the_budget(new):
    """`max_new_tokens` that ends inside a block, on its end, in the first
    one: the promised hop count is exact."""
    ex = _executor("tiny-sdar")
    s = BlockSession(ex, "s", new=new).run()
    hops = -(-(new + len(LONG) % BLK) // BLK)
    st = ex.stats()
    assert len(s.out) == new and s.hops == hops and st["diffusion"]["hops"] == hops
    assert st["ahead_rows"] == st["ahead_claimed"] == hops - 1 and st["ahead_dropped"] == 0
    assert not ex._ahead and not ex._carry
    assert ex.engine.lengths[ex._sessions["s"]] == len(LONG) // BLK * BLK + hops * BLK
    assert st["diffusion"]["block_steps"] == st["batched_steps"] == hops  # no program in vain
    assert s.read == _fresh_blocks(new=new)
    s.end()
    assert ex.stats()["ahead_dropped"] == 0


def test_an_eos_inside_a_block_costs_the_block_run_ahead_of_it():
    ex = _executor("tiny-sdar", lanes=1)
    full = _fresh_blocks(new=22, sampling=SAMPLED, seed=3)[0]
    at = next(i for i in range(4, 22) if full[i] not in full[:i])
    s = BlockSession(ex, "s", new=22, sampling=SAMPLED, seed=3, eos=full[at]).run()
    assert s.out == full[:at + 1]
    lane = ex._sessions["s"]
    # the block after the one that holds `eos` was run before the host knew
    assert ex._ahead[lane].pos == s.pos == ex.engine.lengths[lane]
    s.end()
    st = ex.stats()
    assert st["ahead_dropped"] == 1 and _at_rest(ex) == 0 and not ex._ahead
    assert ex.engine.free == [lane]
    # the lane's next owner reads what a fresh executor's would: nobody reads the block
    nxt = BlockSession(ex, "next", prompt=LONG[1:], new=9)
    assert ex._sessions["next"] == lane
    assert nxt.run().end().read == _fresh_blocks(prompt=LONG[1:], new=9)
    # ... and where the host already knows the block when the next would be
    # dispatched (every drain waited the step before out: `_settle`), `eos`
    # IN it runs nothing ahead
    s = BlockSession(ex, "known", new=22, sampling=SAMPLED, seed=3, eos=full[at])
    ex._chunk = OnTheDevice()
    assert s.run().end().out == full[:at + 1]
    assert ex.stats()["ahead_dropped"] == 1 and _at_rest(ex) == 0


def _block_end(ex, s):
    s.end()


def _block_replay(ex, s):
    """The hop before, sent again (its reply was lost): the lane rolls back a block."""
    res = ex.process(s.sid, {**s.payload(ahead=0, key=s.keys[-2]), "start_pos": s.pos - BLK})
    assert res["key"] == s.keys[-1] and res["order"] == s.orders[-1]
    ex.end_session(s.sid)


def _block_restart(ex, s):
    again = BlockSession(ex, s.sid, new=9)  # the same id at 0: the lane is reset
    assert again.run().end().read == _fresh_blocks(new=9)


def _block_known(ex, s):
    """A block of which the caller fills a place is not the block that was run."""
    p = s.payload(ahead=0, known=1)
    p["tokens"] = [[5] + [0] * (BLK - 1)]
    res = ex.process(s.sid, p)
    assert res["order"][0] == -1 and res["tokens"][0][0] == 5
    ex.end_session(s.sid)


def _block_other_key(ex, s):
    res = ex.process(s.sid, s.payload(ahead=0, key=[1, 2]))
    assert len(res["tokens"][0]) == BLK
    ex.end_session(s.sid)


def _block_prefill(ex, s):
    ex.process(s.sid, {"tokens": [[9, 8, 7, 6]], "start_pos": s.pos, "real_len": BLK,
                       "want_logits": False})
    ex.end_session(s.sid)


def _block_export(ex, s):
    assert ex.export_sessions(only=s.sid)
    ex.end_session(s.sid)


BLOCK_DROPS = {"end_session": _block_end, "replay": _block_replay, "restart": _block_restart,
               "known": _block_known, "another_key": _block_other_key,
               "prefill_chunk": _block_prefill, "export": _block_export}


@pytest.mark.parametrize("what", list(BLOCK_DROPS))
def test_what_is_not_the_promised_block_hop_drops_the_block_and_nobody_reads_it(what):
    ex = _executor("tiny-sdar", lanes=1)
    s = BlockSession(ex, "s", new=22, sampling=SAMPLED, seed=5).run(hops=3)
    lane = ex._sessions["s"]
    rec = ex._ahead[lane]
    assert rec.pos == s.pos == ex.engine.lengths[lane] and rec.width == BLK
    BLOCK_DROPS[what](ex, s)
    st = ex.stats()
    assert st["ahead_dropped"] == 1 and _at_rest(ex) == 0 and not ex._ahead and not ex._carry
    nxt = BlockSession(ex, "next", prompt=LONG[1:], new=9)
    assert ex._sessions["next"] == lane
    assert nxt.run().end().read == _fresh_blocks(prompt=LONG[1:], new=9)


def test_a_block_call_that_promises_runs_the_block_and_end_session_drops_it():
    """What `OTHER_PATHS` held of the parent: a block call with `ahead`."""
    ex = _executor("tiny-sdar")
    ex.process("s", {"tokens": [[1] * BLK], "start_pos": 0, "real_len": BLK})
    res = ex.process("s", {"tokens": [[0] * BLK], "start_pos": BLK, "real_len": BLK,
                           "block": {"known": 0, "ahead": 8}})
    assert len(res["tokens"][0]) == BLK
    s = ex.stats()
    assert (s["ahead_rows"], s["ahead_claimed"], s["ahead_dropped"]) == (1, 0, 0)
    assert ex._ahead[ex._sessions["s"]].pos == 2 * BLK
    ex.end_session("s")
    assert ex.stats()["ahead_dropped"] == 1 and not ex._ahead


def test_a_fork_of_a_lane_with_a_block_run_ahead_is_refused_and_the_block_stays():
    ex = _executor("tiny-sdar")
    s = BlockSession(ex, "s").run(hops=2)
    assert ex.fork_session("child", "s", 2 * BLK) is False  # no fork of such a model yet
    assert len(ex._ahead) == 1 and s.run().end().read == _fresh_blocks()
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


def test_behind_a_prefill_chunk_a_block_drain_waits_out_the_last_step_first():
    """`_settle`, for block steps: another lane's chunk is on the device
    between two block steps, so each drain first finishes the step before
    and feeds the next from the host's copy of its key."""

    ex = _executor("tiny-sdar")
    s = BlockSession(ex, "s", sampling=SAMPLED, seed=2).run(hops=2)
    BlockSession(ex, "other", prompt=OTHER)  # a prefill chunk of another lane cuts in
    assert ex._chunk is not None
    ex._chunk = OnTheDevice()
    seen = []
    real = ex._batcher.drain_pending

    def drain():
        seen.append(ex._last_step.done)
        return real()

    ex._batcher.drain_pending = drain
    s.run()
    assert seen and all(seen)
    assert s.read == _fresh_blocks(sampling=SAMPLED, seed=2)
    st = ex.stats()
    assert st["ahead_claimed"] == st["ahead_rows"] == s.hops - 1 and st["ahead_dropped"] == 0


@pytest.mark.parametrize("who", ["the_block_run_ahead", "a_rider"])
def test_a_block_dispatch_that_raises_fails_its_own_entries_only(who):
    ex = _executor("tiny-sdar")
    real = ex.engine._block_step

    def boom(*a, **kw):
        ex.engine._block_step = real  # once
        raise RuntimeError("injected dispatch failure")

    s = BlockSession(ex, "s").run(hops=2)
    rows = ex.stats()["ahead_rows"]
    if who == "a_rider":
        r = BlockSession(ex, "r", prompt=LONG[1:], new=9)
        ex.engine._block_step = boom
        with pytest.raises(RuntimeError, match="injected dispatch failure"):
            r.hop()
        # nothing moved on its lane, and nothing is left to answer from
        lane = ex._sessions["r"]
        assert ex.engine.lengths[lane] == r.pos and lane not in ex._ahead and lane not in ex._carry
        assert r.run().end().read == _fresh_blocks(prompt=LONG[1:], new=9)
    else:
        ex.engine._block_step = boom
        s.hop()  # answered from the step that ran its block; the next block's dispatch failed
        assert not ex._ahead and ex.stats()["ahead_rows"] == rows
    assert s.run().end().read == _fresh_blocks()  # its next hop rode, and the rest ran ahead
    assert _at_rest(ex) == 0 and ex.stats()["ahead_dropped"] == 0


def test_the_block_counters_add_up_over_a_generation():
    ex = _executor("tiny-sdar")
    a = BlockSession(ex, "a", new=22).run()
    b = BlockSession(ex, "b", prompt=LONG[1:], new=22, eos=a.out[9]).run()
    a.end(), b.end()
    s = ex.stats()
    hops = a.hops + b.hops
    assert s["diffusion"]["hops"] == hops == s["ahead_claimed"] + 2  # each first hop rode
    assert s["ahead_rows"] == s["ahead_claimed"] + s["ahead_dropped"] and s["ahead_dropped"] <= 1
    assert s["diffusion"]["block_steps"] == s["batched_steps"] == s["batched_tokens"]
    assert s["batched_tokens"] == hops + s["ahead_dropped"]  # the blocks run for a session
    assert s["diffusion"]["lane_passes"] == 3 * s["batched_tokens"]
    assert s["sampled_rows"] == s["logit_rows"] == 0  # those count one-token hops


def _reader(metric):
    """A per-layer reader of the benchmark, loaded as its harness loads it."""
    import importlib.util
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)  # the reader imports `arith` as the harness has it
    try:
        spec = importlib.util.spec_from_file_location(
            metric.replace(".", "_"), os.path.join(bench, "layer_metrics", f"{metric}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(bench)
    return mod.read


@pytest.mark.parametrize("program", ["this_tree", "the_parent", "a_token_model", "no_hop"])
def test_the_benchmark_reads_the_share_of_block_hops_that_found_their_block_run(program):
    read = _reader("window.block_ahead_claimed_share")
    ex = _executor("tiny-sdar" if program != "a_token_model" else "tiny")
    before = {"executor": ex.stats()}
    if program == "a_token_model":
        Session(ex, "s").run().end()
    elif program != "no_hop":
        a = BlockSession(ex, "a", new=22).run().end()
        b = BlockSession(ex, "b", prompt=OTHER, new=10, ahead=False).run().end()  # promises nothing
    after = {"executor": ex.stats()}
    if program == "the_parent":  # a program without the counter: nothing to read, no error
        for stats in (before, after):
            del stats["executor"]["diffusion"]["hops"]
    got = read({"stats0": before, "stats1": after})
    if program == "this_tree":
        assert got == pytest.approx(100.0 * (a.hops - 1) / (a.hops + b.hops)) and 0 < got < 100
    else:
        assert got is None


# ---------------------------------------------------------------------------
# (g) the mesh executor alone: its lengths live on the devices, one lock is
# its devices' and its session table's, one placement feeds its pass
# ---------------------------------------------------------------------------


def _unfinished(ex):
    """The passes dispatched and not yet waited for, newest first."""
    step, found = ex._last_step, []
    while step is not None and not step.done:
        found.append(step)
        step = step.prev
    return found


def test_a_prefill_that_cuts_in_on_the_mesh_finds_at_most_one_pass_ahead_of_it():
    """A drain waits the pass before its own out under `_lock`, so whoever
    else holds the lock (a prefill: to the end of its pass) has one pass
    between it and the devices at most; and the drain behind a prefill finds
    that pass ended and feeds the next from the host's copy (`_settle`)."""
    ex = _executor("mesh:tiny")
    want_a, want_b = _fresh_stream("mesh:tiny", PROMPT, new=24), _fresh_stream("mesh:tiny", OTHER)
    a = Session(ex, "a", new=24).run(hops=3)
    queued, fed_by_host = [], []
    prefill, feed = ex.engine.step_slot, ex.engine._feed

    def cuts_in(*args, **kw):
        queued.append(len(_unfinished(ex)))
        out = prefill(*args, **kw)
        fed_by_host.append(len(fed_by_host))  # the drain that follows is the one to look at
        return out

    def fed(packed, toks, keys, from_dev):
        if fed_by_host and fed_by_host[-1] is not None:
            fed_by_host[-1] = None
            assert not from_dev.any() and not _unfinished(ex)
        return feed(packed, toks, keys, from_dev)

    ex.engine.step_slot, ex.engine._feed = cuts_in, fed
    t = threading.Thread(target=a.run)
    t.start()
    b = Session(ex, "b", prompt=OTHER).run()  # its prefill cuts in between two of a's hops
    t.join(120)
    assert queued and max(queued) <= 1
    assert a.out == want_a and b.out == want_b
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


@pytest.mark.parametrize("who", ["the_row_run_ahead", "a_rider"])
def test_a_mesh_dispatch_that_raises_fails_its_riders_and_records_no_row(who):
    ex = _executor("mesh:tiny")
    real = ex.engine.dispatch_slots

    def boom(*a, **kw):
        ex.engine.dispatch_slots = real  # once
        raise RuntimeError("injected dispatch failure")

    s = Session(ex, "s").run(hops=2)
    st = ex.stats()
    if who == "a_rider":
        r = Session(ex, "r", prompt=OTHER, new=9)
        ex.engine.dispatch_slots = boom
        with pytest.raises(RuntimeError, match="injected dispatch failure"):
            r.hop()
        now = ex.stats()
        # nothing moved on its slot, and nothing is left to answer from
        slot = _lane(ex, "r")
        assert _length(ex, "r") == r.pos and slot not in ex._ahead and slot not in ex._carry
        assert r.run().end().out == _fresh_stream("mesh:tiny", OTHER)
    else:
        ex.engine.dispatch_slots = boom
        s.hop()  # answered from the pass that ran its row; the next row's dispatch failed
        now = ex.stats()
        assert not ex._ahead and now["ahead_rows"] == st["ahead_rows"]
        assert _length(ex, "s") == s.pos
    # the counters stayed token-true: a pass that was not dispatched ran no row
    assert now["batched_steps"] == st["batched_steps"]
    assert now["batched_tokens"] == st["batched_tokens"]
    assert now["pipeline"]["passes"] - st["pipeline"]["passes"] == (who == "a_rider")  # r's prefill
    assert s.run().end().out == _fresh_stream("mesh:tiny", PROMPT)  # its next hop rode
    assert _at_rest(ex) == 0 and ex.stats()["ahead_dropped"] == 0


def test_the_mesh_compiles_one_pass_whoever_feeds_it():
    """A jitted program is compiled anew for inputs that lie elsewhere: the
    tokens and keys of EVERY pass come out of `_feed`, from the host or from
    the pass before, so the pass compiles once a top-n width. The counters of
    the passes count them when they are dispatched."""
    from inferd_tpu.parallel.mesh import MeshPlan
    from inferd_tpu.runtime.mesh_executor import MeshExecutor

    ex = MeshExecutor(_config("tiny"), _made.setdefault("tiny", qwen3.init_params(
        _config("tiny"), jax.random.PRNGKey(0))), MeshPlan(pp=2), num_slots=2, max_len=32,
        devices=jax.devices()[:2])  # programs of its own
    Session(ex, "s").run().end()
    raw = ex.process("raw", {"tokens": [OTHER], "start_pos": 0, "real_len": len(OTHER)})
    ex.process("raw", {"tokens": [[int(np.argmax(raw["logits"][0]))]], "start_pos": len(OTHER),
                       "real_len": 1})  # a hop that asks nothing: its logits row
    assert ex.engine._step_raw_multi._cache_size() == 1
    s = ex.stats()
    assert s["ahead_claimed"] == NEW - 2 and s["logit_rows"] == 1
    # a pass a row here (one session at a time), and the two prefills
    assert s["pipeline"]["passes"] == s["batched_steps"] + 2 == s["batched_tokens"] + 2 == NEW + 2
    assert s["pipeline"]["stage_ticks_useful"] == 2 * s["pipeline"]["passes"]


@pytest.mark.parametrize("program", ["this_tree", "the_parent", "no_hop"])
def test_the_benchmark_reads_the_share_of_mesh_hops_that_found_their_row_run(program):
    read = _reader("mesh.ahead_claimed_share")
    ex = _executor("mesh:tiny")
    before = {"executor": ex.stats()}
    if program != "no_hop":
        a = Session(ex, "a", new=12).run().end()
        b = Session(ex, "b", prompt=OTHER, new=6, ahead=False).run().end()  # promises nothing
    after = {"executor": ex.stats()}
    if program == "the_parent":  # a program without the counters: nothing to read, no error
        for stats in (before, after):
            for key in ("ahead_rows", "ahead_claimed", "ahead_dropped"):
                del stats["executor"][key]
    got = read({"stats0": before, "stats1": after})
    if program == "this_tree":
        hops = len(a.out) - 1 + len(b.out) - 1
        assert got == pytest.approx(100.0 * (len(a.out) - 2) / hops) and 0 < got < 100
    else:
        assert got is None


# ---------------------------------------------------------------------------
# (h) `device` says whether anyone saw the step end (docs/OBSERVABILITY.md
# "A request's admission"): `waited`, and /stats `executor` `steps_waited` /
# `steps_found_done`, from one `is_ready()` before the wait
# ---------------------------------------------------------------------------


class StillRunning:
    """A step's packed array as a thread finds it that comes while the step
    still runs: not ready until it is waited for."""

    def __init__(self, packed):
        self.packed = packed

    def is_ready(self):
        return False

    def block_until_ready(self):
        self.packed.block_until_ready()

    def __array__(self, *args, **kwargs):
        return np.asarray(self.packed, *args, **kwargs)


SEEN = {"lanes": ("tiny", "decode"), "block": ("tiny-sdar", "block"), "mesh": ("mesh:tiny", "decode")}


@pytest.mark.parametrize("found", ["still_running", "done"])
@pytest.mark.parametrize("who", list(SEEN))
def test_a_device_span_says_whether_its_step_was_waited_for(who, found):
    from inferd_tpu.obs import trace as tracelib

    model, kind = SEEN[who]
    ex = _executor(model)
    ex.tracer = rec = tracelib.SpanRecorder("t")
    s = (BlockSession if who == "block" else Session)(ex, "s").run(hops=2)
    step = ex._last_step  # run ahead of the session's next hop: nobody has come for it
    assert not step.done
    step.packed.block_until_ready()
    if found == "still_running":
        step.packed = StillRunning(step.packed)
    before, had = ex.stats(), {x["span"] for x in rec.spans()}
    ex._finish(step)
    after = ex.stats()
    moved = {k: after[k] - before[k] for k in ("steps_waited", "steps_found_done")}
    assert moved == {"steps_waited": int(found == "still_running"),
                     "steps_found_done": int(found == "done")}
    dev, copy = sorted((x for x in rec.spans() if x["span"] not in had), key=lambda x: x["t1"])
    assert (dev["name"], copy["name"]) == ("device", "copy_out")
    assert dev["attrs"]["kind"] == kind and dev["attrs"]["waited"] == int(found == "still_running")
    ex._finish(step)  # once a step, whoever comes again
    assert ex.stats()["steps_waited"] == after["steps_waited"] and len(rec.spans()) == len(had) + 2
    # both are once a step: the ring keeps them
    assert rec.stats()["kept"] >= 2 and {"device", "copy_out"} <= {x["name"] for x in rec._kept}
    s.run()
    assert s.out == (BlockSession if who == "block" else Session)(
        _executor(model), "fresh", ahead=False).run().out


@pytest.mark.parametrize("program", ["this_tree", "the_parent", "no_step"])
def test_the_benchmark_reads_who_saw_the_steps_end(program):
    from inferd_tpu.obs import trace as tracelib

    share, seen_ms = _reader("window.step_seen_share"), _reader("window.device_seen_ms_p50")
    ex = _executor("tiny")
    ex.tracer = rec = tracelib.SpanRecorder("t")
    before, wall0 = {"executor": ex.stats()}, tracelib.now()
    if program != "no_step":
        s = Session(ex, "s").run(hops=2)
        ex._last_step.packed.block_until_ready()  # the next drain finds this one done
        s.run().end()
    after = {"executor": ex.stats()}
    spans = rec.spans()
    if program == "the_parent":  # a program without the counters and the attribute
        for stats in (before, after):
            del stats["executor"]["steps_waited"], stats["executor"]["steps_found_done"]
        for x in spans:
            (x.get("attrs") or {}).pop("waited", None)
    run = {"stats0": before, "stats1": after, "spans": spans,
           "wall0": wall0, "wall1": tracelib.now()}
    if program != "this_tree":
        assert share(run) is None and seen_ms(run) is None
        return
    waited, done = (after["executor"][k] for k in ("steps_waited", "steps_found_done"))
    assert done >= 1 and waited >= 1  # the lone session's first hop is waited for by its drain
    assert share(run) == pytest.approx(100.0 * waited / (waited + done))
    of = sorted((x["t1"] - x["t0"]) * 1e3 for x in spans
                if x["name"] == "device" and x["attrs"].get("waited") == 1)
    assert len(of) == waited and of[0] <= seen_ms(run) <= of[-1]


@pytest.mark.parametrize("chunk_ms, own_ms", [(None, 40.0), (30.0, 40.0), (37.0, 1.0)])
def test_a_step_seen_behind_a_prefill_chunk_is_left_out(chunk_ms, own_ms):
    """The program starts a step's `device` span at the end of the STEP
    before it; one that queued behind a prefill chunk carries the chunk, and
    `window.device_seen_ms_p50` leaves it out. A chunk that queued behind a
    step ends after it and takes nothing."""
    import statistics

    seen_ms = _reader("window.device_seen_ms_p50")

    def device(t0_ms, t1_ms, kind, **attrs):
        return {"name": "device", "t0": 100 + t0_ms / 1e3, "t1": 100 + t1_ms / 1e3,
                "attrs": {"kind": kind, **attrs}}

    spans = [
        device(5.0, 38.0, "decode", waited=1),  # dispatched at 5: 33 ms to its end
        device(38.0, 46.0, "decode", waited=1),  # behind that step alone: 8 ms
        device(46.0, 46.0 + own_ms, "decode", waited=1),
        device(50.0, 76.0 + own_ms, "prefill"),  # a chunk behind THAT one: it ends later
        device(90.0, 99.0, "decode", waited=0),  # found done: not the step's end
    ]
    if chunk_ms is not None:  # a chunk ahead of the first step, its end seen at `chunk_ms`
        spans.insert(0, device(0.0, chunk_ms, "prefill"))
    run = {"spans": spans, "wall0": 100.0, "wall1": 101.0}
    own = ([33.0] if chunk_ms is None else []) + [8.0, own_ms]
    assert seen_ms(run) == pytest.approx(statistics.median(own), abs=1e-6)
    only_behind = {**run, "spans": spans[:1] + [device(5.0, 38.0, "decode", waited=1)]}
    assert chunk_ms is None or seen_ms(only_behind) is None


@pytest.mark.parametrize("who", list(SEEN))
def test_a_hop_that_waits_for_its_own_step_leaves_a_ride(who):
    """`_ridden` is what a hop's thread does with a step no drain waits for:
    the whole of it is the `ride` span under the hop's `compute`, and a ride
    of /stats `executor`."""
    from inferd_tpu.obs import trace as tracelib

    model, _ = SEEN[who]
    new = BlockSession if who == "block" else Session
    ex = _executor(model)
    ex.tracer = rec = tracelib.SpanRecorder("t")
    s = new(ex, "s").run(hops=2)
    step, lane = ex._last_step, _lane(ex, "s")
    before = ex.stats()
    compute = tracelib.SpanContext(tracelib.new_id(), tracelib.new_id(), keep=True)
    token = tracelib.set_current(compute)
    try:
        reply = ex._ridden(step, lane)
    finally:
        tracelib.reset_current(token)
    assert step.done and reply is step.reply(lane)
    (ride,) = [x for x in rec.spans() if x["name"] == "ride"]
    assert ride["parent"] == compute.span_id and ride["attrs"] == {"behind": step.behind}
    assert 0 <= step.behind <= 1 and ride["span"] in {x["span"] for x in rec._kept}
    dev = next(x for x in rec.spans() if x["name"] == "device" and x["parent"] == compute.span_id)
    assert ride["t0"] <= dev["t1"] <= ride["t1"]  # the rider's own thread saw the step end
    after = ex.stats()
    assert after["rides"] - before["rides"] == 1
    # the same stretch to a few microseconds: the count's ends where the span's last call begins
    assert 0 < after["ride_ms_sum"] - before["ride_ms_sum"] <= (ride["t1"] - ride["t0"]) * 1e3 + 0.1
    s.run()  # a step somebody else finished still feeds the next
    assert s.out == new(_executor(model), "fresh", ahead=False).run().out
