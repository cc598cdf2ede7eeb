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
ahead."""

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
           "ring": "tiny-afmoe", "state": "tiny-granite-h"}


def _config(model):
    if model == "tiny-wide":
        return dataclasses.replace(get_config("tiny"), name=model, head_dim=128)
    return get_config(model)


_made = {}


def _executor(model, **kw):
    """A fresh executor over one set of weights a model (made once)."""
    cfg = _config(model)
    if model not in _made:
        _made[model] = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    kw.setdefault("lanes", 3)
    kw.setdefault("max_len", 64)
    return BatchedExecutor(cfg, _made[model], **kw)


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


@pytest.mark.parametrize("layout", ["rows", "ring", "state"])
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


@pytest.mark.parametrize("new", [2, 3, 6])
def test_no_row_is_run_past_the_budget(new):
    ex = _executor("tiny")
    s = Session(ex, "s", new=new).run()
    st = ex.stats()
    assert len(s.out) == new and _at_rest(ex) == 0 and st["ahead_dropped"] == 0
    assert st["ahead_rows"] == new - 2  # the first hop rides, the last is promised by the one before
    assert not ex._ahead and ex.engine.lengths[ex._sessions["s"]] == len(PROMPT) + new - 1
    s.end()
    assert ex.stats()["ahead_dropped"] == 0


def test_an_eos_costs_at_most_one_row_a_request():
    ex = _executor("tiny")
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


def test_the_last_position_of_a_lane_is_not_run_ahead():
    ex = _executor("tiny", max_len=12)
    s = Session(ex, "s", prompt=PROMPT, new=8).run()  # positions 5..11: the lane ends at 12
    assert len(s.out) == 8 and ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0
    plain = Session(_executor("tiny", max_len=12), "p", new=8, ahead=False).run()
    assert s.out == plain.out


# ---------------------------------------------------------------------------
# (c) what drops a row, and that nobody reads it
# ---------------------------------------------------------------------------


def _ahead_of(ex, sid):
    return ex._ahead.get(ex._sessions[sid])


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


@pytest.mark.parametrize("layout", ["rows", "ring"])
@pytest.mark.parametrize("what", list(DROPS))
def test_what_is_not_the_promised_hop_drops_the_row_and_nobody_reads_it(what, layout):
    model = LAYOUTS[layout]
    ex = _executor(model, lanes=2 if what == "fork" else 1)  # a fork's child takes a lane
    s = Session(ex, "s").run(hops=3)
    lane = ex._sessions["s"]
    rec = _ahead_of(ex, "s")
    assert rec is not None and rec.pos == s.pos == ex.engine.lengths[lane]
    # the row counts as written: a ring has given up its oldest slot to it
    assert ex._lane_hi[lane] == s.pos + 1
    DROPS[what](ex, s)
    st = ex.stats()
    assert st["ahead_dropped"] == 1 and _at_rest(ex) == 0 and not ex._ahead
    # the lane's next session reads what a fresh executor's would
    nxt = Session(ex, "next", prompt=OTHER)
    assert ex._sessions["next"] == lane
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


def test_an_evicted_sessions_row_goes_with_it():
    ex = _executor("tiny", lanes=1)
    Session(ex, "old").run(hops=2)
    assert ex._ahead
    new = Session(ex, "new", prompt=OTHER).run().end()  # the one lane is taken from `old`
    assert new.out == _fresh_stream("tiny", OTHER)
    assert ex.stats()["ahead_dropped"] == 1 and _at_rest(ex) == 0


# ---------------------------------------------------------------------------
# (d) a lone session, a newcomer, a late one
# ---------------------------------------------------------------------------


def test_a_lone_sessions_first_hop_is_answered_with_no_other_submit():
    ex = _executor("tiny")
    s = Session(ex, "s")
    s.hop()  # returns: the flusher waited for the step itself
    st = ex.stats()
    # ... and ran the promised hop's row right behind it
    assert st["batched_steps"] == 2 and st["ahead_rows"] == 1 and st["ahead_claimed"] == 0
    s.hop()
    assert ex.stats()["ahead_claimed"] == 1 and ex.stats()["batched_steps"] == 3
    assert s.run().out == _fresh_stream("tiny", PROMPT)


def test_a_newcomer_rides_beside_the_running_and_their_rows_go_on():
    ex = _executor("tiny")
    alone = [_fresh_stream("tiny", p, new=24) for p in (PROMPT, OTHER)]
    late_alone = _fresh_stream("tiny", PROMPT[:3])
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


def test_a_session_that_comes_back_late_is_left_out_and_rejoins():
    ex = _executor("tiny")
    want_a, want_b = _fresh_stream("tiny", PROMPT, new=12), _fresh_stream("tiny", OTHER, new=12)
    a, b = Session(ex, "a", new=12), Session(ex, "b", prompt=OTHER, new=12)
    a.run(hops=3), b.run(hops=3)
    rows = ex.stats()["ahead_rows"]
    rec = _ahead_of(ex, "a")
    b.run(hops=5)  # `a` sits five steps out: its one row waits, no other is run for it
    assert _ahead_of(ex, "a") is rec and ex.stats()["ahead_rows"] == rows + 5
    a.run(), b.run()
    assert a.out == want_a and b.out == want_b
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


@pytest.mark.parametrize("when", ["before_the_drain", "inside_the_drain"])
def test_a_step_somebody_else_finished_still_feeds_the_next(when, monkeypatch):
    """A rider's own thread may finish the last step at any moment: before
    the drain looks (the host's copy feeds the next step), or between the
    drain's look and its dispatch (the device's copy must still be there)."""
    ex = _executor("tiny")
    s = Session(ex, "s").run(hops=2)
    if when == "inside_the_drain":
        from inferd_tpu.core import sampling as samplib

        real = samplib.ahead_rows

        def finished_meanwhile(packed, *rest):
            ex._finish(ex._last_step)
            return real(packed, *rest)

        monkeypatch.setattr(samplib, "ahead_rows", finished_meanwhile)
    while not s.over:
        if when == "before_the_drain":
            ex._finish(ex._last_step)
        s.hop()
    assert s.out == _fresh_stream("tiny", PROMPT)
    assert ex.stats()["ahead_dropped"] == 0 and _at_rest(ex) == 0


def test_behind_a_prefill_chunk_the_drain_waits_out_the_last_step_first():
    """While a prefill chunk is on the device the next step cannot start,
    so the drain first waits for the step dispatched before it (and so takes
    in a newcomer whose prefill is ending) and feeds the next step from the
    host's copy: same stream, every row still run ahead and claimed."""

    class OnTheDevice:
        def is_ready(self):
            return False

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


def test_a_hop_with_logits_rides_beside_rows_run_ahead():
    ex = _executor("tiny")
    want = _fresh_stream("tiny", PROMPT, new=16)
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
    assert a.out == want and got == _fresh_stream("tiny", OTHER, new=4)
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
                           "block": {"known": 0, "ahead": 8}})
    assert len(res["tokens"][0]) == blk


def _speculating(ex):
    ex.enable_spec(2, 2)
    Session(ex, "s").run().end()  # a promise, but speculative rounds read the lanes' lengths


OTHER_PATHS = {
    "no_budget_key": ("tiny", {}, _no_budget),
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


def test_the_ask_carries_the_budget():
    from inferd_tpu.runtime import executor as execlib

    ask = execlib.parse_decode_ask({"sampling": {}, "ahead": 5, "eos": 2})
    assert (ask.ahead, ask.eos) == (5, 2)
    ask = execlib.parse_decode_ask({"sampling": {}})
    assert (ask.ahead, ask.eos) == (0, -1)  # every caller that says nothing
    assert execlib.parse_decode_ask({"sampling": {}, "ahead": -3}).ahead == 0
    assert execlib.parse_ask({"ahead": 5}).ahead == 0  # a block call, a K-step call: not theirs
