"""The readers of the host turn (PR 40; benchmark/turns.py): each is fed a
hand-made run: three sessions over four steps with known parts, one turn
with a prefill cut in, one that gave up on a session: and returns the number
worked out by hand below; each reads None from the spans of a program that
stamps no `turn`, `deliver`, `resume` or `emit` (the parent commit of PR 40)
and from a `/stats` without formation counters."""

import pytest

import run as harness
import turns

READERS = [
    "window.turn_ms_p50", "window.turn_share", "window.gang_timeout_share",
    "node.deliver_ms_p50", "node.resume_ms_p50", "node.reply_ms_p50",
    "node.between_ms_p50", "node.emit_ms_p50", "node.enter_ms_p50",
    "window.admit_ms_p50", "node.loop_share_of_turn", "window.turn_unaccounted_ms_p50",
]
MS = 1e-3
T_OUT = [1001.0, 1002.0, 1003.0, 1004.0]  # the end of each step's copy_out
# the drain of the step that follows, since that copy_out: the last submit
# (17.5 ms); the same and a prefill of 20 ms that cut in; the cap (20 ms),
# the third session not back
DRAIN = [17.5, 37.5, 20.0]
LEAK = {1: 0.2, 2: 0.4, 3: 0.0}  # ms missing between `queue` and `compute` of the `last` hop


def span(name, t0, t1, sid=None, parent=None, trace="t", **attrs):
    s = {"name": name, "t0": t0, "t1": t1, "span": sid or f"{name}@{t0}@{parent}",
         "parent": parent, "trace": trace}
    if attrs:
        s["attrs"] = attrs
    return s


def a_run():
    """A 10 s window [1000, 1010]. Session i (0, 1, 2), since the copy_out
    of the step that served it: deliver 2 + i, resume 1 + 2i, reply 2,
    between 3 (an `emit` of 1 in it), enter 1, queue 2, admit 0.5: its
    submit comes 11.5 + 3i ms later, so session 2 is the last (17.5) and its
    parts on the loop (reply, between, enter) lie at [3 + 3i, 9 + 3i]. The
    window notes the device free 1 ms after the copy_out. Session 2 stays
    away 40 ms after step 2 (its `between`) and misses step 3."""
    out = []
    for i in range(3):
        gen = f"g{i}"
        out.append(span("generate", 1000.5, 1009.0, gen, None, gen))
        # its prefill chunk: a `step` of the generation that is no hop
        out += [
            span("step", 1000.8, 1000.9, f"sp{i}", gen, gen),
            span("forward", 1000.801, 1000.899, f"fp{i}", f"sp{i}", gen),
            span("queue", 1000.802, 1000.803, None, f"fp{i}", gen),
            span("compute", 1000.803, 1000.89, f"cp{i}", f"fp{i}", gen, kind="prefill", tokens=64),
            span("resume", 1000.89, 1000.891, None, f"fp{i}", gen),
        ]
        submit = 1000.99  # of its first decode call
        for k in range(4):
            if (i, k) == (2, 3):
                break  # session 2 is not back for step 3
            step, fwd, comp = f"s{i}.{k}", f"f{i}.{k}", f"c{i}.{k}"
            drain = T_OUT[k - 1] + DRAIN[k - 1] * MS if k else 1000.995
            last = k and i == (1 if k == 3 else 2)
            c0 = submit - 0.5 * MS
            w1 = T_OUT[k] + (2 + i) * MS  # the worker is back
            resumed = w1 + (1 + 2 * i) * MS
            s1 = resumed + 2 * MS
            out += [
                span("step", c0 - 3 * MS, s1, step, gen, gen),
                span("forward", c0 - 2.8 * MS, s1 - 0.5 * MS, fwd, step, gen, via="local"),
                span("queue", c0 - 2 * MS, c0 - (LEAK[k] * MS if last else 0), None, fwd, gen),
                span("compute", c0, w1, comp, fwd, gen, kind="decode", tokens=1),
                span("lock_wait", submit, submit + 0.1 * MS, None, comp, gen, kind="decode"),
                span("batch_wait", submit + 0.1 * MS, drain, None, comp, gen, flusher=int(i == 0)),
                span("deliver", T_OUT[k], w1, None, comp, gen),
                span("resume", w1, resumed, None, fwd, gen),
                span("emit", s1 + 1 * MS, s1 + 2 * MS, None, gen, gen, tokens=1),
            ]
            stays_away = (i, k) == (2, 2)
            submit = s1 + (40 if stays_away else 3) * MS + 3.5 * MS
            if stays_away:  # it does come back: a `step` whose other spans the ring dropped
                out.append(span("step", s1 + 40 * MS, s1 + 90 * MS, "s2.late", gen, gen))
    for k, (how, formed, cobatch, last) in enumerate(
            [("full", 16.5, 3, "c2.1"), ("full", 16.5, 3, "c2.2"), ("timeout", 19.0, 2, "c1.3")]):
        out.append(span(
            "turn", T_OUT[k] + 1 * MS, T_OUT[k] + DRAIN[k] * MS, f"turn{k + 1}", None, f"w{k}",
            kind="decode", cobatch=cobatch, expected=3, how=how, formed_ms=formed,
            first_ms=10.5, last=last))
    # nobody's: the node idle before the first step; and one that began
    # before the window, 10 ms of it inside
    out.append(span("turn", 1000.1, 1000.7, "idle", None, "wi", kind="decode", cobatch=1,
                    expected=0, how="solo", formed_ms=600.0, first_ms=600.0, last="cp0"))
    out.append(span("turn", 999.99, 1000.01, "early", None, "we", kind="decode", cobatch=2,
                    expected=2, how="full", formed_ms=20.0, first_ms=5.0, last="gone"))
    return {
        "spans": sorted(out, key=lambda s: s["t0"]), "wall0": 1000.0, "wall1": 1010.0,
        "stats0": {"executor": {"gang_full": 10, "gang_timeout": 1}},
        "stats1": {"executor": {"gang_full": 13, "gang_timeout": 2}},
    }


EXPECT = {
    # turn 1: 16.5 (formed 16.5); turn 3: 19 (formed 19); turn 2 is 36.5 against 16.5 formed: left out
    "window.turn_ms_p50": pytest.approx(17.75),
    # 16.5 + 36.5 + 19 + the 10 ms of the early one, of 10 s
    "window.turn_share": pytest.approx(100 * 0.082 / 10),
    "window.gang_timeout_share": pytest.approx(25.0),  # 1 of 3 + 1
    "node.deliver_ms_p50": pytest.approx(3.0),   # four of 2, four of 3, three of 4
    "node.resume_ms_p50": pytest.approx(3.0),    # four of 1, four of 3, three of 5
    "node.reply_ms_p50": pytest.approx(2.0),
    "node.between_ms_p50": pytest.approx(3.0),   # eight of 3 and session 2's 40; the prefill's step starts none
    "node.emit_ms_p50": pytest.approx(1.0),
    "node.enter_ms_p50": pytest.approx(1.0),
    "window.admit_ms_p50": pytest.approx(0.5),
    # on the loop since the copy_out: [3, 9], [6, 12], [9, 15]: 12 of turn 1 and of turn 2;
    # turn 3: [3, 9], [6, 12] and session 2's [9, 51] cut at the drain (20): 17; none of the early one
    "node.loop_share_of_turn": pytest.approx(100 * (12 + 12 + 17) / 82),
    # the parts abut but for the `last` hop's queue, which ends 0.2, 0.4, 0 ms early
    "window.turn_unaccounted_ms_p50": pytest.approx(0.2, abs=1e-6),
}


@pytest.mark.parametrize("metric", READERS)
def test_reader_returns_the_number_worked_out_by_hand(metric):
    assert harness.load_reader(metric)(a_run()) == EXPECT[metric]


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_none_from_the_parents_spans(metric):
    run = a_run()
    run["spans"] = [s for s in run["spans"]
                    if s["name"] not in ("turn", "deliver", "resume", "emit")]
    run["stats0"] = run["stats1"] = {"executor": {"batched_steps": 3}}
    assert harness.load_reader(metric)(run) is None


def test_the_last_entrys_parts_run_from_the_copy_out_to_the_drain():
    chains, skipped = turns.last_chains(a_run())
    assert skipped == 0 and [t["span"] for t, _ in chains] == ["turn1", "turn2", "turn3"]
    turn, parts = chains[1]  # the prefill cut in: 20 ms more in the window
    assert [p[0] for p in parts] == [
        "deliver", "resume", "reply", "between", "enter", "queue", "admit", "wait"]
    assert [round((b - a) * 1e3, 3) for _, a, b in parts] == [
        4.0, 5.0, 2.0, 3.0, 1.0, 1.6, 0.5, 20.0]
    assert parts[0][1] == T_OUT[1] and parts[-1][2] == turn["t1"]


def test_a_turn_whose_last_cannot_be_walked_is_skipped():
    run = a_run()
    run["spans"] = [s for s in run["spans"]
                    if not (s["name"] == "deliver" and s["parent"] == "c2.1")]
    chains, skipped = turns.last_chains(run)
    # turn 1's `last` is no hop any more; turn 2's `last` has no hop before it
    assert skipped == 2 and [t["span"] for t, _ in chains] == ["turn3"]


def test_every_new_reader_is_in_the_manifest_for_the_four_cells_that_decode():
    import json
    import os

    with open(os.path.join(harness.REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in READERS:
        assert per_layer[metric]["workloads"] == [
            "q4b-sat-chat", "q8b-pp4-sat-chat", "dsv2l-long-chat", "sdar-block-chat"]
        assert per_layer[metric]["moves"] == "out_tok_s"
