"""What the readers of the host turn share: the stretch between one decode
(or block) step's copy-out and the next step's drain, laid out in abutting
parts from the node's spans (docs/OBSERVABILITY.md "Between two steps").
Plain Python on the list `/spans` gave, as `spans.py` beside it.

A HOP is one executor call of kind `decode` or `block` with everything the
program stamps around it: `step` (the generation loop's) <- `forward` <-
`queue`, `resume`, `compute` <- `lock_wait` / `batch_wait`, `deliver`. A hop
that lacks one of them (the ring dropped it, or the program is older than
the `deliver` and `resume` spans) is no hop, so on such a program every
reader here reads None. `prev` / `next_step` follow one generation: the
`step` spans of one trace id in the order of their t0.

A TURN is a `turn` span of the window (runtime/window.py): the device freed
-> the next drain, its `last` the `compute` of the entry whose submit came
last. One whose formation was owed no session (`expected` 0: the node was
idle) is nobody's turn and is left out everywhere.

The parts of one session's way from the step that served it (hop `prev`) to
the drain of the step that serves it next (hop `hop`), each (name, t0, t1),
each starting where the one before it ends:

  deliver   prev.deliver            copy_out returned -> its worker is back
  resume    prev.resume             -> its coroutine runs again on the loop
  reply     prev.resume.t1 -> prev.step.t1     the reply's way to the loop
  between   prev.step.t1 -> hop.step.t0        sample, log-probs, emit
  enter     hop.step.t0 -> hop.queue.t0        the forward path's prologue
  queue     hop.queue               the hand-over to the worker pool
  admit     hop.compute.t0 -> submit           the executor before the window
  wait      submit -> the drain (`lock_wait` + `batch_wait`)
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

import arith

HOP_KINDS = ("decode", "block")
Part = Tuple[str, float, float]


def index(run: dict) -> dict:
    """{"hops": [...], "by_compute": {compute span id: hop}}, built once a
    run (kept on it: twelve readers share it)."""
    if "_turn_index" in run:
        return run["_turn_index"]
    by_id = {s["span"]: s for s in run["spans"] if "span" in s}
    kids: Dict[str, List[dict]] = {}
    steps: Dict[str, List[dict]] = {}
    for s in run["spans"]:
        if s.get("parent") is not None:
            kids.setdefault(s["parent"], []).append(s)
        if s.get("name") == "step":
            steps.setdefault(s.get("trace"), []).append(s)
    next_step = {}
    for of_trace in steps.values():
        of_trace.sort(key=lambda s: s["t0"])
        for a, b in zip(of_trace, of_trace[1:]):
            next_step[a["span"]] = b

    def child(parent: dict, name: str) -> Optional[dict]:
        return next((k for k in kids.get(parent["span"], ()) if k.get("name") == name), None)

    hops, by_step = [], {}
    for c in run["spans"]:
        kind = (c.get("attrs") or {}).get("kind")
        if c.get("name") != "compute" or kind not in HOP_KINDS:
            continue
        forward = by_id.get(c.get("parent"))
        step = by_id.get(forward.get("parent")) if forward else None
        if step is None or step.get("name") != "step":
            continue
        waits = [k for k in kids.get(c["span"], ()) if k.get("name") in ("lock_wait", "batch_wait")]
        hop = {
            "kind": kind, "compute": c, "forward": forward, "step": step,
            "queue": child(forward, "queue"), "resume": child(forward, "resume"),
            "deliver": child(c, "deliver"),
            "submit": min((w["t0"] for w in waits), default=None),
            "next_step": next_step.get(step["span"]), "prev": None,
        }
        if None in (hop["queue"], hop["resume"], hop["deliver"], hop["submit"]):
            continue
        hops.append(hop)
        by_step[step["span"]] = hop
    for hop in hops:  # the hop of the generation's next `step`, where that is one, follows this one
        after = hop["next_step"] and by_step.get(hop["next_step"]["span"])
        if after:
            after["prev"] = hop
    run["_turn_index"] = {"hops": hops, "by_compute": {h["compute"]["span"]: h for h in hops}}
    return run["_turn_index"]


def hops(run: dict) -> List[dict]:
    """The hops whose `step` started inside the window."""
    return [h for h in index(run)["hops"]
            if run["wall0"] <= h["step"]["t0"] <= run["wall1"]]


def turns(run: dict, clip: bool = False) -> List[dict]:
    """The `turn` spans that were somebody's (`expected` > 0): those that
    started inside the window, or with `clip` every one that touches it."""
    w0, w1 = run["wall0"], run["wall1"]
    return [
        s for s in run["spans"]
        if s.get("name") == "turn" and (s.get("attrs") or {}).get("expected", 0) > 0
        and (s["t1"] > w0 and s["t0"] < w1 if clip else w0 <= s["t0"] <= w1)
    ]


def median_ms(values) -> Optional[float]:
    return arith.percentile([v * 1e3 for v in values], 50)


def reply(hop: dict) -> Part:
    return ("reply", hop["resume"]["t1"], hop["step"]["t1"])


def between(hop: dict) -> Optional[Part]:
    nxt = hop["next_step"]
    return ("between", hop["step"]["t1"], nxt["t0"]) if nxt else None


def enter(hop: dict) -> Part:
    return ("enter", hop["step"]["t0"], hop["queue"]["t0"])


def admit(hop: dict) -> Part:
    return ("admit", hop["compute"]["t0"], hop["submit"])


def parts(prev: dict, hop: dict, t_drain: float) -> List[Part]:
    """One session's way from hop `prev` to the drain that took hop `hop`
    (the module's table), in order."""
    return [
        ("deliver", prev["deliver"]["t0"], prev["deliver"]["t1"]),
        ("resume", prev["resume"]["t0"], prev["resume"]["t1"]),
        reply(prev),
        ("between", prev["step"]["t1"], hop["step"]["t0"]),
        enter(hop),
        ("queue", hop["queue"]["t0"], hop["queue"]["t1"]),
        admit(hop),
        ("wait", hop["submit"], t_drain),
    ]


def last_chains(run: dict) -> Tuple[List[Tuple[dict, List[Part]]], int]:
    """([(turn, the parts of its `last` entry)], turns skipped): a turn is
    skipped where its `last` names no hop of the run or that session's
    step before was no hop (a prefill chunk, or its spans are gone)."""
    by_compute = index(run)["by_compute"]
    out, skipped = [], 0
    for t in turns(run):
        hop = by_compute.get((t.get("attrs") or {}).get("last"))
        if hop is None or hop["prev"] is None:
            skipped += 1
            continue
        out.append((t, parts(hop["prev"], hop, t["t1"])))
    return out, skipped


def inside(part: Part, t0: float, t1: float) -> float:
    """Seconds of `part` that fall inside [t0, t1]."""
    return max(0.0, min(part[2], t1) - max(part[1], t0))


def merged(intervals) -> List[Tuple[float, float]]:
    """The union of (start, end) intervals as disjoint ones, in order."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered_s(disjoint: List[Tuple[float, float]], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] under `disjoint` (what `merged` returns)."""
    i = max(0, bisect.bisect_right(disjoint, (t0, float("inf"))) - 1)
    total = 0.0
    while i < len(disjoint) and disjoint[i][0] < t1:
        total += max(0.0, min(disjoint[i][1], t1) - max(disjoint[i][0], t0))
        i += 1
    return total
