"""Layer node_http. A request's admission, the median over the requests that
arrived inside the window: its `accept`.t0 (the /generate handler's first
line, microseconds after the server's `generate` span opens; that span itself
is recorded when the request ENDS, after a window's last read for the longest
answers) -> the `t1` of that trace's `step` with `first` = 1: arrival to the
first answered decode (block) hop; /spans, host clock of the node. The stretch
is tiled by `accept`, `open`, the prefill chunks' `step`s, the first token's
`sample` and `emit` and that first hop's `step` (docs/OBSERVABILITY.md "A
request's admission"), all kept by the node's ring. The instrument's own check
rides along: where those parts leave more than 1 ms uncovered (the median over
the requests), the reader reads None and says nothing. None on a program that
stamps no `accept` or marks no `first` step."""

import arith
import reduce_trace

PARTS = ("accept", "open", "step", "sample", "emit")


def admissions(run):
    """[(admission seconds, seconds of it under none of PARTS)], a request
    that arrived inside the window and whose first hop was answered."""
    by_trace = {}
    for s in run["spans"]:
        if s.get("name") in PARTS:
            by_trace.setdefault(s.get("trace"), []).append(s)
    out = []
    for of_trace in by_trace.values():
        came = next((s for s in of_trace if s["name"] == "accept"), None)
        first = next((s for s in of_trace
                      if s["name"] == "step" and (s.get("attrs") or {}).get("first")), None)
        if came is None or first is None or not run["wall0"] <= came["t0"] <= run["wall1"]:
            continue
        t0, t1 = came["t0"], first["t1"]
        covered = reduce_trace.union_s(
            (max(s["t0"], t0), min(s["t1"], t1)) for s in of_trace
            if s["t1"] > t0 and s["t0"] < t1
        )
        out.append((t1 - t0, t1 - t0 - covered))
    return out


def read(run):
    found = admissions(run)
    if not found or arith.percentile([gap * 1e3 for _, gap in found], 50) > 1.0:
        return None
    return arith.percentile([whole * 1e3 for whole, _ in found], 50)
