#!/usr/bin/env python3
"""From a `jax.profiler` trace (.xplane.pb) to the numbers the per-layer
metrics read. Run as a child under JAX_PLATFORMS=cpu: reading a trace needs
JAX's reader and no device.

What a v5e trace holds (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Modules` has one event for each
execution of a jitted program (named `jit_<function>(<fingerprint>)`) and
whose line `XLA Ops` has one event for each operation inside it, a loop
such as the scan over the layers as one long event with its body's
operations as further events beside it (the union counts the time once);
`Async XLA Ops` are copies in flight and are not counted as busy. Host
threads are lines of the plane `/host:CPU` (several python threads share
the line name `python3`). Times are nanoseconds from the start of the
capture: the first host event sits at 0.4 ms. On the CPU backend (rehearsals only) there is no
device plane, and the XLA client's threads stand in for one.

Reduced:
  window_s      first to last event over all planes (the traced window)
  devices       per chip: busy_s = union of its operation intervals
  busy_s        mean over chips
  modules       per program name: count, total_s, median_s (first chip
                that ran it, so that a pipelined program counts once)
  device_ops    [[operation name, seconds]] the 10 that took most time,
                summed over executions, mean over chips
  idle_gaps     [[what the host was doing, seconds]]: every idle gap of
                chip 0 longer than MIN_GAP_S, given to the node span that
                covers most of it (the shortest such span when several
                cover it all), "no span" where none does; summed by name
  alignment     how the trace was put on the spans' clock

Alignment. The node records a `capture` span whose t0 is taken as
`start_trace` returns; the trace's own clock starts as `start_trace` is
called and the python line has that call as an event. So wall = capture.t0
+ (t - end of the start_trace event), good to a few milliseconds. It is
then refined: the shift within +-SEARCH_S that puts most program
executions inside the node's `compute` spans (each executor call runs its
program inside one). Gaps shorter than MIN_GAP_S are not attributed. A
`compute` span starts before the executor's device lock is taken, so a gap
under `compute` is host work or lock hand-over inside an executor call; "no
span" is the node's own token loop between two calls, or no request.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import statistics
import sys

MIN_GAP_S = 0.002
SEARCH_S = 0.05
STEP_S = 0.0005
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# `generate`, the umbrella span of a whole request, covers everything and says nothing
ATTRIBUTED = ("compute", "queue", "window", "wire", "relay", "sample", "step", "forward")
NAME_CHARS = 96  # an operation's name is its whole HLO line: keep the head


def union_s(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals, t0: float, t1: float):
    """Idle (start, end) stretches of [t0, t1] not covered by intervals."""
    out, end = [], t0
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, t1)))
        end = max(end, b)
        if end >= t1:
            break
    if end < t1:
        out.append((end, t1))
    return [(a, b) for a, b in out if b > a]


def module_name(event_name: str) -> str:
    """`jit__decode_logits(123456)` -> `jit__decode_logits`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def find_module(modules: dict, pattern: str):
    """Of the reduced `modules`, the one matching `pattern` (a regular
    expression on the program's name) that ran most often, or None."""
    hits = [m for name, m in modules.items() if re.search(pattern, name)]
    return max(hits, key=lambda m: m["count"]) if hits else None


def read_planes(path: str):
    """The trace as {plane: {line: [(name, start_s, end_s)]}}."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events
            )
    return planes


def device_lines(planes: dict):
    """[(chip name, module events, op events)] for every chip."""
    out = []
    for name in sorted(planes, key=lambda n: (len(n), n)):
        if DEVICE_PLANE.match(name):
            lines = planes[name]
            modules = lines.get("XLA Modules", [])
            ops = lines.get("XLA Ops", []) or modules
            if ops:
                out.append((name, modules, ops))
    if out:
        return out
    # CPU backend (rehearsal): the XLA client's threads stand in for a device
    ops = [
        e for line, evs in planes.get("/host:CPU", {}).items()
        if line.startswith("tf_XLA") for e in evs
        if e[2] > e[1] and not e[0].startswith("ThreadpoolListener")
    ]
    return [("cpu-threads", [], ops)] if ops else []


def fit_shift(executions, compute_spans, base: float):
    """The shift near `base` that puts most executions inside a span.
    Returns (shift, share inside)."""
    if not executions or not compute_spans:
        return base, None
    executions = executions[:: max(1, len(executions) // 2000)]  # enough to fit on
    starts = sorted(s for s, _e in compute_spans)
    ends = {s: e for s, e in compute_spans}

    def inside(shift: float) -> int:
        n = 0
        for a, b in executions:
            i = bisect.bisect_right(starts, a + shift) - 1
            if i >= 0 and b + shift <= ends[starts[i]]:
                n += 1
        return n

    steps = int(SEARCH_S / STEP_S)
    # ties go to the middle of the best stretch, not to its edge
    scored = [(inside(base + k * STEP_S), k) for k in range(-steps, steps + 1)]
    best = max(n for n, _k in scored)
    ks = [k for n, k in scored if n == best]
    return base + ks[len(ks) // 2] * STEP_S, best / len(executions)


def attribute(gap, spans_by_name) -> str:
    """The node span that covers most of the gap; of several that cover it
    equally, the shortest."""
    a, b = gap
    best = ("no span", 0.0, float("inf"))
    for name, spans in spans_by_name.items():
        for s0, s1 in spans:
            if s1 <= a or s0 >= b:
                continue
            cover = min(b, s1) - max(a, s0)
            if cover > best[1] + 1e-9 or (abs(cover - best[1]) <= 1e-9 and s1 - s0 < best[2]):
                best = (name, cover, s1 - s0)
    return best[0] if best[1] >= 0.5 * (b - a) else "no span"


def reduce(planes: dict, spans) -> dict:
    spans_of_lines = [(min(e[1] for e in evs), max(e[2] for e in evs))
                      for lines in planes.values() for evs in lines.values() if evs]
    if not spans_of_lines:
        raise ValueError("the trace holds no event")
    t0, t1 = min(a for a, _b in spans_of_lines), max(b for _a, b in spans_of_lines)
    chips = device_lines(planes)
    if not chips:
        raise ValueError("the trace holds no device operation")

    devices, op_totals = [], {}
    for name, _modules, ops in chips:
        devices.append({"name": name, "events": len(ops),
                        "busy_s": union_s((a, b) for _n, a, b in ops)})
        for op, a, b in ops:
            op = op[:NAME_CHARS]
            op_totals[op] = op_totals.get(op, 0.0) + (b - a) / len(chips)
    modules = {}
    for _name, mods, _ops in chips:
        seen = {}
        for ev, a, b in mods:
            seen.setdefault(module_name(ev), []).append(b - a)
        for mod, ds in seen.items():
            if mod not in modules:
                modules[mod] = {"count": len(ds), "total_s": sum(ds),
                                "median_s": statistics.median(ds)}

    # -- onto the spans' clock ---------------------------------------------
    capture = next((s for s in spans if s.get("name") == "capture"), None)
    alignment = {"method": "none"}
    spans_by_name, idle = {}, []
    if capture is not None:
        started = min(
            (b for evs in planes.get("/host:CPU", {}).values() for n, _a, b in evs
             if "start_trace" in n), default=t0,
        )
        base = capture["t0"] - started
        chip0 = chips[0]
        execs = [(a, b) for _n, a, b in (chip0[1] or chip0[2])]
        compute = [(s["t0"], s["t1"]) for s in spans if s.get("name") == "compute"]
        shift, share = fit_shift(execs, compute, base)
        alignment = {"method": "capture span, refined on compute spans",
                     "shift_from_capture_ms": (shift - base) * 1e3,
                     "executions_inside_compute": share,
                     "good_to_ms": STEP_S * 1e3 if share else 5.0}
        for name in ATTRIBUTED:
            spans_by_name[name] = [(s["t0"] - shift, s["t1"] - shift)
                                   for s in spans if s.get("name") == name]
    by_name = {}
    for gap in gaps([(a, b) for _n, a, b in chips[0][2]], t0, t1):
        if gap[1] - gap[0] >= MIN_GAP_S:
            who = attribute(gap, spans_by_name) if spans_by_name else "not attributed"
            by_name[who] = by_name.get(who, 0.0) + gap[1] - gap[0]
            idle.append(gap[1] - gap[0])
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {
        "window_s": t1 - t0,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "devices": devices,
        "modules": modules,
        "device_ops": top(op_totals),
        "idle_gaps": top(by_name),
        "long_gaps": {"count": len(idle), "total_s": sum(idle), "min_gap_s": MIN_GAP_S},
        "alignment": alignment,
        "structure": {p: {ln: len(evs) for ln, evs in lines.items()}
                      for p, lines in planes.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--spans", help="JSON list of the node's spans")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spans = []
    if args.spans:
        with open(args.spans) as f:
            spans = json.load(f)
    result = reduce(read_planes(args.trace), spans)
    with open(args.out, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
