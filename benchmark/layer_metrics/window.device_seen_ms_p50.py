"""Layer lane_window. The median `device` span of kind `decode` or `block`
that started inside the window and whose end somebody saw (`waited` = 1: the
thread that came for the step waited for it, so the span ends at the step's
end to a wake-up): the step itself where `window.device_ms_p50` reads to the
next drain. The program starts a step's span at its dispatch or at the end of
the STEP before it, so a step that queued behind a prefill chunk carries the
chunk: a step inside whose span a `device` span of kind `prefill` ENDED is
left out (the device runs what it is handed in order: a chunk whose end was
seen before the step's ran before it; one dispatched behind the step ends
after it and takes nothing). Where prefill takes most of the chip few steps
are left (30 of 655 in `q3n-long-docs`: PERF.md section 6, PR 53); /spans,
host clock of the node. None where no such step of the window was waited
for, or on a program that stamps no `waited`."""

import bisect

import arith
import spans


def read(run):
    chunk_ends = sorted(
        s["t1"] for s in run["spans"]
        if s.get("name") == "device" and (s.get("attrs") or {}).get("kind") == "prefill"
    )
    return arith.percentile(
        [spans.ms(s) for kind in ("decode", "block")
         for s in spans.named(run, "device", kind=kind, waited=1)
         if bisect.bisect_right(chunk_ends, s["t0"]) == bisect.bisect_left(chunk_ends, s["t1"])],
        50)
