"""Layer node_http. What the generation loop does between two hops: over the
decode and block hops whose `step` started inside the window, the median of
the same generation's next `step`.t0 - this `step`.t1: the sampler, the
log-probabilities, the `emit`, the loop's bookkeeping, and whatever of the
other sessions' work the one event loop put in between; /spans, host clock
of the node. A prefill chunk's `step` is no start. None where there is no
hop (turns.py)."""

import turns


def read(run):
    return turns.median_ms(
        p[2] - p[1] for p in map(turns.between, turns.hops(run)) if p is not None
    )
