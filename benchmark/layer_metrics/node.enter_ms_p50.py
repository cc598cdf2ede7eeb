"""Layer node_http. The forward path's prologue on the event loop: over the
decode and block hops whose `step` started inside the window, the median of
`queue`.t0 - `step`.t0: the envelope, the in-process call into the forward
path, its deadline, stage and session checks and counters; /spans, host
clock of the node. None where there is no hop (turns.py)."""

import turns


def read(run):
    return turns.median_ms(p[2] - p[1] for p in map(turns.enter, turns.hops(run)))
