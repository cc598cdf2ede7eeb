"""Layer lane_window. The executor before the window: over the decode and
block calls whose `step` started inside the window, the median of the call's
submit (the t0 of its `lock_wait`, or of its `batch_wait` where it has none)
less `compute`.t0: the executor's own table and lock; under `--mesh` a call
that arrives under a running pass waits here; /spans, host clock of the
node. None where there is no hop (turns.py)."""

import turns


def read(run):
    return turns.median_ms(p[2] - p[1] for p in map(turns.admit, turns.hops(run)))
