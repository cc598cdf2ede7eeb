"""Layer node_http. The reply's way back on the event loop: over the decode
and block hops whose `step` started inside the window, the median of
`step`.t1 - `resume`.t1: the scheduler's second lock and announce, the
forward path's epilogue (metrics, the `queue` / `compute` / `resume`
records, the reply), the `forward` record and the loop's unpacking; /spans,
host clock of the node. None on a program that stamps no `resume`."""

import turns


def read(run):
    return turns.median_ms(p[2] - p[1] for p in map(turns.reply, turns.hops(run)))
