"""Layer node_http. A session's wait for the event loop: over the decode and
block hops whose `step` started inside the window, the median `resume` span,
from the worker being back from the executor to the hop's coroutine running
again on the node's one loop (`call_soon_threadsafe`, then the other
sessions' callbacks ahead of it); /spans, host clock of the node. None on a
program that stamps no `resume`."""

import turns


def read(run):
    return turns.median_ms(
        h["resume"]["t1"] - h["resume"]["t0"] for h in turns.hops(run)
    )
