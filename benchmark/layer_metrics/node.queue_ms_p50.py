"""Layer node_http. Median `queue` span that started inside the window: a
/forward request's wait from its hand-over to the node's worker pool until
a worker thread picks it up; /spans, host clock of the node. Under --mesh
without lanes the pool has two threads, so this is where the sessions that
a pipeline pass could have served are waiting."""

import spans


def read(run):
    return spans.median_ms(run, "queue")
