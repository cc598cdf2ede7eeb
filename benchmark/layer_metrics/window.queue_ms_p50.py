"""Layer lane_window. Median `batch_wait` span that started inside the
window: a decode step's wait in the arrival window of runtime/window.py,
from its submit until a flush takes it into a batch; /spans, host clock of
the node. (`/stats` `executor` carries the mean of the same stamps as
queue_wait_ms_sum / queue_waits.)"""

import spans


def read(run):
    return spans.median_ms(run, "batch_wait")
