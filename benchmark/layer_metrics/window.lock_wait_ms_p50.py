"""Layer lane_window. Median `lock_wait` span that started inside the
window: an executor call's wait for the executor's device lock, behind the
other sessions' steps, once for every decode entry of a batch and every
prefill chunk; /spans, host clock of the node."""

import spans


def read(run):
    return spans.median_ms(run, "lock_wait")
