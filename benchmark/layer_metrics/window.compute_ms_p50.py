"""Layer lane_window. Median `compute` span (one executor call: the device
step, the copy of the logits to the host, the lock) that started inside the
window; /spans, host clock of the node."""

import arith


def read(run):
    return arith.percentile(
        arith.span_ms(run["spans"], "compute", run["wall0"], run["wall1"]), 50)
