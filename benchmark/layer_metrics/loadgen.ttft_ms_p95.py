"""Layer loadgen. 95th percentile of send to first streamed token over the
requests sent in the window, at the benchmark's client. Few samples today:
recorded, not judged. Left out where it is not finite (a first token that
had not come by the window's end sits in the tail)."""

import math

import arith


def read(run):
    v = arith.percentile(arith.ttft_ms(run["requests"], run["w0"], run["w1"]), 95)
    return v if v is not None and math.isfinite(v) else None
