"""Layer loadgen. Median of send to first streamed token over the requests
sent in the window, at the benchmark's client: the number that is the
end-to-end `ttft_ms_p50` in cells with enough first tokens. In the
saturating cells a window holds under 20 of them and which prompt lengths
fall into it follows the seed (spread 9.7 % over seeds, under 1 % between
two runs of one seed; my chip runs, PR 24), so there it is recorded, not
judged. Left out where it is not finite."""

import math

import arith


def read(run):
    v = arith.percentile(arith.ttft_ms(run["requests"], run["w0"], run["w1"]), 50)
    return v if v is not None and math.isfinite(v) else None
