"""Layer loadgen. How late the generator sent against its schedule (closed
loop: against the previous completion), 99th percentile over the requests
sent in the window, on the generator's own clock. A starved generator
would otherwise read as a fast server."""

import arith


def read(run):
    lags = [lag for r, lag in zip(run["requests"], run["lags_ms"])
            if run["w0"] <= r["sent"] < run["w1"]]
    return arith.percentile(lags, 99)
