"""Layer device. Share of the whole window in which the host saw a device
step in flight: the union of the `device` spans (jitted call ->
block_until_ready) clipped to the window, over the window; /spans, host
clock of the node. It reads the run's 45 s with the profiler off for all
but the capture's 4 s, where `device.idle_share` reads the capture alone;
it counts a step's dispatch and the wake-up after it as busy, so it is an
upper bound on the chip's own busy share."""

import reduce_trace


def read(run):
    w0, w1 = run["wall0"], run["wall1"]
    dev = [(max(s["t0"], w0), min(s["t1"], w1)) for s in run["spans"]
           if s.get("name") == "device" and s["t1"] > w0 and s["t0"] < w1]
    if not dev:
        return None
    return 100.0 * reduce_trace.union_s(dev) / (w1 - w0)
