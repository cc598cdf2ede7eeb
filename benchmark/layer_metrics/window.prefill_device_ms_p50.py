"""Layer lane_window. Median `device` span of kind `prefill` that started
inside the window: one prefill chunk, from just before the jitted call
until block_until_ready on its result returns; /spans, host clock of the
node."""

import spans


def read(run):
    return spans.median_ms(run, "device", kind="prefill")
