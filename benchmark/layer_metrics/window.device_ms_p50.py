"""Layer lane_window. Median `device` span of kind `decode` that started
inside the window: from just before the jitted decode step is called until
block_until_ready on its result returns, one span a device step; /spans,
host clock of the node. The host's view of the program time that the trace
gives for the configuration's decode program."""

import spans


def read(run):
    return spans.median_ms(run, "device", kind="decode")
