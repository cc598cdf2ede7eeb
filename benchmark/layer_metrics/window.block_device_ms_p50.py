"""Layer lane_window. Median `device` span of kind `block` that started
inside the window: from just before the jitted block step (every denoising
pass and the commit, one dispatch) is called until block_until_ready on its
result returns, one span a device step; /spans, host clock of the node.
None where the program stamps no such span."""

import spans


def read(run):
    return spans.median_ms(run, "device", kind="block")
