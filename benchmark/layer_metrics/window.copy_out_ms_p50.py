"""Layer lane_window. Median `copy_out` span that started inside the
window: np.asarray of a finished step's logits, device to host, after
block_until_ready has returned (so the wait for the device is not in it);
/spans, host clock of the node."""

import spans


def read(run):
    return spans.median_ms(run, "copy_out")
