"""Layer node_http. Median `open` span that started inside the window: the
generation loop's `generate` span opened -> its first chunk's `step` begins
(the block length, the session's id, a pinned prefix's fork); /spans, host
clock of the node. None on a program that stamps no `open`."""

import spans


def read(run):
    return spans.median_ms(run, "open")
