"""Layer node_http. Median `emit` span that started inside the window: what
the caller's `on_token` callback took for one hop's tokens (a token; a
block's), in /generate the streamed line's write; /spans, host clock of the
node. None on a program that stamps no `emit`."""

import spans


def read(run):
    return spans.median_ms(run, "emit")
