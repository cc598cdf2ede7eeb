"""Layer node_http. Median `accept` span that started inside the window:
`/generate` arrived at the node (`_handle_generate_inner` entered) -> the
generation loop's `generate` span opens: the body's read, the unpack, the
sampling config, the loop's client, the stream's `prepare`; what the event
loop's queue costs a newcomer before its first chunk; /spans, host clock of
the node. None on a program that stamps no `accept`."""

import spans


def read(run):
    return spans.median_ms(run, "accept")
