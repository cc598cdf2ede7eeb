"""Layer node_http. Median `close` span that started inside the window: the
generation loop's `finally` entered -> the session is ended and its lane is
free again (`Node._end_session` takes the executor's lock on the loop's
thread); /spans, host clock of the node. None on a program that stamps no
`close`."""

import spans


def read(run):
    return spans.median_ms(run, "close")
