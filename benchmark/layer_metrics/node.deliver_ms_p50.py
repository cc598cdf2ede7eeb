"""Layer node_http. Median `deliver` span that started inside the window:
from the return of the `copy_out` of the step that served a decode or block
call (the flusher's stamp, one a step) to that call's worker thread being
back from the executor: the routing counters, the rows cut out, the events
set, the worker's wake-up; /spans, host clock of the node. None on a program
that stamps no `deliver`."""

import spans


def read(run):
    return spans.median_ms(run, "deliver")
