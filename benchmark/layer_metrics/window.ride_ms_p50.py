"""Layer lane_window. Median `ride` span that started inside the window: a
hop for which no row had been run ahead (a session's first decode or block
hop) was handed the step it rides -> its own thread has waited that step out
and is released (`StepAhead._ridden`; `behind` says how many steps the device
still had before it); /spans, host clock of the node. `executor.ride_ms_sum`
over `executor.rides` is the same without loss. None on a program that
stamps no `ride`."""

import spans


def read(run):
    return spans.median_ms(run, "ride")
