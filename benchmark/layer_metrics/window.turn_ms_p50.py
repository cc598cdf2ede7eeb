"""Layer lane_window. The median TURN: for every `turn` span of the window
(runtime/window.py: the device freed -> the next drain of the same window,
one a step) that started inside the run's window, was somebody's
(`expected` > 0) and in which no prefill cut in (its `formed_ms`, free ->
formation over, within 1 ms of its length), the span's length; /spans, host
clock of the node. What the chip waits between two steps for the sessions
to come back. None on a program that stamps no `turn`."""

import spans
import turns


def read(run):
    return turns.median_ms(
        t["t1"] - t["t0"] for t in turns.turns(run)
        if abs(spans.ms(t) - t["attrs"]["formed_ms"]) <= 1.0
    )
