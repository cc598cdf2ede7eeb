"""Layer lane_window. The complement of `window.turn_ms_p50`: the median
`turn` span of the window that was somebody's (`expected` > 0) and in which a
prefill DID cut in (its `formed_ms`, free -> formation over, more than 1 ms
short of its length: the flusher then waited for the device's lock); what an
admission adds to a cycle; /spans, host clock of the node. None where no such
turn started in the window, or on a program that stamps no `turn`."""

import spans
import turns


def read(run):
    return turns.median_ms(
        t["t1"] - t["t0"] for t in turns.turns(run)
        if spans.ms(t) - t["attrs"]["formed_ms"] > 1.0
    )
