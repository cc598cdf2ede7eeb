"""Layer lane_window. Of the steps dispatched ahead of their sessions, the
share somebody WAITED for: whoever came for the step first (the next drain,
a rider's thread) found it still running, so its `device` span ends at the
step's end. The rest were done when the host came: the host sets the pace,
and `window.device_ms_p50` reads a cycle, not a step. /stats `executor`
steps_waited over steps_waited + steps_found_done, as deltas between the
window's ends. None where the counters are absent or no step was seen."""

import arith


def read(run):
    if arith.dig(run["stats1"], "executor.steps_waited", None) is None:
        return None
    waited = arith.counter_delta(run["stats0"], run["stats1"], "executor.steps_waited")
    found = arith.counter_delta(run["stats0"], run["stats1"], "executor.steps_found_done")
    if waited + found <= 0:
        return None
    return 100.0 * waited / (waited + found)
