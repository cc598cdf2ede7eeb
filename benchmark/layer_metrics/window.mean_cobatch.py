"""Layer lane_window. Tokens a decode step advanced, mean over the window:
/stats `executor` batched_tokens over batched_steps, as deltas between the
window's ends."""

import arith


def read(run):
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_steps")
    if steps <= 0:
        return None
    return arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_tokens") / steps
