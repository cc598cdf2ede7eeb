"""Layer engine_programs. How unevenly the decode steps' tokens fell on the
routed experts: of the assignments made in the window (live rows x experts
per token x sparse layers, per step), the share that fell on each layer's
most loaded expert, times the number of experts (`moe.experts`): 1 is an
even spread, experts / experts per token is every token on the same ones.
Deltas of /stats `executor` `moe.assignments_hottest` and `moe.assignments`
between the window's ends.
Nothing to read where the program has no such counters."""

import arith


def read(run):
    made = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.assignments")
    if made <= 0:
        return None
    hottest = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.assignments_hottest")
    return hottest * arith.dig(run["stats1"], "executor.moe.experts") / made
