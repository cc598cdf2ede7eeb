"""Layer engine_programs. Of the assignments the decode steps' rows made in
the window (rows x experts per token x sparse layers, over the router's
whole width), the share that fell on the experts this chip HOLDS: deltas of
/stats `executor` `moe.assignments_here` over `moe.assignments`. An even
router gives held / router's width (12.5 % for 32 of 256): how near an
expert's load here is to its load in the deployment, where the other ranks'
rows arrive by the exchange. Nothing to read where the program has no such
counter."""

import arith


def read(run):
    made = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.assignments")
    if made <= 0 or arith.dig(run["stats1"], "executor.moe.assignments_here", None) is None:
        return None
    here = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.assignments_here")
    return 100.0 * here / made
