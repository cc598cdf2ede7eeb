"""Layer lane_window. How many decode hops of the mesh found their row
already run: of the one-token decode hops the window's passes answered, the
share answered from a row that a pass ran BEFORE the hop arrived, from the
token and key the pass before left on the devices (the mesh executor keeps
one pass ahead of the sessions whose asks promise their next hop); /stats
`executor` ahead_claimed over sampled_rows + logit_rows, as deltas between
the window's ends. The rest rode a pass dispatched after they arrived: a
session's first decode hop, a hop that promised nothing. None where the
counters are absent (a program whose mesh runs nothing ahead) or no decode
hop was answered."""

import arith


def read(run):
    if arith.dig(run["stats1"], "executor.ahead_claimed", None) is None:
        return None
    claimed = arith.counter_delta(run["stats0"], run["stats1"], "executor.ahead_claimed")
    rows = (arith.counter_delta(run["stats0"], run["stats1"], "executor.sampled_rows")
            + arith.counter_delta(run["stats0"], run["stats1"], "executor.logit_rows"))
    if rows <= 0:
        return None
    return 100.0 * claimed / rows
