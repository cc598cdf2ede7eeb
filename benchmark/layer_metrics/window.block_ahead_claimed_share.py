"""Layer lane_window. How many BLOCK hops found their block already run: of
the block hops the window's steps answered (a model generated by blocks), the
share answered from a block that a step ran BEFORE the hop arrived, under the
key the step before left on the device (the lane executor keeps one step
ahead of the sessions whose `block` calls promise their next hop); /stats
`executor` ahead_claimed over diffusion.hops, as deltas between the window's
ends. The rest rode a step dispatched after they arrived: a session's first
block hop, a hop that promised nothing. None where `diffusion.hops` is absent
(a program that runs no block ahead; a model generated token by token) or no
block hop was answered."""

import arith


def read(run):
    if arith.dig(run["stats1"], "executor.diffusion.hops", None) is None:
        return None
    hops = arith.counter_delta(run["stats0"], run["stats1"], "executor.diffusion.hops")
    if hops <= 0:
        return None
    return 100.0 * arith.counter_delta(run["stats0"], run["stats1"], "executor.ahead_claimed") / hops
