"""Layer node_http. Gossip records this node built, a decode step: /stats
`announce.builds` over `executor.batched_steps`, as deltas between the
window's ends. The record is rebuilt when somebody is about to read it (a
gossip send a second, the telemetry tick, a local replica pick), not when
a hop moves the load, so this reads well under 1; a node that rebuilt it
on every hop's way in and out read 2 x `window.mean_cobatch`. None where
the program has no such counter or no step ran."""

import arith


def read(run):
    if arith.dig(run["stats1"], "announce.builds", None) is None:
        return None
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_steps")
    if steps <= 0:
        return None
    return arith.counter_delta(run["stats0"], run["stats1"], "announce.builds") / steps
