"""Layer lane_window. How often a formation gave up on a session: of the
formations that waited for expected sessions in the window, the share that
ended because the cap ran out and not because every one was back; /stats
`executor` gang_timeout over gang_full + gang_timeout, as deltas between
the window's ends. A stall (a session that stays away) reads high here; a
tail (all back, late) does not. None where no formation waited."""

import arith


def read(run):
    full = arith.counter_delta(run["stats0"], run["stats1"], "executor.gang_full")
    gave_up = arith.counter_delta(run["stats0"], run["stats1"], "executor.gang_timeout")
    if full + gave_up <= 0:
        return None
    return 100.0 * gave_up / (full + gave_up)
