"""Layer lane_window. Share of the run's window in which the device was
free and a session was owed a step: the `turn` spans with `expected` > 0,
clipped to the window, summed, over the window; /spans, host clock of the
node. To be read beside `device.idle_share` (the 4 s capture) and 100 -
`device.busy_share_host`: the chip is idle under `copy_out`, under the
delivery before the window notes the device free, and from the drain to the
dispatch as well, which a turn does not cover; and a turn is not all idle:
on the lanes a prefill chunk runs on the device under it, under `--mesh` a
prefill pass lies inside it. None where there is no `turn`."""

import turns


def read(run):
    w0, w1 = run["wall0"], run["wall1"]
    mine = turns.turns(run, clip=True)
    if not mine:
        return None
    return 100.0 * sum(min(t["t1"], w1) - max(t["t0"], w0) for t in mine) / (w1 - w0)
