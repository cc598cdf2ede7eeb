"""Layer lane_window. The instrument's own check: for every turn of the
window whose `last` entry can be walked back to the step before (turns.py,
`last_chains`), the turn's length less what of that session's parts
(`deliver`, `resume`, `reply`, `between`, `enter`, `queue`, `admit`, the wait
in the window) falls inside the turn; the median, in ms. The parts abut and
the last submit ends the wait, so this is 0 but for a boundary that leaks;
/spans, host clock of the node. None where no turn can be walked."""

import turns


def read(run):
    chains, _skipped = turns.last_chains(run)
    return turns.median_ms(
        (t["t1"] - t["t0"]) - sum(turns.inside(p, t["t0"], t["t1"]) for p in ps)
        for t, ps in chains
    )
