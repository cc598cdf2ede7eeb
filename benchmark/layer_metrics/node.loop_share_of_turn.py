"""Layer node_http. Of the summed turns (the `turn` spans with `expected` >
0, clipped to the window), the share covered by the UNION over all sessions
of their on-loop parts (`reply`, `between`, `enter`: turns.py): near 100 the
node's one event loop is what the chip waits for; well under it the thread
hand-overs (`deliver`, `resume`, `queue`) and the formation's own wait are;
/spans, host clock of the node. None where there is no turn or no hop."""

import turns


def read(run):
    w0, w1 = run["wall0"], run["wall1"]
    mine = turns.turns(run, clip=True)
    all_hops = turns.index(run)["hops"]
    if not mine or not all_hops:
        return None
    on_loop = turns.merged(
        (p[1], p[2]) for h in all_hops
        for p in (turns.reply(h), turns.between(h), turns.enter(h)) if p is not None
    )
    clipped = [(max(t["t0"], w0), min(t["t1"], w1)) for t in mine]
    return 100.0 * sum(turns.covered_s(on_loop, a, b) for a, b in clipped) / sum(
        b - a for a, b in clipped
    )
