"""Layer engine_programs. Share of the pipeline's stage-ticks that did no
live session's work: 1 - delta `pipeline.stage_ticks_useful` / delta
`pipeline.stage_ticks` of /stats `executor` between the window's ends. A
pass over n in-flight microbatches is a scan of n + pp - 1 ticks on pp
stages; a live slot uses pp of them, the rest are fill, drain and idle
slots. Counted on the host from each pass's shape and active mask."""

import arith


def read(run):
    ticks = arith.counter_delta(run["stats0"], run["stats1"], "executor.pipeline.stage_ticks")
    if ticks <= 0:
        return None
    useful = arith.counter_delta(run["stats0"], run["stats1"],
                                 "executor.pipeline.stage_ticks_useful")
    return 100.0 * (1.0 - useful / ticks)
