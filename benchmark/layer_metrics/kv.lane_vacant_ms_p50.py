"""Layer kv_manager. How long a lane (mesh: slot) stood free before a session
took it: the median `vacant_ms` of the `lane` spans of the window that bound a
NEW session (`new` = 1) to a lane used before: the executor's stamp when the
lane was given back -> the bind; /spans, host clock of the node. The loss-free
form between two reads of `/stats` is `executor.lane_vacant_ms_sum` over
`executor.admissions`. None on a program that stamps no `lane`."""

import arith
import spans


def read(run):
    return arith.percentile(
        [s["attrs"]["vacant_ms"] for s in spans.named(run, "lane", new=1)
         if s["attrs"].get("vacant_ms") is not None], 50)
