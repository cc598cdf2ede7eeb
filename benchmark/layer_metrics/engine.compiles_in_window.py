"""Layer engine_programs. Programs compiled inside the window: journal
`compile.begin` events stamped in it plus the growth of the persistent
cache's misses between its ends. Should be 0; `correct` fails otherwise."""

import arith


def read(run):
    begun = sum(1 for e in run["events1"]
                if e.get("type") == "compile.begin"
                and run["wall0"] <= e.get("ts", 0) <= run["wall1"])
    return begun + arith.counter_delta(run["stats0"], run["stats1"], "compile_cache.misses")
