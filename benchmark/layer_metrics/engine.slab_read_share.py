"""Layer engine_programs. Of the slots the dense lanes hold in a full layer's
slab, the share the window's programs read: deltas of /stats `executor`
`kv.slots_read` over `kv.slots_held`, counted per dispatched decode step,
block pass and prefill chunk as rows x the rung the program chose from the
lengths it was handed (models.qwen3.read_rungs / read_rung, the functions
the program itself asks) over rows x `--max-len`. 100 % is a slab read whole
whatever the lanes hold; the longest row sets the rung, so ragged lanes read
more than they hold. Nothing to read where the program has no such counter."""

import arith


def read(run):
    if arith.dig(run["stats1"], "executor.kv.slots_held", None) is None:
        return None
    held = arith.counter_delta(run["stats0"], run["stats1"], "executor.kv.slots_held")
    if held <= 0:
        return None
    return 100.0 * arith.counter_delta(run["stats0"], run["stats1"], "executor.kv.slots_read") / held
