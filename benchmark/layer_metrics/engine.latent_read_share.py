"""Layer engine_programs. Of the slots the lanes of a latent cache hold in a
layer (a latent and a roped key a slot), the share the window's programs
read: deltas of /stats `executor` `kv.slots_read` over `kv.slots_held`,
counted per dispatched decode step and prefill chunk as rows x the rung the
program chose from the lengths it was handed (models.qwen3.read_rungs /
read_rung, the functions the program itself asks) over rows x `--max-len`.
100 % is every lane read whole whatever it holds (the absorbed step's read of
all its latents, a chunk's expansion of all its slots to heads); the longest
row sets a step's rung, so ragged lanes read more than they hold. Nothing to
read where the program has no such counter for a latent cache."""

import arith


def read(run):
    if arith.dig(run["stats1"], "executor.kv.slots_held", None) is None:
        return None
    held = arith.counter_delta(run["stats0"], run["stats1"], "executor.kv.slots_held")
    if held <= 0:
        return None
    return 100.0 * arith.counter_delta(run["stats0"], run["stats1"], "executor.kv.slots_read") / held
