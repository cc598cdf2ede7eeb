"""Layer engine_programs. Tokens a lane gains per pass of a model generated
by blocks: places made known in the window (those a prompt filled are not
counted) over live lanes x passes, the deltas of /stats `executor`
`diffusion.tokens` and `diffusion.lane_passes` between the window's ends.
A whole block of B places in `denoising_steps` + 1 passes is B / (steps + 1);
less where prompts open blocks. It is the number that says a step is not a
token. Nothing to read where the program has no such counters."""

import arith


def read(run):
    passes = arith.counter_delta(run["stats0"], run["stats1"], "executor.diffusion.lane_passes")
    if passes <= 0:
        return None
    return arith.counter_delta(run["stats0"], run["stats1"], "executor.diffusion.tokens") / passes
