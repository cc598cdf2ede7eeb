"""Layer lane_window. How many decode hops were answered with their token:
of the one-token decode rows the window's steps served, the share whose
token was chosen on the device and left it in the step's one small
transfer, against those whose [V] logits row was copied out for the
generation loop to sample; /stats `executor` sampled_rows over
sampled_rows + logit_rows, as deltas between the window's ends. Under 100
means hops that carried no ask (a raw /forward from outside) or one the
device sampler does not cover. None where neither moved: no decode hop,
or a program without the counters."""

import arith


def read(run):
    sampled = arith.counter_delta(run["stats0"], run["stats1"], "executor.sampled_rows")
    logits = arith.counter_delta(run["stats0"], run["stats1"], "executor.logit_rows")
    if sampled + logits <= 0:
        return None
    return 100.0 * sampled / (sampled + logits)
