"""Layer kernels. The decode step's share of its roofline for a model with
latent attention and experts: the least time one chip of this kind could
take for a step (opsbytes_mla_moe.decode_step: the weights the step touches
once, of the routed experts those its tokens were routed to, the latents of
the tokens live at the time, the absorbed form's operations; over
peaks.json) over the median device time of the decode program in the trace
(the configuration's `trace_modules.decode`). The experts a step touched are
the deltas of /stats `executor` `moe.experts_touched` over `moe.steps`.
Nothing to read where the program has no such counters."""

import arith
import opsbytes
import opsbytes_mla_moe
import reduce_trace


def live_kv_tokens(run) -> float:
    """Context tokens held by the requests in flight, at the window's middle."""
    mid = (run["w0"] + run["w1"]) / 2
    return float(sum(
        r["prompt_len"] + sum(1 for t in r["token_t"] if t <= mid)
        for r in run["requests"]
        if r["sent"] <= mid and (r.get("done") or float("inf")) > mid and not r.get("error")
    ))


def read(run):
    mod = reduce_trace.find_module(
        run["trace"]["modules"], run["config"]["trace_modules"]["decode"])
    routed_steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.steps")
    if mod is None or run["rehearse"] or routed_steps <= 0:
        return None
    touched = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.experts_touched")
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_steps")
    toks = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_tokens")
    work = opsbytes_mla_moe.decode_step(
        run["config"], toks / steps if steps else 1.0, live_kv_tokens(run),
        touched / routed_steps)
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
