"""Layer kernels. The block step's share of its roofline for a model
generated by blocks with routed experts: the least time one chip of this
kind could take for a step (opsbytes_block_moe.block_step: per pass the
attention weights and routers once, of the experts those its live rows were
routed to, the keys and values of the tokens live at the time, the head in
the denoising passes, the operations of each row's own experts; over
peaks.json) over the median device time of the block program in the trace
(the configuration's `trace_modules.block`). The experts a pass touched are
the deltas of /stats `executor` `moe.experts_touched` over `moe.steps`
(which counts passes), the live lanes `diffusion.lane_passes` over passes.
Nothing to read where the program has no such counters."""

import arith
import opsbytes
import opsbytes_block_moe
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
    pattern = (run["config"].get("trace_modules") or {}).get("block")
    passes = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.steps")
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.diffusion.block_steps")
    if pattern is None or run["rehearse"] or passes <= 0 or steps <= 0:
        return None
    mod = reduce_trace.find_module(run["trace"]["modules"], pattern)
    if mod is None:
        return None
    touched = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.experts_touched")
    lane_passes = arith.counter_delta(
        run["stats0"], run["stats1"], "executor.diffusion.lane_passes")
    work = opsbytes_block_moe.block_step(
        run["config"], lane_passes / passes, live_kv_tokens(run), touched / passes)
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
