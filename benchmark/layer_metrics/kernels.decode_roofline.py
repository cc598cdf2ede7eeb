"""Layer kernels. The decode step's share of its roofline: the least time
one chip of this kind could take for a step (opsbytes.decode_step: weights
once, the keys and values of the tokens live at the time, the operations of
the tokens advanced; over peaks.json) over the median device time of the
decode program in the trace (the configuration's `trace_modules.decode`).
Under --mesh the program is one pipeline pass over all stages, and the
least time is that of the whole model's bytes through one chip's memory:
the stages of one token run one after another."""

import arith
import opsbytes
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
    if mod is None or run["rehearse"]:
        return None
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_steps")
    toks = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_tokens")
    work = opsbytes.decode_step(run["config"], toks / steps if steps else 1.0, live_kv_tokens(run))
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
