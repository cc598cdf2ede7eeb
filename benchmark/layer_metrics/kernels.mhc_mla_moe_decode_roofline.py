"""Layer kernels. The decode step's share of its roofline for a model whose
residual is a stream of hidden states (mHC) around latent attention with
compressed queries and routed + shared experts: the least time one chip of
this kind could take for a step (opsbytes_mhc_mla_moe.decode_step: the
weights the step touches once, of the routed experts those its rows were
routed to, the latents of the sessions decoding at the window's middle in the
absorbed form, the stream read and written once a sublayer and its maps'
products; over peaks.json) over the median device time of the decode program
in the trace (the configuration's `trace_modules.decode`). The experts a step
touched are the deltas of /stats `executor` `moe.experts_touched` over
`moe.steps`. Nothing to read where the program reports no stream (/stats
`model.stream_width`)."""

import arith
import opsbytes
import opsbytes_mhc_mla_moe
import reduce_trace


def decoding_kv_tokens(run) -> float:
    """Context tokens held by the requests that are decoding (their first
    token has come) at the window's middle."""
    mid = (run["w0"] + run["w1"]) / 2
    return float(sum(
        r["prompt_len"] + sum(1 for t in r["token_t"] if t <= mid)
        for r in run["requests"]
        if r["sent"] <= mid and (r.get("done") or float("inf")) > mid and not r.get("error")
        and r["token_t"] and r["token_t"][0] <= mid
    ))


def read(run):
    pattern = (run["config"].get("trace_modules") or {}).get("decode")
    if pattern is None or run["rehearse"]:
        return None
    if arith.dig(run["stats1"], "model.stream_width", None) is None:
        return None
    mod = reduce_trace.find_module(run["trace"]["modules"], pattern)
    routed_steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.steps")
    if mod is None or routed_steps <= 0:
        return None
    touched = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.experts_touched")
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_steps")
    toks = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_tokens")
    work = opsbytes_mhc_mla_moe.decode_step(
        run["config"], toks / steps if steps else 1.0, decoding_kv_tokens(run),
        touched / routed_steps)
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
