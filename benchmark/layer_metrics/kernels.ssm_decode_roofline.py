"""Layer kernels. The decode step's share of its roofline for a model with
state-space layers: the least time one chip of this kind could take for a
step (opsbytes_ssm.decode_step: every weight once, per live lane each Mamba
layer's state read and written, the attention layers' keys and values of the
tokens live at the time, the one-token operations; over peaks.json) over the
median device time of the decode program in the trace (the configuration's
`trace_modules.decode`). Nothing to read where the program holds no
recurrent state (/stats `executor` has no `state_bytes_per_session`)."""

import arith
import opsbytes
import opsbytes_ssm
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
    pattern = (run["config"].get("trace_modules") or {}).get("decode")
    if pattern is None or run["rehearse"]:
        return None
    if not arith.dig(run["stats1"], "executor.state_bytes_per_session", None):
        return None
    mod = reduce_trace.find_module(run["trace"]["modules"], pattern)
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_steps")
    if mod is None or steps <= 0:
        return None
    toks = arith.counter_delta(run["stats0"], run["stats1"], "executor.batched_tokens")
    work = opsbytes_ssm.decode_step(run["config"], toks / steps, live_kv_tokens(run))
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
