"""Layer kernels. The decode step's share of its roofline for a model whose
mixer is a gated delta rule in most layers and full attention in the rest,
every layer with a dense MLP: the least time one chip of this kind could take
for a step (opsbytes_gdn_dense.decode_step: every weight once; each live
session's recurrent state and kept columns read and written once a linear
layer at their unpadded bytes; keys and values of the live tokens in the full
layers, for the sessions live at the window's middle; over peaks.json) over
the median device time of the decode program in the trace (the configuration's
`trace_modules.decode`). Nothing to read where the program holds no recurrent
state (/stats `executor` `state_bytes_per_session`)."""

import arith
import opsbytes
import opsbytes_gdn_dense
import reduce_trace


def live_contexts(run) -> list:
    """Tokens each request in flight at the window's middle holds."""
    mid = (run["w0"] + run["w1"]) / 2
    return [
        r["prompt_len"] + sum(1 for t in r["token_t"] if t <= mid)
        for r in run["requests"]
        if r["sent"] <= mid and (r.get("done") or float("inf")) > mid and not r.get("error")
        and r["token_t"] and r["token_t"][0] <= mid  # decoding, not still in prefill
    ]


def read(run):
    pattern = (run["config"].get("trace_modules") or {}).get("decode")
    if pattern is None or run["rehearse"]:
        return None
    if arith.dig(run["stats1"], "executor.state_bytes_per_session", None) is None:
        return None
    mod = reduce_trace.find_module(run["trace"]["modules"], pattern)
    contexts = live_contexts(run)
    if mod is None or not contexts:
        return None
    work = opsbytes_gdn_dense.decode_step(run["config"], contexts)
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
