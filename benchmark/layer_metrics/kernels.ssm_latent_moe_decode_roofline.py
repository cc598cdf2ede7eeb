"""Layer kernels. The decode step's share of its roofline for a model whose
layers are one sublayer each, Mamba-2, attention or routed experts that work
in a latent, this chip holding a share of the experts: the least time one chip
of this kind could take for a step (opsbytes_ssm_latent_moe.decode_step: the
weights the step touches once, of the HELD experts those its rows chose, two
matrices each; each live session's recurrent state and kept columns read and
written once a Mamba sublayer; keys and values of the whole context in the
attention sublayers, for the sessions decoding at the window's middle; each
row's own chosen held experts' operations; over peaks.json) over the median
device time of the decode program in the trace (the configuration's
`trace_modules.decode`). The held experts a step touched and the assignments
that fell on them are the deltas of /stats `executor` `moe.experts_touched_here`
and `moe.assignments_here` over `moe.steps`. Nothing to read where the program
reports no latent for its experts (`moe.latent_size`) or holds no recurrent
state."""

import arith
import opsbytes
import opsbytes_ssm_latent_moe
import reduce_trace


def live_contexts(run) -> list:
    """Tokens each request decoding at the window's middle holds."""
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
    if (arith.dig(run["stats1"], "executor.moe.latent_size", None) is None
            or arith.dig(run["stats1"], "executor.state_bytes_per_session", None) is None):
        return None
    mod = reduce_trace.find_module(run["trace"]["modules"], pattern)
    steps = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.steps")
    contexts = live_contexts(run)
    if mod is None or steps <= 0 or not contexts:
        return None
    touched = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.experts_touched_here")
    here = arith.counter_delta(run["stats0"], run["stats1"], "executor.moe.assignments_here")
    work = opsbytes_ssm_latent_moe.decode_step(
        run["config"], contexts, touched / steps, here / steps)
    least = opsbytes.least_time_s(work, run["device"]["device_kind"])
    return 100.0 * least["seconds"] / mod["median_s"]
