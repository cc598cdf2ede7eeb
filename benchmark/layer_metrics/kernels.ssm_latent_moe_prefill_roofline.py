"""Layer kernels. The prefill program's share of its roofline over the traced
stretch for a model whose layers are one sublayer each, Mamba-2, attention or
routed experts that work in a latent, this chip holding a share of the
experts: the least time one chip could take for the prompt work done in it
(opsbytes_ssm_latent_moe.prefill per request: every sublayer over every real
token with the held experts each token chose, not all of them, two matrices
each; the chunked recurrence in the Mamba sublayers, causal attention in the
attention ones; the head once; over peaks.json) over the device time of the
prefill program in the trace (the configuration's `trace_modules.prefill`, all
its executions, whatever chunks and buckets the node cut the prompts into). A
request's prompt work is spread evenly from its send to its first token, and
the part inside the traced stretch counts. A tile's padding rows, bucket
padding and the weights read again for a second chunk count against the
program. Nothing to read where the program reports no latent for its experts
(`moe.latent_size`) or holds no recurrent state."""

import arith
import opsbytes
import opsbytes_ssm_latent_moe
import reduce_trace


def read(run):
    pattern = (run["config"].get("trace_modules") or {}).get("prefill")
    capture = next((s for s in run["spans"] if s.get("name") == "capture"), None)
    if pattern is None or capture is None or run["rehearse"]:
        return None
    if (arith.dig(run["stats1"], "executor.moe.latent_size", None) is None
            or arith.dig(run["stats1"], "executor.state_bytes_per_session", None) is None):
        return None
    mod = reduce_trace.find_module(run["trace"]["modules"], pattern)
    if mod is None:
        return None
    # the traced stretch on the harness's clock
    a = run["w0"] + (capture["t0"] - run["wall0"])
    b = a + run["trace"]["window_s"]
    least = 0.0
    for r in run["requests"]:
        if r.get("error") or not r["token_t"]:
            continue
        t0, t1 = r["sent"], r["token_t"][0]
        inside = max(0.0, min(b, t1) - max(a, t0)) / max(t1 - t0, 1e-9)
        work = opsbytes_ssm_latent_moe.prefill(run["config"], r["prompt_len"])
        least += inside * opsbytes.least_time_s(work, run["device"]["device_kind"])["seconds"]
    return 100.0 * least / mod["total_s"] if least > 0 else None
