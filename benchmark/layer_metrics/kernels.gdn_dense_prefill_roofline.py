"""Layer kernels. The prefill program's share of its roofline over the
traced stretch for a model whose mixer is a gated delta rule in most layers
and full attention in the rest, every layer with a dense MLP: the least time
one chip could take for the prompt work done in it (opsbytes_gdn_dense.prefill
per request: every layer over every real token; the chunked recurrence in the
linear layers, causal attention in the full ones; the head once; every weight
ONCE a prompt; over peaks.json) over the device time of the prefill program in
the trace (the configuration's `trace_modules.prefill`, all its executions,
whatever chunks and buckets the node cut the prompts into). A request's
prompt work is spread evenly from its send to its first token, and the part
inside the traced stretch counts. The weights read again for a second chunk,
bucket padding and anything computed twice count against the program. Nothing
to read where the program holds no recurrent state."""

import arith
import opsbytes
import opsbytes_gdn_dense
import reduce_trace


def read(run):
    pattern = (run["config"].get("trace_modules") or {}).get("prefill")
    capture = next((s for s in run["spans"] if s.get("name") == "capture"), None)
    if pattern is None or capture is None or run["rehearse"]:
        return None
    if arith.dig(run["stats1"], "executor.state_bytes_per_session", None) is None:
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
        work = opsbytes_gdn_dense.prefill(run["config"], r["prompt_len"])
        least += inside * opsbytes.least_time_s(work, run["device"]["device_kind"])["seconds"]
    return 100.0 * least / mod["total_s"] if least > 0 else None
