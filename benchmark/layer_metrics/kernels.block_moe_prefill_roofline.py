"""Layer kernels. Prefill's share of its roofline for a model generated by
blocks with routed experts: over the traced stretch, the least time one chip
of this kind could take for the prompts' whole blocks ingested in it
(opsbytes_block_moe.prefill: each token's own experts' operations,
block-causal attention, no head; over peaks.json) over the device time of
the prefill program in the trace (the configuration's
`trace_modules.prefill`, all its executions, whatever chunks and buckets the
node made of the prompts). A request's prompt counts by the share of its
send-to-first-token interval that lies inside the traced stretch (its last
tokens open the first block and are in the block step, not here). Nothing
to read where the program has no `diffusion.*` counters."""

import arith
import opsbytes
import opsbytes_block_moe
import reduce_trace


def read(run):
    pattern = (run["config"].get("trace_modules") or {}).get("prefill")
    capture = next((s for s in run["spans"] if s.get("name") == "capture"), None)
    if pattern is None or capture is None or run["rehearse"]:
        return None
    if not arith.dig(run["stats1"], "executor.diffusion", None):
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
        work = opsbytes_block_moe.prefill(run["config"], r["prompt_len"])
        least += inside * opsbytes.least_time_s(work, run["device"]["device_kind"])["seconds"]
    return 100.0 * least / mod["total_s"] if least > 0 else None
