"""Layer node_http. What one block hop costs the node outside its executor
call: for every `step` span of the node's own /generate client that started
inside the window and whose /forward ran one `compute` of kind `block`
(parent links step <- forward <- compute), the step's duration minus the
compute's; the median. The sibling of node.token_host_ms_p50, which reads
the hops of kind `decode`: a block hop carries a block's tokens, so this is
the host's time a block, not a token. None where no `compute` is of that
kind."""

import arith
import spans


def read(run):
    by_id = {s["span"]: s for s in run["spans"] if "span" in s}
    per_step = {}
    for c in run["spans"]:
        if c.get("name") != "compute" or (c.get("attrs") or {}).get("kind") != "block":
            continue
        forward = by_id.get(c.get("parent"))
        step = by_id.get(forward.get("parent")) if forward else None
        if step is not None and step.get("name") == "step":
            per_step.setdefault(step["span"], []).append(c)
    costs = [
        spans.ms(by_id[sid]) - spans.ms(cs[0])
        for sid, cs in per_step.items()
        if len(cs) == 1 and run["wall0"] <= by_id[sid]["t0"] <= run["wall1"]
    ]
    return arith.percentile(costs, 50)
