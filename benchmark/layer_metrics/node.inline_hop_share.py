"""Layer node_http. How many decode and block hops held no thread: of the
hops the executor answered, the share that the node admitted on its event
loop, handed to the window and resumed from the drain that served them, with
no worker of the pool taken, woken or parked (runtime/node.py
`_process_hop`); /stats `executor` hops_inline over hops_inline +
hops_pooled, as deltas between the window's ends. The rest took a worker: a
request's first hop, which rides the step it arrived under and waits for it;
a hop whose ask no step's sampler covers. None where the program has no
such counter (the parent; the mesh executor, which offers no such form and
whose every hop takes a worker) or no hop was answered."""

import arith


def read(run):
    if arith.dig(run["stats1"], "executor.hops_inline", None) is None:
        return None
    inline = arith.counter_delta(run["stats0"], run["stats1"], "executor.hops_inline")
    hops = inline + arith.counter_delta(run["stats0"], run["stats1"], "executor.hops_pooled")
    if hops <= 0:
        return None
    return 100.0 * inline / hops
