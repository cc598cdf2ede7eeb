"""Layer node_http. Median `forward` server span (the node's /forward
handler, one per token or prompt) that started inside the window; /spans,
host clock of the node."""

import arith


def read(run):
    return arith.percentile(
        arith.span_ms(run["spans"], "forward", run["wall0"], run["wall1"]), 50)
