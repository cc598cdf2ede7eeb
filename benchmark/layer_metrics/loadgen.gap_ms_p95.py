"""Layer loadgen. 95th percentile over every gap between consecutive
streamed tokens that ended inside the window, at the benchmark's client:
the number that is the end-to-end `gap_ms_p95` in the one-chip cells. Under
--mesh it falls on one of two values a prefill chunk apart (475 or 503 ms)
according to how the seed arranges the prompts (5.9 % between seeds, 0.3 %
between two runs of one seed; my chip runs, PR 24), more than any bound the
contract allows could carry, so there it is recorded, not judged."""

import arith


def read(run):
    return arith.percentile(arith.gaps_ms(run["requests"], run["w0"], run["w1"]), 95)
