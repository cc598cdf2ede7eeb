"""The metric arithmetic on hand-made samples."""

import math

import arith


def req(sent, token_t, asked=4, tokens=None, error=None, done=None):
    return {"sent": sent, "token_t": token_t, "asked": asked, "error": error, "done": done,
            "tokens": list(range(len(token_t))) if tokens is None else tokens}


def test_percentile_plain_and_interpolated():
    assert arith.percentile([], 50) is None
    assert arith.percentile([7.0], 95) == 7.0
    assert arith.percentile([1, 2, 3, 4], 50) == 2.5
    assert arith.percentile(range(1, 102), 95) == 96


def test_percentile_with_infinity():
    inf = math.inf
    assert arith.percentile([1, 2, 3, inf], 50) == 2.5      # the rank does not touch it
    assert arith.percentile([1, 2, inf, inf], 50) == inf    # between 2 and inf
    assert arith.percentile([1, inf, inf], 50) == inf
    assert arith.percentile([1, 2, 3, inf], 95) == inf
    assert arith.percentile([inf, inf], 50) == inf


def test_ttft_counts_missing_first_tokens_as_infinity_and_only_window_sends():
    reqs = [
        req(0.5, [1.0]),             # sent before the window: not a sample
        req(10.0, [10.4, 10.5]),     # 400 ms
        req(19.8, [20.3]),           # first token after the window's end: +inf
        req(19.9, []),               # none at all: +inf
        req(12.0, [], error="HTTP 503"),  # failed: counted as failed, no latency
    ]
    got = arith.ttft_ms(reqs, 10.0, 20.0)
    assert got[0] == 400.0000000000009 or abs(got[0] - 400) < 1e-6
    assert got[1:] == [math.inf, math.inf]
    assert len(arith.sent_in_window(reqs, 10.0, 20.0)) == 4


def test_gaps_are_cut_at_the_windows_end_and_start():
    reqs = [req(0.0, [9.0, 9.9, 10.1, 10.4, 19.9, 20.2])]
    got = [round(g) for g in arith.gaps_ms(reqs, 10.0, 20.0)]
    # 9.0->9.9 ended before the window; 19.9->20.2 ended after it
    assert got == [200, 300, 9500]


def test_tokens_in_window_counts_requests_sent_earlier():
    reqs = [req(1.0, [9.5, 10.0, 15.0, 20.0, 20.1]), req(12.0, [12.5])]
    assert arith.tokens_in_window(reqs, 10.0, 20.0) == 4


def test_counter_delta_and_missing_keys():
    before = {"executor": {"batched_steps": 100, "batched_tokens": 130}}
    after = {"executor": {"batched_steps": 150, "batched_tokens": 205}, "compile_cache": {"misses": 2}}
    assert arith.counter_delta(before, after, "executor.batched_steps") == 50
    assert arith.counter_delta(before, after, "executor.batched_tokens") == 75
    assert arith.counter_delta(before, after, "compile_cache.misses") == 2
    assert arith.counter_delta(before, after, "executor.nothing") == 0


def test_span_ms_takes_spans_that_start_in_the_window():
    spans = [
        {"name": "compute", "t0": 99.0, "t1": 99.5},
        {"name": "compute", "t0": 100.5, "t1": 100.54},
        {"name": "forward", "t0": 100.4, "t1": 100.56},
        {"name": "compute", "t0": 109.99, "t1": 110.2},
    ]
    got = arith.span_ms(spans, "compute", 100.0, 110.0)
    assert [round(x) for x in got] == [40, 210]


def test_failed_reason():
    assert arith.failed_reason(req(0, [1, 2], tokens=[5, 6]), 10) is None
    assert "vocabulary" in arith.failed_reason(req(0, [1, 2], tokens=[5, 10]), 10)
    assert "asked" in arith.failed_reason(req(0, [1, 2, 3], asked=2), 10)
    assert arith.failed_reason(req(0, [], error="HTTP 503"), 10) == "HTTP 503"
