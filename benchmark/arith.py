"""The arithmetic from raw samples to metrics. Everything here is plain
Python on lists of numbers, so tests/test_arith.py can check it on
hand-made samples; nothing reads a clock or the program."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

INF = float("inf")


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """q-th percentile (0-100) by linear interpolation between the closest
    ranks; +infinity is a legal sample (a request that never got its first
    token) and wins wherever the rank touches it. None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or xs[lo] == xs[hi]:
        return xs[lo]
    if math.isinf(xs[hi]):
        return INF
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def sent_in_window(requests: Sequence[dict], w0: float, w1: float) -> List[dict]:
    return [r for r in requests if w0 <= r["sent"] < w1]


def tokens_in_window(requests: Sequence[dict], w0: float, w1: float) -> int:
    """Output tokens that reached a client inside [w0, w1], whenever their
    request was sent."""
    return sum(1 for r in requests for t in r["token_t"] if w0 <= t <= w1)


def ttft_ms(requests: Sequence[dict], w0: float, w1: float) -> List[float]:
    """Send to first streamed token, over requests sent in the window and
    not failed; one whose first token had not come by w1 counts +inf."""
    out = []
    for r in sent_in_window(requests, w0, w1):
        if r.get("error"):
            continue
        first = next((t for t in r["token_t"] if t <= w1), None)
        out.append(INF if first is None else (first - r["sent"]) * 1e3)
    return out


def gaps_ms(requests: Sequence[dict], w0: float, w1: float) -> List[float]:
    """Every gap between consecutive streamed tokens of one request that
    ended inside the window (the later token arrived in [w0, w1])."""
    out = []
    for r in requests:
        ts = r["token_t"]
        out.extend(
            (b - a) * 1e3 for a, b in zip(ts, ts[1:]) if w0 <= b <= w1
        )
    return out


def dig(tree: dict, path: str, default=0):
    """tree["a"]["b"] for path "a.b"; default where a key is missing."""
    cur = tree
    for key in path.split("."):
        if not isinstance(cur, dict) or key not in cur:
            return default
        cur = cur[key]
    return cur


def counter_delta(before: dict, after: dict, path: str) -> float:
    """after - before of one counter in two /stats snapshots."""
    return float(dig(after, path)) - float(dig(before, path))


def span_ms(spans: Sequence[dict], name: str, w0: float, w1: float) -> List[float]:
    """Durations (ms) of the spans called `name` that started inside the
    wall-clock window [w0, w1]."""
    return [
        (s["t1"] - s["t0"]) * 1e3
        for s in spans
        if s.get("name") == name and w0 <= s["t0"] <= w1
    ]


def failed_reason(r: dict, vocab: int) -> Optional[str]:
    """Why a request counts as failed, or None: refused or errored, a token
    outside the vocabulary, or more tokens than asked."""
    if r.get("error"):
        return str(r["error"])
    if len(r["tokens"]) > r["asked"]:
        return f"{len(r['tokens'])} tokens for {r['asked']} asked"
    bad = [t for t in r["tokens"] if not (isinstance(t, int) and 0 <= t < vocab)]
    if bad:
        return f"token {bad[0]} outside the vocabulary"
    return None
