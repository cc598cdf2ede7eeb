"""What the readers of the spans inside an executor call share: picking the
node's spans of one name (and attributes) that started inside the window,
their median, and the parent links. Plain Python on the list `/spans` gave;
a span is {"span", "parent", "name", "t0", "t1", "attrs"?} on the node's
clock (epoch seconds), and `run["wall0"]`/`run["wall1"]` bound the window
on that clock. The program stamps `batch_wait`, `lock_wait`, `device` and
`copy_out` under each `compute` span since PR 25 (docs/OBSERVABILITY.md);
on an older program there are none and every reader here reads None."""

from __future__ import annotations

from typing import List, Optional

import arith


def named(run: dict, name: str, **attrs) -> List[dict]:
    """The spans called `name` that started inside the window and carry
    every given attribute with the given value."""
    return [
        s for s in run["spans"]
        if s.get("name") == name and run["wall0"] <= s["t0"] <= run["wall1"]
        and all((s.get("attrs") or {}).get(k) == v for k, v in attrs.items())
    ]


def ms(span: dict) -> float:
    return (span["t1"] - span["t0"]) * 1e3


def median_ms(run: dict, name: str, **attrs) -> Optional[float]:
    return arith.percentile([ms(s) for s in named(run, name, **attrs)], 50)
