"""One general traffic generator. A mix is a data file
`traffic/<name>.json`: `kind` names a module `traffic_kinds/<kind>.py`
(found by name), the rest are its parameters. What every kind shares lives
here: the fixed pool of request sizes and the seeded prompt ids.

Every seed gets the SAME multiset of (prompt length, output length) pairs
— the stratified quantiles of the file's distributions — in another order,
so that two seeds do the same work and differ only in arrangement."""

from __future__ import annotations

import importlib.util
import json
import math
import os
import random
import re
from statistics import NormalDist
from typing import List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def load_module(path: str, what: str):
    """A module found by its file name (names may hold dots): how kinds and
    per-layer readers arrive as files without a table naming them."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {what}: {path} is missing")
    spec = importlib.util.spec_from_file_location(re.sub(r"\W", "_", what), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str, root: str = HERE):
    """The generator module for a `kind`, found by its file name."""
    return load_module(os.path.join(root, "traffic_kinds", f"{kind}.py"),
                       f"traffic kind {kind!r}")


def quantile(dist: dict, u: float) -> int:
    """The u-quantile (0 < u < 1) of a length distribution, clipped."""
    kind = dist["dist"]
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(round(min(max(x, dist.get("min", x)), dist.get("max", x))))


def size_pool(mix: dict, scale: float = 1.0) -> List[Tuple[int, int]]:
    """`pool` pairs (prompt length, output length): stratified quantiles of
    both distributions, paired through a fixed stride so that long prompts
    do not all meet long answers. No seed enters. `scale` (< 1 only in a
    rehearsal at a tiny size) shrinks the lengths, never the count."""
    n = int(mix["pool"])
    stride = next(s for s in range(max(2, n // 3), 2 * n + 3) if math.gcd(s, n) == 1)
    prompts = [quantile(mix["prompt_len"], (i + 0.5) / n) for i in range(n)]
    outs = [quantile(mix["output_len"], (i + 0.5) / n) for i in range(n)]
    return [
        (max(4, int(prompts[i] * scale)), max(2, int(outs[(i * stride) % n] * scale)))
        for i in range(n)
    ]


def bucket_len(n: int, minimum: int = 16) -> int:
    """The program's prefill bucket for n tokens (copy of
    core.generate.bucket_len): powers of two from 16."""
    b = minimum
    while b < n:
        b *= 2
    return b


def prompt_ids(seed: int, client: int, index: int, length: int, vocab: int) -> List[int]:
    """Token ids of one request: a stream of its own from (seed, client,
    index), so that no two requests share a prefix by construction."""
    rng = random.Random(f"{seed}/{client}/{index}")
    return [rng.randrange(vocab) for _ in range(length)]
