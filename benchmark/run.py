#!/usr/bin/env python3
"""benchmark/run.py — one cell, one run, one line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It serves the cell's configuration with the stock
`inferd_tpu.tools.run_node` on the chip, drives it with the cell's traffic
mix from its own client, and prints as the last line of standard output one
JSON object with exactly the keys `correct`, `attempted`, `failed`,
`metrics`, `device` (and `breakdown` in a traced run). `--trace 0` reports
the cell's end-to-end metrics, read at this client with the profiler off;
`--trace 1` reports its per-layer metrics from the node's spans, counters
and one profiler capture in the middle of the window.

Nothing here knows a configuration, a mix, a generator kind or a per-layer
metric by name: a cell of BENCHMARK.json names `configs/<config>.json` and
`traffic/<traffic>.json`; a mix names `traffic_kinds/<kind>.py`; a
per-layer metric is `layer_metrics/<metric-name>.py` with one function
`read(run) -> number | None`. A later PR adds files and entries.

What a configuration's file may say besides its published keys, its
`preset`, `node_flags`, `slots`, `weights_seed`, `logprob_tolerance` and
`rehearse`; each key optional, an absent key meaning the default:

  "reference": "<name>"   the plain reference is `references/<name>.py`;
                          default `reference.py`. The contract of either:
                          arguments `--ckpt --model --config <the
                          configuration's file> --device --prompt-ids
                          --continue-ids --out`; of the program it uses only
                          `parallel.stages.load_stage_checkpoint`; every
                          size comes from `--config`; float32,
                          `default_matmul_precision("highest")`, layers
                          streamed; it writes `[M, V]` float32
                          log-probabilities, row j at position
                          len(prompt) - 1 + j of ONE full forward pass over
                          prompt + continue (no cache, no sampling).
  "preset_check": {...}   published key (a dotted path into the file) ->
                          the attribute of the program's preset it must
                          equal; default PRESET_CHECK. Every key of the
                          manifest entry's `reduced` has to be among them.
  "probe": {"prompt_len": N, "new": M}   default 64 and 16.

Set-up (all of it `setup_s`): weights through `tools.split_model
--random-init --seed <weights_seed>` into `benchmark/.cache/` on the first
run of a configuration in a checkout; the node; one warm-up request per
prompt length the mix can draw; the probe; the lead-in. Then the window of
`--seconds`. Once the node has exited and freed the chip, the reference runs
there over the probe's prompt and the tokens the node answered (kept beside
the checkpoint under a digest of both, of the reference's file and of the
configuration's, so one run per configuration and checkout); that is not
set-up.

`correct`: the probe alone and the probe with other sessions resident give
the same tokens; the node's top log-probabilities of the probe's first token
(prefill) and of the later ones (decode through the cache) lie, in the mean,
within the configuration's tolerance of the float32 reference's one forward
pass, whose argmax is among them at every position;
every streamed token is in the vocabulary and no request got more than it
asked; nothing compiled in the window; the node exited with code 0.

The parent never initializes a JAX backend. There is no CPU fall-back:
a node that does not report platform `tpu` and the cell's chips ends the
run with a non-zero code and no result line. `--rehearse` runs the control
flow at the `tiny` preset on the CPU backend (virtual devices for four
chips) and can only end in `correct: false`.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import glob
import hashlib
import json
import math
import os
import random
import shutil
import sys
import time
import traceback

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import arith  # noqa: E402
import traffic  # noqa: E402
import validate_manifest  # noqa: E402
from procs import Children, Out, Refused, free_port, parent_backend_live  # noqa: E402

CACHE = os.path.join(HERE, ".cache")
PROBE, PROBE_TOP = {"prompt_len": 64, "new": 16}, 8
# published key -> attribute of the program's preset, where the file names no pairs of its own
PRESET_CHECK = {
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_word_embeddings",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps",
}
TRACE_SECONDS = 4.0
OUT = Out()
say = OUT.say


# ---------------------------------------------------------------------------
# the manifest and the cell's files
# ---------------------------------------------------------------------------


def load_cell(workload: str) -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Refused(f"BENCHMARK.json has no workload {workload!r}")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": config,
        "config_file": os.path.join(REPO, entry["file"]),
        "reduced": entry["reduced"],
        "mix": traffic.load_mix(cell["traffic"]),
        "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
        "per_layer": [m for m in manifest["per_layer"] if reported(m)],
    }


def check_preset(config: dict, reduced, preset) -> None:
    """The file's published sizes are the ones the program's preset runs,
    and what the configuration cuts is among what is compared."""
    pairs = config.get("preset_check", PRESET_CHECK)
    unchecked = [k for k in reduced if k not in pairs]
    if unchecked:
        raise Refused(f"reduced keys that the preset check does not compare: {unchecked}")
    lacks = {k: a for k, a in pairs.items() if not hasattr(preset, a)}
    if lacks:
        raise Refused(f"preset {preset.name!r} has no attribute for (key: attribute) {lacks}")
    wrong = {k: (arith.dig(config, k, "(absent)"), getattr(preset, a)) for k, a in pairs.items()
             if arith.dig(config, k, "(absent)") != getattr(preset, a)}
    if wrong:
        raise Refused(f"preset {preset.name!r} differs from the file (file, program): {wrong}")


def rehearsal_config(config: dict, preset, path: str) -> str:
    """A rehearsal serves another preset than the file describes: its
    reference reads a copy of the file in which every checked key holds the
    rehearsal preset's value."""
    out = copy.deepcopy(config)
    for key, attr in config.get("preset_check", PRESET_CHECK).items():
        *parents, leaf = key.split(".")
        group = arith.dig(out, ".".join(parents)) if parents else out
        group[leaf] = getattr(preset, attr)
    with open(path, "w") as f:
        json.dump(out, f)
    return path


def reference_script(config: dict) -> str:
    name = config.get("reference")
    if name is None:
        return os.path.join(HERE, "reference.py")
    if not (isinstance(name, str) and validate_manifest.NAME.match(name)):
        raise Refused(f"reference {name!r} is no name (letters, digits, '_', '.', '-')")
    path = os.path.join(HERE, "references", f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"no reference {name!r}: {path} is missing")
    return path


def probe_sizes(config: dict, flags) -> tuple:
    """(prompt length, new tokens) of the probe; it has to fit a session."""
    probe = {**PROBE, **config.get("probe", {})}
    n, m = probe["prompt_len"], probe["new"]
    if not (isinstance(n, int) and isinstance(m, int) and n >= 1 and m >= 2):
        raise Refused(f"probe {probe}: prompt_len >= 1 and new >= 2, whole numbers")
    max_len = int(flags[flags.index("--max-len") + 1]) if "--max-len" in flags else None
    if max_len is not None and n + m > max_len:
        raise Refused(f"probe of {n} + {m} tokens does not fit the node's --max-len {max_len}")
    return n, m


def load_reader(metric_name: str):
    try:
        return traffic.load_module(
            os.path.join(HERE, "layer_metrics", f"{metric_name}.py"),
            f"reader of the per-layer metric {metric_name!r}").read
    except FileNotFoundError as e:
        raise Refused(str(e))


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------


class NodeClient:
    """The benchmark's own client of one node: the requests it times and
    the node's read-only endpoints."""

    def __init__(self, http, port: int):
        from inferd_tpu.runtime import wire

        self.http, self.wire = http, wire
        self.base = f"http://127.0.0.1:{port}"

    async def get_json(self, path: str):
        async with self.http.get(self.base + path) as r:
            return await r.json()

    async def get_lines(self, path: str):
        async with self.http.get(self.base + path) as r:
            text = await r.text()
        return [json.loads(line) for line in text.splitlines() if line.strip()]

    async def post(self, path: str, body: dict):
        async with self.http.post(self.base + path, data=self.wire.pack(body)) as r:
            return r.status, self.wire.unpack(await r.read())

    async def generate(self, ids, max_new: int, top: int = 0, rec=None) -> dict:
        """One streamed greedy /generate, every token stamped on arrival
        into `rec`, which a caller that may be cancelled keeps."""
        rec = {} if rec is None else rec
        rec.update(prompt_len=len(ids), asked=max_new, tokens=[], token_t=[], tops=[],
                   error=None, done=None, sent=time.monotonic())
        body = {
            "prompt_ids": ids, "max_new_tokens": max_new, "stream": True,
            "sampling": {"temperature": 0.0, "top_k": 0, "top_p": 1.0},
        }
        if top:
            body.update(logprobs=True, top_logprobs=top)
        try:
            async with self.http.post(
                self.base + "/generate", data=self.wire.pack(body)
            ) as r:
                if r.status != 200:
                    rec["error"] = f"HTTP {r.status}"
                    return rec
                async for raw in r.content:
                    now = time.monotonic()
                    msg = json.loads(raw)
                    if "t" in msg:
                        rec["tokens"].append(msg["t"])
                        rec["token_t"].append(now)
                        if "top" in msg:
                            rec["tops"].append(msg["top"])
                    elif msg.get("restart"):
                        rec["error"] = "the node restarted the generation"
                    elif "error" in msg:
                        rec["error"] = msg["error"]
                    elif msg.get("done"):
                        rec["done"] = now
        except asyncio.CancelledError:
            rec["cut"] = True  # the window ended: not a failure
            raise
        except Exception as e:  # a refused or broken request is a failure
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        if rec["done"] is None and not rec["error"]:
            rec["error"] = "stream ended without its last line"
        return rec


class Ctx:
    """What a traffic kind sees of the harness: a clock that starts at the
    lead-in, a gate for each client's first request, and `request`."""

    def __init__(self, client: NodeClient, seed: int, vocab: int, gate_client, gate):
        self.client, self.seed, self.vocab = client, seed, vocab
        self.gate_client, self.gate = gate_client, gate
        self.t0 = time.monotonic()
        self.requests = []  # every request record, in the order sent
        self.lags_ms = []

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def sleep_until(self, t: float) -> None:
        await asyncio.sleep(max(0.0, t - self.now()))

    async def may_start(self, c: int) -> None:
        if c == self.gate_client:
            await self.gate.wait()

    async def request(self, c: int, i: int, n_prompt: int, n_out: int, due: float):
        ids = traffic.prompt_ids(self.seed, c, i, n_prompt, self.vocab)
        rec = {}
        self.requests.append(rec)
        self.lags_ms.append((self.now() - due) * 1e3)
        return await self.client.generate(ids, n_out, rec=rec)


# ---------------------------------------------------------------------------
# set-up: weights, node; after the node: the reference
# ---------------------------------------------------------------------------


def file_digest(*paths: str) -> str:
    h = hashlib.sha256()
    for path in paths:
        with open(path, "rb") as f:
            h.update(hashlib.sha256(f.read()).digest())
    return h.hexdigest()


def ensure_weights(config: dict, model: str, preset, children: Children, parts_dir: str,
                   timings: dict) -> None:
    """The seeded checkpoint, made once per configuration and checkout, and
    anew where the program's preset is no longer the one it was made of."""
    home = os.path.dirname(parts_dir)
    made = os.path.join(home, "checkpoint.ok")  # the split does not write atomically
    of = f"{config['weights_seed']} {preset!r}\n"
    if os.path.isfile(made) and open(made).read() == of:
        return
    shutil.rmtree(home, ignore_errors=True)
    os.makedirs(home)
    t0 = time.monotonic()
    children.run("split", [
        sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", model,
        "--stages", "1", "--random-init", "--seed", str(config["weights_seed"]),
        "--device", "cpu", "--out", parts_dir,
    ], timeout=900)
    with open(made, "w") as f:
        f.write(of)
    timings["checkpoint_s"] = time.monotonic() - t0


def run_reference(script: str, model: str, config_file: str, dev: str, children: Children,
                  parts_dir: str, prompt, more) -> str:
    """The reference's `[1 + len(more), V]` log-probabilities of one forward
    pass over `prompt + more`, kept beside the checkpoint under a digest of
    the tokens, the reference's file and the configuration's (an edit to
    either makes the rows anew). The chip has to be free: the node has exited."""
    ids = [",".join(map(str, x)) for x in (prompt, more)]
    digest = hashlib.sha256(
        "/".join(ids + [file_digest(script, config_file)]).encode()).hexdigest()[:16]
    ref_path = os.path.join(os.path.dirname(parts_dir), f"probe_ref-{digest}.npy")
    if not os.path.isfile(ref_path):
        children.run("reference", [
            sys.executable, script,
            "--ckpt", os.path.join(parts_dir, "stage_000.msgpack"), "--model", model,
            "--config", config_file, "--device", dev, "--prompt-ids", ids[0],
            "--continue-ids", ids[1], "--out", ref_path + ".tmp.npy",
        ], timeout=900)
        os.replace(ref_path + ".tmp.npy", ref_path)
    return ref_path


def probe_prompt(config: dict, vocab: int, n: int):
    rng = random.Random(f"probe/{config['weights_seed']}")
    return [rng.randrange(vocab) for _ in range(n)]


async def wait_ready(node, client: NodeClient, children: Children, timeout: float):
    t0 = time.monotonic()
    while True:
        if node.poll() is not None:
            raise Refused(
                f"run_node exited with code {node.returncode} before serving: "
                f"{children.tail('node')}"
            )
        try:
            evs = await client.get_lines("/events")
            if any(e["type"].startswith("executor.warmup_") for e in evs):
                return
        except Exception:
            pass
        if time.monotonic() - t0 > timeout:
            raise Refused(f"node not ready within {timeout:.0f}s: {children.tail('node')}")
        await asyncio.sleep(0.5)


def compiles(events, stats) -> int:
    """Programs the node compiled so far: `compile.begin` events plus
    persistent-cache misses (a miss is a compile the journal may not see)."""
    cc = stats.get("compile_cache") or {}
    return sum(1 for e in events if e["type"] == "compile.begin") + int(cc.get("misses", 0))


def check_reference(probe: dict, ref_path: str, tolerance: dict) -> dict:
    """The node's top log-probabilities of every token of the probe against
    the float32 reference's rows: the first token (the prefill's) as
    `probe_reference`, the others (decoded through the cache) as
    `probe_decode_reference`, each (ok, detail). The number compared is the
    MEAN |node - reference| over the node's top log-probabilities at the
    check's positions, held to the configuration's one tolerance: the
    largest single difference swings from probe to probe by as much as a
    step down in precision moves it, the mean does not (PERF.md section 4).
    At every position the reference's argmax is among the node's top. The
    detail names the position of the largest difference."""
    import numpy as np

    ref = np.load(ref_path)
    tops = probe["tops"]  # a streamed `top` field: [ids, log-probabilities]
    if len(tops) != len(ref) or len(tops) != len(probe["tokens"]):
        why = (f"the probe's {len(probe['tokens'])} tokens came with {len(tops)} top_logprobs; "
               f"the reference has {len(ref)} rows")
        return {"probe_reference": (False, why), "probe_decode_reference": (False, why)}
    rows = []  # per position: differences, argmax among the node's top, argmax, node's first
    for (ids, lps), row in zip(tops, ref):
        ids = [int(i) for i in ids]
        diffs = [abs(float(lp) - float(row[i])) for i, lp in zip(ids, lps)]
        rows.append((diffs, int(row.argmax()) in ids, int(row.argmax()), ids[0]))
    limit = float(tolerance["value"])

    def verdict(positions):
        if not positions:
            return False, "the probe answered one token: none went through the cache"
        every = [d for j in positions for d in rows[j][0]]
        mean = sum(every) / len(every)
        j = max(positions, key=lambda j: (not rows[j][1], max(rows[j][0])))
        diffs, among, argmax, node_first = rows[j]
        return mean <= limit and all(rows[j][1] for j in positions), (
            f"mean |node - reference| over the node's top {len(diffs)} log-probabilities "
            f"{mean:.4g} (tolerance {limit}); largest {max(diffs):.4g} at position {j} of "
            f"{positions[0]}-{positions[-1]}; there the reference's argmax {argmax}"
            f"{'' if among else ' is NOT among them'}, the node's first {node_first}"
            + (f"; mean by position {' '.join(f'{sum(rows[j][0]) / len(rows[j][0]):.3g}' for j in positions)}"
               if len(positions) > 1 else "")
        )

    return {"probe_reference": verdict(range(0, 1)),
            "probe_decode_reference": verdict(range(1, len(rows)))}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


async def drive(args, loaded: dict, node, port: int, children: Children, probe_ids,
                probe_new: int, timings: dict, checks: dict, work: str) -> dict:
    import aiohttp

    from inferd_tpu.config import get_config

    config, mix = loaded["config"], loaded["mix"]
    model = config["rehearse"]["model"] if args.rehearse else config["preset"]
    vocab = get_config(model).vocab_size
    scale = config["rehearse"]["length_scale"] if args.rehearse else 1.0
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=300)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        client = NodeClient(http, port)
        t0 = time.monotonic()
        await wait_ready(node, client, children, timeout=900)
        timings["node_ready_s"] = time.monotonic() - t0
        stats = await client.get_json("/stats")
        device = dict(stats.get("device") or {})
        say(f"node device: {json.dumps({k: v for k, v in device.items() if k != 'memory'})}")
        want = "cpu" if args.rehearse else "tpu"
        if device.get("platform") != want or int(device.get("device_count") or 0) < loaded["cell"]["chips"]:
            raise Refused(
                f"the node reports platform {device.get('platform')!r} with "
                f"{device.get('device_count')} devices; the cell needs {want} x "
                f"{loaded['cell']['chips']}"
            )

        # -- warm-up: every prompt length the mix can draw, once. The pool is
        # the same for every seed, so whatever shapes the node and its own
        # client make of a length (buckets, chunks) are compiled here ---------
        t0 = time.monotonic()
        pool = traffic.size_pool(mix, scale)
        for n_prompt in sorted({n for n, _n_out in pool}):
            ids = traffic.prompt_ids(args.seed, -1, n_prompt, n_prompt, vocab)
            rec = await client.generate(ids, 3, top=PROBE_TOP)
            if rec["error"]:
                raise Refused(f"warm-up with {n_prompt} tokens failed: {rec['error']}")
        timings["warmup_s"] = time.monotonic() - t0
        say(f"warmed {len({n for n, _ in pool})} prompt lengths in {timings['warmup_s']:.1f}s")

        # -- probe, alone ----------------------------------------------------
        t0 = time.monotonic()
        solo = await client.generate(probe_ids, probe_new, top=PROBE_TOP)
        timings["probe_s"] = time.monotonic() - t0
        if solo["error"]:
            raise Refused(f"the probe failed: {solo['error']}")

        # -- lead-in: traffic starts, the probe again with sessions resident -
        kind = traffic.load_kind(mix["kind"])
        slots = int(config["slots"])
        plan = kind.plan(mix, pool, slots, args.seed)
        gate = asyncio.Event()
        gate_client = max(range(plan["clients"]), key=lambda c: plan["starts"][c])
        ctx = Ctx(client, args.seed, vocab, gate_client, gate)
        compiled_before = compiles(await client.get_lines("/events"), await client.get_json("/stats"))
        t_lead = time.monotonic()
        load = asyncio.create_task(kind.run(plan, ctx))
        polls = []

        span_by_id = {}

        async def poll_node():
            """Traced runs only: the executor's gauges once a second, and
            the span ring (8192 spans, it can wrap within a window) every
            fifth second, merged by span id."""
            n = 0
            while True:
                st = await client.get_json("/stats")
                polls.append((time.monotonic(), st.get("executor") or {}))
                if n % 5 == 4:
                    span_by_id.update((s["span"], s) for s in await client.get_lines("/spans"))
                n += 1
                await asyncio.sleep(1.0)

        try:
            await asyncio.sleep(0.3 * float(mix["lead_in_s"]))
            again = await client.generate(probe_ids, probe_new, top=PROBE_TOP)
            gate.set()
            checks["probe_same_with_sessions_resident"] = (
                not again["error"] and again["tokens"] == solo["tokens"],
                f"alone {solo['tokens'][:4]}..., with {len(ctx.requests)} requests "
                f"sent {again['tokens'][:4]}... {again['error'] or ''}",
            )
            await ctx.sleep_until(float(mix["lead_in_s"]))
            timings["lead_in_s"] = time.monotonic() - t_lead

            # -- the window ---------------------------------------------------
            poller = asyncio.create_task(poll_node()) if args.trace else None
            events0 = await client.get_lines("/events")
            stats0 = await client.get_json("/stats")
            compiled0 = compiles(events0, stats0)
            w0, wall0 = time.monotonic(), time.time()
            timings["setup_s"] = w0 - T_PROCESS_START
            say(f"window opens: setup_s {timings['setup_s']:.3f}")
            capture = None
            if args.trace:
                s = min(TRACE_SECONDS, args.seconds / 2)
                await asyncio.sleep(max(0.0, (args.seconds - s) / 2))
                status, capture = await client.post(
                    "/profile", {"action": "window", "seconds": s, "capture_id": "bench"}
                )
                if status != 200:
                    raise Refused(f"/profile refused: {status} {capture}")
            await asyncio.sleep(max(0.0, w0 + args.seconds - time.monotonic()))
            w1, wall1 = time.monotonic(), time.time()
            stats1 = await client.get_json("/stats")
            events1 = await client.get_lines("/events")
            if poller:
                poller.cancel()
                await asyncio.gather(poller, return_exceptions=True)
        finally:
            load.cancel()
            await asyncio.gather(load, return_exceptions=True)
        if load.done() and not load.cancelled() and load.exception():
            raise load.exception()
        if args.trace:
            for _ in range(120):  # the capture records its span as it closes
                if any(e["type"] == "profile.capture_done"
                       for e in await client.get_lines("/events")):
                    break
                await asyncio.sleep(0.5)
            span_by_id.update((s["span"], s) for s in await client.get_lines("/spans"))
        spans = sorted(span_by_id.values(), key=lambda s: s["t0"])

    checks["no_compile_in_window"] = (
        compiles(events1, stats1) == compiled0,
        f"compile.begin + cache misses {compiled_before} before the lead-in, "
        f"{compiled0} at the window's opening, {compiles(events1, stats1)} at its end",
    )
    return {
        "workload": loaded["cell"]["name"], "config": config, "mix": mix, "vocab": vocab,
        "seconds": w1 - w0, "w0": w0, "w1": w1, "wall0": wall0, "wall1": wall1,
        "requests": ctx.requests, "lags_ms": ctx.lags_ms, "slots": slots,
        "stats0": stats0, "stats1": stats1, "events0": events0, "events1": events1,
        "spans": spans, "polls": polls, "device": device, "capture": capture,
        "work": work, "rehearse": args.rehearse, "probe": solo,
    }


def end_to_end(run: dict) -> dict:
    reqs, w0, w1 = run["requests"], run["w0"], run["w1"]
    ttft, gaps = arith.ttft_ms(reqs, w0, w1), arith.gaps_ms(reqs, w0, w1)
    n_tok = arith.tokens_in_window(reqs, w0, w1)
    say(f"samples: {n_tok} output tokens in the window, {len(ttft)} first tokens "
        f"({sum(1 for x in ttft if math.isinf(x))} not yet come), {len(gaps)} gaps, "
        f"generator lag p99 {arith.percentile(run['lags_ms'], 99):.2f} ms")
    return {
        "out_tok_s": n_tok / run["seconds"],
        "ttft_ms_p50": arith.percentile(ttft, 50),
        "gap_ms_p95": arith.percentile(gaps, 95),
    }


def reduce_trace(run: dict, children: Children) -> dict:
    found = sorted(glob.glob(os.path.join(run["work"], "profiles", "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise Refused(f"the capture left no .xplane.pb under {run['work']}/profiles")
    out = os.path.join(run["work"], "trace.json")
    spans_path = os.path.join(run["work"], "spans.json")
    with open(spans_path, "w") as f:
        json.dump(run["spans"], f)
    env = dict(children.env, JAX_PLATFORMS="cpu")
    children.run("reduce", [
        sys.executable, os.path.join(HERE, "reduce_trace.py"), found[-1],
        "--spans", spans_path, "--out", out,
    ], timeout=300, env=env)
    shutil.copy(out, children.log_dir)  # kept with the logs, to be read by hand
    with open(out) as f:
        return json.load(f)


def run_cell(args) -> dict:
    loaded = load_cell(args.workload)
    config, cell = loaded["config"], loaded["cell"]
    try:
        from inferd_tpu.config import get_config
    except ImportError as e:
        raise Refused(f"the program is not in this checkout: {e}")
    model = config["rehearse"]["model"] if args.rehearse else config["preset"]
    check_preset(config, loaded["reduced"], get_config(config["preset"]))
    flags = config["rehearse"]["node_flags"] if args.rehearse else config["node_flags"]
    probe_len, probe_new = probe_sizes(config, flags)
    probe_ids = probe_prompt(config, get_config(model).vocab_size, probe_len)
    script = reference_script(config)  # one that is not there is refused before the node starts
    dev = "cpu" if args.rehearse else "tpu"
    tag = f"{args.workload}-s{args.seed}-t{args.trace}"
    work = os.path.join(CACHE, "work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        env["JAX_PLATFORMS"] = "cpu"
        if cell["chips"] > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={cell['chips']}").strip()
    children = Children(env, os.path.join(REPO, "chiprun_out", "benchmark", tag), work, OUT)
    timings, checks = {}, {}
    home = os.path.join(CACHE, f"{config['name']}-w{config['weights_seed']}" +
                        ("-rehearse" if args.rehearse else ""))
    parts_dir = os.path.join(home, "parts")
    try:
        ensure_weights(config, model, get_config(model), children, parts_dir, timings)
        port = free_port()
        node = children.spawn("node", [
            sys.executable, "-m", "inferd_tpu.tools.run_node", "--model", model, *flags,
            "--device", dev, "--parts", parts_dir, "--host", "127.0.0.1",
            "--port", str(port), "--gossip-port", str(free_port()), "--name", "bench",
            *(["--enable-profiling"] if args.trace else []),
        ])
        try:
            run = asyncio.run(drive(args, loaded, node, port, children, probe_ids, probe_new,
                                    timings, checks, work))
        finally:
            code = children.stop(node)
        checks["node_exit_0"] = (code == 0, f"exit code {code}")
        # the chip is free and the node's peak memory has been read: the reference
        t0 = time.monotonic()
        config_file = loaded["config_file"] if not args.rehearse else rehearsal_config(
            config, get_config(model), os.path.join(work, "reference_config.json"))
        ref_path = run_reference(script, model, config_file, dev, children, parts_dir,
                                 probe_ids, run["probe"]["tokens"][:-1])
        timings["reference_s"] = time.monotonic() - t0
        checks.update(check_reference(run["probe"], ref_path, config["logprob_tolerance"]))
    finally:
        children.stop_all()

    reqs = arith.sent_in_window(run["requests"], run["w0"], run["w1"])
    why_failed = {id(r): arith.failed_reason(r, run["vocab"]) for r in run["requests"]}
    reasons = [x for x in why_failed.values() if x]
    failed = sum(1 for r in reqs if why_failed[id(r)])
    checks["every_token_well_formed"] = (not reasons, "; ".join(reasons[:3]))
    say("time by part (the reference runs after the window): "
        + ", ".join(f"{k} {v:.1f}" for k, v in timings.items()))

    if args.trace:
        run["trace"] = reduce_trace(run, children)
        values = {}
        for m in loaded["per_layer"]:
            v = load_reader(m["name"])(run)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = run["trace"]
        run["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"][:10], "idle_gaps": tr["idle_gaps"][:10]}
        say(f"trace: {tr['window_s']:.3f}s window, busy {tr['busy_s']:.3f}s, alignment "
            f"{tr.get('alignment')}")
    else:
        e2e = end_to_end(run)
        e2e["setup_s"] = timings["setup_s"]
        units = {m["name"]: m["unit"] for m in loaded["end_to_end"]}
        values = {k: {"value": e2e[k], "unit": u} for k, u in units.items()}
        breakdown = None
    for name, v in values.items():
        say(f"metric {name} = {v['value']} {v['unit']}")

    checks["parent_held_no_backend"] = (not parent_backend_live(), "")
    for name, (ok, detail) in checks.items():
        line = f"{'PASS' if ok else 'FAIL'} {name}{': ' + detail if detail else ''}"
        say(line)
        print(line, file=sys.stderr)  # the driver's record keeps the end of standard error
    odd = {k: v["value"] for k, v in values.items()
           if not (isinstance(v["value"], (int, float)) and math.isfinite(v["value"]))}
    if odd:
        raise Refused(f"metrics that are no finite number: {odd}")
    mem = run["stats1"].get("device", {}).get("memory") or []
    result = {
        "correct": bool(all(ok for ok, _ in checks.values()) and not args.rehearse),
        "attempted": len(reqs),
        "failed": failed,
        "metrics": values,
        "device": {
            "platform": run["device"].get("platform"),
            "kind": run["device"].get("device_kind"),
            "count": int(run["device"].get("device_count") or 0),
            "memory_peak_bytes": max((int(m["peak_bytes_in_use"]) for m in mem), default=0),
            **({"busy_s": run["device"]["busy_s"], "window_s": run["device"]["window_s"]}
               if args.trace else {}),
        },
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny preset on the CPU backend; tests the control flow, "
                    "always ends in correct=false")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    OUT.claim()
    try:
        result = run_cell(args)
    except Refused as e:
        say(f"no result: {e}")
        return 2
    except BaseException as e:
        traceback.print_exc(file=sys.stderr)
        say(f"no result: {type(e).__name__}: {e}"[:600])
        return 3
    OUT.last(json.dumps(result, allow_nan=False))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
