#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that inferd-tpu still starts on the chip.

    python chip_smoke.py              # one chip: Qwen3-0.6B behind run_node
    python chip_smoke.py --chips 4    # four chips: Qwen3-8B, run_node --mesh pp=4

One chip (the run the driver makes). Through the stock entry points only:
`tools.split_model --random-init --seed S` writes the 28-layer bf16
Qwen3-0.6B (published widths, vocabulary 151 936) from the seed on the host;
`tools.run_node --batch-lanes 8 --device tpu` serves it; a `SwarmClient`
sends a 300-token prompt twice, three generations in flight together, and
one server-side /generate, 32 greedy tokens each; after the node has exited,
`tools.generate --engine plain --device tpu` runs the same prompt through the
single-process engine on the same chip. Every phase can fail the run:

  * answers are well formed: the asked number of tokens, all in vocabulary;
  * the node itself reports platform `tpu` (/stats `device`); the journal
    has `executor.warmup_ok` and no `executor.warmup_failed`;
  * the same greedy request gives the same tokens alone, repeated, in a
    co-batch with other sessions, and through /generate — one lane's row of a
    static [lanes, 1] step does not depend on its neighbours, so these are
    exact on one device;
  * repeating a request compiles nothing (no `compile.begin`, no new
    persistent-cache request): a shape already served is already compiled;
  * the generations sent together were resident together (/stats
    `executor.lanes_busy` >= 2 while they ran). How many decode steps they
    actually shared (batched_tokens / batched_steps) is printed, not gated:
    the lane executor merges only arrivals that fall inside its 3 ms
    window, which is a property to measure, not one a smoke run can force;
  * against the plain engine the gate is the FIRST token. It comes from the
    prefill logits, which both paths compute with the same [1, S_bucket]
    program over the same seeded weights, so a mismatch means wrong weights,
    positions or KV writes — not rounding. Later tokens come from an
    [8, 1]-row step on the node and a [1, 1]-row step in the engine; bf16
    matmuls of different shapes round differently, and random-init logits
    are nearly flat, so the length of the agreeing prefix is printed and not
    gated.

Four chips (`--chips 4`, run by hand): only the mesh path and what it is
compared with. `run_node --model qwen3-8b --mesh pp=4 --device tpu` — 36
layers, 16.4 GB of bf16 weights, the configuration that does not fit one
16 GB chip — answers the same kinds of requests; every device must hold its
share of the bytes and none the whole (memory_stats of each device, printed);
then the same checkpoint is run stage after stage on ONE device (each 9-layer
part loaded, run over the prompt, freed — `--stagewise-child`, this file in a
process of its own) and the mesh's first token must be the argmax of those
last-position logits.

The parent never initializes a JAX backend: a chip belongs to one process,
and the processes that need it run one after another. The device in the last
line is what the node reported. `--rehearse` runs every phase at the `tiny`
preset on the CPU backend (virtual devices for `--chips 4`); it exists to
test this script's control flow and can only end in `"ok": false`.

The last line of standard output is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`
and nothing else is written to standard output after it; on any failure the
same shape with `"ok": false` and a non-zero exit code. Logs of the child
processes are kept under chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
WORK_DIR = os.path.join(REPO, ".chip_smoke")  # seeded weights; removed at exit

NEW_TOKENS = 32
PROMPT_LENS = (300, 12, 40)  # the long one is the request every check repeats
LANES = 8

# The real standard output, once main() has claimed it (claim_stdout): the
# only writers are `say` and the last line.
_REAL_STDOUT = sys.stdout


def claim_stdout() -> None:
    """Keep a private handle on the real standard output and point fd 1 (and
    sys.stdout) at stderr, so that no library, warning or child process can
    put a line after the contract's last one."""
    global _REAL_STDOUT
    _REAL_STDOUT = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr


def say(msg: str) -> None:
    _REAL_STDOUT.write(f"[smoke] {msg}\n")
    _REAL_STDOUT.flush()


def last_line(ok: bool, device: dict) -> str:
    """The contract's last line, from the device facts the node reported."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": device.get("platform"),
            "kind": device.get("device_kind"),
            "count": int(device.get("device_count") or 0),
        },
    })


class CheckFailed(Exception):
    """A phase cannot go on (the node is gone, a child failed)."""


class Phases:
    """Ordered record of what ran and what each check found."""

    def __init__(self):
        self.rows = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.rows.append((name, bool(ok), detail))
        say(f"{'PASS' if ok else 'FAIL'} {name}{': ' + detail if detail else ''}")
        return bool(ok)

    @property
    def all_ok(self) -> bool:
        return bool(self.rows) and all(ok for _n, ok, _d in self.rows)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_prompts(seed: int, vocab: int):
    rng = random.Random(seed)
    return [[rng.randrange(vocab) for _ in range(n)] for n in PROMPT_LENS]


class Children:
    """Every process this script starts, so that none outlives it."""

    def __init__(self, env):
        self.env = env
        self.live = []

    def spawn(self, name: str, argv, capture: bool = False):
        """stderr (and stdout, unless captured) go to chiprun_out/chip_smoke/
        <name>.log — a child never inherits this script's standard output."""
        log = open(os.path.join(LOG_DIR, f"{name}.log"), "w")
        proc = subprocess.Popen(
            argv, cwd=REPO, env=self.env, stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE if capture else log,
            stderr=log, start_new_session=True, text=True,
        )
        proc._log = log
        self.live.append(proc)
        return proc

    def run(self, name: str, argv, timeout: float) -> str:
        """Run a child to its end; returns its captured stdout."""
        t0 = time.monotonic()
        proc = self.spawn(name, argv, capture=True)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop(proc, grace=5.0)
            raise CheckFailed(f"{name}: no end within {timeout:.0f}s")
        self._forget(proc)
        if proc.returncode != 0:
            raise CheckFailed(
                f"{name}: exit code {proc.returncode}: {tail_log(name)}"
            )
        say(f"{name}: done in {time.monotonic() - t0:.1f}s")
        return out

    def stop(self, proc, grace: float = 30.0) -> int:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
                proc.wait(timeout=grace)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=30)
        self._forget(proc)
        return proc.returncode

    def _forget(self, proc) -> None:
        proc._log.close()
        if proc in self.live:
            self.live.remove(proc)

    def stop_all(self) -> None:
        for proc in list(self.live):
            self.stop(proc, grace=5.0)


def tail_log(name: str, n: int = 1500) -> str:
    try:
        with open(os.path.join(LOG_DIR, f"{name}.log")) as f:
            return f.read()[-n:].strip().replace("\n", " | ")
    except OSError:
        return "(no log)"


def last_log_line(name: str) -> str:
    return tail_log(name, 4000).rsplit(" | ", 1)[-1]


def well_formed(ids, vocab: int) -> bool:
    return (
        isinstance(ids, list) and len(ids) == NEW_TOKENS
        and all(isinstance(t, int) and 0 <= t < vocab for t in ids)
    )


def agreeing_prefix(a, b) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def memory_shares_ok(memory, weight_bytes: int, layer_share: int):
    """Each device of the mesh holds at least its slice of the layer stack
    and none holds as much as the whole model: code that has only ever seen
    one chip would put everything on the first."""
    if len(memory) < 2:
        return False, f"{len(memory)} devices report memory_stats"
    used = [int(m["bytes_in_use"]) for m in memory]
    ok = all(layer_share <= u < 0.6 * weight_bytes for u in used)
    return ok, (
        f"bytes_in_use per device {used}; layer slice {layer_share}, "
        f"whole model {weight_bytes}"
    )


# ---------------------------------------------------------------------------
# the node phase (both flavors): start run_node, drive it, stop it
# ---------------------------------------------------------------------------


async def drive_node(node, port: int, prompts, vocab: int, phases: Phases,
                     device: dict, ready_timeout: float):
    """Everything asked of the running node. Returns the long prompt's
    tokens and the per-device memory the node reported at the end."""
    import aiohttp

    from inferd_tpu.client.swarm_client import SwarmClient
    from inferd_tpu.config import SamplingConfig

    base = f"http://127.0.0.1:{port}"

    async with aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=60)
    ) as http:

        async def stats():
            async with http.get(base + "/stats") as r:
                return await r.json()

        async def events():
            async with http.get(base + "/events") as r:
                text = await r.text()
            return [json.loads(line) for line in text.splitlines() if line.strip()]

        def compiles(evs, st):
            cc = st.get("compile_cache") or {}
            return (
                sum(1 for e in evs if e["type"] == "compile.begin"),
                int(cc.get("hits", 0)) + int(cc.get("misses", 0)),
            )

        async def say_span_medians(since: float):
            """Where a round trip's time goes at the node, on the host's
            clock: medians over the spans of one solo generation (printed
            for PERF.md, gated by nothing)."""
            async with http.get(base + "/spans") as r:
                text = await r.text()
            spans = [json.loads(l) for l in text.splitlines() if l.strip()]
            for name in ("forward", "compute"):
                ms = sorted((sp["t1"] - sp["t0"]) * 1e3 for sp in spans
                            if sp.get("name") == name and sp["t0"] >= since)
                if ms:
                    say(f"node span '{name}': median {ms[len(ms) // 2]:.2f} ms "
                        f"over {len(ms)} spans of solo #2 (host clock; "
                        f"'compute' includes the device sync)")

        # -- ready: the node answers and its warm-up is on the record --------
        t0 = time.monotonic()
        evs = []
        while True:
            if node.poll() is not None:
                raise CheckFailed(
                    f"run_node exited with code {node.returncode} before "
                    f"serving: {tail_log('node')}"
                )
            try:
                evs = await events()
                if any(e["type"].startswith("executor.warmup_") for e in evs):
                    break
            except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
                pass
            if time.monotonic() - t0 > ready_timeout:
                raise CheckFailed(
                    f"node not ready within {ready_timeout:.0f}s: "
                    f"{tail_log('node')}"
                )
            await asyncio.sleep(0.5)
        say(f"node ready after {time.monotonic() - t0:.1f}s")

        st = await stats()
        device.update(st.get("device") or {})
        say(f"node device: {json.dumps(st.get('device'))}; wire codec "
            f"{st.get('wire_codec')}; compile cache {json.dumps(st.get('compile_cache'))}")
        phases.check(
            "node_reports_tpu", device.get("platform") == "tpu",
            f"platform={device.get('platform')!r} kind={device.get('device_kind')!r} "
            f"count={device.get('device_count')}",
        )
        types = [e["type"] for e in evs]
        warm = next((e for e in evs if e["type"] == "executor.warmup_ok"), None)
        phases.check(
            "warmup_ok",
            warm is not None and "executor.warmup_failed" not in types,
            f"{(warm or {}).get('attrs')}" if warm else f"events: {types}",
        )

        greedy = SamplingConfig(temperature=0.0)
        long_p = prompts[0]
        async with SwarmClient(
            [("127.0.0.1", port)], sampling=greedy, timeout_s=600.0
        ) as client:

            async def gen(p):
                return await client.generate_ids(
                    p, max_new_tokens=NEW_TOKENS, session_retries=0
                )

            t = time.monotonic()
            first = await gen(long_p)
            say(f"solo #1: {len(long_p)}-token prompt -> {first[:8]}... "
                f"in {time.monotonic() - t:.1f}s")
            phases.check("solo_well_formed", well_formed(first, vocab),
                         f"{len(first)} tokens")

            before = compiles(await events(), await stats())
            t, wall0 = time.monotonic(), time.time()
            again = await gen(long_p)
            after = compiles(await events(), await stats())
            await say_span_medians(wall0)
            dt = time.monotonic() - t
            say(f"solo #2 in {dt:.2f}s: {dt / (NEW_TOKENS + 1) * 1e3:.1f} ms a "
                f"round trip seen by the client (1 prefill + {NEW_TOKENS} "
                f"decode steps, nothing compiling)")
            phases.check("repeat_same_tokens", again == first,
                         f"agreeing prefix {agreeing_prefix(again, first)}/{NEW_TOKENS}")
            phases.check(
                "repeat_compiles_nothing", after == before,
                f"(compile.begin, cache requests) {before} -> {after}",
            )

            busy = []

            async def watch_lanes():
                while True:
                    ex = (await stats()).get("executor") or {}
                    busy.append(int(ex.get("lanes_busy") or ex.get("sessions") or 0))
                    await asyncio.sleep(0.05)

            t = time.monotonic()
            watcher = asyncio.create_task(watch_lanes())
            try:
                outs = await asyncio.gather(*(gen(p) for p in prompts))
            finally:
                watcher.cancel()
                await asyncio.gather(watcher, return_exceptions=True)
            say(f"{len(prompts)} generations in flight together: "
                f"{time.monotonic() - t:.1f}s")
            phases.check(
                "concurrent_well_formed",
                all(well_formed(o, vocab) for o in outs),
                f"lengths {[len(o) for o in outs]}",
            )
            phases.check(
                "cobatch_same_tokens", outs[0] == first,
                f"agreeing prefix {agreeing_prefix(outs[0], first)}/{NEW_TOKENS}",
            )
            phases.check(
                "sessions_resident_together", max(busy, default=0) >= 2,
                f"most lanes busy at once: {max(busy, default=0)}",
            )
            ex = (await stats()).get("executor") or {}
            say(f"decode steps shared: batched_tokens={ex.get('batched_tokens')} "
                f"batched_steps={ex.get('batched_steps')} "
                f"mean_batch={ex.get('mean_batch')}")

            before = compiles(await events(), await stats())
            t = time.monotonic()
            served = await client.generate_server_side(
                long_p, max_new_tokens=NEW_TOKENS
            )
            after = compiles(await events(), await stats())
            say(f"server-side /generate in {time.monotonic() - t:.1f}s")
            phases.check(
                "server_side_same_tokens",
                well_formed(served, vocab) and served == first,
                f"agreeing prefix {agreeing_prefix(served, first)}/{NEW_TOKENS}",
            )
            phases.check(
                "server_side_compiles_nothing", after == before,
                f"(compile.begin, cache requests) {before} -> {after}",
            )

        st = await stats()
        evs = await events()
        n_compiles, _ = compiles(evs, st)
        say(f"compiles seen by the node: {n_compiles} compile.begin events; "
            f"persistent cache {json.dumps(st.get('compile_cache'))}")
        phases.check(
            "no_warmup_failure_later",
            not any(e["type"] == "executor.warmup_failed" for e in evs),
        )
        return first, (st.get("device") or {}).get("memory") or []


def split_and_serve(args, children: Children, phases: Phases, device: dict,
                    model: str, mode_flags, timeout: float):
    """What both flavors share: seeded weights through `split_model` on the
    host, then `run_node <mode_flags>` driven by drive_node and stopped.
    Returns (cfg, platform flag, prompts, checkpoint path, the long
    prompt's tokens, per-device memory)."""
    from inferd_tpu.config import get_config

    dev = "cpu" if args.rehearse else "tpu"
    cfg = get_config(model)
    prompts = make_prompts(args.seed, cfg.vocab_size)
    parts = os.path.join(WORK_DIR, "parts")
    say(f"model {model}: {cfg.num_layers} layers (no depth cut), hidden "
        f"{cfg.hidden_size}, vocab {cfg.vocab_size}, dtype {cfg.dtype}; "
        f"run_node {' '.join(mode_flags)}; seed {args.seed}")
    children.run("split", [
        sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", model,
        "--stages", "1", "--random-init", "--seed", str(args.seed),
        "--device", "cpu", "--out", parts,
    ], timeout=timeout)

    port = free_port()
    node = children.spawn("node", [
        sys.executable, "-m", "inferd_tpu.tools.run_node", "--model", model,
        *mode_flags, "--device", dev, "--parts", parts, "--host", "127.0.0.1",
        "--port", str(port), "--gossip-port", str(free_port()),
        "--name", "smoke",
    ])
    try:
        first, memory = asyncio.run(drive_node(
            node, port, prompts, cfg.vocab_size, phases, device, timeout
        ))
    finally:
        code = children.stop(node)
        phases.check("node_exit", code == 0, f"exit code {code}")
    ckpt = os.path.join(parts, "stage_000.msgpack")
    return cfg, dev, prompts, ckpt, first, memory


# ---------------------------------------------------------------------------
# the two flavors
# ---------------------------------------------------------------------------


def smoke_one_chip(args, children: Children, phases: Phases, device: dict):
    model = "tiny" if args.rehearse else "qwen3-0.6b"
    cfg, dev, prompts, _ckpt, first, _memory = split_and_serve(
        args, children, phases, device, model,
        ["--batch-lanes", str(LANES)], timeout=600,
    )

    # the plain single-process engine, alone on the chip the node has left
    out = children.run("plain", [
        sys.executable, "-m", "inferd_tpu.tools.generate", "--model", model,
        "--random-init",
        "--seed", str(args.seed), "--engine", "plain", "--device", dev,
        "--temperature", "0", "--max-new-tokens", str(NEW_TOKENS),
        "--prompt-ids", ",".join(map(str, prompts[0])),
    ], timeout=600)
    say(f"plain engine: {last_log_line('plain')}")
    line = next(
        (l for l in out.splitlines() if l.startswith("generated ids:")), ""
    )
    plain = json.loads(line.split(":", 1)[1]) if line else []
    agree = agreeing_prefix(plain, first)
    say(f"node vs plain engine: agreeing prefix {agree}/{NEW_TOKENS}")
    phases.check(
        "plain_engine_first_token",
        well_formed(plain, cfg.vocab_size) and agree >= 1,
        f"plain {plain[:4]}... node {first[:4]}...",
    )


def smoke_four_chips(args, children: Children, phases: Phases, device: dict):
    model = "tiny" if args.rehearse else "qwen3-8b"
    cfg, dev, prompts, ckpt, first, memory = split_and_serve(
        args, children, phases, device, model, ["--mesh", "pp=4"],
        timeout=1500,
    )
    weight_bytes = os.path.getsize(ckpt)
    say(f"checkpoint {weight_bytes} bytes")
    for m in memory:
        say(f"device memory: {json.dumps(m)}")
    phases.check("four_devices", device.get("device_count") == 4,
                 f"device_count={device.get('device_count')}")
    # a rank's slice of the layer stack: everything but the embedding and
    # the head, over four ranks
    table = cfg.vocab_size * cfg.hidden_size * 2
    heads = table * (1 if cfg.tie_word_embeddings else 2)
    phases.check(
        "every_device_holds_its_share",
        *memory_shares_ok(memory, weight_bytes, (weight_bytes - heads) // 4),
    )

    # the same checkpoint, stage after stage on ONE device
    out = children.run("stagewise", [
        sys.executable, os.path.abspath(__file__), "--stagewise-child",
        "--model", model, "--device", dev, "--ckpt", ckpt,
        "--prompt-ids", ",".join(map(str, prompts[0])),
    ], timeout=1500)
    ref = json.loads(out.strip().splitlines()[-1])
    say(f"stage after stage on one device: {json.dumps(ref)}")
    phases.check(
        "mesh_first_token_is_stagewise_argmax",
        bool(first) and first[0] == ref["argmax"],
        f"mesh {first[:1]} stagewise argmax {ref['argmax']} "
        f"(top-2 margin {ref['margin']:.4g})",
    )


def stagewise_child(args) -> int:
    """Child process: the 1-stage checkpoint cut into four parts, each
    loaded on ONE device, run over the prompt and freed, through the stock
    stage executor. Prints the last-position logits' argmax as JSON."""
    import gc

    import numpy as np

    from inferd_tpu.utils.platform import (
        enable_compile_cache, force_platform, require_platform,
    )

    force_platform(args.device)
    enable_compile_cache()
    facts = require_platform(args.device)

    from inferd_tpu.config import get_config
    from inferd_tpu.parallel import stages as stagelib
    from inferd_tpu.runtime.executor import make_executor

    cfg = get_config(args.model)
    full, _spec, _name = stagelib.load_stage_checkpoint(args.ckpt)
    prompt = [int(t) for t in args.prompt_ids.split(",")]
    payload = {
        "tokens": np.asarray([prompt], np.int32), "start_pos": 0,
        "real_len": len(prompt),
    }
    for spec in stagelib.Manifest.even_split(args.model, 4).stage_specs():
        part = stagelib.extract_stage_params(full, cfg, spec)
        ex = make_executor(cfg, spec, part, max_len=1024)
        out = ex.process("cmp", payload)
        print(f"stage {spec.stage}: layers {spec.start_layer}-{spec.end_layer} "
              f"on {facts['platform']}", file=sys.stderr)
        payload = {
            "hidden": out.get("hidden"), "start_pos": 0,
            "real_len": len(prompt),
        }
        del ex, part
        gc.collect()
    logits = np.asarray(out["logits"], np.float32)[0]
    top = np.argsort(logits)[-2:][::-1]
    _REAL_STDOUT.write(json.dumps({
        "argmax": int(top[0]), "runner_up": int(top[1]),
        "margin": float(logits[top[0]] - logits[top[1]]),
        "finite": bool(np.isfinite(logits).all()),
    }) + "\n")
    _REAL_STDOUT.flush()
    return 0


# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 = only the qwen3-8b --mesh pp=4 path and its "
                    "stage-after-stage comparison")
    ap.add_argument("--seed", type=int, default=22,
                    help="seed of the weights and the prompts")
    ap.add_argument("--rehearse", action="store_true",
                    help="every phase at the tiny preset on the CPU backend; "
                    "tests this script, always ends in ok=false")
    ap.add_argument("--stagewise-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--model", help=argparse.SUPPRESS)
    ap.add_argument("--device", help=argparse.SUPPRESS)
    ap.add_argument("--ckpt", help=argparse.SUPPRESS)
    ap.add_argument("--prompt-ids", help=argparse.SUPPRESS)
    return ap


def parent_backend_live() -> bool:
    """Did THIS process initialize a JAX backend? It must not: the chip
    belongs to the child that computes on it."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    claim_stdout()
    if args.stagewise_child:
        return stagewise_child(args)

    t_start = time.monotonic()
    phases = Phases()
    device: dict = {}
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        # XLA:CPU can refuse, or crash on, executables another process cached
        # on the same host (tests/conftest.py): the rehearsal compiles anew
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        if args.chips == 4:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()
    children = Children(env)
    try:
        os.makedirs(LOG_DIR, exist_ok=True)
        shutil.rmtree(WORK_DIR, ignore_errors=True)
        os.makedirs(WORK_DIR)
        import logging

        logging.basicConfig(stream=sys.stderr, level=logging.WARNING)
        from inferd_tpu import native

        say(f"chips={args.chips} rehearse={args.rehearse} "
            f"JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r} "
            f"wire codec (client) "
            f"{'native' if native.codec is not None else 'python'}")
        if args.chips == 4:
            smoke_four_chips(args, children, phases, device)
        else:
            smoke_one_chip(args, children, phases, device)
    except BaseException as e:  # the last stdout line is ours, whatever happened
        traceback.print_exc(file=sys.stderr)
        phases.check("ran_to_the_end", False, f"{type(e).__name__}: {e}"[:600])
    finally:
        children.stop_all()
        shutil.rmtree(WORK_DIR, ignore_errors=True)
    phases.check("parent_held_no_backend", not parent_backend_live())
    ok = phases.all_ok and device.get("platform") == "tpu" and not args.rehearse
    say(f"{sum(ok_ for _n, ok_, _d in phases.rows)}/{len(phases.rows)} checks "
        f"passed in {time.monotonic() - t_start:.1f}s")
    _REAL_STDOUT.write(last_line(ok, device) + "\n")
    _REAL_STDOUT.flush()
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
