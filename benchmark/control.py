#!/usr/bin/env python3
"""benchmark/control.py — the readings a `logprob_tolerance` is set from.

    python3 benchmark/control.py --config <name> [--prompts 12] \
        --variant sound= --variant quant-int8="--quant int8" [--rehearse]

Not part of a run: a builder's tool, one call on the chip per configuration.
It serves the configuration as `run.py` does (the same checkpoint, the same
`node_flags`), once per `--variant` with that variant's extra `run_node`
flags: none for the sound program, the program's own 8-bit paths for the
control (the precision next below the bf16 the configuration states). Each
node answers the same probes alone, greedy, `top_logprobs` 8: probe 0 is the
one `run.py` sends in every run, the others are drawn from `weights_seed`
the same way, all of the configuration's `probe` lengths. Once the last
node has exited, the configuration's reference (its `logprobs`) reads every
(prompt, answered tokens but the last) in ONE pass over the weights, and
each probe is reduced as `run.check_reference` reduces it: the mean |node -
reference| over the node's 8 log-probabilities at position 0 (the prefill)
and over positions 1..M-1 (decode through the cache), and whether the
reference's argmax is among the 8. Beside them two numbers that were read
and found to swing too far from probe to probe to carry a limit: the
largest single difference, and the widest gap by which a served token's
log-probability lies below the reference's best.

A limit holds where the control's SMALLEST reading is some three times the
sound program's LARGEST; both are printed per variant and written, with
every probe's differences, to `chiprun_out/control/<config>.json`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import shlex
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path[:0] = [HERE, REPO]

import run as harness  # noqa: E402
import traffic  # noqa: E402
from procs import Children, Refused, free_port  # noqa: E402

say = harness.say


def probes(config: dict, vocab: int, n: int, count: int):
    """Probe 0 is run.py's own; the others are drawn alike."""
    out = [harness.probe_prompt(config, vocab, n)]
    for k in range(1, count):
        rng = random.Random(f"control/{config['weights_seed']}/{k}")
        out.append([rng.randrange(vocab) for _ in range(n)])
    return out


async def ask(node, port: int, children: Children, prompts, new: int):
    import aiohttp

    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30, sock_read=600)
    async with aiohttp.ClientSession(timeout=timeout) as http:
        client = harness.NodeClient(http, port)
        await harness.wait_ready(node, client, children, timeout=900)
        device = (await client.get_json("/stats")).get("device") or {}
        answers = []
        for ids in prompts:
            rec = await client.generate(ids, new, top=harness.PROBE_TOP)
            if rec["error"] or len(rec["tops"]) != new:
                raise Refused(f"a probe failed: {rec['error']} ({len(rec['tops'])} tops)")
            answers.append({"tokens": rec["tokens"], "tops": rec["tops"]})
        return answers, {k: device.get(k) for k in ("platform", "device_kind", "device_count")}


def reduce_probe(tops, ref) -> dict:
    """One probe as `run.check_reference` reads it, position by position."""
    diffs = [[abs(float(lp) - float(row[int(i)])) for i, lp in zip(ids, lps)]
             for (ids, lps), row in zip(tops, ref)]
    among = [int(row.argmax()) in [int(i) for i in ids] for (ids, _), row in zip(tops, ref)]
    gaps = [float(row.max() - row[int(ids[0])]) for (ids, _), row in zip(tops, ref)]
    return {"diffs": diffs, "among": among, "gaps": gaps,
            "first_mean": sum(diffs[0]) / len(diffs[0]),
            "decode_mean": sum(map(sum, diffs[1:])) / sum(map(len, diffs[1:])),
            "first_max": max(diffs[0]), "decode_max": max(max(d) for d in diffs[1:]),
            "gap": max(gaps), "all_among": all(among)}


def reference_job(args) -> int:
    """The child on the freed chip: every sequence of the job in one pass."""
    os.environ["JAX_PLATFORMS"] = args.device
    import jax
    import numpy as np

    if jax.devices()[0].platform != args.device:
        print(f"asked for {args.device}, JAX gave {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    reference = traffic.load_module(args.script, "the configuration's reference")
    with open(args.reference_job) as f:
        job = json.load(f)
    with open(args.config_file) as f:
        config = json.load(f)
    params, _spec, _name = load_stage_checkpoint(args.ckpt)
    seqs = sorted({tuple(a["prompt"] + a["tokens"][:-1]) for a in job})
    rows = len(job[0]["tokens"])
    lp = reference.logprobs(params, np.asarray(seqs), rows, config)
    at = {s: lp[i] for i, s in enumerate(seqs)}
    out = [dict({k: a[k] for k in ("variant", "probe", "tokens")},
                **reduce_probe(a["tops"], at[tuple(a["prompt"] + a["tokens"][:-1])]))
           for a in job]
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


def summary(rows, variants) -> dict:
    out = {}
    for name in variants:
        mine = [r for r in rows if r["variant"] == name]
        out[name] = {
            key: {"smallest": min(r[key] for r in mine), "largest": max(r[key] for r in mine),
                  "by_probe": [r[key] for r in mine]}
            for key in ("first_mean", "decode_mean", "first_max", "decode_max", "gap")}
        out[name]["argmax_among_top"] = [r["all_among"] for r in mine]
        out[name]["same_tokens_as_first_variant"] = [
            r["tokens"] == s["tokens"]
            for r, s in zip(mine, [x for x in rows if x["variant"] == variants[0]])]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", help="a configuration of BENCHMARK.json")
    ap.add_argument("--prompts", type=int, default=12)
    ap.add_argument("--variant", action="append", default=[], metavar="NAME=FLAGS",
                    help="extra run_node flags; `sound=` for none. The first is compared with")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--reference-job", help=argparse.SUPPRESS)
    for hidden in ("--script", "--config-file", "--ckpt", "--device", "--out"):
        ap.add_argument(hidden, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.reference_job:
        return reference_job(args)

    from inferd_tpu.config import get_config

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == args.config)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    variants = dict(v.split("=", 1) for v in args.variant)
    model = config["rehearse"]["model"] if args.rehearse else config["preset"]
    flags = config["rehearse"]["node_flags"] if args.rehearse else config["node_flags"]
    harness.check_preset(config, entry["reduced"], get_config(config["preset"]))
    n, new = harness.probe_sizes(config, flags)
    prompts = probes(config, get_config(model).vocab_size, n, args.prompts)
    dev = "cpu" if args.rehearse else "tpu"
    work = os.path.join(harness.CACHE, "work", f"control-{args.config}")
    os.makedirs(work, exist_ok=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearse:
        env.update(JAX_ENABLE_COMPILATION_CACHE="false", JAX_PLATFORMS="cpu",
                   XLA_FLAGS=(env.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
                              f"device_count={config['chips']}").strip())
    out_dir = os.path.join(REPO, "chiprun_out", "control")
    children = Children(env, os.path.join(out_dir, f"{args.config}-logs"), work, harness.OUT)
    home = os.path.join(harness.CACHE, f"{config['name']}-w{config['weights_seed']}" +
                        ("-rehearse" if args.rehearse else ""))
    parts_dir = os.path.join(home, "parts")
    job, devices = [], {}
    try:
        harness.ensure_weights(config, model, get_config(model), children, parts_dir, {})
        for name, extra in variants.items():
            port = free_port()
            node = children.spawn(f"node-{name}", [
                sys.executable, "-m", "inferd_tpu.tools.run_node", "--model", model, *flags,
                *shlex.split(extra), "--device", dev, "--parts", parts_dir,
                "--host", "127.0.0.1", "--port", str(port), "--gossip-port", str(free_port()),
                "--name", "bench"])
            try:
                answers, devices[name] = asyncio.run(ask(node, port, children, prompts, new))
            except Refused as e:  # a control that crashes has failed, and gives no number
                say(f"variant {name}: no reading: {e}")
                continue
            finally:
                children.stop(node)
            say(f"variant {name} ({extra or 'no extra flags'}): {len(answers)} probes answered")
            job += [dict(a, variant=name, probe=k, prompt=prompts[k])
                    for k, a in enumerate(answers)]
        config_file = os.path.join(REPO, entry["file"]) if not args.rehearse else \
            harness.rehearsal_config(config, get_config(model), os.path.join(work, "config.json"))
        job_path, rows_path = os.path.join(work, "job.json"), os.path.join(work, "rows.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        children.run("reference", [
            sys.executable, os.path.abspath(__file__), "--reference-job", job_path,
            "--script", harness.reference_script(config), "--config-file", config_file,
            "--ckpt", os.path.join(parts_dir, "stage_000.msgpack"), "--device", dev,
            "--out", rows_path], timeout=1500)
    finally:
        children.stop_all()
    with open(rows_path) as f:
        rows = json.load(f)
    names = [v for v in variants if any(r["variant"] == v for r in rows)]
    result = {"config": args.config, "model": model, "probe": [n, new], "variants": variants,
              "devices": devices, "tolerance": config["logprob_tolerance"]["value"],
              "summary": summary(rows, names), "rows": rows}
    with open(os.path.join(out_dir, f"{args.config}{'-rehearse' if args.rehearse else ''}.json"),
              "w") as f:
        json.dump(result, f)
    for name in names:
        for key, v in result["summary"][name].items():
            if isinstance(v, dict):
                say(f"{name} {key}: smallest {v['smallest']:.4g} largest {v['largest']:.4g}   "
                    + " ".join(f"{x:.3g}" for x in v["by_probe"]))
            else:
                say(f"{name} {key}: {sum(v)} of {len(v)}")
    return 0


if __name__ == "__main__":
    harness.OUT.claim()
    try:
        sys.exit(main())
    except Refused as e:
        say(f"no result: {e}")
        sys.exit(2)
