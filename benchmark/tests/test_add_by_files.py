"""Section 7 of the issue: a configuration, a mix, a generator kind and a
per-layer metric each arrive as NEW files and NEW entries. In a temporary
copy all four are added without touching a file that is there; the
manifest still validates and the new cell runs (as a rehearsal) and
reports the new metric."""

import hashlib
import json
import os
import shutil

from conftest import BENCH, REPO
from test_last_line import rehearse

NEW_KIND = '''
"""Open loop at a fixed rate: a request every 1/rate seconds, whether or
not earlier ones have ended."""
import asyncio


def plan(mix, pool, slots, seed):
    import random
    sizes = list(pool)
    random.Random(seed).shuffle(sizes)
    return {"clients": 1, "starts": [0.0], "sizes": sizes, "rate": float(mix["rate_per_s"])}


async def run(plan_, ctx):
    tasks, i = [], 0
    try:
        while True:
            due = i / plan_["rate"]
            await ctx.sleep_until(due)
            n_prompt, n_out = plan_["sizes"][i % len(plan_["sizes"])]
            tasks.append(asyncio.create_task(ctx.request(0, i, n_prompt, n_out, due)))
            i += 1
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
'''

NEW_READER = '''
"""Layer kv_manager. Prompt tokens the executor prefilled in the window (/stats)."""
import arith


def read(run):
    return arith.counter_delta(run["stats0"], run["stats1"], "executor.prefill_tokens")
'''


def digest(root):
    out = {}
    for base, _dirs, files in os.walk(root):
        if ".cache" in base or "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_four_additions_touch_no_existing_file(tmp_path):
    import validate_manifest as vm

    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "inferd_tpu"), os.path.join(root, "inferd_tpu"))
    before = digest(os.path.join(root, "benchmark"))

    # 1. a configuration that needs other run_node flags
    with open(os.path.join(root, "benchmark/configs/qwen3-4b-1chip.json")) as f:
        config = json.load(f)
    config.update(name="qwen3-4b-paged", node_flags=["--batch-lanes", "4", "--paged-kv", "32"],
                  slots=4)
    config["rehearse"] = dict(config["rehearse"],
                              node_flags=["--batch-lanes", "4", "--paged-kv", "16", "--max-len", "512"])
    with open(os.path.join(root, "benchmark/configs/qwen3-4b-paged.json"), "w") as f:
        json.dump(config, f)
    # 2. a mix, 3. of a new kind
    mix = {"kind": "open", "rate_per_s": 4, "lead_in_s": 3, "pool": 8,
           "prompt_len": {"dist": "uniform", "min": 64, "max": 512},
           "output_len": {"dist": "fixed", "value": 24}}
    with open(os.path.join(root, "benchmark/traffic/open-steady.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark/traffic_kinds/open.py"), "w") as f:
        f.write(NEW_KIND)
    # 4. a per-layer metric with a reader of its own
    with open(os.path.join(root, "benchmark/layer_metrics/kv.prefill_tokens_in_window.py"), "w") as f:
        f.write(NEW_READER)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "qwen3-4b-paged", "source": config["source"] + "#paged",
        "file": "benchmark/configs/qwen3-4b-paged.json", "reduced": [], "why": "paged KV"})
    manifest["workloads"].append({
        "name": "q4b-open-steady", "config": "qwen3-4b-paged", "traffic": "open-steady",
        "chips": 1, "why": "open loop at a fixed rate"})
    manifest["per_layer"].append({
        "name": "kv.prefill_tokens_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kv_manager", "moves": "out_tok_s",
        "workloads": ["q4b-open-steady"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    after = digest(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    assert len(after) == len(before) + 4
    assert vm.validate(manifest, root) == []

    done = rehearse(root, "q4b-open-steady", 1, seconds="4")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["attempted"] >= 8 and last["failed"] == 0
    assert last["metrics"]["kv.prefill_tokens_in_window"]["value"] >= 8 * 6
    assert "kernels.decode_roofline" not in last["metrics"]  # lists other cells
