"""Section 7 of the issue: a configuration, a mix, a generator kind and a
per-layer metric each arrive as NEW files and NEW entries. In a temporary
copy all four are added without touching a file that is there; the
manifest still validates and the new cell runs (as a rehearsal) and
reports the new metric. So does a configuration of another architecture
(PR 28): the program's `tiny-moe` preset with a reference, a size check and
a probe of its own, and both reference checks pass on it."""

import glob
import hashlib
import json
import os
import shutil

import pytest

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


# a reference that stands alone, written from the expert layer's equations
MOE_REFERENCE = os.path.join(BENCH, "tests", "data", "moe-softmax-topk.py")

MOE_CONFIG = {
    "name": "tiny-moe-1chip", "source": "inferd_tpu/config.py TINY_MOE",
    "model_type": "qwen3_moe", "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "tie_word_embeddings": True,
    "rope_parameters": {"rope_theta": 1000000.0}, "rms_norm_eps": 1e-06,
    "chips": 1, "preset": "tiny-moe", "weights_seed": 28, "slots": 4,
    "node_flags": ["--batch-lanes", "4", "--max-len", "512"],
    "reference": "moe-softmax-topk",
    "preset_check": {
        "hidden_size": "hidden_size", "moe_intermediate_size": "moe_intermediate_size",
        "num_experts": "num_experts", "num_experts_per_tok": "num_experts_per_tok",
        "norm_topk_prob": "norm_topk_prob", "num_hidden_layers": "num_layers",
        "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
        "head_dim": "head_dim", "vocab_size": "vocab_size",
        "tie_word_embeddings": "tie_word_embeddings",
        "rope_parameters.rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps"},
    "probe": {"prompt_len": 96, "new": 24},
    "logprob_tolerance": {"value": 0.001, "why": "float32 on both sides: rounding"},
    "rehearse": {"model": "tiny-moe", "node_flags": ["--batch-lanes", "4", "--max-len", "512"],
                 "length_scale": 0.1},
}


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


def copy_of_the_benchmark(root):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "inferd_tpu"), os.path.join(root, "inferd_tpu"))
    return digest(os.path.join(root, "benchmark"))


def add_moe_cell(root, config=MOE_CONFIG, reduced=("num_hidden_layers", "num_experts")):
    """The tiny-moe configuration, its reference and one cell: files and
    entries only. Returns the manifest as written."""
    os.makedirs(os.path.join(root, "benchmark/references"), exist_ok=True)
    shutil.copy(MOE_REFERENCE, os.path.join(root, "benchmark/references/moe-softmax-topk.py"))
    with open(os.path.join(root, "benchmark/configs/tiny-moe-1chip.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny-moe-1chip", "source": "https://example.org/tiny-moe/config.json",
        "file": "benchmark/configs/tiny-moe-1chip.json", "reduced": list(reduced),
        "why": "8 experts, 2 a token: an expert layer where the others have a dense one"})
    manifest["workloads"].append({
        "name": "tmoe-sat-chat", "config": "tiny-moe-1chip", "traffic": "sat-chat", "chips": 1,
        "why": "the saturating chat on the expert layer"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return manifest


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """A copy of the benchmark with the tiny-moe cell added, rehearsed once."""
    import validate_manifest as vm

    root = str(tmp_path_factory.mktemp("moe"))
    before = copy_of_the_benchmark(root)
    manifest = add_moe_cell(root)
    after = digest(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    assert sorted(set(after) - set(before)) == ["configs/tiny-moe-1chip.json",
                                                "references/moe-softmax-topk.py"]
    assert vm.validate(manifest, root) == []
    return root, rehearse(root, "tmoe-sat-chat", 0, seconds="3")


def test_another_architecture_enters_by_files_and_meets_its_reference(moe_root):
    _root, done = moe_root
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    assert "PASS probe_reference" in done.stdout, done.stdout[-3000:]
    assert "PASS probe_decode_reference" in done.stdout and "of 1-23;" in done.stdout
    assert "FAIL" not in done.stdout  # a rehearsal is `correct: false` for being one
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["attempted"] > 0 and last["failed"] == 0


def test_a_shifted_reference_row_fails_the_decode_check_at_its_position(moe_root):
    """The cached reference with row 7 moved until the mean over the 23
    decoded rows passes the tolerance (0.001): the prefill's check still
    passes, the decode check fails and names position 7."""
    import numpy as np

    root, _done = moe_root
    (cached,) = glob.glob(os.path.join(root, "benchmark/.cache/*-rehearse/probe_ref-*.npy"))
    ref = np.load(cached)
    assert ref.shape == (24, 256)
    ref[7] += 0.05
    np.save(cached, ref)
    done = rehearse(root, "tmoe-sat-chat", 0, seconds="3")
    assert "PASS probe_reference" in done.stdout, done.stdout[-3000:]
    fail = [x for x in done.stdout.splitlines() if "FAIL probe_decode_reference" in x]
    assert len(fail) == 1 and " at position 7 of 1-23;" in fail[0], done.stdout[-3000:]
    assert "FAIL probe_decode_reference" in done.stderr  # the driver keeps the end of stderr


@pytest.mark.parametrize("flags", [["--quant", "int8"], ["--kv-dtype", "float8_e4m3fn"]],
                         ids=["weights-int8", "kv-fp8"])
def test_the_8_bit_control_fails_a_reference_check(tmp_path, flags):
    """The control of `correct`, at a size a test can hold: the same cell
    served through one of the program's own 8-bit paths (the precision next
    below the one the configuration states) is not correct, and the line
    that says so names a reference check. On the chip at the cells' own
    sizes: `benchmark/control.py`, PERF.md section 4."""
    root = str(tmp_path)
    copy_of_the_benchmark(root)
    rehearse_as = dict(MOE_CONFIG["rehearse"],
                       node_flags=MOE_CONFIG["rehearse"]["node_flags"] + flags)
    add_moe_cell(root, dict(MOE_CONFIG, rehearse=rehearse_as))
    done = rehearse(root, "tmoe-sat-chat", 0, seconds="3")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    failed = [x.split()[2].rstrip(":") for x in done.stdout.splitlines() if "] FAIL " in x]
    assert failed and set(failed) <= {"probe_reference", "probe_decode_reference"}, (
        done.stdout[-3000:])


@pytest.mark.parametrize("change, reduced, said", [
    ({"preset_check": dict(MOE_CONFIG["preset_check"], n_shared_experts="num_shared_experts")},
     ["num_experts"], "n_shared_experts"),
    ({}, ["num_experts", "max_position_embeddings"], "max_position_embeddings"),
    ({"num_experts": 16}, ["num_experts"], "num_experts"),
    ({"probe": {"prompt_len": 500, "new": 24}}, [], "--max-len 512"),
    ({"reference": "no-such-reference"}, [], "no-such-reference"),
])
def test_a_configuration_the_checks_do_not_cover_is_refused(tmp_path, change, reduced, said):
    """A checked attribute the preset lacks, a reduced key that is not
    checked, a checked key that differs, a probe longer than a session, a
    reference that is not there: exit code 2 before anything starts."""
    root = str(tmp_path)
    copy_of_the_benchmark(root)
    add_moe_cell(root, dict(MOE_CONFIG, **change), reduced)
    done = rehearse(root, "tmoe-sat-chat", 0, seconds="3")
    assert done.returncode == 2, done.stdout[-2000:] + done.stderr[-2000:]
    assert "no result" in done.stdout and said in done.stdout
    assert not os.path.exists(os.path.join(root, "benchmark/.cache"))


def test_four_additions_touch_no_existing_file(tmp_path):
    import validate_manifest as vm

    root = str(tmp_path)
    before = copy_of_the_benchmark(root)

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
