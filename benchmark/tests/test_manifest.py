"""validate_manifest.py: the committed BENCHMARK.json passes, and each rule
of form refuses a manifest that breaks it."""

import copy
import json
import os

import pytest

import validate_manifest as vm
from conftest import REPO


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def faults(m):
    return vm.validate(m, REPO)


def test_committed_manifest_passes():
    assert faults(manifest()) == []
    assert vm.main([os.path.join(REPO, "BENCHMARK.json")]) == 0


def edit(path, value):
    def apply(m):
        cur = m
        for key in path[:-1]:
            cur = cur[key]
        if value is KeyError:
            del cur[path[-1]]
        else:
            cur[path[-1]] = value
    return apply


def add_four_chip_cell(m):
    m["workloads"].append(dict(m["workloads"][1], name="extra", chips=4))


CASES = {
    "layer of plain words (ledger, PR 23)": (edit(["per_layer", 0, "layer"], "load generator"), "layer must be"),
    "layer starting with a dot": (edit(["per_layer", 0, "layer"], ".loadgen"), "layer must be"),
    "layer of 65 characters": (edit(["per_layer", 0, "layer"], "a" * 65), "layer must be"),
    "metric name with a space": (edit(["end_to_end", 1, "name"], "out tok"), "a name is"),
    "metric name starting with a dash": (edit(["per_layer", 2, "name"], "-x"), "a name is"),
    "unit with a space": (edit(["end_to_end", 1, "unit"], "tokens per s"), "unit"),
    "unit of 17 characters": (edit(["end_to_end", 1, "unit"], "t" * 17), "unit"),
    "greek unit": (edit(["end_to_end", 2, "unit"], "µs"), "unit"),
    "source over 200 characters": (edit(["configs", 0, "source"], "x" * 201), "source"),
    "why on two lines": (edit(["workloads", 0, "why"], "a\nb"), "why"),
    "run_seconds 52": (edit(["run_seconds"], 52), "run_seconds"),
    "run_seconds 12.5": (edit(["run_seconds"], 12.5), "run_seconds"),
    "bound over the limit": (edit(["end_to_end", 2, "bound"], 0.11), "bound"),
    "bound of zero": (edit(["end_to_end", 1, "bound"], 0), "bound"),
    "bound per cell": (edit(["end_to_end", 1, "bound"], {"q4b-sat-chat": 0.03}), "bound"),
    "setup_s missing": (edit(["end_to_end", 0, "name"], "startup_s"), "setup_s"),
    "moves a per-layer metric": (edit(["per_layer", 0, "moves"], "window.mean_cobatch"), "moves"),
    "moves a metric its cells lack": (edit(["end_to_end", 1, "workloads"], ["q4b-sat-chat"]), "do not report"),
    "unknown key on a metric": (edit(["per_layer", 0, "why"], "because"), "unknown"),
    "unknown top-level key": (edit(["notes"], "x"), "top-level"),
    "end-to-end read from a span": (edit(["end_to_end", 1, "source"], "program_span"), "host_clock"),
    "bad source": (edit(["per_layer", 0, "source"], "guess"), "source"),
    "better sideways": (edit(["per_layer", 0, "better"], "same"), "better"),
    "config file missing": (edit(["configs", 0, "file"], "benchmark/configs/none.json"), "missing"),
    "config file outside paths": (edit(["configs", 0, "file"], "README.md"), "under paths"),
    "traffic file missing": (edit(["workloads", 0, "traffic"], "nothing"), "no data file"),
    "cell of an unknown config": (edit(["workloads", 0, "config"], "gpt"), "not in configs"),
    "three chips": (edit(["workloads", 0, "chips"], 3), "chips"),
    "two four-chip cells of four": (add_four_chip_cell, "4 chips"),
    "reduced names a width": (edit(["configs", 0, "reduced"], ["hidden_size"]), "width"),
    "reduced names a rank": (edit(["configs", 0, "reduced"], ["kv_lora_rank"]), "width"),
    "absolute command word": (edit(["command", 1], "/root/repo/benchmark/run.py"), "command"),
    "command outside paths": (edit(["command", 1], "bench.py"), "outside paths"),
    "paths leading out": (edit(["paths"], ["../benchmark"]), "paths"),
    "metric without a reader": (edit(["per_layer", 0, "name"], "loadgen.nothing"), "no reader"),
    "same name twice": (edit(["per_layer", 1, "name"], "loadgen.lag_ms_p99"), "twice"),
}


def test_depth_is_no_width():
    m = copy.deepcopy(manifest())
    m["configs"][0]["reduced"] = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert faults(m) == []


@pytest.mark.parametrize("case", sorted(CASES))
def test_rule_refuses(case):
    change, expect = CASES[case]
    m = copy.deepcopy(manifest())
    change(m)
    found = faults(m)
    assert any(expect in line for line in found), found


def test_file_over_64_kib():
    assert any("bytes" in line for line in vm.validate(manifest(), REPO, raw_bytes=70000))
