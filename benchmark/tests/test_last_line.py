"""`run.py --rehearse` as a subprocess, both --trace values: the last line
of standard output parses, has exactly the contract's keys, and nothing
follows it. (A rehearsal runs the tiny preset on the CPU backend and can
only end in `correct: false`, exit code 1.)"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(root, workload, trace, seconds="4", seed="3000000001"):
    env = dict(os.environ, BENCH_RUN="7")  # the driver sets it; the benchmark ignores it
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--workload", workload,
         "--seed", seed, "--seconds", seconds, "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )


def manifest_metrics(kind, workload):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    return {x["name"] for x in m[kind] if workload in x.get("workloads", [workload])}


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_is_the_contracts(trace):
    workload = "q4b-long-prompt"
    done = rehearse(REPO, workload, trace)
    assert done.returncode == 1, done.stderr[-2000:]
    assert done.stdout.endswith("\n") and not done.stdout.endswith("\n\n")
    lines = done.stdout.splitlines()
    last = json.loads(lines[-1])
    assert set(last) == KEYS | ({"breakdown"} if trace else set())
    assert last["correct"] is False  # a rehearsal is never a result
    assert isinstance(last["attempted"], int) and last["attempted"] > 0 and last["failed"] == 0
    assert set(last["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert last["device"]["platform"] == "cpu"
    for line in lines[:-1]:
        assert line.startswith("[bench] ")
    names = set(last["metrics"])
    if trace:
        assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in last["breakdown"].values())
        # a reader that finds nothing to read leaves its metric out
        assert names <= manifest_metrics("per_layer", workload) and "device.idle_share" in names
    else:
        assert names == manifest_metrics("end_to_end", workload)
    for v in last["metrics"].values():
        assert set(v) == {"value", "unit"} and isinstance(v["value"], (int, float))


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths`: another exit code than 0, and no result line."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    done = rehearse(str(tmp_path), "q4b-sat-chat", 0)
    assert done.returncode not in (0, 1)
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_unknown_workload_is_no_result():
    done = rehearse(REPO, "no-such-cell", 0)
    assert done.returncode not in (0, 1) and "{" not in done.stdout
