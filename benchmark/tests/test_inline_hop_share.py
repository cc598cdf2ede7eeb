"""`node.inline_hop_share` (PR 58): of the decode and block hops the executor
answered in the window, the share that held no worker thread; deltas of two
`/stats` snapshots, nothing where the program has no such counter (the
parent, the mesh executor) or no hop was answered; and the manifest lists it,
last, for the nine lane cells whose hops leave a `deliver` span (the mesh's
cell, the tenth of those, has no such counter: every hop takes a worker)."""

import json
import os

import pytest

import run as harness
import validate_manifest
from conftest import REPO

NAME = "node.inline_hop_share"


def stats(inline, pooled):
    return {"executor": {"hops_inline": inline, "hops_pooled": pooled, "batched_steps": 7}}


@pytest.mark.parametrize("stats0, stats1, want", [
    # 32 sessions' warm-up hops before the window; in it 6 000 hops, 40 of them a request's first
    (stats(310, 64), stats(310 + 5960, 64 + 40), 100 * 5960 / 6000),
    (stats(0, 500), stats(0, 900), 0.0),  # a node of kinds that all take a worker (`--paged-kv`)
    (stats(5, 0), stats(25, 0), 100.0),
])
def test_it_reads_the_deltas_of_two_stats_snapshots(stats0, stats1, want):
    assert harness.load_reader(NAME)({"stats0": stats0, "stats1": stats1}) == pytest.approx(want)


@pytest.mark.parametrize("stats1", [
    {"executor": {"batched_steps": 9, "ahead_claimed": 4}},  # the parent: no such counter
    {},  # no executor at all
    stats(310, 64),  # no hop was answered in the window
])
def test_it_reads_nothing_where_there_is_nothing_to_read(stats1):
    assert harness.load_reader(NAME)({"stats0": stats(310, 64), "stats1": stats1}) is None


def test_the_manifest_lists_it_last_for_the_cells_that_report_deliver():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert validate_manifest.validate(manifest, REPO) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert manifest["per_layer"][-1]["name"] == NAME
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "node_http", "moves": "out_tok_s",
        "workloads": [w for w in by_name["node.deliver_ms_p50"]["workloads"]
                      if w != "q8b-pp4-sat-chat"]}
    assert len(by_name[NAME]["workloads"]) == 9
