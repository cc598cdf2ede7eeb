"""`engine.latent_read_share` (PR 56): of the slots a latent cache's lanes
hold in a layer, the share the window's decode steps and prefill chunks read;
deltas of two `/stats` snapshots, nothing where the program has no such
counter (the parent's latent node); and the manifest lists it, last, for the
two cells that serve a latent cache."""

import json
import os

import pytest

import run as harness
import validate_manifest
from conftest import REPO

NAME = "engine.latent_read_share"


def stats(**kv):
    return {"executor": {"kv_layout": "latent", "kv": kv}}


def test_it_reads_the_deltas_of_two_stats_snapshots():
    run = {"stats0": stats(slots_read=16 * 16384, slots_held=16 * 16384),  # the warm-up's whole lanes
           "stats1": stats(slots_read=16 * 16384 + 13 * 4096 + 256 * 16 * 12288,
                           slots_held=16 * 16384 + (13 + 256 * 16) * 16384)}
    want = 100 * (13 * 4096 + 256 * 16 * 12288) / ((13 + 256 * 16) * 16384)
    assert harness.load_reader(NAME)(run) == pytest.approx(want)
    assert 70 < want < 75.1


@pytest.mark.parametrize("stats1", [
    {"executor": {"kv_layout": "latent"}},  # the parent: a latent cache had no such counter
    {},  # no executor at all
    stats(slots_read=16 * 16384, slots_held=16 * 16384),  # no program ran in the window
])
def test_it_reads_nothing_where_there_is_nothing_to_read(stats1):
    run = {"stats0": stats(slots_read=16 * 16384, slots_held=16 * 16384), "stats1": stats1}
    assert harness.load_reader(NAME)(run) is None


def test_the_manifest_lists_it_for_the_two_latent_cells_and_leaves_the_dense_readers_list_alone():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert validate_manifest.validate(manifest, REPO) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert by_name[NAME] == {
        "name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "engine_programs", "moves": "out_tok_s",
        "workloads": ["dsv2l-long-chat", "xing-latent-docs"]}
    assert not set(by_name[NAME]["workloads"]) & set(by_name["engine.slab_read_share"]["workloads"])
