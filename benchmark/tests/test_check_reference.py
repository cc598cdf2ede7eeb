"""The comparison that decides `probe_reference` and
`probe_decode_reference` (the mean difference over a check's positions, the
reference's argmax among the node's top at each), the size check and the
keys of what is kept in a checkout, on hand-made inputs: what each passes,
what each refuses, and which position a failure names."""

import types

import numpy as np
import pytest

import run as harness
from procs import Refused

V, M, TOP = 32, 6, 4


def a_probe(tmp_path, shift=None, tolerance=None):
    """A reference of M rows over V tokens and a node that agrees with it
    to 0.01 everywhere, but for `shift`: {position: (what, by)}."""
    rng = np.random.default_rng(28)
    ref = np.log(rng.dirichlet(np.ones(V), size=M)).astype(np.float32)
    tops = []
    for j, row in enumerate(ref):
        ids = [int(i) for i in np.argsort(-row)[:TOP]]
        lps = [float(row[i]) + 0.01 for i in ids]
        what, by = (shift or {}).get(j, (None, 0.0))
        if what == "logprob":  # every log-probability of the position
            lps = [lp + by for lp in lps]
        if what == "one":  # one of them
            lps[2] += by
        if what == "argmax":  # the node's top leaves out the reference's best
            ids = [int(i) for i in np.argsort(-row)[1:TOP + 1]]
            lps = [float(row[i]) for i in ids]
        tops.append([ids, lps])
    path = str(tmp_path / "ref.npy")
    np.save(path, ref)
    probe = {"tokens": [t[0][0] for t in tops], "tops": tops}
    return harness.check_reference(probe, path, tolerance or {"value": 0.1})


# the node is 0.01 off everywhere; a decode check reads 5 positions x 4 values, limit 0.1
CASES = {
    "all agree": (None, None, True, True, None),
    "the first token is off": ({0: ("logprob", 0.2)}, None, False, True, None),
    "a decoded position is off": ({4: ("logprob", -0.6)}, None, True, False, 4),
    "two are off, the worse is named": ({2: ("logprob", 0.3), 5: ("logprob", 0.5)}, None,
                                        True, False, 5),
    "one far value of twenty moves the mean little, and is named": (
        {3: ("one", 0.4)}, None, True, True, 3),
    "the reference's best is not in the node's top": ({3: ("argmax", 0)}, None, True, False, 3),
    "one limit decides both: a wider one passes both": (
        {0: ("logprob", 0.2), 4: ("logprob", 0.6)}, {"value": 0.3}, True, True, 4),
    "and a key `decode` beside it changes nothing": (
        {4: ("logprob", 0.6)}, {"value": 0.1, "decode": 0.3}, True, False, 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_check_reference(tmp_path, case):
    shift, tolerance, first_ok, decode_ok, position = CASES[case]
    got = a_probe(tmp_path, shift, tolerance)
    assert list(got) == ["probe_reference", "probe_decode_reference"]
    assert got["probe_reference"][0] is first_ok and got["probe_decode_reference"][0] is decode_ok
    assert " at position 0 of 0-0;" in got["probe_reference"][1]
    if position is not None:
        assert f" at position {position} of 1-{M - 1};" in got["probe_decode_reference"][1]


def test_rows_that_do_not_match_the_probe_fail_both(tmp_path):
    np.save(str(tmp_path / "ref.npy"), np.zeros((M - 1, V), np.float32))
    probe = {"tokens": [0] * M, "tops": [[[0], [0.0]]] * M}
    got = harness.check_reference(probe, str(tmp_path / "ref.npy"), {"value": 0.1})
    assert not got["probe_reference"][0] and not got["probe_decode_reference"][0]


PRESET = types.SimpleNamespace(name="p", hidden_size=64, num_layers=4, rope_theta=1e6)
FILE = {"hidden_size": 64, "num_hidden_layers": 4, "rope_parameters": {"rope_theta": 1e6},
        "preset_check": {"hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
                         "rope_parameters.rope_theta": "rope_theta"}}


def test_check_preset_follows_the_files_own_pairs():
    harness.check_preset(FILE, ["num_hidden_layers"], PRESET)


@pytest.mark.parametrize("change, reduced, said", [
    ({"num_hidden_layers": 8}, [], "num_hidden_layers"),
    ({"rope_parameters": {}}, [], "rope_parameters.rope_theta"),
    ({"rope_parameters": 5}, [], "rope_parameters.rope_theta"),
    ({}, ["vocab_size"], "vocab_size"),
    ({"preset_check": {"num_experts": "num_experts"}}, [], "num_experts"),
])
def test_check_preset_refuses(change, reduced, said):
    with pytest.raises(Refused, match=said):
        harness.check_preset(dict(FILE, **change), reduced, PRESET)


def test_without_pairs_of_its_own_a_file_is_held_to_the_ten():
    import json
    import os

    from conftest import REPO
    from inferd_tpu.config import get_config

    for name, preset in (("qwen3-4b-1chip", "qwen3-4b"), ("qwen3-8b-pp4", "qwen3-8b")):
        with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
            config = json.load(f)
        assert not {"reference", "preset_check", "probe"} & set(config)
        harness.check_preset(config, [], get_config(preset))
        with pytest.raises(Refused, match="intermediate_size"):
            harness.check_preset(dict(config, intermediate_size=1), [], get_config(preset))
        assert harness.probe_sizes(config, config["node_flags"]) == (64, 16)
        assert harness.reference_script(config) == os.path.join(REPO, "benchmark", "reference.py")


def test_a_rehearsal_reads_the_rehearsal_presets_sizes(tmp_path):
    import json

    small = types.SimpleNamespace(name="s", hidden_size=8, num_layers=2, rope_theta=1e4)
    with open(harness.rehearsal_config(FILE, small, str(tmp_path / "c.json"))) as f:
        out = json.load(f)
    assert (out["hidden_size"], out["num_hidden_layers"], out["rope_parameters"]) == (
        8, 2, {"rope_theta": 1e4})
    assert FILE["hidden_size"] == 64  # the file's own object is left alone


class FakeChildren:
    """Stands for `procs.Children`: counts the children and writes what
    the split and the reference would."""

    def __init__(self):
        self.ran = []

    def run(self, name, argv, timeout):
        self.ran.append(name)
        if "--out" in argv and name == "reference":
            np.save(argv[argv.index("--out") + 1], np.zeros((2, V), np.float32))


def test_reference_rows_are_made_anew_when_the_script_or_the_file_changes(tmp_path):
    """The cached rows are keyed by the tokens AND by the bytes of the
    reference and of the configuration's file: an edit to either in the same
    checkout is not decided against the old rows."""
    script, config_file = tmp_path / "ref.py", tmp_path / "c.json"
    script.write_text("# a reference\n")
    config_file.write_text("{}")
    parts = str(tmp_path / "home" / "parts")
    (tmp_path / "home").mkdir()
    children = FakeChildren()

    def rows(prompt=(1, 2, 3), more=(4,)):
        return harness.run_reference(str(script), "m", str(config_file), "cpu", children, parts,
                                     list(prompt), list(more))

    first = rows()
    assert rows() == first and children.ran == ["reference"]
    script.write_text("# a reference, edited\n")
    second = rows()
    config_file.write_text('{"num_hidden_layers": 2}')
    third = rows()
    assert len({first, second, third}) == 3 and children.ran == ["reference"] * 3
    assert rows(more=(5,)) not in (first, second, third)


def test_a_checkpoint_of_another_preset_is_made_anew(tmp_path):
    parts = str(tmp_path / "home" / "parts")
    children, config = FakeChildren(), {"weights_seed": 3}
    small = types.SimpleNamespace(name="s", hidden_size=8)
    harness.ensure_weights(config, "s", small, children, parts, {})
    harness.ensure_weights(config, "s", small, children, parts, {})
    assert children.ran == ["split"]
    small.hidden_size = 16
    harness.ensure_weights(config, "s", small, children, parts, {})
    harness.ensure_weights(dict(config, weights_seed=4), "s", small, children, parts, {})
    assert children.ran == ["split"] * 3
