"""The cell `trinl-window-docs`: its arithmetic (`opsbytes_swa_moe.py`) by
hand, its four readers on a hand-made run, its files in the manifest and
against the program's preset, the reference against the program at
`tiny-afmoe`, and the whole cell rehearsed on the CPU."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_swa_moe as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kernels.swa_moe_decode_roofline", "kernels.swa_moe_prefill_roofline", "moe.held_share",
       "kv.ring_bytes_per_session")
CELL = "trinl-window-docs"


def config():
    with open(os.path.join(REPO, "benchmark", "configs", "trinity-large-ep8-1chip.json")) as f:
        return json.load(f)


# by hand, as ISSUE 44 writes them down (parameters)
ATTN = 3 * 3072 * 6144 + 2 * 3072 * 1024 + 2 * 128      # q, gate, o; k, v; two head norms: 62 914 816
NORMS = 4 * 3072
DENSE_MLP = 3 * 3072 * 12288                            # 113 246 208
EXPERT = 3 * 3072 * 3072                                # 28 311 552
ROUTER = 3072 * 256
HEAD = 3072 * 25024
KV = 2 * 8 * 128 * 2                                    # bytes a token and layer


def test_the_counts_of_the_issue():
    s = ob.sizes(config())
    assert s["attn_params"] == ATTN == 62_914_816 and s["norm_params"] == NORMS
    assert ATTN + NORMS + DENSE_MLP == 176_173_312                       # the dense layer
    assert ATTN + NORMS + ROUTER + EXPERT + 32 * EXPERT == 997_994_752   # a sparse layer
    assert s["embed_head"] == 2 * HEAD + 3072 == 153_750_528
    assert ob.weight_params(s) == 176_173_312 + 4 * 997_994_752 + 153_750_528 == 4_321_902_848
    assert ob.weight_params(s) * 2 == pytest.approx(8.64e9, rel=1e-3)
    assert (s["windowed_layers"], s["full_layers"], s["dense_layers"], s["sparse_layers"]) == (4, 1, 1, 4)
    assert s["kv_bytes_per_token_layer"] == KV == 4096
    assert ob.ring_bytes_per_session(config()) == 4 * 4160 * 4096 == 68_157_440
    # 16 lanes x 16 384: rings and one slab, against five slabs
    assert 16 * (68_157_440 + 16384 * 4096) == 2_164_260_864
    assert 16 * 5 * 16384 * 4096 == pytest.approx(5.37e9, rel=1e-3)


def test_a_session_of_6000_tokens_by_hand():
    """The windowed layers read their last 4 096 tokens, the full layer all
    6 000; a step touches 7 of the 32 held experts in each of 4 layers and
    its 16 rows made 8 assignments to held experts."""
    c = config()
    s = ob.sizes(c)
    assert ob.visible_tokens(s, [6000]) == 4 * 4096 + 6000 == 22_384
    assert ob.visible_tokens(s, [1000]) == 5 * 1000
    work = ob.decode_step(c, [6000] * 16, held_touched=28, held_assignments=8)
    weights = 5 * (ATTN + NORMS) + DENSE_MLP + 4 * (ROUTER + EXPERT) + 28 * EXPERT + HEAD
    assert work["bytes"] == 2 * weights + 4096 * 16 * 22_384
    assert 2 * weights == pytest.approx(2.83e9, rel=1e-2) and 4096 * 16 * 22_384 == pytest.approx(1.47e9, rel=1e-2)
    per_row = 5 * ATTN + DENSE_MLP + 4 * (ROUTER + EXPERT) + HEAD
    assert work["flops"] == 2 * per_row * 16 + 2 * EXPERT * 8 + 4 * 6144 * 16 * 22_384
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(5.24e-3, rel=1e-2)
    # what the program reads as it stands: every held expert, whichever were chosen
    all_held = ob.decode_step(c, [6000] * 16, held_touched=128, held_assignments=8)
    assert all_held["bytes"] - work["bytes"] == 2 * 100 * EXPERT


def test_a_prompt_of_5120_tokens_by_hand():
    c = config()
    work = ob.prefill(c, 5120)
    macs = 5 * ATTN + DENSE_MLP + 4 * (ROUTER + EXPERT + 0.5 * EXPERT)   # 4 of 256 chosen, 32 held: 0.5 a layer
    pairs = 5120 * 5120 / 2 + 4 * (4096 * 4096 / 2 + 1024 * 4096)
    assert work["flops"] == 2 * macs * 5120 + 2 * HEAD + 4 * 6144 * pairs
    assert work["bytes"] == 2 * 4_321_902_848 + 5 * 4096 * 5120
    assert opsbytes.least_time_s(work, "TPU v5 lite")["bound"] == "compute"
    short = ob.prefill(c, 2000)  # inside the window every layer is causal and no more
    assert short["flops"] == 2 * macs * 2000 + 2 * HEAD + 4 * 6144 * 5 * 2000 * 2000 / 2


def swa_run():
    run = a_run()
    run["config"] = config()
    run["stats0"]["executor"]["moe"] = dict(
        steps=1000, assignments=100_000, assignments_here=12_000, experts_touched_here=20_000)
    run["stats1"]["executor"].update(
        moe=dict(steps=1200, assignments=100_000 + 200 * 24, assignments_here=12_000 + 640,
                 experts_touched_here=20_000 + 200 * 5, experts=256, experts_held=32),
        kv={"window": 4096, "ring_bytes_per_session": 68_157_440})
    return run


def test_the_four_readers_read_by_hand():
    run = swa_run()
    assert harness.load_reader("kv.ring_bytes_per_session")(run) == 68_157_440
    assert harness.load_reader("moe.held_share")(run) == pytest.approx(100 * 640 / 4800)
    # a_run: two sessions decoding at the window's middle with 300 + 117 and 300 + 116 tokens;
    # 200 routed steps touched 5 held experts each and made 3.2 assignments to them
    work = ob.decode_step(run["config"], [417, 416], 5.0, 3.2)
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader("kernels.swa_moe_decode_roofline")(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    value = harness.load_reader("kernels.swa_moe_prefill_roofline")(run)
    assert value == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW[:2]:
        assert 0 < harness.load_reader(metric)(run) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(metric):
    """The parent commit holds no share and no ring here: its line leaves
    the metric out."""
    run = a_run()
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    run["stats1"]["executor"]["moe"] = dict(steps=5, assignments=40, experts=64)  # a model with every expert
    run["stats0"]["executor"]["moe"] = dict(steps=0, assignments=0)
    assert harness.load_reader(metric)(run) is None
    if metric.startswith("kernels."):
        assert harness.load_reader(metric)(dict(swa_run(), rehearse=True)) is None
        bare = swa_run()
        bare["trace"]["modules"] = {}
        assert harness.load_reader(metric)(bare) is None


def test_the_cell_is_in_the_manifest_with_its_files_and_the_preset_is_the_file():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    mix = loaded["mix"]
    assert (mix["kind"], mix["clients"], mix["lead_in_s"], mix["pool"]) == ("closed", "slots", 16, 32)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 5120, "sigma": 0.5, "min": 1024, "max": 12288}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    names = {x["name"] for x in loaded["per_layer"]}
    assert names >= set(NEW) | {"kv.bytes_per_token", "moe.load_imbalance", "window.device_ms_p50",
                                "window.turn_ms_p50", "loadgen.gap_ms_p95", "device.hbm_peak_share"}
    assert not names & {"kernels.decode_roofline", "kernels.mla_moe_decode_roofline", "kv.state_bytes_per_session"}
    assert [x["name"] for x in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    for x in m["per_layer"]:
        if x["name"] in NEW:
            assert x["workloads"] == [CELL]
    from inferd_tpu.config import get_config
    file, cfg = loaded["config"], get_config(loaded["config"]["preset"])
    harness.check_preset(file, loaded["reduced"], cfg)      # every reduced key is compared
    assert set(loaded["reduced"]) <= set(file["preset_check"])
    assert file["published"] == {"num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
                                 "vocab_size": 200192}
    assert (file["num_experts"], file["router_experts"], file["num_experts_per_tok"]) == (32, 256, 4)
    kinds = {"sliding_attention": "sliding", "full_attention": "global"}
    assert [kinds[k] for k in file["layer_types"][:5]] == file["layer_kinds"] == cfg.layer_type_names
    assert harness.probe_sizes(file, file["node_flags"]) == (4608, 16)
    assert harness.probe_sizes(file, file["rehearse"]["node_flags"]) == (4608, 16)
    assert harness.reference_script(file).endswith("references/afmoe.py")
    pool = __import__("traffic").size_pool(mix)
    assert max(n + out for n, out in pool) <= 12288 + 512 < 16384
    assert sum(1 for n, _ in pool if n < 4096) == 10   # a third decode inside the window
    for key, other in (("num_experts", 256), ("router_experts", 32), ("vocab_size", 200192),
                       ("num_hidden_layers", 60), ("num_dense_layers", 6), ("sliding_window", 2048),
                       ("route_scale", 1.0), ("layer_kinds", ["global"] * 5), ("attn_gate", False)):
        wrong = copy.deepcopy(file)
        wrong[key] = other
        with pytest.raises(harness.Refused, match=key):
            harness.check_preset(wrong, loaded["reduced"], cfg)
    with pytest.raises(harness.Refused, match="does not compare"):
        harness.check_preset({**file, "preset_check": {k: v for k, v in file["preset_check"].items()
                                                       if k != "num_experts"}}, loaded["reduced"], cfg)


def test_the_catalogs_published_keys_are_all_in_the_file_but_the_reduced_ones():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Large-Preview")
    mine = config()
    assert mine["source"] == row["source_url"]
    differs = {k for k in row["config"] if mine.get(k, "(absent)") != row["config"][k]}
    assert differs == set(mine["reduced"])
    assert {k: row["config"][k] for k in differs} == mine["published"]


def test_the_reference_reads_what_the_program_serves_at_the_tiny_preset(tmp_path):
    """`run.py --rehearse`'s pieces without the node: the seeded checkpoint
    `split_model --random-init` writes, the rehearsal's copy of the file, the
    reference as a script, against the program's own cache-free forward."""
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    parts = str(tmp_path / "parts")
    subprocess.run([sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", "tiny-afmoe",
                    "--stages", "1", "--random-init", "--seed", "44", "--device", "cpu", "--out", parts],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    cfg = get_config("tiny-afmoe")
    file = harness.rehearsal_config(config(), cfg, str(tmp_path / "config.json"))
    prompt, more = [t % cfg.vocab_size for t in range(3, 103)], [7, 9, 11]
    out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, harness.reference_script(config()), "--ckpt",
                    os.path.join(parts, "stage_000.msgpack"), "--model", "tiny-afmoe", "--config", file,
                    "--device", "cpu", "--prompt-ids", ",".join(map(str, prompt)),
                    "--continue-ids", ",".join(map(str, more)), "--out", out],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    ref = np.load(out)
    assert ref.shape == (4, cfg.vocab_size)
    params, _, _ = load_stage_checkpoint(os.path.join(parts, "stage_000.msgpack"))
    with jax.default_matmul_precision("highest"):
        logits, _, _ = qwen3.forward(jax.tree.map(jnp.asarray, params), cfg, jnp.asarray([prompt + more]))
    got = np.asarray(jax.nn.log_softmax(logits[0, len(prompt) - 1:], axis=-1))
    np.testing.assert_allclose(got, ref, atol=5e-6)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-afmoe` on the CPU: float32 on both sides, so
    the node (a probe of 4 608 tokens in nine chunks through rings that wrap
    fifty times, then decode) and the reference agree to 1e-5."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "12", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]   # a rehearsal is never `correct`
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["kv.ring_bytes_per_session"]["value"] == 7 * 80 * 2 * 2 * 16 * 4   # seven windowed layers
    assert metrics["moe.held_share"]["value"] == 100.0     # the tiny preset holds every expert
    assert metrics["moe.load_imbalance"]["value"] >= 1.0
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.device_sampled_share"]["value"] == 100.0
    assert "kernels.swa_moe_decode_roofline" not in metrics   # a device number: none on a CPU
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        assert float(line.split("log-probabilities ")[1].split(" ")[0]) < 1e-5
    assert "FAIL" not in out.stdout
