"""The cell `olmoh-reason-chat`: its arithmetic (`opsbytes_gdn_dense.py`) by
hand at the published sizes, its two readers on a hand-made run, its files in
the manifest and against the program's preset, the reference against the
program at `tiny-olmo-hybrid`, the whole cell rehearsed on the CPU, and its
8-bit control failing a reference check there."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_gdn_dense as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kernels.gdn_dense_decode_roofline", "kernels.gdn_dense_prefill_roofline")
CELL = "olmoh-reason-chat"
FILE = os.path.join(REPO, "benchmark", "configs", "olmo-hybrid-7b-1chip.json")


def config():
    with open(FILE) as f:
        return json.load(f)


# by hand, as ISSUE 51 writes them down (parameters)
LINEAR = 3840 * 17280 + 3840 * 60 + 4 * 11520 + 2 * 30 + 192 + 5760 * 3840   # 88 750 332
FULL = 4 * 3840 * 3840 + 2 * 3840                                            # 58 990 080
MLP = 3 * 3840 * 11008                                                       # 126 812 160
NORMS = 2 * 3840
HEAD = 3840 * 100352
STATE = 30 * 96 * 192 * 4 + 3 * 11520 * 2                                    # 2 280 960 B a linear layer
KV = 2 * 30 * 128 * 2                                                        # 15 360 B a token and full layer


def test_the_counts_of_the_issue():
    s = ob.sizes(config())
    assert s["linear_mixer"] == LINEAR == 88_750_332 and s["full_mixer"] == FULL == 58_990_080
    assert s["mlp"] == MLP == 126_812_160
    assert (s["layers"], s["linear_layers"], s["full_layers"]) == (16, 12, 4)
    assert LINEAR + MLP + NORMS == 215_570_172 and FULL + MLP + NORMS == 185_809_920
    assert ob.layer_params(s) == 3_330_081_744 and s["embed_head"] == 2 * HEAD + 3840 == 770_707_200
    assert ob.weight_params(s) == 4_100_788_944
    assert ob.weight_params(s) * 2 == 8_201_577_888 == pytest.approx(8.20e9, rel=1e-3)
    assert s["state_bytes_layer"] == STATE == 2_211_840 + 69_120
    assert ob.state_bytes_per_session(config()) == 27_371_520
    assert s["kv_bytes_per_token_layer"] == KV == 15_360
    # 16 lanes x 4096: four slabs a lane and twelve states
    assert 16 * (4 * 4096 * KV + 27_371_520) == 4_464_476_160


def test_a_step_of_16_sessions_of_2000_tokens_by_hand():
    c = config()
    work = ob.decode_step(c, [2000] * 16)
    weights = 12 * LINEAR + 4 * FULL + 16 * (NORMS + MLP) + HEAD
    seen = 4 * 16 * 2000
    assert work["bytes"] == 2 * weights + KV * seen + 2 * 16 * 12 * STATE
    assert 2 * 16 * 12 * STATE == pytest.approx(0.876e9, rel=1e-2)          # the states, read and written
    assert KV * seen == pytest.approx(1.97e9, rel=1e-2)
    assert 2 * weights == pytest.approx(7.43e9, rel=1e-2)
    assert work["flops"] == 2 * weights * 16 + 4 * 3840 * seen + 16 * 12 * 7 * 30 * 96 * 192
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(12.5e-3, rel=0.05)


def test_a_prompt_of_1024_tokens_by_hand():
    c = config()
    work = ob.prefill(c, 1024)
    macs = 12 * LINEAR + 4 * FULL + 16 * (NORMS + MLP)
    scan = 30 * (4 * 64 * 96 + 64 * (96 + 192) + 6 * 96 * 192 + 2 * 64 * 192)
    assert work["flops"] == 2 * macs * 1024 + 2 * HEAD + 4 * 3840 * 4 * 1024 * 1024 / 2 + 12 * scan * 1024
    assert work["bytes"] == 2 * (macs + HEAD) + 4 * KV * 1024 + 2 * 12 * STATE
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(0.035, rel=0.1)


def gdn_run():
    run = a_run()
    run["config"] = config()
    run["stats1"]["executor"].update(state_bytes=16 * 27_371_520, state_bytes_per_session=27_371_520)
    return run


def test_the_two_readers_read_by_hand():
    run = gdn_run()
    # a_run: two sessions decoding at the window's middle with 300 + 117 and 300 + 116 tokens
    work = ob.decode_step(run["config"], [417, 416])
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader(NEW[0])(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    assert harness.load_reader(NEW[1])(run) == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW:
        assert 0 < harness.load_reader(metric)(run) < 100
    assert harness.load_reader("kv.state_bytes_per_session")(run) == 27_371_520


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(metric):
    """The parent commit cannot run this configuration: its line leaves the
    metric out, and so does a run that holds no recurrent state."""
    run = a_run()
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    assert harness.load_reader(metric)(dict(gdn_run(), rehearse=True)) is None
    bare = gdn_run()
    bare["trace"]["modules"] = {}
    assert harness.load_reader(metric)(bare) is None


def test_the_cell_is_in_the_manifest_with_its_files_and_the_preset_is_the_file():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    assert len(m["configs"]) == 8 and len(m["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["reduced"] == ["num_hidden_layers"]
    mix = loaded["mix"]
    assert (mix["kind"], mix["clients"], mix["lead_in_s"], mix["pool"]) == ("closed", "slots", 32, 32)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64, "max": 1536}
    assert mix["output_len"] == {"dist": "uniform", "min": 512, "max": 2048}
    names = {x["name"] for x in loaded["per_layer"]}
    assert names >= set(NEW) | {"kv.state_bytes_per_session", "kv.bytes_per_token", "window.turn_ms_p50",
                                "window.ahead_claimed_share", "device.hbm_peak_share", "loadgen.gap_ms_p95",
                                "loadgen.ttft_ms_p50", "engine.slab_read_share", "node.token_host_ms_p50",
                                "window.device_ms_p50", "window.device_sampled_share"}
    assert not {n for n in names if n.startswith("moe.")}
    assert not names & {"kernels.gdn_moe_decode_roofline", "kernels.ssm_decode_roofline",
                        "kv.ring_bytes_per_session", "kernels.decode_roofline"}
    assert [x["name"] for x in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    for x in m["per_layer"]:
        if x["name"] in NEW:
            assert x["workloads"] == [CELL] and x["moves"] == "out_tok_s" and x["layer"] == "kernels"
    from inferd_tpu.config import get_config
    file, cfg = loaded["config"], get_config(loaded["config"]["preset"])
    harness.check_preset(file, loaded["reduced"], cfg)      # every reduced key is compared
    assert set(loaded["reduced"]) <= set(file["preset_check"])
    assert set(file["preset_check"]) >= {
        "linear_conv_kernel_dim", "linear_key_head_dim", "linear_value_head_dim", "linear_num_key_heads",
        "linear_num_value_heads", "linear_allow_neg_eigval", "norm_placement", "qk_norm",
        "position_embedding", "layer_kinds", "hidden_act", "attention_bias", "intermediate_size"}
    assert file["published"] == {"num_hidden_layers": 32}
    assert file["layer_kinds"] == cfg.layer_type_names
    assert len(file["layer_types"]) == 32      # the published list whole; the first 16 are served
    assert harness.probe_sizes(file, file["node_flags"]) == (1600, 16)
    assert harness.probe_sizes(file, file["rehearse"]["node_flags"]) == (1600, 16)
    assert harness.reference_script(file).endswith("references/olmo-hybrid.py")
    pool = __import__("traffic").size_pool(mix)
    assert max(n + out for n, out in pool) <= 1536 + 2048 == 3584 < 4096
    for key, other in (("num_hidden_layers", 32), ("linear_allow_neg_eigval", False),
                       ("norm_placement", "before"), ("qk_norm", "head"), ("position_embedding", "rope"),
                       ("linear_key_head_dim", 192), ("linear_value_head_dim", 96),
                       ("num_key_value_heads", 6), ("layer_kinds", ["attention"] * 16),
                       ("intermediate_size", 11264), ("tie_word_embeddings", True)):
        wrong = copy.deepcopy(file)
        wrong[key] = other
        with pytest.raises(harness.Refused, match=key):
            harness.check_preset(wrong, loaded["reduced"], cfg)


def test_the_catalogs_published_keys_are_all_in_the_file_but_the_reduced_ones():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    mine = config()
    assert mine["source"] == row["source_url"]
    differs = {k for k in row["config"] if mine.get(k, "(absent)") != row["config"][k]}
    assert differs == set(mine["reduced"])
    assert {k: row["config"][k] for k in differs} == mine["published"]


def test_the_reference_imports_nothing_of_the_program_but_the_checkpoint_reader():
    with open(harness.reference_script(config())) as f:
        src = f.read()
    lines = [x.strip() for x in src.splitlines() if "inferd_tpu" in x and "import" in x]
    assert lines == ["from inferd_tpu.parallel.stages import load_stage_checkpoint"]


def test_the_reference_reads_what_the_program_serves_at_the_tiny_preset(tmp_path):
    """`run.py --rehearse`'s pieces without the node: the seeded checkpoint
    `split_model --random-init` writes (both weight stacks, no input norm),
    the rehearsal's copy of the file, the reference as a script, against the
    program's own cache-free forward."""
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    parts = str(tmp_path / "parts")
    subprocess.run([sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", "tiny-olmo-hybrid",
                    "--stages", "1", "--random-init", "--seed", "51", "--device", "cpu", "--out", parts],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    cfg = get_config("tiny-olmo-hybrid")
    file = harness.rehearsal_config(config(), cfg, str(tmp_path / "config.json"))
    prompt, more = [t % cfg.vocab_size for t in range(3, 103)], [7, 9, 11]
    out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, harness.reference_script(config()), "--ckpt",
                    os.path.join(parts, "stage_000.msgpack"), "--model", "tiny-olmo-hybrid", "--config", file,
                    "--device", "cpu", "--prompt-ids", ",".join(map(str, prompt)),
                    "--continue-ids", ",".join(map(str, more)), "--out", out],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    ref = np.load(out)
    assert ref.shape == (4, cfg.vocab_size)
    params, _, _ = load_stage_checkpoint(os.path.join(parts, "stage_000.msgpack"))
    assert {"ba_proj", "post_ffn_norm"} <= set(params["state_layers"])
    assert "input_norm" not in params["state_layers"] and "input_norm" not in params["layers"]
    with jax.default_matmul_precision("highest"):
        logits, _, _ = qwen3.forward(jax.tree.map(jnp.asarray, params), cfg, jnp.asarray([prompt + more]))
    got = np.asarray(jax.nn.log_softmax(logits[0, len(prompt) - 1:], axis=-1))
    np.testing.assert_allclose(got, ref, atol=3e-5)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-olmo-hybrid` on the CPU: float32 on both
    sides, so the node (a probe of 1 600 tokens in four chunks whose state
    crosses every chunk boundary, then decode through the state as it is held
    and the rows read by their prefix) and the reference's sequential scan
    agree to 3e-5."""
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
    # six linear layers: a float32 state of 4 x 96 x 192 (held 2 x 96 x 384) and three
    # float32 columns of 1536 channels
    assert metrics["kv.state_bytes_per_session"]["value"] == 6 * (4 * 96 * 192 * 4 + 3 * 1536 * 4) == 1880064
    assert metrics["kv.bytes_per_token"]["value"] == 2 * 2 * 4 * 16 * 4   # two full layers, float32
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.device_sampled_share"]["value"] == 100.0
    assert 0 < metrics["engine.slab_read_share"]["value"] < 100   # lanes of 2048 slots: read by rung
    assert not set(NEW) & set(metrics)   # device numbers: none on a CPU
    assert not {n for n in metrics if n.startswith("moe.")}
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        assert float(line.split("log-probabilities ")[1].split(" ")[0]) < 3e-5
    assert "FAIL" not in out.stdout


def test_the_8_bit_control_fails_a_reference_check(tmp_path):
    """The control of `correct`, at a size a test can hold: the cell served
    through `--quant int8` (both weight stacks' projections, both MLPs, the
    head) is not correct at the limit a float32 rehearsal is held to, and the
    line that says so names a reference check. On the chip at the cell's own
    size: `benchmark/control.py`, PERF.md section 4."""
    from test_add_by_files import copy_of_the_benchmark
    from test_last_line import rehearse

    root = str(tmp_path)
    copy_of_the_benchmark(root)
    c = config()
    c["rehearse"] = dict(c["rehearse"], node_flags=c["rehearse"]["node_flags"] + ["--quant", "int8"])
    c["logprob_tolerance"] = {"value": 1e-4, "why": "float32 both sides reads 2e-6"}
    with open(os.path.join(root, "benchmark/configs/olmo-hybrid-7b-1chip.json"), "w") as f:
        json.dump(c, f)
    done = rehearse(root, CELL, 0, seconds="3")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    failed = [x.split()[2].rstrip(":") for x in done.stdout.splitlines() if "] FAIL " in x]
    assert failed and set(failed) <= {"probe_reference", "probe_decode_reference"}, done.stdout[-3000:]
