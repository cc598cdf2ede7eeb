"""The cell `q3n-long-docs`: its arithmetic (`opsbytes_gdn_moe.py`) by hand at
the published sizes, its two readers on a hand-made run, its files in the
manifest and against the program's preset, the reference against the program
at `tiny-qwen3-next`, the whole cell rehearsed on the CPU, and its 8-bit
control failing a reference check there."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_gdn_moe as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kernels.gdn_moe_decode_roofline", "kernels.gdn_moe_prefill_roofline")
CELL = "q3n-long-docs"
FILE = os.path.join(REPO, "benchmark", "configs", "qwen3-next-80b-ep4-1chip.json")


def config():
    with open(FILE) as f:
        return json.load(f)


# by hand, as ISSUE 48 writes them down (parameters)
LINEAR = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 2 * 32 + 128 + 4096 * 2048   # 33 718 464
FULL = 2 * 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256             # 27 263 488
NORMS = 2 * 2048
ROUTER = 2048 * 512
EXPERT = 3 * 2048 * 512                                                     # 3 145 728
SHARED = EXPERT + 2048
HEAD = 2048 * 37984
STATE = 32 * 128 * 128 * 4 + 3 * 8192 * 2                                   # 2 146 304 B a linear layer
KV = 2 * 2 * 256 * 2                                                        # bytes a token and full layer


def test_the_counts_of_the_issue():
    s = ob.sizes(config())
    assert s["linear_mixer"] == LINEAR == 33_718_464 and s["full_mixer"] == FULL == 27_263_488
    assert ROUTER + SHARED + 128 * EXPERT == 406_849_536                    # an expert block
    assert (s["layers"], s["linear_layers"], s["full_layers"]) == (8, 6, 2)
    layers = 6 * LINEAR + 2 * FULL + 8 * (NORMS + 406_849_536)
    assert layers == 3_511_666_816 and s["embed_head"] == 2 * HEAD + 2048 == 155_584_512
    assert ob.weight_params(s) == 3_667_251_328
    assert ob.weight_params(s) * 2 == 7_334_502_656 == pytest.approx(7.33e9, rel=1e-3)
    assert s["state_bytes_layer"] == STATE == 2_097_152 + 49_152
    assert ob.state_bytes_per_session(config()) == 12_877_824
    assert s["kv_bytes_per_token_layer"] == KV == 2048
    # 16 lanes x 32 768: two slabs a lane and six states
    assert 16 * (2 * 32768 * KV + 12_877_824) == 2_353_528_832


def test_a_step_of_16_sessions_of_9000_tokens_by_hand():
    """A step touches 36 of the 128 held experts in each of 8 layers and its
    16 rows made 40 assignments to held experts (a quarter of 160)."""
    c = config()
    work = ob.decode_step(c, [9000] * 16, held_touched=8 * 36, held_assignments=8 * 40)
    weights = 6 * LINEAR + 2 * FULL + 8 * (NORMS + ROUTER + SHARED) + 288 * EXPERT + HEAD
    seen = 2 * 16 * 9000
    assert work["bytes"] == 2 * weights + KV * seen + 2 * 16 * 6 * STATE
    assert 2 * 16 * 6 * STATE == pytest.approx(0.412e9, rel=1e-2)           # the states, read and written
    assert KV * seen == pytest.approx(0.59e9, rel=1e-2)
    per_row = 6 * LINEAR + 2 * FULL + 8 * (ROUTER + SHARED) + HEAD
    assert work["flops"] == (2 * per_row * 16 + 2 * EXPERT * 320 + 4 * 4096 * seen
                             + 16 * 6 * 7 * 32 * 128 * 128)
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(4.2e-3, rel=0.05)
    all_held = ob.decode_step(c, [9000] * 16, held_touched=8 * 128, held_assignments=320)
    assert all_held["bytes"] - work["bytes"] == 2 * 8 * 92 * EXPERT


def test_a_prompt_of_8192_tokens_by_hand():
    c = config()
    work = ob.prefill(c, 8192)
    macs = 6 * LINEAR + 2 * FULL + 8 * (ROUTER + SHARED + 2.5 * EXPERT)     # 10 of 512 chosen, 128 held
    scan = 32 * (4 * 64 * 128 + 64 * 256 + 6 * 128 * 128 + 2 * 64 * 128)
    assert work["flops"] == (2 * macs * 8192 + 2 * HEAD + 4 * 4096 * 2 * 8192 * 8192 / 2
                             + 6 * scan * 8192)
    assert work["bytes"] == 2 * 3_667_251_328 + 2 * KV * 8192 + 2 * 6 * STATE
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(0.036, rel=0.1)


def gdn_run():
    run = a_run()
    run["config"] = config()
    run["stats0"]["executor"]["moe"] = dict(
        steps=1000, assignments=100_000, assignments_here=25_000, experts_touched_here=20_000)
    run["stats1"]["executor"].update(
        moe=dict(steps=1200, assignments=100_000 + 200 * 160, assignments_here=25_000 + 200 * 5,
                 experts_touched_here=20_000 + 200 * 4.5, experts=512, experts_held=128),
        state_bytes=16 * 12_877_824, state_bytes_per_session=12_877_824)
    return run


def test_the_two_readers_read_by_hand():
    run = gdn_run()
    # a_run: two sessions decoding at the window's middle with 300 + 117 and 300 + 116 tokens;
    # 200 routed steps touched 4.5 held experts each and made 5 assignments to them
    work = ob.decode_step(run["config"], [417, 416], 4.5, 5.0)
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader(NEW[0])(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    assert harness.load_reader(NEW[1])(run) == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW:
        assert 0 < harness.load_reader(metric)(run) < 100
    assert harness.load_reader("kv.state_bytes_per_session")(run) == 12_877_824
    assert harness.load_reader("moe.held_share")(run) == pytest.approx(100 * 1000 / 32000)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(metric):
    """The parent commit has neither a state beside experts nor this
    configuration: its line leaves the metric out."""
    run = a_run()
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    held = gdn_run()
    del held["stats1"]["executor"]["state_bytes_per_session"]   # experts held, no recurrent state
    assert harness.load_reader(metric)(held) is None
    state = gdn_run()
    del state["stats1"]["executor"]["moe"]                      # a state, no experts
    assert harness.load_reader(metric)(state) is None
    assert harness.load_reader(metric)(dict(gdn_run(), rehearse=True)) is None
    bare = gdn_run()
    bare["trace"]["modules"] = {}
    assert harness.load_reader(metric)(bare) is None


def test_the_cell_is_in_the_manifest_with_its_files_and_the_preset_is_the_file():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    mix = loaded["mix"]
    assert (mix["kind"], mix["clients"], mix["lead_in_s"], mix["pool"]) == ("closed", "slots", 16, 32)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 8192, "sigma": 0.4, "min": 2048, "max": 24576}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    names = {x["name"] for x in loaded["per_layer"]}
    assert names >= set(NEW) | {"kv.state_bytes_per_session", "kv.bytes_per_token", "moe.held_share",
                                "moe.load_imbalance", "moe.multiplied_fill_share", "window.turn_ms_p50",
                                "window.ahead_claimed_share", "device.hbm_peak_share"}
    assert not names & {"kernels.swa_moe_decode_roofline", "kernels.ssm_decode_roofline",
                        "kv.ring_bytes_per_session", "kernels.decode_roofline"}
    assert [x["name"] for x in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    for x in m["per_layer"]:
        if x["name"] in NEW:
            assert x["workloads"] == [CELL] and x["moves"] == "out_tok_s"
    from inferd_tpu.config import get_config
    file, cfg = loaded["config"], get_config(loaded["config"]["preset"])
    harness.check_preset(file, loaded["reduced"], cfg)      # every reduced key is compared
    assert set(loaded["reduced"]) <= set(file["preset_check"])
    assert set(file["preset_check"]) >= {
        "linear_conv_kernel_dim", "linear_key_head_dim", "linear_value_head_dim", "linear_num_key_heads",
        "linear_num_value_heads", "partial_rotary_factor", "full_attention_interval",
        "shared_expert_intermediate_size", "shared_expert_gate", "router_experts", "num_experts_per_tok",
        "norm_topk_prob"}
    assert file["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert (file["num_experts"], file["router_experts"], file["num_experts_per_tok"]) == (128, 512, 10)
    assert file["layer_kinds"] == cfg.layer_type_names
    assert harness.probe_sizes(file, file["node_flags"]) == (4608, 16)
    assert harness.probe_sizes(file, file["rehearse"]["node_flags"]) == (4608, 16)
    assert harness.reference_script(file).endswith("references/qwen3-next.py")
    pool = __import__("traffic").size_pool(mix)
    assert max(n + out for n, out in pool) <= 24576 + 512 == 25088 < 32768
    for key, other in (("num_experts", 512), ("router_experts", 128), ("vocab_size", 151936),
                       ("num_hidden_layers", 48), ("full_attention_interval", 2),
                       ("partial_rotary_factor", 1.0), ("linear_num_value_heads", 16),
                       ("shared_expert_intermediate_size", 1024), ("shared_expert_gate", False),
                       ("layer_kinds", ["attention"] * 8), ("num_experts_per_tok", 8)):
        wrong = copy.deepcopy(file)
        wrong[key] = other
        with pytest.raises(harness.Refused, match=key):
            harness.check_preset(wrong, loaded["reduced"], cfg)


def test_the_catalogs_published_keys_are_all_in_the_file_but_the_reduced_ones():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
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
    `split_model --random-init` writes (both weight stacks, the new
    parameters), the rehearsal's copy of the file, the reference as a script,
    against the program's own cache-free forward."""
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    parts = str(tmp_path / "parts")
    subprocess.run([sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", "tiny-qwen3-next",
                    "--stages", "1", "--random-init", "--seed", "48", "--device", "cpu", "--out", parts],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    cfg = get_config("tiny-qwen3-next")
    file = harness.rehearsal_config(config(), cfg, str(tmp_path / "config.json"))
    prompt, more = [t % cfg.vocab_size for t in range(3, 103)], [7, 9, 11]
    out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, harness.reference_script(config()), "--ckpt",
                    os.path.join(parts, "stage_000.msgpack"), "--model", "tiny-qwen3-next", "--config", file,
                    "--device", "cpu", "--prompt-ids", ",".join(map(str, prompt)),
                    "--continue-ids", ",".join(map(str, more)), "--out", out],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    ref = np.load(out)
    assert ref.shape == (4, cfg.vocab_size)
    params, _, _ = load_stage_checkpoint(os.path.join(parts, "stage_000.msgpack"))
    assert {"ba_proj", "shared_expert_gate", "router"} <= set(params["state_layers"])
    with jax.default_matmul_precision("highest"):
        logits, _, _ = qwen3.forward(jax.tree.map(jnp.asarray, params), cfg, jnp.asarray([prompt + more]))
    got = np.asarray(jax.nn.log_softmax(logits[0, len(prompt) - 1:], axis=-1))
    np.testing.assert_allclose(got, ref, atol=5e-6)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-qwen3-next` on the CPU: float32 on both sides,
    so the node (a probe of 4 608 tokens in nine chunks whose state crosses
    every chunk boundary, then decode through the cache) and the reference's
    sequential scan agree to 1e-5."""
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
    # six linear layers: a float32 state of 4 x 16 x 16 and three float32 columns of 128 channels
    assert metrics["kv.state_bytes_per_session"]["value"] == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 4) == 33792
    assert metrics["kv.bytes_per_token"]["value"] == 2 * 2 * 2 * 32 * 4   # two full layers, float32
    assert metrics["moe.held_share"]["value"] == 100.0     # the tiny preset holds every expert
    assert metrics["moe.load_imbalance"]["value"] >= 1.0
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.device_sampled_share"]["value"] == 100.0
    assert not set(NEW) & set(metrics)   # device numbers: none on a CPU
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        assert float(line.split("log-probabilities ")[1].split(" ")[0]) < 1e-5
    assert "FAIL" not in out.stdout


@pytest.mark.parametrize("flags", [["--quant", "int8"], ["--kv-dtype", "float8_e4m3fn"]],
                         ids=["weights-int8", "kv-fp8"])
def test_the_8_bit_control_fails_a_reference_check(tmp_path, flags):
    """The control of `correct`, at a size a test can hold: the cell served
    through one of the program's own 8-bit paths (`--quant int8`: both weight
    stacks, the held experts, the shared expert; `--kv-dtype float8_e4m3fn`:
    the two full layers' keys and values) is not correct at the limit a
    float32 rehearsal is held to, and the line that says so names a reference
    check. On the chip at the cell's own size int8 is the control that
    separates: `benchmark/control.py`, PERF.md section 4."""
    from test_add_by_files import copy_of_the_benchmark
    from test_last_line import rehearse

    root = str(tmp_path)
    copy_of_the_benchmark(root)
    c = config()
    c["rehearse"] = dict(c["rehearse"], node_flags=c["rehearse"]["node_flags"] + flags)
    c["logprob_tolerance"] = {"value": 1e-4, "why": "float32 both sides reads 3e-7"}
    with open(os.path.join(root, "benchmark/configs/qwen3-next-80b-ep4-1chip.json"), "w") as f:
        json.dump(c, f)
    done = rehearse(root, CELL, 0, seconds="3")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    failed = [x.split()[2].rstrip(":") for x in done.stdout.splitlines() if "] FAIL " in x]
    assert failed and set(failed) <= {"probe_reference", "probe_decode_reference"}, done.stdout[-3000:]
