"""The cell `nem3s-many-chat`: its arithmetic (`opsbytes_ssm_latent_moe.py`) by
hand at the published sizes, one M, one E, one * sublayer and the whole cut;
its two readers on a hand-made run; its files in the manifest and against the
program's preset and the catalog's row; the reference against the program at
`tiny-nemotron-h`, and the whole cell rehearsed on the CPU."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_ssm_latent_moe as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kernels.ssm_latent_moe_decode_roofline", "kernels.ssm_latent_moe_prefill_roofline")
CELL = "nem3s-many-chat"
FILE = os.path.join(REPO, "benchmark", "configs", "nemotron-3-super-120b-ep4-1chip.json")


def config():
    with open(FILE) as f:
        return json.load(f)


# by hand, as ISSUE 57 writes them down (parameters)
CONV = 8192 + 2 * 8 * 128                                                   # 10 240 channels
M_MATMUL = 4096 * (8192 + CONV + 128) + 8192 * 4096                         # W_in 76 021 760, W_out
M_SMALL = 4096 + 4 * CONV + CONV + 3 * 128 + 8192                           # norm, taps, bias, 3 vectors, gate norm
A_MATMUL = 2 * 4096 * 4096 + 2 * 4096 * 256
EXPERT = 2 * 1024 * 2688                                                    # TWO matrices: 5 505 024
E_MATMUL = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376                   # router, latent in and out, shared
E_SMALL = 4096 + 2 * 512                                                    # norm; the float32 bias
HEAD = 4096 * 32768
STATE = 128 * 64 * 128 * 4 + 3 * CONV * 2                                   # 4 255 744 B an M sublayer
KV = 2 * 2 * 128 * 2                                                        # bytes a token in the * sublayer


def test_the_counts_of_the_issue_one_sublayer_of_each_kind():
    s = ob.sizes(config())
    assert (s["m_layers"], s["e_layers"], s["a_layers"]) == (5, 5, 1)
    assert s["m_matmul"] == M_MATMUL == 76_021_760 + 33_554_432
    assert s["m_matmul"] + s["m_small"] == 109_640_064 == M_MATMUL + M_SMALL          # an M: 0.219 GB
    assert s["a_matmul"] + s["a_small"] == 35_655_680 == A_MATMUL + 4096              # a *: 0.071 GB
    assert s["expert"] == EXPERT and 128 * EXPERT == 704_643_072
    assert s["e_matmul"] + s["e_small"] + 128 * EXPERT == E_MATMUL + E_SMALL + 128 * EXPERT
    # the program's own E sublayer counts the float32 bias as 512 parameters, this file as 1 024 bf16
    assert E_MATMUL + E_SMALL + 128 * EXPERT == 759_173_632 + 512                     # an E: 1.518 GB
    assert ob.weight_params(s) == 4_648_163_712 + 5 * 512
    assert ob.weight_params(s) * 2 == pytest.approx(9.296e9, rel=1e-4)
    assert s["state_bytes_layer"] == STATE == 4_194_304 + 61_440
    assert ob.state_bytes_per_session(config()) == 21_278_720
    assert s["kv_bytes_per_token_layer"] == KV == 1024
    # 32 lanes x 4096: one slab a lane and five states
    assert 32 * (4096 * KV + 21_278_720) == 815_136_768


def test_a_step_of_32_sessions_of_400_tokens_by_hand():
    """A step touches 96 of the 128 held experts in each of 5 E sublayers and
    its 32 rows made 176 assignments to held experts (a quarter of 32 x 22)."""
    c = config()
    work = ob.decode_step(c, [400] * 32, held_touched=5 * 96, held_assignments=5 * 176)
    weights = (5 * (M_MATMUL + M_SMALL) + A_MATMUL + 4096 + 5 * (E_MATMUL + E_SMALL)
               + 480 * EXPERT + HEAD)
    seen = 32 * 400
    assert work["bytes"] == 2 * weights + KV * seen + 2 * 32 * 5 * STATE
    assert 2 * 480 * EXPERT == pytest.approx(5.28e9, rel=1e-2)              # the touched experts
    assert 2 * 32 * 5 * STATE == pytest.approx(1.36e9, rel=1e-2)            # the states, read and written
    assert 2 * 5 * M_MATMUL == pytest.approx(1.10e9, rel=1e-2) and 2 * 5 * E_MATMUL == pytest.approx(0.545e9, rel=1e-2)
    per_row = 5 * M_MATMUL + A_MATMUL + 5 * E_MATMUL + HEAD
    one_token = 5 * (5 * 128 * 64 * 128 + 2 * 4 * CONV)
    assert work["flops"] == ((2 * per_row + one_token) * 32 + 2 * EXPERT * 880 + 4 * 4096 * seen)
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(10.55e-3, rel=0.02)
    all_held = ob.decode_step(c, [400] * 32, held_touched=5 * 128, held_assignments=880)
    assert all_held["bytes"] - work["bytes"] == 2 * 5 * 32 * EXPERT


def test_a_prompt_of_512_tokens_by_hand():
    c = config()
    work = ob.prefill(c, 512)
    macs = 5 * M_MATMUL + A_MATMUL + 5 * (E_MATMUL + 5.5 * EXPERT)           # 22 of 512 chosen, 128 held
    chunked = 5 * 512 * (128 * (8 * 128 + 128 * 64) + 4 * 128 * 64 * 128 + 2 * 4 * CONV)
    assert work["flops"] == 2 * macs * 512 + 2 * HEAD + 4 * 4096 * 512 * 512 / 2 + chunked
    assert work["bytes"] == 2 * (ob.weight_params(ob.sizes(c)) - HEAD - 4096) + KV * 512 + 2 * 5 * STATE
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and work["flops"] == pytest.approx(1.05e12, rel=0.02)


def nem_run():
    run = a_run()
    run["config"] = config()
    run["stats0"]["executor"]["moe"] = dict(
        steps=1000, assignments=100_000, assignments_here=25_000, experts_touched_here=20_000)
    run["stats1"]["executor"].update(
        moe=dict(steps=1200, assignments=100_000 + 200 * 220, assignments_here=25_000 + 200 * 55,
                 experts_touched_here=20_000 + 200 * 50, experts=512, experts_held=128,
                 latent_size=1024),
        state_bytes=32 * 21_278_720, state_bytes_per_session=21_278_720)
    return run


def test_the_two_readers_read_by_hand():
    run = nem_run()
    # a_run: two sessions decoding at the window's middle with 300 + 117 and 300 + 116 tokens;
    # 200 routed steps touched 50 held experts each and made 55 assignments to them
    work = ob.decode_step(run["config"], [417, 416], 50.0, 55.0)
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader(NEW[0])(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    assert harness.load_reader(NEW[1])(run) == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW:
        assert 0 < harness.load_reader(metric)(run) < 100
    assert harness.load_reader("kv.state_bytes_per_session")(run) == 21_278_720
    assert harness.load_reader("moe.held_share")(run) == pytest.approx(100 * 11_000 / 44_000)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(metric):
    """The parent commit knows no latent and not this configuration: its line
    leaves the metric out."""
    run = a_run()
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    held = nem_run()
    del held["stats1"]["executor"]["moe"]["latent_size"]        # experts held at full width
    assert harness.load_reader(metric)(held) is None
    state = nem_run()
    del state["stats1"]["executor"]["state_bytes_per_session"]  # a latent, no recurrent state
    assert harness.load_reader(metric)(state) is None
    assert harness.load_reader(metric)(dict(nem_run(), rehearse=True)) is None
    bare = nem_run()
    bare["trace"]["modules"] = {}
    assert harness.load_reader(metric)(bare) is None


def test_the_cell_is_in_the_manifest_with_its_files_and_the_preset_is_the_file():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1 and loaded["cell"]["traffic"] == "many-chat"
    assert loaded["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size",
                                 "hybrid_override_pattern"]
    mix = loaded["mix"]
    assert (mix["kind"], mix["clients"], mix["lead_in_s"], mix["pool"]) == ("closed", "slots", 16, 64)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32, "max": 1024}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 384}
    names = {x["name"] for x in loaded["per_layer"]}
    assert names >= set(NEW) | {"kv.state_bytes_per_session", "kv.bytes_per_token", "moe.held_share",
                                "moe.load_imbalance", "moe.multiplied_fill_share", "window.turn_ms_p50",
                                "window.ahead_claimed_share", "device.hbm_peak_share",
                                "engine.slab_read_share"}
    assert not names & {"kernels.ssm_decode_roofline", "kernels.gdn_moe_decode_roofline",
                        "kv.ring_bytes_per_session", "kernels.decode_roofline"}
    assert [x["name"] for x in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    for x in m["per_layer"]:
        if x["name"] in NEW:
            assert x["workloads"] == [CELL] and x["moves"] == "out_tok_s"
    from inferd_tpu.config import get_config
    file, cfg = loaded["config"], get_config(loaded["config"]["preset"])
    harness.check_preset(file, loaded["reduced"], cfg)      # every reduced key is compared
    assert set(loaded["reduced"]) <= set(file["preset_check"])
    assert file["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512, "vocab_size": 131072,
        "hybrid_override_pattern": get_config("nemotron-3-super-120b-a12b").hybrid_override_pattern}
    assert (file["n_routed_experts"], file["router_experts"], file["num_experts_per_tok"]) == (128, 512, 22)
    assert file["hybrid_override_pattern"] == cfg.hybrid_override_pattern == "MEMEMEM*EME"
    assert harness.probe_sizes(file, file["node_flags"]) == (600, 16)
    assert harness.reference_script(file).endswith("references/nemotron-h.py")
    pool = __import__("traffic").size_pool(mix)
    assert max(n + out for n, out in pool) <= 1024 + 384 < 4096
    for key, other in (("n_routed_experts", 512), ("router_experts", 128), ("vocab_size", 131072),
                       ("num_hidden_layers", 88), ("hybrid_override_pattern", "MEMEMEMEMEM"),
                       ("moe_latent_size", 2048), ("n_groups", 1), ("mamba_num_heads", 64),
                       ("moe_shared_expert_intermediate_size", 2688), ("num_experts_per_tok", 8),
                       ("mlp_hidden_act", "silu"), ("routed_scaling_factor", 1.0), ("chunk_size", 256)):
        wrong = copy.deepcopy(file)
        wrong[key] = other
        with pytest.raises(harness.Refused, match=key):
            harness.check_preset(wrong, loaded["reduced"], cfg)


def test_the_catalogs_published_keys_are_all_in_the_file_but_the_reduced_ones():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
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
    `split_model --random-init` writes (three weight stacks), the rehearsal's
    copy of the file, the reference as a script, against the program's own
    cache-free forward."""
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    parts = str(tmp_path / "parts")
    subprocess.run([sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", "tiny-nemotron-h",
                    "--stages", "1", "--random-init", "--seed", "57", "--device", "cpu", "--out", parts],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    cfg = get_config("tiny-nemotron-h")
    file = harness.rehearsal_config(config(), cfg, str(tmp_path / "config.json"))
    prompt, more = [t % cfg.vocab_size for t in range(3, 103)], [7, 9, 11]
    out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, harness.reference_script(config()), "--ckpt",
                    os.path.join(parts, "stage_000.msgpack"), "--model", "tiny-nemotron-h", "--config", file,
                    "--device", "cpu", "--prompt-ids", ",".join(map(str, prompt)),
                    "--continue-ids", ",".join(map(str, more)), "--out", out],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    ref = np.load(out)
    assert ref.shape == (4, cfg.vocab_size)
    params, _, _ = load_stage_checkpoint(os.path.join(parts, "stage_000.msgpack"))
    assert {"latent_in_proj", "router", "post_norm"} <= set(params["ffn_layers"])
    assert "up_proj" not in params["state_layers"] and "gate_proj" not in params["ffn_layers"]
    with jax.default_matmul_precision("highest"):
        logits, _, _ = qwen3.forward(jax.tree.map(jnp.asarray, params), cfg, jnp.asarray([prompt + more]))
    got = np.asarray(jax.nn.log_softmax(logits[0, len(prompt) - 1:], axis=-1))
    np.testing.assert_allclose(got, ref, atol=5e-6)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-nemotron-h` on the CPU: float32 on both sides,
    so the node (a probe of 600 tokens in two chunks, the second padded to its
    bucket, then decode through the states and the slab at 32 lanes) and the
    reference's sequential scan agree to 1e-5."""
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
    # three M sublayers: a float32 state of 8 x 16 x 16 and three float32 columns of 256 channels
    assert metrics["kv.state_bytes_per_session"]["value"] == 3 * (8 * 16 * 16 * 4 + 3 * 256 * 4) == 33792
    assert metrics["kv.bytes_per_token"]["value"] == 2 * 2 * 16 * 4       # ONE * sublayer, float32
    assert 15.0 < metrics["moe.held_share"]["value"] < 35.0               # 4 of the router's 16
    assert metrics["moe.load_imbalance"]["value"] >= 1.0
    assert 0 < metrics["moe.multiplied_fill_share"]["value"] <= 100.0
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.device_sampled_share"]["value"] == 100.0
    assert not set(NEW) & set(metrics)   # device numbers: none on a CPU
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        assert float(line.split("log-probabilities ")[1].split(" ")[0]) < 1e-5
    assert "FAIL" not in out.stdout


def test_the_8_bit_control_fails_a_reference_check(tmp_path):
    """The control of `correct`, at a size a test can hold: the cell served
    through `--quant int8` (the three stacks' projections, the held experts,
    the shared expert, both latent projections) is not correct at the limit a
    float32 rehearsal is held to, and the line that says so names a reference
    check. On the chip at the cell's own size: `benchmark/control.py`,
    PERF.md section 4."""
    from test_add_by_files import copy_of_the_benchmark
    from test_last_line import rehearse

    root = str(tmp_path)
    copy_of_the_benchmark(root)
    c = config()
    c["rehearse"] = dict(c["rehearse"], node_flags=c["rehearse"]["node_flags"] + ["--quant", "int8"])
    c["logprob_tolerance"] = {"value": 1e-4, "why": "float32 both sides reads 2e-7"}
    with open(os.path.join(root, "benchmark/configs/nemotron-3-super-120b-ep4-1chip.json"), "w") as f:
        json.dump(c, f)
    done = rehearse(root, CELL, 0, seconds="3")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    failed = [x.split()[2].rstrip(":") for x in done.stdout.splitlines() if "] FAIL " in x]
    assert failed and set(failed) <= {"probe_reference", "probe_decode_reference"}, done.stdout[-3000:]
