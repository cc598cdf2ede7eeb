"""The cell `xing-latent-docs`: its arithmetic (`opsbytes_mhc_mla_moe.py`) by
hand at the published sizes, its two readers on a hand-made run, its files in
the manifest and against the program's preset, the reference against the
program at `tiny-xing4`, the whole cell rehearsed on the CPU, and its 8-bit
control failing a reference check there."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_mhc_mla_moe as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kernels.mhc_mla_moe_decode_roofline", "kernels.mhc_mla_moe_prefill_roofline")
CELL = "xing-latent-docs"
FILE = os.path.join(REPO, "benchmark", "configs", "xing4.0-29b-a4b-1chip.json")


def config():
    with open(FILE) as f:
        return json.load(f)


# by hand, as ISSUE 55 writes them down (parameters)
MIXER = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 4096 * 3584   # 28 409 856
MIXER_NORMS = 768 + 512
MAPS = 14336 * 24 + 24 + 3                                                       # 344 091 a sublayer
NORMS = 2 * 3584
MLP = 3 * 3584 * 9216                                                            # 99 090 432
EXPERT = 3 * 3584 * 1024                                                         # 11 010 048
ROUTER = 3584 * 64
HEAD = 3584 * 131072
LATENT = (512 + 64) * 2                                                          # 1 152 B a token and layer
STREAM = 2 * 2 * 4 * 3584 * 2                                                    # a layer: read + written, twice


def test_the_counts_of_the_issue():
    s = ob.sizes(config())
    assert s["mixer_macs"] == MIXER == 28_409_856 and MIXER + MIXER_NORMS == 28_411_136
    assert s["stream_map_params"] == MAPS == 344_091 and 2 * MAPS == 688_182
    assert s["stream_map_macs"] == 14336 * 24
    assert s["dense_mlp"] == MLP == 99_090_432 and s["expert"] == s["shared"] == EXPERT == 11_010_048
    assert 64 * EXPERT == 704_643_072 and s["router"] + s["router_bias"] == ROUTER + 64 == 229_440
    assert (s["layers"], s["dense_layers"], s["sparse_layers"]) == (6, 1, 5)
    dense = MIXER + MIXER_NORMS + NORMS + 2 * MAPS + MLP
    sparse = MIXER + MIXER_NORMS + NORMS + 2 * MAPS + ROUTER + 64 + 65 * EXPERT
    assert ob.layer_params(s, False, 0) == dense == 128_196_918
    assert ob.layer_params(s, True, 64) == sparse == 744_989_046
    assert s["embed"] + s["head"] == 2 * HEAD == 939_524_096
    assert ob.held_params(config()) == dense + 5 * sparse + 2 * HEAD + 3584 == 4_792_669_828
    assert ob.held_params(config()) * 2 == pytest.approx(9.585e9, rel=1e-4)
    assert s["cache_bytes_per_token"] == 6 * LATENT == 6912
    assert 16 * 16384 * 6912 == 1_811_939_328
    assert s["stream_bytes_per_token"] == 6 * STREAM == 6 * 114_688
    published = dict(config(), **config()["published"])        # the model as published: 2 + 38 layers
    assert ob.held_params(published) == 2 * dense + 38 * sparse + 2 * HEAD + 3584 == 29_505_505_264


def test_a_step_of_16_sessions_of_7000_tokens_by_hand():
    c = config()
    touched = 5 * 41.2          # 16 rows x 4 of 64 reach some 41 experts a sparse layer
    work = ob.decode_step(c, 16, 16 * 7000, touched)
    weights = (6 * (MIXER + MIXER_NORMS + NORMS + 2 * MAPS) + MLP + 5 * (ROUTER + 64 + EXPERT)
               + touched * EXPERT + HEAD)
    assert work["bytes"] == pytest.approx(2 * weights + 6 * LATENT * 16 * 7000 + 6 * STREAM * 16)
    assert 2 * (weights - touched * EXPERT) == pytest.approx(1.60e9, rel=1e-2)   # all but the routed experts
    assert 2 * touched * EXPERT == pytest.approx(4.54e9, rel=1e-2)
    assert 6 * LATENT * 16 * 7000 == pytest.approx(0.774e9, rel=1e-2)
    macs = 6 * (MIXER + 2 * 14336 * 24) + MLP + 5 * (ROUTER + EXPERT + 4 * EXPERT)
    assert work["flops"] == pytest.approx(2 * (macs + HEAD) * 16 + 2 * 6 * 32 * (2 * 512 + 64) * 16 * 7000)
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(8.45e-3, rel=0.03)


def test_a_prompt_of_6144_tokens_by_hand():
    c = config()
    work = ob.prefill(c, 6144)
    macs = 6 * (MIXER + 2 * 14336 * 24) + MLP + 5 * (ROUTER + EXPERT + 4 * EXPERT)
    assert work["flops"] == 2 * macs * 6144 + 2 * HEAD + 2 * 6 * 32 * (128 + 64 + 128) * 6144 * 6144 / 2
    weights = (6 * (MIXER + MIXER_NORMS + NORMS + 2 * MAPS) + MLP + 5 * (ROUTER + 64 + 65 * EXPERT) + HEAD)
    assert work["bytes"] == 2 * weights + (6 * LATENT + 6 * STREAM) * 6144
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "compute" and least["seconds"] == pytest.approx(0.0465, rel=0.05)


def stream_run():
    run = a_run()
    run["config"] = config()
    run["stats0"]["executor"]["moe"] = {"steps": 1000, "experts_touched": 100_000}
    run["stats1"]["executor"]["moe"] = {"steps": 1200, "experts_touched": 140_000}
    run["stats1"]["model"] = {"name": "xing4.0-29b-a4b-6l", "block_length": 1, "stream_width": 4}
    return run


def test_the_two_readers_read_by_hand():
    run = stream_run()
    # a_run: two sessions decoding at the window's middle with 300 + 117 and 300 + 116 tokens,
    # 300 tokens over 200 steps, 40 000 experts touched over 200 routed steps
    work = ob.decode_step(run["config"], 1.5, 417 + 416, 200.0)
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader(NEW[0])(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    assert harness.load_reader(NEW[1])(run) == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW:
        assert 0 < harness.load_reader(metric)(run) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counter_gives_nothing_and_does_not_raise(metric):
    """The parent commit cannot run this configuration: its line leaves the
    metric out, and so does a run of a program that reports no stream."""
    run = a_run()
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    no_stream = stream_run()
    del no_stream["stats1"]["model"]["stream_width"]
    assert harness.load_reader(metric)(no_stream) is None
    assert harness.load_reader(metric)(dict(stream_run(), rehearse=True)) is None
    bare = stream_run()
    bare["trace"]["modules"] = {}
    assert harness.load_reader(metric)(bare) is None


def test_the_cell_is_in_the_manifest_with_its_files_and_the_preset_is_the_file():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    assert len(m["configs"]) >= 9 and len(m["workloads"]) >= 10
    assert m["configs"][8]["name"] == "xing4.0-29b-a4b-1chip" and m["workloads"][9]["name"] == CELL
    assert sum(w["chips"] == 4 for w in m["workloads"][:10]) == 1
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1
    assert loaded["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    mix = loaded["mix"]
    assert (mix["kind"], mix["clients"], mix["lead_in_s"], mix["pool"]) == ("closed", "slots", 16, 32)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 6144, "sigma": 0.4, "min": 3072, "max": 12288}
    assert mix["output_len"] == {"dist": "uniform", "min": 128, "max": 384}
    names = {x["name"] for x in loaded["per_layer"]}
    assert names >= set(NEW) | {"kv.bytes_per_token", "moe.load_imbalance", "moe.multiplied_fill_share",
                                "window.device_ms_p50", "window.turn_ms_p50", "window.ahead_claimed_share",
                                "device.hbm_peak_share", "loadgen.gap_ms_p95", "loadgen.ttft_ms_p50",
                                "node.token_host_ms_p50", "window.device_sampled_share",
                                "node.record_builds_per_step", "node.admission_ms_p50"}
    assert not names & {"engine.slab_read_share", "kernels.mla_moe_decode_roofline", "moe.held_share",
                        "kv.ring_bytes_per_session", "kv.state_bytes_per_session", "kernels.decode_roofline",
                        "window.prefill_device_ms_p50"}
    assert [x["name"] for x in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    for x in m["per_layer"]:
        if x["name"] in NEW:
            assert x["workloads"] == [CELL] and x["moves"] == "out_tok_s" and x["layer"] == "kernels"
            assert (x["unit"], x["better"], x["source"]) == ("%", "higher", "device_trace")
    from inferd_tpu.config import get_config
    file, cfg = loaded["config"], get_config(loaded["config"]["preset"])
    harness.check_preset(file, loaded["reduced"], cfg)      # every reduced key is compared
    assert set(loaded["reduced"]) <= set(file["preset_check"])
    assert set(file["preset_check"]) >= {
        "hidden_size", "intermediate_size", "moe_intermediate_size", "kv_lora_rank", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "n_routed_experts",
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_max", "seeded_routed_scale",
        "vocab_size", "num_attention_heads", "router_mode", "rope_scaling.mscale_all_dim"}
    assert file["mhc_h_res_clamp_min"] == -file["mhc_h_res_clamp_max"] == -cfg.hc_res_clamp  # one clamp, +-
    assert file["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2}
    assert (file["weights_seed"], file["preset"], file["reference"]) == (55, "xing4.0-29b-a4b-6l", "xing4")
    assert file["node_flags"] == ["--batch-lanes", "16", "--max-len", "16384"]
    assert file["trace_modules"] == {"decode": "^jit__decode_logits", "prefill": "^jit__prefill_lane_logits"}
    assert harness.probe_sizes(file, file["node_flags"]) == (4608, 64)       # nine chunks of 512, then 64
    assert harness.probe_sizes(file, file["rehearse"]["node_flags"]) == (4608, 64)
    assert harness.reference_script(file).endswith("references/xing4.py")
    pool = __import__("traffic").size_pool(mix)
    assert max(n + out for n, out in pool) <= 12288 + 384 == 12672 < 16384
    for key, other in (("num_hidden_layers", 40), ("first_k_dense_replace", 2), ("hc_mult", 2),
                       ("hc_sinkhorn_iters", 10), ("hc_eps", 1e-5), ("mhc_h_res_clamp_max", 20),
                       ("seeded_routed_scale", 1.0), ("q_lora_rank", 1536), ("kv_lora_rank", 256),
                       ("hidden_size", 4096), ("n_routed_experts", 32), ("num_experts_per_tok", 6),
                       ("router_mode", "softmax_topk"), ("routed_scaling_factor", 1.0),
                       ("vocab_size", 65536), ("tie_word_embeddings", True)):
        wrong = copy.deepcopy(file)
        wrong[key] = other
        with pytest.raises(harness.Refused, match=key):
            harness.check_preset(wrong, loaded["reduced"], cfg)


def test_the_catalogs_published_keys_are_all_in_the_file_but_the_reduced_ones():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
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
    `split_model --random-init` writes (the stream's maps, the compressed
    query, no q_proj), the rehearsal's copy of the file, the reference as a
    script, against the program's own cache-free forward."""
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    parts = str(tmp_path / "parts")
    subprocess.run([sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", "tiny-xing4",
                    "--stages", "1", "--random-init", "--seed", "55", "--device", "cpu", "--out", parts],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    cfg = get_config("tiny-xing4")
    file = harness.rehearsal_config(config(), cfg, str(tmp_path / "config.json"))
    prompt, more = [t % cfg.vocab_size for t in range(3, 103)], [7, 9, 11]
    out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, harness.reference_script(config()), "--ckpt",
                    os.path.join(parts, "stage_000.msgpack"), "--model", "tiny-xing4", "--config", file,
                    "--device", "cpu", "--prompt-ids", ",".join(map(str, prompt)),
                    "--continue-ids", ",".join(map(str, more)), "--out", out],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    ref = np.load(out)
    assert ref.shape == (4, cfg.vocab_size)
    params, _, _ = load_stage_checkpoint(os.path.join(parts, "stage_000.msgpack"))
    assert {"q_a_proj", "q_b_proj", "hc_attn_proj", "hc_ffn_scale"} <= set(params["layers"])
    assert "q_proj" not in params["layers"] and "router" not in params["dense_layers"]
    with jax.default_matmul_precision("highest"):
        logits, _, _ = qwen3.forward(jax.tree.map(jnp.asarray, params), cfg, jnp.asarray([prompt + more]))
    got = np.asarray(jax.nn.log_softmax(logits[0, len(prompt) - 1:], axis=-1))
    np.testing.assert_allclose(got, ref, atol=2e-5)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-xing4` on the CPU: float32 on both sides, so
    the node (a probe of 4 608 tokens in nine chunks of expanded attention
    over the lane, then absorbed decode) and the reference's one forward pass
    agree to 2e-5."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "12", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=1200, cwd=REPO)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]   # a rehearsal is never `correct`
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["kv.bytes_per_token"]["value"] == 4 * (32 + 8) * 4   # four layers of latents, float32
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.device_sampled_share"]["value"] == 100.0
    assert metrics["moe.multiplied_fill_share"]["value"] == 25.0       # 8 experts top 2: the dense product
    assert "moe.load_imbalance" in metrics
    assert not set(NEW) & set(metrics)   # device numbers: none on a CPU
    assert "engine.slab_read_share" not in metrics
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        assert float(line.split("log-probabilities ")[1].split(" ")[0]) < 2e-5
    assert "FAIL" not in out.stdout


def test_the_8_bit_control_fails_a_reference_check(tmp_path):
    """The control of `correct`, at a size a test can hold: the cell served
    with its latents held in 8 bits (`--kv-dtype float8_e4m3fn`; `--quant` is
    refused for a latent model) is not correct at the limit a float32
    rehearsal is held to, and the line that says so names a reference check.
    On the chip at the cell's own size: `benchmark/control.py`, PERF.md
    section 4."""
    from test_add_by_files import copy_of_the_benchmark
    from test_last_line import rehearse

    root = str(tmp_path)
    copy_of_the_benchmark(root)
    c = config()
    c["rehearse"] = dict(c["rehearse"],
                         node_flags=c["rehearse"]["node_flags"] + ["--kv-dtype", "float8_e4m3fn"])
    c["logprob_tolerance"] = {"value": 1e-4, "why": "float32 both sides reads 2e-7"}
    with open(os.path.join(root, "benchmark/configs/xing4.0-29b-a4b-1chip.json"), "w") as f:
        json.dump(c, f)
    done = rehearse(root, CELL, 0, seconds="3")
    assert done.returncode == 1, done.stdout[-3000:] + done.stderr[-2000:]
    failed = [x.split()[2].rstrip(":") for x in done.stdout.splitlines() if "] FAIL " in x]
    assert failed and set(failed) <= {"probe_reference", "probe_decode_reference"}, done.stdout[-3000:]
