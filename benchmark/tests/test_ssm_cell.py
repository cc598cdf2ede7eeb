"""The cell `g4hm-many-chat`: its arithmetic (`opsbytes_ssm.py`) by hand for
one layer of each kind, its three readers on a hand-made run, its files in
the manifest, the reference against the program at `tiny-granite-h`, and the
whole cell rehearsed on the CPU."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_ssm as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kv.state_bytes_per_session", "kernels.ssm_decode_roofline", "kernels.ssm_prefill_roofline")
CELL = "g4hm-many-chat"


def config():
    with open(os.path.join(REPO, "benchmark", "configs", "granite-4.0-h-micro-1chip.json")) as f:
        return json.load(f)


# one layer of each kind, by hand (parameters), as ISSUE 42 writes them down
MLP = 3 * 2048 * 8192                                   # 50 331 648
ATTN = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048       # q, k, v, o: 10 485 760
W_IN, W_OUT = 2048 * (4096 + 4352 + 64), 4096 * 2048    # 17 432 576 and 8 388 608
CONV, SCALARS = 4352 * 4 + 4352, 3 * 64 + 4096          # 21 760 and 4 288
HEAD = 2048 * 100352
STATE = 64 * 64 * 128 * 4 + 3 * 4352 * 2                # a Mamba layer's state and columns, bytes


def test_the_counts_of_the_issue():
    s = ob.sizes(config())
    assert s["attn_matmul"] + s["attn_small"] == ATTN + MLP + 4096 == 60_821_504
    assert s["mamba_matmul"] + s["mamba_small"] == W_IN + W_OUT + CONV + SCALARS + MLP + 4096 == 76_182_976
    assert (s["attn_layers"], s["mamba_layers"]) == (4, 36)
    assert ob.weight_bytes(s) == 2 * (36 * 76_182_976 + 4 * 60_821_504 + HEAD + 2048)
    assert ob.weight_bytes(s) == pytest.approx(6.38e9, rel=1e-3)
    assert s["state_bytes_layer"] == STATE and ob.state_bytes_per_session(config()) == 76_437_504
    assert s["kv_bytes_per_token_layer"] == 2048


def test_a_decode_step_of_32_lanes_by_hand():
    """Every weight once; per lane 36 states read AND written; 16 000 live
    tokens at 2 048 B in each of four layers."""
    work = ob.decode_step(config(), 32, 16000)
    assert work["bytes"] == ob.weight_bytes(ob.sizes(config())) + 2 * 32 * 36 * STATE + 4 * 2048 * 16000
    assert work["bytes"] == pytest.approx(11.41e9, rel=1e-3)
    matmul = 36 * (W_IN + W_OUT + MLP) + 4 * (ATTN + MLP) + HEAD
    one_token = 36 * (5 * 64 * 64 * 128 + 2 * 4 * 4352)
    assert work["flops"] == (2 * matmul + one_token) * 32 + 4 * 4 * 2048 * 16000
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(13.93e-3, rel=1e-3)
    # the state is most of what a step moves beside the weights, whatever the context
    assert 2 * 32 * 36 * STATE == pytest.approx(4.89e9, rel=1e-2)


def test_a_prompt_of_300_tokens_by_hand():
    work = ob.prefill(config(), 300)
    body = 36 * (W_IN + W_OUT + MLP) + 4 * (ATTN + MLP)
    chunked = 36 * 300 * (256 * (128 + 4096) + 4 * 64 * 64 * 128 + 2 * 4 * 4352)
    assert work["flops"] == 2 * body * 300 + 2 * HEAD + chunked + 4 * 4 * 2048 * 300 * 300 / 2
    assert work["bytes"] == ob.weight_bytes(ob.sizes(config())) + 2 * 36 * STATE + 4 * 2048 * 300
    assert opsbytes.least_time_s(work, "TPU v5 lite")["bound"] == "compute"
    assert ob.prefill(config(), 100)["flops"] < work["flops"] / 2.9  # a tile is the prompt where it is shorter


def ssm_run():
    run = a_run()
    run["config"] = config()
    run["stats1"]["executor"].update(state_bytes=32 * 76_437_504, state_bytes_per_session=76_437_504,
                                     kv_bytes_per_token=8192)
    return run


def test_the_three_readers_read_by_hand():
    run = ssm_run()
    assert harness.load_reader("kv.state_bytes_per_session")(run) == 76_437_504
    assert harness.load_reader("kv.bytes_per_token")(run) == 8192
    # a_run: 300 tokens in 200 steps; 300 + 117 and 300 + 116 tokens live at the window's middle
    work = ob.decode_step(run["config"], 1.5, 833)
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader("kernels.ssm_decode_roofline")(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    value = harness.load_reader("kernels.ssm_prefill_roofline")(run)
    assert value == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW[1:]:
        assert 0 < harness.load_reader(metric)(run) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counter_gives_nothing_and_does_not_raise(metric):
    """The parent commit holds no recurrent state and reports no
    `state_bytes_per_session`: its line leaves the metric out."""
    run = a_run()
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    if metric.startswith("kernels."):
        assert harness.load_reader(metric)(dict(ssm_run(), rehearse=True)) is None
        bare = ssm_run()
        bare["trace"]["modules"] = {}
        assert harness.load_reader(metric)(bare) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    loaded = harness.load_cell(CELL)
    assert loaded["cell"]["chips"] == 1 and loaded["reduced"] == []
    assert loaded["mix"]["clients"] == "slots" and loaded["mix"]["pool"] == 64
    assert loaded["mix"]["lead_in_s"] == 16
    names = {x["name"] for x in loaded["per_layer"]}
    assert names >= set(NEW) | {
        "kv.bytes_per_token", "loadgen.gap_ms_p95", "loadgen.ttft_ms_p50", "node.token_host_ms_p50",
        "window.device_ms_p50", "window.turn_ms_p50", "window.device_sampled_share",
        "node.loop_share_of_turn", "window.gang_timeout_share"}
    assert "kernels.decode_roofline" not in names and "moe.load_imbalance" not in names
    assert [x["name"] for x in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    from inferd_tpu.config import get_config
    cfg = get_config(loaded["config"]["preset"])
    harness.check_preset(loaded["config"], loaded["reduced"], cfg)
    assert cfg.has_state_layers and cfg.num_layers == 40
    assert harness.probe_sizes(loaded["config"], loaded["config"]["node_flags"]) == (600, 16)
    assert harness.reference_script(loaded["config"]).endswith("references/granite-hybrid.py")
    pool = __import__("traffic").size_pool(loaded["mix"])
    assert max(n + out for n, out in pool) <= 1024 + 384 < 4096
    assert loaded["config"]["published"] == {} and len(loaded["config"]["layer_types"]) == 40
    # every width and count of the published config is paired with the preset's
    pairs = loaded["config"]["preset_check"]
    assert {k for k in loaded["config"] if k.startswith("mamba_") and k not in pairs} == {
        "mamba_conv_bias", "mamba_proj_bias"}  # flags: no width, no count
    for key, other in (("mamba_d_state", 64), ("residual_multiplier", 1.0), ("attention_multiplier", 0.125),
                       ("layer_types", ["mamba"] * 40)):
        wrong = copy.deepcopy(loaded["config"])
        wrong[key] = other
        with pytest.raises(harness.Refused, match=key):
            harness.check_preset(wrong, loaded["reduced"], cfg)


def test_the_catalogs_published_keys_are_all_in_the_file():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    mine = config()
    assert mine["source"] == row["source_url"]
    assert {k: mine.get(k, "(absent)") for k in row["config"]} == row["config"]


def test_the_reference_reads_what_the_program_serves_at_the_tiny_preset(tmp_path):
    """`run.py --rehearse`'s pieces without the node: the seeded checkpoint
    `split_model --random-init` writes, the rehearsal's copy of the file, the
    reference as a script, against the program's own cache-free forward."""
    import subprocess
    import sys

    import numpy as np

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    parts = str(tmp_path / "parts")
    subprocess.run([sys.executable, "-m", "inferd_tpu.tools.split_model", "--model", "tiny-granite-h",
                    "--stages", "1", "--random-init", "--seed", "42", "--device", "cpu", "--out", parts],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.models import qwen3
    from inferd_tpu.parallel.stages import load_stage_checkpoint

    cfg = get_config("tiny-granite-h")
    file = harness.rehearsal_config(config(), cfg, str(tmp_path / "config.json"))
    prompt, more = list(range(3, 40)), [7, 9, 11]
    out = str(tmp_path / "ref.npy")
    subprocess.run([sys.executable, harness.reference_script(config()), "--ckpt",
                    os.path.join(parts, "stage_000.msgpack"), "--model", "tiny-granite-h", "--config", file,
                    "--device", "cpu", "--prompt-ids", ",".join(map(str, prompt)),
                    "--continue-ids", ",".join(map(str, more)), "--out", out],
                   check=True, env=env, cwd=REPO, capture_output=True, timeout=600)
    ref = np.load(out)
    assert ref.shape == (4, cfg.vocab_size)
    params, _, _ = load_stage_checkpoint(os.path.join(parts, "stage_000.msgpack"))
    with jax.default_matmul_precision("highest"):
        logits, _, _ = qwen3.forward(jax.tree.map(jnp.asarray, params), cfg, jnp.asarray([prompt + more]))
    got = np.asarray(jax.nn.log_softmax(logits[0, len(prompt) - 1:], axis=-1))
    np.testing.assert_allclose(got, ref, atol=2e-6)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-granite-h` on the CPU: float32 on both sides,
    so the node (prefill in two chunks, the second padded, then decode
    through the state) and the reference agree to 1e-5."""
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
    assert metrics["kv.bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 4   # two attention layers
    assert metrics["kv.state_bytes_per_session"]["value"] == 6 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.device_sampled_share"]["value"] == 100.0
    assert metrics["window.mean_cobatch"]["value"] > 4
    assert "kernels.ssm_decode_roofline" not in metrics   # a device number: none on a CPU
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        assert float(line.split("log-probabilities ")[1].split(" ")[0]) < 1e-5
    assert "FAIL" not in out.stdout
