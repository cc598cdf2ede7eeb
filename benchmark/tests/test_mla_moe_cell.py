"""The cell `dsv2l-long-chat`: its arithmetic (`opsbytes_mla_moe.py`) by
hand, its four readers on a hand-made run, its files in the manifest."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_mla_moe as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("kernels.mla_moe_decode_roofline", "kernels.mla_moe_prefill_roofline",
       "kv.bytes_per_token", "moe.load_imbalance")


def config():
    with open(os.path.join(REPO, "benchmark", "configs", "deepseek-v2-lite-1chip.json")) as f:
        return json.load(f)


# the sizes of one layer, by hand (parameters)
ATTN = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 2048 * 2048   # 13 762 560
EXPERT = 3 * 2048 * 1408                                             # 8 650 752
SHARED, ROUTER = 2 * EXPERT, 2048 * 64
DENSE_MLP, HEAD = 3 * 2048 * 10944, 2048 * 102400


def test_decode_bytes_at_16_tokens_and_51_experts_a_layer():
    """8 layers of attention weights, the dense layer's MLP, 7 x (router +
    shared), 7 x 51 routed experts, the head, each at 2 bytes; 16 sessions
    of 2500 tokens at 8 x 576 x 2 = 9 216 B a token."""
    work = ob.decode_step(config(), 16, 16 * 2500, 7 * 51)
    params = 8 * ATTN + DENSE_MLP + 7 * (ROUTER + SHARED) + 7 * 51 * EXPERT + HEAD
    assert work["bytes"] == 2 * params + 9216 * 40000
    assert work["bytes"] == pytest.approx(7.563e9, rel=1e-3)   # 9.56 GB if all 64 experts of a layer are read
    macs = 8 * ATTN + DENSE_MLP + 7 * (ROUTER + SHARED + 6 * EXPERT) + HEAD
    assert work["flops"] == 2 * macs * 16 + 2 * 8 * 16 * (2 * 512 + 64) * 40000
    assert opsbytes.least_time_s(work, "TPU v5 lite")["bound"] == "memory"


def test_operations_of_a_512_token_chunk():
    """Each token through 8 layers' attention projections, the dense MLP,
    and per sparse layer router + shared + its OWN 6 experts (not 64);
    causal expanded attention 16 heads x (192 + 128) x 512^2 / 2 a layer,
    2 flops a multiply-accumulate; the head once."""
    work = ob.prefill(config(), 512)
    macs = 8 * ATTN + DENSE_MLP + 7 * (ROUTER + SHARED + 6 * EXPERT)
    attn = 2 * 8 * 16 * 320 * 512 * 512 / 2
    assert work["flops"] == 2 * macs * 512 + 2 * HEAD + attn
    assert work["flops"] == pytest.approx(0.6898e12, rel=1e-3)   # every-expert dispatch: 4.29e12
    all_params = 8 * ATTN + DENSE_MLP + 7 * (ROUTER + SHARED + 64 * EXPERT) + HEAD
    assert work["bytes"] == 2 * all_params + 9216 * 512
    assert opsbytes.least_time_s(work, "TPU v5 lite")["bound"] == "memory"
    assert opsbytes.least_time_s(ob.prefill(config(), 3000), "TPU v5 lite")["bound"] == "compute"


def test_a_configuration_with_no_shared_expert():
    c = dict(config(), n_shared_experts=0)
    with_shared, without = ob.decode_step(config(), 1, 0, 7 * 6), ob.decode_step(c, 1, 0, 7 * 6)
    assert with_shared["bytes"] - without["bytes"] == 2 * 7 * SHARED
    assert with_shared["flops"] - without["flops"] == 2 * 7 * SHARED
    assert ob.sizes(c)["shared"] == 0
    few = ob.prefill(c, 4)   # 4 tokens reach at most 24 experts a layer
    assert few["bytes"] == 2 * (8 * ATTN + DENSE_MLP + 7 * (ROUTER + 24 * EXPERT) + HEAD) + 9216 * 4


def dsv2_run():
    run = a_run()
    run["config"] = config()
    run["stats0"]["executor"]["moe"] = dict(
        experts=64, steps=1000, assignments=100000, assignments_hottest=5000, experts_touched=300000)
    run["stats1"]["executor"].update(kv_bytes_per_token=9216, kv_cache_bytes=603979776, moe=dict(
        experts=64, steps=1200, assignments=100000 + 200 * 16 * 42, assignments_hottest=5000 + 200 * 35,
        experts_touched=300000 + 200 * 350))
    return run


def test_the_four_readers_read_by_hand():
    run = dsv2_run()
    assert harness.load_reader("kv.bytes_per_token")(run) == 9216
    # 7000 of 134 400 assignments on the fullest expert of a layer, 64 experts
    assert harness.load_reader("moe.load_imbalance")(run) == pytest.approx(7000 * 64 / 134400)
    work = ob.decode_step(run["config"], 1.5, 833, 350)   # a_run: 1.5 tokens a step, 300 + 117 and 300 + 116 live
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.0326
    assert harness.load_reader("kernels.mla_moe_decode_roofline")(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    value = harness.load_reader("kernels.mla_moe_prefill_roofline")(run)
    assert value == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW[:2]:
        assert 0 < harness.load_reader(metric)(run) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(metric):
    """The parent commit serves no such model and stamps no such counter:
    its line leaves the metric out."""
    run = a_run()   # a dense model's /stats: no `moe`, no `kv_bytes_per_token`
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    rehearsal = dict(dsv2_run(), rehearse=True)   # no device time on a CPU
    if metric.startswith("kernels."):
        assert harness.load_reader(metric)(rehearsal) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    loaded = harness.load_cell("dsv2l-long-chat")
    assert loaded["cell"]["chips"] == 1 and loaded["reduced"] == ["num_hidden_layers"]
    assert loaded["mix"]["clients"] == "slots" and loaded["mix"]["pool"] == 32
    assert {m["name"] for m in loaded["per_layer"]} >= set(NEW)
    assert [m["name"] for m in loaded["end_to_end"]] == ["setup_s", "out_tok_s", "gap_ms_p95"]
    from inferd_tpu.config import get_config
    harness.check_preset(loaded["config"], loaded["reduced"], get_config(loaded["config"]["preset"]))
    assert harness.probe_sizes(loaded["config"], loaded["config"]["node_flags"]) == (640, 16)
    assert harness.reference_script(loaded["config"]).endswith("references/deepseek-v2.py")
    lengths = [n + m_ for n, m_ in __import__("traffic").size_pool(loaded["mix"])]
    assert max(lengths) <= 3968 < 4096
    wrong = copy.deepcopy(loaded["config"])
    wrong["kv_lora_rank"] = 256
    with pytest.raises(harness.Refused, match="kv_lora_rank"):
        harness.check_preset(wrong, loaded["reduced"], get_config(wrong["preset"]))


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters(tmp_path):
    """The whole cell at `tiny-dsv2` on the CPU: float32 on both sides, so
    the node and the reference agree to 1e-4 at the prefill (two chunks) and
    through the latent cache; the two program counters are reported (the two
    roofline shares are device numbers and have none on a CPU)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload", "dsv2l-long-chat",
         "--seed", "2147483659", "--seconds", "12", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]   # a rehearsal is never `correct`
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"]["kv.bytes_per_token"]["value"] == 4 * (32 + 8) * 4
    assert 1.0 <= result["metrics"]["moe.load_imbalance"]["value"] <= 4.0
    assert result["metrics"]["engine.compiles_in_window"]["value"] == 0
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        mean = float(line.split("log-probabilities ")[1].split(" ")[0])
        assert mean < 1e-4
    assert "FAIL" not in out.stdout
