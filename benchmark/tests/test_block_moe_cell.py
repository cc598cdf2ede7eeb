"""The cell `sdar-block-chat`: its arithmetic (`opsbytes_block_moe.py`) by
hand, its five readers on a hand-made run, its files in the manifest, and
the whole cell rehearsed at `tiny-sdar` on the CPU."""

import copy
import json
import os

import pytest

import opsbytes
import opsbytes_block_moe as ob
import run as harness
from conftest import REPO
from test_layer_readers import a_run

NEW = ("diffusion.tokens_per_pass", "window.block_device_ms_p50", "node.block_host_ms_p50",
       "kernels.block_moe_step_roofline", "kernels.block_moe_prefill_roofline")


def config():
    with open(os.path.join(REPO, "benchmark", "configs", "sdar-30b-a3b-1chip.json")) as f:
        return json.load(f)


# the sizes of one layer, by hand (parameters)
ATTN = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048     # q, k, v, o: 18 874 368
EXPERT = 3 * 2048 * 768                               # 4 718 592
ROUTER, HEAD = 2048 * 128, 2048 * 151936
KV_TOKEN = 2 * 7 * 4 * 128 * 2                        # 14 336 B a token, 2 048 B a layer


def test_the_counts_of_the_issue():
    layer = ATTN + ROUTER + 128 * EXPERT
    assert layer == pytest.approx(623.1e6, rel=1e-3)
    assert 7 * layer + 2 * HEAD == pytest.approx(4.98e9, rel=2e-3)   # 9.97 GB in bf16
    assert ob.sizes(config())["kv_bytes_per_token"] == KV_TOKEN == 14336


def test_block_step_bytes_at_16_lanes_and_100_experts_a_layer():
    """Three passes, each reading 7 layers of attention weights and routers
    and 7 x 100 experts; the head in the two denoising passes; 16 sessions
    of 800 tokens at 14 336 B a token, read by every pass."""
    work = ob.block_step(config(), 16, 16 * 800, 7 * 100)
    per_pass = 7 * (ATTN + ROUTER) + 700 * EXPERT
    assert work["bytes"] == 2 * (3 * per_pass + 2 * HEAD) + 3 * KV_TOKEN * 12800
    assert work["bytes"] == pytest.approx(22.43e9, rel=1e-3)  # 29.1 GB if all 128 experts of a layer are read
    macs = 7 * (ATTN + ROUTER + 8 * EXPERT)
    attn = 4 * 7 * 4096 * 4 * 12800
    assert work["flops"] == 3 * (2 * macs * 64 + attn) + 2 * 2 * HEAD * 64
    least = opsbytes.least_time_s(work, "TPU v5 lite")
    assert least["bound"] == "memory" and least["seconds"] == pytest.approx(27.4e-3, rel=1e-2)


def test_operations_of_a_514_token_prompt():
    """The 512 tokens of its whole blocks through 7 layers' attention
    projections, router and their OWN 8 experts (not 128); block-causal
    attention 32 heads x 128 x 2 x 512 x 516 / 2 a layer, 2 flops a
    multiply-accumulate; no head; every expert's weights once."""
    work = ob.prefill(config(), 514)
    macs = 7 * (ATTN + ROUTER + 8 * EXPERT)
    assert work["flops"] == 2 * macs * 512 + 4 * 7 * 4096 * 512 * 516 / 2
    assert work["bytes"] == 2 * 7 * (ATTN + ROUTER + 128 * EXPERT) + KV_TOKEN * 512
    assert opsbytes.least_time_s(work, "TPU v5 lite")["bound"] == "memory"
    assert ob.prefill(config(), 3)["flops"] == 0   # no whole block: all of it opens the first block
    few = ob.prefill(config(), 4)   # 4 tokens reach at most 32 experts a layer
    assert few["bytes"] == 2 * 7 * (ATTN + ROUTER + 32 * EXPERT) + KV_TOKEN * 4


def block_run():
    """a_run's window with a block model's counters and spans: 200 block
    steps of 15 live lanes, 3 passes each, 100 experts a layer and pass."""
    run = a_run()
    run["config"] = config()
    run["stats0"]["executor"].update(
        moe=dict(experts=128, steps=3000, assignments=0, assignments_hottest=0, experts_touched=10 ** 6),
        diffusion=dict(block_steps=1000, lane_passes=45000, tokens=59000, rows=180000))
    run["stats1"]["executor"].update(
        kv_bytes_per_token=14336,
        moe=dict(experts=128, steps=3600, assignments=600 * 60 * 7 * 8, assignments_hottest=600 * 7 * 9,
                 experts_touched=10 ** 6 + 600 * 700),
        diffusion=dict(block_steps=1200, lane_passes=54000, tokens=59000 + 11800, rows=216000))
    run["trace"]["modules"]["jit__block_step"] = {"count": 40, "total_s": 3.2, "median_s": 0.08}
    for i in range(100):  # a step span over each hand-made forward, a block compute beneath it
        t = 1000.04 + 0.1 * i
        run["spans"] += [
            {"span": f"s{i}", "name": "step", "t0": t - 0.004, "t1": t + 0.068},
            {"span": f"f{i}", "parent": f"s{i}", "name": "forward", "t0": t, "t1": t + 0.066},
            {"span": f"c{i}", "parent": f"f{i}", "name": "compute", "t0": t + 0.001, "t1": t + 0.061,
             "attrs": {"kind": "block"}},
            {"name": "device", "t0": t + 0.002, "t1": t + 0.002 + 0.05 + 0.0001 * i,
             "attrs": {"kind": "block", "passes": 3}},
            {"name": "device", "t0": t + 0.07, "t1": t + 0.09, "attrs": {"kind": "prefill"}},
        ]
    return run


def test_the_five_readers_read_by_hand():
    run = block_run()
    # 11 800 places made known in 9 000 lane-passes
    assert harness.load_reader("diffusion.tokens_per_pass")(run) == pytest.approx(11800 / 9000)
    assert harness.load_reader("window.block_device_ms_p50")(run) == pytest.approx(54.95)
    assert harness.load_reader("node.block_host_ms_p50")(run) == pytest.approx(12.0)
    # 15 live lanes a pass, 700 experts touched a pass; a_run: 300 + 117 and 300 + 116 live
    work = ob.block_step(run["config"], 15, 833, 700)
    want = 100 * opsbytes.least_time_s(work, "TPU v5 lite")["seconds"] / 0.08
    assert harness.load_reader("kernels.block_moe_step_roofline")(run) == pytest.approx(want)
    least = opsbytes.least_time_s(ob.prefill(run["config"], 3000), "TPU v5 lite")
    value = harness.load_reader("kernels.block_moe_prefill_roofline")(run)
    assert value == pytest.approx(100 * least["seconds"] / 0.33)
    for metric in NEW[3:]:
        assert 0 < harness.load_reader(metric)(run) < 100
    assert harness.load_reader("kv.bytes_per_token")(run) == 14336
    assert harness.load_reader("moe.load_imbalance")(run) == pytest.approx(9 * 128 / (60 * 8))


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_counters_gives_nothing_and_does_not_raise(metric):
    """The parent commit serves no such model, stamps no span of kind
    `block` and no `diffusion.*` counter: its line leaves the metric out."""
    run = a_run()   # a dense model's /stats and spans, a configuration with no `trace_modules.block`
    assert harness.load_reader(metric)(run) is None
    run["config"] = config()
    assert harness.load_reader(metric)(run) is None
    rehearsal = dict(block_run(), rehearse=True)   # no device time on a CPU
    if metric.startswith("kernels."):
        assert harness.load_reader(metric)(rehearsal) is None


def test_the_cell_is_in_the_manifest_with_its_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    import validate_manifest as vm
    assert vm.validate(m, REPO) == []
    loaded = harness.load_cell("sdar-block-chat")
    assert loaded["cell"]["chips"] == 1 and loaded["reduced"] == ["num_hidden_layers"]
    assert loaded["mix"]["clients"] == "slots" and loaded["mix"]["pool"] == 32
    names = {m["name"] for m in loaded["per_layer"]}
    assert names >= set(NEW) | {"kv.bytes_per_token", "moe.load_imbalance", "loadgen.gap_ms_p95"}
    # it reads the hops of kind `decode`, of which this cell has none
    assert "node.token_host_ms_p50" not in names and "window.device_ms_p50" not in names
    assert [m["name"] for m in loaded["end_to_end"]] == ["setup_s", "out_tok_s"]
    from inferd_tpu.config import get_config
    cfg = get_config(loaded["config"]["preset"])
    harness.check_preset(loaded["config"], loaded["reduced"], cfg)
    assert cfg.is_block_diffusion and cfg.num_layers == 7
    assert harness.probe_sizes(loaded["config"], loaded["config"]["node_flags"]) == (642, 16)
    assert harness.reference_script(loaded["config"]).endswith("references/sdar.py")
    pool = __import__("traffic").size_pool(loaded["mix"])
    assert max(n + m_ for n, m_ in pool) <= 2560 < 4096
    assert sum(1 for n, _ in pool if n % 4) >= 20   # most prompts open their first block
    published = loaded["config"]["published"]
    assert published == {"num_hidden_layers": 48} and loaded["config"]["num_experts"] == 128
    wrong = copy.deepcopy(loaded["config"])
    wrong["block_length"] = 8
    with pytest.raises(harness.Refused, match="block_length"):
        harness.check_preset(wrong, loaded["reduced"], cfg)


def test_rehearsal_passes_both_reference_checks_and_reports_the_counters():
    """The whole cell at `tiny-sdar` on the CPU: float32 on both sides, so
    the node and the reference agree to 1e-4 at the first block (opened by
    prompt tokens after a prefill in chunks) and through the cache over the
    later blocks; the program counters are reported (the two roofline shares
    are device numbers and have none on a CPU)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload", "sdar-block-chat",
         "--seed", "2147483659", "--seconds", "12", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]   # a rehearsal is never `correct`
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["failed"] == 0 and result["attempted"] > 0
    metrics = result["metrics"]
    assert metrics["kv.bytes_per_token"]["value"] == 2 * 4 * 2 * 16 * 4
    assert 1.2 <= metrics["diffusion.tokens_per_pass"]["value"] <= 4 / 3
    assert metrics["engine.compiles_in_window"]["value"] == 0
    assert metrics["window.block_device_ms_p50"]["value"] > 0
    assert metrics["node.block_host_ms_p50"]["value"] > 0
    assert metrics["window.mean_cobatch"]["value"] > 4
    assert "node.token_host_ms_p50" not in metrics
    for check in ("probe_reference", "probe_decode_reference"):
        line = next(l for l in out.stdout.splitlines() if f"PASS {check}:" in l)
        mean = float(line.split("log-probabilities ")[1].split(" ")[0])
        assert mean < 1e-4
    assert "FAIL" not in out.stdout
