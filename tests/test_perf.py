"""The perf subsystem (inferd_tpu/perf/): roofline cost model, autotune
registry + dispatch integration, regression gate,
and the round-6 sampling fast path.

Hand-computed roofline expectations are derived INDEPENDENTLY here (byte
arithmetic written out per preset/mode, plus a ground-truth cross-check
against the actual init_params leaf bytes for the tiny preset) so a drift
in perf/roofline's accounting fails loudly instead of self-certifying.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inferd_tpu.config import PRESETS, SamplingConfig, get_config
from inferd_tpu.core import sampling as samplib
from inferd_tpu.perf import autotune, gate as gatelib, roofline as rl
from inferd_tpu.perf.__main__ import main as perf_main

R05 = gatelib.DEFAULT_ARTIFACT


# ---------------------------------------------------------------------------
# roofline: hand-computed byte accounting
# ---------------------------------------------------------------------------


def _hand_linear(k, n, quant, dsize):
    """Independent re-derivation of stored linear bytes (duplicated on
    purpose — this is the change detector for the model's accounting)."""
    if quant == "none":
        return k * n * dsize
    if quant in ("int8", "w8a8", "int8-kernel"):
        return k * n + 4 * n
    assert quant == "int4"
    g = min(128, k)
    while k % g:
        g -= 1
    return (k // 2 * n if k % 2 == 0 else k * n) + 4 * (k // g) * n


def _hand_weight_bytes(cfg, quant):
    """Per-step weight read (attn + mlp + head + norms), dense configs."""
    h, d, L, i = cfg.hidden_size, cfg.head_dim, cfg.num_layers, cfg.intermediate_size
    qd, kvd = cfg.num_heads * d, cfg.num_kv_heads * d
    dsize = jnp.dtype(cfg.dtype).itemsize
    lin = sum(
        _hand_linear(k, n, quant, dsize)
        for k, n in [(h, qd), (h, kvd), (h, kvd), (qd, h),
                     (h, i), (h, i), (i, h)]
    ) * L
    norms = (L * (2 * h + (2 * d if cfg.qk_norm else 0)) + h) * dsize
    if cfg.attn_bias:
        norms += L * (qd + 2 * kvd) * dsize
    if cfg.tie_word_embeddings and quant == "none":
        head = h * cfg.vocab_size * dsize
    else:
        head = _hand_linear(h, cfg.vocab_size, quant, dsize)
    return lin + norms + head


@pytest.mark.parametrize("preset", ["qwen3-0.6b", "qwen3-8b", "qwen2-0.5b", "tiny"])
@pytest.mark.parametrize("quant", ["none", "int8", "int4"])
@pytest.mark.parametrize("kv_dtype", ["model", "float8_e4m3fn"])
def test_decode_step_cost_hand_computed(preset, quant, kv_dtype):
    cfg = get_config(preset)
    ctx = 1024
    c = rl.decode_step_cost(cfg, quant=quant, kv_dtype=kv_dtype, ctx=ctx)
    assert c.weight_bytes == _hand_weight_bytes(cfg, quant)
    kv_size = jnp.dtype(
        cfg.dtype if kv_dtype == "model" else kv_dtype
    ).itemsize
    kvd = cfg.num_kv_heads * cfg.head_dim
    assert c.kv_read_bytes == 2 * cfg.num_layers * ctx * kvd * kv_size
    assert c.kv_write_bytes == 2 * cfg.num_layers * kvd * kv_size
    assert c.embed_gather_bytes == cfg.hidden_size * jnp.dtype(cfg.dtype).itemsize
    # monotonicity: quantization and KV compression only shrink the step
    base = rl.decode_step_cost(cfg, ctx=ctx)
    assert c.read_bytes <= base.read_bytes


def test_quant_shrinks_bytes_strictly():
    cfg = get_config("qwen3-0.6b")
    none = rl.decode_step_cost(cfg).read_bytes
    i8 = rl.decode_step_cost(cfg, quant="int8").read_bytes
    i4 = rl.decode_step_cost(cfg, quant="int4").read_bytes
    assert i4 < i8 < none
    # fp8 KV halves the KV read at long context
    bf = rl.decode_step_cost(cfg, ctx=8192)
    f8 = rl.decode_step_cost(cfg, ctx=8192, kv_dtype="float8_e4m3fn")
    assert f8.kv_read_bytes * 2 == bf.kv_read_bytes


def test_tiny_bf16_read_matches_real_param_tree():
    """Ground truth: for a tied, unquantized model the per-step weight
    read equals the actual parameter tree's stored bytes (the embed table
    doubles as the unembed read), within the embed-gather rounding."""
    from inferd_tpu.models import qwen3

    cfg = get_config("tiny")
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    leaf_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    c = rl.decode_step_cost(cfg)
    assert c.weight_bytes == leaf_bytes


def test_moe_counts_active_experts_only():
    cfg = get_config("tiny-moe")
    c = rl.decode_step_cost(cfg)
    dsize = jnp.dtype(cfg.dtype).itemsize
    h, mi = cfg.hidden_size, cfg.moe_intermediate_size
    per_expert = 3 * h * mi * dsize
    router = h * cfg.num_experts * dsize
    assert c.mlp_weight_bytes == cfg.num_layers * (
        router + cfg.num_experts_per_tok * per_expert
    )


def test_roofline_reproduces_round5_decode_frac():
    """Acceptance: the analytic floor reproduces the committed round-5
    decode leg's hbm_roofline_frac 0.114 within +-10% (and the ctx8k /
    fp8-KV legs' recorded fracs too)."""
    cfg = get_config("qwen3-0.6b")
    chip = rl.get_chip("v5e")
    for kwargs, measured, recorded in [
        (dict(), 78.19, 0.114),
        (dict(ctx=8192), 35.17, 0.092),
        (dict(ctx=8192, kv_dtype="float8_e4m3fn"), 35.62, 0.072),
    ]:
        frac = rl.roofline_frac(measured, rl.decode_step_cost(cfg, **kwargs), chip)
        assert abs(frac - recorded) <= 0.10 * recorded, (kwargs, frac, recorded)


def test_report_cli_prints_table_and_rederivation(capsys):
    assert perf_main(["report", "--preset", "qwen3-0.6b"]) == 0
    out = capsys.readouterr().out
    assert "ceiling tok/s" in out and "int4" in out
    if os.path.exists(R05):
        import re

        m = re.search(r"decode: measured 78\.19 .* frac (0\.\d+)", out)
        assert m, out
        assert abs(float(m.group(1)) - 0.114) <= 0.0114


def test_chip_table_and_detect():
    assert rl.get_chip("v5e").hbm_gbps == 819.0
    with pytest.raises(KeyError):
        rl.get_chip("v99")
    assert rl.detect_chip().key == "cpu"  # tests run on CPU


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    """A TPU whose kind is not in the peaks table raises — it is not handed
    another generation's numbers."""
    from inferd_tpu.utils import platform

    monkeypatch.setattr(platform, "is_tpu", lambda: True)
    monkeypatch.setattr(platform, "device_kind", lambda: "TPU v5 lite")
    assert rl.detect_chip().key == "v5e"
    monkeypatch.setattr(platform, "device_kind", lambda: "TPU v99 mega")
    with pytest.raises(KeyError, match="v99"):
        rl.detect_chip()


# ---------------------------------------------------------------------------
# autotune registry
# ---------------------------------------------------------------------------


@pytest.fixture
def reg_path(tmp_path, monkeypatch):
    p = str(tmp_path / "autotune.json")
    monkeypatch.setenv("INFERD_AUTOTUNE", p)
    autotune.reset()
    yield p
    autotune.reset()


def test_registry_round_trip(reg_path):
    reg = autotune.get_registry()
    assert not reg.entries
    key = autotune.attn_key("v5e", 1, 1, 8192, 16, 8, 128, "bfloat16", False)
    reg.record(key, "xla", {"xla": 2656.0, "stream": 1780.0}, source="test")
    reg.record(autotune.int4_key("v5e"), "grouped", source="test")
    assert reg.save() == reg_path
    fresh = autotune.Registry.load(reg_path)
    assert fresh.winner(key, ("flash", "xla")) == "xla"
    assert fresh.winner(autotune.int4_key("v5e"), ("grouped", "dequant")) == "grouped"
    assert fresh.lookup(key)["rates"]["xla"] == 2656.0


def test_registry_corrupt_file_is_cold_not_fatal(reg_path, capsys):
    with open(reg_path, "w") as f:
        f.write("{not json at all")
    autotune.reset()
    reg = autotune.get_registry()
    assert reg.corrupt and not reg.entries
    assert autotune.attn_winner(get_config("tiny"), 8192) is None
    # save() rewrites the corrupt file whole and it loads clean after
    reg.record(autotune.int4_key("cpu"), "dequant")
    reg.save()
    assert not autotune.Registry.load(reg_path).corrupt


def test_registry_rejects_wrong_schema(reg_path):
    with open(reg_path, "w") as f:
        json.dump({"version": 999, "entries": {}}, f)
    autotune.reset()
    assert autotune.get_registry().corrupt


def test_registry_out_of_vocab_winner_treated_cold(reg_path):
    reg = autotune.get_registry()
    reg.record(autotune.int4_key("cpu"), "warp-drive")
    reg.save()
    autotune.reset()
    assert autotune.int4_winner("cpu") is None


def _frozen_flash_heuristic(cfg, kv_buf_len, compressed, q_len, batch, on_tpu):
    """The pre-registry `auto` rule, restated independently."""
    if compressed or not on_tpu:
        return False
    return 4 * batch * cfg.num_heads * q_len * kv_buf_len > 256 * 1024 * 1024


@pytest.mark.parametrize("on_tpu", [False, True])
def test_flash_enabled_cold_matches_frozen_heuristic(reg_path, monkeypatch, on_tpu):
    """Acceptance: with a COLD registry the `auto` dispatch is bit-for-bit
    the frozen heuristic, on every shape in a grid spanning both sides of
    the score budget."""
    from inferd_tpu.ops import attention as att

    monkeypatch.setattr(att, "is_tpu", lambda: on_tpu)
    cfg = get_config("qwen3-0.6b")  # attn_impl == "auto"
    for t in (2048, 8192, 65536, 1 << 20):
        for q_len in (1, 512, 4096):
            for compressed in (False, True):
                got = att.flash_enabled(
                    cfg, t, compressed_kv=compressed, q_len=q_len, batch=1
                )
                want = _frozen_flash_heuristic(
                    cfg, t, compressed, q_len, 1, on_tpu
                )
                assert got == want, (t, q_len, compressed, on_tpu)


def test_flash_enabled_consults_populated_registry(reg_path, monkeypatch):
    """A populated entry overrides the heuristic in BOTH directions —
    including the compressed-KV caution (the fp8-KV kernel enablement
    VERDICT r05 item 4 asks for) — and only for its own shape bucket."""
    from inferd_tpu.ops import attention as att

    cfg = get_config("qwen3-0.6b")
    reg = autotune.get_registry()
    # chip is "cpu" under tests; record a flash win at t=8192 decode,
    # compressed KV — the frozen rule would refuse both (cpu + compressed)
    reg.record(
        autotune.attn_key("cpu", 1, 1, 8192, cfg.num_heads, cfg.num_kv_heads,
                          cfg.head_dim, cfg.dtype, True),
        "flash",
    )
    # and an explicit xla win at a huge-prefill shape where the patched-TPU
    # heuristic would pick the kernel
    reg.record(
        autotune.attn_key("cpu", 1, 4096, 1 << 20, cfg.num_heads,
                          cfg.num_kv_heads, cfg.head_dim, cfg.dtype, False),
        "xla",
    )
    reg.save()
    autotune.reset()
    assert att.flash_enabled(cfg, 8192, compressed_kv=True, q_len=1, batch=1)
    monkeypatch.setattr(att, "is_tpu", lambda: True)
    assert not att.flash_enabled(
        cfg, 1 << 20, compressed_kv=False, q_len=4096, batch=1
    )
    # a different bucket stays on the heuristic (uncontaminated)
    assert not att.flash_enabled(cfg, 2048, compressed_kv=False, q_len=1, batch=1)
    # FORCE_FLASH and explicit impls still outrank the registry
    monkeypatch.setattr(att, "FORCE_FLASH", False)
    assert not att.flash_enabled(cfg, 8192, compressed_kv=True, q_len=1, batch=1)


def test_int4_mode_cold_and_populated(reg_path):
    from inferd_tpu.ops import quant

    assert quant.INT4_MODE == "auto"
    assert quant._int4_mode() == "grouped"  # cold CPU default, bit-for-bit
    reg = autotune.get_registry()
    reg.record(autotune.int4_key("cpu"), "dequant", source="test")
    reg.save()
    autotune.reset()
    assert quant._int4_mode() == "dequant"
    # explicit INT4_MODE still outranks the registry
    old = quant.INT4_MODE
    try:
        quant.INT4_MODE = "grouped"
        assert quant._int4_mode() == "grouped"
    finally:
        quant.INT4_MODE = old


def test_sweep_attn_populates_registry(reg_path, monkeypatch):
    """tools/sweep_attn --populate records winners the dispatch can read
    back (tiny shapes via a monkeypatched shape list, CPU interpreter)."""
    from inferd_tpu.tools import sweep_attn

    monkeypatch.setattr(
        sweep_attn, "shapes", lambda: iter([("decode", 1, 256, 3)])
    )
    monkeypatch.setattr("sys.argv", ["sweep_attn", "--populate"])
    sweep_attn.main()
    autotune.reset()
    reg = autotune.get_registry()
    assert any(k.startswith("attn|cpu|") for k in reg.entries), reg.entries
    (key,) = [k for k in reg.entries if k.startswith("attn|cpu|")]
    assert reg.entries[key]["winner"] in ("flash", "xla")
    assert reg.entries[key]["rates"]


# ---------------------------------------------------------------------------
# gate
# ---------------------------------------------------------------------------


def _battery_line(leg, result):
    return json.dumps({"leg": leg, "ts": "t", "argv": [], "rc": 0,
                       "result": result})


def _good_leg(**over):
    base = {
        "metric": "qwen3_0.6b_decode_tok_per_s_bs1",
        "value": 78.19, "unit": "tok/s", "e2e_tok_per_s": 60.0,
        "steady_timing_valid": True, "steady_spread_pt": 3.0,
        "timing_methodology": "interleaved-paired",
        "hbm_roofline_frac": 0.114, "device": "tpu",
    }
    base.update(over)
    return base


def test_gate_passes_committed_round5_artifacts():
    assert os.path.exists(R05), "committed round-5 battery artifact missing"
    findings, ok = gatelib.gate(R05)
    assert ok, [f.line() for f in findings]
    # the known round-5 inversion IS flagged — as an advisory warning
    assert any(
        f.check == "ordering" and f.leg == "decode" and f.severity == "warning"
        for f in findings
    )


def test_gate_fails_on_steady_e2e_inversion(tmp_path):
    """Acceptance: a new-methodology leg with steady < e2e (tok/s) fails."""
    art = tmp_path / "bad.jsonl"
    art.write_text(_battery_line(
        "decode", _good_leg(value=78.19, e2e_tok_per_s=119.07)
    ) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(f.check == "ordering" and f.severity == "error" for f in findings)
    # the same inversion WITHOUT the new-methodology marker is advisory
    legacy = dict(_good_leg(value=78.19, e2e_tok_per_s=119.07))
    legacy.pop("steady_spread_pt")
    legacy.pop("timing_methodology")
    art2 = tmp_path / "legacy.jsonl"
    art2.write_text(_battery_line("decode", legacy) + "\n")
    findings, ok = gatelib.gate(str(art2))
    assert ok
    assert any(f.check == "ordering" and f.severity == "warning" for f in findings)


def test_gate_swarm_agg_ordering(tmp_path):
    """swarm co-batching invariant: concurrent aggregate < serial baseline
    is an ERROR (the window/coalescing machinery regressed below
    one-session-at-a-time); >= serial passes. Also gates the committed
    round-6 swarm artifact."""
    leg = {
        "metric": "tiny_swarm_agg_tok_per_s", "value": 8.0,
        "unit": "tok/s", "serial_tok_per_s": 10.0, "sessions": 8,
    }
    art = tmp_path / "swarm.jsonl"
    art.write_text(_battery_line("swarm_agg", leg) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(
        f.check == "ordering" and f.severity == "error" and "serial" in f.message
        for f in findings
    )
    leg["value"] = 40.0
    art.write_text(_battery_line("swarm_agg", leg) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert ok, [f.line() for f in findings]
    committed = os.path.join(
        os.path.dirname(R05), "BENCH_swarm_r06.json"
    )
    assert os.path.exists(committed), "committed swarm_agg artifact missing"
    findings, ok = gatelib.gate(committed)
    assert ok, [f.line() for f in findings]


def test_gate_fails_on_roofline_regression(tmp_path):
    prior = tmp_path / "prior.jsonl"
    cur = tmp_path / "cur.jsonl"
    prior.write_text(_battery_line("decode", _good_leg()) + "\n")
    cur.write_text(_battery_line(
        "decode", _good_leg(value=50.0, e2e_tok_per_s=40.0,
                            hbm_roofline_frac=0.073)
    ) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert not ok
    assert any(f.check == "regression" for f in findings)
    # a <20% dip passes
    cur.write_text(_battery_line(
        "decode", _good_leg(value=70.0, e2e_tok_per_s=60.0,
                            hbm_roofline_frac=0.102)
    ) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]


def test_gate_regression_not_fooled_by_accounting_change(tmp_path):
    """An r05-accounting prior (no methodology marker, frac 0.06) vs an
    r06 leg at the SAME measured tok/s (frac 0.039 under the new model)
    is NOT a regression — cross-generation pairs compare raw values."""
    prior_leg = {
        "metric": "qwen3_0.6b_decode_tok_per_s_bs1_int8",
        "value": 53.94, "unit": "tok/s", "e2e_tok_per_s": 50.0,
        "steady_timing_valid": True, "hbm_roofline_frac": 0.06,
        "device": "tpu",
    }
    cur_leg = _good_leg(
        metric="qwen3_0.6b_decode_tok_per_s_bs1_int8",
        value=53.94, e2e_tok_per_s=50.0, hbm_roofline_frac=0.039,
    )
    prior = tmp_path / "r05.jsonl"
    cur = tmp_path / "r06.jsonl"
    prior.write_text(_battery_line("decode_int8", prior_leg) + "\n")
    cur.write_text(_battery_line("decode_int8", cur_leg) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]
    # but a real tok/s drop across generations still fails
    cur.write_text(_battery_line(
        "decode_int8",
        _good_leg(metric="qwen3_0.6b_decode_tok_per_s_bs1_int8",
                  value=40.0, e2e_tok_per_s=35.0, hbm_roofline_frac=0.029),
    ) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert not ok
    assert any(f.check == "regression" for f in findings)


def test_gate_fails_on_impossible_fraction(tmp_path):
    art = tmp_path / "impossible.jsonl"
    art.write_text(_battery_line(
        "decode", _good_leg(value=5000.0, e2e_tok_per_s=4000.0,
                            hbm_roofline_frac=7.3)
    ) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(f.check == "physics" and f.severity == "error" for f in findings)


def test_gate_cli_exit_codes(tmp_path, capsys):
    art = tmp_path / "ok.jsonl"
    art.write_text(_battery_line("decode", _good_leg()) + "\n")
    assert perf_main(["check", "--artifact", str(art)]) == 0
    assert "PASS" in capsys.readouterr().out
    bad = tmp_path / "bad.jsonl"
    bad.write_text(_battery_line(
        "decode", _good_leg(value=78.19, e2e_tok_per_s=119.07)
    ) + "\n")
    assert perf_main(["check", "--artifact", str(bad), "--json"]) == 1
    obj = json.loads(capsys.readouterr().out)
    assert obj["ok"] is False and obj["findings"]


def test_gate_tolerates_truncated_artifact_line(tmp_path):
    """A battery killed mid-append leaves a truncated final line; the
    intact legs must still be checked (warning, not a crash)."""
    art = tmp_path / "truncated.jsonl"
    art.write_text(
        _battery_line("decode", _good_leg()) + "\n"
        + '{"leg": "decode_int8", "result": {"metr'
    )
    findings, ok = gatelib.gate(str(art))
    assert ok
    assert any(
        f.check == "artifact" and "unparseable" in f.message for f in findings
    )


def test_gate_uses_per_leg_roofline_chip(tmp_path):
    """A leg recorded against a faster chip must be re-derived against
    THAT chip — a correct v5p measurement above the v5e ceiling is not a
    physics error."""
    cfg = get_config("qwen3-0.6b")
    v5p_ceiling = rl.roofline(rl.decode_step_cost(cfg), rl.get_chip("v5p")).ceiling_tok_s
    value = round(v5p_ceiling * 0.5, 2)  # 50% of v5p > 100% of v5e
    leg = _good_leg(value=value, e2e_tok_per_s=value * 0.8,
                    hbm_roofline_frac=0.5, roofline_chip="v5p")
    art = tmp_path / "v5p.jsonl"
    art.write_text(_battery_line("decode", leg) + "\n")
    findings, ok = gatelib.gate(str(art))  # default --chip v5e
    assert ok, [f.line() for f in findings]
    # without the chip stamp the same leg IS flagged (legacy behavior)
    leg2 = dict(leg)
    leg2.pop("roofline_chip")
    art.write_text(_battery_line("decode", leg2) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok


def test_parse_decode_metric_variants():
    cfg, quant, kv, ctx = gatelib.parse_decode_metric(
        "qwen3_0.6b_decode_tok_per_s_bs1_ctx8192_kv-float8_e4m3fn"
    )
    assert cfg.name == "qwen3-0.6b" and kv == "float8_e4m3fn" and ctx == 8192
    assert quant == "none"
    cfg, quant, kv, ctx = gatelib.parse_decode_metric(
        "qwen3_8b_decode_tok_per_s_bs1_int8-kernel"
    )
    assert cfg.name == "qwen3-8b" and quant == "int8-kernel" and ctx == 0
    assert gatelib.parse_decode_metric("flash_gqa_decode_t8192_calls_per_s") is None
    assert gatelib.parse_decode_metric("nonexistent_decode_tok_per_s_bs1") is None


# ---------------------------------------------------------------------------
# sampling fast path (satellite: greedy / temperature-only skip the
# full-vocab warp chain; HF-parity regression)
# ---------------------------------------------------------------------------


def test_passthrough_predicate():
    V = 151936
    assert samplib.passthrough_filters(0, 1.0, 0.0, V)
    assert samplib.passthrough_filters(V, 1.0, 0.0, V)  # top_k >= vocab
    assert not samplib.passthrough_filters(20, 1.0, 0.0, V)
    assert not samplib.passthrough_filters(0, 0.95, 0.0, V)
    assert not samplib.passthrough_filters(0, 1.0, 0.1, V)


def test_temperature_only_sample_parity_with_full_chain():
    """The fast path must draw BIT-IDENTICAL tokens to the full warp
    chain (whose filters are all identity for this config)."""
    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, 512), jnp.float32)
    fast = samplib.sample(logits, key, temperature=0.8, top_k=0, top_p=1.0)
    slow = jax.random.categorical(
        key,
        samplib.min_p_filter(
            samplib.top_p_filter(
                samplib.top_k_filter(logits / jnp.float32(0.8), 0), 1.0
            ),
            0.0,
        ),
        axis=-1,
    )
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


def test_greedy_sample_is_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(2), (3, 257), jnp.float32)
    tok = samplib.sample(logits, jax.random.PRNGKey(0), temperature=0.0)
    np.testing.assert_array_equal(
        np.asarray(tok), np.asarray(jnp.argmax(logits, axis=-1))
    )


def test_warped_logits_greedy_is_point_mass():
    """temperature == 0 used to divide by zero (NaN softmax); it must be
    the argmax point mass — the distribution greedy `sample` draws from."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (2, 64), jnp.float32)
    probs = samplib.warped_probs(logits, SamplingConfig(temperature=0.0))
    p = np.asarray(probs)
    assert np.isfinite(p).all()
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(
        p.argmax(-1), np.asarray(jnp.argmax(logits, -1))
    )
    assert (p.max(-1) > 0.999).all()


def test_warped_logits_temperature_only_is_scaled_identity():
    logits = jax.random.normal(jax.random.PRNGKey(4), (2, 64), jnp.float32)
    out = samplib.warped_logits(logits, 0.7, 0, 1.0, 0.0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(logits / jnp.float32(0.7)), rtol=1e-6
    )


def test_sampled_path_unchanged_with_filters_active():
    """Regression guard: the non-passthrough path (top-k active) still
    matches the scatter-free candidate draw it had before this change."""
    key = jax.random.PRNGKey(9)
    logits = jax.random.normal(jax.random.PRNGKey(5), (4, 512), jnp.float32)
    got = samplib.sample(logits, key, temperature=0.6, top_k=20, top_p=0.95)
    scaled = logits / jnp.float32(0.6)
    vals, idx = jax.lax.top_k(scaled, 20)
    vals = samplib.min_p_filter(samplib.top_p_filter(vals, 0.95), 0.0)
    choice = jax.random.categorical(key, vals, axis=-1)
    want = jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# battery integration (CPU stand-ins for the round-6 hardware legs)
# ---------------------------------------------------------------------------


def test_battery_has_round6_legs():
    from inferd_tpu.tools.bench_battery import DEFAULT_LEGS, SMOKE_LEGS

    names = {n for n, _, _ in DEFAULT_LEGS}
    assert "decode_8b_int8" in names
    tail = dict((n, t) for n, t, _ in DEFAULT_LEGS)["decode_8b_int8"]
    assert "--model" in tail and "qwen3-8b" in tail and "int8" in tail
    assert "decode_tiny_int8" in {n for n, _, _ in SMOKE_LEGS}


@pytest.mark.slow
def test_battery_smoke_runs_int8_leg(tmp_path):
    """Dryrun the int8 battery leg end to end on CPU: the artifact line
    must carry an int8 decode result."""
    from inferd_tpu.tools.bench_battery import main

    out = tmp_path / "smoke.jsonl"
    rc = main(["--smoke", "--legs", "decode_tiny_int8", "--out", str(out)])
    assert rc == 0
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    by_leg = {l["leg"]: l for l in lines}
    dec = by_leg["decode_tiny_int8"]["result"]
    assert dec["metric"].endswith("_int8") and dec["quant"] == "int8"
    assert dec["timing_methodology"] == "interleaved-paired"


# ---------------------------------------------------------------------------
# round 7: multi-step fused decode evidence (gate + battery)
# ---------------------------------------------------------------------------

MULTISTEP_ARTIFACT = os.path.join(
    os.path.dirname(R05), "BENCH_multistep_cpu_r07.json"
)


def _multistep_leg(**over):
    base = {
        "metric": "tiny_decode_multistep_tok_per_s_bs1",
        "value": 1200.0, "unit": "tok/s",
        "per_k": {"1": 400.0, "4": 900.0, "8": 1200.0},
        "k_best": "8", "speedup_best_vs_k1": 3.0,
        "token_exact": True, "steady_timing_valid": True,
        "timing_methodology": "interleaved-paired", "device": "cpu",
    }
    base.update(over)
    return base


def test_gate_multistep_ordering(tmp_path):
    """decode_multistep's claim is CI-enforced: when every K>1 rate falls
    below K=1 (fused inner loop slower than per-token dispatch) the gate
    hard-errors; a single lagging K is advisory."""
    art = tmp_path / "ms.jsonl"
    art.write_text(_battery_line("decode_multistep", _multistep_leg(
        per_k={"1": 1000.0, "4": 500.0, "8": 700.0}
    )) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(
        f.check == "ordering" and f.severity == "error"
        and "K-step" in f.message
        for f in findings
    )
    # one K below base but the best K above: warning only
    art.write_text(_battery_line("decode_multistep", _multistep_leg(
        per_k={"1": 1000.0, "4": 500.0, "8": 1400.0}
    )) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert ok, [f.line() for f in findings]
    assert any(
        f.check == "ordering" and f.severity == "warning" for f in findings
    )


def test_gate_multistep_token_exact_failure_is_hard(tmp_path):
    """A leg that measured token_exact=False is a CORRECTNESS regression,
    not an advisory hiccup: the gate hard-errors (run.sh step 0b2 is
    documented HARD and must not pass a divergent K-step stream). An
    errored leg WITHOUT a token-exactness verdict stays advisory."""
    art = tmp_path / "ms.jsonl"
    art.write_text(_battery_line("decode_multistep", _multistep_leg(
        token_exact=False,
        error="K>1 greedy stream diverged from the K=1 loop",
    )) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(
        f.check == "artifact" and f.severity == "error" for f in findings
    )
    # plain environmental error (no exactness verdict): advisory
    leg = _multistep_leg(error="no TPU on this box")
    del leg["token_exact"]
    art.write_text(_battery_line("decode_multistep", leg) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert ok, [f.line() for f in findings]
    assert any(
        f.check == "artifact" and f.severity == "warning" for f in findings
    )


def test_gate_multistep_speedup_regression(tmp_path):
    """The committed K-speedup prior gates regressions on the
    DIMENSIONLESS ratio (machine-portable), not raw tok/s: a fresh
    artifact on a slower box with the same speedup passes; a collapsed
    speedup fails."""
    prior = tmp_path / "prior.jsonl"
    prior.write_text(_battery_line(
        "decode_multistep", _multistep_leg(speedup_best_vs_k1=3.0)
    ) + "\n")
    # slower box, same amortization ratio: PASS
    cur = tmp_path / "cur.jsonl"
    cur.write_text(_battery_line("decode_multistep", _multistep_leg(
        value=120.0, per_k={"1": 40.0, "4": 90.0, "8": 120.0},
        speedup_best_vs_k1=3.0,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]
    # collapsed amortization: FAIL
    cur.write_text(_battery_line("decode_multistep", _multistep_leg(
        value=420.0, per_k={"1": 400.0, "4": 410.0, "8": 420.0},
        speedup_best_vs_k1=1.05,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert not ok
    assert any(
        f.check == "regression" and "speedup_best_vs_k1" in f.message
        for f in findings
    )
    # a multistep pair missing the ratio on either side must SKIP the
    # regression compare, not fall back to raw tok/s (cross-host false
    # fail): slower box, no K=1 in the sweep -> no finding
    cur.write_text(_battery_line("decode_multistep", _multistep_leg(
        value=120.0, per_k={"4": 90.0, "8": 120.0},
        speedup_best_vs_k1=None,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]
    assert not any(f.check == "regression" for f in findings)


def test_gate_passes_committed_multistep_artifact():
    """The committed CPU-proxy artifact (the raised prior this round's
    win is pinned to) must itself pass the gate, and must actually claim
    a >= 1.3x K-speedup (the round-7 acceptance bar)."""
    assert os.path.exists(MULTISTEP_ARTIFACT), "committed multistep artifact missing"
    findings, ok = gatelib.gate(MULTISTEP_ARTIFACT)
    assert ok, [f.line() for f in findings]
    legs = gatelib.load_artifact(MULTISTEP_ARTIFACT)
    res = dict(legs)["tiny_decode_multistep_tok_per_s_bs1"]
    assert res["token_exact"] is True
    assert res["speedup_best_vs_k1"] >= 1.3
    base = res["per_k"]["1"]
    assert any(
        v >= 1.3 * base for kk, v in res["per_k"].items() if kk != "1"
    )


def test_battery_has_round7_legs():
    from inferd_tpu.tools.bench_battery import DEFAULT_LEGS, SMOKE_LEGS

    names = {n for n, _, _ in DEFAULT_LEGS}
    assert "decode_multistep" in names
    smoke = dict((n, t) for n, t, _ in SMOKE_LEGS)
    assert "decode_multistep_tiny" in smoke
    assert "--config" in smoke["decode_multistep_tiny"]
    assert "decode-multistep" in smoke["decode_multistep_tiny"]


# ---------------------------------------------------------------------------
# round 8: paged-KV mixed-workload gate (swarm-mixed ordering + ratio prior)
# ---------------------------------------------------------------------------

PAGED_ARTIFACT = os.path.join(
    os.path.dirname(R05), "BENCH_paged_cpu_r08.json"
)


def _mixed_leg(**over):
    base = {
        "metric": "tiny_swarm_mixed_tok_per_s",
        "value": 110.0, "unit": "tok/s",
        "vs_baseline": 1.6, "paged_vs_dense": 1.6,
        "dense_tok_per_s": 68.0, "sessions": 4, "waves": 2,
        "prefix_tokens": 192, "block_size": 16,
        "token_exact": True, "device": "cpu",
    }
    base.update(over)
    return base


def test_gate_swarm_mixed_ordering(tmp_path):
    """The paged-vs-dense ordering is CI-enforced: a paged aggregate
    below dense on the same cluster hard-errors (the block pool must WIN
    on the mixed-length shared-prefix workload it exists for)."""
    art = tmp_path / "mx.jsonl"
    art.write_text(_battery_line("swarm_mixed", _mixed_leg(
        value=60.0, dense_tok_per_s=68.0, paged_vs_dense=0.88,
        vs_baseline=0.88,
    )) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(
        f.check == "ordering" and f.severity == "error"
        and "dense" in f.message
        for f in findings
    )
    art.write_text(_battery_line("swarm_mixed", _mixed_leg()) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert ok, [f.line() for f in findings]


def test_gate_swarm_mixed_token_exact_hard(tmp_path):
    """A divergent paged stream is a correctness regression, errored leg
    or not: token_exact=False hard-fails even when the leg 'succeeded'."""
    art = tmp_path / "mx.jsonl"
    art.write_text(_battery_line("swarm_mixed", _mixed_leg(
        token_exact=False,
    )) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert not ok
    assert any(
        f.check == "artifact" and f.severity == "error"
        and "token_exact" in f.message
        for f in findings
    )


def test_gate_swarm_mixed_ratio_regression(tmp_path):
    """The committed prior regresses on the DIMENSIONLESS paged/dense
    ratio (machine-portable), never raw tok/s; a pair missing the ratio
    on either side SKIPS instead of false-failing cross-host."""
    prior = tmp_path / "prior.jsonl"
    prior.write_text(_battery_line("swarm_mixed", _mixed_leg()) + "\n")
    # slower box, same dedupe ratio: PASS
    cur = tmp_path / "cur.jsonl"
    cur.write_text(_battery_line("swarm_mixed", _mixed_leg(
        value=11.0, dense_tok_per_s=6.8,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]
    # collapsed dedupe win: FAIL on the ratio
    cur.write_text(_battery_line("swarm_mixed", _mixed_leg(
        value=70.0, paged_vs_dense=1.02, vs_baseline=1.02,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert not ok
    assert any(
        f.check == "regression" and "paged_vs_dense" in f.message
        for f in findings
    )
    # ratio missing on one side: SKIP (no regression finding)
    leg = _mixed_leg(value=11.0, dense_tok_per_s=6.8)
    del leg["paged_vs_dense"]
    cur.write_text(_battery_line("swarm_mixed", leg) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]
    assert not any(f.check == "regression" for f in findings)


def test_gate_committed_paged_artifact():
    """The committed round-8 CPU-proxy artifact passes the gate, and
    passes as its own prior (run.sh step 0b3's shape)."""
    findings, ok = gatelib.gate(PAGED_ARTIFACT, PAGED_ARTIFACT)
    assert ok, [f.line() for f in findings]


def test_battery_has_round8_legs():
    from inferd_tpu.tools.bench_battery import DEFAULT_LEGS, SMOKE_LEGS

    names = {n for n, _, _ in DEFAULT_LEGS}
    # the config starts CPU-pinned node processes: no chip form, so it is
    # not in the chip battery (bench.py refuses it under --device tpu)
    assert "swarm_mixed" not in names
    smoke = dict((n, t) for n, t, _ in SMOKE_LEGS)
    assert "swarm_mixed_tiny" in smoke
    assert "swarm-mixed" in smoke["swarm_mixed_tiny"]
    assert "--tiny" in smoke["swarm_mixed_tiny"]


# ---------------------------------------------------------------------------
# round 10: overload-containment gate (goodput floor + hung + hedge budget)
# ---------------------------------------------------------------------------

OVERLOAD_ARTIFACT = os.path.join(
    os.path.dirname(R05), "BENCH_overload_cpu_r10.json"
)


def _overload_leg(**over):
    base = {
        "metric": "tiny_overload_goodput_tok_per_s",
        "value": 200.0, "unit": "tok/s",
        "vs_baseline": 0.9, "goodput_ratio": 0.9,
        "fault_free_tok_per_s": 222.0, "hung_requests": 0,
        "hedge_extra_frac": 0.01, "deadline_s": 25.0,
        "token_exact": True, "device": "cpu",
    }
    base.update(over)
    return base


def test_gate_overload_invariants(tmp_path):
    """The overload leg's three HARD invariants: goodput >= 70% of
    fault-free, zero requests hung past their deadline, hedge extra
    load within the 5% budget."""
    art = tmp_path / "ov.jsonl"
    art.write_text(_battery_line("overload", _overload_leg()) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert ok, [f.line() for f in findings]
    # burst exemption: a short leg whose hedges stayed within the
    # RatioBudget's burst floor may read above the cap as a FRACTION
    # without the budget having over-admitted — no error
    art.write_text(_battery_line("overload", _overload_leg(
        hedge_extra_frac=0.08, hedge_fired=2,
    )) + "\n")
    findings, ok = gatelib.gate(str(art))
    assert ok, [f.line() for f in findings]
    for bad, needle in (
        ({"goodput_ratio": 0.5, "vs_baseline": 0.5}, "goodput ratio"),
        ({"hung_requests": 2}, "past their deadline"),
        ({"hedge_extra_frac": 0.11}, "hedge extra load"),
        ({"hedge_extra_frac": 0.11, "hedge_fired": 9}, "hedge extra load"),
    ):
        art.write_text(
            _battery_line("overload", _overload_leg(**bad)) + "\n"
        )
        findings, ok = gatelib.gate(str(art))
        assert not ok, bad
        assert any(
            f.check == "ordering" and f.severity == "error"
            and needle in f.message
            for f in findings
        ), (bad, [f.line() for f in findings])


def test_gate_overload_ratio_regression(tmp_path):
    """Regression vs the prior gates on the DIMENSIONLESS goodput ratio
    (machine-portable); raw tok/s is never compared for this leg."""
    prior = tmp_path / "prior.jsonl"
    prior.write_text(_battery_line("overload", _overload_leg()) + "\n")
    # slower host, same containment quality: PASS
    cur = tmp_path / "cur.jsonl"
    cur.write_text(_battery_line("overload", _overload_leg(
        value=20.0, fault_free_tok_per_s=22.2,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert ok, [f.line() for f in findings]
    # containment collapsed (ratio 0.9 -> 0.71, a >20% drop): FAIL
    cur.write_text(_battery_line("overload", _overload_leg(
        goodput_ratio=0.71, vs_baseline=0.71,
    )) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert not ok
    assert any(
        f.check == "regression" and "goodput_ratio" in f.message
        for f in findings
    )
    # ratio missing on one side: SKIP, never raw tok/s
    leg = _overload_leg(value=20.0)
    del leg["goodput_ratio"]
    cur.write_text(_battery_line("overload", leg) + "\n")
    findings, ok = gatelib.gate(str(cur), str(prior))
    assert not any(f.check == "regression" for f in findings)


def test_gate_committed_overload_artifact():
    """The committed round-10 CPU-proxy artifact passes the gate, and
    passes as its own prior (run.sh step 0b4's shape)."""
    findings, ok = gatelib.gate(OVERLOAD_ARTIFACT, OVERLOAD_ARTIFACT)
    assert ok, [f.line() for f in findings]


def test_battery_has_round10_legs():
    from inferd_tpu.tools.bench_battery import DEFAULT_LEGS, SMOKE_LEGS

    names = {n for n, _, _ in DEFAULT_LEGS}
    assert "overload" not in names  # CPU-pinned node processes: no chip form
    smoke = dict((n, t) for n, t, _ in SMOKE_LEGS)
    assert "overload_tiny" in smoke
    assert "overload" in smoke["overload_tiny"]
    assert "--tiny" in smoke["overload_tiny"]
