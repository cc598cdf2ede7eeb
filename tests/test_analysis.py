"""jaxlint (inferd_tpu.analysis): per-rule fixtures, the repo self-scan
gate, and the runtime sanitizers.

Each rule gets one minimal positive and one negative fixture; J002, J003
and J006 additionally get regression fixtures reproducing the real
pre-fix bugs this PR fixed (the literal `default_backend() == "tpu"`
probe from ops/quant.py, the donated-cache-reuse shape, the
decode-loop host sync). The self-scan test is the CI gate: zero
non-baselined findings over inferd_tpu/ + tests/.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from inferd_tpu.analysis import (
    Baseline,
    NanError,
    RetraceError,
    RetraceGuard,
    check_paths,
    check_source,
    nan_guard,
)
from inferd_tpu.analysis import retrace_guard as retrace_guard_cm

REPO = Path(__file__).resolve().parents[1]


def rules_of(src: str):
    return sorted({f.rule for f in check_source(src)})


def findings(src: str, rule: str):
    return [f for f in check_source(src) if f.rule == rule]


# --------------------------------------------------------------- J001


def test_j001_python_scalar_param_not_static():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, n: int):\n"
        "    return x * n\n"
    )
    assert [f.rule for f in findings(src, "J001")] == ["J001"]


def test_j001_mutable_default_and_mutated_global():
    src = (
        "import jax\n"
        "STATE = 0\n"
        "def bump():\n"
        "    global STATE\n"
        "    STATE += 1\n"
        "@jax.jit\n"
        "def f(x, buf=[]):\n"
        "    return x + STATE\n"
    )
    msgs = [f.message for f in findings(src, "J001")]
    assert any("mutable default" in m for m in msgs)
    assert any("global `STATE`" in m for m in msgs)


def test_j001_negative_pytree_carry_annotation():
    # a fixed-structure pytree carry is the idiomatic NON-static jit arg
    src = (
        "import jax\n"
        "from typing import Tuple\n"
        "@jax.jit\n"
        "def step(carry: Tuple, x: tuple):\n"
        "    return carry, x\n"
    )
    assert findings(src, "J001") == []


def test_j001_negative_static_argnames():
    src = (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, static_argnames=('n',))\n"
        "def f(x, n: int):\n"
        "    return x * n\n"
    )
    assert findings(src, "J001") == []


# --------------------------------------------------------------- J002


DONATING_STEP = (
    "import jax\n"
    "from functools import partial\n"
    "@partial(jax.jit, donate_argnames=('cache',))\n"
    "def step(tok, cache):\n"
    "    return tok, cache\n"
)


def test_j002_use_after_donate():
    src = DONATING_STEP + (
        "def run(tok, cache):\n"
        "    out, _ = step(tok, cache)\n"
        "    return cache.sum()\n"
    )
    out = findings(src, "J002")
    assert len(out) == 1 and "donated" in out[0].message


def test_j002_loop_never_rebinds():
    # the decode-loop shape: donating the cache every iteration without
    # ever rebinding it re-donates a consumed buffer
    src = DONATING_STEP + (
        "def run(tok, cache):\n"
        "    for _ in range(8):\n"
        "        out = step(tok, cache)\n"
        "    return out\n"
    )
    out = findings(src, "J002")
    assert len(out) == 1 and "loop" in out[0].message


def test_j002_negative_rebound():
    src = DONATING_STEP + (
        "def run(tok, cache):\n"
        "    out, cache = step(tok, cache)\n"
        "    return cache.sum()\n"
        "def run_loop(tok, cache):\n"
        "    for _ in range(8):\n"
        "        tok, cache = step(tok, cache)\n"
        "    return tok\n"
    )
    assert findings(src, "J002") == []


def test_j002_jit_call_form_with_argnums():
    src = (
        "import jax\n"
        "def _step(tok, cache):\n"
        "    return tok, cache\n"
        "step = jax.jit(_step, donate_argnums=(1,))\n"
        "def run(tok, cache):\n"
        "    out, _ = step(tok, cache)\n"
        "    return cache.sum()\n"
    )
    assert len(findings(src, "J002")) == 1


def test_j002_negative_def_inside_loop_is_separate_scope():
    # a callback *defined* per iteration never executes in the loop —
    # its donating call must not be attributed to the loop body
    src = DONATING_STEP + (
        "def run(toks, cache):\n"
        "    cbs = []\n"
        "    for tok in toks:\n"
        "        def cb():\n"
        "            return step(tok, cache)\n"
        "        cbs.append(cb)\n"
        "    return cbs\n"
    )
    assert findings(src, "J002") == []


def test_j002_negative_conditional_call_rebound_in_outer_loop_body():
    # call sits in a nested if, the rebind in the outer loop body: the
    # loop DOES rebind every iteration — must not flag
    src = DONATING_STEP + (
        "def run(toks, cache):\n"
        "    for tok in toks:\n"
        "        if tok > 0:\n"
        "            out = step(tok, cache)\n"
        "        tok2, cache = out\n"
        "    return out\n"
    )
    assert findings(src, "J002") == []


# --------------------------------------------------------------- J003


def test_j003_sync_in_decode_loop():
    # the real pre-fix bug class: per-token host reads in a decode loop
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def decode(step, tok):\n"
        "    out = []\n"
        "    while len(out) < 8:\n"
        "        tok = step(tok, jnp.int32(1))\n"
        "        out.append(int(tok[0]))\n"
        "        np.asarray(tok)\n"
        "        tok.block_until_ready()\n"
        "    return out\n"
    )
    msgs = [f.message for f in findings(src, "J003")]
    assert len(msgs) == 3
    assert any("int(tok[0])" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert any("block_until_ready" in m for m in msgs)


def test_j003_sync_in_while_condition():
    # the canonical decode shape with the per-token sync in the TEST
    src = (
        "import jax.numpy as jnp\n"
        "def decode(step, tok, done):\n"
        "    while int(tok[0]) != 2:\n"
        "        tok = step(tok, jnp.int32(1))\n"
        "    while not done.item():\n"
        "        done = step(tok, jnp.int32(0))\n"
        "    return tok\n"
    )
    assert len(findings(src, "J003")) == 2


def test_j003_negative_host_only_loop():
    # int(line[0]) in a loop that never touches jax: not a device sync
    src = (
        "import jax\n"
        "def count(lines):\n"
        "    total = 0\n"
        "    for line in lines:\n"
        "        total += int(line[0])\n"
        "    return total\n"
    )
    assert findings(src, "J003") == []


def test_j003_negative_sync_outside_loop():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def summarize(x):\n"
        "    y = jnp.sum(x)\n"
        "    return np.asarray(y)\n"
    )
    assert findings(src, "J003") == []


# --------------------------------------------------------------- J004


def test_j004_print_and_np_random_under_jit():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    print('tracing', x)\n"
        "    return x + np.random.rand()\n"
    )
    msgs = [f.message for f in findings(src, "J004")]
    assert any("print" in m for m in msgs)
    assert any("np.random.rand" in m for m in msgs)


def test_j004_append_in_scan_body():
    src = (
        "from jax import lax\n"
        "def outer(xs):\n"
        "    acc = []\n"
        "    def body(c, x):\n"
        "        acc.append(x)\n"
        "        return c, x\n"
        "    return lax.scan(body, 0.0, xs)\n"
    )
    out = findings(src, "J004")
    assert len(out) == 1 and "acc" in out[0].message


def test_j004_negative_jax_random_and_local_append():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, key):\n"
        "    parts = []\n"
        "    parts.append(jax.random.normal(key, x.shape))\n"
        "    return x + parts[0]\n"
    )
    assert findings(src, "J004") == []


# --------------------------------------------------------------- J005


def test_j005_blocking_sleep_and_dropped_coroutine():
    src = (
        "import time\n"
        "async def worker():\n"
        "    time.sleep(1)\n"
        "async def main():\n"
        "    worker()\n"
    )
    msgs = [f.message for f in findings(src, "J005")]
    assert any("time.sleep" in m for m in msgs)
    assert any("never awaited" in m for m in msgs)


def test_j005_negative_awaited_and_other_object():
    # `other.start()` must NOT match an unrelated `async def start`
    # elsewhere in the module (the Balancer-vs-Node false positive)
    src = (
        "import asyncio\n"
        "class Node:\n"
        "    async def start(self):\n"
        "        await asyncio.sleep(0)\n"
        "    async def boot(self, balancer):\n"
        "        await self.start()\n"
        "        balancer.start()\n"
    )
    assert findings(src, "J005") == []


def test_j005_self_method_dropped():
    src = (
        "import asyncio\n"
        "class Node:\n"
        "    async def start(self):\n"
        "        await asyncio.sleep(0)\n"
        "    async def boot(self):\n"
        "        self.start()\n"
    )
    assert len(findings(src, "J005")) == 1


# --------------------------------------------------------------- J006


def test_j006_regression_prefix_quant_pattern():
    # the EXACT line ops/quant.py once carried: a platform choice made by
    # a string compare at the call site instead of the one probe helper
    src = (
        "import jax\n"
        "INT4_MODE = 'auto'\n"
        "def _int4_mode():\n"
        "    if INT4_MODE != 'auto':\n"
        "        return INT4_MODE\n"
        "    return 'dequant' if jax.default_backend() == 'tpu' else 'grouped'\n"
    )
    out = findings(src, "J006")
    assert len(out) == 1 and out[0].line == 6


def test_j006_tainted_variable_and_interpret_kwarg():
    # the other two pre-fix shapes: quant.py:251's `!=` kwarg and the
    # assigned-then-compared variable
    src = (
        "import jax\n"
        "def pick(kernel):\n"
        "    backend = jax.default_backend()\n"
        "    if backend == 'tpu':\n"
        "        return kernel(interpret=jax.default_backend() != 'tpu')\n"
        "    return None\n"
    )
    assert len(findings(src, "J006")) == 2


def test_j006_taint_is_per_scope():
    # an unrelated variable sharing the name `backend` in ANOTHER
    # function must not inherit the taint
    src = (
        "import jax\n"
        "def probe():\n"
        "    backend = jax.default_backend()\n"
        "    return backend\n"
        "def send(backend: str):\n"
        "    return backend == 'grpc'\n"
    )
    assert findings(src, "J006") == []


def test_j006_negative_helper():
    src = (
        "from inferd_tpu.utils.platform import is_tpu\n"
        "def pick():\n"
        "    return 'dequant' if is_tpu() else 'grouped'\n"
    )
    assert findings(src, "J006") == []


# ------------------------------------------------- suppressions/baseline


def test_inline_suppression_requires_reason():
    base = (
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'{}\n"
    )
    with_reason = base.format("  # jaxlint: disable=J006 -- fixture")
    without = base.format("  # jaxlint: disable=J006")
    assert findings(with_reason, "J006") == []
    bad = findings(without, "J006")
    assert len(bad) == 1 and "missing a `-- reason`" in bad[0].note


def test_suppression_in_string_literal_is_ignored():
    # quoting the directive syntax (docs, fixtures) must not actually
    # suppress anything — only real COMMENT tokens count
    src = (
        "import jax\n"
        "DOC = '# jaxlint: file-disable=J006 -- just quoting the syntax'\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'\n"
    )
    assert len(findings(src, "J006")) == 1


def test_reasonless_directive_does_not_shadow_file_disable():
    src = (
        "# jaxlint: file-disable=J006 -- fixture-wide reason\n"
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'  # jaxlint: disable=J006\n"
    )
    assert findings(src, "J006") == []


def test_j003_negative_orelse_runs_once():
    # a for/while `else:` clause runs ONCE after the loop — not per
    # iteration
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def reduce(xs, dev):\n"
        "    for x in xs:\n"
        "        dev = dev + jnp.float32(x)\n"
        "    else:\n"
        "        out = np.asarray(dev)\n"
        "    return out\n"
    )
    assert findings(src, "J003") == []


def test_j003_suppression_on_last_line_of_multiline_call():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def drain(step, t):\n"
        "    for _ in range(4):\n"
        "        t = step(t, jnp.int32(1))\n"
        "        v = np.asarray(\n"
        "            t)  # jaxlint: disable=J003 -- fixture: trailing the last line\n"
        "    return v\n"
    )
    assert findings(src, "J003") == []


def test_j003_negative_lambda_in_loop():
    # a callback *defined* in a loop doesn't sync per iteration
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def register(handlers, state):\n"
        "    cbs = []\n"
        "    for h in handlers:\n"
        "        s = jnp.sum(state)\n"
        "        cbs.append(lambda: np.asarray(s))\n"
        "    return cbs\n"
    )
    assert findings(src, "J003") == []


def test_baseline_empty_reason_entry_is_not_stale(tmp_path):
    src = (
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'\n"
    )
    f = check_source(src, path="pkg/mod.py")
    path = tmp_path / "base.json"
    Baseline.write(str(path), f)  # empty reasons
    b = Baseline.load(str(path))
    assert len(b.filter(list(f))) == 1  # does not suppress...
    assert b.unused() == []  # ...but matches code that exists: not stale


def test_baseline_count_limits_duplicate_occurrences(tmp_path):
    # a NEW duplicate of a baselined line must resurface, not ride the
    # existing entry
    one = (
        "import jax\n"
        "def pick():\n"
        "    a = jax.default_backend() == 'tpu'\n"
        "    return a\n"
    )
    two = (  # the SAME line duplicated -> identical fingerprint
        "import jax\n"
        "def pick():\n"
        "    a = jax.default_backend() == 'tpu'\n"
        "    a = jax.default_backend() == 'tpu'\n"
        "    return a\n"
    )
    path = tmp_path / "base.json"
    Baseline.write(str(path), check_source(one, path="m.py"))
    data = json.loads(path.read_text())
    assert data["entries"][0]["count"] == 1
    data["entries"][0]["reason"] = "fixture"
    path.write_text(json.dumps(data))
    b = Baseline.load(str(path))
    assert b.filter(check_source(one, path="m.py")) == []  # covered
    # a Baseline instance accumulates hits for ONE scan; load fresh
    leaked = Baseline.load(str(path)).filter(check_source(two, path="m.py"))
    assert len(leaked) == 1 and "NEW duplicate" in leaked[0].note


def test_write_baseline_preserves_reasons(tmp_path):
    # regenerating the baseline must carry hand-written reasons over
    src = (
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'\n"
    )
    mod = tmp_path / "m.py"
    mod.write_text(src)
    base = tmp_path / "base.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(*extra):
        return subprocess.run(
            [sys.executable, "-m", "inferd_tpu.analysis", "check",
             str(mod), *extra],
            capture_output=True, text=True, env=env, cwd=str(REPO),
        )

    run("--baseline", "none", "--write-baseline", str(base))
    data = json.loads(base.read_text())
    data["entries"][0]["reason"] = "hand-written justification"
    base.write_text(json.dumps(data))
    r = run("--baseline", "none", "--write-baseline", str(base))
    assert "1 with carried-over reasons" in r.stdout, r.stdout
    data = json.loads(base.read_text())
    assert data["entries"][0]["reason"] == "hand-written justification"
    # also across directories: entries re-key into the new file's frame
    sub = tmp_path / "sub"
    sub.mkdir()
    r = run("--baseline", str(base), "--write-baseline", str(sub / "b2.json"))
    assert "1 with carried-over reasons" in r.stdout, r.stdout
    data2 = json.loads((sub / "b2.json").read_text())
    assert data2["entries"][0]["reason"] == "hand-written justification"
    assert data2["entries"][0]["file"] == "../m.py"
    # a PARTIAL refresh (--rules subset) must keep out-of-scope entries
    # verbatim instead of silently deleting them and their reasons
    r = run("--baseline", str(base), "--rules", "J003",
            "--write-baseline", str(base))
    assert "1 out-of-scope entry kept" in r.stdout, r.stdout
    data3 = json.loads(base.read_text())
    assert len(data3["entries"]) == 1
    assert data3["entries"][0]["rule"] == "J006"
    assert data3["entries"][0]["reason"] == "hand-written justification"


def test_chip_probe_refuses_wrong_backend(monkeypatch):
    # once a backend exists, the main() pin cannot switch it; the probe
    # must refuse rather than time another device under the asked name
    import inferd_tpu.utils.platform as plat
    from inferd_tpu.tools import chip_probe

    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "cpu"))
    monkeypatch.setattr(plat, "force_platform", lambda d: None)
    monkeypatch.setattr(plat, "device_facts", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
    })
    assert chip_probe.main(["--device=cpu", "--small", "--skip-model"]) == 2


def test_chip_probe_tpu_request_on_cpu_gets_mismatch_message(
    capsys, monkeypatch
):
    # the honest diagnostic, not 'pass --device cpu to probe the host'.
    # main()'s force_platform mutates JAX_PLATFORMS + jax config; register
    # the env key with monkeypatch and restore the config so later tests'
    # subprocesses never inherit a "tpu" pin
    import jax

    from inferd_tpu.tools import chip_probe

    jax.devices()  # the cpu backend exists: main()'s pin is then a no-op
    # and the verification after it is what must catch the mismatch
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "cpu"))
    try:
        rc = chip_probe.main(["--device=tpu", "--small", "--skip-model"])
    finally:
        jax.config.update("jax_platforms", "cpu")
    assert rc == 2
    err = capsys.readouterr().err
    assert "--device tpu was requested but JAX resolved 'cpu'" in err


def test_baseline_roundtrip_and_empty_reason(tmp_path):
    src = (
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'\n"
    )
    f = check_source(src, path="pkg/mod.py")
    assert len(f) == 1
    path = tmp_path / "base.json"
    Baseline.write(str(path), f)
    b = Baseline.load(str(path))
    # empty reason does not suppress
    assert len(b.filter(list(f))) == 1
    data = json.loads(path.read_text())
    data["entries"][0]["reason"] = "fixture"
    path.write_text(json.dumps(data))
    b = Baseline.load(str(path))
    assert b.filter(list(f)) == []
    assert b.unused() == []


def test_self_scan_zero_unbaselined_findings():
    """The CI gate: the committed baseline covers everything, nothing
    else fires across the package, the test tree, and the root-level
    entry points (bench.py is where the J006 bug class actually lived)."""
    found = check_paths(
        [
            str(REPO / "inferd_tpu"),
            str(REPO / "tests"),
            str(REPO / "bench.py"),
            str(REPO / "__graft_entry__.py"),
        ],
        rel_to=str(REPO),
    )
    baseline = Baseline.load(str(REPO / "analysis-baseline.json"))
    remaining = baseline.filter(found)
    assert remaining == [], "\n".join(f.render() for f in remaining)


def test_cli_check_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "check", str(bad),
         "--baseline", "none"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 1 and "J006" in r.stdout
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "check", str(ok),
         "--baseline", "none"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # a mistyped scan path must FAIL the gate, not silently scan nothing
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "check",
         str(tmp_path / "no_such_dir"), "--baseline", "none"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 2 and "does not exist" in r.stderr
    # ...and so must an existing file that isn't Python (e.g. a typo'd
    # `bench.sh` for `bench.py`): scanning nothing must not pass
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "check", "run.sh",
         "--baseline", "none"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 2 and "not a Python file" in r.stderr


def test_cli_gate_matches_baseline_from_any_cwd():
    # finding fingerprints are relative to the baseline file's directory,
    # so invoking the gate from a subdirectory still matches entries; and
    # entries for files OUTSIDE the scanned paths are not called stale
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO)
    )
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "check",
         "../inferd_tpu/core/batch.py",
         "--baseline", "../analysis-baseline.json"],
        capture_output=True, text=True, env=env, cwd=str(REPO / "tests"),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stderr and "4 baselined" in r.stderr
    assert "stale" not in r.stderr


def test_cli_rules_subset_does_not_misreport_stale_baseline():
    # scanning with --rules J006 must not flag the J003 baseline entries
    # as stale (they never got a chance to match this run)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "check",
         "inferd_tpu/", "tests/", "bench.py", "__graft_entry__.py",
         "--baseline", "analysis-baseline.json", "--rules", "J006",
         "--warn-unused-baseline"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stale" not in r.stderr


# ------------------------------------------------------ chip_probe fixes


def test_chip_probe_layers_step_kv_write_survives_dce():
    """regression for the layers_ms undercount: with the KV buffers
    threaded through the probe's carry, the compiled loop must keep the
    update (dynamic-update-slice) alive. (Returned-and-dropped, XLA once
    DCE'd the cache write out of the scan; since the layer scan carries
    the stacked cache and a layer's attention reads the stack its write
    returned, that shape keeps its writes too.)"""
    import jax
    import jax.numpy as jnp

    from inferd_tpu.config import get_config
    from inferd_tpu.core.cache import KVCache
    from inferd_tpu.models import qwen3

    cfg = get_config("tiny")
    params = qwen3.init_params(cfg, jax.random.PRNGKey(0))
    cache = KVCache.create(cfg, cfg.num_layers, 1, 64, ring=False)
    pos = jnp.full((1, 1), 3, jnp.int32)
    h0 = jnp.ones((1, 1, cfg.hidden_size), cfg.jnp_dtype)

    def fwd(h, k, v):
        h, nc, _ = qwen3.forward_layers_cached(
            params["layers"], cfg, h, pos, KVCache(k=k, v=v, length=cache.length),
            jnp.int32(3),
        )
        return h, nc.k, nc.v

    @jax.jit
    def dead(x):  # the pre-fix shape: KV returned and dropped
        def body(c, _):
            out, _, _ = fwd(c, cache.k, cache.v)
            return out, None

        return jax.lax.scan(body, x, None, length=2)[0]

    @jax.jit
    def live(x):  # the fixed shape: KV threaded through the carry
        def body(c, _):
            h, k, v = c
            return fwd(h, k, v), None

        return jax.lax.scan(body, x, None, length=2)[0]

    def dus_count(fn, arg):
        txt = fn.lower(arg).compile().as_text()
        return txt.count("dynamic-update-slice")

    n_live = dus_count(live, (h0, cache.k, cache.v))
    n_dead = dus_count(dead, h0)
    assert n_live > 0, "carried KV write was eliminated"
    assert n_live >= n_dead > 0, (
        f"a layer's attention reads what the layer wrote: neither shape may "
        f"lose cache writes to DCE (live={n_live}, dead={n_dead})"
    )


# ------------------------------------------------------------ sanitizers


def test_retrace_guard_catches_shape_unstable_loop():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return x * 2

    step(jnp.ones((4,)))  # warm
    with pytest.raises(RetraceError, match="step"):
        with retrace_guard_cm() as g:
            g.register(step)
            for n in range(1, 4):  # deliberately shape-unstable
                step(jnp.ones((n,)))


def test_retrace_guard_stable_loop_passes():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return x + 1

    step(jnp.ones((4,)))
    with retrace_guard_cm() as g:
        g.register(step)
        for _ in range(5):
            step(jnp.ones((4,)))
    assert g.traces("step") == 0


def test_retrace_guard_instrument_path():
    import jax
    import jax.numpy as jnp

    g = RetraceGuard()  # default budget 0 RE-traces
    f = jax.jit(g.instrument(lambda x: x + 1, name="inc"))
    f(jnp.ones((2,)))  # initial compile is free, not a re-trace
    f(jnp.ones((2,)))  # same shape: no retrace
    assert g.traces("inc") == 0  # same convention as the register() path
    g.check()
    f(jnp.ones((3,)))  # retrace
    with pytest.raises(RetraceError, match="inc"):
        g.check()


def test_retrace_guard_fixture(retrace_guard):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return x - 1

    step(jnp.ones((2,)))
    retrace_guard.register(step)
    step(jnp.ones((2,)))  # fixture's teardown check must pass


def test_nan_guard():
    import jax.numpy as jnp

    @nan_guard
    def bad(x):
        return {"h": x, "lp": jnp.log(x - 1.0)}  # log(0) = -inf

    @nan_guard
    def good(x):
        return {"h": x * 2, "ids": jnp.ones((2,), jnp.int32)}

    good(jnp.ones((2,)))
    with pytest.raises(NanError, match="lp"):
        bad(jnp.ones((2,)))


# ------------------------------------------- J007 lock-order (project)


def test_j007_inversion_fires():
    # the seeded inversion fixture: mu (rank 2) held while taking the
    # device lock (rank 1) — the static half of the double catch (the
    # dynamic half is tests/test_lockwatch.py's live WatchedLock raise)
    src = (
        "class Exec:\n"
        "    def step(self):\n"
        "        with self._mu:\n"
        "            with self._dev_lock:\n"
        "                pass\n"
    )
    out = findings(src, "J007")
    assert len(out) == 1
    assert "'dev' while holding 'mu'" in out[0].message


def test_j007_canonical_order_passes():
    src = (
        "class Exec:\n"
        "    def step(self):\n"
        "        with self._dev_lock:\n"
        "            with self._mu:\n"
        "                pass\n"
    )
    assert findings(src, "J007") == []


def test_j007_blocking_acquire_edge_and_bounded_exemption():
    fires = (
        "class Exec:\n"
        "    def a(self):\n"
        "        with self._mu:\n"
        "            self._dev_lock.acquire()\n"
    )
    assert len(findings(fires, "J007")) == 1
    bounded = (
        "class Exec:\n"
        "    def a(self):\n"
        "        with self._mu:\n"
        "            if not self._dev_lock.acquire(blocking=False):\n"
        "                return\n"
        "    def b(self):\n"
        "        with self._mu:\n"
        "            self._dev_lock.acquire(timeout=0.1)\n"
    )
    assert findings(bounded, "J007") == []


def test_j007_reverse_nesting_names_the_deadlock_pair():
    src = (
        "class Exec:\n"
        "    def a(self):\n"
        "        with self._dev_lock:\n"
        "            with self._mu:\n"
        "                pass\n"
        "    def b(self):\n"
        "        with self._mu:\n"
        "            with self._dev_lock:\n"
        "                pass\n"
    )
    out = findings(src, "J007")
    assert len(out) == 1
    assert "reverse nesting exists" in out[0].message
    assert "deadlock" in out[0].message


def test_j007_class_qualified_lock_names():
    # StandbyStore._mu is 'repl' (rank 4) — under the device lock (rank
    # 1) that is canonical, NOT an inversion of the executor 'mu'
    ok = (
        "class StandbyStore:\n"
        "    def apply(self):\n"
        "        with self._dev_lock:\n"
        "            with self._mu:\n"
        "                pass\n"
    )
    assert findings(ok, "J007") == []
    # WindowedBatcher._mu is 'window' (rank 5): taking the device lock
    # under it contradicts the canonical order
    bad = (
        "class WindowedBatcher:\n"
        "    def flush(self):\n"
        "        with self._mu:\n"
        "            with self._dev_lock:\n"
        "                pass\n"
    )
    out = findings(bad, "J007")
    assert len(out) == 1 and "'window'" in out[0].message


def test_j007_multi_item_with_is_sequential():
    src = (
        "class Exec:\n"
        "    def a(self):\n"
        "        with self._mu, self._dev_lock:\n"
        "            pass\n"
    )
    assert len(findings(src, "J007")) == 1
    ok = (
        "class Exec:\n"
        "    def a(self):\n"
        "        with self._dev_lock, self._mu:\n"
        "            pass\n"
    )
    assert findings(ok, "J007") == []


# ---------------------------------------- J008 host work under dev lock


def test_j008_host_io_under_device_lock():
    src = (
        "import time\n"
        "class Exec:\n"
        "    def step(self):\n"
        "        with self._dev_lock:\n"
        "            time.sleep(0.01)\n"
        "            open('/tmp/x').read()\n"
    )
    out = findings(src, "J008")
    assert len(out) == 2
    assert any("time.sleep" in f.message for f in out)
    assert any("open" in f.message for f in out)


def test_j008_negative_boundary_fetch_and_outside():
    # np.asarray under the device lock is the DESIGNED boundary
    # transfer; host I/O outside the lock is fine
    src = (
        "import time\n"
        "import numpy as np\n"
        "class Exec:\n"
        "    def step(self):\n"
        "        with self._dev_lock:\n"
        "            out = np.asarray(self.logits)\n"
        "        time.sleep(0.01)\n"
        "        return out\n"
    )
    assert findings(src, "J008") == []


def test_j008_negative_other_lock():
    src = (
        "import time\n"
        "class Exec:\n"
        "    def step(self):\n"
        "        with self._mu:\n"
        "            time.sleep(0.01)\n"
    )
    assert findings(src, "J008") == []


# ------------------------------------------- J009 blocking in async def


def test_j009_sync_lock_in_async_handler():
    # the seeded blocking-async fixture: static half of the double
    # catch (the dynamic half is the LoopStallDetector live test)
    src = (
        "class Node:\n"
        "    async def handle(self, request):\n"
        "        with self._mu:\n"
        "            return self.sessions.copy()\n"
    )
    out = findings(src, "J009")
    assert len(out) == 1
    assert "sync `with` on threading lock 'mu'" in out[0].message


def test_j009_unbounded_acquire_and_inline_dispatch():
    src = (
        "class Node:\n"
        "    async def handle(self, request):\n"
        "        self._mu.acquire()\n"
        "        return self.executor.process(request)\n"
    )
    out = findings(src, "J009")
    assert len(out) == 2
    assert any("unbounded `.acquire()`" in f.message for f in out)
    assert any("dispatches jit work inline" in f.message for f in out)


def test_j009_negative_bounded_and_executor_hop():
    src = (
        "import asyncio\n"
        "class Node:\n"
        "    async def handle(self, request):\n"
        "        if not self._mu.acquire(blocking=False):\n"
        "            return None\n"
        "        self._mu.release()\n"
        "        loop = asyncio.get_running_loop()\n"
        "        return await loop.run_in_executor(\n"
        "            None, self.executor.process, request\n"
        "        )\n"
    )
    assert findings(src, "J009") == []


def test_j009_negative_sync_def_untouched():
    src = (
        "class Node:\n"
        "    def snapshot(self):\n"
        "        with self._mu:\n"
        "            return dict(self.sessions)\n"
    )
    assert findings(src, "J009") == []


# ------------------------------------------ J010 cross-thread registries


def test_j010_direct_metric_dict_write():
    src = (
        "def reset(m):\n"
        "    m.counters['c'] = 0.0\n"
        "    m.gauges['g'] += 1\n"
    )
    out = findings(src, "J010")
    assert len(out) == 2
    assert all("Metrics._lock" in f.message for f in out)


def test_j010_negative_inside_metrics_and_api():
    src = (
        "class Metrics:\n"
        "    def inc(self, name, by=1.0):\n"
        "        with self._lock:\n"
        "            self.counters[name] = self.counters.get(name, 0) + by\n"
        "def use(m):\n"
        "    m.inc('c')\n"
    )
    assert findings(src, "J010") == []


def test_j010_ring_buffer_mutation_outside_owner():
    src = (
        "class Sweeper:\n"
        "    def drop(self, journal):\n"
        "        journal._buf.clear()\n"
    )
    out = findings(src, "J010")
    assert len(out) == 1 and "_buf" in out[0].message
    owner = (
        "class EventJournal:\n"
        "    def emit(self, etype):\n"
        "        with self._lock:\n"
        "            self._buf.append(etype)\n"
    )
    assert findings(owner, "J010") == []


# --------------------------------------------- J011 stale disables


def test_j011_stale_disable_fires():
    src = "x = 1  # jaxlint: disable=J005 -- excused a sleep long gone\n"
    out = findings(src, "J011")
    assert len(out) == 1
    assert "suppresses nothing" in out[0].message


def test_j011_live_disable_passes():
    # the directive still suppresses a real J006 finding -> not stale
    src = (
        "import jax\n"
        "def pick():\n"
        "    return jax.default_backend() == 'tpu'  "
        "# jaxlint: disable=J006 -- fixture\n"
    )
    assert findings(src, "J006") == []
    assert findings(src, "J011") == []


def test_j011_audit_skips_inactive_rules():
    # a --rules run that never evaluated J005 cannot judge its disables
    src = "x = 1  # jaxlint: disable=J005 -- maybe still needed\n"
    from inferd_tpu.analysis.rules import ALL_RULES

    subset = [r for r in ALL_RULES if r.id in ("J006", "J011")]
    assert check_source(src, rules=subset) == []


# ------------------------------------------- parallel scan (--jobs)


def test_jobs_parallel_matches_serial():
    paths = [
        str(REPO / "inferd_tpu" / "analysis"),
        str(REPO / "inferd_tpu" / "utils"),
    ]
    serial = check_paths(paths, rel_to=str(REPO))
    parallel = check_paths(paths, rel_to=str(REPO), jobs=2)
    assert [f.fingerprint() for f in serial] == [
        f.fingerprint() for f in parallel
    ]


def test_step0_wall_time_budget():
    """run.sh step 0's scan must stay under its 30 s budget — the gate
    only stays HARD while it is cheap enough that nobody routes around
    it."""
    import time as _time

    t0 = _time.perf_counter()
    check_paths(
        [
            str(REPO / "inferd_tpu"),
            str(REPO / "tests"),
            str(REPO / "bench.py"),
            str(REPO / "__graft_entry__.py"),
        ],
        rel_to=str(REPO),
        jobs=os.cpu_count() or 1,
    )
    elapsed = _time.perf_counter() - t0
    assert elapsed < 30.0, f"step-0 scan took {elapsed:.1f}s (budget 30s)"


# --------------------------------------------- contracts drift lint


def _contracts_slice(tmp_path, code, doc, allow=None):
    (tmp_path / "inferd_tpu").mkdir(exist_ok=True)
    (tmp_path / "inferd_tpu" / "mod.py").write_text(code)
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(doc)
    if allow is not None:
        (tmp_path / "analysis-contracts.json").write_text(json.dumps(allow))
    from inferd_tpu.analysis.contracts import run_contracts

    return run_contracts(str(tmp_path))


CONTRACTS_DOC = (
    "# obs\n\n"
    "| event | emitted by | meaning |\n"
    "|-------|-----------|---------|\n"
    "| `thing.start` | mod | it began |\n"
    "| `thing.ghost` | mod | never actually emitted |\n\n"
    "| key | meaning |\n"
    "|-----|---------|\n"
    "| `load` | inflight count |\n\n"
    "The `requests` counter counts requests.\n"
)

CONTRACTS_CODE = (
    "class N:\n"
    "    def go(self):\n"
    "        self.journal.emit('thing.start', x=1)\n"
    "        self.journal.emit('thing.new')\n"
    "        self.metrics.inc('requests')\n"
    "        self.dht.announce({'load': 1, 'mystery': 2})\n"
)


def test_contracts_distinct_drift_codes(tmp_path):
    found, code, _allow = _contracts_slice(
        tmp_path, CONTRACTS_CODE, CONTRACTS_DOC
    )
    by_code = {f.code: f.name for f in found}
    # undocumented emitted event / dead doc row / ungated gossip key
    assert by_code.get("C001") == "thing.new"
    assert by_code.get("C002") == "thing.ghost"
    assert by_code.get("C003") == "mystery"
    assert "C005" not in by_code  # `requests` is doc-tokened
    assert code.events["thing.start"][0] == "mod.py"


def test_contracts_allowlist_needs_reason(tmp_path):
    reasoned = {
        "version": 1,
        "allow": [
            {"code": "C003", "name": "mystery", "reason": "rollout gap"},
            {"code": "C001", "name": "thing.new", "reason": "doc follows"},
            {"code": "C002", "name": "thing.ghost", "reason": "dynamic"},
            {"code": "C004", "name": "never_used", "reason": "stale entry"},
        ],
    }
    found, _code, allow = _contracts_slice(
        tmp_path, CONTRACTS_CODE, CONTRACTS_DOC, allow=reasoned
    )
    assert found == []
    # the C004 entry matched nothing: reported stale, not silently kept
    assert [e["name"] for e in allow.unused()] == ["never_used"]


def test_contracts_reasonless_allowlist_entry_never_suppresses(tmp_path):
    bare = {
        "version": 1,
        "allow": [{"code": "C003", "name": "mystery", "reason": "  "}],
    }
    found, _code, _allow = _contracts_slice(
        tmp_path, CONTRACTS_CODE, CONTRACTS_DOC, allow=bare
    )
    assert any(f.code == "C003" and f.name == "mystery" for f in found)


def test_contracts_metric_families_and_wildcards(tmp_path):
    code = (
        "class N:\n"
        "    def go(self):\n"
        "        self.metrics.observe('hop.wire_ms', 1.0)\n"
        "        self.metrics.set_gauge('repl.lag_tokens', 2.0)\n"
        "        self.metrics.inc('orphan.series')\n"
    )
    doc = (
        "# obs\n\n"
        "| event | emitted by | meaning |\n"
        "|-------|-----------|---------|\n\n"
        "| key | meaning |\n"
        "|-----|---------|\n\n"
        "* `inferd_hop_wire_ms` histogram\n"
        "* `inferd_repl_*` — the replication family\n"
    )
    found, _code, _allow = _contracts_slice(tmp_path, code, doc)
    names = {(f.code, f.name) for f in found}
    assert ("C005", "orphan.series") in names
    assert not any(n == "hop.wire_ms" for _c, n in names)
    assert not any(n == "repl.lag_tokens" for _c, n in names)


def test_contracts_repo_self_scan_clean():
    """The CI gate's second half: the real tree's emitted vocabulary
    matches docs/OBSERVABILITY.md (modulo the reasoned allowlist)."""
    from inferd_tpu.analysis.contracts import run_contracts

    found, _code, allow = run_contracts(str(REPO))
    assert found == [], "\n".join(f.render() for f in found)
    assert allow.unused() == [], allow.unused()


def test_contracts_cli_exit_codes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    (tmp_path / "inferd_tpu").mkdir()
    (tmp_path / "inferd_tpu" / "m.py").write_text(
        "def f(j):\n    j.emit('lonely.event')\n"
    )
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(
        "| event | meaning |\n|---|---|\n"
    )
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "contracts",
         "--root", str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 1 and "C001" in r.stdout
    (tmp_path / "docs" / "OBSERVABILITY.md").write_text(
        "| event | meaning |\n|---|---|\n| `lonely.event` | doc |\n"
    )
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "contracts",
         "--root", str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(
        [sys.executable, "-m", "inferd_tpu.analysis", "contracts",
         "--root", str(tmp_path / "nowhere")],
        capture_output=True, text=True, env=env, cwd=str(REPO),
    )
    assert r.returncode == 2
