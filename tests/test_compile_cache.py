"""The persistent compilation cache rule (utils.platform.enable_compile_cache):
where JAX_COMPILATION_CACHE_DIR is set the cache lives there and no directory
is set in code; where it is not, one fixed path inside the checkout. A jit
run populates it, and a second process sharing it warm-starts from the cached
executables.

Everything runs in SUBPROCESSES that switch the cache back on for
themselves: the pytest process and every other child keep it off
(JAX_ENABLE_COMPILATION_CACHE=false, tests/conftest.py) — XLA:CPU AOT
artifacts recorded by one process can fail feature validation when reloaded
by a sibling on the same host and risk SIGILL."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
from inferd_tpu.utils.platform import compile_cache_dir, enable_compile_cache
before = jax.config.jax_compilation_cache_dir
stats = enable_compile_cache()
import jax.numpy as jnp
out = 0.0
if "nojit" not in sys.argv:
    out = jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(1017.0))
print(json.dumps({
    "result": float(out), "config_before": before,
    "config_after": jax.config.jax_compilation_cache_dir,
    "dir": stats.directory, "rule_dir": compile_cache_dir(),
    "hits": stats.hits, "misses": stats.misses,
}))
"""


def _run(cache_env, *argv):
    """One child with the cache switched on; `cache_env` is the value of
    JAX_COMPILATION_CACHE_DIR, or None to leave it unset."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true")
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, *argv], capture_output=True,
        text=True, timeout=300, env=env, cwd=REPO,
    )
    return r, (json.loads(r.stdout.strip().splitlines()[-1])
               if r.returncode == 0 else None)


def test_env_dir_is_the_only_place_and_is_not_set_in_code(tmp_path):
    d = str(tmp_path / "cc")
    r1, o1 = _run(d)
    assert r1.returncode == 0, r1.stderr[-800:]
    # jax read the variable itself; the function set no directory
    assert o1["config_before"] == d and o1["config_after"] == d
    assert o1["dir"] == d and o1["rule_dir"] == d
    assert os.listdir(d), "compilation cache dir empty after a jit run"
    assert o1["hits"] == 0 and o1["misses"] >= 1

    # warm start: a SECOND process sharing the dir must produce the same
    # result from the cached executable. XLA:CPU's AOT loader is known to
    # reject same-host artifacts on feature-validation grounds in some
    # environments (conftest note) — that exact failure mode skips rather
    # than fails, anything else is a real bug.
    r2, o2 = _run(d)
    if r2.returncode != 0:
        blob = (r2.stderr + r2.stdout)[-2000:]
        if "XLA:CPU" in blob or "Machine type" in blob or "cpu_aot" in blob:
            pytest.skip(f"XLA:CPU AOT reload rejected on this host: {blob[-200:]}")
        raise AssertionError(blob)
    assert o2["result"] == o1["result"]


def test_unset_env_uses_the_fixed_path_in_the_checkout():
    """Two processes, no variable: the same path both times, inside the
    checkout, git-ignored — never one built from a temporary name, a pid
    or the time."""
    from inferd_tpu.utils.platform import DEFAULT_COMPILE_CACHE_DIR

    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    outs = []
    for _ in range(2):
        # (no jit here: the checkout's cache is shared with real runs, and
        # XLA:CPU entries written by one process can trip the next)
        r, o = _run(None, "nojit")
        assert r.returncode == 0, r.stderr[-800:]
        outs.append(o)
    for o in outs:
        assert o["config_before"] is None
        assert o["config_after"] == o["dir"] == DEFAULT_COMPILE_CACHE_DIR


def test_jax_switch_keeps_the_cache_off():
    """JAX_ENABLE_COMPILATION_CACHE=false (what conftest exports for this
    suite and its children) wins over enable_compile_cache: nothing is
    read or written."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=300, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-800:]
    o = json.loads(r.stdout.strip().splitlines()[-1])
    assert o["hits"] == 0 and o["misses"] == 0


def test_compile_cache_hits_counted_across_processes(tmp_path):
    """The substrate-independent witness: the SECOND process records
    persistent-cache HITS via jax.monitoring — an auditable number showing
    re-jit was avoided, not inferred from timing. Uses bench.py's
    _CC_SCRIPT (one definition — the same code the bench leg runs) on the
    tiny model. (Where XLA:CPU rejects the AOT reload, hits stay 0 and the
    test skips — anything else is a real bug. No timing assert: sub-second
    compiles on a timeshared host would flake; the hit count IS the proof.)"""
    sys.path.insert(0, REPO)
    import bench

    d = str(tmp_path / "cc")
    outs = []
    for _ in range(2):
        r = subprocess.run(
            [sys.executable, "-c", bench._CC_SCRIPT, "cpu", "tiny"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=d,
                     JAX_ENABLE_COMPILATION_CACHE="true"),
            cwd=REPO,
        )
        assert r.returncode == 0, r.stderr[-800:]
        outs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    cold, warm = outs
    assert cold["cache_dir"] == warm["cache_dir"] == d
    assert cold["hits"] == 0
    if warm["hits"] == 0:
        pytest.skip("persistent-cache reload unavailable on this host")
    assert warm["hits"] >= 1
