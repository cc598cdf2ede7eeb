"""Test harness: force JAX onto a virtual 8-device CPU platform so mesh /
sharding / collective tests run without TPU hardware (the driver separately
dry-runs the multi-chip path; see __graft_entry__.dryrun_multichip).

Tests never touch a chip: the env var pins the CPU backend for a jax that is
not imported yet and for every child process a test starts, and the config
update pins it for a jax that something imported earlier.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The persistent compilation cache stays OFF in this process and in every
# child a test starts (jax's own switch; the CLIs enable the cache
# unconditionally — utils.platform.enable_compile_cache — and the cache
# tests switch it back on for their own children). It was tried here
# (halves warm re-runs) and reverted: XLA:CPU AOT results recorded by one
# process can fail feature validation when reloaded by another on the same
# host ("Machine type used for XLA:CPU compilation doesn't match...",
# cpu_aot_loader.cc) and risk SIGILL mid-test — observed crashing a node
# subprocess in tests/test_batch_node.py.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# Tests assume the FROZEN `auto` dispatch heuristics (ops/attention,
# ops/quant). A committed bench_artifacts/autotune.json would silently
# flip them per-chip (that's its job in serving), so point the registry
# at a path that never exists; autotune tests override per-test.
os.environ.setdefault("INFERD_AUTOTUNE", os.devnull + ".absent-autotune.json")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402

from inferd_tpu.utils import lockwatch  # noqa: E402

# Suite-wide lock-order sanitizer (docs/ANALYSIS.md): every named lock
# the runtime constructs during tests becomes an order-checking proxy,
# and a blocking acquisition that contradicts lockwatch.LOCK_ORDER
# RAISES — an inversion anywhere in tier-1 is a test failure, not a
# latent production deadlock. Kill switch: INFERD_LOCKWATCH=0 (e.g. to
# bisect whether a failure is the sanitizer's). instrument() must run at
# import time, before any executor/node constructs its locks.
if os.environ.get("INFERD_LOCKWATCH", "").strip().lower() not in (
    "0", "off", "false", "no"
):
    lockwatch.instrument(strict=True)


class Ports:
    """One test module's block of ports on 127.0.0.1: node `idx` of the
    module serves HTTP on `http(idx)` and gossips (UDP) on `gossip(idx)`."""

    #: a module's `idx` stays under HTTP_SLOTS; a gossip port is GOSSIP_AT
    #: past the node's HTTP port (TCP and UDP do not meet)
    HTTP_SLOTS, GOSSIP_AT = 200, 100
    WIDTH = HTTP_SLOTS + GOSSIP_AT
    #: blocks start here and end under the kernel's ephemeral range (32768)
    FIRST, END = 4096, 32768

    def __init__(self, base: int):
        self.base = base

    def http(self, idx: int = 0) -> int:
        assert 0 <= idx < self.HTTP_SLOTS, idx
        return self.base + idx

    def gossip(self, idx: int = 0) -> int:
        return self.http(idx) + self.GOSSIP_AT


def port_block(test_file: str) -> Ports:
    """The block of the test module in `test_file` (hand it `__file__`): by
    the module's place among tests/test_*.py, so no two modules overlap
    whichever xdist workers run them, and nobody keeps a table. A helper
    imported from another module takes the IMPORTER's block as an argument
    (test_node_e2e._mk_node's `ports`)."""
    here = os.path.dirname(os.path.abspath(__file__))
    files = sorted(f for f in os.listdir(here) if f.startswith("test_") and f.endswith(".py"))
    base = Ports.FIRST + files.index(os.path.basename(test_file)) * Ports.WIDTH
    assert Ports.FIRST + len(files) * Ports.WIDTH <= Ports.END, "tests/ outgrew its ports: narrow Ports.WIDTH"
    return Ports(base)


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run test in an event loop")
    config.addinivalue_line(
        "markers", "slow: long-running e2e/soak test (minutes, not seconds)"
    )


def pytest_pyfunc_call(pyfuncitem):
    """Minimal async test support (pytest-asyncio isn't installed here).

    When lockwatch is on (suite default), each async test's loop also
    runs a LoopStallDetector: stalls are RECORDED (journal hook only, a
    stall never fails a test by itself — CI boxes under load would flake)
    so stall-detection tests and postmortems can read them."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            n: pyfuncitem.funcargs[n] for n in pyfuncitem._fixtureinfo.argnames
        }
        if lockwatch.watching():

            async def _with_stall_watch():
                det = lockwatch.LoopStallDetector().start()
                try:
                    await fn(**kwargs)
                finally:
                    det.stop()

            asyncio.run(_with_stall_watch())
        else:
            asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs[:8]


def own_lane_programs(eng):
    """Give a BatchedEngine programs of its own, apart from its
    configuration's shared set (core.batch.lane_programs): for a test that
    patches what the model reads when it is TRACED (a function, a module
    constant) and so must not hand its traces to, or take them from, any
    other engine. The instance's attributes shadow; the shared set is not
    touched."""
    from inferd_tpu.core import batch

    made = batch.lane_programs.__wrapped__(
        eng.cfg, eng.sampling, eng.lanes, eng.pool is not None)
    for name, program in made._asdict().items():
        setattr(eng, "_" + name, program)
    return eng


@pytest.fixture
def retrace_guard():
    """Hot-loop retrace sanitizer (inferd_tpu.analysis.sanitizers): register
    jitted step fns after warmup; the teardown check fails the test if any
    of them re-traced during the test body. See docs/ANALYSIS.md."""
    from inferd_tpu.analysis.sanitizers import RetraceGuard

    guard = RetraceGuard()
    yield guard
    guard.check()
