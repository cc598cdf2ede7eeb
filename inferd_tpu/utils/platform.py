"""JAX platform selection, device facts, and the persistent compile cache.

A CLI pins its platform with `force_platform` before the first backend
initialization; `jax.config.update("jax_platforms", ...)` wins even when
jax was imported earlier with another JAX_PLATFORMS in the environment. A
platform that was asked for by name and cannot be initialized is an error
(`require_platform`), never a reason to compute somewhere else.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Union

# Fixed, git-ignored home of the persistent compilation cache when
# JAX_COMPILATION_CACHE_DIR does not place it from outside: one path per
# checkout, so every process of a run (node, plain engine, bench children)
# finds what the others compiled. Never a temporary name — a cache that
# moves never hits.
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def is_tpu() -> bool:
    """True when the active JAX backend is a TPU. The one probe helper:
    kernel-vs-interpret choices, quant schemes and chip tables ask this
    instead of comparing platform strings at each site (jaxlint J006)."""
    import jax

    return jax.default_backend() == "tpu"  # jaxlint: disable=J006 -- the canonical probe helper itself


def is_cpu() -> bool:
    """True when JAX is doing the math on the host CPU."""
    import jax

    return jax.default_backend() == "cpu"  # jaxlint: disable=J006 -- the canonical probe helper itself


def device_kind() -> str:
    """The attached accelerator's self-reported kind string (e.g.
    "TPU v5 lite", "cpu"), or "" when no backend can be initialized.
    Initializes the active backend — never call at module scope (the
    package-import test forbids it) or before the CLI pin."""
    import jax

    try:
        return str(jax.devices()[0].device_kind)
    except Exception:
        return ""


def device_facts() -> Dict[str, Union[str, int]]:
    """What this process computes on, as JAX reports it: the stamp every
    node (`node.start`, /stats) and every result line carries so a number
    can never be attributed to a device it did not run on. Initializes the
    active backend."""
    import jax

    devs = jax.devices()
    return {
        "platform": str(devs[0].platform),
        "device_kind": str(devs[0].device_kind),
        "device_count": len(devs),
    }


def force_platform(device: Optional[str]) -> None:
    """Pin jax to `device` ("cpu", "tpu", ...). None/"auto" leaves jax's
    own platform discovery alone."""
    if device in (None, "auto", ""):
        return
    import jax

    os.environ["JAX_PLATFORMS"] = device  # covers not-yet-imported jax too
    jax.config.update("jax_platforms", device)


def require_platform(device: Optional[str]) -> Dict[str, Union[str, int]]:
    """Initialize the backend and fail unless it is the platform that was
    asked for by name. `force_platform` is a silent no-op once a backend
    exists, so a process that was told `tpu` verifies what it got before
    it serves or measures anything; "auto" accepts whatever JAX found.
    Returns device_facts()."""
    facts = device_facts()
    if device not in (None, "auto", "") and facts["platform"] != device:
        raise RuntimeError(
            f"--device {device} was requested but JAX resolved "
            f"{facts['platform']!r} ({facts['device_kind']}); refusing to "
            "run on another device in its place"
        )
    return facts


class CompileCacheStats:
    """Persistent-cache hit/miss counts of this process, fed by
    jax.monitoring events — an auditable count of compiles avoided, not a
    timing inference."""

    def __init__(self, directory: str):
        self.directory = directory
        self.hits = 0
        self.misses = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1

    def as_dict(self) -> Dict[str, Union[str, int]]:
        return {"dir": self.directory, "hits": self.hits, "misses": self.misses}


def compile_cache_dir() -> str:
    """Where this process keeps compiled programs: JAX_COMPILATION_CACHE_DIR
    when the environment places the cache, else the fixed path inside the
    checkout."""
    return os.environ.get(COMPILE_CACHE_ENV) or DEFAULT_COMPILE_CACHE_DIR


def enable_compile_cache() -> CompileCacheStats:
    """Turn on JAX's persistent compilation cache for this process, so
    node restarts, stage migrations and sibling processes (the plain
    engine next to a node) load compiled executables instead of re-running
    XLA.

    Where JAX_COMPILATION_CACHE_DIR is set the cache lives there and no
    directory is set in code (jax reads the variable itself); where it is
    not, the fixed DEFAULT_COMPILE_CACHE_DIR is used. Thresholds are set
    either way: min_entry_size -1 and min_compile_time 0 cache everything,
    small kernels included (a reshard replays many small jits).
    JAX_ENABLE_COMPILATION_CACHE=false (jax's own switch) still turns the
    cache off — the test suite relies on it, see tests/conftest.py."""
    import jax

    if not os.environ.get(COMPILE_CACHE_ENV):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    stats = CompileCacheStats(compile_cache_dir())
    jax.monitoring.register_event_listener(stats._on_event)
    return stats
