"""Process discipline of the benchmark: the parent never touches JAX, its
standard output carries nothing but the harness's own lines, and no child
outlives it. Copied from chip_smoke.py (PR 22) so that the benchmark does
not depend on a root script a later PR may change."""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time


class Refused(Exception):
    """The run cannot produce a result (no chip, no program, a child that
    failed before the window): exit non-zero and print no result line."""


class Out:
    """The real standard output, once claimed: fd 1 and sys.stdout point at
    stderr afterwards, so no library, warning or child can write a line
    after the contract's last one."""

    def __init__(self):
        self.stream = sys.stdout

    def claim(self) -> None:
        self.stream = os.fdopen(os.dup(1), "w")
        os.dup2(2, 1)
        sys.stdout = sys.stderr

    def say(self, msg: str) -> None:
        self.stream.write(f"[bench] {msg}\n")
        self.stream.flush()

    def last(self, line: str) -> None:
        self.stream.write(line + "\n")
        self.stream.flush()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parent_backend_live() -> bool:
    """Did THIS process initialize a JAX backend? It must not: the chip
    belongs to the child that computes on it."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


class Children:
    """Every process the harness starts, so that none outlives it. Logs go
    to `log_dir/<name>.log`; a child never inherits the harness's stdout."""

    def __init__(self, env: dict, log_dir: str, cwd: str, out: Out):
        self.env, self.log_dir, self.cwd, self.out = env, log_dir, cwd, out
        self.live = []
        os.makedirs(log_dir, exist_ok=True)

    def spawn(self, name: str, argv, capture: bool = False, env=None):
        log = open(os.path.join(self.log_dir, f"{name}.log"), "w")
        proc = subprocess.Popen(
            argv, cwd=self.cwd, env=env or self.env, stdin=subprocess.DEVNULL,
            stdout=subprocess.PIPE if capture else log,
            stderr=log, start_new_session=True, text=True,
        )
        proc._log = log
        self.live.append(proc)
        return proc

    def run(self, name: str, argv, timeout: float, env=None) -> str:
        """Run a child to its end; returns its captured stdout."""
        t0 = time.monotonic()
        proc = self.spawn(name, argv, capture=True, env=env)
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.stop(proc, grace=5.0)
            raise Refused(f"{name}: no end within {timeout:.0f}s")
        self._forget(proc)
        if proc.returncode != 0:
            raise Refused(
                f"{name}: exit code {proc.returncode}: {self.tail(name)}"
            )
        self.out.say(f"{name}: done in {time.monotonic() - t0:.1f}s")
        return out

    def stop(self, proc, grace: float = 30.0) -> int:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
                proc.wait(timeout=grace)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait(timeout=30)
        self._forget(proc)
        return proc.returncode

    def _forget(self, proc) -> None:
        proc._log.close()
        if proc in self.live:
            self.live.remove(proc)

    def stop_all(self) -> None:
        for proc in list(self.live):
            self.stop(proc, grace=5.0)

    def tail(self, name: str, n: int = 1500) -> str:
        try:
            with open(os.path.join(self.log_dir, f"{name}.log")) as f:
                return f.read()[-n:].strip().replace("\n", " | ")
        except OSError:
            return "(no log)"
