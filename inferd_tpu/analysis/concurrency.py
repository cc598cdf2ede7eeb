"""jaxlint concurrency rules J007-J011.

The swarm runs three interacting concurrency domains — the executor device
lock, per-subsystem mutexes, and the aiohttp event loop with worker
threads — and CHANGES.md PRs 10-15 fixed the same hand-found bug family
repeatedly (host I/O under the device lock, cross-thread snapshot races,
blocking calls in async handlers). These rules machine-check those shapes.
The canonical lock order is imported from utils.lockwatch (the runtime
sanitizer), so the static and dynamic checkers can never disagree.

Pure stdlib; imports ONLY engine + utils.lockwatch (itself stdlib-only) so
registration from rules.py is cycle-free.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from inferd_tpu.analysis.engine import (
    Ctx,
    Finding,
    Rule,
    _dotted,
    _walk_skipping,
)
from inferd_tpu.utils.lockwatch import LOCK_ORDER, LOCK_RANK

# ------------------------------------------------------- lock resolution
#
# A `with`/`.acquire` site names a lock via its attribute; `_mu` and
# `_lock` are reused across classes, so class-qualified overrides map each
# owner's instance onto its rank. A generic `_lock` in an UNLISTED class
# stays unranked on purpose: executor.py/mesh_executor.py use `_lock` for
# single-executor state with no cross-subsystem nesting, and guessing a
# rank for unknown locks would invent false inversions.

_ATTR_DEFAULT = {
    "_dev_lock": "dev",
    "_mu": "mu",
}
_CLASS_ATTR = {
    ("AdapterRegistry", "_mu"): "registry",
    ("StandbyStore", "_mu"): "repl",
    ("WindowedBatcher", "_mu"): "window",
    ("Metrics", "_lock"): "metrics",
    ("Histogram", "_lock"): "metrics",
    ("EventJournal", "_lock"): "events",
}


def _lock_name(cls: Optional[str], expr: ast.AST) -> Optional[str]:
    """Resolve a lock expression (`self._mu`, `self._dev_lock`) to its
    canonical LOCK_ORDER name, or None if unnamed/unranked."""
    d = _dotted(expr)
    if not d or "." not in d:
        return None
    head, attr = d.rsplit(".", 1)
    if head != "self":
        # e.g. `self.executor._mu.acquire()` from outside the owner:
        # still the executor's mu — resolve by attribute alone
        return _ATTR_DEFAULT.get(attr)
    if cls is not None and (cls, attr) in _CLASS_ATTR:
        return _CLASS_ATTR[(cls, attr)]
    return _ATTR_DEFAULT.get(attr)


def _scopes_with_class(
    tree: ast.AST,
) -> List[Tuple[Optional[str], ast.AST]]:
    """[(enclosing class name or None, function def)] for every def in
    the module, innermost class wins; plus (None, module) for top-level
    statements."""
    out: List[Tuple[Optional[str], ast.AST]] = [(None, tree)]

    def visit(node: ast.AST, cls: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                visit(child, child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append((cls, child))
                visit(child, cls)
            else:
                visit(child, cls)

    visit(tree, None)
    return out


_SKIP_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)


def _blocking_acquire(call: ast.Call) -> bool:
    """Is this `.acquire(...)` call an UNBOUNDED blocking wait? Bounded
    waits (`timeout=`) and try-acquires (`blocking=False`) cannot hold a
    thread forever, so they are not deadlock-cycle edges."""
    if not (
        isinstance(call.func, ast.Attribute) and call.func.attr == "acquire"
    ):
        return False
    if len(call.args) >= 2:
        return False  # positional timeout
    if call.args:
        a = call.args[0]
        if isinstance(a, ast.Constant) and a.value is False:
            return False
        # non-constant positional blocking flag: can't prove — assume
        # blocking (conservative)
    for kw in call.keywords:
        if kw.arg == "timeout":
            return False
        if kw.arg == "blocking":
            if isinstance(kw.value, ast.Constant) and kw.value.value is False:
                return False
    return True


# ------------------------------------------------------------------ J007


class LockOrder(Rule):
    """Project rule: whole-repo lock acquisition graph vs LOCK_ORDER.

    `collect` records lexical acquisition edges per file — a `with` (or
    unbounded `.acquire()`) on named lock B while named lock A is held by
    an enclosing `with` is the edge A->B; multi-item `with a, b:` is
    sequential acquisition. `finalize` merges all files' edges and flags
    every edge whose direction contradicts the committed canonical order.
    Because LOCK_ORDER is a TOTAL order over the named locks, any cycle
    in the merged graph necessarily contains a contradicting edge, so the
    rank check subsumes cycle detection; when the reverse edge was also
    observed somewhere, the finding names it — that pair IS a deadlock,
    not just a convention violation.

    Cross-function nesting (helper called under a lock acquires another)
    is invisible to lexical analysis — that half is covered dynamically
    by utils.lockwatch, which enforces the same LOCK_ORDER at runtime.
    """

    id = "J007"
    title = "lock acquisition contradicts canonical order"
    hint = (
        "acquire in LOCK_ORDER ("
        + " -> ".join(LOCK_ORDER)
        + "); restructure to take the lower-ranked lock first, or use a "
        "bounded try-acquire (blocking=False / timeout=) for the "
        "out-of-order one"
    )

    # record: (outer, inner, line, col, qual, snippet)

    def collect(self, ctx: Ctx) -> List[tuple]:
        records: List[tuple] = []
        for cls, scope in _scopes_with_class(ctx.tree):
            held: List[str] = []
            for stmt in (
                scope.body if hasattr(scope, "body") else []
            ):
                self._walk(ctx, cls, stmt, held, records)
        return records

    def _walk(
        self,
        ctx: Ctx,
        cls: Optional[str],
        node: ast.AST,
        held: List[str],
        records: List[tuple],
    ) -> None:
        if isinstance(node, _SKIP_DEFS):
            return  # nested defs execute elsewhere; scanned as own scope
        if isinstance(node, ast.With):
            pushed = 0
            for item in node.items:
                name = _lock_name(cls, item.context_expr)
                if name is not None:
                    if held:
                        records.append(
                            (
                                held[-1],
                                name,
                                node.lineno,
                                node.col_offset,
                                ctx.qual(node),
                                self._snip(ctx, node.lineno),
                            )
                        )
                    held.append(name)
                    pushed += 1
            for stmt in node.body:
                self._walk(ctx, cls, stmt, held, records)
            if pushed:
                del held[-pushed:]
            return
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "acquire"
            and _blocking_acquire(node)
        ):
            name = _lock_name(cls, node.func.value)
            if name is not None and held:
                records.append(
                    (
                        held[-1],
                        name,
                        node.lineno,
                        node.col_offset,
                        ctx.qual(node),
                        self._snip(ctx, node.lineno),
                    )
                )
        for child in ast.iter_child_nodes(node):
            self._walk(ctx, cls, child, held, records)

    @staticmethod
    def _snip(ctx: Ctx, line: int) -> str:
        return (
            ctx.lines[line - 1].strip()
            if 0 < line <= len(ctx.lines)
            else ""
        )

    def finalize(self, records: Dict[str, List[tuple]]) -> List[Finding]:
        # merged direction index for the deadlock-pair callout
        observed: Dict[Tuple[str, str], Tuple[str, int]] = {}
        for path, recs in records.items():
            for outer, inner, line, _col, _qual, _snip in recs:
                observed.setdefault((outer, inner), (path, line))
        out: List[Finding] = []
        seen: Set[Tuple[str, str, str, int]] = set()
        for path, recs in records.items():
            for outer, inner, line, col, qual, snippet in recs:
                if LOCK_RANK[inner] >= LOCK_RANK[outer]:
                    continue
                key = (path, outer, inner, line)
                if key in seen:
                    continue
                seen.add(key)
                msg = (
                    f"acquires '{inner}' while holding '{outer}' — "
                    f"canonical order is {' -> '.join(LOCK_ORDER)}"
                )
                rev = observed.get((inner, outer))
                if rev is not None:
                    msg += (
                        f"; the reverse nesting exists at {rev[0]}:{rev[1]}"
                        " — this pair can deadlock"
                    )
                out.append(
                    Finding(
                        rule=self.id,
                        path=path,
                        line=line,
                        col=col,
                        message=msg,
                        hint=self.hint,
                        context=qual,
                        snippet=snippet,
                    )
                )
        return out


# ------------------------------------------------------------------ J008


class HostWorkUnderDeviceLock(Rule):
    """Host I/O lexically inside a device-lock `with` block: every other
    lane/flusher queues behind the device lock, so a file read or sleep
    under it multiplies into fleet-visible tail latency (the PR-10/12
    post-review bug family). `np.asarray` is deliberately NOT flagged —
    fetching the step's outputs under the device lock is the executors'
    designed boundary transfer."""

    id = "J008"
    title = "host work under the device lock"
    hint = (
        "move host I/O (files, sockets, sleeps, device_get) outside the "
        "device-lock block; only device dispatch and the designed output "
        "fetch belong under it"
    )

    HOST_CALLS = {
        "time.sleep",
        "open",
        "os.system",
        "jax.device_get",
        "device_get",
        "urllib.request.urlopen",
        "socket.create_connection",
        "socket.socket",
    }
    HOST_PREFIXES = ("requests.", "subprocess.")

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        for cls, scope in _scopes_with_class(ctx.tree):
            for node in _walk_skipping(scope, _SKIP_DEFS):
                if not isinstance(node, ast.With):
                    continue
                if not any(
                    _lock_name(cls, item.context_expr) == "dev"
                    for item in node.items
                ):
                    continue
                for stmt in node.body:
                    yield from self._scan(ctx, stmt)

    def _scan(self, ctx: Ctx, stmt: ast.AST) -> Iterator[Finding]:
        nodes = [stmt] if not isinstance(stmt, _SKIP_DEFS) else []
        if nodes:
            nodes += list(_walk_skipping(stmt, _SKIP_DEFS))
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d:
                continue
            if d in self.HOST_CALLS or d.startswith(self.HOST_PREFIXES):
                yield ctx.finding(
                    self,
                    node,
                    f"`{d}(...)` runs host work while holding the device "
                    "lock — every other lane queues behind it",
                )


# ------------------------------------------------------------------ J009


class BlockingInAsync(Rule):
    """Blocking concurrency primitives inside `async def`, complementing
    J005 (which flags blocking LIBRARY calls — sleep, sync HTTP): sync
    threading-lock holds, unbounded `.acquire()`, and inline executor jit
    dispatch all freeze the event loop and with it every in-flight
    request on the node. The dispatch leg is a curated method list on
    `*executor*` receivers: those methods run jit steps for their whole
    duration, the exact work the node routes through run_in_executor."""

    id = "J009"
    title = "blocking concurrency primitive in async handler"
    hint = (
        "hop to a worker thread (loop.run_in_executor) for lock-holding "
        "or jit-dispatching work; an async handler must only await"
    )

    DISPATCH = {
        "process",
        "process_batch",
        "import_session",
        "warmup",
        "spec_warmup",
        "fork_session",
    }

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        for cls, scope in _scopes_with_class(ctx.tree):
            if not isinstance(scope, ast.AsyncFunctionDef):
                continue
            for node in _walk_skipping(scope, _SKIP_DEFS):
                # sync `with <named threading lock>:` — `async with` on
                # asyncio locks is ast.AsyncWith and stays legal
                if isinstance(node, ast.With):
                    for item in node.items:
                        name = _lock_name(cls, item.context_expr)
                        if name is not None:
                            yield ctx.finding(
                                self,
                                node,
                                f"sync `with` on threading lock '{name}' "
                                f"inside `async def {scope.name}` blocks "
                                "the event loop while waiting and while "
                                "held",
                            )
                if not isinstance(node, ast.Call):
                    continue
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "acquire"
                    and _blocking_acquire(node)
                ):
                    name = _lock_name(cls, node.func.value)
                    if name is not None:
                        yield ctx.finding(
                            self,
                            node,
                            f"unbounded `.acquire()` on lock '{name}' "
                            f"inside `async def {scope.name}` can block "
                            "the event loop indefinitely",
                            hint=(
                                "pass timeout=/blocking=False, or hop to "
                                "a worker thread"
                            ),
                        )
                    continue
                d = _dotted(node.func)
                if (
                    d
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.DISPATCH
                    and any(
                        "executor" in part for part in d.lower().split(".")
                    )
                ):
                    yield ctx.finding(
                        self,
                        node,
                        f"`{d}(...)` dispatches jit work inline in "
                        f"`async def {scope.name}` — the loop is frozen "
                        "for the whole device step",
                    )


# ------------------------------------------------------------------ J010


class ThreadSharedState(Rule):
    """Writes to known cross-thread registries outside their owning lock
    helpers: the Metrics counter/gauge/histogram dicts (owned by
    `Metrics._lock` via inc/set_gauge/set_counter/observe) and the
    journal/trace ring `_buf` deques (owned by EventJournal/SpanRecorder
    `_lock`). A bare `m.counters[k] = v` from another thread races the
    owner's read-modify-write and tears snapshots."""

    id = "J010"
    title = "cross-thread state written outside its owning lock helper"
    hint = (
        "go through the owner's API (Metrics.inc/set_counter/set_gauge/"
        "observe, EventJournal.emit) — it takes the owning lock"
    )

    METRIC_DICTS = {"counters", "gauges", "histograms"}
    BUF_MUTATORS = {"append", "appendleft", "extend", "clear", "pop", "popleft"}
    BUF_OWNERS = {"EventJournal", "SpanRecorder"}

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets
                    if isinstance(node, ast.Assign)
                    else [node.target]
                )
                for tgt in targets:
                    if not (
                        isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Attribute)
                        and tgt.value.attr in self.METRIC_DICTS
                    ):
                        continue
                    if "Metrics" in ctx.qual(node).split("."):
                        continue
                    yield ctx.finding(
                        self,
                        node,
                        f"direct write to `.{tgt.value.attr}[...]` "
                        "bypasses Metrics._lock — racing the owner's "
                        "read-modify-write tears counters and snapshots",
                    )
            elif (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in self.BUF_MUTATORS
                and isinstance(node.func.value, ast.Attribute)
                and node.func.value.attr == "_buf"
            ):
                quals = set(ctx.qual(node).split("."))
                if quals & self.BUF_OWNERS:
                    continue
                yield ctx.finding(
                    self,
                    node,
                    f"`._buf.{node.func.attr}(...)` mutates a journal "
                    "ring outside its owner — the owning class holds "
                    "`_lock` around every mutation",
                )


# ------------------------------------------------------------------ J011


class StaleDisable(Rule):
    """Audit rule: `# jaxlint: disable=...` directives that no longer
    match ANY raw finding are dead weight — the hazard they documented
    was refactored away, and keeping them re-suppresses whatever lands
    on that line next. Runs after all other rules' suppression
    accounting; a directive counts as live if it targeted any raw
    finding, reasoned or not. Directives for rules OUTSIDE the active
    set are skipped (a `--rules J003` run can't judge a J005 disable)."""

    id = "J011"
    title = "stale jaxlint disable directive"
    hint = (
        "delete the directive — it no longer suppresses any finding "
        "(the code it excused was fixed or moved)"
    )

    def audit(
        self,
        path: str,
        lines: List[str],
        supp,
        used: Set[Tuple[str, int]],
        active_ids: Set[str],
    ) -> List[Finding]:
        out: List[Finding] = []
        for rule, key_line, _reason, report_line in supp.directives():
            if rule in (self.id, "J000"):
                continue
            if rule not in active_ids:
                continue
            if (rule, key_line) in used:
                continue
            snippet = (
                lines[report_line - 1].strip()
                if 0 < report_line <= len(lines)
                else ""
            )
            kind = "file-disable" if key_line == 0 else "disable"
            out.append(
                Finding(
                    rule=self.id,
                    path=path,
                    line=report_line,
                    col=0,
                    message=(
                        f"`# jaxlint: {kind}={rule}` suppresses nothing — "
                        f"{rule} no longer fires here"
                    ),
                    hint=self.hint,
                    context="<module>",
                    snippet=snippet,
                )
            )
        return out


CONCURRENCY_RULES: List[Rule] = [
    LockOrder(),
    HostWorkUnderDeviceLock(),
    BlockingInAsync(),
    ThreadSharedState(),
    StaleDisable(),
]
