"""jaxlint rules J001–J006 (the concurrency rules J007–J011 live in
analysis/concurrency.py and are registered into ALL_RULES at the bottom).

Each rule is a class with an `id`, `title`, one-line `hint`, and a
`check(ctx) -> Iterator[Finding]`. Rules are deliberately heuristic: they
catch the mechanically-detectable shape of each bug class (the same shapes
the round-5 ADVICE review found by hand) and lean on the baseline /
inline-suppression layer for deliberate exceptions, instead of trying to
prove intent. False-positive budget is "a handful per rule across this
repo"; anything noisier gets its matcher narrowed, not baselined en masse.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from inferd_tpu.analysis.engine import (  # noqa: F401  (re-exported)
    Ctx,
    Finding,
    Rule,
    _dotted,
    _walk_skipping,
)

# ---------------------------------------------------------------- helpers


def _const_strs(node: ast.AST) -> Optional[List[str]]:
    """Str constant or tuple/list/set of str constants -> the strings."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for elt in node.elts:
            if not (
                isinstance(elt, ast.Constant) and isinstance(elt.value, str)
            ):
                return None
            out.append(elt.value)
        return out
    return None


_JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}


class JitInfo:
    def __init__(self) -> None:
        self.static_names: Set[str] = set()
        self.static_nums: Set[int] = set()
        self.donate_names: Set[str] = set()
        self.donate_nums: Set[int] = set()

    def absorb_kwargs(self, call: ast.Call) -> None:
        for kw in call.keywords:
            if kw.arg in ("static_argnames", "donate_argnames"):
                names = _const_strs(kw.value) or []
                getattr(
                    self,
                    "static_names"
                    if kw.arg == "static_argnames"
                    else "donate_names",
                ).update(names)
            elif kw.arg in ("static_argnums", "donate_argnums"):
                nums: List[int] = []
                vals = (
                    kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value]
                )
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(
                        v.value, int
                    ):
                        nums.append(v.value)
                getattr(
                    self,
                    "static_nums"
                    if kw.arg == "static_argnums"
                    else "donate_nums",
                ).update(nums)


def _jit_call_info(call: ast.Call) -> Optional[JitInfo]:
    """`jax.jit(...)` / `partial(jax.jit, ...)` call -> JitInfo, else None."""
    fn = _dotted(call.func)
    if fn in _JIT_NAMES:
        info = JitInfo()
        info.absorb_kwargs(call)
        return info
    if fn in ("partial", "functools.partial") and call.args:
        inner = _dotted(call.args[0])
        if inner in _JIT_NAMES:
            info = JitInfo()
            info.absorb_kwargs(call)
            return info
    return None


def _decorated_jit_info(fn_def: ast.AST) -> Optional[JitInfo]:
    """JitInfo for an @jax.jit / @partial(jax.jit, ...) decorated def."""
    for deco in getattr(fn_def, "decorator_list", []):
        if _dotted(deco) in _JIT_NAMES:
            return JitInfo()
        if isinstance(deco, ast.Call):
            info = _jit_call_info(deco)
            if info is not None:
                return info
    return None


def _param_names(fn_def) -> List[str]:
    a = fn_def.args
    return [p.arg for p in a.posonlyargs + a.args]


def _bound_names(fn_def) -> Set[str]:
    """Names bound inside a def: params, assignment/loop/with targets,
    imports, nested defs — i.e. NOT free variables."""
    bound: Set[str] = set()
    a = fn_def.args
    for p in a.posonlyargs + a.args + a.kwonlyargs:
        bound.add(p.arg)
    if a.vararg:
        bound.add(a.vararg.arg)
    if a.kwarg:
        bound.add(a.kwarg.arg)
    for node in ast.walk(fn_def):
        if isinstance(node, ast.Name) and isinstance(
            node.ctx, (ast.Store, ast.Del)
        ):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn_def:
                bound.add(node.name)
        elif isinstance(node, ast.ClassDef):
            bound.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                bound.add((alias.asname or alias.name).split(".")[0])
    return bound


# ------------------------------------------------------------------ J001


class RetraceHazards(Rule):
    """Jitted fns whose call signature invites silent recompilation."""

    id = "J001"
    title = "retrace hazard in jitted function"
    hint = (
        "list Python-valued params in static_argnames/static_argnums (or "
        "pass arrays); never use mutable defaults or mutated globals under "
        "jit — each new value re-traces or freezes stale state"
    )

    SCALARS = {"int", "float", "bool", "str", "bytes"}
    # NOTE: tuple/Tuple/Sequence are deliberately absent — a
    # fixed-structure pytree carry (`carry: Tuple[...]`) is the idiomatic
    # NON-static way to pass arrays to jit and only retraces on structure
    # change; annotating it must not trip the gate
    CONTAINERS = {
        "list",
        "dict",
        "set",
        "List",
        "Dict",
        "Set",
        "Mapping",
        "FrozenSet",
    }

    def _ann_heads(self, ann: ast.AST) -> List[str]:
        """Head identifier(s) of an annotation, looking through
        Optional/Union and string annotations."""
        if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
            try:
                ann = ast.parse(ann.value, mode="eval").body
            except SyntaxError:
                return []
        if isinstance(ann, ast.Name):
            return [ann.id]
        if isinstance(ann, ast.Attribute):
            return [ann.attr]
        if isinstance(ann, ast.Subscript):
            head = self._ann_heads(ann.value)
            if head and head[0] in ("Optional", "Union"):
                inner = ann.slice
                elts = (
                    inner.elts if isinstance(inner, ast.Tuple) else [inner]
                )
                out: List[str] = []
                for e in elts:
                    out.extend(self._ann_heads(e))
                return out
            return head
        if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
            return self._ann_heads(ann.left) + self._ann_heads(ann.right)
        return []

    def _mutated_globals(self, tree: ast.AST) -> Set[str]:
        """Names a function in this module mutates via `global X; X = ...`."""
        mutated: Set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            declared: Set[str] = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Global):
                    declared.update(sub.names)
            if not declared:
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and isinstance(
                    sub.ctx, ast.Store
                ):
                    if sub.id in declared:
                        mutated.add(sub.id)
        return mutated

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        mutated_globals = self._mutated_globals(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            info = _decorated_jit_info(node)
            if info is None:
                continue
            # (a) Python-typed params not marked static
            pos = _param_names(node)
            annotated = list(
                zip(pos, (p.annotation for p in node.args.posonlyargs + node.args.args))
            ) + [(p.arg, p.annotation) for p in node.args.kwonlyargs]
            for name, ann in annotated:
                if ann is None:
                    continue
                if name in info.static_names:
                    continue
                if name in pos and pos.index(name) in info.static_nums:
                    continue
                heads = set(self._ann_heads(ann))
                bad = heads & (self.SCALARS | self.CONTAINERS)
                if bad:
                    yield ctx.finding(
                        self,
                        ann,
                        f"jitted `{node.name}` takes Python-valued param "
                        f"`{name}: {ast.unparse(ann)}` that is not in "
                        "static_argnames/static_argnums — every distinct "
                        "value (or container structure) re-traces",
                    )
            # (b) mutable default args
            for default in node.args.defaults + node.args.kw_defaults:
                if default is None:
                    continue
                is_mutable = isinstance(
                    default, (ast.List, ast.Dict, ast.Set)
                ) or (
                    isinstance(default, ast.Call)
                    and _dotted(default.func) in ("list", "dict", "set")
                )
                if is_mutable:
                    yield ctx.finding(
                        self,
                        default,
                        f"jitted `{node.name}` has a mutable default "
                        "argument — it is captured at trace time and "
                        "mutations after the first call are silently lost",
                    )
            # (c) closure over mutated globals
            if mutated_globals:
                bound = _bound_names(node)
                seen: Set[str] = set()
                for sub in ast.walk(node):
                    if (
                        isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Load)
                        and sub.id in mutated_globals
                        and sub.id not in bound
                        and sub.id not in seen
                    ):
                        seen.add(sub.id)
                        yield ctx.finding(
                            self,
                            sub,
                            f"jitted `{node.name}` closes over global "
                            f"`{sub.id}` that is mutated elsewhere via "
                            "`global` — the traced value is frozen at "
                            "first call and later mutations don't retrace",
                        )


# ------------------------------------------------------------------ J002


class DonationMisuse(Rule):
    """A buffer passed to a donate_argnames position is dead after the
    call — referencing it again reads deallocated (or aliased) memory."""

    id = "J002"
    title = "donated buffer referenced after jitted call"
    hint = (
        "rebind the result over the donated name (`cache = step(.., cache)`) "
        "or drop the donation; a donated arg's buffer is consumed by the call"
    )

    def _jitted_defs(self, tree: ast.AST) -> Dict[str, Tuple[JitInfo, List[str]]]:
        """name -> (JitInfo-with-donation, positional param names), for both
        decorated defs and `name = jax.jit(fn, donate_...)` assignments."""
        defs_by_name: Dict[str, ast.AST] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, node)
        out: Dict[str, Tuple[JitInfo, List[str]]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                info = _decorated_jit_info(node)
                if info and (info.donate_names or info.donate_nums):
                    out[node.name] = (info, _param_names(node))
            elif isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                info = _jit_call_info(node.value)
                if not info or not (info.donate_names or info.donate_nums):
                    continue
                params: List[str] = []
                if node.value.args:
                    wrapped = _dotted(node.value.args[0])
                    if wrapped and wrapped in defs_by_name:
                        params = _param_names(defs_by_name[wrapped])
                for tgt in node.targets:
                    name = _dotted(tgt)
                    if name:
                        out[name.split(".")[-1]] = (info, params)
        return out

    def _donated_args(
        self, call: ast.Call, info: JitInfo, params: List[str]
    ) -> List[Tuple[str, ast.AST]]:
        """-> [(dotted_name, node)] of call args in donated positions."""
        donated_pos: Set[int] = set(info.donate_nums)
        for name in info.donate_names:
            if name in params:
                donated_pos.add(params.index(name))
        out: List[Tuple[str, ast.AST]] = []
        for i, arg in enumerate(call.args):
            if i in donated_pos:
                d = _dotted(arg)
                if d:
                    out.append((d, arg))
        for kw in call.keywords:
            if kw.arg in info.donate_names:
                d = _dotted(kw.value)
                if d:
                    out.append((d, kw.value))
        return out

    @staticmethod
    def _stmt_rebinds(stmt: ast.stmt, dotted: str) -> bool:
        targets: List[ast.AST] = []
        for node in ast.walk(stmt):
            if isinstance(node, ast.Assign):
                targets.extend(node.targets)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For)):
                targets.append(node.target)
            elif isinstance(node, ast.withitem) and node.optional_vars:
                targets.append(node.optional_vars)
        for tgt in targets:
            for sub in ast.walk(tgt):
                if _dotted(sub) == dotted:
                    return True
        return False

    @staticmethod
    def _stmt_reads(stmt: ast.stmt, dotted: str) -> Optional[ast.AST]:
        root = dotted.split(".")[0]
        for node in ast.walk(stmt):
            if (
                isinstance(node, ast.Name)
                and node.id == root
                and isinstance(node.ctx, ast.Load)
                and "." not in dotted
            ):
                return node
            if isinstance(node, ast.Attribute) and _dotted(node) == dotted:
                return node
        return None

    @staticmethod
    def _loop_rebinds(loop: ast.AST, dotted: str) -> bool:
        """Is `dotted` rebound ANYWHERE in the loop's subtree (any branch,
        any nesting — conservative on purpose: a conditional rebind is
        enough to not flag the re-donation)?"""
        return any(
            DonationMisuse._stmt_rebinds(s, dotted)
            for s in ast.walk(loop)
            if isinstance(s, ast.stmt)
        )

    def _scan_body(
        self,
        ctx: Ctx,
        body: Sequence[ast.stmt],
        jitted: Dict[str, Tuple[JitInfo, List[str]]],
        loop: Optional[ast.AST],
    ) -> Iterator[Finding]:
        for idx, stmt in enumerate(body):
            # nested defs/classes are separate scopes (visited via
            # `scopes`); a call merely *defined* inside one does not
            # execute here — skip both collection and recursion
            if isinstance(
                stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue
            for call in _walk_skipping(
                stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                if not isinstance(call, ast.Call):
                    continue
                fname = _dotted(call.func)
                if not fname:
                    continue
                leaf = fname.split(".")[-1]
                if leaf not in jitted:
                    continue
                info, params = jitted[leaf]
                for dotted, arg_node in self._donated_args(
                    call, info, params
                ):
                    rebound_here = self._stmt_rebinds(stmt, dotted)
                    use = None
                    for later in body[idx + 1 :]:
                        use = self._stmt_reads(later, dotted)
                        if use is not None:
                            break
                        if self._stmt_rebinds(later, dotted):
                            break
                    if use is not None and not rebound_here:
                        yield ctx.finding(
                            self,
                            use,
                            f"`{dotted}` was donated to jitted `{leaf}` "
                            f"(line {call.lineno}) and is read again here "
                            "without being rebound — its buffer no longer "
                            "holds the pre-call value",
                        )
                    elif (
                        loop is not None
                        and use is None
                        and not rebound_here
                        and not self._loop_rebinds(loop, dotted)
                    ):
                        yield ctx.finding(
                            self,
                            call,
                            f"`{dotted}` is donated to jitted `{leaf}` "
                            "inside a loop but never rebound in the loop "
                            "body — the next iteration re-donates a "
                            "consumed buffer",
                        )
            # nested loops become the nearest enclosing loop; other nested
            # blocks (if/try/with) inherit the current one
            inner_loop = (
                stmt if isinstance(stmt, (ast.For, ast.While)) else loop
            )
            for attr in ("body", "orelse", "finalbody"):
                nested = getattr(stmt, attr, None)
                if nested:
                    yield from self._scan_body(ctx, nested, jitted, inner_loop)
            for handler in getattr(stmt, "handlers", []) or []:
                yield from self._scan_body(
                    ctx, handler.body, jitted, inner_loop
                )

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        jitted = self._jitted_defs(ctx.tree)
        if not jitted:
            return
        seen: Set[Tuple[int, int, str]] = set()
        scopes: List[Sequence[ast.stmt]] = [ctx.tree.body]
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node.body)
        for scope in scopes:
            for f in self._scan_body(ctx, scope, jitted, loop=None):
                key = (f.line, f.col, f.message)
                if key not in seen:
                    seen.add(key)
                    yield f


# ------------------------------------------------------------------ J003


class HostSyncInLoop(Rule):
    """Per-iteration host-device synchronization inside (decode) loops."""

    id = "J003"
    title = "host-device sync inside a hot loop"
    hint = (
        "hoist the transfer out of the loop, batch everything the host "
        "reads into ONE np.asarray per step, or keep the value on device "
        "(see core/generate.py's single-transfer decode loop)"
    )

    SYNC_METHODS = {"item", "tolist", "block_until_ready"}
    SYNC_CALLS = {
        "np.asarray",
        "np.array",
        "numpy.asarray",
        "numpy.array",
        "jax.device_get",
        "jax.block_until_ready",
    }

    def _file_is_jaxy(self, tree: ast.AST) -> bool:
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in node.names]
                mod = getattr(node, "module", "") or ""
                if any(
                    n.split(".")[0] == "jax" for n in names
                ) or mod.split(".")[0] == "jax":
                    return True
        return False

    def _fn_mentions_jax(self, fn: ast.AST) -> bool:
        for node in ast.walk(fn):
            d = _dotted(node) if isinstance(node, (ast.Attribute, ast.Name)) else None
            if d and d.split(".")[0] in ("jax", "jnp", "lax"):
                return True
        return False

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        if not self._file_is_jaxy(ctx.tree):
            return
        # map each loop to its enclosing def (or module) for the jax gate
        enclosing: Dict[ast.AST, ast.AST] = {}

        def mark(owner: ast.AST, node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                new_owner = (
                    child
                    if isinstance(
                        child, (ast.FunctionDef, ast.AsyncFunctionDef)
                    )
                    else owner
                )
                if isinstance(child, (ast.While, ast.For, ast.AsyncFor)):
                    enclosing[child] = new_owner
                mark(new_owner, child)

        mark(ctx.tree, ctx.tree)

        gate_cache: Dict[ast.AST, bool] = {}
        for loop, owner in enclosing.items():
            if owner not in gate_cache:
                gate_cache[owner] = self._fn_mentions_jax(owner)
            if not gate_cache[owner]:
                continue
            for node in self._iter_loop_nodes(loop):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                msg = None
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.SYNC_METHODS
                ):
                    msg = (
                        f"`.{node.func.attr}()` inside a loop forces a "
                        "device sync + host transfer every iteration"
                    )
                elif d in self.SYNC_CALLS:
                    msg = (
                        f"`{d}(...)` inside a loop materializes device "
                        "memory on the host every iteration"
                    )
                elif (
                    d in ("int", "float", "bool")
                    and len(node.args) == 1
                    and isinstance(node.args[0], (ast.Subscript, ast.Attribute))
                ):
                    msg = (
                        f"`{d}({ast.unparse(node.args[0])})` inside a loop "
                        "blocks on the device value every iteration"
                    )
                if msg:
                    yield ctx.finding(self, node, msg)

    @staticmethod
    def _iter_loop_nodes(loop: ast.AST) -> Iterator[ast.AST]:
        """Walk a loop's per-iteration nodes: the body plus, for `while`,
        the condition (`while int(tok[0]) != eos:` syncs every iteration
        too — the canonical decode-loop shape). NOT descended into:
        nested loops (reported on their own), nested defs/lambdas (only
        *defined* per iteration), and the `else:` clause (runs ONCE after
        the loop, same as following code)."""
        skip = (
            ast.While,
            ast.For,
            ast.AsyncFor,
            ast.FunctionDef,
            ast.AsyncFunctionDef,
            ast.Lambda,
        )
        if isinstance(loop, ast.While):
            yield loop.test
            yield from _walk_skipping(loop.test, skip)
        for stmt in loop.body:
            if isinstance(stmt, skip):
                continue
            yield stmt
            yield from _walk_skipping(stmt, skip)


# ------------------------------------------------------------------ J004


class PurityViolations(Rule):
    """Side effects inside traced code run once at trace time, then never
    again — the classic 'my print/append/RNG stopped happening' bug."""

    id = "J004"
    title = "impure operation under jit/scan tracing"
    hint = (
        "use jax.debug.print for tracing-safe prints, jax.random with an "
        "explicit key for randomness, and carry accumulators through the "
        "scan instead of appending to enclosing lists"
    )

    TRACE_ENTRY = {
        "lax.scan": [0],
        "jax.lax.scan": [0],
        "lax.while_loop": [0, 1],
        "jax.lax.while_loop": [0, 1],
        "lax.fori_loop": [2],
        "jax.lax.fori_loop": [2],
        "lax.cond": [1, 2],
        "jax.lax.cond": [1, 2],
        "lax.switch": None,  # every arg after the index may be a branch
        "jax.lax.switch": None,
        "lax.map": [0],
        "jax.lax.map": [0],
    }

    def _traced_defs(self, tree: ast.AST) -> List[ast.AST]:
        defs_by_name: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, []).append(node)
        traced: List[ast.AST] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if _decorated_jit_info(node) is not None:
                    traced.append(node)
            elif isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d not in self.TRACE_ENTRY:
                    continue
                idxs = self.TRACE_ENTRY[d]
                args = (
                    node.args
                    if idxs is None
                    else [node.args[i] for i in idxs if i < len(node.args)]
                )
                for arg in args:
                    name = _dotted(arg)
                    if name and name in defs_by_name:
                        traced.extend(defs_by_name[name])
        return traced

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        seen: Set[Tuple[int, int, str]] = set()
        for fn in self._traced_defs(ctx.tree):
            bound = _bound_names(fn)
            for node in ast.walk(fn):
                finding = None
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    if d == "print":
                        finding = ctx.finding(
                            self,
                            node,
                            f"`print` inside traced `{fn.name}` runs only "
                            "at trace time — use jax.debug.print to see "
                            "runtime values",
                        )
                    elif (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("append", "extend")
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id not in bound
                    ):
                        finding = ctx.finding(
                            self,
                            node,
                            f"`.{node.func.attr}` on enclosing-scope "
                            f"`{node.func.value.id}` inside traced "
                            f"`{fn.name}` appends tracers once at trace "
                            "time, not values per step — carry it through "
                            "the scan instead",
                        )
                elif isinstance(node, ast.Attribute):
                    d = _dotted(node)
                    if d and (
                        d.startswith("np.random.")
                        or d.startswith("numpy.random.")
                        or d.startswith("random.")
                    ):
                        finding = ctx.finding(
                            self,
                            node,
                            f"`{d}` inside traced `{fn.name}` draws ONE "
                            "value at trace time and bakes it into the "
                            "graph — use jax.random with an explicit key",
                        )
                if finding is not None:
                    key = (finding.line, finding.col, finding.rule)
                    if key not in seen:
                        seen.add(key)
                        yield finding


# ------------------------------------------------------------------ J005


class AsyncioHazards(Rule):
    """Blocking calls and dropped coroutines in async code paths."""

    id = "J005"
    title = "asyncio hazard"
    hint = (
        "await asyncio.sleep / run blocking work via "
        "loop.run_in_executor; a blocked event loop stalls every "
        "in-flight request on the node"
    )

    BLOCKING = {
        "time.sleep": "blocks the event loop — use `await asyncio.sleep`",
        "subprocess.run": "blocks the event loop — use asyncio.create_subprocess_exec",
        "subprocess.call": "blocks the event loop — use asyncio.create_subprocess_exec",
        "subprocess.check_call": "blocks the event loop — use asyncio.create_subprocess_exec",
        "subprocess.check_output": "blocks the event loop — use asyncio.create_subprocess_exec",
        "os.system": "blocks the event loop — use asyncio.create_subprocess_shell",
        "requests.get": "sync HTTP blocks the event loop — use aiohttp",
        "requests.post": "sync HTTP blocks the event loop — use aiohttp",
        "requests.put": "sync HTTP blocks the event loop — use aiohttp",
        "requests.request": "sync HTTP blocks the event loop — use aiohttp",
        "urllib.request.urlopen": "sync HTTP blocks the event loop — use aiohttp",
        "socket.create_connection": "sync connect blocks the event loop",
    }

    @staticmethod
    def _async_maps(tree: ast.AST):
        """(module-level async fn names, class -> async method names,
        async def node -> enclosing class). `self.meth()` only matches
        methods of the SAME class — a sync `other.start()` must not trip
        on an unrelated `async def start` elsewhere in the module."""
        free: Set[str] = set()
        by_class: Dict[ast.ClassDef, Set[str]] = {}
        owner_of: Dict[ast.AST, ast.ClassDef] = {}

        def visit(node: ast.AST, cls: Optional[ast.ClassDef]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    by_class.setdefault(child, set())
                    visit(child, child)
                    continue
                if isinstance(child, ast.AsyncFunctionDef):
                    if cls is not None:
                        by_class[cls].add(child.name)
                        owner_of[child] = cls
                    else:
                        free.add(child.name)
                visit(child, cls)

        visit(tree, None)
        return free, by_class, owner_of

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        free_async, by_class, owner_of = self._async_maps(ctx.tree)
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            own_methods = by_class.get(owner_of.get(fn), set())
            # walk the async body, skipping nested defs (sync helpers may
            # legitimately sleep; nested async defs get their own visit)
            skip = (ast.FunctionDef, ast.AsyncFunctionDef)
            for node in _walk_skipping(fn, skip):
                if isinstance(node, ast.Expr) and isinstance(
                    node.value, ast.Call
                ):
                    d = _dotted(node.value.func)
                    leaf = None
                    if d and "." not in d and d in free_async:
                        leaf = d
                    elif (
                        d
                        and d.startswith("self.")
                        and d.count(".") == 1
                        and d.split(".")[1] in own_methods
                    ):
                        leaf = d.split(".")[1]
                    if leaf is not None:
                        yield ctx.finding(
                            self,
                            node,
                            f"coroutine `{leaf}(...)` is called but never "
                            "awaited — it silently never runs",
                            hint=(
                                "await it, or schedule it with "
                                "asyncio.create_task(...) and keep a "
                                "reference"
                            ),
                        )
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    if d in self.BLOCKING:
                        yield ctx.finding(
                            self,
                            node,
                            f"`{d}(...)` inside `async def {fn.name}` "
                            + self.BLOCKING[d],
                        )


# ------------------------------------------------------------------ J006


class FragilePlatformProbe(Rule):
    """Literal string comparison against jax.default_backend(): the
    platform decides kernel-vs-interpret, quant scheme and chip table, so
    it is asked through ONE probe helper, not by string compares scattered
    over the call sites (a new platform name then changes one function)."""

    id = "J006"
    title = "fragile platform probe"
    hint = (
        "use inferd_tpu.utils.platform.is_tpu()/is_cpu() — one probe "
        "helper, not string compares"
    )

    PROBES = {"jax.default_backend", "default_backend"}

    def _is_probe_call(self, node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call) and _dotted(node.func) in self.PROBES
        )

    def check(self, ctx: Ctx) -> Iterator[Finding]:
        # taint (names assigned from a default_backend() call) is tracked
        # PER SCOPE: an unrelated variable that happens to share the name
        # in another function must not be flagged
        skip = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        scopes = [ctx.tree] + [
            n for n in ast.walk(ctx.tree) if isinstance(n, skip[:2])
        ]
        for scope in scopes:
            nodes = list(_walk_skipping(scope, skip))
            tainted: Set[str] = {
                tgt.id
                for node in nodes
                if isinstance(node, ast.Assign)
                and self._is_probe_call(node.value)
                for tgt in node.targets
                if isinstance(tgt, ast.Name)
            }
            for node in nodes:
                if not isinstance(node, ast.Compare):
                    continue
                if not all(
                    isinstance(op, (ast.Eq, ast.NotEq, ast.In, ast.NotIn))
                    for op in node.ops
                ):
                    continue
                sides = [node.left] + list(node.comparators)
                has_probe = any(
                    self._is_probe_call(s)
                    or (isinstance(s, ast.Name) and s.id in tainted)
                    for s in sides
                )
                literals = None
                for s in sides:
                    literals = literals or _const_strs(s)
                if has_probe and literals:
                    yield ctx.finding(
                        self,
                        node,
                        "literal comparison against jax.default_backend() "
                        f"(vs {literals!r}) — platform choices go through "
                        "the one probe helper, so they cannot drift apart",
                    )


ALL_RULES: List[Rule] = [
    RetraceHazards(),
    DonationMisuse(),
    HostSyncInLoop(),
    PurityViolations(),
    AsyncioHazards(),
    FragilePlatformProbe(),
]

# The concurrency plane (J007-J011) lives in its own module; it imports
# only engine + utils.lockwatch, so registering it here is cycle-free in
# either import order.
from inferd_tpu.analysis.concurrency import CONCURRENCY_RULES  # noqa: E402

ALL_RULES.extend(CONCURRENCY_RULES)


def rule_catalog() -> List[Tuple[str, str, str]]:
    """[(id, title, hint)] for docs and the `rules` CLI subcommand."""
    return [(r.id, r.title, r.hint) for r in ALL_RULES]
