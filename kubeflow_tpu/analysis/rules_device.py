"""Family A — device-hygiene rules.

Tuned to how this codebase dispatches work at XLA: persistent device
buffers donated through jitted programs (serve/engine.py, serve/paged.py,
serve/device_state.py), a host-side scheduler that must never block the
hot loop, and trace sets kept log-bounded by constructing every ``jax.jit``
once at init. Each rule encodes one way PRs 1–4 actually regressed (or
nearly did):

- D101 ``host-sync-in-jit``: a blocking host sync (``jax.device_get``,
  ``.item()``, ``.block_until_ready()``, ``np.asarray``, ``float()``/
  ``int()`` on a traced parameter) inside a function compiled under
  ``jax.jit`` — at best a tracer error in prod, at worst a silent
  per-call sync when the function also runs eagerly.
- D102 ``host-sync-in-hot-loop``: the same blocking syncs inside a
  ``# hot-loop`` function (the dispatch/consume path). The ONE designed
  fetch per round is annotated ``# sync-point: <reason>``.
- D103 ``full-buffer-reupload``: ``jnp.asarray``/``jnp.array``/
  ``jax.device_put`` of a persistent ``self.*`` buffer inside a hot-loop
  function — the PR-4 per-round full-table upload, as a rule.
- D104 ``donated-buffer-reuse``: an argument donated to a jitted program
  (``donate_argnums``) read again before being rebound — donated buffers
  are invalid after dispatch.
- D105 ``jit-in-loop``: ``jax.jit(...)`` constructed inside a loop or a
  hot-loop function — a fresh compile cache entry per call.
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from kubeflow_tpu.analysis.core import (
    Finding, Module, Rule, jit_table, register,
)

_JIT = {"jax.jit"}
_UPLOAD = {"jax.numpy.asarray", "jax.numpy.array", "jax.device_put"}
_HOST_FETCH = {"jax.device_get"}
_HOST_NP = {"numpy.asarray", "numpy.array"}
_SYNC_METHODS = {"item", "block_until_ready"}


def _is_jit_call(mod: Module, node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and mod.qualname(node.func) in _JIT)


def _jit_target(mod: Module, call: ast.Call) -> Optional[ast.AST]:
    """The function object a ``jax.jit(...)`` call wraps: a Lambda, a
    local FunctionDef resolved by name, or (for ``partial(jax.jit, ...)``
    used as a decorator) None — decorators are handled separately."""
    if not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Lambda):
        return arg
    if isinstance(arg, ast.Name):
        scope = mod.enclosing_function(call)
        body = scope.body if scope is not None and not isinstance(
            scope, ast.Lambda) else mod.tree.body
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and stmt.name == arg.id:
                return stmt
        # module scope fallback
        for stmt in mod.tree.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and stmt.name == arg.id:
                return stmt
    return None


def jit_regions(mod: Module) -> list[ast.AST]:
    """Every function/lambda body compiled under ``jax.jit`` that this
    module can see syntactically: ``@jax.jit`` / ``@partial(jax.jit,..)``
    decorated defs, ``jax.jit(fn_or_lambda, ...)`` wrappings, and defs
    annotated ``# traced`` (jit-wrapped from another module)."""
    regions: list[ast.AST] = []
    for node in mod.walk():
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if mod.annotation(node, "traced") is not None:
                regions.append(node)
                continue
            for dec in node.decorator_list:
                qn = mod.qualname(dec)
                if qn in _JIT:
                    regions.append(node)
                    break
                if isinstance(dec, ast.Call):
                    dqn = mod.qualname(dec.func)
                    if dqn in _JIT:
                        regions.append(node)
                        break
                    if dqn in ("functools.partial", "partial") and dec.args \
                            and mod.qualname(dec.args[0]) in _JIT:
                        regions.append(node)
                        break
        elif _is_jit_call(mod, node):
            target = _jit_target(mod, node)
            if target is not None:
                regions.append(target)
    return regions


def _params_of(fn: ast.AST) -> set[str]:
    args = getattr(fn, "args", None)
    if args is None:
        return set()
    names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
    if args.vararg:
        names.append(args.vararg.arg)
    return set(names) - {"self", "cls"}


def hot_loop_functions(mod: Module) -> list[ast.FunctionDef]:
    return mod.memo("hot_loop_functions", lambda m: [
        node for node in m.walk()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
        and m.annotation(node, "hot_loop") is not None])


def _walk_own(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body without descending into nested defs/lambdas
    (a nested def has its own hot-loop/jit classification)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _self_attr(node: ast.AST) -> Optional[str]:
    """'self.X' (or 'self.X.Y...') rendered, when node is rooted at self."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name) and node.id == "self" and parts:
        return "self." + ".".join(reversed(parts))
    return None


def _followed_helpers(mod: Module, regions: list[ast.AST]) -> list[ast.AST]:
    """Call-following into helpers whose bodies execute traced.

    Same-module (ISSUE 7): a helper called from jit regions whose EVERY
    resolvable caller is itself traced counts as a region — host syncs
    inside are the same defect. Helpers also reachable from host-side
    code are skipped (they may be the designed host path). With a whole-
    program ``Program`` attached (ISSUE 8) the following is TRANSITIVE
    with the program's depth bound, so a jit fact propagates through a
    helper chain instead of stopping one call deep."""
    region_ids = {id(r) for r in regions}
    cg = mod.callgraph
    out: list[ast.AST] = []
    traced = set(region_ids)
    frontier = list(regions)
    depth = 1 if mod.program is None else mod.program.MAX_DEPTH
    for _ in range(depth):
        nxt: list[ast.AST] = []
        for region in frontier:
            for callee in cg.callees(region):
                if id(callee) in traced:
                    continue
                callers = cg.callers_of(callee)
                if callers and all(id(c) in traced for c in callers):
                    traced.add(id(callee))
                    out.append(callee)
                    nxt.append(callee)
        frontier = nxt
        if not frontier:
            break
    return out


@register
class HostSyncInJit(Rule):
    id = "D101"
    name = "host-sync-in-jit"
    doc = ("blocking host sync inside a jax.jit-compiled function "
           "(device_get/.item()/.block_until_ready()/np.asarray/"
           "float|int on a traced parameter), including one-level "
           "same-module helpers only ever called from jitted code")

    def check(self, mod: Module) -> Iterable[Finding]:
        seen: set[int] = set()
        regions = jit_regions(mod)
        for region in regions + _followed_helpers(mod, regions):
            if id(region) in seen:
                continue
            seen.add(id(region))
            params = _params_of(region)
            for node in _walk_own(region):
                if not isinstance(node, ast.Call):
                    continue
                qn = mod.qualname(node.func)
                if qn in _HOST_FETCH or qn in _HOST_NP:
                    yield mod.finding(
                        self, node,
                        f"'{qn}' forces a host sync inside a jitted "
                        "function; keep results device-resident")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SYNC_METHODS \
                        and not node.args:
                    yield mod.finding(
                        self, node,
                        f"'.{node.func.attr}()' blocks on device inside "
                        "a jitted function")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in ("float", "int", "bool") \
                        and node.args \
                        and isinstance(node.args[0], ast.Name) \
                        and node.args[0].id in params:
                    yield mod.finding(
                        self, node,
                        f"'{node.func.id}()' on traced parameter "
                        f"'{node.args[0].id}' forces a concrete value "
                        "(host sync / tracer error) inside a jitted "
                        "function")


@register
class HostSyncInHotLoop(Rule):
    id = "D102"
    name = "host-sync-in-hot-loop"
    doc = ("blocking host sync inside a '# hot-loop' function without a "
           "'# sync-point:' justification")

    def check(self, mod: Module) -> Iterable[Finding]:
        for fn in hot_loop_functions(mod):
            for node in _walk_own(fn):
                if not isinstance(node, ast.Call):
                    continue
                qn = mod.qualname(node.func)
                hit = None
                if qn in _HOST_FETCH:
                    hit = f"'{qn}'"
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SYNC_METHODS \
                        and not node.args:
                    hit = f"'.{node.func.attr}()'"
                elif qn == "time.sleep":
                    hit = "'time.sleep'"
                if hit is None:
                    continue
                if mod.line_annotation(node.lineno, "sync_point") is not None:
                    continue
                yield mod.finding(
                    self, node,
                    f"{hit} blocks the decode hot loop in "
                    f"'{fn.name}'; batch the fetch or mark the one "
                    "designed sync with '# sync-point: <reason>'")


@register
class FullBufferReupload(Rule):
    id = "D103"
    name = "full-buffer-reupload"
    doc = ("jnp.asarray/jnp.array/jax.device_put of a persistent self.* "
           "buffer inside a '# hot-loop' function (the PR-4 per-round "
           "full-table upload)")

    def check(self, mod: Module) -> Iterable[Finding]:
        for fn in hot_loop_functions(mod):
            for node in _walk_own(fn):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                if mod.qualname(node.func) not in _UPLOAD:
                    continue
                attr = _self_attr(node.args[0]) or (
                    _self_attr(node.args[0].value)
                    if isinstance(node.args[0], ast.Subscript) else None)
                if attr is None:
                    continue
                if mod.line_annotation(node.lineno, "sync_point") is not None:
                    continue
                yield mod.finding(
                    self, node,
                    f"full upload of persistent buffer '{attr}' every "
                    f"round in '{fn.name}'; keep it device-resident and "
                    "sync a round's dirty indices together "
                    "(serve/device_state.py: DecodeState.sync)")


def _donating_callables(mod: Module) -> dict[str, tuple[int, ...]]:
    """Map of callee spellings ('self._paged_decode_n' / 'decode_n') to donated
    positional indices — read from the shared jit-fact table
    (``core.jit_table``), the same source the F6xx dispatch-signature
    rules use, so donation facts can't drift between families."""
    return {name: fact.donate_argnums
            for name, fact in jit_table(mod).items()
            if fact.donate_argnums}


def _expr_key(node: ast.AST) -> Optional[str]:
    """Stable text for simple expressions (names / self-attr chains)."""
    if isinstance(node, ast.Name):
        return node.id
    return _self_attr(node)


def _assigned_keys(stmt: ast.stmt) -> set[str]:
    keys: set[str] = set()
    targets: list[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    for t in targets:
        for node in ast.walk(t):
            k = _expr_key(node)
            if k:
                keys.add(k)
    return keys


@register
class DonatedBufferReuse(Rule):
    id = "D104"
    name = "donated-buffer-reuse"
    doc = ("a buffer donated to a jitted dispatch (donate_argnums) is "
           "read again before being rebound")

    def check(self, mod: Module) -> Iterable[Finding]:
        donors = _donating_callables(mod)
        if not donors:
            return
        for fn in mod.walk():
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_body(mod, fn)

    def _check_body(self, mod: Module, fn: ast.AST) -> Iterable[Finding]:
        donors = _donating_callables(mod)
        # watched donated-expression -> (callee, call line)
        watched: dict[str, tuple[str, int]] = {}

        def helper_touch(call: ast.Call, keys: set[str]
                         ) -> tuple[set[str], set[str]]:
            """One-level call-following: (reads, writes) of watched
            ``self.*`` keys inside a same-class helper this call resolves
            to. A helper that writes the key rebinds it (no finding); one
            that only reads it is a donated-buffer use."""
            target = mod.callgraph.resolve_call(call, fn)
            self_keys = {k for k in keys if k.startswith("self.")}
            if target is None or not self_keys \
                    or not isinstance(call.func, ast.Attribute):
                return set(), set()
            reads: set[str] = set()
            writes: set[str] = set()
            for node in ast.walk(target):
                if isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        k = _expr_key(t)
                        if k in self_keys:
                            writes.add(k)
                elif isinstance(node, ast.Attribute) \
                        and isinstance(node.ctx, ast.Load):
                    k = _expr_key(node)
                    if k in self_keys:
                        reads.add(k)
            return reads - writes, writes

        def process(nodes: list[ast.AST], stmt: ast.stmt,
                    rebound: set[str]) -> Iterable[Finding]:
            """Handle the expression payload of ONE statement (a simple
            statement's whole tree, or just a compound statement's
            header): reads of watched buffers, then new donations."""
            new_watch: dict[str, tuple[str, int]] = {}
            reads: set[str] = set()
            for root in nodes:
                for node in ast.walk(root):
                    if isinstance(node, ast.Call):
                        callee = _expr_key(node.func)
                        if callee in donors:
                            for pos in donors[callee]:
                                if pos < len(node.args):
                                    key = _expr_key(node.args[pos])
                                    if key:
                                        new_watch[key] = (callee,
                                                          node.lineno)
                        elif watched:
                            h_reads, h_writes = helper_touch(
                                node, set(watched))
                            reads.update(k for k in h_reads
                                         if k not in rebound)
                            for k in h_writes:
                                watched.pop(k, None)
                    if isinstance(node, (ast.Name, ast.Attribute)):
                        k = _expr_key(node)
                        if k in watched and k not in rebound:
                            reads.add(k)
            for k in sorted(reads):
                callee, _line = watched.pop(k)
                yield Finding(
                    rule=self.id, name=self.name, path=mod.relpath,
                    line=stmt.lineno, col=stmt.col_offset + 1,
                    message=(f"'{k}' was donated to '{callee}' and is "
                             "used again without being rebound; donated "
                             "buffers are invalid after dispatch"),
                    symbol=mod.symbol_for(stmt))
            for k in rebound:
                watched.pop(k, None)
            for k, v in new_watch.items():
                if k not in rebound:
                    watched[k] = v

        _BODY_FIELDS = ("body", "orelse", "finalbody")

        def scan(stmts: list[ast.stmt]) -> Iterable[Finding]:
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                    continue    # visited separately by check()
                compound = any(getattr(stmt, f, None) for f in _BODY_FIELDS)
                if not compound:
                    yield from process([stmt], stmt, _assigned_keys(stmt))
                    continue
                # compound: only the header expressions execute "here";
                # the bodies are scanned statement-by-statement below.
                header: list[ast.AST] = []
                for f in ("test", "iter", "subject"):
                    v = getattr(stmt, f, None)
                    if v is not None:
                        header.append(v)
                for item in getattr(stmt, "items", []) or []:
                    header.append(item.context_expr)
                if header:
                    yield from process(header, stmt, set())
                # Branches are mutually exclusive: each starts from the
                # same snapshot; survivors union afterwards (a donation in
                # one branch must not read as a use in its sibling).
                snapshot = dict(watched)
                survivors: dict[str, tuple[str, int]] = {}
                bodies = [getattr(stmt, f, None) for f in _BODY_FIELDS]
                bodies += [h.body for h in
                           (getattr(stmt, "handlers", []) or [])]
                for sub in bodies:
                    if not sub:
                        continue
                    watched.clear()
                    watched.update(snapshot)
                    yield from scan(sub)
                    survivors.update(watched)
                watched.clear()
                watched.update(survivors)

        body = getattr(fn, "body", [])
        yield from scan(body)


@register
class JitInLoop(Rule):
    id = "D105"
    name = "jit-in-loop"
    doc = ("jax.jit(...) constructed inside a loop or hot-loop function "
           "(per-call retrace / compile-cache churn)")

    def check(self, mod: Module) -> Iterable[Finding]:
        hot = {id(fn) for fn in hot_loop_functions(mod)}
        for node in mod.walk():
            if not _is_jit_call(mod, node):
                continue
            cur = getattr(node, "_parent", None)
            in_loop = False
            while cur is not None:
                if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                    in_loop = True
                    break
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if id(cur) in hot:
                        in_loop = True
                    break
                cur = getattr(cur, "_parent", None)
            if in_loop:
                yield mod.finding(
                    self, node,
                    "jax.jit constructed per iteration; build it once "
                    "at init and reuse the compiled program")
