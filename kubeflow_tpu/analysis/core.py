"""``kftpu lint`` — codebase-aware static analysis for the platform.

The last three PRs each burned a debugging session on a defect class a
machine can catch from the AST alone: an implicit per-round host→device
upload hiding in the decode hot loop (the PR-4 ``jnp.asarray(self._table)``
bug), control-plane state mutated cross-thread without its lock (chaos
tests catch that only probabilistically), and metric-name hygiene enforced
only at render time. This package is the machine: an AST walker, a rule
registry, and two rule families tuned to how THIS codebase is written —
device hygiene over the serving/ops/parallel hot paths, lock discipline
over the threaded control plane — plus the metric-name rules ported from
``obs/registry.lint()`` to definition sites.

Annotation grammar (comments; same line as the construct or the line
directly above):

- ``# guarded_by: <lock_attr>`` — on an attribute's ``__init__``
  assignment: every mutation of the attribute outside ``__init__`` must
  hold ``self.<lock_attr>`` (lexically under ``with self.<lock_attr>`` /
  a Condition built from it, or in a method that declares the lock held).
- ``# lockfree: <reason>`` — on an attribute's ``__init__`` assignment:
  deliberately unsynchronized (thread-confined, delegated, GIL-atomic);
  the reason is required and shows up in ``--list-annotations`` audits.
- ``# requires_lock: <lock_attr>`` — on a ``def``: callers hold the lock;
  the body counts as guarded. Methods named ``*_locked`` get this
  implicitly (the codebase's existing convention).
- ``# hot-loop`` — on a ``def``: the function is on the decode/dispatch
  hot path; blocking host syncs and full-buffer uploads are findings.
- ``# traced`` — on a ``def``: the body is compiled under ``jax.jit``
  (used where the jit wrapping happens in another module); host syncs
  inside are findings.
- ``# sync-point: <reason>`` — on a line inside a hot-loop function: this
  host sync is the designed one (e.g. the pipelined consume fetch).
- ``# mesh-context: <reason>`` — on a ``def``: the function runs under a
  mesh / ``shard_map`` context established by a caller this module cannot
  see; collectives with literal axis names inside are bound there (S405).
- ``# retrace-ok: <reason>`` — on a line inside a function: this jitted
  call site's dispatch-signature instability is intentional (a cold path
  where the retrace is cheaper than padding); closes the F6xx
  compilation-stability rules on that line.
- ``# contract: <reason>`` — on a name-exchange site (metric series
  reference, header set/read, ``KFTPU_*`` env access, status-field
  read): this name is INTENTIONALLY one-sided — a user-facing knob
  nothing in the tree sets, a value exported for code outside the lint
  scan — and the X7xx cross-component contract rules accept it with the
  stated reason on record.
- ``# blocking-ok: <reason>`` — on a blocking call site (or the line
  above): this call is DELIBERATELY unbounded — a fault injector's
  wedge, a final reap after terminate, a durability wait whose caller
  owns the deadline — and the T8xx liveness rules accept it with the
  stated reason on record.
- ``# lint: disable=D101[,C301...]`` — suppress specific rules on this
  line.

Interprocedural core (ISSUE 7): every module gets a call graph with
ONE-level call-following (``Module.callgraph``) so dataflow rules — jit
region scanning, donation tracking, lock-held regions, resource pairing —
see through same-module helper calls without whole-program analysis, plus
a shared resource-pairing primitive (``leaky_allocs``) for the
alloc/free-on-exception-path rule family.

Whole-program core (ISSUE 8): one ``Program`` per lint run parses every
``kubeflow_tpu/*`` module exactly ONCE (a process-level AST cache shares
parses across rule families, seeded-regression re-lints, and ``--changed``
subsets that still need package-wide resolution context), resolves
imports across modules (``from kubeflow_tpu.serve.spec_decode import
paged_verify_step`` makes the callee's def visible to a rule scanning the
importer), and propagates jit/donation/static-argnum facts transitively
through the cross-module call graph with a depth bound
(``Program.transitive_callees``). The compilation-stability family
(``rules_compile.py``, F6xx) is built on this: a dispatch-signature fact
attached to a jitted callable in one module follows it to call sites in
every other.

Baseline: a checked-in JSON file (default ``.kftpu-lint-baseline.json``,
discovered upward from the scanned paths) holding fingerprints of known
pre-existing findings with a one-line justification each, so legacy debt
does not block CI while new findings still fail it. Fingerprints are
line-number-free (rule | path | enclosing symbol | message), so unrelated
edits don't invalidate the baseline.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import io
import json
import os
import re
import sys
import time
import tokenize
from collections import Counter
from typing import Iterable, Optional

# -- findings ------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str          # e.g. "D103"
    name: str          # e.g. "full-buffer-reupload"
    path: str          # repo-relative, '/'-separated
    line: int
    col: int
    message: str
    symbol: str = ""   # enclosing Class.method qualname (baseline key part)

    @property
    def fingerprint(self) -> str:
        # Deliberately line-free: the baseline must survive unrelated edits.
        return f"{self.rule}|{self.path}|{self.symbol}|{self.message}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"[{self.name}] {self.message}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# -- annotations ---------------------------------------------------------------

_ANNOT_RES = {
    "guarded_by": re.compile(r"#\s*guarded_by:\s*([A-Za-z_]\w*)"),
    "lockfree": re.compile(r"#\s*lockfree:\s*(\S.*)"),
    "requires_lock": re.compile(r"#\s*requires_lock:\s*([A-Za-z_]\w*)"),
    "hot_loop": re.compile(r"#\s*hot-loop\b"),
    "traced": re.compile(r"#\s*traced\b"),
    "sync_point": re.compile(r"#\s*sync-point:\s*(\S.*)"),
    "mesh_context": re.compile(r"#\s*mesh-context:\s*(\S.*)"),
    "retrace_ok": re.compile(r"#\s*retrace-ok:\s*(\S.*)"),
    "contract": re.compile(r"#\s*contract:\s*(\S.*)"),
    "blocking_ok": re.compile(r"#\s*blocking-ok:\s*(\S.*)"),
}
_DISABLE_RE = re.compile(r"#\s*lint:\s*disable=([A-Z]+\d+(?:\s*,\s*[A-Z]+\d+)*)")


class Module:
    """One parsed source file: AST with parent links, comment map, import
    aliases, and the annotation lookups every rule shares."""

    def __init__(self, relpath: str, text: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.text = text
        self.tree = ast.parse(text)
        # One walk serves both the parent links and the cached node list
        # (``Module.walk``): every whole-tree scan a rule family does
        # afterwards iterates this list instead of re-walking the tree.
        self._nodes: list[ast.AST] = [self.tree]
        for node in self._nodes:        # grows while iterating: BFS
            for child in ast.iter_child_nodes(node):
                child._parent = node  # type: ignore[attr-defined]
                self._nodes.append(child)
        self.comments: dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(io.StringIO(text).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except tokenize.TokenError:
            pass
        self.aliases = self._build_aliases()
        self._callgraph: Optional["CallGraph"] = None
        # Set by Program when this module is linted in a whole-program
        # run; None for standalone lint_source fixtures (rules degrade to
        # module-local analysis).
        self.program: Optional["Program"] = None
        self._memo: dict = {}

    def memo(self, key: str, build):
        """Per-module computed-structure cache (class models, hot-loop
        lists, jit tables): each is derived from the immutable tree, so
        rule families share ONE computation per module instead of
        re-deriving it per rule — the parse-once contract extended to
        everything parsed FROM the parse."""
        if key not in self._memo:
            self._memo[key] = build(self)
        return self._memo[key]

    def walk(self, *types: type) -> Iterable[ast.AST]:
        """Whole-tree node iteration off the cached list built at parse
        (``ast.walk(mod.tree)`` re-walks the tree per call — at ~30
        whole-tree scans per module across the rule families that was
        the self-scan's single biggest cost). ``types`` filters by
        isinstance."""
        if not types:
            return iter(self._nodes)
        return (n for n in self._nodes if isinstance(n, types))

    @property
    def callgraph(self) -> "CallGraph":
        if self._callgraph is None:
            self._callgraph = CallGraph(self)
        return self._callgraph

    # -- imports / names ---------------------------------------------------

    def _build_aliases(self) -> dict[str, str]:
        aliases: dict[str, str] = {}
        for node in self._nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        aliases[a.asname] = a.name
                    else:
                        # ``import urllib.request`` binds the TOP package
                        # name only; the attribute chain already spells
                        # the rest (mapping urllib -> urllib.request
                        # would double the segment:
                        # urllib.request.request.urlopen).
                        top = a.name.split(".")[0]
                        aliases.setdefault(top, top)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        return aliases

    def qualname(self, node: ast.AST) -> Optional[str]:
        """Dotted, alias-expanded name of a Name/Attribute chain
        (``np.asarray`` → ``numpy.asarray``), or None for anything
        dynamic."""
        parts: list[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            base = self.aliases.get(node.id, node.id)
            return ".".join([base] + list(reversed(parts)))
        return None

    # -- annotations -------------------------------------------------------

    def _lines_for(self, node: ast.AST) -> Iterable[int]:
        line = getattr(node, "lineno", None)
        if line is None:
            return ()
        end = getattr(node, "end_lineno", line) or line
        return range(line - 1, end + 1)

    def annotation(self, node: ast.AST, name: str) -> Optional[str]:
        """Value of annotation ``name`` attached to ``node`` (its line
        span or the line directly above), else None. Marker annotations
        (hot-loop/traced) return "" when present."""
        regex = _ANNOT_RES[name]
        for ln in self._lines_for(node):
            m = regex.search(self.comments.get(ln, ""))
            if m:
                return m.group(1).strip() if m.groups() else ""
        return None

    def line_annotation(self, line: int, name: str) -> Optional[str]:
        m = _ANNOT_RES[name].search(self.comments.get(line, ""))
        if m:
            return m.group(1).strip() if m.groups() else ""
        return None

    def suppressed(self, line: int, rule: str) -> bool:
        for ln in (line, line - 1):
            m = _DISABLE_RE.search(self.comments.get(ln, ""))
            if m and rule in {r.strip() for r in m.group(1).split(",")}:
                return True
        return False

    # -- structure ---------------------------------------------------------

    def symbol_for(self, node: ast.AST) -> str:
        parts: list[str] = []
        cur = getattr(node, "_parent", None)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            parts.append(node.name)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = getattr(cur, "_parent", None)
        return ".".join(reversed(parts))

    def enclosing_function(self, node: ast.AST):
        cur = getattr(node, "_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return cur
            cur = getattr(cur, "_parent", None)
        return None

    def finding(self, rule: "Rule", node: ast.AST, message: str,
                symbol: Optional[str] = None) -> Finding:
        return Finding(rule=rule.id, name=rule.name, path=self.relpath,
                       line=getattr(node, "lineno", 0),
                       col=getattr(node, "col_offset", 0) + 1,
                       message=message,
                       symbol=symbol if symbol is not None
                       else self.symbol_for(node))


# -- interprocedural core ------------------------------------------------------


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


class CallGraph:
    """Module-level call graph with ONE-level call-following.

    Resolution is deliberately modest — exactly what same-module helper
    calls need and no more: a bare ``name(...)`` resolves to a
    module-level ``def name``; ``self.m(...)`` inside a method resolves to
    that class's method ``m``. Anything dynamic stays unresolved. Rules use
    ``callees`` to peek one level into helpers (donation reads, jit-region
    host syncs, lock acquisitions) and ``callers`` to stay conservative
    (skip a helper that is also reachable from a context the rule does not
    model)."""

    def __init__(self, mod: Module):
        self.mod = mod
        self.module_fns: dict[str, ast.AST] = {}
        self.class_methods: dict[str, dict[str, ast.AST]] = {}
        # attr name -> same-module class name, from `self.X = Cls(...)`
        self.attr_class: dict[tuple[str, str], str] = {}
        for stmt in mod.tree.body:
            if isinstance(stmt, _FUNC_NODES):
                self.module_fns[stmt.name] = stmt
            elif isinstance(stmt, ast.ClassDef):
                methods = {}
                for s in stmt.body:
                    if isinstance(s, _FUNC_NODES):
                        methods[s.name] = s
                self.class_methods[stmt.name] = methods
        for cname, methods in self.class_methods.items():
            init = methods.get("__init__")
            if init is None:
                continue
            for node in ast.walk(init):
                if not isinstance(node, ast.Assign) or \
                        not isinstance(node.value, ast.Call):
                    continue
                callee = node.value.func
                tgt_cls = callee.id if isinstance(callee, ast.Name) else None
                if tgt_cls not in self.class_methods:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        self.attr_class[(cname, t.attr)] = tgt_cls
        self._callers: Optional[dict[int, set[int]]] = None

    def enclosing_class(self, fn: ast.AST) -> Optional[str]:
        cur = getattr(fn, "_parent", None)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur.name
            cur = getattr(cur, "_parent", None)
        return None

    def resolve_call(self, call: ast.Call, fn: ast.AST) -> Optional[ast.AST]:
        """The same-module FunctionDef a call site targets, or None."""
        func = call.func
        if isinstance(func, ast.Name):
            return self.module_fns.get(func.id)
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name):
            cls = self.enclosing_class(fn)
            if func.value.id == "self" and cls is not None:
                return self.class_methods.get(cls, {}).get(func.attr)
        # self.<attr>.m() where __init__ bound attr to a same-module class
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Attribute) \
                and isinstance(func.value.value, ast.Name) \
                and func.value.value.id == "self":
            cls = self.enclosing_class(fn)
            tgt = self.attr_class.get((cls or "", func.value.attr))
            if tgt is not None:
                return self.class_methods.get(tgt, {}).get(func.attr)
        return None

    def callees(self, fn: ast.AST) -> list[ast.AST]:
        out, seen = [], {id(fn)}
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                target = self.resolve_call(node, fn)
                if target is not None and id(target) not in seen:
                    seen.add(id(target))
                    out.append(target)
        return out

    def callers_of(self, fn: ast.AST) -> list[ast.AST]:
        if self._callers is None:
            self._callers = {}
            all_fns = list(self.module_fns.values()) + [
                m for ms in self.class_methods.values()
                for m in ms.values()]
            self._by_id = {id(f): f for f in all_fns}
            for f in all_fns:
                for callee in self.callees(f):
                    self._callers.setdefault(id(callee), set()).add(id(f))
        return [self._by_id[i] for i in self._callers.get(id(fn), ())]


# -- jit facts -----------------------------------------------------------------


_JIT_CTOR_QNS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}


@dataclasses.dataclass
class JitFact:
    """What the analyzer knows about one jitted-callable spelling: the
    constructor call, which positional args are static (hashed, not
    traced), and which are donated. The single source every dispatch-
    signature rule (F6xx) and donation rule (D104/S401) reads, so the
    fact set can't drift between families."""

    name: str               # call-site spelling ('self._paged_decode_n')
    ctor: ast.AST                   # the jax.jit(...) call or decorated def
    static_argnums: tuple[int, ...] = ()
    static_argnames: tuple[str, ...] = ()
    donate_argnums: tuple[int, ...] = ()
    donate_argnames: tuple[str, ...] = ()
    fn_node: Optional[ast.AST] = None   # the wrapped def, when resolvable

    @property
    def donates(self) -> bool:
        return bool(self.donate_argnums or self.donate_argnames)


def _int_tuple(node: Optional[ast.AST]) -> tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def _str_tuple(node: Optional[ast.AST]) -> tuple[str, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str))
    return ()


def _fact_from_ctor(mod: Module, name: str, call: ast.Call) -> JitFact:
    fact = JitFact(name=name, ctor=call)
    for kw in call.keywords:
        if kw.arg in ("static_argnums", "donate_argnums"):
            setattr(fact, kw.arg, _int_tuple(kw.value))
        elif kw.arg in ("static_argnames", "donate_argnames"):
            setattr(fact, kw.arg, _str_tuple(kw.value))
    if call.args and isinstance(call.args[0], ast.Name):
        cg = mod.callgraph
        fact.fn_node = cg.module_fns.get(call.args[0].id)
    return fact


def _expr_spelling(node: ast.AST) -> Optional[str]:
    """Dotted source spelling of a Name/Attribute chain (``self._fn``,
    ``engine._paged_decode_n``) — the call-site key jit facts are stored
    under."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        return ".".join([node.id] + list(reversed(parts)))
    return None


def jit_table(mod: Module) -> dict[str, JitFact]:
    """Every jitted-callable spelling this module defines: ``X = jax.jit
    (...)`` / ``self.X = jax.jit(...)`` assignments anywhere, plus
    ``@jax.jit`` / ``@partial(jax.jit, ...)`` decorated defs (keyed by
    the def's name). Cached on the module."""
    return mod.memo("jit_table", _build_jit_table)


def _build_jit_table(mod: Module) -> dict[str, JitFact]:
    out: dict[str, JitFact] = {}
    for node in mod.walk():
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.value, ast.Call) \
                and mod.qualname(node.value.func) in _JIT_CTOR_QNS:
            name = _expr_spelling(node.targets[0])
            if name:
                out[name] = _fact_from_ctor(mod, name, node.value)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if mod.qualname(dec) in _JIT_CTOR_QNS:
                    out[node.name] = JitFact(name=node.name, ctor=node,
                                             fn_node=node)
                    break
                if isinstance(dec, ast.Call):
                    dqn = mod.qualname(dec.func)
                    if dqn in _JIT_CTOR_QNS or (
                            dqn in ("functools.partial", "partial")
                            and dec.args
                            and mod.qualname(dec.args[0]) in _JIT_CTOR_QNS):
                        fact = _fact_from_ctor(mod, node.name, dec)
                        fact.fn_node = node
                        out[node.name] = fact
                        break
    return out


# -- whole-program core --------------------------------------------------------


def module_dotted_name(relpath: str) -> Optional[str]:
    """``kubeflow_tpu/serve/engine.py`` → ``kubeflow_tpu.serve.engine``;
    ``kubeflow_tpu/__init__.py`` → ``kubeflow_tpu``. None for paths
    outside an importable layout (scripts, bench drivers)."""
    if not relpath.endswith(".py"):
        return None
    parts = relpath[:-3].replace(os.sep, "/").split("/")
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    if not parts or not all(p.isidentifier() for p in parts):
        return None
    return ".".join(parts)


# Process-level parse cache: (abspath, mtime_ns, size, relpath) → Module.
# One lint run parses each file once and every rule family shares the
# tree; repeated runs in one process (the seeded-regression self-checks,
# test suites) re-parse only files that actually changed.
_MODULE_CACHE: dict[str, tuple[int, int, str, Module]] = {}


def load_module(path: str, relpath: str) -> Module:
    apath = os.path.abspath(path)
    st = os.stat(apath)
    hit = _MODULE_CACHE.get(apath)
    if hit is not None and hit[:3] == (st.st_mtime_ns, st.st_size, relpath):
        return hit[3]
    with open(apath, encoding="utf-8") as f:
        text = f.read()
    mod = Module(relpath, text)
    _MODULE_CACHE[apath] = (st.st_mtime_ns, st.st_size, relpath, mod)
    return mod


class Program:
    """Whole-program view over one lint run: every module parsed once,
    imports resolved across ``kubeflow_tpu/*``, and jit/donation facts
    followable transitively (depth-bounded) through the cross-module call
    graph. Rules receive it via ``Module.program`` and must degrade to
    module-local analysis when it is None (standalone fixtures)."""

    #: Transitive call-following stops here: deep enough to cross a
    #: dispatch helper chain, shallow enough that one mega-module cannot
    #: make the analysis quadratic.
    MAX_DEPTH = 4

    def __init__(self, modules: Iterable[Module]):
        self.modules: list[Module] = list(modules)
        self.by_path: dict[str, Module] = {}
        self.by_name: dict[str, Module] = {}
        for m in self.modules:
            self.by_path[m.relpath] = m
            dotted = module_dotted_name(m.relpath)
            if dotted is not None:
                self.by_name[dotted] = m
            m.program = self
        self._jit_by_qual: Optional[dict[str, JitFact]] = None
        self._memo: dict = {}

    def memo(self, key: str, build):
        """Per-program computed-structure cache (the X-family contract
        table): whole-program aggregates are derived once per lint run
        and shared by every rule that needs them — the per-module
        ``Module.memo`` contract lifted to the Program."""
        if key not in self._memo:
            self._memo[key] = build(self)
        return self._memo[key]

    # -- name resolution ---------------------------------------------------

    def resolve(self, qualname: str
                ) -> Optional[tuple[Module, ast.AST]]:
        """(module, def/class node) for a fully-dotted name — longest
        module prefix wins, then module-level ``def``/``class`` or one
        ``Class.method`` level."""
        parts = qualname.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            mod = self.by_name.get(".".join(parts[:cut]))
            if mod is None:
                continue
            rest = parts[cut:]
            cg = mod.callgraph
            if len(rest) == 1:
                fn = cg.module_fns.get(rest[0])
                if fn is not None:
                    return mod, fn
                for stmt in mod.tree.body:
                    if isinstance(stmt, ast.ClassDef) \
                            and stmt.name == rest[0]:
                        return mod, stmt
            elif len(rest) == 2:
                m = cg.class_methods.get(rest[0], {}).get(rest[1])
                if m is not None:
                    return mod, m
            return None
        return None

    def resolve_call(self, mod: Module, call: ast.Call, fn: ast.AST
                     ) -> Optional[tuple[Module, ast.AST]]:
        """Cross-module call resolution: same-module first (the ISSUE-7
        callgraph), then the alias-expanded qualname against the program
        (``paged_verify_step(...)`` under ``from ..spec_decode import
        paged_verify_step`` lands on the def in spec_decode.py)."""
        local = mod.callgraph.resolve_call(call, fn)
        if local is not None:
            return mod, local
        qn = mod.qualname(call.func)
        if qn is None:
            return None
        return self.resolve(qn)

    def transitive_callees(self, mod: Module, fn: ast.AST,
                           depth: int = MAX_DEPTH
                           ) -> list[tuple[Module, ast.AST]]:
        """BFS over the cross-module call graph from ``fn``, depth-
        bounded — the propagation primitive jit-region scanning and the
        F6xx fact-following use."""
        out: list[tuple[Module, ast.AST]] = []
        seen = {id(fn)}
        frontier: list[tuple[Module, ast.AST]] = [(mod, fn)]
        for _ in range(max(depth, 0)):
            nxt: list[tuple[Module, ast.AST]] = []
            for cmod, cfn in frontier:
                for node in ast.walk(cfn):
                    if not isinstance(node, ast.Call):
                        continue
                    got = self.resolve_call(cmod, node, cfn)
                    if got is None or id(got[1]) in seen:
                        continue
                    seen.add(id(got[1]))
                    out.append(got)
                    nxt.append(got)
            frontier = nxt
            if not frontier:
                break
        return out

    # -- jit facts ---------------------------------------------------------

    def jit_facts(self, mod: Module) -> dict[str, JitFact]:
        """The jit table visible AT CALL SITES in ``mod``: its own
        definitions plus imported spellings that resolve to jitted
        module-level names elsewhere in the program (``from a import G``
        with ``G = jax.jit(...)`` in a.py makes ``G(...)`` here carry
        a.py's static/donate facts)."""
        out = dict(jit_table(mod))
        if self._jit_by_qual is None:
            self._jit_by_qual = {}
            for m in self.modules:
                dotted = module_dotted_name(m.relpath)
                if dotted is None:
                    continue
                for name, fact in jit_table(m).items():
                    if "." not in name:      # module-level spellings only
                        self._jit_by_qual[f"{dotted}.{name}"] = fact
        for alias, target in mod.aliases.items():
            fact = self._jit_by_qual.get(target)
            if fact is not None and alias not in out:
                out[alias] = fact
        return out


def leaky_allocs(fn: ast.AST, is_alloc, releases_var):
    """Shared resource-pairing dataflow: yield ``(alloc_call, var,
    risky_stmt)`` for every ``var = <alloc>`` whose resource can leak on an
    exception path.

    ``is_alloc(call)`` classifies allocation calls; ``releases_var(stmt,
    var)`` says whether a statement releases/consumes ownership of ``var``
    (stores it into an owning structure, frees it, returns it, or passes it
    on). A statement between the alloc and the consumption that contains
    any call can raise — at which point nothing owns the resource — unless
    the alloc is inside a ``try`` whose handler or ``finally`` releases
    the var."""
    protected: set[int] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Try):
            continue
        cleanup = [s for h in node.handlers for s in h.body]
        cleanup += list(node.finalbody)
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call) and is_alloc(sub):
                    var = _alloc_target(sub)
                    if var and any(releases_var(c, var) for c in cleanup):
                        protected.add(id(sub))

    def scan(stmts, cont):
        """``cont`` is the statement continuation after this block (the
        rest of every enclosing block, in execution order) — ownership is
        routinely taken a block boundary later (alloc inside ``try``,
        recorded after it)."""
        for i, stmt in enumerate(stmts):
            if isinstance(stmt, _FUNC_NODES + (ast.ClassDef,)):
                continue
            rest = stmts[i + 1:] + cont
            alloc = None
            if isinstance(stmt, ast.Assign) and \
                    isinstance(stmt.value, ast.Call) and \
                    is_alloc(stmt.value) and id(stmt.value) not in protected:
                alloc = stmt.value
                var = _alloc_target(alloc)
            if alloc is not None and var:
                consumed = False
                for later in rest:
                    if releases_var(later, var):
                        consumed = True
                        break
                    if any(isinstance(n, ast.Call)
                           for n in ast.walk(later)):
                        yield alloc, var, later
                        consumed = True   # reported once; stop tracking
                        break
                if not consumed:
                    yield alloc, var, stmt
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, field, None)
                if sub:
                    yield from scan(sub, rest)
            for h in getattr(stmt, "handlers", []) or []:
                yield from scan(h.body, rest)

    yield from scan(list(getattr(fn, "body", [])), [])


def _alloc_target(call: ast.Call) -> Optional[str]:
    stmt = getattr(call, "_parent", None)
    if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
            and isinstance(stmt.targets[0], ast.Name):
        return stmt.targets[0].id
    return None


_CANONICAL_AXES_FALLBACK = (
    "dcn", "pipeline", "data", "fsdp", "expert", "seq", "model",
)
_canonical_axes_cache: Optional[tuple[str, ...]] = None


def canonical_mesh_axes() -> tuple[str, ...]:
    """The platform's canonical mesh-axis names, read from
    ``runtime/mesh.py``'s ``MESH_AXES`` assignment BY AST (the analyzer
    stays import-light: no jax). Falls back to the baked-in tuple when the
    source moves."""
    global _canonical_axes_cache
    if _canonical_axes_cache is not None:
        return _canonical_axes_cache
    axes = _CANONICAL_AXES_FALLBACK
    mesh_py = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "runtime", "mesh.py")
    try:
        with open(mesh_py, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        for stmt in tree.body:
            targets = stmt.targets if isinstance(stmt, ast.Assign) \
                else [stmt.target] if isinstance(stmt, ast.AnnAssign) else []
            for t in targets:
                if isinstance(t, ast.Name) and t.id == "MESH_AXES" \
                        and isinstance(stmt.value, (ast.Tuple, ast.List)):
                    vals = tuple(e.value for e in stmt.value.elts
                                 if isinstance(e, ast.Constant)
                                 and isinstance(e.value, str))
                    if vals:
                        axes = vals
    except (OSError, SyntaxError, ValueError):
        pass
    _canonical_axes_cache = axes
    return axes


# -- rule registry -------------------------------------------------------------


class Rule:
    id: str = ""
    name: str = ""
    doc: str = ""

    def check(self, mod: Module) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError


_RULES: list[Rule] = []


def register(cls: type) -> type:
    _RULES.append(cls())
    return cls


def all_rules() -> list[Rule]:
    _load_rules()
    return list(_RULES)


_loaded = False


def _load_rules() -> None:
    global _loaded
    if _loaded:
        return
    _loaded = True
    from kubeflow_tpu.analysis import (  # noqa: F401  (registration import)
        rules_compile, rules_concurrency, rules_contracts, rules_device,
        rules_liveness, rules_metrics, rules_resources, rules_sharding,
    )


# -- baseline ------------------------------------------------------------------


class Baseline:
    """Checked-in known-findings file: each entry a line-free fingerprint
    plus a one-line justification. Matching is multiset-aware (the same
    fingerprint may legitimately occur N times)."""

    def __init__(self, entries: Optional[list[dict]] = None,
                 path: Optional[str] = None):
        self.path = path
        self.entries = entries or []

    @classmethod
    def load(cls, path: str) -> "Baseline":
        with open(path) as f:
            doc = json.load(f)
        return cls(doc.get("entries", []), path=path)

    @classmethod
    def from_findings(cls, findings: Iterable[Finding],
                      reason: str = "baselined pre-existing debt"
                      ) -> "Baseline":
        # Sorted at construction AND at save: --update-baseline output is
        # a pure function of the finding SET, so rewriting the baseline
        # from a differently-ordered scan produces a byte-identical file
        # and baseline diffs stay reviewable.
        return cls(sorted(({"fingerprint": f.fingerprint, "reason": reason}
                           for f in findings),
                          key=lambda e: e["fingerprint"]))

    def save(self, path: str) -> None:
        doc = {"version": 1,
               "entries": sorted(self.entries,
                                 key=lambda e: (e["fingerprint"],
                                                e.get("reason", "")))}
        with open(path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")

    def split(self, findings: list[Finding]
              ) -> tuple[list[Finding], list[Finding]]:
        """(new, baselined)."""
        budget = Counter(e["fingerprint"] for e in self.entries)
        new, matched = [], []
        for f in findings:
            if budget.get(f.fingerprint, 0) > 0:
                budget[f.fingerprint] -= 1
                matched.append(f)
            else:
                new.append(f)
        return new, matched


BASELINE_FILENAME = ".kftpu-lint-baseline.json"


def find_baseline(paths: list[str]) -> Optional[str]:
    """Walk upward from the scanned paths (then the cwd) looking for the
    checked-in baseline file."""
    starts = [os.path.abspath(p) for p in paths] + [os.getcwd()]
    for start in starts:
        cur = start if os.path.isdir(start) else os.path.dirname(start)
        while True:
            cand = os.path.join(cur, BASELINE_FILENAME)
            if os.path.isfile(cand):
                return cand
            parent = os.path.dirname(cur)
            if parent == cur:
                break
            cur = parent
    return None


# -- running -------------------------------------------------------------------


@dataclasses.dataclass
class LintResult:
    new: list[Finding]
    baselined: list[Finding]
    errors: list[Finding]
    files_scanned: int
    wall_time_s: float = 0.0

    @property
    def ok(self) -> bool:
        return not self.new and not self.errors


def iter_py_files(paths: Iterable[str]) -> list[str]:
    out: list[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(d for d in dirs
                             if d != "__pycache__" and not d.startswith("."))
            out.extend(os.path.join(root, f) for f in sorted(files)
                       if f.endswith(".py"))
    return out


def lint_module(mod: Module, rules: Optional[list[Rule]] = None
                ) -> list[Finding]:
    """All non-suppressed findings for one parsed module."""
    findings: list[Finding] = []
    for rule in rules if rules is not None else all_rules():
        for f in rule.check(mod):
            if not mod.suppressed(f.line, f.rule):
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings


def lint_source(text: str, relpath: str = "<memory>.py",
                rules: Optional[list[Rule]] = None) -> list[Finding]:
    """Test/embedding entry point: lint one source string."""
    return lint_module(Module(relpath, text), rules=rules)


def lint_sources(sources: dict[str, str],
                 lint: Optional[list[str]] = None,
                 rules: Optional[list[Rule]] = None) -> list[Finding]:
    """Multi-module fixture entry point: parse every source under its
    relpath, wire them into one Program (cross-module resolution works),
    and lint ``lint`` (default: all of them)."""
    mods = {rel: Module(rel, text) for rel, text in sources.items()}
    Program(mods.values())
    findings: list[Finding] = []
    for rel in (lint if lint is not None else sorted(mods)):
        findings.extend(lint_module(mods[rel], rules=rules))
    return findings


class _ParseError(Rule):
    id = "E000"
    name = "parse-error"


_PARSE_ERROR = _ParseError()


def _package_context(root: str) -> list[str]:
    """Files the whole-program resolver should see even when only a
    subset is being linted (the ``--changed`` pre-commit path): the main
    package under ``root`` plus the smoke/bench drivers. The drivers
    matter to the X-family contract rules — they are the in-scan
    CONSUMERS of several metric series and the writers of sanitizer env
    vars, so a changed-file lint without them would misread two-sided
    names as orphans."""
    out: list[str] = []
    pkg = os.path.join(root, "kubeflow_tpu")
    if os.path.isdir(pkg):
        out.extend(iter_py_files([pkg]))
    scripts = os.path.join(root, "scripts")
    if os.path.isdir(scripts):
        out.extend(iter_py_files([scripts]))
    for name in ("bench.py", "bench_serve.py"):
        path = os.path.join(root, name)
        if os.path.isfile(path):
            out.append(path)
    return out


def build_program(paths: list[str], root: Optional[str] = None) -> Program:
    """Parse ``paths`` plus the package-wide resolution context into one
    ``Program`` WITHOUT linting — the entry scripts and tests use to
    reach whole-program tables (the X-family contract extractor, jit
    facts) directly. Unparseable files are skipped; their own lint run
    reports them."""
    root = os.path.abspath(root or os.getcwd())
    mods: list[Module] = []
    seen: set[str] = set()
    for path in iter_py_files(paths) + _package_context(root):
        apath = os.path.abspath(path)
        if apath in seen:
            continue
        seen.add(apath)
        rel = os.path.relpath(apath, root)
        try:
            mods.append(load_module(path, rel))
        except (OSError, SyntaxError, ValueError, UnicodeDecodeError):
            continue
    return Program(mods)


def run_lint(paths: list[str], baseline: Optional[Baseline] = None,
             root: Optional[str] = None) -> LintResult:
    """Lint every .py under ``paths``. Finding paths are relative to
    ``root`` (default: cwd), matching how the baseline was recorded.

    All modules — the linted set plus the package-wide resolution
    context — are parsed once into one ``Program`` shared by every rule
    family; ``wall_time_s`` on the result covers parse + all rules."""
    t0 = time.perf_counter()
    root = os.path.abspath(root or os.getcwd())
    findings: list[Finding] = []
    errors: list[Finding] = []
    files = iter_py_files(paths)
    mods: list[Module] = []
    for path in files:
        rel = os.path.relpath(os.path.abspath(path), root)
        try:
            mods.append(load_module(path, rel))
        except (OSError, SyntaxError, ValueError, UnicodeDecodeError) as exc:
            errors.append(Finding(
                rule="E000", name="parse-error",
                path=rel.replace(os.sep, "/"),
                line=getattr(exc, "lineno", 0) or 0, col=1,
                message=f"cannot parse: {exc}"))
    lint_paths = {m.relpath for m in mods}
    context = list(mods)
    for path in _package_context(root):
        rel = os.path.relpath(os.path.abspath(path), root)
        if rel in lint_paths:
            continue
        try:
            context.append(load_module(path, rel))
        except (OSError, SyntaxError, ValueError, UnicodeDecodeError):
            continue    # context only — its own lint run reports it
    Program(context)
    for mod in mods:
        findings.extend(lint_module(mod))
    if baseline is not None:
        new, matched = baseline.split(findings)
    else:
        new, matched = findings, []
    return LintResult(new=new, baselined=matched, errors=errors,
                      files_scanned=len(files),
                      wall_time_s=time.perf_counter() - t0)


# -- CLI -----------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="kftpu lint",
        description="codebase-aware static analysis (device hygiene + "
                    "lock discipline + sharding/SPMD + resource pairing "
                    "+ metric naming)")
    p.add_argument("paths", nargs="*", default=["kubeflow_tpu"],
                   help="files or directories to scan")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output")
    p.add_argument("--baseline", default=None,
                   help=f"baseline file (default: nearest "
                        f"{BASELINE_FILENAME})")
    p.add_argument("--no-baseline", action="store_true",
                   help="report every finding, ignoring the baseline")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline to the current findings "
                        "(each entry still needs a hand-written reason)")
    p.add_argument("--show-baselined", action="store_true",
                   help="also print findings matched by the baseline")
    p.add_argument("--changed", nargs="?", const="HEAD", default=None,
                   metavar="BASE",
                   help="lint only .py files changed vs BASE (default "
                        "HEAD: the working tree — the fast pre-commit "
                        "path); includes untracked files")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--contracts-json", action="store_true",
                   dest="contracts_json",
                   help="dump the statically-extracted cross-component "
                        "contract table (metric series produced/consumed, "
                        "X-Kftpu-* headers set/read, KFTPU_* env vars, "
                        "status fields) as JSON and exit — the manifest "
                        "the KFTPU_SANITIZE=contract runtime auditor "
                        "diffs against")
    return p


def changed_files(base: str = "HEAD",
                  root: Optional[str] = None) -> list[str]:
    """Paths of .py files changed vs ``base`` (plus untracked ones).

    Parses ``git diff --name-status`` rather than ``--name-only`` so
    deleted files (status ``D``) and the OLD half of a rename (``Rxxx``)
    are skipped by STATUS, not by racing the filesystem — a removed .py
    must never reach the file walker (it would error the pre-commit
    path). Git emits paths relative to the repo toplevel, so they are
    resolved there and returned relative to ``root`` (default cwd).
    Raises RuntimeError outside a git checkout (the caller turns that
    into a CLI error)."""
    import subprocess

    root = os.path.abspath(root or os.getcwd())

    def git(*args: str) -> list[str]:
        proc = subprocess.run(["git", *args], cwd=root,
                              capture_output=True, text=True, timeout=60)
        if proc.returncode != 0:
            raise RuntimeError(
                f"git {' '.join(args)} failed: {proc.stderr.strip()}")
        return [p for p in proc.stdout.split("\0") if p]

    toplevel = git("rev-parse", "--show-toplevel")[0].strip()
    files: set[str] = set()
    fields = git("diff", "--name-status", "-z", base, "--")
    i = 0
    while i < len(fields):
        status = fields[i]
        if status.startswith(("R", "C")):
            # Rxxx/Cxxx carry two paths: the old name (gone for R) and
            # the new one — only the new name is lintable.
            if i + 2 < len(fields):
                files.add(fields[i + 2])
            i += 3
        else:
            if not status.startswith("D"):      # deleted: nothing to lint
                files.add(fields[i + 1])
            i += 2
    files |= set(git("ls-files", "-o", "--exclude-standard",
                     "--full-name", "-z"))
    out = []
    for f in sorted(files):
        if not f.endswith(".py"):
            continue
        abspath = os.path.join(toplevel, f)
        # Belt and braces: a path added in the diff but removed from the
        # working tree since (or a directory shadowing it) is skipped.
        if os.path.isfile(abspath):
            out.append(os.path.relpath(abspath, root))
    return out


def main(argv: Optional[list[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.list_rules:
        for rule in sorted(all_rules(), key=lambda r: r.id):
            print(f"{rule.id}  {rule.name:28} {rule.doc}")
        return 0
    paths = args.paths or ["kubeflow_tpu"]
    if args.changed is not None:
        if args.update_baseline:
            print("--update-baseline needs a full scan, not --changed "
                  "(a changed-only rewrite would drop every other entry)",
                  file=sys.stderr)
            return 2
        try:
            paths = changed_files(args.changed)
        except RuntimeError as exc:
            print(f"--changed: {exc}", file=sys.stderr)
            return 2
        if not paths:
            print(f"0 files changed vs {args.changed}; nothing to lint")
            return 0
    if args.contracts_json:
        from kubeflow_tpu.analysis import rules_contracts

        program = build_program(paths)
        print(json.dumps(rules_contracts.contract_manifest(program),
                         indent=2, sort_keys=True))
        return 0
    baseline: Optional[Baseline] = None
    baseline_path = args.baseline
    if not args.no_baseline and not args.update_baseline:
        if baseline_path is None:
            baseline_path = find_baseline(paths)
        if baseline_path is not None and os.path.isfile(baseline_path):
            baseline = Baseline.load(baseline_path)
    result = run_lint(paths, baseline=baseline)
    if args.update_baseline:
        target = args.baseline or find_baseline(paths) or BASELINE_FILENAME
        Baseline.from_findings(result.new,
                               reason="baselined by --update-baseline; "
                                      "replace with a real justification"
                               ).save(target)
        print(f"wrote {len(result.new)} entries to {target}")
        return 0
    if args.as_json:
        print(json.dumps({
            "files_scanned": result.files_scanned,
            "wall_time_s": round(result.wall_time_s, 4),
            "findings": [f.to_json() for f in result.new],
            "baselined": [f.to_json() for f in result.baselined],
            "errors": [f.to_json() for f in result.errors],
            "ok": result.ok,
        }, indent=2))
    else:
        for f in result.errors + result.new:
            print(f.render())
        if args.show_baselined:
            for f in result.baselined:
                print(f"{f.render()}  (baselined)")
        tail = (f"{result.files_scanned} files, "
                f"{len(result.new)} finding(s), "
                f"{len(result.baselined)} baselined, "
                f"{result.wall_time_s:.2f}s")
        if baseline is not None and baseline.path:
            tail += f" ({os.path.basename(baseline.path)})"
        print(tail)
    return 0 if result.ok else 1


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
