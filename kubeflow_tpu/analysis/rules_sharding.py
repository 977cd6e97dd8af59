"""Family S — sharding / SPMD correctness rules (ISSUE 7 tentpole).

The next platform steps are a 3-D GSPMD ``pjit`` mesh and cross-request KV
sharing — exactly the territory where a silent sharding mistake costs 2×
HBM (an undonated carry), a wrong collective (a typo'd axis name), or a
per-round host round-trip. These rules encode the mesh/sharding contracts
the codebase already follows:

- S401 ``undonated-carry``: a ``jax.jit``/``pjit`` callable constructed
  WITHOUT ``donate_argnums`` whose call sites are carry-style — an
  argument expression reappears among the call's assignment targets
  (``self.cache = self._fn(self.cache)``). The old buffer stays resident
  while the new one materializes: 2× HBM for the platform's biggest
  arrays.
- S402 ``unknown-mesh-axis``: a hard-coded mesh-axis string in an axis
  position (``PartitionSpec``/``NamedSharding`` specs, ``Mesh`` axis
  names, ``axis_name=`` keywords) that is not one of the canonical axis
  names from ``runtime/mesh.py``'s ``MESH_AXES``. GSPMD treats an unknown
  axis as a fresh size-1 axis — the op silently stops being sharded.
- S403 ``host-round-trip``: a value fetched to host (``jax.device_get``,
  ``np.asarray``, ``.item()``) flows back into a jitted dispatch in the
  same function — a device→host→device bounce per call on the value's
  own dispatch path.
- S404 ``implicit-replication``: ``jax.device_put`` of a params/weights
  pytree with no sharding argument in a module that works with meshes —
  every chip gets a full copy; ``parallel/sharding.shard_params`` exists
  for exactly this call.
- S405 ``unbound-collective``: a collective (``psum``/``all_gather``/
  ``ppermute``/...) with a LITERAL ``axis_name`` in a function this
  module never places under ``shard_map``/``pjit`` (by the one-level call
  graph) and that isn't annotated ``# mesh-context: <reason>`` — at best
  a NameError at trace time, at worst a collective over the wrong axis
  when an outer binding happens to share the name.
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from kubeflow_tpu.analysis.core import (
    Finding, Module, Rule, canonical_mesh_axes, jit_table, register,
)

_SPEC_QNS = {
    "jax.sharding.PartitionSpec",
    "jax.sharding.NamedSharding",     # axis literals ride in its spec arg
}
_MESH_QNS = {"jax.sharding.Mesh", "jax.make_mesh"}
_HOST_FETCH_QNS = {"jax.device_get", "numpy.asarray", "numpy.array"}
_COLLECTIVE_QNS = {
    "jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax", "jax.lax.pmin",
    "jax.lax.all_gather", "jax.lax.all_to_all", "jax.lax.ppermute",
    "jax.lax.psum_scatter", "jax.lax.axis_index", "jax.lax.axis_size",
}
_SHARD_MAP_QNS = {
    "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
}


def _expr_key(node: ast.AST) -> Optional[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        return ".".join([node.id] + list(reversed(parts)))
    return None


def _jit_assignments(mod: Module) -> dict[str, tuple[ast.AST, bool]]:
    """Jitted-callable spellings with their donation flag, read from the
    shared jit-fact table (``core.jit_table``) — assignments and
    ``@partial(jax.jit, ...)`` decorations alike; bare-decorated defs
    are excluded (their ctor carries no argument spec to inspect)."""
    return {name: (fact.ctor, fact.donates)
            for name, fact in jit_table(mod).items()
            if isinstance(fact.ctor, ast.Call)}


@register
class UndonatedCarry(Rule):
    id = "S401"
    name = "undonated-carry"
    doc = ("jit/pjit callable called carry-style (an argument returns "
           "into itself) but constructed without donate_argnums — the "
           "old buffer stays resident: 2x HBM on the carry")

    def check(self, mod: Module) -> Iterable[Finding]:
        ctors = _jit_assignments(mod)
        undonated = {n: c for n, (c, d) in ctors.items() if not d}
        if not undonated:
            return
        reported: set[str] = set()
        for node in mod.walk():
            if not isinstance(node, ast.Assign):
                continue
            call = node.value
            if not isinstance(call, ast.Call):
                continue
            callee = _expr_key(call.func)
            if callee not in undonated or callee in reported:
                continue
            target_keys: set[str] = set()
            for t in node.targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                    else [t]
                for e in elts:
                    k = _expr_key(e)
                    if k:
                        target_keys.add(k)
            carried = sorted(
                k for k in (_expr_key(a) for a in call.args)
                if k and k in target_keys)
            if not carried:
                continue
            reported.add(callee)
            ctor = undonated[callee]
            yield mod.finding(
                self, ctor,
                f"'{callee}' is called carry-style ('{carried[0]}' "
                f"returns into its own argument at line {node.lineno}) "
                "but has no donate_argnums; donate the carry so the old "
                "buffer's HBM is reused")


def _axis_literals(node: ast.AST) -> Iterable[ast.Constant]:
    """String constants in an axis position of ``node`` (a spec/axis
    argument): bare strings and strings inside tuples/lists."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        yield node
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            yield from _axis_literals(e)
    elif isinstance(node, ast.BoolOp):
        # `batch_axes or None` — literals live in the operands
        for v in node.values:
            yield from _axis_literals(v)
    elif isinstance(node, ast.Starred):
        yield from _axis_literals(node.value)


@register
class UnknownMeshAxis(Rule):
    id = "S402"
    name = "unknown-mesh-axis"
    doc = ("hard-coded mesh-axis string that is not a canonical axis "
           "name from runtime/mesh.py MESH_AXES (GSPMD silently treats "
           "it as an unsharded fresh axis)")

    def check(self, mod: Module) -> Iterable[Finding]:
        axes = set(canonical_mesh_axes())
        for node in mod.walk():
            if not isinstance(node, ast.Call):
                continue
            qn = mod.qualname(node.func)
            spots: list[ast.AST] = []
            if qn in _SPEC_QNS and qn.endswith("PartitionSpec"):
                spots.extend(node.args)
            elif qn in _MESH_QNS:
                # Mesh(devices, axis_names) / make_mesh(shape, axis_names)
                spots.extend(node.args[1:2])
                spots.extend(kw.value for kw in node.keywords
                             if kw.arg == "axis_names")
            elif qn in _COLLECTIVE_QNS:
                spots.extend(node.args[1:2])
                spots.extend(kw.value for kw in node.keywords
                             if kw.arg == "axis_name")
            else:
                spots.extend(kw.value for kw in node.keywords
                             if kw.arg == "axis_name")
            for spot in spots:
                for lit in _axis_literals(spot):
                    if lit.value not in axes:
                        yield mod.finding(
                            self, lit,
                            f"mesh axis {lit.value!r} is not a canonical "
                            f"axis name ({', '.join(sorted(axes))}); a "
                            "typo'd axis silently unshards the op")


class _TaintVisitor:
    """Order-aware single-function taint: vars assigned from a host fetch
    (device_get / np.asarray / .item()) are tainted; so is anything
    assigned FROM a tainted var. A tainted var appearing in the arguments
    of a known-jitted callable is the round trip."""

    def __init__(self, mod: Module, jitted: set[str]):
        self.mod = mod
        self.jitted = jitted
        self.tainted: set[str] = set()

    def _is_fetch(self, call: ast.Call) -> bool:
        qn = self.mod.qualname(call.func)
        if qn in _HOST_FETCH_QNS:
            return True
        return (isinstance(call.func, ast.Attribute)
                and call.func.attr == "item" and not call.args)

    def _mentions_taint(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in self.tainted:
                return True
        return False

    def scan(self, fn: ast.AST) -> Iterable[tuple[ast.Call, str]]:
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                src_tainted = (
                    (isinstance(node.value, ast.Call)
                     and self._is_fetch(node.value))
                    or self._mentions_taint(node.value))
                for t in node.targets:
                    elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                        else [t]
                    for e in elts:
                        if isinstance(e, ast.Name):
                            if src_tainted:
                                self.tainted.add(e.id)
                            else:
                                self.tainted.discard(e.id)
            elif isinstance(node, ast.Call):
                callee = _expr_key(node.func)
                if callee in self.jitted:
                    for a in node.args:
                        for sub in ast.walk(a):
                            if isinstance(sub, ast.Name) \
                                    and sub.id in self.tainted:
                                yield node, sub.id
                                break
                        else:
                            continue
                        break


@register
class HostRoundTrip(Rule):
    id = "S403"
    name = "host-round-trip"
    doc = ("a host-fetched value (device_get/np.asarray/.item()) flows "
           "back into a jitted dispatch in the same function — a "
           "device->host->device bounce per call")

    def check(self, mod: Module) -> Iterable[Finding]:
        jitted = set(_jit_assignments(mod))
        if not jitted:
            return
        for fn in mod.walk():
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            visitor = _TaintVisitor(mod, jitted)
            for call, var in visitor.scan(fn):
                yield mod.finding(
                    self, call,
                    f"'{var}' was fetched to host earlier in "
                    f"'{fn.name}' and rides back into the jitted "
                    f"dispatch '{_expr_key(call.func)}'; keep the value "
                    "device-resident across the round trip")


_PARAMISH = ("param", "weight", "state_dict")


@register
class ImplicitReplication(Rule):
    id = "S404"
    name = "implicit-replication"
    doc = ("jax.device_put of a params/weights pytree without a sharding "
           "argument in a mesh-aware module — every chip gets a full "
           "replica; use parallel/sharding.shard_params")

    def check(self, mod: Module) -> Iterable[Finding]:
        text = mod.text
        mesh_aware = ("NamedSharding" in text or "make_mesh" in text
                      or "parallel.sharding" in text
                      or "Mesh(" in text)
        if not mesh_aware:
            return
        for node in mod.walk():
            if not isinstance(node, ast.Call):
                continue
            if mod.qualname(node.func) != "jax.device_put":
                continue
            if len(node.args) >= 2 or any(
                    kw.arg in ("device", "sharding")
                    for kw in node.keywords):
                continue
            if not node.args:
                continue
            key = (_expr_key(node.args[0]) or "").lower()
            if any(p in key for p in _PARAMISH):
                yield mod.finding(
                    self, node,
                    f"device_put of '{_expr_key(node.args[0])}' without "
                    "a sharding in a mesh-aware module replicates the "
                    "full pytree on every chip; pass shard_params(...) "
                    "(parallel/sharding.py)")


@register
class UnboundCollective(Rule):
    id = "S405"
    name = "unbound-collective"
    doc = ("collective with a literal axis_name in a function this "
           "module never places under shard_map/pjit; annotate "
           "'# mesh-context: <reason>' if the caller binds it")

    def check(self, mod: Module) -> Iterable[Finding]:
        cg = mod.callgraph
        bound: set[int] = set()
        # functions handed to shard_map (by name) are bound; so is
        # anything THEY call (one level), and jit-wrapped/# traced defs
        # (pjit axes bind via the mesh context manager at dispatch).
        for node in mod.walk():
            if not isinstance(node, ast.Call):
                continue
            if mod.qualname(node.func) in _SHARD_MAP_QNS and node.args:
                tgt = node.args[0]
                fn = None
                if isinstance(tgt, ast.Name):
                    fn = cg.module_fns.get(tgt.id)
                elif isinstance(tgt, ast.Call):
                    # shard_map(partial(fn, ...)) — first partial arg
                    inner = tgt.args[0] if tgt.args else None
                    if isinstance(inner, ast.Name):
                        fn = cg.module_fns.get(inner.id)
                if fn is not None:
                    bound.add(id(fn))
                    for callee in cg.callees(fn):
                        bound.add(id(callee))
        for fn in mod.walk():
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if id(fn) in bound:
                continue
            if mod.annotation(fn, "mesh_context") is not None \
                    or mod.annotation(fn, "traced") is not None:
                continue
            # a fn whose CALLERS are all bound is bound too (one level up)
            callers = cg.callers_of(fn)
            if callers and all(id(c) in bound for c in callers):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if mod.qualname(node.func) not in _COLLECTIVE_QNS:
                    continue
                axis = None
                if len(node.args) >= 2:
                    axis = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "axis_name":
                        axis = kw.value
                if isinstance(axis, ast.Constant) \
                        and isinstance(axis.value, str):
                    yield mod.finding(
                        self, node,
                        f"collective over literal axis "
                        f"{axis.value!r} in '{fn.name}', which this "
                        "module never places under shard_map/jit; bind "
                        "the axis or annotate '# mesh-context:'")
