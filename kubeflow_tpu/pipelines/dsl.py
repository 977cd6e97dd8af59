"""Pipeline DSL — ``@component`` / ``@pipeline`` + control flow.

The KFP-SDK analog ((U) kubeflow/pipelines sdk/python/kfp dsl: @dsl.component,
@dsl.pipeline, dsl.Condition, dsl.ParallelFor, dsl.ExitHandler; SURVEY.md
§2.5#37). Tracing model: calling a component inside a pipeline function
records a task node; the compiler (compiler.py) turns the trace into the IR.

Differences from KFP, by design:
- components are plain Python callables executed in-process by the DAG
  executor (no container images); every output is stored content-addressed
  and tracked in the metadata store, so artifact-vs-parameter annotation
  boilerplate disappears while lineage parity remains.
- multi-output components return a typing.NamedTuple; single-output
  components use the task's ``.output``.
"""

from __future__ import annotations

import contextvars
import inspect
from typing import Any, Callable, Optional

_trace: contextvars.ContextVar[Optional["_PipelineTrace"]] = \
    contextvars.ContextVar("pipeline_trace", default=None)

_COMPARE_OPS = {"==", "!=", "<", "<=", ">", ">="}


class Reference:
    """A value placeholder inside a pipeline trace (param / task output /
    loop item). Comparisons build condition expressions."""

    def ref(self) -> dict[str, Any]:
        raise NotImplementedError

    def _cmp(self, op: str, other: Any) -> "Comparison":
        return Comparison(self, op, other)

    def __eq__(self, other):  # type: ignore[override]
        return self._cmp("==", other)

    def __ne__(self, other):  # type: ignore[override]
        return self._cmp("!=", other)

    def __lt__(self, other):
        return self._cmp("<", other)

    def __le__(self, other):
        return self._cmp("<=", other)

    def __gt__(self, other):
        return self._cmp(">", other)

    def __ge__(self, other):
        return self._cmp(">=", other)

    def __hash__(self):
        return id(self)

    def __bool__(self):
        raise RuntimeError(
            "pipeline references are placeholders; use dsl.Condition(...) "
            "instead of Python if/and/or on them")


class Comparison:
    def __init__(self, lhs: Any, op: str, rhs: Any):
        assert op in _COMPARE_OPS
        self.lhs, self.op, self.rhs = lhs, op, rhs

    def __bool__(self):
        raise RuntimeError(
            "pipeline references are placeholders; wrap comparisons in "
            "dsl.Condition(...) instead of Python if/and/or")

    def to_ir(self) -> dict[str, Any]:
        return {"op": self.op, "lhs": _as_ref(self.lhs), "rhs": _as_ref(self.rhs)}


def _as_ref(v: Any) -> dict[str, Any]:
    if isinstance(v, Reference):
        return v.ref()
    return {"constant": v}


class PipelineParam(Reference):
    def __init__(self, name: str):
        self.name = name

    def ref(self) -> dict[str, Any]:
        return {"param": self.name}


class LoopItem(Reference):
    """The per-iteration value inside a ParallelFor; index with ["key"] for
    dict items."""

    def __init__(self, loop_id: str, subpath: Optional[str] = None):
        self.loop_id = loop_id
        self.subpath = subpath

    def __getitem__(self, key: str) -> "LoopItem":
        return LoopItem(self.loop_id, key)

    def ref(self) -> dict[str, Any]:
        out: dict[str, Any] = {"loop_item": self.loop_id}
        if self.subpath is not None:
            out["subpath"] = self.subpath
        return out


class TaskOutput(Reference):
    def __init__(self, task: "Task", name: str):
        self.task = task
        self.name = name

    def ref(self) -> dict[str, Any]:
        return {"task_output": f"{self.task.name}.{self.name}"}


class Task:
    """One traced component invocation."""

    def __init__(self, name: str, component: "Component",
                 arguments: dict[str, dict[str, Any]],
                 groups: tuple["_Group", ...]):
        self.name = name
        self.component = component
        self.arguments = arguments
        self.groups = groups
        self.explicit_deps: list[str] = []

    def after(self, *tasks: "Task") -> "Task":
        self.explicit_deps.extend(t.name for t in tasks)
        return self

    @property
    def output(self) -> TaskOutput:
        outs = self.component.outputs
        if len(outs) != 1:
            raise AttributeError(
                f"{self.component.name} has outputs {sorted(outs)}; "
                "use .outputs['<name>']")
        return TaskOutput(self, next(iter(outs)))

    @property
    def outputs(self) -> dict[str, TaskOutput]:
        return {n: TaskOutput(self, n) for n in self.component.outputs}


class _Group:
    kind = "group"


class Condition(_Group):
    """``with dsl.Condition(task.output > 0.5):`` — tasks inside run iff the
    comparison holds at execution time."""

    kind = "condition"

    def __init__(self, comparison: Comparison):
        if not isinstance(comparison, Comparison):
            raise TypeError("dsl.Condition takes a comparison over pipeline "
                            "references, e.g. Condition(t.output > 0)")
        self.comparison = comparison

    def __enter__(self) -> "Condition":
        _require_trace("Condition").push_group(self)
        return self

    def __exit__(self, *exc) -> None:
        _require_trace("Condition").pop_group(self)


class ParallelFor(_Group):
    """``with dsl.ParallelFor(items) as item:`` — the body is instantiated per
    item at run time; downstream tasks outside the loop see a task's outputs
    fan-in as a list (KFP dsl.Collected semantics)."""

    kind = "loop"

    def __init__(self, items: Any):
        # Numbered within the pipeline being traced, so that compiling a
        # pipeline gives the same IR whatever the process compiled before.
        trace = _require_trace("ParallelFor")
        trace.loops += 1
        self.loop_id = f"loop-{trace.loops}"
        self.items = items

    def __enter__(self) -> LoopItem:
        _require_trace("ParallelFor").push_group(self)
        return LoopItem(self.loop_id)

    def __exit__(self, *exc) -> None:
        _require_trace("ParallelFor").pop_group(self)


class ExitHandler(_Group):
    """``with dsl.ExitHandler(cleanup(...)):`` — the exit task runs when the
    wrapped tasks finish, regardless of failures."""

    kind = "exit_handler"

    def __init__(self, exit_task: Task):
        self.exit_task = exit_task
        exit_task_ir = _require_trace("ExitHandler").tasks[exit_task.name]
        exit_task_ir["exit_handler"] = True

    def __enter__(self) -> "ExitHandler":
        _require_trace("ExitHandler").push_group(self)
        return self

    def __exit__(self, *exc) -> None:
        _require_trace("ExitHandler").pop_group(self)


#: Live components by entrypoint string — lets the in-process executor run
#: components whose qualname isn't importable (defined in function scope).
component_registry: dict[str, "Component"] = {}


class Component:
    def __init__(self, fn: Callable, *, name: Optional[str] = None,
                 cache: bool = True, resources: Optional[dict] = None):
        self.fn = fn
        self.name = name or fn.__name__
        self.cache = cache
        self.resources = resources or {}
        sig = inspect.signature(fn)
        self.inputs = {
            p.name: _type_name(p.annotation) for p in sig.parameters.values()}
        self.defaults = {
            p.name: p.default for p in sig.parameters.values()
            if p.default is not inspect.Parameter.empty}
        self.outputs = _output_spec(sig.return_annotation)
        self.entrypoint = f"{fn.__module__}:{fn.__qualname__}"
        # Only function-scoped components need the live registry (importable
        # qualnames resolve via importlib); keeping module-level ones out
        # bounds growth and avoids most collisions. Same-qualname locals
        # still collide (last definition wins) — unavoidable with a string
        # key, so flag it.
        if "<locals>" in fn.__qualname__:
            if self.entrypoint in component_registry:
                import logging

                logging.getLogger("kubeflow_tpu.pipelines").warning(
                    "component %s redefined; pipelines compiled against the "
                    "previous definition will run the new body",
                    self.entrypoint)
            component_registry[self.entrypoint] = self

    def __call__(self, *args, **kwargs):
        trace = _trace.get()
        if trace is None:
            # Outside a pipeline: behave as the plain function (unit tests
            # of components need no harness).
            return self.fn(*args, **kwargs)
        if args:
            raise TypeError(
                f"component {self.name}: use keyword arguments in pipelines "
                "(argument names become IR wiring)")
        unknown = set(kwargs) - set(self.inputs)
        if unknown:
            raise TypeError(f"component {self.name}: unknown inputs {unknown}")
        missing = set(self.inputs) - set(kwargs) - set(self.defaults)
        if missing:
            raise TypeError(f"component {self.name}: missing inputs {missing}")
        return trace.add_task(self, kwargs)


def _type_name(ann: Any) -> str:
    if ann is inspect.Parameter.empty or ann is None:
        return "Any"
    return getattr(ann, "__name__", str(ann))


def _output_spec(ann: Any) -> dict[str, str]:
    if ann is inspect.Signature.empty or ann is None:
        return {"output": "Any"}
    fields = getattr(ann, "_fields", None)
    if fields:  # typing.NamedTuple → one output per field
        types = getattr(ann, "__annotations__", {})
        return {f: _type_name(types.get(f)) for f in fields}
    return {"output": _type_name(ann)}


def component(fn: Optional[Callable] = None, *, name: Optional[str] = None,
              cache: bool = True, resources: Optional[dict] = None):
    if fn is not None:
        return Component(fn)
    return lambda f: Component(f, name=name, cache=cache, resources=resources)


class _PipelineTrace:
    def __init__(self):
        self.components: dict[str, dict[str, Any]] = {}
        self.tasks: dict[str, dict[str, Any]] = {}
        self._group_stack: list[_Group] = []
        self._names: dict[str, int] = {}
        self.loops = 0

    def push_group(self, g: _Group) -> None:
        self._group_stack.append(g)

    def pop_group(self, g: _Group) -> None:
        assert self._group_stack and self._group_stack[-1] is g
        self._group_stack.pop()

    def _task_name(self, base: str) -> str:
        n = self._names.get(base, 0)
        self._names[base] = n + 1
        return base if n == 0 else f"{base}-{n + 1}"

    def add_task(self, comp: Component, kwargs: dict[str, Any]) -> Task:
        if comp.name not in self.components:
            self.components[comp.name] = {
                "name": comp.name,
                "entrypoint": comp.entrypoint,
                "inputs": dict(comp.inputs),
                "outputs": dict(comp.outputs),
                "cache_enabled": comp.cache,
                "resources": dict(comp.resources),
            }
        name = self._task_name(comp.name)
        arguments = {}
        depends = set()
        for k, v in kwargs.items():
            if isinstance(v, Task):
                v = v.output  # single-output coercion
            arguments[k] = _as_ref(v)
            if isinstance(v, TaskOutput):
                depends.add(v.task.name)
        # Group semantics → IR fields.
        conditions = []
        loops = []
        for g in self._group_stack:
            if isinstance(g, Condition):
                conditions.append(g.comparison.to_ir())
                for side in (g.comparison.lhs, g.comparison.rhs):
                    if isinstance(side, TaskOutput):
                        depends.add(side.task.name)
            elif isinstance(g, ParallelFor):
                loops.append(g)
            # ExitHandler scope adds no per-task IR: only the exit task
            # itself (flagged in ExitHandler.__init__) is special.
        # Nested ParallelFor stacks loop levels outermost→innermost (the
        # group-stack order); an inner level's items may reference the
        # outer loop_item (iterating a field of each outer element) — the
        # executor substitutes it per outer instance at expansion time.
        iterate = None
        if loops:
            iterate = []
            for g in loops:
                items_ref = _as_ref(g.items)
                if isinstance(g.items, (list, tuple)):
                    items_ref = {"constant": list(g.items)}
                iterate.append({"loop_id": g.loop_id, "items": items_ref})
                if isinstance(g.items, TaskOutput):
                    depends.add(g.items.task.name)
        task = Task(name, comp, arguments, tuple(self._group_stack))
        self.tasks[name] = {
            "name": name,
            "component": comp.name,
            "arguments": arguments,
            "depends_on": sorted(depends),
            "condition": ({"all": conditions} if conditions else None),
            "iterate_over": iterate,
            "exit_handler": False,
            "_task_obj": task,
        }
        return task

    def finalize_deps(self) -> None:
        for t in self.tasks.values():
            obj: Task = t["_task_obj"]
            deps = set(t["depends_on"]) | set(obj.explicit_deps)
            t["depends_on"] = sorted(deps)
            del t["_task_obj"]


def _require_trace(what: str) -> _PipelineTrace:
    tr = _trace.get()
    if tr is None:
        raise RuntimeError(f"dsl.{what} used outside a @pipeline function")
    return tr


class PipelineDef:
    def __init__(self, fn: Callable, name: Optional[str] = None,
                 description: str = ""):
        self.fn = fn
        self.name = name or fn.__name__.replace("_", "-")
        self.description = description or (fn.__doc__ or "").strip()
        sig = inspect.signature(fn)
        self.parameters = {
            p.name: (None if p.default is inspect.Parameter.empty else p.default)
            for p in sig.parameters.values()}

    def trace(self) -> _PipelineTrace:
        tr = _PipelineTrace()
        token = _trace.set(tr)
        try:
            self.fn(**{n: PipelineParam(n) for n in self.parameters})
        finally:
            _trace.reset(token)
        tr.finalize_deps()
        return tr


def pipeline(fn: Optional[Callable] = None, *, name: Optional[str] = None,
             description: str = ""):
    if fn is not None:
        return PipelineDef(fn)
    return lambda f: PipelineDef(f, name=name, description=description)
