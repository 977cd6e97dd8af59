"""Compiler tests — the KFP compiler golden-file pattern ((U) kubeflow/
pipelines sdk/python/kfp/compiler/compiler_test.py; SURVEY.md §4.4): compile
the DSL, diff against a checked-in IR YAML snapshot; plus DAG validation."""

import os
from typing import NamedTuple

import pytest

from kubeflow_tpu.pipelines import dsl
from kubeflow_tpu.pipelines.compiler import (
    compile_pipeline, from_yaml, to_yaml, topo_order,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "demo_pipeline.yaml")


@dsl.component
def ingest(source: str) -> list:
    return [source]


@dsl.component
def transform(data: list, factor: int = 2) -> NamedTuple(
        "Out", [("rows", list), ("count", int)]):
    from collections import namedtuple
    return namedtuple("Out", ["rows", "count"])(data * factor, len(data) * factor)


@dsl.component(cache=False, resources={"tpu_chips": 1})
def train(rows: list) -> float:
    return float(len(rows))


@dsl.component
def notify(score: float) -> str:
    return f"score={score}"


@dsl.pipeline(name="demo-pipeline", description="golden-file demo")
def demo(source: str = "db", factor: int = 2):
    i = ingest(source=source)
    t = transform(data=i.output, factor=factor)
    tr = train(rows=t.outputs["rows"])
    with dsl.Condition(tr.output >= 1.0):
        notify(score=tr.output)


class TestCompile:
    def test_golden_file(self):
        got = to_yaml(compile_pipeline(demo))
        if not os.path.exists(GOLDEN):  # bootstrap the snapshot
            os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
            with open(GOLDEN, "w") as f:
                f.write(got)
        with open(GOLDEN) as f:
            want = f.read()
        assert got == want, (
            "compiled IR drifted from the golden snapshot; if intentional, "
            f"delete {GOLDEN} and rerun")

    def test_yaml_round_trip(self):
        ir = compile_pipeline(demo)
        assert from_yaml(to_yaml(ir)) == ir

    def test_structure(self):
        ir = compile_pipeline(demo)
        assert set(ir.tasks) == {"ingest", "transform", "train", "notify"}
        assert ir.tasks["transform"].depends_on == ["ingest"]
        assert ir.tasks["notify"].condition == {"all": [{
            "op": ">=", "lhs": {"task_output": "train.output"},
            "rhs": {"constant": 1.0}}]}
        assert not ir.components["train"].cache_enabled
        assert ir.components["train"].resources == {"tpu_chips": 1}
        assert ir.parameters == {"source": "db", "factor": 2}
        assert topo_order(ir) == ["ingest", "transform", "train", "notify"]

    def test_duplicate_invocations_get_unique_names(self):
        @dsl.pipeline
        def twice():
            ingest(source="a")
            ingest(source="b")

        ir = compile_pipeline(twice)
        assert set(ir.tasks) == {"ingest", "ingest-2"}


class TestValidation:
    def test_unknown_kwarg(self):
        @dsl.pipeline
        def bad():
            ingest(sauce="a")

        with pytest.raises(TypeError, match="unknown inputs"):
            compile_pipeline(bad)

    def test_missing_input(self):
        @dsl.pipeline
        def bad():
            ingest()

        with pytest.raises(TypeError, match="missing inputs"):
            compile_pipeline(bad)

    def test_positional_args_rejected(self):
        @dsl.pipeline
        def bad():
            ingest("a")

        with pytest.raises(TypeError, match="keyword"):
            compile_pipeline(bad)

    def test_condition_outside_pipeline(self):
        with pytest.raises(RuntimeError, match="outside a @pipeline"):
            with dsl.Condition(dsl.PipelineParam("x") > 1):
                pass

    def test_bool_of_reference_is_an_error(self):
        @dsl.pipeline
        def bad(x: int = 1):
            if dsl.PipelineParam("x") > 1:  # plain if on a placeholder
                ingest(source="a")

        with pytest.raises(RuntimeError, match="placeholder"):
            compile_pipeline(bad)

    def test_component_plain_call_outside_pipeline(self):
        # Outside a trace a component is just the function (unit-testable).
        assert ingest(source="s") == ["s"]
        assert train(rows=[1, 2]) == 2.0


@dsl.component
def shard_work(group: str, item: int) -> int:
    return item


@dsl.component
def collect(items: list) -> int:
    return len(items)


NESTED_GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                             "nested_loops_pipeline.yaml")


@dsl.pipeline(name="nested-loops", description="nested ParallelFor demo")
def nested_loops():
    groups = [{"name": "a", "xs": [1, 2]}, {"name": "b", "xs": [3]}]
    with dsl.ParallelFor(groups) as g:
        with dsl.ParallelFor(g["xs"]) as x:
            w = shard_work(group=g["name"], item=x)
    collect(items=w.output)


class TestNestedLoopIR:
    def test_nested_golden_file(self):
        """Nested ParallelFor compiles to stacked iterate_over levels
        (outermost→innermost), the inner items referencing the outer
        loop_item — pinned as a golden snapshot (the KFP compiler-test
        pattern)."""
        got = to_yaml(compile_pipeline(nested_loops))
        if not os.path.exists(NESTED_GOLDEN):  # bootstrap the snapshot
            os.makedirs(os.path.dirname(NESTED_GOLDEN), exist_ok=True)
            with open(NESTED_GOLDEN, "w") as f:
                f.write(got)
        with open(NESTED_GOLDEN) as f:
            want = f.read()
        assert got == want, (
            "compiled IR drifted from the golden snapshot; if intentional, "
            f"delete {NESTED_GOLDEN} and rerun")

    def test_compiling_twice_gives_the_same_ir(self):
        """Loop ids are numbered within the pipeline, not by a counter of
        the process (which made the golden file above depend on what the
        worker had compiled before it)."""
        assert to_yaml(compile_pipeline(nested_loops)) == \
            to_yaml(compile_pipeline(nested_loops))

    def test_nested_ir_structure(self):
        ir = compile_pipeline(nested_loops)
        t = ir.tasks["shard_work"]
        assert len(t.iterate_over) == 2
        outer, inner = t.iterate_over
        assert "constant" in outer["items"]
        assert inner["items"]["loop_item"] == outer["loop_id"]
        assert inner["items"]["subpath"] == "xs"
        # Single-level IR stays a one-element list (dict form coerces too).
        assert from_yaml(to_yaml(ir)) == ir
