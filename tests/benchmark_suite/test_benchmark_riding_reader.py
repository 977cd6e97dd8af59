"""PR 60's one reader, ``engine.step_riding_share.longdoc``: the share of a
window's chunk programs that carried the live slots' decode step, from two
counters every parent since PR 49 has."""

from benchmark import manifest as mf

NAME = "engine.step_riding_share.longdoc"
CELL = "solar-open2-250b.batch-longdoc"


def _run(before: dict, after: dict) -> dict:
    return {"window_s": 51.0, "counters_before": {"engine": before},
            "counters_after": {"engine": after}}


def test_it_is_the_share_of_the_windows_chunk_programs_that_carried_a_step():
    read = mf.load_layer_metric(NAME).read
    before = {"mixed_programs_dispatched": 10,
              "prefill_programs_dispatched": 40}
    after = {"mixed_programs_dispatched": 700,
             "prefill_programs_dispatched": 1365}
    assert read(_run(before, after)) == 100.0 * 690 / 1325
    # the parent: the counter is there and stands still
    assert read(_run(before, {**after, "mixed_programs_dispatched": 10})) \
        == 0.0
    # a window that sent no chunk program
    assert read(_run(before, before)) == 0.0
    # a program without the counters, another kind of run
    assert read(_run({}, {})) is None
    assert read({"window_s": 1.0}) is None


def test_it_is_declared_for_the_long_document_cell_alone():
    manifest = mf.load_manifest()
    entry = manifest["per_layer"][-1]
    assert entry == {"name": NAME, "workloads": [CELL],
                     **mf.load_layer_metric(NAME).DECLARATION}
    assert NAME in mf.declared(manifest, CELL, "per_layer")
