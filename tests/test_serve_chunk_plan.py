"""The plan of the prefill pass's chunk programs (ISSUE 59), as a table: which
program carries a pass's chunks, how wide and at which context is one pure
function of what the engine observes (``serve/chunk_programs.py``), so no
engine is built here and nothing is compiled. ``PLANS`` was read off the
engines of the commit before the module (72f5d8c: its five flags and whether
its one-row program was ``_OneContext``), once; ``CELLS`` off the plans of
the benchmark's serving cells, which ``ChunkPlan.send``'s docstring names.
Since ISSUE 60 the plan also says whether the slots' step rides a program of
so many chunks (``rides``): the table's sixth case, several rows wide with no
row to send ahead, and one engine of that plan on the CPU. Since ISSUE 63 a
stack of SSD mixers beside attention sends rows ahead (the mixer hands a
row's end state to the row behind it): the Nemotron-like preset's rows of
``PLANS`` and the agent-turns cell's of ``CELLS``, every other row as it
was."""

import dataclasses

import jax
import pytest

from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec, SpeculativeSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.serve.chunk_programs import (
    OWN_BUCKET, WHOLE_TABLE, ChunkPlan, Sent, plan_chunks,
)
from kubeflow_tpu.serve.engine import serving_configs
from kubeflow_tpu.serve.paged import engine_pool_shapes


def plan_of(cfg, batching: BatchingSpec, impl: str) -> ChunkPlan:
    """The plan of an engine over ``cfg`` and ``batching`` whose
    ``paged_attn_impl`` resolved to ``impl``, from the pool's names, shapes
    and dtypes alone."""
    b = batching
    pre, dec = serving_configs(cfg, b)
    pages = int(b.max_pages or b.max_batch_size * (b.max_seq_len
                                                   // b.page_size))
    cache = {name: jax.ShapeDtypeStruct(shape, dt) for name, (shape, dt) in
             engine_pool_shapes(dec, b.max_batch_size, pages, b.page_size,
                                b.kv_cache_dtype == "int8").items()}
    return plan_chunks(pre, cache, b, impl)


BASE = dict(max_batch_size=3, max_seq_len=128, page_size=16,
            chunked_prefill_tokens=32, enable_prefix_caching=False)
# heads of 128: what the chunk kernel takes (a tiny preset's heads of 16 stay
# on the gathered form, whatever the arm: ``paged._chunk_in_place``)
WIDE = {"tiny": dict(head_dim=128), "tiny-moe": dict(head_dim=128),
        "tiny-falconh1": dict(n_heads=2, n_kv_heads=1, head_dim=128),
        "tiny-solar": dict(n_layers=4, n_heads=2, n_kv_heads=1, head_dim=128,
                           linear_heads=2, linear_head_dim=128,
                           linear_gate_rank=16),
        "tiny-nemotron-h": dict(n_heads=2, n_kv_heads=1, head_dim=128,
                                ssd_heads=4, ssd_head_dim=64, ssd_groups=2)}
# a dense chunk of 256 tokens is over the ridge: one chunk a program
RIDGE = dict(max_seq_len=1024, chunked_prefill_tokens=256)
OPTIONS = {
    "int8": dict(kv_cache_dtype="int8"),
    "lora": dict(lora=LoRASpec(max_adapters=2, rank=4, targets=("wq", "wv"))),
    "spec": dict(speculative=SpeculativeSpec(mode="ngram", k=4)),
}

T, F = True, False
# "preset|arm|concurrent prefills|what else" ->
#   (carries_step, rows, lone_at_last, ahead, rows_only, one_context)
PLANS = {
    "tiny|pallas|1|": (F, 1, T, F, F, F),
    "tiny|pallas|2|": (F, 2, F, F, F, F),
    "tiny|gather|1|": (F, 1, T, F, F, F),
    "tiny|gather|2|": (F, 2, F, F, F, F),
    "tiny-moe|pallas|1|": (F, 1, T, F, F, F),
    "tiny-moe|pallas|2|": (F, 2, F, F, F, F),
    "tiny-moe|gather|1|": (F, 1, T, F, F, F),
    "tiny-moe|gather|2|": (F, 2, F, F, F, F),
    "tiny-glm|pallas|1|": (T, 1, F, F, F, F),
    "tiny-glm|pallas|2|": (T, 2, F, T, T, F),
    "tiny-glm|gather|1|": (F, 1, T, F, F, F),
    "tiny-glm|gather|2|": (F, 2, F, F, F, F),
    "tiny-lfm2|pallas|1|": (F, 1, T, F, F, F),
    "tiny-lfm2|pallas|2|": (F, 2, F, F, F, F),
    "tiny-lfm2|gather|1|": (F, 1, T, F, F, F),
    "tiny-lfm2|gather|2|": (F, 2, F, F, F, F),
    "tiny-exaone|pallas|1|": (F, 1, T, F, F, F),
    "tiny-exaone|pallas|2|": (F, 2, F, F, F, F),
    "tiny-exaone|gather|1|": (F, 1, T, F, F, F),
    "tiny-exaone|gather|2|": (F, 2, F, F, F, F),
    "tiny-solar|pallas|1|": (F, 1, T, F, F, F),
    "tiny-solar|pallas|2|": (F, 2, F, F, F, F),
    "tiny-solar|gather|1|": (F, 1, T, F, F, F),
    "tiny-solar|gather|2|": (F, 2, F, F, F, F),
    "tiny-phi4flash|pallas|1|": (F, 1, T, F, F, F),
    "tiny-phi4flash|pallas|2|": (F, 2, T, F, F, F),
    "tiny-phi4flash|gather|1|": (F, 1, T, F, F, F),
    "tiny-phi4flash|gather|2|": (F, 2, T, F, F, F),
    "tiny-falconh1|pallas|1|": (F, 1, T, F, F, F),
    "tiny-falconh1|pallas|2|": (F, 2, F, F, F, F),
    "tiny-falconh1|gather|1|": (F, 1, T, F, F, F),
    "tiny-falconh1|gather|2|": (F, 2, F, F, F, F),
    "tiny-glm-5|pallas|1|": (T, 1, F, F, F, F),
    "tiny-glm-5|pallas|2|": (T, 2, F, T, T, F),
    "tiny-glm-5|gather|1|": (F, 1, T, F, F, F),
    "tiny-glm-5|gather|2|": (F, 2, F, F, F, F),
    "tiny-longcat-flash|pallas|1|": (T, 1, F, F, F, F),
    "tiny-longcat-flash|pallas|2|": (T, 2, F, T, T, F),
    "tiny-longcat-flash|gather|1|": (F, 1, T, F, F, F),
    "tiny-longcat-flash|gather|2|": (F, 2, F, F, F, F),
    "tiny|pallas|2|int8": (F, 2, F, F, F, F),
    "tiny|pallas|2|lora": (F, 2, F, F, F, F),
    "tiny|pallas|2|spec": (F, 2, F, F, F, F),
    "tiny-moe|pallas|2|int8": (F, 2, F, F, F, F),
    "tiny-moe|pallas|2|lora": (F, 2, F, F, F, F),
    "tiny-moe|pallas|2|spec": (F, 2, F, F, F, F),
    "tiny|pallas|1|wide": (T, 1, F, F, F, T),
    "tiny|pallas|2|wide": (T, 2, F, T, F, T),
    "tiny|pallas|2|wide+int8": (F, 2, F, F, F, F),
    "tiny|pallas|2|wide+lora": (F, 2, F, F, F, T),
    "tiny|pallas|2|wide+spec": (F, 2, F, F, F, T),
    "tiny|pallas|2|wide+ridge": (T, 1, F, F, F, T),
    "tiny|gather|2|wide+ridge": (F, 1, T, F, F, F),
    "tiny-moe|pallas|1|wide": (T, 1, F, F, F, T),
    "tiny-moe|pallas|2|wide": (T, 2, F, T, F, T),
    "tiny-moe|pallas|2|wide+int8": (F, 2, F, F, F, F),
    "tiny-moe|pallas|2|wide+lora": (F, 2, F, F, F, T),
    "tiny-moe|pallas|2|wide+spec": (F, 2, F, F, F, T),
    "tiny-falconh1|pallas|1|wide": (T, 1, F, F, F, T),
    "tiny-falconh1|pallas|2|wide": (T, 2, F, F, F, T),
    "tiny-falconh1|pallas|2|wide+ridge": (T, 1, F, F, F, T),
    "tiny-falconh1|gather|2|wide+ridge": (F, 1, T, F, F, F),
    # since PR 60 the kind "linear" carries the step (sorted experts: two
    # rows by the ridge whatever the chunk); two rows wide it is, with the
    # parallel stack two rows wide above, the table's sixth case
    "tiny-solar|pallas|1|wide": (T, 1, F, F, F, T),
    "tiny-solar|pallas|2|wide": (T, 2, F, F, F, T),
    "tiny-solar|gather|2|wide": (F, 2, F, F, F, F),
    # the kind "ssd" carries the step since PR 61 (two rows by the ridge:
    # sorted experts); since PR 63 its rows follow, so two rows wide it sends
    # its spare row ahead, and keeps the one-row program (ONE context) for a
    # prompt's odd last chunk with no slot live: the batch cell's plan
    "tiny-nemotron-h|pallas|1|": (F, 1, T, F, F, F),
    "tiny-nemotron-h|pallas|2|": (F, 2, F, F, F, F),
    "tiny-nemotron-h|gather|2|": (F, 2, F, F, F, F),
    "tiny-nemotron-h|pallas|1|wide": (T, 1, F, F, F, T),
    "tiny-nemotron-h|pallas|2|wide": (T, 2, F, T, F, T),
    "tiny-nemotron-h|gather|2|wide": (F, 2, F, F, F, F),
}


def _plan(case: str) -> ChunkPlan:
    name, impl, prefills, what = case.split("|")
    over, kw = {}, {}
    for w in filter(None, what.split("+")):
        if w == "wide":
            over = dict(WIDE[name])
        elif w == "ridge":
            over["max_seq_len"] = RIDGE["max_seq_len"]
            kw.update(RIDGE)
        else:
            kw.update(OPTIONS[w])
    cfg = preset(name, dtype="float32", param_dtype="float32", **over)
    return plan_of(cfg, BatchingSpec(**{
        **BASE, "max_concurrent_prefills": int(prefills),
        "paged_attn_impl": impl, **kw}), impl)


@pytest.mark.parametrize("case", sorted(PLANS))
def test_the_plan_is_what_the_engine_before_the_module_observed(case):
    assert dataclasses.astuple(_plan(case)) == PLANS[case]


def _before(plan: ChunkPlan, n: int, rides: bool, idle: bool) -> Sent:
    """``_dispatch_chunks``' rule as it stood before the module (72f5d8c:
    ``together``, ``rows``, ``by_rows`` and the three-way branch)."""
    together = n > 1 or rides or plan.rows_only or (
        plan.carries_step and plan.rows == 1 and not idle)
    rows = plan.rows if together else 1
    if plan.carries_step and together:
        return Sent("mixed", rows, WHOLE_TABLE)
    if together or plan.lone_at_last:
        return Sent("rows", rows, WHOLE_TABLE if rows > 1 else OWN_BUCKET)
    return Sent("lone", 1, OWN_BUCKET)


@pytest.mark.parametrize("case", sorted(PLANS))
def test_every_pass_is_sent_as_it_was_and_through_a_program_that_is_built(
        case):
    """Over every ``(n_chunks, step_rides, otherwise_idle)`` a pass can ask
    for: the program, width and context of the rule as it stood; and no
    case names a program ``ChunkPrograms`` was not asked to build
    (``programs()``: what ``LLMEngine.__init__`` asks for)."""
    plan = ChunkPlan(*PLANS[case])
    # a step rides only where the plan says so of a program of so many
    # chunks (``rides``: where the program carries it at all, and its rows
    # are all filled or the plan fills them itself); an engine with nothing
    # else to do has one chunk and no live slot
    cases = [(n, rides, idle) for n in range(1, plan.rows + 1)
             for rides in (False, True)[:1 + plan.rides(n)]
             for idle in (False, True)[:1 + (n == 1 and not rides)]]
    filled_only = plan.carries_step and plan.rows > 1 and not plan.ahead
    assert [plan.rides(n) for n in range(1, plan.rows + 1)] == [
        plan.carries_step and (n == plan.rows or not filled_only)
        for n in range(1, plan.rows + 1)]
    assert len(cases) == plan.rows * (1 + plan.carries_step) + 1 - (
        plan.rows - 1) * filled_only
    if filled_only:         # the sixth case: one chunk "lone" and no ride
        assert plan.send(1, False, False) == Sent("lone", 1, OWN_BUCKET)
        assert plan.send(plan.rows, True, False) == plan.send(
            plan.rows, False, False) == Sent("mixed", plan.rows, WHOLE_TABLE)
        assert plan.programs() == {"mixed", "lone"}
    for n, rides, idle in cases:
        sent = plan.send(n, rides, idle)
        assert sent == _before(plan, n, rides, idle), (n, rides, idle)
        assert sent.program in plan.programs()
        assert sent.rows in (1, plan.rows) and sent.rows >= n
        assert (sent.context == OWN_BUCKET) == (
            sent.program != "mixed" and sent.rows == 1)
    assert ("mixed" in plan.programs()) == plan.carries_step
    # the program over rows is built only where a case sends it
    assert ("rows" in plan.programs()) == (
        not plan.carries_step or plan.lone_at_last)
    assert ("lone" in plan.programs()) == (
        not plan.rows_only and not plan.lone_at_last)


# cell -> its plan, and what carries (one chunk beside a live slot, two
# chunks, one chunk of an engine with nothing else to do)
MIXED1 = ((T, 1, F, F, F, T), "mixed 1", None, "lone 1")
AHEAD_ONE_CONTEXT = ((T, 2, F, T, F, T), "mixed 2", "mixed 2", "lone 1")
CELLS = {
    "mistral-7b.chat-open": MIXED1,
    "falcon-h1-34b.batch-assistant": MIXED1,
    "mixtral-8x7b.batch-longprompt": AHEAD_ONE_CONTEXT,
    # since PR 63 (PR 62's: ``(T, 2, F, F, F, T)``, longdoc's row below: a
    # lone chunk "lone 1" beside a live slot, 40% of its chunk programs)
    "nemotron-3-super-120b-a12b.batch-agentturns": AHEAD_ONE_CONTEXT,
    **{cell: ((T, 2, F, T, T, F), "mixed 2", "mixed 2", "mixed 2")
       for cell in ("glm-4.7-flash.batch-longcontext",
                    "glm-5.batch-agentcontext",
                    "longcat-flash-omni.batch-voiceturns")},
    "lfm2-24b-a2b.batch-longanswer": (
        (F, 2, F, F, F, F), "lone 1", "rows 2", "lone 1"),
    "k-exaone-236b-a23b.batch-mixedlength": (
        (F, 2, F, F, F, T), "lone 1", "rows 2", "lone 1"),
    # since PR 60: a pair carries the step, a lone chunk keeps its one-row
    # program (the step does not ride a program with a dead row)
    "solar-open2-250b.batch-longdoc": (
        (T, 2, F, F, F, T), "lone 1", "mixed 2", "lone 1"),
    "phi-4-mini-flash.batch-reasoning": (
        (F, 2, T, F, F, T), "rows 1", "rows 2", "rows 1"),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_serving_cell_takes_the_case_the_table_names(cell):
    """The benchmark's eleven serving cells, at their own sizes (configuration
    and traffic files; on one chip the arm resolves to "pallas")."""
    from scripts.aot_weight_copies import serving_cell

    want, beside_a_slot, two, idle = CELLS[cell]
    plan = plan_of(*serving_cell(cell), "pallas")
    assert dataclasses.astuple(plan) == want

    def carries(n, rides, otherwise_idle):
        sent = plan.send(n, rides, otherwise_idle)
        return f"{sent.program} {sent.rows}"

    assert carries(1, plan.rides(1), False) == beside_a_slot
    assert two is None or carries(2, plan.rides(2), False) == two
    assert carries(1, False, True) == idle


def test_a_lone_chunk_sends_the_decode_program_and_a_pair_does_not():
    """An engine two rows wide that sends no row ahead and carries the step
    (the long-document cell's plan, a tiny Solar stack with the kernels
    interpreted): beside a live stream, an iteration whose pass has ONE chunk
    due sends it through the one-row program and the stream's step as
    ``paged_decode``; an iteration with a PAIR due sends one program, which
    carries the step, and no ``paged_decode``."""
    from test_serve_mixed_program import LONG, _engine

    from kubeflow_tpu.serve.engine import SamplingParams

    eng = _engine("linear")
    assert dataclasses.astuple(eng._plan) == (T, 2, F, F, F, T)
    assert eng._plan.programs() == {"mixed", "lone"}
    assert not hasattr(eng._programs, "rows")
    sent = []
    for name, at in (("lone", eng._programs), ("mixed", eng._programs),
                     ("_paged_decode_n", eng)):
        def spy(*args, name=name, program=getattr(at, name)):
            sent.append(name.strip("_"))
            return program(*args)
        setattr(at, name, spy)
    sp = SamplingParams(max_new_tokens=8, temperature=0.0)
    stream = eng.submit(list(map(int, LONG[0][:40])), SamplingParams(
        max_new_tokens=100, temperature=0.0))
    while stream.first_token_time is None:
        eng.step()
    eng.step()
    del sent[:]
    alone = eng.submit(list(map(int, LONG[0][:90])), sp)    # three chunks
    eng.step()
    assert sent == ["lone", "paged_decode_n"]
    eng.step()
    assert sent == ["lone", "paged_decode_n"] * 2
    while not alone.done.is_set():
        eng.step()
    del sent[:]
    before = eng.counters()
    pair = [eng.submit(list(map(int, LONG[j][:90])), sp) for j in (1, 2)]
    eng.step()
    assert sent == ["mixed"]
    eng.step()
    assert sent == ["mixed", "mixed"]
    c = eng.counters()
    for name, n in (("mixed_programs_dispatched", 2), ("decode_rounds", 2),
                    ("prefill_programs_dispatched", 2),
                    ("prefill_chunks_dispatched", 4),
                    ("prefill_rows_dead", 0)):
        assert c[name] == before[name] + n, name
    assert c["mixed_decode_rows_sum"] == before["mixed_decode_rows_sum"] + 2
    while not all(r.done.is_set() for r in (stream, *pair)):
        eng.step()
    eng._allocator.assert_quiescent()
