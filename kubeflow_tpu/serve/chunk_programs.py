"""The prefill pass's chunk programs and the plan that chooses among them.

Which program carries the chunks of an admit pass, how wide and at which
static context is decided here, once, from what the engine observes of its
model, pool and options (``plan_chunks`` -> ``ChunkPlan``, whose ``send`` is
the table); ``ChunkPrograms`` builds, packs, warms and sends them. The engine
keeps the scheduler's part: whose chunks are due, their pages, the round that
rides, the spans, the counters. How each case came to be: PERF.md section 6.

- "lone": ONE prompt's chunk (tokens [1,C], its table row, scalar start and
  valid length) -> [C,V] logits, every position's: for callers OUTSIDE the
  engine, which compare them all (``engine._paged_chunk``).
- "rows": a chunk of each of R prompts (tokens [R,C]; a table row, a start, a
  valid length and "ends its prompt" a row) -> [R,V], the head at each row's
  LAST valid position, and nowhere where no row ends its prompt (over every
  position it was 7% of a long-context cell's device time, 634 MB a result).
- "mixed": "rows" that also CARRIES one decode step of the live slots: an
  iteration with both reads every weight once (a sparse model's step is its
  weights' bytes: 40% of the batch cell's iteration). ``ride`` false: "rows".
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.serve.paged import (
    MOE_ROWS, chunk_carries_step, chunk_reads_context, chunk_rows_follow,
    context_bucket, paged_chunk_prefill, paged_mixed_step,
)

#: Rows a bf16 weight matrix must multiply before the matrix work takes as
#: long as reading the matrix: a row costs 2 FLOPs a parameter and the
#: matrix 2 bytes a parameter, so rows = peak FLOP/s over peak bytes/s. On
#: a v5e that is 197e12 / 819e9 = 240 rows; 256, the next whole tile. Below
#: it a program is bound by its weights' bytes, and rows added to it are
#: nearly free; at or above it they cost their own time.
RIDGE_ROWS = 256


def chunk_rows_per_weight(cfg: DecoderConfig, chunk: int) -> float:
    """Rows the least-used weight matrix of the model multiplies in ONE
    prefill chunk of ``chunk`` tokens: every token for a dense model, the
    ``experts_per_token / num_experts`` share of them that one expert of an
    expert layer sees (Mixtral at 512 tokens: 128; 4 of 64 experts: 32).
    Against ``RIDGE_ROWS`` it decides whether the chunks of all in-flight
    prefills go into one program."""
    if not cfg.is_moe or cfg.moe_impl == "dense":   # every expert, every row
        return chunk
    return chunk * cfg.experts_per_token / cfg.num_experts


def program_key(name: str, *variant) -> str:
    """A program variant's name in ``program_kernels`` and in
    ``start_programs()``: the program and what tells its variants apart (the
    token block's shape, the static arguments)."""
    return f"{name}[{','.join(map(str, variant))}]"


#: ``Sent.context``: the whole page table, or the chunk's own bucket.
WHOLE_TABLE, OWN_BUCKET = "table", "bucket"


class Sent(NamedTuple):
    """What carries a pass's chunks: "mixed" | "rows" | "lone", how wide."""
    program: str
    rows: int
    context: str


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """What an engine's chunk programs are, fixed when it is built."""

    #: the chunk program carries the slots' decode step
    carries_step: bool
    #: chunks one program takes: ONE width (each further one is a program
    #: loaded and run at every start: 0.75 s warm, 5 s cold on a v5e, 5.7 s
    #: at the long-context cell's; PERF.md PR 29, 49, 53)
    rows: int
    #: a chunk sent alone takes "rows" at one row: the head at one position
    lone_at_last: bool
    #: a program's spare rows take the NEXT chunks of the prompts in it
    ahead: bool
    #: no chunk of the traffic takes "lone": it would be a program a bucket
    #: (2-3 s each to trace and load at a start: PR 56)
    rows_only: bool
    #: "lone" is ONE program whatever bucket a call names (``_OneContext``)
    one_context: bool

    def send(self, n_chunks: int, step_rides: bool,
             otherwise_idle: bool) -> Sent:
        """The program for ``n_chunks`` chunks (rows past them dead), with
        or without the live slots' step riding; ``otherwise_idle``: the one
        prefill being sent is all the engine has to do. The table, first
        match: what is sent, how wide, at which context, and the cells whose
        traffic takes the case (tests/test_serve_chunk_plan.py):

        1. the step is carried, and several chunks go together or the step
           rides (``rides``: the caller's question before this one) or the
           plan is ``rows_only`` -> "mixed", ``rows`` wide: batch, longctx,
           agentcontext, voiceturns, agentturns (two rows, spare rows ahead);
           chat, assistant (one row); longdoc (two rows and none ahead: a
           PAIR, with the step riding or with no slot live).
        2. the step is carried, ONE row wide, and the engine has more to do
           than this prefill -> "mixed", one row, no step riding (the head at
           the chunk's last position if it ends its prompt, else nowhere):
           chat, assistant. A pass's second program; a burst after idleness,
           whose ``[C, V]`` results, float32 and allocated when sent, would
           pile up (48 prompts at a vocabulary of 261120: 15.5 GB of 16).
        3. several chunks -> "rows", ``rows`` wide, the whole table:
           longanswer, mixedlength (by the ridge), reasoning (its
           stateless tail). ONE static context: a row's attention follows
           its own context whatever the table's length (the chunk kernels
           skip the pages behind their chunk): a bucket spares a gather.
        4. one chunk, ``lone_at_last`` -> "rows", one row, its own bucket
           (as many programs as "lone" had, named alike): reasoning.
        5. one chunk -> "lone", its own bucket: longanswer, mixedlength
           (a prefill alone); longdoc (a prefill alone, beside live slots
           too: no step rides a program with a dead row, ``rides``); batch,
           agentturns (a prompt's odd last chunk with no slot live); chat,
           assistant (a prompt sent alone to an idle engine: a caller's way
           to reach every bucket's name, and nobody waits on it)."""
        if self.carries_step and (
                n_chunks > 1 or step_rides or self.rows_only
                or (self.rows == 1 and not otherwise_idle)):
            return Sent("mixed", self.rows, WHOLE_TABLE)
        if n_chunks > 1:
            return Sent("rows", self.rows, WHOLE_TABLE)
        return Sent("rows" if self.lone_at_last else "lone", 1, OWN_BUCKET)

    def rides(self, n_chunks: int) -> bool:
        """Whether the live slots' step rides the program of ``n_chunks``
        chunks: the caller asks BEFORE it readies a round, and hands the
        answer (and whether a slot is live) to ``send`` as ``step_rides``.
        Only a program whose rows are all filled carries the step. One row
        wide it always is; where the plan fills spare rows itself (``ahead``)
        it is as far as the prompts reach, and the odd row left dead is the
        price of ONE program. Several rows wide with no row to send ahead
        (a layer hands a state from a chunk's END to the next chunk's start
        and its operator does not do so from row to row: longdoc) that is
        ``n_chunks == rows``: a lone chunk beside a dead row
        would cost a two-row program where the one-row program and a step of
        its own are cheaper (31 + 2 ms against 18 + 9.5: ROADMAP Speed 0), so
        it goes "lone" and the iteration's step goes out as its own
        program."""
        return self.carries_step and (
            self.rows == 1 or self.ahead or n_chunks == self.rows)

    def programs(self) -> frozenset:
        """The programs ``send`` can name: those the engine builds."""
        return frozenset(
            self.send(n, rides, idle).program
            for n in range(1, self.rows + 1)
            for rides in (False, self.rides(n))
            for idle in (False, n == 1 and not rides))


def plan_chunks(cfg_prefill: DecoderConfig, cache: dict,
                batching: BatchingSpec, attn_impl: str) -> ChunkPlan:
    """The plan of an engine of ``batching`` over ``cache`` (the pool's
    planes, or their names, shapes and dtypes alone: ``engine_pool_shapes``
    as ``jax.ShapeDtypeStruct``) whose chunk programs run ``cfg_prefill``
    with ``attn_impl`` ("pallas" | "gather", resolved). Observed, not set:
    there is one algorithm, "fill the program the pass sends"."""
    b = batching
    chunk = max(0, int(b.chunked_prefill_tokens)) or int(b.page_size)
    prefills = max(1, int(b.max_concurrent_prefills))
    # Nothing may ride the programs that the carrying program does not
    # carry: adapter buffers, a speculative round.
    carries_step = (chunk_carries_step(cache, cfg_prefill, None, attn_impl)
                    and b.speculative.mode == "off"
                    and not b.lora.max_adapters)
    # SEVERAL rows only where one chunk leaves the weights under-used, or
    # the stack ENDS in layers that keep no state: "rows" runs that tail at
    # the one position a row whose logits are read (``paged._pool_forward``).
    tail_at_last = cfg_prefill.stateless_tail > 0
    by_ridge = chunk_rows_per_weight(cfg_prefill, chunk) < RIDGE_ROWS
    rows = prefills if by_ridge or tail_at_last else 1
    # (``chunk_rows_follow`` given that the chunk meets the pool in place,
    # which ``carries_step`` tests)
    ahead = carries_step and rows > 1 and chunk_rows_follow(cfg_prefill)
    one_context = not chunk_reads_context(cache, cfg_prefill, None, attn_impl)
    return ChunkPlan(
        carries_step=carries_step, rows=rows,
        lone_at_last=tail_at_last or (rows == 1 and not carries_step),
        ahead=ahead, rows_only=ahead and not one_context,
        one_context=one_context)


def pack_rows(rows, width: int, chunk: int, mpp: int) -> tuple:
    """The five host arrays of a program ``width`` rows wide: tokens
    [width,chunk], table [width,mpp], start, valid and ends [width]. A row of
    ``rows``: (the chunk's real tokens, its table row, its start, whether it
    ends its prompt); rows past them are DEAD: no valid position or page."""
    block = np.zeros((width, chunk), np.int32)
    table = np.full((width, mpp), -1, np.int32)
    start = np.zeros((width,), np.int32)
    valid = np.zeros((width,), np.int32)
    ends = np.zeros((width,), np.bool_)
    for r, (tokens, table_row, pos, end) in enumerate(rows):
        valid[r] = len(tokens)
        block[r, :valid[r]] = tokens
        table[r], start[r], ends[r] = table_row, pos, end
    return block, table, start, valid, ends


def _row0(out):
    """A one-row chunk program's ([1,C,V] logits, cache) as ([C,V], cache)."""
    return out[0][0], out[1]


class _OneContext:
    """A chunk program of a pool whose chunk does not read its context
    bucket (``paged.chunk_reads_context``): whatever bucket a call names, it
    runs and lowers the program of ``context``: ONE program to trace, load
    and warm at a start, not one a bucket. A call with LoRA takes the
    gathered form, which reads its bucket, and keeps it."""

    def __init__(self, jitted, context: int, at: int):
        """``at``: where the bucket stands among the arguments."""
        self.jitted, self.context, self.at = jitted, context, at

    def _at_one(self, args):    # (p, c, t, tr, st, vl[, ends], ncp[, lr, ai])
        at = self.at
        if len(args) > at + 1 and args[at + 1] is not None:
            return args
        return args[:at] + (self.context,) + args[at + 1:]

    def __call__(self, *args):
        return self.jitted(*self._at_one(args))

    def lower(self, *args):
        return self.jitted.lower(*self._at_one(args))


class ChunkPrograms:
    """The chunk programs of one engine, over its parameters, pool, decode
    state and key, each built when it is first asked for (``ask``): here,
    those the plan sends. ``introspected``: on the TPU, the engine's wrapper
    that records a program's kernels at its first dispatch."""

    def __init__(self, engine, plan: ChunkPlan, cfg_prefill: DecoderConfig,
                 attn_impl: str, introspected: Optional[Callable] = None):
        self.plan, self._introspected = plan, introspected
        self._eng, self._cfg, self._impl = engine, cfg_prefill, attn_impl
        self._chunk, self._mpp = engine.chunk_size, engine._mpp
        for name in sorted(plan.programs()):
            self.ask(name)

    def ask(self, name: str):
        """The program ``name`` ("lone" | "rows" | "mixed"), built now if
        nobody asked before (a ``jax.jit`` object costs nothing uncalled)."""
        if not hasattr(self, name):
            getattr(self, f"_build_{name}")()
            if self._introspected is not None:      # under its name there
                setattr(self, name, self._introspected(
                    "paged_mixed" if name == "mixed"
                    else "paged_chunk_prefill", getattr(self, name)))
        return getattr(self, name)

    def _chunk_rows(self, p, c, t, tr, st, vl, ncp, lr, ai,
                    logits_at="all", wanted=None):
        logits, cache = paged_chunk_prefill(
            p, c, t, tr, st, vl, self._cfg, context_pages=ncp, lora=lr,
            adapter_idx=ai, paged_attn_impl=self._impl, logits_at=logits_at,
            wanted=wanted)
        return logits, self._eng._pin(cache)

    # Jitted lambdas: what finds a decode step by its module's name finds
    # decode-only steps. Each donates the pool: it mutates in place in HBM.

    def _build_lone(self) -> None:
        self.lone = jax.jit(
            lambda p, c, t, tr, st, vl, ncp, lr=None, ai=None: _row0(
                self._chunk_rows(p, c, t, tr[None], st[None], vl[None], ncp,
                                 lr, ai)),
            static_argnums=(6,), donate_argnums=(1,))
        if self.plan.one_context:
            self.lone = _OneContext(self.lone, self._mpp, at=6)

    def _build_rows(self) -> None:
        self.rows = jax.jit(
            lambda p, c, t, tr, st, vl, ends, ncp, lr=None, ai=None:
            self._chunk_rows(p, c, t, tr, st, vl, ncp, lr, ai, "last", ends),
            static_argnums=(7,), donate_argnums=(1,))
        if self.plan.lone_at_last and self.plan.one_context:    # own bucket
            self.rows = _OneContext(self.rows, self._mpp, at=7)

    def _build_mixed(self) -> None:
        def mixed(p, c, t, tr, s0, vl, ends, ride, st, tbl, key, m):
            logits, out, cache, tokens, lengths, live, budgets = \
                paged_mixed_step(
                    p, {**c, "table": tbl}, t, tr, s0, vl, ends, ride,
                    st["tokens"], st["lengths"], st["live"], st["temps"],
                    st["top_k"], st["top_p"], st["stops"], st["budgets"],
                    key, self._cfg, sample_mode=m, attn_impl=self._impl)
            table = cache.pop("table")
            st = {**st, "tokens": tokens, "lengths": lengths,
                  "live": live, "budgets": budgets}
            rows = cache[MOE_ROWS] + 0 if MOE_ROWS in cache else None
            return logits, out, self._eng._pin(cache), st, table, rows

        self.mixed = jax.jit(
            lambda p, c, t, tr, s0, vl, ends, ride, st, tbl, key, m:
            mixed(p, c, t, tr, s0, vl, ends, ride, st, tbl, key, m),
            static_argnums=(11,), donate_argnums=(1, 8, 9))

    def pack(self, rows, width: int) -> tuple:
        """``pack_rows`` at this engine's chunk and table lengths."""
        return pack_rows(rows, width, self._chunk, self._mpp)

    def send(self, sent: Sent, packed: tuple, mode: Optional[str] = None,
             adapters=()):
        """Enqueue ``sent.program`` over ``packed`` (``pack`` at
        ``sent.rows``) and adopt the pool, and of "mixed" the decode state,
        that it returns. ``mode``: the sampling mode of the live slots' step
        where they ride "mixed" (a key is drawn), else None (every decode
        row dead: greedy, the key not drawn from). ``adapters``: the live
        rows' adapter indices, read where the engine has adapters. Returns
        (logits, of "mixed" (the round's token buffer, the expert rows' sums
        as the program leaves them) else None)."""
        eng = self._eng
        chunk, table, start, valid, ends = packed
        if sent.program == "mixed":
            ride = mode is not None
            logits, out, eng.cache, st, tbl, rows = self.mixed(
                eng.params, eng.cache, jnp.asarray(chunk),
                jnp.asarray(table), jnp.asarray(start), jnp.asarray(valid),
                jnp.asarray(ends), jnp.asarray(ride), eng._dstate.arrays,
                eng._dstate.table, eng._next_key() if ride else eng._rng,
                mode or "greedy")
            eng._dstate.adopt(st, tbl)
            return logits, (out, rows)
        # A chunk's own bucket: the next power of two covering the pages it
        # can see, so its cost tracks its position, not max_len, with a
        # log-bounded trace set. Its writes address per token off the table
        # row: the position may sit mid-page (the radix COW tail resume).
        context = self._mpp if sent.context == WHOLE_TABLE else \
            context_bucket(int(start[0]), self._chunk, eng.page_size,
                           self._mpp)
        lora = () if eng._lora is None else (
            eng._lora.buffers, jnp.asarray(np.asarray(
                [*adapters] + [-1] * (sent.rows - len(adapters)), np.int32)))
        if sent.program == "rows":
            logits, eng.cache = self.rows(
                eng.params, eng.cache, jnp.asarray(chunk),
                jnp.asarray(table), jnp.asarray(start), jnp.asarray(valid),
                jnp.asarray(ends), context, *lora)
        else:
            logits, eng.cache = self.lone(
                eng.params, eng.cache, jnp.asarray(chunk),
                jnp.asarray(table[0]), jnp.int32(start[0]),
                jnp.int32(valid[0]), context, *lora)
        return logits, None

    def warm(self, warm: Callable) -> None:
        """Compile or load, and run once, now (``warm(program's key, run)``:
        ``LLMEngine._warm``), on DEAD rows, what no warm-up of a caller's can
        be relied on to reach (several concurrent prefills, a chunk beside a
        live slot): the program set is the engine's own, and fixed from here
        on. The state and the pool go in as traffic hands them in; no key is
        drawn. The program of the plan's one width where it carries a step
        or several chunks (and the read of each row's logits); "lone" under
        every bucket's name (ONE program; callers outside ask
        ``program_kernels`` for the names) where the engine sends chunks
        ahead and keeps it: its traffic reaches one bucket a prompt."""
        eng, plan, C = self._eng, self.plan, self._chunk
        rows = plan.rows
        if plan.carries_step or rows > 1:
            program, name, last = ("mixed", "paged_mixed", "greedy") \
                if plan.carries_step else (
                    "rows", "paged_chunk_prefill", self._mpp)

            def run():
                logits, _ = self.send(Sent(program, rows, WHOLE_TABLE),
                                      self.pack([], rows))
                return [logits[r] for r in range(rows)]

            warm(program_key(name, f"{rows}x{C}", last), run)
        if plan.ahead and not plan.rows_only:
            block = jnp.zeros((1, C), jnp.int32)
            row = jnp.full((self._mpp,), -1, jnp.int32)
            for ctx in sorted({context_bucket(pos, C, eng.page_size,
                                              self._mpp)
                               for pos in range(0, eng.max_len,
                                                eng.page_size)}):
                def run(ctx=ctx):
                    logits, eng.cache = self.lone(
                        eng.params, eng.cache, block, row, jnp.int32(0),
                        jnp.int32(0), ctx)
                    return logits

                warm(program_key("paged_chunk_prefill", f"1x{C}", ctx), run)
