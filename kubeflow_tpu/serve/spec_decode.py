"""Speculative decoding — draft + batched verify over the page pool.

Why it wins on v5e: a decode step is dispatch- and HBM-bound (the whole
param read for ONE token per slot), so scoring k+1 positions per slot in a
single dispatch costs barely more than scoring one — the params are read
once either way. If a cheap drafter can guess the next k tokens, greedy
verification accepts the longest prefix that matches the target's own
argmax and emits one extra "correction" token from the position that broke
the match, so every round emits between 1 and k+1 tokens at output
TOKEN-IDENTICAL to plain greedy decode (the accepted tokens ARE the
target's argmax chain by construction).

Two draft sources (core/serving.py ``SpeculativeSpec``):

- **ngram** (prompt/self lookup, vLLM's ``ngram`` analog): match the last
  n-gram of prompt+generated against its own earlier occurrences and
  propose the continuation that followed. Free (no model), and strong
  exactly where serving traffic is decode-heavy: templated suffixes,
  extraction, code, and greedy generations that fall into repeating cycles.
- **draft_model**: a small decoder (same vocab) runs ``k`` autoregressive
  steps per round against its OWN page pool, whose table is the identity
  (slot s owns pages s*mpp .. (s+1)*mpp-1: no allocator); the target
  verifies. The draft cache tracks the true sequence via a per-slot
  consumed-length pointer — on rejection the pointer rewinds (draft KV
  past it is garbage but every position is rewritten before it is ever
  attended, the same overwrite-before-read invariant the decode step
  already relies on).

Verification is exact for GREEDY requests only (argmax chains compose);
the engine falls back to the normal decode path whenever a sampling
request shares the batch.

The verify dispatch is the page pool's one builder at ``T = k+1`` tokens a
row (``paged._pool_forward`` over ``paged._pool_block``, whose ``T = 1`` is
the decode step): the pool rides it flat and is written in place, and what
attends is the gathered form whatever kernels the decode step runs.

KV rollback: the verify dispatch writes K/V for all k+1 positions before
acceptance is known. Rejected positions hold garbage (overwritten before
read), and the engine truncates each slot's page table back to the
accepted length (engine._truncate_slot_pages) so the pool's refcounts
always account for exactly the tokens a slot actually kept.

Scheduler-state residency: ``paged_verify_step`` consumes the SAME
device-resident page table the plain decode path owns
(serve/device_state.py) — the engine syncs dirty rows as deltas (with
whatever else the round dirtied, in one program) and donates the table
through the dispatch, so a verify round never re-uploads the full table.
The ``[B, T]`` token matrix and the ``[B]`` lengths/live masks are
inherently per-round host data (the drafts were proposed on host), and
rollback marks the affected rows dirty for the next sync.
Because verification is a host-side decision between dispatches, spec
rounds do not pipeline — the engine drains any in-flight plain round
before entering a spec round.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.models.decoder import Params
from kubeflow_tpu.serve.paged import (
    _head_logits, _paged_decode_step, _planes_of, _pool_forward,
    _pool_planes,
)


# -- drafting ------------------------------------------------------------------

def ngram_propose(ctx: Sequence[int], k: int, ngram_max: int,
                  ngram_min: int) -> list[int]:
    """Prompt/self-lookup drafting: find the most recent earlier occurrence
    of the context's last n-gram (longest n first) and propose the up-to-k
    tokens that followed it. Returns [] when nothing matches — the engine
    then decodes that slot normally (a wrong draft costs a wasted verify
    column; no draft costs nothing)."""
    ln = len(ctx)
    for n in range(min(ngram_max, ln - 1), ngram_min - 1, -1):
        pat = tuple(ctx[ln - n:])
        # rightmost earlier occurrence: recent history predicts the
        # immediate future better than the distant past
        for i in range(ln - n - 1, -1, -1):
            if tuple(ctx[i:i + n]) == pat:
                out = list(ctx[i + n:i + n + k])
                if out:
                    return out
                break       # match flush against the suffix: nothing follows
    return []


# -- batched verify -------------------------------------------------------------

def paged_verify_step(params: Params, cache: dict, tokens: jax.Array,  # traced
                      lengths: jax.Array, live: jax.Array,
                      cfg: DecoderConfig):
    """ONE dispatch scoring T = k+1 positions per slot over the page pool:
    the pool's one builder (``paged._pool_forward``) at ``T`` tokens a row
    and the argmax (cache carries "table"; the host pre-allocates pages
    covering all T write positions, exactly like paged_decode_multi's
    contract). tokens [B,T] = [last_token, draft_1..draft_k] (pad columns
    are scored too — the host just ignores them); lengths [B] = the write
    position of tokens[:,0], exactly as in paged._paged_decode_step. Every
    column of a live row writes its K/V; a dead row, an unmapped page and a
    position past the table's reach aim out of bounds and DROP. Attention
    is the gathered form whatever the engine's decode step runs: verify in
    place through the chunk kernel waits for a cell that times speculation
    (ROADMAP Design 1).

    Returns ([B,T] int32 greedy next-token ids, new cache): row b column t
    is the target's argmax continuation after consuming tokens[b, :t+1] —
    the verification oracle for draft t+1 and the correction/bonus token
    when the match breaks there."""
    table = cache["table"]
    x, flat = _pool_forward(
        params, cache, tokens, table, lengths,
        jnp.where(live, tokens.shape[1], 0), cfg, "gather")
    greedy = jnp.argmax(_head_logits(params, x, cfg), axis=-1)
    return greedy.astype(jnp.int32), {**_pool_planes(flat, cache),
                                      "table": table}


# -- draft-model proposal ------------------------------------------------------

def draft_propose(params: Params, cache: dict, deltas: jax.Array,  # traced
                  delta_lens: jax.Array, draft_pos: jax.Array,
                  live: jax.Array, cfg: DecoderConfig, num_steps: int):
    """Catch-up + autoregressive drafting for the small model in ONE
    dispatch of ``num_steps`` single-token decode steps over its own page
    pool (``cache`` carries the identity "table"; paged._paged_decode_step
    reused verbatim — the draft is just another decoder).

    Per slot b: steps t < delta_lens[b] feed deltas[b, t] (the true tokens
    the draft hasn't consumed yet — the previous round's accepted suffix);
    later steps feed the draft's own greedy prediction from the step
    before. Every step's argmax lands in out[:, t]; the host reads slot
    b's k drafts at columns delta_lens[b]-1 .. delta_lens[b]-1+k-1.

    Returns (out [B, num_steps] int32, new cache)."""
    b = deltas.shape[0]
    dmax = deltas.shape[1]
    max_len = cache["table"].shape[1] * cache[_planes_of(cache)[0]].shape[2]

    def body(carry, t):
        cache, prev = carry
        fed = jnp.where(t < delta_lens,
                        deltas[:, jnp.clip(t, 0, dmax - 1)], prev)
        lengths = draft_pos + t
        step_live = live & (lengths < max_len)
        logits, cache = _paged_decode_step(params, cache, fed, lengths,
                                           step_live, cfg)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (cache, g), g

    (cache, _), outs = jax.lax.scan(
        body, (cache, jnp.zeros((b,), jnp.int32)),
        jnp.arange(num_steps, dtype=jnp.int32))
    return outs.T, cache
