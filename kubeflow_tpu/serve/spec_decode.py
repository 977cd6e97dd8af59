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

KV rollback: the verify dispatch writes K/V for all k+1 positions before
acceptance is known. Rejected positions hold garbage (overwritten before
read), and the engine truncates each slot's page table back to the
accepted length (engine._truncate_slot_pages) so the pool's refcounts
always account for exactly the tokens a slot actually kept.

Scheduler-state residency: ``paged_verify_step`` consumes the SAME
device-resident page table the plain decode path owns
(serve/device_state.py) — the engine syncs dirty rows as deltas and
donates the table through the dispatch, so a verify round never re-uploads
the full table. The ``[B, T]`` token matrix and the ``[B]`` lengths/live
masks are inherently per-round host data (the drafts were proposed on
host), and rollback marks the affected rows dirty for the next sync.
Because verification is a host-side decision between dispatches, spec
rounds do not pipeline — the engine drains any in-flight plain round
before entering a spec round.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.models.decoder import Params
from kubeflow_tpu.serve.paged import (
    _paged_decode_step, _planes_of, paged_gather,
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

def _spec_attention(q, ck, cv, lengths, cfg: DecoderConfig):  # traced
    """T-query attention over the slots' gathered pages (the verify-length
    generalization of paged._decode_attention). q [B,T,H,Dh]; ck/cv
    [B,Smax,KV,Dh]; query t sits at position lengths[b]+t and attends
    kpos <= that."""
    b, t = q.shape[0], q.shape[1]
    smax = ck.shape[1]
    groups = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, t, cfg.n_kv_heads, groups, cfg.head_dim)
    scores = jnp.einsum("btkgd,bskd->btkgs", qg, ck,
                        preferred_element_type=jnp.float32)
    scores *= cfg.head_dim ** -0.5
    kpos = jnp.arange(smax, dtype=jnp.int32)
    qpos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    mask = kpos[None, None, :] <= qpos[:, :, None]            # [B,T,Smax]
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(ck.dtype)
    out = jnp.einsum("btkgs,bskd->btkgd", probs, cv)
    return out.reshape(b, t, cfg.n_heads, cfg.head_dim)


def _paged_spec_block(bp, x, positions, lengths, live, pool_k, pool_v,  # traced
                      table, cfg: DecoderConfig, pool_ks=None, pool_vs=None):
    """Verify block against the page pool (paged._paged_decode_block with a
    verify-length axis; always the gather attention impl — the Pallas
    paged-attention kernel is single-query). Position -> (page, offset)
    per token; unmapped pages, dead rows and positions past the table's
    reach aim out of bounds and DROP."""
    dt = cfg.activation_dtype
    kv_quant = pool_ks is not None
    pg = pool_k.shape[1]
    mpp = table.shape[1]
    h = L.rmsnorm(x, bp["ln1"], cfg)
    q = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", h, bp["attn"]["wv"].astype(dt))
    q, k = L.qk_rope(bp["attn"], q, k, positions, cfg)
    bidx = jnp.arange(x.shape[0])[:, None]                    # [B,1]
    page_slot = positions // pg                               # [B,T]
    page_id = table[bidx, jnp.clip(page_slot, 0, mpp - 1)]
    ok = live[:, None] & (page_id >= 0) & (positions < mpp * pg)
    pidx = jnp.where(ok, page_id, pool_k.shape[0])
    off = positions % pg
    nks = nvs = None
    if kv_quant:
        from kubeflow_tpu.ops.quantization import dequantize_kv, quantize_kv

        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        nk = pool_k.at[pidx, off].set(kq, mode="drop")
        nv = pool_v.at[pidx, off].set(vq, mode="drop")
        nks = pool_ks.at[pidx, off].set(ks, mode="drop")
        nvs = pool_vs.at[pidx, off].set(vs, mode="drop")
        ck = dequantize_kv(paged_gather(nk, table),
                           paged_gather(nks, table), dt)
        cv = dequantize_kv(paged_gather(nv, table),
                           paged_gather(nvs, table), dt)
    else:
        nk = pool_k.at[pidx, off].set(k, mode="drop")
        nv = pool_v.at[pidx, off].set(v, mode="drop")
        ck = paged_gather(nk, table)
        cv = paged_gather(nv, table)
    attn = _spec_attention(q, ck, cv, lengths, cfg)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, bp["attn"]["wo"].astype(dt))
    h = L.rmsnorm(x, bp["ln2"], cfg)
    if cfg.is_moe:
        mlp_out, _ = L.moe_block(bp["mlp"], h, cfg)
    else:
        mlp_out = L.mlp_block(bp["mlp"], h, cfg)
    return x + mlp_out, nk, nv, nks, nvs


def paged_verify_step(params: Params, cache: dict, tokens: jax.Array,  # traced
                      lengths: jax.Array, live: jax.Array,
                      cfg: DecoderConfig):
    """ONE dispatch scoring T = k+1 positions per slot over the page pool
    (cache carries "table"; the host pre-allocates pages covering all T
    write positions, exactly like paged_decode_multi's contract). tokens
    [B,T] = [last_token, draft_1..draft_k] (pad columns are scored too —
    the host just ignores them); lengths [B] = the write position of
    tokens[:,0], exactly as in paged._paged_decode_step.

    Returns ([B,T] int32 greedy next-token ids, new cache): row b column t
    is the target's argmax continuation after consuming tokens[b, :t+1] —
    the verification oracle for draft t+1 and the correction/bonus token
    when the match breaks there."""
    dt = cfg.activation_dtype
    kv_quant = "ks" in cache
    t = tokens.shape[1]
    x = params["embed"].astype(dt)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden ** 0.5, dt)
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    table = cache["table"]

    if kv_quant:
        def body(x, scan_in):
            bp, pk, pv, pks, pvs = scan_in
            x, nk, nv, nks, nvs = _paged_spec_block(
                bp, x, positions, lengths, live, pk, pv, table, cfg,
                pool_ks=pks, pool_vs=pvs)
            return x, (nk, nv, nks, nvs)

        x, scanned = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["ks"], cache["vs"]))
    else:
        def body(x, scan_in):
            bp, pk, pv = scan_in
            x, nk, nv, _, _ = _paged_spec_block(
                bp, x, positions, lengths, live, pk, pv, table, cfg)
            return x, (nk, nv)

        x, scanned = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["final_norm"], cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x, head.astype(dt),
                        preferred_element_type=jnp.float32)
    if cfg.logits_softcap is not None:
        logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
    out = {"k": scanned[0], "v": scanned[1], "table": table}
    if kv_quant:
        out["ks"], out["vs"] = scanned[2], scanned[3]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), out


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
