"""Pallas TPU paged-attention kernels: decode over per-head K/V pools
(first), latent pools and packed rows, a chunk of queries over a latent
pool, an indexer's scores over a row's pages and the exact selection behind
them (``paged_index_scores``, ``paged_select_keys``: the latent kernels take
the selection as a second mask), and a chunk of queries over per-head pools
(the end of the file).

The paged engine's XLA path reads KV twice per step: a gather materializes
each slot's pages into the [B, S, K, D] layout, then attention reads the
gathered buffer — 2× the HBM traffic of the contiguous cache (serve/paged.py
module notes). The kernels read pages DIRECTLY, each from the pool exactly
once, and the online softmax accumulates across pages in VMEM — the TPU form
of vLLM's PagedAttention (same role as the public jax pallas paged kernels;
written against this repo's pool/table layout and GQA grouping).

Every decode kernel WALKS A ROW'S LIVE PAGES ITSELF (PR 45: per-head pools,
described here; PR 48: latent pools and packed rows, ``_rows_decode_kernel``,
the same walk over planes of whole-token rows). Grid ``(rows,)``, in order;
the pools stay in HBM and the page table, the lengths and a window layer's
lower bounds ride in as scalar prefetch.
Inside a row a loop runs over the pages its context holds and no other, from
the page of ``lower[b]`` (0 without it) to the page of ``lengths[b]``,
``DECODE_PAGES_PER_TURN`` a turn: a turn waits for its K and V pages in one
half of a double buffer (``_walk_live_pages``: one copy a page a plane, a
DMA semaphore a half) while the next turn's copies, or the next row's first,
are in flight in the other. A table id below 0 is never copied and never
read; a row with nothing to attend to costs its scalars and writes zeros. A
page ``[page, K, D]`` is parted into its KV heads' ``[page, D]`` rows by the
strided load the chunk kernel uses (``_word_heads``), each head's queries
``[g, D]`` against them in the pool's type with float32 accumulation, the
probabilities in the pool's type too, as the XLA form has them; planes that
load does not take (int8, one KV head, narrow heads) go head-major through
float32 a page at a time.

The pool operand is whatever ``[P, page, K, D]`` array the table's ids index.
The decode step (serve/paged.py) hands in the WHOLE pool viewed flat
``[L*P, page, K, D]`` with the layer's table offset by ``l*P``, so no
per-layer slab is ever sliced out for the kernel: pages are copied from
where they lie.

int8 pools (``kv_cache_dtype="int8"``) ride the same walk with two more
planes: the per-token-per-head scales ``[P, page, K]`` (f32,
ops/quantization.quantize_kv layout), a page's copied beside it head-major
``[K, page]``. The kernel dequantizes in VMEM, on the small side of each
product: ``(q . k_int8) * ks`` and ``(p * vs) . v_int8``, so the HBM read
per decode step is the int8 page plus a 4/Dh-sized scale row instead of a
full-dtype page: the capacity win and the bandwidth win come from the same
bytes."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops import VMEM_BUDGET_BYTES, auto_interpret
from kubeflow_tpu.ops.attention import NEG_INF


# Most pages of a plane a turn of the decode walk copies and attends to at
# once over TWO planes (K and V); fewer where the planes' double buffers
# would take over half of ``VMEM_BUDGET_BYTES`` (``_pages_a_turn``), or the
# table row is shorter. A walk over ONE plane (a latent pool) takes twice as
# many, the same eight copies in flight a turn: four of its 164 KB pages a
# turn read 76% of the bus on a v5e, eight 90%, sixteen 90% (PERF.md, PR 48).
DECODE_PAGES_PER_TURN = 4


def _pages_a_turn(page_bytes: int, mpp: int, planes: int = 2) -> int:
    """Pages of EACH of ``planes`` planes a turn holds: two halves of them
    lie in fast memory beside the scores."""
    fit = VMEM_BUDGET_BYTES // 2 // (2 * planes * page_bytes)
    return max(1, min(DECODE_PAGES_PER_TURN * 2 // planes, fit, mpp))


def _walk_live_pages(table_ref, span, planes, sem, side_ref, attend):
    """The walk over this grid step's table row (grid ``(rows,)``, in
    order): its LIVE pages, ``first <= j < last`` of ``span(row)``, ``n`` a
    turn, copied from where they lie in HBM into one half of a double
    buffer while the turn before is attended to in the other. A row's last
    turn starts the first copies of the next row that has a turn, so a copy's
    latency is paid once a call and not once a row. ``planes``: ``(pool_ref
    [P, ...] in HBM, buf_ref [2, n, ...])`` a plane; ``side_ref`` (SMEM,
    [1]): the half the row's first turn lies in, carried from row to row;
    ``attend(half, j0, mapped)`` is called once a turn, its pages landed in
    ``buf_ref[half]``: ``j0`` the turn's first page and ``mapped`` a flag a
    page. A page with no id (a hole: -1) or past ``last`` (the last turn is
    short) is neither copied nor waited for: its flag is False and its
    buffer holds ANYTHING (NaN too), so ``attend`` takes nothing from it. A
    turn with no mapped page is not attended to at all, so a dead row (every
    id -1) costs its scalars."""
    n = planes[0][1].shape[1]
    mpp = table_ref.shape[1]
    b, rows = pl.program_id(0), pl.num_programs(0)

    def turns_of(r):
        first, last = span(r)
        return jax.lax.div(jnp.maximum(last - first, 0) + n - 1, n)

    def row_behind(r):
        """The first row behind ``r`` that has a turn (``rows``: none)."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(r < rows, turns_of(
                jnp.minimum(r, rows - 1)) == 0), lambda r: r + 1, r + 1)

    def pages_of(r, t):
        """(the page's id, whether it is copied) for each of turn t's."""
        first, last = span(r)
        out = []
        for i in range(n):
            j = first + t * n + i
            pid = table_ref[r, jnp.minimum(j, mpp - 1)]
            out.append((pid, jnp.logical_and(j < last, pid >= 0)))
        return out

    def copies(half, i, pid):
        return [pltpu.make_async_copy(pool.at[pid], buf.at[half, i],
                                      sem.at[half])
                for pool, buf in planes]

    def start(r, t, half):
        for i, (pid, mapped) in enumerate(pages_of(r, t)):
            @pl.when(mapped)
            def _():
                for copy in copies(half, i, pid):
                    copy.start()

    @pl.when(b == 0)
    def _():
        side_ref[0] = 0
        r = row_behind(-1)
        pl.when(r < rows)(lambda: start(jnp.minimum(r, rows - 1), 0, 0))

    turns = turns_of(b)
    side = side_ref[0]

    def turn(t, _):
        half = jax.lax.rem(side + t, 2)
        more = t + 1 < turns            # else: the next row's first turn
        r = jnp.where(more, b, row_behind(b))
        pl.when(r < rows)(lambda: start(
            jnp.minimum(r, rows - 1), jnp.where(more, t + 1, 0), 1 - half))
        pages = pages_of(b, t)
        for i, (pid, mapped) in enumerate(pages):
            @pl.when(mapped)
            def _():
                for copy in copies(half, i, pid):
                    copy.wait()     # blocking-ok: a DMA semaphore, in-kernel
        mapped = [m for _, m in pages]
        pl.when(functools.reduce(jnp.logical_or, mapped))(
            lambda: attend(half, span(b)[0] + t * n, mapped))

    jax.lax.fori_loop(0, turns, turn, None)
    side_ref[0] = jax.lax.rem(side + turns, 2)


def _turn_seen(shape, pg: int, j0, mapped, length, lowest=None):
    """Which keys of a turn a row attends to, ``shape`` ``[.., n * pg]``:
    positions from the turn's first page ``j0`` on, up to ``length`` and,
    with a bound, from ``lowest``; none of a page never copied, whatever
    its buffer holds."""
    at = len(shape) - 1
    kv_page = jax.lax.broadcasted_iota(jnp.int32, shape, at) // pg
    kv_pos = j0 * pg + jax.lax.broadcasted_iota(jnp.int32, shape, at)
    seen = kv_pos <= length
    if lowest is not None:
        seen = jnp.logical_and(seen, kv_pos >= lowest)
    for i, m in enumerate(mapped):
        seen = jnp.logical_and(seen, jnp.logical_or(m, kv_page != i))
    return seen


def _decode_kernel(table_ref, len_ref, *rest, page_size: int,
                   sm_scale: float, quantized: bool, bounded: bool,
                   strided: bool):
    lo_ref = None
    if bounded:                         # a third scalar operand: the lowest
        lo_ref, rest = rest[0], rest[1:]    # position each row attends to
    q_ref, *rest = rest
    num = 4 if quantized else 2         # planes: K, V and, int8, their scales
    pools, o_ref, bufs = rest[:num], rest[num], rest[num + 1:2 * num + 1]
    sem, side_ref, q_rows, m_ref, l_ref, acc_ref, s_ref = rest[2 * num + 1:]
    k_buf, v_buf = bufs[:2]
    b = pl.program_id(0)
    pg = page_size
    n = k_buf.shape[1]
    kv, g, d = acc_ref.shape
    mpp = table_ref.shape[1]
    length = len_ref[b]                 # position being decoded (inclusive)
    lowest = jnp.maximum(lo_ref[b], 0) if bounded else 0

    def span(r):
        """Row ``r``'s live pages: from the page of the lowest position it
        attends to up to the page of its length (a length below 0: none)."""
        first = jax.lax.div(jnp.maximum(lo_ref[r], 0), pg) if bounded else 0
        return first, jnp.clip(jax.lax.div(len_ref[r] + pg, pg), 0, mpp)

    _softmax_init(m_ref, l_ref, acc_ref)
    # The queries a KV head: q and the result keep the shapes the projections
    # around the call have, [H, D] (another shape moves the layouts XLA gives
    # those projections' weights: tests/test_chip_compile.py, the copies).
    q_rows[:] = q_ref[0, 0].astype(jnp.float32).reshape(kv, g, d)
    # the heads of a 32-bit word of a row (the strided form): _word_heads
    per = 4 // k_buf.dtype.itemsize if strided else 1

    def page_heads(buf, half, i):
        """Page ``i`` of the half head-major ``[KV, page, D]`` in float32:
        the form for planes the strided load does not take (int8 pages,
        still to be scaled; one KV head, whose page is ``[page, D]``; an
        odd count of two-byte heads; rows that are not 128 values)."""
        x = buf[half, i].astype(jnp.float32)
        return x[None] if x.ndim == 2 else jnp.swapaxes(x, 0, 1)

    def attend(half, j0, mapped):
        at = [slice(i * pg, (i + 1) * pg) for i in range(n)]
        if strided:
            def scores(w, _):
                for i in range(n):
                    words = _page_words(k_buf.at[half, i], per)[
                        pl.ds(w, pg, stride=kv // per), :]
                    for part, rows in enumerate(_word_heads(
                            words, k_buf.dtype)):
                        head = w * per + part
                        s_ref[head, :, at[i]] = jax.lax.dot_general(
                            q_rows[head].astype(rows.dtype), rows,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [g, pg]

            # One trip a word of heads, not KV unrolled copies of it: the
            # body is lowered again at every call site (PERF.md, PR 36).
            jax.lax.fori_loop(0, kv // per, scores, None)
        else:
            for i in range(n):
                s = jax.lax.dot_general(
                    q_rows[:], page_heads(k_buf, half, i),
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)  # [KV, g, pg]
                if quantized:   # an int8 page's scales, [KV, pg], act on
                    s = s * bufs[2][half, i][:, None, :]     # its scores
                s_ref[:, :, at[i]] = s

        seen = _turn_seen((1, 1, n * pg), pg, j0, mapped, length,
                          lowest if bounded else None)
        s = jnp.where(seen, s_ref[:] * sm_scale, NEG_INF)     # [KV, g, n*pg]
        m_prev = m_ref[:]                                # [KV, g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=2, keepdims=True)
        m_ref[:] = m_new
        s_ref[:] = p

        acc_ref[:] = acc_ref[:] * alpha

        if strided:
            # Probabilities go to the MXU in the pool's type, as in the XLA
            # form (serve/paged.py::_decode_attention); a page never copied
            # adds nothing, whatever its buffer holds (0 x NaN is NaN).
            def weighted(w, _):
                out = [jnp.zeros((g, d), jnp.float32) for _ in range(per)]
                for i in range(n):
                    words = _page_words(v_buf.at[half, i], per)[
                        pl.ds(w, pg, stride=kv // per), :]
                    for part, rows in enumerate(_word_heads(
                            words, v_buf.dtype)):
                        out[part] += jnp.where(mapped[i], jax.lax.dot_general(
                            s_ref[w * per + part, :, at[i]].astype(
                                rows.dtype),
                            rows, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32), 0)  # [g, D]
                for part in range(per):
                    acc_ref[w * per + part] += out[part]

            jax.lax.fori_loop(0, kv // per, weighted, None)
        else:
            for i in range(n):
                p = s_ref[:, :, at[i]]
                if quantized:                   # and on its probabilities
                    p = p * bufs[3][half, i][:, None, :]
                acc_ref[:] += jnp.where(mapped[i], jax.lax.dot_general(
                    p, page_heads(v_buf, half, i),
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32), 0)  # [KV, g, D]

    _walk_live_pages(table_ref, span, list(zip(pools, bufs)), sem, side_ref,
                     attend)
    # A row that attended to nothing (a dead slot: every id -1) keeps l ==
    # 0 and emits zeros; the host discards them anyway.
    o_ref[0, 0] = _softmax_result(l_ref, acc_ref, o_ref.dtype).reshape(
        kv * g, d)


def paged_decode_attention(
    q: jax.Array,                 # [B, 1, H, D] — one decode token per slot
    pool_k: jax.Array,            # [P, page, K, D]
    pool_v: jax.Array,            # [P, page, K, D]
    table: jax.Array,             # [B, mpp] int32 page ids (-1 = unmapped)
    lengths: jax.Array,           # [B] position being decoded (attend <=)
    *,
    pool_ks: Optional[jax.Array] = None,   # [P, page, K] f32 (int8 pools)
    pool_vs: Optional[jax.Array] = None,
    sm_scale: Optional[float] = None,
    lower: Optional[jax.Array] = None,     # [B] lowest position attended to
    kv_heads: int = 0,            # planes kept as rows [P, page * K, D]
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Exact decode attention over the page pool; returns [B, 1, H, D].

    ``kv_heads`` > 0: the planes are kept as rows, ``[P, page * K, D]``
    (``_page_words``): the same bytes in the same order with no padded
    dimension, for a head count that is no whole tile.

    If ``pool_ks``/``pool_vs`` are given, ``pool_k``/``pool_v`` hold int8
    pages and the kernel dequantizes in VMEM (per-token-per-head scales).

    ``lower`` (a window layer's call): row ``b`` attends to positions
    ``lower[b] <= j <= lengths[b]`` of ITS table row, keys behind the bound
    are masked and a page wholly behind it is not read. Positions count
    from the table row's first page, so a caller that hands in only the
    pages a window touches (serve/paged.py: two of a ring) reads no other;
    the call is named ``paged_window_decode_attention`` in a trace."""
    b, one, h, d = q.shape
    if one != 1:
        raise ValueError("paged decode attention takes one token per slot")
    kh = kv_heads or pool_k.shape[2]
    if h % kh:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {kh}")
    if (pool_ks is None) != (pool_vs is None):
        raise ValueError("pool_ks and pool_vs must be given together")
    if kv_heads and not (kh > 1 and pool_ks is None
                         and chunk_attention_supported(kh, d, pool_k.dtype)):
        # rows the strided form does not read (one head, rows that are not
        # 128 values: no plane of a chip's): as pages of heads again
        pool_k, pool_v = (pool.reshape(pool.shape[0], -1, kh, d)
                          for pool in (pool_k, pool_v))
        kv_heads = 0
    return _decode_attention_call(
        q, pool_k, pool_v, table, lengths, pool_ks, pool_vs, lower,
        sm_scale=sm_scale if sm_scale is not None else d ** -0.5,
        interpret=interpret if interpret is not None else auto_interpret(),
        # no keyword where the planes are pages of heads: the call lowers
        # under the name it had
        **({"kv_heads": kv_heads} if kv_heads else {}))


# Traced ONCE for each set of shapes and inlined wherever it is called: the
# programs of a decode ladder (serve/pacing.py) attend through the same call.
@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "kv_heads"),
                   inline=True)
def _decode_attention_call(q, pool_k, pool_v, table, lengths, pool_ks,
                           pool_vs, lower, *, sm_scale: float,
                           interpret: bool, kv_heads: int = 0):
    b, _, h, d = q.shape
    if kv_heads:
        p_total, kh, page = pool_k.shape[0], kv_heads, \
            pool_k.shape[1] // kv_heads
    else:
        p_total, page, kh, _ = pool_k.shape
    quantized = pool_ks is not None
    g = h // kh
    mpp = table.shape[1]
    n = _pages_a_turn(page * kh * d * pool_k.dtype.itemsize, mpp)
    bounded = lower is not None
    kernel = functools.partial(
        _decode_kernel, page_size=page, sm_scale=sm_scale,
        quantized=quantized, bounded=bounded,
        strided=not quantized and kh > 1
        and chunk_attention_supported(kh, d, pool_k.dtype))
    scalars = (table, lengths, lower) if bounded else (table, lengths)
    planes = [pool_k, pool_v]
    if kh == 1:     # one KV head: a page is its rows (a bitcast; the chip's
        # copy engine takes no slice of a dimension of 1 padded to a tile)
        planes = [pool.reshape(p_total, page, d) for pool in planes]
    if quantized:   # a page's scales head-major [K, page]: how the chip
        # lays the plane out (a bitcast there), whole tiles for the copy
        planes += [jnp.swapaxes(scales.astype(jnp.float32), 1, 2)
                   for scales in (pool_ks, pool_vs)]

    def row(bi, *_):
        return (bi, 0, 0, 0)

    return pl.pallas_call(
        kernel,
        name=("paged_window_decode_attention" if bounded
              else "paged_decode_attention"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b,),
            in_specs=[pl.BlockSpec((1, 1, h, d), row)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(planes),
            out_specs=pl.BlockSpec((1, 1, h, d), row),
            scratch_shapes=[
                pltpu.VMEM((2, n, *plane.shape[1:]), plane.dtype)
                for plane in planes] + [
                pltpu.SemaphoreType.DMA((2,)),          # one a half
                pltpu.SMEM((1,), jnp.int32),    # the half a row starts in
                pltpu.VMEM((kh, g, d), jnp.float32),    # queries a KV head
                pltpu.VMEM((kh, g, 1), jnp.float32),    # running max m
                pltpu.VMEM((kh, g, 1), jnp.float32),    # running denom l
                pltpu.VMEM((kh, g, d), jnp.float32),    # output accumulator
                pltpu.VMEM((kh, g, n * page), jnp.float32),  # scores, probs
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # rows in order: a row's last turn starts the next row's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, q, *planes)


# -- latent (MLA) pools ----------------------------------------------------------
#
# A latent pool holds ONE row a token for all heads, ``[P, page, W]``: the
# compressed key/value latent (after its norm), the rotary key values every
# head shares, zeros up to whole 128-value lanes (models/layers.py::
# latent_qkv). Attention over it is ABSORBED: the caller folds the key
# expansion into the query (``layers.latent_query``: a query laid out like a
# row, so a head's score is ONE product with the row) and expands the
# attended row afterwards (``layers.latent_output``), so a kernel is
# multi-query attention of H heads against one shared row that is also the
# value, and no per-head K or V of the context ever exists. Two kernels,
# blockwise softmax, float32 accumulation. One query a slot (decode) takes
# the decode walk (``_rows_decode_kernel``: grid ``(rows,)``, a row's live
# pages by ``_walk_live_pages``' copies, eight a turn). A chunk of queries of
# one slot (chunk prefill) is a grid ``(heads, blocks)``, four pages a step
# through their index maps.

def _online_softmax_step(s, rows, m_ref, l_ref, acc_ref, at=slice(None),
                         kept=None):
    """One block of the running softmax: ``s`` [Q, T] masked scores (f32),
    ``rows`` [T, W] the block's values (a latent block's cache rows are
    both); ``at``: the state's rows these queries own. ``kept`` (the decode
    walk's): a flag a page of ``rows``, then a buffer [n, T / n, W] read a
    page at a time; a page whose flag is False holds ANYTHING (NaN too, and
    ``0 x NaN`` is NaN), so its product is dropped whole."""
    m_prev = m_ref[at]                               # [Q, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                           # [Q, T]
    alpha = jnp.exp(m_prev - m_new)
    l_ref[at] = alpha * l_ref[at] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_ref[at] * alpha

    def weighted(p, rows):
        # Probabilities go to the MXU in the pool's type, as in the XLA form.
        return jax.lax.dot_general(
            p.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # [Q, W]

    if kept is None:
        attended = weighted(p, rows)
    else:
        t = rows.shape[1]
        attended = functools.reduce(jnp.add, [
            jnp.where(k, weighted(p[:, i * t:(i + 1) * t], rows[i]), 0)
            for i, k in enumerate(kept)])
    acc_ref[at] = acc + attended
    m_ref[at] = m_new


def _softmax_init(m_ref, l_ref, acc_ref):
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _softmax_result(l_ref, acc_ref, dtype):
    l = l_ref[:]
    safe = jnp.where(l == 0.0, 1.0, l)               # dead rows emit zeros
    return (acc_ref[:] / safe).astype(dtype)


def _rows_decode_kernel(table_ref, len_ref, q_ref, *rest, page_size: int,
                        sm_scale: float, num_planes: int,
                        selecting: bool = False):
    """One table row of decode attention over planes of whole-token rows
    ``[P, page, W]``: ONE plane whose rows are keys and values both (a
    latent pool), or a plane of K rows and a plane of V rows (packed
    heads). The row's ``[H, W]`` queries against a turn's ``n * page`` keys
    in one product; a page's values in a product of their own, whose result
    a page never copied does not reach (its buffer holds ANYTHING, and
    ``0 x NaN`` is NaN). ``selecting``: a second mask beside the causal
    one, ``sel_ref`` ``[1, pages, page]`` (not 0: the key is attended to),
    a row of it a page."""
    sel_ref, rest = (rest[0], rest[1:]) if selecting else (None, rest)
    pools, o_ref = rest[:num_planes], rest[num_planes]
    bufs = rest[num_planes + 1:2 * num_planes + 1]
    sem, side_ref, m_ref, l_ref, acc_ref = rest[2 * num_planes + 1:]
    k_buf, v_buf = bufs[0], bufs[-1]
    b = pl.program_id(0)
    pg = page_size
    n, w = k_buf.shape[1], k_buf.shape[3]
    mpp = table_ref.shape[1]
    length = len_ref[b]                 # position being decoded (inclusive)

    def span(r):
        """Row ``r``'s live pages: up to the page of its length (a length
        below 0: none)."""
        return 0, jnp.clip(jax.lax.div(len_ref[r] + pg, pg), 0, mpp)

    _softmax_init(m_ref, l_ref, acc_ref)

    def attend(half, j0, mapped):
        s = jax.lax.dot_general(
            q_ref[0], k_buf[half].reshape(n * pg, w),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [H, n*pg]
        seen = _turn_seen((1, n * pg), pg, j0, mapped, length)
        if selecting:
            picked = jnp.concatenate(
                [sel_ref[0, pl.ds(j0 + i, 1), :] for i in range(n)], axis=1)
            seen = jnp.logical_and(seen, picked != 0)
        s = jnp.where(seen, s * sm_scale, NEG_INF)
        _online_softmax_step(s, v_buf.at[half], m_ref, l_ref, acc_ref,
                             kept=mapped)

    _walk_live_pages(table_ref, span, list(zip(pools, bufs)), sem, side_ref,
                     attend)
    # A row that attended to nothing (a dead slot: every id -1) keeps l ==
    # 0 and emits zeros; the host discards them anyway.
    o_ref[0] = _softmax_result(l_ref, acc_ref, o_ref.dtype)


# Traced ONCE for each set of shapes and inlined wherever it is called: the
# programs of a decode ladder (serve/pacing.py) and a stack's layers attend
# through the same call.
@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret", "name"),
                   inline=True)
def _rows_decode_call(q, planes, table, lengths, selected=None, *,
                      sm_scale: float, interpret: bool, name: str):
    b, h, w = q.shape
    page = planes[0].shape[1]
    n = _pages_a_turn(page * w * planes[0].dtype.itemsize, table.shape[1],
                      len(planes))
    kernel = functools.partial(
        _rows_decode_kernel, page_size=page, sm_scale=sm_scale,
        num_planes=len(planes), **(
            {} if selected is None else {"selecting": True}))

    def row(bi, *_):
        return (bi, 0, 0)

    picked = ()
    if selected is not None:
        # a row a page, and a turn of pages past the table (the last turn's
        # loads stay inside the block)
        picked = (jnp.pad(selected.astype(jnp.int32),
                          ((0, 0), (0, n), (0, 0))),)

    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, w), row)]
            + [pl.BlockSpec((1, *a.shape[1:]), row) for a in picked]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(planes),
            out_specs=pl.BlockSpec((1, h, w), row),
            scratch_shapes=[
                pltpu.VMEM((2, n, page, w), plane.dtype)
                for plane in planes] + [
                pltpu.SemaphoreType.DMA((2,)),      # one a half
                pltpu.SMEM((1,), jnp.int32),        # the half a row starts in
                pltpu.VMEM((h, 1), jnp.float32),    # running max m
                pltpu.VMEM((h, 1), jnp.float32),    # running denom l
                pltpu.VMEM((h, w), jnp.float32),    # row accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, w), q.dtype),
        # rows in order: a row's last turn starts the next row's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(table, lengths, q, *picked, *planes)


def paged_latent_decode_attention(
    q: jax.Array,                 # [B, H, W]: layers.latent_query
    pool: jax.Array,              # [P, page, W]
    table: jax.Array,             # [B, mpp] int32 page ids (-1 = unmapped)
    lengths: jax.Array,           # [B] position being decoded (attend <=)
    *,
    sm_scale: float,
    selected: Optional[jax.Array] = None,   # [B, mpp, page], not 0: attend
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Absorbed decode attention over a latent page pool; returns the
    attended row [B, H, W] (the caller expands it: layers.latent_output).
    Every page a context holds is read once, for all heads, and no other.
    ``selected``: the keys an indexer chose for each row's query
    (``paged_select_keys``), a second mask beside ``<= lengths``, a row of
    it a page."""
    return _rows_decode_call(
        q, (pool,), table, lengths, selected, sm_scale=sm_scale,
        interpret=interpret if interpret is not None else auto_interpret(),
        name="paged_latent_decode_attention")


# -- packed K/V rows (heads narrower than the lanes) -----------------------------
#
# Where a head is narrower than the 128-value lanes (64), the pool holds all
# of a token's KV heads side by side in ONE row a plane, ``[P, page, KV*D]``
# (serve/paged.py::pool_planes). Attention over it is the latent decode
# kernel (``_rows_decode_kernel``) with the values in a plane of their own:
# every query head is laid out like a row, its D values in its KV head's
# place and zeros elsewhere, so a head's score is one product with the K row
# (the zeros add nothing), and the attended V row holds the head's output in
# that same place.

def paged_packed_decode_attention(
    q: jax.Array,                 # [B, 1, H, D] — one decode token per slot
    pool_k: jax.Array,            # [P, page, KV*D]
    pool_v: jax.Array,            # [P, page, KV*D]
    table: jax.Array,             # [B, mpp] int32 page ids (-1 = unmapped)
    lengths: jax.Array,           # [B] position being decoded (attend <=)
    num_kv_heads: int,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Exact decode attention over packed K/V rows; returns [B, 1, H, D].
    Every page of each plane a context holds is read once, for all heads."""
    b, _, h, d = q.shape
    w = pool_k.shape[2]
    kv, g = num_kv_heads, h // num_kv_heads
    place = jnp.eye(kv, dtype=q.dtype)[None, :, None, :, None]
    rows = (q.reshape(b, kv, g, 1, d) * place).reshape(b, h, w)
    out = _rows_decode_call(
        rows, (pool_k, pool_v), table, lengths, sm_scale=d ** -0.5,
        interpret=interpret if interpret is not None else auto_interpret(),
        name="paged_packed_decode_attention")
    # A head's output lies in its KV head's place of the attended row.
    own = out.reshape(b, kv, g, kv, d) * place
    return own.sum(axis=3).reshape(b, 1, h, d)


# Pages a grid step of the chunk kernel attends to at once: 4 pages of 128
# are a 512-row block, which keeps the MXU's operands large; each is its own
# operand (the same pool, another index map), DMA'd from where it lies.
CHUNK_PAGES_PER_STEP = 4


def _latent_chunk_kernel(table_ref, start_ref, q_ref, *rest, page_size: int,
                         sm_scale: float, num_blocks: int,
                         selecting: bool = False):
    n = CHUNK_PAGES_PER_STEP
    sel_ref, rest = (rest[0], rest[1:]) if selecting else (None, rest)
    page_refs, (o_ref, rows_ref, m_ref, l_ref, acc_ref) = rest[:n], rest[n:]
    j = pl.program_id(1)
    c = q_ref.shape[1]
    block = n * page_size
    pl.when(j == 0)(lambda: _softmax_init(m_ref, l_ref, acc_ref))
    start = start_ref[0]                # position of the chunk's first query

    # Causal skip: the whole block lies behind every query of the chunk.
    @pl.when(j * block <= start + c - 1)
    def _compute():
        for i, ref in enumerate(page_refs):
            rows_ref[i * page_size:(i + 1) * page_size, :] = ref[0]
        rows = rows_ref[:]                           # [block, W]
        s = jax.lax.dot_general(
            q_ref[0], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [C, block]
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (c, block), 0)
        kv_pos = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (c, block), 1)
        seen = kv_pos <= q_pos
        if selecting:
            # the second mask's [C, block] of this step, laid page-major
            # [tiles, n, tile, page]: a tile's pages side by side, the
            # tiles one under the other
            picked = jnp.concatenate([jnp.concatenate(
                [sel_ref[t, i] for i in range(n)], axis=1)
                for t in range(sel_ref.shape[0])], axis=0)
            seen = jnp.logical_and(seen, picked != 0)
        s = jnp.where(seen, s * sm_scale, NEG_INF)
        _online_softmax_step(s, rows, m_ref, l_ref, acc_ref)

    @pl.when(j == num_blocks - 1)
    def _finalize():
        o_ref[0] = _softmax_result(l_ref, acc_ref, o_ref.dtype)


def paged_latent_chunk_attention(
    q: jax.Array,                 # [H, C, W]: one slot's chunk, head-major
    pool: jax.Array,              # [P, page, W]
    table_row: jax.Array,         # [n] int32: the slot's pages in order
    start: jax.Array,             # scalar int32: position of query 0
    *,
    sm_scale: float,
    selected: Optional[jax.Array] = None,   # [tiles, pages, C / tiles, page]
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal absorbed attention of a chunk of ``C`` queries (positions
    ``start ..``) over ONE slot's latent pages, the chunk's own rows among
    them (the caller writes them first); returns the attended rows
    [H, C, W]. Pages behind the chunk's last query are skipped, so the cost
    follows the context and not the table's length; a query attends to
    positions <= its own, which are all mapped and written. ``selected``:
    the keys an indexer chose for each query, a second mask beside the
    causal one (not 0: attend), page-major as ``paged_select_keys`` leaves it
    for the chunk's tiles of queries; a grid step takes its pages' part."""
    h, c, w = q.shape
    page = pool.shape[1]
    n = CHUNK_PAGES_PER_STEP
    num_blocks = -(-table_row.shape[0] // n)
    table = jnp.pad(table_row, (0, num_blocks * n - table_row.shape[0]),
                    constant_values=-1)
    kernel = functools.partial(
        _latent_chunk_kernel, page_size=page, sm_scale=sm_scale,
        num_blocks=num_blocks, selecting=selected is not None)
    picked, picked_specs = (), []
    if selected is not None:
        tiles, pages, tile, _ = selected.shape
        picked = (jnp.pad(selected, (
            (0, 0), (0, num_blocks * n - pages), (0, 0), (0, 0))),)
        picked_specs = [pl.BlockSpec(
            (tiles, n, tile, page),
            lambda hi, ji, table_ref, start_ref: (0, ji, 0, 0))]

    def q_map(hi, ji, table_ref, start_ref):
        return (hi, 0, 0)

    def page_map(i):
        return lambda hi, ji, table_ref, start_ref: (
            jnp.maximum(table_ref[ji * n + i], 0), 0, 0)

    return pl.pallas_call(
        kernel,
        name="paged_latent_chunk_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, num_blocks),
            in_specs=[pl.BlockSpec((1, c, w), q_map)] + picked_specs
            + [pl.BlockSpec((1, page, w), page_map(i)) for i in range(n)],
            out_specs=pl.BlockSpec((1, c, w), q_map),
            scratch_shapes=[
                pltpu.VMEM((n * page, w), pool.dtype),  # the block's rows
                pltpu.VMEM((c, 1), jnp.float32),        # running max m
                pltpu.VMEM((c, 1), jnp.float32),        # running denom l
                pltpu.VMEM((c, w), jnp.float32),        # row accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, c, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret if interpret is not None else auto_interpret(),
    )(table, jnp.reshape(start, (1,)).astype(jnp.int32), q, *picked,
      *([pool] * n))


# -- the indexer's scores over a row's live pages ---------------------------------
#
# ``I(t, s) = sum_j w[t, j] ReLU(q[t, j] . key[s])`` for the queries of a row
# against every key its context holds, the keys read from the ``idx`` plane
# where they lie (256 bytes a token at the published width) by the decode
# kernels' walk (``_walk_live_pages``: a grid step a row of the table, its
# live pages and no other, a turn's copies in flight behind the turn
# before). A chunk's queries go a tile of ``INDEX_QUERY_TILE`` a grid step,
# each tile a row of the walk whose context ends at its last query: all
# heads of the tile head-major ``[Hi x tile, Di]`` against a page's keys in
# one product on the matrix unit, ReLU, the heads' weights, the sum over
# heads. The result leaves page-major, ``[pages, tile, page]``, so that a
# page's scores are stored at an index of an untiled axis.

INDEX_QUERY_TILE = 128
# Fast memory the call may take: a tile's result over a whole table row
# (98 pages x 128 x 128 float32: 6.4 MB, twice), its queries and weights.
INDEX_VMEM_BYTES = 48 * 2 ** 20


def _index_scores_kernel(table_ref, pos_ref, q_ref, w_ref, pool_ref, o_ref,
                         buf, sem, side_ref, *, page_size: int, heads: int,
                         tile: int):
    b = pl.program_id(0)
    pg = page_size
    n, mpp = buf.shape[1], table_ref.shape[1]
    first = pos_ref[b]              # position of the row's first query

    def span(r):
        """Row ``r``'s live pages: up to the page of its last query."""
        return 0, jnp.clip(jax.lax.div(pos_ref[r] + tile - 1 + pg, pg),
                           0, mpp)

    o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, o_ref.dtype)

    def scores(keys):
        """[Hi x tile, Di] queries against ``keys`` [K, Di] -> [tile, K]."""
        dots = jax.lax.dot_general(
            q_ref[0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [Hi*tile, K]
        weighed = jnp.maximum(dots, 0.0) * w_ref[0]
        if tile == 1:
            return jnp.sum(weighed, axis=0, keepdims=True)
        return jnp.sum(weighed.reshape(heads, tile, keys.shape[0]), axis=0)

    def store(page, total):
        """A page's [tile, pg] scores, ``-inf`` behind each query."""
        kv_pos = page * pg + jax.lax.broadcasted_iota(
            jnp.int32, (tile, pg), 1)
        q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (tile, pg), 0)
        o_ref[0, page] = jnp.where(kv_pos <= q_pos, total, -jnp.inf)

    def attend(half, j0, mapped):
        if tile == 1:
            # One query a row: a turn's pages in ONE product (a product a
            # page would be thirty-two rows against 128 keys each time).
            total = scores(buf[half].reshape(n * pg, buf.shape[3]))
            for i, m in enumerate(mapped):
                pl.when(m)(functools.partial(
                    store, j0 + i, total[:, i * pg:(i + 1) * pg]))
            return
        for i, m in enumerate(mapped):
            pl.when(m)(lambda i=i: store(j0 + i, scores(buf[half, i])))

    _walk_live_pages(table_ref, span, [(pool_ref, buf)], sem, side_ref,
                     attend)


def paged_index_scores(
    q: jax.Array,                 # [B, T, Hi, Di]: layers.index_qkw
    w: jax.Array,                 # [B, T, Hi] float32
    pool: jax.Array,              # [P, page, Di]: the pool's ``idx`` plane
    table: jax.Array,             # [B, mpp] int32 page ids (-1 = unmapped)
    start: jax.Array,             # [B] position of each row's first query
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The indexer's scores of ``T`` queries a row (positions ``start[b]
    ..``) against the keys of the row's pages, where they lie: float32,
    ``-inf`` where a key lies behind its query, on a page the row's context
    does not hold or on an unmapped one (what ``layers.index_scores`` gives
    over the gathered pages). PAGE-MAJOR, as the kernel leaves them and
    ``paged_select_keys`` takes them: ``[B x tiles, mpp, tile, page]``, a
    tile of ``min(T, INDEX_QUERY_TILE)`` queries a row of the walk."""
    b, t, hi, di = q.shape
    page, mpp = pool.shape[1], table.shape[1]
    tile = min(t, INDEX_QUERY_TILE)
    if t % tile:
        raise ValueError(f"{t} queries a row are no whole tiles of {tile}")
    tiles = t // tile
    # a tile a row of the walk: [B x tiles, Hi x tile, Di], head-major
    rows = jnp.swapaxes(q.reshape(b, tiles, tile, hi, di), 2, 3).reshape(
        b * tiles, hi * tile, di)
    weights = jnp.swapaxes(
        w.astype(jnp.float32).reshape(b, tiles, tile, hi), 2, 3).reshape(
            b * tiles, hi * tile, 1)
    first = (start[:, None].astype(jnp.int32) + tile * jnp.arange(
        tiles, dtype=jnp.int32)[None, :]).reshape(-1)
    return _index_scores_call(
        rows, weights, pool, jnp.repeat(table, tiles, axis=0), first,
        heads=hi,
        interpret=interpret if interpret is not None else auto_interpret())


@functools.partial(jax.jit, static_argnames=("heads", "interpret"),
                   inline=True)
def _index_scores_call(q, w, pool, table, first, *, heads: int,
                         interpret: bool):
    r, rows, di = q.shape
    tile = rows // heads
    page, mpp = pool.shape[1], table.shape[1]
    n = _pages_a_turn(page * di * pool.dtype.itemsize, mpp, 1)
    kernel = functools.partial(_index_scores_kernel, page_size=page,
                               heads=heads, tile=tile)

    def row(ri, *_):
        return (ri, 0, 0)

    return pl.pallas_call(
        kernel,
        name="paged_index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r,),
            in_specs=[pl.BlockSpec((1, rows, di), row),
                      pl.BlockSpec((1, rows, 1), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, mpp, tile, page),
                                   lambda ri, *_: (ri, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, n, page, di), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),      # one a half
                pltpu.SMEM((1,), jnp.int32),        # the half a row starts in
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((r, mpp, tile, page), jnp.float32),
        # rows in order: a row's last turn starts the next row's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=INDEX_VMEM_BYTES),
        interpret=interpret,
    )(table, first, q, w, pool)


# -- the selection: each query's ``k`` best-scored keys, exactly ------------------
#
# ``layers.select_keys`` in fast memory, over scores laid page-major as
# ``paged_index_scores`` leaves them (``[R, pages, tile, page]``: a page's
# ``[tile, page]`` block at an index of an untiled axis): a grid step a tile
# of queries, whose scores' bit patterns are folded once into integers that
# order as the numbers do and then COUNTED against a threshold built bit by
# bit from the top (32 passes over the tile's live pages: the largest value
# that at least ``k`` scores reach), and the ties AT the threshold by
# position the same way (the first ``k - (scores above)`` of them: as many
# passes as a position has bits). No sort,
# and nothing ``[tile, S]`` leaves fast memory but the mask.

SELECT_VMEM_BYTES = 48 * 2 ** 20


def _select_kernel(live_ref, s_ref, o_ref, f_ref, *, k: int):
    mpp, tile, pg = f_ref.shape
    live = live_ref[pl.program_id(0)]       # pages some query of the tile sees
    lowest = jnp.iinfo(jnp.int32).min

    def each(body, init=None):
        return jax.lax.fori_loop(0, live, body, init)

    def fold(p, _):
        x = s_ref[0, p]
        bits = pltpu.bitcast(jnp.where(x == 0, 0.0, x), jnp.int32)
        f_ref[p] = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))

    each(fold)

    def count(hit):
        """[tile, 1]: how many of a query's live scores ``hit`` (a page's
        folded scores and the page's index -> bool) takes."""
        acc = each(lambda p, acc: acc + hit(f_ref[p], p).astype(jnp.int32),
                   jnp.zeros((tile, pg), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    thr = jnp.where(count(lambda f, p: f >= 0) >= k, 0, lowest)

    def bit(i, thr):
        cand = thr + jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda f, p: f >= cand) >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 31, bit, thr)
    room = k - count(lambda f, p: f > thr)              # >= 1

    def position(p):
        return p * pg + jax.lax.broadcasted_iota(jnp.int32, (tile, pg), 1)

    # the ties kept lie below ``ahead + 1``: ``ahead`` the largest position
    # with fewer than ``room`` ties in front of it, bit by bit over the
    # bits a position of this table can have
    bits = (mpp * pg).bit_length()

    def place(i, ahead):
        cand = ahead + jnp.left_shift(jnp.int32(1), bits - 1 - i)
        ties = count(lambda f, p: jnp.logical_and(f == thr,
                                                  position(p) < cand))
        return jnp.where(ties < room, cand, ahead)

    ahead = jax.lax.fori_loop(0, bits, place,
                              jnp.zeros((tile, 1), jnp.int32))

    o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    def keep(p, _):
        f = f_ref[p]
        kept = jnp.logical_or(f > thr, jnp.logical_and(
            f == thr, position(p) <= ahead))
        o_ref[0, p] = jnp.logical_and(
            kept, s_ref[0, p] > -jnp.inf).astype(o_ref.dtype)

    each(keep)


@functools.partial(jax.jit, static_argnames=("k", "interpret"), inline=True)
def _select_call(scores, live, *, k: int, interpret: bool):
    r, mpp, tile, page = scores.shape

    def row(ri, *_):
        return (ri, 0, 0, 0)

    return pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        name="dsa_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r,),
            in_specs=[pl.BlockSpec((1, mpp, tile, page), row)],
            out_specs=pl.BlockSpec((1, mpp, tile, page), row),
            scratch_shapes=[pltpu.VMEM((mpp, tile, page), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SELECT_VMEM_BYTES),
        interpret=interpret,
    )(live, scores)


def paged_select_keys(
    scores: jax.Array,            # [R, pages, tile, page] float32
    last: jax.Array,              # [R] position of each tile's last query
    k: int,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``layers.select_keys`` over page-major scores
    (``paged_index_scores``'s): for each query the mask of
    its ``k`` largest visible scores, a tie to the lower position, every
    visible key where there are at most ``k``; int32 ``[R, pages, tile,
    page]``, not 0 = attend. ``last`` bounds the pages a tile's queries can
    see; the pages behind them are not read and come back 0."""
    r, mpp, tile, page = scores.shape
    live = jnp.clip(last.astype(jnp.int32) // page + 1, 0, mpp)
    return _select_call(
        scores, live, k=int(k),
        interpret=interpret if interpret is not None else auto_interpret())



# -- a chunk of queries over per-head pools --------------------------------------
#
# The chunk kernel's schedule again (pages DMA'd from where they lie through
# the table row, ``CHUNK_PAGES_PER_STEP`` of each plane a grid step, pages
# behind the queries skipped, blockwise softmax in float32), over K and V
# planes ``[P, page, KV, D]``. A page is one block with all its KV heads, and
# a grid step attends EVERY head of a tile of queries to it, so a page is
# read once a tile: a group's query heads lie head-major ``[g x tile, D]``
# against their KV head's ``[block, D]`` keys (no repeat of K or V), and
# nothing ``[H, C, context]`` ever leaves fast memory.

# Queries a grid step takes, all heads of them, halved while heads x tile is
# over CHUNK_STATE_ROWS: 128 x 32 heads keep the float32 accumulator, the
# running max and the denominator at 2 MiB each (a tile of 256 measured a
# third slower on a v5e: PERF.md, PR 36).
CHUNK_QUERY_TILE = 128
CHUNK_STATE_ROWS = 4096
# What a grid step then keeps in fast memory: those 6 MiB, the queries' and
# the output's blocks twice (4), eight page blocks twice (4), a head's scores
# and probabilities (3): over the compiler's default of 16 MiB, a quarter of
# a v5e's 128.
CHUNK_VMEM_BYTES = 32 * 2 ** 20


def chunk_attention_supported(num_kv_heads: int, head_dim: int,
                              dtype) -> bool:
    """Whether ``paged_chunk_attention`` takes K/V planes ``[P, page, KV,
    D]`` of this type: a head's rows are parted from a page's by a strided
    load, which the chip has for 128-value rows of 32 bits; bfloat16 rides
    it two heads a word (``_word_heads``), so its heads must pair."""
    dtype = jnp.dtype(dtype)
    return head_dim == 128 and (
        dtype == jnp.float32
        or (dtype == jnp.bfloat16 and num_kv_heads % 2 == 0))


def _page_words(page_ref, per: int):
    """A page ``[page, KV, D]`` in fast memory as rows of 32-bit words
    ``[page * KV / per, D]`` (``per`` heads a word: 1 for float32, 2 for
    two-byte rows): every (KV / per)-th row from ``w`` on is word ``w`` of
    the page's tokens in order. A plane KEPT AS ROWS, ``[P, page * KV, D]``
    (``kv_heads`` of the calls below: a head count such as 10, which a
    ``[page, KV, D]`` page pads to a whole tile of 16 in device memory, whose
    copy engine then takes no page of it), comes as those rows already."""
    if len(page_ref.shape) == 2:        # a plane kept as rows (below)
        rows = page_ref
    else:
        page, kv, d = page_ref.shape
        rows = page_ref.reshape(page * kv, d)
    return rows if per == 1 else rows.bitcast(jnp.uint32)


def _word_heads(words, dtype) -> list:
    """The heads a page's strided rows hold, [page, D] each in ``dtype``: a
    float32 row is its head; a 32-bit word of two-byte rows holds heads 2i
    (its low half) and 2i+1 (its high half), and a half moved to the upper
    bits of a float32 IS that bfloat16 value."""
    if words.dtype != jnp.uint32:
        return [words]
    return [pltpu.bitcast(half, jnp.float32).astype(dtype)
            for half in (words << 16, words & jnp.uint32(0xFFFF0000))]


def _chunk_kernel(table_ref, start_ref, q_ref, *rest, page_size: int,
                  sm_scale: float, window: int = 0, kv_heads: int = 0):
    n = CHUNK_PAGES_PER_STEP
    k_refs, v_refs = rest[:n], rest[n:2 * n]
    o_ref, k_rows, v_rows, m_ref, l_ref, acc_ref = rest[2 * n:]
    j = pl.program_id(1)
    h, tile, d = q_ref.shape
    kv = kv_heads or k_refs[0].shape[2]
    per = k_rows.shape[0]               # heads a 32-bit word of a row holds
    g = h // kv
    block = n * page_size
    pl.when(j == 0)(lambda: _softmax_init(m_ref, l_ref, acc_ref))
    first = start_ref[0] + pl.program_id(0) * tile   # the tile's first query

    planes = [(k_rows, [_page_words(r.at[0], per) for r in k_refs]),
              (v_rows, [_page_words(r.at[0], per) for r in v_refs])]

    def attend(masked: bool):
        if masked:
            q_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (tile, block), 0)
            kv_pos = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (tile, block), 1)
            allowed = kv_pos <= q_pos
            if window:
                allowed = jnp.logical_and(allowed, kv_pos > q_pos - window)
            allowed = allowed[None]

        def one_word(w, _):
            for rows_ref, pages in planes:
                for i, page in enumerate(pages):
                    at = slice(i * page_size, (i + 1) * page_size)
                    words = page[pl.ds(w, page_size, stride=kv // per), :]
                    for half, rows in enumerate(_word_heads(
                            words, rows_ref.dtype)):
                        rows_ref[half, at, :] = rows
            for half in range(per):
                head = w * per + half
                q = q_ref[pl.ds(head * g, g)].reshape(g * tile, d)
                s = jax.lax.dot_general(
                    q, k_rows[half], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * sm_scale
                if masked:                           # [g * tile, block]
                    s = jnp.where(allowed, s.reshape(g, tile, block),
                                  NEG_INF).reshape(g * tile, block)
                own = pl.ds(pl.multiple_of(head * g * tile, g * tile),
                            g * tile)
                _online_softmax_step(s, v_rows[half], m_ref, l_ref, acc_ref,
                                     own)

        # One trip a word of heads, not KV unrolled copies of it: a kernel's
        # body is traced and lowered again at every call site of every
        # program (unrolled, 2.5 s a site on the chip's host: PERF.md, PR 36).
        jax.lax.fori_loop(0, kv // per, one_word, None)

    # A block is attended if a query of the tile sees it and the row has it
    # (a dead row's table is unmapped: it attends to nothing and emits
    # zeros); only a block that reaches past the tile's FIRST query pays for
    # the causal mask.
    seen = jnp.logical_and(j * block <= first + tile - 1,
                           table_ref[j * n] >= 0)
    if window:
        # A window layer's call: a block wholly behind the window of the
        # tile's first query is not attended, and every block that is pays
        # for the mask (a block is never wholly inside every query's
        # window at a window of a page).
        pl.when(jnp.logical_and(
            seen, (j + 1) * block - 1 > first - window))(
                lambda: attend(True))
    else:
        whole = (j + 1) * block - 1 <= first
        pl.when(jnp.logical_and(seen, whole))(lambda: attend(False))
        pl.when(jnp.logical_and(seen, jnp.logical_not(whole)))(
            lambda: attend(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # The state's rows lie KV head, then the group's heads, then the
        # tile's queries: query heads in order.
        o_ref[:] = _softmax_result(l_ref, acc_ref, o_ref.dtype).reshape(
            h, tile, d)


def paged_chunk_attention(
    q: jax.Array,                 # [H, C, D]: one slot's chunk, head-major
    pool_k: jax.Array,            # [P, page, KV, D]
    pool_v: jax.Array,            # [P, page, KV, D]
    table_row: jax.Array,         # [n] int32: the slot's pages in order
    start: jax.Array,             # scalar int32: position of query 0
    *,
    window: int = 0,
    kv_heads: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention of a chunk of ``C`` queries (positions ``start ..``)
    over ONE slot's K/V pages, the chunk's own rows among them (the caller
    writes them first); returns [H, C, D]. The contract of
    ``paged_latent_chunk_attention``: cost follows the context, a query
    attends to positions <= its own. Planes this takes:
    ``chunk_attention_supported``.

    ``window`` > 0 (static; a window layer's call, named
    ``paged_window_chunk_attention`` in a trace): query ``i`` sees keys ``i
    - window < j <= i``; blocks wholly behind a tile's window are neither
    fetched nor attended. Positions count from ``table_row``'s first page,
    so a caller hands in the pages the chunk and the window before it touch
    (serve/paged.py: a ring's) and no other is read. With no window the
    kernel is what it was. ``kv_heads`` > 0: the planes are kept as rows,
    ``[P, page * KV, D]`` (``paged_decode_attention``)."""
    h, d = q.shape[0], q.shape[2]
    kv = kv_heads or pool_k.shape[2]
    if not chunk_attention_supported(kv, d, pool_k.dtype) or h % kv:
        raise ValueError(
            f"paged_chunk_attention over {pool_k.dtype} planes of {kv} heads "
            f"x {pool_k.shape[3]}, {h} query heads x {d}")
    return _chunk_attention_call(
        q, pool_k, pool_v, table_row, jnp.asarray(start, jnp.int32),
        interpret=interpret if interpret is not None else auto_interpret(),
        # no keyword where no window is set: the call lowers under the name
        # it had (tests/test_chip_compile.py pins the older cells' programs)
        **({"window": window} if window else {}),
        **({"kv_heads": kv_heads} if kv_heads else {}))


# Traced ONCE for each set of shapes and inlined wherever it is called: every
# row of every chunk program of an engine attends through the same call.
@functools.partial(jax.jit,
                   static_argnames=("interpret", "window", "kv_heads"),
                   inline=True)
def _chunk_attention_call(q, pool_k, pool_v, table_row, start, *,
                          interpret: bool, window: int = 0,
                          kv_heads: int = 0):
    h, c, d = q.shape
    if kv_heads:
        page, kv = pool_k.shape[1] // kv_heads, kv_heads
    else:
        page, kv = pool_k.shape[1:3]
    per = 4 // pool_k.dtype.itemsize
    n = CHUNK_PAGES_PER_STEP
    tile = CHUNK_QUERY_TILE
    while h * tile > CHUNK_STATE_ROWS and tile > 16:
        tile //= 2
    if c % tile:
        tile = c
    block = n * page
    num_blocks = -(-table_row.shape[0] // n)
    table = jnp.pad(table_row, (0, num_blocks * n - table_row.shape[0]),
                    constant_values=-1)
    kernel = functools.partial(
        _chunk_kernel, page_size=page, sm_scale=d ** -0.5, window=window,
        **({"kv_heads": kv_heads} if kv_heads else {}))
    # a page's block: its heads' rows, or all its rows where kept as rows
    a_page = (1, page * kv, d) if kv_heads else (1, page, kv, d)

    def q_map(ti, ji, table_ref, start_ref):
        return (0, ti, 0)

    def page_map(i):
        def index(ti, ji, table_ref, start_ref):
            # A block behind the tile's last query is not attended: it stays
            # on the last block that is, which is not fetched again.
            ji = jnp.minimum(ji, (start_ref[0] + (ti + 1) * tile - 1) // block)
            if window:      # nor is one wholly behind the tile's window
                ji = jnp.maximum(ji, jnp.maximum(
                    start_ref[0] + ti * tile - window + 1, 0) // block)
            return (jnp.maximum(table_ref[ji * n + i], 0),) \
                + (0,) * (len(a_page) - 1)
        return index

    return pl.pallas_call(
        kernel,
        name=("paged_window_chunk_attention" if window
              else "paged_chunk_attention"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(c // tile, num_blocks),
            in_specs=[pl.BlockSpec((h, tile, d), q_map)]
            + [pl.BlockSpec(a_page, page_map(i))
               for _ in range(2) for i in range(n)],
            out_specs=pl.BlockSpec((h, tile, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((per, block, d), pool_k.dtype),  # a word's keys
                pltpu.VMEM((per, block, d), pool_v.dtype),  # and its values
                pltpu.VMEM((h * tile, 1), jnp.float32),     # running max m
                pltpu.VMEM((h * tile, 1), jnp.float32),     # running denom l
                pltpu.VMEM((h * tile, d), jnp.float32),     # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((h, c, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        interpret=interpret,
    )(table, jnp.reshape(start, (1,)), q, *([pool_k] * n), *([pool_v] * n))
