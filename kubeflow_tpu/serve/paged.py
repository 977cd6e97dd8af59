"""Paged KV cache: page pool + page tables + prefix caching — the TPU-native
analog of vLLM's PagedAttention memory manager ((U) kserve
python/huggingfaceserver vLLM backend; SURVEY.md §2.3#27 'continuous
batching, paged KV').

Why paging matters on v5e: a contiguous cache of one row a slot reserves
``slots × max_seq_len`` HBM whether or not requests use it; high-density
serving wants HBM proportional to *actual* tokens resident. Here KV lives in
a fixed pool of pages ``[L, P, page, KV, Dh]`` (``pool_planes``: K and V per
head, their scales when int8, or a latent model's one row a token); each
slot owns an ordered page list (its page table), and:

- **Allocation** is a host-side free list with O(1) alloc/free between
  device steps — the device never sees allocation, only page-id arrays.
- **Prefix caching**: pages holding FULL prompt prefixes are content-hashed
  (chained: page i's key folds page i-1's key), refcounted, and reused
  across requests — a shared system-prompt costs its KV once. Freed pages
  linger in the hash map (ref=0, LRU) until the pool needs them.
- **Preemption = recompute**: if the pool can't cover a running slot's next
  tokens even after evicting cached pages, the youngest slot releases its
  pages and its request requeues with prompt+generated so far (vLLM's
  recompute preemption).

Device side, the page table rides into the dispatch as a
``[B, max_pages_per_slot]`` int32 array; reads gather pages back into the
``[B, S, KV, Dh]`` layout XLA already tiles well, writes scatter
``(page, offset)`` with out-of-bounds drops for dead rows. No program that
meets the pool where it lies takes a layer's slab out of it: it carries
every plane whole, viewed flat ``[L*P, page, KV, Dh]``, through its loops
and its layer scan, writes layer ``l``'s rows in place at flat page ``l*P +
page`` and reads through the table offset by ``l*P``, so a program moves the
rows it writes and the pages it attends to, not the pool. Those programs
are ONE builder (``_pool_forward``) over ONE block (``_pool_block``) for
``B`` rows of ``T`` tokens: the decode step is its ``T = 1``
(``_paged_decode_step``), the chunk prefill wherever a kernel can attend a
chunk of queries over the pool its ``T = chunk``
(``_paged_chunk_in_place``), the speculative verify its ``T = k+1``
(serve/spec_decode.py ``paged_verify_step``: k+1 (page, offset) writes per
slot per round, in place like every other program's). Rejection rolls the
page table back to the accepted length (engine._truncate_slot_pages):
truncated pages return to the free list, so pool refcounts account for
exactly the tokens each slot kept. The second way a chunk reaches the pool
(``paged_chunk_prefill``'s gathered form, through ``decoder_forward``'s
cache path) stays for int8 pools, packed rows, LoRA and "gather".
Exactness: the "gather" attention impl runs the plain einsums over the
gathered pages (the engine's greedy tokens are pinned against a
full-recompute ``decoder_forward`` loop); the "pallas" impl
(ops/paged_attention.py) is mathematically exact blockwise softmax with
fp32 accumulation — numerically equal, not bitwise (its probabilities are
never rounded to bf16 before the PV product).
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import OrderedDict
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models import layers as L
from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.models.decoder import (
    LINEAR_PLANES, SSD_PLANES, SSM_PLANES, WINDOW_PLANES, Params, block_kind,
    layer_groups, period_units, plane_kind, split_dense_stack, unit_blocks,
)


# -- host-side page allocator --------------------------------------------------

class PagePoolExhausted(Exception):
    pass


@dataclasses.dataclass
class _CachedPage:
    page: int
    key: tuple


class PageAllocator:
    """Free-list page allocator with chained-hash prefix caching.

    Pages are ints in [0, num_pages). A page is in exactly one of:
    - allocated (ref > 0): owned by one or more slots;
    - cached (ref == 0, still hash-mapped): reusable prefix content, evicted
      LRU when the free list runs dry;
    - free: on the free list.
    """

    def __init__(self, num_pages: int, page_size: int,
                 enable_prefix_caching: bool = True, ring_pages: int = 0,
                 first_pages: int = 0):
        """``ring_pages`` (a stack with window, linear or ssm layers): the
        page ids below it exist in those layers' planes too and are handed
        out ONLY on request (``alloc(n, ring=r)``: a sequence's first pages,
        over which its window layers keep their ring, ``ring_table``, and at
        the first of which its linear and ssm layers keep its state,
        ``sequence_planes``); every other page comes from the ids above.
        ``first_pages`` (a stack with a ring of several pages AND a state a
        sequence): the ids below IT are handed out only as a sequence's very
        FIRST page (``alloc(.., first=True)``), so the state's planes hold
        an entry a slot and not one a ring page."""
        self.num_pages = num_pages
        self.page_size = page_size
        self.prefix_caching = enable_prefix_caching
        self.ring_pages = ring_pages
        self.first_pages = first_pages
        self._free: list[int] = list(range(num_pages - 1, ring_pages - 1, -1))
        self._free_ring: list[int] = list(
            range(ring_pages - 1, first_pages - 1, -1))
        self._free_first: list[int] = list(range(first_pages - 1, -1, -1))
        self._ref = np.zeros((num_pages,), np.int32)
        # content key -> page id (for reuse); page id -> key (for eviction)
        self._by_key: dict[tuple, int] = {}
        self._key_of: dict[int, tuple] = {}
        # ref==0 pages that still hold cached content, LRU order
        self._reclaimable: "OrderedDict[int, None]" = OrderedDict()
        # Radix-index integration (serve/kvtier.py): pages the index wants
        # kept reclaimable at ref==0 even without a flat-hash key, and the
        # callback the LRU eviction path fires so the index can drop the
        # node (and cascade its now-unreachable subtree) when the pool
        # reclaims one of them.
        self.retained: set[int] = set()
        self.on_evict = None
        self.stats = {"prefix_hits": 0, "prefix_queries": 0, "evictions": 0,
                      "stamped_allocs": 0}
        # KFTPU_SANITIZE=refcount (runtime/sanitize.py): stamp every
        # alloc/incref with owner + call site so assert_quiescent can say
        # WHO leaked, not just that someone did. One stamp per outstanding
        # reference, popped LIFO by free().
        from kubeflow_tpu.runtime.sanitize import enabled

        self.refcount_debug = enabled("refcount")
        self._stamps: dict[int, list[str]] = {}

    # -- refcount sanitizer ------------------------------------------------

    def _stamp(self, page: int, owner: Optional[str]) -> None:
        from kubeflow_tpu.runtime.sanitize import call_site

        label = owner if owner is not None else call_site((__file__,))
        self._stamps.setdefault(page, []).append(label)
        self.stats["stamped_allocs"] += 1

    def _unstamp(self, page: int) -> None:
        stamps = self._stamps.get(page)
        if stamps:
            stamps.pop()
            if not stamps:
                del self._stamps[page]

    def leak_report_by_owner(self) -> dict:
        """owner label -> number of page references it still holds
        (refcount mode only; {} when quiescent). The chaos suite's
        per-owner zero-leak assertion reads this."""
        out: dict[str, int] = {}
        for page in np.flatnonzero(self._ref > 0):
            for label in self._stamps.get(int(page), ()) or ["<unstamped>"]:
                out[label] = out.get(label, 0) + 1
        return out

    # -- raw pages ---------------------------------------------------------

    def available(self, ring: bool = False) -> int:
        if ring:    # a new sequence takes one first page for its others
            return len(self._free_ring) + len(self._free_first)
        return len(self._free) + len(self._reclaimable)

    def cached(self) -> int:
        """Pages holding reusable prefix content at ref==0 — freely
        evictable, so NOT load (the decode router's split gauge)."""
        return len(self._reclaimable)

    def ref(self, page: int) -> int:
        return int(self._ref[page])

    def reclaimable_lru(self) -> list[int]:
        """Ref-0 cached pages, least-recently-released first — the
        demotion scan's candidate order (serve/kvtier.py)."""
        return list(self._reclaimable)

    def drop_cached(self, pages: Sequence[int]) -> None:
        """Discard ref-0 cached pages outright (content no longer
        reachable — an evicted radix subtree, or pages whose bytes just
        migrated to the host tier): straight to the free list."""
        for p in pages:
            assert self._ref[p] == 0, f"drop_cached of referenced page {p}"
            key = self._key_of.pop(p, None)
            if key is not None:
                self._by_key.pop(key, None)
            self.retained.discard(p)
            if p in self._reclaimable:       # values are None: test by key
                del self._reclaimable[p]
                self._free.append(p)

    def in_use(self) -> int:
        """Pages currently referenced by at least one slot. The speculative
        rollback invariant (engine._truncate_slot_pages) is audited against
        this: after every request finishes, in_use() must return to 0 —
        rejected-draft pages were freed exactly once, accepted ones exactly
        once at slot release."""
        return int((self._ref > 0).sum())

    def leak_report(self) -> dict:
        """Pages still referenced and their refcounts ({} when quiescent) —
        the chaos suite's post-scenario audit payload."""
        held = np.flatnonzero(self._ref > 0)
        return {int(p): int(self._ref[p]) for p in held}

    def assert_quiescent(self) -> None:
        """Refcount-balance invariant for the chaos suite: once every
        request has completed or been reaped, every alloc/incref must have
        been balanced by exactly one free — no page may stay referenced.
        Under ``KFTPU_SANITIZE=refcount`` the failure names the owners
        whose stamps are still outstanding."""
        leaked = self.leak_report()
        if leaked:
            msg = (f"KV page leak: {len(leaked)} page(s) still referenced "
                   f"(page -> ref): {dict(list(leaked.items())[:16])}")
            if self.refcount_debug:
                by_owner = self.leak_report_by_owner()
                msg += ("; outstanding references by owner: "
                        + ", ".join(f"{o}={n}" for o, n in
                                    sorted(by_owner.items())))
            raise AssertionError(msg)

    def alloc(self, n: int, owner: Optional[str] = None,
              ring: int = 0, first: bool = False) -> list[int]:
        """n fresh pages (ref=1 each). Evicts cached pages LRU if needed.
        ``ring``: the first ``ring`` of them from the ids the window layers'
        planes hold too; ``first``: the first of those is a sequence's first
        page (from the ids kept for first pages, where there are any). All
        of them or none: nothing is taken where any kind runs short."""
        first = bool(first and ring and self.first_pages)
        if len(self._free_ring) < ring - first \
                or len(self._free_first) < first \
                or self.available() < n - ring:
            raise PagePoolExhausted(
                f"need {ring} ring + {n - ring}, have "
                f"{self.available(ring=True)} + {self.available()}")
        out = []
        for i in range(n):
            if i == 0 and first:
                p = self._free_first.pop()
            elif i < ring:
                p = self._free_ring.pop()
            elif self._free:
                p = self._free.pop()
            else:
                p, _ = self._reclaimable.popitem(last=False)   # LRU evict
                key = self._key_of.pop(p, None)
                if key is not None:
                    self._by_key.pop(key, None)
                if p in self.retained:
                    self.retained.discard(p)
                    if self.on_evict is not None:
                        # The radix index drops the node; its subtree's
                        # cached pages cascade to the free list via
                        # drop_cached, which this loop then consumes.
                        self.on_evict(p)
                self.stats["evictions"] += 1
            self._ref[p] = 1
            if self.refcount_debug:
                self._stamps.pop(p, None)   # fresh ownership history
                self._stamp(p, owner)
            out.append(p)
        return out

    def incref(self, pages: Sequence[int],
               owner: Optional[str] = None) -> None:
        for p in pages:
            if self._ref[p] == 0:
                self._reclaimable.pop(p, None)
            self._ref[p] += 1
            if self.refcount_debug:
                self._stamp(p, owner)

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference; ref-0 pages become reclaimable (cached) if
        hashed, else go straight to the free list."""
        for p in pages:
            self._ref[p] -= 1
            assert self._ref[p] >= 0, f"double free of page {p}"
            if self.refcount_debug:
                self._unstamp(p)
            if self._ref[p] == 0:
                if p < self.first_pages:
                    self._free_first.append(p)
                elif p < self.ring_pages:
                    self._free_ring.append(p)
                elif p in self._key_of or p in self.retained:
                    self._reclaimable[p] = None    # keep content, LRU
                else:
                    self._free.append(p)

    # -- prefix caching ----------------------------------------------------

    @staticmethod
    def chain_keys(tokens: Sequence[int], page_size: int,
                   namespace: str = "") -> list[tuple]:
        """Chained content keys for every FULL page of ``tokens``.
        ``namespace`` salts the chain root: KV content depends on the
        model VARIANT that computed it, so multi-tenant LoRA serving
        keys each adapter's pages apart (same prompt, different
        adapter → different KV → must never cross-match)."""
        keys, parent = [], (namespace,) if namespace else ()
        for i in range(len(tokens) // page_size):
            parent = (hash((parent, tuple(tokens[i * page_size:(i + 1) * page_size]))),)
            keys.append(parent)
        return keys

    def match_prefix(self, tokens: Sequence[int],
                     owner: Optional[str] = None,
                     namespace: str = "") -> list[int]:
        """Longest run of cached pages for ``tokens``' full-page prefix
        (capped so at least one prompt token remains to prefill — the first
        sampled token needs real last-token logits). Bumps refs on the hit
        pages; caller owns them."""
        if not self.prefix_caching:
            return []
        self.stats["prefix_queries"] += 1
        max_reuse = (len(tokens) - 1) // self.page_size
        hit: list[int] = []
        for key in self.chain_keys(tokens, self.page_size,
                                   namespace)[:max_reuse]:
            page = self._by_key.get(key)
            if page is None:
                break
            hit.append(page)
        if hit:
            self.incref(hit, owner=owner)
            self.stats["prefix_hits"] += 1
        return hit

    def register_prefix(self, tokens: Sequence[int],
                        pages: Sequence[int],
                        namespace: str = "") -> None:
        """Hash ``pages`` as holding ``tokens``' full-page prefixes (called
        after the KV is actually written)."""
        if not self.prefix_caching:
            return
        for key, page in zip(self.chain_keys(tokens, self.page_size,
                                             namespace), pages):
            old = self._by_key.get(key)
            if old is not None and old != page:
                continue     # first writer wins; duplicates just aren't hashed
            self._by_key[key] = page
            self._key_of[page] = key


# -- device-side paged steps ---------------------------------------------------
#
# Cache pytree: one [L,P,pg,*trailing] array per PLANE of the pool, as
# ``pool_planes`` describes them for the model, plus "table" [B, mpp] int32
# where mpp = max_seq_len // page. Table entries are page ids; -1 = unmapped
# (reads are length-masked, writes aimed out of bounds and dropped).


# What rides in a cache pytree beside the pool's planes: the page table, and
# where a layer holds a share of its experts the running sums of the rows
# its expert layers routed and held (int32 [2], wrapping: a reader works on
# differences; [3] where the router has zero experts: the rows that chose
# one, last).
MOE_ROWS = "moe_rows"
# What rides through a program's layer scans beside the planes where the
# stack has gated memory units: the scan output [B,T,E] float32 of the last
# ssm layer in front of them. A program's own: no cache pytree holds it.
SSM_MEMORY = "ssm_memory"
# a kind of layer whose state is one entry a SEQUENCE -> its planes
SEQUENCE_PLANES = {"linear": LINEAR_PLANES, "ssm": SSM_PLANES,
                   "ssd": SSD_PLANES}


def pool_planes(cfg: DecoderConfig, kv_quant: bool = False) -> tuple:
    """What one token holds in one layer of the page pool: (name, trailing
    shape, type) per plane. The ONE description the pool is built from and
    that the page copy, the decode write, the chunk's gather and scatter and
    the bytes accounting walk.

    Per-head K and V: "k"/"v" [KV, Dh] (int8 pools add the per-token
    per-head scale planes "ks"/"vs" [KV] f32). Latent attention: ONE plane
    "ckv" [W], the row of ``layers.latent_qkv``: the compressed latent
    after its norm, the rotary key values after RoPE (shared by all
    heads), zeros up to whole 128-value lanes (576 -> 640 at the published
    ranks). One padded row and not two planes of 512 and 64: the chip's
    compiler copies a 64-wide plane WHOLE, twice, around every decode
    step's row write (its tiled layout has no 64-value rows), and a page is
    then one aligned block and one DMA for the kernels (PERF.md, PR 28).
    Where an indexer selects the keys (``cfg.index_topk``), a SECOND plane
    "idx" [index_head_dim] under the same page ids: the indexer's key a
    token (``layers.index_qkw``). Two planes and not one wider row: the
    indexer reads its 256 bytes at EVERY position of a context, attention
    its 1280 at the selected ones."""
    dt = cfg.activation_dtype
    if cfg.is_latent:
        if kv_quant:
            raise ValueError("int8 KV over a latent (ckv) pool")
        idx = (("idx", (cfg.index_head_dim,), dt),) if cfg.index_topk else ()
        return (("ckv", (L.latent_row_width(cfg),), dt), *idx)
    kv = _kv_row(cfg)
    if cfg.kv_heads_packed:
        # Heads narrower than the 128-value lanes: all of a token's heads
        # side by side in ONE row, for the same reason as the latent row
        # (an [8, 64] plane compiles to twice its bytes and is copied whole
        # around each decode write: PERF.md, PR 35).
        if kv_quant:
            raise ValueError("int8 KV over packed K/V rows")
        kv = (cfg.n_kv_heads * cfg.head_dim,)
    if kv_quant:
        f32 = jnp.dtype(jnp.float32)
        return (("k", kv, jnp.dtype(jnp.int8)), ("v", kv, jnp.dtype(jnp.int8)),
                ("ks", kv[:1], f32), ("vs", kv[:1], f32))
    return (("k", kv, dt), ("v", kv, dt))


def _kv_row(cfg: DecoderConfig) -> tuple:
    """The trailing shape of a token's K (or V) in a per-head plane: [KV,
    Dh]; under differential attention the adjacent heads of a pair side by
    side, [KV / 2, 2 Dh] (``layers.diff_kv``: heads of 64 then fill the 128
    lanes, and the paged kernels see plain GQA)."""
    if cfg.diff_attention:
        return (cfg.n_kv_heads // 2, 2 * cfg.head_dim)
    return (cfg.n_kv_heads, cfg.head_dim)


def kept_as_rows(cfg: DecoderConfig) -> int:
    """Rows a token holds in a per-head plane that is KEPT AS ROWS, ``[L, P,
    page * rows, D]`` where every other plane is ``[L, P, page, KV, D]`` (0:
    the planes are pages of heads). Differential attention's are: its 10
    pairs of 128 values a token are no whole tile of the chip's memory
    (``[page, 10, 128]`` is padded to 16 rows a token, 60% more bytes to
    hold and to read, and the kernels' copy engine takes no page of a padded
    plane), while the same bytes in the same order as ``page * 10`` rows of
    128 are. The kernels read a page as rows anyway
    (``ops/paged_attention.py::_page_words``); what writes and gathers here
    goes through ``_token_rows``."""
    return _kv_row(cfg)[0] if cfg.diff_attention else 0


def _token_rows(off, rows: int):  # traced
    """Where in a page kept as rows the token at offset ``off`` ([..]) lies:
    [.., rows] row indices, its heads in order."""
    return off[..., None] * rows + jnp.arange(rows, dtype=jnp.int32)


def state_planes(cfg: DecoderConfig) -> tuple:
    """What one PAGE holds in one conv layer of the pool, beside the token
    planes of the attention layers: (name, trailing shape, type). "conv":
    the ``conv_taps - 1`` gated rows (``layers.conv_block``) as they stood
    after the last token written in that page, so a sequence's state is
    reached through its page table like its K and V: a decode step at
    position ``t`` reads the page of ``t - 1`` and writes the page of ``t``,
    a chunk reads at ``start - 1`` and writes the end of every page it
    fills and its last token's, and whole pages are shared, copied and
    preempted with the state they end in. () for a stack without conv
    layers."""
    if not cfg.layers_of("conv"):
        return ()
    return (("conv", (cfg.conv_taps - 1, cfg.hidden), cfg.activation_dtype),)


def window_planes(cfg: DecoderConfig, kv_quant: bool = False) -> tuple:
    """What one token holds in one WINDOW layer of the pool, in planes of
    that kind's own: ``decoder.WINDOW_PLANES`` [KV, Dh], a global layer's K
    and V under other names. Their pool has fewer pages than the global
    layers' (a sequence keeps a ring of ``ring_pages`` there:
    ``ring_table``). () for a stack without window layers."""
    if not cfg.layers_of("window"):
        return ()
    if kv_quant or cfg.is_latent or cfg.kv_heads_packed:
        raise ValueError("window layers over an int8, latent or packed pool")
    kv, dt = _kv_row(cfg), cfg.activation_dtype
    return tuple((n, kv, dt) for n in WINDOW_PLANES)


def sequence_planes(cfg: DecoderConfig) -> tuple:
    """What one SEQUENCE holds in one linear layer of the pool: (name,
    trailing shape, type). "kda_state": the recurrent matrix a head,
    float32; "kda_conv": the ``conv_taps - 1`` projected rows before the
    next token of each of q, k and v, side by side (``layers.kda_inputs``).
    A state of 4 MB a layer can be neither copied into every page (as the
    conv layers' tails are) nor found by slot (a program is handed a page
    table row and no slot), so an entry lies at the id of the sequence's
    FIRST page, ``table_row[0]``: the allocator hands first pages out from a
    range of ids of their own (``PageAllocator(ring_pages=...)``, ``own_first_pages``), as
    many as the planes have entries, and a row alone finds its state. A
    chunk that starts at 0 and a decode step at length 0 start from zeros
    whatever the entry holds, so an entry needs no clearing when its page
    changes hands. An ssm layer's entry is found the same way:
    "ssm_state", the recurrent state ``[ssm_state, ssm_inner]`` float32
    (``ops/ssm.py``: channels on the lanes), and "ssm_conv", the
    ``conv_taps - 1`` projected rows before the next token. An ssd layer's
    too, and a parallel layer's beside the K and V rows a token it keeps in
    the global planes: "ssd_state" ``[ssd_heads, ssd_state, ssd_head_dim]``
    float32 (``ops/ssd.py``: a head's values on the lanes; heads narrower
    than the 128 lanes side by side, ``[ssd_heads / r, ssd_state, r
    ssd_head_dim]``: ``ssd.pack_state``) and "ssd_conv", the ``conv_taps -
    1`` rows of ``[x | B | C]`` before the next token. () for a stack with
    none of these kinds."""
    from kubeflow_tpu.ops.ssd import heads_a_tile

    out = ()
    if cfg.layers_of("linear"):
        h, dk = cfg.linear_heads, cfg.linear_head_dim
        out += ((LINEAR_PLANES[0], (h, dk, dk), jnp.dtype(jnp.float32)),
                (LINEAR_PLANES[1], (L.kda_conv_rows(cfg), h * dk),
                 cfg.activation_dtype))
    if cfg.layers_of("ssm"):
        out += ((SSM_PLANES[0], (cfg.ssm_state, cfg.ssm_inner),
                 jnp.dtype(jnp.float32)),
                (SSM_PLANES[1], (cfg.conv_taps - 1, cfg.ssm_inner),
                 cfg.activation_dtype))
    if cfg.layers_holding("ssd"):
        r = heads_a_tile(cfg.ssd_heads, cfg.ssd_groups, cfg.ssd_head_dim)
        out += ((SSD_PLANES[0], (cfg.ssd_heads // r, cfg.ssd_state,
                                 r * cfg.ssd_head_dim),
                 jnp.dtype(jnp.float32)),
                (SSD_PLANES[1], (cfg.conv_taps - 1, cfg.ssd_conv_dim),
                 cfg.activation_dtype))
    return out


def own_first_pages(cfg: DecoderConfig) -> int:
    """How many of a sequence's first pages come from the range of ids kept
    for first pages: its ring where the stack has window layers
    (``window_ring_pages``), one where it has linear or ssm layers (the id
    is the sequence's entry in their planes), the larger where it has both;
    0 for any other stack."""
    return max(cfg.window_ring_pages, int(bool(sequence_planes(cfg))))


def ring_pages(cfg: DecoderConfig, chunk: int, page_size: int,
               mpp: int) -> int:
    """Pages a sequence keeps in a window layer: what a chunk of ``chunk``
    tokens and the window before it span, plus one for a chunk that starts
    inside a page (6 at a chunk of 512, a window and a page of 128), at most
    the table's length. 0 for a stack without window layers."""
    if not cfg.layers_of("window"):
        return 0
    behind = -(-(cfg.attn_window - 1) // page_size)
    return min(mpp, -(-chunk // page_size) + behind + 1)


def _ring_len(cfg: DecoderConfig, mpp: int) -> int:
    """A ring's pages under a table of ``mpp``: the whole row where the
    config sets none."""
    return min(cfg.window_ring_pages or mpp, mpp)


def _ring_slot(slot, cfg: DecoderConfig, mpp: int):  # traced
    """Where in its row's table a window layer keeps logical page ``slot``:
    ``slot mod R``, a ring over the sequence's own first ``R`` pages
    (``_ring_len``). THE ring arithmetic: ``ring_table`` (reads), and the
    write indices of both ways to the pool go through it."""
    return slot % _ring_len(cfg, mpp)


def ring_table(table: jax.Array, first: jax.Array, n: int,  # traced
               cfg: DecoderConfig) -> jax.Array:
    """Where a window layer keeps a sequence's logical pages ``first ..
    first + n``: ``table`` [B, mpp] rows, ``first`` [B] -> [B, n] page ids.
    A global layer keeps logical page ``i`` at ``row[i]``; a window layer
    keeps it at ``row[i mod R]``, a ring over the sequence's own first ``R``
    pages (``cfg.window_ring_pages``; the whole row where none is set), in
    which page ``i`` overwrites page ``i - R``, which no query still sees.
    Found from the row and a position alone: no slot, no second table."""
    at = _ring_slot(first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :],
                    cfg, table.shape[1])
    return jnp.take_along_axis(table, at, axis=1)


def pool_shapes(cfg: DecoderConfig, num_pages: int, page_size: int,
                kv_quant: bool = False,
                window_pages: Optional[int] = None,
                sequence_entries: Optional[int] = None) -> dict:
    """The pool an engine builds: {plane: (shape, type)}. A token plane is
    ``[layers of attention, P, page, ...]``, a state plane ``[layers of
    conv, P, ...]``, a window layer's ``[layers of window, H, page, ...]``
    with ``H`` = ``window_pages``, the ids a sequence's first pages come
    from (the whole pool's where not given), and a sequence plane ``[layers
    of linear or ssm, S, ...]`` with ``S`` = ``sequence_entries``, the ids a
    sequence's very first page comes from (``window_pages`` where not
    given): each over the layers of ITS kind (``decoder.plane_kind``), so a
    stack whose layers are all attention keeps ``[L, P, page, ...]``."""
    def page_of(trail):
        rows = kept_as_rows(cfg)
        return (page_size * rows, *trail[1:]) if rows \
            else (page_size, *trail)

    out = {n: ((cfg.layers_holding("attention"), num_pages,
                *page_of(trail)), dt)
           for n, trail, dt in pool_planes(cfg, kv_quant)
           if cfg.layers_holding("attention")}
    out.update({n: ((cfg.layers_of("conv"), num_pages, *trail), dt)
                for n, trail, dt in state_planes(cfg)})
    out.update({n: ((cfg.layers_of("window"),
                     num_pages if window_pages is None else window_pages,
                     *page_of(trail)), dt)
                for n, trail, dt in window_planes(cfg, kv_quant)})
    held = num_pages if window_pages is None else window_pages
    out.update({n: ((cfg.layers_holding(plane_kind(n)),
                     held if sequence_entries is None else sequence_entries,
                     *trail), dt) for n, trail, dt in sequence_planes(cfg)})
    return out


def engine_pool_shapes(cfg: DecoderConfig, slots: int, num_pages: int,
                       page_size: int, kv_quant: bool = False) -> dict:
    """The cache pytree of an engine of ``slots`` slots over ``cfg`` as its
    programs take it (``engine.serving_configs`` has set the ring):
    ``pool_shapes`` with a ring for every slot in the window layers' planes
    (``slots * own_first_pages`` ids, those a sequence's first pages come
    from) and an entry for every slot in the linear and ssm layers' (the ids
    a sequence's very first page comes from: ``first_page_ids``) and, where
    a layer holds a share of its experts, the rows' running sums."""
    if cfg.layers_of("window") and not cfg.window_ring_pages:
        raise ValueError("an engine's pool over window layers needs "
                         "cfg.window_ring_pages (paged.ring_pages)")
    out = pool_shapes(cfg, num_pages, page_size, kv_quant,
                      window_pages=min(num_pages,
                                       slots * own_first_pages(cfg)),
                      sequence_entries=min(num_pages, slots))
    if cfg.experts_held:
        out[MOE_ROWS] = ((2 + bool(cfg.zero_experts),),
                         jnp.dtype(jnp.int32))
    return out


def first_page_ids(cfg: DecoderConfig, slots: int) -> int:
    """The ids an engine of ``slots`` slots keeps for a sequence's very
    first page (``PageAllocator(first_pages=...)``): one a slot where a
    sequence keeps a ring of several pages AND a state; 0 where the ring's
    ids are first pages anyway (a ring of one) or nothing is found there."""
    return slots if sequence_planes(cfg) and own_first_pages(cfg) > 1 else 0


def _plane_bytes(planes: tuple) -> int:
    return sum(int(np.prod(trail)) * jnp.dtype(dt).itemsize
               for _, trail, dt in planes)


def pool_bytes_per_token(cfg: DecoderConfig, kv_quant: bool = False) -> int:
    """Bytes one token holds over all layers of the pool that keep rows a
    token: the attention layers (a latent row's padding included: 1280 a
    layer at the published ranks for 1152 of content). A conv layer holds
    none a token; what it holds a page is ``state_bytes_per_page``."""
    return cfg.layers_holding("attention") * _plane_bytes(
        pool_planes(cfg, kv_quant))


def state_bytes_per_page(cfg: DecoderConfig) -> int:
    """Bytes one page holds over all conv layers: their state's tail."""
    return cfg.layers_of("conv") * _plane_bytes(state_planes(cfg))


def state_bytes_per_sequence(cfg: DecoderConfig) -> int:
    """Bytes one sequence holds over all linear and ssm layers, whatever
    its length: the recurrent states and the convolutions' tails."""
    return sum(cfg.layers_holding(plane_kind(plane[0]))
               * _plane_bytes((plane,)) for plane in sequence_planes(cfg))


def window_bytes_per_page(cfg: DecoderConfig, page_size: int) -> int:
    """Bytes one ring page holds over all window layers."""
    return cfg.layers_of("window") * page_size * _plane_bytes(
        window_planes(cfg))


def _pool_geometry(cache: dict, cfg: Optional[DecoderConfig] = None) -> tuple:
    """(pages, page size) of a cache pytree: its first token plane's (a
    global layer's where the stack has one); ``cfg``: the stack's, where its
    planes may be kept as rows (``kept_as_rows``)."""
    names = sorted((n for n in _planes_of(cache)
                    if plane_kind(n) not in ("conv", *SEQUENCE_PLANES)),
                   key=lambda n: plane_kind(n) != "attention")
    pages, page = cache[names[0]].shape[1:3]
    rows = kept_as_rows(cfg) if cfg is not None else 0
    return pages, page // rows if rows else page


_NOT_PLANES = ("table", MOE_ROWS)


def _planes_of(cache: dict) -> tuple:
    """The names of a cache pytree's pool planes (everything but the page
    table and the expert rows' sums)."""
    return tuple(n for n in cache if n not in _NOT_PLANES)


def _pages_by_kind(cache: dict) -> dict:
    """Pages a layer holds in the planes of each kind."""
    return {plane_kind(n): cache[n].shape[1] for n in _planes_of(cache)}


def paged_gather(pool: jax.Array, table: jax.Array) -> jax.Array:  # traced
    """[P,pg,K,D] pool + [B,mpp] table -> [B, mpp*pg, K, D] per-slot view."""
    b, mpp = table.shape
    pages = pool[jnp.clip(table, 0, pool.shape[0] - 1)]   # [B,mpp,pg,K,D]
    return pages.reshape(b, mpp * pool.shape[1], *pool.shape[2:])


def _embed(params: Params, tokens: jax.Array, cfg: DecoderConfig):  # traced
    x = params["embed"].astype(cfg.activation_dtype)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden ** 0.5, cfg.activation_dtype)
    return L.scaled(x, cfg.embed_multiplier)


def _head_logits(params: Params, x: jax.Array, cfg: DecoderConfig,  # traced
                 normed: bool = False):
    """Final norm (unless ``x`` is ``normed`` already) and output head:
    [B,S,D] -> [B,S,V] float32."""
    if not normed:
        x = L.rmsnorm(x, params["final_norm"], cfg,
                      bias=params.get("final_norm_b"))
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", L.scaled(x, cfg.head_multiplier),
                        head.astype(cfg.activation_dtype),
                        preferred_element_type=jnp.float32)
    if cfg.logits_softcap is not None:
        logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
    return logits


def _feed_forward(bp, hs, valids, cfg: DecoderConfig,  # traced
                  expert_stack=None, *, pools: dict):
    """The block's feed-forward (or expert) layer over the tokens of every
    group of ``hs`` ([B,T,D] each; ``valids`` [B] each: a row's real tokens)
    TOGETHER, so a weight is read once for all of them: (a group's output
    each, what the block STARTED for each group or None, pools), the carried
    planes, where a share of the experts is held with the layer's routed
    and held rows added to ``pools[MOE_ROWS]``.

    One group is the layer as it always ran: an expert layer is told which
    tokens are real and takes its capacity a row at several tokens a row,
    and neither at one (a row is a token). Two groups are a chunk's rows and
    the decode rows that ride with them (``paged_mixed_step``): a plain MLP
    and the experts without a capacity take the tokens in a row, the decode
    rows behind the chunk's (``mixed_step_rows`` of them: sorted rows in
    whole tiles); a dispatch layer keeps the chunk rows' capacity a row and
    takes the decode tokens as a group that cannot drop
    (``layers._moe_dispatch``'s ``tail``), whatever the chunk rows claim.

    Under a shortcut (``cfg.moe_shortcut``) every block's feed-forward is
    its dense MLP, and the first block of a pair ALSO runs its expert layer
    ("moe") over the same tokens: that result is what the block started,
    which joins the stream a block later (``_pool_block``)."""
    if len(hs) == 1:
        (h,), t = hs, hs[0].shape[1]
        valid_len, per_row = (None if t == 1 else valids[0]), t > 1

        def parted(out):
            return (out,)
    else:
        chunk, step = hs
        if len(hs) != 2 or chunk.shape[1] == 1 or step.shape[1] != 1:
            raise NotImplementedError(
                "groups other than a chunk's rows and one token a row "
                "beside them")
        (r, c, d), b = chunk.shape, step.shape[0]
        if cfg.is_moe and cfg.moe_impl == "dispatch":
            (out, beside), _ = L.moe_block(
                bp["mlp"], chunk, cfg, valid_len=valids[0],
                capacity_per_row=True, tail=step[:, 0])
            return (out, beside[:, None]), None, pools
        rows = [chunk.reshape(1, r * c, d), step.reshape(1, b, d)]
        pad = mixed_step_rows(cfg, r * c, b) - b
        if pad:
            rows.append(jnp.zeros((1, pad, d), chunk.dtype))
        h, valid_len, per_row = jnp.concatenate(rows, axis=1), None, False

        def parted(out):
            return (out[:, :r * c].reshape(r, c, d),
                    out[0, r * c:r * c + b][:, None])

    def experts(at: str, pools):
        counted = MOE_ROWS in pools
        out = L.moe_block(bp[at], h, cfg, valid_len=valid_len,
                          expert_stack=expert_stack,
                          capacity_per_row=per_row, rows_out=counted)
        if counted:
            pools = {**pools, MOE_ROWS: pools[MOE_ROWS] + out[2]}
        return parted(out[0]), pools

    if cfg.is_moe and not cfg.moe_shortcut:
        fed, pools = experts("mlp", pools)
        return fed, None, pools
    fed, started = parted(L.mlp_block(bp["mlp"], h, cfg)), None
    if "moe" in bp:
        started, pools = experts("moe", pools)
    return fed, started, pools


def mixed_step_rows(cfg: DecoderConfig, chunk_tokens: int, rows: int) -> int:
    """Rows the decode group of a mixed program (``paged_mixed_step``) takes
    in a feed-forward beside ``chunk_tokens`` tokens of chunks: ``rows``;
    where the experts are sorted, the next count at which the whole
    program's sorted rows, ``(chunk_tokens + rows) x k``, are whole tiles of
    the grouped matmul (``layers.grouped_matmul`` takes its kernel only
    then, and ``ragged_dot`` is 2.5x from it: 16 rows beside 1024 at k = 4
    become 32). The rows added are dead: zeros in, nothing read out."""
    if not (cfg.is_moe and cfg.moe_impl == "sorted"):
        return rows
    tile, k = L.GROUPED_TILE_ROWS, cfg.experts_per_token
    return next(n for n in range(rows, rows + tile)
                if (chunk_tokens + n) * k % tile == 0)


def _scan_layer_groups(params: Params, cfg: DecoderConfig, carry, block,  # traced
                       lora=None, groups=None):
    """``carry`` through every layer, one scan per group (decoder.
    layer_groups), a period of the group's pattern an iteration:
    ``block(bp, carry, layer, gcfg, lora_view, expert_stack) -> carry``.
    ``layer`` is the layer's index among the stack's layers of ITS kind,
    which is its index into the pool's planes of that kind (for a stack of
    alike layers: its index in the stack); ``bp`` holds its operator under
    its kind's key (``"conv" in bp``). A sorted expert group's expert leaves
    are taken whole, with the layer's index in the group
    (layers.split_expert_stack). ``groups``: those of ``layer_groups(cfg)``
    to run (all of them where None)."""
    for name, gcfg, first in layer_groups(cfg) if groups is None else groups:
        stack, experts = L.split_expert_stack(params[name], gcfg)
        stack, dense = split_dense_stack(stack, gcfg)
        period = gcfg.period
        at = {kind: cfg.kinds[:first].count(kind) for kind in period}

        def body(carry, scan_in, gcfg=gcfg, experts=experts, period=period,
                 at=at, dense=dense):
            unit, lsl, u = scan_in
            for j, (kind, i, bp) in enumerate(
                    unit_blocks(unit, gcfg, dense, u)):
                carry = block(
                    bp, carry, at[kind] + u * period.count(kind) + i, gcfg,
                    L.layer_view(lora, lsl),
                    None if experts is None
                    else (experts, gcfg.expert_layer(u * len(period) + j)))
            return carry, None

        carry, _ = jax.lax.scan(
            body, carry,
            (period_units(stack, gcfg), L.slice_layers(lora),
             jnp.arange(gcfg.n_layers // len(period), dtype=jnp.int32)))
    return carry


def _decode_attention(q, ck, cv, lengths, cfg: DecoderConfig,  # traced
                      lower=None):
    """Attention of ``T`` queries a row over the row's gathered pages, the
    decode step's one and the verify step's ``k+1`` alike.

    q [B,T,H,Dh]; ck/cv [B,Smax,KV,Dh]; lengths [B] = position of a row's
    first query (its K/V were just written at that index on, so query ``t``
    attends to kpos <= lengths[b] + t); ``lower`` [B] or [B,T] (a window
    layer): and to kpos >= lower."""
    b, t, smax = q.shape[0], q.shape[1], ck.shape[1]
    # heads and widths as the arrays have them (differential attention's
    # are pairs: ``layers.diff_q``)
    heads, kv_heads, width = q.shape[2], ck.shape[2], q.shape[3]
    qg = q.reshape(b, t, kv_heads, heads // kv_heads, width)
    scores = jnp.einsum("btkgd,bskd->btkgs", qg, ck,
                        preferred_element_type=jnp.float32)
    scores *= width ** -0.5
    kpos = jnp.arange(smax, dtype=jnp.int32)
    qpos = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    mask = kpos[None, None, :] <= qpos[:, :, None]            # [B,T,Smax]
    if lower is not None:
        mask = mask & (kpos[None, None, :] >= lower.reshape(b, -1, 1))
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(ck.dtype)
    out = jnp.einsum("btkgs,bskd->btkgd", probs, cv)
    return out.reshape(b, t, heads, width)


class _Rows(NamedTuple):
    """A group of a program's rows against the pool: ``B`` rows of ``T``
    tokens at ``positions`` [B,T] = ``start[b] .. start[b]+T-1`` of the
    sequences whose pages are ``table`` [B,mpp], ``valid`` [B] of a row's
    tokens real."""
    positions: jax.Array
    start: jax.Array
    valid: jax.Array
    table: jax.Array


def _pool_block(bp, xs, groups, pools, layer, num_pages: dict,  # traced
                page_size: int, cfg: DecoderConfig,
                attn_impl: str = "gather", lora=None, expert_stack=None):
    """One transformer block for GROUPS of rows against the page pool, a
    group ``B`` rows of ``T`` tokens: ``xs[g]`` [B,T,D] at ``groups[g]``
    (``_Rows``). THE block of every program that meets the pool where it
    lies: the decode step (one group, ``T`` 1), the speculative verify
    (``T`` k+1), the chunk prefill in place (``T`` the chunk), and the
    chunk's rows with the decode rows beside them (two groups:
    ``paged_mixed_step``). First norm, the operator of the block's kind
    (``decoder.block_kind``), residual and second norm, a group at a time
    (``_operator``: groups are different sequences, so their pages are
    disjoint and each writes its rows before it attends; so are their
    entries where a layer keeps a state a sequence: a parallel layer's
    ``ssd_chunk`` leaves the chunk row's entry and ``ssd_step`` the slots',
    a linear layer's ``kda_chunk`` and ``kda_step`` alike: two writers of
    one plane in one program, neither copying it); then
    ``_feed_forward`` ONCE over every group's tokens; residual:
    ``decoder._block_forward``'s skeleton, the only other copy. A block
    whose parameters hold no feed-forward part (``cfg.ffn_free``: no "mlp")
    is the operator and its residual alone. Returns (a group's output each,
    the planes as written).

    Under a shortcut (``cfg.moe_shortcut``) the first block of a pair also
    STARTS its expert layer on the normed input of its dense MLP, and a
    group's output is then the pair (``x``, what was started), which only
    the second block of the pair takes: it adds what was started behind its
    own attention and dense MLP, and hands on ``x`` alone (a layer scan's
    iteration is a pair, so no scan carries the pair).

    A group's ROWS may be consecutive chunks of ONE sequence (the engine's
    rows ahead, ``LLMEngine._rows_of``: row ``r + 1`` the same table row at
    ``start[r] + T``) where every layer's operator hands a chunk's END to the
    chunk behind INSIDE the program (``chunk_rows_follow``). A layer of kind
    "attention" does by the pool: the operator writes EVERY row's keys (the
    ``idx`` plane's beside a latent row) before any row attends, a row
    attends to nothing but the pool, from key 0 of its own table to its own
    position, and the expert layer takes its capacity a row. A layer of kind
    "ssd" does by its operator (``_ssd``): it sees from the rows' entries,
    starts and valid lengths which row follows which, runs a sequence's rows
    one behind the other, each from the state and the conv tail the row in
    front ended in, and lets the last of them write the entry. Either way
    the chunk behind computes what it computes a program later. Not so, yet,
    a linear layer's state, an ssm state, a ring or a conv layer's tail: the
    chunk behind needs the END state of the chunk in front, which their
    operators do not hand from row to row.

    ``pools`` holds every plane of the WHOLE pool viewed flat —
    ``k``/``v`` ``[L*P,pg,KV,Dh]`` (``[L*P,pg,KV*Dh]`` where the heads are
    packed) and, iff the pool stores int8, the per-token-per-head scales
    ``ks``/``vs`` ``[L*P,pg,KV]`` f32; a latent pool's one plane ``ckv``
    ``[L*P,pg,W]``; the conv layers' state ``conv`` ``[Lc*P,taps-1,D]``; a
    window layer's K and V ``[Lw*H,pg,KV,Dh]``; a linear or ssm layer's
    state a sequence ``[Ll*S,...]``; beside them, where the stack has gated
    memory units, ``SSM_MEMORY`` — and ``layer`` (a traced scalar: the block's
    index among the layers of its kind) picks this block's ``num_pages``
    (P) pages out of its kind's planes: page ``p`` of layer ``l`` is flat
    page ``l*P + p``. The block writes its tokens' rows into the planes it
    was handed and returns them, so the caller can carry them through its
    loops and the write lands in place; nothing here slices a layer's slab
    out or puts one back.

    ``valid`` [B]: how many of a row's ``T`` tokens are real, the first
    ones (a bool counts as 0 or 1: the decode step's ``live``). A token
    past them, past the table or on an unmapped page writes nothing: its
    write aims past the END of the flat pool and DROPS (one past this
    layer's pages, ``base + P``, is the next layer's page 0). A dead row
    reads nothing that is kept and writes nothing.

    What follows ``T``, read from the shapes and from nothing else. Where
    ``T`` is statically 1 a row IS its token and its addressing has no token
    axis ([B] indices, as the decode programs always lowered). The add and
    the second norm are two operations at one token a row and
    ``L.add_rmsnorm`` (one fused pass where the kernels are on) at several;
    the expert layer is told which tokens are real and takes its capacity a
    row at several tokens a row, and neither at one (a row is a token). What
    attends is the operator's choice (``_kv_attention``).

    ``attn_impl``: "gather" materializes the rows' pages into the
    contiguous layout and runs the XLA attention (2x KV read); "pallas"
    reads pages directly via the paged kernels (ops/paged_attention.py).
    int8 pools quantize on write and dequantize on read (in the einsum's
    operand, or in the decode kernel's VMEM): the pool, the resident thing,
    holds 2x the tokens per byte either way. A latent pool is attended in
    the ABSORBED form, so no program holds per-head K or V of the
    context."""
    out = []
    for x, rows in zip(xs, groups):
        x, joining = x if isinstance(x, tuple) else (x, None)
        proj, pools = _operator(bp, x, rows, pools, layer, num_pages,
                                page_size, cfg, attn_impl, lora)
        if "mlp" not in bp:     # a block of ONE sublayer (``cfg.ffn_free``)
            out.append(x + proj)
            continue
        if x.shape[1] == 1:
            x = x + proj
            h = L.rmsnorm(x, bp["ln2"], cfg, bias=bp.get("ln2_b"))
        else:
            x, h = L.add_rmsnorm(x, proj, bp["ln2"], cfg,
                                 bias=bp.get("ln2_b"))
        out.append((x, h, joining))
    if "mlp" not in bp:
        return tuple(out), pools
    fed, started, pools = _feed_forward(
        bp, [h for _, h, _ in out], [rows.valid for rows in groups], cfg,
        expert_stack, pools=pools)
    xs = tuple(x + y if joining is None else x + y + joining
               for (x, _, joining), y in zip(out, fed))
    return (xs if started is None else tuple(zip(xs, started))), pools


def _operator(bp, x, rows: _Rows, pools, layer, num_pages: dict,  # traced
              page_size: int, cfg: DecoderConfig, attn_impl: str, lora):
    """The first norm and the operator of the block's kind over ONE group of
    rows (``_pool_block``): (the operator's output [B,T,D], the planes with
    the group's rows written)."""
    positions, start, valid, table = rows
    kind = block_kind(bp)
    t, pg = x.shape[1], page_size
    # The planes the block meets: its own kind's; a cross layer's are the
    # LAST attention layer's (it writes none); a gated memory unit has none;
    # a parallel layer meets an ssd layer's planes, and the attention planes
    # with its own index too.
    met = {"cross": "attention", "gmu": None, "parallel": "ssd"}.get(
        kind, kind)

    def lies(of: str):
        """(``layer``'s first flat page in the planes of kind ``of``, its
        pages there, the planes' flat length)."""
        plane = next(pools[n] for n in pools
                     if n not in (MOE_ROWS, SSM_MEMORY)
                     and plane_kind(n) == of)
        total, pages = plane.shape[0], num_pages[of]
        return (total - pages if kind == "cross" else layer * pages), \
            pages, total

    if met is not None:
        own = lies(met)

    def entry():
        """Where each row's sequence keeps its state in this layer."""
        return _sequence_entry(table, valid.astype(bool), *own)

    def token_rows(at: tuple, ring_cfg=None):
        """Where the rows' tokens are written in the planes ``at``
        (``lies``): (flat page [B] or [B,T], past the planes where nothing
        is written; the positions alike)."""
        base, pages, total = at
        if t == 1:
            pos, real = start, valid.astype(bool)
        else:
            pos = positions
            real = (jnp.arange(t, dtype=jnp.int32)[None, :]
                    < valid[:, None]) & (pos < table.shape[1] * pg)
        page_id = _token_pages(table, pos, pg, pages, ring_cfg)
        return jnp.where(real & (page_id >= 0), base + page_id, total), pos

    def attention(a, h, at: tuple):
        # This layer's page table into the flat pool; -1 stays unmapped.
        pidx, pos = token_rows(at)
        attend = _latent_attention if cfg.is_latent else _kv_attention
        return attend(a, h, positions, start, pools, pidx, pos % pg,
                      jnp.where(table >= 0, table + at[0], -1), cfg,
                      attn_impl, lora)

    h = L.rmsnorm(x, bp["ln1"], cfg, bias=bp.get("ln1_b"))
    if kind == "gmu":
        proj = L.gmu_block(bp["gmu"], h, pools[SSM_MEMORY], cfg)
    elif kind == "cross":
        proj, pools = _kv_attention(
            bp["cross"], h, positions, start, pools, None, None,
            jnp.where(table >= 0, table + own[0], -1), cfg, attn_impl,
            lora, cross=True)
    elif kind == "ssm":
        proj, pools = _ssm(bp["ssm"], h, start, valid, pools, entry(), cfg,
                           attn_impl)
    elif kind == "linear":
        proj, pools = _kda(bp["linear"], h, start, valid, pools, entry(),
                           cfg, attn_impl)
    elif kind == "ssd":
        proj, pools = _ssd(bp["ssd"], h, start, valid, pools, entry(), cfg,
                           attn_impl)
    elif kind == "parallel":
        # Both branches on the one normed input, each between its
        # multipliers: they read and write different planes, so neither
        # waits for the other.
        a_in, a_out, _ = cfg.attn_multipliers or (1.0, 1.0, 1.0)
        with jax.named_scope("parallel_attention"):
            attn, pools = attention(bp["parallel"], L.scaled(h, a_in),
                                    lies("attention"))
        with jax.named_scope("parallel_ssd"):
            mixed, pools = _ssd(bp["parallel"], h, start, valid, pools,
                                entry(), cfg, attn_impl)
        proj = L.scaled(attn, a_out) + mixed
    elif kind == "conv":
        pidx, _ = token_rows(own)
        proj, pools = _conv(bp["conv"], h, start, pools, pidx, own[0], table,
                            pg, cfg)
    elif kind == "window":
        pidx, pos = token_rows(own, cfg)
        touched, seen = _window_pages(table, start, t, own[1], pg, cfg)
        proj, pools = _kv_attention(
            bp["window"], h, positions, seen, pools, pidx, pos % pg,
            jnp.where(touched >= 0, touched + own[0], -1), cfg, attn_impl,
            lora, tuple(WINDOW_PLANES), cfg.attn_window)
    else:
        proj, pools = attention(bp["attn"], h, own)
    return proj, pools


def _token_pages(table, pos, pg: int, pages: int, ring_cfg=None):  # traced
    """The page each token's rows are written to, off its row's table:
    ``pos`` [B] or [B,T] positions -> page ids alike, -1 unmapped.
    ``ring_cfg``: a window layer's planes, where a position's page is its
    ring's (``_ring_slot``); an id those planes do not hold (a caller that
    put a sequence's first pages elsewhere) is unmapped like no page."""
    mpp = table.shape[1]
    rows = jnp.arange(table.shape[0]).reshape(-1, *[1] * (pos.ndim - 1))
    slot = pos // pg
    if ring_cfg is None:
        return table[rows, jnp.clip(slot, 0, mpp - 1)]
    page_id = table[rows, _ring_slot(slot, ring_cfg, mpp)]
    return jnp.where(page_id < pages, page_id, -1)


def _window_pages(table, start, t: int, pages: int, pg: int,  # traced
                  cfg: DecoderConfig):
    """What ``T`` queries a row from ``start`` [B] read of a window layer:
    (the pages their windows touch and no other [B,n], -1 where unmapped or
    not held by the window planes; ``start`` [B] counted from the first of
    them). From the page of position ``start - window + 1`` to the page of
    ``start + T - 1`` (two at one token and a window of a page; a ring at a
    chunk), found through the ring. Those pages are all a kernel sees of
    the context: its time does not grow with it."""
    first = jnp.maximum(start - cfg.attn_window + 1, 0) // pg
    n = min(_ring_len(cfg, table.shape[1]),
            -(-(t + cfg.attn_window - 2) // pg) + 1)
    touched = ring_table(table, first, n, cfg)
    return jnp.where(touched < pages, touched, -1), start - first * pg


def _conv(c, h, start, pools, pidx, base, table, pg: int,  # traced
          cfg: DecoderConfig):
    """A conv layer, one token a row: the state as it stood after position
    ``t - 1`` is in the page of ``t - 1`` (nothing before a sequence's first
    token), the state after ``t`` goes to the page of ``t`` (``pidx``: past
    the pool for a dead row). Several tokens a row never come here: a conv
    stack's chunks go the gathered way (``_chunk_in_place``), which leaves
    the tail every page ends in. Returns (the operator's output [B,1,D],
    the planes as written)."""
    if h.shape[1] != 1:
        raise NotImplementedError(
            "a conv layer over several tokens a row of the pool in place")
    state = pools["conv"]
    before = jnp.maximum(start - 1, 0) // pg
    prev = table[jnp.arange(h.shape[0]),
                 jnp.clip(before, 0, table.shape[1] - 1)]
    tail = state[jnp.clip(base + prev, 0, state.shape[0] - 1)]
    tail = jnp.where(((start > 0) & (prev >= 0))[:, None, None], tail, 0)
    proj, zs = L.conv_block(c, h, cfg, tail)
    return proj, {**pools, "conv": state.at[pidx].set(zs[:, 1:],
                                                      mode="drop")}


def _sequence_entry(table_rows, live, base, entries: int,  # traced
                    total: int):
    """Where each row's sequence keeps its state in a linear layer's flat
    planes: ``base`` (the layer's first entry) plus the id of the row's
    first page; ``total`` (past the end: reads are masked, writes drop) for
    a dead row, a row without a first page, and a first page whose id the
    planes do not hold."""
    first = table_rows[:, 0]
    ok = live & (first >= 0) & (first < entries)
    return jnp.where(ok, base + first, total)


def _state_at(plane, entry, fresh):  # traced
    """What ``plane`` ([N, ...]: one kind of a linear layer's state, flat)
    holds at ``entry`` [B], zeros for a row that starts ``fresh`` and for an
    entry past the plane (a dead row)."""
    n = plane.shape[0]
    blank = fresh | (entry >= n)
    held = plane[jnp.clip(entry, 0, n - 1)]
    return jnp.where(blank.reshape(-1, *[1] * (held.ndim - 1)), 0, held)


def _kda(lin, h, start, valid, pools, entry, cfg: DecoderConfig,  # traced
         attn_impl: str):
    """A linear layer over ``T`` tokens a row: from the state at ``entry``
    [B] of the layer's planes (zeros for a row that starts its sequence) to
    the state after the row's last valid token, written back to the entry.
    One token goes through the recurrence with the state read and written
    where it lies ("pallas": ``ops/kda.py::kda_step``; "gather": the same in
    XLA), several through the chunked form (``kda_chunk``) from the state
    gathered. An ``entry`` past the planes is a dead row: nothing read,
    nothing written. Returns (the operator's output [B,T,D], the planes as
    written)."""
    from kubeflow_tpu.ops import kda

    t = h.shape[1]
    mats, tails = (pools[n] for n in LINEAR_PLANES)
    fresh = start == 0
    impl = "pallas" if attn_impl == "pallas" else "xla"
    q, k, v, g, beta, tail = L.kda_inputs(
        lin, h, cfg, _state_at(tails, entry, fresh),
        None if t == 1 else valid)
    if t == 1:
        o, mats = kda.kda_step(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], mats, entry,
            fresh, entry < mats.shape[0], impl=impl)
        o = o[:, None]
    else:
        o, mat = kda.kda_chunk(q, k, v, g, beta,
                               _state_at(mats, entry, fresh), impl=impl)
        mats = mats.at[entry].set(mat, mode="drop")
    pools = {**pools, LINEAR_PLANES[0]: mats,
             LINEAR_PLANES[1]: tails.at[entry].set(
                 tail.astype(tails.dtype), mode="drop")}
    return L.kda_output(lin, h, o, cfg), pools


def _ssm(sp, h, start, valid, pools, entry, cfg: DecoderConfig,  # traced
         attn_impl: str):
    """An ssm layer over ``T`` tokens a row: from the state at ``entry`` [B]
    of the layer's planes (zeros for a row that starts its sequence) to the
    state after the row's last valid token, written back to the entry. One
    token is the recurrence's one step in XLA (gather, step, scatter),
    several the scan (``ops/ssm.py``: "pallas" the kernel ``ssm_scan``,
    "gather" the scan over positions). An ``entry`` past the planes is a
    dead row: nothing read, nothing written. The scan's output before its
    gate goes to ``pools[SSM_MEMORY]`` where the program carries one.
    Returns (the operator's output [B,T,D], the planes as written)."""
    from kubeflow_tpu.ops import ssm

    t = h.shape[1]
    states, tails = (pools[n] for n in SSM_PLANES)
    fresh = start == 0
    c, z, delta, bm, cm, tail = L.ssm_inputs(
        sp, h, cfg, _state_at(tails, entry, fresh), None if t == 1 else valid)
    args = (L.ssm_decay(sp), sp["d_skip"].astype(jnp.float32),
            _state_at(states, entry, fresh))
    if t == 1:
        y, state = ssm.ssm_step_xla(c[:, 0], delta[:, 0], bm[:, 0], cm[:, 0],
                                    *args)
        y = y[:, None]
    else:
        y, state = ssm.ssm_scan(
            c, delta, bm, cm, *args,
            impl="pallas" if attn_impl == "pallas" else "xla")
    pools = {**pools,
             SSM_PLANES[0]: states.at[entry].set(state, mode="drop"),
             SSM_PLANES[1]: tails.at[entry].set(tail.astype(tails.dtype),
                                                mode="drop")}
    if SSM_MEMORY in pools:
        pools[SSM_MEMORY] = y
    return L.ssm_output(sp, y, z, cfg), pools


def _rows_follow(entry, start, valid, t: int, total: int):  # traced
    """Which rows of a group are the chunk BEHIND the row in front ([B] bool;
    never row 0): both live, the same sequence entry (``_sequence_entry``:
    the same first page), the row's start the start in front plus ``t``,
    and the row in front FULL (``valid == t``). Observed from what the
    program is handed: the engine's rows ahead (``LLMEngine._rows_of``) are
    such rows, and nobody tells the program so."""
    behind = (entry[1:] == entry[:-1]) & (entry[1:] < total) \
        & (start[1:] == start[:-1] + t) & (valid[:-1] == t)
    return jnp.concatenate([jnp.zeros((1,), bool), behind])


def _ssd(sp, h, start, valid, pools, entry, cfg: DecoderConfig,  # traced
         attn_impl: str):
    """An SSD mixer (an ssd layer's operator, a parallel layer's second
    branch) over ``T`` tokens a row: from the state
    at ``entry`` [B] of the layer's planes (zeros for a row that starts its
    sequence) to the state after the row's last valid token, written back to
    the entry. One token goes through the recurrence with the state read and
    written where it lies ("pallas": ``ops/ssd.py::ssd_step``; "gather": the
    same in XLA), several through the chunked form (``ssd_chunk``: "pallas"
    the kernel, "gather" token by token) from the state gathered. An
    ``entry`` past the planes is a dead row: nothing read, nothing written.

    Several rows of several tokens may be consecutive chunks of ONE sequence
    (``_rows_follow``). A row behind then takes the END state and the conv
    tail of the row in front as its start, inside the one ``ssd_chunk`` call
    and the one ``ssd_inputs``: float32 and the tail plane's type, the bits
    the entry would have held between two programs. Such rows name the SAME
    entry, and a scatter with a repeated index has no defined winner: only
    the LAST row of a run writes it. Returns (the mixer's output [B,T,D],
    the planes as written)."""
    from kubeflow_tpu.ops import ssd

    b, t = h.shape[:2]
    mats, tails = (pools[n] for n in SSD_PLANES)
    fresh = start == 0
    impl = "pallas" if attn_impl == "pallas" else "xla"
    follows = None
    if b > 1 and t >= cfg.conv_taps:    # (chunks; a whole tail to hand on)
        follows = _rows_follow(entry, start, valid, t, mats.shape[0])
    xs, z, dt, bm, cm, tail = L.ssd_inputs(
        sp, h, cfg, _state_at(tails, entry, fresh), None if t == 1 else valid,
        follows)
    a, d = L.ssd_decay(sp), sp["d_skip"].astype(jnp.float32)
    if t == 1:
        y, mats = ssd.ssd_step(
            xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, mats, entry, fresh,
            entry < mats.shape[0], impl=impl)
        y = y[:, None]
    else:
        # (a plane holds narrow heads side by side: ``ssd.pack_state``)
        y, mat = ssd.ssd_chunk(
            xs, dt, a, bm, cm, d, ssd.unpack_state(
                _state_at(mats, entry, fresh), cfg.ssd_heads),
            follows=follows, impl=impl, block=cfg.ssd_chunk)
        if follows is not None:     # a row that is followed writes nothing
            entry = jnp.where(jnp.roll(follows, -1).at[-1].set(False),
                              mats.shape[0], entry)
        mats = mats.at[entry].set(
            ssd.pack_state(mat, cfg.ssd_heads // mats.shape[1]), mode="drop")
    pools = {**pools, SSD_PLANES[0]: mats,
             SSD_PLANES[1]: tails.at[entry].set(tail.astype(tails.dtype),
                                                mode="drop")}
    return L.ssd_output(sp, y, z, cfg), pools


def _qkv_rope(a, h, positions, cfg: DecoderConfig, lora=None,  # traced
              window: int = 0):
    """Per-head projections of ``h`` [B,S,D] at ``positions`` [B,S]: (q
    [B,S,H,Dh], k and v [B,S,KV,Dh]), q and k normed and rotated (a global
    layer, ``window`` 0, not rotated where only window layers are)."""
    dt = cfg.activation_dtype
    q = jnp.einsum("bsd,dhk->bshk", h, a["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", h, a["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"].astype(dt))
    if lora is not None:
        # Multi-adapter decode (serve/lora.py): per-row low-rank deltas
        # on the shared projections; adapter_idx = -1 rows add exact 0.
        q = L.apply_lora_layer(lora, "wq", h, q)
        k = L.apply_lora_layer(lora, "wk", h, k)
        v = L.apply_lora_layer(lora, "wv", h, v)
    q, k = L.qk_rope(a, q, k, positions, cfg, window)
    return q, k, v


def _kv_attention(a, h, positions, start, pools, pidx, off, ltable,  # traced
                  cfg: DecoderConfig, attn_impl: str, lora,
                  planes: tuple = ("k", "v"), window: int = 0,
                  cross: bool = False):
    """Per-head K/V over ``T`` tokens a row: project, write the tokens' rows
    at (pidx, off) of the K and V ``planes`` ([B] where ``T`` is 1, else
    [B,T]), attend causally to the pages of ``ltable`` from key 0 of its
    first page, ``start`` [B] being a row's first query, project out. A
    window layer hands its own planes, ``window``, and the pages its
    queries' windows touch with ``start`` counted from the first of them.

    What attends follows ``attn_impl`` and ``T``: "pallas" at one token the
    decode kernels (all rows one call), at several ``paged_chunk_attention``
    (one call a row; it takes neither int8 planes nor packed rows nor a
    call with LoRA, which ``_chunk_in_place`` keeps from it); "gather" ONE
    masked attention over the gathered pages whatever ``T``. Differential
    attention (``cfg.diff_attention``) is its projections in front and its
    subtraction, norm and output behind the same calls (``layers.diff_q``:
    to them it is GQA over paired heads). ``cross``: queries only, over
    ``planes`` as another layer wrote them; nothing is written here.
    Returns (the block's attention output [B,T,D], the planes as
    written)."""
    dt = cfg.activation_dtype
    nk, nv = planes
    t = h.shape[1]
    kv_quant = "ks" in pools
    if cfg.diff_attention:
        if lora is not None:
            raise NotImplementedError("LoRA over differential attention")
        q = L.diff_q(a, h, cfg)
        k, v = (None, None) if cross else L.diff_kv(a, h, cfg)
    else:
        q, k, v = _qkv_rope(a, h, positions, cfg, lora, window)
    if t == 1 and not cross:
        k, v = k[:, 0], v[:, 0]
    rows = {} if cross else {nk: k, nv: v}
    as_rows = kept_as_rows(cfg)         # [L*P, pg*KV, Dh]: a head a row
    # [L*P, pg, KV*Dh]: heads in one row
    packed = pools[nk].ndim == 3 and not as_rows
    if as_rows and not cross:           # a token's rows, its heads in order
        pidx, off = pidx[..., None], _token_rows(off, as_rows)
    if packed:
        rows = {n: r.reshape(*r.shape[:-2], -1) for n, r in rows.items()}
    if kv_quant:
        from kubeflow_tpu.ops.quantization import dequantize_kv, quantize_kv

        rows[nk], rows["ks"] = quantize_kv(k)
        rows[nv], rows["vs"] = quantize_kv(v)
    pools = {**pools, **{
        name: pools[name].at[pidx, off].set(row, mode="drop")
        for name, row in rows.items()}}
    by_row = attn_impl == "pallas" and t > 1     # heads-major [B,H,T,Dh]
    if by_row:
        from kubeflow_tpu.ops.paged_attention import paged_chunk_attention

        attn = jnp.stack([
            paged_chunk_attention(jnp.swapaxes(q[r], 0, 1), pools[nk],
                                  pools[nv], ltable[r], start[r],
                                  window=window, kv_heads=as_rows)
            for r in range(h.shape[0])])
    else:
        lower = None
        if window:      # the first key each query still sees
            qpos = start if t == 1 else start[:, None] + jnp.arange(
                t, dtype=jnp.int32)[None, :]
            lower = jnp.maximum(qpos - window + 1, 0)
        if attn_impl == "pallas" and packed:
            from kubeflow_tpu.ops.paged_attention import (
                paged_packed_decode_attention,
            )

            attn = paged_packed_decode_attention(
                q, pools[nk], pools[nv], ltable, start, cfg.n_kv_heads)
        elif attn_impl == "pallas":
            from kubeflow_tpu.ops.paged_attention import (
                paged_decode_attention,
            )

            attn = paged_decode_attention(
                q, pools[nk], pools[nv], ltable, start,
                pool_ks=pools.get("ks"), pool_vs=pools.get("vs"),
                lower=lower, kv_heads=as_rows)
        else:
            ck = paged_gather(pools[nk], ltable)
            cv = paged_gather(pools[nv], ltable)
            if as_rows:
                ck = ck.reshape(ck.shape[0], -1, as_rows, ck.shape[-1])
                cv = cv.reshape(cv.shape[0], -1, as_rows, cv.shape[-1])
            if packed:
                ck = ck.reshape(*ck.shape[:2], cfg.n_kv_heads, cfg.head_dim)
                cv = cv.reshape(*cv.shape[:2], cfg.n_kv_heads, cfg.head_dim)
            if kv_quant:
                ck = dequantize_kv(ck, paged_gather(pools["ks"], ltable), dt)
                cv = dequantize_kv(cv, paged_gather(pools["vs"], ltable), dt)
            attn = _decode_attention(q, ck, cv, start, cfg, lower=lower)
    if cfg.diff_attention:
        return L.diff_output(a, jnp.swapaxes(attn, 1, 2) if by_row else attn,
                             cfg), pools
    attn = L.gate_attention(a, h, attn, cfg, heads_axis=1 if by_row else 2)
    proj = jnp.einsum("bhsk,hkd->bsd" if by_row else "bshk,hkd->bsd", attn,
                      a["wo"].astype(dt))
    if lora is not None and "wo" in lora["targets"]:
        proj = L.apply_lora_layer(
            lora, "wo", attn.reshape(*attn.shape[:2], -1), proj)
    return proj, pools


def _latent_attention(a, h, positions, start, pools, pidx, off,  # traced
                      ltable, cfg: DecoderConfig, attn_impl: str, lora):
    """Latent attention over ``T`` tokens a row, absorbed: write the tokens'
    cache rows, fold the key expansion into the query, attend causally over
    the row's pages ("pallas": at one token ``paged_latent_decode_attention``
    reads each page once for all rows' heads, at several
    ``paged_latent_chunk_attention``, one call a row; "gather": the same
    sums in XLA over the gathered rows, whatever ``T``), expand the attended
    row into values. Same return as the per-head form.

    Where an indexer selects the keys (``cfg.index_topk``) the tokens'
    index keys are written beside their cache rows, at the same (page,
    offset) of the ``idx`` plane; every query is scored against every key
    of its row's pages (``dsa.index``: ``paged_index_scores`` over the
    pages where they lie, or the XLA form over the gathered ones), the
    selection is made (``dsa.select``, exact: the kernel
    ``paged_select_keys`` over scores left page-major, or
    ``layers.select_keys``) and attention reads under it as a second mask
    beside the causal one (``dsa.attend``), in every form alike."""
    if lora is not None:
        raise NotImplementedError("LoRA over latent attention projections")
    from kubeflow_tpu.ops import paged_attention as PA

    t, pg = h.shape[1], pools["ckv"].shape[1]
    q_nope, q_rope, row, cq = L.latent_qkv(a, h, positions, cfg)
    flat = pools["ckv"].at[pidx, off].set(row[:, 0] if t == 1 else row,
                                          mode="drop")
    written, selected = {"ckv": flat}, None
    b, mpp, kernels = h.shape[0], ltable.shape[1], attn_impl == "pallas"
    if cfg.index_topk:
        with jax.named_scope("dsa.index"):
            qi, ki, wi = L.index_qkw(a, h, cq, positions, cfg)
            keys = written["idx"] = pools["idx"].at[pidx, off].set(
                ki[:, 0] if t == 1 else ki, mode="drop")
            if kernels:     # page-major: [B x tiles, mpp, tile, pg]
                scores = PA.paged_index_scores(qi, wi, keys, ltable, start)
            else:
                scores = L.index_scores(qi, wi, paged_gather(keys, ltable),
                                        positions)
        with jax.named_scope("dsa.select"):
            if not kernels:
                selected = L.select_keys(scores, cfg.index_topk)  # [B,T,S]
            elif t == 1:
                # the rows' single queries as ONE tile of the selection:
                # [B, mpp, 1, pg] -> [1, mpp, B, pg] and back, [B, mpp, pg]
                selected = jnp.swapaxes(PA.paged_select_keys(
                    jnp.swapaxes(scores, 0, 2), jnp.max(start)[None],
                    cfg.index_topk), 0, 2)[:, :, 0]
            else:
                tile = scores.shape[2]
                last = (start[:, None] + tile * (1 + jnp.arange(
                    t // tile, dtype=jnp.int32))[None, :] - 1).reshape(-1)
                selected = PA.paged_select_keys(
                    scores, last, cfg.index_topk).reshape(
                        b, t // tile, mpp, tile, pg)

    def chosen(r):      # the kernels' second mask, a row's or every row's
        return None if selected is None else selected[r]

    with jax.named_scope("dsa.attend") if cfg.index_topk \
            else contextlib.nullcontext():
        if kernels and t == 1:
            q = L.latent_query(a, q_nope[:, 0], q_rope[:, 0], cfg)  # [B,H,W]
            o_row = PA.paged_latent_decode_attention(
                q, flat, ltable, start, sm_scale=L.latent_scale(cfg),
                selected=chosen(slice(None)))
            attn = L.latent_output(a, o_row, cfg)[:, None]
        elif kernels:
            q = L.latent_query(a, q_nope, q_rope, cfg)         # [B,T,H,W]
            o_row = jnp.stack([
                PA.paged_latent_chunk_attention(
                    jnp.swapaxes(q[r], 0, 1), flat, ltable[r], start[r],
                    sm_scale=L.latent_scale(cfg), selected=chosen(r))
                for r in range(b)])
            attn = L.latent_output(a, jnp.swapaxes(o_row, 1, 2), cfg)
        else:
            rows = paged_gather(flat, ltable)                  # [B, S, W]
            seen = jnp.arange(rows.shape[1], dtype=jnp.int32)[
                None, None, :] <= positions[:, :, None]        # [B, T, S]
            if selected is not None:
                seen = seen & selected
            attn = L.latent_absorbed_attention(
                a, q_nope, q_rope, rows, seen[:, None], cfg)
    return jnp.einsum("bshk,hkd->bsd", attn,
                      a["wo"].astype(cfg.activation_dtype)), {
                          **pools, **written}


def _pool_forward(params: Params, cache: dict, tokens, table, start,  # traced
                  valid, cfg: DecoderConfig, attn_impl: str, lora=None,
                  tail_at: str = "all", wanted=None):
    """``tokens`` [B,T] at positions ``start[b] ..`` of the sequences whose
    pages are ``table`` [B,mpp], through every layer against the pool where
    it lies (``_pool_block``): the ONE builder under the decode step, the
    chunk prefill in place, the speculative verify and the program that
    carries a chunk's rows and the decode rows together. Returns (the last
    layer's output [B,T,D], the planes as written, still flat:
    ``_pool_planes`` hands them back as the cache holds them, once the
    caller's head has read ``x``).

    Several GROUPS of rows go through the one layer scan where ``tokens``,
    ``table``, ``start`` and ``valid`` are tuples, a group's each
    (``paged_mixed_step``: rows of a chunk and rows of one token); what comes
    back first is then a tuple too, a group's output each. A group runs a
    layer's operator by itself and all of them its feed-forward together.

    The pool is a CARRY of the layer scan, never a scanned input/output: a
    scan's stacked outputs are a new buffer, so scanning over ``[L,P,...]``
    copies every layer's slab out and back to write B rows of it. Each
    plane is viewed flat ``[L*P,...]`` (a bitcast), carried beside ``x`` and
    written in place by the block at ``layer*P + page``; the scanned inputs
    are the layer's weights, its LoRA slice and its index.

    A stack's STATELESS TAIL (``cfg.stateless_tail``: gated memory units
    and cross layers, which write nothing) runs behind the layers that keep
    state, over ``x`` alone: the planes are read where they lie and are no
    carry of its scans. ``tail_at`` (STATIC) says at which positions: "all",
    every one, like the layers in front; "last", ONE a row, its last valid
    one (``x`` and the memory gathered there, a cross layer's one query
    over the row's pages up to it), and ``x`` comes back ``[B,1,D]``: what
    a chunk program whose caller reads one position's logits a row needs.
    ``wanted`` ([B] bool, with "last"): where it names no row the tail is
    not run at all (one ``lax.cond``; what comes back is then read by
    nobody)."""
    several = isinstance(tokens, tuple)
    if not several:
        tokens, table, start, valid = (tokens,), (table,), (start,), (valid,)
    xs, groups = [], []
    for tok, tbl, st, vl in zip(tokens, table, start, valid):
        xs.append(_embed(params, tok, cfg))
        positions = st[:, None]
        if tok.shape[1] > 1:
            positions = positions + jnp.arange(
                tok.shape[1], dtype=jnp.int32)[None, :]
        groups.append(_Rows(positions, st, vl, tbl))
    xs, groups = tuple(xs), tuple(groups)
    pg = _pool_geometry(cache, cfg)[1]
    num_pages = _pages_by_kind(cache)
    flat = _flat_pools(cache)
    stretches = layer_groups(cfg)
    tail = [g for g in stretches
            if g[2] >= cfg.n_layers - cfg.stateless_tail]

    def block(bp, carry, layer, gcfg, lora_view, expert_stack):
        return _pool_block(
            bp, carry[0], groups, carry[1], layer, num_pages, pg, gcfg,
            attn_impl=attn_impl, lora=lora_view, expert_stack=expert_stack)

    if not tail:
        xs, flat = _scan_layer_groups(params, cfg, (xs, flat), block, lora)
        return (xs if several else xs[0]), flat
    if several:
        raise NotImplementedError(
            "several groups of rows through a stateless tail")
    if MOE_ROWS in flat:
        raise NotImplementedError(
            "a stateless tail whose expert layers hold a share")
    (b, t), (positions, start, valid, table) = tokens[0].shape, groups[0]
    if cfg.layers_of("gmu"):
        flat[SSM_MEMORY] = jnp.zeros((b, t, cfg.ssm_inner), jnp.float32)
    (x,), flat = _scan_layer_groups(
        params, cfg, (xs, flat), block, lora,
        stretches[:len(stretches) - len(tail)])
    seen = {n: flat.pop(n) for n in (SSM_MEMORY,) if n in flat}
    if tail_at == "last" and t > 1:
        at = jnp.maximum(valid - 1, 0)
        x, seen = jax.tree.map(
            lambda a: jnp.take_along_axis(a, at[:, None, None], axis=1),
            (x, seen))
        start, valid = start + at, valid > 0
        positions = start[:, None]
    behind = _Rows(positions, start, valid, table)

    def tail_block(bp, x, layer, gcfg, lora_view, expert_stack):
        return _pool_block(
            bp, (x,), (behind,), {**flat, **seen}, layer, num_pages, pg,
            gcfg, attn_impl=attn_impl, lora=lora_view,
            expert_stack=expert_stack)[0][0]

    def run(x):
        return _scan_layer_groups(params, cfg, x, tail_block, lora, tail)

    if wanted is None:
        return run(x), flat
    return jax.lax.cond(jnp.any(wanted), run, lambda x: x, x), flat


def _paged_decode_step(params: Params, cache: dict, tokens: jax.Array,  # traced
                       lengths: jax.Array, live: jax.Array,
                       cfg: DecoderConfig, attn_impl: str = "gather",
                       lora=None):
    """One [B,1] decode step over the page pool (``_pool_forward`` at one
    token a row): tokens [B] (last sampled), lengths [B] (their positions),
    live [B] (rows whose KV write is real). Returns (logits [B,V] fp32, new
    cache)."""
    table = cache["table"]
    x, flat = _pool_forward(params, cache, tokens[:, None], table, lengths,
                            live, cfg, attn_impl, lora)
    logits = _head_logits(params, x, cfg)[:, 0]
    return logits, {**_pool_planes(flat, cache), "table": table}


def _pool_planes(flat: dict, cache: dict) -> dict:  # traced
    """What ``_flat_pools`` made of ``cache``, as the cache holds it:
    ``[L,P,...]`` again, so every other program sees the cache it always
    saw."""
    return {n: p.reshape(cache[n].shape) for n, p in flat.items()}


def _flat_pools(cache: dict) -> dict:  # traced
    """What a program carries through its layer scans: every plane of the
    pool viewed flat ``[L*P, ...]`` (a bitcast) and, where the cache has
    them, the expert rows' running sums."""
    flat = {n: cache[n].reshape(-1, *cache[n].shape[2:])
            for n in _planes_of(cache)}
    if MOE_ROWS in cache:
        flat[MOE_ROWS] = cache[MOE_ROWS]
    return flat


def paged_decode_multi(params: Params, cache: dict, tokens: jax.Array,  # traced
                       lengths: jax.Array, live: jax.Array, temps: jax.Array,
                       top_k: jax.Array, top_p: jax.Array,
                       stop_tokens: jax.Array, budgets: jax.Array,
                       key: jax.Array, cfg: DecoderConfig, num_steps: int,
                       sample_mode: str = "full", attn_impl: str = "gather",
                       lora=None, adapter_idx=None):
    """Up to ``num_steps`` decode+sample steps in ONE dispatch over the page
    pool (the host pre-allocates pages covering ``lengths + num_steps`` so
    mid-dispatch page-boundary crossings always land on mapped pages — with
    pipelined dispatch the engine adds one in-flight round of slack on
    top). Sampling runs on-device inside a ``while_loop`` that exits as
    soon as every slot is finished (stop token, token budget, or
    cache-length cap). Dead rows (free or finished slots, a slot
    mid-chunked-prefill) still flow through the batch so shapes never
    change; their KV writes are DROPPED, never replayed (a replayed write
    would corrupt KV a chunked prefill already wrote), and their tokens
    are discarded via ``live``. Emitted tokens surface as
    ``out`` [B, num_steps] with -1 in never-emitted cells. Returns (out,
    cache, tokens, lengths, live, budgets): the advanced carry is the next
    round's input, kept device-resident by the engine
    (serve/device_state.py)."""
    from kubeflow_tpu.serve.engine import _sample_batch

    b = tokens.shape[0]
    mpp = cache["table"].shape[1]
    max_len = mpp * _pool_geometry(cache, cfg)[1]
    out0 = jnp.full((b, num_steps), -1, jnp.int32)
    lr = (None if lora is None
          else {**lora, "aidx": adapter_idx})

    def cond(carry):
        i, _, _, _, live, _, _, _ = carry
        return (i < num_steps) & jnp.any(live)

    def body(carry):
        i, cache, tokens, lengths, live, budgets, key, out = carry
        logits, cache = _paged_decode_step(params, cache, tokens, lengths,
                                           live, cfg, attn_impl=attn_impl,
                                           lora=lr)
        key, sub = jax.random.split(key)
        sampled = _sample_batch(logits, sub, temps, top_k, top_p,
                                mode=sample_mode)
        tokens = jnp.where(live, sampled, tokens)
        out = out.at[:, i].set(jnp.where(live, sampled, -1))
        lengths = jnp.where(live, lengths + 1, lengths)
        budgets = jnp.where(live, budgets - 1, budgets)
        live = live & (sampled != stop_tokens) & (budgets > 0) \
            & (lengths + 1 < max_len)
        return i + 1, cache, tokens, lengths, live, budgets, key, out

    _, cache, tokens, lengths, live, budgets, _, out = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), cache, tokens, lengths, live, budgets, key, out0))
    return out, cache, tokens, lengths, live, budgets


def paged_mixed_step(params: Params, cache: dict, chunk: jax.Array,  # traced
                     table_rows: jax.Array, start: jax.Array,
                     valid_len: jax.Array, wanted: jax.Array, ride,
                     tokens: jax.Array, lengths: jax.Array, live: jax.Array,
                     temps: jax.Array, top_k: jax.Array, top_p: jax.Array,
                     stop_tokens: jax.Array, budgets: jax.Array,
                     key: jax.Array, cfg: DecoderConfig,
                     sample_mode: str = "full", attn_impl: str = "pallas"):
    """One chunk of each of ``R`` prompts AND one decode step of the ``B``
    slots in ONE program, so an iteration that has both reads every weight
    once: ``paged_chunk_prefill``'s in-place form at ``logits_at="last"``
    (``chunk`` [R,C], ``table_rows``, ``start``, ``valid_len``, ``wanted``:
    its arguments) beside one step of ``paged_decode_multi`` (``tokens`` ..
    ``key``: its arguments; ``cache`` carries "table"), two groups of rows
    through one layer scan (``_pool_forward``). In a layer each group runs
    the operator as it does in its own program (the chunk's rows first: they
    are other sequences than the slots', so the pages written are disjoint,
    and where a layer keeps a state a sequence, the entries),
    the feed-forward runs once over all their tokens, and behind the last
    layer the norm and the head run once, over the chunk rows' last valid
    positions and the slots' tokens (not at all where no row is ``wanted``
    and no slot live). A decode row's expert layer cannot drop it, whatever
    the chunk rows claim (``_feed_forward``).

    ``ride`` (a traced bool): the slots take their step. Where false every
    decode row is dead for this program, whatever ``live`` says: nothing of
    the slots' state is read into a result, written or advanced (the chunk
    program alone, under the same name). Returns (the chunk rows' logits
    [R,V] float32, out [B,1] as a round's token buffer: -1 where nothing was
    emitted, cache, tokens, lengths, live, budgets)."""
    from kubeflow_tpu.serve.engine import _sample_batch

    if not chunk_carries_step(cache, cfg, None, attn_impl):
        raise NotImplementedError(
            "a chunk and a decode step in one program: layers of kind "
            f"{' or '.join(sorted(STEP_CARRYING_KINDS))} over a pool the "
            "chunk meets in place")
    table = cache["table"]
    on = live & ride
    (xc, xd), flat = _pool_forward(
        params, cache, (chunk, tokens[:, None]), (table_rows, table),
        (start, lengths), (valid_len, on), cfg, attn_impl)
    rows = chunk.shape[0]
    last = jnp.take_along_axis(
        xc, jnp.maximum(valid_len - 1, 0)[:, None, None], axis=1)
    logits = _last_logits(
        params, jnp.concatenate([last, xd]),
        jnp.ones((rows + tokens.shape[0],), jnp.int32), cfg,
        jnp.concatenate([wanted, on]))
    sampled = _sample_batch(logits[rows:], jax.random.split(key)[1], temps,
                            top_k, top_p, mode=sample_mode)
    # the slots' state after the step: ``paged_decode_multi``'s own rules
    max_len = table.shape[1] * _pool_geometry(cache, cfg)[1]
    tokens = jnp.where(on, sampled, tokens)
    out = jnp.where(on, sampled, -1)[:, None]
    lengths = jnp.where(on, lengths + 1, lengths)
    budgets = jnp.where(on, budgets - 1, budgets)
    live = jnp.where(on, (sampled != stop_tokens) & (budgets > 0)
                     & (lengths + 1 < max_len), live)
    return (logits[:rows], out,
            {**_pool_planes(flat, cache), "table": table}, tokens, lengths,
            live, budgets)


def copy_pages(cache: dict, src: jax.Array, dst: jax.Array) -> dict:  # traced
    """Page-to-page pool copy: ``dst[i] <- src[i]`` for every pool plane
    (k/v and, when quantized, their scales; a latent pool's one padded row;
    the conv layers' state, which a page ends in; a linear layer's entry
    with a sequence's first page)
    — the radix index's copy-on-write primitive (serve/kvtier.py): a
    request diverging inside a shared block gets a private copy of the
    partial tail in ONE dispatch instead of recomputing it. Out-of-range
    ``dst`` ids (the power-of-two pad) drop their writes."""
    out = dict(cache)
    for name in _planes_of(cache):
        pool = cache[name]
        npages = pool.shape[1]
        d = jnp.where((dst >= 0) & (dst < npages), dst, npages)
        if plane_kind(name) in SEQUENCE_PLANES:
            # A sequence's entry goes with its first page: to another first
            # page, from one; any other pair copies nothing here.
            d = jnp.where((src >= 0) & (src < npages), d, npages)
        out[name] = pool.at[:, d].set(
            pool[:, jnp.clip(src, 0, npages - 1)], mode="drop")
    return out


def context_bucket(pos: int, chunk: int, page_size: int, mpp: int) -> int:
    """Static context-page bucket for a chunk prefill at ``pos``: the next
    power of two covering ceil((pos + chunk) / page_size), clamped to the
    slot's table length. ONE policy shared by the engine dispatch and the
    microbench (scripts/bench_chunk_prefill.py) so recorded numbers always
    describe what the engine runs."""
    need = -(-(pos + chunk) // page_size)
    ctx = 1
    while ctx < need:
        ctx *= 2
    return min(ctx, mpp)


def paged_chunk_prefill(params: Params, cache: dict, tokens: jax.Array,  # traced
                        table_rows: jax.Array, start: jax.Array,
                        valid_len: jax.Array, cfg: DecoderConfig,
                        attn_impl: str = "xla",
                        context_pages: Optional[int] = None,
                        lora=None, adapter_idx=None,
                        paged_attn_impl: str = "gather",
                        logits_at: str = "all", wanted=None):
    """Prefill one chunk of EACH of ``B`` prompts in one program: row ``b``
    is ``tokens[b]`` ([B,C]) at positions [start[b], start[b]+C) of the slot
    whose pages are ``table_rows[b]`` ([B,mpp]), ``valid_len[b]`` of them
    real. Everything that multiplies by a weight (projections, experts, the
    head) sees the ``B x C`` rows together, so each weight matrix is read
    once for all of them; attention stays per prompt (a row attends to its
    own pages from its own start). A row's result does not depend on the
    other rows: the capacity of a dispatch expert layer is taken per row
    (``layers._moe_dispatch``), nothing else crosses rows. A DEAD row
    (``valid_len`` 0, table -1) computes and writes nothing that is kept.

    A row's K/V scatters back per token as (page, offset) writes off its
    table row — exactly the decode write's addressing — so a ``start`` needs
    NO page alignment. Sub-page prefix reuse (the radix index's
    copy-on-write tail, serve/kvtier.py) resumes prefill mid-page through
    this path; only a row's first ``valid_len`` positions write (the padded
    tail and any unmapped page aim out of bounds and DROP).

    Two forms, chosen from the cache and the call (``_chunk_in_place``).
    IN PLACE (``_paged_chunk_in_place``; a latent pool always, a per-head
    pool where ``paged_attn_impl``, the engine's "gather" | "pallas", is
    "pallas" and the kernel takes its planes): the pool rides flat through
    the layer scans, a layer writes its rows where they belong and each
    prompt attends over its own pages where they lie. GATHERED (every other
    per-head pool: int8, packed rows beside conv layers, a call with LoRA,
    "gather"): the page table is gathered into the contiguous layout
    decoder_forward's cache path expects (one start a row; each row then
    attends over the shortest span of the bucket ladder that holds its own
    context, ``layers._cached_attention_by_row``) and only the chunk's
    tokens are scattered back, every plane of the pool (``pool_planes``)
    the same way; the conv layers of a patterned stack take the state their
    row's chunk starts from and leave the tails its pages end in
    (``state_planes``). ``context_pages`` (STATIC, one for all rows: the
    largest row's) bounds the pages looked at to those covering
    [0, start+C): chunk cost then tracks the resident context, not max_len —
    without it a long prompt pays O(max_len²/C) in gathers (round-2 weak
    #4). The caller buckets the count (powers of two) so the trace set stays
    logarithmic. The in-place form of a per-head pool does not read it: its
    kernel's cost follows each row's own context whatever the table's
    length.

    ``logits_at`` (STATIC) says which positions' logits come back. "all":
    ([B,C,V] logits, cache), the head over every position of every row.
    "last": ([B,V] logits, cache), the final norm and the head at ONE
    position a row, its last valid one (``valid_len[b] - 1``): what a
    caller that samples a prompt's first token reads, at a ``C``-th of the
    head's work and of the result's bytes. The same norm and matrix on the
    same row of activations in both forms of the program; a dead row's is a
    row nobody reads. The pool is written alike either way. ``wanted``
    ([B] bool, with "last" only) names the rows whose logits the caller
    will read: where NO row is wanted (six in seven programs of long
    prompts: only a prompt's last chunk is sampled from) the norm and the
    head are not run at all and zeros come back, one ``lax.cond`` around
    them and nothing else; where any is, every row's come back as without
    it."""
    from kubeflow_tpu.models.decoder import decoder_forward

    if logits_at not in ("all", "last"):
        raise ValueError(f"unknown logits_at {logits_at!r}; one of all|last")
    if wanted is not None and logits_at != "last":
        raise ValueError('wanted rows are named with logits_at="last" only')
    if cfg.is_latent and lora is not None:
        raise NotImplementedError("LoRA over latent attention projections")
    whole_rows = table_rows     # a window layer's ring lies in its first pages
    if context_pages is not None and chunk_reads_context(
            cache, cfg, lora, paged_attn_impl):
        table_rows = table_rows[:, :min(context_pages, table_rows.shape[1])]
    if _chunk_in_place(cache, cfg, lora, paged_attn_impl):
        return _paged_chunk_in_place(params, cache, tokens, table_rows,
                                     start, valid_len, cfg, paged_attn_impl,
                                     logits_at, wanted)
    planes = tuple(n for n in _planes_of(cache)
                   if plane_kind(n) not in ("conv", *SEQUENCE_PLANES))
    num_pages, pg = _pool_geometry(cache, cfg)
    pages_of = _pages_by_kind(cache)
    b, c = tokens.shape
    kv_quant = "ks" in cache
    # [L, P, pg, KV*Dh]
    packed = "k" in cache and cache["k"].ndim == 4 \
        and not kept_as_rows(cfg)
    # Gather each slot's visible cache row, every plane: [L,B,ctx*pg,...]
    # (the bucket covers the chunk's own pages too: the [start, start+C)
    # update-slice window below). A window layer's logical page ``i`` lies
    # in its ring (``ring_table``): the pages its chunk and the window
    # before it touch come back in their places, every older place holds a
    # newer page's rows, which the window's mask never lets a query see.
    # Pad the rows by one chunk of scratch positions so the final chunk's
    # C-wide dynamic_update_slice window can never clamp and overwrite
    # earlier KV (prefix-cache hits start chunks at page — not chunk —
    # alignment, so start + C may exceed the bucket edge). The scratch tail
    # is causal-masked (kv position > any query position) and never
    # scattered back to pages.
    tables = {"attention": table_rows}
    if "window" in pages_of:
        tables["window"] = ring_table(
            whole_rows, jnp.zeros((b,), jnp.int32), table_rows.shape[1], cfg)
    rows = {n: jax.vmap(lambda pool, n=n: paged_gather(
        pool, tables[plane_kind(n)]))(cache[n]) for n in planes}
    if packed:
        rows = {n: r.reshape(*r.shape[:3], cfg.n_kv_heads, cfg.head_dim)
                for n, r in rows.items()}
    as_rows = kept_as_rows(cfg)
    if as_rows:     # [L,B,ctx*pg*rows,D]: a token's heads, rows in order
        rows = {n: r.reshape(*r.shape[:2], -1, as_rows, r.shape[-1])
                for n, r in rows.items()}
    if kv_quant:
        from kubeflow_tpu.ops.quantization import dequantize_kv, quantize_kv

        dt = cfg.activation_dtype
        rows = {"k": dequantize_kv(rows["k"], rows["ks"], dt),
                "v": dequantize_kv(rows["v"], rows["vs"], dt)}
    caches = {n: jnp.pad(row, [(0, 0), (0, 0), (0, c)]
                         + [(0, 0)] * (row.ndim - 3))
              for n, row in rows.items()}
    if "conv" in cache:
        caches["conv"] = _chunk_state_before(cache["conv"], table_rows,
                                             start, pg)
    entries = {}
    for kind in set(SEQUENCE_PLANES) & set(pages_of):
        # a row's state where its first page's id says, zeros at a start
        entry = entries[kind] = _sequence_entry(
            whole_rows, valid_len > 0, 0, pages_of[kind], pages_of[kind])
        caches.update({n: jax.vmap(
            lambda plane, entry=entry: _state_at(
                plane, entry, start == 0))(cache[n])
            for n in SEQUENCE_PLANES[kind]})
    caches["len"] = start
    lr = None if lora is None else {**lora, "aidx": adapter_idx}
    logits, filled, _ = decoder_forward(params, tokens, cfg, kv_caches=caches,
                                        attn_impl=attn_impl,
                                        valid_len=valid_len, lora=lr,
                                        moe_capacity_per_row=True,
                                        skip_head=logits_at == "last")
    if logits_at == "last":     # ``logits`` is the final norm's output here
        logits = _last_logits(params, logits, valid_len, cfg, wanted,
                              normed=True)
    # Scatter the chunks' tokens back into the pool per (page, offset):
    # row b's position start[b]+i lands on table_rows[b, (start[b]+i)//pg]
    # at offset (start[b]+i)%pg. Invalid rows (past valid_len, or an
    # unmapped/-1 page) aim out of bounds and drop.
    written = {n: jnp.stack(
        [jax.lax.dynamic_slice_in_dim(filled[n][:, r], start[r], c, axis=1)
         for r in range(b)], axis=1) for n in rows}           # [L,B,C,...]
    pidx, off = _chunk_write_index(table_rows, start, valid_len, c, pg,
                                   num_pages)
    if kv_quant:
        written["k"], written["ks"] = quantize_kv(written["k"])
        written["v"], written["vs"] = quantize_kv(written["v"])
    if packed:
        out = {n: _scatter_flat(cache[n], pidx, off,
                                w.reshape(*w.shape[:3], -1))
               for n, w in written.items()}
    else:
        if as_rows:
            pidx, off = pidx[..., None], _token_rows(off, as_rows)
        out = {n: cache[n].at[:, pidx, off].set(written[n], mode="drop")
               for n in planes if plane_kind(n) == "attention"}
    if "window" in pages_of:
        widx, _ = _chunk_write_index(whole_rows, start, valid_len, c, pg,
                                     pages_of["window"], cfg)
        if as_rows:
            widx = widx[..., None]
        out.update({n: cache[n].at[:, widx, off].set(written[n], mode="drop")
                    for n in planes if plane_kind(n) == "window"})
    if "conv" in cache:
        out["conv"] = _chunk_state_after(cache["conv"], filled["conv"],
                                         table_rows, start, valid_len, c, pg)
    for kind, entry in entries.items():
        out.update({n: cache[n].at[:, entry].set(
            filled[n].astype(cache[n].dtype), mode="drop")
            for n in SEQUENCE_PLANES[kind]})
    if MOE_ROWS in cache:
        # The gathered form runs the model's own forward pass, which keeps
        # no sums; its expert layers' rows are counted by what it was given
        # (every row of every chunk is routed) and the router's choices are
        # not seen here: the in-place form (a chip's) counts them.
        out[MOE_ROWS] = cache[MOE_ROWS]
    return logits, out


def _scatter_flat(pool: jax.Array, pidx: jax.Array, off: jax.Array,  # traced
                  written: jax.Array) -> jax.Array:
    """``pool[l, pidx, off] = written[l]`` for every layer, as ONE scatter
    into the pool viewed flat ``[L*P, page, W]`` (the decode write's
    addressing): scattered under a leading layer axis, a packed-row pool of
    two layers is re-laid out whole, four pool-sized copies a chunk program
    (the chip's compiler, PR 35). A dropped write (``pidx`` past the
    layer's pages) aims past the END of the flat pool."""
    layers, pages = pool.shape[:2]
    flat = pool.reshape(layers * pages, *pool.shape[2:])
    at = jnp.where(
        pidx < pages,
        pidx + pages * jnp.arange(layers, dtype=jnp.int32)[:, None, None],
        layers * pages)
    return flat.at[at, off].set(written, mode="drop").reshape(pool.shape)


def _chunk_state_before(state: jax.Array, table_rows: jax.Array,  # traced
                        start: jax.Array, pg: int) -> jax.Array:
    """The conv layers' state each row's chunk starts from, [Lc,B,taps-1,D]:
    what the page of position ``start - 1`` ends in, zeros at a sequence's
    start and for a row without that page."""
    before = jnp.maximum(start - 1, 0) // pg
    prev = jnp.take_along_axis(
        table_rows, jnp.clip(before, 0, table_rows.shape[1] - 1)[:, None],
        axis=1)[:, 0]
    tail = state[:, jnp.clip(prev, 0, state.shape[1] - 1)]
    return jnp.where(((start > 0) & (prev >= 0))[None, :, None, None],
                     tail, 0)


def _chunk_state_after(state: jax.Array, zs: jax.Array,  # traced
                       table_rows: jax.Array, start: jax.Array,
                       valid_len: jax.Array, c: int, pg: int) -> jax.Array:
    """The state plane with the chunks' tails written: for every page a
    row's VALID tokens touch, the state as it stood after the last of them
    in that page (the page's end, or the chunk's last valid token), so that
    the next chunk, the first decode step and a later request that reuses
    whole pages each find theirs. ``zs`` [Lc,B,taps-1+C,D]
    (``layers.conv_block``): the state after chunk position ``i`` is its
    rows ``i+1 .. i+taps-1``. A dead row and an unmapped page aim past the
    pool and drop."""
    num_pages, keep = state.shape[1], state.shape[2]
    ends = (pg - 1 - start % pg)[:, None] + pg * jnp.arange(
        -(-c // pg) + 1, dtype=jnp.int32)[None, :]              # [B,M]
    i = jnp.minimum(ends, valid_len[:, None] - 1)
    pslot = (start[:, None] + i) // pg
    page = jnp.take_along_axis(
        table_rows, jnp.clip(pslot, 0, table_rows.shape[1] - 1), axis=1)
    ok = (i >= 0) & (page >= 0) & (pslot < table_rows.shape[1]) \
        & (page < num_pages)
    rows = jnp.maximum(i, 0)[..., None] + 1 + jnp.arange(keep)   # [B,M,keep]
    tails = zs[:, jnp.arange(zs.shape[1])[:, None, None], rows]
    return state.at[:, jnp.where(ok, page, num_pages)].set(tails,
                                                           mode="drop")


def _chunk_write_index(table_rows: jax.Array, start: jax.Array,  # traced
                       valid_len: jax.Array, c: int, pg: int, num_pages: int,
                       ring_cfg: Optional[DecoderConfig] = None):
    """Where the ``C`` positions of each row's chunk are written: (page
    [B,C], offset [B,C]) off the rows' page tables. A position past its
    row's ``valid_len``, past the table or on an unmapped page gets page
    ``num_pages``, one past the pool: the write drops. ``ring_cfg``: a
    window layer's planes, where a position's page is its ring's
    (``ring_table``)."""
    i = jnp.arange(c, dtype=jnp.int32)[None, :]
    pos = start[:, None] + i
    pslot = pos // pg
    if ring_cfg is not None:
        pslot_at = _ring_slot(pslot, ring_cfg, table_rows.shape[1])
    else:
        pslot_at = jnp.clip(pslot, 0, table_rows.shape[1] - 1)
    page_id = jnp.take_along_axis(table_rows, pslot_at, axis=1)
    ok = (i < valid_len[:, None]) & (page_id >= 0) \
        & (pslot < table_rows.shape[1]) & (page_id < num_pages)
    return jnp.where(ok, page_id, num_pages), pos % pg


def _chunk_in_place(cache: dict, cfg: DecoderConfig, lora,
                    attn_impl: str) -> bool:
    """Whether a chunk program over this cache is built in place
    (``_paged_chunk_in_place``): a latent pool always; a per-head pool
    where the engine runs the kernels ("pallas") and
    ``paged_chunk_attention`` takes its planes. What stays on the gathered
    form: int8 pools (scale planes), packed rows and the conv state beside
    them, a call with LoRA, planes the kernel cannot part by head. A window
    layer's planes are K and V per head like a global layer's and go the
    same way; a linear, ssm, ssd or parallel layer's state planes ride beside
    them (their operator reads a state a row, not pages)."""
    if cfg.is_latent:
        return True
    from kubeflow_tpu.ops.paged_attention import chunk_attention_supported

    planes = set(_planes_of(cache)).difference(
        *SEQUENCE_PLANES.values())
    k = cache[next(n for n in ("k", *WINDOW_PLANES) if n in cache)]
    rows = kept_as_rows(cfg)    # [L, P, page * rows, D], else [L, P, page, KV, D]
    heads = (rows, k.shape[-1]) if rows else k.shape[3:]
    return (attn_impl == "pallas" and lora is None
            and planes in ({"k", "v"}, {"k", "v", *WINDOW_PLANES},
                           set(WINDOW_PLANES))
            and k.ndim == (4 if rows else 5)
            and chunk_attention_supported(*heads, k.dtype))


#: The kinds of layer a chunk program can carry the decode step over
#: (``chunk_carries_step``): each one's chunk operator and decode operator
#: are held side by side in ONE program, against the chunk program and then
#: the decode step, by tests of its own (tests/test_serve_mixed_program.py).
#: "attention": per-head planes or a latent pool, whatever the feed-forward.
#: "parallel": the same attention beside an SSD mixer whose state a sequence
#: is ONE entry of planes of its own; the chunk's row and the slots' rows are
#: different sequences, so ``ssd_chunk``'s one entry and ``ssd_step``'s are
#: disjoint, as their pages are. "linear": a KDA mixer whose recurrent
#: matrices and conv tails are ONE entry a sequence alike (``kda_chunk`` and a
#: scatter of the end state for the chunk's rows, ``kda_step`` in place for the
#: slots'). "ssd": the parallel layer's mixer as a block's only operator, the
#: same two kernels on the same planes (tests/test_serve_nemotronh.py). The
#: kinds that keep a ring or a conv tail, an ssm state, or end in a stateless
#: tail are the next names here (ROADMAP Speed 0).
STEP_CARRYING_KINDS = frozenset({"attention", "parallel", "linear", "ssd"})


def chunk_carries_step(cache: dict, cfg: DecoderConfig, lora,
                       attn_impl: str) -> bool:
    """Whether a chunk program over this cache can carry the slots' decode
    step (``paged_mixed_step``): the kernels are on, the chunk meets the
    pool in place (``_chunk_in_place``: no int8 pool, no packed rows, no
    call with LoRA) and every layer is of a kind whose two operators are
    held side by side in one program (``STEP_CARRYING_KINDS``)."""
    return (attn_impl == "pallas"
            and set(cfg.kinds) <= STEP_CARRYING_KINDS
            and _chunk_in_place(cache, cfg, lora, attn_impl))


def chunk_rows_follow(cfg: DecoderConfig) -> bool:
    """Whether the rows of ONE chunk program may be consecutive chunks of one
    sequence (the engine's rows ahead, ``LLMEngine._rows_of``), given that
    the chunk meets the pool in place: every layer of a kind whose operator
    hands a chunk's end to the row behind it inside the program
    (``_pool_block``). "attention": nothing but the keys in the pool, which
    every row writes before any attends. "ssd": the state and the conv tail a
    sequence, which ``_ssd`` hands from a row to the row that follows it
    (tests/test_serve_nemotronh.py). The kinds that keep a state a sequence
    by another operator ("linear", "ssm"; "parallel", whose plans are one
    row wide where it is served), a ring or a conv layer's tail are the next
    names here, each beside tests of its own operators."""
    return set(cfg.kinds) <= {"attention", "ssd"}


def chunk_reads_context(cache: dict, cfg: DecoderConfig, lora,
                        attn_impl: str) -> bool:
    """Whether ``paged_chunk_prefill`` over this cache reads its
    ``context_pages``. The in-place form of a per-head pool does not: its
    kernel takes the table whole and skips what lies behind its chunk, so
    every context bucket is ONE program, and every row of every program
    one call that is traced once (``paged_chunk_attention``)."""
    return cfg.is_latent or not _chunk_in_place(cache, cfg, lora, attn_impl)


def _last_logits(params: Params, x: jax.Array, valid_len: jax.Array,  # traced
                 cfg: DecoderConfig, wanted=None,
                 normed: bool = False) -> jax.Array:
    """[B,C,D] -> [B,V] float32: ``_head_logits`` of each row at its last
    valid position (a dead row's position 0); zeros, and no head, where
    ``wanted`` ([B] bool) is given and names no row."""
    def head():
        at = jnp.maximum(valid_len - 1, 0)
        last = jnp.take_along_axis(x, at[:, None, None], axis=1)  # [B,1,D]
        return _head_logits(params, last, cfg, normed)[:, 0]

    if wanted is None:
        return head()
    return jax.lax.cond(
        jnp.any(wanted), head,
        lambda: jnp.zeros((x.shape[0], cfg.vocab_size), jnp.float32))


def _paged_chunk_in_place(params: Params, cache: dict,  # traced
                          tokens: jax.Array, table_rows: jax.Array,
                          start: jax.Array, valid_len: jax.Array,
                          cfg: DecoderConfig, attn_impl: str,
                          logits_at: str = "all", wanted=None):
    """``paged_chunk_prefill`` built like the decode step and not like
    ``decoder_forward``'s cache path (``_pool_forward`` at a chunk a row):
    a layer writes every row's ``C`` cache rows in place at ``(layer*P +
    page, offset)`` and then each prompt attends, causally, over its own
    pages where they lie (the chunk's own among them; the kernels skip the
    pages behind the chunk). Same contract: only a row's first
    ``valid_len`` positions write; ``table_rows`` holds the pages looked
    at."""
    x, flat = _pool_forward(params, cache, tokens, table_rows, start,
                            valid_len, cfg, attn_impl, tail_at=logits_at,
                            wanted=wanted)
    if logits_at == "last" and cfg.stateless_tail:
        # the tail ran at each row's last valid position only: x is [B,1,D]
        valid_len = jnp.minimum(valid_len, 1)
    logits = _last_logits(params, x, valid_len, cfg, wanted) \
        if logits_at == "last" else _head_logits(params, x, cfg)
    return logits, _pool_planes(flat, cache)
