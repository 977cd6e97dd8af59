"""Paged-KV handoff between prefill- and decode-specialized engines —
the transfer contract of disaggregated serving (ROADMAP item 2; the
DistServe/Splitwise motif, TPU-native).

Prefill and decode have opposite compute profiles: prefill is
FLOPs-bound (one big causal block over the prompt), decode is
HBM-bandwidth-bound (one token per step against the whole KV). A
unified engine interleaves them on one chip, so a long prefill
head-of-line-blocks every resident decode stream's tokens. Splitting
the fleet into role-specialized pools removes that interference — IF
the prompt's KV can move from the prefill chip to the decode chip. The
page is the natural transfer unit: the prefill side exports the slot's
pages (one batched device→host fetch per admit round), the decode side
adopts them into its OWN ``PageAllocator`` pool (alloc + scatter upload
+ page-table row rebuild, ``owner=`` stamped so
``KFTPU_SANITIZE=refcount`` attributes leaks across the boundary).

Ownership protocol (who owns pages when):

1. **Export** (prefill engine, scheduler thread): the first token is
   sampled, the slot's KV is fetched to host, and the slot is freed —
   but its page references move to a HOLD keyed by request id, not to
   the free list. The payload is now host memory; the pages back it
   until the decode side confirms receipt.
2. **Ack** (prefill model server): the decode replica answered the
   handoff POST — the payload bytes are in its memory — so the hold is
   released (``engine.complete_handoff``). The release is marshalled
   through a queue onto the scheduler thread; the allocator stays
   single-owner.
3. **Failure = recompute**: if the decode side never acks (connect
   failure, 5xx, death mid-POST), ``engine.fail_handoff`` frees the
   hold and the model server re-submits the request LOCALLY as a
   unified request — the prefix cache usually makes the recompute one
   admission. A hold whose request is cancelled or past its deadline is
   reaped by the scheduler like any abandoned request, so a killed
   server can never strand pages (the mid-handoff SIGKILL chaos
   scenario audits exactly this).

Adoption seeds the decode slot at the exact state ``_admit_with_token``
would have left it: ``length = plen`` (the prompt's KV is written; the
first token's is not), ``last_token = first_token``, and the request's
``prompt_tokens`` carry ``prompt + [first_token]`` so recompute
preemption and speculative context reconstruction keep their
invariants. Greedy outputs are therefore token-identical to the unified
path (pinned in tests).

Wire format: one JSON metadata line + raw little-endian KV bytes
(dtype/shape in the metadata — bf16 rides as raw ml_dtypes bytes, no
pickle). Rides ``POST /v1/handoff`` with the usual ``X-Kftpu-*``
headers, so a handed-off request keeps ONE trace with a new ``handoff``
phase between ``prefill`` and the decode side's ``queued``/``decode``.

Wire format v2 (int8 KV pools, ``kv_cache_dtype="int8"``): the metadata
gains a ``cache_dtype`` tag plus ``scale_dtype``/``scale_shape``, and the
per-token-per-head f32 scale blobs ride after the page bytes —
``K + V + scale_K + scale_V``. A v1 blob carries no tag and decodes
exactly as before (scales come back ``None``), so mixed-dtype fleets
interoperate during a rollout: the adopting side rejects a cache-dtype
mismatch explicitly instead of misreading bytes. int8 payloads are the
wire-bytes win the bench rounds measure: ~half the KV bytes per handoff
and per host-tier demotion at 4/Dh scale overhead.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import numpy as np


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, including the ml_dtypes extras (bfloat16)
    numpy itself does not know."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


@dataclasses.dataclass
class HandoffPayload:
    """One prefilled request's transferable state: identity + sampling
    contract + the prompt's KV as contiguous host arrays
    ``[L, plen, KV, Dh]`` (page structure is re-imposed by the adopting
    pool — its page size, its free list, its refcounts)."""

    request_id: str
    prompt_tokens: list[int]        # the plen tokens whose KV rides along
    first_token: int                # sampled on the prefill side (TTFT)
    max_new_tokens: int             # REMAINING decode budget (>= 1)
    temperature: float
    top_k: int
    top_p: float
    stop_token: Optional[int]
    qos: str
    kv_k: np.ndarray
    kv_v: np.ndarray
    # int8 pools only (wire v2): per-token-per-head f32 scales
    # ``[L, plen, KV]`` — kv shape minus head_dim (quantize_kv layout).
    kv_scale_k: Optional[np.ndarray] = None
    kv_scale_v: Optional[np.ndarray] = None

    @property
    def kv_len(self) -> int:
        return int(self.kv_k.shape[1])

    @property
    def cache_dtype(self) -> Optional[str]:
        """"int8" when scales ride along; None = full-dtype KV."""
        return "int8" if self.kv_scale_k is not None else None

    @property
    def wire_bytes(self) -> int:
        """KV payload bytes as they ride the wire (pages + scale blobs,
        metadata line excluded) — the handoff wire-bytes series' source,
        computed without re-encoding."""
        n = self.kv_k.nbytes + self.kv_v.nbytes
        if self.kv_scale_k is not None:
            n += self.kv_scale_k.nbytes + self.kv_scale_v.nbytes
        return n

    def validate(self) -> None:
        if self.kv_k.shape != self.kv_v.shape:
            raise ValueError("kv_k/kv_v shape mismatch")
        if self.kv_k.ndim != 4:
            raise ValueError(
                f"KV must be [L, plen, KV, Dh]; got {self.kv_k.shape}")
        if self.kv_len != len(self.prompt_tokens):
            raise ValueError(
                f"KV covers {self.kv_len} positions but the payload "
                f"names {len(self.prompt_tokens)} prompt tokens")
        if self.max_new_tokens < 1:
            raise ValueError("handoff with no decode budget left")
        if (self.kv_scale_k is None) != (self.kv_scale_v is None):
            raise ValueError("kv scale blobs must come as a pair")
        if self.kv_scale_k is not None:
            if self.kv_k.dtype != np.int8:
                raise ValueError(
                    "scale blobs ride only with int8 KV pages; got "
                    f"{self.kv_k.dtype}")
            want = self.kv_k.shape[:-1]
            if (self.kv_scale_k.shape != want
                    or self.kv_scale_v.shape != want):
                raise ValueError(
                    f"scale shape must be KV shape minus head_dim {want}; "
                    f"got {self.kv_scale_k.shape}/{self.kv_scale_v.shape}")

    # -- wire format -------------------------------------------------------

    def to_wire(self) -> bytes:
        """JSON metadata line + raw K bytes + raw V bytes (+ scale K/V
        bytes when the pool is int8 — wire v2)."""
        k = np.ascontiguousarray(self.kv_k)
        v = np.ascontiguousarray(self.kv_v)
        meta = {
            "request_id": self.request_id,
            "prompt_tokens": list(self.prompt_tokens),
            "first_token": int(self.first_token),
            "max_new_tokens": int(self.max_new_tokens),
            "temperature": float(self.temperature),
            "top_k": int(self.top_k),
            "top_p": float(self.top_p),
            "stop_token": self.stop_token,
            "qos": self.qos,
            "dtype": str(k.dtype),
            "shape": list(k.shape),
        }
        blob = k.tobytes() + v.tobytes()
        if self.kv_scale_k is not None:
            sk = np.ascontiguousarray(self.kv_scale_k, np.float32)
            sv = np.ascontiguousarray(self.kv_scale_v, np.float32)
            meta["cache_dtype"] = "int8"
            meta["scale_dtype"] = str(sk.dtype)
            meta["scale_shape"] = list(sk.shape)
            blob += sk.tobytes() + sv.tobytes()
        return json.dumps(meta).encode() + b"\n" + blob

    @classmethod
    def from_wire(cls, data: bytes) -> "HandoffPayload":
        head, sep, raw = data.partition(b"\n")
        if not sep:
            raise ValueError("handoff payload missing metadata line")
        meta = json.loads(head)
        dtype = _np_dtype(meta["dtype"])
        shape = tuple(int(x) for x in meta["shape"])
        n = int(np.prod(shape)) * dtype.itemsize
        sk = sv = None
        sn = 0
        if meta.get("cache_dtype") is not None:
            sdtype = _np_dtype(meta["scale_dtype"])
            sshape = tuple(int(x) for x in meta["scale_shape"])
            sn = int(np.prod(sshape)) * sdtype.itemsize
        if len(raw) != 2 * n + 2 * sn:
            raise ValueError(
                f"handoff payload truncated: {len(raw)} KV bytes, "
                f"expected {2 * n + 2 * sn}")
        kv_k = np.frombuffer(raw[:n], dtype=dtype).reshape(shape)
        kv_v = np.frombuffer(raw[n:2 * n], dtype=dtype).reshape(shape)
        if sn:
            sk = np.frombuffer(
                raw[2 * n:2 * n + sn], dtype=sdtype).reshape(sshape)
            sv = np.frombuffer(raw[2 * n + sn:], dtype=sdtype).reshape(sshape)
        payload = cls(
            request_id=str(meta["request_id"]),
            prompt_tokens=[int(t) for t in meta["prompt_tokens"]],
            first_token=int(meta["first_token"]),
            max_new_tokens=int(meta["max_new_tokens"]),
            temperature=float(meta["temperature"]),
            top_k=int(meta["top_k"]),
            top_p=float(meta["top_p"]),
            stop_token=(None if meta["stop_token"] is None
                        else int(meta["stop_token"])),
            qos=str(meta["qos"]),
            kv_k=kv_k, kv_v=kv_v, kv_scale_k=sk, kv_scale_v=sv)
        payload.validate()
        return payload


def pages_to_wire(kv_k: np.ndarray, kv_v: np.ndarray, *,
                  kv_sk: Optional[np.ndarray] = None,
                  kv_sv: Optional[np.ndarray] = None) -> bytes:
    """Raw page-byte encoding shared with the KV host tier
    (serve/kvtier.py): the same JSON-metadata-line + little-endian raw
    K/V layout ``to_wire`` ships over ``POST /v1/handoff``, minus the
    request identity — a demoted page block is content, not a request.
    ``kv_*`` are any equal-shape arrays (host-tier use: ``[L, pg, KV,
    Dh]`` per page block). int8 pools pass ``kv_sk``/``kv_sv`` — the
    per-token-per-head scale rows ``[L, pg, KV]`` — and get the tagged
    v2 layout ``K + V + scale_K + scale_V``."""
    k = np.ascontiguousarray(kv_k)
    v = np.ascontiguousarray(kv_v)
    meta = {"dtype": str(k.dtype), "shape": list(k.shape)}
    blob = k.tobytes() + v.tobytes()
    if kv_sk is not None:
        sk = np.ascontiguousarray(kv_sk, np.float32)
        sv = np.ascontiguousarray(kv_sv, np.float32)
        meta["cache_dtype"] = "int8"
        meta["scale_dtype"] = str(sk.dtype)
        meta["scale_shape"] = list(sk.shape)
        blob += sk.tobytes() + sv.tobytes()
    return json.dumps(meta).encode() + b"\n" + blob


def pages_from_wire(data: bytes) -> tuple[
        np.ndarray, np.ndarray,
        Optional[np.ndarray], Optional[np.ndarray]]:
    """Decode ``pages_to_wire`` bytes back into (k, v, scale_k, scale_v)
    views — zero-copy ``frombuffer``, so host→device promotion pays one
    upload, not an extra host memcpy. Scales are ``None`` for untagged
    (v1 / full-dtype) blobs."""
    head, sep, raw = data.partition(b"\n")
    if not sep:
        raise ValueError("page wire blob missing metadata line")
    meta = json.loads(head)
    dtype = _np_dtype(meta["dtype"])
    shape = tuple(int(x) for x in meta["shape"])
    n = int(np.prod(shape)) * dtype.itemsize
    sk = sv = None
    sn = 0
    if meta.get("cache_dtype") is not None:
        sdtype = _np_dtype(meta["scale_dtype"])
        sshape = tuple(int(x) for x in meta["scale_shape"])
        sn = int(np.prod(sshape)) * sdtype.itemsize
    if len(raw) != 2 * n + 2 * sn:
        raise ValueError(
            f"page wire blob truncated: {len(raw)} bytes, "
            f"expected {2 * n + 2 * sn}")
    kv_k = np.frombuffer(raw[:n], dtype=dtype).reshape(shape)
    kv_v = np.frombuffer(raw[n:2 * n], dtype=dtype).reshape(shape)
    if sn:
        sk = np.frombuffer(
            raw[2 * n:2 * n + sn], dtype=sdtype).reshape(sshape)
        sv = np.frombuffer(raw[2 * n + sn:], dtype=sdtype).reshape(sshape)
    return kv_k, kv_v, sk, sv


def payload_from_export(req, kv_k: np.ndarray, kv_v: np.ndarray,
                        plen: int,
                        kv_sk: Optional[np.ndarray] = None,
                        kv_sv: Optional[np.ndarray] = None) -> HandoffPayload:
    """Build the payload at flush time: ``kv_*`` are the fetched host
    arrays (whole pages — trim to ``plen``), and the decode budget is the
    original budget minus the first token the prefill side already
    emitted. int8 pools pass the fetched scale rows too."""
    p = req.params
    payload = HandoffPayload(
        request_id=req.id,
        prompt_tokens=list(req.prompt_tokens),
        first_token=int(req.output_tokens[0]),
        max_new_tokens=int(p.max_new_tokens) - 1,
        temperature=float(p.temperature),
        top_k=int(p.top_k),
        top_p=float(p.top_p),
        stop_token=p.stop_token,
        qos=req.qos,
        kv_k=np.ascontiguousarray(kv_k[:, :plen]),
        kv_v=np.ascontiguousarray(kv_v[:, :plen]),
        kv_scale_k=(None if kv_sk is None
                    else np.ascontiguousarray(kv_sk[:, :plen])),
        kv_scale_v=(None if kv_sv is None
                    else np.ascontiguousarray(kv_sv[:, :plen])))
    payload.validate()
    return payload
