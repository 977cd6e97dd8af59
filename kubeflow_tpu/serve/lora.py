"""Multi-tenant LoRA serving: adapter registry + batched multi-adapter
dispatch math (ROADMAP item 4 — the more-MODELS-per-chip axis).

Thousands of fine-tuned variants cannot mean thousands of engines: one
engine serves N rank-r LoRA adapters over ONE set of shared base
weights (the S-LoRA/Punica motif, TPU-native). The pieces:

- **Packed adapter buffers.** Every loaded adapter occupies one SLOT of
  a packed device buffer per target projection: ``A [L, S, d_in, r]``
  and ``B [L, S, r, d_out]`` (S = ``LoRASpec.max_adapters`` slots, r =
  the spec's rank cap — lower-rank adapters zero-pad, which leaves
  ``A@B`` exact). The buffers ride into every dispatch whole, so the
  trace set is FIXED regardless of which adapters are hot: adapter
  churn swaps slot contents through a donated scatter, never shapes —
  the packed buffer IS the pow2 pad of the active-adapter set, and the
  recompile sanitizer sees zero steady-state retraces across churn.
- **Batched multi-adapter dispatch.** Each engine slot carries an
  ``adapter_idx`` (device-resident, serve/device_state.py); the decode
  and prefill dispatches gather each row's slices and apply the
  low-rank update as one gather + two einsums per target
  (``lora_contrib``). ``adapter_idx = -1`` multiplies the delta by an
  exact 0.0, so base-traffic rows are bit-identical to a LoRA-free
  engine — one compiled program serves every base/adapter mix.
- **Hot-load / evict.** The registry LRU-loads adapters into slots on
  demand (weights from the artifact store or an in-process source) and
  evicts only ref-0 adapters; every reference is owner-stamped so
  ``KFTPU_SANITIZE=refcount`` names leakers and ``assert_quiescent``
  stays exact per owner — the same discipline as the page allocator.

Correctness contract: greedy decode under every loaded adapter is
token-identical to a single-model engine running the MERGED weights
(``merged_params``; tests/test_serve_lora.py), and
prefix-cache KV is namespaced per adapter (engine._kv_match) so two
tenants sharing a prompt never share each other's KV.
"""

from __future__ import annotations

import dataclasses
import io
import logging
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubeflow_tpu.models.config import DecoderConfig
from kubeflow_tpu.models.decoder import Params
# The traced per-row low-rank math and the scan-threading helpers live
# with the model layers (the prefill forward applies them there);
# re-exported here so engine/paged code imports one LoRA surface.
from kubeflow_tpu.models.layers import (  # noqa: F401
    apply_lora_layer, index_layer, layer_view, lora_contrib, slice_layers,
)

logger = logging.getLogger("kubeflow_tpu.serve.lora")

#: Attention projections LoRA may target, with (d_in, d_out) factories.
LORA_TARGETS = ("wq", "wk", "wv", "wo")


class AdapterSlotsExhausted(Exception):
    """Every adapter slot is referenced by a live request: the arrival
    cannot hot-load until one releases. The engine treats this as
    admission backpressure (requeue, not failure) — exactly the page
    allocator's exhaustion discipline."""


def target_dims(cfg: DecoderConfig, target: str) -> tuple[int, int]:
    """(d_in, d_out) of one attention projection's LoRA factors."""
    d = cfg.hidden
    if target == "wq":
        return d, cfg.n_heads * cfg.head_dim
    if target == "wk" or target == "wv":
        return d, cfg.n_kv_heads * cfg.head_dim
    if target == "wo":
        return cfg.n_heads * cfg.head_dim, d
    raise ValueError(f"unknown LoRA target {target!r}; one of {LORA_TARGETS}")


@dataclasses.dataclass
class AdapterSpec:
    """One registered adapter. ``weights`` maps target -> (A [L, d_in, r],
    B [L, r, d_out]) numpy/JAX arrays; ``source`` is a lazy alternative
    (called once, at hot-load — the artifact-store pull path). ``alpha``
    scales the delta as alpha/rank (the classic LoRA scaling)."""

    name: str
    rank: int
    alpha: float = 16.0
    weights: Optional[dict[str, tuple]] = None
    source: Optional[Callable[[], dict[str, tuple]]] = None

    @property
    def scale(self) -> float:
        return self.alpha / max(self.rank, 1)

    def resolve_weights(self) -> dict[str, tuple]:
        if self.weights is not None:
            return self.weights
        if self.source is None:
            raise ValueError(f"adapter {self.name!r} has no weights/source")
        w = self.source()
        return w


def init_adapter_weights(key: jax.Array, cfg: DecoderConfig, rank: int,
                         targets: Sequence[str] = ("wq", "wv"),
                         scale: float = 0.5) -> dict[str, tuple]:
    """Random nonzero A/B factors (synthetic fine-tunes for tests and
    loadgen). Real LoRA training initializes B to zero; a SERVED adapter
    has trained nonzero B — a zero-delta adapter would make every
    token-identity assertion vacuously true, so both factors draw."""
    out: dict[str, tuple] = {}
    for t in targets:
        din, dout = target_dims(cfg, t)
        key, ka, kb = jax.random.split(key, 3)
        a = jax.random.normal(ka, (cfg.n_layers, din, rank),
                              jnp.float32) * (scale / np.sqrt(din))
        b = jax.random.normal(kb, (cfg.n_layers, rank, dout),
                              jnp.float32) * (scale / np.sqrt(rank))
        out[t] = (np.asarray(a), np.asarray(b))
    return out


def adapter_delta(weights: dict[str, tuple], target: str,
                  scale: float) -> Optional[np.ndarray]:
    """Dense [L, d_in, d_out] delta of one target (None if untargeted)."""
    ab = weights.get(target)
    if ab is None:
        return None
    a, b = np.asarray(ab[0]), np.asarray(ab[1])
    return np.einsum("ldr,lro->ldo", a, b) * scale


def merged_params(params: Params, cfg: DecoderConfig,
                  spec: AdapterSpec) -> Params:
    """Base params with ``spec``'s delta FOLDED into the attention
    weights — the single-model reference the multi-adapter dispatch must
    be token-identical to (the acceptance-criteria oracle). Handles both
    the scanned ([L, ...]-stacked) and list-of-blocks layer layouts."""
    weights = spec.resolve_weights()
    out = jax.tree.map(lambda x: x, params)          # fresh containers

    def merge_attn(attn: dict, layer: Optional[int]) -> dict:
        attn = dict(attn)
        for t in LORA_TARGETS:
            delta = adapter_delta(weights, t, spec.scale)
            if delta is None:
                continue
            if layer is not None:
                delta = delta[layer]
            w = np.asarray(attn[t], np.float32)
            attn[t] = jnp.asarray(w + delta.reshape(w.shape),
                                  attn[t].dtype)
        return attn

    layers = out["layers"]
    if isinstance(layers, list):
        out["layers"] = [
            {**blk, "attn": merge_attn(blk["attn"], i)}
            for i, blk in enumerate(layers)]
    else:
        layers = dict(layers)
        layers["attn"] = merge_attn(layers["attn"], None)
        out["layers"] = layers
    return out


# -- artifact-store round trip -------------------------------------------------

def adapter_to_bytes(weights: dict[str, tuple], *, rank: int,
                     alpha: float) -> bytes:
    """Serialize adapter factors as an npz blob (the artifact-store
    payload: ``store.put_bytes`` + ``store.register`` publishes it;
    ``adapter_spec_from_store`` pulls it back lazily at hot-load)."""
    arrs: dict[str, np.ndarray] = {
        "__meta_rank": np.asarray([rank], np.int32),
        "__meta_alpha": np.asarray([alpha], np.float32),
    }
    for t, (a, b) in weights.items():
        arrs[f"{t}.a"] = np.asarray(a)
        arrs[f"{t}.b"] = np.asarray(b)
    buf = io.BytesIO()
    np.savez(buf, **arrs)
    return buf.getvalue()


def adapter_from_bytes(name: str, blob: bytes) -> AdapterSpec:
    with np.load(io.BytesIO(blob)) as z:
        rank = int(z["__meta_rank"][0])
        alpha = float(z["__meta_alpha"][0])
        weights: dict[str, tuple] = {}
        for key in z.files:
            if key.endswith(".a"):
                t = key[:-2]
                weights[t] = (z[f"{t}.a"], z[f"{t}.b"])
    return AdapterSpec(name=name, rank=rank, alpha=alpha, weights=weights)


def adapter_spec_from_store(store, name: str, uri: str, *, rank: int,
                            alpha: float = 16.0) -> AdapterSpec:
    """Registry entry whose weights pull from the platform artifact
    store at HOT-LOAD time (not registration) — registering a thousand
    tenants costs a thousand dict entries, not a thousand uploads."""

    def source() -> dict[str, tuple]:
        spec = adapter_from_bytes(name, store.get_bytes(store.resolve(uri)))
        return spec.resolve_weights()

    return AdapterSpec(name=name, rank=rank, alpha=alpha, source=source)


# -- the registry --------------------------------------------------------------

def _upload_slot(buffers: dict, slot, scale, updates: dict) -> dict:  # traced
    """Scatter one adapter's padded factors into its packed slot
    (donated in/out — a hot-load swaps slot contents, never shapes)."""
    out = dict(buffers)
    out["scale"] = buffers["scale"].at[slot].set(scale)
    tgt = dict(buffers["targets"])
    for t, (a, b) in updates.items():
        pa, pb = tgt[t]
        tgt[t] = (pa.at[:, slot].set(a), pb.at[:, slot].set(b))
    out["targets"] = tgt
    return out


class AdapterRegistry:
    """Registered adapters + the packed device buffers their hot slots
    live in.

    Thread contract: ``register``/``known``/``names`` are thread-safe
    (the model server's submit path checks membership from handler
    threads); slot state, refcounts and the device buffers are
    SCHEDULER-CONFINED like the page allocator — ``acquire``/``release``
    run on the engine scheduler thread only."""

    def __init__(self, cfg: DecoderConfig, *, max_adapters: int,
                 rank: int, targets: Sequence[str] = ("wq", "wv"),
                 dtype=None):
        if max_adapters < 1:
            raise ValueError("max_adapters must be >= 1")
        for t in targets:
            target_dims(cfg, t)                   # validates the name
        self.cfg = cfg
        self.max_adapters = int(max_adapters)
        self.rank = int(rank)
        self.targets = tuple(targets)
        dt = cfg.activation_dtype if dtype is None else dtype
        self._lock = threading.Lock()
        self._specs: dict[str, AdapterSpec] = {}   # guarded_by: _lock
        # Slot state below: lockfree: scheduler-confined
        self._slot_of: dict[str, int] = {}
        self._name_of: list[Optional[str]] = [None] * self.max_adapters
        self._lru: "OrderedDict[str, None]" = OrderedDict()
        self._refs: dict[str, int] = {}
        self._stamps: dict[str, list[str]] = {}
        self.last_hot_load: Optional[str] = None  # lockfree: scheduler-confined
        self.stats = {"acquires": 0, "hits": 0, "loads": 0,  # lockfree: scheduler-confined
                      "evictions": 0}
        from kubeflow_tpu.runtime.sanitize import enabled

        self.refcount_debug = enabled("refcount")
        L = cfg.n_layers
        S = self.max_adapters
        self.buffers: dict[str, Any] = {  # lockfree: scheduler-confined
            "scale": jnp.zeros((S,), jnp.float32),
            "targets": {},
        }
        for t in self.targets:
            din, dout = target_dims(cfg, t)
            self.buffers["targets"][t] = (
                jnp.zeros((L, S, din, self.rank), dt),
                jnp.zeros((L, S, self.rank, dout), dt))
        self._upload = jax.jit(_upload_slot, donate_argnums=(0,))

    # -- registration (thread-safe) ----------------------------------------

    def register(self, spec: AdapterSpec) -> AdapterSpec:
        if spec.rank < 1 or spec.rank > self.rank:
            raise ValueError(
                f"adapter {spec.name!r} rank {spec.rank} exceeds the "
                f"engine's packed rank cap {self.rank}")
        with self._lock:
            self._specs[spec.name] = spec
        return spec

    def known(self, name: str) -> bool:
        with self._lock:
            return name in self._specs

    def names(self) -> list[str]:
        with self._lock:
            return list(self._specs)

    def spec(self, name: str) -> AdapterSpec:
        with self._lock:
            return self._specs[name]

    # -- observability -----------------------------------------------------

    def resident(self) -> list[str]:
        """Adapters currently hot in a device slot (the
        ``kftpu_engine_adapters_resident`` series' label set)."""
        return [n for n in self._name_of if n is not None]

    def slot_idx(self, name: str) -> Optional[int]:
        return self._slot_of.get(name)

    def refs(self, name: str) -> int:
        return self._refs.get(name, 0)

    def pending_pressure(self) -> bool:
        """True when every slot is referenced — an arriving new tenant
        cannot hot-load until something drains. The engine folds this
        into the KV-tier pressure signal (HBM headroom is shared)."""
        free = sum(1 for n in self._name_of
                   if n is None or self._refs.get(n, 0) == 0)
        return free == 0

    def packed_bytes(self) -> int:
        total = 0
        for a, b in self.buffers["targets"].values():
            total += a.size * a.dtype.itemsize + b.size * b.dtype.itemsize
        return total

    def snapshot(self) -> dict:
        out = dict(self.stats)
        out["resident"] = len(self._slot_of)
        out["slots"] = self.max_adapters
        return out

    # -- refcount sanitizer -------------------------------------------------

    def _stamp(self, name: str, owner: Optional[str]) -> None:
        from kubeflow_tpu.runtime.sanitize import call_site

        label = owner if owner is not None else call_site((__file__,))
        self._stamps.setdefault(name, []).append(label)

    def _unstamp(self, name: str) -> None:
        stamps = self._stamps.get(name)
        if stamps:
            stamps.pop()
            if not stamps:
                del self._stamps[name]

    def leak_report_by_owner(self) -> dict:
        """owner -> outstanding adapter references (refcount mode; {}
        when quiescent) — the lora chaos suite's per-owner audit."""
        out: dict[str, int] = {}
        for name, n in self._refs.items():
            if n <= 0:
                continue
            for label in self._stamps.get(name, ()) or ["<unstamped>"]:
                out[label] = out.get(label, 0) + 1
        return out

    def assert_quiescent(self) -> None:
        held = {n: r for n, r in self._refs.items() if r > 0}
        if held:
            msg = f"adapter slot leak: {held}"
            if self.refcount_debug:
                msg += ("; outstanding references by owner: "
                        + ", ".join(f"{o}={n}" for o, n in
                                    sorted(self.leak_report_by_owner()
                                           .items())))
            raise AssertionError(msg)

    # -- acquire / release (scheduler thread) -------------------------------

    def acquire(self, name: str, owner: Optional[str] = None
                ) -> tuple[int, bool]:
        """One reference on ``name``'s slot, hot-loading on miss.
        Returns ``(slot_idx, hot_loaded)``. Raises ``KeyError`` for an
        unregistered name (the protocol layers' 404) and
        ``AdapterSlotsExhausted`` when every slot is referenced (the
        engine's admission-backpressure signal)."""
        with self._lock:
            spec = self._specs.get(name)
        if spec is None:
            raise KeyError(f"unknown model {name!r}: adapter not registered")
        self.stats["acquires"] += 1
        hot = False
        slot = self._slot_of.get(name)
        if slot is None:
            slot = self._load_slot(spec)
            hot = True
        else:
            self.stats["hits"] += 1
        self._refs[name] = self._refs.get(name, 0) + 1
        if self.refcount_debug:
            self._stamp(name, owner)
        self._lru.move_to_end(name)
        self.last_hot_load = name if hot else None
        return slot, hot

    def release(self, name: str, owner: Optional[str] = None) -> None:
        n = self._refs.get(name, 0) - 1
        assert n >= 0, f"double release of adapter {name!r}"
        self._refs[name] = n
        if self.refcount_debug:
            self._unstamp(name)

    def _load_slot(self, spec: AdapterSpec) -> int:
        """Place ``spec`` into a free slot, evicting the LRU ref-0
        resident if none is free, and scatter its padded factors into
        the packed buffers (ONE fixed-shape donated dispatch)."""
        slot = None
        for i, n in enumerate(self._name_of):
            if n is None:
                slot = i
                break
        if slot is None:
            victim = next((n for n in self._lru
                           if self._refs.get(n, 0) == 0), None)
            if victim is None:
                raise AdapterSlotsExhausted(
                    f"all {self.max_adapters} adapter slots referenced")
            slot = self._slot_of.pop(victim)
            self._lru.pop(victim, None)
            self._name_of[slot] = None
            self.stats["evictions"] += 1
            logger.info("evicting adapter %s (LRU) from slot %d",
                        victim, slot)
        weights = spec.resolve_weights()
        updates: dict[str, tuple] = {}
        L = self.cfg.n_layers
        dt = self.buffers["targets"][self.targets[0]][0].dtype
        for t in self.targets:
            din, dout = target_dims(self.cfg, t)
            pa = np.zeros((L, din, self.rank), dt)
            pb = np.zeros((L, self.rank, dout), dt)
            ab = weights.get(t)
            if ab is not None:
                a, b = np.asarray(ab[0]), np.asarray(ab[1])
                if a.shape != (L, din, spec.rank) \
                        or b.shape != (L, spec.rank, dout):
                    raise ValueError(
                        f"adapter {spec.name!r} target {t}: shapes "
                        f"{a.shape}/{b.shape} do not match "
                        f"{(L, din, spec.rank)}/{(L, spec.rank, dout)}")
                pa[:, :, :spec.rank] = a
                pb[:, :spec.rank, :] = b
            updates[t] = (jnp.asarray(pa), jnp.asarray(pb))
        self.buffers = self._upload(
            self.buffers, jax.device_put(np.int32(slot)),
            jax.device_put(np.float32(spec.scale)), updates)
        self._slot_of[spec.name] = slot
        self._name_of[slot] = spec.name
        self._lru[spec.name] = None
        self._lru.move_to_end(spec.name)
        self.stats["loads"] += 1
        return slot
