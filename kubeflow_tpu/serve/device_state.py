"""Device-resident decode scheduler state — the host-overhead half of the
hot-loop elimination (ISSUE 4 tentpole (a)).

Before this module, every decode round re-materialised the scheduler's
tensor-shaped state from host Python: eight ``[B]`` arrays
(tokens/lengths/live/temps/top_k/top_p/stops/budgets) rebuilt with numpy and
``jnp.asarray``-uploaded per dispatch, plus the FULL
``[B, max_pages_per_slot]`` page table. Each of those uploads pays the
per-dispatch host overhead the multi-step dispatch exists to amortize, and
the re-materialisation itself is host work serialized against device
compute.

Here the state lives on device, owned by the engine for the engine's
lifetime:

- **One full upload, ever** (per array, at construction). The counter in
  ``stats`` proves it: steady-state decode rounds perform ZERO full-array
  host→device uploads of scheduler state (``tests/test_serve_hotloop.py``
  asserts the counters stay flat while rounds accumulate).
- **Deltas, not snapshots.** Host-side scheduler events (admission into a
  slot, reap/cancel, preemption, a speculative round advancing a slot,
  page-table growth) mark the slot/row DIRTY; immediately before the next
  dispatch the engine flushes each dirty index through a small donated
  ``jit`` scatter — a handful of scalars (or one ``[mpp]`` row) per changed
  slot, instead of the whole batch every round.
- **The device is the mirror master in steady state.** The decode dispatch
  itself consumes the state and returns the advanced state (same donated
  buffers); because the device applies the exact finish rules the host
  scheduler does (stop token, budget, cache edge), a slot that decodes
  without host interference never needs a sync at all.

The dirty-set discipline (who marks what) lives in ``serve/engine.py``;
this module is the mechanism: the arrays, the scatter programs, and the
upload accounting.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

#: Per-slot scheduler state riding into every decode dispatch, in scatter
#: order. ``tokens`` = last sampled token (the next step's input);
#: ``lengths`` = its KV write position; ``live`` masks dead rows; the rest
#: are per-slot sampling params and the remaining token budget.
STATE_FIELDS = ("tokens", "lengths", "live", "temps", "top_k", "top_p",
                "stops", "budgets", "adapter")

_DTYPES = {"tokens": jnp.int32, "lengths": jnp.int32, "live": jnp.bool_,
           "temps": jnp.float32, "top_k": jnp.int32, "top_p": jnp.float32,
           "stops": jnp.int32, "budgets": jnp.int32, "adapter": jnp.int32}

#: Values a freed slot scatters back to (live=False is the one that
#: matters — a dead row's other fields are never read by the dispatch).
DEAD_SLOT = (0, 0, False, 0.0, 0, 1.0, -1, 0, -1)


def _scatter_slot(arrays: dict, idx, tok, length, live, temp, tk, tp,
                  stop, budget, adapter) -> dict:
    """One slot's state delta as a scatter at ``idx`` (donated in/out)."""
    return {
        "tokens": arrays["tokens"].at[idx].set(tok),
        "lengths": arrays["lengths"].at[idx].set(length),
        "live": arrays["live"].at[idx].set(live),
        "temps": arrays["temps"].at[idx].set(temp),
        "top_k": arrays["top_k"].at[idx].set(tk),
        "top_p": arrays["top_p"].at[idx].set(tp),
        "stops": arrays["stops"].at[idx].set(stop),
        "budgets": arrays["budgets"].at[idx].set(budget),
        "adapter": arrays["adapter"].at[idx].set(adapter),
    }


class DecodeState:
    """Persistent on-device scheduler state + dirty-index delta sync.

    ``arrays`` is the dict of eight ``[B]`` device arrays the decode
    dispatch donates and returns; ``table`` is the ``[B, mpp]`` device page
    table threaded through the dispatches the same way. ``adopt()`` swaps
    in a dispatch's returned handles; the ``mark_*``/``sync_*`` pair applies
    host-side scheduler deltas as per-index donated scatters."""

    def __init__(self, num_slots: int, mpp: int):
        self.num_slots = num_slots
        self.arrays: dict[str, jax.Array] = {
            "tokens": jnp.zeros((num_slots,), jnp.int32),
            "lengths": jnp.zeros((num_slots,), jnp.int32),
            "live": jnp.zeros((num_slots,), jnp.bool_),
            "temps": jnp.zeros((num_slots,), jnp.float32),
            "top_k": jnp.zeros((num_slots,), jnp.int32),
            "top_p": jnp.ones((num_slots,), jnp.float32),
            "stops": jnp.full((num_slots,), -1, jnp.int32),
            "budgets": jnp.zeros((num_slots,), jnp.int32),
            # Multi-tenant LoRA (serve/lora.py): the packed-buffer slot
            # whose low-rank delta applies to this row; -1 = base model.
            "adapter": jnp.full((num_slots,), -1, jnp.int32),
        }
        self.table = jnp.full((num_slots, mpp), -1, jnp.int32)
        # Upload accounting — the tentpole's proof obligation. "full"
        # counters may only ever reflect construction; sync counters grow
        # with scheduler events, never with steady-state decode rounds.
        self.stats = {
            "full_state_uploads": 1,
            "full_table_uploads": 1,
            "slot_syncs": 0,
            "table_row_syncs": 0,
        }
        self.dirty_slots: set[int] = set()
        self.dirty_rows: set[int] = set()
        self._scatter = jax.jit(_scatter_slot, donate_argnums=(0,))
        self._row_set = jax.jit(lambda t, i, row: t.at[i].set(row),
                                donate_argnums=(0,))

    # -- dirty marking (host scheduler events) -----------------------------

    def mark_slot(self, idx: int) -> None:
        self.dirty_slots.add(idx)

    def mark_row(self, idx: int) -> None:
        self.dirty_rows.add(idx)

    # -- delta sync (immediately before a dispatch that reads the state) ---

    def sync_slots(self, values_for: Callable[[int], tuple]) -> None:  # hot-loop
        """Scatter every dirty slot's current host-side values.
        ``values_for(idx)`` returns the STATE_FIELDS tuple (DEAD_SLOT for a
        freed slot). Scalars upload via EXPLICIT ``jax.device_put`` so the
        sync stays legal under ``jax.transfer_guard("disallow")`` (the
        KFTPU_SANITIZE runtime guard, and the steady-state guard the
        hot-loop tests apply): every intended transfer is explicit and
        accounted; an implicit one anywhere is a regression. (In this
        jax, ``jnp.asarray`` of a *scalar* still counts as implicit —
        only ``device_put`` is unconditionally explicit.)"""
        put = jax.device_put
        for idx in sorted(self.dirty_slots):
            (tok, length, live, temp, tk, tp, stop, budget,
             adapter) = values_for(idx)
            self.arrays = self._scatter(
                self.arrays, put(np.int32(idx)),
                put(np.int32(tok)), put(np.int32(length)),
                put(np.bool_(live)), put(np.float32(temp)),
                put(np.int32(tk)), put(np.float32(tp)),
                put(np.int32(stop)), put(np.int32(budget)),
                put(np.int32(adapter)))
            self.stats["slot_syncs"] += 1
        self.dirty_slots.clear()

    def sync_rows(self, row_for: Callable[[int], np.ndarray]) -> None:  # hot-loop
        """Scatter every dirty page-table row (one ``[mpp]`` upload each —
        page-table GROWTH costs one row, never the full table)."""
        for idx in sorted(self.dirty_rows):
            self.table = self._row_set(
                self.table, jax.device_put(np.int32(idx)),
                jax.device_put(np.ascontiguousarray(row_for(idx),
                                                    np.int32)))
            self.stats["table_row_syncs"] += 1
        self.dirty_rows.clear()

    # -- post-dispatch adoption --------------------------------------------

    def adopt(self, arrays: dict, table: jax.Array) -> None:
        """Swap in the advanced state a decode dispatch returned (the
        donated buffers' successors). Deltas applied after this chain onto
        the dispatch's outputs — JAX's program-order queueing keeps the
        one-round-deep pipeline coherent without host synchronization."""
        self.arrays = arrays
        self.table = table
