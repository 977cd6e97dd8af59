"""Device-resident decode scheduler state — the host-overhead half of the
hot-loop elimination (ISSUE 4 tentpole (a)).

Before this module, every decode round re-materialised the scheduler's
tensor-shaped state from host Python: the ``[B]`` arrays of ``STATE_FIELDS``
(tokens/lengths/live/temps/top_k/top_p/stops/budgets/adapter) rebuilt with
numpy and ``jnp.asarray``-uploaded per dispatch, plus the FULL
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
  dispatch the engine flushes everything dirty TOGETHER: one host array of
  one fixed shape (entry ``i`` is slot ``i``'s: a flag and its nine values,
  a flag and its ``[mpp]`` row), ONE explicit ``jax.device_put``, ONE
  donated ``jit`` program that writes the flagged entries and leaves the
  rest as they were — whatever the round dirtied, and nothing at all when
  it dirtied nothing.
- **The device is the mirror master in steady state.** The decode dispatch
  itself consumes the state and returns the advanced state (same donated
  buffers); because the device applies the exact finish rules the host
  scheduler does (stop token, budget, cache edge), a slot that decodes
  without host interference never needs a sync at all.

The dirty-set discipline (who marks what) lives in ``serve/engine.py``;
this module is the mechanism: the arrays, the one sync program, and the
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


#: The sync's host array, ``[B, 2 + len(STATE_FIELDS) + mpp]`` int32. Entry
#: ``i`` is slot ``i``'s: ``[_SLOT_FLAG]`` says whether its state is
#: written, the ``STATE_FIELDS`` values follow (float32 fields as their
#: BITS, ``live`` as 0 / 1, so every value reaches the device bit for
#: bit); ``[_ROW_FLAG]`` says whether its page-table row is written, and
#: the row's page ids follow.
_SLOT_FLAG = 0
_VALUES = 1
_ROW_FLAG = _VALUES + len(STATE_FIELDS)
_ROW = _ROW_FLAG + 1
_FLOATS = tuple(i for i, name in enumerate(STATE_FIELDS)
                if _DTYPES[name] == jnp.float32)


def _write_packed(arrays: dict, table, pack):
    """Every flagged entry of ``pack`` written over the state and the page
    table (both donated in/out); an entry whose flag is 0 keeps what the
    device holds."""
    slot_dirty = pack[:, _SLOT_FLAG] != 0
    out = {}
    for col, name in enumerate(STATE_FIELDS, _VALUES):
        old = arrays[name]
        new = pack[:, col]
        if old.dtype == jnp.bool_:
            new = new != 0
        elif old.dtype != new.dtype:
            new = jax.lax.bitcast_convert_type(new, old.dtype)
        out[name] = jnp.where(slot_dirty, new, old)
    row_dirty = pack[:, _ROW_FLAG] != 0
    return out, jnp.where(row_dirty[:, None], pack[:, _ROW:], table)


class DecodeState:
    """Persistent on-device scheduler state + dirty-index delta sync.

    ``arrays`` is the dict of the ``[B]`` device arrays of ``STATE_FIELDS``
    the decode dispatch donates and returns; ``table`` is the ``[B, mpp]``
    device page table threaded through the dispatches the same way.
    ``adopt()`` swaps in a dispatch's returned handles; ``mark_*`` notes a
    host-side scheduler delta and ``sync()`` sends a round's deltas, slots
    and rows together, as one transfer and one donated program."""

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
        # with scheduler events, never with steady-state decode rounds:
        # slots and rows SENT, and the programs that carried them (one a
        # sync that found anything dirty).
        self.stats = {
            "full_state_uploads": 1,
            "full_table_uploads": 1,
            "slot_syncs": 0,
            "table_row_syncs": 0,
            "sync_dispatches": 0,
        }
        self.dirty_slots: set[int] = set()
        self.dirty_rows: set[int] = set()
        self._pack_shape = (num_slots, _ROW + mpp)
        self._write = jax.jit(_write_packed, donate_argnums=(0, 1))

    # -- dirty marking (host scheduler events) -----------------------------

    def mark_slot(self, idx: int) -> None:
        self.dirty_slots.add(idx)

    def mark_row(self, idx: int) -> None:
        self.dirty_rows.add(idx)

    # -- delta sync (immediately before a dispatch that reads the state) ---

    def sync(self, values_for: Callable[[int], tuple],
             row_for: Callable[[int], np.ndarray]) -> None:  # hot-loop
        """Send every dirty slot's current host-side values and every dirty
        page-table row: ONE upload and ONE program whatever is dirty,
        neither when nothing is. ``values_for(idx)`` returns the
        STATE_FIELDS tuple (DEAD_SLOT for a freed slot), ``row_for(idx)``
        the row's ``[mpp]`` page ids; both are read here, once an index."""
        if not (self.dirty_slots or self.dirty_rows):
            return
        pack = np.zeros(self._pack_shape, np.int32)
        for idx in self.dirty_slots:
            values = list(values_for(idx))
            for col in _FLOATS:
                values[col] = np.float32(values[col]).view(np.int32)
            pack[idx, _SLOT_FLAG] = 1
            pack[idx, _VALUES:_ROW_FLAG] = values
        for idx in self.dirty_rows:
            pack[idx, _ROW_FLAG] = 1
            pack[idx, _ROW:] = row_for(idx)
        self._send(pack)
        self.stats["slot_syncs"] += len(self.dirty_slots)
        self.stats["table_row_syncs"] += len(self.dirty_rows)
        self.stats["sync_dispatches"] += 1
        self.dirty_slots.clear()
        self.dirty_rows.clear()

    def warm(self):
        """Compile (or load) and run the sync's program once, on a pack
        that flags nothing: the state comes back as it went in, and no
        counter moves. Called once the state lies where traffic will find
        it (the program is one a placement). Returns what to wait for."""
        self._send(np.zeros(self._pack_shape, np.int32))
        return self.arrays, self.table

    def _send(self, pack: np.ndarray) -> None:  # hot-loop
        """The upload is an EXPLICIT ``jax.device_put``, so the sync stays
        legal under ``jax.transfer_guard("disallow")`` (the KFTPU_SANITIZE
        runtime guard, and the steady-state guard the hot-loop tests
        apply): every intended transfer is explicit and accounted; an
        implicit one anywhere is a regression. Where the state is
        committed (to a device, as a relaid engine's is, or over a mesh)
        the pack goes with the state's own sharding, so the program and
        what it returns stay the ones traffic's other programs see."""
        table = self.table
        self.arrays, self.table = self._write(
            self.arrays, table, jax.device_put(
                pack, table.sharding if table.committed else None))

    # -- post-dispatch adoption --------------------------------------------

    def adopt(self, arrays: dict, table: jax.Array) -> None:
        """Swap in the advanced state a decode dispatch returned (the
        donated buffers' successors). Deltas applied after this chain onto
        the dispatch's outputs — JAX's program-order queueing keeps the
        one-round-deep pipeline coherent without host synchronization."""
        self.arrays = arrays
        self.table = table
