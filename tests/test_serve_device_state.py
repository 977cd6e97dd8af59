"""``DecodeState.sync`` (serve/device_state.py): a round's dirty slots and
page-table rows go to the device together, as ONE explicit upload and ONE
donated program of ONE shape, and what they leave there is what one
``.at[i].set`` an index of the same values leaves, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.serve.device_state import (
    DEAD_SLOT, STATE_FIELDS, DecodeState)

SLOTS, MPP = 6, 5


def random_values(rng) -> tuple:
    """One slot's STATE_FIELDS tuple as the engine's scheduler hands it
    over: Python numbers, temperatures and ``top_p`` that are not round in
    binary, ``live`` either way, ``stop`` and ``adapter`` set or -1."""
    return (int(rng.integers(0, 2**31 - 1)), int(rng.integers(0, 4096)),
            bool(rng.integers(0, 2)), float(rng.uniform(0.0, 2.0)) / 3.0,
            int(rng.integers(0, 100)), float(rng.uniform(0.05, 1.0)) * 0.7,
            int(rng.choice([-1, int(rng.integers(0, 50000))])),
            int(rng.integers(0, 2048)),
            int(rng.choice([-1, int(rng.integers(0, 8))])))


def random_row(rng) -> np.ndarray:
    """A page-table row: page ids, then the -1 of the pages not held."""
    row = np.full((MPP,), -1, np.int32)
    held = int(rng.integers(0, MPP + 1))
    row[:held] = rng.integers(0, 1000, held)
    return row


def by_index(arrays: dict, table, values: dict, rows: dict):
    """The reference: one ``.at[i].set`` a dirty index and field, each value
    cast as the per-index sync the engine had cast it."""
    arrays = dict(arrays)
    for i, vals in values.items():
        for name, v in zip(STATE_FIELDS, vals):
            a = arrays[name]
            arrays[name] = a.at[i].set(np.asarray(v).astype(a.dtype))
    for i, row in rows.items():
        table = table.at[i].set(jnp.asarray(row, jnp.int32))
    return arrays, table


def assert_same_bits(state: DecodeState, arrays: dict, table) -> None:
    for name in STATE_FIELDS:
        got, want = np.asarray(state.arrays[name]), np.asarray(arrays[name])
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert got.tobytes() == want.tobytes(), name
    assert np.asarray(state.table).tobytes() == np.asarray(table).tobytes()


def programs_compiled(state: DecodeState) -> int:
    """Entries of the sync's ``jit`` cache. Every ``DecodeState`` of a
    process jits the one function, and JAX keeps one cache a function: the
    count is the process's, so a test reads it once its states are warm and
    holds it still from there."""
    return state._write._cache_size()


def sync(state: DecodeState, values: dict, rows: dict) -> None:
    for i in values:
        state.mark_slot(i)
    for i in rows:
        state.mark_row(i)
    state.sync(values.__getitem__, rows.__getitem__)


@pytest.mark.parametrize("seed", range(8))
def test_a_packed_sync_leaves_what_one_set_an_index_leaves(seed):
    """Seeded random dirty sets, several rounds on one state: each round's
    sync is held against per-index sets over the state the round found."""
    rng = np.random.default_rng(seed)
    state = DecodeState(SLOTS, MPP)
    for _ in range(4):
        slots = rng.choice(SLOTS, int(rng.integers(0, SLOTS + 1)),
                           replace=False)
        dirty_rows = rng.choice(SLOTS, int(rng.integers(0, SLOTS + 1)),
                                replace=False)
        values = {int(i): DEAD_SLOT if rng.integers(0, 4) == 0
                  else random_values(rng) for i in slots}
        rows = {int(i): random_row(rng) for i in dirty_rows}
        # the reference first: the sync donates the arrays it reads
        want = by_index(
            {k: jnp.array(v) for k, v in state.arrays.items()},
            jnp.array(state.table), values, rows)
        jax.block_until_ready(want)
        sync(state, values, rows)
        assert_same_bits(state, *want)
        assert not state.dirty_slots and not state.dirty_rows


def test_float_fields_arrive_as_their_own_bits():
    """0.1, 1/3 and a denormal are not round in binary: what the device
    holds is ``np.float32`` of the host's number, to the bit; ``live``
    false, ``stop`` and ``adapter`` -1 ride the same integers."""
    state = DecodeState(SLOTS, MPP)
    temps = {0: 0.1, 2: 1.0 / 3.0, 5: 1e-42}
    values = {i: (7, 9, i != 2, t, 3, 1.0 - t, -1, 11, -1)
              for i, t in temps.items()}
    sync(state, values, {})
    got_t, got_p = np.asarray(state.arrays["temps"]), \
        np.asarray(state.arrays["top_p"])
    for i, t in temps.items():
        assert got_t[i].tobytes() == np.float32(t).tobytes()
        assert got_p[i].tobytes() == np.float32(1.0 - t).tobytes()
    assert np.asarray(state.arrays["live"]).tolist() == [
        True, False, False, False, False, True]
    assert np.asarray(state.arrays["stops"])[[0, 2, 5]].tolist() == [-1] * 3
    assert np.asarray(state.arrays["adapter"]).tolist() == [-1] * SLOTS
    # the slots nothing marked are what construction left
    assert got_t[[1, 3, 4]].tolist() == [0.0] * 3
    assert got_p[[1, 3, 4]].tolist() == [1.0] * 3


def test_a_slot_dirty_as_slot_and_as_row_goes_in_one_program():
    rng = np.random.default_rng(54)
    state = DecodeState(SLOTS, MPP)
    values, rows = {3: random_values(rng)}, {3: random_row(rng)}
    want = by_index(dict(state.arrays), state.table, values, rows)
    jax.block_until_ready(want)
    sync(state, values, rows)
    assert_same_bits(state, *want)
    assert state.stats["sync_dispatches"] == 1
    assert state.stats["slot_syncs"] == 1
    assert state.stats["table_row_syncs"] == 1


def test_every_slot_and_every_row_dirty_at_once_is_the_capacity():
    rng = np.random.default_rng(55)
    state = DecodeState(SLOTS, MPP)
    values = {i: random_values(rng) for i in range(SLOTS)}
    rows = {i: random_row(rng) for i in range(SLOTS)}
    want = by_index(dict(state.arrays), state.table, values, rows)
    jax.block_until_ready(want)
    sync(state, values, rows)
    assert_same_bits(state, *want)
    assert state.stats["sync_dispatches"] == 1
    assert state.stats["slot_syncs"] == SLOTS
    assert state.stats["table_row_syncs"] == SLOTS


def test_values_and_rows_are_read_once_an_index_at_sync_time():
    state = DecodeState(SLOTS, MPP)
    asked = {"values": [], "rows": []}

    def values_for(i):
        asked["values"].append(i)
        return DEAD_SLOT

    def row_for(i):
        asked["rows"].append(i)
        return np.full((MPP,), i, np.int32)

    for i in (4, 1, 4, 1):              # marked twice: a set, sent once
        state.mark_slot(i)
    state.mark_row(2)
    state.mark_row(2)
    assert asked == {"values": [], "rows": []}      # nothing read yet
    state.sync(values_for, row_for)
    assert sorted(asked["values"]) == [1, 4] and asked["rows"] == [2]
    assert np.asarray(state.table)[2].tolist() == [2] * MPP


def test_nothing_dirty_sends_nothing():
    """No upload, no dispatch, no call for a value: legal under the guard
    that refuses every transfer, and no counter moves."""
    state = DecodeState(SLOTS, MPP)
    jax.block_until_ready((state.arrays, state.table))
    before = dict(state.stats)
    held = dict(state.arrays), state.table

    def never(i):
        raise AssertionError(f"asked for index {i} with nothing dirty")

    with jax.transfer_guard("disallow"):
        state.sync(never, never)
    assert state.stats == before
    # not even a donated program ran: the arrays are the SAME objects
    assert all(state.arrays[k] is held[0][k] for k in STATE_FIELDS)
    assert state.table is held[1]


def test_a_sync_is_legal_under_the_guard_that_refuses_implicit_transfers():
    rng = np.random.default_rng(56)
    state = DecodeState(SLOTS, MPP)
    state.warm()
    values, rows = {0: random_values(rng)}, {5: random_row(rng)}
    with jax.transfer_guard("disallow"):
        sync(state, values, rows)
        jax.block_until_ready((state.arrays, state.table))
    assert np.asarray(state.table)[5].tolist() == rows[5].tolist()


def test_every_dirty_count_hits_one_compiled_program():
    """1..SLOTS dirty slots, then rows, then both, after the warm-up's
    all-clean pack: the ``jit``'s cache holds ONE entry throughout, and
    ``sync_dispatches`` rises by one a syncing call."""
    rng = np.random.default_rng(57)
    state = DecodeState(SLOTS, MPP)
    jax.block_until_ready(state.warm())
    programs = programs_compiled(state)
    assert state.stats["sync_dispatches"] == 0      # a warm-up is no sync
    sent = 0
    for n in range(1, SLOTS + 1):
        picked = [int(i) for i in rng.choice(SLOTS, n, replace=False)]
        for values, rows in (
                ({i: random_values(rng) for i in picked}, {}),
                ({}, {i: random_row(rng) for i in picked}),
                ({i: random_values(rng) for i in picked},
                 {i: random_row(rng) for i in picked[:1]})):
            before = dict(state.stats)
            sync(state, values, rows)
            sent += 1
            assert state.stats["sync_dispatches"] == sent
            assert state.stats["slot_syncs"] \
                == before["slot_syncs"] + len(values)
            assert state.stats["table_row_syncs"] \
                == before["table_row_syncs"] + len(rows)
            assert programs_compiled(state) == programs
    assert state.stats["full_state_uploads"] == 1
    assert state.stats["full_table_uploads"] == 1


def test_the_warm_up_writes_nothing_and_counts_nothing():
    rng = np.random.default_rng(58)
    state = DecodeState(SLOTS, MPP)
    sync(state, {i: random_values(rng) for i in range(SLOTS)},
         {i: random_row(rng) for i in range(SLOTS)})
    want = {k: np.asarray(v).copy() for k, v in state.arrays.items()}, \
        np.asarray(state.table).copy()
    stats = dict(state.stats)
    jax.block_until_ready(state.warm())
    assert_same_bits(state, *want)
    assert state.stats == stats


def test_a_committed_state_gets_its_pack_where_it_lies():
    """A state committed to its device (a relaid engine's) stays committed
    through a sync, and an uncommitted one stays uncommitted: the programs
    that take the state next see what they were compiled for."""
    rng = np.random.default_rng(59)
    plain, pinned = DecodeState(SLOTS, MPP), DecodeState(SLOTS, MPP)
    pinned.arrays, pinned.table = jax.device_put(
        (pinned.arrays, pinned.table),
        jax.tree.map(lambda x: x.sharding, (pinned.arrays, pinned.table)))
    values, rows = {1: random_values(rng)}, {1: random_row(rng)}
    for state in (plain, pinned):
        jax.block_until_ready(state.warm())
    programs = programs_compiled(plain)     # one cache for every instance
    for state in (plain, pinned):
        sync(state, values, rows)
    assert programs_compiled(plain) == programs
    assert not any(x.committed for x in jax.tree.leaves(
        (plain.arrays, plain.table)))
    assert all(x.committed for x in jax.tree.leaves(
        (pinned.arrays, pinned.table)))
    assert_same_bits(plain, pinned.arrays, pinned.table)


def test_a_state_replicated_over_a_mesh_syncs_in_one_program():
    """Over a mesh the state comes back from a program replicated and
    committed; the pack goes with the table's own sharding and the sync is
    one program whose results lie as the state lay."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if len(jax.devices()) < 2:
        pytest.skip("one device: no mesh to replicate over")
    rng = np.random.default_rng(60)
    replicated = NamedSharding(Mesh(np.array(jax.devices()[:2]), ("model",)),
                               PartitionSpec())
    state, plain = DecodeState(SLOTS, MPP), DecodeState(SLOTS, MPP)
    state.arrays, state.table = jax.device_put(
        (state.arrays, state.table), replicated)
    jax.block_until_ready((state.warm(), plain.warm()))
    programs = programs_compiled(state)
    for _ in range(3):
        values = {int(i): random_values(rng)
                  for i in rng.choice(SLOTS, 2, replace=False)}
        rows = {int(i): random_row(rng)
                for i in rng.choice(SLOTS, 3, replace=False)}
        sync(state, values, rows)
        sync(plain, values, rows)
    assert programs_compiled(state) == programs
    assert all(x.sharding == replicated for x in jax.tree.leaves(
        (state.arrays, state.table)))
    assert_same_bits(state, plain.arrays, plain.table)


@pytest.mark.parametrize("tp", [1, 2])
def test_an_engine_compiles_its_sync_when_it_is_built_and_never_again(tp):
    """The constructor runs the sync's program once, after the decode
    ladder, over the state as a program left it (replicated and committed
    over a mesh, as it was made on one device): traffic's syncs find that
    program, send one a syncing round, and the tokens are the one-device
    engine's."""
    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.models.decoder import init_decoder_params
    from kubeflow_tpu.runtime.mesh import build_mesh
    from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams

    if len(jax.devices()) < tp:
        pytest.skip(f"fewer than {tp} devices")
    cfg = preset("tiny", dtype="float32")
    params = init_decoder_params(jax.random.PRNGKey(0), cfg)

    def build(tp):
        mesh = build_mesh({"model": tp}, jax.devices()[:tp]) if tp > 1 \
            else None
        return LLMEngine(cfg, BatchingSpec(
            max_batch_size=4, max_seq_len=96, page_size=16,
            chunked_prefill_tokens=32), params=params, seed=0, mesh=mesh)

    def run(eng):
        reqs = [eng.submit(p, SamplingParams(max_new_tokens=20))
                for p in ([5, 17, 3, 99, 42], [7] * 20, [9, 8, 7, 6])]
        while not all(r.done.is_set() for r in reqs):
            eng.step()
        return [r.output_tokens for r in reqs]

    want = run(build(1))
    eng = build(tp)
    assert list(eng.start_programs())[-1] == "state_sync[4,6]"
    programs = programs_compiled(eng._dstate)
    assert eng.counters()["state_sync_dispatches"] == 0
    assert run(eng) == want
    assert programs_compiled(eng._dstate) == programs
    c = eng.counters()
    assert c["state_sync_dispatches"] == c["state_sync_rounds"] > 0
    assert c["state_slot_syncs"] + c["state_row_syncs"] \
        > c["state_sync_dispatches"]
