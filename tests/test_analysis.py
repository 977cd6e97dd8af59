"""``kftpu lint`` — the static analyzer itself (ISSUE 5).

Contracts pinned here:
- every rule fires on its minimal positive fixture and stays silent on
  the matching negative (annotations close the false positives they are
  documented to close);
- ``# lint: disable=`` suppression and the baseline round-trip work, and
  baseline fingerprints survive unrelated line shifts;
- the two seeded regressions from the acceptance criteria: re-introducing
  the PR-4 per-round ``jnp.asarray(self._table)`` upload into the REAL
  engine and removing one REAL router lock acquisition each produce
  exactly the expected finding — the rules are tuned to this codebase,
  not just to fixtures;
- the repo itself scans clean against the committed baseline.
"""

import json
import os
import subprocess
import sys

from kubeflow_tpu.analysis import (
    Baseline, all_rules, find_baseline, lint_source, run_lint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(src: str, path: str = "kubeflow_tpu/serve/fixture.py"):
    return [f.rule for f in lint_source(src, path)]


# -- Family A: device hygiene --------------------------------------------------


class TestHostSyncInJit:
    def test_np_asarray_in_jitted_fn(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return np.asarray(x) + 1\n")
        assert rules_of(src) == ["D101"]

    def test_item_and_float_on_traced_param(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def step(x, y):\n"
            "    return x.item() + float(y)\n")
        assert rules_of(src) == ["D101", "D101"]

    def test_partial_jit_decorator_and_traced_annotation(self):
        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def a(x, n):\n"
            "    x.block_until_ready()\n"
            "    return x\n"
            "def b(x):  # traced\n"
            "    return jax.device_get(x)\n")
        assert rules_of(src) == ["D101", "D101"]

    def test_jit_wrapped_local_fn(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def build():\n"
            "    def inner(x):\n"
            "        return np.asarray(x)\n"
            "    return jax.jit(inner)\n")
        assert rules_of(src) == ["D101"]

    def test_same_calls_outside_jit_are_clean(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def host_side(x):\n"
            "    return np.asarray(jax.device_get(x)).item()\n")
        assert rules_of(src) == []


class TestHostSyncInHotLoop:
    def test_device_get_in_hot_loop(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def consume(self):  # hot-loop\n"
            "        return jax.device_get(self.buf)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["D102"]
        assert "consume" in fs[0].message

    def test_sync_point_annotation_is_the_designed_fetch(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def consume(self):  # hot-loop\n"
            "        return jax.device_get(self.buf)"
            "  # sync-point: the one designed fetch\n")
        assert rules_of(src) == []

    def test_sleep_in_hot_loop(self):
        src = (
            "import time\n"
            "def spin():  # hot-loop\n"
            "    time.sleep(0.01)\n")
        assert rules_of(src) == ["D102"]

    def test_unannotated_function_is_clean(self):
        src = (
            "import jax\n"
            "def consume(buf):\n"
            "    return jax.device_get(buf)\n")
        assert rules_of(src) == []


class TestFullBufferReupload:
    POSITIVE = (
        "import jax.numpy as jnp\n"
        "class E:\n"
        "    def dispatch(self):  # hot-loop\n"
        "        return jnp.asarray(self._table)\n")

    def test_persistent_self_buffer_uploaded_per_round(self):
        fs = lint_source(self.POSITIVE)
        assert [f.rule for f in fs] == ["D103"]
        assert "self._table" in fs[0].message

    def test_device_put_of_self_buffer_also_fires(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def dispatch(self):  # hot-loop\n"
            "        return jax.device_put(self._state.arrays)\n")
        assert rules_of(src) == ["D103"]

    def test_local_array_upload_is_clean(self):
        src = (
            "import jax.numpy as jnp\n"
            "class E:\n"
            "    def dispatch(self, row):  # hot-loop\n"
            "        return jnp.asarray(row)\n")
        assert rules_of(src) == []

    def test_lint_disable_suppresses(self):
        src = self.POSITIVE.replace(
            "return jnp.asarray(self._table)",
            "return jnp.asarray(self._table)  # lint: disable=D103")
        assert rules_of(src) == []


class TestDonatedBufferReuse:
    def test_read_after_donating_dispatch(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self):\n"
            "        out = self._fn(self.cache)\n"
            "        return self.cache\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["D104"]
        assert "self.cache" in fs[0].message

    def test_rebind_then_read_is_clean(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self):\n"
            "        self.cache = self._fn(self.cache)\n"
            "        return self.cache\n")
        assert rules_of(src) == []

    def test_donation_in_one_branch_not_read_in_sibling(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self, paged):\n"
            "        if paged:\n"
            "            self.cache = self._fn(self.cache)\n"
            "        else:\n"
            "            out = self.cache\n"
            "        return out\n")
        assert rules_of(src) == []


class TestJitInLoop:
    def test_jit_constructed_per_iteration(self):
        src = (
            "import jax\n"
            "def run(xs):\n"
            "    for x in xs:\n"
            "        f = jax.jit(lambda v: v)\n"
            "        f(x)\n")
        assert rules_of(src) == ["D105"]

    def test_jit_in_hot_loop_function(self):
        src = (
            "import jax\n"
            "def dispatch(x):  # hot-loop\n"
            "    return jax.jit(lambda v: v)(x)\n")
        assert rules_of(src) == ["D105"]

    def test_jit_at_init_is_clean(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda v: v)\n")
        assert rules_of(src) == []


# -- Family B: lock discipline -------------------------------------------------


class TestUnlockedSharedMutation:
    def test_inferred_cross_thread_mutation(self):
        src = (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = []\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run,\n"
            "                         daemon=True).start()\n"
            "    def _run(self):\n"
            "        self._items.append(1)\n"
            "    def results(self):\n"
            "        return list(self._items)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["C301"]
        assert "Worker._items" in fs[0].message

    def test_lock_held_everywhere_is_clean(self):
        src = (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = []\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run,\n"
            "                         daemon=True).start()\n"
            "    def _run(self):\n"
            "        with self._lock:\n"
            "            self._items.append(1)\n"
            "    def results(self):\n"
            "        with self._lock:\n"
            "            return list(self._items)\n")
        assert rules_of(src) == []

    def test_guarded_by_contract_checked_without_threads(self):
        # guarded_by turns the attribute into a contract even when the
        # class spawns no threads this module can see.
        src = (
            "import threading\n"
            "class G:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded_by: _lock\n"
            "    def bump(self):\n"
            "        self._n += 1\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["C301"]
        assert "guarded_by" in fs[0].message and "bump" in fs[0].message

    def test_guarded_by_satisfied_under_lock(self):
        src = (
            "import threading\n"
            "class G:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded_by: _lock\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n")
        assert rules_of(src) == []

    def test_locked_suffix_counts_as_holding(self):
        src = (
            "import threading\n"
            "class G:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded_by: _lock\n"
            "    def _bump_locked(self):\n"
            "        self._n += 1\n")
        assert rules_of(src) == []

    def test_requires_lock_annotation(self):
        src = (
            "import threading\n"
            "class G:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded_by: _lock\n"
            "    def _bump(self):  # requires_lock: _lock\n"
            "        self._n += 1\n")
        assert rules_of(src) == []

    def test_lockfree_annotation_closes_inference(self):
        src = (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._items = []  # lockfree: scheduler-confined\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._run,\n"
            "                         daemon=True).start()\n"
            "    def _run(self):\n"
            "        self._items.append(1)\n"
            "    def results(self):\n"
            "        return list(self._items)\n")
        assert rules_of(src) == []

    def test_condition_guard_counts_as_its_lock(self):
        src = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cv = threading.Condition(self._lock)\n"
            "        self._pending = {}  # guarded_by: _cv\n"
            "    def add(self, k):\n"
            "        with self._cv:\n"
            "            self._pending[k] = None\n")
        assert rules_of(src) == []


class TestBlockingCallUnderLock:
    def test_sleep_under_lock(self):
        src = (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def poll(self):\n"
            "        with self._lock:\n"
            "            time.sleep(0.1)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["C302"]
        assert "time.sleep" in fs[0].message

    def test_thread_join_under_lock(self):
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def stop(self):\n"
            "        with self._lock:\n"
            "            self._thread.join()\n")
        assert rules_of(src) == ["C302"]

    def test_sleep_outside_lock_is_clean(self):
        src = (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def poll(self):\n"
            "        with self._lock:\n"
            "            n = 1\n"
            "        time.sleep(0.1)\n")
        assert rules_of(src) == []

    def test_condition_wait_is_exempt(self):
        # Condition.wait releases the lock — the whole point of a CV.
        src = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._cv = threading.Condition(self._lock)\n"
            "    def pop(self):\n"
            "        with self._cv:\n"
            "            self._cv.wait(1.0)\n")
        assert rules_of(src) == []


class TestSwallowedException:
    def test_bare_except_pass(self):
        src = (
            "def reconcile(work):\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        pass\n")
        assert rules_of(src) == ["C303"]

    def test_logged_broad_except_is_clean(self):
        src = (
            "import logging\n"
            "def reconcile(work):\n"
            "    try:\n"
            "        work()\n"
            "    except Exception:\n"
            "        logging.exception('reconcile failed')\n")
        assert rules_of(src) == []

    def test_narrow_except_pass_is_clean(self):
        src = (
            "def probe(work):\n"
            "    try:\n"
            "        work()\n"
            "    except ValueError:\n"
            "        pass\n")
        assert rules_of(src) == []

    def test_reraise_is_clean(self):
        src = (
            "def run(work):\n"
            "    try:\n"
            "        work()\n"
            "    except BaseException:\n"
            "        raise\n")
        assert rules_of(src) == []


# -- Family S: sharding / SPMD -------------------------------------------------


class TestUndonatedCarry:
    def test_carry_without_donation(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c)\n"
            "    def go(self):\n"
            "        self.cache = self._fn(self.cache)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["S401"]
        assert "self._fn" in fs[0].message and "self.cache" in fs[0].message

    def test_tuple_target_carry(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda p, c: (1, c))\n"
            "    def go(self):\n"
            "        out, self.cache = self._fn(self.params, self.cache)\n")
        assert rules_of(src) == ["S401"]

    def test_donated_carry_is_clean(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self):\n"
            "        self.cache = self._fn(self.cache)\n")
        assert rules_of(src) == []

    def test_non_carry_call_is_clean(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda x: x)\n"
            "    def go(self):\n"
            "        out = self._fn(self.logits)\n"
            "        return out\n")
        assert rules_of(src) == []


class TestUnknownMeshAxis:
    def test_typo_in_partition_spec(self):
        src = (
            "from jax.sharding import PartitionSpec\n"
            "spec = PartitionSpec('modle', None)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["S402"]
        assert "modle" in fs[0].message

    def test_axis_name_kwarg_and_tuple(self):
        src = (
            "import jax\n"
            "from jax.sharding import PartitionSpec as P\n"
            "spec = P(('dcn', 'dat'), None)\n"
            "def f(x):  # mesh-context: test fixture\n"
            "    return jax.lax.psum(x, axis_name='modell')\n")
        assert rules_of(src) == ["S402", "S402"]

    def test_canonical_axes_clean(self):
        src = (
            "from jax.sharding import PartitionSpec as P\n"
            "spec = P(('dcn', 'data', 'fsdp'), 'seq', 'model')\n")
        assert rules_of(src) == []

    def test_canonical_set_matches_runtime_mesh(self):
        from kubeflow_tpu.analysis.core import canonical_mesh_axes
        from kubeflow_tpu.runtime.mesh import MESH_AXES

        assert canonical_mesh_axes() == MESH_AXES


class TestHostRoundTrip:
    def test_fetch_then_dispatch(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self, st):\n"
            "        lens = jax.device_get(st)\n"
            "        return self._fn(lens)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["S403"]
        assert "lens" in fs[0].message

    def test_taint_propagates_through_assignment(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self, st):\n"
            "        host = np.asarray(st)\n"
            "        padded = host + 1\n"
            "        return self._fn(padded)\n")
        assert rules_of(src) == ["S403"]

    def test_fetch_after_dispatch_is_clean(self):
        # the engine's draft-propose pattern: dispatch first, fetch after
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self, st):\n"
            "        out = self._fn(st)\n"
            "        host = jax.device_get(out)\n"
            "        return host\n")
        assert rules_of(src) == []

    def test_rebinding_clears_taint(self):
        src = (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self, st):\n"
            "        host = jax.device_get(st)\n"
            "        host = jnp.zeros((4,))\n"
            "        return self._fn(host)\n")
        assert rules_of(src) == []


class TestImplicitReplication:
    def test_unsharded_params_device_put(self):
        src = (
            "import jax\n"
            "from jax.sharding import NamedSharding\n"
            "def load(params):\n"
            "    return jax.device_put(params)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["S404"]
        assert "shard_params" in fs[0].message

    def test_sharded_put_is_clean(self):
        src = (
            "import jax\n"
            "from jax.sharding import NamedSharding\n"
            "def load(params, sh):\n"
            "    return jax.device_put(params, sh)\n")
        assert rules_of(src) == []

    def test_non_mesh_module_is_clean(self):
        src = (
            "import jax\n"
            "def load(params):\n"
            "    return jax.device_put(params)\n")
        assert rules_of(src) == []


class TestUnboundCollective:
    def test_literal_axis_without_shard_map(self):
        src = (
            "import jax\n"
            "def allreduce(x):\n"
            "    return jax.lax.psum(x, 'model')\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["S405"]
        assert "model" in fs[0].message

    def test_shard_mapped_fn_is_bound(self):
        src = (
            "import jax\n"
            "from jax.experimental.shard_map import shard_map\n"
            "def worker(x):\n"
            "    return jax.lax.psum(x, 'model')\n"
            "def build(mesh, spec):\n"
            "    return shard_map(worker, mesh=mesh, in_specs=(spec,),\n"
            "                     out_specs=spec)\n")
        assert rules_of(src) == []

    def test_one_level_callee_of_shard_mapped_fn_is_bound(self):
        src = (
            "import jax\n"
            "from jax.experimental.shard_map import shard_map\n"
            "def reduce_part(x):\n"
            "    return jax.lax.psum(x, 'model')\n"
            "def worker(x):\n"
            "    return reduce_part(x) + 1\n"
            "def build(mesh, spec):\n"
            "    return shard_map(worker, mesh=mesh, in_specs=(spec,),\n"
            "                     out_specs=spec)\n")
        assert rules_of(src) == []

    def test_mesh_context_annotation_closes_it(self):
        src = (
            "import jax\n"
            "def allreduce(x):  # mesh-context: stage fn, bound in pipeline.py\n"
            "    return jax.lax.psum(x, 'model')\n")
        assert rules_of(src) == []

    def test_variable_axis_is_clean(self):
        src = (
            "import jax\n"
            "def allreduce(x, axis_name):\n"
            "    return jax.lax.psum(x, axis_name)\n")
        assert rules_of(src) == []


# -- Family R: resources & ordering --------------------------------------------


class TestLeakedAlloc:
    def test_risky_call_between_alloc_and_record(self):
        src = (
            "class E:\n"
            "    def grow(self, idx, n):\n"
            "        new = self._allocator.alloc(n)\n"
            "        self._refresh_gauge()\n"
            "        self._slot_pages[idx].extend(new)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["R501"]
        assert "new" in fs[0].message and "grow" in fs[0].message

    def test_immediate_record_is_clean(self):
        src = (
            "class E:\n"
            "    def grow(self, idx, n):\n"
            "        new = self._allocator.alloc(n)\n"
            "        self._slot_pages[idx].extend(new)\n"
            "        self._refresh_gauge()\n")
        assert rules_of(src) == []

    def test_handler_free_is_clean(self):
        src = (
            "class E:\n"
            "    def grow(self, idx, n):\n"
            "        try:\n"
            "            new = self._allocator.alloc(n)\n"
            "            self._risky_dispatch()\n"
            "        except Exception:\n"
            "            self._allocator.free(new)\n"
            "            raise\n"
            "        self._slot_pages[idx].extend(new)\n")
        assert rules_of(src) == []

    def test_record_after_try_is_clean(self):
        # the engine's real _ensure_pages shape: alloc inside try (for
        # PagePoolExhausted), ownership recorded right after the try.
        src = (
            "class E:\n"
            "    def grow(self, idx, n):\n"
            "        try:\n"
            "            new = self._allocator.alloc(n)\n"
            "        except PagePoolExhausted:\n"
            "            return False\n"
            "        self._slot_pages[idx].extend(new)\n"
            "        return True\n")
        assert rules_of(src) == []

    def test_never_recorded_alloc_fires(self):
        src = (
            "class E:\n"
            "    def grow(self, n):\n"
            "        new = self._allocator.alloc(n)\n")
        assert rules_of(src) == ["R501"]


class TestUnauditedPagedTest:
    def test_paged_test_without_audit(self):
        src = (
            "def test_paged_decode(mk_engine):\n"
            "    eng = mk_engine(paged=True)\n"
            "    eng.generate([1, 2, 3])\n")
        fs = lint_source(src, "tests/test_fixture_x.py")
        assert [f.rule for f in fs] == ["R502"]

    def test_direct_audit_is_clean(self):
        src = (
            "def test_paged_decode(mk_engine):\n"
            "    eng = mk_engine(paged=True)\n"
            "    eng.generate([1, 2, 3])\n"
            "    eng._allocator.assert_quiescent()\n")
        assert [f.rule for f in lint_source(
            src, "tests/test_fixture_x.py")] == []

    def test_helper_audit_one_level_is_clean(self):
        src = (
            "def audit(eng):\n"
            "    assert eng.kv_pages_in_use() == 0\n"
            "def test_paged_decode(mk_engine):\n"
            "    eng = mk_engine(paged=True)\n"
            "    eng.generate([1, 2, 3])\n"
            "    audit(eng)\n")
        assert [f.rule for f in lint_source(
            src, "tests/test_fixture_x.py")] == []

    def test_non_test_path_ignored(self):
        src = (
            "def test_paged_decode(mk_engine):\n"
            "    eng = mk_engine(paged=True)\n")
        assert [f.rule for f in lint_source(
            src, "kubeflow_tpu/serve/fixture.py")] == []


class TestLockOrderInversion:
    INVERTED = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a:\n"
        "                pass\n")

    def test_two_lock_cycle(self):
        fs = lint_source(self.INVERTED)
        assert [f.rule for f in fs] == ["R503"]
        assert "S._a" in fs[0].message and "S._b" in fs[0].message

    def test_consistent_order_is_clean(self):
        src = self.INVERTED.replace(
            "        with self._b:\n"
            "            with self._a:\n",
            "        with self._a:\n"
            "            with self._b:\n")
        assert rules_of(src) == []

    def test_one_level_helper_acquisition(self):
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._a:\n"
            "            self._grab_b()\n"
            "    def _grab_b(self):\n"
            "        with self._b:\n"
            "            pass\n"
            "    def two(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n")
        assert rules_of(src) == ["R503"]

    def test_condition_canonicalizes_to_its_lock(self):
        # Condition(self._a) IS lock _a: with-ing the condition in one
        # method and the lock in another is NOT an inversion.
        src = (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._cv = threading.Condition(self._a)\n"
            "        self._b = threading.Lock()\n"
            "    def one(self):\n"
            "        with self._cv:\n"
            "            with self._b:\n"
            "                pass\n"
            "    def two(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n")
        assert rules_of(src) == []


class TestUnhandledCheckpointIO:
    def test_bare_save_and_unguarded_restore_fire(self):
        src = (
            "def resume(ckpt, abstract):\n"
            "    state = ckpt.restore(abstract)\n"
            "    return state\n"
            "class T:\n"
            "    def save(self, step):\n"
            "        self.ckpt.save(step, self.state)\n")
        fs = lint_source(src, "kubeflow_tpu/train/fixture.py")
        assert [f.rule for f in fs] == ["R504", "R504"]
        assert "restore" in fs[0].message and "save" in fs[1].message

    def test_try_handler_is_clean(self):
        src = (
            "def resume(ckpt, abstract):\n"
            "    try:\n"
            "        return ckpt.restore(abstract)\n"
            "    except CheckpointCorruptionError:\n"
            "        return None\n"
            "class T:\n"
            "    def save(self, step):\n"
            "        try:\n"
            "            self.ckpt.save(step, self.state)\n"
            "        except OSError:\n"
            "            self.failures += 1\n")
        assert rules_of(src, "kubeflow_tpu/train/fixture.py") == []

    def test_consumed_save_return_is_clean(self):
        src = (
            "class T:\n"
            "    def save(self, step):\n"
            "        accepted = self.ckpt.save(step, self.state)\n"
            "        if not accepted:\n"
            "            self.failures += 1\n")
        assert rules_of(src, "kubeflow_tpu/train/fixture.py") == []

    def test_non_checkpoint_receiver_ignored(self):
        src = (
            "def load(mgr, path):\n"
            "    mgr.restore(path)\n"
            "    store.save(path)\n")
        assert rules_of(src, "kubeflow_tpu/serve/fixture.py") == []

    def test_test_paths_exempt(self):
        src = (
            "def test_resume(ckpt, abstract):\n"
            "    state = ckpt.restore(abstract)\n"
            "    ckpt.save(1, state)\n")
        assert [f.rule for f in lint_source(src, "tests/test_x.py")] == []

    def test_suppression_comment(self):
        src = (
            "def resume(ckpt, abstract):\n"
            "    return ckpt.restore(abstract)  # lint: disable=R504\n")
        assert rules_of(src, "kubeflow_tpu/train/fixture.py") == []

    def test_real_trainer_is_clean(self):
        """The shipped Trainer handles both: try_resume walks tiers under
        a fallback, save checks the acceptance bool inside try/except."""
        relpath = "kubeflow_tpu/train/trainer.py"
        with open(os.path.join(REPO, relpath)) as f:
            fs = [x for x in lint_source(f.read(), relpath)
                  if x.rule == "R504"]
        assert fs == []


# -- interprocedural core (one-level call-following) ---------------------------


class TestCallFollowing:
    def test_d101_sees_through_helper(self):
        # helper only ever called from jitted code: its host sync fires
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def fetch(x):\n"
            "    return np.asarray(x)\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return fetch(x) + 1\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["D101"]
        assert fs[0].symbol == "fetch"

    def test_d101_skips_helper_shared_with_host_path(self):
        src = (
            "import jax\n"
            "import numpy as np\n"
            "def fetch(x):\n"
            "    return np.asarray(x)\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return fetch(x) + 1\n"
            "def host_side(x):\n"
            "    return fetch(x)\n")
        assert rules_of(src) == []

    def test_d104_read_inside_helper(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self):\n"
            "        out = self._fn(self.cache)\n"
            "        self._peek()\n"
            "        return out\n"
            "    def _peek(self):\n"
            "        return self.cache.shape\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["D104"]
        assert "self.cache" in fs[0].message

    def test_d104_helper_rebind_is_clean(self):
        src = (
            "import jax\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(lambda c: c, donate_argnums=(0,))\n"
            "    def go(self):\n"
            "        out = self._fn(self.cache)\n"
            "        self._rebuild()\n"
            "        return self.cache\n"
            "    def _rebuild(self):\n"
            "        self.cache = None\n")
        assert rules_of(src) == []

    def test_c301_caller_held_lock_inference(self):
        # private helper only called under the lock: its mutation counts
        # as guarded WITHOUT a # requires_lock annotation
        src = (
            "import threading\n"
            "class G:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded_by: _lock\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._bump_inner()\n"
            "    def _bump_inner(self):\n"
            "        self._n += 1\n")
        assert rules_of(src) == []

    def test_c301_mixed_call_sites_still_fire(self):
        # one call site does NOT hold the lock: inference must not silence
        src = (
            "import threading\n"
            "class G:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0  # guarded_by: _lock\n"
            "    def bump(self):\n"
            "        with self._lock:\n"
            "            self._bump_inner()\n"
            "    def bump_unlocked(self):\n"
            "        self._bump_inner()\n"
            "    def _bump_inner(self):\n"
            "        self._n += 1\n")
        assert rules_of(src) == ["C301"]

    def test_c302_blocking_helper_under_lock(self):
        # the helper is only ever called under the lock, so caller-held
        # inference flags its sleep DIRECTLY (one finding, in the helper)
        src = (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def poll(self):\n"
            "        with self._lock:\n"
            "            self._wait_a_bit()\n"
            "    def _wait_a_bit(self):\n"
            "        time.sleep(0.1)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["C302"]
        assert fs[0].symbol.endswith("_wait_a_bit")

    def test_c302_helper_followed_from_mixed_call_sites(self):
        # one unlocked call site kills the inference; the lock-held call
        # site still reports via one-level following
        src = (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def poll(self):\n"
            "        with self._lock:\n"
            "            self._wait_a_bit()\n"
            "    def idle(self):\n"
            "        self._wait_a_bit()\n"
            "    def _wait_a_bit(self):\n"
            "        time.sleep(0.1)\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["C302"]
        assert "_wait_a_bit" in fs[0].message


# -- metric-name rules ---------------------------------------------------------


class TestMetricRules:
    def test_missing_prefix(self):
        src = "def setup(reg):\n    reg.counter('queue_depth', 'help')\n"
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["M201"]
        assert "kftpu_" in fs[0].message

    def test_bad_grammar(self):
        src = "def setup(reg):\n    reg.gauge('kftpu_bad-name', 'help')\n"
        assert rules_of(src) == ["M201"]

    def test_fstring_head_checked(self):
        src = (
            "def setup(reg, kind):\n"
            "    reg.gauge(f'queue_{kind}_depth', 'help')\n"
            "    reg.gauge(f'kftpu_{kind}_depth', 'help')\n")
        assert rules_of(src) == ["M201"]

    def test_duplicate_family_in_one_function(self):
        src = (
            "def setup(reg):\n"
            "    reg.counter('kftpu_reqs_total', 'a')\n"
            "    reg.counter('kftpu_reqs_total', 'b')\n")
        assert rules_of(src) == ["M202"]

    def test_good_names_clean(self):
        src = (
            "def setup(reg):\n"
            "    reg.counter('kftpu_reqs_total', 'a')\n"
            "    reg.histogram('kftpu_latency_seconds', 'b')\n")
        assert rules_of(src) == []

    def test_fstring_expanded_via_literal_loop(self):
        # the PR-6 labeled-series idiom: the loop's literal values expand
        # the f-string, so FULL grammar (not just the prefix) is checked
        src = (
            "def setup(reg, snap):\n"
            "    for k in ('ttft_p95_ms', 'bad-grammar'):\n"
            "        reg.gauge(f'kftpu_serving_{k}').set(snap[k])\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["M201"]
        assert "bad-grammar" in fs[0].message

    def test_fstring_loop_expansion_all_good_is_clean(self):
        src = (
            "def setup(reg, snap):\n"
            "    for k in ('ttft_p95_ms', 'queue_delay_p95_ms'):\n"
            "        reg.gauge(f'kftpu_serving_{k}').set(snap[k])\n")
        assert rules_of(src) == []

    def test_fstring_loop_expansion_duplicate_detected(self):
        src = (
            "def setup(reg):\n"
            "    for k in ('depth', 'depth'):\n"
            "        reg.gauge(f'kftpu_q_{k}')\n")
        assert rules_of(src) == ["M202"]

    def test_reserved_label_at_sample_site(self):
        src = (
            "def setup(reg):\n"
            "    g = reg.gauge('kftpu_latency_p95_ms')\n"
            "    g.set(1.0, le='0.5')\n")
        fs = lint_source(src)
        assert [f.rule for f in fs] == ["M203"]
        assert "le" in fs[0].message

    def test_reserved_label_in_dict_splat(self):
        src = (
            "def setup(reg):\n"
            "    reg.counter('kftpu_reqs_total').inc(1, **{'quantile': 'x'})\n")
        assert rules_of(src) == ["M203"]

    def test_normal_labels_clean(self):
        src = (
            "def setup(reg, name, cls):\n"
            "    q = reg.counter('kftpu_serving_qos_requests_total')\n"
            "    q.inc(3, model=name, qos=cls)\n")
        assert rules_of(src) == []


# -- core machinery ------------------------------------------------------------


class TestBaseline:
    SRC = TestFullBufferReupload.POSITIVE

    def test_round_trip(self, tmp_path):
        findings = lint_source(self.SRC, "pkg/mod.py")
        assert findings
        path = str(tmp_path / "baseline.json")
        Baseline.from_findings(findings, reason="seed fixture").save(path)
        loaded = Baseline.load(path)
        new, matched = loaded.split(lint_source(self.SRC, "pkg/mod.py"))
        assert new == [] and len(matched) == len(findings)
        # the file is valid JSON with a reason per entry
        doc = json.loads(open(path).read())
        assert all(e["reason"] for e in doc["entries"])

    def test_fingerprints_survive_line_shifts(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        Baseline.from_findings(
            lint_source(self.SRC, "pkg/mod.py")).save(path)
        shifted = "# a new header comment\n\n" + self.SRC
        new, matched = Baseline.load(path).split(
            lint_source(shifted, "pkg/mod.py"))
        assert new == [] and matched

    def test_second_occurrence_is_new(self, tmp_path):
        # The baseline budget is a multiset: one entry forgives ONE
        # occurrence, a second identical defect is still a finding.
        path = str(tmp_path / "baseline.json")
        Baseline.from_findings(
            lint_source(self.SRC, "pkg/mod.py")).save(path)
        doubled = self.SRC + (
            "    def dispatch2(self):  # hot-loop\n"
            "        return jnp.asarray(self._table)\n")
        new, matched = Baseline.load(path).split(
            lint_source(doubled, "pkg/mod.py"))
        assert len(matched) == 1 and len(new) == 1

    def test_committed_baseline_exists(self):
        path = find_baseline([os.path.join(REPO, "kubeflow_tpu")])
        assert path is not None
        assert os.path.basename(path) == ".kftpu-lint-baseline.json"
        assert os.path.dirname(path) == REPO


class TestRegistry:
    def test_all_families_registered(self):
        ids = {r.id for r in all_rules()}
        assert {"D101", "D102", "D103", "D104", "D105",
                "C301", "C302", "C303", "M201", "M202", "M203",
                "S401", "S402", "S403", "S404", "S405",
                "R501", "R502", "R503", "R504",
                "F601", "F602", "F603", "F604", "F605",
                "T801", "T802", "T803", "T804", "T805"} <= ids

    def test_parse_error_is_reported_not_raised(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        result = run_lint([str(bad)], root=str(tmp_path))
        assert not result.ok
        assert [e.rule for e in result.errors] == ["E000"]


# -- Family F: compilation stability (ISSUE 8) ---------------------------------


_F_PRELUDE = (
    "import jax\n"
    "import jax.numpy as jnp\n"
    "import numpy as np\n"
    "def _impl(x, y=None):\n"
    "    return x\n"
)


class TestUnstableTraceShape:
    def test_len_derived_shape_into_dispatch(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, reqs):\n"
            "        n = len(reqs)\n"
            "        toks = np.zeros((n, 8), np.int32)\n"
            "        return self._fn(jnp.asarray(toks))\n")
        assert rules_of(src) == ["F601"]

    def test_pow2_padded_width_is_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, reqs):\n"
            "        n = len(reqs)\n"
            "        width = 1\n"
            "        while width < n:\n"
            "            width *= 2\n"
            "        toks = np.zeros((width, 8), np.int32)\n"
            "        return self._fn(jnp.asarray(toks))\n")
        assert rules_of(src) == []

    def test_bucket_helper_stabilizes(self):
        src = _F_PRELUDE + (
            "def _bucket_for(n):\n"
            "    return 64\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, reqs):\n"
            "        n = _bucket_for(len(reqs))\n"
            "        return self._fn(np.zeros((n, 8), np.int32))\n")
        assert rules_of(src) == []

    def test_tainted_slice_bound(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, buf, reqs):\n"
            "        n = len(reqs)\n"
            "        return self._fn(buf[:n])\n")
        assert rules_of(src) == ["F601"]

    def test_retrace_ok_annotation_closes(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, reqs):\n"
            "        n = len(reqs)\n"
            "        # retrace-ok: cold admin path, one call per restart\n"
            "        return self._fn(np.zeros((n,), np.int32))\n")
        assert rules_of(src) == []

    def test_lint_disable_suppresses(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, reqs):\n"
            "        n = len(reqs)\n"
            "        return self._fn(np.zeros((n,), np.int32))  "
            "# lint: disable=F601\n")
        assert rules_of(src) == []


class TestWeakTypeLeak:
    def test_scalar_literal_into_traced_arg(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x):\n"
            "        return self._fn(x, 0.5)\n")
        assert rules_of(src) == ["F602"]

    def test_float_result_var_and_dtype_less_asarray(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, raw):\n"
            "        t = float(raw)\n"
            "        return self._fn(x, jnp.asarray(t))\n")
        assert rules_of(src) == ["F602"]

    def test_explicit_dtype_is_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, raw):\n"
            "        t = float(raw)\n"
            "        a = self._fn(x, jnp.float32(0.5))\n"
            "        b = self._fn(x, jnp.asarray(t, jnp.float32))\n"
            "        return a, b\n")
        assert rules_of(src) == []

    def test_static_argnum_position_is_exempt(self):
        # the engine's `self._decode_n(..., k_steps, mode)` idiom: ints
        # in static positions are hashed, not traced — no weak type
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnums=(1,))\n"
            "    def run(self, x):\n"
            "        return self._fn(x, 16)\n")
        assert rules_of(src) == []

    def test_static_argname_kwarg_is_exempt(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnames=('y',))\n"
            "    def run(self, x):\n"
            "        return self._fn(x, y=16)\n")
        assert rules_of(src) == []

    def test_suppression(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x):\n"
            "        return self._fn(x, 0.5)  # lint: disable=F602\n")
        assert rules_of(src) == []


class TestDtypePromotionDrift:
    def test_sites_disagree_on_dtype(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def a(self, x):\n"
            "        return self._fn(jnp.asarray(x, jnp.float32))\n"
            "    def b(self, x):\n"
            "        return self._fn(jnp.asarray(x, jnp.bfloat16))\n")
        found = rules_of(src)
        assert found == ["F603"]

    def test_consistent_dtype_is_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def a(self, x):\n"
            "        return self._fn(jnp.asarray(x, jnp.float32))\n"
            "    def b(self, x):\n"
            "        return self._fn(x.astype(jnp.float32))\n")
        assert rules_of(src) == []

    def test_suppression(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def a(self, x):\n"
            "        return self._fn(jnp.asarray(x, jnp.float32))\n"
            "    def b(self, x):\n"
            "        return self._fn(jnp.asarray(x, jnp.bfloat16))  "
            "# lint: disable=F603\n")
        assert rules_of(src) == []


class TestStaticArgInstability:
    def test_fresh_tuple_of_runtime_values(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnums=(1,))\n"
            "    def run(self, x, n):\n"
            "        return self._fn(x, (n, 1))\n")
        assert rules_of(src) == ["F604"]

    def test_constant_tuple_is_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnums=(1,))\n"
            "    def run(self, x):\n"
            "        return self._fn(x, (4, 5))\n")
        assert rules_of(src) == []

    def test_fresh_lambda_and_partial(self):
        src = _F_PRELUDE + (
            "import functools\n"
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnums=(1,))\n"
            "    def a(self, x):\n"
            "        return self._fn(x, lambda v: v)\n"
            "    def b(self, x, g):\n"
            "        return self._fn(x, functools.partial(g, 1))\n")
        assert rules_of(src) == ["F604", "F604"]

    def test_non_static_tuple_is_fine(self):
        # a tuple in a TRACED position is just a pytree of leaves
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, a, b):\n"
            "        return self._fn((a, b))\n")
        assert rules_of(src) == []

    def test_retrace_ok_escape(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnums=(1,))\n"
            "    def run(self, x, n):\n"
            "        # retrace-ok: shapes enumerate a tiny fixed set\n"
            "        return self._fn(x, (n, 1))\n")
        assert rules_of(src) == []

    def test_lint_disable_suppresses(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl, static_argnums=(1,))\n"
            "    def run(self, x, n):\n"
            "        return self._fn(x, (n, 1))  # lint: disable=F604\n")
        assert rules_of(src) == []


class TestPytreeStructureInstability:
    def test_call_sites_disagree_on_keys(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def a(self, x):\n"
            "        return self._fn({'a': x, 'b': x})\n"
            "    def b(self, x):\n"
            "        return self._fn({'a': x})\n")
        assert rules_of(src) == ["F605"]

    def test_conditional_key_insert_before_dispatch(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, flag):\n"
            "        d = {'a': x}\n"
            "        if flag:\n"
            "            d['c'] = x\n"
            "        return self._fn(d)\n")
        assert rules_of(src) == ["F605"]

    def test_same_keys_and_unconditional_insert_are_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def a(self, x):\n"
            "        return self._fn({'a': x, 'b': x})\n"
            "    def b(self, x):\n"
            "        d = {'a': x}\n"
            "        d['b'] = x\n"
            "        return self._fn(d)\n")
        assert rules_of(src) == []

    def test_value_update_is_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, flag):\n"
            "        d = {'a': x}\n"
            "        if flag:\n"
            "            d['a'] = x + 1\n"
            "        return self._fn(d)\n")
        assert rules_of(src) == []

    def test_insert_in_same_branch_as_dispatch_is_clean(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, flag):\n"
            "        d = {'a': x}\n"
            "        if flag:\n"
            "            d['c'] = x\n"
            "            return self._fn(d)\n"
            "        return x\n")
        assert rules_of(src) == []

    def test_spread_rebuild_is_opaque(self):
        # the engine's `{**st, 'tokens': t}` rebuild preserves structure
        # by construction and must not be compared against literals
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def a(self, st, t):\n"
            "        return self._fn({**st, 'tokens': t})\n"
            "    def b(self, x):\n"
            "        return self._fn({'a': x})\n")
        assert rules_of(src) == []

    def test_suppression(self):
        src = _F_PRELUDE + (
            "class E:\n"
            "    def __init__(self):\n"
            "        self._fn = jax.jit(_impl)\n"
            "    def run(self, x, flag):\n"
            "        d = {'a': x}\n"
            "        if flag:\n"
            "            d['c'] = x\n"
            "        return self._fn(d)  # lint: disable=F605\n")
        assert rules_of(src) == []


# -- whole-program core (ISSUE 8 tentpole) -------------------------------------


class TestProgram:
    A = (
        "import jax\n"
        "def g(x, k):\n"
        "    return x\n"
        "G = jax.jit(g, static_argnums=(1,))\n")

    def test_imported_jit_fact_carries_static_argnums(self):
        """A jitted callable defined in one module keeps its static-arg
        spec at call sites in another: the importing module's bare int in
        the static position is NOT a weak-type leak, and a fresh tuple
        there IS an F604."""
        b_ok = (
            "from kubeflow_tpu.a import G\n"
            "def run(x):\n"
            "    return G(x, 16)\n")
        b_bad = (
            "from kubeflow_tpu.a import G\n"
            "def run(x, n):\n"
            "    return G(x, (n, 1))\n")
        from kubeflow_tpu.analysis import lint_sources
        assert lint_sources({"kubeflow_tpu/a.py": self.A,
                             "kubeflow_tpu/b.py": b_ok},
                            lint=["kubeflow_tpu/b.py"]) == []
        found = lint_sources({"kubeflow_tpu/a.py": self.A,
                              "kubeflow_tpu/b.py": b_bad},
                             lint=["kubeflow_tpu/b.py"])
        assert [f.rule for f in found] == ["F604"]

    def test_resolve_and_transitive_callees(self):
        from kubeflow_tpu.analysis import Module, Program

        a = Module("kubeflow_tpu/a.py", "def leaf():\n    return 1\n")
        b = Module("kubeflow_tpu/b.py",
                   "from kubeflow_tpu.a import leaf\n"
                   "def mid():\n"
                   "    return leaf()\n")
        c = Module("kubeflow_tpu/c.py",
                   "from kubeflow_tpu.b import mid\n"
                   "def top():\n"
                   "    return mid()\n")
        prog = Program([a, b, c])
        got = prog.resolve("kubeflow_tpu.a.leaf")
        assert got is not None and got[0] is a
        top = c.callgraph.module_fns["top"]
        names = [fn.name for _, fn in prog.transitive_callees(c, top)]
        assert names == ["mid", "leaf"]
        # depth bound: 1 stops at mid
        names1 = [fn.name
                  for _, fn in prog.transitive_callees(c, top, depth=1)]
        assert names1 == ["mid"]

    def test_standalone_module_still_lints(self):
        # no Program attached: rules degrade to module-local facts
        src = _F_PRELUDE + (
            "F = jax.jit(_impl)\n"
            "def run(x):\n"
            "    return F(x, 0.5)\n")
        assert rules_of(src) == ["F602"]

    def test_jit_table_collects_decorated_and_assigned(self):
        from kubeflow_tpu.analysis import Module, jit_table

        src = (
            "import jax\n"
            "from functools import partial\n"
            "@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))\n"
            "def dec(a, b, c):\n"
            "    return a\n"
            "def _imp(x):\n"
            "    return x\n"
            "J = jax.jit(_imp, donate_argnames=('x',))\n")
        table = jit_table(Module("kubeflow_tpu/t.py", src))
        assert table["dec"].static_argnums == (2,)
        assert table["dec"].donate_argnums == (0,)
        assert table["J"].donate_argnames == ("x",)
        assert table["J"].donates


# -- Family X: cross-component name contracts (ISSUE 10 tentpole) --------------


def xrules(sources: dict, lint=None):
    from kubeflow_tpu.analysis import lint_sources

    return [f for f in lint_sources(sources, lint=lint)
            if f.rule.startswith("X7")]


class TestConsumedSeriesNeverProduced:
    PRODUCER = (
        "def reg_metrics(reg, snap):\n"
        "    reg.counter('kftpu_fix_total')\n"
        "    reg.histogram('kftpu_fix_delay_seconds', [0.1])\n"
        "    for k in ('util',):\n"
        "        reg.gauge(f'kftpu_fixd_{k}')\n"
        "    for k, v in snap.items():\n"
        "        reg.gauge(f'kftpu_fixdyn_{k}').set(v)\n")

    def _consumer(self, *names):
        chain = "".join(
            f"        elif name == '{n}':\n            out.append(value)\n"
            for n in names)
        return ("def probe(samples):\n"
                "    out = []\n"
                "    for name, labels, value in samples:\n"
                "        if False:\n"
                "            pass\n" + chain + "    return out\n")

    def _lint_consumer(self, *names):
        return xrules(
            {"kubeflow_tpu/serve/prod.py": self.PRODUCER,
             "kubeflow_tpu/serve/cons.py": self._consumer(*names)},
            lint=["kubeflow_tpu/serve/cons.py"])

    def test_exact_loop_expanded_suffix_and_prefix_names_match(self):
        """Every producer spelling counts: literal, loop-expanded
        f-string, histogram ``_bucket`` fan-out, dynamic f-string
        prefix."""
        assert self._lint_consumer(
            "kftpu_fix_total", "kftpu_fixd_util",
            "kftpu_fix_delay_seconds_bucket",
            "kftpu_fixdyn_anything") == []

    def test_renamed_consumer_is_caught(self):
        found = self._lint_consumer("kftpu_fix_total", "kftpu_fix_missing")
        assert [f.rule for f in found] == ["X701"]
        assert "kftpu_fix_missing" in found[0].message

    def test_contract_annotation_closes_it(self):
        src = ("def probe(samples):\n"
               "    for name, labels, value in samples:\n"
               "        # contract: produced by an out-of-scan exporter\n"
               "        if name == 'kftpu_fix_external':\n"
               "            return value\n")
        assert xrules({"kubeflow_tpu/serve/cons.py": src}) == []

    def test_standalone_lint_source_is_silent(self):
        """Without a Program the cross-component family must not guess
        from one module's half of the contract."""
        found = lint_source(self._consumer("kftpu_fix_missing"),
                            "kubeflow_tpu/serve/cons.py")
        assert [f for f in found if f.rule.startswith("X7")] == []


class TestProducedSeriesUnconsumed:
    def test_unconsumed_undocumented_series_is_caught(self):
        src = ("def reg_metrics(reg):\n"
               "    reg.counter('kftpu_fix_orphan_total')\n")
        found = xrules({"kubeflow_tpu/serve/prod.py": src})
        assert [f.rule for f in found] == ["X702"]
        assert "kftpu_fix_orphan_total" in found[0].message

    def test_consumed_in_sibling_module_is_clean(self):
        found = xrules(
            {"kubeflow_tpu/serve/prod.py":
                TestConsumedSeriesNeverProduced.PRODUCER,
             "kubeflow_tpu/serve/cons.py":
                TestConsumedSeriesNeverProduced()._consumer(
                    "kftpu_fix_total", "kftpu_fixd_util",
                    "kftpu_fix_delay_seconds_bucket")},
            lint=["kubeflow_tpu/serve/prod.py"])
        assert found == []

    def test_readme_catalog_counts_as_consumed(self):
        """A series documented in the real README metric catalog needs no
        in-scan consumer (dashboards are consumers the AST cannot see)."""
        src = ("def reg_metrics(reg):\n"
               "    reg.gauge('kftpu_serving_queue_depth')\n")
        assert xrules({"kubeflow_tpu/serve/prod.py": src}) == []


class TestHeaderContractDrift:
    def test_read_never_set_is_caught(self):
        src = ("def qos(h):\n"
               "    return h.get('X-Kftpu-Qoss')\n")
        found = xrules({"kubeflow_tpu/serve/s.py": src})
        assert [f.rule for f in found] == ["X703"]
        assert "X-Kftpu-Qoss" in found[0].message

    def test_set_never_read_is_caught(self):
        src = ("def fwd(h, out):\n"
               "    out['X-Kftpu-Dead'] = h['User-Agent']\n")
        found = xrules({"kubeflow_tpu/serve/s.py": src})
        assert [f.rule for f in found] == ["X703"]

    def test_constants_resolve_across_modules(self):
        """The centralized-constants idiom (core/headers.py) is the X703
        fix: both sides import ONE spelling, so the pair always
        matches."""
        sources = {
            "kubeflow_tpu/hdrs.py": "BUDGET = 'X-Kftpu-Budget'\n",
            "kubeflow_tpu/serve/a.py": (
                "from kubeflow_tpu.hdrs import BUDGET\n"
                "def stamp(out, ms):\n"
                "    out[BUDGET] = str(ms)\n"),
            "kubeflow_tpu/serve/b.py": (
                "from kubeflow_tpu.hdrs import BUDGET\n"
                "def read(h):\n"
                "    return h.get(BUDGET.lower())\n"),
        }
        assert xrules(sources) == []

    def test_case_drift_is_caught(self):
        src = ("def f(h, out):\n"
               "    out['X-Kftpu-Qos'] = h.get('X-KFTPU-QOS')\n")
        found = xrules({"kubeflow_tpu/serve/s.py": src})
        assert found and all(f.rule == "X703" for f in found)
        assert any("drift" in f.message for f in found)

    def test_serving_path_header_missing_from_forward_list(self):
        sources = {
            "kubeflow_tpu/hdrs.py": (
                "DEADLINE = 'X-Kftpu-Deadline-Ms'\n"
                "BUDGET = 'X-Kftpu-Budget'\n"
                "FORWARD_HEADERS = (DEADLINE,)\n"),
            "kubeflow_tpu/serve/a.py": (
                "from kubeflow_tpu.hdrs import BUDGET, DEADLINE\n"
                "def fwd(h, out):\n"
                "    out[DEADLINE] = h.get(DEADLINE)\n"
                "    out[BUDGET] = h.get(BUDGET)\n"),
        }
        found = xrules(sources, lint=["kubeflow_tpu/hdrs.py"])
        assert [f.rule for f in found] == ["X703"]
        assert "X-Kftpu-Budget" in found[0].message
        assert "forward" in found[0].message


class TestOrphanEnvVar:
    def test_read_never_set_is_caught(self):
        src = ("import os\n"
               "def knob():\n"
               "    return os.environ.get('KFTPU_FIX_KNOB')\n")
        found = xrules({"kubeflow_tpu/rt.py": src})
        assert [f.rule for f in found] == ["X704"]
        assert "KFTPU_FIX_KNOB" in found[0].message

    def test_set_in_child_env_dict_pairs_with_read(self):
        sources = {
            "kubeflow_tpu/cp.py": (
                "def child_env(v):\n"
                "    return {'KFTPU_FIX_KNOB': v}\n"),
            "kubeflow_tpu/rt.py": (
                "import os\n"
                "def knob():\n"
                "    return os.environ.get('KFTPU_FIX_KNOB')\n"),
        }
        assert xrules(sources) == []

    def test_set_never_read_is_caught_via_constant(self):
        sources = {
            "kubeflow_tpu/names.py": "ROOT = 'KFTPU_FIX_ROOT'\n",
            "kubeflow_tpu/cp.py": (
                "from kubeflow_tpu.names import ROOT\n"
                "def launch(env):\n"
                "    env[ROOT] = '/tmp'\n"),
        }
        found = xrules(sources, lint=["kubeflow_tpu/cp.py"])
        assert [f.rule for f in found] == ["X704"]
        assert "KFTPU_FIX_ROOT" in found[0].message

    def test_contract_annotation_closes_user_knobs(self):
        src = ("import os\n"
               "def knob():\n"
               "    # contract: operator-facing knob\n"
               "    return os.environ.get('KFTPU_FIX_KNOB')\n")
        assert xrules({"kubeflow_tpu/rt.py": src}) == []


class TestStatusFieldDrift:
    WRITER = ("def emit(step):\n"
              "    rec = {'step': step}\n"
              "    rec['loss_x'] = 1.0\n"
              "    return rec\n")

    def test_loop_tuple_consumption_catches_renamed_writer(self):
        reader = ("import json\n"
                  "def scrape(line, status):\n"
                  "    m = json.loads(line)\n"
                  "    status.step = m.get('step')\n"
                  "    for field in ('loss_x', 'mfu_x'):\n"
                  "        value = m.get(field)\n"
                  "        if value is not None:\n"
                  "            setattr(status, field, value)\n")
        found = xrules({"kubeflow_tpu/train/w.py": self.WRITER,
                        "kubeflow_tpu/op/r.py": reader},
                       lint=["kubeflow_tpu/op/r.py"])
        assert [f.rule for f in found] == ["X705"]
        assert "mfu_x" in found[0].message

    def test_produced_keys_are_clean(self):
        reader = ("import json\n"
                  "def scrape(line):\n"
                  "    m = json.loads(line)\n"
                  "    return m.get('step'), m.get('loss_x')\n")
        assert xrules({"kubeflow_tpu/train/w.py": self.WRITER,
                       "kubeflow_tpu/op/r.py": reader}) == []

    def test_gets_on_non_json_vars_are_ignored(self):
        src = ("def conf(d):\n"
               "    return d.get('whatever_missing_key')\n")
        assert xrules({"kubeflow_tpu/op/r.py": src}) == []


# -- Family T: distributed liveness (ISSUE 20) ---------------------------------


class TestUnboundedBlockingCall:
    def test_urlopen_without_timeout(self):
        src = ("import urllib.request\n"
               "def probe(url):\n"
               "    with urllib.request.urlopen(url) as r:\n"
               "        return r.read()\n")
        assert rules_of(src) == ["T801"]

    def test_explicit_timeout_none_still_fires(self):
        src = ("import urllib.request\n"
               "def probe(url):\n"
               "    return urllib.request.urlopen(url, timeout=None)\n")
        assert rules_of(src) == ["T801"]

    def test_bounded_urlopen_is_clean(self):
        src = ("import urllib.request\n"
               "def probe(url):\n"
               "    return urllib.request.urlopen(url, timeout=1.0)\n")
        assert rules_of(src) == []

    def test_queueish_get_and_zero_arg_wait(self):
        src = ("def pump(self):\n"
               "    item = self._work_q.get()\n"
               "    self._done.wait()\n")
        assert rules_of(src) == ["T801", "T801"]

    def test_bounded_get_nonblocking_get_and_str_join_clean(self):
        src = ("def pump(self, parts):\n"
               "    a = self._work_q.get(timeout=1.0)\n"
               "    b = self._work_q.get(block=False)\n"
               "    return ','.join(parts)\n")
        assert rules_of(src) == []

    def test_subprocess_without_timeout(self):
        src = ("import subprocess\n"
               "def run(cmd):\n"
               "    return subprocess.check_output(cmd)\n")
        assert rules_of(src) == ["T801"]

    def test_blocking_ok_annotation_closes_it(self):
        src = ("def pump(self):\n"
               "    # blocking-ok: close() pushes a None sentinel\n"
               "    return self._work_q.get()\n")
        assert rules_of(src) == []

    def test_wrapper_default_none_without_arg(self):
        """Call into a local wrapper whose timeout defaults to None and
        flows into urlopen: the call site must pass the budget."""
        src = ("import urllib.request\n"
               "def fetch(url, timeout=None):\n"
               "    return urllib.request.urlopen(url, timeout=timeout)\n"
               "def probe(url):\n"
               "    return fetch(url)\n")
        assert rules_of(src) == ["T801"]

    def test_wrapper_called_with_budget_is_clean(self):
        src = ("import urllib.request\n"
               "def fetch(url, timeout=None):\n"
               "    return urllib.request.urlopen(url, timeout=timeout)\n"
               "def probe(url):\n"
               "    return fetch(url, timeout=2.0)\n")
        assert rules_of(src) == []

    def test_wrapper_branching_on_none_is_designed(self):
        """A wrapper that BRANCHES on ``timeout is None`` has designed
        None-semantics (non-blocking drain): the default is a choice."""
        src = ("import urllib.request\n"
               "def fetch(url, timeout=None):\n"
               "    if timeout is None:\n"
               "        return None\n"
               "    return urllib.request.urlopen(url, timeout=timeout)\n"
               "def probe(url):\n"
               "    return fetch(url)\n")
        assert rules_of(src) == []

    def test_wrapper_plumbing_not_blocking_is_clean(self):
        """Forwarding the budget into a dataclass/other wrapper is
        plumbing, not a wait this call site could wedge on."""
        src = ("def submit(self, prompt, deadline=None):\n"
               "    return self._mk_request(prompt, deadline=deadline)\n"
               "def caller(self, prompt):\n"
               "    return self.submit(prompt)\n")
        assert rules_of(src) == []

    def test_test_paths_exempt(self):
        src = ("import urllib.request\n"
               "def test_probe(url):\n"
               "    return urllib.request.urlopen(url)\n")
        assert rules_of(src, "tests/test_fixture_t.py") == []


class TestAdHocRetryLoop:
    RETRY = ("import time\n"
             "def nudge(cp):\n"
             "    for _ in range(20):\n"
             "        try:\n"
             "            cp.patch({'x': 1})\n"
             "            break\n"
             "        except OSError:\n"
             "            time.sleep(0.05)\n")

    def test_sleep_and_swallow_loop(self):
        assert rules_of(self.RETRY) == ["T802"]

    def test_blessed_helper_is_clean(self):
        src = ("from kubeflow_tpu.serve.retry import call_with_retry\n"
               "def nudge(cp):\n"
               "    call_with_retry(lambda a: cp.patch({'x': 1}),\n"
               "                    retry_on=(OSError,))\n")
        assert rules_of(src) == []

    def test_reraising_handler_is_clean(self):
        src = self.RETRY.replace("            time.sleep(0.05)\n",
                                 "            time.sleep(0.05)\n"
                                 "            raise\n")
        assert rules_of(src) == []

    def test_sleep_without_retry_is_clean(self):
        src = ("import time\n"
               "def poll(pred):\n"
               "    while not pred():\n"
               "        time.sleep(0.05)\n")
        assert rules_of(src) == []

    def test_blocking_ok_on_loop_closes_it(self):
        src = self.RETRY.replace(
            "    for _ in range(20):\n",
            "    # blocking-ok: startup-only conflict window\n"
            "    for _ in range(20):\n")
        assert rules_of(src) == []


_T_CLASS = ("import threading\n"
            "class Pump:\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=self._loop)\n"
            "        self._thread.start()\n"
            "    def _loop(self):\n"
            "        pass\n")


class TestLeakedThread:
    def test_stop_surface_never_joins(self):
        src = _T_CLASS + ("    def stop(self):\n"
                          "        self._stop.set()\n")
        fs = lint_source(src, "kubeflow_tpu/serve/fixture.py")
        assert [f.rule for f in fs] == ["T803"]
        assert "Pump._thread" in fs[0].message

    def test_joining_stop_is_clean(self):
        src = _T_CLASS + ("    def stop(self):\n"
                          "        self._thread.join(timeout=5.0)\n")
        assert rules_of(src) == []

    def test_local_thread_never_joined(self):
        src = ("import threading\n"
               "def run(work):\n"
               "    t = threading.Thread(target=work)\n"
               "    t.start()\n"
               "    return 1\n")
        assert rules_of(src) == ["T803"]

    def test_local_joined_daemon_or_escaping_clean(self):
        src = ("import threading\n"
               "def a(work):\n"
               "    t = threading.Thread(target=work)\n"
               "    t.start()\n"
               "    t.join(timeout=5.0)\n"
               "def b(work):\n"
               "    t = threading.Thread(target=work, daemon=True)\n"
               "    t.start()\n"
               "def c(work, sink):\n"
               "    t = threading.Thread(target=work)\n"
               "    t.start()\n"
               "    sink.append(t)\n"
               "def d(work):\n"
               "    t = threading.Thread(target=work)\n"
               "    t.start()\n"
               "    return t\n")
        assert rules_of(src) == []


class TestThreadLifecycle:
    def test_thread_in_class_without_stop_surface(self):
        src = ("import threading\n"
               "class Fire:\n"
               "    def launch(self):\n"
               "        t = threading.Thread(target=self._loop)\n"
               "        t.start()\n"
               "    def _loop(self):\n"
               "        pass\n")
        fs = lint_source(src, "kubeflow_tpu/serve/fixture.py")
        assert [f.rule for f in fs] == ["T804"]
        assert "'Fire'" in fs[0].message
        assert "stop/close/shutdown" in fs[0].message

    def test_daemon_thread_without_stop_surface_is_clean(self):
        src = ("import threading\n"
               "class Fire:\n"
               "    def launch(self):\n"
               "        t = threading.Thread(target=self._loop,\n"
               "                             daemon=True)\n"
               "        t.start()\n"
               "    def _loop(self):\n"
               "        pass\n")
        assert rules_of(src) == []

    def test_unbounded_queue_get_under_lock(self):
        """The attr-based wait C302's fixed call set misses: held-lock
        sites report as T804, never ALSO as T801."""
        src = ("import threading\n"
               "class R:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "    def drain(self):\n"
               "        with self._lock:\n"
               "            return self._work_q.get()\n")
        fs = lint_source(src, "kubeflow_tpu/serve/fixture.py")
        assert [f.rule for f in fs] == ["T804"]
        assert "while holding" in fs[0].message

    def test_bounded_get_under_lock_is_clean(self):
        src = ("import threading\n"
               "class R:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "    def drain(self):\n"
               "        with self._lock:\n"
               "            return self._work_q.get(timeout=1.0)\n")
        assert rules_of(src) == []

    def test_c302_site_not_double_reported(self):
        """urlopen under a lock is C302's finding — T804 must not also
        fire on it."""
        src = ("import threading\n"
               "import urllib.request\n"
               "class R:\n"
               "    def __init__(self):\n"
               "        self._lock = threading.Lock()\n"
               "    def fetch(self, url):\n"
               "        with self._lock:\n"
               "            return urllib.request.urlopen(url, timeout=1)\n")
        assert rules_of(src) == ["C302"]


class TestDeadlinePropagationDrift:
    _H = ("import urllib.request\n"
          "class Handler:\n"
          "    def _budget_s(self):\n"
          "        return self.headers.get('X-Kftpu-Deadline-Ms')\n")

    def test_fixed_literal_timeout_in_reading_scope(self):
        src = self._H + (
            "    def relay(self, req):\n"
            "        return urllib.request.urlopen(req, timeout=30.0)\n")
        fs = lint_source(src, "kubeflow_tpu/serve/fixture.py")
        assert [f.rule for f in fs] == ["T805"]
        assert "timeout=30.0" in fs[0].message

    def test_derived_timeout_is_clean(self):
        src = self._H + (
            "    def relay(self, req, remaining):\n"
            "        return urllib.request.urlopen(req, timeout=remaining)\n")
        assert rules_of(src) == []

    def test_scope_without_deadline_read_is_clean(self):
        src = ("import urllib.request\n"
               "class Other:\n"
               "    def relay(self, req):\n"
               "        return urllib.request.urlopen(req, timeout=30.0)\n")
        assert rules_of(src) == []


# -- seeded regressions against the REAL codebase (acceptance criteria) --------


def _new_findings(relpath: str, old: str, new: str):
    with open(os.path.join(REPO, relpath)) as f:
        src = f.read()
    mutated = src.replace(old, new, 1)
    assert mutated != src, f"mutation anchor vanished from {relpath}"
    before = {f.fingerprint for f in lint_source(src, relpath)}
    return [f for f in lint_source(mutated, relpath)
            if f.fingerprint not in before]


class TestSeededRegressions:
    def test_pr4_full_table_reupload_is_caught(self):
        """Re-introducing the PR-4 bug — a per-round full page-table
        upload in the dispatch hot loop (since PR 49 ``_ready_round``: what
        stands before the decode program and before the chunk program that
        carries a step alike) — produces exactly one D103."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/engine.py",
            "        self._sync_decode_state()\n",
            "        self._sync_decode_state()\n"
            "        table = jnp.asarray(self._table)\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "D103" and "self._table" in f.message
        assert "_ready_round" in f.message

    def test_removed_router_lock_is_caught(self):
        """Dropping one router lock acquisition produces exactly one C301
        naming the attribute and the offending method."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/router.py",
            "    def note_activity(self) -> None:\n"
            "        with self._lock:\n",
            "    def note_activity(self) -> None:\n"
            "        if True:\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "C301"
        assert "_last_activity" in f.message
        assert "note_activity" in f.message

    def test_bad_metric_name_is_caught(self):
        """A metric family registered without the kftpu_ prefix fails at
        lint time (obs/registry.lint() made static)."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/server.py",
            'reg.gauge("kftpu_serving_queue_depth")',
            'reg.gauge("serving_queue_depth")')
        assert [f.rule for f in fresh] == ["M201"]

    def test_dropped_decode_donation_is_caught(self):
        """Removing the decode dispatch's donate_argnums — the 2x-HBM
        carry — produces exactly one S401."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/engine.py",
            "            _paged_decode_fn, static_argnums=(5, 6),\n"
            "            donate_argnums=(1, 2, 3))",
            "            _paged_decode_fn, static_argnums=(5, 6))")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "S401" and "self._paged_decode_n" in f.message

    def test_exception_path_page_leak_is_caught(self):
        """A raise-capable call between the page alloc and its ownership
        recording produces exactly one R501."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/engine.py",
            "owner=self._slot_owner(slot_idx))\n",
            "owner=self._slot_owner(slot_idx))\n"
            "            self._refresh_pool_gauge()\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "R501" and "_ensure_pages" in f.message

    def test_fire_and_forget_trainer_save_is_caught(self):
        """A bare ``self.ckpt.save(...)`` dropped into the training loop
        (the pre-ISSUE-9 Trainer.save shape) produces exactly one R504."""
        fresh = _new_findings(
            "kubeflow_tpu/train/trainer.py",
            "        start = self.try_resume()\n",
            "        start = self.try_resume()\n"
            "        self.ckpt.save(0, self.task.state)\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "R504" and "self.ckpt.save" in f.message

    def test_injected_router_lock_inversion_is_caught(self):
        """A second router lock acquired in both orders produces exactly
        one R503 naming the cycle."""
        relpath = "kubeflow_tpu/serve/router.py"
        with open(os.path.join(REPO, relpath)) as f:
            src = f.read()
        mut = src.replace(
            "        self._lock = threading.Lock()\n",
            "        self._lock = threading.Lock()\n"
            "        self._aux_lock = threading.Lock()\n", 1)
        mut = mut.replace(
            "    def note_activity(self) -> None:\n",
            "    def _seed_ab(self):\n"
            "        with self._lock:\n"
            "            with self._aux_lock:\n"
            "                pass\n\n"
            "    def _seed_ba(self):\n"
            "        with self._aux_lock:\n"
            "            with self._lock:\n"
            "                pass\n\n"
            "    def note_activity(self) -> None:\n", 1)
        assert mut != src
        before = {f.fingerprint for f in lint_source(src, relpath)}
        fresh = [f for f in lint_source(mut, relpath)
                 if f.fingerprint not in before]
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "R503"
        assert "Router._aux_lock" in f.message and "Router._lock" in f.message

    def test_weak_type_scalar_into_decode_dispatch_is_caught(self):
        """Replacing the decode dispatch's PRNG key with a bare
        Python float — a weak-typed cache entry per dispatch — produces
        exactly one F602."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/engine.py",
            "            out, self.cache, st, tbl, rows = self._paged_decode_n(\n"
            "                self.params, self.cache, self._dstate.arrays,\n"
            "                self._dstate.table, key, k_steps, mode)",
            "            out, self.cache, st, tbl, rows = self._paged_decode_n(\n"
            "                self.params, self.cache, self._dstate.arrays,\n"
            "                self._dstate.table, 0.5, k_steps, mode)")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "F602" and "self._paged_decode_n" in f.message

    def test_fresh_tuple_static_arg_is_caught(self):
        """Feeding the decode dispatch's static num_steps position a
        per-call tuple produces exactly one F604."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/engine.py",
            "            out, self.cache, st, tbl, rows = self._paged_decode_n(\n"
            "                self.params, self.cache, self._dstate.arrays,\n"
            "                self._dstate.table, key, k_steps, mode)",
            "            out, self.cache, st, tbl, rows = self._paged_decode_n(\n"
            "                self.params, self.cache, self._dstate.arrays,\n"
            "                self._dstate.table, key, (k_steps,), mode)")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "F604" and "self._paged_decode_n" in f.message


def _new_findings_prog(relpath: str, old: str, new: str):
    """The X-family seeded-regression helper: lint the (mutated) module
    under the FULL package Program so the cross-component table sees the
    real other side of each contract."""
    from kubeflow_tpu.analysis import core

    with open(os.path.join(REPO, relpath)) as f:
        src = f.read()
    mutated = src.replace(old, new, 1)
    assert mutated != src, f"mutation anchor vanished from {relpath}"

    def lint(text: str):
        mods = []
        for path in core.iter_py_files(
                [os.path.join(REPO, p) for p in
                 ("kubeflow_tpu", "scripts", "bench.py", "bench_serve.py")]):
            rel = os.path.relpath(os.path.abspath(path), REPO).replace(
                os.sep, "/")
            if rel == relpath:
                mods.append(core.Module(relpath, text))
            else:
                mods.append(core.load_module(path, rel))
        core.Program(mods)
        target = next(m for m in mods if m.relpath == relpath)
        return core.lint_module(target)

    before = {f.fingerprint for f in lint(src)}
    return [f for f in lint(mutated) if f.fingerprint not in before]


class TestContractSeededRegressions:
    def test_renamed_probe_series_is_caught(self):
        """Renaming one series the SLO autoscaler's probe scrapes — while
        the engine keeps emitting the old name — produces exactly one
        X701: the silent-HOLD drift class ISSUE 10 exists to kill."""
        fresh = _new_findings_prog(
            "kubeflow_tpu/serve/isvc_controller.py",
            '"kftpu_serving_requests_total"',
            '"kftpu_serving_requests_totals"')
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "X701"
        assert "kftpu_serving_requests_totals" in f.message

    def test_typoed_header_literal_is_caught(self):
        """Replacing the model server's QOS_HEADER constant read with a
        typoed literal produces exactly one X703 — nothing sets the
        misspelled header, so every request silently defaults."""
        fresh = _new_findings_prog(
            "kubeflow_tpu/serve/server.py",
            'raw = self.headers.get(QOS_HEADER) or body.get("qos")',
            'raw = self.headers.get("X-Kftpu-Qoss") or body.get("qos")')
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "X703" and "X-Kftpu-Qoss" in f.message

    def test_dropped_forward_list_entry_is_caught(self):
        """Removing the trace header from core/headers.FORWARD_HEADERS
        produces exactly one X703 on the forward-list — the ChaosProxy
        would silently break trace continuity through it."""
        fresh = _new_findings_prog(
            "kubeflow_tpu/core/headers.py",
            "FORWARD_HEADERS = (DEADLINE_HEADER, QOS_HEADER, TRACE_HEADER,\n"
            "                   DECODE_BACKEND_HEADER, DECODE_ALTS_HEADER,\n"
            "                   MODEL_HEADER, HANDOFF_DTYPE_HEADER,\n"
            "                   HANDOFF_WIRE_HEADER)",
            "FORWARD_HEADERS = (DEADLINE_HEADER, QOS_HEADER,\n"
            "                   DECODE_BACKEND_HEADER, DECODE_ALTS_HEADER,\n"
            "                   MODEL_HEADER, HANDOFF_DTYPE_HEADER,\n"
            "                   HANDOFF_WIRE_HEADER)")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "X703" and "X-Kftpu-Trace" in f.message

    def test_orphaned_rendezvous_env_is_caught(self):
        """Renaming one rendezvous env var on the WRITE side (bootstrap's
        child-env dict) produces X704 on the now-orphaned pair."""
        fresh = _new_findings_prog(
            "kubeflow_tpu/runtime/bootstrap.py",
            '"KFTPU_REPLICA_INDEX": str(self.replica_index)',
            '"KFTPU_REPLICA_IDX": str(self.replica_index)')
        assert {f.rule for f in fresh} == {"X704"}
        assert any("KFTPU_REPLICA_IDX" in f.message for f in fresh)


class TestLivenessSeededRegressions:
    def test_stripped_probe_timeout_is_caught(self):
        """Removing the router metrics probe's urlopen timeout — the
        exact unbounded wait that wedged a router behind a SIGKILLed
        replica — produces exactly one T801."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/router.py",
            'with urllib.request.urlopen(url + "/metrics",\n'
            '                                            timeout=1.0) as r:',
            'with urllib.request.urlopen(url + "/metrics") as r:')
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "T801" and "urllib.request.urlopen" in f.message

    def test_inline_retry_loop_is_caught(self):
        """An inline sleep-and-swallow retry loop instead of the blessed
        serve/retry.py helper produces exactly one T802."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/handoff.py",
            "    def validate(self) -> None:\n",
            "    def validate(self) -> None:\n"
            "        import time\n"
            "        for _ in range(5):\n"
            "            try:\n"
            "                self.kv_len\n"
            "                len(self.prompt_tokens)\n"
            "                break\n"
            "            except ValueError:\n"
            "                time.sleep(0.05)\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "T802" and "call_with_retry" in f.message

    def test_dropped_kv_migrate_join_is_caught(self):
        """Dropping the kv-migrate join from the tiered cache's close()
        produces exactly one T803 — the leak KFTPU_SANITIZE=threads
        would catch live at stop."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/kvtier.py",
            "            self._queue.put(None)\n"
            "            self._thread.join(timeout=5.0)\n",
            "            self._queue.put(None)\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "T803" and "._thread" in f.message

    def test_queue_get_under_router_lock_is_caught(self):
        """An unbounded queue get while holding the router lock — the
        attr-based wait C302's fixed call set misses — produces exactly
        one T804 (and NOT also a T801: one finding per defect)."""
        fresh = _new_findings(
            "kubeflow_tpu/serve/router.py",
            "    def note_activity(self) -> None:\n",
            "    def _drain_locked(self):\n"
            "        with self._lock:\n"
            "            return self._retire_q.get()\n\n"
            "    def note_activity(self) -> None:\n")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "T804" and "while holding" in f.message

    def test_fixed_relay_timeout_is_caught(self):
        """Hardening the relay's derived ``timeout=remaining`` to a
        literal — while the handler scope reads the deadline header,
        resolved through the Program-wide header table — produces
        exactly one T805."""
        fresh = _new_findings_prog(
            "kubeflow_tpu/serve/router.py",
            "resp = urllib.request.urlopen(req, timeout=remaining)",
            "resp = urllib.request.urlopen(req, timeout=30.0)")
        assert len(fresh) == 1
        f = fresh[0]
        assert f.rule == "T805" and "timeout=30.0" in f.message


# -- self-scan + CLI -----------------------------------------------------------


class TestSelfScan:
    def test_repo_is_clean_against_committed_baseline(self):
        baseline_path = find_baseline([os.path.join(REPO, "kubeflow_tpu")])
        baseline = Baseline.load(baseline_path) if baseline_path else None
        result = run_lint(
            [os.path.join(REPO, p) for p in
             ("kubeflow_tpu", "scripts", "bench.py", "bench_serve.py")],
            baseline=baseline, root=REPO)
        assert result.files_scanned > 50
        assert result.errors == []
        assert result.new == [], "\n".join(
            f.render() for f in result.new)


class TestCli:
    def test_kftpu_lint_exit_codes(self, tmp_path):
        from kubeflow_tpu.cli import main as cli_main

        dirty = tmp_path / "dirty.py"
        dirty.write_text(TestFullBufferReupload.POSITIVE)
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert cli_main(["lint", "--no-baseline", str(clean)]) == 0
        assert cli_main(["lint", "--no-baseline", str(dirty)]) == 1

    def test_json_output_has_clickable_locations(self, tmp_path):
        dirty = tmp_path / "dirty.py"
        dirty.write_text(TestFullBufferReupload.POSITIVE)
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--json",
             "--no-baseline", str(dirty)],
            capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        assert doc["ok"] is False and len(doc["findings"]) == 1
        f = doc["findings"][0]
        assert f["rule"] == "D103" and f["line"] == 4 and f["col"] >= 1

    def test_update_baseline_then_clean(self, tmp_path):
        from kubeflow_tpu.cli import main as cli_main

        dirty = tmp_path / "dirty.py"
        dirty.write_text(TestFullBufferReupload.POSITIVE)
        bl = tmp_path / "bl.json"
        assert cli_main(["lint", "--update-baseline",
                         "--baseline", str(bl), str(dirty)]) == 0
        assert cli_main(["lint", "--baseline", str(bl), str(dirty)]) == 0
        assert cli_main(["lint", "--no-baseline", str(dirty)]) == 1

    def test_list_rules(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--list-rules"],
            capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 0
        for rid in ("D103", "C301", "M201", "S401", "R503", "X701",
                    "X703", "X704", "X705"):
            assert rid in proc.stdout

    def test_contracts_json_round_trips(self):
        """--contracts-json emits the whole-program contract table, and
        the CLI output equals the in-process extraction byte for byte
        (after JSON round-trip) — the manifest the runtime contract
        auditor diffs against."""
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis",
             "--contracts-json", "kubeflow_tpu"],
            capture_output=True, text=True, cwd=REPO)
        assert proc.returncode == 0, proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["version"] == 1
        produced = doc["series"]["produced"]
        assert "kftpu_serving_requests_total" in produced
        assert all(":" in s for s in
                   produced["kftpu_serving_requests_total"])  # clickable
        assert "kftpu_router_" in doc["series"]["produced_prefixes"]
        assert "kftpu_serving_queue_delay_seconds" in \
            doc["series"]["histograms"]
        assert "kftpu_serving_qos_ttft_p95_ms" in doc["series"]["consumed"]
        for h in ("X-Kftpu-Deadline-Ms", "X-Kftpu-Qos", "X-Kftpu-Trace"):
            assert h in doc["headers"]["set"] and h in doc["headers"]["read"]
            assert h in doc["headers"]["forward_list"]
        assert "KFTPU_PROCESS_ID" in doc["env"]["set"]
        assert "KFTPU_PROCESS_ID" in doc["env"]["read"]
        assert "goodput" in doc["fields"]["consumed"]
        assert "goodput" in doc["fields"]["produced"]

        from kubeflow_tpu.analysis import build_program
        from kubeflow_tpu.analysis.rules_contracts import contract_manifest

        local = json.loads(json.dumps(contract_manifest(
            build_program([os.path.join(REPO, "kubeflow_tpu")],
                          root=REPO))))
        assert local == doc

    def _git_repo(self, tmp_path):
        def git(*args):
            subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                 *args], cwd=tmp_path, check=True, capture_output=True)
        git("init", "-q")
        (tmp_path / "clean.py").write_text("x = 1\n")
        git("add", "clean.py")
        git("commit", "-qm", "seed")
        return git

    def test_changed_lints_only_touched_files(self, tmp_path):
        self._git_repo(tmp_path)
        # clean.py is committed and untouched; dirty.py is new + dirty
        (tmp_path / "dirty.py").write_text(
            TestFullBufferReupload.POSITIVE)
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--changed",
             "--no-baseline", "--json"],
            capture_output=True, text=True, cwd=tmp_path, env=env)
        doc = json.loads(proc.stdout)
        assert proc.returncode == 1
        assert doc["files_scanned"] == 1       # dirty.py only
        assert [f["rule"] for f in doc["findings"]] == ["D103"]

    def test_changed_with_nothing_touched_is_ok(self, tmp_path):
        self._git_repo(tmp_path)
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--changed",
             "--no-baseline"],
            capture_output=True, text=True, cwd=tmp_path, env=env)
        assert proc.returncode == 0
        assert "0 files changed" in proc.stdout

    def test_changed_rejects_update_baseline(self, tmp_path):
        self._git_repo(tmp_path)
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--changed",
             "--update-baseline"],
            capture_output=True, text=True, cwd=tmp_path, env=env)
        assert proc.returncode == 2
        assert "full scan" in proc.stderr

    def test_changed_skips_deleted_files(self, tmp_path):
        """A tracked .py removed from the working tree must not reach the
        file walker (it used to error the pre-commit path): the deletion
        shows up in the diff but is excluded by its D status."""
        git = self._git_repo(tmp_path)
        git("rm", "-q", "clean.py")
        (tmp_path / "dirty.py").write_text(
            TestFullBufferReupload.POSITIVE)
        env = dict(os.environ, PYTHONPATH=REPO)
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--changed",
             "--no-baseline", "--json"],
            capture_output=True, text=True, cwd=tmp_path, env=env)
        doc = json.loads(proc.stdout)
        assert proc.returncode == 1, proc.stderr
        assert doc["errors"] == []
        assert doc["files_scanned"] == 1       # dirty.py; NOT clean.py
        assert [f["rule"] for f in doc["findings"]] == ["D103"]

    def test_changed_rename_lints_only_new_name(self, tmp_path):
        """A committed rename lints the NEW path only — the old name is
        gone from disk and must be skipped by its R status."""
        git = self._git_repo(tmp_path)
        git("mv", "clean.py", "renamed.py")
        git("commit", "-qm", "rename")
        from kubeflow_tpu.analysis import changed_files

        files = changed_files("HEAD~1", root=str(tmp_path))
        assert files == ["renamed.py"]

    def test_json_reports_wall_time(self, tmp_path):
        (tmp_path / "one.py").write_text("x = 1\n")
        proc = subprocess.run(
            [sys.executable, "-m", "kubeflow_tpu.analysis", "--json",
             "--no-baseline", str(tmp_path / "one.py")],
            capture_output=True, text=True, cwd=REPO)
        doc = json.loads(proc.stdout)
        assert proc.returncode == 0
        assert doc["wall_time_s"] > 0

    def test_update_baseline_is_deterministic(self, tmp_path):
        """The baseline file is a pure function of the finding SET:
        shuffled finding order writes byte-identical output, so baseline
        diffs are reviewable."""
        import random

        from kubeflow_tpu.analysis import lint_source

        src = TestFullBufferReupload.POSITIVE + (
            "    def again(self):  # hot-loop\n"
            "        jnp.asarray(self._other)\n")
        findings = lint_source(src, "kubeflow_tpu/serve/fixture.py")
        assert len(findings) >= 2
        blobs = []
        for seed in (0, 1, 2):
            shuffled = list(findings)
            random.Random(seed).shuffle(shuffled)
            out = tmp_path / f"bl{seed}.json"
            Baseline.from_findings(shuffled).save(str(out))
            blobs.append(out.read_bytes())
        assert blobs[0] == blobs[1] == blobs[2]
