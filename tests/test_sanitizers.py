"""Runtime sanitizers (ISSUEs 7/8): KFTPU_SANITIZE mode parsing, the
refcount owner-stamping allocator, the lockorder watchdog — the dynamic
cross-checks of the S4xx/R5xx static rules — and the recompile watchdog,
the dynamic half of the F6xx compilation-stability family: zero
steady-state recompiles on warmed dense/paged/spec engines and a warmed
train step, every warmup trace attributed to a call site.

The watchdog tests install/uninstall within the process; every test
restores the real threading factories / logging wiring on exit (the
uninstall is in a finally) so the rest of the suite runs unpatched."""

import logging
import threading

import pytest

from kubeflow_tpu.runtime import sanitize
from kubeflow_tpu.runtime.sanitize import (
    LockOrderError, RecompileError, install_lockorder_watchdog,
    install_recompile_watchdog, recompile_report, sanitize_modes,
    uninstall_lockorder_watchdog, uninstall_recompile_watchdog,
)


class TestModeParsing:
    def test_unset_and_zero_are_off(self, monkeypatch):
        monkeypatch.delenv("KFTPU_SANITIZE", raising=False)
        assert sanitize_modes() == frozenset()
        monkeypatch.setenv("KFTPU_SANITIZE", "0")
        assert sanitize_modes() == frozenset()

    def test_legacy_one_means_transfer(self, monkeypatch):
        monkeypatch.setenv("KFTPU_SANITIZE", "1")
        assert sanitize_modes() == {"transfer"}

    def test_named_modes_and_lists(self, monkeypatch):
        monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
        assert sanitize_modes() == {"refcount"}
        monkeypatch.setenv("KFTPU_SANITIZE", "refcount,lockorder")
        assert sanitize_modes() == {"refcount", "lockorder"}
        monkeypatch.setenv("KFTPU_SANITIZE", "all")
        assert sanitize_modes() == {"transfer", "refcount", "lockorder",
                                    "recompile", "contract", "threads"}

    def test_recompile_and_contract_are_named_modes(self, monkeypatch):
        # neither must degrade to the legacy transfer fallback
        monkeypatch.setenv("KFTPU_SANITIZE", "recompile")
        assert sanitize_modes() == {"recompile"}
        monkeypatch.setenv("KFTPU_SANITIZE", "contract")
        assert sanitize_modes() == {"contract"}

    def test_threads_is_a_named_mode(self, monkeypatch):
        monkeypatch.setenv("KFTPU_SANITIZE", "threads")
        assert sanitize_modes() == {"threads"}

    def test_unknown_token_degrades_to_transfer(self, monkeypatch):
        # pre-ISSUE-7 setups used arbitrary truthy values for the
        # transfer guard; they must keep meaning what they meant
        monkeypatch.setenv("KFTPU_SANITIZE", "yes")
        assert sanitize_modes() == {"transfer"}

    def test_refcount_mode_does_not_engage_transfer_guard(self, monkeypatch):
        monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
        assert "transfer" not in sanitize_modes()


class TestRefcountStamping:
    @pytest.fixture()
    def pool(self, monkeypatch):
        monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
        from kubeflow_tpu.serve.paged import PageAllocator

        return PageAllocator(8, 4)

    def test_owner_attribution_and_balance(self, pool):
        assert pool.refcount_debug
        # deliberately unrecorded allocs: the leak report is the subject
        a = pool.alloc(2, owner="req-A")  # lint: disable=R501
        b = pool.alloc(1, owner="req-B")  # lint: disable=R501
        rep = pool.leak_report_by_owner()
        assert rep == {"req-A": 2, "req-B": 1}
        pool.free(a)
        assert pool.leak_report_by_owner() == {"req-B": 1}
        pool.free(b)
        assert pool.leak_report_by_owner() == {}
        pool.assert_quiescent()
        assert pool.stats["stamped_allocs"] == 3

    def test_incref_stacks_stamps(self, pool):
        pages = pool.alloc(1, owner="first")
        pool.incref(pages, owner="second")
        assert pool.leak_report_by_owner() == {"first": 1, "second": 1}
        pool.free(pages)     # LIFO: pops "second"
        assert pool.leak_report_by_owner() == {"first": 1}
        pool.free(pages)
        pool.assert_quiescent()

    def test_quiescence_failure_names_the_owner(self, pool):
        pool.alloc(1, owner="req-leaky")
        with pytest.raises(AssertionError, match="req-leaky"):
            pool.assert_quiescent()

    def test_site_stamp_when_no_owner(self, pool):
        pool.alloc(1)
        (label,) = pool.leak_report_by_owner()
        assert "test_sanitizers.py" in label

    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("KFTPU_SANITIZE", raising=False)
        from kubeflow_tpu.serve.paged import PageAllocator

        pool = PageAllocator(4, 4)
        pool.free(pool.alloc(2, owner="x"))
        assert not pool.refcount_debug
        assert pool._stamps == {}
        assert pool.stats["stamped_allocs"] == 0
        pool.assert_quiescent()


class TestLockOrderWatchdog:
    def test_inversion_raises_and_releases(self):
        wd = install_lockorder_watchdog()
        try:
            a = threading.Lock()
            b = threading.Lock()
            with a:
                with b:
                    pass
            with pytest.raises(LockOrderError, match="inversion"):
                with b:
                    with a:
                        pass
            # the failed acquisition must not leave 'a' locked
            assert a.acquire(timeout=1)
            a.release()
            rep = wd.report()
            assert any(rep.values())
        finally:
            uninstall_lockorder_watchdog()

    def test_consistent_order_is_silent(self):
        install_lockorder_watchdog()
        try:
            a = threading.Lock()
            b = threading.Lock()
            for _ in range(3):
                with a:
                    with b:
                        pass
        finally:
            uninstall_lockorder_watchdog()

    def test_same_site_instances_are_exempt(self):
        # ordered traversal over same-class instances (two Routers' _lock
        # from one creation line) is legitimate, not an inversion
        install_lockorder_watchdog()
        try:
            def mk():
                return threading.Lock()

            a, b = mk(), mk()
            with a:
                with b:
                    pass
            with b:
                with a:
                    pass
        finally:
            uninstall_lockorder_watchdog()

    def test_condition_event_queue_still_work(self):
        import queue

        install_lockorder_watchdog()
        try:
            q = queue.Queue()
            q.put(1)
            assert q.get(timeout=1) == 1
            ev = threading.Event()
            ev.set()
            assert ev.wait(0.5)
            lk = threading.Lock()
            cv = threading.Condition(lk)
            hits = []

            def waiter():
                with cv:
                    while not hits:
                        cv.wait(1.0)

            t = threading.Thread(target=waiter)
            t.start()
            with cv:
                hits.append(1)
                cv.notify_all()
            t.join(timeout=5)
            assert not t.is_alive()
        finally:
            uninstall_lockorder_watchdog()

    def test_cross_thread_edges_compose(self):
        # thread 1 records a->b; the MAIN thread closing b->a still fails:
        # the graph is process-wide, not per-thread
        install_lockorder_watchdog()
        try:
            a = threading.Lock()
            b = threading.Lock()

            def t1():
                with a:
                    with b:
                        pass

            t = threading.Thread(target=t1)
            t.start()
            t.join(timeout=5)
            with pytest.raises(LockOrderError):
                with b:
                    with a:
                        pass
        finally:
            uninstall_lockorder_watchdog()

    def test_install_is_idempotent_and_uninstall_restores(self):
        orig = threading.Lock
        wd1 = install_lockorder_watchdog()
        try:
            wd2 = install_lockorder_watchdog()
            assert wd1 is wd2
        finally:
            uninstall_lockorder_watchdog()
        assert threading.Lock is orig
        assert sanitize.lockorder_watchdog() is None


@pytest.fixture()
def recompile_wd():
    wd = install_recompile_watchdog()
    wd.reset()
    try:
        yield wd
    finally:
        uninstall_recompile_watchdog()


class TestRecompileWatchdog:
    def test_counts_and_attributes_each_compile(self, recompile_wd):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        f = jax.jit(lambda x: x * 2)
        f(jnp.ones(3))
        f(jnp.ones(3))              # cache hit: not a compile
        recompile_wd.mark_warm()
        recompile_wd.assert_no_steady_recompiles()   # still clean
        f(jnp.ones(5))              # new shape: steady retrace
        rep = recompile_report()
        assert rep["warm"] is True
        assert any(e["fn"] == "<lambda>" for e in rep["warmup"])
        # every entry — warmup and steady — is attributed to THIS file
        for e in rep["warmup"] + rep["steady"]:
            assert "test_sanitizers.py" in e["site"], e
        assert rep["steady_count"] >= 1
        with pytest.raises(RecompileError) as exc:
            recompile_wd.assert_no_steady_recompiles()
        assert "test_sanitizers.py" in str(exc.value)

    def test_weak_type_is_its_own_cache_entry(self, recompile_wd):
        """The F602 defect, observed dynamically: a Python scalar and an
        explicitly-dtyped scalar of the same value are two compiles."""
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        f = jax.jit(lambda x: x + 1)
        f(jnp.float32(2.0))
        recompile_wd.mark_warm()
        # retrace-ok: the weak-typed retrace IS this test's subject
        f(2.0)
        assert recompile_wd.steady_count() >= 1

    def test_install_is_idempotent_and_uninstall_restores(self):
        lg = logging.getLogger("jax._src.interpreters.pxla")
        level, prop = lg.level, lg.propagate
        wd1 = install_recompile_watchdog()
        try:
            assert install_recompile_watchdog() is wd1
            assert lg.level == logging.DEBUG and lg.propagate is False
        finally:
            uninstall_recompile_watchdog()
        assert lg.level == level and lg.propagate == prop
        assert sanitize.recompile_watchdog() is None
        assert recompile_report() == {}          # off = empty payload

    def test_warnings_still_reach_parent_handlers(self, recompile_wd):
        """Propagation is cut to keep DEBUG compile records off the
        console, but WARNING+ records must still reach the jax logger's
        own handlers."""
        seen = []

        class Probe(logging.Handler):
            def emit(self, record):
                seen.append(record.getMessage())

        probe = Probe()
        parent = logging.getLogger("jax")
        parent.addHandler(probe)
        try:
            logging.getLogger("jax._src.interpreters.pxla").warning(
                "a real warning")
        finally:
            parent.removeHandler(probe)
        assert seen == ["a real warning"]


class TestSteadyStateZeroRecompiles:
    """The acceptance criterion: warmed engines and a warmed train step
    hold a FIXED trace set — identical steady-state traffic compiles
    nothing, and every warmup trace is attributed to a named site."""

    PROMPTS = [[3, 5, 7, 3, 5, 7, 3, 5], [2, 4, 6, 2, 4, 6, 2, 4]]

    def _drive(self, eng, wd):
        from kubeflow_tpu.serve.engine import SamplingParams

        for p in self.PROMPTS:
            eng.generate(p, SamplingParams(max_new_tokens=8))
        wd.mark_warm()
        for p in self.PROMPTS:
            eng.generate(p, SamplingParams(max_new_tokens=8))
        rep = recompile_report()
        assert rep["warmup"], "warmup must record attributed compiles"
        assert all(e["site"] != "<unknown>" for e in rep["warmup"])
        assert rep["steady_count"] == 0, rep["steady"]
        wd.assert_no_steady_recompiles()

    def test_dense_and_spec_engines(self, recompile_wd):
        jax = pytest.importorskip("jax")  # noqa: F841
        from kubeflow_tpu.core.serving import BatchingSpec, SpeculativeSpec
        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.serve.engine import LLMEngine

        cfg = preset("tiny")
        # One chunk covers any prompt here, so every chunk program is at
        # the whole table's context: a prefix hit on the second pass moves
        # a chunk's start, not its context bucket.
        self._drive(LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=64, page_size=16)),
            recompile_wd)
        recompile_wd.reset()
        self._drive(LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=64, page_size=16,
            speculative=SpeculativeSpec(mode="ngram", k=3))),
            recompile_wd)

    def test_paged_engine(self, recompile_wd):
        jax = pytest.importorskip("jax")  # noqa: F841
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.serve.engine import LLMEngine

        cfg = preset("tiny")
        eng = LLMEngine(cfg, BatchingSpec(
            max_batch_size=2, max_seq_len=64, paged=True, page_size=16))
        self._drive(eng, recompile_wd)
        eng._allocator.assert_quiescent()

    @pytest.mark.slow  # tier-1 budget (ISSUE 14): slowest fast tests re-marked
    def test_warmed_train_step(self, recompile_wd):
        jax = pytest.importorskip("jax")
        import numpy as np

        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.runtime.mesh import build_mesh
        from kubeflow_tpu.train.optim import OptimizerConfig
        from kubeflow_tpu.train.step import setup_train

        cfg = preset("tiny", vocab_size=256, max_seq_len=32)
        task = setup_train(cfg, OptimizerConfig(warmup_steps=0),
                           build_mesh({"data": 8}))
        batch = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 17), dtype=np.int32)
        put = lambda: jax.device_put(batch, task.batch_sharding)  # noqa: E731
        state, _ = task.step_fn(task.state, put())
        recompile_wd.mark_warm()
        state, _ = task.step_fn(state, put())
        assert recompile_wd.steady_count() == 0, recompile_report()["steady"]


class TestEngineWiring:
    def test_transfer_flag_tracks_mode(self, monkeypatch):
        """engine.sanitize (the transfer guard) engages for transfer-ish
        values only — refcount/lockorder runs must not change the decode
        path's transfer semantics."""
        jax = pytest.importorskip("jax")  # noqa: F841
        from kubeflow_tpu.core.serving import BatchingSpec
        from kubeflow_tpu.models.config import preset
        from kubeflow_tpu.serve.engine import LLMEngine

        cfg = preset("tiny")

        def mk():
            return LLMEngine(
                cfg, BatchingSpec(max_batch_size=1, max_seq_len=32,
                                  page_size=16, chunked_prefill_tokens=16),
                seed=0)

        monkeypatch.setenv("KFTPU_SANITIZE", "1")
        assert mk().sanitize is True
        monkeypatch.setenv("KFTPU_SANITIZE", "transfer,refcount")
        assert mk().sanitize is True
        monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
        assert mk().sanitize is False
        monkeypatch.delenv("KFTPU_SANITIZE")
        assert mk().sanitize is False


# -- contract auditor (the dynamic half of the X7xx rules, ISSUE 10) -----------


class TestContractAuditor:
    def test_install_note_report_uninstall(self):
        from kubeflow_tpu.runtime.sanitize import (
            contract_report, install_contract_auditor,
            uninstall_contract_auditor,
        )

        wd = install_contract_auditor()
        try:
            assert install_contract_auditor() is wd   # idempotent
            wd.note_series("kftpu_b", "produced")
            wd.note_series("kftpu_a", "produced")
            wd.note_series("kftpu_a", "produced")     # set semantics
            wd.note_series("kftpu_a", "consumed")
            wd.note_header("X-Kftpu-Qos", "set")
            wd.note_header("X-Kftpu-Trace", "read")
            rep = contract_report()
            assert rep["series_produced"] == ["kftpu_a", "kftpu_b"]
            assert rep["series_consumed"] == ["kftpu_a"]
            assert rep["headers_set"] == ["X-Kftpu-Qos"]
            assert rep["headers_read"] == ["X-Kftpu-Trace"]
            wd.reset()
            assert contract_report() == {
                "series_produced": [], "series_consumed": [],
                "headers_set": [], "headers_read": []}
        finally:
            uninstall_contract_auditor()
        assert contract_report() == {}

    def test_diff_matches_exact_suffix_and_prefix(self):
        from kubeflow_tpu.runtime.sanitize import contract_diff

        static = {
            "series": {"produced": ["kftpu_delay_seconds", "kftpu_x"],
                       "consumed": ["kftpu_scraped"],
                       "produced_prefixes": ["kftpu_router_"]},
            "headers": {"set": ["X-Kftpu-Qos"], "read": ["X-Kftpu-Trace"]},
        }
        report = {
            "series_produced": [
                "kftpu_x",                        # exact
                "kftpu_delay_seconds_bucket",     # histogram suffix
                "kftpu_router_whatever",          # declared prefix
                "kftpu_rogue_total",              # UNDECLARED
            ],
            "series_consumed": ["kftpu_scraped"],
            "headers_set": ["x-kftpu-qos"],       # case-insensitive
            "headers_read": ["X-Kftpu-Rogue"],    # UNDECLARED
        }
        diff = contract_diff(report, static)
        assert diff["undeclared_series"] == ["kftpu_rogue_total"]
        assert diff["undeclared_headers"] == ["X-Kftpu-Rogue"]

    def test_diff_accepts_manifest_shaped_dicts(self):
        # --contracts-json emits {name: [sites]} maps; iteration over
        # them must mean "the declared names", not the site lists.
        from kubeflow_tpu.runtime.sanitize import contract_diff

        static = {"series": {"produced": {"kftpu_x": ["a.py:1"]},
                             "consumed": {}},
                  "headers": {"set": {"X-Kftpu-Qos": ["b.py:2"]},
                              "read": {}}}
        report = {"series_produced": ["kftpu_x"],
                  "headers_set": ["X-Kftpu-Qos"]}
        diff = contract_diff(report, static)
        assert diff == {"undeclared_series": [], "undeclared_headers": []}

    def test_maybe_install_contract_mode(self, monkeypatch):
        from kubeflow_tpu.runtime.sanitize import (
            contract_auditor, maybe_install, uninstall_contract_auditor,
        )

        uninstall_contract_auditor()
        monkeypatch.setenv("KFTPU_SANITIZE", "contract")
        try:
            maybe_install()
            assert contract_auditor() is not None
        finally:
            uninstall_contract_auditor()

    def test_registry_render_hook_is_noop_when_off(self):
        # The obs/registry bridge resolves through sys.modules and must
        # not record (or fail) when no auditor is installed.
        from kubeflow_tpu.obs.registry import (
            MetricsRegistry, contract_note_series,
        )
        from kubeflow_tpu.runtime.sanitize import (
            contract_report, install_contract_auditor,
            uninstall_contract_auditor,
        )

        uninstall_contract_auditor()
        contract_note_series("kftpu_whatever", "produced")   # no-op
        install_contract_auditor()
        try:
            reg = MetricsRegistry()
            reg.gauge("kftpu_hooked").set(1)
            reg.render()
            assert "kftpu_hooked" in contract_report()["series_produced"]
        finally:
            uninstall_contract_auditor()


# -- thread sanitizer (the dynamic half of the T8xx rules, ISSUE 20) -----------


class TestThreadSanitizer:
    @pytest.fixture()
    def san(self):
        san = sanitize.install_thread_sanitizer()
        try:
            yield san
        finally:
            sanitize.uninstall_thread_sanitizer()

    def test_stamp_site_and_owner_from_bound_target(self, san):
        class Comp:
            def _loop(self, ev):
                ev.wait(5.0)

        comp = Comp()
        ev = threading.Event()
        t = threading.Thread(target=comp._loop, args=(ev,))
        t.start()
        try:
            mine = [r for r in sanitize.thread_report()
                    if r["owner"] == "Comp"]
            assert mine, sanitize.thread_report()
            assert "test_sanitizers.py" in mine[0]["site"]
            assert mine[0]["daemon"] is False
        finally:
            ev.set()
            t.join(timeout=5.0)

    def test_owner_scope_labels_unbound_targets(self, san):
        ev = threading.Event()
        with sanitize.thread_owner("scrape-loop"):
            t = threading.Thread(target=ev.wait, args=(5.0,))
        t.start()
        try:
            rep = sanitize.thread_leak_report_by_owner()
            assert "scrape-loop" in rep, rep
            assert len(rep["scrape-loop"]) == 1
        finally:
            ev.set()
            t.join(timeout=5.0)

    def test_quiescence_raises_with_site_then_clears(self, san):
        class Comp:
            def _loop(self, ev):
                ev.wait(10.0)

        comp = Comp()
        ev = threading.Event()
        t = threading.Thread(target=comp._loop, args=(ev,))
        t.start()
        try:
            with pytest.raises(sanitize.ThreadLeakError) as exc:
                sanitize.assert_threads_quiescent(owner=comp, grace_s=0.2)
            assert "Comp" in str(exc.value)
            assert "test_sanitizers.py" in str(exc.value)
        finally:
            ev.set()
            t.join(timeout=5.0)
        # the same assert passes once the thread is joined
        sanitize.assert_threads_quiescent(owner=comp, grace_s=1.0)

    def test_owner_filter_ignores_other_components(self, san):
        class A:
            def _loop(self, ev):
                ev.wait(10.0)

        a, other = A(), A()
        ev = threading.Event()
        t = threading.Thread(target=a._loop, args=(ev,))
        t.start()
        try:
            # `other` owns nothing: its stop-side assert must not trip
            # on a's still-running thread
            sanitize.assert_threads_quiescent(owner=other, grace_s=0.2)
        finally:
            ev.set()
            t.join(timeout=5.0)

    def test_explicit_thread_list_audit(self, san):
        ev = threading.Event()
        t = threading.Thread(target=ev.wait, args=(10.0,))
        t.start()
        try:
            with pytest.raises(sanitize.ThreadLeakError):
                sanitize.assert_threads_quiescent(threads=(t,),
                                                  grace_s=0.2)
        finally:
            ev.set()
            t.join(timeout=5.0)
        sanitize.assert_threads_quiescent(threads=(t,), grace_s=1.0)

    def test_timer_subclass_still_constructs(self, san):
        # threading.Timer calls the module-global Thread.__init__ on a
        # non-subtype self; the patched class must tolerate it
        tm = threading.Timer(60.0, lambda: None)
        tm.cancel()

    def test_install_is_idempotent_and_uninstall_restores(self):
        orig = threading.Thread
        san1 = sanitize.install_thread_sanitizer()
        try:
            assert sanitize.install_thread_sanitizer() is san1
            assert threading.Thread is not orig
            assert threading.Thread.__name__ == "Thread"
        finally:
            sanitize.uninstall_thread_sanitizer()
        assert threading.Thread is orig
        assert sanitize.thread_sanitizer() is None
        assert sanitize.thread_report() == []
        sanitize.assert_threads_quiescent()          # no-op when off

    def test_maybe_install_threads_mode(self, monkeypatch):
        monkeypatch.setenv("KFTPU_SANITIZE", "threads")
        try:
            sanitize.maybe_install()
            assert sanitize.thread_sanitizer() is not None
        finally:
            sanitize.uninstall_thread_sanitizer()
