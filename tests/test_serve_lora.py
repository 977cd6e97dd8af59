"""Multi-tenant LoRA serving (serve/lora.py + engine integration).

The acceptance contract (ISSUE 14): greedy decode under every loaded
adapter is token-identical to a single-model engine running the MERGED
weights, while base traffic through the same batched
dispatch stays identical to a LoRA-free engine. Identity is pinned at
f32 compute (the factored delta and the merged matmul are mathematically
equal; bf16 rounds them differently, flipping argmax on near-ties —
documented, not pinned). Plus: registry hot-load/evict + per-owner
refcounts, per-adapter prefix-cache namespacing (tenants never share
KV), model-id routing signals, and the /metrics adapter series.
"""

import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from kubeflow_tpu.core.serving import BatchingSpec, LoRASpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.lora import (
    AdapterRegistry, AdapterSlotsExhausted, AdapterSpec, adapter_from_bytes,
    adapter_to_bytes, init_adapter_weights, merged_params, target_dims,
)

ALL_TARGETS = ("wq", "wk", "wv", "wo")


@pytest.fixture(scope="module")
def cfg():
    # f32 compute: factored-vs-merged identity is exact to ~1e-6 — bf16
    # would re-round the two (mathematically equal) paths differently.
    return preset("tiny", dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def specs(cfg):
    # Adapter 0 targets the classic (wq, wv) pair; adapter 1 targets all
    # four projections; adapter 2 has a SMALLER rank than the packed cap
    # (the zero-pad path). All factors nonzero — a zero-delta adapter
    # would make every identity assertion vacuous.
    return [
        AdapterSpec("tenant-a", rank=4, alpha=8.0,
                    weights=init_adapter_weights(
                        jax.random.PRNGKey(11), cfg, 4, ("wq", "wv"))),
        AdapterSpec("tenant-b", rank=4, alpha=4.0,
                    weights=init_adapter_weights(
                        jax.random.PRNGKey(12), cfg, 4, ALL_TARGETS)),
        AdapterSpec("tenant-c", rank=2, alpha=8.0,
                    weights=init_adapter_weights(
                        jax.random.PRNGKey(13), cfg, 2, ("wq", "wv"))),
    ]


def mk_engine(cfg, params, *, paged: bool = True, lora_slots: int = 2,
              max_new_room: int = 128, kv_dtype=None):
    b = BatchingSpec(
        max_batch_size=4, max_seq_len=max_new_room,
        paged=paged, page_size=16,
        kv_cache_dtype=kv_dtype,
        lora=(LoRASpec(max_adapters=lora_slots, rank=4,
                       targets=ALL_TARGETS) if lora_slots else LoRASpec()))
    return LLMEngine(cfg, b, params=params)


def run_to_done(engine, req):
    while not req.done.is_set():
        engine.step()
    return req.result(5)


PROMPT = [5, 17, 3, 99, 42, 8, 8, 1]


@pytest.fixture(scope="module")
def merged_refs(cfg, params, specs):
    """name -> tokens from a merged-weights engine — the single-model
    oracle the multi-adapter dispatch must match."""
    out = {}
    for spec in specs:
        eng = mk_engine(cfg, merged_params(params, cfg, spec), lora_slots=0)
        out[spec.name] = eng.generate(PROMPT,
                                      SamplingParams(max_new_tokens=10))
    return out


@pytest.fixture(scope="module")
def base_refs(cfg, params):
    eng = mk_engine(cfg, params, lora_slots=0)
    return eng.generate(PROMPT, SamplingParams(max_new_tokens=10))


class TestTokenIdentity:
    @pytest.mark.parametrize("paged", [True])
    def test_every_adapter_matches_merged_reference(
            self, cfg, params, specs, merged_refs, base_refs, paged):
        """3 adapters through 2 packed slots (forces a hot-load + LRU
        evict mid-run): every output token-identical to its merged
        single-model reference, base traffic identical to a LoRA-free
        engine, zero adapter-slot leaks."""
        eng = mk_engine(cfg, params, paged=paged, lora_slots=2)
        for s in specs:
            eng._lora.register(s)
        base = eng.generate(PROMPT, SamplingParams(max_new_tokens=10))
        assert base == base_refs, \
            "base traffic must be bit-identical to a LoRA-free engine"
        for s in specs:
            got = run_to_done(eng, eng.submit(
                PROMPT, SamplingParams(max_new_tokens=10), adapter=s.name))
            want = merged_refs[s.name]
            assert got == want, (s.name, got, want)
            assert got != base, "adapter must actually change the output"
        assert eng._lora.stats["evictions"] >= 1, \
            "3 adapters over 2 slots must have evicted"
        eng._lora.assert_quiescent()
        eng._allocator.assert_quiescent()

    @pytest.mark.slow  # tier-1 budget: 3 merged-reference engines on an int8 pool
    def test_int8_kv_every_adapter_matches_merged_reference(
            self, cfg, params, specs):
        """Tentpole pin (quantized base + f32 LoRA deltas): the int8
        paged pool under multi-adapter decode. Adapter K/V deltas apply
        BEFORE the pool write, so both the factored and the merged
        engine quantize the same K/V values — greedy output must stay
        token-identical through the int8 rounding, and base traffic
        identical to a LoRA-free int8 engine."""
        eng = mk_engine(cfg, params, paged=True, lora_slots=2,
                        kv_dtype="int8")
        for s in specs:
            eng._lora.register(s)
        base_ref = mk_engine(cfg, params, paged=True, lora_slots=0,
                             kv_dtype="int8").generate(
            PROMPT, SamplingParams(max_new_tokens=10))
        base = eng.generate(PROMPT, SamplingParams(max_new_tokens=10))
        assert base == base_ref, \
            "base traffic must match a LoRA-free int8 engine"
        for s in specs:
            got = run_to_done(eng, eng.submit(
                PROMPT, SamplingParams(max_new_tokens=10), adapter=s.name))
            ref = mk_engine(cfg, merged_params(params, cfg, s), paged=True,
                            lora_slots=0, kv_dtype="int8")
            want = ref.generate(PROMPT, SamplingParams(max_new_tokens=10))
            assert got == want, (s.name, got, want)
            assert got != base, "adapter must actually change the output"
        eng._lora.assert_quiescent()
        eng._allocator.assert_quiescent()

    def test_mixed_batch_decodes_concurrently(self, cfg, params, specs,
                                              merged_refs, base_refs):
        """One BATCHED dispatch serves base + two different adapters in
        neighboring slots without cross-talk (the whole point of the
        packed gather: no per-tenant dispatch)."""
        eng = mk_engine(cfg, params, paged=True, lora_slots=2)
        for s in specs[:2]:
            eng._lora.register(s)
        reqs = [
            eng.submit(PROMPT, SamplingParams(max_new_tokens=10)),
            eng.submit(PROMPT, SamplingParams(max_new_tokens=10),
                       adapter="tenant-a"),
            eng.submit(PROMPT, SamplingParams(max_new_tokens=10),
                       adapter="tenant-b"),
        ]
        while not all(r.done.is_set() for r in reqs):
            eng.step()
        assert reqs[0].output_tokens == list(base_refs)
        assert reqs[1].output_tokens == list(merged_refs["tenant-a"])
        assert reqs[2].output_tokens == list(merged_refs["tenant-b"])
        eng._lora.assert_quiescent()
        eng._allocator.assert_quiescent()

    def test_chunked_prefill_applies_adapter(self, cfg, params, specs):
        """A prompt long enough to chunk (paged admission always chunks;
        48 tokens = 3 pages) prefills THROUGH the adapter — the delta
        applies to prompt KV, not just decode steps."""
        spec = specs[0]
        long_prompt = [(7 * i) % 250 + 1 for i in range(48)]
        eng = mk_engine(cfg, params, paged=True, lora_slots=2)
        eng._lora.register(spec)
        got = run_to_done(eng, eng.submit(
            long_prompt, SamplingParams(max_new_tokens=8),
            adapter=spec.name))
        ref = mk_engine(cfg, merged_params(params, cfg, spec), paged=True,
                        lora_slots=0)
        want = ref.generate(long_prompt, SamplingParams(max_new_tokens=8))
        assert got == want
        eng._lora.assert_quiescent()


class TestPrefixIsolation:
    @pytest.mark.slow  # tier-1 budget: ~9s; lora_smoke gates KV namespacing
    def test_adapters_never_share_kv(self, cfg, params, specs):
        """Same prompt under adapter A, adapter B, then A again and
        base, on a radix prefix-cache engine: only the same-adapter
        re-arrival may hit the index; every output still matches its
        merged reference (no cross-tenant KV reuse)."""
        prompt = list(range(2, 50))
        eng = mk_engine(cfg, params, paged=True, lora_slots=2)
        for s in specs[:2]:
            eng._lora.register(s)
        order = ["tenant-a", "tenant-b", "tenant-a", None]
        outs = []
        for name in order:
            outs.append(run_to_done(eng, eng.submit(
                prompt, SamplingParams(max_new_tokens=8), adapter=name)))
        tier = eng.kv_tier_stats()
        assert tier["prefix_queries"] == 4
        assert tier["prefix_hits"] == 1, \
            "only the tenant-a re-arrival may match the index"
        for name, got in zip(order, outs):
            if name is None:
                continue
            ref = mk_engine(cfg, merged_params(
                params, cfg, next(s for s in specs if s.name == name)),
                paged=True, lora_slots=0)
            assert got == ref.generate(prompt,
                                       SamplingParams(max_new_tokens=8))
        assert outs[0] == outs[2] and outs[0] != outs[1] != outs[3]
        eng._lora.assert_quiescent()
        eng._allocator.assert_quiescent()

    def test_flat_hash_namespacing(self):
        """PageAllocator.chain_keys: the namespace salts the chain root,
        so the flat cache can never cross-match adapters either."""
        from kubeflow_tpu.serve.paged import PageAllocator

        toks = list(range(32))
        base = PageAllocator.chain_keys(toks, 16)
        ns = PageAllocator.chain_keys(toks, 16, namespace="tenant-a")
        assert base != ns
        assert PageAllocator.chain_keys(toks, 16, namespace="tenant-a") == ns
        assert PageAllocator.chain_keys(toks, 16) == base


class TestRegistry:
    def test_acquire_release_lru_evict(self, cfg):
        reg = AdapterRegistry(cfg, max_adapters=2, rank=4)
        for i in range(3):
            reg.register(AdapterSpec(
                f"a{i}", rank=4,
                weights=init_adapter_weights(jax.random.PRNGKey(i), cfg, 4)))
        s0, hot0 = reg.acquire("a0", owner="r0")
        assert hot0 and reg.resident() == ["a0"]
        s1, _ = reg.acquire("a1", owner="r1")
        assert s0 != s1
        reg.release("a0")
        reg.release("a1")
        # a0 is LRU among ref-0 residents: a2 evicts it, not a1.
        s2, hot2 = reg.acquire("a2", owner="r2")
        assert hot2 and s2 == s0
        assert set(reg.resident()) == {"a1", "a2"}
        assert reg.stats["evictions"] == 1
        # re-acquire of a resident adapter is a hit, not a load
        _, hot1b = reg.acquire("a1", owner="r3")
        assert not hot1b
        reg.release("a1")
        reg.release("a2")
        reg.assert_quiescent()

    def test_referenced_adapters_never_evict(self, cfg):
        reg = AdapterRegistry(cfg, max_adapters=2, rank=4)
        for i in range(3):
            reg.register(AdapterSpec(
                f"a{i}", rank=4,
                weights=init_adapter_weights(jax.random.PRNGKey(i), cfg, 4)))
        reg.acquire("a0", owner="r0")
        reg.acquire("a1", owner="r1")
        with pytest.raises(AdapterSlotsExhausted):
            reg.acquire("a2", owner="r2")
        reg.release("a0")
        reg.acquire("a2", owner="r2")      # now a0's slot frees up
        assert set(reg.resident()) == {"a1", "a2"}

    def test_unknown_adapter_keyerror(self, cfg):
        reg = AdapterRegistry(cfg, max_adapters=2, rank=4)
        with pytest.raises(KeyError):
            reg.acquire("nope")

    def test_rank_cap(self, cfg):
        reg = AdapterRegistry(cfg, max_adapters=2, rank=4)
        with pytest.raises(ValueError):
            reg.register(AdapterSpec("big", rank=8))

    def test_quiescence_names_leaker(self, cfg, monkeypatch):
        import kubeflow_tpu.runtime.sanitize as sanitize

        monkeypatch.setattr(sanitize, "enabled",
                            lambda mode=None: True)
        reg = AdapterRegistry(cfg, max_adapters=2, rank=4)
        reg.register(AdapterSpec(
            "a0", rank=4,
            weights=init_adapter_weights(jax.random.PRNGKey(0), cfg, 4)))
        reg.acquire("a0", owner="req-leaky")
        assert reg.leak_report_by_owner() == {"req-leaky": 1}
        with pytest.raises(AssertionError, match="req-leaky"):
            reg.assert_quiescent()
        reg.release("a0")
        reg.assert_quiescent()

    def test_packed_bytes_and_dims(self, cfg):
        reg = AdapterRegistry(cfg, max_adapters=4, rank=8,
                              targets=ALL_TARGETS)
        assert reg.packed_bytes() > 0
        d = cfg.hidden
        assert target_dims(cfg, "wq") == (d, cfg.n_heads * cfg.head_dim)
        assert target_dims(cfg, "wk") == (d, cfg.n_kv_heads * cfg.head_dim)
        assert target_dims(cfg, "wo") == (cfg.n_heads * cfg.head_dim, d)
        with pytest.raises(ValueError):
            target_dims(cfg, "mlp_up")


class TestArtifactRoundTrip:
    def test_bytes_round_trip(self, cfg):
        w = init_adapter_weights(jax.random.PRNGKey(3), cfg, 4,
                                 ("wq", "wv"))
        blob = adapter_to_bytes(w, rank=4, alpha=12.0)
        spec = adapter_from_bytes("t", blob)
        assert spec.rank == 4 and spec.alpha == 12.0
        for t in ("wq", "wv"):
            np.testing.assert_array_equal(spec.weights[t][0], w[t][0])
            np.testing.assert_array_equal(spec.weights[t][1], w[t][1])

    def test_store_pull_is_lazy(self, cfg, tmp_path):
        from kubeflow_tpu.pipelines.artifacts import ArtifactStore
        from kubeflow_tpu.serve.lora import adapter_spec_from_store

        store = ArtifactStore(str(tmp_path))
        w = init_adapter_weights(jax.random.PRNGKey(4), cfg, 4)
        uri = store.put_bytes(adapter_to_bytes(w, rank=4, alpha=16.0))
        store.register("tenant-x", "1", uri)
        spec = adapter_spec_from_store(store, "tenant-x",
                                       "artifact://tenant-x", rank=4)
        assert spec.weights is None          # nothing pulled yet
        got = spec.resolve_weights()
        np.testing.assert_array_equal(got["wq"][0], w["wq"][0])


class TestEngineLifecycle:
    def test_submit_unknown_adapter_404s(self, cfg, params):
        eng = mk_engine(cfg, params, lora_slots=2)
        with pytest.raises(KeyError):
            eng.submit(PROMPT, adapter="nobody")
        # LoRA-free engines reject every adapter id the same way.
        bare = mk_engine(cfg, params, lora_slots=0)
        with pytest.raises(KeyError):
            bare.submit(PROMPT, adapter="tenant-a")

    def test_slot_backpressure_requeues(self, cfg, params, specs):
        """Every adapter slot referenced by a live request: the next
        adapter's request WAITS (requeued, not failed) and completes
        once a slot drains."""
        eng = mk_engine(cfg, params, lora_slots=1)
        for s in specs[:2]:
            eng._lora.register(s)
        r1 = eng.submit(PROMPT, SamplingParams(max_new_tokens=6),
                        adapter="tenant-a")
        r2 = eng.submit(PROMPT, SamplingParams(max_new_tokens=6),
                        adapter="tenant-b")
        while not (r1.done.is_set() and r2.done.is_set()):
            eng.step()
        assert r1.finish_reason == "length"
        assert r2.finish_reason == "length"
        assert eng._lora.stats["evictions"] == 1
        eng._lora.assert_quiescent()

    @pytest.mark.slow  # tier-1 budget (ISSUE 20): ~9s; quiescence +
    # refcount discipline stays fast via the other lifecycle tests
    def test_cancel_releases_adapter_ref(self, cfg, params, specs):
        eng = mk_engine(cfg, params, paged=True, lora_slots=2)
        eng._lora.register(specs[0])
        req = eng.submit(PROMPT, SamplingParams(max_new_tokens=64),
                         adapter="tenant-a")
        eng.step()                      # admit + start decoding
        req.cancel()
        while not req.done.is_set():
            eng.step()
        assert req.finish_reason == "cancelled"
        eng._lora.assert_quiescent()
        eng._allocator.assert_quiescent()

    def test_adapter_load_phase_on_trace(self, cfg, params, specs):
        from kubeflow_tpu.obs.trace import get_tracer, phase_durations

        tracer = get_tracer()
        tracer.reset()
        eng = mk_engine(cfg, params, paged=True, lora_slots=2)
        eng._lora.register(specs[0])
        root = tracer.start_span("test.request")
        req = eng.submit(PROMPT, SamplingParams(max_new_tokens=4),
                         adapter="tenant-a", trace_parent=root)
        run_to_done(eng, req)
        root.end("ok")
        tr = tracer.trace(root.trace_id)
        ph = phase_durations(tr["spans"])
        assert "adapter_load_ms" in ph, ph
        # Resident now: a second request must NOT pay the load phase.
        root2 = tracer.start_span("test.request2")
        req2 = eng.submit(PROMPT, SamplingParams(max_new_tokens=4),
                          adapter="tenant-a", trace_parent=root2)
        run_to_done(eng, req2)
        root2.end("ok")
        ph2 = phase_durations(tracer.trace(root2.trace_id)["spans"])
        assert "adapter_load_ms" not in ph2, ph2


class TestRoutingSignals:
    def test_metrics_registry_renders_adapter_series(self, cfg, params,
                                                     specs):
        from kubeflow_tpu.obs.registry import parse_exposition
        from kubeflow_tpu.serve.server import serving_metrics_registry

        eng = mk_engine(cfg, params, lora_slots=2)
        eng._lora.register(specs[0])
        run_to_done(eng, eng.submit(PROMPT,
                                    SamplingParams(max_new_tokens=4),
                                    adapter="tenant-a"))
        text = serving_metrics_registry([("m", eng)]).render()
        samples = {(n, labels.get("adapter")): v
                   for n, labels, v in parse_exposition(text)}
        assert samples[("kftpu_engine_adapters_resident", "tenant-a")] == 1
        assert samples[("kftpu_engine_adapter_loads_total", None)] == 1
        assert samples[("kftpu_engine_adapter_evictions_total", None)] == 0
        # LoRA-free engines still render the series (0 / no labels) so
        # the loadgen's ATTRIBUTION_SERIES pin holds fleet-wide.
        bare = mk_engine(cfg, params, lora_slots=0)
        names = {n for n, _, _ in parse_exposition(
            serving_metrics_registry([("m", bare)]).render())}
        assert "kftpu_engine_adapters_resident" in names

    def test_router_parses_adapter_residency(self):
        from kubeflow_tpu.serve.router import Router

        text = (
            "kftpu_engine_adapters_resident{model=\"m\","
            "adapter=\"tenant-a\"} 1\n"
            "kftpu_engine_adapters_resident{model=\"m\","
            "adapter=\"tenant-b\"} 1\n"
            "kftpu_serving_in_flight 2\n")
        sig = Router._parse_signals(text)
        assert sig["adapters"] == {"tenant-a", "tenant-b"}
        assert sig["in_flight"] == 2.0

    def test_pick_prefers_warm_backend(self):
        from kubeflow_tpu.serve.router import Router

        router = Router(port=0)
        router.start()        # stop() joins serve_forever — it must run
        try:
            urls = ["http://127.0.0.1:9001", "http://127.0.0.1:9002",
                    "http://127.0.0.1:9003"]
            router.set_backends({"latest": urls})
            router.note_signals(urls[1], {"adapters": {"tenant-a"}})
            picks = {router.pick(model="tenant-a") for _ in range(6)}
            assert picks == {urls[1]}, \
                "the warm backend must win while it is the only one"
            # Nobody has tenant-z hot: the pick falls back to the whole
            # rotation (and thereby warms someone).
            cold = {router.pick(model="tenant-z") for _ in range(6)}
            assert cold == set(urls)
            # Two warm backends round-robin.
            router.note_signals(urls[2], {"adapters": {"tenant-a"}})
            two = {router.pick(model="tenant-a") for _ in range(6)}
            assert two == {urls[1], urls[2]}
        finally:
            router.stop()


class TestKvPressure:
    def test_pressure_fn_overrides_pool_rule(self):
        """ISSUE 14 en passant: demotion urgency is pluggable — the
        default reproduces the quarter-pool rule exactly, and an
        injected pressure (the engine folds queue-delay-vs-budget and
        adapter hot-load backpressure into it) flips tick into urgent
        mode regardless of the free-list level."""
        from kubeflow_tpu.serve.kvtier import RadixPrefixIndex
        from kubeflow_tpu.serve.paged import PageAllocator

        alloc = PageAllocator(16, 4)
        idx = RadixPrefixIndex(alloc, 4)
        try:
            assert idx.pressure() < 1.0           # empty pool: calm
            held = alloc.alloc(13)                # available 3 <= 16//4
            assert idx.pressure() >= 1.0          # the classic rule
            alloc.free(held)
        finally:
            idx.close()
        hot = {"x": 0.0}
        idx2 = RadixPrefixIndex(alloc, 4, pressure_fn=lambda: hot["x"])
        try:
            assert idx2.pressure() == 0.0
            hot["x"] = 2.0
            assert idx2.pressure() == 2.0         # external signal wins
        finally:
            idx2.close()

    def test_engine_pressure_folds_adapter_backpressure(self, cfg,
                                                        params, specs):
        eng = mk_engine(cfg, params, paged=True, lora_slots=1)
        eng._lora.register(specs[0])
        assert eng._kv_pressure() < 1.0
        # Every adapter slot referenced + a waiting backlog: urgent.
        eng._lora.acquire(specs[0].name, owner="r0")
        eng.submit([1, 2, 3])
        eng._drain_waiting()
        assert eng._kv_pressure() >= 1.0
        eng._lora.release(specs[0].name)
        assert eng._kv_pressure() < 1.0


class TestServerRouting:
    @pytest.fixture()
    def server(self, cfg, params, specs):
        from kubeflow_tpu.serve.server import ModelServer

        eng = mk_engine(cfg, params, lora_slots=2)
        for s in specs[:2]:
            eng._lora.register(s)
        srv = ModelServer("base", eng, port=0)
        srv.start()
        yield srv
        srv.stop()

    def _post(self, srv, body, headers=None):
        import http.client
        import json as _json

        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        try:
            conn.request("POST", "/v1/completions", body=_json.dumps(body),
                         headers={"Content-Type": "application/json",
                                  **(headers or {})})
            resp = conn.getresponse()
            return resp.status, _json.loads(resp.read() or b"{}")
        finally:
            conn.close()

    @pytest.mark.slow  # tier-1 budget: full HTTP server + 3 generations
    def test_model_field_header_and_404(self, server, cfg, params, specs):
        from kubeflow_tpu.core.headers import MODEL_HEADER

        base_prompt = "hello tenants"
        status, obj = self._post(server, {"prompt": base_prompt,
                                          "max_tokens": 6})
        assert status == 200
        base_text = obj["choices"][0]["text"]
        # body "model" field routes to the adapter
        status, obj = self._post(server, {"prompt": base_prompt,
                                          "max_tokens": 6,
                                          "model": "tenant-a"})
        assert status == 200
        adapted = obj["choices"][0]["text"]
        assert adapted != base_text
        # the header overrides the body field
        status, obj = self._post(
            server, {"prompt": base_prompt, "max_tokens": 6,
                     "model": "tenant-b"},
            headers={MODEL_HEADER: "tenant-a"})
        assert status == 200
        assert obj["choices"][0]["text"] == adapted
        # unknown ids 404 — never a silent base fallthrough
        status, obj = self._post(server, {"prompt": base_prompt,
                                          "max_tokens": 6,
                                          "model": "tenant-zzz"})
        assert status == 404
        # /v1/models lists base + adapters
        import json as _json
        import urllib.request

        with urllib.request.urlopen(server.url + "/v1/models",
                                    timeout=10) as r:
            models = _json.loads(r.read())["models"]
        assert set(models) == {"base", "tenant-a", "tenant-b"}
