"""Disaggregated prefill/decode handoff (ISSUE 12 tentpole): greedy
token identity across the prefill→handoff→decode boundary, the
page-ownership protocol (holds released only on
ack, failure/reap paths refcount-balanced), and the wire format."""

import time

import numpy as np
import pytest
import jax

from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine, SamplingParams
from kubeflow_tpu.serve.handoff import HandoffPayload

CFG = preset("tiny", vocab_size=512)
PARAMS = init_decoder_params(jax.random.PRNGKey(0), CFG)


def spec(role="unified", paged=True, **kw):
    base = dict(max_batch_size=2, max_seq_len=96, paged=paged, page_size=16,
                chunked_prefill_tokens=16, decode_steps=4, role=role)
    base.update(kw)
    return BatchingSpec(**base)


def engine(role="unified", paged=True, **kw):
    return LLMEngine(CFG, spec(role=role, paged=paged, **kw), params=PARAMS)


def drive(eng, req, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not req.done.is_set():
        eng.step()
        assert time.monotonic() < deadline, "request never finished"
    return req


def drain(eng, timeout=30.0):
    deadline = time.monotonic() + timeout
    while (eng.kv_pages_in_use() > 0 or eng._rounds
           or eng._handoff_holds):
        eng.step()
        assert time.monotonic() < deadline, "engine did not quiesce"


PROMPTS = [list(range(3, 23)), [7, 9, 11] * 9, list(range(40, 45))]


@pytest.mark.parametrize("paged", [True], ids=["paged"])
def test_greedy_token_identity_across_handoff(paged):
    """The acceptance pin: unified output == prefill→handoff→decode
    output, token for token."""
    uni = engine(paged=paged)
    pre = engine(role="prefill", paged=paged)
    dec = engine(role="decode", paged=paged)
    params = SamplingParams(max_new_tokens=12, temperature=0.0)
    for prompt in PROMPTS:
        want = uni.generate(prompt, params)
        p_req = drive(pre, pre.submit(prompt, params))
        assert p_req.finish_reason == "handoff"
        payload = p_req.handoff
        assert payload is not None
        assert payload.first_token == want[0]
        assert payload.kv_len == len(prompt)
        # Round-trip the wire format — the HTTP path ships exactly this.
        payload = HandoffPayload.from_wire(payload.to_wire())
        d_req = drive(dec, dec.submit_handoff(payload))
        assert d_req.finish_reason in ("stop", "length")
        got = [payload.first_token] + d_req.output_tokens
        assert got == want, (prompt, got, want)
        pre.complete_handoff(p_req.id)
    drain(pre)
    drain(dec)
    pre._allocator.assert_quiescent()
    dec._allocator.assert_quiescent()


def test_handoff_hold_released_only_on_ack():
    """Paged ownership: exported pages stay referenced (backing the
    payload) until complete_handoff, then free refcount-balanced."""
    pre = engine(role="prefill", paged=True)
    req = drive(pre, pre.submit(PROMPTS[0], SamplingParams(max_new_tokens=8)))
    assert req.finish_reason == "handoff"
    assert pre.kv_pages_in_use() > 0, "hold should still reference pages"
    assert req.id in pre._handoff_holds
    pre.complete_handoff(req.id)
    drain(pre)
    pre._allocator.assert_quiescent()


def test_handoff_failure_and_reap_paths_free_pages():
    pre = engine(role="prefill", paged=True)
    # fail_handoff (decode side never acked): freed + counted failed.
    r1 = drive(pre, pre.submit(PROMPTS[0], SamplingParams(max_new_tokens=8)))
    pre.fail_handoff(r1.id)
    drain(pre)
    assert pre.metrics.snapshot()["handoffs_failed"] == 1
    # Abandoned hold (server died before any ack): the reaper frees it.
    r2 = drive(pre, pre.submit(PROMPTS[1], SamplingParams(max_new_tokens=8)))
    assert pre.kv_pages_in_use() > 0
    r2.cancel()
    drain(pre)
    assert pre.metrics.snapshot()["handoffs_failed"] == 2
    pre._allocator.assert_quiescent()


def test_prefill_role_finishes_short_requests_locally():
    """A request finished AT the first token (budget 1) never hands off
    — there is nothing to decode."""
    pre = engine(role="prefill", paged=True)
    req = drive(pre, pre.submit(PROMPTS[0], SamplingParams(max_new_tokens=1)))
    assert req.finish_reason == "length"
    assert req.handoff is None
    assert len(req.output_tokens) == 1
    drain(pre)
    pre._allocator.assert_quiescent()


def test_unified_fallback_submit_on_prefill_engine():
    """handoff=False on a prefill-role engine = full local decode (the
    router's unified-fallback path when the decode pool is unhealthy)."""
    uni = engine()
    pre = engine(role="prefill")
    params = SamplingParams(max_new_tokens=10, temperature=0.0)
    want = uni.generate(PROMPTS[0], params)
    req = drive(pre, pre.submit(PROMPTS[0], params, handoff=False))
    assert req.finish_reason in ("stop", "length")
    assert req.output_tokens == want


def test_adopted_pages_register_prefix_for_reuse():
    """Handed-off KV becomes prefix-cache content on the decode engine:
    a same-prefix adoption hits the cached pages."""
    pre = engine(role="prefill", paged=True)
    dec = engine(role="decode", paged=True)
    prompt = list(range(1, 33))          # two full 16-token pages
    params = SamplingParams(max_new_tokens=6, temperature=0.0)
    p1 = drive(pre, pre.submit(prompt, params))
    drive(dec, dec.submit_handoff(HandoffPayload.from_wire(
        p1.handoff.to_wire())))
    pre.complete_handoff(p1.id)
    hits_before = dec._allocator.stats["prefix_hits"]
    p2 = drive(pre, pre.submit(prompt, params, request_id="again"))
    drive(dec, dec.submit_handoff(p2.handoff))
    pre.complete_handoff(p2.id)
    assert dec._allocator.stats["prefix_hits"] > hits_before
    drain(pre)
    drain(dec)
    dec._allocator.assert_quiescent()


def test_adoption_rejects_shape_and_budget_mismatch():
    dec = engine(role="decode", paged=True)
    good = HandoffPayload(
        request_id="x", prompt_tokens=[1, 2, 3], first_token=4,
        max_new_tokens=4, temperature=0.0, top_k=0, top_p=1.0,
        stop_token=None, qos="standard",
        kv_k=np.zeros((CFG.n_layers, 3, CFG.n_kv_heads, CFG.head_dim),
                      np.float32),
        kv_v=np.zeros((CFG.n_layers, 3, CFG.n_kv_heads, CFG.head_dim),
                      np.float32))
    import dataclasses

    bad_budget = dataclasses.replace(good, max_new_tokens=0)
    with pytest.raises(ValueError, match="budget"):
        dec.submit_handoff(bad_budget)
    bad_shape = dataclasses.replace(
        good, kv_k=good.kv_k[:, :, :1], kv_v=good.kv_v[:, :, :1])
    with pytest.raises(ValueError, match="shape"):
        dec.submit_handoff(bad_shape)


@pytest.mark.slow   # ~20s: four engines, three prompts each
def test_int8_greedy_token_identity_across_handoff():
    """Tentpole pin: the quantized fabric end to end. An int8-pool
    prefill engine exports v2 blobs (int8 pages + scale rows); the int8
    decode engine adopts them and must reproduce the int8 UNIFIED
    engine's greedy output token for token — same quantized KV, so the
    wire/adopt rebuild cannot introduce any divergence."""
    uni = engine(paged=True, kv_cache_dtype="int8")
    pre = engine(role="prefill", paged=True, kv_cache_dtype="int8")
    dec = engine(role="decode", paged=True, kv_cache_dtype="int8")
    params = SamplingParams(max_new_tokens=12, temperature=0.0)
    for prompt in PROMPTS:
        want = uni.generate(prompt, params)
        p_req = drive(pre, pre.submit(prompt, params))
        assert p_req.finish_reason == "handoff"
        payload = p_req.handoff
        assert payload.cache_dtype == "int8"
        assert payload.kv_k.dtype == np.int8
        assert payload.kv_scale_k is not None
        # The v2 wire round trip the HTTP path ships.
        wire = payload.to_wire()
        payload = HandoffPayload.from_wire(wire)
        assert payload.cache_dtype == "int8"
        d_req = drive(dec, dec.submit_handoff(payload))
        got = [payload.first_token] + d_req.output_tokens
        assert got == want, (prompt, got, want)
        pre.complete_handoff(p_req.id)
        # Wire savings: int8+scales vs the full-dtype payload for the
        # same prompt (~0.625x at tiny's Dh=16; ~0.52x at Dh=128).
        full = engine(role="prefill", paged=True)
        f_req = drive(full, full.submit(prompt, params))
        assert len(wire) < len(f_req.handoff.to_wire()) * 0.8
        full.complete_handoff(f_req.id)
    # Byte metrics flowed on both sides.
    assert pre.metrics.snapshot()["handoff_bytes_exported"] > 0
    assert dec.metrics.snapshot()["handoff_bytes_adopted"] > 0
    drain(pre)
    drain(dec)
    pre._allocator.assert_quiescent()
    dec._allocator.assert_quiescent()


@pytest.mark.slow  # tier-1 budget: two engines + adoption round trips
def test_int8_adopted_pages_register_prefix_for_reuse():
    """Adoption rebuilds pages AND scale rows into the radix index: a
    same-prefix re-adoption on the int8 decode engine hits cache."""
    pre = engine(role="prefill", paged=True, kv_cache_dtype="int8")
    dec = engine(role="decode", paged=True, kv_cache_dtype="int8")
    prompt = list(range(1, 33))
    params = SamplingParams(max_new_tokens=6, temperature=0.0)
    p1 = drive(pre, pre.submit(prompt, params))
    drive(dec, dec.submit_handoff(HandoffPayload.from_wire(
        p1.handoff.to_wire())))
    pre.complete_handoff(p1.id)
    hits_before = dec._allocator.stats["prefix_hits"]
    p2 = drive(pre, pre.submit(prompt, params, request_id="again"))
    drive(dec, dec.submit_handoff(p2.handoff))
    pre.complete_handoff(p2.id)
    assert dec._allocator.stats["prefix_hits"] > hits_before
    drain(pre)
    drain(dec)
    dec._allocator.assert_quiescent()


@pytest.mark.slow  # tier-1 budget: four engines; negative path also covered by wire-v2 tests
def test_adoption_rejects_cache_dtype_mismatch():
    """A mixed fleet mid-rollout must fail LOUDLY, both directions: a
    full-dtype payload on an int8 engine and vice versa."""
    pre8 = engine(role="prefill", paged=True, kv_cache_dtype="int8")
    pre16 = engine(role="prefill", paged=True)
    dec8 = engine(role="decode", paged=True, kv_cache_dtype="int8")
    dec16 = engine(role="decode", paged=True)
    params = SamplingParams(max_new_tokens=4, temperature=0.0)
    p8 = drive(pre8, pre8.submit(PROMPTS[0], params))
    p16 = drive(pre16, pre16.submit(PROMPTS[0], params))
    with pytest.raises(ValueError, match="cache-dtype mismatch"):
        dec16.submit_handoff(p8.handoff)
    with pytest.raises(ValueError, match="cache-dtype mismatch"):
        dec8.submit_handoff(p16.handoff)
    # The matched pairs still work.
    drive(dec8, dec8.submit_handoff(p8.handoff))
    drive(dec16, dec16.submit_handoff(p16.handoff))
    pre8.complete_handoff(p8.id)
    pre16.complete_handoff(p16.id)
    for e in (pre8, pre16, dec8, dec16):
        drain(e)
        e._allocator.assert_quiescent()


def test_wire_v2_rejects_malformed_scales():
    """v2 validation: scales without int8 payload, one-sided scales, and
    a scale shape that disagrees with the page shape all fail validate()
    before anything ships."""
    kv8 = np.ones((1, 2, 1, 4), np.int8)
    sc = np.ones((1, 2, 1), np.float32)
    base = dict(request_id="w", prompt_tokens=[1, 2], first_token=3,
                max_new_tokens=2, temperature=0.0, top_k=0, top_p=1.0,
                stop_token=None, qos="standard")
    with pytest.raises(ValueError, match="pair"):
        HandoffPayload(kv_k=kv8, kv_v=kv8, kv_scale_k=sc, **base).validate()
    with pytest.raises(ValueError, match="int8"):
        HandoffPayload(kv_k=kv8.astype(np.float32),
                       kv_v=kv8.astype(np.float32),
                       kv_scale_k=sc, kv_scale_v=sc, **base).validate()
    with pytest.raises(ValueError, match="scale"):
        HandoffPayload(kv_k=kv8, kv_v=kv8, kv_scale_k=sc[:, :1],
                       kv_scale_v=sc[:, :1], **base).validate()
    # Truncating the scale segment off a v2 blob is detected.
    good = HandoffPayload(kv_k=kv8, kv_v=kv8, kv_scale_k=sc,
                          kv_scale_v=sc, **base)
    wire = good.to_wire()
    with pytest.raises(ValueError, match="truncated"):
        HandoffPayload.from_wire(wire[:-2])


def test_wire_format_rejects_truncation():
    payload = HandoffPayload(
        request_id="w", prompt_tokens=[1, 2], first_token=3,
        max_new_tokens=2, temperature=0.0, top_k=0, top_p=1.0,
        stop_token=None, qos="standard",
        kv_k=np.ones((1, 2, 1, 4), np.float32),
        kv_v=np.ones((1, 2, 1, 4), np.float32))
    wire = payload.to_wire()
    back = HandoffPayload.from_wire(wire)
    assert back.prompt_tokens == [1, 2]
    assert np.array_equal(back.kv_k, payload.kv_k)
    with pytest.raises(ValueError, match="truncated"):
        HandoffPayload.from_wire(wire[:-3])
