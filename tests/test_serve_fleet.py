"""Fleet-wide KV fabric (ISSUE 17): conversation failover through the
remote third tier, cross-host handoff retry/fallback, and the
mixed-version negotiation guard — driven over real HTTP model servers.

The chaos gate: SIGKILL an engine that holds a multi-turn conversation,
and the NEXT turn must land on a survivor, adopt the stored prefix from
the artifact store (prefix-hit counter > 0), and produce token-identical
output — while every injected handoff fault degrades to local recompute
with the request still resolving (failure costs a prefill, never the
request), and both pools balance their refcounts afterwards."""

import json
import socket
import time
import urllib.error
import urllib.request

import pytest
import jax

from kubeflow_tpu.core.headers import (
    DECODE_ALTS_HEADER, DECODE_BACKEND_HEADER, HANDOFF_DTYPE_HEADER,
    HANDOFF_WIRE_HEADER,
)
from kubeflow_tpu.core.serving import BatchingSpec
from kubeflow_tpu.models.config import preset
from kubeflow_tpu.models.decoder import init_decoder_params
from kubeflow_tpu.serve.engine import LLMEngine
from kubeflow_tpu.serve.faults import ChaosProxy, kill_model_server
from kubeflow_tpu.serve.server import ModelServer


@pytest.fixture(scope="module")
def cfg():
    return preset("tiny", vocab_size=512)      # byte tokenizer fits


@pytest.fixture(scope="module")
def params(cfg):
    return init_decoder_params(jax.random.PRNGKey(0), cfg)


def spec(role="unified", *, remote_root=None, prefix=True):
    kw = {}
    if remote_root is not None:
        kw.update(host_kv_pages=64, kv_demote_after_s=0.05,
                  kv_remote_after_s=0.05, remote_kv_root=str(remote_root),
                  prefix_index="radix")
    return BatchingSpec(max_batch_size=2, max_seq_len=96,
                        paged=True, page_size=16,
                        chunked_prefill_tokens=16, decode_steps=4,
                        enable_prefix_caching=prefix, role=role, **kw)


def mk_server(name, cfg, params, sp):
    srv = ModelServer(name, LLMEngine(cfg, sp, params=params), port=0)
    srv.start()
    return srv


def completion(url: str, prompt: str, *, headers=(), max_tokens: int = 8,
               timeout_s: float = 20.0) -> tuple[int, str]:
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "timeout": timeout_s}).encode()
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(dict(headers))
    req = urllib.request.Request(url + "/v1/completions", data=body,
                                 headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout_s + 5) as r:
            obj = json.loads(r.read())
            return r.status, obj["choices"][0]["text"]
    except urllib.error.HTTPError as exc:
        return exc.code, exc.read().decode(errors="replace")


def dead_url() -> str:
    """A URL nothing listens on: bound then immediately closed, so a
    connect fails fast with ECONNREFUSED (the dead-replica fault)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"http://127.0.0.1:{port}"


def audit_quiescent(*servers, deadline_s: float = 20.0) -> None:
    """Post-scenario refcount audit (the chaos-suite invariant): cancel
    anything stranded, drive the reaper, assert zero page leaks."""
    for srv in servers:
        eng = srv.engine
        for s in eng.slots:
            if s is not None:
                s.request.cancel()
        for lane in (eng._backlog, eng._preempted):
            for req in lane:
                req.cancel()
        for ch in list(eng._chunkings):
            ch.request.cancel()
        for hreq, _pages in list(eng._handoff_holds.values()):
            hreq.cancel()
        deadline = time.monotonic() + deadline_s
        while eng.kv_pages_in_use() > 0 or eng._handoff_holds:
            eng.step()
            assert time.monotonic() < deadline, \
                f"{srv.name}: KV pages leaked after scenario"
        eng._allocator.assert_quiescent()
        while eng._rounds:
            eng.step()


def stop_all(*servers):
    for s in servers:
        try:
            s.stop()
        except OSError:
            pass


@pytest.mark.slow  # tier-1 budget: three engines + store roundtrip, ~15s
def test_failover_sigkill_then_resume_on_survivor(cfg, params, tmp_path,
                                                  monkeypatch):
    """The chaos gate: turn 1 lands on replica A, the conversation goes
    idle and spills to the remote tier, A is SIGKILLed, and turn 2 on
    replica B (same store root, no live connection to A ever existed)
    adopts the stored prefix — token-identical with an untier-ed engine,
    prefix-hit counter > 0, refcounts exact under the sanitizer."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    a = mk_server("fleet-a", cfg, params, spec(remote_root=tmp_path))
    b = mk_server("fleet-b", cfg, params, spec(remote_root=tmp_path))
    ref = mk_server("fleet-ref", cfg, params, spec(prefix=False))
    try:
        turn1 = "fleet failover: the conversation must survive the host"
        st, text1 = completion(a.url, turn1)
        assert st == 200
        st, want1 = completion(ref.url, turn1)
        assert st == 200 and text1 == want1
        # Idle: the background tier scan demotes the released
        # conversation to host RAM, then spills it into the store.
        deadline = time.monotonic() + 20.0
        while a.engine.kv_tier_stats().get("pages_demoted_remote", 0) < 3:
            time.sleep(0.02)
            assert time.monotonic() < deadline, \
                f"no remote spill happened: {a.engine.kv_tier_stats()}"
        # SIGKILL the conversation's home replica.
        kill_model_server(a)
        # Turn 2 on the SURVIVOR: prompt = turn 1 + its actual output +
        # new tokens. B has never seen this conversation — the only way
        # it can match the prefix is through the store.
        turn2 = turn1 + text1 + " and then"
        st, text2 = completion(b.url, turn2)
        assert st == 200
        st, want2 = completion(ref.url, turn2)
        assert st == 200 and text2 == want2
        tier = b.engine.kv_tier_stats()
        assert tier["remote_registry_hits"] > 0, tier
        assert tier["pages_promoted_remote"] >= 3, tier
        assert tier["prefix_hits"] >= 1, tier
        audit_quiescent(b, ref)
        for srv in (b, ref):
            assert srv.engine._allocator.leak_report_by_owner() == {}
    finally:
        stop_all(a, b, ref)


@pytest.mark.slow  # tier-1 budget: prefill+decode pair + chaos proxy, ~10s
def test_decode_ack_loss_mid_adoption_recomputes(cfg, params, monkeypatch):
    """Dropped handoff ack AFTER send (the decode side fully adopted the
    payload; the prefill side never heard): the prefill must take the
    terminal fallback — local recompute, same greedy text, request
    resolves — and BOTH pools balance, including the decode side's
    orphaned adoption."""
    monkeypatch.setenv("KFTPU_SANITIZE", "refcount")
    pre = mk_server("pre-a", cfg, params, spec("prefill"))
    dec = mk_server("dec-b", cfg, params, spec("decode"))
    proxy = ChaosProxy(dec.url)
    proxy.start()
    try:
        prompt = "handoff ack loss: the request must still resolve"
        hdr = [(DECODE_BACKEND_HEADER, proxy.url)]
        # Healthy handoff first: pins the expected text and proves the
        # disaggregated path is actually in play.
        st, want = completion(pre.url, prompt, headers=hdr)
        assert st == 200
        assert pre.engine.metrics.snapshot()["handoffs_exported"] >= 1
        assert dec.engine.metrics.snapshot()["handoffs_adopted"] >= 1
        # Arm the fault: the decode target processes the POST fully,
        # the ack never reaches the prefill side.
        proxy.drop_response()
        st, got = completion(pre.url, prompt, headers=hdr)
        assert st == 200 and got == want
        snap = pre.engine.metrics.snapshot()
        assert snap["handoffs_fallback"] >= 1, snap
        assert proxy.stats["responses_dropped"] >= 1
        proxy.undrop_response()
        audit_quiescent(pre, dec)
        for srv in (pre, dec):
            assert srv.engine._allocator.leak_report_by_owner() == {}
    finally:
        proxy.stop()
        stop_all(pre, dec)


@pytest.mark.slow  # tier-1 budget: two engine servers + dead-replica probe, ~7s
def test_handoff_retry_lands_on_alternate_replica(cfg, params):
    """Dead primary decode replica + router-stamped alternate: the
    bounded retry targets the DIFFERENT replica and the handoff
    completes there — counted in handoffs_retried, no fallback."""
    pre = mk_server("pre-a", cfg, params, spec("prefill"))
    dec = mk_server("dec-b", cfg, params, spec("decode"))
    try:
        st, text = completion(
            pre.url, "retry onto the alternate decode replica",
            headers=[(DECODE_BACKEND_HEADER, dead_url()),
                     (DECODE_ALTS_HEADER, dec.url)])
        assert st == 200 and text
        snap = pre.engine.metrics.snapshot()
        assert snap["handoffs_retried"] >= 1, snap
        assert snap["handoffs_fallback"] == 0, snap
        assert dec.engine.metrics.snapshot()["handoffs_adopted"] >= 1
        audit_quiescent(pre, dec)
    finally:
        stop_all(pre, dec)


def test_handoff_negotiation_rejects_409(cfg, params):
    """Mixed-version fleet guard: an unsupported wire version or a
    cache-dtype mismatch 409s at submit — BEFORE the payload bytes are
    interpreted — so the prefill side retries elsewhere or recomputes
    instead of the decode pool corrupting pages."""
    dec = mk_server("dec-b", cfg, params, spec("decode"))
    try:
        def post_handoff(headers):
            req = urllib.request.Request(
                dec.url + "/v1/handoff", data=b"",
                headers={"Content-Type": "application/octet-stream",
                         **headers})
            try:
                with urllib.request.urlopen(req, timeout=10) as r:
                    return r.status, r.read().decode()
            except urllib.error.HTTPError as exc:
                return exc.code, exc.read().decode(errors="replace")

        st, body = post_handoff({HANDOFF_WIRE_HEADER: "99"})
        assert st == 409 and "wire version" in body
        st, body = post_handoff({HANDOFF_WIRE_HEADER: "2",
                                 HANDOFF_DTYPE_HEADER: "int8"})
        assert st == 409 and "dtype" in body
        audit_quiescent(dec)
    finally:
        stop_all(dec)
