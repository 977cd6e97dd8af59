"""Microbench the decode dispatch path on-chip: time K-step dispatches and
the chunk-prefill program, separating model time from per-dispatch
overhead."""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.core.serving import BatchingSpec
    from kubeflow_tpu.models.config import preset
    from kubeflow_tpu.serve.engine import LLMEngine

    cfg = preset(
        "llama3-8b",
        n_layers=8, hidden=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        mlp_dim=8192, vocab_size=32000, max_seq_len=2048)
    eng = LLMEngine(cfg, BatchingSpec(max_batch_size=16, max_seq_len=2048,
                                      chunked_prefill_tokens=512))
    nb = eng.num_slots
    # Every slot's pages mapped: slot s owns pages s*mpp .. (s+1)*mpp-1.
    table = jnp.arange(nb * eng._mpp, dtype=jnp.int32).reshape(nb, eng._mpp)

    key = jax.random.PRNGKey(0)

    def fresh_state():
        # The engine's device-resident state shape (serve/device_state.py):
        # the dispatch donates and returns it, so the loop below re-feeds
        # the advanced carry exactly like the hot loop does.
        return {
            "tokens": jnp.zeros((nb,), jnp.int32),
            "lengths": jnp.full((nb,), 600, jnp.int32),
            "live": jnp.ones((nb,), bool),
            "temps": jnp.zeros((nb,), jnp.float32),
            "top_k": jnp.zeros((nb,), jnp.int32),
            "top_p": jnp.ones((nb,), jnp.float32),
            "stops": jnp.full((nb,), -1, jnp.int32),
            "budgets": jnp.full((nb,), 10**6, jnp.int32),
            "adapter": jnp.full((nb,), -1, jnp.int32),
        }

    for k_steps in (1, 8, 16, 32):
        state = fresh_state()
        # compile
        out, eng.cache, state, table = eng._paged_decode_n(
            eng.params, eng.cache, state, table, key, k_steps, "greedy")
        _ = out.block_until_ready()
        _ = int(jax.device_get(out)[0, 0])  # fence
        reps = 6
        t0 = time.perf_counter()
        for _ in range(reps):
            out, eng.cache, state, table = eng._paged_decode_n(
                eng.params, eng.cache, state, table, key, k_steps, "greedy")
            _ = int(jax.device_get(out)[0, 0])  # fence via host fetch
        dt = (time.perf_counter() - t0) / reps
        print(json.dumps({
            "k_steps": k_steps,
            "dispatch_ms": round(dt * 1e3, 2),
            "ms_per_token_step": round(dt * 1e3 / k_steps, 2),
            "agg_tok_s": round(nb * k_steps / dt, 1),
        }), flush=True)

    # chunk-prefill program timing: one 512-token chunk into slot 0
    toks = jnp.zeros((1, 512), jnp.int32)

    def chunk():
        logits, eng.cache = eng._paged_chunk(
            eng.params, eng.cache, toks, table[0], jnp.int32(0),
            jnp.int32(500), 4)
        return float(jax.device_get(logits[499, 0]))

    chunk()
    t0 = time.perf_counter()
    for _ in range(4):
        chunk()
    print(json.dumps({"chunk512_ms":
                      round((time.perf_counter() - t0) / 4 * 1e3, 2)}))


if __name__ == "__main__":
    main()
