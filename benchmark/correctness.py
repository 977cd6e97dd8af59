"""What decides ``correct``: the program's outputs against the plain
reference (the architecture's ``reference.py``), at the cell's own widths
and cut depth, on weights made from the seed, outside the timed window.

Serving compares LOGITS (with random weights the largest logit changes on
rounding, so tokens say nothing): a seeded sequence goes through the
engine's own chunked-prefill program and then, token by token, through its
decode step over the paged cache; the reference runs one full forward over
the same tokens. The number compared is, per position, the error's 2-norm
over the centred reference logits' 2-norm, and over positions the MEDIAN:
rounding touches every position, while an MoE token dropped at capacity
touches a few, which the 90th percentile beside it shows.

Training compares the first step's loss and gradient norm, which the trainer
reports itself, with the reference's on the same batch.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import architecture, reference
from benchmark.stats import percentile


def position_errors(got, want) -> np.ndarray:
    """[T, V] logits each -> [T] relative errors."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    centred = want - jnp.mean(want, axis=-1, keepdims=True)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
        centred, axis=-1)
    return np.asarray(jax.device_get(err))


def check_tokens(seed: int, k: int, n: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 4, k]).integers(
        3, vocab, n).astype(np.int32)


def engine_logits(engine, tokens: np.ndarray, plen: int, n_decode: int):
    """The engine's own programs on its own params and cache: chunked
    prefill of ``tokens[:plen]`` into slot 0's pages, then ``n_decode``
    teacher-forced decode steps. Returns ([real_last + n_decode, V] logits,
    real_last): the last chunk's valid positions, then one row per decode
    step. The engine must not be started; its cache is left dirty (no page
    is allocated, so the allocator never sees it)."""
    from kubeflow_tpu.serve.paged import _paged_decode_step, context_bucket

    pg, chunk, mpp = engine.page_size, engine.chunk_size, engine._mpp
    n_pages = math.ceil((plen + n_decode) / pg)
    row = np.full((mpp,), -1, np.int32)
    row[:n_pages] = np.arange(n_pages, dtype=np.int32)
    last, real = None, 0
    for pos in range(0, plen, chunk):
        real = min(chunk, plen - pos)
        block = np.zeros((1, chunk), np.int32)
        block[0, :real] = tokens[pos:pos + real]
        last, engine.cache = engine._paged_chunk(
            engine.params, engine.cache, jnp.asarray(block), jnp.asarray(row),
            jnp.int32(pos), jnp.int32(real),
            context_bucket(pos, chunk, pg, mpp))
    rows = [last[:real]]
    table = np.full((engine.num_slots, mpp), -1, np.int32)
    table[0] = row
    table = jnp.asarray(table)
    cfg, impl = engine._cfg_decode, engine.paged_attn_impl
    step = jax.jit(
        lambda p, c, t, ln, lv: _paged_decode_step(
            p, {**c, "table": table}, t, ln, lv, cfg, attn_impl=impl),
        donate_argnums=(1,))
    live = jnp.asarray(np.arange(engine.num_slots) == 0)
    for i in range(n_decode):
        tok = np.zeros((engine.num_slots,), np.int32)
        tok[0] = tokens[plen + i]
        lens = np.zeros((engine.num_slots,), np.int32)
        lens[0] = plen + i
        lg, cache = step(engine.params, engine.cache, jnp.asarray(tok),
                         jnp.asarray(lens), live)
        cache.pop("table", None)
        engine.cache = engine._pin(cache)
        rows.append(lg[:1])
    return jnp.concatenate(rows, axis=0), real


def reference_logits(params, tokens: np.ndarray, hf: dict, last: int,
                     quant=None):
    logits = architecture.part(hf, "reference").logits
    fn = jax.jit(lambda p, t: logits(
        p, t, hf, quant or reference.same, last=last))
    with jax.default_matmul_precision("highest"):
        return fn(params, jnp.asarray(tokens))


def sample_sequences(spec: dict, seed: int, vocab: int) -> list:
    return [(check_tokens(seed, k, plen + n_dec, vocab), plen, n_dec)
            for k, (plen, n_dec) in enumerate(spec["sequences"])]


def last_chunk_len(plen: int, chunk: int) -> int:
    return plen - (math.ceil(plen / chunk) - 1) * chunk


def reference_side(params, hf: dict, spec: dict, seed: int, chunk: int,
                   quant=None) -> list:
    """The reference's logits for every sample sequence: the valid positions
    of the prompt's last chunk, then the decode positions."""
    return [reference_logits(params, toks, hf,
                             last=last_chunk_len(plen, chunk) + n_dec,
                             quant=quant)
            for toks, plen, n_dec in sample_sequences(spec, seed,
                                                      hf["vocab_size"])]


def compare_sides(got: list, want: list, spec: dict, chunk: int) -> dict:
    """The two numbers a serving cell compares, and what was seen beside
    them. ``got`` / ``want``: one [positions, V] block per sample
    sequence."""
    pre, dec = [], []
    for g, w, (plen, _) in zip(got, want, spec["sequences"]):
        real = last_chunk_len(plen, chunk)
        err = position_errors(g, w)
        pre.extend(err[:real].tolist())
        dec.extend(err[real:].tolist())
    return {"prefill_logit_err": percentile(pre, 50),
            "decode_logit_err": percentile(dec, 50),
            "prefill_logit_err_p90": percentile(pre, 90),
            "prefill_logit_err_max": max(pre),
            "decode_logit_err_max": max(dec),
            "positions": len(pre) + len(dec)}


def engine_side(engine, hf: dict, spec: dict, seed: int) -> list:
    return [engine_logits(engine, toks, plen, n_dec)[0]
            for toks, plen, n_dec in sample_sequences(spec, seed,
                                                      hf["vocab_size"])]


def serving_numbers(engine, params, hf: dict, spec: dict, seed: int) -> dict:
    """A run's comparison: the engine's own programs against the float32
    reference on the same weights and tokens."""
    chunk = engine.chunk_size
    return compare_sides(engine_side(engine, hf, spec, seed),
                         reference_side(params, hf, spec, seed, chunk),
                         spec, chunk)


def loss_and_grad_norm_program(hf: dict, n_targets: int, *, quant=None,
                               batch_axes=None):
    """The function ``reference_loss_and_grad_norm`` jits: (params, passes
    [P, micro, S + 1]) -> (mean loss, global gradient norm) over
    ``n_targets`` targets. Apart so that ``benchmark/aot_sizes.py`` lowers
    the same program for a described chip."""
    q = quant or reference.same
    sequence_nll = architecture.part(hf, "reference").sequence_nll

    def total_nll(p, mb):
        return jnp.sum(jax.vmap(
            lambda t: sequence_nll(p, t, hf, q),
            spmd_axis_name=batch_axes)(mb))

    def run(p, passes):
        def one(acc, mb):
            nll, g = jax.value_and_grad(total_nll)(p, mb)
            return (acc[0] + nll, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.float32(0), jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), p))
        (nll, g), _ = jax.lax.scan(one, zero, passes)
        sq = sum(jnp.sum(jnp.square(x / n_targets))
                 for x in jax.tree.leaves(g))
        return nll / n_targets, jnp.sqrt(sq)

    return run


def reference_loss_and_grad_norm(params, batch, hf: dict, *, quant=None,
                                 mesh=None, batch_axes=None, micro: int = 0):
    """Loss and global gradient norm of one batch [B, S + 1], float32 at
    ``highest``. ``micro``: sequences per pass (the batch is walked in
    passes and the gradients summed, so that a pass's activations fit).
    Under a ``mesh`` the sequences of a pass are spread over ``batch_axes``
    (``vmap``'s ``spmd_axis_name``: the harness's data parallelism around a
    per-sequence function that knows nothing of devices)."""
    import contextlib

    b = batch.shape[0]
    micro = micro or b
    passes = jnp.asarray(batch).reshape(b // micro, micro, batch.shape[1])
    run = loss_and_grad_norm_program(hf, b * (batch.shape[1] - 1),
                                     quant=quant, batch_axes=batch_axes)
    with jax.default_matmul_precision("highest"), \
            (mesh if mesh is not None else contextlib.nullcontext()):
        loss, gnorm = jax.jit(run)(params, passes)
    return float(loss), float(gnorm)


def relative(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def judge(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """Every number that has a limit, beside it; correct when each is
    finite and at most its limit."""
    lines, ok = [], True
    for name, limit in sorted(limits.items()):
        value = numbers[name]
        good = math.isfinite(value) and value <= limit
        ok = ok and good
        lines.append(f"compared {name} = {value:.6g} limit {limit:.6g} "
                     f"{'ok' if good else 'OVER'}")
    return ok, lines
