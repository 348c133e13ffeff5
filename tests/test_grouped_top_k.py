"""The sampler's exact grouped top-k (ISSUE 31).

``ops/sampling.select_top_k`` must return ``jax.lax.top_k``'s values AND
indices on every input, ties included, so that every sampled stream is
the parent's bit for bit. The tiny presets' 258-row vocabulary takes the
direct path by shape, so these tests reach the grouped path on purpose.
"""

from __future__ import annotations

import dataclasses
import functools
import queue

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.ops import sampling
from inference_gateway_tpu.ops.sampling import MASK_NEG, select_top_k, top_k_plan
from inference_gateway_tpu.serving import speculative
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler

# 50257 and 100003 are no multiples of the group (padded); at k = 64 the first three take the
# direct path and the last three the grouped one, at k = 1 all but 258 are grouped.
VOCABS = (258, 32000, 50257, 100003, 151936, 152064)
KINDS = ("gaussian", "integers", "all_equal", "bench_bias", "masked")


def _logits(kind: str, B: int, V: int) -> np.ndarray:
    rng = np.random.default_rng(B * 1_000_003 + V)
    if kind == "gaussian":
        return rng.standard_normal((B, V)).astype(np.float32) * 4
    if kind == "integers":  # ties across the k-th place and across group borders
        return rng.integers(-3, 4, (B, V)).astype(np.float32)
    if kind == "all_equal":
        return np.full((B, V), 1.5, np.float32)
    x = rng.standard_normal((B, V)).astype(np.float32)
    if kind == "bench_bias":  # the benchmark's logit_bias: all 64 winners in ONE group
        x[:, 32:127] += 100.0
        return x
    allowed = rng.choice(V, size=10, replace=False)  # fewer than k = 64 allowed ids
    bias = np.full((V,), MASK_NEG, np.float32)
    bias[allowed] = 0.0
    return x + bias  # every masked id reads exactly MASK_NEG: one long tie


@functools.lru_cache(maxsize=None)
def _jitted(k: int):
    return (jax.jit(lambda x: jax.lax.top_k(x, k)),
            jax.jit(lambda x: select_top_k(x, k)))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", (1, 64))
@pytest.mark.parametrize("B", (1, 24, 48))
@pytest.mark.parametrize("V", VOCABS)
def test_selection_equals_lax_top_k(V, B, k, kind):
    x = jnp.asarray(_logits(kind, B, V))
    reference, selection = _jitted(k)
    want_v, want_i = reference(x)
    got_v, got_i = selection(x)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_plan_is_a_function_of_the_shape_alone():
    assert top_k_plan(152064, 64) == {"top_k": 64, "path": "grouped", "groups": 1188,
                                      "candidates": 64 * sampling.GROUP_LANES}
    assert top_k_plan(151936, 64)["groups"] == 1187
    assert top_k_plan(100003, 64)["groups"] == 782  # padded, never truncated
    assert top_k_plan(50257, 1) == {"top_k": 1, "path": "grouped", "groups": 393, "candidates": 128}
    assert top_k_plan(65536, 64)["path"] == "grouped"  # 512 groups: the crossover
    for V, k in ((258, 64), (258, 258), (152064, 152064), (32000, 0), (32000, 64), (50257, 64),
                 (65535 - 127, 64)):
        assert top_k_plan(V, k) == {"top_k": k, "path": "direct", "groups": 0,
                                    "candidates": V}, (V, k)


def test_leading_axes_are_kept():
    """The speculative verifier filters (B, K + 1, V) logits in one call."""
    x = jnp.asarray(np.random.default_rng(3).integers(-2, 3, (3, 5, 8192 + 5)).astype(np.float32))
    want_v, want_i = jax.lax.top_k(x, 8)
    got_v, got_i = select_top_k(x, 8)
    assert top_k_plan(8192 + 5, 8)["path"] == "grouped"
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


# -- the samplers against the same samplers over plain lax.top_k -------------

B_S, V_S, K_S = 12, 66000, 64


def _sampler_inputs():
    rng = np.random.default_rng(31)
    logits = rng.integers(-6, 7, (B_S, V_S)).astype(np.float32) / 2  # ties in the strip
    logits[::3, 32:127] += 100.0
    temps = np.where(np.arange(B_S) % 4 == 0, 0.0, 0.7).astype(np.float32)  # greedy rows mixed in
    top_ps = np.where(np.arange(B_S) % 2 == 0, 1.0, 0.9).astype(np.float32)
    seeds = rng.integers(0, 2**31 - 1, B_S).astype(np.int32)
    use_seed = np.arange(B_S) % 3 != 1  # seeded and unseeded rows
    positions = rng.integers(0, 100, B_S).astype(np.int32)
    return tuple(jnp.asarray(a) for a in (logits, temps, top_ps, seeds, use_seed, positions))


def _sample_tokens(logits, temps, top_ps, seeds, use_seed, positions):
    rng = jax.random.PRNGKey(7)
    keys = sampling.per_row_keys(rng, seeds, use_seed, positions)
    return (sampling.sample_tokens(logits, rng, temps, top_ps, top_k=K_S, row_keys=keys),
            sampling.sample_tokens(logits, rng, temps, top_ps, top_k=K_S))


def _sample_tokens_pregumbel(logits, temps, top_ps, seeds, use_seed, positions):
    keys = sampling.chunk_row_keys(jax.random.PRNGKey(7), seeds, use_seed, positions, 3)
    gumbels = sampling.chunk_gumbels(keys, K_S)
    return jnp.stack([sampling.sample_tokens_pregumbel(logits, temps, top_ps, gumbels[i], K_S)
                      for i in range(3)])


def _speculative_filter(logits, temps, top_ps, seeds, use_seed, positions):
    keys = sampling.per_row_keys(jax.random.PRNGKey(7), seeds, use_seed, positions)
    gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (K_S,)))(keys)
    probs, idx = speculative.strip_dist(logits, temps, top_ps, K_S)
    return probs, idx, speculative.strip_sample(probs, idx, gumbel, temps <= sampling.GREEDY_EPS)


@pytest.mark.parametrize("sampler", (_sample_tokens, _sample_tokens_pregumbel, _speculative_filter),
                         ids=("sample_tokens", "sample_tokens_pregumbel", "speculative_filter"))
def test_samplers_emit_the_reference_arms_tokens(sampler, monkeypatch):
    assert top_k_plan(V_S, K_S)["path"] == "grouped"
    inputs = _sampler_inputs()
    got = jax.jit(sampler)(*inputs)
    monkeypatch.setattr(sampling, "select_top_k", jax.lax.top_k)  # the reference arm
    want = jax.jit(lambda *a: sampler(*a))(*inputs)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- a tiny engine whose vocabulary takes the grouped path -------------------

BIG_VOCAB = "test-tiny-vocab16384"
ENGINE_TOP_K = 8
MODES = {
    "decode_chunk": dict(decode_chunk=4),
    "mixed_step": dict(decode_chunk=4, attention="paged", page_size=16, mixed_step=True),
    "decode_chunk_1": dict(decode_chunk=1),
}


def _streams(mode: str) -> list[list[int]]:
    engine = Engine(EngineConfig(
        model=BIG_VOCAB, max_slots=4, max_seq_len=128, dtype="float32", max_prefill_batch=2,
        use_mesh=False, prefill_buckets=(16, 32), top_k=ENGINE_TOP_K, **MODES[mode]))
    assert engine.sampler_plan["path"] == "grouped"
    if mode == "mixed_step":
        assert engine.mixed_ok
    sched = Scheduler(engine)
    sched.start()
    try:
        rng = np.random.default_rng(5)
        done: list[tuple[list[int], object]] = []
        for i in range(6):  # more requests than slots: admissions while others decode
            q: queue.Queue = queue.Queue()
            toks: list[int] = []
            done.append((toks, q))
            sched.submit(GenRequest(
                prompt_ids=[int(t) for t in rng.integers(1, 16000, size=5 + 7 * i)],
                max_tokens=20, temperature=0.0 if i % 3 == 2 else 0.9, top_p=0.95,
                seed=1000 + i, callback=lambda t, lp, fin, r, q=q: q.put((t, fin))))
        for toks, q in done:
            while True:
                t, fin = q.get(timeout=120)
                toks.append(t)
                if fin:
                    break
        return [toks for toks, _ in done]
    finally:
        sched.stop()


@pytest.mark.parametrize("mode", list(MODES))
def test_engine_streams_equal_the_reference_arm(mode, monkeypatch):
    monkeypatch.setitem(llama.PRESETS, BIG_VOCAB,
                        dataclasses.replace(llama.PRESETS["test-tiny"], vocab_size=16384))
    got = _streams(mode)
    traced = []

    def plain(x, k):
        traced.append(x.shape)
        return jax.lax.top_k(x, k)

    with monkeypatch.context() as m:
        m.setattr(sampling, "select_top_k", plain)  # traced by a NEW engine's programs
        want = _streams(mode)
    assert traced and all(shape[-1] == 16384 for shape in traced)
    assert got == want
    assert all(len(s) == 20 for s in got)
    assert len({tuple(s) for s in got}) > 1
