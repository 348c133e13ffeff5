"""The sampler's exact grouped top-k (ISSUE 31).

``ops/sampling.select_top_k`` must return ``jax.lax.top_k``'s values AND
indices on every input, ties included, so that every sampled stream is
the parent's bit for bit. The tiny presets' 258-row vocabulary takes the
direct path by shape, so these tests reach the grouped path on purpose:
at 128 columns a group and, under 512 such groups, at 64 and 32 (ISSUE 57),
over two axes and over a drafting round's three.
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

# 50257 and 100003 are no multiples of the group (padded); at k = 64, 258 takes the direct path,
# 19200 and 32000 groups of 32 columns, 50257 of 64 and the last three of 128; at k = 1 all but
# 258 take 128.
VOCABS = (258, 19200, 32000, 50257, 100003, 151936, 152064)
KINDS = ("gaussian", "integers", "all_equal", "bench_bias", "masked")
LEADS = ((1,), (24,), (48,), (64, 2))  # (B,), and a drafting round's verify queries: two a row


def _logits(kind: str, lead: tuple, V: int) -> np.ndarray:
    rng = np.random.default_rng(int(np.prod(lead)) * 1_000_003 + V)
    if kind == "gaussian":
        return rng.standard_normal((*lead, V)).astype(np.float32) * 4
    if kind == "integers":  # ties across the k-th place and across group borders
        return rng.integers(-3, 4, (*lead, V)).astype(np.float32)
    if kind == "all_equal":
        return np.full((*lead, V), 1.5, np.float32)
    x = rng.standard_normal((*lead, V)).astype(np.float32)
    if kind == "bench_bias":  # the benchmark's logit_bias: all 64 winners in ONE group
        x[..., 32:127] += 100.0
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
@pytest.mark.parametrize("lead", LEADS, ids=lambda lead: "x".join(map(str, lead)))
@pytest.mark.parametrize("V", VOCABS)
def test_selection_equals_lax_top_k(V, lead, k, kind):
    x = jnp.asarray(_logits(kind, lead, V))
    reference, selection = _jitted(k)
    want_v, want_i = reference(x)
    got_v, got_i = selection(x)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def _plan(k, width, V):
    groups = -(-V // width) if width else 0
    return {"top_k": k, "path": "grouped" if width else "direct", "group_width": width, "groups": groups,
            "candidates": k * width if width else V}


# The benchmark's large vocabularies keep 128 columns a group: their programs are PR 31's.
LARGE_VOCABS = {65536: 512, 100352: 784, 131072: 1024, 151936: 1187, 152064: 1188, 163840: 1280,
                200064: 1563, 200192: 1564}


@pytest.mark.parametrize("V,groups", LARGE_VOCABS.items())
def test_large_vocabularies_keep_their_plan(V, groups):
    assert top_k_plan(V, 64) == {"top_k": 64, "path": "grouped", "group_width": 128, "groups": groups,
                                 "candidates": 8192}


def test_plan_is_a_function_of_the_shape_alone():
    assert top_k_plan(100003, 64)["groups"] == 782  # padded, never truncated
    assert top_k_plan(50257, 1) == _plan(1, 128, 50257)
    assert top_k_plan(65536, 64) == _plan(64, 128, 65536)  # 512 groups of 128: the widest width's crossover
    # under it the widest width that still holds 8 groups a candidate: the
    # benchmark's 19,200 and 32,000 rows take 32 (600 and 1,000 groups)
    assert top_k_plan(65408, 64) == _plan(64, 64, 65408) == {
        "top_k": 64, "path": "grouped", "group_width": 64, "groups": 1022, "candidates": 4096}
    assert top_k_plan(50257, 64) == _plan(64, 64, 50257)
    assert top_k_plan(32768, 64) == _plan(64, 64, 32768)
    assert top_k_plan(32000, 64) == _plan(64, 32, 32000) == {
        "top_k": 64, "path": "grouped", "group_width": 32, "groups": 1000, "candidates": 2048}
    assert top_k_plan(19200, 64) == _plan(64, 32, 19200)
    assert top_k_plan(16384, 64) == _plan(64, 32, 16384)
    assert top_k_plan(4096, 8) == _plan(8, 64, 4096)
    for V, k in ((258, 64), (258, 258), (152064, 152064), (32000, 0), (16352, 64), (19200, 128), (2016, 8)):
        assert top_k_plan(V, k) == _plan(k, 0, V), (V, k)


@pytest.mark.parametrize("V,width", ((8192 + 5, 128), (4096 + 5, 64), (2048 + 5, 32), (200, 0)))
def test_leading_axes_are_kept(V, width):
    """The speculative verifier filters (B, K + 1, V) logits in one call."""
    x = jnp.asarray(np.random.default_rng(3).integers(-2, 3, (3, 5, V)).astype(np.float32))
    want_v, want_i = jax.lax.top_k(x, 8)
    got_v, got_i = select_top_k(x, 8)
    assert top_k_plan(V, 8)["group_width"] == width
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_every_top_k_inside_sees_two_axes(monkeypatch):
    """Over three axes a TPU answers lax.top_k with a sort of the whole
    vocabulary (PERF.md §5, PR 57): whichever path, the rows are folded."""
    seen = []
    top_k = jax.lax.top_k
    monkeypatch.setattr(jax.lax, "top_k", lambda x, k: seen.append(x.shape) or top_k(x, k))
    for V in (200, 2048 + 5, 8192 + 5):
        vals, idx = select_top_k(jnp.zeros((3, 5, V)), 8)
        assert vals.shape == idx.shape == (3, 5, 8)
    assert len(seen) == 5 and all(len(shape) == 2 and shape[0] == 15 for shape in seen)


# -- the samplers against the same samplers over plain lax.top_k -------------

B_S, K_S = 12, 64
V_SAMPLERS = {66000: 128, 19200: 32}  # the vocabulary: its group width at K_S


def _sampler_inputs(V_S):
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


def _verify_strip(logits, temps, top_ps, seeds, use_seed, positions):
    """A drafting round's verify strip (engine._mtp_round): two queries a row,
    (S, 2, V), the row's temperature and top_p under both."""
    S = B_S // 2
    pair = lambda a: jnp.broadcast_to(a[:S, None], (S, 2))
    return speculative.strip_dist(logits.reshape(S, 2, -1), pair(temps), pair(top_ps), K_S)


@pytest.mark.parametrize("V_S", V_SAMPLERS)
@pytest.mark.parametrize("sampler", (_sample_tokens, _sample_tokens_pregumbel, _speculative_filter, _verify_strip),
                         ids=("sample_tokens", "sample_tokens_pregumbel", "speculative_filter", "verify_strip"))
def test_samplers_emit_the_reference_arms_tokens(sampler, V_S, monkeypatch):
    assert top_k_plan(V_S, K_S)["group_width"] == V_SAMPLERS[V_S]
    inputs = _sampler_inputs(V_S)
    got = jax.jit(sampler)(*inputs)
    monkeypatch.setattr(sampling, "select_top_k", jax.lax.top_k)  # the reference arm
    want = jax.jit(lambda *a: sampler(*a))(*inputs)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- a tiny engine whose vocabulary takes the grouped path -------------------

BIG_VOCAB = "test-tiny-vocab"
ENGINE_TOP_K = 8
ENGINE_VOCABS = {16384: 128, 2048: 32}  # the vocabulary: its group width at ENGINE_TOP_K
MODES = {
    "decode_chunk": dict(decode_chunk=4),
    "mixed_step": dict(decode_chunk=4, attention="paged", page_size=16, mixed_step=True),
    "decode_chunk_1": dict(decode_chunk=1),
}


def _streams(mode: str, vocab: int) -> list[list[int]]:
    engine = Engine(EngineConfig(
        model=BIG_VOCAB, max_slots=4, max_seq_len=128, dtype="float32", max_prefill_batch=2,
        use_mesh=False, prefill_buckets=(16, 32), top_k=ENGINE_TOP_K, **MODES[mode]))
    assert engine.sampler_plan["group_width"] == ENGINE_VOCABS[vocab]
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
                prompt_ids=[int(t) for t in rng.integers(1, vocab - 300, size=5 + 7 * i)],
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


@pytest.mark.parametrize("vocab", ENGINE_VOCABS)
@pytest.mark.parametrize("mode", list(MODES))
def test_engine_streams_equal_the_reference_arm(mode, vocab, monkeypatch):
    monkeypatch.setitem(llama.PRESETS, BIG_VOCAB,
                        dataclasses.replace(llama.PRESETS["test-tiny"], vocab_size=vocab))
    got = _streams(mode, vocab)
    traced = []

    def plain(x, k):
        traced.append(x.shape)
        return jax.lax.top_k(x, k)

    with monkeypatch.context() as m:
        m.setattr(sampling, "select_top_k", plain)  # traced by a NEW engine's programs
        want = _streams(mode, vocab)
    assert traced and all(shape[-1] == vocab for shape in traced)
    assert got == want
    assert all(len(s) == 20 for s in got)
    assert len({tuple(s) for s in got}) > 1
