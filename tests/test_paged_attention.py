"""Paged attention + paged KV cache tests.

The Pallas kernel itself runs in interpreter mode on CPU; the engine's
paged path (allocator, flat write indices, lazy page growth, release)
must produce token streams identical to the dense-cache engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.ops.paged_attention import (
    GLOBAL_WINDOW,
    decode_walk_plan,
    paged_attention_jax,
    paged_attention_tpu,
)
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.kv_cache import OutOfPagesError, PageAllocator, PagedCacheConfig
from inference_gateway_tpu.serving.scheduler import Scheduler, generate_sync


def test_kernel_interpret_matches_reference():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, ps, P, mp = 3, 8, 4, 64, 16, 32, 8
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(P)[: B * mp].reshape(B, mp).astype(np.int32))
    lengths = jnp.asarray([37, 1, 0], dtype=jnp.int32)

    ref = paged_attention_jax(q, k, v, pt, lengths, Hkv)
    out = paged_attention_tpu(q, k, v, pt, lengths, Hkv, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:2]), np.asarray(ref[:2]), rtol=1e-5, atol=1e-5)
    assert not np.asarray(out[2]).any()  # an empty slot's row is zeros (ISSUE 37)


def test_kernel_multi_slot_block_matches_reference():
    """Eight slots, two of them empty: the DMA chain crosses slot
    boundaries and passes over the empty ones — every active row must
    still match the reference exactly."""
    rng = np.random.default_rng(7)
    B, Hq, Hkv, D, ps, P, mp = 8, 8, 4, 64, 16, 64, 8
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(P)[: B * mp].reshape(B, mp).astype(np.int32))
    # Mixed occupancy: full, mid, page-boundary, 1-token, empty...
    lengths = jnp.asarray([128, 37, 32, 1, 0, 97, 16, 0], dtype=jnp.int32)

    ref = paged_attention_jax(q, k, v, pt, lengths, Hkv)
    out = paged_attention_tpu(q, k, v, pt, lengths, Hkv, interpret=True)
    active = [i for i, n in enumerate([128, 37, 32, 1, 0, 97, 16, 0]) if n]
    np.testing.assert_allclose(np.asarray(out)[active], np.asarray(ref)[active],
                               rtol=1e-5, atol=1e-5)


# The decode kernel's walk (ISSUE 37): N pages a visit, only live pages fetched,
# no visit for an empty slot. name -> (lengths, window, options). Small pages so
# the interpreter is quick; ``pages_per_visit`` 3 of 8 so that contexts end
# before, on and after a visit's edge (the layout's own N would be all 8).
PS, MP, N_TEST = 16, 8, 3
WALK_CASES = {
    "empty_slot_first": ([0, 37, 100, 5], None, {}),
    "empty_slots_in_the_middle": ([37, 0, 0, 100], None, {}),
    "empty_slot_last": ([37, 100, 5, 0], None, {}),
    "every_slot_empty": ([0, 0, 0, 0], None, {}),
    "one_token": ([1, 128, 1, 16], None, {}),
    "a_token_short_of_a_visit": ([N_TEST * PS - 1, 2 * N_TEST * PS - 1, 0, 9], None, {}),
    "a_whole_visit": ([N_TEST * PS, 2 * N_TEST * PS, 0, 128], None, {}),
    "a_token_past_a_visit": ([N_TEST * PS + 1, 2 * N_TEST * PS + 1, 0, 17], None, {}),
    "window_starts_inside_a_visit": ([120, 70, 0, 9], 50, {}),  # 120 - 50 = 70: page 4, the second of its visit
    "window_starts_on_a_page_edge": ([112, 80, 64, 128], 48, {}),  # 112 - 48 = 64 = 4 pages
    "window_as_the_operand": ([120, 70, 0, 9], 50, {"operand": True}),
    "no_window_as_the_operand": ([120, 70, 0, 9], None, {"operand": True}),
    "bf16_pool": ([37, 0, 128, 100], None, {"dtype": jnp.bfloat16, "atol": 0.0156}),
    "bf16_pool_windowed": ([120, 70, 0, 9], 50, {"dtype": jnp.bfloat16, "atol": 0.0156}),
    # Hkv·D = 192: the page DMA fills the valid prefix of a 256-lane buffer (_page_dst)
    "folded_axis_off_the_lane_grid": ([37, 0, 128, 100], None, {"hkv": 3}),
    "folded_axis_off_the_lane_grid_windowed": ([120, 70, 0, 9], 50, {"hkv": 3}),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_decode_kernel_walk_matches_reference(case):
    lengths, window, opt = WALK_CASES[case]
    rng = np.random.default_rng(len(case))
    B, Hkv, G, D = len(lengths), opt.get("hkv", 2), 3, 64
    dtype = opt.get("dtype", jnp.float32)
    P = B * MP
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), dtype)
    k = jnp.asarray(rng.normal(size=(P, PS, Hkv * D)), dtype)
    v = jnp.asarray(rng.normal(size=(P, PS, Hkv * D)), dtype)
    pt = jnp.asarray(rng.permutation(P).reshape(B, MP).astype(np.int32))
    lens = jnp.asarray(lengths, jnp.int32)

    ref = paged_attention_jax(q, k, v, pt, lens, Hkv, window=window)
    kw = {"window": window}
    if opt.get("operand"):
        kw = {"window_dyn": jnp.int32(window or GLOBAL_WINDOW)}
    out = paged_attention_tpu(q, k, v, pt, lens, Hkv, interpret=True, pages_per_visit=N_TEST, **kw)
    atol = opt.get("atol", 1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=atol, atol=atol)
    assert not np.asarray(out, np.float32)[np.asarray(lengths) == 0].any()  # exactly zero


# The decode kernel under a mask (ISSUE 45: a learned sparse attention's chosen
# set as a mask over the row; every page the slot holds is still walked whole).
# name -> (lengths, what the mask keeps of a row's positions, options).
def _keep(share, seed=0):
    return lambda B, S: np.random.default_rng(seed).random((B, S)) < share


def _but_pages(*pages, share=0.4):
    """``share`` of the positions, and nothing of ``pages``."""
    def mask(B, S):
        m = _keep(share, 1)(B, S)
        for p in pages:
            m[:, p * PS:(p + 1) * PS] = False
        return m
    return mask


MASKED_CASES = {
    "ragged_lengths_with_an_empty_slot": ([0, 37, 100, 5], _keep(0.4), {}),
    "empty_slots_in_the_middle": ([128, 0, 0, 61], _keep(0.4), {}),
    "a_page_with_nothing_chosen": ([100, 37, 0, 128], _but_pages(1, 4), {}),
    # one page a visit: the first VISIT attends nothing, and the next rescales it away
    "a_first_visit_with_nothing_chosen": ([100, 37, 0, 128], _but_pages(0), {"pages_per_visit": 1}),
    "a_visits_last_page_partly_live": ([N_TEST * PS + 5, 2 * N_TEST * PS - 7, 0, 2 * PS + 1], _keep(0.5), {}),
    "one_position_chosen": ([100, 37, 9, 128], lambda B, S: np.arange(S)[None, :] == np.asarray([[63], [5], [8], [127]]), {}),
    "a_row_at_or_under_k": ([100, 37, 0, 128], lambda B, S: np.ones((B, S), bool), {"unmasked": True}),
    "bf16_pool": ([37, 0, 128, 100], _keep(0.4), {"dtype": jnp.bfloat16, "atol": 0.0156}),
    "bf16_pool_a_page_with_nothing_chosen": ([100, 37, 0, 128], _but_pages(0, 5), {"dtype": jnp.bfloat16, "atol": 0.0156}),
    "under_a_window": ([120, 70, 0, 9], _keep(0.6), {"window": 50}),
    "folded_axis_off_the_lane_grid": ([37, 0, 128, 100], _keep(0.4), {"hkv": 3}),
}


@pytest.mark.parametrize("case", list(MASKED_CASES))
def test_decode_kernel_walk_under_a_mask_matches_reference(case):
    lengths, keep, opt = MASKED_CASES[case]
    rng = np.random.default_rng(len(case))
    B, Hkv, G, D = len(lengths), opt.get("hkv", 2), 3, 64
    dtype = opt.get("dtype", jnp.float32)
    P = B * MP
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), dtype)
    k = jnp.asarray(rng.normal(size=(P, PS, Hkv * D)), dtype)
    v = jnp.asarray(rng.normal(size=(P, PS, Hkv * D)), dtype)
    pt = jnp.asarray(rng.permutation(P).reshape(B, MP).astype(np.int32))
    lens = jnp.asarray(lengths, jnp.int32)
    mask = np.asarray(keep(B, MP * PS))
    for b, n in enumerate(lengths):  # every live row attends something (as a top-k's mask does)
        window = opt.get("window") or n
        if n and not mask[b, max(n - window, 0):n].any():
            mask[b, n - 1] = True
    mask = jnp.asarray(mask)

    kw = {"window": opt.get("window"), "pages_per_visit": opt.get("pages_per_visit", N_TEST)}
    ref = paged_attention_jax(q, k, v, pt, lens, Hkv, window=kw["window"], mask=mask)
    out = paged_attention_tpu(q, k, v, pt, lens, Hkv, interpret=True, mask=mask, name="masked_walk", **kw)
    atol = opt.get("atol", 1e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=atol, atol=atol)
    assert not np.asarray(out, np.float32)[np.asarray(lengths) == 0].any()
    plain = paged_attention_tpu(q, k, v, pt, lens, Hkv, interpret=True, **kw)
    if opt.get("unmasked"):  # a mask that keeps everything: the unmasked call's sums, bit for bit
        assert (np.asarray(out) == np.asarray(plain)).all()
    else:  # and a mask that does not shows
        assert np.abs(np.asarray(out, np.float32) - np.asarray(plain, np.float32)).max() > 0.1


@pytest.mark.parametrize("how,equations", [("no_window", 130), ("window", 141), ("window_operand", 144), ("mask", 138)])
def test_without_a_mask_the_decode_kernel_traces_what_it_traced(how, equations):
    """The mask is an OPTIONAL operand: without one neither it, its block nor
    the and is traced, and the launch is the parent's equation for equation
    (130 / 141 / 144 on the tree before ISSUE 45, at this layout); with one,
    eight more."""
    from test_chip_compile import _equations  # the count the start-up ceilings there use

    sds = jax.ShapeDtypeStruct
    B, Hkv, D = 4, 2, 64
    pool = sds((B * MP, PS, Hkv * D), jnp.float32)
    args = [sds((B, 3 * Hkv, D), jnp.float32), pool, pool, sds((B, MP), jnp.int32), sds((B,), jnp.int32)]
    kw = {"window": 50} if how == "window" else {}
    extra = {"window_operand": ("window_dyn", sds((), jnp.int32)), "mask": ("mask", sds((B, MP * PS), jnp.bool_))}.get(how)
    fn = lambda *a: paged_attention_tpu(*a[:5], Hkv, interpret=True, pages_per_visit=N_TEST, **kw,
                                        **({extra[0]: a[5]} if extra else {}))
    traced = jax.make_jaxpr(fn)(*args, *([extra[1]] if extra else []))
    assert _equations(traced.jaxpr) == equations
    assert (f"i32[{B},{(MP + N_TEST) * PS}]" in str(traced)) == (how == "mask")  # the padded mask, an operand


@pytest.mark.parametrize("dtype,visits", [(jnp.float32, 3), (jnp.bfloat16, 2)], ids=["float32", "bfloat16"])
def test_decode_kernel_at_the_layouts_own_pages_per_visit(dtype, visits):
    """Pages of 128 tokens x 4 KV heads of 128, as the chip serves them: N is
    the layout's own (3 pages of 256 KB in float32, 7 of 128 KB in bf16), the
    head loops are the lane-aligned ones, and a context of 8 pages takes three
    visits or two."""
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, ps, mp = 4, 8, 4, 128, 128, 8
    n = decode_walk_plan(Hq, Hkv, D, ps, mp, dtype)["pages_per_visit"]
    assert -(-mp // n) == visits
    P = B * mp
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), dtype)
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)), dtype)
    pt = jnp.asarray(rng.permutation(P).reshape(B, mp).astype(np.int32))
    lens = jnp.asarray([1024, 0, n * ps + 1, 300], jnp.int32)
    atol = 1e-5 if dtype == jnp.float32 else 0.0156
    for window in (None, 300):
        ref = paged_attention_jax(q, k, v, pt, lens, Hkv, window=window)
        out = paged_attention_tpu(q, k, v, pt, lens, Hkv, interpret=True, window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=atol, atol=atol)
        assert not np.asarray(out[1], np.float32).any()


def test_decode_walk_plan_follows_the_layout():
    """N from the page's bytes alone: the serving layouts of the benchmark's
    configurations (bf16 pool, pages of 128 tokens, 16 pages a row)."""
    def n(hq, hkv, d=128, page=128, max_pages=16, dtype=jnp.bfloat16):
        return decode_walk_plan(hq, hkv, d, page, max_pages, dtype)["pages_per_visit"]

    assert n(28, 4) > n(32, 8) >= n(40, 10) >= 1  # Qwen2.5 / the mixture, Mistral, the hybrid
    assert n(28, 4, max_pages=2) == 2  # never more than a row holds
    assert n(32, 8, dtype=jnp.float32) <= n(32, 8)
    assert n(64, 64, page=1024) == 1  # a page too large for the budget is still visited


def test_a_done_row_hands_the_decode_kernel_no_context(monkeypatch):
    """The decode chunk's step hands the paged forward length 0 for a row
    that is done (ISSUE 37): the kernel passes over it, where a frozen
    row's stale context cost every step its pages. Live rows keep
    position + 1."""
    from inference_gateway_tpu.ops.sampling import chunk_gumbels, chunk_row_keys, effective_top_k
    from inference_gateway_tpu.serving.engine import STOP_TABLE_WIDTH

    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=64, dtype="float32",
                                 use_mesh=False, attention="paged", page_size=16))
    seen = {}
    real = engine._model.forward_paged

    def spy(params, cfg, tokens, positions, lengths, *args, **kw):
        seen["lengths"] = np.asarray(lengths)
        return real(params, cfg, tokens, positions, lengths, *args, **kw)

    monkeypatch.setattr(engine._model, "forward_paged", spy)
    S = 4
    pos = jnp.asarray([5, 40, 0, 17], jnp.int32)
    done = jnp.asarray([False, True, True, False])
    k_eff = effective_top_k(engine.config.top_k, engine.model_cfg.vocab_size)
    keys = chunk_row_keys(jax.random.PRNGKey(0), jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool), pos, 1)
    page_table = jnp.asarray(engine.allocator.page_table())
    engine._chunk_step_ee(
        engine.params, jnp.int32(0), engine.cache, jnp.ones((S,), jnp.int32), pos, jnp.zeros((S,), jnp.int32),
        done, jnp.full((S,), 8, jnp.int32), chunk_gumbels(keys, k_eff), k_eff, jnp.zeros((S,), jnp.float32),
        jnp.ones((S,), jnp.float32), jnp.full((S, STOP_TABLE_WIDTH), -1, jnp.int32),
        (False, None, None, None, None), (page_table, jnp.full((S,), 64, jnp.int32)))
    assert seen["lengths"].tolist() == [6, 0, 0, 18]


def test_page_allocator():
    cfg = PagedCacheConfig(page_size=16, max_slots=4, max_seq_len=64)
    alloc = PageAllocator(cfg)
    assert alloc.num_pages == 16  # full reservation

    alloc.ensure_capacity(0, 20)  # 2 pages
    assert len(alloc.pages_of(0)) == 2
    assert alloc.free_page_count() == 14
    # Growing within current pages is a no-op.
    alloc.ensure_capacity(0, 30)
    assert len(alloc.pages_of(0)) == 2
    alloc.ensure_capacity(0, 33)  # crosses into page 3
    assert len(alloc.pages_of(0)) == 3

    idx = alloc.flat_write_indices(0, 16, 2)
    pages = alloc.pages_of(0)
    assert idx[0] == pages[1] * 16 and idx[1] == pages[1] * 16 + 1

    alloc.release(0)
    assert alloc.free_page_count() == 16
    with pytest.raises(OutOfPagesError):
        alloc.ensure_capacity(1, 65)  # > per-slot max


def test_paged_engine_matches_dense():
    """Same seed, same prompts: the paged engine must emit exactly the
    dense engine's greedy tokens."""
    common = dict(model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
                  max_prefill_batch=2, use_mesh=False)
    dense = Engine(EngineConfig(**common, attention="dense"))
    paged = Engine(EngineConfig(**common, attention="paged", page_size=16))
    assert paged.paged

    sched_d = Scheduler(dense)
    sched_p = Scheduler(paged)
    sched_d.start()
    sched_p.start()
    try:
        rng = np.random.default_rng(7)
        prompts = [[int(x) for x in rng.integers(1, 250, size=n)] for n in (5, 20, 33)]
        for prompt in prompts:
            want, _ = generate_sync(sched_d, prompt, max_tokens=24, temperature=0.0)
            got, _ = generate_sync(sched_p, prompt, max_tokens=24, temperature=0.0)
            assert got == want
    finally:
        sched_d.stop()
        sched_p.stop()
    # All pages accounted for: free + prefix-cache holds == pool.
    held = paged.prefix_cache.stats()["cached_pages"] if paged.prefix_cache else 0
    assert paged.allocator.free_page_count() + held == paged.allocator.num_pages


def test_paged_engine_concurrent_reuse():
    """Slot/page reuse across more requests than slots."""
    import threading

    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 attention="paged", page_size=16))
    sched = Scheduler(engine)
    sched.start()
    try:
        rng = np.random.default_rng(1)
        prompts = [[int(x) for x in rng.integers(1, 250, size=rng.integers(3, 20))] for _ in range(6)]
        results = [None] * len(prompts)

        def worker(i):
            results[i], _ = generate_sync(sched, prompts[i], max_tokens=8, temperature=0.0)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert all(r is not None and len(r) > 0 for r in results)
    finally:
        sched.stop()
    held = engine.prefix_cache.stats()["cached_pages"] if engine.prefix_cache else 0
    assert engine.allocator.free_page_count() + held == engine.allocator.num_pages


def test_kernel_window_matches_reference():
    """Windowed decode: kernel (interpret) == gather reference, and only
    the last `window` tokens influence the output."""
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, ps, P, mp = 2, 8, 4, 64, 16, 32, 8
    q = jnp.asarray(rng.normal(size=(B, Hq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(P, ps, Hkv * D)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(P)[: B * mp].reshape(B, mp).astype(np.int32))
    lengths = jnp.asarray([70, 9], dtype=jnp.int32)
    W = 24

    ref = paged_attention_jax(q, k, v, pt, lengths, Hkv, window=W)
    out = paged_attention_tpu(q, k, v, pt, lengths, Hkv, interpret=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    # Corrupting KV before the window must not change the result: row 0's
    # window covers tokens [70-24, 70) = pages >= 2; poison pages 0-1.
    k_bad = k.at[pt[0, 0]].set(1e3).at[pt[0, 1]].set(1e3)
    v_bad = v.at[pt[0, 0]].set(1e3).at[pt[0, 1]].set(1e3)
    out_bad = paged_attention_tpu(q, k_bad, v_bad, pt, lengths, Hkv, interpret=True, window=W)
    np.testing.assert_allclose(np.asarray(out_bad[0]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)


def test_paged_sliding_window_matches_dense():
    """Mistral-style config served paged must emit the dense engine's
    tokens once context exceeds the window (round-1 verdict weak #4)."""
    from inference_gateway_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                      num_kv_heads=2, intermediate_size=128, max_position_embeddings=512,
                      sliding_window=8)
    common = dict(model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
                  max_prefill_batch=2, use_mesh=False)
    dense = Engine(EngineConfig(**common, attention="dense"), model_cfg=cfg)
    paged = Engine(EngineConfig(**common, attention="paged", page_size=16), model_cfg=cfg,
                   params=jax.tree.map(lambda x: x, dense.params))

    sched_d = Scheduler(dense)
    sched_p = Scheduler(paged)
    sched_d.start()
    sched_p.start()
    try:
        rng = np.random.default_rng(11)
        # Prompts longer than the window, decodes crossing page boundaries.
        for n in (6, 20, 40):
            prompt = [int(x) for x in rng.integers(1, 250, size=n)]
            want, _ = generate_sync(sched_d, prompt, max_tokens=30, temperature=0.0)
            got, _ = generate_sync(sched_p, prompt, max_tokens=30, temperature=0.0)
            assert got == want, f"prompt len {n}: paged+window diverged from dense"
    finally:
        sched_d.stop()
        sched_p.stop()


def test_paged_engine_serves_all_llama_family_variants():
    """The paged path is family-generic: Qwen2 (qkv biases), Gemma
    (norm offset + embed scale + gelu + custom head_dim), and Mistral
    (sliding window) must all produce identical tokens paged vs dense."""
    from inference_gateway_tpu.models import llama

    variants = {
        "qwen2-like": llama.LlamaConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
            intermediate_size=96, max_position_embeddings=256, qkv_bias=True,
            tie_word_embeddings=True),
        "gemma-like": llama.LlamaConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=1,
            intermediate_size=96, head_dim=16, max_position_embeddings=256,
            tie_word_embeddings=True, hidden_act="gelu_tanh", norm_offset=True,
            embed_scale=True, rms_norm_eps=1e-6),
        "mistral-like": llama.LlamaConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, num_kv_heads=2,
            intermediate_size=96, max_position_embeddings=256, sliding_window=20),
    }
    rng = np.random.default_rng(17)
    for name, cfg in variants.items():
        common = dict(model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
                      max_prefill_batch=1, use_mesh=False, decode_chunk=4,
                      prefill_buckets=(16, 32, 64))
        dense = Engine(EngineConfig(**common, attention="dense"), model_cfg=cfg)
        paged = Engine(EngineConfig(**common, attention="paged", page_size=8), model_cfg=cfg)
        sd, sp = Scheduler(dense), Scheduler(paged)
        sd.start(); sp.start()
        try:
            prompt = [int(x) for x in rng.integers(1, 250, size=24)]
            want, _ = generate_sync(sd, prompt, max_tokens=8, temperature=0.0)
            got, _ = generate_sync(sp, prompt, max_tokens=8, temperature=0.0)
            assert got == want, f"{name}: paged vs dense divergence"
        finally:
            sd.stop(); sp.stop()
