"""EvaByte's block (ISSUE 61, ``evabyte``): EVA attention over a page pool whose
rows are not one a token. A query reads the keys of its own window exactly and
every earlier window as one summary a chunk; the pool holds both in one row
space (``ops/eva_attention.cached_rows``) and a window is FOLDED into one page of
summaries when a row writes the next window's first token. Seeded, on the CPU, at
the tiny preset (a window of 32, chunks of 4, pages of 8, two layers, four heads),
in float32: the fold against the benchmark's plain reference
(``bench/references/eva_dense.py``) on the summaries themselves; the served path
(prompts in chunks through mixed steps, then decode chunks through the paged
engine, and the scheduler's own cut of a chunk at a window's end) against that
reference's full forward on LOGPROBS of every served token; the allocator's pages;
what reads the pool a row a token, refused by name."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import llama
from inference_gateway_tpu.models.family import family_of
from inference_gateway_tpu.ops import eva_attention
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow
from inference_gateway_tpu.serving.kv_cache import PageAllocator, PagedCacheConfig
from tests.test_lfm2 import decode   # rows through decode chunks, the first fresh, the others chained

BENCH = Path(__file__).resolve().parent.parent / "bench"
PRESET = "evabyte-test-tiny"
CFG = llama.PRESETS[PRESET]
W, C, PAGE = CFG.eva_window, CFG.eva_chunk, CFG.eva_window // CFG.eva_chunk
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 320, "rope_theta": 100000.0, "rms_norm_eps": 1e-5, "window_size": 32,
    "chunk_size": 4, "norm_add_unit_offset": True, "num_pred_heads": 2, "tie_word_embeddings": False}
# Float32 on both sides, the program's sums in another order (a chunk's queries against
# a page walk; the fold's elementwise sums): 2e-4 nats is ten times what the cases read.
TOLERANCE = 2e-4


def load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load(BENCH / "references" / "eva_dense.py", "bench_reference_eva_dense")


def prompt(n: int, seed: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(0, 256, size=n)]


@pytest.fixture(scope="module")
def eng():
    return Engine(EngineConfig(model=PRESET, dtype="float32", attention="paged", page_size=PAGE, max_slots=3,
                               max_seq_len=160, use_mesh=False, prefix_cache=False))


def reference_logprobs(eng, ids: list[int], mechanisms: dict | None = None):
    """log-softmax [T, V] of the plain reference over ``ids`` on the engine's weights."""
    layers = eng.params["layers"]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    sizes = REF.sizes({**MODEL, "assumed_mechanisms": mechanisms or {}})
    logits = REF.forward(ids, eng.params["embed"], lambda i: {n: f32(layers[n][i]) for n in REF.LEAVES},
                         f32(eng.params["final_norm"]), f32(eng.params["lm_head"]), sizes)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def admit(eng, slot: int, ids: list[int], beside=None, budget: int = 36):
    """``ids`` into ``slot`` through mixed steps of at most ``budget`` prompt tokens, each
    cut where the engine says a chunk ends (``prompt_chunk``: a window's end); the rows
    of ``beside`` {slot: [token, position, tokens, logprobs]} decode one token in each.
    Returns (first token, its logprob, every step's stats, the chunks' lengths)."""
    done, stats, cuts, beside = 0, [], [], beside or {}
    while done < len(ids):
        n = eng.prompt_chunk(done, min(budget, len(ids) - done))
        rows = [MixedRow(slot=s, token_ids=[r[0]], start=r[1]) for s, r in beside.items()]
        h = eng.mixed_step_submit(rows + [MixedRow(slot=slot, token_ids=ids[done:done + n], start=done, kind="prefill")])
        t, lp = eng.mixed_step_fetch(h)
        for s, r in beside.items():
            r[0], r[1] = int(t[s]), r[1] + 1
            r[2].append(int(t[s]))
            r[3].append(float(lp[s]))
        done += n
        stats.append(h.stats)
        cuts.append(n)
    return int(t[slot]), float(lp[slot]), stats, cuts


def held_against_reference(eng, ids, start, served):
    """The served logprobs of ``ids[start:]`` against the reference's, and greedy."""
    ref = reference_logprobs(eng, ids[:-1])
    want = ref[np.arange(start - 1, len(ids) - 1), ids[start:]]
    assert np.abs(want - np.asarray(served)).max() < TOLERANCE, (np.abs(want - np.asarray(served)).max(), start)
    assert [int(t) for t in ref[start - 1:].argmax(-1)] == ids[start:]


# -- the fold's op, and the rows ---------------------------------------------------------------

def test_cached_rows_is_one_function_of_the_context_for_ints_numpy_and_traced_arrays():
    rows = lambda n: eva_attention.cached_rows(n, 2048, 16)
    assert [rows(n) for n in (0, 1, 2048, 2049, 4096, 4097, 12288, 12289, 12800)] == [
        0, 1, 2048, 129, 2176, 257, 2688, 769, 1280]
    n = np.arange(0, 12801)
    assert rows(n).max() == 2688 and (rows(n)[1:] - rows(n)[:-1] != 1).sum() == 6   # it falls 1,920 at each of six rolls
    assert np.array_equal(np.asarray(jax.jit(rows)(jnp.asarray(n))), rows(n))
    # a token's row: its window's summaries' page count, then its offset in the window
    assert rows(5000 + 1) - 1 == 128 * 2 + 5000 % 2048


def test_the_fold_rewrites_a_windows_first_page_with_the_references_summaries():
    rng = np.random.RandomState(1)
    L, P, H, D = 2, 12, 4, 16
    k, v = (jnp.asarray(rng.randn(L, P, PAGE, H * D), jnp.float32) for _ in range(2))
    mu, phi = (jnp.asarray(np.clip(rng.randn(L, H, D), -1, 1), jnp.float32) for _ in range(2))
    # Three rows: slot 0 writes position 64 (window 2 starts: window 1 is folded, which lies
    # behind ONE page of summaries), slot 1 position 40 (no roll), slot 2 is idle at a
    # position that would roll.
    table = jnp.asarray([[3, 7, 1, 9, 4, 0, 0, 0], [2, 5, 0, 0, 0, 0, 0, 0], [6, 8, 10, 11, 0, 0, 0, 0]], jnp.int32)
    cache, rolls = jax.jit(lambda c, t, p, live: eva_attention.fold(c, t, p, live, mu, phi, window=W, chunk=C))(
        {"k": k, "v": v}, table, jnp.asarray([64, 40, 32]), jnp.asarray([True, True, False]))
    assert int(rolls) == 1
    sizes = REF.sizes(MODEL)
    for layer in range(L):
        window = [7, 1, 9, 4]   # slot 0's pages behind its one page of summaries
        ks, vs = REF.summaries(k[layer, jnp.asarray(window)].reshape(W, H, D), v[layer, jnp.asarray(window)].reshape(W, H, D),
                               mu[layer], phi[layer], sizes)
        np.testing.assert_allclose(cache["k"][layer, 7].reshape(PAGE, H, D), ks, atol=1e-6)
        np.testing.assert_allclose(cache["v"][layer, 7].reshape(PAGE, H, D), vs, atol=1e-6)
    untouched = np.ones((P,), bool)
    untouched[7] = False
    assert np.array_equal(cache["k"][:, untouched], k[:, untouched]) and np.array_equal(cache["v"][:, untouched], v[:, untouched])


def test_a_chunks_pooling_weights_differ_severalfold_under_the_seeded_vectors(eng):
    """(The benchmark's control that a mean would not pass for the model rests on this.)"""
    ids = prompt(64, 2)
    layers = eng.params["layers"]
    h = eng.params["embed"][jnp.asarray(ids)]
    h = REF.rms_norm(h, layers["attn_norm"][0], REF.sizes(MODEL))
    k = REF.rotary((h @ layers["wk"][0]).reshape(64, 4, 16), jnp.arange(64), 100000.0)
    a = jax.nn.softmax(16 ** -0.5 * jnp.einsum("jmhd,hd->jmh", k.reshape(16, C, 4, 16), layers["eva_mu"][0]), axis=1)
    assert float(jnp.median(a.max(axis=1) / a.min(axis=1))) > 2.0


# -- the served path against the reference's full forward ----------------------------------------

CASES = {
    # prompt tokens, budget of a step, decode chunks: what the case holds
    "the_roll_falls_inside_a_decode_chunk": (W - 3, 36, 2),       # positions 29..44 decode: 32 rolls at the chunk's 4th step
    "the_prompt_ends_on_a_windows_end": (W, 36, 1),                # the first decode step rolls
    "three_and_a_half_windows_chunks_cut_at_each_end": (3 * W + W // 2, 36, 2),
    "one_token_chunks_across_a_roll": (W + 2, 1, 1),
}


@pytest.mark.parametrize("case", CASES)
def test_prompt_in_chunks_then_decode_chunks_match_the_reference(eng, case):
    n, budget, chunks = CASES[case]
    ids = prompt(n, 3)
    tok, lp, stats, cuts = admit(eng, 0, ids, budget=budget)
    assert all(sum(cuts[:i + 1]) % W == 0 or sum(cuts[:i + 1]) == n or c == budget for i, c in enumerate(cuts))
    assert not any((sum(cuts[:i]) // W) != ((sum(cuts[:i + 1]) - 1) // W) for i in range(len(cuts))), cuts   # none straddles
    assert sum(s["eva_rolls"] for s in stats) == (n - 1) // W
    # what the steps read: the rows of the pool, against the tokens of context
    ends = np.cumsum(cuts)
    assert [s["eva_context"] for s in stats] == [float(e) for e in ends]
    assert [s["eva_rows"] for s in stats] == [float(eva_attention.cached_rows(int(e), W, C)) for e in ends]
    assert [s["eva_pairs"] for s in stats] == [s["eva_rows"] * c - c * (c - 1) // 2 for s, c in zip(stats, cuts)]
    assert [s["eva_queries"] for s in stats] == [float(c) for c in cuts]
    toks, lps, dstats = decode(eng, {0: (tok, n)}, chunks)
    steps = 8 * chunks
    assert sum(s["eva_rolls"] for s in dstats) == (n + steps - 1) // W - (n - 1) // W
    held_against_reference(eng, ids + [tok] + toks[0], n, [lp] + lps[0])
    # pages: never more than a page a folded window and the window's own; all returned
    held = len(eng.allocator.pages_of(0))
    assert held == -(-eng.page_cfg.peak_rows(n + steps) // PAGE) <= (n + steps - 1) // W + W // PAGE
    free = eng.allocator.free_page_count()
    eng.release_slot(0)
    assert eng.allocator.free_page_count() == free + held == eng.allocator.num_pages


def test_a_mixed_step_with_rows_in_different_windows_one_of_them_rolling(eng):
    """Slot 0 decodes across position 64 (a roll in a MIXED step, as a decode row) beside
    slot 1's prompt, whose chunks sit in windows 0, 1 and 2 and roll twice themselves."""
    a, b = prompt(2 * W - 2, 5), prompt(2 * W + 5, 6)
    tok, lp, _, _ = admit(eng, 0, a)
    row_a = [tok, len(a), [tok], [lp]]
    tok_b, lp_b, stats, cuts = admit(eng, 1, b, beside={0: row_a}, budget=20)
    assert cuts == [20, 12, 20, 12, 5]
    # slot 0 rolls in the step that writes its position 64 (the third), slot 1 in the third and fifth
    assert [s["eva_rolls"] for s in stats] == [0.0, 0.0, 2.0, 0.0, 1.0]
    toks, lps, _ = decode(eng, {0: (row_a[0], row_a[1]), 1: (tok_b, len(b))}, 1)
    held_against_reference(eng, a + row_a[2] + toks[0], len(a), row_a[3] + lps[0])
    held_against_reference(eng, b + [tok_b] + toks[1], len(b), [lp_b] + lps[1])
    eng.release_slot(0)
    eng.release_slot(1)


def test_a_preempted_row_recomputed_reads_the_same_logprobs(eng):
    """``_preempt``'s recompute: the prompt and what was generated, admitted again as a
    prompt into other pages, goes on as the row would have."""
    ids = prompt(W + 20, 7)
    tok, lp, _, _ = admit(eng, 0, ids)
    toks, lps, _ = decode(eng, {0: (tok, len(ids))}, 3)
    eng.release_slot(0)
    again = ids + [tok] + toks[0][:9]          # preempted after ten tokens, in window 1; window 2 starts 2 tokens on
    tok2, lp2, _, _ = admit(eng, 2, again)
    toks2, lps2, _ = decode(eng, {2: (tok2, len(again))}, 1)
    assert [tok2] + toks2[2] == toks[0][9:18]
    np.testing.assert_allclose([lp2] + lps2[2], lps[0][9:18], atol=TOLERANCE)
    eng.release_slot(2)


@pytest.mark.parametrize("control", [
    {"summaries": "none"}, {"summaries": "one_window_early"}, {"pooling": "mean"}, {"summaries": "stale_rows"},
    {"precision": "bfloat16"}, {"precision": "e4m3"}])
def test_the_reference_with_one_mechanism_changed_is_another_model(eng, control):
    """The benchmark's controls, here: each moves the logprobs by far more than the tolerance
    that holds the served path to the reference above. (On the chip the comparison's limits
    are the harness's one pair, a thousand times this tolerance, and see two of the four
    mechanisms: PERF.md section 6, PR 61. These cases are what holds the others.)"""
    ids = prompt(3 * W + 9, 8)
    sound, changed = reference_logprobs(eng, ids), reference_logprobs(eng, ids, control)
    assert np.abs(sound[:W] - changed[:W]).max() < 1e-5 or control == {"summaries": "one_window_early"} or "precision" in control
    assert np.abs(sound[2 * W:] - changed[2 * W:]).max() > 100 * TOLERANCE


# -- the scheduler's cut, the prefix cache, the allocator ----------------------------------------

def test_the_scheduler_cuts_a_chunk_at_a_windows_end_and_declines_prefix_hits():
    from inference_gateway_tpu.serving.scheduler import Scheduler, generate_sync

    eng = Engine(EngineConfig(model=PRESET, dtype="float32", attention="paged", page_size=PAGE, max_slots=2,
                              max_seq_len=160, use_mesh=False, mixed_step_tokens=24))
    sched = Scheduler(eng)

    class Records:   # a timeline that keeps the steps' records
        def __init__(self):
            self.spans = []

        def record(self, kind, duration, spans=None, **rest):
            self.spans.append(spans or {})

        def record_host_gap(self, kind, gap_ms):
            pass

    sched.timeline = Records()
    sched.start()
    try:
        ids = prompt(2 * W + 11, 9)
        out, reason = generate_sync(sched, ids, max_tokens=12)
        again, _ = generate_sync(sched, ids, max_tokens=12)
    finally:
        sched.stop()
    assert reason == "length" and out == again
    ref = reference_logprobs(eng, ids + out[:-1])
    assert [int(t) for t in ref[len(ids) - 1:].argmax(-1)] == out
    assert eng.prefix_cache.declined == 1 and eng.prefix_cache.hits == 0
    assert sum(s.get("eva_rolls", 0) for s in sched.timeline.spans) == 4   # two requests, two windows folded each
    status = eng.family_status()["eva"]
    assert status["prefix_hits_declined"] == 1 and status["window"] == W and status["chunk"] == C
    assert status["longest_slot"] == {"tokens": 160, "cached_rows": 64, "pages_held_at_most": 8, "pages_full_attention": 20}


def test_the_allocator_holds_the_most_rows_a_slot_has_held_on_the_way():
    rows = family_of(CFG).cached_rows
    cfg = PagedCacheConfig(page_size=PAGE, max_slots=2, max_seq_len=160, rows=rows)
    assert cfg.max_pages_per_slot == 8 and cfg.resolve_num_pages() == 16   # 20 a slot a row a token
    alloc = PageAllocator(cfg)
    for n in range(1, 161):
        alloc.ensure_capacity(0, n)
        w = (n - 1) // W
        assert len(alloc.pages_of(0)) == -(-cfg.peak_rows(n) // PAGE) <= w + W // PAGE
    # a whole prompt at once holds what its chunks would have come to; writes land in its rows
    alloc.ensure_capacity(1, 70)
    assert len(alloc.pages_of(1)) == 1 + W // PAGE
    pages = alloc.pages_of(1)
    assert list(alloc.flat_write_indices(1, 64, 3)) == [pages[2] * PAGE + i for i in range(3)]   # window 2: behind two pages
    alloc.release(0)
    alloc.release(1)
    assert alloc.free_page_count() == 16


# -- the record, the refusals -------------------------------------------------------------------

def test_the_family_record_and_the_refusals_by_name():
    fam = family_of(CFG)
    assert fam.module is llama and fam.pool == "kv" and fam.mixed_alone and not fam.state_store
    assert fam.chunk_stats == fam.mixed_stats == ("eva_rows", "eva_context", "eva_rolls", "eva_pairs", "eva_queries")
    assert fam.cached_rows.window == W and fam.cached_rows.page_size == PAGE and fam.cached_rows(W + 1) == PAGE + 1
    assert family_of(llama.PRESETS["test-tiny"]).cached_rows is None
    base = dict(model=PRESET, dtype="float32", attention="paged", page_size=PAGE, max_slots=2, max_seq_len=64)
    for asked, names in (({"spec_draft": "ngram"}, "speculative"), ({"vision_model": "vit-test-tiny"}, "multimodal"),
                         ({"quantize": "int4"}, "int8 is the one"), ({"mesh_shape": {"tp": 2}}, "no mesh"),
                         ({"page_size": 16}, "pages of 8 rows")):
        with pytest.raises(ValueError, match=names):
            Engine(EngineConfig(**{**base, **asked}))


def test_an_engine_over_the_dense_cache_builds_the_tree_and_refuses_to_serve():
    """(The benchmark's reference child asks for the weights so.)"""
    eng = Engine(EngineConfig(model=PRESET, dtype="float32", attention="dense", max_slots=1, max_seq_len=16,
                              use_mesh=False))
    assert eng.params["layers"]["eva_mu"].shape == (2, 4, 16) and eng.params["lm_head"].shape == (64, 640)
    assert float(jnp.abs(eng.params["layers"]["eva_mu"]).max()) <= 1.0
    with pytest.raises(NotImplementedError, match="paged pool alone"):
        eng.prefill([[1, 2, 3]], [0], [0.0], [1.0])
