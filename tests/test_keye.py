"""Keye-VL-2.0's text block on the normal path (ISSUE 36), at the tiny preset on
the CPU: two layers, q/k norms, 8 experts top-2, an indexer of 4 heads of 16
whose topk (24) is shorter than the sequences, seeded random weights.

The program against ``bench/references/moe_dsa_gqa.py`` (float32, precision
"highest", no cache, no kernels; it imports nothing from the program): a prompt
chunked over four mixed steps beside other rows, then decode through the paged
cache; the chosen sets of both selections against the reference's; contexts at
or under topk against the skeleton's plain attention; the reference's M-RoPE
against plain rotary; a prefix hit; the counters; the refusals.

A one-query row's read follows the width of a page-table row (ISSUE 45,
``sa.one_query_read``): rows of ``WALK_UNDER`` x topk = 288 tokens take the
masked walk of whole pages behind the exact k-th score, rows a page longer
the gather behind ``lax.top_k`` (on the CPU the walk is the
decode kernel's twin, ``paged_attention_jax``; the kernel itself against the twin
is ``tests/test_paged_attention.py``'s). ``READS`` holds both sides.
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import mixtral
from inference_gateway_tpu.ops import sparse_attention as sa
from inference_gateway_tpu.ops.quant import QTensor
from inference_gateway_tpu.ops.rope import apply_rope, rope_cos_sin, rope_inv_freq
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow

PRESET = "keye-test-tiny"
CFG = mixtral.PRESETS[PRESET]
TOPK = CFG.indexer_topk
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "head_dim": 16, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": TOPK}}


def load_reference():
    path = Path(__file__).resolve().parent.parent / "bench" / "references" / "moe_dsa_gqa.py"
    spec = importlib.util.spec_from_file_location("bench_reference_moe_dsa_gqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def f32(w):
    return w.q.astype(jnp.float32) * w.scale if isinstance(w, QTensor) else w.astype(jnp.float32)


def layer_f32(params, i):
    return {n: f32(jax.tree.map(lambda a: a[i], params["layers"][n])) for n in REF.LEAVES}


def reference_logits(params, ids, model=MODEL):
    """The reference over ``ids`` with the program's weights as data."""
    return REF.forward(ids, params["embed"], lambda i: layer_f32(params, i), f32(params["final_norm"]),
                       f32(params["lm_head"]), REF.sizes(model))[:]


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


# A one-query row's read -> the engine's ``max_seq_len`` that takes it (36 pages of 8 = 288 = 12 x 24, and a page more).
READS = {"gather": sa.WALK_UNDER * TOPK + 8, "walk": sa.WALK_UNDER * TOPK}


def engine(dtype="float32", quantize=None, model=PRESET, **kw):
    kw = {"page_size": 8, "max_slots": 4, "max_seq_len": 256, "mixed_step_tokens": 40, "decode_chunk": 4,
          "use_mesh": False, **kw}
    return Engine(EngineConfig(model=model, attention="paged", dtype=dtype, quantize=quantize, **kw))


def test_presets_follow_the_source():
    stage = mixtral.PRESETS["keye-vl2-30b-a3b-stage8"]
    assert stage.num_layers == 8
    for cfg in (stage, CFG):
        assert cfg.qk_norm and cfg.router_input == "ffn" and cfg.hidden_act == "silu" and not cfg.qkv_bias
        assert cfg.rms_norm_eps == 1e-6 and cfg.sliding_window is None and not cfg.tie_word_embeddings
    assert (stage.hidden_size, stage.num_heads, stage.num_kv_heads, stage.hd) == (2048, 32, 4, 128)
    assert (stage.num_experts, stage.experts_per_token, stage.intermediate_size) == (128, 8, 768)
    assert (stage.vocab_size, stage.rope_theta, stage.max_position_embeddings) == (151936, 1e7, 262144)
    assert (stage.indexer_heads, stage.indexer_head_dim, stage.indexer_topk) == (16, 64, 2048)


# |served - reference| of a token's logprob, in nats. The seeded build makes the
# chosen set show in the logits (ops/quant.seeded_std, seeded_qk_gain: heads
# that rest on few keys, an attention as large as the stream), measured over
# three pairs of prompts at this size: float32 reads 0 to 5e-7 (summation order
# alone), where a reference that keeps ONE key fewer (topk 23 for 24) reads
# 0.04-0.17 and one that attends over every key 0.11-0.22
# (test_the_selection_matters), so the float32 cases hold the sets key for
# key. bfloat16 / int8: the served side rounds activations, the residual stream
# and the cached keys (the indexer's too) to bf16 while the reference keeps
# float32 on the same (dequantised) weights, and a score within bf16's rounding
# of the cut may swap one key of a set: 0.001-0.040. 0.1 lies between that and
# the absence of a selection; those cases hold the path and that a selection is
# made, not each key.
TOLERANCE = {"float32": 2e-5, "bfloat16": 0.1}
CASES = [("float32", None), ("bfloat16", None), ("bfloat16", "int8")]


def logprob_gap(eng, seq, start, served):
    """Largest |served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1]), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return float(np.max(np.abs(want - np.asarray(served, np.float32))))


def decode(eng, rows, chunks):
    """Greedy decode of ``rows`` {slot: (token, position)} for ``chunks`` chunks,
    the first fresh, the others chained; returns ({slot: tokens}, {slot:
    logprobs}, the handles' stats)."""
    S = eng.config.max_slots
    t, p, active = np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool)
    for slot, (tok, pos) in rows.items():
        t[slot], p[slot], active[slot] = tok, pos, True
    toks, lps, stats = {s: [] for s in rows}, {s: [] for s in rows}, []
    for i in range(chunks):
        h = eng.decode_chunk_submit(t, p, active, np.zeros(S, np.float32), np.ones(S, np.float32), chain=i > 0)
        a, b = eng.decode_chunk_fetch(h)
        for s in rows:
            toks[s] += [int(x) for x in a[:, s]]
            lps[s] += [float(x) for x in b[:, s]]
        stats.append(h.stats)
    return toks, lps, stats


def admit(eng, slot, ids, beside=None, chunk=36):
    """``ids`` into ``slot`` through mixed steps of ``chunk`` prompt tokens; the
    rows of ``beside`` {slot: [token, position, tokens, logprobs]} decode one
    token in each. Returns (first token, its logprob, every step's stats)."""
    done, stats, beside = 0, [], beside or {}
    while done < len(ids):
        n = min(chunk, len(ids) - done)
        rows = [MixedRow(slot=s, token_ids=[r[0]], start=r[1]) for s, r in beside.items()]
        h = eng.mixed_step_submit(rows + [MixedRow(slot=slot, token_ids=ids[done:done + n], start=done,
                                                   kind="prefill")])
        t, lp = eng.mixed_step_fetch(h)
        for s, r in beside.items():
            r[0], r[1] = int(t[s]), r[1] + 1
            r[2].append(int(t[s]))
            r[3].append(float(lp[s]))
        done += n
        stats.append(h.stats)
    return int(t[slot]), float(lp[slot]), stats


@pytest.mark.parametrize("read", list(READS))
@pytest.mark.parametrize("dtype,quantize", CASES)
def test_chunked_prompt_then_decode_beside_other_rows_matches_the_reference(dtype, quantize, read):
    """Slot 0 takes a 30-token prompt and decodes; slot 1's 130-token prompt then
    goes in over four mixed steps (36, 36, 36, 22: a chunk of several tiles'
    worth it is not, a row of several queries past topk it is) with slot 0's
    decode row beside every chunk, one query at 31-34 tokens, past topk = 24;
    both rows then decode two chunks through the paged cache, slot 1 at 131
    and more. Every logprob of both rows is held to the reference."""
    eng = engine(dtype, quantize, max_seq_len=READS[read])
    assert eng.mixed_ok and eng.family.pool == "kv+index" and eng.mesh_axes == {"tp": 1}
    assert eng.family_status()["dsa"]["one_query_row"]["read"] == read
    a, b = prompt(30, 3), prompt(130, 4)
    tok, lp, _ = admit(eng, 0, a)
    row_a = [tok, 30, [tok], [lp]]
    tok_b, lp_b, stats = admit(eng, 1, b, beside={0: row_a})
    assert len(stats) == 4
    toks, lps, _ = decode(eng, {0: (row_a[0], row_a[1]), 1: (tok_b, 130)}, 2)
    assert logprob_gap(eng, a + row_a[2] + toks[0], 30, row_a[3] + lps[0]) < TOLERANCE[dtype]
    assert logprob_gap(eng, b + [tok_b] + toks[1], 130, [lp_b] + lps[1]) < TOLERANCE[dtype]


@pytest.mark.parametrize("read", list(READS))
@pytest.mark.parametrize("topk,least", [(10 ** 6, 0.1), (TOPK // 2, 0.1), (TOPK - 1, 0.03)])
def test_the_selection_matters(topk, least, read):
    """The control of the comparison above: the same served row against a
    reference that selects nothing (topk past every context), one that keeps
    half the set, and one that keeps one key fewer. Each differs from the
    served logprobs by thousands of times the float32 tolerance (measured on
    this prompt 0.152, 0.273 and 0.054 nats; over three prompts 0.11-0.22,
    0.14-0.27 and 0.04-0.17), and the first two by more than the bfloat16 one:
    a path that read another set than the exact top-k would not pass (either
    read of a one-query row: the decode chunk's four tokens are its)."""
    eng = engine(max_seq_len=READS[read])
    b = prompt(130, 4)
    tok, lp, _ = admit(eng, 1, b)
    toks, lps, _ = decode(eng, {1: (tok, 130)}, 1)
    seq = b + [tok] + toks[1]
    other = dict(MODEL, sa_config=dict(MODEL["sa_config"], topk=topk))
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1], other), axis=-1)
    want = np.asarray(logp[np.arange(129, len(seq) - 1), np.asarray(seq[130:])])
    gap = float(np.max(np.abs(want - np.asarray([lp] + lps[1], np.float32))))
    assert gap > least >= 0.3 * TOLERANCE["bfloat16"] > 1000 * TOLERANCE["float32"]


def layer0_indexer(params, ids):
    """Layer 0's indexer on both sides over ``ids``: the reference's (qi, ki, wi)
    and the program's, from the same normed embeddings."""
    w = layer_f32(params, 0)
    pos = jnp.arange(len(ids))
    h = REF.dense.rms_norm(params["embed"][jnp.asarray(ids)].astype(jnp.float32), w["attn_norm"], 1e-6)
    lp = jax.tree.map(lambda a: a[0], {n: params["layers"][n] for n in
                                       ("wq_idx", "wk_idx", "ww_idx", "k_idx_norm", "k_idx_bias")})
    return REF.indexer(h, w, pos, REF.sizes(MODEL)), mixtral._indexer(h, lp, pos, CFG), pos


def test_a_prompt_chunks_chosen_sets_are_the_references():
    """The mask a tile of queries attends under (the exact k-th largest by
    counting passes, ties by position) against the reference's stable sort, for
    every query of a 150-token sequence: the same sets, key for key."""
    params = mixtral.init_params(jax.random.PRNGKey(36), CFG, dtype=jnp.float32)
    (qi, ki, wi), (pqi, pki, pwi), pos = layer0_indexer(params, prompt(150, 5))
    want = np.asarray(REF.selection(qi, ki, wi, pos, TOPK))
    visible = pos[None, :] <= pos[:, None]
    scores = jnp.where(visible, sa.index_scores(pqi, pwi, pki.T[None]), -jnp.inf)   # one page of 150
    got = np.asarray(sa.topk_mask(scores, visible, TOPK))
    assert (got == want).all()
    assert (want.sum(axis=1) == np.minimum(np.arange(150) + 1, TOPK)).all()


def test_equal_scores_go_to_the_lower_position():
    """Rows built of few distinct values, so the cut falls among equals: both of
    the program's selections keep the lower positions, as the reference's
    stable descending sort does."""
    rng = np.random.default_rng(7)
    scores = jnp.asarray(rng.integers(0, 4, size=(6, 64)).astype(np.float32))
    visible = jnp.ones((6, 64), bool)
    order = np.argsort(-np.asarray(scores), axis=1, kind="stable")
    want = np.zeros((6, 64), bool)
    np.put_along_axis(want, order[:, :20], True, axis=1)
    assert (np.asarray(sa.topk_mask(scores, visible, 20)) == want).all()
    _, idx = jax.lax.top_k(scores, 20)
    got = np.zeros((6, 64), bool)
    np.put_along_axis(got, np.asarray(idx), True, axis=1)
    assert (got == want).all()


def _one_query_row(pki, pqi, pwi, n, table_pages, topk, ps=8):
    """``sparse_decode`` of ONE row of ``n`` tokens whose indexer keys are
    ``pki`` (n, 16), through a page table of ``table_pages`` that scatters the
    tokens over a pool of 40 pages; equal keys (uniform weights) and values
    that name their token: (mean of the chosen positions, of their squares)."""
    pages = np.random.default_rng(1).permutation(40)[:table_pages]
    rows = (pages[:, None] * ps + np.arange(ps)[None, :]).reshape(-1)[:n]
    ki_pages = jnp.zeros((40, 16, ps), jnp.float32).at[rows // ps, :, rows % ps].set(pki)
    flat_k = jnp.zeros((40 * ps, 2 * 16), jnp.float32)
    flat_v = jnp.zeros((40 * ps, 2 * 16), jnp.float32).at[rows, 0].set(jnp.arange(n, dtype=jnp.float32))
    flat_v = flat_v.at[rows, 1].set(jnp.arange(n, dtype=jnp.float32) ** 2)
    out = sa.sparse_decode(jnp.ones((1, 4, 16)), pqi, pwi, flat_k, flat_v, ki_pages, jnp.asarray(pages)[None, :],
                           jnp.asarray([n]), topk=topk, num_kv_heads=2, page_size=ps)
    return float(out[0, 0, 0]), float(out[0, 0, 1])


# A one-query row's read -> pages of 8 a row of the page table holds: 13 hold the
# row's 100 tokens (104 <= 288: the walk), 37 are past WALK_UNDER x topk (296: the gather).
TABLE_PAGES = {"walk": 13, "gather": sa.WALK_UNDER * TOPK // 8 + 1}


@pytest.mark.parametrize("read", list(TABLE_PAGES))
def test_a_one_query_rows_equal_scores_go_to_the_lower_position(read):
    """The same through ``sparse_decode``, either read: an indexer of one live
    head whose keys hold one of four values, so a row's scores are those values
    and the cut (24 of 100) falls among equals."""
    assert sa.one_query_read(TOPK, TABLE_PAGES[read] * 8) == read
    values = np.random.default_rng(8).integers(0, 4, size=100).astype(np.float32)
    pki = jnp.zeros((100, 16), jnp.float32).at[:, 0].set(values)
    pqi = jnp.zeros((1, 4, 16), jnp.float32).at[0, 0, 0].set(1.0)
    pwi = jnp.zeros((1, 4), jnp.float32).at[0, 0].set(1.0)
    chosen = np.argsort(-values, kind="stable")[:TOPK]
    assert values[chosen].min() == values[np.argsort(-values, kind="stable")[TOPK]]   # the cut is among equals
    mean, mean_sq = _one_query_row(pki, pqi, pwi, 100, TABLE_PAGES[read], TOPK)
    np.testing.assert_allclose(mean, chosen.mean(), rtol=1e-5)
    np.testing.assert_allclose(mean_sq, (chosen ** 2).mean(), rtol=1e-5)


@pytest.mark.parametrize("read", list(TABLE_PAGES))
def test_a_decode_rows_chosen_set_is_the_references(read):
    """What ``sparse_decode`` reads for a one-query row, through a page table
    that scatters the tokens over the pool, by the gather and by the masked
    walk: with values that name their token (v[s] = one-hot-ish code of s) the
    attention's output is a mixture over exactly the reference's set."""
    assert sa.one_query_read(TOPK, TABLE_PAGES[read] * 8) == read
    params = mixtral.init_params(jax.random.PRNGKey(36), CFG, dtype=jnp.float32)
    n = 100
    (qi, ki, wi), (pqi, pki, pwi), pos = layer0_indexer(params, prompt(n, 6))
    want = np.asarray(REF.selection(qi[-1:], ki, wi[-1:], pos[-1:], TOPK))[0]
    mean, mean_sq = _one_query_row(pki, pqi[-1:], pwi[-1:], n, TABLE_PAGES[read], TOPK)
    chosen = np.nonzero(want)[0]
    assert len(chosen) == TOPK
    np.testing.assert_allclose(mean, chosen.mean(), rtol=1e-5)
    np.testing.assert_allclose(mean_sq, (chosen ** 2).mean(), rtol=1e-5)


@pytest.mark.parametrize("read", list(READS))
def test_contexts_at_or_under_topk_are_the_skeletons_plain_attention(read):
    """A row that holds topk tokens or fewer keeps every key, by the same path
    as any other (there is no plain-attention branch): the served logprobs equal,
    to float32's summation order (measured 5e-7), those of the same tree served
    with no indexer, the skeleton's own attention, through a mixed step with a
    decode row beside a prompt and a decode chunk that ends at exactly topk
    tokens, and the tokens are the same. One token more and the two part."""
    with_indexer = engine(max_seq_len=READS[read])
    without = Engine(EngineConfig(model=PRESET, attention="paged", dtype="float32", page_size=8, max_slots=4,
                                  max_seq_len=READS[read], mixed_step=True, mixed_step_tokens=40, decode_chunk=4,
                                  use_mesh=False),
                     params=with_indexer.params, model_cfg=dataclasses.replace(CFG, indexer_topk=0))
    assert without.family.pool == "kv" and set(without.cache) == {"k", "v"} and set(with_indexer.cache) == {"k", "v", "ki"}
    outs = []
    for eng in (with_indexer, without):
        a, b = prompt(12, 8), prompt(TOPK - 4, 9)
        tok, lp, _ = admit(eng, 0, a)
        row_a = [tok, 12, [tok], [lp]]
        tok_b, lp_b, _ = admit(eng, 1, b, beside={0: row_a})
        toks, lps, _ = decode(eng, {0: (row_a[0], row_a[1]), 1: (tok_b, TOPK - 4)}, 1)   # row 1 ends at topk
        more, more_lps, _ = decode(eng, {1: (toks[1][-1], TOPK)}, 1)
        outs.append(((row_a[2], tok_b, toks), row_a[3] + [lp_b] + lps[0] + lps[1], more_lps[1]))
    assert outs[0][0] == outs[1][0]
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=TOLERANCE["float32"], rtol=0)
    assert np.max(np.abs(np.asarray(outs[0][2]) - np.asarray(outs[1][2]))) > 100 * TOLERANCE["float32"]


def test_selecting_everything_equals_plain_attention():
    """A row that sees topk tokens or fewer beside a row past topk: the short
    row's logprobs are the reference's, whose selection keeps every key there."""
    eng = engine()
    long_row, short = prompt(60, 10), prompt(15, 11)
    tok, lp, _ = admit(eng, 0, long_row)
    row = [tok, 60, [tok], [lp]]
    tok_s, lp_s, _ = admit(eng, 2, short, beside={0: row})
    toks, lps, _ = decode(eng, {0: (row[0], row[1]), 2: (tok_s, 15)}, 1)
    assert logprob_gap(eng, short + [tok_s] + toks[2], 15, [lp_s] + lps[2]) < TOLERANCE["float32"]


def test_mrope_with_equal_position_rows_is_plain_rotary():
    """The reference's multimodal rotary over three equal rows against its own
    plain rotary and the program's; with a row shifted, its section moves."""
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 4, 16), jnp.float32)
    pos = jnp.arange(9) + 3
    got = REF.mrope(x, jnp.stack([pos] * 3), 10000.0, (2, 3, 3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(REF.dense.rotary(x, pos, 10000.0)), atol=1e-6)
    cos, sin = rope_cos_sin(pos, rope_inv_freq(16, 10000.0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(apply_rope(x, cos, sin)), atol=1e-6)
    moved = REF.mrope(x, jnp.stack([pos, pos + 5, pos]), 10000.0, (2, 3, 3))
    diff = np.abs(np.asarray(moved - got)).max(axis=(0, 1))
    assert (diff[[2, 3, 4, 10, 11, 12]] > 1e-3).all() and (diff[[0, 1, 5, 6, 7, 8, 9, 13, 14, 15]] == 0).all()


def test_a_prefix_hit_adopts_the_indexers_pages():
    """Two prompts share their first 64 tokens (8 full pages). The second
    adopts the first's pages, the indexer's keys with them (one page id names
    all three arrays), prefills its tail alone, and its first token's logprob
    is the reference's over the whole prompt, past topk."""
    eng = engine(prefix_cache=True)
    shared, a_tail, b_tail = prompt(64, 12), prompt(10, 13), prompt(20, 14)
    a, b = shared + a_tail, shared + b_tail
    admit(eng, 0, a)
    eng.prefix_cache.insert(a, eng.allocator.pages_of(0))
    pages, matched = eng.prefix_cache.match(b)
    assert matched == 64 and pages == eng.allocator.pages_of(0)[:8]
    eng.allocator.adopt_pages(1, pages)
    h = eng.mixed_step_submit([MixedRow(slot=1, token_ids=b[64:], start=64, kind="prefill")])
    t, lp = eng.mixed_step_fetch(h)
    assert eng.allocator.pages_of(1)[:8] == pages
    assert logprob_gap(eng, b + [int(t[1])], 84, [float(lp[1])]) < TOLERANCE["float32"]
    ki = np.asarray(eng.cache["ki"])
    assert np.abs(ki[:, pages]).sum() > 0


@pytest.mark.parametrize("read", list(READS))
def test_the_counters_are_the_counts_by_hand(read):
    """``dsa_context_tokens``: what the rows' queries could see, summed over
    queries and layers. ``dsa_kv_tokens_read``: a one-query row reads topk
    tokens on the gather and the pages it holds on the walk, in the decode
    chunk and beside a chunk in a mixed step, and a prompt chunk's query every
    block of keys up to its tile's last position, whatever the row holds. The
    status names the read and what chose it."""
    eng = engine(max_seq_len=READS[read])
    L = CFG.num_layers
    _, _, stats = admit(eng, 0, prompt(20, 15))
    block = sa.block_tokens(8, READS[read])
    assert (stats[0]["dsa_context_tokens"], stats[0]["dsa_kv_tokens_read"]) == (L * sum(range(1, 21)), L * 20 * block)
    tok, _, stats = admit(eng, 1, prompt(70, 16))
    assert [s["dsa_context_tokens"] for s in stats] == [L * sum(range(1, 37)), L * sum(range(37, 71))]
    assert [s["dsa_kv_tokens_read"] for s in stats] == [L * 36 * block, L * 34 * block]
    _, _, stats = decode(eng, {1: (tok, 70)}, 1)
    assert stats[0]["dsa_context_tokens"] == L * sum(range(71, 75))
    pages_held = sum(-(-ctx // 8) * 8 for ctx in range(71, 75))   # 72 + 72 + 80 + 80
    assert stats[0]["dsa_kv_tokens_read"] == L * {"gather": 4 * TOPK, "walk": pages_held}[read]
    assert stats[0]["experts_read"] == 2.0
    # a mixed step: slot 1's one-query row at 75 tokens beside a 10-token chunk
    row = [int(prompt(1, 17)[0]), 74, [], []]
    _, _, stats = admit(eng, 2, prompt(10, 18), beside={1: row})
    assert stats[0]["dsa_context_tokens"] == L * (75 + sum(range(1, 11)))
    assert stats[0]["dsa_kv_tokens_read"] == L * ({"gather": TOPK, "walk": 80}[read] + 10 * block)
    status = eng.family_status()["dsa"]
    assert (status["topk"], status["indexer_heads"], status["indexer_head_dim"]) == (TOPK, 4, 16)
    assert status["one_query_row"] == {"read": read, "row_tokens": READS[read], "walk_under": sa.WALK_UNDER,
                                       "walk_up_to_tokens": sa.WALK_UNDER * TOPK}
    assert ("whole pages under the mask" in status["read"]["one_query_row"]) == (read == "walk")
    assert ("k-th largest" in status["select"]["one_query_row"]) == (read == "walk")


@pytest.mark.parametrize("kw,what", [
    ({"spec_draft": "ngram"}, "speculative"),
    ({"vision_model": "vit-test-tiny"}, "vision tower"),
    ({"quantize": "int4"}, "int4"),
    ({"mesh_shape": {"tp": 2}, "use_mesh": True}, "mesh"),
])
def test_refusals_by_name(kw, what):
    with pytest.raises(ValueError, match=what) as e:
        engine(**kw)
    assert PRESET in str(e.value)


def test_the_dense_cache_builds_and_refuses_to_run():
    """The benchmark's reference child asks for the tree under a one-row dense
    cache: it builds; a forward over that cache has no indexer and says so."""
    eng = Engine(EngineConfig(model=PRESET, attention="dense", max_slots=1, max_seq_len=16, dtype="float32",
                              use_mesh=False))
    assert set(REF.LEAVES) <= set(eng.params["layers"])
    with pytest.raises(NotImplementedError, match="paged"):
        eng.prefill([[1, 2, 3]], [0], [0.0], [1.0])


def test_an_image_part_is_refused_by_name():
    from inference_gateway_tpu.serving.server import SidecarServer, _BadRequest

    server = SidecarServer.__new__(SidecarServer)
    server.engine, server.model_name = engine(), PRESET
    body = {"messages": [{"role": "user", "content": [
        {"type": "text", "text": "what is this"},
        {"type": "image_url", "image_url": {"url": "data:image/png;base64,AAAA"}}]}]}
    with pytest.raises(_BadRequest, match="vision tower") as e:
        server._prepare(body)
    assert PRESET in str(e.value)


def test_layerwise_quantised_build_matches_the_trees_shape():
    """The build a published-size tree takes (a layer's block at a time, never
    the full-precision tree) against quantise-after-init: the same leaves,
    shapes and dtypes, the indexer's among them."""
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params, quantize_llama_params

    a = init_quantized_llama_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16)
    b = quantize_llama_params(mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(a)] == [(x.shape, x.dtype) for x in jax.tree.leaves(b)]
    assert isinstance(a["layers"]["wq_idx"], QTensor) and not isinstance(a["layers"]["wk_idx"], QTensor)
    spread = np.asarray(f32(a["layers"]["wq_idx"])).std()
    assert abs(spread - CFG.hidden_size ** -0.5) < 0.01


@pytest.mark.parametrize("leaf,fan_in", [("wv", 64), ("wo", 64), ("wq_idx", 64), ("wk_idx", 64), ("ww_idx", 64)])
def test_the_seeded_build_makes_the_chosen_set_show(leaf, fan_in):
    """Values, output projection and the indexer's projections at their fan-in
    ** -0.5 and the q/k norms' gains at 1.4, in both builds (what makes a wrong
    set move the logits: ops/quant.seeded_std); every other model keeps the
    scales it had."""
    from inference_gateway_tpu.models.llama import PRESETS as LLAMA
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params, seeded_qk_gain, seeded_std

    assert seeded_std(CFG, leaf) == fan_in ** -0.5 and seeded_qk_gain(CFG) == 1.4
    for build in (mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32),
                  init_quantized_llama_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16)):
        assert abs(float(np.asarray(f32(build["layers"][leaf])).std()) - fan_in ** -0.5) < 0.01
        assert np.allclose(np.asarray(build["layers"]["q_norm"], np.float32), 1.4, atol=0.01)
        assert np.allclose(np.asarray(build["layers"]["k_norm"], np.float32), 1.4, atol=0.01)
    stage = mixtral.PRESETS["keye-vl2-30b-a3b-stage8"]
    assert (seeded_std(stage, "wo"), seeded_std(stage, "wv")) == (4096 ** -0.5, 2048 ** -0.5)
    for other in (mixtral.PRESETS["smallthinker-test-tiny"], mixtral.PRESETS["mixtral-test-tiny"]):
        assert (seeded_std(other, "wv"), seeded_std(other, "wo"), seeded_qk_gain(other)) == (0.02, 0.005, 1.0)
    dense = LLAMA["test-tiny"]
    assert (seeded_std(dense, "wv"), seeded_std(dense, "wo"), seeded_qk_gain(dense)) == (0.02, 0.02, 1.0)
