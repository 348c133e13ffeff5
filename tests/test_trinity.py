"""Trinity-Mini's block on the normal path (ISSUE 41), at the tiny preset on the
CPU: the stage's own shape at toy widths (2 dense + 6 expert layers, two
periods of sliding, sliding, sliding, full, so the first period straddles the
two stacks; window 16; 8 experts top-2 and one shared), seeded random weights.

The program against ``bench/references/afmoe.py`` (float32, precision
"highest", no cache, no kernels; it imports nothing from the program), logits
and logprobs, never tokens: the dense ``forward``; a prompt chunked over mixed
steps beside another row, then decode through the paged cache, at contexts of
2-14 windows; the mixed step against the bucketed path; the router against a
hand computation; the window and expert counters by hand; the controls (the
reference with one mechanism changed has to read NOT correct by the
benchmark's own limits); the refusals.
"""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import mixtral
from inference_gateway_tpu.ops.moe import router_sigmoid
from inference_gateway_tpu.ops.quant import QTensor
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow

BENCH = Path(__file__).resolve().parent.parent / "bench"
PRESET = "trinity-test-tiny"
CFG = mixtral.PRESETS[PRESET]
WINDOW = CFG.sliding_window
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "head_dim": 16, "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 8, "num_experts_per_tok": 2,
    "num_dense_layers": 2, "num_shared_experts": 1, "sliding_window": WINDOW, "layer_types": KINDS * 2,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 1.414, "mup_enabled": True,
    "n_group": 1, "topk_group": 1}
# The reference with ONE mechanism changed: what a program that lacked the
# mechanism, or had it wrong, would compute (ISSUE 41's controls a-e).
CONTROLS = {
    "a_window_dropped": {"sliding_window": 10 ** 9},
    "b_gate_dropped": {"assumed_mechanisms": {"attention_output_gate": False}},
    "c_bias_dropped_from_selection": {"assumed_mechanisms": {"selection_bias": "none"}},
    "d_shared_expert_dropped": {"num_shared_experts": 0},
    "e_rotary_in_global_layers": {"assumed_mechanisms": {"global_layers_rope": True}},
}


def load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load(BENCH / "references" / "afmoe.py", "bench_reference_afmoe")


def f32(w):
    return w.q.astype(jnp.float32) * w.scale if isinstance(w, QTensor) else w.astype(jnp.float32)


def reference_logits(params, ids, model=MODEL):
    """The reference over ``ids`` with the program's weights as data, a layer's
    leaves sliced as the benchmark's child slices them: EVERY declared leaf at
    the index asked for, clamped to the leaf's own leading axis."""
    def layer(i):
        return {n: f32(jax.tree.map(lambda a: a[min(i, a.shape[0] - 1)], params["layers"][n])) for n in REF.LEAVES}

    return REF.forward(ids, params["embed"], layer, f32(params["final_norm"]), f32(params["lm_head"]),
                       REF.sizes(model))[:]


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def engine(dtype="float32", quantize=None, **kw):
    kw = {"page_size": 8, "max_slots": 4, "max_seq_len": 256, "mixed_step": True, "mixed_step_tokens": 40,
          "decode_chunk": 4, "use_mesh": False, **kw}
    return Engine(EngineConfig(model=PRESET, attention="paged", dtype=dtype, quantize=quantize, **kw))


def test_presets_follow_the_source():
    stage = mixtral.PRESETS["trinity-mini-stage8"]
    for cfg in (stage, CFG):
        assert (cfg.num_layers, cfg.num_dense_layers, cfg.expert_layers, cfg.shared_experts) == (8, 2, 6, 1)
        assert cfg.layer_window == cfg.layer_rope == (1, 1, 1, 0, 1, 1, 1, 0)
        assert cfg.qk_norm and cfg.attn_gate and cfg.sandwich_norm and cfg.embed_scale
        assert (cfg.router_score, cfg.router_input, cfg.hidden_act) == ("sigmoid", "ffn", "silu")
        assert cfg.rms_norm_eps == 1e-5 and not cfg.tie_word_embeddings and not cfg.qkv_bias
    assert (stage.route_scale, CFG.route_scale) == (2.826, 1.414)       # sqrt(top-k) at either size
    assert (stage.hidden_size, stage.num_heads, stage.num_kv_heads, stage.hd) == (2048, 32, 4, 128)
    assert (stage.num_experts, stage.experts_per_token, stage.intermediate_size) == (128, 8, 1024)
    assert (stage.dense_intermediate_size, stage.sliding_window) == (6144, 2048)
    assert (stage.vocab_size, stage.rope_theta, stage.max_position_embeddings) == (200192, 1e4, 131072)


def test_the_two_stacks_share_one_layer_index():
    """The expert layers under the mixtures' names (leading axis 6), the dense
    prefix under ``dense_*`` names (leading axis 2), all in ``params["layers"]``:
    every leaf the reference declares is there, and no other."""
    layers = mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)["layers"]
    assert set(layers) == set(REF.LEAVES)
    for name, leaf in layers.items():
        assert leaf.shape[0] == (2 if name.startswith(mixtral.PREFIX) else 6), name
    assert layers["dense_wg"].shape == (2, 64, 96) and layers["wg"].shape == (6, 8, 64, 32)
    assert layers["attn_gate"].shape == (6, 64, 64) and layers["router_bias"].shape == (6, 8)


def test_dense_forward_matches_the_reference():
    """``mixtral.forward`` (no cache, masked attention) against the reference on
    seeded float32 weights over 60 tokens, 3.75 windows: both compute in float32
    and differ in the order of their sums over eight layers of width 64;
    measured 1.7e-6 on logits of spread 1.0, where the weakest control
    (below) moves them by 1e-2 and more."""
    params = mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    ids = prompt(60, 0)
    with jax.default_matmul_precision("highest"):
        got, _ = mixtral.forward(params, CFG, jnp.asarray([ids]), jnp.arange(60)[None, :], jnp.asarray([60]))
    want = np.asarray(reference_logits(params, ids))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=2e-5, rtol=0)
    assert want.std() > 0.5   # not a comparison of zeros


# |served - reference| of a token's logprob, in nats. float32 reads 1e-6
# (summation order alone): those cases hold every mechanism exactly. bfloat16 /
# int8: the served side rounds activations, the stream and the cached keys to
# bf16 while the reference keeps float32 on the same (dequantised) weights:
# measured over six pairs of prompts a largest of 0.16-0.31, a mean of
# 0.047-0.081 and a median of 0.036-0.069, twice what the chip reads at the
# published widths (PERF.md section 6): a router score within bf16's rounding
# of the cut swaps one of a token's TWO experts here, a quarter of the
# sublayer's normed output (an eighth at the published top-8 of 128). Those
# cases are held to the benchmark's own limits, as its child holds them.
CASES = [("float32", None), ("bfloat16", None), ("bfloat16", "int8")]


def gaps(eng, seq, start, served, model=MODEL):
    """|served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1], model), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return np.abs(want - np.asarray(served, np.float32))


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


def serve_two_rows(eng):
    """Slot 0 takes a 40-token prompt (2.5 windows) and decodes; slot 1's
    225-token prompt (14 windows) then goes in over seven mixed steps with slot
    0's decode row beside every chunk; both rows then decode two chunks through
    the paged cache. Returns [(sequence, where the served tokens start, their
    logprobs)] for both rows."""
    a, b = prompt(40, 3), prompt(225, 4)
    tok, lp, _ = admit(eng, 0, a)
    row_a = [tok, 40, [tok], [lp]]
    tok_b, lp_b, stats = admit(eng, 1, b, beside={0: row_a})
    assert len(stats) == 7
    toks, lps, _ = decode(eng, {0: (row_a[0], row_a[1]), 1: (tok_b, 225)}, 2)
    return [(a + row_a[2] + toks[0], 40, row_a[3] + lps[0]), (b + [tok_b] + toks[1], 225, [lp_b] + lps[1])]


def benchmark_limits():
    sys.path.insert(0, str(BENCH))
    import reference

    return reference.TOLERANCE_MAX_NATS, reference.TOLERANCE_MEAN_NATS


@pytest.mark.parametrize("dtype,quantize", CASES)
def test_chunked_prompt_then_decode_past_the_window_matches_the_reference(dtype, quantize):
    eng = engine(dtype, quantize, max_seq_len=512)
    assert eng.mixed_ok and "context_pages" in eng.family.mixed_stats and eng.mesh is None
    diffs = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in serve_two_rows(eng)])
    assert len(diffs) == 25   # row 0: 1 + 7 beside the chunks + 8; row 1: 1 + 8
    if dtype == "float32":
        assert diffs.max() < 2e-5
    else:
        largest, mean = benchmark_limits()
        assert diffs.max() <= largest and diffs.mean() <= mean, (diffs.max(), diffs.mean())


@pytest.mark.parametrize("control", list(CONTROLS))
def test_controls_read_not_correct(control):
    """The comparison that decides the benchmark's ``correct`` has to SEE each
    mechanism: the rows served above (int8 weights, bf16, the mixed step and
    the decode chunks) against the reference with the mechanism changed read NOT
    correct by the benchmark's rule (the largest difference past 0.45 or the
    mean past 0.12), where the sound reference reads inside both (the test
    above), and their mean is more than one and a half times the sound one's on
    the same rows. Measured over six pairs of prompts, largest / mean in nats,
    sound 0.16-0.31 / 0.047-0.081: (a) the window dropped 6.7-9.3 / 3.5-4.3,
    (b) the gate dropped 4.8-8.6 / 2.2-3.1, (c) the bias dropped from selection
    0.24-1.34 / 0.10-0.21 (the thinnest: it changes one expert of two in a third
    of the tokens, and one pair in six read inside the limits: the float32
    test below holds it exactly), (d) the shared expert dropped 3.8-8.2 /
    1.9-2.6, (e) rotary in global layers 2.4-3.4 / 0.65-1.2. PERF.md section 6
    has the chip's, where (c) reads outside the limits in every run."""
    eng = engine("bfloat16", "int8", max_seq_len=512)
    rows = serve_two_rows(eng)
    sound = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in rows])
    diffs = np.concatenate([gaps(eng, seq, start, served, {**MODEL, **CONTROLS[control]})
                            for seq, start, served in rows])
    largest, mean = benchmark_limits()
    assert diffs.max() > largest or diffs.mean() > mean, (control, diffs.max(), diffs.mean())
    assert diffs.mean() > 1.5 * sound.mean(), (control, diffs.mean(), sound.mean())


@pytest.mark.parametrize("how", ["none", "weights"])
def test_the_bias_decides_who_is_chosen_and_never_what_a_chosen_expert_weighs(how):
    """Control (c) in float32, where nothing else moves: a reference that drops
    the bias from the selection, or that weighs the chosen by score + bias, reads
    thousands of times the float32 tolerance from the served logprobs, so a
    program that did either would fail the float32 case above. (Added to the
    weights it is too small for the benchmark's bf16 limits, which is why the
    control of record drops it from the selection.)"""
    eng = engine()
    b = prompt(100, 4)
    tok, lp, _ = admit(eng, 1, b)
    toks, lps, _ = decode(eng, {1: (tok, 100)}, 1)
    model = {**MODEL, "assumed_mechanisms": {"selection_bias": how}}
    assert gaps(eng, b + [tok] + toks[1], 100, [lp] + lps[1], model).max() > 100 * 2e-5


def test_the_mixed_step_matches_the_bucketed_path():
    """The same prompt through the ragged mixed step (chunks of 36) and through
    the bucketed paged prefill, then a decode chunk each: the same logprobs to
    float32's summation order, past the window (100 tokens)."""
    outs = []
    for mixed in (True, False):
        eng = engine(mixed_step=mixed, prefill_buckets=(128,))
        assert eng.mixed_ok is mixed
        ids = prompt(100, 7)
        if mixed:
            tok, lp, _ = admit(eng, 0, ids)
        else:
            r = eng.prefill([ids], [0], [0.0], [1.0])[0]
            tok, lp = r.first_token, r.logprob
        toks, lps, _ = decode(eng, {0: (tok, 100)}, 1)
        outs.append(([tok] + toks[0], [lp] + lps[0]))
    assert outs[0][0] == outs[1][0]
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=2e-5, rtol=0)


def test_the_router_against_a_hand_computation():
    """Sigmoid scores; the chosen are the largest of score + bias; the weights
    are the chosen scores WITHOUT the bias, normalised, times the scale. A bias
    that lifts the fourth-best expert over the second changes the chosen set and
    no weight's formula."""
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.5, 0.4, -3.0, 0.3]])
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    w, idx = router_sigmoid(logits, 2, bias=jnp.zeros((4,)), scale=2.826)
    assert np.asarray(idx).tolist() == [[0, 1], [0, 1]]
    np.testing.assert_allclose(np.asarray(w)[0], 2.826 * s[0, :2] / s[0, :2].sum(), rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])
    w, idx = router_sigmoid(logits, 2, bias=bias, scale=2.826)
    assert np.asarray(idx).tolist() == [[0, 3], [3, 0]]          # expert 3 chosen now, first in row 1
    np.testing.assert_allclose(np.asarray(w)[0], 2.826 * s[0, [0, 3]] / s[0, [0, 3]].sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w)[1], 2.826 * s[1, [3, 0]] / s[1, [3, 0]].sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.826, rtol=1e-6)


def test_the_counters_are_the_counts_by_hand():
    """``window_pages_read`` / ``context_pages``: over the 6 windowed layers, a
    row whose queries see lengths first..last reads the pages from the one
    holding position first - window on and holds ceil(last / page) (pages of
    8, window 16); ``experts_read`` beside them on ``mixed`` and ``decode``
    stats alike."""
    eng = engine()
    _, _, stats = admit(eng, 0, prompt(20, 15))                      # one chunk, lengths 1..20: 3 pages, none skipped
    assert (stats[0]["window_pages_read"], stats[0]["context_pages"]) == (6 * 3, 6 * 3)
    tok, _, stats = admit(eng, 1, prompt(70, 16))                    # chunks 1..36 and 37..70
    assert [s["context_pages"] for s in stats] == [6 * 5, 6 * 9]
    assert [s["window_pages_read"] for s in stats] == [6 * 5, 6 * (9 - (37 - 16) // 8)]
    assert all(1.0 <= s["experts_read"] <= 8.0 and s["expert_tokens_max"] >= s["expert_tokens_mean"] for s in stats)
    _, _, stats = decode(eng, {1: (tok, 70)}, 1)                     # four steps at lengths 71..74: 9-10 pages held
    held = [-(-n // 8) for n in range(71, 75)]
    assert stats[0]["context_pages"] == 6 * sum(held)
    assert stats[0]["window_pages_read"] == 6 * sum(h - (n - 16) // 8 for h, n in zip(held, range(71, 75)))
    assert stats[0]["experts_read"] == 2.0                           # one row, top-2
    status = eng.family_status()
    assert status["cache_layers"] == {"window": 6, "full": 2}
    assert (status["router"]["score"], status["shared_experts"], status["dense_prefix"]) == ("sigmoid", 1, 2)
    assert status["router"]["chosen_by"] == "score + bias" and status["router"]["route_scale"] == 1.414


def test_other_mixtures_status_and_counters():
    """SmallThinker (windowed layers, softmax router) carries the window counters
    too; Mixtral (no window) does not, and neither has a prefix or a shared expert."""
    small = Engine(EngineConfig(model="smallthinker-test-tiny", attention="paged", dtype="float32", page_size=8,
                                max_slots=2, max_seq_len=64, mixed_step=True, use_mesh=False))
    assert "context_pages" in small.family.mixed_stats and small.family_status()["cache_layers"] == {"window": 3, "full": 1}
    assert small.family_status()["router"]["score"] == "softmax"
    plain = Engine(EngineConfig(model="mixtral-test-tiny", attention="paged", dtype="float32", page_size=8,
                                max_slots=2, max_seq_len=64, mixed_step=True, use_mesh=False))
    assert "context_pages" not in plain.family.mixed_stats and "cache_layers" not in plain.family_status()
    assert (plain.family_status()["shared_experts"], plain.family_status()["dense_prefix"]) == (0, 0)


def test_a_prefix_hit_adopts_pages_of_both_stacks():
    """Two prompts share their first 64 tokens (8 full pages). The second adopts
    the first's pages (one page id names all 8 layers' copies, dense and expert
    alike), prefills its tail alone, and its first token's logprob is the
    reference's over the whole prompt, past the window."""
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
    assert gaps(eng, b + [int(t[1])], 84, [float(lp[1])]).max() < 2e-5


@pytest.mark.parametrize("kw,what", [
    ({"spec_draft": "ngram"}, "speculative"),
    ({"vision_model": "vit-test-tiny"}, "image input"),
    ({"quantize": "int4"}, "int4"),
    ({"mesh_shape": {"tp": 2}, "use_mesh": True}, "mesh"),
])
def test_refusals_by_name(kw, what):
    with pytest.raises(ValueError, match=what) as e:
        engine(**kw)
    assert PRESET in str(e.value)


def test_the_dense_cache_engine_holds_the_tree_the_child_reads():
    """The benchmark's reference child asks for the tree under a one-row dense
    cache: it builds, holds every leaf the reference declares, and serves."""
    eng = Engine(EngineConfig(model=PRESET, attention="dense", max_slots=1, max_seq_len=16, dtype="float32",
                              use_mesh=False))
    assert set(REF.LEAVES) == set(eng.params["layers"])
    r = eng.prefill([[1, 2, 3]], [0], [0.0], [1.0])[0]
    assert 0 <= r.first_token < CFG.vocab_size


def test_layerwise_quantised_build_matches_the_trees_shape():
    """The build a published-size tree takes (a layer's block at a time) against
    quantise-after-init: the same leaves, shapes and dtypes, both stacks'."""
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params, quantize_llama_params

    a = init_quantized_llama_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16)
    b = quantize_llama_params(mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(a)] == [(x.shape, x.dtype) for x in jax.tree.leaves(b)]
    for name in ("attn_gate", "shared_wg", "shared_wd", "dense_wq", "dense_attn_gate", "dense_wg", "wg"):
        assert isinstance(a["layers"][name], QTensor), name
    for name in ("router", "router_bias", "post_attn_norm", "dense_post_ffn_norm", "q_norm"):
        assert not isinstance(a["layers"][name], QTensor), name


def test_the_seeded_build_makes_each_mechanism_show():
    """Per fan-in where a scale stands in front of a nonlinearity (ops/quant.
    _sandwich_std), in both builds; every other model keeps the scales it had."""
    from inference_gateway_tpu.models.llama import PRESETS as LLAMA
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params, seeded_qk_gain, seeded_std

    want = {"embed": 0.125, "lm_head": 0.25, "attn_gate": 0.25, "router": 0.125, "router_bias": 0.04,
            "shared_wd": 0.08, "shared_wg": 0.02, "wd": 0.02}
    assert {leaf: seeded_std(CFG, leaf) for leaf in want} == want and seeded_qk_gain(CFG) == 1.4
    stage = mixtral.PRESETS["trinity-mini-stage8"]
    assert (seeded_std(stage, "embed"), seeded_std(stage, "attn_gate")) == (2048 ** -0.5, 2 * 2048 ** -0.5)
    for build in (mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32),
                  init_quantized_llama_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16)):
        spread = lambda leaf: float(np.asarray(f32(leaf)).std())
        assert abs(spread(build["embed"]) - 0.125) < 0.01 and abs(spread(build["lm_head"]) - 0.25) < 0.015
        assert abs(spread(build["layers"]["shared_wd"]) - 0.08) < 0.01 and abs(spread(build["layers"]["wd"]) - 0.02) < 0.005
        assert np.allclose(np.asarray(build["layers"]["q_norm"], np.float32), 1.4, atol=0.01)
        assert np.allclose(np.asarray(build["layers"]["dense_k_norm"], np.float32), 1.4, atol=0.01)
        assert abs(spread(build["layers"]["attn_gate"]) - 0.25) < 0.02
        assert abs(spread(build["layers"]["dense_attn_gate"]) - 0.25) < 0.02
        assert abs(spread(build["layers"]["router"]) - 0.125) < 0.01
        assert 0.02 < spread(build["layers"]["router_bias"]) < 0.06
    for other in (mixtral.PRESETS["smallthinker-test-tiny"], mixtral.PRESETS["mixtral-test-tiny"],
                  mixtral.PRESETS["keye-test-tiny"]):
        assert (seeded_std(other, "embed"), seeded_std(other, "router"), seeded_std(other, "lm_head")) == (1.0, 0.06, 0.02)
    assert (seeded_std(LLAMA["test-tiny"], "embed"), seeded_std(LLAMA["test-tiny"], "lm_head")) == (0.02, 0.02)


PARENT_SUMS = {"mixtral-test-tiny": 16454.17, "smallthinker-test-tiny": 17859.04, "keye-test-tiny": 18009.53}


def test_the_older_mixtures_seeded_values_are_the_ones_they_had():
    """``init_params`` draws the older leaves from the keys it always drew them
    from: a fixed fingerprint of each older tiny preset's tree (sums taken on the
    parent commit)."""
    sums = {}
    for name in ("mixtral-test-tiny", "smallthinker-test-tiny", "keye-test-tiny"):
        params = mixtral.init_params(jax.random.PRNGKey(0), mixtral.PRESETS[name], dtype=jnp.float32)
        sums[name] = round(float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(params))), 2)
    assert sums == PARENT_SUMS, sums

