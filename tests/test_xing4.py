"""Xing4.0's block on the normal path (ISSUE 47), at the tiny preset on the CPU:
2 dense + 2 expert layers at toy widths, a latent attention (latent 32, rotary
key 8: a pool row of 40 values in 128 lanes) under YaRN, four residual streams
mixed by hyper-connections, 8 experts top-2 beside a shared expert, seeded
random weights.

The program against ``bench/references/mhc_mla_moe.py`` (float32, precision
"highest", no cache, no absorption, k_nope and v materialised a head; it
imports nothing from the program), logits and logprobs, never tokens: a whole
prompt through one mixed step; a prompt chunked over mixed steps beside another
row's decode rows, then decode through the paged latent pool; the kernel
(interpreted) against its twin; a prompt adopted from the prefix cache; the
hyper-connection maps; YaRN by hand; the pool's bytes; the counters; the
controls; the refusals.
"""

import functools
import importlib.util
import math
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import llama, mixtral
from inference_gateway_tpu.ops import hyper_connections as hc
from inference_gateway_tpu.ops import mla_attention
from inference_gateway_tpu.ops.quant import QTensor, quantize_tensor
from inference_gateway_tpu.ops.rope import rope_inv_freq, yarn_mscale
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow

BENCH = Path(__file__).resolve().parent.parent / "bench"
PRESET = "xing4-test-tiny"
CFG = mixtral.PRESETS[PRESET]
YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1}
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "first_k_dense_replace": 2, "n_shared_experts": 1, "routed_scaling_factor": 2.0,
    "kv_lora_rank": 32, "q_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": YARN, "scoring_func": "sigmoid", "norm_topk_prob": True, "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1}
# The reference with ONE mechanism changed: what a program that lacked it would compute.
CONTROLS = {"a_residual_mixing_a_plain_sum": {"residual_mixing": False},
            "b_rope_part_of_the_scores_dropped": {"rope_scores": False}}


def load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load(BENCH / "references" / "mhc_mla_moe.py", "bench_reference_mhc_mla_moe")


def f32(w):
    return w.q.astype(jnp.float32) * w.scale if isinstance(w, QTensor) else w.astype(jnp.float32)


def reference_logits(params, ids, model=MODEL):
    """The reference over ``ids`` with the program's weights as data, a layer's
    leaves sliced as the benchmark's child slices them (clamped to a stack's end)."""
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


def gaps(eng, seq, start, served, model=MODEL):
    """|served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1], model), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return np.abs(want - np.asarray(served, np.float32))


def decode(eng, rows, chunks):
    """Greedy decode of ``rows`` {slot: (token, position)}; ({slot: tokens}, {slot: logprobs}, stats)."""
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


def admit(eng, slot, ids, beside=None, chunk=36, start=0):
    """``ids[start:]`` into ``slot`` through mixed steps of ``chunk`` prompt
    tokens; the rows of ``beside`` {slot: [token, position, tokens, logprobs]}
    decode one token in each. Returns (first token, its logprob, stats)."""
    done, stats, beside = start, [], beside or {}
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
    """Slot 0 takes a 40-token prompt and decodes; slot 1's 150-token prompt
    then goes in over five mixed steps with slot 0's decode row beside every
    chunk; both rows then decode two chunks through the latent pool."""
    a, b = prompt(40, 3), prompt(150, 4)
    tok, lp, _ = admit(eng, 0, a, chunk=17)
    row_a = [tok, 40, [tok], [lp]]
    tok_b, lp_b, stats = admit(eng, 1, b, beside={0: row_a})
    assert len(stats) == 5
    toks, lps, _ = decode(eng, {0: (row_a[0], row_a[1]), 1: (tok_b, 150)}, 2)
    return [(a + row_a[2] + toks[0], 40, row_a[3] + lps[0]), (b + [tok_b] + toks[1], 150, [lp_b] + lps[1])]


def benchmark_limits():
    sys.path.insert(0, str(BENCH))
    import reference

    return reference.TOLERANCE_MAX_NATS, reference.TOLERANCE_MEAN_NATS


def test_presets_follow_the_source():
    whole = mixtral.PRESETS["xing4.0-stage10"]
    assert (whole.num_layers, whole.num_dense_layers, whole.expert_layers) == (10, 2, 8)
    assert (whole.hidden_size, whole.num_heads, whole.q_lora_rank, whole.kv_lora_rank) == (3584, 32, 768, 512)
    assert (whole.qk_nope_head_dim, whole.qk_rope_head_dim, whole.v_head_dim, whole.hd) == (128, 64, 128, 192)
    assert (whole.num_experts, whole.experts_per_token, whole.intermediate_size, whole.dense_intermediate_size,
            whole.shared_experts, whole.route_scale, whole.vocab_size) == (64, 4, 1024, 9216, 1, 2.0, 131072)
    assert (whole.hc_mult, whole.hc_sinkhorn_iters, whole.hc_eps, whole.hc_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (whole.latent_width, whole.latent_row, CFG.latent_width, CFG.latent_row) == (576, 640, 40, 128)
    for cfg in (whole, CFG):
        assert cfg.router_score == "sigmoid" and not cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-6


def test_yarn_against_hand_arithmetic():
    """The published scaling over the 64 rotary dims: pairs under correction dim
    10 keep theta^(-2j/64), pairs from 23 on have it over 64, a linear ramp
    between; m = 0.1 ln 64 + 1; and no scaling is the plain frequencies."""
    whole = mixtral.PRESETS["xing4.0-stage10"]
    inv = np.asarray(rope_inv_freq(64, 10000.0, whole.rope_scaling_dict))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    lo = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000.0)))
    hi = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000.0)))
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    ramp = (15 - lo) / (hi - lo)
    np.testing.assert_allclose(inv[15], plain[15] / 64 * ramp + plain[15] * (1 - ramp), rtol=1e-6)
    assert abs(yarn_mscale(whole.rope_scaling_dict) - 1.4158883) < 1e-6 and yarn_mscale(None) == 1.0
    assert abs(mixtral.mla_scale(whole) - 192 ** -0.5 * 1.4158883 ** 2) < 1e-6
    np.testing.assert_allclose(np.asarray(rope_inv_freq(64, 10000.0)), plain, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(REF.yarn_inv_freq(64, 10000.0, whole.rope_scaling_dict)), inv, rtol=1e-6)


def test_the_pool_is_one_array_of_latent_rows():
    from inference_gateway_tpu.serving.profiles import kv_bytes_per_token

    eng = engine()
    pages = eng.allocator.num_pages
    assert {n: a.shape for n, a in eng.cache.items()} == {"kv": (4, pages, 8, 128)}
    whole = mixtral.PRESETS["xing4.0-stage10"]
    # 576 values a token a layer, stored and moved in 640 lanes: both are said.
    assert kv_bytes_per_token(whole) == 10 * 640 * 2 and kv_bytes_per_token(CFG) == 4 * 128 * 2
    status = eng.family_status()["latent_pool"]
    assert (status["row_values"], status["row_lanes"], status["arrays"], status["residual_streams"]) == (40, 128, ["kv"], 4)
    assert status["bytes_per_token"] == 4 * 128 * 4     # this engine's pool is float32
    assert eng.ragged_attention_plan["one_query_rows"] == "mla_decode_attn_kernel"
    assert eng.mixed_ok and eng.is_mla and eng.mesh is None and "context_pages" in eng.family.mixed_stats


def test_a_whole_prompt_in_one_step_matches_the_reference():
    eng = engine(mixed_step_tokens=80)
    ids = prompt(60, 1)
    h = eng.mixed_step_submit([MixedRow(slot=2, token_ids=ids, start=0, kind="prefill")])
    tok, lp = eng.mixed_step_fetch(h)
    logp = jax.nn.log_softmax(reference_logits(eng.params, ids), axis=-1)[-1]
    assert int(jnp.argmax(logp)) == int(tok[2]) and abs(float(logp[int(tok[2])]) - float(lp[2])) < 5e-5


@pytest.mark.parametrize("dtype,quantize", [("float32", None), ("bfloat16", None), ("bfloat16", "int8")])
def test_chunked_prompt_beside_decode_rows_then_decode_matches_the_reference(dtype, quantize):
    eng = engine(dtype, quantize)
    diffs = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in serve_two_rows(eng)])
    assert len(diffs) == 23   # row 0: 1 + 5 beside the chunks + 8; row 1: 1 + 8
    if dtype == "float32":
        assert diffs.max() < 5e-5
    else:
        largest, mean = benchmark_limits()
        assert diffs.max() <= largest and diffs.mean() <= mean, (diffs.max(), diffs.mean())


@pytest.mark.parametrize("control", list(CONTROLS))
def test_controls_read_not_correct(control):
    """The comparison deciding ``correct`` SEES each new mechanism: the float32
    program's logprobs against the reference with it changed pass the limits."""
    eng = engine()
    model = {**MODEL, "assumed_mechanisms": CONTROLS[control]}
    diffs = np.concatenate([gaps(eng, seq, start, served, model) for seq, start, served in serve_two_rows(eng)])
    largest, mean = benchmark_limits()
    assert diffs.max() > largest and diffs.mean() > mean, (control, diffs.max(), diffs.mean())


def test_the_kernel_against_its_twin(monkeypatch):
    """Both launches interpreted, on a pool of this preset's rows: one-query
    rows in the packed prefix, a chunk of two tiles, an empty slot, rows packed
    in another order than their slots'."""
    rng = np.random.default_rng(5)
    H, ps, P = 4, 8, 64
    pages = jnp.asarray(rng.normal(size=(P, ps, 128)), jnp.float32).at[:, :, 40:].set(0)
    table = jnp.asarray(rng.permutation(P)[:60].reshape(5, 12), jnp.int32)
    q = jnp.asarray(rng.normal(size=(64, H, 40)), jnp.float32)
    q_starts, q_lens = jnp.asarray([31, 32, 0, 30, 0], jnp.int32), jnp.asarray([1, 20, 0, 1, 30], jnp.int32)
    kv_lens = jnp.asarray([17, 20, 0, 90, 75], jnp.int32)
    want = mla_attention.mla_ragged_attention(q, pages, table, q_starts, q_lens, kv_lens, value_dim=32, scale=0.3)
    for visit in (1, 4):
        got = mla_attention.mla_ragged_attention_tpu(q, pages, table, q_starts, q_lens, kv_lens, value_dim=32,
                                                     scale=0.3, interpret=True, pages_per_visit=visit,
                                                     row_pages_per_visit=2)
        assert got.shape == (64, H, 32) and float(jnp.max(jnp.abs(got - want))) < 1e-5
    lengths = jnp.asarray([17, 0, 8, 90, 1], jnp.int32)
    want = mla_attention.mla_paged_attention(q[:5], pages, table, lengths, value_dim=32, scale=0.3)
    got = mla_attention.mla_paged_attention_tpu(q[:5], pages, table, lengths, value_dim=32, scale=0.3, interpret=True)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5 and float(jnp.max(jnp.abs(got[1]))) == 0.0


def test_absorbed_against_materialised_attention():
    """The absorbed read of the pool's rows (q_nope through W_UK, the weighted
    latents through W_UV) against keys and values materialised a head."""
    eng = engine()
    lp = jax.tree.map(lambda a: a[0], mixtral.prefix_leaves(eng.params["layers"]))
    h = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)), jnp.float32)
    pos = jnp.arange(12, dtype=jnp.int32)
    q, row, w_uv = mixtral._latent_project(h, lp, pos, CFG)
    assert q.shape == (12, 4, 40) and row.shape == (12, 128) and float(jnp.abs(row[:, 40:]).max()) == 0.0
    pages = jnp.zeros((2, 8, 128), jnp.float32).reshape(16, 128).at[:12].set(row).reshape(2, 8, 128)
    lat = mla_attention.mla_ragged_attention(q, pages, jnp.asarray([[0, 1]], jnp.int32), jnp.asarray([0], jnp.int32),
                                             jnp.asarray([12], jnp.int32), jnp.asarray([12], jnp.int32),
                                             value_dim=32, scale=mixtral.mla_scale(CFG))
    absorbed = mixtral._latent_out(lat, w_uv, jnp.float32) @ lp["wo"]
    w = {n: f32(lp[n]) for n in ("wdq", "q_a_norm", "wuq", "wdkv", "kv_a_norm", "wukv", "wo")}
    with jax.default_matmul_precision("highest"):
        materialised = REF.latent_attention(h, w, pos, REF.sizes(MODEL))
    assert float(jnp.max(jnp.abs(absorbed - materialised))) < 2e-5


LATENT_LEAVES = ("wq", "wdq", "q_a_norm", "wuq", "wdkv", "kv_a_norm", "wukv", "wo")
ABSORB_CHECKS = ("definition_float32", "definition_bfloat16", "materialised", "lowering")


@functools.cache
def int8_wukv_layer(preset):
    """One latent layer's float32 leaves of a seeded tree of ``preset``, W_UKV
    alone rounded to int8 under its columns' scales."""
    layers = mixtral.init_params(jax.random.PRNGKey(3), mixtral.PRESETS[preset], jnp.float32)["layers"]
    lp = {n: layers[n][0] for n in LATENT_LEAVES if n in layers}
    return {**lp, "wukv": quantize_tensor(lp["wukv"])}


def absorbed_from_an_int8_wukv(preset, check, materialised):
    """ISSUE 59, a case a check (``ABSORB_CHECKS``); tests/test_kimi_linear.py
    and tests/test_openpangu.py run it on their presets. ``_latent_attention``
    with a ``QTensor`` W_UKV (12 tokens of one row through the twin's read)
    against (a) the parent's form kept HERE as the definition: W_UKV
    dequantised whole in the activations' dtype, cut into the heads' blocks, two
    einsums; the same mathematics, so float32 agrees to its own rounding and
    bfloat16 to a few of its steps (the definition rounds the scale and the
    scaled weight, the program rounds the scaled query); (b)
    ``materialised(h, w, positions)``, the file's float32 reference with k_nope
    and v made a head from the dequantised leaves, at the plain leaf's limit.
    And the lowering: no float value of W_UKV's size among the program's, where
    the definition's jaxpr holds the dequantised copy."""
    cfg, lp = mixtral.PRESETS[preset], int8_wukv_layer(preset)
    Hq, Dn, Dv, rank, page = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank, 8
    N, pos = 12, jnp.arange(12, dtype=jnp.int32)
    one = lambda v: jnp.asarray([v], jnp.int32)
    read = lambda q, pages, **kw: mla_attention.mla_ragged_attention(
        q, pages, jnp.asarray([[0, 1, 2]], jnp.int32), one(0), one(N), one(N), **kw)

    def program(h, lp):
        pool = jnp.zeros((3 * page, cfg.latent_row), h.dtype)
        return mixtral._latent_attention(h, lp, pos, pool, pos, page, cfg, read)[0]

    def definition(h, lp):
        dense = lp["wukv"].q.astype(h.dtype) * lp["wukv"].scale.astype(h.dtype)   # the whole matrix, as the parent wrote it
        q, row, plain = mixtral._latent_project(h, {**lp, "wukv": dense}, pos, cfg)   # a plain leaf: the einsum on the array as it is
        assert not isinstance(plain, QTensor)
        pool = jnp.zeros((3 * page, cfg.latent_row), h.dtype).at[pos].set(row)
        attn = read(q, pool.reshape(-1, page, cfg.latent_row), value_dim=rank, scale=mixtral.mla_scale(cfg))
        out = jnp.einsum("nhc,chd->nhd", attn, dense.reshape(rank, Hq, Dn + Dv)[..., Dn:], preferred_element_type=jnp.float32)
        return out.reshape(N, -1).astype(h.dtype)

    h = jnp.asarray(np.random.default_rng(2).normal(size=(N, cfg.hidden_size)), jnp.float32)
    if check == "materialised":
        with jax.default_matmul_precision("highest"):
            want = materialised(h, {n: f32(a) for n, a in lp.items()}, pos)
        assert float(jnp.max(jnp.abs(program(h, lp) @ lp["wo"] - want))) < 2e-5
        return
    dtype = jnp.float32 if check == "definition_float32" else jnp.bfloat16
    h, lp = h.astype(dtype), {n: a if isinstance(a, QTensor) else a.astype(dtype) for n, a in lp.items() if n != "wo"}
    if check == "lowering":
        sized = lambda fn: [(d, shape) for d, shape in re.findall(r"\b(f32|bf16|f16)\[([\d,]+)\]", str(jax.make_jaxpr(fn)(h, lp)))
                            if math.prod(map(int, shape.split(","))) == rank * Hq * (Dn + Dv)]
        assert sized(definition) and not sized(program), sized(program)
        return
    got, want = (np.asarray(fn(h, lp), np.float32) for fn in (program, definition))
    steps = 2.0 ** -23 if dtype == jnp.float32 else 2.0 ** -8
    assert np.abs(want).max() > 0.1 and np.abs(got - want).max() < 8 * steps * np.abs(want).max(), np.abs(got - want).max()


@pytest.mark.parametrize("check", ABSORB_CHECKS)
def test_absorbed_from_an_int8_wukv_against_its_definitions(check):
    absorbed_from_an_int8_wukv(PRESET, check, lambda h, w, pos: REF.latent_attention(h, w, pos, REF.sizes(MODEL)))


def test_a_prompt_adopted_from_the_prefix_cache_answers_identically():
    """The latent pool's pages are handed over as any pages are: the second
    request adopts the first's and computes only its tail, to the same logprob."""
    eng = engine(prefix_cache=True)
    ids = prompt(70, 9)
    tok, lp, _ = admit(eng, 0, ids)
    eng.prefix_cache.insert(ids, eng.allocator.pages_of(0))
    pages, matched = eng.prefix_cache.match(ids)
    assert matched == 64 and len(pages) == 8
    eng.allocator.adopt_pages(1, pages)
    tok2, lp2, stats = admit(eng, 1, ids, start=matched)
    assert len(stats) == 1 and (tok2, lp2) == (tok, lp)


def test_the_hyper_connection_maps():
    """H_res is doubly stochastic and far from both the identity and 1/n at the
    seeded leaves; with one stream and every map fixed at 1 the seam is the
    plain residual of the shared skeleton."""
    eng = engine()
    lp = jax.tree.map(lambda a: a[1], {n: eng.params["layers"][n] for n in eng.params["layers"] if n.startswith("hc_")})
    X = jnp.asarray(np.random.default_rng(7).normal(size=(50, 4, 64)), jnp.float32)
    pre, post, res = hc.maps(jnp.moveaxis(X, 1, 0), lp["hc_attn_phi"], lp["hc_attn_alpha"], lp["hc_attn_bias"], iters=20, eps=1e-6,
                             clamp=(-30.0, 30.0), norm_eps=1e-6)
    assert pre.shape == (4, 50) and post.shape == (4, 50) and res.shape == (4, 4, 50)
    assert float(jnp.abs(res.sum(0) - 1).max()) < 1e-3 and float(jnp.abs(res.sum(1) - 1).max()) < 1e-3
    assert float(jnp.abs(res - 0.25).mean()) > 0.08 and float(jnp.abs(res - jnp.eye(4)[:, :, None]).mean()) > 0.2
    assert 0 < float(pre.min()) and float(pre.max()) < 1 and float(post.max()) < 2
    ref_pre, ref_post, ref_res = REF.hyper_maps(X, f32(lp["hc_attn_phi"]), f32(lp["hc_attn_alpha"]),
                                                f32(lp["hc_attn_bias"]), REF.sizes(MODEL))
    np.testing.assert_allclose(np.asarray(res), np.moveaxis(np.asarray(ref_res), 0, -1), atol=2e-6)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(ref_pre).T, atol=2e-6)
    # One stream, the maps at 1: read is the stream, write is x + y.
    x, y = X[None, :, 0], X[:, 1]
    ones = jnp.ones((1, 50))
    assert float(jnp.abs(hc.read(x, ones) - x[0]).max()) == 0.0
    assert float(jnp.abs(hc.write(x, y, ones, ones[None]) - (x + y[None])).max()) < 1e-6
    # the kernel the chip runs (interpreted here) against the plain iterations
    logits = jnp.asarray(np.random.default_rng(8).normal(size=(16, 70)) * 1.4, jnp.float32)
    got = hc.sinkhorn_exp_tpu(logits, n=4, iters=20, eps=1e-6, interpret=True)
    assert float(jnp.abs(got - hc.sinkhorn(jnp.exp(logits).reshape(4, 4, 70), 20, 1e-6)).max()) < 1e-6
    assert llama._block_in(None, x, {}, "attn") == (x, None) and llama._block_out(None, 1.0, 2.0, None) == 3.0


def test_the_counters_are_the_counts_by_hand():
    """``latent_pages_read``: the pages the rows' tiles walked over the 4 layers
    (pages of 8; a tile is the whole chunk at this width); ``context_pages``
    what the rows hold; ``experts_read`` beside them on both kinds of record."""
    eng = engine()
    tok, _, stats = admit(eng, 1, prompt(70, 16))                    # chunks 1..36 and 37..70
    assert [(s["latent_pages_read"], s["context_pages"], s["latent_row_pages_read"]) for s in stats] == [
        (4 * 5, 4 * 5, 0), (4 * 9, 4 * 9, 0)]
    assert all(1.0 <= s["experts_read"] <= 8.0 and "window_pages_read" not in s for s in stats)
    _, _, stats = decode(eng, {1: (tok, 70)}, 1)                     # four steps at lengths 71..74
    held = sum(-(-n // 8) for n in range(71, 75))
    assert (stats[0]["context_pages"], stats[0]["latent_pages_read"], stats[0]["latent_row_pages_read"]) == (
        4 * held, 4 * held, 4 * held)
    assert stats[0]["experts_read"] == 2.0
    status = eng.family_status()
    assert (status["router"]["score"], status["shared_experts"], status["dense_prefix"]) == ("sigmoid", 1, 2)
    # Tiles of the published plan: a 512-token chunk at 12k of context walks 16 tiles.
    whole = mixtral.PRESETS["xing4.0-stage10"]
    first, last = jnp.asarray([12001, 8000]), jnp.asarray([12512, 8000])
    got = mixtral.latent_pages(whole, 128, first, last, 544)
    tiles = sum(-(-min(12000 + 32 * (c + 1), 12512) // 128) for c in range(16))
    assert [float(x) for x in got] == [10 * (tiles + 63), 10 * (98 + 63), 10 * 63]


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


def test_a_paged_engine_is_a_mixed_engine_and_the_dense_cache_has_no_path():
    eng = engine(mixed_step=False)
    assert eng.mixed_ok and eng.max_prompt_len() > 200
    with pytest.raises(NotImplementedError, match="mixed step"):
        mixtral.forward_paged(eng.params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None, :],
                              jnp.asarray([8]), eng.cache, jnp.arange(8)[None, :], jnp.zeros((1, 32), jnp.int32),
                              mode="prefill")
    # The benchmark's reference child asks for the tree under a one-row dense cache.
    dense = Engine(EngineConfig(model=PRESET, attention="dense", max_slots=1, max_seq_len=16, dtype="float32",
                                use_mesh=False))
    assert set(REF.LEAVES) == set(dense.params["layers"])
    with pytest.raises(NotImplementedError, match="paged"):
        mixtral.forward(dense.params, CFG, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None, :], jnp.asarray([4]))


def test_layerwise_quantised_build_matches_the_trees_shape():
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params, quantize_llama_params
    from inference_gateway_tpu.serving.profiles import mixtral_param_count

    a = init_quantized_llama_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16)
    b = quantize_llama_params(mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.bfloat16))
    assert jax.tree.structure(a) == jax.tree.structure(b)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(a)] == [(x.shape, x.dtype) for x in jax.tree.leaves(b)]
    for name in ("wdq", "wuq", "wdkv", "wukv", "wo", "dense_wukv", "dense_wg", "shared_wd", "wd"):
        assert isinstance(a["layers"][name], QTensor), name
    for name in ("hc_attn_phi", "dense_hc_ffn_phi", "hc_ffn_bias", "router", "router_bias", "q_a_norm", "kv_a_norm"):
        assert not isinstance(a["layers"][name], QTensor), name
    assert a["layers"]["hc_attn_phi"].dtype == jnp.float32 and a["layers"]["hc_attn_phi"].shape == (2, 256, 24)
    for name in (PRESET, "xing4.0-stage10", "trinity-test-tiny", "lfm2-test-tiny"):
        cfg = mixtral.PRESETS[name]
        shapes = jax.eval_shape(lambda cfg=cfg: mixtral.init_params(jax.random.PRNGKey(0), cfg))
        assert mixtral_param_count(cfg) == sum(x.size for x in jax.tree.leaves(shapes)), name
