"""Kimi-Linear's block (ISSUE 54, ``kimi_linear``): Kimi Delta Attention
(``ops/kda.py``: a delta rule with a decay a channel, in blocks, its matrix
state in the state store) beside a latent attention with no query bottleneck
and no rotary whose pool holds its layers alone, a dense layer in front, and a
chip's SHARE of the experts under the whole router. Seeded, on the CPU, at the
tiny preset: the chunk kernel (interpreted) and its twin against the token
recurrence; the served path (chunked prompts through mixed steps, then decode
chunks, through the paged engine) against the benchmark's plain reference
(``bench/references/kda_mla_moe.py``) on LOGPROBS; the share against the whole
layer; what the state and the pool rule out, refused by name."""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from inference_gateway_tpu.models import mixtral
from inference_gateway_tpu.ops import kda, ssd
from inference_gateway_tpu.ops.quant import QTensor
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow
from tests.test_lfm2 import admit, decode   # the same drivers: rows through mixed steps, then decode chunks
from tests.test_xing4 import ABSORB_CHECKS, absorbed_from_an_int8_wukv

BENCH = Path(__file__).resolve().parent.parent / "bench"
PRESET = "kimi-linear-test-tiny"
CFG = mixtral.PRESETS[PRESET]
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4, "routed_experts": 8,
    "num_experts_per_token": 2, "first_k_dense_replace": 1, "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_use_nope": True, "rope_scaling": None, "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
    "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu", "tie_word_embeddings": False,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8], "head_dim": 32,
                           "num_heads": 4, "short_conv_kernel_size": 4}}
# The reference with ONE mechanism changed: what a program that lacked the
# mechanism, or had it wrong, would compute (the benchmark's four controls;
# the state lost at every boundary of this engine's chunks of 36).
CONTROLS = {
    "a_delta_term_dropped": {"delta_rule": False},
    "b_one_decay_a_head": {"decay_per_channel": False},
    "c_state_lost_at_chunk_boundaries": {"state_restart_every": 36},
    "d_absent_experts_computed": {"absent_experts": "computed"},
}


def load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load(BENCH / "references" / "kda_mla_moe.py", "bench_reference_kda_mla_moe")


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


@pytest.fixture(scope="module")
def eng32():
    """One float32 engine for the tests that only admit and decode rows: a slot
    that is admitted again starts from zero by position, so they share it."""
    return engine()


def test_presets_follow_the_source():
    big = mixtral.PRESETS["kimi-linear-48b-a3b-stage8"]
    for cfg in (CFG, big):
        assert cfg.layer_conv == (3, 3, 3, 0, 3, 3, 3, 0) and cfg.kda and not cfg.mamba2 and cfg.scans_runs
        assert (cfg.conv_layers, cfg.cache_layers, cfg.num_dense_layers, cfg.expert_layers) == (6, 2, 1, 7)
        assert cfg.layer_rope == (0,) * 8 and cfg.q_lora_rank == 0 and cfg.router_score == "sigmoid"
        assert cfg.hd == cfg.qk_nope_head_dim + cfg.qk_rope_head_dim and cfg.conv_channels == 3 * cfg.kda_inner
        assert cfg.held_experts * 2 == cfg.num_experts and cfg.experts_first == 0 and not cfg.tie_word_embeddings
    assert (big.hidden_size, big.vocab_size, big.kda_heads, big.kda_head_dim, big.num_experts, big.held_experts,
            big.experts_per_token, big.intermediate_size, big.dense_intermediate_size, big.kv_lora_rank,
            big.latent_row, big.route_scale) == (2304, 163840, 32, 128, 256, 128, 8, 1024, 9216, 512, 640, 2.446)
    assert big.state_bytes_per_slot == 6 * (32 * 128 * 128 + 3 * 12288) * 4 == 6 * 2_244_608
    with pytest.raises(ValueError, match="kda_heads"):
        dataclasses.replace(CFG, kda_heads=0)
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(CFG, experts_held=6, experts_first=4)


def test_four_stacks_under_names_of_their_own():
    params = mixtral.init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    lead = {n: jax.tree.leaves(w)[0].shape[0] for n, w in params["layers"].items()}
    assert {n for n, c in lead.items() if c == 6} == set(mixtral.KDA_LEAVES)
    assert {n for n, c in lead.items() if c == 2} == {"wq", "wdkv", "kv_a_norm", "wukv", "wo"}
    assert {n for n, c in lead.items() if c == 1} == {"dense_" + n for n in ("attn_norm", "mlp_norm", "wg", "wu", "wd")}
    assert set(REF.LEAVES) == set(lead)
    assert params["layers"]["router"].shape == (7, 64, 8) and params["layers"]["wg"].shape == (7, 4, 64, 32)
    cache = mixtral.init_paged_cache(CFG, engine_page_cfg())
    assert {n: a.shape for n, a in cache.items()} == {"kv": (2, 8, 8, 128), "conv": (6, 2, 3, 384), "kda": (6, 2, 4, 32, 32)}


def engine_page_cfg():
    from inference_gateway_tpu.serving.kv_cache import PagedCacheConfig

    return PagedCacheConfig(8, 0, 2, 32)


# ---------------------------------------------------------------------------
# ops/kda.py against the token recurrence
# ---------------------------------------------------------------------------
LAYERS, SLOTS, HH, D, Q = 2, 5, 2, 32, 16   # blocks of 16 tokens, halved at 8, 4, 2 and 1


def operands(T, seed, strength, strong_from=0):
    """q, k normalised as the mixer does; log decays spread over two decades a
    head, times ``strength`` at the tokens ``strong_from`` and beyond of every
    run of 8 (a half of a block of 16) and times 1 in front of them: at 40 a
    channel forgets by e^-100 and more a token, and e^{-G} over a block
    overflows float32 many times over."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = (unit(rng.normal(size=(T, HH, D))) * D ** -0.5).astype(np.float32)
    k = unit(rng.normal(size=(T, HH, D))).astype(np.float32)
    v = rng.normal(size=(T, HH, D)).astype(np.float32)
    strong = np.where(np.arange(T) % 8 >= strong_from, strength, 1.0)[:, None, None]
    g = (-np.exp(rng.normal(size=(T, HH, D)) * 1.5 - 2.0) * strong).astype(np.float32)
    beta = (1 / (1 + np.exp(-rng.normal(size=(T, HH))))).astype(np.float32)
    return q, k, v, g, beta


def chunk_case(lens, starts, kernel, strength=1.0, T=64, seed=0, strong_from=0, block=Q):
    """Rows of ``lens`` tokens packed back to back in slot order, row r from
    position ``starts[r]``, through ``kda_chunk`` on entry 1 of a store of random
    states. Returns (blocks walked, the largest difference from the token
    recurrence over outputs and last states of the rows of more than one token,
    the visits the kernel's grid runs and the plan's bound, the first output
    block); asserts that every other slot and the other entry are untouched."""
    SLOTS = len(lens)
    q_lens, position = np.asarray(lens, np.int32), np.asarray(starts, np.int32)
    q_starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    ops = operands(T, seed, strength, strong_from)
    store = np.random.default_rng(seed + 1).normal(size=(LAYERS, SLOTS, HH, D, D)).astype(np.float32)
    meta, blocks = ssd.block_plan(jnp.asarray(q_starts), jnp.asarray(q_lens), jnp.asarray(position + q_lens), T, block)
    o, new = kda.kda_chunk(jnp.asarray(store), 1, *map(jnp.asarray, ops), meta, Q=block, kernel=kernel)
    worst = 0.0
    for r in range(SLOTS):
        if q_lens[r] <= 1:
            assert np.array_equal(np.asarray(new[1, r]), store[1, r])
            continue
        first = np.zeros((HH, D, D), np.float32) if position[r] == 0 else store[1, r]    # zero by position
        at = slice(q_starts[r], q_starts[r] + q_lens[r])
        want_o, want_s = kda.recurrence(jnp.asarray(first), *(jnp.asarray(a[at]) for a in ops))
        worst = max(worst, float(np.abs(np.asarray(want_o) - np.asarray(o[at])).max()),
                    float(np.abs(np.asarray(want_s) - np.asarray(new[1, r])).max()))
    assert np.array_equal(np.asarray(new[0]), store[0])
    return float(blocks), worst, (int(ssd.live_visits(meta)), meta.shape[0]), np.asarray(o[:block])


# (lengths a slot, positions a slot, the blocks of 16 the rows touch[, the packed tokens]): lengths 1, Q - 1, Q,
# Q + 1 and beyond with and without a carried state; decode rows in front of one long chunk; no row at all; rows
# of two; rows that begin and end inside one half of a block (of 8, and of 4) beside foreign rows: identity steps
# at every level. And (ISSUE 65: the kernel's visit axis ends at the plan's last live visit) plans whose live
# visits are far under the bound (one long row beside 15 of one token), AT it (every row of two tokens starts
# mid-block, the last across a block's edge) and none at all (every row one token: visit 0 alone runs, and
# zeroes one block).
RAGGED = {
    "one_to_two_blocks_and_three": ([1, 15, 16, 17, 14], [5, 0, 3, 0, 11], 5),
    "the_same_all_carried": ([1, 15, 16, 17, 14], [5, 2, 3, 9, 11], 5),
    "decode_rows_then_a_chunk": ([1, 1, 1, 0, 50], [5, 2, 3, 0, 11], 4),
    "no_row": ([0, 0, 0, 0, 0], [5, 2, 3, 0, 11], 0),
    "rows_of_two": ([2, 2, 2, 2, 2], [0, 2, 3, 0, 11], 5),
    "rows_inside_one_half": ([1, 5, 3, 3, 9], [5, 2, 0, 3, 11], 5),
    "live_visits_far_under_the_bound": ([1] * 5 + [40] + [1] * 10, [5, 2, 3, 0, 11, 9] + [4] * 10, 3),
    "live_visits_at_the_bound": ([1] + [2] * 8, [5, 0, 3, 9, 11, 0, 7, 2, 4], 9, 17),
    "no_live_visit": ([1, 1, 1, 1, 1], [5, 2, 3, 0, 11], 0),
}
GRID = {"live_visits_far_under_the_bound": (3, 4 + 15), "live_visits_at_the_bound": (9, 9), "no_live_visit": (1, 4 + 4)}


# (the twin walks the plan's visits as it did: ISSUE 65's plans go through the kernel alone)
FORMS = [(case, kernel) for case in RAGGED for kernel in (False, True) if kernel or case not in GRID]


@pytest.mark.parametrize("case,kernel", FORMS, ids=[f"{c}-{'interpreted_kernel' if k else 'twin'}" for c, k in FORMS])
def test_the_chunk_form_is_the_token_recurrence_for_ragged_rows(case, kernel):
    """Float32 products at the highest precision on both sides: they differ in
    the order of their sums and in the solve; measured 5e-7 on outputs of ~0.4."""
    lens, starts, want_blocks, *T = RAGGED[case]
    blocks, worst, grid, first_block = chunk_case(lens, starts, kernel, T=T[0] if T else 64)
    assert blocks == want_blocks and worst < 2e-5
    assert grid == GRID.get(case, grid)
    if not blocks:
        assert not first_block.any()


@pytest.mark.parametrize("kernel", [False, True], ids=["twin", "interpreted_kernel"])
def test_blocks_of_64_tokens_as_the_cell_walks_them(kernel):
    """The published block: six levels, of which those of 8, 16 and 32 tokens
    feed only the first halves' columns to their product, and a solve whose
    powers of 8, 16 and 32 leave their zero rows out."""
    blocks, worst, *_ = chunk_case([1, 150, 1, 40, 2], [5, 7, 3, 0, 11], kernel, T=200, block=64)
    assert blocks == 3 + 1 + 1 and worst < 2e-5


@pytest.mark.parametrize("kernel", [False, True], ids=["twin", "interpreted_kernel"])
@pytest.mark.parametrize("strong_from", [0, 1, 2, 4, 7], ids=lambda n: f"strong_from_token_{n}_of_a_half")
def test_decays_whose_inverse_overflows_float32_stay_finite_and_right(kernel, strong_from):
    """Log decays of -100 and more a token in many channels: e^{-G} over a block
    is inf in float32, so a form that divides by the running decay reads nan.
    Every exponent the halving takes is <= 0: level h's rows about their half's
    first token, its columns up to it. The strong decays begin at token 0, 1,
    2, 4 or 7 of every run of 8, behind weak ones: at one level they fall all
    into the rows' exponent, at another all into the columns', at a third they
    straddle the boundary."""
    _, worst, *_ = chunk_case([1, 15, 16, 17, 14], [5, 2, 3, 0, 11], kernel, strength=40.0, strong_from=strong_from)
    assert worst < 2e-5
    g = operands(64, 0, 40.0, strong_from)[3]
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(-np.cumsum(g[:16], axis=0), dtype=np.float32)).all()


def halved_alone(q, k, kb, g):
    """``kda._halved`` on one head's block, in a kernel of its own (interpreted): (L, A)."""
    n = q.shape[0]

    def body(q_ref, k_ref, kb_ref, g_ref, l_ref, a_ref):
        l_ref[...], a_ref[...], _, _ = kda._halved(q_ref[...], k_ref[...], kb_ref[...], g_ref[...], kda._levels(n))

    square = jax.ShapeDtypeStruct((n, n), jnp.float32)
    return pl.pallas_call(body, out_shape=[square, square], interpret=True)(q, k, kb, g)


@pytest.mark.parametrize("strength", [1.0, 40.0, 400.0])
@pytest.mark.parametrize("block", [16, 64])
def test_l_and_a_by_halving_are_the_pairs_formed_alone(block, strength):
    """The kernel's L and A themselves, a head's block with foreign tokens in
    it (g = 0, beta = 0), against the twin's matrices (every pair alone, float32:
    the tolerance that stands) and against the same pairs in float64. The
    halving sums g inside a half, so it stays at float32's rounding of ONE
    pair (measured 3e-8 to 7e-8) where the twin's difference of two block-long
    sums reads 1e-6 to 6e-6 at the strong decays."""
    q, k, _, g, beta = (a[:block, 0] for a in operands(block, 3, strength))
    mine = (np.arange(block) >= 3) & (np.arange(block) < block - 2)
    g, beta = np.where(mine[:, None], g, 0.0).astype(np.float32), np.where(mine, beta, 0.0).astype(np.float32)
    kb = k * beta[:, None]
    got_l, got_a = map(np.asarray, halved_alone(*map(jnp.asarray, (q, k, kb, g))))
    twin_l, twin_a, _ = kda._pairs_alone(*(jnp.asarray(a)[:, None] for a in (q, k, kb, g)))
    G = np.cumsum(g.astype(np.float64), axis=0)
    lower = np.tri(block, k=-1)[..., None]
    pair = np.exp(np.minimum(G[:, None] - G[None, :], 0.0)) * k[None, :].astype(np.float64)
    want_l = (lower * kb[:, None] * pair).sum(-1)
    want_a = ((lower + np.eye(block)[..., None]) * q[:, None] * pair).sum(-1)
    assert np.abs(got_l - np.asarray(twin_l[..., 0])).max() < 2e-5 and np.abs(got_a - np.asarray(twin_a[..., 0])).max() < 2e-5
    assert np.abs(got_l - want_l).max() < 5e-7 and np.abs(got_a - want_a).max() < 5e-7
    assert not got_l[np.triu_indices(block)].any() and not got_a[np.triu_indices(block, 1)].any()


def test_bfloat16_operands_round_the_products_and_nothing_that_is_carried():
    """``mm`` bfloat16 rounds the operands of the products with the state and
    with U; the sums, the decays, L, the solve and the state stay float32, so
    the error is a rounding's and does not grow with the blocks."""
    lens, starts = [1, 1, 1, 0, 60], [5, 2, 3, 0, 11]
    q_lens = np.asarray(lens, np.int32)
    q_starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    ops = operands(64, 0, 1.0)
    store = np.random.default_rng(1).normal(size=(LAYERS, SLOTS, HH, D, D)).astype(np.float32)
    meta, _ = ssd.block_plan(jnp.asarray(q_starts), jnp.asarray(q_lens), jnp.asarray(np.asarray(starts) + q_lens), 64, Q)
    exact, _ = kda.kda_chunk(jnp.asarray(store), 1, *map(jnp.asarray, ops), meta, Q=Q, kernel=False)
    for kernel in (False, True):
        got, new = kda.kda_chunk(jnp.asarray(store), 1, *map(jnp.asarray, ops), meta, Q=Q, mm=jnp.bfloat16, kernel=kernel)
        err = np.abs(np.asarray(got[3:63]) - np.asarray(exact[3:63]))
        assert 1e-5 < err.max() < 0.05 and new.dtype == jnp.float32
        assert err[-16:].mean() < 2 * err[:16].mean() + 1e-3


@pytest.mark.parametrize("kernel", [False, True], ids=["definition", "interpreted_kernel"])
@pytest.mark.parametrize("advancing", [[1, 1, 0, 1, 1], [0, 0, 1, 0, 0], [0, 0, 0, 0, 0], [1, 1, 1, 1, 1]],
                         ids=["most", "one", "none", "all"])
def test_the_one_token_update_is_one_step_of_the_recurrence(advancing, kernel):
    """``update_store`` on entry 1 of a store of random states: a row that
    advances holds the recurrence's next state (from zero where it is fresh) and
    reads o from it; every other row, and the other entry, stay as they were to
    the bit. The kernel walks the rows that advance alone."""
    q, k, v, g, beta = (a[:SLOTS] for a in operands(SLOTS, 2, 3.0))
    store = np.random.default_rng(3).normal(size=(LAYERS, SLOTS, HH, D, D)).astype(np.float32)
    valid, fresh = np.asarray(advancing, bool), np.asarray([0, 1, 0, 0, 1], bool)
    o, new = kda.update_store(jnp.asarray(store), 1, *map(jnp.asarray, (q, k, v, g, beta, valid, fresh)), kernel=kernel)
    assert np.array_equal(np.asarray(new[0]), store[0])
    for r in range(SLOTS):
        if not valid[r]:
            assert np.array_equal(np.asarray(new[1, r]), store[1, r])
            continue
        first = np.zeros_like(store[1, r]) if fresh[r] else store[1, r]
        want_o, want_s = kda.recurrence(jnp.asarray(first), *(jnp.asarray(a[r:r + 1]) for a in (q, k, v, g, beta)))
        np.testing.assert_allclose(np.asarray(o[r]), np.asarray(want_o[0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(new[1, r]), np.asarray(want_s), atol=1e-5)


# ---------------------------------------------------------------------------
# The share of the experts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["dropless", "dense"])
def test_two_shares_and_the_shared_expert_once_add_up_to_the_whole_layer(impl):
    """Experts 0-3 and 4-7 of 8 under the SAME router of 8: each share computes
    its experts' part and nothing in place of the rest, so the two outputs, with
    the shared expert counted once, are the uncut reference's whole layer; and
    the rows a share counts are its own experts' alone."""
    whole = dataclasses.replace(CFG, experts_held=0, moe_impl=impl)
    params = mixtral.init_params(jax.random.PRNGKey(1), whole, dtype=jnp.float32)["layers"]
    assert params["wg"].shape == (7, 8, 64, 32)
    li, stack = 3, 2                                                       # layer 3 is the expert stacks' entry 2
    lp = {n: params[n][stack] for n in ("moe_norm", "router", "router_bias", "shared_wg", "shared_wu", "shared_wd")}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32) * 3.0
    parts, rows = [], []
    for first in (0, 4):
        cfg = dataclasses.replace(whole, experts_held=4, experts_first=first)
        experts = {n: params[n][:, first:first + 4] for n in mixtral.EXPERT_LEAVES}
        out, counted = mixtral._moe_ffn(x, x, lp, li, None, cfg=cfg, experts=experts)
        parts.append(out[0])
        rows.append(np.asarray(counted))
    m = REF.dense.rms_norm(x[0], lp["moe_norm"], 1e-5)
    shared = REF.latent.swiglu(m, lp["shared_wg"], lp["shared_wu"], lp["shared_wd"])
    own = {**lp, **{n: params[n][stack] for n in mixtral.EXPERT_LEAVES}}
    with jax.default_matmul_precision("highest"):
        want = REF.ffn_block(x[0], own, REF.sizes({**MODEL, "num_experts": 8}), False) - x[0]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] - shared), np.asarray(want), atol=2e-4)
    assert float(jnp.abs(parts[0] - shared).max()) > 0.05 and float(jnp.abs(parts[1] - shared).max()) > 0.05
    if impl == "dropless":
        assert not rows[0][4:].any() and not rows[1][:4].any() and (rows[0] + rows[1]).sum() == 24 * 2
        one = REF.ffn_block(x[0], {**own, **{n: own[n][:4] for n in mixtral.EXPERT_LEAVES}}, REF.sizes(MODEL), False) - x[0]
        np.testing.assert_allclose(np.asarray(parts[0]), np.asarray(one), atol=2e-4)    # the reference's share is the program's


# ---------------------------------------------------------------------------
# The program against the reference
# ---------------------------------------------------------------------------
CASES = [("float32", None), ("bfloat16", "int8")]


def gaps(eng, seq, start, served, model=MODEL):
    """|served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1], model), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return np.abs(want - np.asarray(served, np.float32))


def serve_two_rows(eng):
    """Slot 0 takes a 40-token prompt in chunks of 1, 7, 9 and then 23 tokens (a
    one-token row from position 0; a chunk inside a block of 8; one that crosses
    a block; one of three blocks that starts inside a packed block) and decodes;
    slot 1's 150-token prompt then goes in over five mixed steps of 36 (each
    chunk behind slot 0's decode row: five packed blocks, four block boundaries,
    and the state handed to the next step); both rows then decode two chunks
    through the latent pool and the state store."""
    a, b = prompt(40, 3), prompt(150, 4)
    tok, lp, stats = admit(eng, 0, a, chunks=(1, 7, 9, 23))
    assert len(stats) == 4
    row_a = [tok, 40, [tok], [lp]]
    tok_b, lp_b, stats = admit(eng, 1, b, beside={0: row_a})
    assert len(stats) == 5
    toks, lps, _ = decode(eng, {0: (row_a[0], row_a[1]), 1: (tok_b, 150)}, 2)
    return [(a + row_a[2] + toks[0], 40, row_a[3] + lps[0]), (b + [tok_b] + toks[1], 150, [lp_b] + lps[1])]


def benchmark_limits():
    sys.path.insert(0, str(BENCH))
    import reference

    return reference.TOLERANCE_MAX_NATS, reference.TOLERANCE_MEAN_NATS


@pytest.mark.parametrize("dtype,quantize", CASES)
def test_chunked_prompt_then_decode_across_chunk_and_block_boundaries_matches_the_reference(dtype, quantize, eng32):
    eng = eng32 if dtype == "float32" else engine(dtype, quantize)
    assert eng.mixed_ok and eng.carries_state and eng.is_mla and eng._model is mixtral and eng.mesh is None
    diffs = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in serve_two_rows(eng)])
    assert len(diffs) == 23   # row 0: 1 + 5 beside the chunks + 8; row 1: 1 + 8
    if dtype == "float32":
        assert diffs.max() < 5e-5
    else:
        largest, mean = benchmark_limits()
        assert diffs.max() <= largest and diffs.mean() <= mean, (diffs.max(), diffs.mean())


@pytest.mark.parametrize("control", list(CONTROLS))
def test_every_control_moves_the_float32_logprobs(control, eng32):
    """The served float32 logprobs sit 5e-5 from the reference; from the
    reference with one mechanism changed, hundreds of times further: the
    seeding (``ops/quant._kimi_std``) makes each mechanism SHOW."""
    b = prompt(100, 11)
    tok, lp, _ = admit(eng32, 1, b)
    toks, lps, _ = decode(eng32, {1: (tok, 100)}, 2)
    model = {**MODEL, "assumed_mechanisms": CONTROLS[control]}
    assert gaps(eng32, b + [tok] + toks[1], 100, [lp] + lps[1]).max() < 5e-5
    assert gaps(eng32, b + [tok] + toks[1], 100, [lp] + lps[1], model).max() > 100 * 5e-5


def test_a_slot_admitted_again_a_row_recomputed_and_a_probe_sent_twice(eng32):
    """A row that starts at position 0 starts from zero whatever the slot held:
    slot 0 serves one request, then another with no reset in between
    (admission), and the row is recomputed from position 0 after its pages were
    given up (``_preempt``'s recompute, other chunks): the logprobs are the
    reference's for a fresh sequence. The same prompt sent again in the same
    chunks reads the same token and logprob to the bit (its prefix hit would be
    declined: a state cannot be adopted as pages can)."""
    eng = eng32
    resets = eng.metrics["state_resets"]
    admit(eng, 0, prompt(30, 21))
    decode(eng, {0: (5, 30)}, 1)                               # the slot's state now holds the first request's
    second = prompt(45, 22)
    tok, lp, _ = admit(eng, 0, second, chunks=(7, 36))
    assert gaps(eng, second + [tok], 45, [lp]).max() < 5e-5
    again = admit(eng, 0, second, chunks=(7, 36))[:2]
    assert again == (tok, lp)
    eng.allocator.release(0)
    tok, lp, _ = admit(eng, 0, second[:33], chunks=(2, 36))    # recomputed: the same tokens again, other chunks
    assert gaps(eng, second[:33] + [tok], 33, [lp]).max() < 5e-5
    assert eng.metrics["state_resets"] - resets == 4 == eng.family_status()["state_resets"] - resets
    assert eng.prefix_cache is None or eng.prefix_cache.decline_hits


@pytest.mark.parametrize("check", ABSORB_CHECKS)
def test_absorbed_from_an_int8_wukv_against_its_definitions(check):
    """ISSUE 59 on this preset's latent layers (no query bottleneck, no rotary): tests/test_xing4.py has the cases."""
    absorbed_from_an_int8_wukv(PRESET, check, lambda h, w, pos: REF.mla(h, w, REF.sizes(MODEL)))


def test_the_mixed_step_matches_the_decode_program(eng32):
    """The same row's next tokens through mixed steps (the one-token update at a
    packed position, ``conv_ragged``, the latent read's one-query tile) and
    through the decode chunk: the same tokens, the logprobs to float32's
    summation order."""
    eng, ids, outs = eng32, prompt(50, 7), []
    for slot, mixed in ((2, True), (3, False)):
        tok, lp, _ = admit(eng, slot, ids)
        if mixed:
            row = [tok, 50, [], []]
            for _ in range(4):
                h = eng.mixed_step_submit([MixedRow(slot=slot, token_ids=[row[0]], start=row[1])])
                t, l = eng.mixed_step_fetch(h)
                row = [int(t[slot]), row[1] + 1, row[2] + [int(t[slot])], row[3] + [float(l[slot])]]
            outs.append((row[2], row[3]))
        else:
            toks, lps, _ = decode(eng, {slot: (tok, 50)}, 1)
            outs.append((toks[slot], lps[slot]))
    assert outs[0][0] == outs[1][0]
    np.testing.assert_allclose(outs[0][1], outs[1][1], atol=5e-5, rtol=0)


def test_the_counters_and_the_status_by_hand(eng32):
    """``kda_blocks``: the packed blocks of 8 a step's rows of more than one
    token touch, times the six KDA layers; ``state_rows``: a mixed step's
    one-token rows, a decode chunk's live rows summed over its steps; the page
    counts over the TWO latent layers; ``experts_read`` never past the four held."""
    eng = eng32
    _, _, stats = admit(eng, 0, prompt(40, 3), chunks=(1, 7, 9, 23))
    assert [s["kda_blocks"] for s in stats] == [0.0, 6.0, 12.0, 18.0]      # 0, 1, 2, 3 blocks x 6 layers
    assert [s["state_rows"] for s in stats] == [1.0, 0.0, 0.0, 0.0]        # the first chunk is ONE token
    row_a = [5, 40, [], []]
    _, _, stats = admit(eng, 1, prompt(36, 5), beside={0: row_a})
    assert stats[0]["kda_blocks"] == 5 * 6 and stats[0]["state_rows"] == 1.0    # packed 1..36 touches blocks 0-4
    bounds = {s["chunk_visits_bound"] for s in stats}                           # the plan's length, whatever the rows
    assert stats[0]["state_bytes"] == 2 * CFG.state_bytes_per_slot == 2 * 6 * (4 * 32 * 32 + 3 * 384) * 4
    assert stats[0]["context_pages"] == 2 * ((41 + 7) // 8 + (36 + 7) // 8)
    assert all(0 <= s["experts_read"] <= 4 for s in stats)
    _, _, stats = decode(eng, {0: (5, 41), 1: (6, 36)}, 1)
    assert stats[0]["kda_blocks"] == 0.0 and stats[0]["state_rows"] == 2.0 * 4 and 0 <= stats[0]["experts_read"] <= 4
    assert stats[0]["chunk_visits_bound"] == 0.0 and bounds == {6.0 * ssd.max_visits(eng.mixed_budget, 4, 8)}   # x 6 layers
    status = eng.family_status()
    assert status["state_store"] == {"slots": 4, "layers": 6, "bytes": 4 * CFG.state_bytes_per_slot}
    assert status["cache_layers"] == {"full": 2, "kda": 6}
    assert status["kda"]["block"] == 8 and status["kda"]["state"] == [32, 32] and status["kda"]["decay"] == "a channel"
    assert status["kda"]["rows_of_more_than_one_token"] == "kda_chunk_twin"
    router = eng.family_status()["router"]
    assert (router["experts"], router["experts_held"], router["experts_first"]) == (8, 4, 0)
    pool = eng.family_status()["latent_pool"]
    assert (pool["layers"], pool["row_values"], pool["row_lanes"]) == (2, 40, 128)
    assert pool["bytes_per_token"] == 2 * 128 * 4
    assert eng.cache["kv"].shape[0] == 2 and eng.cache["kda"].shape == (6, 4, 4, 32, 32)
    assert eng._mixed_stats[-3:] == eng._chunk_stats[-3:] == ("kda_blocks", "state_rows", "chunk_visits_bound")


def test_what_the_state_and_the_pool_rule_out_is_refused_or_declined_by_name():
    from inference_gateway_tpu.models.family import family_of
    from inference_gateway_tpu.serving.engine import choose_mesh

    big = mixtral.PRESETS["kimi-linear-48b-a3b-stage8"]
    assert all(family_of(c).state_store and family_of(c).pool == "latent" for c in (CFG, big))
    config = EngineConfig(model=PRESET, attention="paged", mesh_shape={"tp": 2})
    with pytest.raises(ValueError, match="no mesh"):
        choose_mesh(config, CFG, 4)
    assert choose_mesh(EngineConfig(model=PRESET, attention="paged"), CFG, 4) == (None, False)
    with pytest.raises(ValueError, match="speculative decoding"):
        Engine(EngineConfig(model=PRESET, attention="paged", spec_draft="test-tiny", use_mesh=False))
    with pytest.raises(ValueError, match="int8 is the one"):
        Engine(EngineConfig(model=PRESET, attention="paged", quantize="int4", use_mesh=False))
    with pytest.raises(ValueError, match="image input has no path"):
        Engine(EngineConfig(model=PRESET, attention="paged", vision_model="vit-test-tiny", use_mesh=False))
    params = jax.eval_shape(lambda: mixtral.init_params(jax.random.PRNGKey(0), CFG))
    with pytest.raises(NotImplementedError, match="served over the paged cache"):
        mixtral.forward(params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None], jnp.asarray([8]))
    cache = mixtral.init_paged_cache(CFG, engine_page_cfg())
    with pytest.raises(NotImplementedError, match="admits through the mixed step"):
        mixtral.forward_paged(params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None], jnp.asarray([8]),
                              cache, jnp.arange(8)[None], jnp.zeros((1, 4), jnp.int32), mode="prefill")
