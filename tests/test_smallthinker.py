"""SmallThinker's block on the normal path (ISSUE 28), at the tiny preset on the
CPU: one period (a global NoPE layer and three windowed RoPE layers), 8 experts
top-3, a window of 16 under sequences of 40 and more, seeded random weights.

The program against ``bench/references/moe_early_router_gqa.py`` (float32,
precision "highest", no cache, no kernels; it imports nothing from the
program): a full prefill, prefill then decode through the paged cache, and rows
admitted through the mixed step beside decoding rows. Then the dropless expert
layer against the all-experts oracle under a skewed router, the two kinds of
layer each shown to matter, and the program's expert count against one by hand.
"""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import mixtral
from inference_gateway_tpu.ops.moe import moe_dense, moe_dropless
from inference_gateway_tpu.ops.quant import QTensor
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow

PRESET = "smallthinker-test-tiny"
CFG = mixtral.PRESETS[PRESET]
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "head_dim": 16, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "sliding_window_size": 16,
    "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1]}


def load_reference():
    path = Path(__file__).resolve().parent.parent / "bench" / "references" / "moe_early_router_gqa.py"
    spec = importlib.util.spec_from_file_location("bench_reference_moe_early_router_gqa", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def f32(w):
    return w.q.astype(jnp.float32) * w.scale if isinstance(w, QTensor) else w.astype(jnp.float32)


def reference_logits(params, ids, model=MODEL):
    """The reference over ``ids`` with the program's weights as data."""
    def layer(i):
        return {n: f32(jax.tree.map(lambda a: a[i], params["layers"][n])) for n in REF.LEAVES}

    return REF.forward(ids, params["embed"], layer, f32(params["final_norm"]), f32(params["lm_head"]),
                       REF.sizes(model))


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def test_presets_follow_the_source():
    stage = mixtral.PRESETS["smallthinker-21b-a3b-stage16"]
    assert stage.num_layers == 16
    for cfg in (stage, CFG):
        assert cfg.layer_rope == cfg.layer_window == tuple(int(i % 4 != 0) for i in range(cfg.num_layers))
        assert cfg.router_input == "attn" and cfg.hidden_act == "relu" and not cfg.qkv_bias
    assert (stage.hidden_size, stage.num_heads, stage.num_kv_heads, stage.hd) == (2560, 28, 4, 128)
    assert (stage.num_experts, stage.experts_per_token, stage.intermediate_size) == (64, 6, 768)
    assert (stage.vocab_size, stage.sliding_window, stage.rope_theta) == (151936, 4096, 1.5e6)


def test_full_prefill_matches_the_reference():
    """float32 on both sides: they differ in the order of their sums over four
    layers of width 64, so logits of size ~0.5 agree to 1e-4; a router reading
    the wrong state, a rotated NoPE layer or an unwindowed layer moves them by
    1e-2 or more (the tests below)."""
    params = mixtral.init_params(jax.random.PRNGKey(28), CFG, dtype=jnp.float32)
    ids = prompt(48, 1)
    with jax.default_matmul_precision("highest"):
        got, _ = mixtral.forward(params, CFG, jnp.asarray([ids]), jnp.arange(48)[None, :], jnp.asarray([48]))
    want = reference_logits(params, ids)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=1e-4, rtol=0)
    assert float(jnp.max(jnp.abs(want))) > 0.05


def engine(dtype, quantize, mixed):
    return Engine(EngineConfig(
        model=PRESET, attention="paged", page_size=8, max_slots=4, max_seq_len=128, dtype=dtype,
        quantize=quantize, mixed_step=mixed, mixed_step_tokens=64, use_mesh=False,
        prefill_buckets=(16, 32, 64), decode_chunk=4))


# |served - reference| of a token's logprob, in nats. float32: summation order
# alone. bfloat16 / int8: the served side rounds activations and the residual
# stream to bf16 over four layers while the reference keeps float32 on the same
# (dequantised) weights; measured 0.001-0.006 at this size (sandbox, PR 28), and the benchmark's
# own tiny check allows 0.05 for two layers. Skipping the window, rotating the
# NoPE layer or routing from the wrong state moves logprobs by 0.1-1.
TOLERANCE = {"float32": 2e-5, "bfloat16": 0.03}
CASES = [("float32", None), ("bfloat16", None), ("bfloat16", "int8")]


def logprob_gap(eng, seq, start, served):
    """Largest |served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1]), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return float(np.max(np.abs(want - np.asarray(served, np.float32))))


def decode(eng, tok, pos, chunks):
    """Greedy decode of slot 0 for ``chunks`` chunks, the first fresh, the
    others chained; returns (tokens, logprobs, the handles' stats)."""
    S = eng.config.max_slots
    t, p, active = np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool)
    t[0], p[0], active[0] = tok, pos, True
    toks, lps, stats = [], [], []
    for i in range(chunks):
        h = eng.decode_chunk_submit(t, p, active, np.zeros(S, np.float32), np.ones(S, np.float32), chain=i > 0)
        a, b = eng.decode_chunk_fetch(h)
        toks += [int(x) for x in a[:, 0]]
        lps += [float(x) for x in b[:, 0]]
        stats.append(h.stats)
    return toks, lps, stats


@pytest.mark.parametrize("dtype,quantize", CASES)
def test_prefill_then_paged_decode_matches_the_reference(dtype, quantize):
    """A 40-token prompt through the bucketed paged prefill, then eight tokens
    through fresh and chained decode chunks over the paged cache: positions
    40-47 lie 24 and more past the window's edge, in pages of 8."""
    eng = engine(dtype, quantize, mixed=False)
    ids = prompt(40, 2)
    first = eng.prefill([ids], [0], [0.0], [1.0])[0]
    toks, lps, _ = decode(eng, first.first_token, 40, 2)
    seq = ids + [first.first_token] + toks
    assert logprob_gap(eng, seq, 40, [first.logprob] + lps) < TOLERANCE[dtype]


@pytest.mark.parametrize("dtype,quantize", CASES)
def test_rows_admitted_through_the_mixed_step_match_the_reference(dtype, quantize):
    """Slot 0 is admitted by a mixed step and decodes; slot 1's prompt is then
    admitted by a mixed step that carries slot 0's decode row beside it. Both
    rows' logprobs are held to the reference, and the step's expert counts are
    the live rows': 34 tokens x 3 choices over 8 experts."""
    eng = engine(dtype, quantize, mixed=True)
    assert eng.mixed_ok and eng.mesh_axes == {"tp": 1}
    a, b = prompt(40, 3), prompt(33, 4)
    h = eng.mixed_step_submit([MixedRow(slot=0, token_ids=a, start=0, kind="prefill")])
    t, lp = eng.mixed_step_fetch(h)
    a_toks, a_lps = [int(t[0])], [float(lp[0])]
    h = eng.mixed_step_submit([MixedRow(slot=0, token_ids=[a_toks[-1]], start=40),
                               MixedRow(slot=1, token_ids=b, start=0, kind="prefill")])
    t, lp = eng.mixed_step_fetch(h)
    assert h.stats["expert_tokens_mean"] == pytest.approx(34 * 3 / 8)
    assert 3 * 34 / 8 <= h.stats["expert_tokens_max"] <= 34 and 3 <= h.stats["experts_read"] <= 8
    # the kernels' visits (ISSUE 42): one an expert with rows while none holds more than a visit's rows
    assert h.stats["expert_visits"] == h.stats["experts_read"]
    a_toks.append(int(t[0]))
    a_lps.append(float(lp[0]))
    toks, lps, _ = decode(eng, a_toks[-1], 41, 1)
    assert logprob_gap(eng, a + a_toks + toks, 40, a_lps + lps) < TOLERANCE[dtype]
    assert logprob_gap(eng, b + [int(t[1])], 33, [float(lp[1])]) < TOLERANCE[dtype]


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float32", "int8"])
def test_dropless_layer_loses_no_assignment_under_a_skewed_router(kernel, quantized):
    """Every token's first choice is expert 2 (a capacity of 2 x N x k / E would
    drop most of them): all N x k assignments are computed, expert 2 multiplies
    N rows, and the output is the all-experts oracle's."""
    from inference_gateway_tpu.ops.quant import qeinsum, quantize_tensor

    rng = np.random.default_rng(5)
    L, E, H, I, N, k = 2, 8, 64, 96, 40, 3
    ws = [jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.1)
          for s in ((L, E, H, I), (L, E, H, I), (L, E, I, H))]
    if quantized:
        ws = [quantize_tensor(w) for w in ws]
    x = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32)).at[:, 2].add(100.0)

    def expert_fn(inp):
        lw = [jax.tree.map(lambda a: a[1], w) for w in ws]
        h = jax.nn.relu(qeinsum("enh,ehi->eni", inp, lw[0])) * qeinsum("enh,ehi->eni", inp, lw[1])
        return qeinsum("eni,eih->enh", h, lw[2])

    want = moe_dense(x, logits, k, expert_fn)
    got, rows = moe_dropless(x, logits, k, *ws, 1, act="relu", kernel=kernel)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert int(rows.sum()) == N * k and int(rows[2]) == N
    # rows that carry no token choose no expert and come back zero
    valid = jnp.arange(N) < 17
    part, rows = moe_dropless(x, logits, k, *ws, 1, act="relu", valid=valid, kernel=kernel)
    np.testing.assert_allclose(np.asarray(part[:17]), np.asarray(want[:17]), atol=2e-5, rtol=1e-5)
    assert int(rows.sum()) == 17 * k and not np.asarray(part[17:]).any()


@pytest.mark.parametrize("kernel", [False, True], ids=["ragged_dot", "pallas_interpret"])
@pytest.mark.parametrize("ep,tp", [(4, 1), (1, 4), (2, 2)], ids=["ep4", "tp4", "ep2_tp2"])
def test_dropless_layer_on_a_mesh_is_the_one_device_layer(ep, tp, kernel):
    """Experts over ``ep`` and each expert's columns over ``tp`` (param_specs'
    layout, int8 stacks): every shard multiplies its own experts' span of the
    sorted rows and one psum finishes the layer. Same output and the same count
    of rows an expert as on one device, a skewed router (expert 5 takes every
    token, so one ep shard holds most rows and another may hold none) and rows
    without a token included."""
    from jax.sharding import NamedSharding

    from inference_gateway_tpu.ops.quant import quantize_tensor
    from inference_gateway_tpu.parallel.mesh import create_moe_mesh
    from inference_gateway_tpu.parallel.sharding import quantized_specs

    rng = np.random.default_rng(11)
    L, E, H, I, N, k = 2, 8, 64, 96, 40, 3
    shapes = {"wg": (L, E, H, I), "wu": (L, E, H, I), "wd": (L, E, I, H)}
    ws = {n: quantize_tensor(jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.1)) for n, s in shapes.items()}
    x = jnp.asarray(rng.normal(size=(N, H)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32)).at[:, 5].add(100.0)
    valid = jnp.arange(N) < 33
    want, want_rows = moe_dropless(x, logits, k, *ws.values(), 1, act="relu", valid=valid, kernel=kernel)

    mesh = create_moe_mesh(dp=8 // (ep * tp), ep=ep, tp=tp)
    specs = quantized_specs(mixtral.param_specs(CFG))["layers"]
    sharded = [jax.tree.map(lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec)), ws[n], specs[n])
               for n in shapes]
    got, rows = jax.jit(lambda x, logits, *stacks: moe_dropless(
        x, logits, k, *stacks, 1, act="relu", valid=valid, mesh=mesh, kernel=kernel))(x, logits, *sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert list(np.asarray(rows)) == list(np.asarray(want_rows)) and int(rows[5]) == 33


@pytest.mark.parametrize("mesh_shape,mixed", [(None, False), ({"ep": 2, "tp": 2}, False), ({"tp": 2}, True)],
                         ids=["default_ep8", "ep2_tp2", "tp2_mixed_step"])
def test_engine_on_a_mesh_serves_what_one_device_serves(mesh_shape, mixed):
    """The same seed served from a mesh and from one device, float32: the first
    token of a 40-token prompt (bucketed paged prefill, or the mixed step on the
    mesh without an ``ep`` axis) and eight decoded tokens, to 1e-5 nats. Experts
    sharded over ``ep`` under GSPMD alone (``jax.lax.ragged_dot`` with its groups
    split) came out 0.13 off in the logits (sandbox, PR 28), which is why the
    layer runs under ``shard_map`` wherever there is a mesh."""
    def serve(**kw):
        eng = Engine(EngineConfig(
            model=PRESET, attention="paged", page_size=8, max_slots=4, max_seq_len=128, dtype="float32",
            mixed_step=mixed, mixed_step_tokens=64, prefill_buckets=(16, 32, 64), decode_chunk=4, **kw))
        ids = prompt(40, 6)
        if eng.mixed_ok:
            t, lp = eng.mixed_step_fetch(eng.mixed_step_submit(
                [MixedRow(slot=0, token_ids=ids, start=0, kind="prefill")]))
            first, first_lp = int(t[0]), float(lp[0])
        else:
            r = eng.prefill([ids], [0], [0.0], [1.0])[0]
            first, first_lp = r.first_token, float(r.logprob)
        toks, lps, _ = decode(eng, first, 40, 2)
        return eng, [first] + toks, np.asarray([first_lp] + lps)

    one, want_toks, want_lps = serve(use_mesh=False)
    eng, toks, lps = serve(mesh_shape=mesh_shape)
    assert eng.mesh is not None and eng.mixed_ok == mixed
    assert eng.mesh_axes == {"dp": 1, "sp": 1, "ep": 8, "tp": 1} if mesh_shape is None \
        else {k: eng.mesh_axes[k] for k in mesh_shape} == mesh_shape
    assert toks == want_toks
    np.testing.assert_allclose(lps, want_lps, atol=1e-5, rtol=0)


@pytest.mark.parametrize("what", ["window", "nope", "router"])
def test_each_kind_of_layer_changes_the_logits(what):
    """The window (16 keys under a 40-token sequence), the NoPE layer and the
    router's input each move the last position's logits, in the program and in
    the reference alike: program and reference agree under each variant and the
    variants disagree with the model. Inside the window nothing changes."""
    variants = {
        "window": (dataclasses.replace(CFG, layer_window=(0, 0, 0, 0)), {**MODEL, "sliding_window_layout": [0] * 4}),
        "nope": (dataclasses.replace(CFG, layer_rope=(1, 1, 1, 1)), {**MODEL, "rope_layout": [1] * 4}),
        "router": (dataclasses.replace(CFG, router_input="ffn"), None),
    }
    cfg, model = variants[what]
    params = mixtral.init_params(jax.random.PRNGKey(28), CFG, dtype=jnp.float32)
    # Weights of std 0.02 give near-uniform attention, which no position
    # encoding moves much: sharpen the scores so that where a key lies matters.
    params["layers"] = {**params["layers"], "wq": params["layers"]["wq"] * 10, "wk": params["layers"]["wk"] * 10}
    # At width 64 a layer adds 0.16 of the scale of what it reads (0.02 x sqrt(64); 1.0 at
    # width 2,560), which beside a mixture's unit-scale seeded embedding (ops/quant.
    # seeded_std) moves a logit by 0.005: shrink the embedding so the layers lead here too.
    params["embed"] = params["embed"] * 0.02
    ids = prompt(40, 6)

    def run(c, n):
        with jax.default_matmul_precision("highest"):
            out, _ = mixtral.forward(params, c, jnp.asarray([ids[:n]]), jnp.arange(n)[None, :], jnp.asarray([n]))
        return np.asarray(out[0, -1])

    base, other = run(CFG, 40), run(cfg, 40)
    assert np.max(np.abs(base - other)) > 1e-2
    if model is not None:
        np.testing.assert_allclose(other, np.asarray(reference_logits(params, ids, model))[-1], atol=1e-4, rtol=0)
    if what == "window":
        np.testing.assert_allclose(run(CFG, 16), run(cfg, 16), atol=1e-6, rtol=0)


def test_experts_read_is_the_count_by_hand():
    """One live decode row chooses 3 experts a layer: ``experts_read`` 3.0 on
    fresh and chained chunks, the dead slots choosing none. Then two live rows,
    against the distinct choices of the reference's own router on the same
    tokens (a count by hand, layer by layer)."""
    eng = engine("float32", None, mixed=False)
    a, b = prompt(24, 7), prompt(24, 8)
    firsts = eng.prefill([a, b], [0, 1], [0.0, 0.0], [1.0, 1.0])
    _, _, stats = decode(eng, firsts[0].first_token, 24, 2)
    assert [s["experts_read"] for s in stats] == [3.0, 3.0]
    assert [s["expert_visits"] for s in stats] == [3.0, 3.0]  # ISSUE 42: one visit an expert with rows

    S = eng.config.max_slots
    t, p, active = np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool)
    for slot, (ids, first) in enumerate(zip((a, b), firsts)):
        t[slot], p[slot], active[slot] = first.first_token, 24, True
    h = eng.decode_chunk_submit(t, p, active, np.zeros(S, np.float32), np.ones(S, np.float32), n_steps=1)
    eng.decode_chunk_fetch(h)

    sizes = REF.sizes(MODEL)
    chosen = []  # per sequence, per layer: the experts its last token chose
    for ids, first in zip((a, b), firsts):
        seq = ids + [first.first_token]
        x = eng.params["embed"][jnp.asarray(seq)].astype(jnp.float32)
        per_layer = []
        for i in range(4):
            w = {n: f32(eng.params["layers"][n][i]) for n in REF.LEAVES}
            hid = REF.dense.rms_norm(x, w["attn_norm"], sizes["rms_norm_eps"])
            per_layer.append(set(np.asarray(jax.lax.top_k(hid[-1] @ w["router"], 3)[1]).tolist()))
            with jax.default_matmul_precision("highest"):
                x = REF.block(x, w, jnp.arange(len(seq)), sizes, bool(MODEL["rope_layout"][i]),
                              bool(MODEL["sliding_window_layout"][i]))
        chosen.append(per_layer)
    by_hand = np.mean([len(chosen[0][i] | chosen[1][i]) for i in range(4)])
    assert h.stats["experts_read"] == pytest.approx(by_hand) and 3 <= by_hand <= 6
    assert h.stats["expert_visits"] == h.stats["experts_read"]


def test_engine_builds_through_the_two_functions():
    """``choose_mesh`` and ``build_params`` are what ``Engine.__init__`` calls:
    the tree they give is the engine's, bit for bit, and a mixture's mesh
    honours ``mesh_shape`` (experts split over tp alone, no ep axis)."""
    from inference_gateway_tpu.serving import engine as eng_mod

    config = EngineConfig(model=PRESET, quantize="int8", use_mesh=False, max_slots=1, max_seq_len=16)
    eng = Engine(config)
    mesh, pp = eng_mod.choose_mesh(config, CFG, len(jax.devices()))
    assert mesh is None and not pp
    tree = eng_mod.build_params(CFG, mixtral, config.seed, jnp.bfloat16, "int8", None)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(eng.params)):
        assert x.dtype == y.dtype and np.array_equal(np.asarray(x.astype(jnp.float32)), np.asarray(y.astype(jnp.float32)))
    if len(jax.devices()) >= 2:
        sharded = dataclasses.replace(config, use_mesh=True, mesh_shape={"tp": 2})
        mesh, _ = eng_mod.choose_mesh(sharded, CFG, len(jax.devices()))
        assert mesh.shape["tp"] == 2 and mesh.shape["ep"] == 1


def test_layerwise_quantised_build_matches_the_trees_shape():
    """The build that never holds a float32 expert leaf gives the same tree
    (names, shapes, dtypes) as quantise-after-init, with a bf16 router."""
    from inference_gateway_tpu.ops.quant import init_quantized_llama_params, quantize_llama_params

    built = init_quantized_llama_params(jax.random.PRNGKey(0), CFG)
    want = jax.eval_shape(lambda: quantize_llama_params(mixtral.init_params(jax.random.PRNGKey(0), CFG)))
    assert jax.tree.structure(built) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(built), jax.tree.leaves(want)))
    assert built["layers"]["router"].dtype == jnp.bfloat16
    assert float(jnp.std(built["layers"]["wg"].q[3].astype(jnp.float32))) > 10  # every layer was written
