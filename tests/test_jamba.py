"""Jamba's block on the normal path (ISSUE 64), at the tiny preset on the CPU:
m a m m m a m at toy widths (runs of one and of three Mamba-1 mixers with normed
Delta, B and C around two NoPE attention layers of ONE KV head under 4 queries, a
dense gated MLP in every layer, a tied head), seeded random weights.

The chunk form of the selective scan (ops/ssm.py: the twin AND the interpreted
kernel) against the token recurrence ``scan_ragged`` for ragged rows, whole
against chunked to the bit; the program against ``bench/references/mamba1_mqa.py``
(float32, precision "highest", the recurrence a token at a time, no cache; it
imports nothing from the program), logprobs, never tokens: a prompt chunked
unevenly over mixed steps beside another row, then decode through the paged
cache and the state store; the three mutants (a bfloat16 state, no inner norms,
no reset at position 0) each FAIL the same comparison; a group of 20 over one KV
head through both paged paths against the gather path; a slot admitted again and
a preempted row recomputed; the counters and the status by hand; the refusals.
"""

import contextlib
import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import jamba
from inference_gateway_tpu.ops import paged_attention as pa
from inference_gateway_tpu.ops import ssd, ssm
from inference_gateway_tpu.ops.quant import QTensor
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from tests.test_lfm2 import admit, decode   # the same drivers: rows through mixed steps, then decode chunks

BENCH = Path(__file__).resolve().parent.parent / "bench"
PRESET = "jamba-test-tiny"
CFG = jamba.PRESETS[PRESET]
MODEL = {  # the preset as the reference reads it: the source's keys
    "hidden_size": 64, "num_hidden_layers": 7, "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
    "vocab_size": 256, "intermediate_size": 128, "rms_norm_eps": 1e-6, "mamba_d_state": 8, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4, "mamba_conv_bias": True, "mamba_proj_bias": False, "attn_layer_offset": 1,
    "attn_layer_period": 4, "num_experts": 1, "hidden_act": "silu", "sliding_window": None, "tie_word_embeddings": True}
# The reference with ONE mechanism changed: what a program that lacked it would compute.
MUTANTS = {
    "a_state_kept_in_bfloat16": {"state_dtype": "bfloat16"},
    "b_inner_norms_left_out": {"inner_norms": False},
    "c_state_not_zeroed_at_position_0": {"state_from_previous": True},
    "c36_state_lost_at_chunk_boundaries": {"state_restart_every": 36},
}


def load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load(BENCH / "references" / "mamba1_mqa.py", "bench_reference_mamba1_mqa")


def f32(w):
    return w.q.astype(jnp.float32) * w.scale if isinstance(w, QTensor) else w.astype(jnp.float32)


def reference_logits(params, ids, model=MODEL):
    """The reference over ``ids`` with the program's weights as data, a layer's
    leaves sliced as the benchmark's child slices them: EVERY declared leaf at
    the index asked for, clamped to the leaf's own leading axis."""
    def layer(i):
        return {n: f32(jax.tree.map(lambda a: a[min(i, a.shape[0] - 1)], params["layers"][n])) for n in REF.LEAVES}

    return REF.forward(ids, params["embed"], layer, f32(params["final_norm"]), f32(params["embed"]).T,
                       REF.sizes(model))[:]


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def engine(dtype="float32", quantize=None, **kw):
    kw = {"page_size": 8, "max_slots": 4, "max_seq_len": 256, "mixed_step": True, "mixed_step_tokens": 40,
          "decode_chunk": 4, "use_mesh": False, **kw}
    return Engine(EngineConfig(model=PRESET, attention="paged", dtype=dtype, quantize=quantize, **kw))


def test_the_presets_follow_the_source():
    big = jamba.PRESETS["jamba2-3b"]
    assert [l for l, k in enumerate(big.layer_kinds) if k == 0] == [7, 21] and big.state_layers == 26
    assert (big.d_inner, big.mamba_d_state, big.mamba_d_conv, big.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (big.num_heads, big.num_kv_heads, big.hd, big.intermediate_size, big.vocab_size) == (20, 1, 128, 8192, 65536)
    assert big.layer_rope == (0,) * 28 and big.tie_word_embeddings and big.rms_norm_eps == 1e-6
    assert big.state_bytes_per_slot == 26 * 389_120 == 10_117_120
    # the embedding 167,772,160 + 28 x (an MLP 62,914,560 + two norms) + 26 mixers of 41,241,792 + 2 x 13,762,560 + a norm
    assert big.param_count() == 3_029_337_472
    tree = jax.eval_shape(lambda: jamba.init_params(jax.random.PRNGKey(0), CFG))
    assert CFG.param_count() == sum(a.size for a in jax.tree.leaves(tree))
    assert CFG.layer_kinds == (1, 0, 1, 1, 1, 0, 1)
    with pytest.raises(ValueError, match="both kinds"):
        jamba.JambaConfig(num_layers=4, attn_layer_period=8, attn_layer_offset=5)


def test_three_stacks_under_names_of_their_own():
    eng = engine("bfloat16", "int8")
    layers = eng.params["layers"]
    lead = {n: jax.tree.leaves(layers[n])[0].shape[0] for n in layers}
    assert {lead[n] for n in jamba.MAMBA_LEAVES} == {5} and {lead[n] for n in jamba.ATTENTION_LEAVES} == {2}
    assert {lead[n] for n in jamba.MLP_LEAVES + ("attn_norm", "mlp_norm")} == {7}
    assert all(isinstance(layers[n], QTensor) for n in jamba.QUANT_LEAVES)
    assert layers["x_proj"].dtype == layers["dt_proj"].dtype == jnp.bfloat16
    assert layers["A_log"].dtype == layers["dt_bias"].dtype == layers["conv_w"].dtype == jnp.float32
    assert eng.cache["ssm"].shape == (5, 4, 8, 128) and eng.cache["ssm"].dtype == jnp.float32
    assert eng.cache["conv"].shape == (5, 4, 3, 128) and eng.cache["k"].shape[0] == 2


# ---- the chunk form against the recurrence --------------------------------------------------------

# (lengths a slot, where each row's first token stands): rows that share packed blocks of 64, start at
# position 0 and mid-row, one-token rows, an empty slot; packed in slot order. And (ISSUE 65: the chunk kernel's
# visit axis ends at the plan's last live visit) plans whose live visits are far under the bound (one long row
# beside 15 of one token), AT it (every row starts mid-block, in the block the row before ends in) and none at all
# (every row one token: visit 0 alone runs). GRID: the visits the kernel's grid runs, and the plan's bound.
RAGGED = {
    "one_long_row_beside_one_token_rows": ([1, 150, 1, 0, 1], [9, 0, 0, 0, 30]),
    "rows_that_share_a_block": ([20, 30, 2, 70, 3], [0, 64, 5, 100, 0]),
    "a_row_that_ends_on_a_block": ([64, 64, 1, 0, 0], [0, 7, 0, 0, 0]),
    "live_visits_far_under_the_bound": ([1] * 7 + [120] + [1] * 8, [9, 0, 4, 0, 30, 2, 1, 50] + [3] * 8),
    "live_visits_at_the_bound": ([40, 60, 92], [0, 64, 5]),
    "no_live_visit": ([1, 1, 1, 1, 1], [9, 0, 4, 0, 30]),
}
GRID = {"live_visits_far_under_the_bound": (2, 3 + 15), "live_visits_at_the_bound": (5, 5), "no_live_visit": (1, 3 + 4)}


@contextlib.contextmanager
def forms(kernel: bool):
    """The mixed step's scan by the kernels, interpreted (the chunk kernel and the
    one-token kernel), or by their plain forms (the twin and ``scan_decode``)."""
    chunk, entry = ssm.scan_chunk, ssm._decode_entry
    if kernel:
        ssm.scan_chunk = functools.partial(ssm.scan_chunk_tpu, interpret=True)
        ssm._decode_entry = lambda store, o, d, c, B, C, A, D, rows, fresh=None: ssm.scan_decode_tpu(
            store, o, d, c, B, C, A, D, rows, jnp.zeros_like(rows) if fresh is None else fresh, interpret=True)
    else:
        ssm.scan_chunk = ssm.scan_chunk_twin
    try:
        yield
    finally:
        ssm.scan_chunk, ssm._decode_entry = chunk, entry


def scan_case(lens, base, kernel, T=192, seed=0):
    rng = np.random.default_rng(seed)
    S, Di, N = len(lens), 256, 8
    lens, base = np.asarray(lens, np.int32), np.asarray(base, np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (T, Di))), jnp.float32)
    c, B, C, D, store = arr(T, Di), arr(T, N), arr(T, N), arr(Di), arr(2, S, N, Di)
    A = -jnp.abs(arr(N, Di)) - 0.1
    positions = np.zeros(T, np.int32)
    for s in range(S):
        positions[starts[s]:starts[s] + lens[s]] = base[s] + np.arange(lens[s])
    qs, ql, kv = jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(base + lens)
    want_y, want_s = ssm.scan_ragged(store[1], delta, c, B, C, A, D, ssm.token_segments(qs, ql, T),
                                     jnp.asarray(positions), int(lens.sum()))
    with forms(kernel):
        scan, _, _, counts = ssm.ragged_scan(qs, ql, kv, T)
        got_y, got_s = jax.jit(lambda s: scan(s, 1, delta, c, B, C, A, D))(store)
    return got_y, got_s, want_y, want_s, store, int(lens.sum()), counts


# (the twin walks the plan's visits as it did: ISSUE 65's plans go through the kernel alone)
FORMS = [(case, kernel) for case in RAGGED for kernel in (False, True) if kernel or case not in GRID]


@pytest.mark.parametrize("case,kernel", FORMS, ids=[f"{c}-{'interpreted_kernel' if k else 'twin'}" for c, k in FORMS])
def test_the_chunk_form_is_the_token_recurrence_for_ragged_rows(case, kernel):
    lens, base = RAGGED[case]
    got_y, got_s, want_y, want_s, store, n, counts = scan_case(lens, base, kernel)
    np.testing.assert_allclose(np.asarray(got_y[:n]), np.asarray(want_y[:n]), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s[1]), np.asarray(want_s), atol=2e-6, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(store[0]))      # the other layer's entry as it was
    more = [l for l in lens if l > 1]
    bound = ssd.max_visits(192, len(lens), ssm.CHUNK_TOKENS)
    assert [float(v) for v in counts] == [sum(more), len(more), sum(l == 1 for l in lens), bound]
    if case in GRID:
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        meta, _ = ssd.block_plan(*(jnp.asarray(a, jnp.int32) for a in (starts, lens, np.add(base, lens))), 192, ssm.CHUNK_TOKENS)
        assert (int(ssd.live_visits(meta)), meta.shape[0]) == GRID[case] and meta.shape[0] == bound


@pytest.mark.parametrize("kernel", [False, True], ids=["twin", "interpreted_kernel"])
def test_a_row_run_whole_and_run_in_chunks_leaves_the_same_state_to_the_bit(kernel):
    """THE INVARIANT: 150 tokens as one row, and as rows of 37, 65 and 48 over
    three steps at another packed position behind another row: the same y and the
    same last state, bit for bit in the twin (one sequence of operations a token,
    whatever the cut; the interpreted kernel to float32's rounding). With a ONE-token row among the cuts (37, 1, 64, 48: ``scan_decode``,
    another program of the same operations) the same to float32's rounding: the
    CPU's compiler may contract a multiply-add in one program and not in the
    other; the chip's vector unit has none to contract (0.0 against the
    recurrence there: PERF.md section 6, PR 64)."""
    rng = np.random.default_rng(3)
    Di, N, n = 256, 8, 150
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (n, Di))), jnp.float32)
    c, B, C, D = arr(n, Di), arr(n, N), arr(n, N), arr(Di)
    A = -jnp.abs(arr(N, Di)) - 0.1
    def run(cuts, lead):
        """The row in slot 1 in chunks ``cuts``, each step's chunk behind ``lead`` one-token rows of slot 0."""
        store, ys, done = jnp.zeros((1, 2, N, Di), jnp.float32), [], 0
        for cut in cuts:
            T = 192
            pad = lambda a: jnp.zeros((T,) + a.shape[1:], a.dtype).at[lead:lead + cut].set(a[done:done + cut]).at[:lead].set(1.0)
            lens, base = np.asarray([lead, cut], np.int32), np.asarray([500, done], np.int32)
            scan, *_ = ssm.ragged_scan(jnp.asarray([0, lead], jnp.int32), jnp.asarray(lens), jnp.asarray(base + lens), T)
            y, store = scan(store, 0, pad(delta), pad(c), pad(B), pad(C), A, D)
            ys.append(y[lead:lead + cut])
            done += cut
        return jnp.concatenate(ys), store[0, 1]

    with forms(kernel):
        whole_y, whole_s = run([150], 0)
        cut_y, cut_s = run([37, 65, 48], 1)
        one_y, one_s = run([37, 1, 64, 48], 1)
    if kernel:   # interpreted, the kernel's body is the CPU compiler's too: each program's own contractions
        np.testing.assert_allclose(np.asarray(whole_y), np.asarray(cut_y), atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(whole_s), np.asarray(cut_s), atol=2e-6, rtol=2e-6)
    else:
        np.testing.assert_array_equal(np.asarray(whole_y), np.asarray(cut_y))
        np.testing.assert_array_equal(np.asarray(whole_s), np.asarray(cut_s))
    np.testing.assert_allclose(np.asarray(whole_y), np.asarray(one_y), atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(whole_s), np.asarray(one_s), atol=2e-6, rtol=2e-6)


def test_the_one_token_kernel_is_scan_decode_on_the_rows_that_advance():
    """``ssm_scan_decode_kernel``, interpreted: the rows that advance (one of them from zero: its token at
    position 0) get ``scan_decode``'s y and state; a slot that does not advance keeps its bits and reads a y
    of zero; the other layer's entry is as it was."""
    rng = np.random.default_rng(7)
    S, Di, N = 6, 256, 8
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (S, Di))), jnp.float32)
    c, B, C, D, store = arr(S, Di), arr(S, N), arr(S, N), arr(Di), arr(2, S, N, Di)
    A = -jnp.abs(arr(N, Di)) - 0.1
    valid, fresh = jnp.asarray([1, 0, 1, 1, 0, 1], bool), jnp.asarray([0, 0, 1, 0, 1, 0], bool)
    want_y, want_s = ssm.scan_decode(store[1], delta, c, B, C, A, D, valid, fresh)
    got_y, got_s = ssm.scan_decode_tpu(store, 1, delta, c, B, C, A, D, valid, fresh, interpret=True)
    on = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got_y)[on], np.asarray(want_y)[on], atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got_s[1])[on], np.asarray(want_s)[on], atol=2e-6, rtol=2e-6)
    assert not np.asarray(got_y)[~on].any()
    np.testing.assert_array_equal(np.asarray(got_s[1])[~on], np.asarray(store[1])[~on])
    np.testing.assert_array_equal(np.asarray(got_s[0]), np.asarray(store[0]))
    none_y, none_s = ssm.scan_decode_tpu(store, 0, delta, c, B, C, A, D, jnp.zeros_like(valid), fresh, interpret=True)
    assert not np.asarray(none_y).any()
    np.testing.assert_array_equal(np.asarray(none_s), np.asarray(store))


def test_the_plan_is_the_kernels_on_a_tpu_and_the_twins_elsewhere(monkeypatch):
    assert (ssm.chunk_plan(5120, 16)["path"], ssm.chunk_plan(5120, 16)["one_token_rows"]) == ("scan_chunk_twin", "scan_decode")
    monkeypatch.setattr("inference_gateway_tpu.utils.platform.device_platform", lambda: "tpu")
    assert (ssm.chunk_plan(100, 16)["path"], ssm.chunk_plan(100, 16)["one_token_rows"]) == ("scan_chunk_twin", "scan_decode")
    plan = ssm.chunk_plan(5120, 16)
    assert (plan["path"], plan["one_token_rows"]) == ("ssm_scan_chunk_kernel", "ssm_scan_decode_kernel")
    assert (plan["token_block"], plan["channel_block"], plan["grid"]) == (64, 5120, "live visits")
    assert ssd.max_visits(544, 32, plan["token_block"]) == 40


# ---- the program against the reference ---------------------------------------------------------------

# Activations in bf16 and weights in int8 are held to the benchmark's own limits, as its child holds them.
CASES = [("float32", None), ("bfloat16", "int8")]


def gaps(eng, seq, start, served, model=MODEL):
    """|served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1], model), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return np.abs(want - np.asarray(served, np.float32))


def serve_two_rows(eng):
    """Slot 0 takes a 40-token prompt in chunks of 1, 2, 3 and then 34 tokens (a
    one-token row from position 0; every way a chunk can end inside the
    convolution's reach) and decodes; slot 1's 150-token prompt then goes in over
    five mixed steps of 36 behind slot 0's decode row; both rows then decode two
    chunks through the paged cache and the state store. Returns [(sequence, where
    the served tokens start, their logprobs)] for both rows."""
    a, b = prompt(40, 3), prompt(150, 4)
    tok, lp, stats = admit(eng, 0, a, chunks=(1, 2, 3, 34))
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


@pytest.fixture(scope="module")
def served_rows():
    """The int8 / bfloat16 engine's two rows, served once for the comparisons below."""
    eng = engine("bfloat16", "int8")
    return eng, serve_two_rows(eng)


@pytest.fixture(scope="module")
def float32_rows():
    """The float32 engine's two rows, served once."""
    eng = engine()
    return eng, serve_two_rows(eng)


def test_chunked_prompt_then_decode_matches_the_reference_in_float32(float32_rows):
    eng, rows = float32_rows
    assert eng.mixed_ok and eng.carries_state and eng._model is jamba and eng.mesh is None
    diffs = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in rows])
    assert len(diffs) == 23 and diffs.max() < 5e-5, diffs.max()


def test_the_served_precision_reads_correct_by_the_benchmarks_limits(served_rows):
    eng, rows = served_rows
    diffs = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in rows])
    largest, mean = benchmark_limits()
    assert diffs.max() <= largest and diffs.mean() <= mean, (diffs.max(), diffs.mean())


@pytest.mark.parametrize("mutant", list(MUTANTS))
def test_each_mutant_fails_the_float32_comparison(float32_rows, mutant):
    """In float32, where nothing else moves, the reference with one mechanism
    changed reads tens to thousands of times the float32 tolerance from the
    served logprobs: a program that lacked the mechanism would fail the float32
    case above. (The bfloat16 state is the smallest: 150 tokens round it 150 times.)"""
    eng, rows = float32_rows
    seq, start, served = rows[1]   # the 150-token prompt in five chunks, then eight decode steps
    assert gaps(eng, seq, start, served, {**MODEL, "assumed_mechanisms": MUTANTS[mutant]}).max() > 20 * 5e-5


@pytest.mark.parametrize("mutant", [m for m in MUTANTS if not m.startswith("a_")])
def test_each_mutant_reads_not_correct_by_the_benchmarks_limits(served_rows, mutant):
    """The comparison that decides the benchmark's ``correct`` has to SEE each
    mechanism: the rows served in int8 / bfloat16 against the reference with the
    mechanism changed read NOT correct by the benchmark's rule (the largest
    difference past 0.45 or the mean past 0.12), and their mean is more than
    five times the sound one's on the same rows (measured: 25 to 60 times). The
    bfloat16 state needs the benchmark's thousands of tokens to show at these
    limits (``hack/jamba_correct_control.py`` on the chip); here it fails the
    float32 comparison above."""
    eng, rows = served_rows
    sound = np.concatenate([gaps(eng, seq, start, served) for seq, start, served in rows])
    model = {**MODEL, "assumed_mechanisms": MUTANTS[mutant]}
    diffs = np.concatenate([gaps(eng, seq, start, served, model) for seq, start, served in rows])
    largest, mean = benchmark_limits()
    assert diffs.max() > largest or diffs.mean() > mean, (mutant, diffs.max(), diffs.mean())
    assert diffs.mean() > 5 * sound.mean(), (mutant, diffs.mean(), sound.mean())


def test_a_bfloat16_state_in_the_program_fails_the_float32_comparison(monkeypatch):
    """The state stays float32 whatever the activations are: with the store
    rounded to bfloat16 after every scan that writes it, a 150-token prompt and
    eight decode steps later the served logprobs fail the float32 tolerance by
    orders of magnitude."""
    def served(eng):
        ids = prompt(150, 4)
        tok, lp, _ = admit(eng, 1, ids)
        toks, lps, _ = decode(eng, {1: (tok, 150)}, 2)
        return gaps(eng, ids + [tok] + toks[1], 150, [lp] + lps[1])

    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    mixer = ssm.mamba_mixer

    def drifting(h, mp, store, ordinal, conv, scan, eps=1e-6):
        return mixer(h, mp, store, ordinal, conv, lambda *a: (lambda y, s: (y, rounded(s)))(*scan(*a)), eps)

    monkeypatch.setattr(ssm, "mamba_mixer", drifting)
    jax.clear_caches()
    try:
        drifted = served(engine())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert drifted.max() > 20 * 5e-5, drifted.max()


def test_a_slot_admitted_again_and_a_row_recomputed_need_no_reset():
    """A row that starts at position 0 starts from zero whatever the slot held:
    slot 0 serves one request, then another with no reset in between, and the
    row is recomputed from position 0 after its pages were given up
    (``_preempt``'s recompute, other chunks): the logprobs are the reference's
    for a fresh sequence, and ``state_resets`` counts the three."""
    eng = engine()
    first = prompt(30, 21)
    admit(eng, 0, first)
    decode(eng, {0: (5, 30)}, 1)                               # the slot's state now holds the first request's
    second = prompt(45, 22)
    tok, lp, _ = admit(eng, 0, second, chunks=(7, 36))
    assert gaps(eng, second + [tok], 45, [lp]).max() < 5e-5
    eng.allocator.release(0)
    tok, lp, _ = admit(eng, 0, second[:33], chunks=(2, 36))    # recomputed: the same tokens again, other chunks
    assert gaps(eng, second[:33] + [tok], 33, [lp]).max() < 5e-5
    assert eng.metrics["state_resets"] == 3 and eng.family_status()["state_resets"] == 3


def test_the_counters_and_the_status_by_hand():
    eng = engine()
    _, _, stats = admit(eng, 0, prompt(40, 3), chunks=(1, 7, 32))
    assert [s["ssm_chunk_tokens"] for s in stats] == [0.0, 7.0 * 5, 32.0 * 5]     # x the five Mamba layers
    assert [s["ssm_chunk_rows"] for s in stats] == [0.0, 5.0, 5.0]
    assert [s["state_rows"] for s in stats] == [1.0, 0.0, 0.0]                    # the first chunk is ONE token
    _, _, stats = admit(eng, 1, prompt(36, 5), beside={0: [5, 40, [], []]})
    assert (stats[0]["ssm_chunk_tokens"], stats[0]["ssm_chunk_rows"], stats[0]["state_rows"]) == (36.0 * 5, 5.0, 1.0)
    assert stats[0]["chunk_visits_bound"] == 5.0 * ssd.max_visits(eng.mixed_budget, 4, ssm.CHUNK_TOKENS)   # x 5 layers
    assert stats[0]["state_bytes"] == 2 * CFG.state_bytes_per_slot == 2 * 5 * (8 + 3) * 128 * 4
    _, _, stats = decode(eng, {0: (5, 41), 1: (6, 36)}, 1)
    assert stats[0]["ssm_chunk_tokens"] == 0.0 and stats[0]["state_rows"] == 2.0 * 4 and stats[0]["chunk_visits_bound"] == 0.0
    status = eng.family_status()
    assert status["state_store"] == {"slots": 4, "layers": 5, "bytes_per_slot": CFG.state_bytes_per_slot,
                                     "bytes": 4 * CFG.state_bytes_per_slot}
    assert status["cache_layers"] == {"full": 2, "mamba": 5}
    assert status["ssm_scan"]["rows_of_more_than_one_token"]["path"] == "scan_chunk_twin"
    assert eng._mixed_stats == eng._chunk_stats == jamba.SSM_STATS
    assert eng.ragged_attention_plan["rows_per_pass"] % 4 == 0


def test_what_the_state_rules_out_is_refused_or_declined_by_name():
    from inference_gateway_tpu.models.family import family_of, preset_config
    from inference_gateway_tpu.serving.engine import choose_mesh

    assert preset_config("jamba2-3b") is jamba.PRESETS["jamba2-3b"] and family_of(CFG).state_store
    with pytest.raises(ValueError, match="no mesh"):
        choose_mesh(EngineConfig(model=PRESET, attention="paged", mesh_shape={"tp": 2}), CFG, 4)
    assert choose_mesh(EngineConfig(model=PRESET, attention="paged"), CFG, 4) == (None, False)
    with pytest.raises(ValueError, match="speculative decoding"):
        Engine(EngineConfig(model=PRESET, attention="paged", spec_draft="test-tiny", use_mesh=False))
    with pytest.raises(ValueError, match="int8 is the one"):
        Engine(EngineConfig(model=PRESET, attention="paged", quantize="int4", use_mesh=False))
    eng = engine()
    assert eng.prefix_cache is None or eng.prefix_cache.decline_hits
    with pytest.raises(NotImplementedError, match="admits through the mixed step"):
        jamba.forward_paged(eng.params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None], jnp.asarray([8]),
                            eng.cache, jnp.arange(8)[None], jnp.zeros((1, 32), jnp.int32), mode="prefill")
    with pytest.raises(NotImplementedError, match="paged cache alone"):
        jamba.forward()


# ---- a group of 20 over ONE KV head ------------------------------------------------------------------

def test_a_group_of_twenty_over_one_kv_head_through_both_paged_kernels():
    """(Hq 20, Hkv 1, D 128): the first group wider than a tile's 8 sublanes and
    not a multiple of them, the first pool of one KV head. Both kernels,
    interpreted, against the gather path on the same pages."""
    rng = np.random.default_rng(20)
    Hq, Hkv, D, page, S, maxp = 20, 1, 128, 16, 4, 6
    pool = lambda: jnp.asarray(rng.normal(size=(S * maxp, page, Hkv * D)), jnp.float32)
    k, v = pool(), pool()
    table = jnp.asarray(rng.permutation(S * maxp).reshape(S, maxp), jnp.int32)
    lengths = jnp.asarray([90, 0, 17, 64], jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, Hq, D)), jnp.float32)
    got = pa.paged_attention_tpu(q, k, v, table, lengths, Hkv, interpret=True)
    want = pa.paged_attention_jax(q, k, v, table, lengths, Hkv)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-5, rtol=1e-5)
    lens = np.asarray([40, 0, 1, 7], np.int32)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
    T = 56
    qr = jnp.asarray(rng.normal(size=(T, Hq, D)), jnp.float32)
    args = (qr, k, v, table, jnp.asarray(starts), jnp.asarray(lens), jnp.asarray([90, 0, 17, 64], jnp.int32))
    got = pa.ragged_paged_attention_tpu(*args, Hkv, interpret=True)
    want = pa.ragged_paged_attention_jax(*args, Hkv)
    np.testing.assert_allclose(np.asarray(got)[:48], np.asarray(want)[:48], atol=2e-5, rtol=1e-5)
    plan = pa.ragged_tile_plan(Hq, Hkv, D, 128, 100)
    assert plan["wide_tile"] == 48 and plan["rows_per_pass"] == 960     # 512 KB of scores / (4 B x 20 x 128), on the sublane grid
    assert pa.decode_walk_plan(Hq, Hkv, D, 128, 100)["pages_per_visit"] == 28


# ---- the configuration's files (bench/tests/test_jamba2_files.py), collected as they stand ------------
# (As tests/test_bench_reference.py collects the other configurations' checks; here, so that the longest
# file of tier-1 grows by nothing.)
sys.path.insert(0, str(BENCH))
_child = load(BENCH / "tests" / "test_reference_child.py", "bench_test_reference_child_for_jamba")
sys.modules.setdefault("test_reference_child", _child)
_files = load(BENCH / "tests" / "test_jamba2_files.py", "bench_test_jamba2_files")
globals().update({f"{name}_files": fn for name, fn in vars(_files).items() if name.startswith("test_")})


def test_the_child_builds_the_sidecars_tree():
    """The reference child's engine (a one-row dense cache, which this family builds and refuses to serve
    through) holds the serving engine's weights bit for bit."""
    _child.test_the_childs_weights_are_the_serving_engines_bit_for_bit(PRESET, "int8")
