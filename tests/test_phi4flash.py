"""Phi-4-mini-flash on the normal path (ISSUE 32), at the tiny preset on the CPU:
eight layers that hold all five kinds (3 Mamba mixers, 2 windowed and 1 full
differential attention, 1 gated memory unit, 1 cross attention), a window of 16
under contexts of 40 and more, seeded random weights with the published
initialisation of the recurrence.

The program against ``bench/references/sambay_diff.py`` (float32, precision
"highest", no cache, no kernels, one ``lax.scan`` for the state; it imports
nothing from the program): prefill through one mixed step, a prompt chunked
over three mixed steps, decode through the state store and the caches. Then the
state's lifecycle (a reused slot, a packed decode row beside a prompt chunk, a
preempted request, identical requests), the zero-padded query form against the
four-softmax form of the published code, the state operations against a
token-by-token loop, and what the engine refuses by name.
"""

import importlib.util
import math
import queue
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from inference_gateway_tpu.models import family as family_mod
from inference_gateway_tpu.models import phi4flash
from inference_gateway_tpu.ops import ssm
from inference_gateway_tpu.ops.quant import QTensor
from inference_gateway_tpu.serving.engine import Engine, EngineConfig, MixedRow
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler

PRESET = "phi4flash-test-tiny"
CFG = phi4flash.PRESETS[PRESET]
MODEL = {  # the preset as the reference reads it: the source's keys and the assumed ones
    "hidden_size": 64, "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "vocab_size": 256, "sliding_window": 16, "layer_norm_eps": 1e-5,
    "mb_per_layer": 2, "hidden_act": "silu", "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4}


def load_reference():
    path = Path(__file__).resolve().parent.parent / "bench" / "references" / "sambay_diff.py"
    spec = importlib.util.spec_from_file_location("bench_reference_sambay_diff", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def f32(w):
    return w.q.astype(jnp.float32) * w.scale if isinstance(w, QTensor) else w.astype(jnp.float32)


def reference_logits(params, ids):
    """The reference over ``ids`` with the program's weights as data, sliced
    as ``bench/reference.py`` slices them: every leaf at one index, clamped."""
    def layer(i):
        return {n: f32(jax.tree.map(lambda a: a[min(max(i, 0), a.shape[0] - 1)], params["layers"][n]))
                for n in REF.LEAVES}

    return REF.forward(ids, params["embed"], layer, f32(params["final_norm"]), f32(params["embed"]).T,
                       REF.sizes(MODEL))


def prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(0, CFG.vocab_size, size=n)]


def engine(dtype="float32", quantize=None, **kw):
    base = dict(model=PRESET, attention="paged", page_size=8, max_slots=4, max_seq_len=128, dtype=dtype,
                quantize=quantize, mixed_step_tokens=64, use_mesh=False, prefill_buckets=(16, 32, 64),
                decode_chunk=4)
    return Engine(EngineConfig(**{**base, **kw}))


# |served - reference| of a token's logprob, in nats (ops/quant.seeded_std:
# the recurrence in its published regime, logits of scale 0.16 at this
# width). float32: the two sides differ in the order of their sums alone,
# measured 0 to 5e-7 over eight layers of width 64 (sandbox, PR 32); 5e-6 is
# what a state rounded to bfloat16 between steps breaks fourteenfold (7e-5)
# and a stale state by four orders (0.03): the tests below. bfloat16 / int8:
# the served side rounds activations and the residual stream to bf16 while the
# reference keeps float32 on the same (dequantised) weights: measured
# 0.0007-0.004; a stale or leaked state moves a logprob by 0.03 in every type.
TOLERANCE = {"float32": 5e-6, "bfloat16": 0.01}
CASES = [("float32", None), ("bfloat16", None), ("bfloat16", "int8")]


def logprob_gap(eng, seq, start, served):
    """Largest |served - reference| over the tokens seq[start:]."""
    logp = jax.nn.log_softmax(reference_logits(eng.params, seq[:-1]), axis=-1)
    want = np.asarray(logp[np.arange(start - 1, len(seq) - 1), np.asarray(seq[start:])])
    return float(np.max(np.abs(want - np.asarray(served, np.float32))))


def admit(eng, slot, ids, chunk=32, between=None):
    """``ids`` into ``slot`` through mixed steps of at most ``chunk`` prompt
    tokens; ``between(eng)`` runs between two steps. Returns (token, logprob)."""
    t = lp = None
    for start in range(0, len(ids), chunk):
        if start and between is not None:
            between(eng)
        h = eng.mixed_step_submit([MixedRow(slot=slot, token_ids=ids[start:start + chunk], start=start,
                                            kind="prefill")])
        t, lp = eng.mixed_step_fetch(h)
    return int(t[slot]), float(lp[slot])


def decode(eng, rows, chunks, n_steps=None, between=None):
    """Greedy decode of ``rows`` {slot: (token, position)} for ``chunks``
    chunks, the first fresh, the others chained (or all fresh when ``between``
    touches the cache). Returns {slot: (tokens, logprobs)} and the stats."""
    S = eng.config.max_slots
    t, p, active = np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool)
    for s, (tok, pos) in rows.items():
        t[s], p[s], active[s] = tok, pos, True
    out = {s: ([], []) for s in rows}
    stats = []
    for i in range(chunks):
        chain = i > 0 and between is None
        h = eng.decode_chunk_submit(t, p, active, np.zeros(S, np.float32), np.ones(S, np.float32),
                                    n_steps=n_steps, chain=chain)
        a, b = eng.decode_chunk_fetch(h)
        stats.append(h.stats)
        for s in rows:
            out[s][0].extend(int(x) for x in a[:, s])
            out[s][1].extend(float(x) for x in b[:, s])
            t[s], p[s] = a[-1, s], p[s] + a.shape[0]
        if between is not None:
            between(eng)
    return out, stats


def test_presets_follow_the_source_and_the_engine_knows_them_by_name():
    cfg = phi4flash.PRESETS["phi-4-mini-flash"]
    assert family_mod.PHI4FLASH_PRESETS == tuple(phi4flash.PRESETS)
    assert (cfg.hidden_size, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.hd) == (2560, 32, 40, 20, 64)
    assert (cfg.intermediate_size, cfg.vocab_size, cfg.sliding_window) == (10240, 200064, 512)
    assert (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (cfg.state_layers, cfg.mid, cfg.cross_layers, cfg.cache_layers) == (9, 8, 7, 9)
    assert cfg.state_bytes_per_slot == 9 * (327_680 + 61_440)
    # ISSUE 32's hand count of the matrices and the embedding: the published 3.8 B.
    matrices = (32 * 78_643_200 + 9 * 41_123_840 + 9 * 19_660_800 + 7 * 2 * 13_107_200 + 7 * 13_107_200
                + 200_064 * 2560)
    assert matrices == 3_851_059_200
    small = cfg.param_count() - matrices  # norms, biases, convolution, A, D, dt bias, lambdas
    assert 0 < small < 0.001 * matrices
    for c in (cfg, CFG):  # all five kinds in both
        assert c.mid >= 2 and c.cross_layers >= 1 and c.num_heads // c.num_kv_heads == 2


def test_the_tree_is_stacked_by_pair_and_quantised_where_the_file_says():
    eng = engine("bfloat16", "int8")
    layers = eng.params["layers"]
    P, M = CFG.pairs, CFG.mid
    assert set(layers) == set(REF.LEAVES)
    assert {n for n, w in layers.items() if isinstance(w, QTensor)} == set(phi4flash.QUANT_LEAVES)
    lead = {n: jax.tree.leaves(w)[0].shape[0] for n, w in layers.items()}
    assert all(lead[n] == P for n in phi4flash.PAIR_LEAVES)
    assert all(lead[n] == M + 1 for n in phi4flash.MAMBA_LEAVES) and lead["g_in"] == P - M - 1
    assert eng.params["final_norm"].shape == (2, CFG.hidden_size) and "lm_head" not in eng.params
    # The published initialisation of the recurrence.
    np.testing.assert_allclose(np.exp(np.asarray(layers["m_A_log"][0, 0])), np.arange(1, CFG.mamba_d_state + 1),
                               rtol=1e-6)
    step = np.log1p(np.exp(np.asarray(layers["m_dt_b"])))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001 and np.asarray(layers["m_D"]).min() == 1.0
    assert layers["m_A_log"].dtype == layers["m_dt_b"].dtype == eng.cache["ssm"].dtype == jnp.float32


@pytest.mark.parametrize("dtype,quantize", CASES)
def test_prefill_chunked_prefill_and_decode_match_the_reference(dtype, quantize):
    """Slot 0: a 40-token prompt through ONE mixed step. Slot 1: a 70-token
    prompt chunked over THREE mixed steps (32 + 32 + 6: the state, the
    convolution's tail and the window all cross the chunk edges). Then both
    decode eight tokens through fresh and chained chunks: positions 40-47 and
    70-77 lie three and more windows of 16 deep, in pages of 8."""
    eng = engine(dtype, quantize)
    assert eng.mixed_ok and eng._model is phi4flash and eng.mesh_axes == {"tp": 1}
    a, b = prompt(40, 1), prompt(70, 2)
    ta, la = admit(eng, 0, a, chunk=64)
    tb, lb = admit(eng, 1, b, chunk=32)
    out, _ = decode(eng, {0: (ta, 40), 1: (tb, 70)}, 2)
    assert logprob_gap(eng, a + [ta] + out[0][0], 40, [la] + out[0][1]) < TOLERANCE[dtype]
    assert logprob_gap(eng, b + [tb] + out[1][0], 70, [lb] + out[1][1]) < TOLERANCE[dtype]
    assert eng.metrics["state_resets"] == 2


def test_a_state_kept_in_bfloat16_fails_the_float32_tolerance():
    """The same float32 engine and path, its state store rounded to bfloat16
    between steps (what a bfloat16 store would hold): over 70 prompt tokens in
    three chunks and eight decode steps the logprobs leave the reference by
    ten times the float32 tolerance and more."""
    def rounded(eng):
        eng.cache = {**eng.cache, "ssm": eng.cache["ssm"].astype(jnp.bfloat16).astype(jnp.float32)}

    ids = prompt(70, 2)
    gaps = {}
    for name, between in (("float32", None), ("bfloat16", rounded)):
        eng = engine()
        tok, lp = admit(eng, 0, ids, chunk=32, between=between)
        if between is not None:
            between(eng)
        out, _ = decode(eng, {0: (tok, 70)}, 8, n_steps=1, between=between)
        gaps[name] = logprob_gap(eng, ids + [tok] + out[0][0], 70, [lp] + out[0][1])
    assert gaps["float32"] < TOLERANCE["float32"] < 10 * TOLERANCE["float32"] < gaps["bfloat16"], gaps


def test_a_reused_slot_starts_from_zero_and_a_stale_state_fails():
    """Slot 0 serves one request, is released and serves another: the second
    starts at position 0, so from a zeroed state, whatever the slot held. The
    same prompt with the first request's state put back between its chunks
    (a stale state) leaves the reference by a thousand times the tolerance and more."""
    eng = engine()
    a, b = prompt(45, 3), prompt(50, 4)
    ta, _ = admit(eng, 0, a, chunk=64)
    decode(eng, {0: (ta, 45)}, 1)
    stale = {k: eng.cache[k] for k in ("ssm", "conv")}
    stale = jax.tree.map(lambda x: x + 0, stale)  # a copy the next donated step cannot take
    eng.release_slot(0)
    tb, lb = admit(eng, 0, b, chunk=64)
    assert logprob_gap(eng, b + [tb], 50, [lb]) < TOLERANCE["float32"]
    assert eng.metrics["state_resets"] == 2

    eng.release_slot(0)
    tb2, lb2 = admit(eng, 0, b, chunk=32, between=lambda e: setattr(e, "cache", {**e.cache, **stale}))
    assert logprob_gap(eng, b + [tb2], 50, [lb2]) > 1000 * TOLERANCE["float32"]


@pytest.mark.parametrize("dtype,quantize", CASES)
def test_a_decode_row_and_a_prompt_chunk_in_one_mixed_step_do_not_leak_state(dtype, quantize):
    """Slot 2 is admitted and decodes one token inside the mixed step that
    admits slot 0's prompt (packed side by side: decode row first, then the
    chunk); both rows' logprobs are the reference's, before and after."""
    eng = engine(dtype, quantize)
    a, b = prompt(41, 5), prompt(33, 6)
    ta, la = admit(eng, 2, a, chunk=64)
    h = eng.mixed_step_submit([MixedRow(slot=2, token_ids=[ta], start=41),
                               MixedRow(slot=0, token_ids=b[:31], start=0, kind="prefill")])
    t, lp = eng.mixed_step_fetch(h)
    ta2, la2 = int(t[2]), float(lp[2])
    h = eng.mixed_step_submit([MixedRow(slot=0, token_ids=b[31:], start=31, kind="prefill"),
                               MixedRow(slot=2, token_ids=[ta2], start=42)])
    t, lp = eng.mixed_step_fetch(h)
    ta3, la3, tb, lb = int(t[2]), float(lp[2]), int(t[0]), float(lp[0])
    out, _ = decode(eng, {2: (ta3, 43), 0: (tb, 33)}, 1)
    assert logprob_gap(eng, a + [ta, ta2, ta3] + out[2][0], 41, [la, la2, la3] + out[2][1]) < TOLERANCE[dtype]
    assert logprob_gap(eng, b + [tb] + out[0][0], 33, [lb] + out[0][1]) < TOLERANCE[dtype]


def test_the_programs_page_counts_are_the_hand_counts():
    """``window_pages_read`` / ``context_pages`` on both step programs: two
    windowed layers, pages of 8, a window of 16."""
    eng = engine()
    ids = prompt(70, 7)
    h = eng.mixed_step_submit([MixedRow(slot=0, token_ids=ids[:32], start=0, kind="prefill")])
    eng.mixed_step_fetch(h)
    assert (h.stats["window_pages_read"], h.stats["context_pages"]) == (2 * 4, 2 * 4)
    h = eng.mixed_step_submit([MixedRow(slot=0, token_ids=ids[32:64], start=32, kind="prefill")])
    eng.mixed_step_fetch(h)
    # queries see lengths 33..64: the first reads from position 17 (page 2) to page 7
    assert (h.stats["window_pages_read"], h.stats["context_pages"]) == (2 * 6, 2 * 8)
    h = eng.mixed_step_submit([MixedRow(slot=0, token_ids=ids[64:], start=64, kind="prefill")])
    tok, _ = eng.mixed_step_fetch(h)
    assert h.stats["state_bytes"] == CFG.state_bytes_per_slot
    _, stats = decode(eng, {0: (int(tok[0]), 70)}, 1)
    # four steps at lengths 71..74: 9 or 10 pages held, the window starts in page 6 or 7
    held = sum(math.ceil(n / 8) for n in range(71, 75))
    read = sum(math.ceil(n / 8) - (n - 16) // 8 for n in range(71, 75))
    assert (stats[0]["window_pages_read"], stats[0]["context_pages"]) == (2 * read, 2 * held)
    assert stats[0]["state_bytes"] == CFG.state_bytes_per_slot


def collect(scheduler, ids, max_tokens, timeout=120):
    q: queue.Queue = queue.Queue()
    scheduler.submit(GenRequest(prompt_ids=list(ids), max_tokens=max_tokens,
                                callback=lambda tok, lp, fin, reason: q.put((tok, lp, fin, reason))))
    toks, lps = [], []
    deadline = time.monotonic() + timeout
    while True:
        tok, lp, fin, reason = q.get(timeout=max(deadline - time.monotonic(), 0.1))
        if not (fin and reason in ("stop", "error")):
            toks.append(tok)
            lps.append(lp)
        if fin:
            return toks, lps, reason


def test_identical_greedy_requests_give_identical_tokens_and_the_prefix_hit_is_declined():
    """Through the scheduler: the second of two identical requests would hit
    the prefix cache (two full pages of its prompt are known); the hit is
    declined and counted, the request is computed from position 0 and gives
    the first one's tokens and logprobs, which are the reference's."""
    eng = engine()
    s = Scheduler(eng)
    s.start()
    try:
        ids = prompt(21, 8)
        first = collect(s, ids, 10)
        again = collect(s, ids, 10)
    finally:
        s.stop()
    assert first[0] == again[0] and first[1] == again[1] and first[2] == "length"
    status = eng.family_status()
    assert status["prefix_hits_declined"] == 1 and status["state_resets"] == 2
    assert eng.prefix_cache.stats()["hits"] == 0
    assert eng.allocator.free_page_count() == eng.allocator.num_pages  # a declining cache holds no page
    assert logprob_gap(eng, ids + first[0], 21, first[1]) < TOLERANCE["float32"]


def test_a_preempted_and_resumed_request_gives_the_tokens_of_an_uninterrupted_one():
    """A pool too small for two growing requests: the youngest is preempted
    and resumed by recompute (prompt + what it had generated, from position 0,
    so from a zeroed state; the prefix hit on its own pages is declined). Both
    streams are the ones each request gives alone."""
    eng = engine(max_slots=2, max_seq_len=96, num_pages=12, decode_chunk=4)
    s = Scheduler(eng, preempt_max=5)
    s.start()
    try:
        a, b = prompt(40, 9), prompt(33, 10)
        alone_a, alone_b = collect(s, a, 12), collect(s, b, 26)
        results: queue.Queue = queue.Queue()
        streams = {0: [], 1: []}

        def cb(i):
            def on(tok, lp, fin, reason):
                if not (fin and reason in ("stop", "error")):
                    streams[i].append(tok)
                if fin:
                    results.put((i, reason))
            return on

        for i, (ids, n) in enumerate(((a, 12), (b, 26))):
            s.submit(GenRequest(prompt_ids=list(ids), max_tokens=n, callback=cb(i)))
        reasons = dict(results.get(timeout=120) for _ in range(2))
    finally:
        s.stop()
    assert set(reasons.values()) <= {"stop", "length"}
    assert s.preemptions >= 1
    assert streams[0] == alone_a[0] and streams[1] == alone_b[0]


def four_softmax_attention(q, k, v, lam, window):
    """The published code's form: q1, q2 over k1, k2 and the value halves v1,
    v2, each a softmax attention of its own; attn1 = [a11; a12], attn2 = [a21; a22]."""
    T, Hq, D = q.shape
    G = Hq // k.shape[1]
    t, p = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (p <= t) & (p > t - window)

    def attend(qh, kh, vh):
        a = jax.nn.softmax(jnp.where(seen, (qh @ kh.T) / math.sqrt(D), -jnp.inf), axis=-1)
        return a @ vh

    heads = []
    for i in range(Hq // 2):
        j = i // G
        q1, q2, k1, k2 = q[:, 2 * i], q[:, 2 * i + 1], k[:, 2 * j], k[:, 2 * j + 1]
        v1, v2 = v[:, 2 * j], v[:, 2 * j + 1]
        attn1 = jnp.concatenate([attend(q1, k1, v1), attend(q1, k1, v2)], axis=-1)
        attn2 = jnp.concatenate([attend(q2, k2, v1), attend(q2, k2, v2)], axis=-1)
        heads.append(attn1 - lam * attn2)
    return jnp.stack(heads, axis=1)  # (T, Hq / 2, 2D)


def test_the_zero_padded_query_form_equals_the_four_softmax_form():
    """One sequence of 40 tokens in pages of 8 under a window of 16, 8 query
    heads of 16 over 4 key heads: the padded queries through the paged decode
    path's twin give, head for head, [A1 v1; A1 v2] - lambda [A2 v1; A2 v2]."""
    from inference_gateway_tpu.ops.paged_attention import ragged_paged_attention

    rng = np.random.default_rng(11)
    T, Hq, Hkv, D, ps, window = 40, 8, 4, 16, 8, 16
    q, k, v = (jnp.asarray(rng.normal(size=(T, h, D)).astype(np.float32)) for h in (Hq, Hkv, Hkv))
    pages_k, pages_v = (x.reshape(T // ps, ps, Hkv * D) for x in (k, v))
    table = jnp.arange(T // ps, dtype=jnp.int32)[None, :]
    o = ragged_paged_attention(phi4flash.pad_queries(q), pages_k, pages_v, table, jnp.asarray([0]),
                               jnp.asarray([T]), jnp.asarray([T]), Hkv // 2, window=window)
    assert o.shape == (T, Hq, 2 * D)
    lam = 0.37
    got = o.reshape(T, Hq // 2, 2, 2 * D)
    np.testing.assert_allclose(np.asarray(got[:, :, 0] - lam * got[:, :, 1]),
                               np.asarray(four_softmax_attention(q, k, v, lam, window)), atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_ragged_state_operations_equal_a_token_by_token_loop(seed):
    """Random segments packed in a random order of slots, some fresh (they
    start at position 0), some continuing from a random state and tail: the
    packed convolution and scan against one slot and one token at a time."""
    rng = np.random.default_rng(seed)
    S, T, Di, N, K = 5, 40, 12, 4, 4
    lens = np.array([7, 1, 0, 13, 2])
    order = rng.permutation(S)
    starts = np.zeros(S, np.int64)
    off = 0
    for s in order:
        starts[s], off = off, off + lens[s]
    fresh = np.array([True, False, False, False, True])
    base = np.where(fresh, 0, rng.integers(1, 30, size=S))
    positions = np.zeros(T, np.int64)
    for s in range(S):
        positions[starts[s]:starts[s] + lens[s]] = base[s] + np.arange(lens[s])
    arr = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    u, w, b, tails, states = arr(T, Di), arr(K, Di), arr(Di), arr(S, K - 1, Di), arr(S, N, Di)   # a state's channels in the lanes
    delta, B, C, A, D = jnp.abs(arr(T, Di)) * 0.1, arr(T, N), arr(T, N), -jnp.abs(arr(N, Di)), arr(Di)
    seg = ssm.token_segments(jnp.asarray(starts), jnp.asarray(lens), T)
    kv = jnp.asarray(base + lens)
    c, new_tails = ssm.conv_ragged(u, tails, w, b, seg, (kv - jnp.asarray(lens)) == 0, jnp.asarray(starts),
                                   jnp.asarray(lens))
    y, new_states = ssm.scan_ragged(states, delta, c, B, C, A, D, seg, jnp.asarray(positions), int(lens.sum()))

    for s in range(S):
        tail = np.zeros((K - 1, Di), np.float32) if fresh[s] else np.asarray(tails[s])
        state = np.zeros((N, Di), np.float32) if fresh[s] else np.asarray(states[s])
        for t in range(starts[s], starts[s] + lens[s]):
            window = np.concatenate([tail, np.asarray(u[t])[None]])
            ct = np.asarray(jax.nn.silu(jnp.asarray(np.asarray(b) + (window * np.asarray(w)).sum(0))))
            tail = window[1:]
            np.testing.assert_allclose(np.asarray(c[t]), ct, atol=1e-5)
            state = np.exp(np.asarray(delta[t])[None, :] * np.asarray(A)) * state \
                + (np.asarray(delta[t]) * ct)[None, :] * np.asarray(B[t])[:, None]
            np.testing.assert_allclose(np.asarray(y[t]), np.asarray(C[t]) @ state + np.asarray(D) * ct, atol=1e-4)
        if lens[s]:
            np.testing.assert_allclose(np.asarray(new_tails[s]), tail, atol=1e-6)
            np.testing.assert_allclose(np.asarray(new_states[s]), state, atol=1e-4)
        else:  # a slot without tokens keeps what it held
            np.testing.assert_array_equal(np.asarray(new_tails[s]), np.asarray(tails[s]))
            np.testing.assert_array_equal(np.asarray(new_states[s]), np.asarray(states[s]))


REFUSALS = {
    "speculation": (dict(spec_draft="ngram"), "speculative"),
    "a_draft_model": (dict(spec_draft="llama-draft-150m"), "speculative"),
    "vision": (dict(vision_model="vit-test-tiny"), "multimodal"),
    "pp": (dict(use_mesh=True, mesh_shape={"pp": 2}, attention="dense"), "no mesh"),
    "a_tp_mesh": (dict(use_mesh=True, mesh_shape={"tp": 2}), "no mesh"),
    "int4": (dict(quantize="int4"), "int4"),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_the_engine_refuses_by_name_what_cannot_carry_the_state(what):
    kw, says = REFUSALS[what]
    with pytest.raises((ValueError, NotImplementedError), match=says):
        engine(**kw)


def test_left_to_the_default_mesh_it_is_served_on_one_device():
    """Eight (virtual) devices and ``use_mesh``: a dense model would shard; this
    one holds everything on one device and says so."""
    assert len(jax.devices()) > 1
    eng = engine(use_mesh=True)
    assert eng.mesh is None and eng.mesh_axes == {"tp": 1} and eng.mixed_ok


def test_the_paths_that_carry_no_state_refuse_by_name_and_the_dense_engine_still_builds_the_tree():
    """``bench/reference.engine_config`` builds its engine dense with one
    slot, for the tree alone: it must build, bit-equal to the serving engine's,
    and nothing may step over it in silence."""
    dense = Engine(EngineConfig(model=PRESET, dtype="bfloat16", quantize="int8", use_mesh=False,
                                attention="dense", max_slots=1, max_seq_len=16))
    paged = engine("bfloat16", "int8")
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), dense.params, paged.params)
    assert all(jax.tree.leaves(same)) and not dense.mixed_ok
    with pytest.raises(NotImplementedError, match="paged"):
        dense.prefill([[1, 2, 3]], [0], [0.0], [1.0])
    with pytest.raises(NotImplementedError, match="ragged mixed step"):
        phi4flash.forward_paged(paged.params, CFG, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 4), jnp.int32),
                                jnp.asarray([4]), paged.cache, jnp.zeros((1, 4), jnp.int32),
                                jnp.zeros((1, 16), jnp.int32), mode="prefill")


def test_status_and_the_hbm_plan_count_what_is_held():
    from inference_gateway_tpu.otel.device_observatory import DeviceObservatory
    from inference_gateway_tpu.serving.profiles import kv_bytes_per_token

    big = phi4flash.PRESETS["phi-4-mini-flash"]
    assert kv_bytes_per_token(big) == 46_080  # 9 caching layers, not 32
    eng = engine("bfloat16", "int8")
    assert eng.family_status() == {
        "state_store": {"slots": 4, "layers": 3, "bytes": 4 * CFG.state_bytes_per_slot},
        "cache_layers": {"window": 2, "full": 1, "shared_readers": 1},
        "state_resets": 0, "prefix_hits_declined": 0}
    plan = DeviceObservatory._hbm_plan(eng)
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.cache))
    assert plan["kv_pool_bytes"] + plan["state_store_bytes"] == held
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(eng.params))
    assert abs(plan["weights_bytes"] - weights) < 0.15 * weights  # scales and float32 leaves: 11% at this toy size
