"""Pipelined (submit/fetch, device-chained) decode correctness.

Round 3 made the scheduler overlap chunk N's readback with chunk N+1's
execution, chaining chunk inputs off the device-resident scan carry
(serving/scheduler.py run(), serving/engine.py decode_chunk_submit).
These tests pin the invariant that pipelining is a pure latency
optimization: token streams are identical to unpipelined, unbatched
decoding, across admissions (pipeline barriers) and chunk boundaries.
"""

from __future__ import annotations

import queue
import time

import numpy as np

from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler, generate_sync


def _solo_reference(cfg_kwargs, prompt, *, seed=None, temperature=0.0, max_tokens=12):
    """One request, alone, through a fresh engine+scheduler."""
    eng = Engine(EngineConfig(**cfg_kwargs))
    s = Scheduler(eng)
    s.start()
    try:
        toks, reason = generate_sync(
            s, list(prompt), max_tokens=max_tokens, temperature=temperature,
            top_p=0.9 if temperature else 1.0, seed=seed,
        )
    finally:
        s.stop()
    return toks, reason


def test_pipelined_streams_match_solo_references():
    """Staggered submissions force the full pipeline lifecycle — fresh
    submit, chained submits, async admission scatter, slot reuse — and
    every request's stream must equal its solo (batch-independent)
    reference."""
    for attention in ("dense", "paged"):
        cfg_kwargs = dict(model="test-tiny", max_slots=4, max_seq_len=96, dtype="float32",
                          max_prefill_batch=2, use_mesh=False, attention=attention,
                          page_size=16, prefix_cache=False, decode_chunk=3,
                          prefill_buckets=(16, 32, 64))
        prompts = [[1, 2, 3], [7, 5, 9, 11], [4, 4, 8], [13, 2], [6, 10, 3, 5, 2]]
        seeds = [None, 17, None, 99, None]
        temps = [0.0, 0.8, 0.0, 0.6, 0.0]

        refs = [
            _solo_reference(cfg_kwargs, p, seed=sd, temperature=t)
            for p, sd, t in zip(prompts, seeds, temps)
        ]

        eng = Engine(EngineConfig(**cfg_kwargs))
        s = Scheduler(eng)
        s.start()
        try:
            results: "dict[int, list[int]]" = {i: [] for i in range(len(prompts))}
            done: "queue.Queue[int]" = queue.Queue()

            def cb_factory(i):
                def cb(tok, lp, fin, reason):
                    results[i].append(tok)
                    if fin:
                        done.put(i)
                return cb

            # Two waves: the second admits while the first decodes, which
            # exercises the drain-before-admission barrier.
            for i in range(3):
                s.submit(GenRequest(prompt_ids=list(prompts[i]), max_tokens=12,
                                    temperature=temps[i],
                                    top_p=0.9 if temps[i] else 1.0,
                                    seed=seeds[i], callback=cb_factory(i)))
            time.sleep(0.3)
            for i in range(3, len(prompts)):
                s.submit(GenRequest(prompt_ids=list(prompts[i]), max_tokens=12,
                                    temperature=temps[i],
                                    top_p=0.9 if temps[i] else 1.0,
                                    seed=seeds[i], callback=cb_factory(i)))
            for _ in range(len(prompts)):
                done.get(timeout=120)
        finally:
            s.stop()

        for i, (ref_toks, _) in enumerate(refs):
            if temps[i] == 0.0 or seeds[i] is not None:
                assert results[i] == ref_toks, (
                    f"{attention}: request {i} diverged under pipelining: "
                    f"{results[i]} != {ref_toks}")


def test_top_k_disabled_and_oversized_still_decode():
    """top_k=0 ("disabled") and top_k >= vocab must degrade to a
    full-vocab sort in the fused chunk path, not crash lax.top_k
    (code-review round 3)."""
    for top_k in (0, 10_000):
        cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
                           max_prefill_batch=2, use_mesh=False, attention="dense",
                           decode_chunk=2, prefill_buckets=(16, 32), top_k=top_k)
        eng = Engine(cfg)
        s = Scheduler(eng)
        s.start()
        try:
            toks, reason = generate_sync(s, [1, 2, 3], max_tokens=4,
                                         temperature=0.7, top_p=0.9, seed=5)
            assert len(toks) >= 1 and reason in ("stop", "length")
        finally:
            s.stop()


def test_chained_submit_carry_and_admission_scatter():
    """chain=True with no carry ever established must raise; once a
    carry exists, a prefill no longer invalidates it — the admitted
    slot's (first token, position, sampling params) are scattered into
    the device-resident state (engine._admit_scatter_fn_ee), so chained
    decoding continues across admissions with no host sync AND the
    admitted slot's chained tokens match an unchained reference."""
    mk = lambda: Engine(EngineConfig(
        model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
        max_prefill_batch=2, use_mesh=False, attention="dense",
        decode_chunk=2, prefill_buckets=(16, 32)))
    eng = mk()
    cfg = eng.config
    S = cfg.max_slots
    z = np.zeros((S,), np.int32)
    act = np.zeros((S,), bool)
    f = np.zeros((S,), np.float32)
    ones = np.ones((S,), np.float32)

    eng.prefill([[1, 2, 3]], [0], [0.0], [1.0])
    act[0] = True
    import pytest

    with pytest.raises(RuntimeError, match="chain"):
        eng.decode_chunk_submit(z, z, act, f, ones, chain=True)

    # Fresh submit establishes the carry; chained then works and matches
    # the carry semantics (tokens arg ignored).
    h1 = eng.decode_chunk_submit(z + 5, np.full((S,), 3, np.int32), act, f, ones)
    toks1, _ = eng.decode_chunk_fetch(h1)
    h2 = eng.decode_chunk_submit(z, np.full((S,), 3 + cfg.decode_chunk, np.int32),
                                 act, f, ones, chain=True)
    toks2, _ = eng.decode_chunk_fetch(h2)
    assert toks1.shape == toks2.shape == (cfg.decode_chunk, S)

    # Async admission: a prefill with a live carry SCATTERS the new
    # slot's state into it; a chained submit then decodes the admitted
    # slot from its first token with no host round-trip.
    res = eng.prefill([[4, 5]], [1], [0.0], [1.0])[0]
    act2 = act.copy()
    act2[1] = True
    pos_pred = np.asarray([3 + 2 * cfg.decode_chunk, 2], np.int32)
    h3 = eng.decode_chunk_submit(z, pos_pred, act2, f, ones, chain=True)
    toks3, _ = eng.decode_chunk_fetch(h3)

    # Reference: same prompt alone on a fresh engine, unchained chunk
    # from (first_token, pos=2). Greedy + per-row dense cache rows make
    # the stream batch-independent.
    ref = mk()
    rres = ref.prefill([[4, 5]], [1], [0.0], [1.0])[0]
    assert rres.first_token == res.first_token
    rtok = np.zeros((S,), np.int32)
    rpos = np.zeros((S,), np.int32)
    ract = np.zeros((S,), bool)
    rtok[1], rpos[1], ract[1] = rres.first_token, 2, True
    rh = ref.decode_chunk_submit(rtok, rpos, ract, f, ones)
    rtoks, _ = ref.decode_chunk_fetch(rh)
    assert [int(t) for t in toks3[:, 1]] == [int(t) for t in rtoks[:, 1]]


def test_chained_chunks_equal_one_big_chunk():
    """Greedy: two chained 4-step chunks produce the same tokens as one
    8-step chunk from the same starting state (carry fidelity)."""
    for attention in ("dense", "paged"):
        mk = lambda: Engine(EngineConfig(
            model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
            max_prefill_batch=2, use_mesh=False, attention=attention,
            page_size=16, prefix_cache=False, decode_chunk=4,
            prefill_buckets=(16, 32)))
        prompt = [1, 2, 3, 4]

        outs = {}
        for mode in ("one", "chained"):
            eng = mk()
            res = eng.prefill([prompt], [0], [0.0], [1.0])[0]
            S = eng.config.max_slots
            tokens = np.zeros((S,), np.int32)
            positions = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temps = np.zeros((S,), np.float32)
            top_ps = np.ones((S,), np.float32)
            tokens[0] = res.first_token
            positions[0] = len(prompt)
            active[0] = True
            if mode == "one":
                toks, _ = eng.decode_chunk(tokens, positions, active, temps, top_ps, n_steps=8)
                outs[mode] = [int(t) for t in toks[:, 0]]
            else:
                h1 = eng.decode_chunk_submit(tokens, positions, active, temps, top_ps, n_steps=4)
                positions[0] += 4
                h2 = eng.decode_chunk_submit(tokens, positions, active, temps, top_ps,
                                             n_steps=4, chain=True)
                t1, _ = eng.decode_chunk_fetch(h1)
                t2, _ = eng.decode_chunk_fetch(h2)
                outs[mode] = [int(t) for t in t1[:, 0]] + [int(t) for t in t2[:, 0]]
        assert outs["one"] == outs["chained"], (attention, outs)
