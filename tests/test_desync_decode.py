"""Desynchronized decode (ISSUE 14): on-device stopping, early-exit
chunks, and the host-free chained steady state.

Pins the two contracts the tentpole rests on:

1. **Byte identity**: greedy and seeded streams are identical whether a
   row can freeze inside a chunk or not, across dense, paged,
   structured, mixed, and continuation-splice paths — the device stop
   criteria are a strict subset of the host's, so freezing a row can
   never change what the host emits. The reference arm is the same loop
   at ``decode_chunk=1`` (``REF``): a trip count of one, so no row
   freezes mid-chunk, no chunk exits early and the host decides every
   stop. (Until ISSUE 30 the reference was the fixed-scan chunk; that
   PR ran both references and the default three-armed on these inputs
   and all agreed: CHANGES.md, PR 30.)
2. **Host-free**: a chained (chain=True) submit performs zero
   host→device transfers — pinned with jax's transfer guard, fast here
   and best-of-3 under the slow marker.
"""

from __future__ import annotations

import queue
import time

import jax
import numpy as np
import pytest

from inference_gateway_tpu.serving.engine import Engine, EngineConfig, build_stop_row
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler, generate_sync


# The reference arm of every parity test below: one step a chunk.
REF = dict(decode_chunk=1)


def _cfg(attention="dense", **kw):
    base = dict(model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
                max_prefill_batch=2, use_mesh=False, attention=attention,
                page_size=16, prefix_cache=False, decode_chunk=4,
                prefill_buckets=(16, 32, 64))
    base.update(kw)
    return EngineConfig(**base)


def _run_batch(engine, reqs, timeout=180.0):
    """Submit GenRequests through a scheduler; returns [(tokens, reason)]
    in submit order."""
    s = Scheduler(engine)
    s.start()
    try:
        out = [([], [None]) for _ in reqs]
        done: queue.Queue = queue.Queue()

        def cb_factory(i):
            def cb(tok, lp, fin, reason):
                if not (fin and reason in ("stop",)):
                    out[i][0].append(tok)
                if fin:
                    out[i][1][0] = reason
                    done.put(i)
            return cb

        for i, r in enumerate(reqs):
            r.callback = cb_factory(i)
            s.submit(r)
        for _ in reqs:
            done.get(timeout=timeout)
    finally:
        s.stop()
    return [(toks, reason[0]) for toks, reason in out]


def _reqs(stop_sets=None, seeds=(None, 17, None, 99), temps=(0.0, 0.8, 0.0, 0.6),
          max_tokens=(12, 9, 3, 16)):
    prompts = [[1, 2, 3], [7, 5, 9, 11], [4, 4, 8], [13, 2, 6, 10, 3]]
    stop_sets = stop_sets or [frozenset()] * len(prompts)
    return [GenRequest(prompt_ids=list(p), max_tokens=m, temperature=t,
                       top_p=0.9 if t else 1.0, seed=sd,
                       stop_token_ids=stop_sets[i])
            for i, (p, m, t, sd) in enumerate(zip(prompts, max_tokens, temps, seeds))]


def test_streams_byte_identical_ee_on_off_dense_and_paged():
    """Greedy AND seeded sampled streams, mixed finishes (max_tokens of
    3 exercises a mid-chunk stop), identical at one step a chunk and at
    four."""
    for attention in ("dense", "paged"):
        ref = _run_batch(Engine(_cfg(attention, **REF)), _reqs())
        got = _run_batch(Engine(_cfg(attention)), _reqs())
        assert got == ref, (attention, got, ref)


def test_stop_token_streams_byte_identical_incl_table_overflow():
    """Stop-token finishes: ids inside the device table stop on device;
    an overflowing stop set (> STOP_TABLE_WIDTH ids) keeps the overflow
    host-side — streams must be byte-identical either way."""
    base = _run_batch(Engine(_cfg("paged", **REF)),
                      _reqs(max_tokens=(20, 9, 20, 16)))
    # Stop on a token each greedy stream actually emits, mid-stream.
    s0 = frozenset([base[0][0][4]])
    # An oversized set whose REAL hit is the last sorted id — likely off
    # the shipped table (host backstop truncates identically).
    s2 = frozenset(range(2000, 2014)) | frozenset([base[2][0][5]])
    stop_sets = [s0, frozenset(), s2, frozenset()]
    ref = _run_batch(Engine(_cfg("paged", **REF)),
                     _reqs(stop_sets=stop_sets, max_tokens=(20, 9, 20, 16)))
    got = _run_batch(Engine(_cfg("paged")),
                     _reqs(stop_sets=stop_sets, max_tokens=(20, 9, 20, 16)))
    assert got == ref
    # Sanity: the stop sets actually truncated stream 0 and 2.
    assert len(ref[0][0]) < len(base[0][0]) and ref[0][1] == "stop"
    assert len(ref[2][0]) < len(base[2][0]) and ref[2][1] == "stop"


def test_structured_stream_byte_identical_ee_on_off():
    """Grammar-constrained (json_object) greedy streams: the device
    terminal-state gather must stop exactly where the host mirror's
    feed() returns "end"."""
    outs = []
    for arm in (REF, {}):
        eng = Engine(_cfg("paged", **arm))
        session = eng.structured.session_for({"type": "json_object"})
        req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=48, grammar=session)
        outs.append(_run_batch(eng, [req]))
    assert outs[1] == outs[0]
    toks, reason = outs[1][0]
    assert reason in ("stop", "length")


def test_mixed_step_path_byte_identical_ee_on_off():
    """Mixed-step admission (ragged prefill interleaving) followed by
    fused chunks: identical streams at one step a chunk and at four."""
    ref = _run_batch(Engine(_cfg("paged", mixed_step=True, **REF)), _reqs())
    got = _run_batch(Engine(_cfg("paged", mixed_step=True)), _reqs())
    assert got == ref


def test_continuation_splice_byte_identical_ee_on_off():
    """A stream split at a token boundary and resumed from
    prompt+generated-so-far (the ISSUE 9 continuation / ISSUE 7
    preemption resume shape) must reproduce the unsplit stream, with
    once-only billing via resume_generated — at one step a chunk and at
    four, and the two unsplit streams agree."""
    prompt = [5, 6, 7]
    M, k = 14, 5
    fulls = []
    for arm in (REF, {}):
        full = _run_batch(Engine(_cfg("dense", **arm)),
                          [GenRequest(prompt_ids=list(prompt), max_tokens=M)])
        first = _run_batch(Engine(_cfg("dense", **arm)),
                           [GenRequest(prompt_ids=list(prompt), max_tokens=k)])
        head = first[0][0]
        assert head == full[0][0][:k]
        cont = _run_batch(Engine(_cfg("dense", **arm)),
                          [GenRequest(prompt_ids=list(prompt) + head,
                                      max_tokens=M, resume_generated=k)])
        # Once-only billed: the continuation emits exactly the remaining
        # M-k tokens (counting any terminal stop token like `full` does).
        assert head + cont[0][0] == full[0][0]
        assert cont[0][1] == full[0][1]
        fulls.append(full)
    assert fulls[1] == fulls[0]


def _establish_chain(eng, n_chunks=1):
    res = eng.prefill([[1, 2, 3, 4]], [0], [0.0], [1.0])[0]
    S = eng.config.max_slots
    tokens = np.zeros((S,), np.int32)
    positions = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    temps = np.zeros((S,), np.float32)
    top_ps = np.ones((S,), np.float32)
    tokens[0], positions[0], active[0] = res.first_token, 4, True
    h = eng.decode_chunk_submit(tokens, positions, active, temps, top_ps)
    eng.decode_chunk_fetch(h)
    return eng


def test_chained_submit_makes_zero_h2d_transfers():
    """ISSUE 14 acceptance: with the chain established (and the page
    horizon pre-reserved), a chain=True submit uploads NOTHING — pinned
    by jax's host→device transfer guard. pipeline_depth is raised so the
    fresh submit's horizon covers the guarded chunks (the amortized
    horizon refresh is the one legitimate upload, and it must not fall
    inside the steady-state window)."""
    for attention in ("dense", "paged"):
        eng = _establish_chain(Engine(_cfg(
            attention, max_seq_len=256, pipeline_depth=6)))
        with jax.transfer_guard_host_to_device("disallow"):
            h = eng.decode_chunk_submit(None, None, None, None, None, chain=True)
        toks, _ = eng.decode_chunk_fetch(h)  # d2h fetch is the sync point
        assert toks.shape[0] == eng.config.decode_chunk


@pytest.mark.slow
def test_chained_steady_state_zero_uploads_best_of_3():
    """Best-of-3 acceptance run: three consecutive chained submits per
    attempt, all inside the transfer guard — the steady state stays
    upload-free across chunks, not just for one dispatch."""
    failures = 0
    for _attempt in range(3):
        try:
            for attention in ("dense", "paged"):
                eng = _establish_chain(Engine(_cfg(
                    attention, max_seq_len=512, pipeline_depth=8)))
                with jax.transfer_guard_host_to_device("disallow"):
                    handles = [
                        eng.decode_chunk_submit(None, None, None, None, None,
                                                chain=True)
                        for _ in range(3)]
                for h in handles:
                    eng.decode_chunk_fetch(h)
        except Exception:
            failures += 1
    assert failures == 0, f"{failures}/3 attempts saw a host→device transfer"


def test_long_chunk_freezes_at_stop_and_early_exits():
    """A 32-step chunk whose only stream has a 3-token budget emits 3
    real tokens then repeats the frozen token — the device stopped
    sampling (and the while_loop exited) at the finish."""
    eng = Engine(_cfg("paged"))
    res = eng.prefill([[1, 2, 3, 4]], [0], [0.0], [1.0])[0]
    S = eng.config.max_slots
    tokens = np.zeros((S,), np.int32)
    positions = np.zeros((S,), np.int32)
    active = np.zeros((S,), bool)
    tokens[0], positions[0], active[0] = res.first_token, 4, True
    budgets = np.zeros((S,), np.int64)
    budgets[0] = 3
    h = eng.decode_chunk_submit(
        tokens, positions, active, np.zeros((S,), np.float32),
        np.ones((S,), np.float32), n_steps=32, budgets=budgets)
    toks, _ = eng.decode_chunk_fetch(h)
    col = [int(t) for t in toks[:, 0]]
    assert col[3:] == [col[2]] * 29, col
    # Reference engine without the budget: the 3 real tokens match.
    ref = Engine(_cfg("paged"))
    rres = ref.prefill([[1, 2, 3, 4]], [0], [0.0], [1.0])[0]
    rtok = np.zeros((S,), np.int32)
    rtok[0] = rres.first_token
    rh = ref.decode_chunk_submit(rtok, positions, active,
                                 np.zeros((S,), np.float32),
                                 np.ones((S,), np.float32), n_steps=32)
    rcol = [int(t) for t in ref.decode_chunk_fetch(rh)[0][:, 0]]
    assert col[:3] == rcol[:3]


def test_build_stop_row_shape_and_truncation():
    row = build_stop_row(7, [3, 1, 2])
    assert row.tolist()[:4] == [7, 1, 2, 3] and set(row.tolist()[4:]) == {-1}
    # EOS always first; overflow truncates (host backstop covers it).
    row = build_stop_row(0, range(100, 120))
    assert row[0] == 0 and len(row) == 8 and -1 not in row.tolist()


def test_release_patches_done_for_host_only_finishes():
    """A host-only release (frozen=False) must freeze the slot in the
    chained carry so later chunks stop writing into freed pages; a
    device-detected finish (frozen=True) skips the patch — the row is
    already frozen."""
    eng = _establish_chain(Engine(_cfg("paged", pipeline_depth=6)))
    tok, pos, ms, done, bud, rng = eng._dev_carry
    assert not bool(np.asarray(done)[0])
    eng.release_slot(0, frozen=False)
    done_after = np.asarray(eng._dev_carry[3])
    assert bool(done_after[0])
    assert not eng._chain_active[0]
