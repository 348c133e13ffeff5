"""Ragged mixed-step serving (ISSUE 12): scheduler interleaving,
byte-identity vs the bucketed path, and the dispatch-verdict surfacing.

The acceptance pins:
- a long chunked prefill admitted alongside active decode streams no
  longer serializes ahead of them (decode tokens emit during the
  prefill's chunk window);
- greedy stream output is byte-identical to the bucketed path;
- over-length prompts route through the structured ``prompt_too_long``
  path instead of a bare ValueError.
"""

import threading
import time

import numpy as np
import pytest

from inference_gateway_tpu.serving.engine import (
    Engine,
    EngineConfig,
    MixedRow,
    PromptTooLongError,
)
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler, generate_sync

COMMON = dict(model="test-tiny", max_slots=4, max_seq_len=256, dtype="float32",
              max_prefill_batch=2, use_mesh=False, prefill_buckets=(16, 32, 64),
              decode_chunk=4)


def _mk_engine(mixed: bool, **over):
    kw = dict(COMMON, attention="paged", page_size=16, mixed_step=mixed)
    kw.update(over)
    return Engine(EngineConfig(**kw))


def test_mixed_greedy_byte_identical_to_bucketed():
    """Same seed, same prompts: the mixed-step engine must emit exactly
    the bucketed paged engine's greedy tokens."""
    bucketed = _mk_engine(False)
    mixed = _mk_engine(True)
    assert mixed.mixed_ok and not bucketed.mixed_ok
    sb, sm = Scheduler(bucketed), Scheduler(mixed)
    sb.start()
    sm.start()
    try:
        rng = np.random.default_rng(7)
        for n in (5, 20, 33, 64):
            prompt = [int(x) for x in rng.integers(1, 250, size=n)]
            want, wr = generate_sync(sb, prompt, max_tokens=24, temperature=0.0)
            got, gr = generate_sync(sm, prompt, max_tokens=24, temperature=0.0)
            assert got == want, f"prompt len {n}: mixed diverged from bucketed"
            assert gr == wr
    finally:
        sb.stop()
        sm.stop()
    held = mixed.prefix_cache.stats()["cached_pages"] if mixed.prefix_cache else 0
    assert mixed.allocator.free_page_count() + held == mixed.allocator.num_pages


def test_mixed_long_prompt_matches_dense_chunked_path():
    """Paged engines gain a long-prompt path: a prompt beyond the
    largest bucket (previously a structured 400 / admission failure in
    paged mode) now serves via chunked ragged prefill, byte-identical
    to the dense engine's chunked long-prompt path."""
    dense = Engine(EngineConfig(**COMMON, attention="dense"))
    mixed = _mk_engine(True)
    assert mixed.max_prompt_len() == mixed.context_window() - 1
    sd, sm = Scheduler(dense), Scheduler(mixed)
    sd.start()
    sm.start()
    try:
        rng = np.random.default_rng(11)
        prompt = [int(x) for x in rng.integers(1, 250, size=150)]  # > biggest bucket 64
        want, _ = generate_sync(sd, prompt, max_tokens=16, temperature=0.0)
        got, _ = generate_sync(sm, prompt, max_tokens=16, temperature=0.0)
        assert got == want
    finally:
        sd.stop()
        sm.stop()


def test_decode_emits_during_prefill_chunk_window():
    """THE head-of-line acceptance: while a long prompt chunk-prefills,
    an already-active decode stream keeps emitting tokens — between the
    long request's submit and its first token, the short request makes
    progress."""
    engine = _mk_engine(True, mixed_step_tokens=24)  # small budget → many chunks
    sched = Scheduler(engine)
    sched.start()
    try:
        rng = np.random.default_rng(3)
        events: list[tuple[str, int]] = []  # (tag, seq) in emission order
        lock = threading.Lock()
        seq = [0]

        def note(tag):
            with lock:
                events.append((tag, seq[0]))
                seq[0] += 1

        short_done = threading.Event()
        long_done = threading.Event()

        def short_cb(tok, lp, fin, reason):
            note("short")
            if fin:
                short_done.set()

        def long_cb(tok, lp, fin, reason):
            note("long" if not fin else "long")
            if fin:
                long_done.set()

        short = GenRequest(
            prompt_ids=[int(x) for x in rng.integers(1, 250, size=8)],
            max_tokens=200, temperature=0.0, callback=short_cb)
        sched.submit(short)
        # Let the short stream actually start decoding.
        deadline = time.monotonic() + 30
        while not any(t == "short" for t, _ in events):
            assert time.monotonic() < deadline, "short stream never started"
            time.sleep(0.01)
        note("long_submitted")
        long_req = GenRequest(
            prompt_ids=[int(x) for x in rng.integers(1, 250, size=120)],
            max_tokens=4, temperature=0.0, callback=long_cb)
        sched.submit(long_req)
        assert long_done.wait(timeout=120), "long request never finished"
        short.disconnected = True  # let the scheduler retire the short stream
        with lock:
            snapshot = list(events)
        submit_at = next(s for t, s in snapshot if t == "long_submitted")
        long_first = next(s for t, s in snapshot if t == "long")
        interleaved = [s for t, s in snapshot
                       if t == "short" and submit_at < s < long_first]
        # 120 prompt tokens / 24-token budget → ≥ 5 chunk steps, each of
        # which must carry the short stream's decode row.
        assert len(interleaved) >= 3, (
            f"no decode progress during the prefill window: {snapshot}")
    finally:
        sched.stop()


def test_overlength_prompt_routes_through_prompt_too_long():
    """bucket_for raises the structured PromptTooLongError (not a bare
    ValueError), and the sidecar's 400 shape keys off the same limit:
    a mixed paged engine admits up to the context window and rejects
    only beyond it."""
    bucketed = _mk_engine(False)
    with pytest.raises(PromptTooLongError) as ei:
        bucketed.bucket_for(500)
    assert isinstance(ei.value, ValueError)  # back-compat
    assert ei.value.prompt_tokens == 500
    assert ei.value.max_prompt_tokens == bucketed.max_prompt_len()
    assert bucketed.max_prompt_len() == 64  # bucket-bounded without mixed

    mixed = _mk_engine(True)
    assert mixed.max_prompt_len() == mixed.context_window() - 1


def test_sidecar_rejects_overlength_with_structured_400():
    """End-to-end 400 shape: beyond the admittable limit the sidecar
    answers code=prompt_too_long BEFORE any slot/page allocation — on a
    BUCKETED paged engine, where the limit is the largest bucket (a
    mixed engine admits the same prompt via chunked ragged prefill)."""
    import asyncio
    import json

    from inference_gateway_tpu.serving.server import SidecarServer

    engine = _mk_engine(False)

    async def run():
        server = SidecarServer(engine, served_model_name="tiny")
        # An in-process request object is enough: call the handler directly.
        from inference_gateway_tpu.netio.server import Headers, Request

        ids = list(range(1, 100))  # > largest bucket 64, < context window
        body = json.dumps({
            "messages": [{"role": "user", "content": "x"}], "max_tokens": 4,
        }).encode()
        req = Request(method="POST", path="/v1/chat/completions", query={},
                      headers=Headers(), body=body)
        # Patch the tokenizer to produce the oversized prompt directly.
        engine.tokenizer.apply_chat_template = lambda msgs: ids
        resp = await server.chat_completions(req)
        assert resp.status == 400
        payload = json.loads(resp.body)
        assert payload["error"]["code"] == "prompt_too_long"
        server.scheduler.stop()

    asyncio.run(run())


def test_attention_path_surfaced_in_status_and_gauge():
    """The dispatch verdict is a gauge and a /debug/status field: on
    this CPU platform a paged engine reports the gather fallback (the
    ragged reference) — visibly, not silently."""
    import asyncio

    from inference_gateway_tpu.otel.otel import OpenTelemetry
    from inference_gateway_tpu.serving.server import SidecarServer

    engine = _mk_engine(True)
    assert engine.attention_path == "gather"
    assert "not TPU" in engine.attention_path_reason
    otel = OpenTelemetry()

    async def run():
        server = SidecarServer(engine, served_model_name="tiny", otel=otel)
        otel.set_attention_path(server.model_name, engine.attention_path)
        from inference_gateway_tpu.netio.server import Headers, Request

        resp = await server.debug_status(Request(method="GET", path="/debug/status",
                                                 query={}, headers=Headers(), body=b""))
        import json

        status = json.loads(resp.body)
        assert status["attention_path"]["path"] == "gather"
        assert status["attention_path"]["mixed_step"] is True
        assert status["attention_path"]["reason"]
        server.scheduler.stop()

    asyncio.run(run())
    vals = otel.engine_attention_path_gauge.values()
    active = {k: v for k, v in vals.items()}
    assert active[("tiny", "gather")] == 1
    assert active[("tiny", "kernel")] == 0


@pytest.mark.parametrize("vocab, top_k, want", [
    (256, 64, {"top_k": 64, "path": "direct", "group_width": 0, "groups": 0, "candidates": 256}),
    (256, 0, {"top_k": 256, "path": "direct", "group_width": 0, "groups": 0, "candidates": 256}),
    (16384, 8, {"top_k": 8, "path": "grouped", "group_width": 128, "groups": 128, "candidates": 1024}),
    (2048, 8, {"top_k": 8, "path": "grouped", "group_width": 32, "groups": 64, "candidates": 256}),
])
def test_sampler_verdict_surfaced_in_status(vocab, top_k, want, monkeypatch):
    """Which top-k selection an engine's (vocabulary, top_k) takes is
    static per engine: /debug/status reports it beside attention_path
    (ISSUE 31), so a deployment whose shapes went the direct way says so."""
    import asyncio
    import dataclasses
    import json

    from inference_gateway_tpu.models import llama
    from inference_gateway_tpu.netio.server import Headers, Request
    from inference_gateway_tpu.serving.server import SidecarServer

    monkeypatch.setitem(llama.PRESETS, "test-tiny-vocab",
                        dataclasses.replace(llama.PRESETS["test-tiny"], vocab_size=vocab))
    engine = _mk_engine(True, model="test-tiny-vocab", top_k=top_k)
    assert engine.sampler_plan == want

    async def run():
        server = SidecarServer(engine, served_model_name="tiny")
        resp = await server.debug_status(Request(method="GET", path="/debug/status",
                                                 query={}, headers=Headers(), body=b""))
        assert json.loads(resp.body)["sampler"] == want
        server.scheduler.stop()

    asyncio.run(run())


def test_mixed_row_multimodal_falls_back_to_bucketed_admission():
    """Requests the ragged program can't serve (embedding overrides)
    take the bucketed admission path — and still finish."""
    engine = _mk_engine(True)
    sched = Scheduler(engine)
    sched.start()
    try:
        done = threading.Event()
        toks = []

        def cb(tok, lp, fin, reason):
            toks.append(tok)
            if fin:
                done.set()

        # embeds is a non-None marker; the paged prefill path ignores
        # the override (pre-existing contract) but admission must route
        # around the ragged program.
        req = GenRequest(prompt_ids=[1, 2, 3, 4], max_tokens=4, temperature=0.0,
                         callback=cb, embeds=np.zeros((4, 64), np.float32))
        sched.submit(req)
        assert done.wait(timeout=60)
        assert toks
    finally:
        sched.stop()


def test_mixed_admission_adopts_prefix_cache():
    """Review fix: mixed admission must keep the prefix-cache fast path
    — a repeated prompt adopts the cached prefix pages and chunk-
    prefills only the tail (hits counter moves), with identical greedy
    output."""
    engine = _mk_engine(True)
    sched = Scheduler(engine)
    sched.start()
    try:
        rng = np.random.default_rng(21)
        prompt = [int(x) for x in rng.integers(1, 250, size=40)]
        first, _ = generate_sync(sched, prompt, max_tokens=8, temperature=0.0)
        hits_before = engine.prefix_cache.stats()["hits"]
        second, _ = generate_sync(sched, prompt, max_tokens=8, temperature=0.0)
        assert second == first
        assert engine.prefix_cache.stats()["hits"] > hits_before
    finally:
        sched.stop()


def test_mixed_admission_requeues_on_page_pressure():
    """Review fix: recoverable page exhaustion during mixed admission
    REQUEUES the admitting request (ISSUE 7 semantics, same as bucketed
    admission) instead of failing it — both streams complete once the
    running one frees its pages."""
    engine = Engine(EngineConfig(
        model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
        max_prefill_batch=1, use_mesh=False, prefill_buckets=(16, 32, 64),
        decode_chunk=2, attention="paged", page_size=8, num_pages=10,
        prefix_cache=False, mixed_step=True))
    sched = Scheduler(engine, preempt_max=3)
    sched.start()
    try:
        rng = np.random.default_rng(31)
        results: dict = {}
        done = {k: threading.Event() for k in ("a", "b")}

        def cb(name):
            toks = results.setdefault(name, [])

            def _cb(tok, lp, fin, reason):
                toks.append((tok, reason))
                if fin:
                    results[name + "_reason"] = reason
                    done[name].set()
            return _cb

        # A: 20-token prompt growing to ~60 tokens (8 pages of 10).
        sched.submit(GenRequest(
            prompt_ids=[int(x) for x in rng.integers(1, 250, size=20)],
            max_tokens=40, temperature=0.0, callback=cb("a")))
        time.sleep(0.3)  # let A admit and start decoding
        # B: 30-token prompt (4 pages) — cannot fit while A holds 8.
        sched.submit(GenRequest(
            prompt_ids=[int(x) for x in rng.integers(1, 250, size=30)],
            max_tokens=4, temperature=0.0, callback=cb("b")))
        assert done["a"].wait(timeout=120)
        assert done["b"].wait(timeout=120)
        assert results["a_reason"] != "error", results["a_reason"]
        assert results["b_reason"] != "error", results["b_reason"]
    finally:
        sched.stop()
    assert engine.allocator.free_page_count() == engine.allocator.num_pages


def test_warmup_compiles_mixed_program():
    engine = _mk_engine(True)
    engine.warmup()
    # All pages back after warmup's temporary slot use.
    held = engine.prefix_cache.stats()["cached_pages"] if engine.prefix_cache else 0
    assert engine.allocator.free_page_count() + held == engine.allocator.num_pages


def test_mixed_step_submit_is_engine_level_consistent():
    """MixedRow decode result == a one-step decode chunk from the same
    state (the collapse of the per-bucket family can't drift from the
    old paths)."""
    e1 = _mk_engine(False)
    e2 = _mk_engine(True)
    rng = np.random.default_rng(9)
    prompt = [int(x) for x in rng.integers(1, 250, size=12)]
    r1 = e1.prefill([prompt], [0], [0.0], [1.0])[0]
    h = e2.mixed_step_submit([MixedRow(slot=0, token_ids=prompt, start=0,
                                       kind="prefill")])
    t2, _ = e2.mixed_step_fetch(h)
    assert r1.first_token == int(t2[0])
    S = e1.config.max_slots
    tok = np.zeros((S,), np.int32)
    tok[0] = r1.first_token
    pos = np.zeros((S,), np.int32)
    pos[0] = len(prompt)
    t1, _ = e1.decode_chunk(tok, pos, pos > 0, np.zeros((S,), np.float32),
                            np.ones((S,), np.float32), n_steps=1)
    h2 = e2.mixed_step_submit([MixedRow(slot=0, token_ids=[int(t2[0])],
                                        start=len(prompt), kind="decode")])
    t2b, _ = e2.mixed_step_fetch(h2)
    assert int(t1[0, 0]) == int(t2b[0])


def test_two_mixed_steps_in_flight_do_not_share_their_descriptors():
    """ISSUE 25: a submit stages ONE packed buffer and uploads a private
    copy of it, so the next submit's rewrite of the staging views cannot
    reach a step still in flight (the CPU backend aliases an uploaded
    numpy array; tests/test_packed_submit.py pins the copy itself). Two
    submits fetched late equal two fetched in turn."""
    rng = np.random.default_rng(21)
    prompts = [[int(x) for x in rng.integers(1, 250, size=n)] for n in (14, 9)]
    rows = [MixedRow(slot=s, token_ids=p, start=0, kind="prefill", temp=0.9, seed=77 + s)
            for s, p in enumerate(prompts)]
    late, in_turn = _mk_engine(True), _mk_engine(True)
    handles = [late.mixed_step_submit([r]) for r in rows]
    assert [h.h2d_arrays for h in handles] == [1, 1]
    assert all(h.toks_lp.shape == (2, late.config.max_slots) for h in handles)
    got = [late.mixed_step_fetch(h) for h in handles]
    want = [in_turn.mixed_step_fetch(in_turn.mixed_step_submit([r])) for r in rows]
    for s, ((gt, gl), (wt, wl)) in enumerate(zip(got, want)):
        assert int(gt[s]) == int(wt[s]) and gl[s].tobytes() == wl[s].tobytes()


# ---------------------------------------------------------------------------
# Delivery after dispatch (ISSUE 29): at an admission the decode rows' flushes
# wait for the next program to be on the device; nothing else about a stream
# changes.
# ---------------------------------------------------------------------------
class _Stream:
    """One request's delivery as the serving edge sees it: ``cb`` buffers
    on the scheduler thread, ``flush`` hands the step's batch over."""

    def __init__(self, name: str, log: list | None = None) -> None:
        self.name, self.log = name, log if log is not None else []
        self.pending: list = []
        self.delivered: list = []
        self.done = threading.Event()
        self.req: GenRequest | None = None

    def cb(self, tok, lp, fin, reason):
        self.pending.append((tok, lp, fin, reason))

    def flush(self):
        batch = self.pending.copy()
        self.pending.clear()
        if batch:
            self.delivered.extend(batch)
            self.log.append(("flush", self.name))
            if batch[-1][2]:
                self.done.set()

    def request(self, prompt, **kw) -> GenRequest:
        self.req = GenRequest(prompt_ids=prompt, callback=self.cb,
                              flush_callback=self.flush, **kw)
        return self.req

    def wait_tokens(self, n: int, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while len(self.delivered) < n:
            assert time.monotonic() < deadline, f"{self.name}: {len(self.delivered)} of {n} tokens"
            time.sleep(0.005)


def _record_dispatches(engine, log: list, before_mixed_submit=None) -> None:
    """``log`` gets ("submit" | "fetch", "mixed" | "fresh" | "chained") as
    each engine call RETURNS, on the scheduler thread, in line with the
    streams' flush events."""
    mixed_submit, mixed_fetch = engine.mixed_step_submit, engine.mixed_step_fetch
    chunk_submit, chunk_fetch = engine.decode_chunk_submit, engine.decode_chunk_fetch

    def _mixed_submit(rows, **kw):
        if before_mixed_submit is not None:
            before_mixed_submit(rows)
        handle = mixed_submit(rows, **kw)
        log.append(("submit", "mixed"))
        return handle

    def _chunk_submit(*a, **kw):
        handle = chunk_submit(*a, **kw)
        log.append(("submit", "chained" if kw.get("chain") else "fresh"))
        return handle

    engine.mixed_step_submit = _mixed_submit
    engine.decode_chunk_submit = _chunk_submit
    engine.mixed_step_fetch = lambda h: (mixed_fetch(h), log.append(("fetch", "mixed")))[0]
    engine.decode_chunk_fetch = lambda h: (chunk_fetch(h), log.append(("fetch", "chunk")))[0]


def _prompt(rng, n: int) -> list[int]:
    return [int(x) for x in rng.integers(1, 250, size=n)]


def test_decode_rows_deliver_behind_the_next_dispatch_and_a_first_token_before_it():
    # One request an admission; B's 60 tokens take three mixed steps, C's two.
    engine = _mk_engine(True, mixed_step_tokens=24, max_prefill_batch=1)
    log: list = []
    _record_dispatches(engine, log)
    sched = Scheduler(engine)
    a, b, c = _Stream("a", log), _Stream("b", log), _Stream("c", log)
    rng = np.random.default_rng(5)
    sched.start()
    try:
        sched.submit(a.request(_prompt(rng, 8), max_tokens=200, temperature=0.0))
        a.wait_tokens(9)  # decoding, chunks chained
        start = len(log)
        with sched._wake:  # both queued before the loop sees either
            sched.submit(b.request(_prompt(rng, 60), max_tokens=30, temperature=0.0))
            sched.submit(c.request(_prompt(rng, 40), max_tokens=6, temperature=0.0))
        assert c.done.wait(timeout=120)
        end = len(log)
        for s in (a, b):
            s.req.disconnected = True
        assert a.done.wait(timeout=60) and b.done.wait(timeout=60)
    finally:
        sched.stop()
    in_flight, at = 0, []
    for kind, what in log:
        in_flight += {"submit": 1, "fetch": -1}.get(kind, 0)
        at.append(in_flight)
    window = range(start, end)
    mixed = [i for i in window if log[i] == ("submit", "mixed")]
    fresh = [i for i in window if log[i] == ("submit", "fresh")]
    assert len(mixed) >= 5 and fresh
    # A decodes through both admissions: none of its flushes finds the
    # device empty.
    assert all(at[i] >= 1 for i in window if log[i] == ("flush", "a")), log[start:end]
    # The train's first step found the device empty (the chunks in flight
    # were drained for it): what the drained chunk had to deliver went
    # behind it.
    assert log[mixed[0] - 1] == ("fetch", "chunk")
    assert at[mixed[0]] == 1 and log[mixed[0] + 1] == ("flush", "a"), log[start:end]
    # Every later step of the train, across both admissions, and the fresh
    # chunk that ends it were dispatched BEHIND an unfetched mixed step
    # (ISSUE 40); that step is fetched next, and its rows flush at once, a
    # program running behind them.
    for i in mixed[1:] + fresh[:1]:
        assert at[i] == 2 and log[i + 1] == ("fetch", "mixed"), log[start:end]
        # (a first token of that step leaves inside its emit, ahead of A's row)
        assert ("flush", "a") in log[i + 2:i + 4], log[start:end]
    # B's first token leaves when its step is processed, behind the dispatch
    # that followed it: C's first mixed step (the submit stood back for C's
    # admission). From then on B is a decoding row like A.
    first_b = log.index(("flush", "b"))
    assert at[first_b] == 1 and ("submit", "mixed") in log[first_b - 3:first_b], log[start:end]
    assert all(at[i] >= 1 for i in window if log[i] == ("flush", "b")), log[start:end]
    # C's first token was in the train's last step, processed behind the fresh chunk.
    first_c = log.index(("flush", "c"))
    assert at[first_c] == 1 and ("submit", "fresh") in log[first_c - 4:first_c], log[start:end]
    assert sched.flushes_deferred >= 1 and not sched._deferred
    assert len(c.delivered) == 6 and not any(s.pending for s in (a, b, c))


def _watch_pass_ends(sched: Scheduler) -> list[tuple[int, bool]]:
    """Each time run() finishes a pass: the deferred list's length, and
    whether the next pass opens with an admission onto an empty device."""
    seen: list[tuple[int, bool]] = []
    inner = sched._process_handles

    def checked():
        seen.append((len(sched._deferred), sched._admission_due()))
        inner()
    sched._process_handles = checked
    return seen


@pytest.mark.parametrize("way_out", ["abort_all", "stop", "requeue", "submit_fails",
                                     "last_request_finishes"])
def test_nothing_is_held_without_a_dispatch_behind_it(way_out):
    """Every way out of an admission pass empties the deferred list (a
    pass may end holding it only for the admission that opens the next),
    and every token a callback saw reaches a flush."""
    if way_out == "requeue":
        # test_mixed_admission_requeues_on_page_pressure's pool of 10 pages: B's
        # prompt takes 8 and A's 3 or more, so B cannot fit while A runs.
        engine = Engine(EngineConfig(
            model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
            max_prefill_batch=1, use_mesh=False, prefill_buckets=(16, 32, 64),
            decode_chunk=2, attention="paged", page_size=8, num_pages=10,
            prefix_cache=False, mixed_step=True))
        sched = Scheduler(engine, preempt_max=3)
    else:
        engine = _mk_engine(True, mixed_step_tokens=24)
        sched = Scheduler(engine)
    a, b = _Stream("a"), _Stream("b")
    held: list[int] = []  # the list's length where the way out was taken
    armed = threading.Event()  # set once A decodes and B is on its way

    def before_mixed_submit(rows):
        if not armed.is_set():
            return
        if way_out == "abort_all" and not held:
            held.append(len(sched._deferred))
            sched.abort_all()  # the watchdog's thread, while this one is in the engine
            held.append(len(sched._deferred))
        elif way_out == "submit_fails":
            held.append(len(sched._deferred))
            raise RuntimeError("device lost")
        elif way_out == "last_request_finishes":
            a.req.disconnected = True  # A's row of this step is its last

    _record_dispatches(engine, [], before_mixed_submit)
    requeues: list = []
    requeue = sched._requeue_admission
    sched._requeue_admission = lambda *args: (requeues.append(len(sched._deferred)), requeue(*args))[1]
    pass_ends = _watch_pass_ends(sched)
    rng = np.random.default_rng(31)
    sched.start()
    try:
        sched.submit(a.request(_prompt(rng, 12), max_tokens=48 if way_out == "requeue" else 200,
                               temperature=0.0))
        a.wait_tokens(5)
        armed.set()
        sched.submit(b.request(_prompt(rng, 58 if way_out == "requeue" else 44),
                               max_tokens=1 if way_out == "last_request_finishes" else 4,
                               temperature=0.0))
        if way_out == "stop":
            b.wait_tokens(1)
        else:
            assert a.done.wait(timeout=120) and b.done.wait(timeout=120)
    finally:
        sched.stop()
    assert not sched._thread.is_alive()
    assert not sched._deferred
    assert pass_ends and all(held == 0 or follows for held, follows in pass_ends), pass_ends
    assert not a.pending and not b.pending  # every callback's token was handed over
    # The first terminal event a stream saw (after abort_all the thread, not
    # wedged here, emits on; consumers ignore what follows a terminal event).
    reasons = tuple(next((t[3] for t in s.delivered if t[2]), None) for s in (a, b))
    if way_out == "abort_all":
        assert held[0] >= 1 and held[1] == 0 and reasons == ("error", "error")
    elif way_out == "submit_fails":
        assert held[0] >= 1 and reasons == ("error", "error")
    elif way_out == "requeue":
        assert requeues and max(requeues) >= 1  # A's drained rows were waiting when B was put back
        assert reasons == ("length", "length") and len(a.delivered) == 48
    elif way_out == "last_request_finishes":
        assert reasons == ("disconnected", "length") and sched.flushes_deferred >= 1
        assert not sched._slots


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_each_stream_is_what_it_was_with_every_flush_immediate(temperature):
    """Six seeded requests queued before the loop starts, on four slots two
    an admission, so later ones are admitted into a decoding batch: what each
    stream is handed, in order, does not depend on when it is handed over."""
    engine = _mk_engine(True, mixed_step_tokens=24, prefix_cache=False)
    rng = np.random.default_rng(17)
    shapes = [(9, 14), (40, 6), (17, 22), (5, 9), (33, 12), (12, 17)]
    prompts = [_prompt(rng, n) for n, _ in shapes]

    def serve(deferral: bool):
        sched = Scheduler(engine)
        if not deferral:
            flush = sched._flush_emits
            sched._flush_emits = lambda req, defer=False: flush(req)
        streams = [_Stream(str(i)) for i in range(len(shapes))]
        for s, p, (_, out) in zip(streams, prompts, shapes):
            sched.submit(s.request(p, max_tokens=out, temperature=temperature,
                                   seed=1000 + int(s.name), top_p=0.95 if temperature else 1.0))
        sched.start()
        try:
            assert all(s.done.wait(timeout=120) for s in streams)
        finally:
            sched.stop()
        assert not sched._deferred and not any(s.pending for s in streams)
        return sched, [s.delivered for s in streams]

    immediate_sched, immediate = serve(deferral=False)
    deferred_sched, deferred = serve(deferral=True)
    assert immediate_sched.flushes_deferred == 0 and deferred_sched.flushes_deferred > 0
    assert [len(d) for d in deferred] == [out for _, out in shapes]
    assert deferred == immediate
    assert all(d[-1][2:] == (True, "length") and not any(t[2] for t in d[:-1]) for d in deferred)
