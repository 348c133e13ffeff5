"""Serving-path fault tolerance (ISSUE 7).

- KV-pressure preemption: page exhaustion deschedules the youngest
  budgeted request (slot + pages released, re-enqueued with
  prompt+generated-so-far) instead of failing anyone; greedy streams
  resume byte-identical with no token dropped or repeated, and the
  per-request budget degrades livelock to today's clean failure.
- Disconnected early-terminate: an abandoned stream (flag set, or a
  callback that raises) finishes at the next decode step and frees its
  slot/KV pages instead of decoding to max_tokens.
- Oversized-prompt fast-fail: paged-mode prompts above the largest
  prefill bucket get a structured 400 before a slot is allocated.
- Engine hang watchdog + supervised restart: an injected step hang
  trips the step deadline, forensics are captured, in-flight requests
  fail retryably, the Engine is rebuilt in place, and a fresh request
  is served without a process restart (acceptance criterion 2) — all on
  a VirtualClock, zero real sleeps.
"""

import json
import queue
import threading
import time

import pytest

from inference_gateway_tpu.netio.client import HTTPClient
from inference_gateway_tpu.netio.sse import iter_sse_payloads
from inference_gateway_tpu.otel.otel import OpenTelemetry
from inference_gateway_tpu.resilience.clock import VirtualClock
from inference_gateway_tpu.resilience.faults import EngineFaultInjector
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler
from inference_gateway_tpu.serving.server import SidecarServer
from inference_gateway_tpu.serving.watchdog import EngineWatchdog


def _collect_stream(scheduler, prompt, max_tokens=8, timeout=120.0, request_id=""):
    """Submit one request; return (visible_tokens, final_reason).
    Terminal stop/error markers are excluded, matching generate_sync."""
    q: queue.Queue = queue.Queue()
    scheduler.submit(GenRequest(
        prompt_ids=list(prompt), max_tokens=max_tokens, request_id=request_id,
        callback=lambda tok, lp, fin, reason: q.put((tok, fin, reason)),
    ))
    toks = []
    deadline = time.monotonic() + timeout
    while True:
        tok, fin, reason = q.get(timeout=max(deadline - time.monotonic(), 0.1))
        if not (fin and reason in ("stop", "error")):
            toks.append(tok)
        if fin:
            return toks, reason


def _start_many(scheduler, prompts, max_tokens):
    """Submit all prompts concurrently; return {i: (tokens, reason)}."""
    results: "queue.Queue[tuple]" = queue.Queue()
    streams: dict[int, list[int]] = {i: [] for i in range(len(prompts))}

    def cb_factory(i):
        def cb(tok, lp, fin, reason):
            if not (fin and reason in ("stop", "error")):
                streams[i].append(tok)
            if fin:
                results.put((i, reason))
        return cb

    for i, (prompt, mt) in enumerate(zip(prompts, max_tokens)):
        scheduler.submit(GenRequest(prompt_ids=list(prompt), max_tokens=mt,
                                    callback=cb_factory(i), request_id=f"c{i}"))
    got = {}
    for _ in prompts:
        i, reason = results.get(timeout=120)
        got[i] = (streams[i], reason)
    return got


# ---------------------------------------------------------------------------
# KV-pressure preemption
# ---------------------------------------------------------------------------
def test_organic_page_exhaustion_preempts_and_resumes_byte_identical():
    """Acceptance (criterion 1, scheduler level): a paged pool too small
    for two growing requests completes BOTH — the youngest is preempted
    and resumes with a byte-identical total token stream."""
    cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=96, dtype="float32",
                       max_prefill_batch=2, use_mesh=False, attention="paged",
                       page_size=16, num_pages=6, prefix_cache=False, decode_chunk=4,
                       prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    s = Scheduler(eng, preempt_max=5)
    s.start()
    try:
        a_prompt, b_prompt = [2] * 40, [3] * 33
        a_mt, b_mt = 12, 26
        # Baselines: each request alone (no pressure), greedy.
        base_a, ra = _collect_stream(s, a_prompt, a_mt)
        base_b, rb = _collect_stream(s, b_prompt, b_mt)
        assert ra in ("stop", "length") and rb in ("stop", "length")

        got = _start_many(s, [a_prompt, b_prompt], [a_mt, b_mt])
        for i, (toks, reason) in got.items():
            assert reason in ("stop", "length"), (i, reason)
        assert got[0][0] == base_a
        assert got[1][0] == base_b
        assert s.preemptions >= 1
        # Pool bookkeeping intact: everything released after the dust.
        deadline = time.monotonic() + 10
        while s.active_requests() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.allocator.free_page_count() == eng.allocator.num_pages
    finally:
        s.stop()


def test_injected_exhaustion_preempts_youngest_not_starved():
    """An exhaust fault attributed to the OLDEST slot preempts the
    youngest budgeted request; the starved one keeps running."""
    cfg = EngineConfig(model="test-tiny", max_slots=4, max_seq_len=96, dtype="float32",
                       max_prefill_batch=2, use_mesh=False, attention="dense",
                       decode_chunk=2, prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    s = Scheduler(eng, preempt_max=3)
    s.start()
    inj = EngineFaultInjector(eng)
    try:
        base_a, _ = _collect_stream(s, [5, 6, 7], 10)
        base_b, _ = _collect_stream(s, [8, 9], 10)
        # Fault an upcoming decode dispatch (indices are absolute from
        # injector install, so offset past the baselines' calls). The
        # injector tags an active slot; whichever is blamed, the
        # YOUNGEST budgeted request is the victim.
        inj.at("decode_submit", inj.calls["decode_submit"] + 2, "exhaust")
        got = _start_many(s, [[5, 6, 7], [8, 9]], [10, 10])
        assert got[0] == (base_a, got[0][1]) and got[0][1] in ("stop", "length")
        assert got[1] == (base_b, got[1][1]) and got[1][1] in ("stop", "length")
        assert s.preemptions >= 1
    finally:
        inj.uninstall()
        s.stop()


def test_preemption_budget_degrades_to_clean_failure():
    """Exhaustion beyond the per-request budget fails the request with
    finish_reason "error" (today's behavior), never a hang."""
    cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=96, dtype="float32",
                       max_prefill_batch=2, use_mesh=False, attention="dense",
                       decode_chunk=2, prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    s = Scheduler(eng, preempt_max=1)
    s.start()
    inj = EngineFaultInjector(eng)
    try:
        # Every decode dispatch exhausts: the lone request is preempted
        # once (budget), then cleanly failed.
        for i in range(12):
            inj.at("decode_submit", i, "exhaust")
        toks, reason = _collect_stream(s, [4, 5, 6], 8)
        assert reason == "error"
        # Budget respected and the loop survives with faults cleared.
        inj.uninstall()
        toks, reason = _collect_stream(s, [4, 5], 4)
        assert reason in ("stop", "length")
        assert s.preemptions == 1
    finally:
        inj.uninstall()
        s.stop()


def test_admission_exhaustion_requeues_instead_of_failing():
    """A pool that can only hold one request at a time serializes the
    two requests (requeue + page-wait latch) — nobody errors."""
    cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64, dtype="float32",
                       max_prefill_batch=1, use_mesh=False, attention="paged",
                       page_size=16, num_pages=4, prefix_cache=False, decode_chunk=2,
                       prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    s = Scheduler(eng, preempt_max=3)
    s.start()
    try:
        got = _start_many(s, [[2] * 40, [3] * 40], [8, 8])
        for i, (toks, reason) in got.items():
            assert reason in ("stop", "length"), (i, reason)
            assert len(toks) >= 1
    finally:
        s.stop()


def test_preemption_disabled_keeps_fail_on_exhaustion():
    """preempt_max=0 (direct Scheduler construction): page exhaustion
    still fails the request — the pre-ISSUE-7 contract."""
    cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=96, dtype="float32",
                       max_prefill_batch=2, use_mesh=False, attention="dense",
                       decode_chunk=2, prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    s = Scheduler(eng)
    s.start()
    inj = EngineFaultInjector(eng)
    try:
        inj.at("decode_submit", 0, "exhaust")
        toks, reason = _collect_stream(s, [4, 5, 6], 8)
        assert reason == "error"
        assert s.preemptions == 0
    finally:
        inj.uninstall()
        s.stop()


def test_high_water_admission_preemption():
    """With the high-water mark armed, a waiting request preempts the
    youngest running one when KV utilization is above the mark."""
    cfg = EngineConfig(model="test-tiny", max_slots=1, max_seq_len=64, dtype="float32",
                       max_prefill_batch=1, use_mesh=False, attention="paged",
                       page_size=16, num_pages=4, prefix_cache=False, decode_chunk=2,
                       prefill_buckets=(16, 32, 64))
    eng = Engine(cfg)
    s = Scheduler(eng, preempt_max=2, preempt_high_water=0.25)
    s.start()
    try:
        got = _start_many(s, [[2] * 33, [3] * 20], [24, 6])
        for i, (toks, reason) in got.items():
            assert reason in ("stop", "length"), (i, reason)
        # The long request held >0.25 of the pool while the short one
        # waited: at least one high-water preemption fired.
        assert s.preemptions >= 1
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# Serving edge: preemption through the sidecar (acceptance criterion 1)
# ---------------------------------------------------------------------------
async def _sse_text(port, content, max_tokens):
    client = HTTPClient()
    body = json.dumps({"model": "test-tiny", "stream": True, "max_tokens": max_tokens,
                       "temperature": 0,
                       "messages": [{"role": "user", "content": content}]}).encode()
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             body, stream=True)
    assert resp.status == 200
    text, finish = "", None
    async for payload in iter_sse_payloads(resp.iter_lines()):
        chunk = json.loads(payload)
        for choice in chunk.get("choices", []):
            delta = choice.get("delta") or {}
            text += delta.get("content") or ""
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
    return text, finish


def test_preemption_e2e_serving_edge(aloop):
    """Injected exhaustion under concurrent load at the serving edge:
    every stream completes, preempted ones byte-identical to their solo
    baselines, engine.preemptions lands in otel."""
    import asyncio

    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 decode_chunk=2))
    otel = OpenTelemetry()
    sidecar = SidecarServer(engine, served_model_name="test-tiny", otel=otel,
                            preempt_max=3)
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    inj = EngineFaultInjector(engine)
    try:
        prompts = ["alpha beta", "gamma delta"]
        base = [aloop.run(_sse_text(port, p, 10)) for p in prompts]
        for text, finish in base:
            assert finish in ("stop", "length")
        inj.at("decode_submit", inj.calls["decode_submit"] + 2, "exhaust")

        async def both():
            return await asyncio.gather(*(_sse_text(port, p, 10) for p in prompts))

        got = aloop.run(both())
        for (text, finish), (btext, _bf) in zip(got, base):
            assert finish in ("stop", "length")
            assert text == btext
        assert sidecar.scheduler.preemptions >= 1
        vals = otel.engine_preemption_counter.values()
        assert sum(vals.values()) >= 1
        assert ("test-tiny", "kv_pressure") in vals
        # /metrics exports the counter too.
        m = aloop.run(HTTPClient().get(f"http://127.0.0.1:{port}/metrics")).json()
        assert m["preemptions"] >= 1
    finally:
        inj.uninstall()
        aloop.run(sidecar.shutdown())


# ---------------------------------------------------------------------------
# Disconnected early-terminate (ISSUE 7 satellite)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dense_sched():
    eng = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                              dtype="float32", max_prefill_batch=2, use_mesh=False,
                              decode_chunk=2))
    s = Scheduler(eng)
    s.start()
    yield s
    s.stop()


def test_disconnected_terminates_early_and_frees_slot(dense_sched):
    s = dense_sched
    q_: queue.Queue = queue.Queue()
    req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=256,
                     callback=lambda t, lp, fin, r: q_.put((t, fin, r)))
    s.submit(req)
    tok, fin, reason = q_.get(timeout=60)  # first token
    req.disconnected = True
    emitted = 1
    while not fin:
        tok, fin, reason = q_.get(timeout=60)
        emitted += 1
    assert reason == "disconnected"
    # Terminated orders of magnitude before max_tokens (the pipeline
    # can emit at most a few in-flight chunks after the flag).
    assert emitted < 40
    deadline = time.monotonic() + 10
    while s.active_requests() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert s.active_requests() == 0


def test_raising_callback_marks_disconnected_and_terminates(dense_sched):
    s = dense_sched
    calls = {"n": 0}
    done = threading.Event()

    def bad_cb(tok, lp, fin, reason):
        calls["n"] += 1
        if fin:
            done.set()
        if calls["n"] >= 2:
            raise RuntimeError("client went away")

    s.submit(GenRequest(prompt_ids=[7, 8, 9], max_tokens=256, callback=bad_cb))
    assert done.wait(timeout=60), "request never terminated"
    # Early termination, not 256 tokens of silent decode.
    assert calls["n"] < 40


# ---------------------------------------------------------------------------
# Oversized-prompt fast-fail (ISSUE 7 satellite)
# ---------------------------------------------------------------------------
def test_oversized_prompt_fast_fails_400_in_paged_mode(aloop):
    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 attention="paged", page_size=16, prefix_cache=False,
                                 prefill_buckets=(16, 32)))
    sidecar = SidecarServer(engine, served_model_name="test-tiny")
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    try:
        assert engine.max_prompt_len() == 32
        client = HTTPClient()
        # "word " * 4 tokenizes to ~45 ids: above the 32-token bucket,
        # below the 128-token context window — the fast-fail band.
        body = json.dumps({"model": "test-tiny", "max_tokens": 4,
                           "messages": [{"role": "user", "content": "word " * 4}]}).encode()
        resp = aloop.run(client.post(
            f"http://127.0.0.1:{port}/v1/chat/completions", body))
        assert resp.status == 400
        err = resp.json()["error"]
        assert err["code"] == "prompt_too_long"
        assert err["type"] == "invalid_request_error"
        assert err["max_prompt_tokens"] == 32
        # No slot was ever allocated, no page touched.
        assert sidecar.scheduler.active_requests() == 0
        assert engine.allocator.free_page_count() == engine.allocator.num_pages
        # A prompt within the bucket still serves.
        ok = json.dumps({"model": "test-tiny", "max_tokens": 4,
                         "messages": [{"role": "user", "content": "hi"}]}).encode()
        resp = aloop.run(client.post(
            f"http://127.0.0.1:{port}/v1/chat/completions", ok))
        assert resp.status == 200
    finally:
        aloop.run(sidecar.shutdown())


def test_max_prompt_len_dense_engine_allows_window():
    eng = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                              dtype="float32", use_mesh=False, attention="dense",
                              prefill_buckets=(16, 32)))
    # Dense non-MoE has the chunked long-prompt path: window-bounded.
    assert eng.max_prompt_len() == eng.context_window() - 1
    # Multimodal rows can't ride it: bucket-bounded.
    assert eng.max_prompt_len(multimodal=True) == 32


# ---------------------------------------------------------------------------
# Engine hang watchdog + supervised restart (acceptance criterion 2)
# ---------------------------------------------------------------------------
def test_watchdog_deadline_floors_and_scales():
    wd = EngineWatchdog(multiplier=10.0, min_deadline=5.0, clock=VirtualClock())

    class _FakeSched:
        step_ewma = 0.0

    class _FakeSidecar:
        scheduler = _FakeSched()

    wd.bind(_FakeSidecar())
    assert wd.deadline() == 5.0  # floor with no estimate
    _FakeSched.step_ewma = 2.0
    assert wd.deadline() == 20.0  # multiplier × EWMA


@pytest.mark.parametrize("when", ["before_the_first_step", "after_it"])
def test_watchdog_deadline_needs_no_cost_model(when):
    """ISSUE 46: the deadline is max(min_deadline, multiplier x step_ewma)
    and nothing else. A real sidecar carries no cost model to fall back
    on: before a step has been recorded the floor alone decides; after,
    the scheduler's own smoothed step time does."""
    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 decode_chunk=2))
    floor = 5.0 if when == "before_the_first_step" else 1e-9
    wd = EngineWatchdog(multiplier=20.0, min_deadline=floor, clock=VirtualClock())
    sidecar = SidecarServer(engine, served_model_name="test-tiny", engine_watchdog=wd)
    assert not hasattr(sidecar, "accounting") and not hasattr(sidecar.scheduler, "accounting")
    sched = sidecar.scheduler
    assert sched.step_ewma == 0.0
    if when == "before_the_first_step":
        assert wd.deadline() == 5.0
        assert wd.stats()["deadline_seconds"] == 5.0
        return
    sched.start()
    try:
        toks, _ = _collect_stream(sched, [1, 2, 3, 4], max_tokens=8)
        assert toks
    finally:
        sched.stop()
    assert sched.step_ewma > 0  # the timeline's records smoothed it
    assert wd.deadline() == pytest.approx(20.0 * sched.step_ewma)


def test_step_hang_trips_watchdog_and_engine_restarts_in_place(aloop):
    """Acceptance: injected step hang → watchdog trips on the virtual
    clock → forensics captured → in-flight request fails retryably →
    Engine rebuilt in place → a fresh request serves. No process
    restart, no real sleeps."""
    import asyncio

    cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                       dtype="float32", max_prefill_batch=2, use_mesh=False,
                       decode_chunk=2)
    engine = Engine(cfg)
    clk = VirtualClock()
    wd = EngineWatchdog(interval=1.0, multiplier=2.0, min_deadline=5.0, clock=clk)
    otel = OpenTelemetry()
    sidecar = SidecarServer(engine, served_model_name="test-tiny", otel=otel,
                            engine_watchdog=wd,
                            engine_factory=lambda: Engine(cfg))
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    inj = EngineFaultInjector(engine)
    try:
        inj.at("decode_fetch", 0, "hang")

        async def doomed():
            return await _sse_text(port, "hang probe", 32)

        fut = asyncio.run_coroutine_threadsafe(doomed(), aloop.loop)
        # The scheduler thread wedges inside the injected hang.
        assert inj.hanging.wait(timeout=60), "engine never wedged"
        old_sched = sidecar.scheduler
        assert old_sched.active_requests() > 0

        assert aloop.run(wd.check()) is False  # baseline progress tick
        clk.advance(10.0)  # past the 5s deadline, virtually
        assert aloop.run(wd.check()) is True  # tripped + restarted

        # The in-flight stream was MIGRATED out (ISSUE 11): it ends at a
        # token boundary with no terminal frame, so a continuation-
        # capable gateway splices it onto another replica instead of the
        # client ever seeing an error. (migrate_streams=False restores
        # the terminal "error" frame — pinned in test_fleet_migration.)
        text, finish = fut.result(timeout=60)
        assert finish is None
        assert sidecar.migrated_out == 1
        assert sidecar.last_restart["migrated_streams"] == 1
        # Supervised restart: new engine + scheduler objects, in-process.
        assert sidecar.engine is not engine
        assert sidecar.scheduler is not old_sched
        assert sidecar.state == "ok"
        assert sidecar.restarts == 1
        info = sidecar.last_restart
        assert info["reason"] == "step_deadline_exceeded"
        assert info["failed_requests"] >= 1
        assert any("decode" in line or "fetch" in line
                   for line in info["forensics"].get("scheduler_stack", [])), (
            "mid-stall scheduler stack missing from forensics")
        # Telemetry: restart counter + degraded gauge back to 0.
        assert otel.engine_restart_counter.values()[
            ("test-tiny", "step_deadline_exceeded")] == 1
        assert otel.engine_degraded_gauge.values()[("test-tiny",)] == 0
        # Health is ready again and a fresh request serves end to end.
        health = aloop.run(HTTPClient().get(f"http://127.0.0.1:{port}/health"))
        assert health.status == 200
        text, finish = aloop.run(_sse_text(port, "after restart", 6))
        assert finish in ("stop", "length")
        assert text  # real tokens from the rebuilt engine
    finally:
        inj.release_hangs()
        aloop.run(sidecar.shutdown())


def test_prefill_hang_trips_watchdog_and_mid_admission_batch_fails(aloop):
    """Code-review regressions: a prefill that wedges MID-ADMISSION
    leaves its batch in neither _waiting nor _slots — the watchdog's
    busy gate must still see the work (queue/_admitting), abort_all
    must still fail those clients, and a request arriving during the
    restart window gets a retryable 503 instead of hanging on the
    stopped old scheduler."""
    import asyncio

    cfg = EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                       dtype="float32", max_prefill_batch=2, use_mesh=False,
                       decode_chunk=2)
    engine = Engine(cfg)
    clk = VirtualClock()
    wd = EngineWatchdog(interval=1.0, multiplier=2.0, min_deadline=5.0, clock=clk)
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            engine_watchdog=wd, engine_factory=lambda: Engine(cfg))
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    inj = EngineFaultInjector(engine)
    try:
        inj.at("prefill", inj.calls["prefill"], "hang")

        async def doomed():
            return await _sse_text(port, "wedged at admission", 8)

        fut = asyncio.run_coroutine_threadsafe(doomed(), aloop.loop)
        assert inj.hanging.wait(timeout=60), "prefill never wedged"
        old_sched = sidecar.scheduler
        # The wedged batch is invisible to _slots — the old blind spot.
        assert old_sched.active_requests() == 0
        assert old_sched._admitting

        assert aloop.run(wd.check()) is False  # baseline
        clk.advance(10.0)
        # A request arriving mid-restart must not hang: make the restart
        # window observable by checking right after the trip.
        assert aloop.run(wd.check()) is True

        # The mid-admission stream is migrated out, not error-framed
        # (ISSUE 11): no terminal frame, resumable by a continuation-
        # capable gateway from its (empty) relayed prefix.
        text, finish = fut.result(timeout=60)
        assert finish is None
        assert sidecar.restarts == 1
        # Fresh request serves on the rebuilt engine.
        text, finish = aloop.run(_sse_text(port, "after restart", 4))
        assert finish in ("stop", "length")
    finally:
        inj.release_hangs()
        aloop.run(sidecar.shutdown())


def test_submit_to_stopped_scheduler_raises_and_sidecar_503s(aloop):
    from inference_gateway_tpu.serving.scheduler import SchedulerStoppedError

    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64,
                                 dtype="float32", use_mesh=False))
    sidecar = SidecarServer(engine, served_model_name="test-tiny")
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    try:
        # Direct scheduler contract: submit after abort raises instead
        # of enqueueing into a dead loop.
        sidecar.scheduler.abort_all()
        with pytest.raises(SchedulerStoppedError):
            sidecar.scheduler.submit(GenRequest(prompt_ids=[1, 2]))
        # Serving edge during a restart window: retryable 503.
        sidecar.state = "degraded"
        body = json.dumps({"model": "test-tiny", "max_tokens": 4,
                           "messages": [{"role": "user", "content": "x"}]}).encode()
        resp = aloop.run(HTTPClient().post(
            f"http://127.0.0.1:{port}/v1/chat/completions", body))
        assert resp.status == 503
        assert resp.json()["error"]["code"] == "engine_restarting"
        assert resp.headers.get("Retry-After") is not None
    finally:
        sidecar.state = "ok"
        aloop.run(sidecar.shutdown())


def test_abort_all_is_idempotent():
    eng = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64,
                              dtype="float32", use_mesh=False))
    s = Scheduler(eng)
    terminal = []
    s.submit(GenRequest(prompt_ids=[1, 2], callback=lambda t, lp, fin, r:
                        terminal.append(r) if fin else None))
    assert s.abort_all() == 1
    # A second trip (failed engine rebuild → watchdog re-fires) must not
    # re-fail the same clients.
    assert s.abort_all() == 0
    assert terminal == ["error"]


def test_health_degraded_during_restart_window(aloop):
    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64,
                                 dtype="float32", use_mesh=False))
    sidecar = SidecarServer(engine, served_model_name="test-tiny")
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    try:
        sidecar.state = "degraded"
        resp = aloop.run(HTTPClient().get(f"http://127.0.0.1:{port}/health"))
        assert resp.status == 503
        assert resp.json()["status"] == "degraded"
        sidecar.state = "ok"
        resp = aloop.run(HTTPClient().get(f"http://127.0.0.1:{port}/health"))
        assert resp.status == 200
    finally:
        aloop.run(sidecar.shutdown())


def test_non_streaming_engine_failure_is_retryable_503(aloop):
    """An engine-side failure on a buffered request returns 503 +
    Retry-After (the resilience layer retries those), not a 200 with
    finish_reason "error"."""
    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=64,
                                 dtype="float32", max_prefill_batch=1, use_mesh=False,
                                 decode_chunk=2))
    sidecar = SidecarServer(engine, served_model_name="test-tiny", preempt_max=0)
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    inj = EngineFaultInjector(engine)
    try:
        inj.at("prefill", 0, "error")
        body = json.dumps({"model": "test-tiny", "max_tokens": 4,
                           "messages": [{"role": "user", "content": "x"}]}).encode()
        resp = aloop.run(HTTPClient().post(
            f"http://127.0.0.1:{port}/v1/chat/completions", body))
        assert resp.status == 503
        assert resp.json()["error"]["code"] == "engine_failure"
        assert resp.headers.get("Retry-After") is not None
        # The engine recovered: next request serves.
        resp = aloop.run(HTTPClient().post(
            f"http://127.0.0.1:{port}/v1/chat/completions", body))
        assert resp.status == 200
    finally:
        inj.uninstall()
        aloop.run(sidecar.shutdown())
