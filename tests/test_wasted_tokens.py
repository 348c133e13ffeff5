"""engine.wasted_tokens{reason} (ISSUE 6, re-homed by ISSUE 46): tokens the
engine computed that no client saw. The scheduler counts them in a plain
dict by reason (``Scheduler.wasted``, its thread alone writes it); the
sidecar hands the growth since its last sample to otel's counter and shows
the dict in /debug/status. No cost model stands between the event and the
count.
"""

import json
import queue
import time

import numpy as np
import pytest

from inference_gateway_tpu.netio.server import Headers, Request
from inference_gateway_tpu.otel.otel import OpenTelemetry
from inference_gateway_tpu.resilience.faults import EngineFaultInjector
from inference_gateway_tpu.serving.engine import STOP_TABLE_WIDTH, Engine, EngineConfig
from inference_gateway_tpu.serving.scheduler import (
    WASTE_REASONS,
    GenRequest,
    Scheduler,
    generate_sync,
)
from inference_gateway_tpu.serving.server import SidecarServer


def _engine(**over) -> Engine:
    kw = dict(model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
              max_prefill_batch=2, use_mesh=False)
    kw.update(over)
    return Engine(EngineConfig(**kw))


@pytest.fixture(scope="module")
def tiny_engine():
    return _engine()


def _host_only_stop(base: list[int]) -> tuple[int, frozenset[int]]:
    """A stop id the stream ``base`` first emits inside its first chunk, with
    enough smaller ids beside it that it falls off the device's
    STOP_TABLE_WIDTH-wide row: only the host sees that finish."""
    hit = next(t for i, t in enumerate(base)
               if 1 <= i <= 5 and base.index(t) == i and t >= 2 * STOP_TABLE_WIDTH)
    filler = [t for t in range(hit) if t not in base][:STOP_TABLE_WIDTH]
    assert len(filler) == STOP_TABLE_WIDTH  # they fill the row; the hit falls off it
    return hit, frozenset(filler + [hit])


# ---------------------------------------------------------------------------
# The hand-over: the dict's growth reaches the counter once
# ---------------------------------------------------------------------------
def test_wasted_tokens_reach_the_counter(tiny_engine):
    otel = OpenTelemetry()
    sidecar = SidecarServer(tiny_engine, scheduler=Scheduler(tiny_engine),
                            served_model_name="test-tiny", otel=otel)
    sidecar.scheduler.wasted["spec_rejected"] += 7
    sidecar.scheduler.wasted["disconnected"] += 3
    sidecar.sample_engine_gauges()
    sidecar.sample_engine_gauges()  # a second sample of the same counts adds nothing
    vals = otel.wasted_tokens_counter.values()
    assert vals[("test-tiny", "spec_rejected")] == 7
    assert vals[("test-tiny", "disconnected")] == 3
    sidecar.scheduler.wasted["spec_rejected"] += 2
    sidecar.sample_engine_gauges()
    assert otel.wasted_tokens_counter.values()[("test-tiny", "spec_rejected")] == 9
    expo = otel.expose_prometheus()
    assert 'engine_wasted_tokens{gen_ai_request_model="test-tiny",reason="spec_rejected"} 9' in expo


# ---------------------------------------------------------------------------
# Scheduler integration
# ---------------------------------------------------------------------------
def test_scheduler_attributes_disconnects(tiny_engine):
    sched = Scheduler(tiny_engine)
    assert sched.wasted == dict.fromkeys(WASTE_REASONS, 0)
    sched.start()
    try:
        generate_sync(sched, [1, 2, 3, 4], max_tokens=12)
        # A disconnected client terminates at the next decode step
        # (ISSUE 7 early-terminate) — the tokens decoded before the
        # scheduler noticed are still counted as waste (ISSUE 6), but the
        # request no longer burns the full max_tokens.
        req = GenRequest(prompt_ids=[5, 6, 7], max_tokens=64, disconnected=True)
        done = queue.Queue()
        req.callback = lambda t, lp, fin, r: done.put((fin, r)) if fin else None
        sched.submit(req)
        fin, reason = done.get(timeout=60.0)
        assert fin and reason == "disconnected"
        assert 1 <= sched.wasted["disconnected"] < 64
    finally:
        sched.stop()


def test_spec_waste_attribution():
    sched = Scheduler(_engine(max_slots=2, spec_draft="ngram", spec_k=4))
    sched.start()
    try:
        generate_sync(sched, [7, 8, 9, 7, 8, 9, 7, 8], max_tokens=16)
        # Random tiny weights reject most n-gram proposals: rejected
        # verify positions must land in the waste ledger.
        assert sched.wasted["spec_rejected"] > 0
    finally:
        sched.stop()


def test_a_self_drafting_models_refused_positions_are_spec_rejected_waste_from_the_step_programs():
    """ISSUE 56: no separate speculative loop runs; the two step programs count
    their own rounds (a fetch block's rows), and every refused draft's position
    lands under the same reason, nothing under the others."""
    sched = Scheduler(Engine(EngineConfig(model="openpangu-test-tiny", attention="paged", page_size=16, max_slots=2,
                                          max_seq_len=128, dtype="float32", use_mesh=False, decode_chunk=4)))
    assert sched.engine.mtp and not sched.engine.spec
    sched.start()
    try:
        generate_sync(sched, [7, 8, 9, 7, 8, 9, 7, 8], max_tokens=16)
        assert sched.spec_slot_rounds > 0 and sched.spec_rounds > 0
        assert sched.wasted["spec_rejected"] == sched.spec_slot_rounds - sched.spec_accepted > 0
        assert sched.wasted["chunk_overrun"] == 0 and sched.wasted["overshoot"] == 0
    finally:
        sched.stop()


def test_early_exit_zeroes_chunk_overrun_waste():
    """ISSUE 14 regression: a stream that finishes mid-chunk on a
    criterion the DEVICE holds (its max_tokens budget) records zero
    wasted_tokens{reason="chunk_overrun"} — the device froze the row at
    the finish, so the trailing steps were never computed and must not
    be double-counted as waste — at eight steps a chunk and at the
    one-step reference alike. A finish only the HOST sees (a stop id
    past the device's STOP_TABLE_WIDTH-wide row) over-decodes and is
    attributed as ever: the contrast pins that the suppression keys off
    device_stopped, not off the counting going dead."""
    def overrun_of(decode_chunk, **gen):
        sched = Scheduler(_engine(decode_chunk=decode_chunk))
        sched.start()
        try:
            out = generate_sync(sched, [1, 2, 3, 4], **gen)
            # Wait for the pipeline tail (the in-flight chunks carrying
            # the finished stream) to drain — that is where overrun is
            # attributed.
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and (sched._handles or sched._slots):
                time.sleep(0.02)
            return sched.wasted["chunk_overrun"], out
        finally:
            sched.stop()

    # max_tokens=3 finishes in the middle of the first 8-step chunk,
    # with pipeline_depth more chunks already in flight.
    for decode_chunk in (8, 1):
        overrun, _ = overrun_of(decode_chunk, max_tokens=3)
        assert overrun == 0, (
            f"decode_chunk={decode_chunk}: a device-detected finish still "
            f"billed {overrun} overrun tokens")
    _, (base, _reason) = overrun_of(8, max_tokens=8)
    hit, stops = _host_only_stop(base)
    overrun, (toks, reason) = overrun_of(8, max_tokens=8, stop_token_ids=stops)
    assert reason == "stop" and toks == base[:base.index(hit)]
    assert overrun > 0, "a host-only finish stopped attributing overrun"


# ---------------------------------------------------------------------------
# Every reason, through the scheduler path that raises it, to the counter
# and to /debug/status (ISSUE 46). Stepped by hand on the test's thread.
# ---------------------------------------------------------------------------
class _Client:
    def __init__(self, prompt: list[int], **kw) -> None:
        self.seen: list = []
        self.req = GenRequest(prompt_ids=prompt, callback=lambda *a: self.seen.append(a), **kw)

    @property
    def tokens(self) -> list[int]:
        return [t for t, _, _, _ in self.seen]

    @property
    def ends(self) -> list:
        return [r for _, _, fin, r in self.seen if fin]


def _run_out(sched: Scheduler, limit: int = 400) -> None:
    for _ in range(limit):
        if not (sched._waiting or sched._slots or sched._handles or sched._mixed_inflight):
            return
        sched._pass()
    raise AssertionError("the scheduler never ran out of work")


def _prompt(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(1, 250, n)]


def _raise_spec_rejected() -> Scheduler:
    sched = Scheduler(_engine(max_slots=2, spec_draft="ngram", spec_k=4))
    sched.submit(_Client([7, 8, 9, 7, 8, 9, 7, 8], max_tokens=16).req)
    _run_out(sched)
    return sched


def _raise_chunk_overrun() -> Scheduler:
    sched = Scheduler(_engine(decode_chunk=8))
    base = _Client([1, 2, 3, 4], max_tokens=8)
    sched.submit(base.req)
    _run_out(sched)
    assert sched.wasted["chunk_overrun"] == 0  # the budget is the device's to see
    _hit, stops = _host_only_stop(base.tokens)
    cut = _Client([1, 2, 3, 4], max_tokens=8, stop_token_ids=stops)
    sched.submit(cut.req)
    _run_out(sched)
    assert cut.ends == ["stop"]
    return sched


def _raise_disconnected() -> Scheduler:
    sched = Scheduler(_engine())
    gone = _Client([5, 6, 7], max_tokens=64, disconnected=True)
    sched.submit(gone.req)
    _run_out(sched)
    assert gone.ends == ["disconnected"]
    return sched


def _raise_shed_after_prefill() -> Scheduler:
    engine = _engine(decode_chunk=2)
    sched = Scheduler(engine, preempt_max=0)
    EngineFaultInjector(engine).at("decode_fetch", 0, "error")
    lost = _Client([1, 2, 3, 4, 5], max_tokens=16)
    sched.submit(lost.req)
    _run_out(sched)
    assert lost.ends == ["error"]
    # The prompt and whatever was decoded before the device failed.
    assert sched.wasted["shed_after_prefill"] >= len(lost.req.prompt_ids) + 1
    return sched


def _raise_overshoot() -> Scheduler:
    """A's stop id is sampled by one step of B's admission train while the
    next step, with A's row in it, is already dispatched (ISSUE 40)."""
    def mixed() -> Scheduler:
        return Scheduler(_engine(attention="paged", page_size=16, max_seq_len=256, mixed_step=True,
                                 mixed_step_tokens=24, decode_chunk=4, prefix_cache=False))

    ref = _Client(_prompt(1000, 8), max_tokens=40, temperature=0.7, seed=100)
    probe = mixed()
    probe.submit(ref.req)
    _run_out(probe)
    stream = ref.tokens
    sched = mixed()
    a = _Client(_prompt(1000, 8), max_tokens=40, temperature=0.7, seed=100)
    sched.submit(a.req)
    sched._pass()
    sched._drain_all()
    had = len(a.seen)
    k = next(j for j in range(had + 2, 40) if stream[j] not in stream[:j])
    assert k < had + 5, (had, k)  # inside B's six-step train
    a.req.stop_token_ids = frozenset({stream[k]})
    sched.submit(_Client(_prompt(1001, 130), max_tokens=3, temperature=0.7, seed=101).req)
    _run_out(sched)
    assert a.tokens == stream[:k + 1] and a.ends == ["stop"]
    assert sched.wasted["overshoot"] == 1
    return sched


_RAISE = {"spec_rejected": _raise_spec_rejected, "chunk_overrun": _raise_chunk_overrun,
          "disconnected": _raise_disconnected, "shed_after_prefill": _raise_shed_after_prefill,
          "overshoot": _raise_overshoot}


def test_the_scenarios_cover_the_reasons():
    assert set(_RAISE) == set(WASTE_REASONS)


@pytest.mark.parametrize("reason", list(_RAISE))
async def test_every_waste_reason_reaches_counter_and_status(reason):
    sched = _RAISE[reason]()
    counted = sched.wasted[reason]
    assert counted > 0
    otel = OpenTelemetry()
    sidecar = SidecarServer(sched.engine, scheduler=sched, served_model_name="test-tiny", otel=otel)
    get = lambda path: Request(method="GET", path=path, query={}, headers=Headers(), body=b"")  # noqa: E731
    metrics = json.loads((await sidecar.metrics(get("/metrics"))).body)  # a scrape samples the gauges
    assert metrics["wasted_tokens"] == sum(sched.wasted.values())
    assert otel.wasted_tokens_counter.values()[("test-tiny", reason)] == counted
    status = json.loads((await sidecar.debug_status(get("/debug/status"))).body)
    assert set(status["wasted_tokens"]) == set(WASTE_REASONS)
    assert status["wasted_tokens"][reason] == counted
    assert "compute_efficiency" not in status
