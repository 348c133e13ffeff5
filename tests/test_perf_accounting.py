"""Compute-efficiency accounting (ISSUE 6): cost-model closed forms,
plan-vs-engine drift guards, wasted-work attribution, the timeline
failure damper, and the /debug/roofline e2e on the CPU engine.
"""

import json
import time

import pytest

from inference_gateway_tpu.models import mixtral
from inference_gateway_tpu.netio.client import HTTPClient
from inference_gateway_tpu.otel.otel import OpenTelemetry
from inference_gateway_tpu.otel.perf_accounting import (
    CHIP_SPECS,
    PerfAccounting,
    StepCostModel,
    roofline_report,
)
from inference_gateway_tpu.serving.engine import STOP_TABLE_WIDTH, Engine, EngineConfig
from inference_gateway_tpu.serving.profiles import (
    PROFILES,
    ServingProfile,
    hbm_plan,
    kv_bytes_per_token,
    llama_param_count,
    resolve_model_cfg,
)
from inference_gateway_tpu.serving.scheduler import GenRequest, Scheduler, generate_sync
from inference_gateway_tpu.serving.server import SidecarServer

TINY = resolve_model_cfg("test-tiny")
LLAMA8B = resolve_model_cfg("llama-3-8b")


# ---------------------------------------------------------------------------
# StepCostModel closed forms
# ---------------------------------------------------------------------------
def test_decode_flops_follow_2n_params_rule():
    m = StepCostModel(LLAMA8B, chip=CHIP_SPECS["v5e"], n_chips=8)
    N = llama_param_count(LLAMA8B)
    c = m.decode(batch=1, n_steps=1, context_tokens=0)
    assert c.flops == pytest.approx(2 * N)
    # Batch and steps scale linearly; the attention term adds
    # 4·L·Hq·D per (token, context-token) pair.
    c2 = m.decode(batch=7, n_steps=3, context_tokens=0)
    assert c2.flops == pytest.approx(7 * 3 * 2 * N)
    ctx = 1000
    c3 = m.decode(batch=1, n_steps=1, context_tokens=ctx)
    attn = 4 * LLAMA8B.num_layers * LLAMA8B.num_heads * LLAMA8B.hd * ctx
    assert c3.flops - c.flops == pytest.approx(attn)


def test_prefill_quadratic_attention_term():
    m = StepCostModel(LLAMA8B, chip=CHIP_SPECS["v5e"])
    N = llama_param_count(LLAMA8B)
    T = 2048
    c = m.prefill(T, sq_tokens=T * T)
    quad = 4 * LLAMA8B.num_layers * LLAMA8B.num_heads * LLAMA8B.hd * T * T / 2
    assert c.flops == pytest.approx(2 * N * T + quad)
    # Long prefill is compute-bound, single-token decode bandwidth-bound.
    assert c.bound == "compute"
    assert m.decode(batch=1).bound == "bandwidth"


def test_spec_round_prices_k_plus_1_positions_and_model_draft_adds_draft():
    draft = resolve_model_cfg("llama-draft-150m")
    m = StepCostModel(LLAMA8B, chip=CHIP_SPECS["v5e"], spec_k=4, draft_cfg=draft)
    N = llama_param_count(LLAMA8B)
    Nd = llama_param_count(draft)
    B = 8
    ng = m.spec(B, context_tokens=0, ngram=True)
    md = m.spec(B, context_tokens=0, ngram=False)
    assert ng.flops == pytest.approx(B * 5 * 2 * N)  # K+1 = 5 positions
    # The model-draft round pays the draft's K-token forward on top.
    assert md.flops - ng.flops == pytest.approx(B * 4 * 2 * Nd)
    # One weight stream serves all K+1 positions: HBM bytes grow far
    # slower than K+1× a decode step's.
    dec = m.decode(batch=B)
    assert ng.hbm_bytes < 2 * dec.hbm_bytes


def test_decode_roofline_matches_committed_analytic_number():
    """The ROADMAP's item-2 target quotes 6.38 ms/step for v5e-8
    llama-3-8b at full batch / mean occupancy — the cost model must
    reproduce the number the repo already steers by."""
    p = PROFILES["v5e-8-llama-3-8b"]
    m = StepCostModel.from_profile(p)
    ctx = p.max_slots * (p.max_seq_len // 4)
    c = m.decode(batch=p.max_slots, context_tokens=ctx)
    assert c.roofline_s * 1e3 == pytest.approx(6.38, rel=0.02)
    assert c.bound == "bandwidth"


def test_analytic_mfu_monotone_in_batch():
    m = StepCostModel(LLAMA8B, chip=CHIP_SPECS["v5e"], n_chips=8)
    mfus = []
    for batch in (1, 8, 32, 96):
        c = m.decode(batch=batch, context_tokens=batch * 2048)
        mfus.append(c.flops / (c.roofline_s * m.peak_flops_total))
    assert mfus == sorted(mfus)
    assert mfus[0] < mfus[-1]


def test_moe_prices_active_experts_only():
    cfg = mixtral.PRESETS["mixtral-8x7b"]
    m = StepCostModel(cfg, chip=CHIP_SPECS["v5e"], n_chips=16)
    # Active params (2 of 8 experts) are well under the full tree, so a
    # decode token costs far less than 2·N-total.
    assert m.active_params < m.n_params
    c = m.decode(batch=1)
    assert c.flops == pytest.approx(2 * m.active_params)
    # A huge batch touches every expert; a single token only its two.
    small = m.decode(batch=1).hbm_bytes
    big = m.decode(batch=64).hbm_bytes
    assert big > small


def test_cost_model_weight_bytes_match_hbm_plan():
    """The cost model and profiles.hbm_plan must price weights from the
    same arithmetic — divergence would quietly skew every roofline."""
    for name in ("v5e-8-llama-3-8b", "v5e-1-llama-3-8b-int4"):
        p = PROFILES[name]
        plan = hbm_plan(p)
        m = StepCostModel.from_profile(p)
        tp = p.mesh.get("tp", 1)
        # hbm_plan reports per-chip (post-sharding, plus quant-scale
        # overhead rows); the cost model totals over the mesh.
        assert m.weight_bytes / tp == pytest.approx(
            plan["weights_per_chip"], rel=0.08)


# ---------------------------------------------------------------------------
# hbm_plan ↔ Engine allocation drift guard (ISSUE 6 satellite)
# ---------------------------------------------------------------------------
def test_hbm_plan_matches_engine_allocation_for_tiny_profile():
    profile = ServingProfile(
        name="test-tiny-paged", model="test-tiny", n_chips=1,
        max_slots=4, max_seq_len=128, prefill_buckets=(16, 32, 64, 128),
        max_prefill_batch=2, page_size=32, decode_chunk=8,
        attention="paged", mesh={},
    )
    plan = hbm_plan(profile)
    engine = Engine(EngineConfig(**profile.engine_kwargs()))
    try:
        import jax

        # KV: the paged pool the engine actually allocated, byte for byte.
        actual_kv = sum(int(leaf.size * leaf.dtype.itemsize)
                        for leaf in jax.tree.leaves(engine.cache))
        assert actual_kv == plan["kv_per_chip"]
        assert plan["kv_tokens"] == (engine.allocator.num_pages * profile.page_size)
        # Weights: bf16 params as allocated.
        actual_w = sum(int(leaf.size * leaf.dtype.itemsize)
                       for leaf in jax.tree.leaves(engine.params))
        assert actual_w == plan["weights_per_chip"]
        # And the cost model agrees with both (keeps /debug/roofline
        # honest as engine layouts evolve).
        m = StepCostModel.from_engine(engine, chip="v5e")
        assert m.weight_bytes == pytest.approx(actual_w)
        assert m.kv_bytes_per_token == kv_bytes_per_token(engine.model_cfg)
    finally:
        del engine


# ---------------------------------------------------------------------------
# PerfAccounting window + wasted work
# ---------------------------------------------------------------------------
def _tiny_accounting(otel=None, measured=None) -> PerfAccounting:
    return PerfAccounting(StepCostModel(TINY, chip=CHIP_SPECS["v5e"]),
                          otel=otel, model="test-tiny", window_s=60.0,
                          measured=measured)


def test_accounting_window_and_goodput():
    acc = _tiny_accounting(measured=False)
    cost = acc.on_step("decode", 0.004, batch=4, n_steps=8, tokens=32,
                       context_tokens=200)
    assert cost["flops"] > 0 and cost["hbm_bytes"] > 0 and cost["roofline_ms"] > 0
    snap = acc.snapshot()
    assert snap["mfu"] > 0
    assert snap["hbm_bandwidth_util"] > 0
    assert snap["goodput_mfu"] <= snap["mfu"]
    assert snap["measured"] is False
    before = snap["goodput_mfu"]
    # Never-delivered waste (rejected speculation, chunk overrun) was
    # never in the delivered total: it's attributed by reason but must
    # NOT be subtracted from goodput a second time.
    acc.record_wasted("chunk_overrun", 16)
    snap2 = acc.snapshot()
    assert snap2["wasted_tokens"] == {"chunk_overrun": 16}
    assert snap2["goodput_mfu"] == pytest.approx(before, rel=0.05)
    # Delivered-then-wasted tokens (a disconnected stream) WERE counted
    # as delivered: wasting half of them halves goodput, not raw MFU.
    acc.record_wasted("disconnected", 16, delivered=16)
    snap3 = acc.snapshot()
    assert snap3["wasted_tokens"] == {"chunk_overrun": 16, "disconnected": 16}
    assert snap3["goodput_mfu"] < before
    assert snap3["mfu"] == pytest.approx(snap["mfu"], rel=0.2)


def test_accounting_window_prunes_and_aggregates_stay_consistent():
    acc = _tiny_accounting(measured=False)
    for _ in range(10):
        acc.on_step("decode", 0.001, batch=2, n_steps=4, tokens=8)
    acc.record_wasted("disconnected", 5, delivered=5)
    with acc._lock:
        acc._prune(acc._events[0][0] + acc.window_s + 1e9)  # everything stale
        assert not acc._events and not acc._wasted_events
        assert acc._w_tokens == 0 and acc._w_wasted == 0
        assert acc._w_flops == pytest.approx(0.0, abs=1e-3)
        assert not acc._w_kind
    snap = acc.snapshot()
    assert snap["mfu"] == 0.0
    # Lifetime totals and wasted attribution survive the window.
    assert acc.total_tokens == 80
    assert snap["wasted_tokens"] == {"disconnected": 5}


def test_roofline_report_framing_on_and_off_tpu():
    entries = [{"kind": "decode", "duration_ms": 1.0, "tokens": 4,
                "flops": 1e9, "hbm_bytes": 1e6, "roofline_ms": 0.5,
                "bound": "bandwidth"},
               {"kind": "decode", "duration_ms": 2.0, "tokens": 4,
                "flops": 1e9, "hbm_bytes": 1e6, "roofline_ms": 0.5,
                "bound": "bandwidth"},
               {"kind": "prefill", "duration_ms": 3.0, "tokens": 2,
                "flops": 5e9, "hbm_bytes": 2e6, "roofline_ms": 1.0,
                "bound": "compute"},
               {"kind": "decode", "duration_ms": 1.5, "tokens": 4}]  # pre-accounting record
    off = roofline_report(_tiny_accounting(measured=False), entries)
    assert off["measured"] is False
    assert "mfu_measured" not in off  # never synthesized off-TPU
    assert "note" in off
    decode = off["per_kind"]["decode"]
    assert decode["records"] == 2  # the costless record is excluded
    # _pick takes the upper median of [1.0, 2.0] ms against the 0.5 ms
    # analytic p50.
    assert decode["gap_factor"] == pytest.approx(2.0 / 0.5, rel=0.1)
    assert decode["bound"] == "bandwidth"
    assert off["per_kind"]["prefill"]["bound"] == "compute"
    for key in ("step_ms_p50", "step_ms_p99", "analytic_ms_p50",
                "achieved_tflops", "achieved_gbps"):
        assert key in decode

    on = roofline_report(_tiny_accounting(measured=True), entries)
    assert on["measured"] is True
    assert "mfu_measured" in on


def test_wasted_tokens_reach_the_counter():
    otel = OpenTelemetry()
    acc = _tiny_accounting(otel=otel, measured=False)
    acc.record_wasted("spec_rejected", 7)
    acc.record_wasted("disconnected", 3)
    vals = otel.wasted_tokens_counter.values()
    assert vals[("test-tiny", "spec_rejected")] == 7
    assert vals[("test-tiny", "disconnected")] == 3
    expo = otel.expose_prometheus()
    assert 'engine_wasted_tokens{gen_ai_request_model="test-tiny",reason="spec_rejected"} 7' in expo


# ---------------------------------------------------------------------------
# Scheduler integration
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine():
    return Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                               dtype="float32", max_prefill_batch=2, use_mesh=False))


class _CountingLogger:
    def __init__(self):
        self.errors = []

    def error(self, msg, *a):
        self.errors.append(msg)

    def warn(self, *a, **k):
        pass

    def info(self, *a, **k):
        pass


def test_scheduler_prices_steps_and_attributes_disconnects(tiny_engine):
    acc = PerfAccounting(StepCostModel.from_engine(tiny_engine, chip="v5e"),
                         model="test-tiny", measured=False)
    sched = Scheduler(tiny_engine)
    sched.accounting = acc
    sched.start()
    try:
        generate_sync(sched, [1, 2, 3, 4], max_tokens=12)
        assert acc.total_flops > 0
        assert acc.total_tokens >= 12
        # A disconnected client terminates at the next decode step
        # (ISSUE 7 early-terminate) — the tokens decoded before the
        # scheduler noticed are still billed as waste (ISSUE 6), but the
        # request no longer burns the full max_tokens.
        req = GenRequest(prompt_ids=[5, 6, 7], max_tokens=64, disconnected=True)
        import queue as _q

        done = _q.Queue()
        req.callback = lambda t, lp, fin, r: done.put((fin, r)) if fin else None
        sched.submit(req)
        fin, reason = done.get(timeout=60.0)
        assert fin and reason == "disconnected"
        assert 1 <= acc.wasted.get("disconnected", 0) < 64
    finally:
        sched.stop()


def test_timeline_failures_rate_limited_then_disabled(tiny_engine):
    """ISSUE 6 satellite: a broken record path must not logger.error
    once per engine step forever — the scheduler logs the first failure,
    then disables the timeline (and accounting) after 8 consecutive
    ones, and serving continues."""

    class _BrokenTimeline:
        def record(self, *a, **k):
            raise RuntimeError("boom")

    logger = _CountingLogger()
    sched = Scheduler(tiny_engine, logger=logger)
    sched.timeline = _BrokenTimeline()
    sched.start()
    try:
        # 96 tokens = ~13 decode chunks + the prefill: comfortably past
        # the 8-consecutive-failures disable threshold.
        out, reason = generate_sync(sched, [1, 2, 3], max_tokens=96)
        assert len(out) > 0  # serving survived the observer
        # Enough steps ran to cross the disable threshold.
        assert sched.timeline is None
        assert sched.accounting is None
        # Rate limit: first failure + the disable notice, not one per step.
        assert 1 <= len(logger.errors) <= 3, logger.errors
        assert any("disabled" in m for m in logger.errors)
    finally:
        sched.stop()


def test_spec_waste_attribution():
    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 spec_draft="ngram", spec_k=4))
    acc = PerfAccounting(StepCostModel.from_engine(engine, chip="v5e"),
                         model="test-tiny", measured=False)
    sched = Scheduler(engine)
    sched.accounting = acc
    sched.start()
    try:
        generate_sync(sched, [7, 8, 9, 7, 8, 9, 7, 8], max_tokens=16)
        # Random tiny weights reject most n-gram proposals: rejected
        # verify positions must land in the waste ledger.
        assert acc.wasted.get("spec_rejected", 0) > 0
    finally:
        sched.stop()


# ---------------------------------------------------------------------------
# /debug/roofline e2e on the CPU engine (acceptance)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def roofline_stack(aloop, tiny_engine):
    otel = OpenTelemetry()
    sidecar = SidecarServer(tiny_engine, served_model_name="test-tiny", otel=otel,
                            accounting_chip="v5e")
    port = aloop.run(sidecar.start("127.0.0.1", 0))
    yield sidecar, port, otel
    aloop.run(sidecar.shutdown())


async def _chat(port: int, stream: bool = False, max_tokens: int = 8):
    client = HTTPClient()
    body = json.dumps({"model": "test-tiny", "stream": stream,
                       "max_tokens": max_tokens,
                       "messages": [{"role": "user", "content": "roofline probe"}]}).encode()
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             body, stream=stream)
    if stream:
        async for _ in resp.iter_raw():
            pass
    assert resp.status == 200
    return resp


async def test_roofline_endpoint_serves_measured_vs_analytic(roofline_stack):
    sidecar, port, otel = roofline_stack
    await _chat(port, stream=False)
    await _chat(port, stream=True)
    resp = await HTTPClient().get(f"http://127.0.0.1:{port}/debug/roofline")
    assert resp.status == 200
    report = resp.json()
    assert report["model"] == "test-tiny"
    # CPU backend: host wall clock is never framed as a measurement.
    assert report["measured"] is False
    assert "mfu_measured" not in report
    assert "note" in report
    per_kind = report["per_kind"]
    assert "prefill" in per_kind and "decode" in per_kind
    for kind in ("prefill", "decode"):
        agg = per_kind[kind]
        assert agg["records"] > 0
        assert agg["analytic_ms_p50"] > 0
        assert agg["achieved_tflops"] >= 0
        assert agg["gap_factor"] is None or agg["gap_factor"] > 0
        assert agg["bound"] in ("compute", "bandwidth")
    win = report["window"]
    assert win["mfu"] >= 0 and win["hbm_bandwidth_util"] > 0


async def test_efficiency_instruments_in_exposition_and_status(roofline_stack):
    sidecar, port, otel = roofline_stack
    await _chat(port, stream=False)
    expo = otel.expose_prometheus()
    assert 'engine_mfu{gen_ai_request_model="test-tiny",source="tpu-sidecar"}' in expo
    assert ('engine_hbm_bandwidth_util{gen_ai_request_model="test-tiny",'
            'source="tpu-sidecar"}') in expo
    assert "engine_step_roofline_ratio" in expo
    assert "engine_goodput_mfu" in expo
    status = (await HTTPClient().get(
        f"http://127.0.0.1:{port}/debug/status")).json()
    eff = status["compute_efficiency"]
    assert eff["measured"] is False
    assert eff["mfu"] >= 0 and "wasted_tokens" in eff
    metrics = (await HTTPClient().get(
        f"http://127.0.0.1:{port}/metrics")).json()
    assert "mfu" in metrics and "hbm_bandwidth_util" in metrics
    # Per-step cost fields ride the timeline records.
    tl = (await HTTPClient().get(
        f"http://127.0.0.1:{port}/debug/timeline")).json()
    priced = [e for e in tl["entries"] if "flops" in e]
    assert priced and all(e["roofline_ms"] > 0 for e in priced)


async def test_mfu_gauges_roundtrip_through_otlp_push(roofline_stack):
    sidecar, port, _ = roofline_stack
    await _chat(port, stream=False)
    payload = sidecar._otlp_payload()
    names = [m["name"] for rm in payload["resourceMetrics"]
             for sm in rm["scopeMetrics"] for m in sm["metrics"]]
    assert {"engine.mfu", "engine.goodput_mfu",
            "engine.hbm_bandwidth_util"} <= set(names)
    gateway_otel = OpenTelemetry()
    result = gateway_otel.ingest_metrics(payload, "tpu-sidecar")
    assert result["accepted"] >= 3 and result["rejected"] == 0
    # The push's resource service.name rides in as the source label so a
    # remote sidecar's series can't clobber a co-hosted engine's.
    assert ("test-tiny", "tpu-sidecar") in gateway_otel.engine_mfu_gauge.values()
    assert ("test-tiny", "tpu-sidecar") in gateway_otel.engine_hbm_util_gauge.values()


async def test_access_log_carries_per_request_flops():
    import io

    from inference_gateway_tpu.otel.access_log import AccessLog

    # Own engine: the module-scoped roofline_stack sidecar must not
    # share a scheduler-less engine with a second concurrent server.
    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False))
    log = AccessLog(stream=io.StringIO(), service="tpu-sidecar")
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            access_log=log, accounting_chip="v5e")
    port = await sidecar.start("127.0.0.1", 0)
    try:
        await _chat(port, stream=True)
        events = [e for e in log.tail if e.get("route") == "/v1/chat/completions"]
        assert events
        ev = events[-1]
        assert ev["prefill_flops"] > 0
        assert ev["decode_flops"] > 0
        assert ev["output_tokens"] > 0
    finally:
        await sidecar.shutdown()


@pytest.mark.slow
def test_bench_accounting_overhead_under_5pct(aloop):
    """Acceptance: pricing every engine chunk must cost < 5% p99 on the
    streamed sidecar path. Same best-of-3 discipline as the profiling
    overhead gate — shared-CI p99 swings tens of percent from scheduler
    noise alone."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))
    import gateway_bench

    deltas = []
    for _ in range(3):
        result = aloop.run(gateway_bench.bench_accounting_overhead(n=80))
        assert result["p99_delta_pct"] is not None
        deltas.append(result["p99_delta_pct"])
        if result["p99_delta_pct"] < 5.0:
            return
    raise AssertionError(f"p99 overhead above 5% in all 3 runs: {deltas}")


def test_early_exit_zeroes_chunk_overrun_waste():
    """ISSUE 14 regression: a stream that finishes mid-chunk on a
    criterion the DEVICE holds (its max_tokens budget) records zero
    wasted_tokens{reason="chunk_overrun"} — the device froze the row at
    the finish, so the trailing steps were never computed and must not
    be double-counted as waste — at eight steps a chunk and at the
    one-step reference alike. A finish only the HOST sees (a stop id
    past the device's STOP_TABLE_WIDTH-wide row) over-decodes and is
    attributed as ever: the contrast pins that the suppression keys off
    device_stopped, not off the accounting path going dead."""
    def overrun_of(decode_chunk, **gen):
        eng = Engine(EngineConfig(
            model="test-tiny", max_slots=4, max_seq_len=128, dtype="float32",
            max_prefill_batch=2, use_mesh=False, decode_chunk=decode_chunk))
        acc = PerfAccounting(StepCostModel.from_engine(eng, chip="v5e"),
                             model="test-tiny", measured=False)
        sched = Scheduler(eng)
        sched.accounting = acc
        sched.start()
        try:
            out = generate_sync(sched, [1, 2, 3, 4], **gen)
            # Wait for the pipeline tail (the in-flight chunks carrying
            # the finished stream) to drain — that is where overrun is
            # attributed.
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and (sched._handles or sched._slots):
                time.sleep(0.02)
            return acc.wasted.get("chunk_overrun", 0), out
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
    # A token the stream first emits inside the first chunk, large
    # enough to sort behind a row's worth of smaller stop ids.
    hit = next(t for i, t in enumerate(base)
               if 1 <= i <= 5 and base.index(t) == i and t >= 2 * STOP_TABLE_WIDTH)
    filler = [t for t in range(hit) if t not in base][:STOP_TABLE_WIDTH]
    assert len(filler) == STOP_TABLE_WIDTH  # they fill the row; the hit falls off it
    overrun, (toks, reason) = overrun_of(
        8, max_tokens=8, stop_token_ids=frozenset(filler + [hit]))
    assert reason == "stop" and toks == base[:base.index(hit)]
    assert overrun > 0, "a host-only finish stopped attributing overrun"


# ---------------------------------------------------------------------------
# The chip row comes from device_kind; an unknown device has no roofline
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,chip", [("TPU v5 lite", "v5e"), ("TPU v4", "v4"),
                                       ("TPU v6 lite", "v6e"), ("cpu", None),
                                       ("TPU v9 imaginary", None)])
def test_chip_row_is_looked_up_by_device_kind_and_never_assumed(
        monkeypatch, tiny_engine, kind, chip):
    from inference_gateway_tpu.otel.perf_accounting import UnknownChipError
    from inference_gateway_tpu.utils import platform

    monkeypatch.setattr(platform, "device_info", lambda: {
        "platform": "tpu", "device_kind": kind, "device_count": 1})
    if chip is None:
        with pytest.raises(UnknownChipError, match="no roofline"):
            StepCostModel.from_engine(tiny_engine)
        # Naming a chip is the one way to price against it anyway.
        assert StepCostModel.from_engine(tiny_engine, chip="v5e").chip is CHIP_SPECS["v5e"]
    else:
        assert StepCostModel.from_engine(tiny_engine).chip is CHIP_SPECS[chip]
    with pytest.raises(UnknownChipError):
        StepCostModel.from_engine(tiny_engine, chip="v5-typo")


def test_sidecar_on_an_unknown_device_serves_without_accounting(tiny_engine):
    """CPU has no datasheet row: accounting stays off (logged), and
    /debug/roofline says so — it never shows v5e's figures."""
    sidecar = SidecarServer(tiny_engine, served_model_name="test-tiny")
    assert sidecar.accounting is None
