"""ISSUE 3 acceptance e2e: end-to-end request observability.

A streamed chat completion drives the real double hop (gateway →
/proxy loopback → TPU sidecar) with the full telemetry stack on, and the
tests assert the tentpole contract: ONE trace id links the gateway
server span to the sidecar's queue.wait/prefill/decode child spans, the
TPOT and queue-wait histograms record non-zero observations, and the
wide-event access-log lines (gateway + sidecar) carry the same trace id
with phase durations. /debug/status and the sidecar's OTLP push payload
are exercised against the same stack.
"""

import asyncio
import io
import json

import pytest

from inference_gateway_tpu.main import build_gateway
from inference_gateway_tpu.netio.client import HTTPClient
from inference_gateway_tpu.netio.sse import iter_sse_payloads
from inference_gateway_tpu.otel.access_log import AccessLog
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.server import SidecarServer


@pytest.fixture(scope="module")
def stack(aloop):
    env = {
        "TPU_API_URL": "http://127.0.0.1:1/v1",  # repointed after sidecar start
        "OLLAMA_API_URL": "http://127.0.0.1:1/v1",
        "LLAMACPP_API_URL": "http://127.0.0.1:1/v1",
        "SERVER_PORT": "0",
        "TELEMETRY_ENABLE": "true",
        "TELEMETRY_TRACING_ENABLE": "true",
        "TELEMETRY_ACCESS_LOG": "true",
        "TELEMETRY_METRICS_PUSH_ENABLE": "true",
        "TELEMETRY_METRICS_PORT": "0",
    }
    gw = build_gateway(env=env)
    gw.access_log._stream = io.StringIO()  # keep test output clean

    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False))
    sidecar_log = AccessLog(stream=io.StringIO(), service="tpu-sidecar")
    # Co-hosted wiring: the sidecar shares the gateway's tracer (one span
    # buffer) and records its histograms/gauges straight into the
    # gateway's registry; the cross-process path is exercised separately
    # via the OTLP push payload test below.
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            tracer=gw.otel.tracer, otel=gw.otel,
                            access_log=sidecar_log)
    sidecar_port = aloop.run(sidecar.start("127.0.0.1", 0))
    gw.registry.get_providers()["tpu"].url = f"http://127.0.0.1:{sidecar_port}/v1"
    gw_port = aloop.run(gw.start("127.0.0.1", 0))
    yield gw, gw_port, sidecar, sidecar_log
    aloop.run(gw.shutdown())
    aloop.run(sidecar.shutdown())


async def _collect_spans(tracer, wanted: set[str], spans: dict, tries: int = 300) -> dict:
    """Poll-drain the tracer until every wanted span name appeared (the
    sidecar finalizes its spans when its stream generator closes, which
    can land a beat after the client read the last byte)."""
    for _ in range(tries):
        for s in tracer.drain():
            spans.setdefault(s.name, []).append(s)
        if wanted <= set(spans):
            return spans
        await asyncio.sleep(0.01)
    raise AssertionError(f"spans never appeared: {wanted - set(spans)} (have {set(spans)})")


async def test_streamed_request_links_one_trace_e2e(stack):
    gw, port, sidecar, sidecar_log = stack
    gw.otel.tracer.drain()  # start from a clean span buffer
    body = {
        "model": "tpu/test-tiny",
        "messages": [{"role": "user", "content": "stream me"}],
        "max_tokens": 8,
        "stream": True,
    }
    client = HTTPClient()
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode(), stream=True)
    assert resp.status == 200
    chunks = [json.loads(p) async for p in iter_sse_payloads(resp.iter_lines())]
    assert chunks and chunks[0]["object"] == "chat.completion.chunk"

    spans = await _collect_spans(gw.otel.tracer, {
        "POST /v1/chat/completions", "POST /proxy/tpu/chat/completions",
        "tpu_sidecar.chat_completions", "queue.wait", "prefill", "decode",
    }, {})
    root = spans["POST /v1/chat/completions"][0]
    hop = spans["POST /proxy/tpu/chat/completions"][0]
    side = spans["tpu_sidecar.chat_completions"][0]
    qw, pf, dec = (spans[n][0] for n in ("queue.wait", "prefill", "decode"))

    # One trace id across both processes' spans; parentage is the full
    # gateway → loopback hop → sidecar → phase chain.
    trace_id = root.trace_id
    assert {hop.trace_id, side.trace_id, qw.trace_id, pf.trace_id,
            dec.trace_id} == {trace_id}
    assert root.parent_span_id == ""
    assert hop.parent_span_id == root.span_id
    assert side.parent_span_id == hop.span_id
    assert {qw.parent_span_id, pf.parent_span_id, dec.parent_span_id} == {side.span_id}
    # Phase spans tile the request: submit ≤ admit ≤ first_token ≤ finish.
    assert qw.start_ns <= qw.end_ns == pf.start_ns <= pf.end_ns == dec.start_ns <= dec.end_ns
    assert side.attributes["gen_ai.usage.output_tokens"] > 0

    # Token-level histograms recorded non-zero observations: TPOT from
    # both the SSE relay and the scheduler emit path, queue wait from the
    # sidecar phase clock.
    assert gw.otel.time_per_output_token.total_count() > 0
    assert gw.otel.time_in_queue.total_count() > 0

    # Wide-event access-log lines (gateway + sidecar) share the trace id;
    # the sidecar line carries the engine phase durations.
    for _ in range(300):
        gw_events = [e for e in gw.access_log.tail
                     if e.get("route") == "/v1/chat/completions" and "trace_id" in e]
        side_events = [e for e in sidecar_log.tail if e.get("trace_id") == trace_id]
        if any(e.get("trace_id") == trace_id for e in gw_events) and side_events:
            break
        await asyncio.sleep(0.01)
    gw_event = next(e for e in gw_events if e["trace_id"] == trace_id)
    side_event = side_events[0]
    assert gw_event["status"] == 200 and gw_event["stream"] is True
    assert gw_event["provider"] == "tpu"
    assert gw_event["output_tokens"] > 0
    assert gw_event["ttfc_ms"] >= 0
    for key in ("queue_wait_ms", "prefill_ms", "decode_ms"):
        assert side_event[key] >= 0, f"{key} missing from sidecar wide event"
    assert side_event["output_tokens"] == gw_event["output_tokens"]


async def test_non_streaming_request_also_traced(stack):
    gw, port, sidecar, sidecar_log = stack
    gw.otel.tracer.drain()
    body = {"model": "tpu/test-tiny", "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4}
    client = HTTPClient()
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode())
    assert resp.status == 200
    assert resp.json()["usage"]["completion_tokens"] > 0
    spans = await _collect_spans(gw.otel.tracer, {
        "POST /v1/chat/completions", "tpu_sidecar.chat_completions",
        "queue.wait", "prefill", "decode"}, {})
    side = spans["tpu_sidecar.chat_completions"][0]
    assert side.trace_id == spans["POST /v1/chat/completions"][0].trace_id


async def test_debug_status_snapshot(stack):
    gw, port, sidecar, _ = stack
    client = HTTPClient()
    resp = await client.get(f"http://127.0.0.1:{gw.metrics_port}/debug/status")
    assert resp.status == 200
    status = resp.json()
    assert status["app"] and status["version"]
    assert status["uptime_seconds"] >= 0
    assert "streaming" in status["admission"]["classes"]
    assert "buffered" in status["admission"]["classes"]
    assert isinstance(status["breakers"], dict)
    # A tpu request has run by now (fixture-scoped test ordering), so the
    # breaker registry and engine gauges both carry the tpu model.
    assert any(k.startswith("tpu/") for k in status["breakers"])
    occupancy = status["gauges"]["inference_gateway.engine.slot_occupancy"]
    assert "gen_ai_request_model=test-tiny" in occupancy
    kv = status["gauges"]["inference_gateway.engine.kv_page_utilization"]
    assert 0.0 <= kv["gen_ai_request_model=test-tiny"] <= 1.0
    assert isinstance(status.get("access_log_tail"), list)


async def test_prometheus_exposition_carries_new_instruments(stack):
    gw, _, _, _ = stack
    client = HTTPClient()
    resp = await client.get(f"http://127.0.0.1:{gw.metrics_port}/metrics")
    text = resp.body.decode()
    assert "# TYPE gen_ai_server_time_per_output_token histogram" in text
    assert "# TYPE gen_ai_server_time_in_queue histogram" in text
    assert "# TYPE inference_gateway_engine_slot_occupancy gauge" in text


async def test_sidecar_push_payload_roundtrips_through_ingest(stack):
    """The cross-process path: the sidecar's delta OTLP payload (TTFT +
    TPOT + queue wait) must be accepted whole by the gateway ingest."""
    gw, port, sidecar, _ = stack
    client = HTTPClient()
    body = {"model": "tpu/test-tiny", "messages": [{"role": "user", "content": "x"}],
            "max_tokens": 6, "stream": True}
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode(), stream=True)
    async for _ in iter_sse_payloads(resp.iter_lines()):
        pass
    # Wait for the sidecar's finalize (queue-wait sample lands there).
    for _ in range(300):
        if sidecar._queue_wait_samples:
            break
        await asyncio.sleep(0.01)
    payload = sidecar._otlp_payload()
    assert payload is not None
    names = [m["name"] for m in payload["resourceMetrics"][0]["scopeMetrics"][0]["metrics"]]
    assert "gen_ai.server.time_per_output_token" in names
    assert "gen_ai.server.time_in_queue" in names
    result = gw.otel.ingest_metrics(payload, source="tpu-sidecar")
    assert result["rejected"] == 0 and result["accepted"] >= 2


async def test_access_log_captures_shed_requests():
    """A request rejected by admission control still leaves one wide
    event, annotated with the shed reason — the only downstream cost a
    shed request pays."""
    from inference_gateway_tpu.netio.server import Headers, Request, Response
    from inference_gateway_tpu.otel.access_log import access_log_middleware
    from inference_gateway_tpu.resilience.overload import (
        OverloadController,
        admission_middleware,
    )

    class _Cfg:
        enabled = True
        max_concurrent_streaming = 1
        max_concurrent_buffered = 1
        queue_depth_streaming = 0
        queue_depth_buffered = 0
        queue_timeout = 0.1
        shed_high_water = 0.5
        engine_depth_high_water = 0
        drain_deadline = 1.0
        drain_retry_after = 1.0

    log = AccessLog(stream=io.StringIO())
    overload = OverloadController(_Cfg())
    await overload.admit("streaming", 1)  # occupy the only slot
    mw_adm = admission_middleware(overload)

    async def handler(req):
        return Response.json({})

    async def chain(req):
        return await mw_adm(req, handler)

    req = Request(method="POST", path="/v1/chat/completions", query={},
                  headers=Headers(), body=b"{}")
    resp = await access_log_middleware(log)(req, chain)
    assert resp.status == 429
    event = log.tail[-1]
    assert event["shed"] == "capacity"
    assert event["status"] == 429
    assert event["retry_after_s"] >= 1.0
    assert "duration_ms" in event
