"""ISSUE 4 acceptance e2e: performance introspection over the real
gateway → /proxy loopback → TPU sidecar double hop.

Continuous profiling and the event-loop watchdog run for the whole
module; streamed chats drive the engine while the tests assert the
tentpole contract: /debug/profile returns non-empty collapsed stacks
naming a relay frame, /debug/timeline shows the request's decode steps,
and a request breaching the (artificially tiny) slow-request threshold
lands in the forensics log carrying the same trace id as the gateway's
wide event.
"""

import asyncio
import io
import json

import pytest

from inference_gateway_tpu.main import build_gateway
from inference_gateway_tpu.netio.client import HTTPClient
from inference_gateway_tpu.netio.sse import iter_sse_payloads
from inference_gateway_tpu.otel.access_log import AccessLog
from inference_gateway_tpu.otel.profiling import SlowRequestLog
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.server import SidecarServer

# Frames that prove the profiler saw the SSE relay/serving hot path.
RELAY_FRAMES = (
    "netio/server.py:_write_response",
    "netio/server.py:_handle_conn",
    "serving/server.py:_stream_chunks",
    "netio/client.py:",
    "serving/scheduler.py:run",
)


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
        "TELEMETRY_METRICS_PORT": "0",
        "TELEMETRY_PROFILING_ENABLE": "true",
        "TELEMETRY_PROFILING_CONTINUOUS": "true",
        "TELEMETRY_PROFILING_HZ": "97",
        "TELEMETRY_PROFILING_WINDOW": "1s",
        "TELEMETRY_PROFILING_WATCHDOG": "true",
        "TELEMETRY_PROFILING_WATCHDOG_INTERVAL": "100ms",
        "TELEMETRY_PROFILING_WATCHDOG_THRESHOLD": "50ms",
        # Artificially tiny total-latency threshold: every real request
        # "stalls" past it, so forensics capture deterministically.
        "TELEMETRY_SLOW_REQUEST_TOTAL": "1ms",
        "TELEMETRY_SLOW_REQUEST_LOG_SIZE": "16",
        "TELEMETRY_ACCESS_LOG_TAIL": "64",
    }
    gw = build_gateway(env=env)
    gw.access_log._stream = io.StringIO()  # keep test output clean

    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False))
    sidecar_log = AccessLog(stream=io.StringIO(), service="tpu-sidecar")
    side_slow = SlowRequestLog(total_s=0.001, size=16, source="tpu-sidecar")
    sidecar = SidecarServer(engine, served_model_name="test-tiny",
                            tracer=gw.otel.tracer, otel=gw.otel,
                            access_log=sidecar_log, slow_log=side_slow)
    sidecar_port = aloop.run(sidecar.start("127.0.0.1", 0))
    gw.registry.get_providers()["tpu"].url = f"http://127.0.0.1:{sidecar_port}/v1"
    gw_port = aloop.run(gw.start("127.0.0.1", 0))
    yield gw, gw_port, sidecar, sidecar_port, side_slow
    aloop.run(gw.shutdown())
    aloop.run(sidecar.shutdown())


async def _stream_one(port: int, max_tokens: int = 16) -> int:
    body = {"model": "tpu/test-tiny",
            "messages": [{"role": "user", "content": "profile me"}],
            "max_tokens": max_tokens, "stream": True}
    client = HTTPClient()
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                             json.dumps(body).encode(), stream=True)
    assert resp.status == 200
    chunks = [json.loads(p) async for p in iter_sse_payloads(resp.iter_lines())]
    assert chunks and chunks[0]["object"] == "chat.completion.chunk"
    return len(chunks)


async def test_debug_timeline_shows_request_decode_steps(stack):
    gw, port, sidecar, sidecar_port, _ = stack
    await _stream_one(port, max_tokens=12)
    client = HTTPClient()
    resp = await client.get(f"http://127.0.0.1:{sidecar_port}/debug/timeline")
    assert resp.status == 200
    timeline = resp.json()
    assert timeline["model"] == "test-tiny"
    assert timeline["steps"] > 0
    kinds = {e["kind"] for e in timeline["entries"]}
    assert "prefill" in kinds and "decode" in kinds
    decode = [e for e in timeline["entries"] if e["kind"] == "decode"]
    assert sum(e["tokens"] for e in decode) > 0
    assert all(e["duration_ms"] >= 0 for e in timeline["entries"])
    assert any(e["batch"] >= 1 for e in decode)
    # the engine.step_duration histogram fed from the same records
    assert gw.otel.engine_step_duration.total_count() > 0
    # bounded ?n= tail
    resp = await client.get(f"http://127.0.0.1:{sidecar_port}/debug/timeline?n=2")
    assert len(resp.json()["entries"]) <= 2


async def test_debug_profile_names_a_relay_frame(stack):
    gw, port, _, _, _ = stack
    client = HTTPClient()
    for attempt in range(3):
        # Keep the relay genuinely busy while the capture runs.
        streams = [asyncio.ensure_future(_stream_one(port, max_tokens=48))
                   for _ in range(4)]
        try:
            resp = await client.get(
                f"http://127.0.0.1:{gw.metrics_port}/debug/profile?seconds=1.0&hz=199")
        finally:
            await asyncio.gather(*streams)
        assert resp.status == 200
        text = resp.body.decode()
        assert text.strip(), "collapsed-stack capture came back empty"
        if any(frame in text for frame in RELAY_FRAMES):
            break
    else:
        raise AssertionError(f"no relay frame in 3 captures; sample:\n{text[:2000]}")
    # every line is flamegraph-collapsed "stack count"
    for line in text.strip().splitlines():
        stack_part, count = line.rsplit(" ", 1)
        assert int(count) > 0 and ";" in stack_part


async def test_continuous_profile_ring_accumulates(stack):
    gw, port, _, _, _ = stack
    await _stream_one(port, max_tokens=8)
    client = HTTPClient()
    resp = await client.get(
        f"http://127.0.0.1:{gw.metrics_port}/debug/profile?mode=continuous")
    assert resp.status == 200
    assert resp.body.strip()
    assert gw.profiler.stats()["samples"] > 0


async def test_slow_request_lands_in_forensics_with_trace_id(stack):
    gw, port, sidecar, _, side_slow = stack
    await _stream_one(port, max_tokens=8)
    # The sidecar finalizes (and judges) the request when its stream
    # generator closes — poll briefly for the record.
    entry = None
    for _ in range(300):
        entries = side_slow.snapshot()["entries"]
        if entries:
            entry = entries[-1]
            break
        await asyncio.sleep(0.01)
    assert entry is not None, "slow request never captured"
    assert "total" in entry["breach"]
    assert entry["trace_id"], "forensics record lost its trace id"
    assert entry["output_tokens"] > 0
    assert entry["phases_ms"]["decode"] is not None
    assert isinstance(entry.get("engine_steps"), list)
    # Same trace id is visible at the gateway edge (wide event), so the
    # forensics record links to the trace and the access log.
    for _ in range(300):
        gw_ids = {e.get("trace_id") for e in gw.access_log.tail}
        if entry["trace_id"] in gw_ids:
            break
        await asyncio.sleep(0.01)
    assert entry["trace_id"] in gw_ids
    # The gateway edge judged its own wide event too.
    assert gw.slow_log is not None and gw.slow_log.breached > 0


async def test_debug_status_reports_introspection_state(stack):
    gw, port, _, _, _ = stack
    await _stream_one(port, max_tokens=4)
    client = HTTPClient()
    resp = await client.get(f"http://127.0.0.1:{gw.metrics_port}/debug/status")
    assert resp.status == 200
    status = resp.json()
    assert status["profiling"]["continuous"] is True
    assert status["profiling"]["samples"] > 0
    assert status["eventloop"]["watchdog"] is True
    assert status["eventloop"]["beats"] > 0
    assert status["slow_requests"]["entries"]
    assert status["access_log_dropped"] >= 0
    # watchdog heartbeat feeds the lag histogram
    assert gw.otel.eventloop_lag.total_count() > 0
    # Prometheus exposition carries the new instruments
    resp = await client.get(f"http://127.0.0.1:{gw.metrics_port}/metrics")
    text = resp.body.decode()
    assert "# TYPE eventloop_lag histogram" in text
    assert "# TYPE engine_step_duration histogram" in text


async def test_sidecar_debug_status(stack):
    _, _, sidecar, sidecar_port, _ = stack
    client = HTTPClient()
    resp = await client.get(f"http://127.0.0.1:{sidecar_port}/debug/status")
    assert resp.status == 200
    status = resp.json()
    assert status["model"] == "test-tiny"
    assert status["timeline"]["steps"] > 0
    assert status["slow_requests"]["thresholds"]["total_s"] == 0.001
    # guarded jax trace: explicit no-op on the CPU test platform
    resp = await client.get(f"http://127.0.0.1:{sidecar_port}/debug/jax_trace?seconds=0.1")
    assert resp.status == 409
    assert "tpu" in resp.json()["reason"]
