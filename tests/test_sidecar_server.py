"""End-to-end sidecar tests: OpenAI-compatible HTTP over the tiny engine.

Real sockets (ephemeral port), real scheduler thread, real SSE framing —
the netio client consumes what the netio server emits.
"""

import asyncio
import json

import pytest

from inference_gateway_tpu.netio.client import HTTPClient
from inference_gateway_tpu.netio.sse import iter_sse_payloads
from inference_gateway_tpu.serving.engine import Engine, EngineConfig
from inference_gateway_tpu.serving.server import SidecarServer


@pytest.fixture(scope="module")
def sidecar(aloop):
    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False))
    server = SidecarServer(engine, served_model_name="tpu-test-tiny")
    port = aloop.run(server.start("127.0.0.1", 0))
    yield server, port
    aloop.run(server.shutdown())


@pytest.fixture
def client():
    return HTTPClient()


async def test_health(sidecar, client):
    _, port = sidecar
    resp = await client.get(f"http://127.0.0.1:{port}/health")
    assert resp.status == 200


async def test_list_models(sidecar, client):
    _, port = sidecar
    resp = await client.get(f"http://127.0.0.1:{port}/v1/models")
    data = resp.json()
    assert data["object"] == "list"
    assert data["data"][0]["id"] == "tpu-test-tiny"
    assert data["data"][0]["served_by"] == "tpu"


async def test_props_runtime_metadata(sidecar, client):
    _, port = sidecar
    resp = await client.get(f"http://127.0.0.1:{port}/props")
    props = resp.json()
    assert props["default_generation_settings"]["n_ctx"] == 128


async def test_chat_completion_non_streaming(sidecar, client):
    _, port = sidecar
    body = {
        "model": "tpu-test-tiny",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 8,
    }
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions", json.dumps(body).encode())
    assert resp.status == 200
    data = resp.json()
    assert data["object"] == "chat.completion"
    assert data["choices"][0]["message"]["role"] == "assistant"
    assert data["choices"][0]["finish_reason"] in ("stop", "length")
    assert data["usage"]["prompt_tokens"] > 0
    assert data["usage"]["completion_tokens"] > 0
    assert data["usage"]["total_tokens"] == data["usage"]["prompt_tokens"] + data["usage"]["completion_tokens"]


async def test_chat_completion_streaming(sidecar, client):
    _, port = sidecar
    body = {
        "model": "tpu-test-tiny",
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 6,
        "stream": True,
        "stream_options": {"include_usage": True},
    }
    resp = await client.post(
        f"http://127.0.0.1:{port}/v1/chat/completions", json.dumps(body).encode(), stream=True
    )
    assert resp.status == 200
    assert "text/event-stream" in (resp.headers.get("Content-Type") or "")

    chunks = []
    async for payload in iter_sse_payloads(resp.iter_lines()):
        chunks.append(json.loads(payload))

    assert chunks[0]["object"] == "chat.completion.chunk"
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    finishes = [c["choices"][0]["finish_reason"] for c in chunks if c.get("choices")]
    assert finishes[-1] in ("stop", "length")
    # usage rides in the trailing chunk (reference telemetry scans last 4).
    assert "usage" in chunks[-1]
    assert chunks[-1]["usage"]["completion_tokens"] > 0


async def test_streaming_matches_non_streaming(sidecar, client):
    _, port = sidecar
    body = {
        "model": "tpu-test-tiny",
        "messages": [{"role": "user", "content": "determinism"}],
        "max_tokens": 8,
        "temperature": 0,
    }
    non = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions", json.dumps(body).encode())
    text_non = non.json()["choices"][0]["message"]["content"]

    body["stream"] = True
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions", json.dumps(body).encode(), stream=True)
    text_stream = ""
    async for payload in iter_sse_payloads(resp.iter_lines()):
        c = json.loads(payload)
        for choice in c.get("choices", []):
            text_stream += choice.get("delta", {}).get("content") or ""
    assert text_stream == text_non


async def test_bad_request(sidecar, client):
    _, port = sidecar
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions", b"not json")
    assert resp.status == 400
    resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions", b"{}")
    assert resp.status == 400


async def test_concurrent_streams(sidecar, client):
    _, port = sidecar

    async def one(i: int) -> str:
        body = {
            "messages": [{"role": "user", "content": f"request {i}"}],
            "max_tokens": 5,
            "stream": True,
        }
        c = HTTPClient()
        resp = await c.post(f"http://127.0.0.1:{port}/v1/chat/completions", json.dumps(body).encode(), stream=True)
        text = ""
        async for payload in iter_sse_payloads(resp.iter_lines()):
            data = json.loads(payload)
            for choice in data.get("choices", []):
                text += choice.get("delta", {}).get("content") or ""
        return text

    results = await asyncio.gather(*[one(i) for i in range(8)])
    assert len(results) == 8


async def test_metrics_endpoint(sidecar, client):
    _, port = sidecar
    resp = await client.get(f"http://127.0.0.1:{port}/metrics")
    m = resp.json()
    assert m["decode_tokens"] > 0
    assert "queue_depth" in m


async def test_metrics_prometheus_format(sidecar, client):
    """GET /metrics with a text/plain Accept (what Prometheus sends)
    returns the tpu_sidecar_* exposition the monitoring example's
    dashboard queries; JSON stays the default."""
    _, port = sidecar
    resp = await client.get(f"http://127.0.0.1:{port}/metrics",
                            headers={"Accept": "text/plain;version=0.0.4"})
    assert resp.status == 200
    text = resp.body.decode()
    assert "# TYPE tpu_sidecar_decode_tokens counter" in text
    assert "tpu_sidecar_queue_depth" in text
    # JSON default unchanged.
    resp = await client.get(f"http://127.0.0.1:{port}/metrics")
    assert resp.json()["decode_steps"] >= 0


async def test_spec_decoding_sidecar_end_to_end():
    """A speculative-decoding engine behind the full HTTP surface:
    non-streaming chat with usage, and streaming SSE framing with a
    finish_reason + usage chunk."""
    engine = Engine(EngineConfig(model="test-tiny", max_slots=2, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False,
                                 spec_draft="test-tiny", spec_k=3))
    server = SidecarServer(engine, served_model_name="tpu-spec")
    port = await server.start("127.0.0.1", 0)
    try:
        client = HTTPClient()
        body = {"model": "tpu-spec", "max_tokens": 8,
                "messages": [{"role": "user", "content": "hello"}]}
        resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                                 json.dumps(body).encode())
        data = resp.json()
        assert data["choices"][0]["finish_reason"] in ("stop", "length")
        assert data["usage"]["completion_tokens"] >= 1

        sbody = dict(body, stream=True, stream_options={"include_usage": True})
        resp = await client.post(f"http://127.0.0.1:{port}/v1/chat/completions",
                                 json.dumps(sbody).encode(), stream=True)
        chunks = []
        async for payload in iter_sse_payloads(resp.iter_lines()):
            chunks.append(json.loads(payload))
        finishes = [c["choices"][0]["finish_reason"]
                    for c in chunks if c.get("choices")]
        assert any(f in ("stop", "length") for f in finishes)
        assert any(c.get("usage") for c in chunks)
    finally:
        await server.shutdown()


# ---------------------------------------------------------------------------
# What the sidecar says of itself is counted or stamped, never estimated
# (ISSUE 46): the fields left on each surface, exactly.
# ---------------------------------------------------------------------------
ESTIMATES = {"mfu", "goodput_mfu", "hbm_bandwidth_util", "compute_efficiency",
             "prefill_flops", "decode_flops", "flops", "hbm_bytes", "roofline_ms"}


@pytest.fixture(scope="module")
def told(aloop):
    """One streamed chat through a sidecar with every surface on; what each
    surface then carries."""
    from inference_gateway_tpu.otel.access_log import AccessLog
    from inference_gateway_tpu.otel.otel import OpenTelemetry

    engine = Engine(EngineConfig(model="test-tiny", max_slots=4, max_seq_len=128,
                                 dtype="float32", max_prefill_batch=2, use_mesh=False))
    log = AccessLog(service="tpu-sidecar", tail_size=16)
    server = SidecarServer(engine, served_model_name="test-tiny", otel=OpenTelemetry(), access_log=log)
    port = aloop.run(server.start("127.0.0.1", 0))

    async def drive():
        http = HTTPClient()
        body = json.dumps({"model": "test-tiny", "stream": True, "max_tokens": 8,
                           "messages": [{"role": "user", "content": "probe"}]}).encode()
        resp = await http.post(f"http://127.0.0.1:{port}/v1/chat/completions", body, stream=True)
        async for _ in resp.iter_raw():
            pass
        return {"push": server._otlp_payload(),
                "metrics": (await http.get(f"http://127.0.0.1:{port}/metrics")).json(),
                "roofline": (await http.get(f"http://127.0.0.1:{port}/debug/roofline")).status}

    try:
        out = aloop.run(drive())
        out["access"] = [e for e in log.tail if e.get("route") == "/v1/chat/completions"][-1]
        yield out
    finally:
        aloop.run(server.shutdown())


def test_access_log_line_carries_exactly_the_fields_left(told):
    assert set(told["access"]) == {
        "log", "ts", "service", "route", "request_id", "provider", "model", "stream", "finish_reason",
        "input_tokens", "output_tokens", "queue_wait_ms", "prefill_ms", "decode_ms",
        "recv_to_submit_ms", "first_token_to_write_ms"}
    assert told["access"]["output_tokens"] > 0


def test_otlp_push_carries_exactly_the_series_left(told):
    from inference_gateway_tpu.otel.otel import OpenTelemetry

    names = [m["name"] for rm in told["push"]["resourceMetrics"]
             for sm in rm["scopeMetrics"] for m in sm["metrics"]]
    assert sorted(names) == ["engine.hbm.plan_bytes", "gen_ai.server.time_in_queue",
                             "gen_ai.server.time_per_output_token", "gen_ai.server.time_to_first_token"]
    gateway = OpenTelemetry()
    result = gateway.ingest_metrics(told["push"], "tpu-sidecar")
    assert result["accepted"] == len(names) and result["rejected"] == 0
    # A push from a sidecar of the parent's kind is refused by name, not mapped onto a gauge.
    old = {"resourceMetrics": [{"scopeMetrics": [{"metrics": [
        {"name": "engine.mfu", "gauge": {"dataPoints": [{"asDouble": 0.5, "attributes": []}]}}]}]}]}
    refused = gateway.ingest_metrics(old, "tpu-sidecar")
    assert refused["accepted"] == 0 and refused["rejected"] == 1
    assert "engine_mfu" not in gateway.expose_prometheus()


def test_metrics_json_carries_exactly_the_fields_left(told):
    assert set(told["metrics"]) == {
        "active_requests", "queue_depth", "uptime_seconds", "slot_occupancy", "kv_page_utilization",
        "decode_steps", "decode_tokens", "prefill_batches", "prefill_tokens",
        "preemptions", "engine_restarts", "streams_migrated_out", "structured", "wasted_tokens",
        "compiles", "recompiles", "transfers", "h2d_chain_transfers", "hbm_plan_bytes"}
    assert told["metrics"]["wasted_tokens"] == 0
    assert not ESTIMATES & set(told["metrics"])


def test_roofline_is_gone_and_no_remaining_variable_is_refused(told):
    """/debug/roofline answers 404 like any unknown path, and the four
    variables that went with it are not read: a deployment that still sets
    them starts as before, and every TELEMETRY_* variable that is left loads."""
    from inference_gateway_tpu.config import TelemetryConfig

    assert told["roofline"] == 404
    env = {"TELEMETRY_ENABLE": "true", "TELEMETRY_ACCESS_LOG": "true", "TELEMETRY_ACCESS_LOG_TAIL": "8",
           "TELEMETRY_PROFILING_TIMELINE_SIZE": "64", "TELEMETRY_DEVICE_ENABLE": "false",
           "TELEMETRY_DEVICE_LEDGER_SIZE": "32", "TELEMETRY_JOURNEY_ENABLE": "false",
           "TELEMETRY_ACCOUNTING_ENABLE": "true", "TELEMETRY_ACCOUNTING_WINDOW": "10s",
           "TELEMETRY_ACCOUNTING_CHIP": "v5e", "TELEMETRY_DEVICE_COST_ANALYSIS": "true"}
    cfg = TelemetryConfig.load(env)
    assert (cfg.enable, cfg.access_log, cfg.access_log_tail) == (True, True, 8)
    assert cfg.profiling_timeline_size == 64
    assert (cfg.device_enable, cfg.device_ledger_size, cfg.journey_enable) == (False, 32, False)
    gone = {"accounting_enable", "accounting_window", "accounting_chip", "device_cost_analysis"}
    assert not gone & set(vars(cfg))
