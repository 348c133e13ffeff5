"""Telemetry middleware.

Capability parity with reference api/middlewares/telemetry.go:50-284:
observes inference responses, parses token usage and tool calls out of
both non-streaming JSON bodies and SSE streams (scanning only the last 4
chunks of a stream for usage, telemetry.go:195-231), records the GenAI
metrics, and enriches the active span with provider/model/error. For
streams the middleware wraps the chunk iterator — a bounded ring of the
most recent frames replaces the reference's 1 MiB body buffer.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Any

from inference_gateway_tpu.netio.server import Handler, Request, Response, StreamingResponse
from inference_gateway_tpu.providers.routing import determine_provider_and_model_name

INFERENCE_PATHS = ("/v1/chat/completions", "/v1/responses")
USAGE_SCAN_CHUNKS = 4  # telemetry.go:195
MCP_TOOL_PREFIX = "mcp_"


def classify_tool_type(name: str) -> str:
    """``mcp_`` prefix ⇒ "mcp", else "provider" (telemetry.go:278-283)."""
    return "mcp" if name.startswith(MCP_TOOL_PREFIX) else "provider"


def _provider_and_model(req: Request) -> tuple[str, str]:
    body = req.ctx.get("parsed_body")
    if body is None:
        try:
            body = req.json()
        except Exception:
            body = {}
    model = body.get("model", "") if isinstance(body, dict) else ""
    provider = req.query_get("provider")
    if not provider:
        detected, _ = determine_provider_and_model_name(model)
        provider = detected or ""
    return provider, model


def parse_usage(payload: dict[str, Any]) -> tuple[int, int] | None:
    usage = payload.get("usage")
    if not isinstance(usage, dict):
        return None
    if "input_tokens" in usage:  # Responses API shape (/v1/responses)
        return int(usage.get("input_tokens") or 0), int(usage.get("output_tokens") or 0)
    return int(usage.get("prompt_tokens") or 0), int(usage.get("completion_tokens") or 0)


def responses_tool_calls(obj: dict[str, Any]) -> list[str]:
    """Function-call names in a Responses-API object's `output` array —
    the one scan both the streaming (response.completed event) and
    non-streaming branches share."""
    names = []
    for item in obj.get("output") or []:
        if isinstance(item, dict) and item.get("type") == "function_call":
            name = item.get("name")
            if name:
                names.append(name)
    return names


def extract_tool_calls(message: dict[str, Any]) -> list[str]:
    return [
        tc.get("function", {}).get("name", "")
        for tc in message.get("tool_calls") or []
        if isinstance(tc, dict)
    ]


def telemetry_middleware(otel, logger=None, source: str = "gateway", slow_log=None,
                         journeys=None, slo=None):
    """``slow_log`` (otel/profiling.SlowRequestLog) makes this middleware
    the gateway-edge forensics feeder: it already measures TTFC, total
    duration, and token rate for every inference request, so breaches are
    judged here — independent of whether the access log is enabled.

    ``journeys`` (otel/journey.JourneyRecorder) and ``slo``
    (otel/slo.SloTracker) ride the same measurements (ISSUE 18): the
    admitted/first_byte/finished journey events and the
    availability/TTFT/TPOT SLI observations reuse the timestamps this
    middleware already takes — no extra clock reads on the hot path."""

    async def middleware(req: Request, nxt: Handler) -> Response:
        if req.method != "POST" or req.path not in INFERENCE_PATHS:
            return await nxt(req)

        provider, model = _provider_and_model(req)
        team = req.headers.get("X-Team") or ""
        event = req.ctx.get("wide_event")
        if event is not None:
            event["provider"] = provider
            event["model"] = model
            if team:
                event["team"] = team
        span = req.ctx.get("span")
        trace_id = span.trace_id if span is not None else None
        tenant = (req.ctx.get("tenant")
                  or (event.get("tenant") if event is not None else None)
                  or team or None)
        # The pool key for SLO purposes is the requested deployment class
        # — which replica actually served is journey detail, not an SLI
        # scope (a tenant's SLO should not fork per failover hop).
        pool = f"{provider}/{model}" if provider and model else None
        if journeys is not None:
            journeys.record(trace_id, "admitted", path=req.path,
                            provider=provider or None, model=model or None,
                            tenant=tenant)
        start = time.perf_counter()
        resp = await nxt(req)
        if span is not None:
            span.set_attribute("gen_ai.provider.name", provider)
            span.set_attribute("gen_ai.request.model", model)

        def record(error_type: str, usage: tuple[int, int] | None, tool_names: list[str]) -> None:
            otel.record_request_duration(
                source, team, provider, model, error_type, time.perf_counter() - start
            )
            if usage:
                otel.record_token_usage(source, team, provider, model, usage[0], usage[1])
                if event is not None:
                    event["input_tokens"], event["output_tokens"] = usage
            for name in tool_names:
                otel.record_tool_call(source, team, provider, model, classify_tool_type(name), name)
            if error_type and span is not None:
                span.set_status("ERROR", error_type)
                span.set_attribute("error.type", error_type)

        if isinstance(resp, StreamingResponse) and resp.chunks is not None:
            inner = resp.chunks
            ring: deque[bytes] = deque(maxlen=USAGE_SCAN_CHUNKS)

            async def observed():
                # Token-level streaming metrics off the SSE relay (ISSUE
                # 3): time-to-first-chunk, inter-chunk gaps as the
                # gateway-edge TPOT view (the sidecar's emit-path TPOT is
                # the per-token truth; this one includes relay queueing —
                # exactly the delta a saturated relay shows), and
                # tokens/sec over the whole stream once usage is known.
                t_first: float | None = None
                t_last: float | None = None
                n_gaps = 0
                completed = False
                client_closed = False
                try:
                    async for chunk in inner:
                        now = time.perf_counter()
                        if chunk.strip():
                            if t_first is None:
                                t_first = now
                                otel.record_time_to_first_chunk(
                                    source, team, provider, model, now - start)
                                if journeys is not None:
                                    journeys.record(
                                        trace_id, "first_byte",
                                        ttfc_ms=round((now - start) * 1000, 3))
                            elif t_last is not None and not chunk.startswith(b"data: [DONE]"):
                                # Skip the FIRST gap: for OpenAI-style
                                # streams chunk 1 is the role preamble,
                                # so preamble→token-1 is prefill time
                                # (TTFT's job), not inter-token latency.
                                # Trailing finish/usage frames still add
                                # a couple ~0 gaps — unavoidable without
                                # parsing JSON on the relay hot path;
                                # the sidecar's emit-path TPOT is exact.
                                n_gaps += 1
                                if n_gaps >= 2:
                                    # The relay delivers coalesced BLOCKS
                                    # that may carry many SSE frames: an
                                    # N-frame block arriving after gap g
                                    # approximates N tokens at g/N each
                                    # (one cheap bytes.count, no JSON on
                                    # the hot path). Line-anchored so
                                    # "data:" INSIDE token text doesn't
                                    # inflate the frame count.
                                    frames = (chunk.count(b"\ndata:")
                                              + chunk.startswith(b"data:")) or 1
                                    otel.record_tpot(source, team, provider, model,
                                                     (now - t_last) / frames)
                            t_last = now
                            ring.append(chunk)
                        yield chunk
                    completed = True
                except GeneratorExit:
                    # The CLIENT walked away mid-stream — the gateway
                    # delivered everything it was asked for, so this is
                    # not an availability breach.
                    client_closed = True
                    raise
                finally:
                    if event is not None and t_first is not None:
                        event["ttfc_ms"] = round((t_first - start) * 1000, 3)
                    usage = None
                    tool_names: list[str] = []
                    # The relay yields raw transport blocks, not SSE
                    # lines — a `data:` line (e.g. the final usage chunk)
                    # can straddle two blocks. Join the retained window
                    # before splitting so the scan is block-boundary-safe
                    # (advisor round-2). A line whose head fell off the
                    # ring no longer starts with `data:` and is skipped.
                    for line in b"".join(ring).split(b"\n"):
                        line = line.strip()
                        if not line.startswith(b"data:"):
                            continue
                        data = line[5:].strip()
                        if not data or data == b"[DONE]":
                            continue
                        try:
                            payload = json.loads(data)
                        except ValueError:
                            continue
                        usage = parse_usage(payload) or usage
                        # Responses API streams: the final
                        # `response.completed` event carries the nested
                        # `response` object with usage AND the complete
                        # `output` array. Scanning output there (not the
                        # per-item added/done events) is eviction-proof —
                        # the event is always in the ring's tail window —
                        # and counts each function call exactly once
                        # (code-review round 3: item-event matching
                        # double-counted added+done and lost calls whose
                        # events fell off the 4-chunk ring).
                        final = payload.get("response")
                        if isinstance(final, dict):
                            usage = parse_usage(final) or usage
                            tool_names.extend(responses_tool_calls(final))
                        for choice in payload.get("choices") or []:
                            delta = choice.get("delta") or {}
                            for tc in delta.get("tool_calls") or []:
                                name = (tc.get("function") or {}).get("name")
                                if name:
                                    tool_names.append(name)
                    record("", usage, tool_names)
                    rate = None
                    if (usage and usage[1] > 1 and t_first is not None
                            and t_last is not None and t_last > t_first):
                        # First token anchors the clock: N tokens span
                        # N-1 inter-token intervals.
                        rate = (usage[1] - 1) / (t_last - t_first)
                        otel.record_output_token_rate(source, team, provider, model, rate)
                        if event is not None:
                            event["tokens_per_sec"] = round(rate, 2)
                    if slow_log is not None:
                        slow_log.observe_event({
                            "route": req.path,
                            "model": model,
                            "status": resp.status,
                            "stream": True,
                            "trace_id": span.trace_id if span is not None else None,
                            "output_tokens": usage[1] if usage else None,
                            "ttfc_ms": round((t_first - start) * 1000, 3)
                            if t_first is not None else None,
                            "duration_ms": round((time.perf_counter() - start) * 1000, 3),
                            "tokens_per_sec": rate,
                        })
                    ok = (completed or client_closed) and resp.status < 500
                    if journeys is not None:
                        # The terminal journey event carries the billing
                        # evidence: once-only by construction — a relay
                        # that dies with its worker never reaches this
                        # finally, and the continuation stream that
                        # finishes the work bills exactly once, here.
                        journeys.record(
                            trace_id, "finished", status=resp.status, ok=ok,
                            input_tokens=usage[0] if usage else None,
                            output_tokens=usage[1] if usage else None,
                            duration_ms=round(
                                (time.perf_counter() - start) * 1000, 3))
                    if slo is not None:
                        tpot = None
                        if (usage and usage[1] > 1 and t_first is not None
                                and t_last is not None and t_last > t_first):
                            tpot = (t_last - t_first) / (usage[1] - 1)
                        slo.observe(
                            tenant=tenant, pool=pool, ok=ok,
                            ttft=(t_first - start) if t_first is not None
                            else None,
                            tpot=tpot)

            resp.chunks = observed()
            return resp

        error_type = str(resp.status) if resp.status >= 400 else ""
        usage = None
        tool_names: list[str] = []
        if resp.status == 200 and resp.body:
            try:
                payload = json.loads(resp.body)
                usage = parse_usage(payload)
                for choice in payload.get("choices") or []:
                    msg = choice.get("message") or {}
                    tool_names.extend(n for n in extract_tool_calls(msg) if n)
                # Responses API non-streaming bodies carry function calls
                # as `output` items of type function_call, not `choices`.
                tool_names.extend(responses_tool_calls(payload))
            except ValueError:
                pass
        record(error_type, usage, tool_names)
        if slow_log is not None:
            slow_log.observe_event({
                "route": req.path,
                "model": model,
                "status": resp.status,
                "stream": False,
                "trace_id": span.trace_id if span is not None else None,
                "output_tokens": usage[1] if usage else None,
                "duration_ms": round((time.perf_counter() - start) * 1000, 3),
            })
        if journeys is not None:
            journeys.record(
                trace_id, "finished", status=resp.status,
                ok=resp.status < 500,
                input_tokens=usage[0] if usage else None,
                output_tokens=usage[1] if usage else None,
                duration_ms=round((time.perf_counter() - start) * 1000, 3))
        if slo is not None:
            slo.observe(tenant=tenant, pool=pool, ok=resp.status < 500)
        return resp

    return middleware


def _traceparent_trace_id(header: str | None) -> str | None:
    """The 32-hex trace id out of a W3C traceparent header, or None —
    the only parsing a shed request gets (it never reaches the tracer)."""
    if not header:
        return None
    parts = header.split("-")
    if len(parts) >= 2 and len(parts[1]) == 32:
        try:
            int(parts[1], 16)
        except ValueError:
            return None
        return parts[1]
    return None


def journey_shed_middleware(journeys, slo=None):
    """Shed-visibility shim (ISSUE 18): admission rejects OUTSIDE the
    tracing/telemetry middlewares (a shed request costs no span), so a
    journey's ``shed`` event is recorded here — between the access log
    and admission — keyed by the CLIENT's inbound traceparent. A caller
    that propagates one trace id across a retry therefore sees its
    rejections and its eventual service as one journey.

    429s (the tenant's own quota) charge no availability budget; 503
    sheds are gateway-caused unavailability and do."""

    async def middleware(req: Request, nxt: Handler) -> Response:
        resp = await nxt(req)
        if req.method != "POST" or req.path not in INFERENCE_PATHS:
            return resp
        event = req.ctx.get("wide_event")
        shed_reason = event.get("shed") if event is not None else None
        if shed_reason is None and (event is not None
                                    or resp.status not in (429, 503)):
            return resp
        trace_id = _traceparent_trace_id(req.headers.get("traceparent"))
        tenant = (req.ctx.get("tenant")
                  or (event.get("tenant") if event is not None else None))
        journeys.record(trace_id, "shed", status=resp.status,
                        reason=shed_reason, tenant=tenant)
        if slo is not None and tenant and resp.status != 429:
            slo.observe(tenant=tenant, ok=False)
        return resp

    return middleware


def traceparent_relay_middleware():
    """Tracing off (ISSUE 24): hand a well-formed inbound ``traceparent``
    to the provider hop unchanged, where ``tracing_middleware`` would
    have put its own span's. The sidecar's access-log line then carries
    the client's trace id whether or not this gateway traces, so a
    client can join its own records to it."""

    async def middleware(req: Request, nxt: Handler) -> Response:
        header = req.headers.get("traceparent")
        if _traceparent_trace_id(header) is not None:
            req.ctx["traceparent"] = header
        return await nxt(req)

    return middleware


def tracing_middleware(tracer, skip_paths: tuple[str, ...] = ("/health", "/v1/metrics")):
    """otelgin equivalent: span per request, honoring inbound traceparent
    (cmd/gateway/main.go:238-243)."""

    async def middleware(req: Request, nxt: Handler) -> Response:
        if req.path in skip_paths:
            return await nxt(req)
        span = tracer.start_span(
            f"{req.method} {req.path}", traceparent=req.headers.get("traceparent")
        )
        span.set_attribute("http.request.method", req.method)
        span.set_attribute("url.path", req.path)
        req.ctx["span"] = span
        req.ctx["traceparent"] = span.traceparent()
        try:
            resp = await nxt(req)
            span.set_attribute("http.response.status_code", resp.status)
            if resp.status >= 500:
                span.set_status("ERROR", str(resp.status))
            return resp
        finally:
            tracer.end_span(span)

    return middleware
