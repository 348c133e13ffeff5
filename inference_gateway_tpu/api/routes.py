"""Gateway HTTP handlers.

Capability parity with reference api/routes.go:40-1053 — the 8-endpoint
router: ListModels (fan-out + metadata enrichment), ChatCompletions
(selector → provider resolution → allow/deny → vision gate → provider
call with SSE relay), Messages (Anthropic passthrough, no loopback),
ListTools (MCP), MetricsIngestion (OTLP push), Proxy (auth attachment +
streaming relay), Healthcheck, NotFound.
"""

from __future__ import annotations

import asyncio
import gzip
import json
from dataclasses import dataclass
from typing import Any

from inference_gateway_tpu.api.context_window import resolve_context_windows
from inference_gateway_tpu.config import Config
from inference_gateway_tpu.logger import Logger, new_logger
from inference_gateway_tpu.netio.client import HTTPClient, HTTPClientError
from inference_gateway_tpu.netio.server import (
    Headers,
    Request,
    Response,
    Router,
    StreamingResponse,
)
from inference_gateway_tpu.providers import constants, routing
from inference_gateway_tpu.providers.core import HTTPError
from inference_gateway_tpu.providers.registry import (
    ProviderConfig,
    ProviderNotConfiguredError,
    ProviderNotFoundError,
    ProviderRegistry,
)
from inference_gateway_tpu.providers.types import has_image_content, strip_image_content
from inference_gateway_tpu.resilience import (
    BudgetExceededError,
    Resilience,
    UpstreamUnavailableError,
)

MAX_BODY_SIZE = 10 << 20  # routes.go:137
MAX_METRICS_BODY = 4 << 20  # api/metrics.go:15
INCLUDE_KEYS = ("context_window", "pricing")


def error_json(message: str, status: int) -> Response:
    return Response.json({"error": message}, status=status)


def messages_error(status: int, err_type: str, message: str) -> Response:
    """Anthropic error envelope (routes.go:788-793)."""
    return Response.json(
        {"type": "error", "error": {"type": err_type, "message": message}}, status=status
    )


def _failure_category(e: Exception) -> str:
    """Client-safe summary of why a provider call failed — internal
    detail (hosts, ports, exception classes) stays in the server log."""
    if isinstance(e, UpstreamUnavailableError):
        return "unavailable"
    if isinstance(e, (BudgetExceededError, asyncio.TimeoutError)):
        return "timeout"
    if isinstance(e, HTTPError):
        return f"upstream_error_{e.status_code}"
    if isinstance(e, HTTPClientError):
        return "unreachable"
    return "error"


@dataclass
class _Candidate:
    """One failover target: a built provider plus its (provider, model)
    breaker key — Deployment-shaped for Resilience.execute. ``model`` is
    the replica IDENTITY (breakers, probes, ring, telemetry);
    ``serve_model`` is the model name actually sent upstream (ISSUE 11:
    fleet replicas of one model carry unique routing ids)."""

    provider_obj: Any
    provider: str
    model: str
    serve_model: str = ""

    def __post_init__(self) -> None:
        if not self.serve_model:
            self.serve_model = self.model


class _MessagesPassthrough(Exception):
    """Carrier for a non-SSE upstream response on the streamed Messages
    path: not an upstream illness (no breaker charge, no failover) —
    the Anthropic envelope passes through verbatim."""

    def __init__(self, status: int, content_type: str, body: bytes) -> None:
        super().__init__(f"upstream returned {status} non-SSE")
        self.status = status
        self.content_type = content_type
        self.body = body


class RouterImpl:
    """All gateway endpoints (routes.go:52-67 constructor wiring)."""

    def __init__(
        self,
        cfg: Config,
        registry: ProviderRegistry,
        client: HTTPClient,
        logger: Logger | None = None,
        otel=None,
        mcp_client=None,
        mcp_agent=None,
        selector: routing.Selector | None = None,
        resilience: Resilience | None = None,
        overload=None,
        fleet_urls: dict[str, set[str]] | None = None,
        journeys=None,
    ) -> None:
        self.cfg = cfg
        self.registry = registry
        self.client = client
        self.logger = logger or new_logger()
        self.otel = otel
        self.mcp_client = mcp_client
        self.mcp_agent = mcp_agent
        self.selector = selector
        self.resilience = resilience or Resilience(
            getattr(cfg, "resilience", None), otel=otel, logger=self.logger
        )
        # Admission/drain ledger (ISSUE 2): the health handler consults
        # it so LBs see readiness fail the moment a drain begins.
        self.overload = overload
        # Per-provider allowlist of fleet deployment base URLs (ISSUE
        # 11): the only values the /proxy hop's X-Fleet-Url override may
        # take — sourced from the operator's own pools file, so the hop
        # can never be steered to an arbitrary host.
        self.fleet_urls = fleet_urls or {}
        # Journey recorder (ISSUE 18): routed/spliced lifecycle events
        # are recorded here, where the serving candidate is known.
        self.journeys = journeys

    def _trace_id(self, req: Request) -> str | None:
        span = req.ctx.get("span")
        return span.trace_id if span is not None else None

    # -- wiring --------------------------------------------------------
    def build_router(self) -> Router:
        """Route table (cmd/gateway/main.go:256-266)."""
        r = Router()
        r.get("/health", self.healthcheck_handler)
        r.get("/v1/models", self.list_models_handler)
        r.post("/v1/chat/completions", self.chat_completions_handler)
        r.post("/v1/responses", self.responses_handler)
        r.post("/v1/messages", self.messages_handler)
        r.get("/v1/mcp/tools", self.list_tools_handler)
        r.post("/v1/metrics", self.metrics_ingestion_handler)
        r.add("GET", "/proxy/:provider/*path", self.proxy_handler)
        r.add("POST", "/proxy/:provider/*path", self.proxy_handler)
        r.not_found = self.not_found_handler
        return r

    # -- helpers -------------------------------------------------------
    def _build_provider(self, provider_id: str, url: str | None = None):
        return self.registry.build_provider(provider_id, self.client, url=url)

    def _provider_error(self, e: Exception, provider_id: str, envelope=error_json) -> Response:
        if isinstance(e, ProviderNotConfiguredError):
            self.logger.error("provider requires an api key but none configured", e, "provider", provider_id)
            return envelope("Provider requires an API key. Please configure the provider's API key.", 400)
        self.logger.error("provider not found or not supported", e, "provider", provider_id)
        return envelope("Provider not found. Please check the list of supported providers.", 400)

    # -- handlers ------------------------------------------------------
    async def healthcheck_handler(self, req: Request) -> Response:
        if self.overload is not None and self.overload.draining:
            # Readiness flip (ISSUE 2 graceful drain): the listener is
            # still open so in-flight streams can finish, but the LB
            # must stop routing new traffic here.
            return Response.json({"message": "draining"}, status=503)
        return Response.json({"message": "OK"})

    async def not_found_handler(self, req: Request) -> Response:
        self.logger.warn("route not found", "path", req.path, "method", req.method)
        return error_json("Requested route is not found", 404)

    # ------------------------------------------------------------------
    async def list_models_handler(self, req: Request) -> Response:
        """GET /v1/models (routes.go:435-540)."""
        include_raw = req.query_get("include")
        include_keys: list[str] = []
        if include_raw.strip():
            for part in include_raw.split(","):
                key = part.strip()
                if not key:
                    continue
                if key not in INCLUDE_KEYS:
                    return error_json(f"unknown include value {key!r}", 400)
                if key not in include_keys:
                    include_keys.append(key)

        ctx = {"auth_token": req.ctx.get("auth_token"), "traceparent": req.ctx.get("traceparent")}

        # list-models is idempotent — retried with jittered backoff inside
        # the read-timeout budget (ISSUE 1 tentpole (c)).
        async def list_with_retry(provider, pid: str) -> dict[str, Any]:
            async def call(cand: _Candidate, b) -> Any:
                return await cand.provider_obj.list_models(ctx, timeout=b.timeout())

            result, _ = await self.resilience.execute(
                [_Candidate(provider, pid, "")], call,
                budget=self.resilience.new_budget(self.cfg.server.read_timeout),
                idempotent=True,
            )
            return result

        provider_id = req.query_get("provider")
        if provider_id:
            try:
                provider = self._build_provider(provider_id)
            except (ProviderNotFoundError, ProviderNotConfiguredError) as e:
                return self._provider_error(e, provider_id)
            try:
                response = await list_with_retry(provider, provider_id)
            except UpstreamUnavailableError:
                return error_json("Provider temporarily unavailable", 503)
            except (BudgetExceededError, asyncio.TimeoutError):
                return error_json("Request timed out", 504)
            except (HTTPError, HTTPClientError) as e:
                self.logger.error("failed to list models", e, "provider", provider_id)
                return error_json("Failed to list models", 502)
            models = routing.filter_models(
                response["data"], self.cfg.allowed_models, self.cfg.disallowed_models
            )
            response["data"] = models
        else:
            # Parallel fan-out across all configured providers
            # (routes.go:480-517). Unconfigured providers are skipped
            # silently; CALL failures are logged with the provider id and
            # surfaced in a ``failed_providers`` annotation instead of
            # being dropped without a trace.
            async def fetch(pid: str) -> tuple[str, list[dict[str, Any]], str | None]:
                try:
                    provider = self._build_provider(pid)
                except (ProviderNotFoundError, ProviderNotConfiguredError):
                    return pid, [], None
                try:
                    result = await list_with_retry(provider, pid)
                    return pid, result["data"], None
                except Exception as e:
                    # Full detail goes to the log; clients get a sanitized
                    # category (no internal hosts/ports/class names).
                    self.logger.error("failed to list models", e, "provider", pid)
                    return pid, [], _failure_category(e)

            # No outer wait_for: each fetch is individually bounded by its
            # read-timeout budget (connect/read timeouts derive from it),
            # so a hanging provider becomes a failed_providers entry
            # instead of erroring the whole fan-out.
            provider_ids = list(self.registry.get_providers())
            results = await asyncio.gather(*(fetch(pid) for pid in provider_ids))
            models = [m for _, sub, _ in results for m in sub]
            models = routing.filter_models(models, self.cfg.allowed_models, self.cfg.disallowed_models)
            response = {"object": "list", "data": models}
            failed = [{"provider": pid, "error": err} for pid, _, err in results if err]
            if failed:
                response["failed_providers"] = failed

        if "context_window" in include_keys:
            await resolve_context_windows(
                self.client, self.registry.get_providers(), response["data"], logger=self.logger
            )
        return self._render_models_response(response, include_keys)

    def _render_models_response(self, response: dict[str, Any], include_keys: list[str]) -> Response:
        """Explicit nulls for requested-but-missing keys; strip
        non-requested metadata (routes.go:355-403)."""
        for model in response["data"]:
            for key in INCLUDE_KEYS:
                if key not in include_keys:
                    model.pop(key, None)
                elif key not in model:
                    model[key] = None
        return Response.json(response)

    # ------------------------------------------------------------------
    async def chat_completions_handler(self, req: Request) -> Response:
        """POST /v1/chat/completions (routes.go:596-782)."""
        body = req.ctx.get("parsed_body")
        if body is None:
            try:
                body = req.json()
            except (ValueError, UnicodeDecodeError):
                return error_json("Failed to decode request", 400)
        if not isinstance(body, dict):
            return error_json("Failed to decode request", 400)
        # Schema validation against the generated typed surface — the
        # reference rejects at bind time with typed errors
        # (routes.go:599-613 binding oapi-codegen structs); malformed
        # shapes get a 400 naming the offending fields instead of
        # failing ad hoc deep in handler logic.
        from inference_gateway_tpu.api.validation import validate_chat_request

        problems = validate_chat_request(body)
        if problems:
            return error_json("Invalid request: " + "; ".join(problems), 400)

        original_model = body.get("model") or ""
        route = self._resolve_route(req, original_model, body)
        if isinstance(route, Response):
            return route
        candidates, alias = route

        ctx = {"auth_token": req.ctx.get("auth_token"), "traceparent": req.ctx.get("traceparent")}
        budget = self.resilience.new_budget()
        event = req.ctx.get("wide_event")
        if event is not None and alias:
            event["alias"] = alias

        def request_for(cand: _Candidate) -> dict[str, Any]:
            out = dict(body)
            # serve_model, not the replica id: upstream envelopes must be
            # identical across fleet replicas (the migration splice's
            # byte-identity depends on it).
            out["model"] = cand.serve_model
            out["messages"] = self._vision_gate(
                cand.provider_obj, cand.provider, cand.serve_model,
                body.get("messages") or [])
            return out

        if body.get("stream"):
            # Streaming is NOT idempotent once bytes flow — but it IS
            # until the first relayed byte (ISSUE 7): execute_streaming
            # fails over on establishment errors AND on an upstream that
            # dies before any byte reaches the client, under the same
            # trace id. Past the first byte (ISSUE 9), a continuation
            # re-establishes with the generated-so-far prefix on
            # continuation-capable candidates and splices the frames; the
            # returned stream is idle-guarded internally.
            async def call(cand: _Candidate, b) -> Any:
                return await cand.provider_obj.stream_chat_completions(
                    request_for(cand), ctx, timeout=b.timeout())

            continuation = self._make_continuation(candidates, request_for, ctx)
            trace_id = self._trace_id(req)
            if self.journeys is not None and isinstance(
                    body.get("continuation"), dict):
                # The CLIENT re-issued with a generated-so-far prefix
                # (PR 9 contract) — its previous stream died with a
                # worker. Under a propagated traceparent this splice
                # lands in the SAME journey the dead worker's shm slots
                # still hold, so the cross-worker chain reads whole.
                cont = body["continuation"]
                self.journeys.record(
                    trace_id, "spliced",
                    continuation_id=cont.get("id"),
                    prefix_chars=len(cont.get("text") or ""))
            try:
                stream, served = await self.resilience.execute_streaming(
                    candidates, call, budget=budget, alias=alias,
                    event=event, continuation=continuation,
                    trace_id=trace_id)
            except UpstreamUnavailableError as e:
                return error_json(str(e), 503)
            except BudgetExceededError:
                return error_json("Request timed out", 504)
            except HTTPError as e:
                return error_json(e.message, e.status_code)
            except HTTPClientError as e:
                return error_json(str(e), 502)
            if event is not None:
                event["served_provider"] = served.provider
                event["served_model"] = served.model
            if self.journeys is not None:
                self.journeys.record(
                    trace_id, "routed", alias=alias or None,
                    provider=served.provider, model=served.model)
            resp = StreamingResponse.sse(stream)
            if alias:
                resp.headers.set("X-Selected-Provider", served.provider)
                resp.headers.set("X-Selected-Model", served.model)
            return resp

        # Non-streamed completions buffer the whole upstream response, so
        # a failed attempt delivered nothing — safe to retry before the
        # first byte reaches the client (idempotent from its viewpoint).
        async def call(cand: _Candidate, b) -> Any:
            return await cand.provider_obj.chat_completions(
                request_for(cand), ctx, timeout=b.timeout())

        try:
            result, served = await self.resilience.execute(
                candidates, call, budget=budget, idempotent=True, alias=alias,
                event=event)
        except UpstreamUnavailableError as e:
            return error_json(str(e), 503)
        except (BudgetExceededError, asyncio.TimeoutError):
            return error_json("Request timed out", 504)
        except HTTPError as e:
            return error_json(e.message, e.status_code)
        except HTTPClientError as e:
            return error_json(str(e), 502)
        if event is not None:
            event["served_provider"] = served.provider
            event["served_model"] = served.model
        if self.journeys is not None:
            self.journeys.record(
                self._trace_id(req), "routed", alias=alias or None,
                provider=served.provider, model=served.model)
        resp = Response.json(result)
        if alias:
            resp.headers.set("X-Selected-Provider", served.provider)
            resp.headers.set("X-Selected-Model", served.model)
        return resp

    # ------------------------------------------------------------------
    def _resolve_route(self, req: Request, original_model: str,
                       body: dict[str, Any] | None = None):
        """Shared model-routing for chat-shaped endpoints (chat
        completions + responses): routing-pool alias resolution,
        provider/model prefix parsing, allow/deny enforcement on the
        ORIGINAL id (routes.go:641-653), and provider construction.
        Returns ``(candidates, alias)`` — the full ordered failover list
        (healthy replicas first for pool routes; a single candidate for
        direct routes; ``alias`` is the pool alias or "") — or an error
        Response. One implementation so the two endpoints can never
        drift (code-review round 3).

        With a fleet selector (ISSUE 11) and a request ``body``, pool
        ordering is prefix-affine: the prompt head's affinity key steers
        the request to the deployment whose PrefixCache already holds
        its pages. The key is derived only when the selector advertises
        affinity, so non-fleet routes pay nothing."""
        model = original_model
        provider_id = req.query_get("provider")
        alias = ""
        deployments: list[routing.Deployment] | None = None
        if self.selector is not None and not provider_id:
            akey = None
            if body is not None and getattr(self.selector, "affinity_enabled", False):
                from inference_gateway_tpu.fleet.affinity import affinity_key

                akey = affinity_key(
                    body.get("messages") or body.get("input"),
                    getattr(self.selector, "affinity_prefix_bytes", 1024))
            deployments = self.selector.select_candidates(model, affinity_key=akey)
            if deployments:
                alias = original_model
                self.logger.debug("routed logical model", "alias", original_model,
                                  "candidates",
                                  [(d.provider, d.model) for d in deployments])
        if not deployments:
            if not provider_id:
                detected, model = routing.determine_provider_and_model_name(model)
                if detected is None:
                    return error_json(
                        "Unable to determine provider for model. Please specify a provider "
                        "using the ?provider= query parameter or use the provider/model "
                        "format (e.g., openai/gpt-4).", 400)
                provider_id = detected
            deployments = [routing.Deployment(provider=provider_id, model=model)]
        if self.cfg.allowed_models:
            if not routing.model_matches(routing.parse_model_set(self.cfg.allowed_models), original_model):
                return error_json("Model not allowed. Please check the list of allowed models.", 403)
        elif self.cfg.disallowed_models:
            if routing.model_matches(routing.parse_model_set(self.cfg.disallowed_models), original_model):
                return error_json("Model is disallowed. Please use a different model.", 403)
        candidates: list[_Candidate] = []
        build_err: Exception | None = None
        build_err_pid = ""
        for d in deployments:
            try:
                provider = self._build_provider(d.provider, url=d.url or None)
            except (ProviderNotFoundError, ProviderNotConfiguredError) as e:
                build_err, build_err_pid = e, d.provider
                if alias:
                    self.logger.warn("pool deployment provider unavailable",
                                     "alias", alias, "provider", d.provider)
                continue
            candidates.append(_Candidate(provider, d.provider, d.model,
                                         serve_model=getattr(d, "serve_model", "")))
        if not candidates:
            return self._provider_error(build_err, build_err_pid)
        return candidates, alias

    def _vision_gate(self, provider, provider_id: str, model: str, messages: list) -> list:
        """Strip image parts for non-vision providers (routes.go:670-706)."""
        if not self.cfg.enable_vision:
            return messages
        if not any(has_image_content(m) for m in messages if isinstance(m, dict)):
            return messages
        if provider.supports_vision(model):
            return messages
        self.logger.info("filtering images from non-vision model request",
                         "provider", provider_id, "model", model)
        return [strip_image_content(m) if isinstance(m, dict) else m for m in messages]

    def _make_continuation(self, candidates: list[_Candidate], request_for, ctx):
        """Post-first-byte continuation state for a chat-shaped stream
        (ISSUE 9), or None when no candidate advertises the capability.
        ``request_for`` is the handler's per-candidate request builder —
        the continuation re-issues exactly that request plus the
        ``continuation`` extension."""
        if not any(c.provider_obj.supports_stream_continuation(c.model)
                   for c in candidates[1:]):
            # Continuation resumes on a candidate AFTER the establisher
            # (``remaining`` is always a suffix), so a capable candidate
            # at index 0 — or a single-candidate route — can never be a
            # resume target: arming would only buy per-frame parse +
            # prefix accumulation on the hot relay path for nothing
            # (code-review finding: the tpu-primary + foreign-fallback
            # pool rotation).
            return None
        from inference_gateway_tpu.resilience.continuation import ChatStreamContinuation

        def cont_call(cand: _Candidate, b, payload: dict) -> Any:
            return cand.provider_obj.stream_chat_completions(
                dict(request_for(cand), continuation=payload), ctx,
                timeout=b.timeout())

        return ChatStreamContinuation(
            cont_call,
            supports=lambda c: c.provider_obj.supports_stream_continuation(c.model),
            max_buffer=self.resilience.continuation_max_buffer)

    async def responses_handler(self, req: Request) -> Response:
        """POST /v1/responses — OpenAI Responses API, IMPLEMENTED.

        The reference specs this endpoint but registers no handler
        (main.go:256-266); here a stateless translation maps it onto
        the chat-completions surface every provider serves
        (api/responses.py). previous_response_id is rejected (no
        response store by design); store is accepted-and-ignored."""
        from inference_gateway_tpu.api.responses import (
            chat_to_response,
            responses_to_chat_request,
            stream_response_events,
        )
        from inference_gateway_tpu.api.validation import validate

        try:
            body = req.json()
        except (ValueError, UnicodeDecodeError):
            return error_json("Failed to decode request", 400)
        if not isinstance(body, dict):
            return error_json("Failed to decode request", 400)
        problems = validate(body, "CreateResponseRequest")
        if problems:
            return error_json("Invalid request: " + "; ".join(problems), 400)
        if body.get("previous_response_id"):
            return error_json(
                "previous_response_id is not supported: the gateway keeps no "
                "response store (stateless by design)", 400)

        original_model = body.get("model") or ""
        # Same routing/ACL/provider/vision pipeline as the chat path —
        # one implementation (routes.py _resolve_route), so pool aliases,
        # allow/deny semantics, and the vision gate can never drift
        # between the two endpoints.
        route = self._resolve_route(req, original_model, body)
        if isinstance(route, Response):
            return route
        candidates, alias = route

        ctx = {"auth_token": req.ctx.get("auth_token"), "traceparent": req.ctx.get("traceparent")}
        budget = self.resilience.new_budget()
        event = req.ctx.get("wide_event")
        if event is not None and alias:
            event["alias"] = alias

        def chat_req_for(cand: _Candidate) -> dict[str, Any]:
            chat_req = responses_to_chat_request(dict(body, model=cand.serve_model))
            chat_req["messages"] = self._vision_gate(
                cand.provider_obj, cand.provider, cand.serve_model,
                chat_req.get("messages") or [])
            return chat_req

        if body.get("stream"):
            async def call(cand: _Candidate, b) -> Any:
                return await cand.provider_obj.stream_chat_completions(
                    chat_req_for(cand), ctx, timeout=b.timeout())

            # Same recovery contract as the chat streaming path: pre- and
            # post-first-byte (ISSUE 7 + 9) — the continuation rides the
            # underlying chat-chunk stream, BEFORE the Responses-event
            # translation consumes it, so the splice logic is shared.
            continuation = self._make_continuation(candidates, chat_req_for, ctx)
            trace_id = self._trace_id(req)
            try:
                stream, _served = await self.resilience.execute_streaming(
                    candidates, call, budget=budget, alias=alias,
                    event=event, continuation=continuation,
                    trace_id=trace_id)
            except UpstreamUnavailableError as e:
                return error_json(str(e), 503)
            except BudgetExceededError:
                return error_json("Request timed out", 504)
            except HTTPError as e:
                return error_json(e.message, e.status_code)
            except HTTPClientError as e:
                return error_json(str(e), 502)
            if self.journeys is not None:
                self.journeys.record(
                    trace_id, "routed", alias=alias or None,
                    provider=_served.provider, model=_served.model)
            return StreamingResponse.sse(stream_response_events(stream, body))

        async def call(cand: _Candidate, b) -> Any:
            return await cand.provider_obj.chat_completions(
                chat_req_for(cand), ctx, timeout=b.timeout())

        try:
            result, _served = await self.resilience.execute(
                candidates, call, budget=budget, idempotent=True, alias=alias,
                event=event)
        except UpstreamUnavailableError as e:
            return error_json(str(e), 503)
        except (BudgetExceededError, asyncio.TimeoutError):
            return error_json("Request timed out", 504)
        except HTTPError as e:
            return error_json(e.message, e.status_code)
        except HTTPClientError as e:
            return error_json(str(e), 502)
        return Response.json(chat_to_response(result, body))

    async def messages_handler(self, req: Request) -> Response:
        """POST /v1/messages — Anthropic passthrough, no loopback hop
        (routes.go:808-980)."""
        if len(req.body) >= MAX_BODY_SIZE:
            return messages_error(413, "invalid_request_error", "Request body too large")
        try:
            parsed = json.loads(req.body)
        except ValueError:
            return messages_error(400, "invalid_request_error", "Failed to decode request")
        from inference_gateway_tpu.api.validation import validate_messages_request

        problems = validate_messages_request(parsed)
        if problems:
            return messages_error(400, "invalid_request_error",
                                  "Invalid request: " + "; ".join(problems))

        original_model = parsed.get("model") or ""
        model = original_model
        provider_id = req.query_get("provider")
        if not provider_id:
            detected, model = routing.determine_provider_and_model_name(model)
            if detected is None:
                return messages_error(
                    400, "invalid_request_error",
                    "Unable to determine provider for model. Please specify a provider using "
                    "the ?provider= query parameter or use the provider/model format "
                    "(e.g., anthropic/claude-sonnet-4-5).")
            provider_id = detected

        if self.cfg.allowed_models:
            if not routing.model_matches(routing.parse_model_set(self.cfg.allowed_models), original_model):
                return messages_error(403, "invalid_request_error",
                                      "Model not allowed. Please check the list of allowed models.")
        elif self.cfg.disallowed_models:
            if routing.model_matches(routing.parse_model_set(self.cfg.disallowed_models), original_model):
                return messages_error(403, "invalid_request_error",
                                      "Model is disallowed. Please use a different model.")

        if provider_id != constants.ANTHROPIC_ID:
            return messages_error(400, "not_supported_error",
                                  "The Messages API is not supported by this provider yet.")

        try:
            provider = self._build_provider(provider_id)
        except ProviderNotConfiguredError:
            return messages_error(400, "invalid_request_error",
                                  "Provider requires an API key. Please configure the provider's API key.")
        except ProviderNotFoundError:
            return messages_error(400, "invalid_request_error",
                                  "Provider not found. Please check the list of supported providers.")

        body = req.body
        if model != original_model:
            # Byte-for-byte passthrough except the model rewrite
            # (routes.go:884-899).
            parsed["model"] = model
            body = json.dumps(parsed).encode()

        is_streaming = bool(parsed.get("stream"))
        upstream_url = provider.cfg.url.rstrip("/") + "/messages"
        headers = Headers()
        headers.set("Content-Type", "application/json")
        headers.set("Accept", "text/event-stream" if is_streaming else "application/json")
        apply_provider_auth(headers, provider.cfg, None)
        if req.ctx.get("traceparent"):
            headers.set("traceparent", req.ctx["traceparent"])

        deployment = routing.Deployment(provider=provider_id, model=model)

        if is_streaming:
            # Streamed /v1/messages rides execute_streaming (ISSUE 9
            # satellite — it previously had no failover at all): the
            # breaker/budget walk covers establishment, and a death
            # before the first relayed byte re-issues the request on any
            # remaining candidate under the same trace id. No
            # continuation — Anthropic doesn't advertise the capability,
            # so post-first-byte keeps the non-idempotent contract. The
            # returned stream is idle-guarded internally.
            async def stream_call(cand, b) -> Any:
                resp = await self.client.post(
                    upstream_url, body, headers=headers, stream=True,
                    timeout=b.timeout(),
                )
                content_type = resp.headers.get("Content-Type") or ""
                if resp.status == 200 and content_type.startswith("text/event-stream"):
                    # Block-level passthrough, no wrapper generator:
                    # iter_raw already coalesces every buffered upstream
                    # byte into one block per read (SSE framing preserved
                    # verbatim; the telemetry usage scan splits lines
                    # itself), and the server's write path batches blocks
                    # into one transport write per loop pass.
                    return resp.iter_raw()
                # Buffer the non-SSE body (list-accumulate + join once:
                # `bytes +=` is O(n²) on large bodies).
                parts = []
                async for block in resp.iter_raw():
                    parts.append(block)
                raw = b"".join(parts) or resp.body
                if resp.status >= 500 or resp.status == 429:
                    from inference_gateway_tpu.resilience.retry import retry_after_seconds

                    # Upstream illness: raise so the breaker is charged
                    # (and a multi-candidate walk would continue). The
                    # EXACT body bytes + content type ride along so the
                    # passthrough below stays verbatim — decode/encode
                    # round-trips mangle non-UTF-8 bodies.
                    err = HTTPError(resp.status,
                                    raw.decode("utf-8", errors="replace"),
                                    retry_after=retry_after_seconds(resp.headers))
                    err.passthrough = _MessagesPassthrough(resp.status,
                                                           content_type, raw)
                    raise err
                raise _MessagesPassthrough(resp.status, content_type, raw)

            try:
                stream, _served = await self.resilience.execute_streaming(
                    [deployment], stream_call,
                    budget=self.resilience.new_budget(),
                    event=req.ctx.get("wide_event"),
                )
            except _MessagesPassthrough as p:
                # A sub-500 non-SSE answer means the upstream is alive:
                # feed the breaker the same success verdict the buffered
                # path's result_ok records, or a half-open circuit would
                # never close on an upstream that answers stream:true
                # with buffered/4xx responses (code-review finding).
                self.resilience.breakers.get(
                    deployment.provider, deployment.model).record_success()
                out = Response(status=p.status, body=p.body)
                out.headers.set("Content-Type", p.content_type or "application/json")
                return out
            except UpstreamUnavailableError:
                return messages_error(503, "overloaded_error",
                                      "Upstream temporarily unavailable (circuit open)")
            except BudgetExceededError:
                return messages_error(504, "api_error", "Request timed out")
            except HTTPError as e:
                # Verbatim upstream error passthrough (routes.go keeps
                # the Anthropic envelope untouched): the original bytes
                # and content type ride the exception.
                p = getattr(e, "passthrough", None)
                body_out = p.body if p is not None else e.message.encode()
                ctype = (p.content_type if p is not None else "") or "application/json"
                out = Response(status=e.status_code, body=body_out)
                out.headers.set("Content-Type", ctype)
                return out
            except HTTPClientError as e:
                self.logger.error("failed to reach upstream server", e, "url", upstream_url)
                return messages_error(502, "api_error", "Failed to reach upstream server")
            return StreamingResponse.sse(stream)

        # Buffered passthrough is non-idempotent: no retry, but the
        # circuit breaker sheds load from a dead upstream and the
        # deadline budget bounds the whole exchange.
        async def call(cand, b) -> Any:
            return await self.client.post(
                upstream_url, body, headers=headers, stream=False,
                timeout=b.timeout(),
            )

        try:
            resp, _ = await self.resilience.execute(
                [deployment], call,
                budget=self.resilience.new_budget(), idempotent=False,
                event=req.ctx.get("wide_event"),
                # Upstream errors pass through verbatim (no exception), so
                # tell the breaker which responses count as illness.
                result_ok=lambda r: r.status < 500 and r.status != 429,
            )
        except UpstreamUnavailableError:
            return messages_error(503, "overloaded_error",
                                  "Upstream temporarily unavailable (circuit open)")
        except BudgetExceededError:
            return messages_error(504, "api_error", "Request timed out")
        except HTTPClientError as e:
            self.logger.error("failed to reach upstream server", e, "url", upstream_url)
            return messages_error(502, "api_error", "Failed to reach upstream server")

        out = Response(status=resp.status, body=resp.body)
        out.headers.set("Content-Type", resp.headers.get("Content-Type") or "application/json")
        return out

    # ------------------------------------------------------------------
    async def list_tools_handler(self, req: Request) -> Response:
        """GET /v1/mcp/tools (routes.go:1005-1053)."""
        if not self.cfg.mcp.expose:
            return error_json("mcp tools endpoint is not exposed", 403)
        tools: list[dict[str, Any]] = []
        client = self.mcp_client
        if client is not None and client.is_initialized():
            for server_url in client.get_servers():
                try:
                    for tool in client.get_server_tools(server_url):
                        tools.append({
                            "name": "mcp_" + tool.get("name", ""),
                            "description": tool.get("description", ""),
                            "server": server_url,
                            "input_schema": tool.get("inputSchema") or tool.get("input_schema"),
                        })
                except Exception as e:
                    self.logger.error("failed to get tools from mcp server", e, "server", server_url)
        return Response.json({"object": "list", "data": tools})

    # ------------------------------------------------------------------
    async def metrics_ingestion_handler(self, req: Request) -> Response:
        """POST /v1/metrics — OTLP push ingest, JSON encoding, gzip-aware
        (api/metrics.go:25-99). Besides the gen_ai.* histograms, accepts
        the sidecar's last-value gauges: the device observatory's
        engine.hbm.{live,peak,plan}_bytes (ISSUE 19)."""
        if self.otel is None:
            return error_json("metrics push endpoint is not enabled", 403)
        body = req.body
        if len(body) > MAX_METRICS_BODY:
            return error_json("Request body too large", 413)
        if (req.headers.get("Content-Encoding") or "").lower() == "gzip":
            try:
                body = gzip.decompress(body)
            except OSError:
                return error_json("invalid gzip body", 400)
            if len(body) > MAX_METRICS_BODY:
                return error_json("Request body too large", 413)
        content_type = (req.headers.get("Content-Type") or "").split(";")[0].strip()
        if content_type == "application/x-protobuf":
            # Binary OTLP — what OTel SDK exporters send by default
            # (api/metrics.go:25-99 accepts both encodings).
            from inference_gateway_tpu.otel.otlp_proto import (
                ProtoDecodeError,
                decode_export_metrics_request,
            )

            try:
                payload = decode_export_metrics_request(body)
            except ProtoDecodeError as e:
                return error_json(f"invalid OTLP protobuf payload: {e}", 400)
        else:
            try:
                payload = json.loads(body)
            except ValueError:
                return error_json("invalid OTLP JSON payload", 400)

        source = req.headers.get("X-Source") or ""
        result = self.otel.ingest_metrics(payload, source)
        response: dict[str, Any] = {}
        if result["rejected"]:
            response["partialSuccess"] = {
                "rejectedDataPoints": result["rejected"],
                "errorMessage": result.get("error_message", ""),
            }
        return Response.json(response)

    # ------------------------------------------------------------------
    async def proxy_handler(self, req: Request) -> Response:
        """/proxy/:provider/*path — attach provider auth, forward
        (routes.go:94-268)."""
        provider_id = req.params.get("provider", "")
        try:
            provider = self._build_provider(provider_id)
        except (ProviderNotFoundError, ProviderNotConfiguredError) as e:
            return self._provider_error(e, provider_id)

        headers = Headers(req.headers.items())
        headers.remove("Host")
        headers.remove("Content-Length")
        headers.remove("Connection")
        # Fleet replica routing (ISSUE 11): the provider layer re-targets
        # the hop to one deployment's own base URL via X-Fleet-Url. Only
        # URLs the operator's pools file declares for THIS provider are
        # honored — anything else is rejected, so the hop (which attaches
        # provider credentials below) can never become an open proxy.
        fleet_url = (req.headers.get("X-Fleet-Url") or "").strip()
        headers.remove("X-Fleet-Url")
        if fleet_url and fleet_url not in (self.fleet_urls.get(provider_id) or set()):
            self.logger.warn("rejected unregistered fleet url", "provider",
                             provider_id, "url", fleet_url)
            return error_json("Unknown fleet deployment URL", 403)
        try:
            query = apply_provider_auth(headers, provider.cfg, req.query)
        except ValueError:
            return error_json("Unsupported auth type", 422)
        if req.ctx.get("traceparent"):
            headers.set("traceparent", req.ctx["traceparent"])

        base = (fleet_url or provider.cfg.url).rstrip("/")
        path = req.params.get("path", "/")
        url = base + "/" + path.lstrip("/")
        if query:
            url += "?" + "&".join(f"{k}={v}" for k, vs in query.items() for v in vs)

        accept = req.headers.get("Accept") or ""
        content_type = req.headers.get("Content-Type") or ""
        # Substring, not equality: the provider layer sends
        # "text/event-stream, application/json" (provider.go:105 — the
        # reference's own loopback Accept). The reference can get away
        # with an equality check (routes.go:114) because its
        # "non-streaming" branch is httputil.ReverseProxy, which pipes
        # bytes through as they arrive either way; our non-streaming
        # branch buffers, so an exact match silently turned the relay
        # into store-and-forward — TTFT = full generation, and 128
        # concurrent streams each held their whole body in memory
        # (round-2 verdict weak #3, the 128-stream cliff).
        is_streaming = "text/event-stream" in accept or "text/event-stream" in content_type

        if len(req.body) >= MAX_BODY_SIZE:
            return error_json("Request body too large", 413)

        # Development-mode body logging (reference internal/proxy).
        if self.cfg.environment == "development":
            from inference_gateway_tpu.api.proxymod import DevRequestModifier

            DevRequestModifier(
                self.logger, self.cfg.debug_content_truncate_words, self.cfg.debug_max_messages
            ).modify(url, req.body)

        try:
            resp = await self.client.request(
                req.method, url, headers=headers, body=req.body, stream=is_streaming,
                timeout=None if is_streaming else self.cfg.client.timeout,
            )
        except HTTPClientError as e:
            self.logger.error("failed to reach upstream server", e, "url", url)
            return error_json(f"Failed to reach upstream server: {e}", 502)

        if is_streaming and resp.status == 200:
            # Direct passthrough of iter_raw's coalesced blocks — the
            # write path downstream batches them per loop pass.
            return StreamingResponse.sse(resp.iter_raw())

        if is_streaming:
            parts = []
            async for block in resp.iter_raw():
                parts.append(block)
            body_out = b"".join(parts)
        else:
            body_out = resp.body
        if self.cfg.environment == "development":
            from inference_gateway_tpu.api.proxymod import DevResponseModifier

            DevResponseModifier(self.logger).modify(
                url, resp.status, resp.headers.get("Content-Type") or "",
                (resp.headers.get("Content-Encoding") or "").lower(), body_out,
            )
        out = Response(status=resp.status, body=body_out)
        out.headers.set("Content-Type", resp.headers.get("Content-Type") or "application/json")
        return out


def apply_provider_auth(headers: Headers, cfg: ProviderConfig,
                        query: dict[str, list[str]] | None) -> dict[str, list[str]]:
    """Attach the provider credential per auth type (routes.go:271-294).
    Returns the (possibly augmented) query dict for query-auth providers."""
    query = dict(query or {})
    if cfg.auth_type == constants.AUTH_TYPE_BEARER:
        headers.set("Authorization", f"Bearer {cfg.token}")
    elif cfg.auth_type == constants.AUTH_TYPE_XHEADER:
        headers.set("x-api-key", cfg.token)
    elif cfg.auth_type == constants.AUTH_TYPE_QUERY:
        query["key"] = [cfg.token]
    elif cfg.auth_type == constants.AUTH_TYPE_NONE:
        pass
    else:
        raise ValueError(f"unsupported auth type {cfg.auth_type!r}")
    for key, values in cfg.extra_headers.items():
        for value in values:
            headers.add(key, value)
    return query
